"""Cohere2-MoE weights from a seed, made on the device a leaf at a time.

The tree carries the names the program's decoder uses (``embed_tokens``,
``norm/weight``, ``layers_<i>/{input_layernorm/weight, self_attn/{q,k,v,o}
_proj, mlp/{router, gate_proj, up_proj, down_proj, shared_*_proj}}``), so
the program is handed it as it is and the reference reads the same
values. It imports nothing of the program; ``tests`` hold its shapes
equal to the decoder's own.

Every matrix is normal with standard deviation ``init_scale /
sqrt(hidden_size)`` (0.02 at the published 4,096), every norm's weight
one, in the configuration's ``dtype`` (bfloat16). At that scale a layer's
experts and attention add some thirty times the embedding's magnitude to
the residual stream, so the tied head does not put the input token first
(the GPT-2 cell's problem, ``perfbench/weights.py``): the logits over the
held vocabulary are a unit-scale spread whose best and second best lie a
few tenths apart, close enough for a lower precision to show in the
tokens served.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def leaf_shapes(sizes: dict) -> dict:
    """``{path: shape}`` of every parameter, ``None`` marking a norm's
    weight (ones)."""
    c, f = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, \
        sizes["num_key_value_heads"] * hd
    lo, hi = sizes["held_experts"]
    held, shared = hi - lo, sizes["num_shared_experts"]
    out = {("embed_tokens",): (sizes["vocab_size"], c),
           ("norm", "weight"): None}
    for i in range(sizes["num_hidden_layers"]):
        layer = f"layers_{i}"
        out[(layer, "input_layernorm", "weight")] = None
        for name, shape in (("q_proj", (c, q)), ("k_proj", (c, kv)),
                            ("v_proj", (c, kv)), ("o_proj", (q, c))):
            out[(layer, "self_attn", name)] = shape
        for name, shape in (
                ("router", (c, sizes["num_experts_routed"])),
                ("gate_proj", (held, c, f)), ("up_proj", (held, c, f)),
                ("down_proj", (held, f, c)),
                ("shared_gate_proj", (shared, c, f)),
                ("shared_up_proj", (shared, c, f)),
                ("shared_down_proj", (shared, f, c))):
            out[(layer, "mlp", name)] = shape
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(sizes: dict, seed: int, device=None):
    """The parameter tree for ``sizes`` from ``seed`` on ``device``
    (default: the first). One small jitted call a distinct shape; the key
    is an argument, so every seed and leaf reuses them."""
    dtype = DTYPES[sizes["dtype"]]
    c = sizes["hidden_size"]
    std = float(sizes["assumed"]["init_scale"]) / math.sqrt(c)
    root = seed_key(seed)
    tree: dict = {}
    with jax.default_device(device or jax.devices()[0]):
        for n, (path, shape) in enumerate(leaf_shapes(sizes).items()):
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            if shape is None:
                node[path[-1]] = jnp.ones((c,), dtype)
            elif len(shape) == 3:
                # an expert at a time: the float32 draw of sixteen at once
                # is a GiB beside 9 GB of weights
                node[path[-1]] = jnp.stack([
                    _normal(jax.random.fold_in(jax.random.fold_in(root, n),
                                               e), shape[1:], std, dtype)
                    for e in range(shape[0])])
            else:
                node[path[-1]] = _normal(jax.random.fold_in(root, n),
                                         shape, std, dtype)
    return tree
