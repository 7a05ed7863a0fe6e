"""Keye-VL-2.0 language-model weights from a seed, made on the device a
leaf at a time.

The tree carries the names the program's decoder uses (``embed_tokens``,
``lm_head``, ``norm/weight``, ``layers_<i>/{input_layernorm/weight,
post_attention_layernorm/weight, self_attn/{q,k,v,o}_proj, self_attn/
{q,k}_norm, self_attn/index_{q,k,weights}_proj, self_attn/
index_k_norm_{weight,bias}, mlp/{router, gate_proj, up_proj,
down_proj}}``), so the program is handed it as it is and the reference
reads the same values. It imports nothing of the program; ``tests`` hold
its shapes equal to the decoder's own.

Every matrix is normal with standard deviation ``init_scale /
sqrt(hidden_size)``, every norm's weight one and the index key norm's
bias zero, in the configuration's ``dtype`` (bfloat16). The residual
stream then grows by about ``init_scale`` a branch over a unit-scale
embedding, the untied head spreads the logits over the held vocabulary
to a few units, and best and second best lie close enough for a lower
precision to show in the tokens served. The index's scores come out of
the same matrices: their spread over a row's keys is wide (no plateau of
ties), so which 2,048 keys a query keeps is decided by the learned-index
arithmetic and a wrong reading of it moves the logits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ONES, ZEROS = "ones", "zeros"


def leaf_shapes(sizes: dict) -> dict:
    """``{path: shape}`` of every parameter; a norm's weight is ``(ONES,
    n)``, the index key norm's bias ``(ZEROS, n)``."""
    c, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    hd = sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, \
        sizes["num_key_value_heads"] * hd
    sa = sizes["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    lo, hi = sizes["held_experts"]
    held = hi - lo
    out = {("embed_tokens",): (sizes["vocab_size"], c),
           ("lm_head",): (c, sizes["vocab_size"]),
           ("norm", "weight"): (ONES, c)}
    for i in range(sizes["num_hidden_layers"]):
        layer = f"layers_{i}"
        out[(layer, "input_layernorm", "weight")] = (ONES, c)
        out[(layer, "post_attention_layernorm", "weight")] = (ONES, c)
        for name, shape in (
                ("q_proj", (c, q)), ("k_proj", (c, kv)),
                ("v_proj", (c, kv)), ("o_proj", (q, c)),
                ("q_norm", (ONES, hd)), ("k_norm", (ONES, hd)),
                ("index_q_proj", (c, j * di)), ("index_k_proj", (c, di)),
                ("index_weights_proj", (c, j)),
                ("index_k_norm_weight", (ONES, di)),
                ("index_k_norm_bias", (ZEROS, di))):
            out[(layer, "self_attn", name)] = shape
        for name, shape in (
                ("router", (c, sizes["num_experts_routed"])),
                ("gate_proj", (held, c, f)), ("up_proj", (held, c, f)),
                ("down_proj", (held, f, c))):
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
    std = (float(sizes["assumed"]["init_scale"])
           / math.sqrt(sizes["hidden_size"]))
    root = seed_key(seed)
    tree: dict = {}
    with jax.default_device(device or jax.devices()[0]):
        for n, (path, shape) in enumerate(leaf_shapes(sizes).items()):
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            if shape[0] == ONES:
                node[path[-1]] = jnp.ones((shape[1],), dtype)
            elif shape[0] == ZEROS:
                node[path[-1]] = jnp.zeros((shape[1],), dtype)
            else:
                node[path[-1]] = _normal(jax.random.fold_in(root, n),
                                         shape, std, dtype)
    return tree
