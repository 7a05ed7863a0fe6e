"""Kimi-K2 language-model weights from a seed, made on the device a leaf
at a time.

The tree carries the published names and layouts (``embed_tokens``,
``lm_head``, ``norm/weight``, ``layers_<i>/{input_layernorm/weight,
post_attention_layernorm/weight, self_attn/{q_a_proj, q_a_layernorm,
q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}}``; a
leading dense layer's ``mlp/{gate,up,down}_proj``; an expert layer's
``mlp/{router, e_score_correction_bias, gate_proj, up_proj, down_proj,
shared_gate_proj, shared_up_proj, shared_down_proj}``): ``q_b_proj``
[q_lora_rank, heads * (nope + rope)] and ``kv_b_proj`` [kv_lora_rank, heads
* (nope + v)] a head's parts side by side, as the published checkpoint
has them. The reference reads this tree as it is; the program is handed
the same tree and splits the two up-projections itself
(``KimiK2Config.prepare_params``). It imports nothing of the program;
``tests`` hold its shapes equal to the decoder's own.

Every matrix is normal with standard deviation ``init_scale / sqrt(its
fan-in)`` (the low-rank paths have three fan-ins: 7,168, 1,536 and 512),
the embedding has standard deviation 1, every norm's weight is one, in
the configuration's ``dtype`` (bfloat16); the router's selection bias is
float32, normal with standard deviation ``bias_scale``, so that it moves
which experts a token takes (a sigmoid score's spread over the experts is
about 0.2). Queries and keys are then of unit scale a lane, a score's
spread over a row's keys is about ``mscale ** 2`` = 2, the stream grows
by about one a branch, the untied head spreads the logits over the held
vocabulary to a few units, and best and second best lie close enough for
a lower precision to show in the tokens served.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ONES, BIAS = "ones", "bias"


def leaf_shapes(sizes: dict) -> dict:
    """``{path: shape}`` of every parameter; a norm's weight is ``(ONES,
    n)``, the selection bias ``(BIAS, n)``."""
    c, f, fd = (sizes["hidden_size"], sizes["moe_intermediate_size"],
                sizes["intermediate_size"])
    h, rq, r = (sizes["num_attention_heads"], sizes["q_lora_rank"],
                sizes["kv_lora_rank"])
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    lo, hi = sizes["held_experts"]
    held, shared = hi - lo, sizes["n_shared_experts"]
    routed = sizes["n_routed_experts_published"]
    out = {("embed_tokens",): (sizes["vocab_size"], c),
           ("lm_head",): (c, sizes["vocab_size"]),
           ("norm", "weight"): (ONES, c)}
    for i in range(sizes["num_hidden_layers"]):
        layer = f"layers_{i}"
        out[(layer, "input_layernorm", "weight")] = (ONES, c)
        out[(layer, "post_attention_layernorm", "weight")] = (ONES, c)
        for name, shape in (
                ("q_a_proj", (c, rq)), ("q_a_layernorm", (ONES, rq)),
                ("q_b_proj", (rq, h * (nope + rope))),
                ("kv_a_proj_with_mqa", (c, r + rope)),
                ("kv_a_layernorm", (ONES, r)),
                ("kv_b_proj", (r, h * (nope + dv))),
                ("o_proj", (h * dv, c))):
            out[(layer, "self_attn", name)] = shape
        if i < sizes["first_k_dense_replace"]:
            mlp = (("gate_proj", (c, fd)), ("up_proj", (c, fd)),
                   ("down_proj", (fd, c)))
        else:
            mlp = (("router", (c, routed)),
                   ("e_score_correction_bias", (BIAS, routed)),
                   ("gate_proj", (held, c, f)), ("up_proj", (held, c, f)),
                   ("down_proj", (held, f, c)),
                   ("shared_gate_proj", (shared, c, f)),
                   ("shared_up_proj", (shared, c, f)),
                   ("shared_down_proj", (shared, f, c)))
        for name, shape in mlp:
            out[(layer, "mlp", name)] = shape
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(sizes: dict, seed: int, device=None):
    """The parameter tree for ``sizes`` from ``seed`` on ``device``
    (default: the first). One small jitted call a distinct shape and
    scale; the key is an argument, so every seed and leaf reuses them."""
    dtype = DTYPES[sizes["dtype"]]
    scale = float(sizes["assumed"]["init_scale"])
    root = seed_key(seed)
    tree: dict = {}
    with jax.default_device(device or jax.devices()[0]):
        for n, (path, shape) in enumerate(leaf_shapes(sizes).items()):
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            key = jax.random.fold_in(root, n)
            if shape[0] == ONES:
                node[path[-1]] = jnp.ones((shape[1],), dtype)
            elif shape[0] == BIAS:
                node[path[-1]] = _normal(
                    key, (shape[1],),
                    float(sizes["assumed"]["bias_scale"]), jnp.float32)
            elif path == ("embed_tokens",):
                node[path[-1]] = _normal(key, shape, 1.0, dtype)
            else:
                node[path[-1]] = _normal(
                    key, shape, scale / math.sqrt(shape[-2]), dtype)
    return tree
