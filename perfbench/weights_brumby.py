"""Brumby-14B-Base weights from a seed, made on the device a leaf at a
time.

The tree carries the names the program's decoder uses (``embed_tokens``,
``lm_head``, ``norm/weight``, ``layers_<i>/{input_layernorm/weight,
post_attention_layernorm/weight, self_attn/{q,k,v,o}_proj, self_attn/
{q,k}_norm, self_attn/{g_proj, g_bias}, mlp/{gate_proj, up_proj,
down_proj}}``), so the program is handed it as it is and the reference
reads the same values. It imports nothing of the program; ``tests`` hold
its shapes equal to the decoder's own.

Every matrix is normal with standard deviation ``init_scale /
sqrt(rows)`` (its fan-in; the embedding's ``1 / sqrt(hidden_size)``),
every norm's weight one, in the configuration's ``dtype`` (bfloat16), as
``weights_keye.py`` draws them. Two leaves are drawn otherwise, and why:

* **The gate, so that memory lasts.** ``g_proj`` has standard deviation
  ``gate_scale / sqrt(hidden_size)`` and ``g_bias`` is ``gate_bias``
  everywhere: at the configuration's 8.3 and 0.5 a gate is ``sigmoid(8.3
  +- 0.5)``, about ``1 - 1/4,000``, and a key still weighs a third after
  4,000 tokens. With a zero bias every gate is near 0.5, every state
  forgets in twenty tokens, and no test or limit could see a wrong old
  state (one not zeroed at admission, one that took a bucket's padding,
  one kept in bfloat16): the state's whole point would go unmeasured.
* **The output projection, so that the retention shows.** Power
  attention has no temperature: with random q and k its weights are
  ``chi^2_1`` whatever their scale, a query averages some thousand
  values, and the average of a thousand unit values is 0.03: three
  hundredths of the residual stream beside a SwiGLU branch of 1, which no
  comparison of logits could tell from a wrong layer. ``o_proj`` is drawn
  ``retention_out_gain`` times wider (16 at the cell's thousands of
  tokens, 2 in the rehearsal's dozens), which makes the branch a unit
  share of the stream at the context lengths the cell sends.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.weights import seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ONES, FILL = "ones", "fill"


def leaf_shapes(sizes: dict) -> dict:
    """``{path: spec}`` of every parameter: a matrix's ``(rows, cols,
    gain)``, a norm's weight ``(ONES, n)``, the gate's bias ``(FILL, n,
    value)``."""
    c, f = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, \
        sizes["num_key_value_heads"] * hd
    out = {("embed_tokens",): (sizes["vocab_size"], c,
                               math.sqrt(sizes["vocab_size"] / c)),
           ("lm_head",): (c, sizes["vocab_size"], 1.0),
           ("norm", "weight"): (ONES, c)}
    for i in range(sizes["num_hidden_layers"]):
        layer = f"layers_{i}"
        out[(layer, "input_layernorm", "weight")] = (ONES, c)
        out[(layer, "post_attention_layernorm", "weight")] = (ONES, c)
        for name, spec in (
                ("q_proj", (c, q, 1.0)), ("k_proj", (c, kv, 1.0)),
                ("v_proj", (c, kv, 1.0)),
                ("o_proj", (q, c, float(sizes["retention_out_gain"]))),
                ("q_norm", (ONES, hd)), ("k_norm", (ONES, hd)),
                ("g_proj", (c, sizes["num_key_value_heads"],
                            float(sizes["gate_scale"]))),
                ("g_bias", (FILL, sizes["num_key_value_heads"],
                            float(sizes["gate_bias"])))):
            out[(layer, "self_attn", name)] = spec
        for name, spec in (("gate_proj", (c, f, 1.0)),
                           ("up_proj", (c, f, 1.0)),
                           ("down_proj", (f, c, 1.0))):
            out[(layer, "mlp", name)] = spec
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(sizes: dict, seed: int, device=None):
    """The parameter tree for ``sizes`` from ``seed`` on ``device``
    (default: the first). One small jitted call a distinct shape and
    width; the key is an argument, so every seed and leaf reuses them."""
    dtype = DTYPES[sizes["dtype"]]
    scale = float(sizes["assumed"]["init_scale"])
    root = seed_key(seed)
    tree: dict = {}
    with jax.default_device(device or jax.devices()[0]):
        for n, (path, spec) in enumerate(leaf_shapes(sizes).items()):
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            if spec[0] == ONES:
                node[path[-1]] = jnp.ones((spec[1],), dtype)
            elif spec[0] == FILL:
                node[path[-1]] = jnp.full((spec[1],), spec[2], dtype)
            else:
                rows, cols, gain = spec
                node[path[-1]] = _normal(
                    jax.random.fold_in(root, n), (rows, cols),
                    scale * gain / math.sqrt(rows), dtype)
    return tree
