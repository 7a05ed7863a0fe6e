"""Qwen3-Next weights from a seed, made on the device a leaf at a time.

The tree carries the published names and layouts (``embed_tokens``,
``lm_head``, ``norm/weight``, ``layers_<i>/{input_layernorm/weight,
post_attention_layernorm/weight, shared_expert_gate}``; a full layer's
``self_attn/{q_proj, k_proj, v_proj, o_proj, q_norm, k_norm}`` with
``q_proj`` [hidden, heads * 2 * head_dim] a head's query and gate side by
side; a delta layer's ``linear_attn/{in_proj_qkvz, in_proj_ba, conv1d,
A_log, dt_bias, norm, out_proj}`` with ``in_proj_qkvz`` and ``in_proj_ba``
grouped a key head as the published checkpoint has them; every layer's
``mlp/{router, gate_proj, up_proj, down_proj, shared_gate_proj,
shared_up_proj, shared_down_proj}``). The reference reads this tree as it
is; the program is handed the same tree and lays the two grouped
projections out flat itself (``Qwen3NextConfig.prepare_params``). It
imports nothing of the program; ``tests`` hold its shapes equal to the
decoder's own.

Every matrix is normal with standard deviation ``init_scale / sqrt(its
fan-in)`` (the convolution's fan-in is its four taps), the embedding has
standard deviation 1, every norm is at its identity (a zero-centred
weight 0, the delta layers' plain output norm 1), in the configuration's
``dtype`` (bfloat16). Three things are drawn otherwise, and why:

* **The decay, so that memory lasts and differs a head.** ``dt_bias`` is
  1 everywhere and ``A_log`` [value heads] float32 is set so that head
  ``h`` of ``n`` forgets at the rate ``2 ** -(lo + (hi - lo) h / (n -
  1))`` a token at a zero input (``decay_span_log2`` = ``[lo, hi]``: 4 and
  12 at the cell's size, ``alpha`` from 1 - 1/16 to 1 - 1/4,096), and
  ``in_proj_ba`` is ``ba_scale`` (0.5) times narrower than a unit
  projection, so that a token moves its rate by a third either way. The
  published initialisation (``A`` uniform in (0, 16), ``dt_bias`` 1)
  forgets within a token: no test or limit could then see a wrong old
  state (one not zeroed at admission, one that took a bucket's padding,
  one kept in bfloat16).
* **The full layers' output projection, so that attention shows.** A
  softmax over twenty thousand random keys of unit score spread averages
  some seven thousand values: a hundredth of the stream, behind a gate of
  a half. ``o_proj`` is drawn ``attn_out_gain`` times wider (8 at the
  cell's size, 1 in the rehearsal's dozens of tokens). The delta layers
  need none: their output norm makes every head a unit share whatever
  the state's size.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.weights import seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ONES, ZEROS, A_LOG = "ones", "zeros", "a_log"


def is_full(sizes: dict, layer: int) -> bool:
    return (layer + 1) % int(sizes["full_attention_interval"]) == 0


def leaf_shapes(sizes: dict) -> dict:
    """``{path: spec}`` of every parameter: a matrix's ``(rows, cols,
    gain)`` or an expert stack's ``(n, rows, cols, gain)``; ``(ONES | ZEROS
    | A_LOG, n)`` for a norm's weight, ``dt_bias`` and ``A_log``."""
    c, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]
    lo, hi = sizes["held_experts"]
    held, routed = hi - lo, sizes["num_experts_published"]
    ba = float(sizes["ba_scale"])
    out = {("embed_tokens",): (sizes["vocab_size"], c,
                               math.sqrt(sizes["vocab_size"])),
           ("lm_head",): (c, sizes["vocab_size"], 1.0),
           ("norm", "weight"): (ZEROS, c)}
    for i in range(sizes["num_hidden_layers"]):
        layer = f"layers_{i}"
        out[(layer, "input_layernorm", "weight")] = (ZEROS, c)
        out[(layer, "post_attention_layernorm", "weight")] = (ZEROS, c)
        out[(layer, "shared_expert_gate")] = (c, 1, 1.0)
        if is_full(sizes, i):
            for name, spec in (
                    ("q_proj", (c, h * 2 * hd, 1.0)),
                    ("k_proj", (c, kv * hd, 1.0)),
                    ("v_proj", (c, kv * hd, 1.0)),
                    ("o_proj", (h * hd, c, float(sizes["attn_out_gain"]))),
                    ("q_norm", (ZEROS, hd)), ("k_norm", (ZEROS, hd))):
                out[(layer, "self_attn", name)] = spec
        else:
            for name, spec in (
                    ("in_proj_qkvz", (c, 2 * hk * dk + 2 * hv * dv, 1.0)),
                    ("in_proj_ba", (c, 2 * hv, ba)),
                    ("conv1d", (2 * hk * dk + hv * dv, taps,
                                math.sqrt(2 * hk * dk + hv * dv) / math.sqrt(
                                    taps))),
                    ("A_log", (A_LOG, hv)), ("dt_bias", (ONES, hv)),
                    ("norm", (ONES, dv)),
                    ("out_proj", (hv * dv, c, 1.0))):
                out[(layer, "linear_attn", name)] = spec
        for name, spec in (
                ("router", (c, routed, 1.0)),
                ("gate_proj", (held, c, f, 1.0)),
                ("up_proj", (held, c, f, 1.0)),
                ("down_proj", (held, f, c, 1.0)),
                ("shared_gate_proj", (1, c, f, 1.0)),
                ("shared_up_proj", (1, c, f, 1.0)),
                ("shared_down_proj", (1, f, c, 1.0))):
            out[(layer, "mlp", name)] = spec
    return out


def a_log(sizes: dict) -> np.ndarray:
    """``A_log`` [value heads]: head ``h`` forgets ``2 ** -(lo + (hi - lo)
    h / (n - 1))`` a token where its input is zero (``softplus(dt_bias)``
    with ``dt_bias`` 1 is 1.3133)."""
    n = int(sizes["linear_num_value_heads"])
    lo, hi = (float(x) for x in sizes["decay_span_log2"])
    rate = 2.0 ** -(lo + (hi - lo) * np.arange(n) / max(n - 1, 1))
    return np.log(rate / math.log1p(math.e)).astype(np.float32)


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
                # dt_bias rides in float32 beside A_log
                node[path[-1]] = jnp.ones(
                    (spec[1],), jnp.float32 if path[-1] == "dt_bias"
                    else dtype)
            elif spec[0] == ZEROS:
                node[path[-1]] = jnp.zeros((spec[1],), dtype)
            elif spec[0] == A_LOG:
                node[path[-1]] = jnp.asarray(a_log(sizes))
            else:
                *shape, gain = spec
                node[path[-1]] = _normal(
                    jax.random.fold_in(root, n), tuple(shape),
                    scale * gain / math.sqrt(shape[-2]), dtype)
    return tree
