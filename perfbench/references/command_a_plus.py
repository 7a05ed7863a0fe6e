"""The plain reference of the Cohere2-MoE decoder (Command A+): a full
forward over a whole sequence in straightforward ``jax.numpy``, float32
with ``precision=HIGHEST`` matrix products. No kernel, no cache, no
batching, nothing imported from the program: it is what ``correct`` is
decided against.

The equations, for layer ``l`` with input ``x`` [T, hidden] (``sizes`` is
the configuration file: ``config.json``'s keys):

* ``h = LN(x)``: subtract the mean, divide by ``sqrt(var +
  layer_norm_eps)``, times a weight, no bias. One norm feeds both branches
  (``use_parallel_block``).
* attention: ``q = h Wq`` [T, heads, head_dim], ``k = h Wk``, ``v = h Wv``
  [T, kv_heads, head_dim], no bias, no q/k norm. A ``sliding_attention``
  layer rotates q and k over the whole head (``rotary_pct`` 1) in pairs
  ``(2i, 2i+1)`` (``rope_gptj``) with ``rope_theta``, and query ``i`` sees
  key ``j`` iff ``0 <= i - j < sliding_window``. A ``full_attention``
  layer takes no position and is causal. Scores ``/ sqrt(head_dim)``,
  softmax; query heads ``G g .. G g + G - 1`` read key-value head ``g``.
* experts: ``s = sigmoid(h Wr)`` over all ``num_experts_routed``; the
  ``num_experts_per_tok`` largest; ``w = s_top / sum(s_top)``
  (``norm_topk_prob``); ``E(h) = (silu(h Wg) * (h Wu)) Wd``; ``routed =
  sum over the chosen experts in held_experts of w_e E_e(h)``; ``shared =
  mean of the shared experts``.
* ``x' = x + attention + routed + shared``.

After the last layer ``LN``, then ``logits = logit_scale * y E^T`` over
the rows of the embedding that are held.

Departures from the published description, each to fit one chip beside
nothing else or stated by the cut:

* the chip's share: ``held_experts`` = [lo, hi) of the routed experts have
  weights here (``gate_proj`` [hi - lo, hidden, width], ...); what the
  absent experts would add is left out, as in the program; the embedding
  holds ``vocab_size`` rows of the published table;
* ``shared_expert_combination_strategy: "average"`` is read as the mean of
  the shared experts' outputs (the config gives the word, not the
  formula), and ``first_k_dense_replace: 0`` as "every layer has experts";
* the weights are the program's bfloat16 values, raised to float32 one
  matrix at a time where it is used (exact);
* attention is taken a block of queries and one key-value head at a time,
  and a held expert runs on the rows routed to it, gathered into a room
  of ``room`` rows (``forward`` checks that no expert overflowed it and
  doubles it otherwise). Neither changes a value beyond the order of
  float32 additions. The loops over heads and experts are ``lax.map`` and
  ``lax.scan`` and the layer's kind is a traced value, so that a sequence
  length compiles one layer program (a minute each on the chip's host
  when they were unrolled and one a kind).

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16: both operands of every matrix product
rounded to float8 (e4m3, one scale per tensor), their products summed in
float32 (exact: one bfloat16 pass holds e4m3 operands whole).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``shared_summed``, ``route_held_only``,
``window_off``, ``rotary_on_full``, ``topk_not_renormalised``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"
FAULTS = ("shared_summed", "route_held_only", "window_off",
          "rotary_on_full", "topk_not_renormalised")
Q_BLOCK = 1024      # queries a block of attention
POS_BLOCK = 256     # the head's positions come in whole blocks of this


def held_range(sizes: dict):
    lo, hi = sizes["held_experts"]
    return int(lo), int(hi)


def _fp8(x):
    """``(x / scale rounded to float8 e4m3, scale)``, one scale a tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn), scale


def _mm(x, w, mode):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        # e4m3 values are exact in bfloat16 and so are their products in
        # the float32 accumulator: one pass gives what HIGHEST would
        (xq, sx), (wq, sw) = _fp8(x), _fp8(w)
        return jnp.matmul(xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32) * (sx * sw)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, weight, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight.astype(jnp.float32)


def _rotate(x, theta):
    """``x`` [T, heads, d]: pairs (2i, 2i+1) turned by ``pos * theta **
    (-2i / d)``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _attention(h, p, sizes, window, rotary, mode):
    """``window`` (0: none) and ``rotary`` are traced values: window and
    full layers share one compiled program."""
    t = h.shape[0]
    n_q, n_kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    group = n_q // n_kv
    q = _mm(h, p["q_proj"], mode).reshape(t, n_q, hd)
    k = _mm(h, p["k_proj"], mode).reshape(t, n_kv, hd)
    v = _mm(h, p["v_proj"], mode).reshape(t, n_kv, hd)
    theta = float(sizes["rope_theta"])
    q = jnp.where(rotary, _rotate(q, theta), q)
    k = jnp.where(rotary, _rotate(k, theta), k)
    block = math.gcd(t, Q_BLOCK)
    col = jnp.arange(t)[None, :]
    reach = jnp.where(window > 0, window, t + 1)

    def one_head(args):
        qg, kg, vg = args               # [T, group, hd], [T, hd], [T, hd]

        def one_block(args):
            qb, i0 = args                           # [block, group, hd]
            row = i0 + jnp.arange(block)[:, None]
            seen = (col <= row) & (col > row - reach)
            s = _mm(qb.reshape(block * group, hd), kg.T, mode)
            s = s.reshape(block, group, t) / math.sqrt(hd)
            s = jnp.where(seen[:, None, :], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return _mm(a.reshape(block * group, t), vg,
                       mode).reshape(block, group, hd)

        blocks = jax.lax.map(one_block, (
            qg.reshape(t // block, block, group, hd),
            jnp.arange(0, t, block)))
        return blocks.reshape(t, group, hd)

    # one key-value head at a time, its group of query heads with it
    y = jax.lax.map(one_head, (
        jnp.moveaxis(q.reshape(t, n_kv, group, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [kv, T, group, hd]
    y = jnp.moveaxis(y, 0, 1).reshape(t, n_q * hd)
    return _mm(y, p["o_proj"], mode)


def _expert(x, w_gate, w_up, w_down, mode):
    g = _mm(x, w_gate, mode)
    return _mm(jax.nn.silu(g) * _mm(x, w_up, mode), w_down, mode)


def _experts(h, p, sizes, mode, faults, room):
    """``(routed + shared [T, hidden], the fullest held expert's rows)``."""
    t = h.shape[0]
    lo, hi = held_range(sizes)
    k = int(sizes["num_experts_per_tok"])
    router = p["router"]
    if "route_held_only" in faults:
        scores = jax.nn.sigmoid(_mm(h, router[:, lo:hi], mode))
        top_s, top_i = jax.lax.top_k(scores, k)
        top_i = top_i + lo
    else:
        scores = jax.nn.sigmoid(_mm(h, router, mode))
        top_s, top_i = jax.lax.top_k(scores, k)
    w = top_s
    if sizes["norm_topk_prob"] and "topk_not_renormalised" not in faults:
        w = top_s / top_s.sum(-1, keepdims=True)
    h_pad = jnp.concatenate([h, jnp.zeros((1, h.shape[1]))])

    def held_expert(carry, args):
        routed, fullest = carry
        e, w_gate, w_up, w_down = args
        chosen = top_i == lo + e                                 # [T, k]
        w_e = jnp.where(chosen, w, 0.0).sum(-1)                  # [T]
        # the rows routed to this expert, gathered into `room` rows; row
        # t is a row of zeros that spare places point at
        rows = jnp.nonzero(chosen.any(-1), size=room, fill_value=t)[0]
        fullest = jnp.maximum(fullest, chosen.any(-1).sum())
        y_e = _expert(h_pad[rows], w_gate, w_up, w_down, mode)
        w_rows = jnp.concatenate([w_e, jnp.zeros((1,))])[rows]
        routed = routed.at[rows].add(y_e * w_rows[:, None], mode="drop")
        return (routed, fullest), None

    (routed, fullest), _ = jax.lax.scan(
        held_expert, (jnp.zeros_like(h), jnp.zeros((), jnp.int32)),
        (jnp.arange(hi - lo), p["gate_proj"], p["up_proj"], p["down_proj"]))
    n_shared = int(sizes["num_shared_experts"])
    shared = jnp.zeros_like(h)
    if n_shared:
        shared, _ = jax.lax.scan(
            lambda acc, ws: (acc + _expert(h, *ws, mode), None), shared,
            (p["shared_gate_proj"], p["shared_up_proj"],
             p["shared_down_proj"]))
        if "shared_summed" not in faults:
            shared = shared / n_shared
    return routed + shared, fullest


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "mode", "faults", "room"))
def _layer(x, p, sliding, sizes_key, mode, faults, room):
    """One layer; ``sliding`` (a traced bool) says which kind."""
    sizes = dict(sizes_key)
    h = _layer_norm(x, p["input_layernorm"]["weight"],
                    float(sizes["layer_norm_eps"]))
    window = jnp.where(sliding & ("window_off" not in faults),
                       int(sizes["sliding_window"]), 0)
    rotary = sliding | ("rotary_on_full" in faults)
    a = _attention(h, p["self_attn"], sizes, window, rotary, mode)
    m, fullest = _experts(h, p["mlp"], sizes, mode, faults, room)
    return x + a + m, fullest


@functools.partial(jax.jit, static_argnames=("eps", "scale", "mode"))
def _head(x, weight, embed, eps, scale, mode):
    return scale * _mm(_layer_norm(x, weight, eps), embed.T, mode)


_SCALARS = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "sliding_window", "layer_norm_eps",
            "num_experts_per_tok", "num_shared_experts", "norm_topk_prob")


def layer_types(sizes: dict):
    return list(sizes["layer_types"])[:int(sizes["num_hidden_layers"])]


def forward(params, sizes: dict, tokens, positions, mode: str = "f32",
            faults=()):
    """Float32 logits [len(positions), vocab held] of the whole sequence
    ``tokens`` [T] at ``positions`` (attention takes ``gcd(T, Q_BLOCK)``
    queries at a time: pad T to a round number, a causal model never
    looks ahead)."""
    faults = tuple(sorted(faults))
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    key = tuple((k, sizes[k]) for k in _SCALARS) + (
        ("held_experts", tuple(held_range(sizes))),)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = int(tokens.shape[0])
    # a held expert's expected rows are T * k / E; twice that and a bit
    room = min(t, max(64, 2 * t * int(sizes["num_experts_per_tok"])
                      // int(sizes["num_experts_routed"])))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_types(sizes)):
        while True:
            y, fullest = _layer(x, params[f"layers_{i}"], kind == SLIDING,
                                key, mode, faults, room)
            if int(fullest) <= room:
                break
            room = min(t, 2 * room)         # an expert overflowed its room
        x = y
    # the head takes whole blocks of positions (the last repeated), so
    # that requests of any length share a few compiled programs
    positions = np.asarray(positions)
    n = len(positions)
    padded = np.full(-(-n // POS_BLOCK) * POS_BLOCK, positions[-1])
    padded[:n] = positions
    return _head(x[jnp.asarray(padded)], params["norm"]["weight"],
                 params["embed_tokens"], float(sizes["layer_norm_eps"]),
                 float(sizes["logit_scale"]), mode)[:n]


def served_logits(params, sizes: dict, prompt, served, *, pad_multiple: int,
                  mode: str = "f32", faults=()):
    """Logits [len(served), vocab held] at the positions whose next token
    was served: the last prompt position, then every served token but the
    last. The sequence is padded to a multiple of ``pad_multiple`` (causal
    attention never looks at the padding) so that few compiled programs
    serve every request."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    idx = np.zeros(-(-len(seq) // pad_multiple) * pad_multiple, np.int32)
    idx[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return forward(params, sizes, idx, pos, mode, faults)


def served_gaps(params, sizes: dict, prompt, served, *, pad_multiple: int,
                mode: str = "f32", faults=(), ref=None):
    """For one finished greedy request: at every served position, how far
    the served token's reference logit lies below the reference's best.

    With ``mode`` other than ``"f32"`` (or ``faults``) this is the
    control: the token that arithmetic puts first takes the served
    token's place, still judged by the float32 logits. ``ref``: the
    float32 ``served_logits`` of this request, where the caller has them
    already. Returns a float32 array, one gap per served token."""
    if ref is None:
        ref = served_logits(params, sizes, prompt, served,
                            pad_multiple=pad_multiple)
    ref = jnp.asarray(ref)
    if mode == "f32" and not faults:
        tokens = jnp.asarray(np.asarray(served, np.int32))
    else:
        tokens = jnp.argmax(
            served_logits(params, sizes, prompt, served,
                          pad_multiple=pad_multiple, mode=mode,
                          faults=faults), axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)
