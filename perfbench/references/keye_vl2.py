"""The plain reference of Keye-VL-2.0's language model (``model_type:
KeyeVL2``): a full forward over a whole sequence in straightforward
``jax.numpy``, float32 with ``precision=HIGHEST`` matrix products. No
kernel, no cache, no batching, nothing imported from the program: it is
what ``correct`` is decided against.

The equations, for a layer with input ``x`` [T, hidden] (``sizes`` is the
configuration file: ``config.json``'s keys)::

    a  = rms(x; g_in)                     x / sqrt(mean(x^2) + rms_norm_eps) * g
    q  = a Wq [T, heads, head_dim]   k = a Wk, v = a Wv [T, kv_heads, head_dim]
    q  = rms over head_dim (g_q)     k = rms over head_dim (g_k)
    q, k = rope(pos; rope_theta): lane i paired with lane i + d/2, over
           the whole head (mrope_section with t = h = w = pos)
    qI = a WqI [T, J, d]   kI = layer_norm(a WkI; weight, bias) [T, d]
    wI = a Ww [T, J]       qI, kI = rope over all d
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])            s <= t
    S_t = the sa_config.topk positions s <= t of largest I[t, s], all of
          them while there are no more; ties to the lower s; I compared
          as a number (-0 is 0)
    o[t, h] = softmax over S_t of (q[t, h] . k[s, h // G] / sqrt(head_dim)) v
    x  = x + concat(o) Wo
    b  = rms(x; g_post)
    r  = softmax(b Wr) over all num_experts_routed; its
         num_experts_per_tok largest, renormalised (norm_topk_prob)
    x  = x + sum over the chosen experts in held_experts of
         c_e (silu(b Wg_e) * (b Wu_e)) Wd_e

After the last layer ``rms``, then ``logits = y W_head`` (untied) over
the rows of the vocabulary that are held.

Departures, each stated by the cut or made to fit one chip: the chip's
share (``held_experts`` of the routed experts have weights here, the
head and the embedding ``vocab_size`` rows); the weights are the
program's bfloat16 values raised to float32 where used (exact);
attention takes a block of queries and one key-value head at a time and
skips the blocks past ``length`` (the padding), a held expert runs on the
rows routed to it gathered into a room (``forward`` doubles it if one
overflowed). None changes a value beyond the order of float32 additions.
Every sequence is padded to one length so that one layer program serves
every request; loops are ``lax.map`` / ``lax.scan``.

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16 (both operands of every matrix
product rounded to float8 e4m3, one scale a tensor, products summed in
float32).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``dense`` (no selection: every key
attended), ``recent_topk`` (the most recent ``topk`` keys, not the
largest), ``no_relu``, ``index_keys_unrotated`` (``kI`` kept without its
rotation), ``topk_not_renormalised``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("dense", "recent_topk", "no_relu", "index_keys_unrotated",
          "topk_not_renormalised")
Q_BLOCK = 256       # queries a block of attention
POS_BLOCK = 256     # the head's positions come in whole blocks of this


def held_range(sizes: dict):
    lo, hi = sizes["held_experts"]
    return int(lo), int(hi)


def _fp8(x):
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


def _rms(x, weight, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def _layer_norm(x, weight, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * weight.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _rope(x, theta):
    """``x`` [T, heads, d]: lanes (i, i + d/2) turned by ``pos * theta **
    (-2i / d)``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def kept_keys(scores, seen, k: int):
    """bool [n, T]: of every row's entries marked ``seen``, the ``k`` of
    largest score, ties to the lower column; all of them where there are
    at most ``k``. No sort: the ``k``-th largest is found a bit at a time
    on an integer that orders as the scores do."""
    x = jnp.where(scores == 0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >= jnp.uint32(1 << 31), ~bits,
                    bits + jnp.uint32(1 << 31))
    key = jnp.where(seen, key, 0)

    def one_bit(i, thr):
        cand = thr + (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (key >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, one_bit,
                            jnp.zeros(key.shape[:1], jnp.uint32))[:, None]
    above, tie = key > thr, key == thr
    spare = k - above.sum(-1, keepdims=True)
    return seen & (above | (tie & (jnp.cumsum(tie, axis=-1) <= spare)))


def _attention(a, p, sizes, length, mode, faults):
    t = a.shape[0]
    n_q, n_kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    sa = dict(sizes["sa_config"])
    n_j, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                     sa["topk"])
    group = n_q // n_kv
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    q = _mm(a, p["q_proj"], mode).reshape(t, n_q, hd)
    k = _mm(a, p["k_proj"], mode).reshape(t, n_kv, hd)
    v = _mm(a, p["v_proj"], mode).reshape(t, n_kv, hd)
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    qi = _rope(_mm(a, p["index_q_proj"], mode).reshape(t, n_j, di), theta)
    ki = _layer_norm(_mm(a, p["index_k_proj"], mode),
                     p["index_k_norm_weight"], p["index_k_norm_bias"], eps)
    if "index_keys_unrotated" not in faults:
        ki = _rope(ki[:, None, :], theta)[:, 0]
    wi = _mm(a, p["index_weights_proj"], mode)                    # [T, J]
    block = math.gcd(t, Q_BLOCK)
    col = jnp.arange(t)[None, :]
    qg = jnp.moveaxis(q.reshape(t, n_kv, group, hd), 1, 0)
    kg, vg = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)

    def one_block(i0):
        row = i0 + jnp.arange(block)[:, None]
        seen = col <= row
        if "dense" in faults:
            keep = seen
        elif "recent_topk" in faults:
            keep = seen & (col > row - topk)
        else:
            qb = jax.lax.dynamic_slice_in_dim(qi, i0, block)
            wb = jax.lax.dynamic_slice_in_dim(wi, i0, block)
            s = _mm(qb.reshape(block * n_j, di), ki.T,
                    mode).reshape(block, n_j, t)
            if "no_relu" not in faults:
                s = jnp.maximum(s, 0.0)
            keep = kept_keys((s * wb[:, :, None]).sum(1), seen, topk)

        def one_head(args):
            q_h, k_h, v_h = args        # [T, group, hd], [T, hd], [T, hd]
            qb = jax.lax.dynamic_slice_in_dim(q_h, i0, block)
            s = _mm(qb.reshape(block * group, hd), k_h.T, mode)
            s = s.reshape(block, group, t) / math.sqrt(hd)
            s = jnp.where(keep[:, None, :], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return _mm(w.reshape(block * group, t), v_h,
                       mode).reshape(block, group, hd)

        y = jax.lax.map(one_head, (qg, kg, vg))      # [kv, block, G, hd]
        return jnp.moveaxis(y, 0, 1).reshape(block, n_q * hd)

    # the blocks past the sequence's own length are padding: nothing
    # reads them
    y = jax.lax.map(
        lambda i0: jax.lax.cond(
            i0 < length, one_block,
            lambda _i: jnp.zeros((block, n_q * hd), jnp.float32), i0),
        jnp.arange(0, t, block))
    return _mm(y.reshape(t, n_q * hd), p["o_proj"], mode)


def _experts(h, p, sizes, mode, faults, room):
    """``(routed [T, hidden], the fullest held expert's rows)``."""
    t = h.shape[0]
    lo, hi = held_range(sizes)
    k = int(sizes["num_experts_per_tok"])
    r = jax.nn.softmax(_mm(h, p["router"], mode), axis=-1)
    top_r, top_i = jax.lax.top_k(r, k)
    w = top_r
    if sizes["norm_topk_prob"] and "topk_not_renormalised" not in faults:
        w = top_r / top_r.sum(-1, keepdims=True)
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
        x = h_pad[rows]
        y_e = _mm(jax.nn.silu(_mm(x, w_gate, mode)) * _mm(x, w_up, mode),
                  w_down, mode)
        w_rows = jnp.concatenate([w_e, jnp.zeros((1,))])[rows]
        routed = routed.at[rows].add(y_e * w_rows[:, None], mode="drop")
        return (routed, fullest), None

    (routed, fullest), _ = jax.lax.scan(
        held_expert, (jnp.zeros_like(h), jnp.zeros((), jnp.int32)),
        (jnp.arange(hi - lo), p["gate_proj"], p["up_proj"], p["down_proj"]))
    return routed, fullest


def _freeze(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def _thaw(value):
    return {k: dict(v) if k == "sa_config" else v for k, v in value}


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "mode", "faults", "room"))
def _layer(x, p, length, sizes_key, mode, faults, room):
    sizes = _thaw(sizes_key)
    eps = float(sizes["rms_norm_eps"])
    a = _rms(x, p["input_layernorm"]["weight"], eps)
    x = x + _attention(a, p["self_attn"], sizes, length, mode, faults)
    b = _rms(x, p["post_attention_layernorm"]["weight"], eps)
    routed, fullest = _experts(b, p["mlp"], sizes, mode, faults, room)
    return x + routed, fullest


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, weight, head, eps, mode):
    return _mm(_rms(x, weight, eps), head, mode)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rope_theta", "rms_norm_eps", "num_experts_per_tok",
         "norm_topk_prob", "sa_config")


def forward(params, sizes: dict, tokens, positions, length=None,
            mode: str = "f32", faults=()):
    """Float32 logits [len(positions), vocab held] of the whole sequence
    ``tokens`` [T] at ``positions``; ``length``: the tokens before the
    padding (default: all). Attention takes ``gcd(T, Q_BLOCK)`` queries at
    a time: pad T to a round number, a causal model never looks ahead."""
    faults = tuple(sorted(faults))
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    key = _freeze({k: sizes[k] for k in _KEYS}
                  | {"held_experts": held_range(sizes)})
    tokens = jnp.asarray(tokens, jnp.int32)
    t = int(tokens.shape[0])
    length = jnp.asarray(t if length is None else length, jnp.int32)
    # a held expert's expected rows are T * k / E; twice that and a bit
    room = min(t, max(64, 2 * t * int(sizes["num_experts_per_tok"])
                      // int(sizes["num_experts_routed"])))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(int(sizes["num_hidden_layers"])):
        while True:
            y, fullest = _layer(x, params[f"layers_{i}"], length, key,
                                mode, faults, room)
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
                 params["lm_head"], float(sizes["rms_norm_eps"]), mode)[:n]


def served_logits(params, sizes: dict, prompt, served, *, pad_multiple: int,
                  mode: str = "f32", faults=()):
    """Logits [len(served), vocab held] at the positions whose next token
    was served: the last prompt position, then every served token but the
    last. The sequence is padded to a multiple of ``pad_multiple``."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    idx = np.zeros(-(-len(seq) // pad_multiple) * pad_multiple, np.int32)
    idx[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return forward(params, sizes, idx, pos, len(seq), mode, faults)


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
