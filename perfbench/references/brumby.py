"""The plain reference of Brumby-14B-Base (``model_type: brumby``): a full
forward over a whole sequence in straightforward ``jax.numpy``, float32
with ``precision=HIGHEST`` matrix products, power retention in its
ATTENTION form. No state, no recurrence, no feature map, no chunks, no
cache, no batching, nothing imported from the program (which runs the
recurrence and the chunked form): it is what ``correct`` is decided
against.

The equations, for a layer with input ``x`` [T, hidden] (``sizes`` is the
configuration file: ``config.json``'s keys and what ``assumed`` adds)::

    a   = rms(x; g_in)                 x / sqrt(mean(x^2) + rms_norm_eps) * g
    q   = a Wq [T, heads, head_dim]    k = a Wk, v = a Wv [T, kv_heads, head_dim]
    q   = rms over head_dim (g_q)      k = rms over head_dim (g_k)
    q, k = rope(pos; rope_theta): lane i paired with lane i + d/2
    gam = log sigmoid(a Wg + bg) [T, kv_heads]        Gam = cumsum(gam)
    w[t, s] = exp(Gam_t - Gam_s) (q_t[h] . k_s[h // G] / sqrt(head_dim))^2,  s <= t
    y[t, h] = sum_s w[t, s] v_s / (sum_s w[t, s] + retention_eps)
    x   = x + concat(y) Wo
    b   = rms(x; g_post)
    x   = x + (silu(b Wgate) * (b Wup)) Wdown

After the last layer ``rms``, then ``logits = y W_head`` (untied).

Departures, each made to fit one chip: the weights are the program's
bfloat16 values raised to float32 where used (exact); attention takes a
block of queries and one key-value head at a time and skips the blocks
past ``length`` (the padding); the SwiGLU takes a block of rows at a
time and the head an eighth of the vocabulary. None changes a value beyond the order of float32 additions. Every
sequence is padded to one length so that one layer program serves every
request; loops are ``lax.map``.

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16 (both operands of every matrix
product rounded to float8 e4m3, one scale a tensor, products summed in
float32).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``degree_1`` (the score not squared),
``no_gate`` (no decay), ``gate_on_entering_key`` (the key of step ``s``
decayed by its own gate too: ``exp(Gam_t - Gam_{s-1})``),
``no_normaliser`` (the weighted sum not divided), ``gate_per_query_head``
(the gates dealt to the query heads one each, head ``h`` taking gate ``h
mod kv_heads``, not shared by a group).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("degree_1", "no_gate", "gate_on_entering_key", "no_normaliser",
          "gate_per_query_head")
Q_BLOCK = 256       # queries a block of attention
ROW_BLOCK = 2048    # rows a block of the SwiGLU
POS_BLOCK = 256     # the head's positions come in whole blocks of this


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


def _retention(a, p, sizes, length, mode, faults):
    t = a.shape[0]
    n_q, n_kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    group = n_q // n_kv
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    eps_n = float(sizes["retention_eps"])
    q = _mm(a, p["q_proj"], mode).reshape(t, n_q, hd)
    k = _mm(a, p["k_proj"], mode).reshape(t, n_kv, hd)
    v = _mm(a, p["v_proj"], mode).reshape(t, n_kv, hd)
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    gam = jax.nn.log_sigmoid(_mm(a, p["g_proj"], mode)
                             + p["g_bias"].astype(jnp.float32))  # [T, kv]
    if "no_gate" in faults:
        gam = jnp.zeros_like(gam)
    cum = jnp.cumsum(gam, axis=0)                                # Gam_t
    # the decay a key carries: Gam_s, or Gam_{s-1} where the entering key
    # is (wrongly) decayed by its own gate
    cum_key = cum - gam if "gate_on_entering_key" in faults else cum
    # the gate each query head reads: its key-value head's
    heads = np.arange(n_q)
    gate_of = (heads % n_kv if "gate_per_query_head" in faults
               else heads // group)
    block = math.gcd(t, Q_BLOCK)
    col = jnp.arange(t)[None, :]

    def one_block(i0):
        row = i0 + jnp.arange(block)[:, None]
        seen = col <= row                                   # [block, T]

        def one_head(h):
            c = h // group
            g = jnp.asarray(gate_of)[h]
            qb = jax.lax.dynamic_slice_in_dim(q[:, h], i0, block)
            s = _mm(qb, k[:, c].T, mode) / math.sqrt(hd)    # [block, T]
            s = s if "degree_1" in faults else jnp.square(s)
            cum_q = jax.lax.dynamic_slice_in_dim(cum[:, g], i0, block)
            decay = jnp.exp(jnp.where(
                seen, cum_q[:, None] - cum_key[:, g][None, :], -jnp.inf))
            w = s * decay
            num = _mm(w, v[:, c], mode)                     # [block, hd]
            if "no_normaliser" in faults:
                return num
            return num / (w.sum(-1, keepdims=True) + eps_n)

        y = jax.lax.map(one_head, jnp.arange(n_q))      # [heads, block, hd]
        return jnp.moveaxis(y, 0, 1).reshape(block, n_q * hd)

    # the blocks past the sequence's own length are padding: nothing
    # reads them
    y = jax.lax.map(
        lambda i0: jax.lax.cond(
            i0 < length, one_block,
            lambda _i: jnp.zeros((block, n_q * hd), jnp.float32), i0),
        jnp.arange(0, t, block))
    return _mm(y.reshape(t, n_q * hd), p["o_proj"], mode)


def _swiglu(h, p, mode):
    t = h.shape[0]
    block = math.gcd(t, ROW_BLOCK)

    def rows(hb):
        return _mm(jax.nn.silu(_mm(hb, p["gate_proj"], mode))
                   * _mm(hb, p["up_proj"], mode), p["down_proj"], mode)

    return jax.lax.map(rows, h.reshape(t // block, block, -1)).reshape(
        t, -1)


def _freeze(sizes: dict, keys) -> tuple:
    return tuple((k, sizes[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode", "faults"))
def _layer(x, p, length, sizes_key, mode, faults):
    sizes = dict(sizes_key)
    eps = float(sizes["rms_norm_eps"])
    a = _rms(x, p["input_layernorm"]["weight"], eps)
    x = x + _retention(a, p["self_attn"], sizes, length, mode, faults)
    b = _rms(x, p["post_attention_layernorm"]["weight"], eps)
    return x + _swiglu(b, p["mlp"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, weight, head, eps, mode):
    """The final norm and the head, an eighth of the vocabulary at a time
    (the head whole in float32 is 3.1 GB beside 8.4 GB of weights); the
    fp8 control keeps its one scale a tensor."""
    y = _rms(x, weight, eps)
    c, v = head.shape
    n = 8 if v % 8 == 0 else 1
    cols = jnp.moveaxis(head.reshape(c, n, v // n), 1, 0)
    if mode == "fp8":
        yq, sy = _fp8(y)
        sw = jnp.maximum(jnp.max(jnp.abs(head)).astype(jnp.float32),
                         1e-30) / 448.0

        def part(w):
            wq = (w.astype(jnp.float32) / sw).astype(jnp.float8_e4m3fn)
            return jnp.matmul(yq.astype(jnp.bfloat16),
                              wq.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32) * (sy * sw)
    else:
        def part(w):
            return jnp.matmul(y, w.astype(jnp.float32), precision=HIGHEST)
    out = jax.lax.map(part, cols)                       # [n, rows, v / n]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rope_theta", "rms_norm_eps", "retention_eps")


def forward(params, sizes: dict, tokens, positions, length=None,
            mode: str = "f32", faults=()):
    """Float32 logits [len(positions), vocab] of the whole sequence
    ``tokens`` [T] at ``positions``; ``length``: the tokens before the
    padding (default: all). Attention takes ``gcd(T, Q_BLOCK)`` queries at
    a time: pad T to a round number, a causal model never looks ahead."""
    faults = tuple(sorted(faults))
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    key = _freeze(sizes, _KEYS)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = int(tokens.shape[0])
    length = jnp.asarray(t if length is None else length, jnp.int32)
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(int(sizes["num_hidden_layers"])):
        x = _layer(x, params[f"layers_{i}"], length, key, mode, faults)
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
    """Logits [len(served), vocab] at the positions whose next token was
    served: the last prompt position, then every served token but the
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
    ref = np.asarray(ref)
    if mode == "f32" and not faults:
        tokens = np.asarray(served, np.int64)
    else:
        tokens = np.asarray(jnp.argmax(
            served_logits(params, sizes, prompt, served,
                          pad_multiple=pad_multiple, mode=mode,
                          faults=faults), axis=-1))
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return best - got
