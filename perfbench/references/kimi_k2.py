"""The plain reference of Kimi-K2's language model (``model_type:
kimi_k2``, the DeepSeek-V3 block): a full forward over a whole sequence in
straightforward ``jax.numpy``, float32 with ``precision=HIGHEST`` matrix
products, the EXPANDED form of the latent attention only. No kernel, no
cache, no batching, no absorbed product, nothing imported from the
program: it is what ``correct`` is decided against.

The equations, for a layer with input ``x`` [T, hidden] (``sizes`` is the
configuration file: ``config.json``'s keys)::

    a    = rms(x; g_in)              x / sqrt(mean(x^2) + rms_norm_eps) * g
    c_q  = rms(a W_dq; g_q)                          q_lora_rank (1,536)
    [q_nope ; q_rope] = c_q W_uq     a head: qk_nope + qk_rope (128 + 64)
    [c_kv ; k_r] = a W_dkv           kv_lora_rank + qk_rope (512 + 64)
    c_kv = rms(c_kv; g_kv)
    q_rope = rot(q_rope)   k_rope = rot(k_r), ONE for all heads
    [k_nope ; v] = c_kv W_ukv        a head: qk_nope + v_head (128 + 128)
    s[t, u] = scale (q_nope[t] . k_nope[u] + q_rope[t] . k_rope[u])  u <= t
    y[t]  = sum_u softmax_u(s[t, u]) v[u]     out = concat_heads(y) W_o
    x    = x + out
    b    = rms(x; g_post)
    layer < first_k_dense_replace:
        x = x + (silu(b Wg) * (b Wu)) Wd                         18,432
    else:
        g = sigmoid(b W_r)           float32, all n_routed_experts (384)
        chosen = the num_experts_per_tok largest of g + bias     (8)
        w = g[chosen] / sum g[chosen]          the bias is in no weight
        x = x + routed_scaling_factor * sum over the chosen experts in
                held_experts of w_e (silu(b Wg_e) * (b Wu_e)) Wd_e
              + the shared expert's (silu(b Wg_s) * (b Wu_s)) Wd_s

``rot`` turns the pairs ``(2i, 2i + 1)`` of its 64 lanes at position ``t``
by ``t f_i`` with yarn's frequencies: ``f_i = theta^(-2i/64)`` for the
pairs that turn more than ``beta_fast`` times in
``original_max_position_embeddings`` positions, that over ``factor`` for
those that turn fewer than ``beta_slow`` times, a linear ramp between the
two dimensions where those turn counts fall; its gain
``yarn(factor, mscale) / yarn(factor, mscale_all_dim)`` is 1 here.
``scale = (qk_nope + qk_rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1``. After the last layer ``rms``, then ``logits = y W_head``
(untied) over the rows of the vocabulary that are held.
``num_nextn_predict_layers`` is 0 and ``n_group = topk_group = 1``:
nothing is left out and no group limit applies.

Departures, each stated by the cut or made to fit one chip: the chip's
share (``held_experts`` of the routed experts have weights here, the head
and the embedding ``vocab_size`` rows); the weights are the program's
bfloat16 values raised to float32 where used (exact); attention takes one
head and a block of queries at a time against the keys up to the end of
the block's segment of ``KEY_SEGMENT`` positions (no query looks past its
own position, so the softmax is over the same scores); the output
projection, the dense layer and the shared expert take blocks of rows; a
held expert runs on the rows routed to it, gathered a room of them at a
time (as many times as they fill one: a seed's router may send one held
expert five times its share of a long sequence). None changes a value
beyond the order of float32 additions. Every sequence is padded to one
length so that one layer program serves every request, and what lies at
or past ``length`` (the padding) costs next to nothing: its blocks of
queries and of rows are skipped and its rows are routed to no expert
(rows that are all alike would all take the same eight experts).

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16 (both operands of every matrix product
rounded to float8 e4m3, one scale a tensor, products summed in float32).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``k_rope_unrotated``, ``kv_norm_skipped``,
``w_uk_not_transposed`` (a head's up-projection of the keys read in the
other matrix order), ``mscale_left_out``, ``bias_as_weight`` (the chosen
experts weighted by ``g + bias``), ``routed_scale_left_out``,
``dense_layer_given_experts`` (the leading layer run with the next
layer's experts).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("k_rope_unrotated", "kv_norm_skipped", "w_uk_not_transposed",
          "mscale_left_out", "bias_as_weight", "routed_scale_left_out",
          "dense_layer_given_experts")
Q_BLOCK = 256       # queries a block of attention
KEY_SEGMENT = 4096  # a block's keys end where its segment of queries ends
ROW_BLOCK = 2048    # rows a block of the output projection or a SwiGLU
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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """The ``dim / 2`` frequencies ``f_i`` of the docstring."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = float(scaling["factor"])
    if factor <= 1:
        return base.astype(np.float32)
    original = float(scaling["original_max_position_embeddings"])

    def dim_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_turning(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(dim_turning(float(scaling["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (base / factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def _rot(x, freqs, gain):
    """``x`` [T, heads, d]: the pairs (2i, 2i + 1) turned by ``t f_i``."""
    t, heads, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(t, heads, d // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(t, heads, d) * gain


def _rows(fn, h, length, width):
    """``fn`` over ``h`` [T, ...] a block of rows at a time -> [T, width];
    the blocks at or past ``length`` are padding and read as zeros."""
    t = h.shape[0]
    block = math.gcd(t, ROW_BLOCK)
    out = jax.lax.map(
        lambda args: jax.lax.cond(
            args[0] < length, fn,
            lambda _h: jnp.zeros((block, width), jnp.float32), args[1]),
        (jnp.arange(0, t, block), h.reshape(t // block, block, -1)))
    return out.reshape(t, width)


def _attention(a, p, sizes, length, mode, faults):
    t = a.shape[0]
    heads, rq, r = (sizes["num_attention_heads"], sizes["q_lora_rank"],
                    sizes["kv_lora_rank"])
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    eps = float(sizes["rms_norm_eps"])
    scaling = dict(sizes["rope_scaling"])
    factor = float(scaling["factor"])
    freqs = yarn_frequencies(rope, float(sizes["rope_theta"]), scaling)
    gain = (yarn_mscale(factor, float(scaling["mscale"]))
            / yarn_mscale(factor, float(scaling["mscale_all_dim"])))
    m = yarn_mscale(factor, float(scaling["mscale_all_dim"]))
    scale = 1.0 / math.sqrt(nope + rope)
    if "mscale_left_out" not in faults:
        scale = scale * m * m

    c_q = _rms(_mm(a, p["q_a_proj"], mode), p["q_a_layernorm"], eps)
    q = _mm(c_q, p["q_b_proj"], mode).reshape(t, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rot(q[..., nope:], freqs, gain)
    ckv = _mm(a, p["kv_a_proj_with_mqa"], mode)
    c_kv, k_r = ckv[:, :r], ckv[:, r:]
    if "kv_norm_skipped" not in faults:
        c_kv = _rms(c_kv, p["kv_a_layernorm"], eps)
    k_rope = k_r if "k_rope_unrotated" in faults \
        else _rot(k_r[:, None, :], freqs, gain)[:, 0]
    w_ukv = jnp.moveaxis(p["kv_b_proj"].reshape(r, heads, nope + dv), 1, 0)
    segment = KEY_SEGMENT if t % KEY_SEGMENT == 0 else t
    block = math.gcd(segment, Q_BLOCK)

    def one_head(args):
        qn_h, qr_h, w_h = args          # [T, nope], [T, rope], [r, nope+dv]
        w_uk, w_uv = w_h[:, :nope], w_h[:, nope:]
        if "w_uk_not_transposed" in faults:
            w_uk = w_uk.reshape(nope, r).T
        k_h = _mm(c_kv, w_uk, mode)                          # [T, nope]
        v_h = _mm(c_kv, w_uv, mode)                          # [T, dv]

        def one_block(i0, keys):
            row = i0 + jnp.arange(block)[:, None]
            qn = jax.lax.dynamic_slice_in_dim(qn_h, i0, block)
            qr = jax.lax.dynamic_slice_in_dim(qr_h, i0, block)
            s = (_mm(qn, k_h[:keys].T, mode)
                 + _mm(qr, k_rope[:keys].T, mode)) * scale
            s = jnp.where(jnp.arange(keys)[None, :] <= row, s, -jnp.inf)
            return _mm(jax.nn.softmax(s, axis=-1), v_h[:keys], mode)

        # a segment's blocks of queries read the keys up to the segment's
        # end; the blocks past the sequence's own length are padding:
        # nothing reads them
        y = [jax.lax.map(
            lambda i0, keys=end: jax.lax.cond(
                i0 < length, functools.partial(one_block, keys=keys),
                lambda _i: jnp.zeros((block, dv), jnp.float32), i0),
            jnp.arange(end - segment, end, block))
            for end in range(segment, t + 1, segment)]
        return jnp.concatenate(y).reshape(t, dv)

    y = jax.lax.map(one_head, (jnp.moveaxis(q_nope, 1, 0),
                               jnp.moveaxis(q_rope, 1, 0), w_ukv))
    return _rows(lambda yb: _mm(yb, p["o_proj"], mode),
                 jnp.moveaxis(y, 0, 1).reshape(t, heads * dv), length,
                 p["o_proj"].shape[1])


def _swiglu(h, w_gate, w_up, w_down, length, mode):
    def rows(hb):
        return _mm(jax.nn.silu(_mm(hb, w_gate, mode)) * _mm(hb, w_up, mode),
                   w_down, mode)

    return _rows(rows, h, length, w_down.shape[1])


def _experts(h, p, sizes, length, mode, faults, room):
    """Routed (the held experts' share) + shared [T, hidden]."""
    t = h.shape[0]
    lo, hi = held_range(sizes)
    k = int(sizes["num_experts_per_tok"])
    g = jax.nn.sigmoid(_mm(h, p["router"], mode))
    biased = g + p["e_score_correction_bias"].astype(jnp.float32)
    _, top_i = jax.lax.top_k(biased, k)
    top_g = jnp.take_along_axis(
        biased if "bias_as_weight" in faults else g, top_i, axis=-1)
    w = top_g / top_g.sum(-1, keepdims=True) if sizes["norm_topk_prob"] \
        else top_g
    h_pad = jnp.concatenate([h, jnp.zeros((1, h.shape[1]))])
    live = (jnp.arange(t) < length)[:, None]    # the padding takes no room

    def held_expert(routed, args):
        e, w_gate, w_up, w_down = args
        chosen = (top_i == lo + e) & live                        # [T, k]
        w_e = jnp.concatenate([jnp.where(chosen, w, 0.0).sum(-1),
                               jnp.zeros((1,))])
        mine = chosen.any(-1)                   # the rows routed to it
        place = jnp.cumsum(mine) - 1            # a row's place among them

        def one_room(j, routed):
            # the j-th `room` of those rows, gathered; row t is a row of
            # zeros that spare places point at
            rows = jnp.nonzero(mine & (place // room == j), size=room,
                               fill_value=t)[0]
            x = h_pad[rows]
            y_e = _mm(jax.nn.silu(_mm(x, w_gate, mode)) * _mm(x, w_up, mode),
                      w_down, mode)
            return routed.at[rows].add(y_e * w_e[rows][:, None], mode="drop")

        return jax.lax.fori_loop(0, -(-mine.sum() // room), one_room,
                                 routed), None

    routed, _ = jax.lax.scan(
        held_expert, jnp.zeros_like(h),
        (jnp.arange(hi - lo), p["gate_proj"], p["up_proj"], p["down_proj"]))
    if "routed_scale_left_out" not in faults:
        routed = routed * float(sizes["routed_scaling_factor"])
    shared = jnp.zeros_like(h)
    for s in range(int(sizes["n_shared_experts"])):
        shared = shared + _swiglu(h, p["shared_gate_proj"][s],
                                  p["shared_up_proj"][s],
                                  p["shared_down_proj"][s], length, mode)
    # n_shared_experts is one expert of n_shared * moe_intermediate_size
    # in the published code: with 1 it is one expert, added whole
    return routed + shared


def _freeze(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def _thaw(value):
    return {k: dict(v) if k == "rope_scaling" else v for k, v in value}


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "mode", "faults", "room", "dense"))
def _layer(x, p, p_mlp, length, sizes_key, mode, faults, room, dense):
    sizes = _thaw(sizes_key)
    eps = float(sizes["rms_norm_eps"])
    a = _rms(x, p["input_layernorm"]["weight"], eps)
    x = x + _attention(a, p["self_attn"], sizes, length, mode, faults)
    b = _rms(x, p["post_attention_layernorm"]["weight"], eps)
    if dense:
        return x + _swiglu(b, p_mlp["gate_proj"], p_mlp["up_proj"],
                           p_mlp["down_proj"], length, mode)
    return x + _experts(b, p_mlp, sizes, length, mode, faults, room)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, weight, head, eps, mode):
    return _mm(_rms(x, weight, eps), head, mode)


_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_theta", "rope_scaling", "rms_norm_eps",
         "num_experts_per_tok", "norm_topk_prob", "n_shared_experts",
         "routed_scaling_factor")


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
    first_dense = int(sizes["first_k_dense_replace"])
    # a held expert's expected rows are T * k / E; a room is twice that
    room = min(t, max(64, 2 * t * int(sizes["num_experts_per_tok"])
                      // int(sizes["n_routed_experts_published"])))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(int(sizes["num_hidden_layers"])):
        p = params[f"layers_{i}"]
        dense = i < first_dense
        p_mlp = p["mlp"]
        if dense and "dense_layer_given_experts" in faults:
            dense, p_mlp = False, params[f"layers_{first_dense}"]["mlp"]
        x = _layer(x, p, p_mlp, length, key, mode, faults, room, dense)
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
