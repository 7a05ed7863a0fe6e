"""The plain reference of Qwen3-Next (``model_type: qwen3_next``): a full
forward over a whole sequence in straightforward ``jax.numpy``, float32
with ``precision=HIGHEST`` matrix products, the gated delta rule TOKEN BY
TOKEN. No chunked form, no triangular inverse, no kernel, no cache, no
batching, nothing imported from the program: it is what ``correct`` is
decided against.

With ``rms0(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)`` and
layer ``i`` FULL where ``(i + 1) % full_attention_interval == 0``, else
DELTA, for a layer with input ``x`` [T, hidden] (``sizes`` is the
configuration file: ``config.json``'s keys and what ``assumed`` adds)::

    x = x + mixer(rms0(x; w_in));   x = x + moe(rms0(x; w_post))

    full (heads query heads over kv_heads key-value heads of head_dim):
      [q | gate] = a Wq      a head: head_dim of query, then head_dim of gate
      k = a Wk, v = a Wv;    q = rms0(q; w_q), k = rms0(k; w_k) a head
      rotary on lanes 0 .. head_dim * partial_rotary_factor - 1 of a head,
      lane j paired with lane j + half of them; the other lanes pass
      y = softmax(q k^T / sqrt(head_dim), causal) v
      out = (y * sigmoid(gate)) Wo

    delta (hk key heads, hv value heads; r = hv / hk):
      a key head g of a W_qkvz: [q_g | k_g | v_rg .. v_rg+r-1 | z_rg ..]
      a key head g of a W_ba:   [b_rg .. b_rg+r-1 | a_rg .. a_rg+r-1]
      (q, k, v)_t = silu(sum_j w[:, j] x_{t-3+j})  over the channels
                    [q | k | v], x before the sequence zeros
      q, k: each head over its l2 norm (eps 1e-6); q *= dk^-1/2;
      value head h reads key head h // r
      beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a + dt_bias))
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t
      out = concat_h(o_h / sqrt(mean(o_h^2) + eps) * w_n * silu(z_h)) W_out

    moe: p = softmax(u W_r) over all routed experts, float32
         chosen = the num_experts_per_tok largest; w = p / sum p[chosen]
         out = sum over the chosen experts in held_experts of
               w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
             + sigmoid(u w_sg) * the shared expert's (silu(u Wg) * (u Wu)) Wd

After the last layer ``rms0``, then ``logits = y W_head`` (untied) over
the rows of the vocabulary that are held.

Departures, each stated by the cut or made to fit one chip: the chip's
share (``held_experts`` of the routed experts have weights here, the head
and the embedding ``vocab_size`` rows); the weights are the program's
bfloat16 values raised to float32 where used (exact); attention takes one
head and a block of queries at a time against the keys up to the end of
the block's segment of ``KEY_SEGMENT`` positions; the projections and the
shared expert take blocks of rows; a held expert runs on the rows routed
to it, gathered a room of them at a time; the recurrence stops at
``length``. None changes a value beyond the order of float32 additions.
Every sequence is padded to one length so that one layer program serves
every request, and what lies at or past ``length`` (the padding) costs
next to nothing: its blocks are skipped and its rows are routed to no
expert.

``mode``: ``"f32"`` is the reference proper; ``"fp8"`` the control for a
configuration that states bfloat16 (both operands of every matrix product
rounded to float8 e4m3, one scale a tensor, products summed in float32;
the recurrence's own arithmetic, which multiplies no matrices, stays
float32).

``faults`` plants a wrong reading of the description (the tests hold the
comparison to catching each): ``norm_plain_weight`` (``rms0`` scaled by
``w``, not ``1 + w``), ``rotary_whole_head``, ``no_output_gate``,
``decay_after_correction`` (``S_t = alpha_t (S_{t-1} + beta_t k_t (v_t -
S_{t-1}^T k_t)^T)``), ``beta_on_v_only`` (``beta_t v_t - alpha_t S^T k``),
``shared_gate_left_out``, ``conv_inputs_dropped`` (the convolution
restarts from zeros every ``prefill_rows`` positions, as a prefill that
forgot its inputs between passes would), ``state_bf16`` (the state
rounded to bfloat16 after every token).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("norm_plain_weight", "rotary_whole_head", "no_output_gate",
          "decay_after_correction", "beta_on_v_only", "shared_gate_left_out",
          "conv_inputs_dropped", "state_bf16")
Q_BLOCK = 256       # queries a block of attention
KEY_SEGMENT = 4096  # a block's keys end where its segment of queries ends
ROW_BLOCK = 2048    # rows a block of a projection or of the shared expert
POS_BLOCK = 256     # the head's positions come in whole blocks of this


def held_range(sizes: dict):
    lo, hi = sizes["held_experts"]
    return int(lo), int(hi)


def is_full(sizes: dict, layer: int) -> bool:
    return (layer + 1) % int(sizes["full_attention_interval"]) == 0


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


def _rms0(x, weight, eps, faults=()):
    w = weight.astype(jnp.float32)
    if "norm_plain_weight" not in faults:
        w = 1.0 + w
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta, lanes):
    """``x`` [T, heads, d]: lanes (j, j + lanes/2) of the first ``lanes``
    turned by ``pos * theta ** (-2j / lanes)``; the others pass."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :lanes // 2], x[..., lanes // 2:lanes]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., lanes:]], axis=-1)


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


def _project(a, w, length, mode):
    return _rows(lambda ab: _mm(ab, w, mode), a, length, w.shape[1])


def _full_attention(a, p, sizes, length, mode, faults):
    t = a.shape[0]
    heads, kvh, hd = (sizes["num_attention_heads"],
                      sizes["num_key_value_heads"], sizes["head_dim"])
    group = heads // kvh
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    lanes = hd if "rotary_whole_head" in faults \
        else int(hd * float(sizes["partial_rotary_factor"]))
    qg = _project(a, p["q_proj"], length, mode).reshape(t, heads, 2, hd)
    q, gate = qg[:, :, 0], qg[:, :, 1]
    k = _project(a, p["k_proj"], length, mode).reshape(t, kvh, hd)
    v = _project(a, p["v_proj"], length, mode).reshape(t, kvh, hd)
    q = _rope(_rms0(q, p["q_norm"], eps, faults), theta, lanes)
    k = _rope(_rms0(k, p["k_norm"], eps, faults), theta, lanes)
    segment = KEY_SEGMENT if t % KEY_SEGMENT == 0 else t
    block = math.gcd(segment, Q_BLOCK)
    scale = 1.0 / math.sqrt(hd)

    def one_head(args):
        q_h, c = args                       # [T, hd], its key-value head
        k_h, v_h = k[:, c], v[:, c]

        def one_block(i0, keys):
            row = i0 + jnp.arange(block)[:, None]
            qb = jax.lax.dynamic_slice_in_dim(q_h, i0, block)
            s = _mm(qb, k_h[:keys].T, mode) * scale
            s = jnp.where(jnp.arange(keys)[None, :] <= row, s, -jnp.inf)
            return _mm(jax.nn.softmax(s, axis=-1), v_h[:keys], mode)

        # a segment's blocks of queries read the keys up to the segment's
        # end; the blocks past the sequence's own length are padding
        y = [jax.lax.map(
            lambda i0, keys=end: jax.lax.cond(
                i0 < length, functools.partial(one_block, keys=keys),
                lambda _i: jnp.zeros((block, hd), jnp.float32), i0),
            jnp.arange(end - segment, end, block))
            for end in range(segment, t + 1, segment)]
        return jnp.concatenate(y).reshape(t, hd)

    y = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0),
                               jnp.arange(heads) // group))
    y = jnp.moveaxis(y, 0, 1)                               # [T, heads, hd]
    if "no_output_gate" not in faults:
        y = y * jax.nn.sigmoid(gate)
    return _project(y.reshape(t, heads * hd), p["o_proj"], length, mode)


def _delta(a, p, sizes, length, mode, faults):
    t = a.shape[0]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps, r = int(sizes["linear_conv_kernel_dim"]), hv // hk
    eps = float(sizes["rms_norm_eps"])
    # the grouped layouts, a key head at a time
    mixed = _project(a, p["in_proj_qkvz"], length, mode).reshape(
        t, hk, 2 * dk + 2 * r * dv)
    q = mixed[:, :, :dk].reshape(t, hk * dk)
    k = mixed[:, :, dk:2 * dk].reshape(t, hk * dk)
    v = mixed[:, :, 2 * dk:2 * dk + r * dv].reshape(t, hv * dv)
    z = mixed[:, :, 2 * dk + r * dv:].reshape(t, hv, dv)
    ba = _project(a, p["in_proj_ba"], length, mode).reshape(t, hk, 2 * r)
    b, a_in = ba[:, :, :r].reshape(t, hv), ba[:, :, r:].reshape(t, hv)
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(a_in
                                      + p["dt_bias"].astype(jnp.float32)))
    # the convolution as a sum of four shifted products
    x = jnp.concatenate([q, k, v], axis=1)
    w = p["conv1d"].astype(jnp.float32)                     # [ch, taps]
    pos = jnp.arange(t)[:, None]
    y = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1])),
                                   x[:t - back]]) if back else x
        if "conv_inputs_dropped" in faults:
            shifted = jnp.where(pos % int(sizes["prefill_rows"]) >= back,
                                shifted, 0.0)
        y = y + shifted * w[:, j]
    y = jax.nn.silu(y)

    def l2(u):
        return u * jax.lax.rsqrt(jnp.square(u).sum(-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(y[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5, r,
                   axis=1)
    k = jnp.repeat(l2(y[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)), r,
                   axis=1)
    v = y[:, 2 * hk * dk:].reshape(t, hv, dv)

    def token(i, carry):
        s, out = carry                                      # [hv, dk, dv]
        al, be = alpha[i][:, None, None], beta[i][:, None]
        if "decay_after_correction" in faults:
            read = jnp.einsum("hkv,hk->hv", s, k[i], precision=HIGHEST)
            s = al * (s + k[i][:, :, None] * (be * (v[i] - read))[:, None])
        else:
            s = al * s
            read = jnp.einsum("hkv,hk->hv", s, k[i], precision=HIGHEST)
            delta = (be * v[i] - read if "beta_on_v_only" in faults
                     else be * (v[i] - read))
            s = s + k[i][:, :, None] * delta[:, None, :]
        if "state_bf16" in faults:
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        o = jnp.einsum("hkv,hk->hv", s, q[i], precision=HIGHEST)
        return s, jax.lax.dynamic_update_slice_in_dim(out, o[None], i, 0)

    _, o = jax.lax.fori_loop(
        0, length, token, (jnp.zeros((hv, dk, dv), jnp.float32),
                           jnp.zeros((t, hv, dv), jnp.float32)))
    o = o / jnp.sqrt(jnp.square(o).mean(-1, keepdims=True) + eps) \
        * p["norm"].astype(jnp.float32) * jax.nn.silu(z)
    return _project(o.reshape(t, hv * dv), p["out_proj"], length, mode)


def _swiglu(h, w_gate, w_up, w_down, length, mode):
    def rows(hb):
        return _mm(jax.nn.silu(_mm(hb, w_gate, mode)) * _mm(hb, w_up, mode),
                   w_down, mode)

    return _rows(rows, h, length, w_down.shape[1])


def _experts(h, p, w_sg, sizes, length, mode, faults, room):
    """Routed (the held experts' share) + the gated shared expert."""
    t = h.shape[0]
    lo, hi = held_range(sizes)
    k = int(sizes["num_experts_per_tok"])
    probs = jax.nn.softmax(_mm(h, p["router"], mode), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    w = top_p / top_p.sum(-1, keepdims=True) if sizes["norm_topk_prob"] \
        else top_p
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
    shared = _swiglu(h, p["shared_gate_proj"][0], p["shared_up_proj"][0],
                     p["shared_down_proj"][0], length, mode)
    if "shared_gate_left_out" not in faults:
        shared = shared * jax.nn.sigmoid(_mm(h, w_sg, mode))
    return routed + shared


@functools.partial(jax.jit, static_argnames=(
    "sizes_key", "mode", "faults", "room", "full"))
def _layer(x, p, length, sizes_key, mode, faults, room, full):
    sizes = dict(sizes_key)
    eps = float(sizes["rms_norm_eps"])
    a = _rms0(x, p["input_layernorm"]["weight"], eps, faults)
    if full:
        x = x + _full_attention(a, p["self_attn"], sizes, length, mode,
                                faults)
    else:
        x = x + _delta(a, p["linear_attn"], sizes, length, mode, faults)
    u = _rms0(x, p["post_attention_layernorm"]["weight"], eps, faults)
    return x + _experts(u, p["mlp"], p["shared_expert_gate"], sizes, length,
                        mode, faults, room)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "faults"))
def _head(x, weight, head, eps, mode, faults):
    return _mm(_rms0(x, weight, eps, faults), head, mode)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "partial_rotary_factor", "rope_theta", "rms_norm_eps",
         "full_attention_interval", "linear_conv_kernel_dim",
         "linear_key_head_dim", "linear_num_key_heads",
         "linear_num_value_heads", "linear_value_head_dim",
         "num_experts_per_tok", "norm_topk_prob")


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
    key = tuple((k, sizes[k]) for k in _KEYS) + (
        ("held_experts", held_range(sizes)),
        ("prefill_rows", int(sizes.get("prefill_rows", 4096))))
    tokens = jnp.asarray(tokens, jnp.int32)
    t = int(tokens.shape[0])
    length = jnp.asarray(t if length is None else length, jnp.int32)
    # a held expert's expected rows are T * k / E; a room is twice that
    room = min(t, max(64, 2 * t * int(sizes["num_experts_per_tok"])
                      // int(sizes["num_experts_published"])))
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(int(sizes["num_hidden_layers"])):
        x = _layer(x, params[f"layers_{i}"], length, key, mode, faults, room,
                   is_full(sizes, i))
    # the head takes whole blocks of positions (the last repeated), so
    # that requests of any length share a few compiled programs
    positions = np.asarray(positions)
    n = len(positions)
    padded = np.full(-(-n // POS_BLOCK) * POS_BLOCK, positions[-1])
    padded[:n] = positions
    return _head(x[jnp.asarray(padded)], params["norm"]["weight"],
                 params["lm_head"], float(sizes["rms_norm_eps"]), mode,
                 faults)[:n]


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
