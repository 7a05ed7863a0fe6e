"""Fused causal attention for small/medium contexts — custom Pallas kernel.

Why this exists: the dense XLA path materializes f32 logits and probs
([B, H, T, T]) in HBM on both the forward and backward pass; for the
simulator's many-replica workloads (64 vmapped nodes) that attention
traffic dominates the step time. JAX's bundled flash kernel
(`jax.experimental.pallas.ops.tpu.flash_attention`) tiles for long
sequences and large head dims and is overhead-bound at the reference's
shapes (T ≤ 1024, head_dim 32-64).

This kernel fuses mask→softmax→PV entirely in VMEM and stores only the
output and the log-sum-exp; the backward pass recomputes probabilities from
(q, k, lse) — the flash-attention-2 recipe — so probs never touch HBM in
either direction. Each grid program processes a *chunk of batch rows* for
one head with batched MXU dots (grid = [B/bc, H]); chunk size adapts so the
f32 score block stays ≤ ~4 MB of VMEM. Composes with vmap (the
simulated-node axis) through Pallas' standard batching rule, which folds
the vmapped axis into the grid.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# Set True (e.g. from tests) to run kernels in the Pallas
# interpreter — enables CPU parity testing of the TPU kernels.
INTERPRET = False
# budget for ONE [bc, T, T] f32 score block; 3-4 such temporaries are live
# simultaneously (s, p, dp, plus spills) against the 16 MB scoped-VMEM limit
_VMEM_SCORE_BYTES = 1024 * 1024


def _batch_chunk(b: int, t: int) -> int:
    per_row = t * t * 4
    bc = max(1, _VMEM_SCORE_BYTES // per_row)
    while b % bc:
        bc -= 1
    return bc


def _causal(t):
    pos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return pos >= kpos


def _bdot(a, b, dims, prec=jnp.float32):
    """Batched dot over leading axis: a [bc, M, K'], b [bc, ...]."""
    return jax.lax.dot_general(a, b, (dims, ((0,), (0,))),
                               preferred_element_type=prec)


def _bh_spec(bc, t, d):
    return pl.BlockSpec((bc, 1, t, d), lambda i, h: (i, h, 0, 0),
                        memory_space=pltpu.VMEM)


def _lse_spec(bc, t):
    # [B, H, T, 1]: trailing singleton keeps the block 2-D-tileable
    return pl.BlockSpec((bc, 1, t, 1), lambda i, h: (i, h, 0, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_causal_attention(q, k, v, scale=None):
    """softmax(mask(QKᵀ·scale))·V, fully fused on-chip. [B, H, T, D],
    T ≤ 1024 (score block must fit VMEM), no dropout. The whole-context
    causal case of the block kernels below (dlse = 0)."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    o, _ = _blk_fwd(q, k, v, scale, True)
    return o


def _vjp_fwd(q, k, v, scale):
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    o, lse = _blk_fwd(q, k, v, scale, True)
    return o, (q, k, v, o, lse)


def _vjp_bwd(scale, res, do):
    q, k, v, o, lse = res
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _blk_bwd(q, k, v, o, do, lse, jnp.zeros_like(lse),
                          scale, True)
    return dq, dk, dv


fused_causal_attention.defvjp(_vjp_fwd, _vjp_bwd)


def fused_supported(q) -> bool:
    t = q.shape[-2]
    return t <= 1024 and t % 128 == 0


def packed_supported(q, n_head: int) -> bool:
    """Eligibility for the packed [B, T, C] kernels: unlike the per-head
    [B, H, T, D] layout, a packed program keeps ALL heads' rows in VMEM at
    once, so at GPT-2-base shapes (T=1024, C=768) it exceeds the 16 MB
    scoped-VMEM limit. Estimate the backward pass's live set at the chosen
    batch chunk and reject anything near the limit."""
    b, t, c = q.shape[0], q.shape[-2], q.shape[-1]
    if not (fused_supported(q) and c % n_head == 0):
        return False
    bc = _packed_chunk(b, t)
    # bwd live set: 8 packed tensors at the input dtype (the kernels dot
    # at native dtype — no f32 working copies) + f32 s/p/dp score blocks
    vmem = 8 * bc * t * c * q.dtype.itemsize + 3 * bc * t * t * 4
    return vmem <= 10 * 1024 * 1024


# -- block kernels: (o, lse) with differentiable lse ----------------------
#
# The ONE implementation of the FA2 math here: `fused_causal_attention`
# above is the causal whole-context case (dlse = 0), and the ring
# schedule (parallel/ring_attention.py) uses both variants per block,
# merging results in log-sum-exp space: out = Σ_b o_b · exp(lse_b −
# lse_tot). That makes lse a *differentiable* output (∂lse/∂s = p), so
# the backward extends FA2 with the lse cotangent:
# ds = p·(dp − delta + dlse). `causal=False` computes the full
# (un-masked) block — the shape of every non-diagonal ring step.
#
# Dots take the inputs' native dtype (bf16 under autocast) and
# accumulate f32 via preferred_element_type — bit-identical to upcasting
# first (bf16×bf16 products are exact in f32) but runs the MXU at bf16
# rate; the recomputed probs p and score gradient ds are cast back to
# that dtype before their dots (the FA2 precision convention).


def _blk_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal):
    q = q_ref[:, 0]
    k = k_ref[:, 0]
    v = v_ref[:, 0]
    t = q.shape[1]
    s = _bdot(q, k, (((2,), (2,)))) * scale
    if causal:
        s = jnp.where(_causal(t)[None], s, NEG)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=2, keepdims=True)
    lse_ref[:, 0] = m + jnp.log(l)
    o = _bdot((p / l).astype(v.dtype), v, ((2,), (1,)))
    o_ref[:, 0] = o.astype(o_ref.dtype)


def _blk_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dlse_ref,
                    dq_ref, dk_ref, dv_ref, *, scale, causal):
    q = q_ref[:, 0]
    k = k_ref[:, 0]
    v = v_ref[:, 0]
    o = o_ref[:, 0]
    do = do_ref[:, 0]
    lse = lse_ref[:, 0]
    dlse = dlse_ref[:, 0]                         # [bc, T, 1] f32
    t = q.shape[1]
    s = _bdot(q, k, ((2,), (2,))) * scale
    if causal:
        s = jnp.where(_causal(t)[None], s, NEG)
    p = jnp.exp(s - lse)
    dv = _bdot(p.astype(do.dtype), do, ((1,), (1,)))
    dp = _bdot(do, v, ((2,), (2,)))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=2, keepdims=True)
    ds = (p * (dp - delta + dlse) * scale).astype(q.dtype)
    dq = _bdot(ds, k, ((2,), (1,)))
    dk = _bdot(ds, q, ((1,), (1,)))
    dq_ref[:, 0] = dq.astype(dq_ref.dtype)
    dk_ref[:, 0] = dk.astype(dk_ref.dtype)
    dv_ref[:, 0] = dv.astype(dv_ref.dtype)


def _blk_fwd(q, k, v, scale, causal):
    b, h, t, d = q.shape
    bc = _batch_chunk(b, t)
    return pl.pallas_call(
        functools.partial(_blk_fwd_kernel, scale=scale, causal=causal),
        grid=(b // bc, h),
        in_specs=[_bh_spec(bc, t, d)] * 3,
        out_specs=[_bh_spec(bc, t, d), _lse_spec(bc, t)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        interpret=INTERPRET,
        name="attn_fwd_blk",
    )(q, k, v)


def _blk_bwd(q, k, v, o, do, lse, dlse, scale, causal):
    b, h, t, d = q.shape
    bc = _batch_chunk(b, t)
    return pl.pallas_call(
        functools.partial(_blk_bwd_kernel, scale=scale, causal=causal),
        grid=(b // bc, h),
        in_specs=[_bh_spec(bc, t, d)] * 5 + [_lse_spec(bc, t)] * 2,
        out_specs=[_bh_spec(bc, t, d)] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        interpret=INTERPRET,
        name="attn_bwd_blk",
    )(q, k, v, o, do, lse, dlse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_block_attention(q, k, v, causal, scale=None):
    """One attention block for the ring schedule: returns ``(o, lse)``
    with o normalized within the block and lse = logsumexp of the scores
    ([B, H, T, 1] f32). Both outputs are differentiable — the lse
    cotangent from the caller's log-space merge flows into ds. T ≤ 1024
    (whole-block kernel), no dropout."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    return _blk_fwd(q, k, v, scale, causal)


def _vjp_fwd_blk(q, k, v, causal, scale):
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    o, lse = _blk_fwd(q, k, v, scale, causal)
    return (o, lse), (q, k, v, o, lse)


def _vjp_bwd_blk(causal, scale, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _blk_bwd(q, k, v, o, do.astype(q.dtype),
                          lse, dlse.astype(jnp.float32), scale, causal)
    return dq, dk, dv


fused_block_attention.defvjp(_vjp_fwd_blk, _vjp_bwd_blk)


# -- packed layout: [B, T, C] with C = H·D -------------------------------
#
# The standard [B, H, T, D] layout costs two transposes per attention call
# (plus their backward twins) — ~20% of the small-model step time shows up
# as "data formatting" in the profile. These kernels take the projection
# output layout directly and loop heads inside the kernel (static loop,
# lane-dimension slices), so the model never transposes.


def _fwd_packed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, nh):
    q = q_ref[...]                               # [bc, T, C] native dtype
    k = k_ref[...]
    v = v_ref[...]
    t, c = q.shape[1], q.shape[2]
    d = c // nh
    mask = _causal(t)[None]
    outs, lses = [], []
    for h in range(nh):
        sl = slice(h * d, (h + 1) * d)
        s = _bdot(q[:, :, sl], k[:, :, sl], ((2,), (2,))) * scale
        s = jnp.where(mask, s, NEG)
        m = jnp.max(s, axis=2, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=2, keepdims=True)
        lses.append(m + jnp.log(l))              # [bc, T, 1]
        outs.append(_bdot((p / l).astype(v.dtype), v[:, :, sl], ((2,), (1,))))
    o_ref[...] = jnp.concatenate(outs, axis=2).astype(o_ref.dtype)
    lse_ref[...] = jnp.concatenate(lses, axis=2)  # [bc, T, H]


def _bwd_packed_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                       dq_ref, dk_ref, dv_ref, *, scale, nh):
    q = q_ref[...]                                # native dtype dots
    k = k_ref[...]
    v = v_ref[...]
    o = o_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]                            # [bc, T, H]
    t, c = q.shape[1], q.shape[2]
    d = c // nh
    mask = _causal(t)[None]
    dqs, dks, dvs = [], [], []
    for h in range(nh):
        sl = slice(h * d, (h + 1) * d)
        qh, kh, vh = q[:, :, sl], k[:, :, sl], v[:, :, sl]
        oh, doh = o[:, :, sl], do[:, :, sl]
        s = _bdot(qh, kh, ((2,), (2,))) * scale
        s = jnp.where(mask, s, NEG)
        p = jnp.exp(s - lse[:, :, h:h + 1])
        dvs.append(_bdot(p.astype(doh.dtype), doh, ((1,), (1,))))
        dp = _bdot(doh, vh, ((2,), (2,)))
        delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                        axis=2, keepdims=True)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dqs.append(_bdot(ds, kh, ((2,), (1,))))
        dks.append(_bdot(ds, qh, ((1,), (1,))))
    dq_ref[...] = jnp.concatenate(dqs, axis=2).astype(dq_ref.dtype)
    dk_ref[...] = jnp.concatenate(dks, axis=2).astype(dk_ref.dtype)
    dv_ref[...] = jnp.concatenate(dvs, axis=2).astype(dv_ref.dtype)


def _packed_specs(bc, t, c, nh):
    blk = pl.BlockSpec((bc, t, c), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    lse = pl.BlockSpec((bc, t, nh), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    return blk, lse


def _packed_chunk(b: int, t: int) -> int:
    per_row = t * t * 4 * 2  # two live score blocks per head iteration
    bc = max(1, _VMEM_SCORE_BYTES // per_row)
    while b % bc:
        bc -= 1
    return bc


def _fwd_packed(q, k, v, scale, nh):
    b, t, c = q.shape
    bc = _packed_chunk(b, t)
    blk, lse_s = _packed_specs(bc, t, c, nh)
    return pl.pallas_call(
        functools.partial(_fwd_packed_kernel, scale=scale, nh=nh),
        grid=(b // bc,),
        in_specs=[blk] * 3,
        out_specs=[blk, lse_s],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, t, nh), jnp.float32),
        ],
        interpret=INTERPRET,
        name="attn_fwd",
    )(q, k, v)


def _bwd_packed(q, k, v, o, do, lse, scale, nh):
    b, t, c = q.shape
    bc = _packed_chunk(b, t)
    blk, lse_s = _packed_specs(bc, t, c, nh)
    return pl.pallas_call(
        functools.partial(_bwd_packed_kernel, scale=scale, nh=nh),
        grid=(b // bc,),
        in_specs=[blk] * 5 + [lse_s],
        out_specs=[blk] * 3,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        interpret=INTERPRET,
        name="attn_bwd",
    )(q, k, v, o, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_causal_attention_packed(q, k, v, n_head, scale=None):
    """Packed-layout fused attention: q, k, v and output are [B, T, C]
    (C = n_head·head_dim) — no head transposes anywhere. T ≤ 1024, no
    dropout."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1] // n_head)
    o, _ = _fwd_packed(q, k, v, scale, n_head)
    return o


def _vjp_fwd_packed(q, k, v, n_head, scale):
    scale = scale or 1.0 / math.sqrt(q.shape[-1] // n_head)
    o, lse = _fwd_packed(q, k, v, scale, n_head)
    return o, (q, k, v, o, lse)


def _vjp_bwd_packed(n_head, scale, res, do):
    q, k, v, o, lse = res
    scale = scale or 1.0 / math.sqrt(q.shape[-1] // n_head)
    dq, dk, dv = _bwd_packed(q, k, v, o, do, lse, scale, n_head)
    return dq, dk, dv


fused_causal_attention_packed.defvjp(_vjp_fwd_packed, _vjp_bwd_packed)


# -- quantized KV (ISSUE 11: quantized serving) ---------------------------
#
# The decode KV caches (models/nanogpt.py:_decode_attend /
# _decode_attend_paged) become int8-storable: the scatter quantizes each
# written position's per-head K/V vector against its own max-abs scale
# (one f32 scale per (page slot, head) — 4 bytes of sidecar per hd bytes
# of int8 payload, i.e. 4/hd: 6.25% at head dim 64), and the gather
# dequantizes back into the SAME static-shape reduction window the f32
# path reduces over. Quantization is write-once and deterministic
# (round-to-nearest — the QuantizeCodec idiom with stochastic=False and
# the tile specialized to the head vector), so a shared prompt page is
# bit-stable across readers and the paged stream equals the quantized
# UNPAGED reference exactly: both paths quantize the identical K/V
# vectors to identical (int8, scale) pairs and attend over identical
# dequantized windows.

KV_QMAX = 127  # int8 symmetric range, matching QuantizeCodec(bits=8)


def kv_quantize(x: jax.Array):
    """Per-(position, head) symmetric int8 quantization of a K/V chunk:
    x [..., H, hd] f32 → (q int8 [..., H, hd], scale f32 [..., H]) with
    ``scale = amax/127`` over each head vector (scale 1.0 for all-zero
    vectors, so the roundtrip of zeros is exactly zero)."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax > 0, amax / KV_QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale


def kv_dequantize(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`kv_quantize`: q [..., H, hd] int8 x scale
    [..., H] → [..., H, hd] in ``dtype``. Inside the decode programs the
    gather feeds this straight into the attention einsum — XLA fuses the
    convert+multiply into the contraction operand, so the dequantized
    window is a fusion temporary, never a stored f32 cache."""
    return q.astype(dtype) * scale[..., None].astype(dtype)

