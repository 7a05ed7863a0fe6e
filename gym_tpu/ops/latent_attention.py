"""Multi-head latent attention over pages that hold the latent.

A layer of this attention keeps, a position, ONE vector for all its heads:
``[c_kv ; k_rope]``, the key-value latent (``rank`` wide, 512) and one
rotated key (``rope`` wide, 64) that every head shares. The engine's pool
is ``[kv_pages, page_size, lanes]`` a layer, ``lanes`` the latent's width
rounded up to whole lane tiles (``pool_lanes``: 640 for 576; the spare
lanes stay zeros. A row of 576 is laid out by XLA with the page index on
the lanes and copied, the whole pool, around every scatter, and Mosaic
copies no part of a lane tile: ``tests/test_chip_compile_latent.py``). A
head's keys and values are products of the latent, ``[k_nope ; v] = c_kv
W_ukv``, and there are two algebraically equal ways to use a cached
position:

* **absorbed** (a decode step, a speculative verify: few queries over a
  long past). ``q_nope . k_nope = (q_nope W_uk^T) . c_kv``, so the
  up-projection moves to the query: every head scores ``q_abs`` (``rank``
  wide) and ``q_rope`` against the page row as it lies, the weighted sum of
  the page rows' first ``rank`` columns is ``z``, and ``W_uv`` is applied
  to ``z`` after the softmax. All heads read ONE key of ``rank + rope``
  numbers a position and nothing a head wide is ever built:
  ``decode_attend``. On a TPU it is a Pallas walk of the row's live pages
  (``LATENT_KERNEL``: the page walk of ``ops/paged_attention.py`` for one
  key-value head whose value is the first ``rank`` lanes of its key's own
  tile, so a page is copied once); elsewhere, or where the shapes do not
  tile, a gather of the row's table (``LATENT_GATHER``).
* **expanded** (a prefill: as many queries as keys). Keys and values are
  built a head from the latents (``expand``), once for all the queries of
  a pass, and causal attention runs at the head's own sizes, a score of
  ``nope + rope`` (192) and a value of ``v`` (128) lanes: ``prefill_attend``,
  a Pallas flash kernel on a TPU (``latent_prefill``) and an online softmax
  over key blocks in ``jax.numpy`` elsewhere. Absorbed, the same prefill
  would cost ``(rank + rope + rank) / (nope + rope + v)`` = 3.4 times the
  operations.

The rotation is ``models/cohere2_moe.py:rotate_interleaved`` (pairs ``(2i,
2i + 1)``) with yarn's frequencies (``yarn_inv_freq``); the softmax's scale carries yarn's
``mscale ** 2`` (``softmax_scale``). Operands of every product are in the
pool's dtype, sums and statistics in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu  # noqa: F401 — steered here by tests

NEG = -1e30
# Set True (e.g. from tests) to run the kernels in the Pallas interpreter
# on any backend; lane and sublane tiling is then not required.
INTERPRET = False

LATENT_KERNEL = "latent_paged"
LATENT_GATHER = "latent_gather"

_CHUNK = 256        # cached positions folded a step of the decode walk
_TQ = 4             # query positions (all their heads) a decode program
_VMEM = 64 << 20
_PREFILL_TQ = 1024  # queries and keys a step of the prefill kernel
_PREFILL_TK = 1024
_EXPAND = 2048      # cached positions a step of the prefill's expansion
_ABSORBED_MAX_T = 16    # more queries a row than this without last_pos
#                         still take the absorbed form, by the gather


# -- yarn ------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 at ``factor <= 1``)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under yarn: ``theta ** (-2i /
    dim)`` for the pairs that turn more than ``beta_fast`` times in
    ``original`` positions, that over ``factor`` for those that turn fewer
    than ``beta_slow`` times, and a linear ramp between the two
    dimensions where those turn counts fall."""
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return base.astype(np.float32)

    def turns_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (base / factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def softmax_scale(head_width: int, factor: float,
                  mscale_all_dim: float) -> float:
    """``head_width ** -0.5`` times yarn's ``mscale ** 2``."""
    return yarn_mscale(factor, mscale_all_dim) ** 2 / math.sqrt(head_width)


# -- the dispatch point ----------------------------------------------------


def pool_lanes(rank: int, rope: int) -> int:
    """Lanes a pool row takes for a latent of ``rank + rope`` numbers."""
    return -(-(rank + rope) // 128) * 128


def latent_attend_path(page_size: int, dtype, kv_dtype, rank: int,
                       rope: int) -> str:
    """Which implementation the latent attends take, from what the code
    can observe: ``LATENT_KERNEL`` on a TPU when queries and pool share
    one of float32 and bfloat16, the latent is whole lane tiles (the
    value is then whole tiles of the key's own row) and a page is whole
    sublane tiles that divide the walk's chunk; else ``LATENT_GATHER``. The model, the engine's spans
    and the once-a-shape log line all ask here."""
    dtype, kv_dtype = jnp.dtype(dtype), jnp.dtype(kv_dtype)
    ok = dtype == kv_dtype and dtype in (jnp.float32, jnp.bfloat16)
    if INTERPRET:
        return LATENT_KERNEL if ok else LATENT_GATHER
    tiles = (rank % 128 == 0 and rope > 0
             and page_size % (32 // dtype.itemsize) == 0
             and _CHUNK % page_size == 0)
    return LATENT_KERNEL if (_on_tpu() and ok and tiles) else LATENT_GATHER


# -- decode: absorbed, over the pages ---------------------------------------


def _decode_kernel(bt_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   m_ref, l_ref, acc_ref, *, heads, rank, t, tq, page, ppc,
                   mb, scale):
    r, qb = pl.program_id(0), pl.program_id(1)
    rows, ch = tq * heads, ppc * page
    pos0 = pos_ref[r]
    j0 = qb * tq
    j_hi = jnp.minimum(j0 + tq - 1, t - 1)
    kv_len = jnp.minimum(pos0 + j_hi + 1, mb * page)
    # a row redirected to the null page (inactive slot) reads one page
    kv_len = jnp.where(bt_ref[r * mb] == 0, jnp.minimum(kv_len, page),
                       kv_len)
    n_pages = pl.cdiv(kv_len, page)
    n_chunks = pl.cdiv(kv_len, ch)

    def copy(phys, slot, p):
        return pltpu.make_async_copy(pool_hbm.at[phys], buf.at[slot, p],
                                     sem.at[slot])

    # The scalar core starts a page's copy in some tens of cycles, and
    # that, not the bytes, is what a chunk costs (PERF.md section 6, PR 38
    # and PR 39): a whole chunk's copies are started from an unrolled
    # loop and waited for once, as one copy of the chunk's size; only a
    # row's last chunk takes page-by-page loops over the pages it holds.
    def start(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(bt_ref[r * mb + c * ppc + p], slot, p).start()
            return carry

        def whole():
            for p in range(ppc):
                one(p, None)

        pl.when(live == ppc)(whole)
        pl.when(live < ppc)(lambda: jax.lax.fori_loop(0, live, one, None))

    def wait(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(0, slot, p).wait()
            return carry

        pl.when(live == ppc)(pltpu.make_async_copy(
            pool_hbm.at[pl.ds(0, ppc)], buf.at[slot], sem.at[slot]).wait)
        pl.when(live < ppc)(lambda: jax.lax.fori_loop(0, live, one, None))

    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    start(0, 0)

    # row n of the block is (position j0 + n // heads, head n % heads)
    n = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    j = j0 + jnp.floor((n.astype(jnp.float32) + 0.5)
                       * (1.0 / heads)).astype(jnp.int32)
    qpos = pos0 + jnp.minimum(j, t - 1)                       # [rows, 1]
    q = q_ref[0]

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        col = c * ch + jax.lax.broadcasted_iota(jnp.int32, (rows, ch), 1)
        seen = col <= qpos
        kv = buf[slot].reshape(ch, buf.shape[3])              # [ch, lanes]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(seen, s * scale, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        # positions past this block's last are stale buffer or a recycled
        # page's old contents: 0 * NaN is NaN, so select, don't rely on p
        vrow = c * ch + jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
        v = jnp.where(vrow < kv_len, kv[:, :rank], 0)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, body, None)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _decode_paged(q, pool, block_table, cache_pos, rank, scale, interpret):
    b, t, heads, lanes = q.shape
    page, mb = pool.shape[1], block_table.shape[1]
    ppc = max(1, _CHUNK // page)
    tq = min(_TQ, t)
    t_pad = -(-t // tq) * tq
    rows = tq * heads
    qx = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    qx = qx.reshape(b, t_pad * heads, lanes)
    kernel = functools.partial(
        _decode_kernel, heads=heads, rank=rank, t=t, tq=tq, page=page,
        ppc=ppc, mb=mb, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, t_pad // tq),
            in_specs=[pl.BlockSpec((1, rows, lanes),
                                   lambda r, qb, *_: (r, qb, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, rank),
                                   lambda r, qb, *_: (r, qb, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppc, page, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, t_pad * heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="latent_paged_decode",
    )(block_table.reshape(-1).astype(jnp.int32),
      cache_pos.astype(jnp.int32), qx, pool)
    return out.reshape(b, t_pad, heads, rank)[:, :t]


def _decode_gather(q, pool, block_table, cache_pos, rank, scale):
    b, t, heads, lanes = q.shape
    S = block_table.shape[1] * pool.shape[1]
    rows = pool[block_table].reshape(b, S, lanes)
    s = jnp.einsum("bthw,bsw->bths", q, rows,
                   preferred_element_type=jnp.float32) * scale
    qpos = cache_pos[:, None] + jnp.arange(t)[None, :]
    seen = jnp.arange(S)[None, None, :] <= qpos[:, :, None]       # [b,t,S]
    s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # a position past the row's cursor may hold a recycled page's old
    # contents: its weight is 0, its value must not be NaN
    vals = jnp.where(seen.any(axis=1)[:, :, None], rows[..., :rank], 0)
    return jnp.einsum("bths,bsr->bthr", p, vals,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def decode_attend(q, pool, block_table, cache_pos, rank: int, scale: float,
                  path: str):
    """The absorbed attend: ``q`` [b, t, heads, rank + rope] (``q_abs``
    beside the rotated ``q_rope``, in the pool's dtype) over the pages
    ``block_table`` [b, S // page] names in ``pool`` [P, page, lanes]
    (``lanes >= rank + rope``, the spare lanes zeros). Row ``r``'s query ``j`` sits at position ``cache_pos[r] + j``
    and sees the positions up to its own; the new positions are in the
    pool already. Returns ``z`` [b, t, heads, rank] in ``q``'s dtype: the
    softmax-weighted sum of the seen rows' latents, to which the caller
    applies ``W_uv``. ``path``: ``latent_attend_path``'s answer."""
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, pool.shape[2] - q.shape[3]),))
    if path == LATENT_KERNEL and q.shape[1] <= _ABSORBED_MAX_T:
        return _decode_paged(q, pool, block_table, cache_pos, int(rank),
                             float(scale), INTERPRET)
    return _decode_gather(q, pool, block_table, cache_pos, int(rank),
                          float(scale))


# -- prefill: expanded -------------------------------------------------------


def expand(latents, w_uk, w_uv, n_live):
    """Keys and values a head from the latents ``latents`` [S, rank] of
    one row: ``(k_nope [S, heads * nope], v [S, heads * v])``, the heads
    side by side on the lanes as ``w_uk`` [rank, heads * nope] and ``w_uv``
    [rank, heads * v] lay them. Only the first ``n_live`` positions (a
    traced scalar) are expanded, the largest divisor of ``S`` in
    ``_EXPAND`` at a time; the rest stay zeros, which the causal mask
    never admits."""
    S = latents.shape[0]
    block = math.gcd(S, _EXPAND)

    def one(i, kv):
        k, v = kv
        c = jax.lax.dynamic_slice_in_dim(latents, i * block, block)
        kb = jnp.dot(c, w_uk, preferred_element_type=jnp.float32)
        vb = jnp.dot(c, w_uv, preferred_element_type=jnp.float32)
        return (jax.lax.dynamic_update_slice_in_dim(
                    k, kb.astype(k.dtype), i * block, 0),
                jax.lax.dynamic_update_slice_in_dim(
                    v, vb.astype(v.dtype), i * block, 0))

    dt = latents.dtype
    return jax.lax.fori_loop(
        0, (n_live + block - 1) // block, one,
        (jnp.zeros((S, w_uk.shape[1]), dt), jnp.zeros((S, w_uv.shape[1]),
                                                      dt)))


def _prefill_kernel(pos_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, tq, tk, scale):
    qb, kb = pl.program_id(1), pl.program_id(2)
    q_lo = pos_ref[0] + qb * tq          # the block's first query position

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(kb * tk <= q_lo + tq - 1)
    def _():
        s = jax.lax.dot_general(qn_ref[...], kn_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        row = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        col = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        seen = col <= row
        s = jnp.where(seen, s * scale, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _prefill_flash(q_nope, q_rope, k_nope, k_rope, v, pos0, heads, scale,
                   blocks, interpret):
    T, S = q_nope.shape[0], k_nope.shape[0]
    nope, dv, rope = q_nope.shape[1] // heads, v.shape[1] // heads, \
        k_rope.shape[1]
    tq, tk = blocks

    def last_kb(qb, pos):
        # the last key block a query block reads: later ones are past its
        # causal limit and keep this index, so nothing is copied for them
        return (pos[0] + (qb + 1) * tq - 1) // tk

    kernel = functools.partial(_prefill_kernel, tq=tq, tk=tk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, T // tq, S // tk),
            in_specs=[
                pl.BlockSpec((tq, nope), lambda h, qb, kb, pos: (qb, h)),
                pl.BlockSpec((1, tq, rope),
                             lambda h, qb, kb, pos: (h, qb, 0)),
                pl.BlockSpec((tk, nope), lambda h, qb, kb, pos: (
                    jnp.minimum(kb, last_kb(qb, pos)), h)),
                pl.BlockSpec((tk, rope), lambda h, qb, kb, pos: (
                    jnp.minimum(kb, last_kb(qb, pos)), 0)),
                pl.BlockSpec((tk, dv), lambda h, qb, kb, pos: (
                    jnp.minimum(kb, last_kb(qb, pos)), h)),
            ],
            out_specs=pl.BlockSpec((tq, dv), lambda h, qb, kb, pos: (qb, h)),
            scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, heads * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="latent_prefill",
    )(pos0.reshape(1).astype(jnp.int32), q_nope, q_rope, k_nope, k_rope, v)


def _prefill_blocks(q_nope, q_rope, k_nope, k_rope, v, pos0, heads, scale,
                    blocks):
    """``_prefill_flash``'s result in ``jax.numpy``: an online softmax over
    key blocks, one block of queries at a time, the blocks past a query
    block's causal limit not entered."""
    T, S = q_nope.shape[0], k_nope.shape[0]
    nope, dv = q_nope.shape[1] // heads, v.shape[1] // heads
    tq, tk = blocks
    dt = q_nope.dtype
    kn = k_nope.reshape(S, heads, nope)
    vv = v.reshape(S, heads, dv)

    def one_q(qb):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, qb * tq, tq).reshape(
            tq, heads, nope)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, qb * tq, tq, axis=1)
        row = pos0 + qb * tq + jnp.arange(tq)

        def one_k(kb, carry):
            m, l, acc = carry
            k_n = jax.lax.dynamic_slice_in_dim(kn, kb * tk, tk)
            k_r = jax.lax.dynamic_slice_in_dim(k_rope, kb * tk, tk)
            v_b = jax.lax.dynamic_slice_in_dim(vv, kb * tk, tk)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_n,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("hqd,kd->hqk", qr, k_r,
                              preferred_element_type=jnp.float32)) * scale
            seen = (kb * tk + jnp.arange(tk))[None, :] <= row[:, None]
            s = jnp.where(seen[None], s, NEG)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[None], jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "hqk,khd->hqd", p.astype(dt), v_b,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        n_k = (pos0 + (qb + 1) * tq - 1) // tk + 1
        m, l, acc = jax.lax.fori_loop(
            0, jnp.minimum(n_k, S // tk), one_k,
            (jnp.full((heads, tq, 1), NEG, jnp.float32),
             jnp.zeros((heads, tq, 1), jnp.float32),
             jnp.zeros((heads, tq, dv), jnp.float32)))
        return jnp.moveaxis(acc / l, 0, 1).reshape(tq, heads * dv).astype(dt)

    return jax.lax.map(one_q, jnp.arange(T // tq)).reshape(T, heads * dv)


def _block_sizes(T: int, S: int):
    """``(queries, keys)`` a step of the prefill attend for ``T`` queries
    over a row of ``S`` positions: the kernel's blocks where they divide
    both, else the largest power of two that does."""
    return (math.gcd(T, _PREFILL_TQ), math.gcd(S, _PREFILL_TK))


def prefill_attend(q_nope, q_rope, k_nope, k_rope, v, pos0, heads: int,
                   scale: float, path: str):
    """The expanded attend of one row's ``T`` queries, the first at
    position ``pos0`` (a traced scalar), over the row's ``S`` positions:
    ``q_nope`` [T, heads * nope], ``q_rope`` [heads, T, rope] (rotated),
    ``k_nope`` [S, heads * nope], ``k_rope`` [S, rope] (rotated, one for
    all heads), ``v`` [S, heads * v]; query ``j`` sees positions ``0 ..
    pos0 + j``. Returns [T, heads * v] in the queries' dtype."""
    blocks = _block_sizes(q_nope.shape[0], k_nope.shape[0])
    pos0 = jnp.asarray(pos0, jnp.int32)
    nope, dv = q_nope.shape[1] // heads, v.shape[1] // heads
    tiled = (INTERPRET or (nope % 128 == 0 and dv % 128 == 0
                           and min(blocks) >= 128))
    if path == LATENT_KERNEL and tiled:
        return _prefill_flash(q_nope, q_rope, k_nope, k_rope, v, pos0,
                              int(heads), float(scale), blocks, INTERPRET)
    return _prefill_blocks(q_nope, q_rope, k_nope, k_rope, v, pos0,
                           int(heads), float(scale), blocks)
