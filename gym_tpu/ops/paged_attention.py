"""Paged attention that reads only a row's live pages — Pallas TPU kernel.

The serving engine keeps every layer's keys and values in a POOL of
fixed-size pages, ``[kv_pages, page_size, n_embd]`` (all heads of a
position packed on the lanes: 768 = 6 lane tiles at GPT-2 base, so a page
is whole (8, 128) tiles and a row of one position is what a scatter
writes). ``models/nanogpt.py:_decode_attend_paged`` writes the new
positions into the pool and then attends. Its gather path builds a dense
``[b, S, H, hd]`` window out of ``pool[block_table]`` for every row,
whatever the row's cursor says; at 128 slots that window is the size of
the whole pool, read and written again in every layer of every step.

This kernel walks the pool instead: for batch row ``r`` it copies pages
``block_table[r, 0 .. ceil((cache_pos[r] + t) / page) - 1]`` from HBM into
a double-buffered VMEM window, 128 positions at a time, and folds them
into an online softmax (float32 statistics and accumulator). Nothing past
a row's cursor is read or scored, nothing pool-sized is built, and a row
whose table points at the null page (an inactive slot) costs one page.

Heads without lane slicing: a head is 64 lanes of a 768-lane row, and
cutting 64-lane columns out of a packed block costs more than it saves
(``flash_attention.py:packed_flash_attention_or_none`` measured that). So
the wrapper expands the queries instead: query row ``(j, h)`` is position
``j``'s packed query with every lane outside head ``h`` zeroed. Its
product with a packed key row is then head ``h``'s score exactly (the
zeros add nothing), all heads of a decode step are 12 rows of ONE matmul
against the page, and the weighted sum over packed value rows holds head
``h``'s output in head ``h``'s lanes, which the wrapper keeps. The matrix
unit does ``n_head`` times the needed products; it is otherwise idle in a
step that is bound by reading the pages.

Precision: the two products take their operands as bfloat16 and
accumulate in float32. That is what XLA's default precision makes of the
gather path's float32 ``einsum``s on a TPU (one bf16 pass), so the kernel
computes the same products; what differs is the order of the sums
(blocks of 128 positions with a running maximum instead of one softmax
over ``S``), hence a tolerance and not bit-identity against the gather
path on the chip. (Measured there, PR 26: float32 operands at Mosaic's
default precision give the same numbers in the same time, so the cast
only states what the matrix unit does anyway. And XLA lowers the gather
path's one-token products through the vector units in float32, so at
decode the kernel is the one bf16 pass of the default precision and the
gather path is better than that: attention outputs 2.6e-3 apart on
unit-variance inputs.) Under the interpreter (``INTERPRET``, CPU tests)
the operands stay float32, as the gather path's are on the CPU.

Grouped heads, bfloat16 and a window (``paged_attention_gqa``): a model
whose head dimension fills a lane tile (128) needs no lane masking. Its
pool row is ``kv_heads * head_dim`` lanes, key-value head ``g`` the
lane tile(s) ``g * head_dim ..``, and the ``group`` query heads that read
it are rows of one product against that slice. One program takes a block
of query positions with ALL heads (``gqa_tile`` says how many), copies
each page whole (one ``[page, kv_heads * head_dim]`` transfer) and walks
the key-value heads in turn inside a chunk of 256 or 512. Operands keep
the pool's dtype (bfloat16 pools are multiplied as bfloat16, float32
accumulation and statistics). With a ``window``, query ``i`` sees key
``j`` iff ``0 <= i - j < window``: pages wholly older than the block's
first query's window are neither copied nor scored, so a window layer of
a long row reads ``window / page + 1`` pages a step, whatever the row's
table still holds.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu  # noqa: F401 — steered here by tests
from .power_retention import RETENTION

_log = logging.getLogger(__name__)

NEG = -1e30
# Set True (e.g. from tests) to run the kernel in the Pallas interpreter
# on any backend; lane and sublane tiling is then not required.
INTERPRET = False
_CHUNK = 128        # key/value positions folded per inner step
_ROWS = 256         # expanded query rows (position x head) per program

_GQA_CHUNKS = (256, 512)  # the same for the grouped kernel: ``gqa_tile``
_GQA_ROWS, _GQA_SCORES = 1024, 1 << 20    # a head's rows, a chunk's scores
_GQA_VMEM = 64 << 20

KERNEL = "pallas_paged"
KERNEL_WINDOW = "pallas_paged_window"   # the grouped kernel with a window
GATHER = "gather"
# learned sparse attention (``ops/sparse_attention.py``): index scores,
# the exact ``topk`` selection, an attend over the kept keys only
SPARSE = "sparse_topk"
# ``RETENTION`` (imported above), ``GATED_DELTA`` (below): no keys and
# values are kept, a row's block of state is decayed, updated and read


def paged_attend_path(n_embd: int, page_size: int, dtype, kv_dtype,
                      head_dim: int = 0, window: int = 0, sparse_topk: int = 0,
                      retention: bool = False, gated_delta=False) -> str:
    """Which implementation the paged attend takes, from what the code
    can observe. ``n_embd`` is the pool row's width (all key-value heads
    of a position). THE dispatch point — the model and the engine's
    counters both ask here.

    A head dimension that is not whole lane tiles (GPT-2's 64; the
    default ``head_dim=0``): ``KERNEL`` on a TPU when the packed row
    fills whole lane tiles, a page whole sublane tiles and a 128-position
    chunk whole pages, and both the queries and the pool are float32;
    else ``GATHER`` (off the TPU: all of tier-1; an int8 pool: by its
    dtype).

    A ``head_dim`` given (``paged_attention_gqa``: any number of query
    heads a key-value head, each key-value head its own lanes of the pool
    row): ``KERNEL``, or ``KERNEL_WINDOW`` with a ``window``, on a TPU
    when the head dimension is whole lane tiles (a multiple of 128),
    queries and pool share one of float32 and bfloat16 and a page is
    whole sublane tiles of it (8 rows of float32, 16 of bfloat16) that
    divide the chunk; else ``GATHER``.

    ``sparse_topk`` given (a layer that keeps that many keys a query by
    a learned index, ``ops/sparse_attention.py``): ``SPARSE`` on every
    backend and dtype; it reads the kept positions where they lie and
    never builds a row's window.

    ``retention`` / ``gated_delta`` (a layer whose cache is a recurrent
    state, ``ops/power_retention.py`` / ``ops/gated_delta.py``): that id
    on every backend and dtype; there are no pages of keys to walk."""
    if retention or gated_delta:
        return RETENTION if retention else GATED_DELTA
    if sparse_topk:
        return SPARSE
    dtype, kv_dtype = jnp.dtype(dtype), jnp.dtype(kv_dtype)
    if head_dim:
        ok = dtype == kv_dtype and dtype in (jnp.float32, jnp.bfloat16)
        tiles = (head_dim % 128 == 0
                 and page_size % (32 // dtype.itemsize) == 0
                 and _GQA_CHUNKS[0] % page_size == 0)
        kernel = KERNEL_WINDOW if window else KERNEL
        if INTERPRET:
            return kernel if ok else GATHER
        return kernel if (_on_tpu() and ok and tiles) else GATHER
    f32 = dtype == jnp.float32 and kv_dtype == jnp.float32
    if INTERPRET:
        return KERNEL if f32 else GATHER
    tiles = (n_embd % 128 == 0 and page_size % 8 == 0
             and _CHUNK % page_size == 0)
    return KERNEL if (_on_tpu() and f32 and tiles) else GATHER


@functools.cache
def report_path(path: str, shape: tuple, dtype: str) -> None:
    """Log which implementation a paged attend resolved to, once per
    (path, shape, dtype) per process: ``attention path pallas_paged for
    q(128, 1, 768) float32`` — the training kernels' line
    (``flash_attention._report``) on this module's logger, which
    ``chip_smoke.py`` reads to assert the kernel ran."""
    _log.info("attention path %s for q%s %s", path, shape, dtype)


def _kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
            heads, t, page, ppc, mb, scale, mxu_dtype):
    r, rb = pl.program_id(0), pl.program_id(1)
    tr, ch = q_ref.shape[1], ppc * page
    pos0 = pos_ref[r]
    n0 = rb * tr
    # expanded row n is (position j, head h) = divmod(n, heads); the last
    # position of this block bounds the pages it may see
    j_hi = jnp.minimum((n0 + tr - 1) // heads, t - 1)
    kv_len = jnp.minimum(pos0 + j_hi + 1, mb * page)
    # a row redirected to the null page (inactive slot) reads one page
    kv_len = jnp.where(bt_ref[r * mb] == 0, jnp.minimum(kv_len, page),
                       kv_len)
    n_pages = pl.cdiv(kv_len, page)
    n_chunks = pl.cdiv(kv_len, ch)

    def page_copies(c, slot):
        for p in range(ppc):
            pg = c * ppc + p
            phys = bt_ref[r * mb + jnp.minimum(pg, mb - 1)]
            dst = pl.ds(p * page, page)
            yield pg, pltpu.make_async_copy(
                k_hbm.at[phys], kbuf.at[slot, dst], sem.at[0, slot])
            yield pg, pltpu.make_async_copy(
                v_hbm.at[phys], vbuf.at[slot, dst], sem.at[1, slot])

    def start(c, slot):
        for pg, cp in page_copies(c, slot):
            pl.when(pg < n_pages)(cp.start)

    def wait(c, slot):
        for pg, cp in page_copies(c, slot):
            pl.when(pg < n_pages)(cp.wait)

    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    start(0, 0)

    q = q_ref[0].astype(mxu_dtype)
    n = n0 + jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
    # n // heads without a vector integer division: n + 0.5 over heads is
    # never within 0.5 / heads of a whole number, float32 is exact enough
    j = jnp.floor((n.astype(jnp.float32) + 0.5)
                  * (1.0 / heads)).astype(jnp.int32)
    limit = pos0 + jnp.minimum(j, t - 1)                      # [tr, 1]

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        k = kbuf[slot].astype(mxu_dtype)                      # [ch, C]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = c * ch + jax.lax.broadcasted_iota(jnp.int32, (tr, ch), 1)
        s = jnp.where(col <= limit, s * scale, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        # positions past this block's last are stale buffer or a recycled
        # page's old contents: 0 * NaN is NaN, so select, don't rely on p
        vrow = c * ch + jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
        v = jnp.where(vrow < kv_len, vbuf[slot], 0.0).astype(mxu_dtype)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(mxu_dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, body, None)
    o_ref[0] = acc_ref[...] / l_ref[...]


def paged_attention(q, k_pool, v_pool, block_table, cache_pos, n_head):
    """Attention of ``q`` [b, t, C] (heads packed on C) over the pages
    ``block_table`` [b, S // page] names in the pools [P, page, C], row
    ``r``'s query ``j`` seeing positions ``0 .. cache_pos[r] + j``. The
    new positions are in the pool already. Returns [b, t, C] float32.
    ``paged_attend_path`` says whether the shapes qualify."""
    return _paged_attention(q, k_pool, v_pool, block_table, cache_pos,
                            n_head, INTERPRET)


# A jit of its own: a model's layers all call it with the same shapes, so
# the kernel is traced and lowered once a program, not once a layer (the
# Python side of twelve lowerings was 10 s of every served program's
# build on the chip's host, cache hit or not: PERF.md §6, PR 26).
@functools.partial(jax.jit, static_argnums=(5, 6))
def _paged_attention(q, k_pool, v_pool, block_table, cache_pos, n_head,
                     interpret):
    b, t, c = q.shape
    page, mb = k_pool.shape[1], block_table.shape[1]
    hd = c // n_head
    ppc = max(1, _CHUNK // page)
    own = (jnp.arange(c)[None, :] // hd) == jnp.arange(n_head)[:, None]
    n = t * n_head
    tr = min(_ROWS, -(-n // 8) * 8)
    n_pad = -(-n // tr) * tr
    qx = jnp.where(own, q[:, :, None, :], 0.0).reshape(b, n, c)
    qx = jnp.pad(qx, ((0, 0), (0, n_pad - n), (0, 0)))
    kernel = functools.partial(
        _kernel, heads=n_head, t=t, page=page, ppc=ppc, mb=mb,
        scale=1.0 / math.sqrt(hd),
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pad // tr),
            in_specs=[
                pl.BlockSpec((1, tr, c), lambda r, rb, *_: (r, rb, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tr, c), lambda r, rb, *_: (r, rb, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppc * page, c), k_pool.dtype),
                pltpu.VMEM((2, ppc * page, c), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((tr, 1), jnp.float32),
                pltpu.VMEM((tr, 1), jnp.float32),
                pltpu.VMEM((tr, c), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_pad, c), jnp.float32),
        interpret=interpret,
        name="paged_attn_decode" if t == 1 else "paged_attn_prefill",
    )(block_table.reshape(-1).astype(jnp.int32),
      cache_pos.astype(jnp.int32), qx, k_pool, v_pool)
    out = out[:, :n].reshape(b, t, n_head, c)
    return jnp.where(own, out, 0.0).sum(axis=2)


# -- grouped heads, bfloat16, a window --------------------------------------


def gqa_tile(t, group, kvh, page):
    """``(tq, chunk)`` of the grouped kernel for a call of ``t`` positions
    a row: query positions a program (all their heads: ``tq * group``
    rows a key-value head) and key positions a chunk. About 1,024 rows a
    head where ``t`` allows and 512 keys a chunk once a head has 512
    rows, both within 2**20 scores a chunk over all heads (a body's
    length, which the compiler unrolls: 1,024 vregs of scores compile in
    seconds, four times that in a minute); below that PR 27's 256 keys,
    and for a decode step or a speculative verify all of a row's
    positions in one program."""
    rows = min(_GQA_ROWS, _GQA_SCORES // (kvh * _GQA_CHUNKS[0]))
    tq = max(1, min(t, rows // group))
    wide = _GQA_CHUNKS[1] <= tq * group <= _GQA_SCORES // (
        kvh * _GQA_CHUNKS[1])
    return tq, max(page, _GQA_CHUNKS[wide] // page * page)


def _gqa_kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                kvh, group, hd, t, tq, page, ppc, mb, window):
    r, qb = pl.program_id(0), pl.program_id(1)
    rows, ch = tq * group, ppc * page
    pos0 = pos_ref[r]
    j0 = qb * tq
    # the block's last position bounds the pages it may see, its first
    # position's window the pages it need not
    j_hi = jnp.minimum(j0 + tq - 1, t - 1)
    kv_len = jnp.minimum(pos0 + j_hi + 1, mb * page)
    lo = jnp.maximum(pos0 + j0 - window + 1, 0) if window else 0
    # a row redirected to the null page (inactive slot) reads one page
    null = bt_ref[r * mb] == 0
    kv_len = jnp.where(null, jnp.minimum(kv_len, page), kv_len)
    lo = jnp.where(null, 0, lo)
    first_page = lo // page
    c0 = lo // ch
    n_pages = pl.cdiv(kv_len, page)
    n_chunks = pl.cdiv(kv_len, ch)
    # chunks [u0, u1) lie wholly at or below the block's first position,
    # wholly inside the last position's window and wholly in copied
    # pages: every row sees every key of them
    u0, u1 = _gqa_interior(pos0 + j0, pos0 + j_hi, kv_len, c0, n_chunks,
                           ch, window, t, jnp)

    def page_copies(c, slot):
        for p in range(ppc):
            pg = c * ppc + p
            phys = bt_ref[r * mb + jnp.minimum(pg, mb - 1)]
            dst = pl.ds(p * page, page)
            yield pg, pltpu.make_async_copy(
                k_hbm.at[phys], kbuf.at[slot, dst], sem.at[0, slot])
            yield pg, pltpu.make_async_copy(
                v_hbm.at[phys], vbuf.at[slot, dst], sem.at[1, slot])

    def live(pg):
        return (pg >= first_page) & (pg < n_pages)

    def start(c, slot):
        for pg, cp in page_copies(c, slot):
            pl.when(live(pg))(cp.start)

    def wait(c, slot):
        for pg, cp in page_copies(c, slot):
            pl.when(live(pg))(cp.wait)

    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    start(c0, 0)

    # row n of a key-value head's block is (position j0 + n // group,
    # head n % group); the division as in the kernel above
    n = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    j = j0 + jnp.floor((n.astype(jnp.float32) + 0.5)
                       * (1.0 / group)).astype(jnp.int32)
    qpos = pos0 + jnp.minimum(j, t - 1)                       # [rows, 1]

    def fold(c, slot, masked):
        """Chunk ``c`` into the running softmax; ``masked`` unless every
        row sees every key of it and all its pages were copied."""
        if masked:
            col = c * ch + jax.lax.broadcasted_iota(jnp.int32, (rows, ch), 1)
            seen = col <= qpos
            if window:
                seen = seen & (col > qpos - window)
            # pages not copied and positions past this block's last are
            # stale buffer or a recycled page's old contents: 0 * NaN is
            # NaN, so select, don't rely on p
            vrow = c * ch + jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
            vok = (vrow >= first_page * page) & (vrow < kv_len)
        for g in range(kvh):
            lanes = pl.ds(g * hd, hd)
            s = jax.lax.dot_general(
                q_ref[0, g], kbuf[slot, :, lanes],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(seen, s, NEG)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            v = vbuf[slot, :, lanes]
            if masked:
                p, v = jnp.where(seen, p, 0.0), jnp.where(vok, v, 0)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    def body(c, carry):
        slot = jax.lax.rem(c - c0, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        if t == 1:
            fold(c, slot, True)
        else:
            edge = (c < u0) | (c >= u1)
            pl.when(edge)(lambda: fold(c, slot, True))
            pl.when(~edge)(lambda: fold(c, slot, False))
        return carry

    jax.lax.fori_loop(c0, n_chunks, body, None)
    for g in range(kvh):
        o_ref[0, g] = (acc_ref[g] / l_ref[g]).astype(o_ref.dtype)


def paged_attention_gqa(q, k_pool, v_pool, block_table, cache_pos,
                        window: int = 0):
    """Attention of ``q`` [b, kv_heads, t, group, head_dim] over the
    pages ``block_table`` [b, S // page] names in the pools [P, page,
    kv_heads * head_dim]: the ``group`` query heads of key-value head
    ``g`` read lanes ``g * head_dim ..`` of a pool row. Row ``r``'s query
    ``j`` sits at position ``cache_pos[r] + j`` and sees the positions up
    to its own, the last ``window`` of them if one is given. The new
    positions are in the pool already. Returns q's shape and dtype.
    ``paged_attend_path`` says whether the shapes qualify."""
    return _paged_attention_gqa(q, k_pool, v_pool, block_table, cache_pos,
                                int(window), INTERPRET)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _paged_attention_gqa(q, k_pool, v_pool, block_table, cache_pos, window,
                         interpret):
    b, kvh, t, group, hd = q.shape
    page, mb = k_pool.shape[1], block_table.shape[1]
    tq, chunk = gqa_tile(t, group, kvh, page)
    ppc = chunk // page
    t_pad = -(-t // tq) * tq
    rows = tq * group
    # the scores' scale goes onto the queries, once
    qx = (q.astype(jnp.float32) * (1.0 / math.sqrt(hd))).astype(q.dtype)
    qx = jnp.pad(qx, ((0, 0), (0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    qx = qx.reshape(b, kvh, t_pad * group, hd)
    kernel = functools.partial(
        _gqa_kernel, kvh=kvh, group=group, hd=hd, t=t, tq=tq, page=page,
        ppc=ppc, mb=mb, window=window)
    block = pl.BlockSpec((1, kvh, rows, hd), lambda r, qb, *_: (r, 0, qb, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, t_pad // tq),
            in_specs=[block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, ppc * page, kvh * hd), k_pool.dtype),
                pltpu.VMEM((2, ppc * page, kvh * hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, rows, 1), jnp.float32),
                pltpu.VMEM((kvh, rows, 1), jnp.float32),
                pltpu.VMEM((kvh, rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qx.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_GQA_VMEM),
        interpret=interpret,
        name=("paged_gqa_" + ("decode" if t == 1 else "prefill")
              + ("_window" if window else "_full")),
    )(block_table.reshape(-1).astype(jnp.int32),
      cache_pos.astype(jnp.int32), qx, k_pool, v_pool)
    return out.reshape(b, kvh, t_pad, group, hd)[:, :, :t]


def _gqa_interior(first, last, kv_len, c0, n_chunks, ch, window, t, xp):
    """``(u0, u1)``: of the chunks ``[c0, n_chunks)`` a block of queries
    at positions ``first .. last`` walks, those ``[u0, u1)`` need no
    mask: every key of them lies at or below ``first``, inside ``last``'s
    window and below ``kv_len``. A decode step (``t`` 1) masks every
    chunk. The kernel (``xp`` is ``jnp``) and ``gqa_chunks`` (``numpy``)
    both ask here."""
    if t == 1:
        return n_chunks, n_chunks
    u1 = xp.minimum(first + 1, kv_len) // ch
    u0 = -(-xp.maximum(last - window + 1, 0) // ch) if window else c0
    u0 = xp.clip(u0, c0, n_chunks)
    return u0, xp.clip(u1, u0, n_chunks)


# at the END of this file on purpose: a kernel's compiled body carries the
# file and line of the frames it was traced under, so no line above may
# move (``programs/serve_defs.py`` says more)
from .gated_delta import GATED_DELTA  # noqa: E402
import numpy as np  # noqa: E402


def gqa_chunks(pos, t, group, kvh, page, mb, window=0):
    """``[run, unmasked]``: the chunks the programs of ONE call of the
    grouped kernel walk, ``t`` positions a row from the cursors ``pos``
    [b] of live rows over tables of ``mb`` pages, and how many of them
    take the body without masks; summed over rows and query blocks. The
    kernel's own arithmetic on the host, for a counter: the kernel cannot
    say which body it ran."""
    tq, ch = gqa_tile(t, group, kvh, page)
    j0 = np.arange(0, t, tq, dtype=np.int64)
    first = np.asarray(pos, np.int64).reshape(-1, 1) + j0
    last = first + np.minimum(tq - 1, t - 1 - j0)
    kv_len = np.minimum(last + 1, mb * page)
    c0 = (np.maximum(first - window + 1, 0) if window else 0 * first) // ch
    n_chunks = -(-kv_len // ch)
    u0, u1 = _gqa_interior(first, last, kv_len, c0, n_chunks, ch, window,
                           t, np)
    return np.asarray([(n_chunks - c0).sum(), (u1 - u0).sum()], np.int64)
