"""Learned sparse attention over the page pool: a query scores every
resident key of its row with a small index, keeps the ``topk`` best and
attends over those alone (DeepSeek Sparse Attention's published form).

A layer keeps three things a position in the engine's pools: the key and
the value (``[pages, page, kv_heads * head_dim]``, as every paged layer)
and ONE index key shared by the index's heads (``index_pool_shape``: a
page's index keys side by side in rows of 128 lanes, ``[pages, 8, 128]``
at 16 keys of 64, so that a page is one whole tile). For query ``t`` of a
row and resident position ``s <= t``::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)
    S_t     = the topk positions of largest I[t, s]; ties to the lower s
    o[t, h] = softmax over S_t of q[t, h] . k[s, h // G] / sqrt(d), times v

``I`` is compared as a real number (``-0.0`` is ``0.0``). A row of at
most ``topk`` positions keeps them all and the result is plain grouped
attention. Nothing approximates: the kept set is the exact one.

Two implementations, by the number of queries a row brings:

* ``attend_rows`` (a decode step, a speculative step, a prefill of a few
  tokens): on a TPU the index scores are the Pallas kernel
  ``index_keys_paged`` (``sparse_index_decode``), which walks a row's
  LIVE pages of index keys where they lie in the pool, a chunk of pages
  copied into VMEM while the chunk before is scored, and writes only the
  scores' order-preserving integers (``index_path`` decides from backend,
  dtype and shape; elsewhere every row's whole table is gathered and
  scored by ``index_scores_paged``, the kernel's reference, whatever the
  rows hold). The kept positions are found (``kept_mask``) and listed
  (``compact``) without a sort (``lax.top_k`` of 2,048 from 36,864 is a
  sort on the chip: 4.4 ms a layer measured, PERF.md) and only THEIR keys
  and values are gathered out of the pools, ``topk`` rows of a kilobyte a
  query, whatever the row holds.
* ``attend_block`` (a prefill's block of queries): every query has its
  own set, so the set is a mask. The index scores of the block are laid
  down as order-preserving integers a block of keys at a time, the
  ``topk``-th largest of every query is found by a search over the bits
  (``kth_largest``: counts only, no sort), and the attend is a running
  softmax over the key blocks under that mask: on a TPU the Pallas
  kernel ``masked_attend`` (where the shapes are whole tiles and no
  scores tie at a threshold), else the same in ``jax.numpy``. Every loop
  over keys stops at the block's last visible position, so a bucket
  costs what its causal triangle costs and no ``[t, t]`` array is ever
  whole.

Device scopes: ``attn.index`` (scores; the decode step's kernel carries
it), ``attn.select`` (the selection), ``attn.sparse`` (the attend over
the kept keys).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

NEG = -1e30
KERNEL_TQ = 256         # query positions a program of the masked attend
KERNEL_TK = 512         # key positions a step of it
_VMEM = 64 << 20
ROWS_MAX_T = 16         # queries a row up to which ``attend_rows`` runs
_BITS = 2               # bits of the threshold found a pass (divides 32)
LANES = 128
INDEX_CHUNK = 128       # pages of index keys a step of the decode index
INDEX_SLOTS = 3         # chunks whose copies are in flight or being scored
INDEX_UNROLL = 16       # of a whole chunk's loop of page-copy starts
INDEX_KERNEL = "sparse_index_kernel"
INDEX_GATHER = "sparse_index_gather"


def _sortable_i32(x):
    """``sortable``'s uint32 as the int32 of the same bits (what a
    kernel computes in)."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))


def sortable(x):
    """float32 -> uint32 that orders as the numbers do (``-0.0`` with
    ``0.0``); every finite value and both infinities map above 0, which
    is left for "no key here"."""
    return jax.lax.bitcast_convert_type(_sortable_i32(x), jnp.uint32)


def index_scores(qi, wi, ki):
    """``I`` [b, t, s] float32 of index queries ``qi`` [b, t, J, d] with
    head weights ``wi`` [b, t, J] float32 against index keys ``ki``
    [b, s, d]: products in the operands' dtype, sums in float32."""
    s = jnp.einsum("btjd,bsd->btjs", qi, ki,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * wi[..., None]).sum(axis=2)


def index_scores_paged(qi, wi, ki_pages, page: int):
    """``index_scores`` of a few queries a row against the row's pages
    of index keys, ``ki_pages`` [b, pages, page * d] (a page's keys side
    by side, as the pool holds them), without taking the pages apart
    (that is a relayout of the gathered 75 MB a layer): a page's row
    times the queries laid out block-diagonally gives the page's
    ``page x J`` products on the lanes, and the weighted sum over heads
    is a second, small product. The matrix unit multiplies ``page`` times
    the zeros it needs not; it is idle otherwise. [b, t, pages * page]
    float32."""
    b, t, heads, d = qi.shape
    eye = jnp.eye(page, dtype=qi.dtype)
    spread = jnp.einsum("pq,btjd->btpdqj", eye, qi).reshape(
        b, t, page * d, page * heads)
    s = jnp.einsum("bmk,btkn->btmn", ki_pages, spread,
                   preferred_element_type=jnp.float32)
    gather = jnp.einsum("pq,btj->btpjq", jnp.eye(page, dtype=jnp.float32),
                        wi).reshape(b, t, page * heads, page)
    out = jnp.einsum("btmn,btnp->btmp", jax.nn.relu(s), gather,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(b, t, -1)


def kth_largest(count, shape, k: int):
    """The largest uint32 ``T`` [shape] of which at least ``k`` keys are
    ``>= T``, found ``_BITS`` bits a pass from the top. ``count(cands
    [*shape, M], strict)`` gives, for every candidate, how many keys of
    its query are ``>=`` (``>`` if strict) it. Fewer than ``k`` keys
    above 0 leave ``T`` 0."""
    m_all = jnp.arange(1, 1 << _BITS, dtype=jnp.uint32)

    def one_pass(i, t):
        shift = (32 - _BITS * (i + 1)).astype(jnp.uint32)
        cands = t[..., None] | (m_all << shift)
        # counts fall as the candidate rises: as many candidates hold as
        # the new bits say
        held = (count(cands, False) >= k).sum(axis=-1).astype(jnp.uint32)
        return t | (held << shift)

    return jax.lax.fori_loop(0, 32 // _BITS, one_pass,
                             jnp.zeros(shape, jnp.uint32))


def _block(n: int, want: int) -> int:
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _ranks(mask):
    """Where the marked entries of ``mask`` [..., s] stand among
    themselves, in blocks of (up to) 128 so that no scan runs over the
    row: ``(local [..., n, c], before [..., n])``, the 1-based place of
    every entry among the marked of its block (a product with a
    triangle of ones) and the marked in the blocks before."""
    s = mask.shape[-1]
    c = _block(s, 128)
    m = mask.reshape(mask.shape[:-1] + (s // c, c))
    tri = jnp.arange(c)[:, None] <= jnp.arange(c)[None, :]
    local = jnp.einsum("...s,st->...t", m.astype(jnp.bfloat16),
                       tri.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32
                       ).astype(jnp.int32)
    count = local[..., -1]
    return local, jnp.cumsum(count, axis=-1) - count


def kept_mask(keys, k: int):
    """``keys`` [..., s] (``sortable`` scores, 0 where there is no key)
    -> bool [..., s]: the ``k`` largest of every row, ties to the lower
    ``s``; all that are above 0 where there are at most ``k``."""

    def count(cands, strict):
        cmp = jnp.greater if strict else jnp.greater_equal
        return cmp(keys[..., None, :], cands[..., None]).sum(
            axis=-1, dtype=jnp.int32)

    t = kth_largest(count, keys.shape[:-1], k)[..., None]
    above = keys > t
    tie = keys == t
    need = k - above.sum(axis=-1, keepdims=True, dtype=jnp.int32)
    local, before = _ranks(tie)
    rank = (local + before[..., None]).reshape(keys.shape)
    return (above | (tie & (rank <= need))) & (keys > 0)


def compact(mask, k: int):
    """The positions marked in ``mask`` [..., s] (at most ``k`` a row),
    in rising order: ``(idx [..., k] int32, there [..., k] bool)``, the
    places past a row's count unmarked (their ``idx`` 0). No sort, no
    scatter and no gather of single elements, none of which the chip
    does well: a marked entry's place among its block's is a product
    with a triangle, a block's list of its marked positions a reduction
    over a one-hot comparison, and an output's block a comparison with
    the blocks' running counts."""
    local, before = _ranks(mask)                    # [..., n, c], [..., n]
    n, c = local.shape[-2:]
    m = mask.reshape(local.shape)
    lane = jnp.arange(c)
    # place r of block B holds the in-block position of its r-th marked
    listed = ((m[..., :, None] & (local[..., :, None] - 1 == lane))
              * lane[:, None]).sum(axis=-2)               # [..., n, c]
    out = jnp.arange(k)
    block = (before[..., None, :] <= out[:, None]).sum(
        axis=-1, dtype=jnp.int32) - 1                     # [..., k]
    pick = block[..., None] == jnp.arange(n)              # [..., k, n]
    row = jnp.einsum("...kn,...nc->...kc", pick.astype(jnp.bfloat16),
                     listed.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)  # [..., k, c]
    start = (pick * before[..., None, :]).sum(axis=-1)
    place = out - start                                   # [..., k]
    inside = (row * (lane == place[..., None])).sum(axis=-1)
    there = out < (before[..., -1:] + local[..., -1, -1:])
    idx = block * c + inside.astype(jnp.int32)
    return jnp.where(there, idx, 0), there


def attend_rows(q, qi, wi, k_pool, v_pool, ki_pool, block_table, cache_pos,
                topk: int):
    """``q`` [b, kv_heads, t, group, head_dim] (a few queries a row, the
    first at ``cache_pos``) over the pools: each query's ``topk`` kept
    positions' keys and values are gathered where they lie. Returns
    ``q``'s shape and dtype."""
    b, kvh, t, group, hd = q.shape
    page, mb = k_pool.shape[1], block_table.shape[1]
    S = mb * page
    K = min(int(topk), S)
    qpos = cache_pos[:, None] + jnp.arange(t)[None, :]            # [b, t]
    path = index_path(qi, ki_pool, page, mb)
    _pa.report_path(path, tuple(qi.shape), str(qi.dtype))
    with jax.named_scope("attn.index"):
        if path == INDEX_KERNEL:
            keys = index_keys_paged(qi, wi, ki_pool, block_table, cache_pos,
                                    page)
        else:
            scores = index_scores_paged(
                qi, wi, ki_pool[block_table].reshape(b, mb, -1), page)
            seen = jnp.arange(S)[None, None, :] <= qpos[:, :, None]
            keys = jnp.where(seen, sortable(scores), 0)
    with jax.named_scope("attn.select"):
        idx, kept = compact(kept_mask(keys, K), K)             # [b, t, K]
    with jax.named_scope("attn.sparse"):
        phys = (jnp.take_along_axis(
            block_table[:, None, :], idx // page, axis=2) * page
            + idx % page)
        # whole pool rows (all key-value heads of a position): a gather
        # of [kv_heads, head_dim] slices is four times slower on the chip
        k_sel = k_pool.reshape(-1, kvh * hd)[phys].reshape(
            b, t, K, kvh, hd)
        # a place past the row's own positions holds what a recycled
        # page left there: 0 * NaN is NaN, so select
        v_sel = jnp.where(kept[..., None], v_pool.reshape(-1, kvh * hd)[phys],
                          0).reshape(b, t, K, kvh, hd)
        att = jnp.einsum("bktgd,btskd->bktgs", q, k_sel,
                         preferred_element_type=jnp.float32)
        att = jnp.where(kept[:, None, :, None, :], att / math.sqrt(hd),
                        -jnp.inf)
        att = jax.nn.softmax(att, axis=-1).astype(q.dtype)
        return jnp.einsum("bktgs,btskd->bktgd", att, v_sel,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)


def attend_block(q, qi, wi, k_row, v_row, ki_row, pos, topk: int,
                 key_block: int = 2048):
    """One block of queries ``q`` [b, kv_heads, tc, group, head_dim], the
    first at ``pos`` [b] of its row, over the row's keys ``k_row``,
    ``v_row`` [b, S, kv_heads, head_dim] and index keys ``ki_row``
    [b, S, d] (all resident positions up to the block's last are in
    them). Returns ``q``'s shape and dtype."""
    b, kvh, tc, group, hd = q.shape
    S = k_row.shape[1]
    kb = _block(S, key_block)
    K = int(topk)
    use_kernel = ((_pa.INTERPRET or _pa._on_tpu())
                  and masked_attend_shapes_ok(tc, S, kb, hd, q.dtype))
    qpos = pos[:, None] + jnp.arange(tc)[None, :]                 # [b, tc]
    last = jnp.minimum(jnp.max(pos) + tc, S)    # positions any query sees
    n_blocks = (last + kb - 1) // kb
    cols = jnp.arange(kb)

    def lay_down(j, keys):
        ki = jax.lax.dynamic_slice_in_dim(ki_row, j * kb, kb, axis=1)
        seen = (j * kb + cols)[None, None, :] <= qpos[:, :, None]
        blk = jnp.where(seen, sortable(index_scores(qi, wi, ki)), 0)
        return jax.lax.dynamic_update_index_in_dim(keys, blk, j, 0)

    with jax.named_scope("attn.index"):
        keys = jax.lax.fori_loop(
            0, n_blocks, lay_down,
            jnp.zeros((S // kb, b, tc, kb), jnp.uint32))

    def count(cands, strict):
        cmp = jnp.greater if strict else jnp.greater_equal

        def add(j, c):
            blk = jax.lax.dynamic_index_in_dim(keys, j, 0, keepdims=False)
            return c + cmp(blk[:, :, None, :], cands[..., None]).sum(
                axis=-1, dtype=jnp.int32)

        return jax.lax.fori_loop(0, n_blocks, add,
                                 jnp.zeros(cands.shape, jnp.int32))

    with jax.named_scope("attn.select"):
        thr = kth_largest(count, (b, tc), K)
        n_above = count(thr[..., None], True)[..., 0]
        n_from = count(thr[..., None], False)[..., 0]
        need = K - n_above
        # equal scores at the threshold: rare, and only then is a
        # query's place among its ties worth counting
        ties = jnp.any((n_from > K) & (thr > 0))

    scale = 1.0 / math.sqrt(hd)

    def walk(with_ties: bool):
        def fold(j, carry):
            m, l, acc, tied = carry
            blk = jax.lax.dynamic_index_in_dim(keys, j, 0, keepdims=False)
            if with_ties:
                tie = blk == thr[..., None]
                rank = tied[..., None] + jnp.cumsum(tie, axis=-1,
                                                    dtype=jnp.int32)
                keep = (blk > thr[..., None]) | (tie
                                                 & (rank <= need[..., None]))
                tied = tied + tie.sum(axis=-1, dtype=jnp.int32)
            else:
                keep = blk >= thr[..., None]
            keep = (keep & (blk > 0))[:, None, :, None, :]
            kk = jax.lax.dynamic_slice_in_dim(k_row, j * kb, kb, axis=1)
            vv = jax.lax.dynamic_slice_in_dim(v_row, j * kb, kb, axis=1)
            # past the block's last position lies what a recycled page
            # left there: 0 * NaN is NaN, so select
            vv = jnp.where(((j * kb + cols) < last)[None, :, None, None],
                           vv, 0)
            s = jnp.einsum("bktgd,bskd->bktgs", q, kk,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + p.sum(axis=-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bktgs,bskd->bktgd", p.astype(q.dtype), vv,
                preferred_element_type=jnp.float32)
            return m_new, l, acc, tied

        def run():
            stat = (b, kvh, tc, group)
            m, l, acc, _ = jax.lax.fori_loop(
                0, n_blocks, fold,
                (jnp.full(stat, NEG, jnp.float32),
                 jnp.zeros(stat, jnp.float32),
                 jnp.zeros(stat + (hd,), jnp.float32),
                 jnp.zeros((b, tc), jnp.int32)))
            return (acc / l[..., None]).astype(q.dtype)

        return run

    def kernel():
        y = masked_attend(
            jnp.moveaxis(q, 3, 2).reshape(b, kvh * group, tc, hd),
            k_row.reshape(b, S, kvh * hd), v_row.reshape(b, S, kvh * hd),
            keys, thr, pos, jnp.broadcast_to(last, (b,)))
        return jnp.moveaxis(y.reshape(b, kvh, group, tc, hd), 2, 3)

    with jax.named_scope("attn.sparse"):
        # equal scores at a threshold take the plain walk, which counts
        # each query's place among its ties
        return jax.lax.cond(ties, walk(True),
                            kernel if use_kernel else walk(False))


# -- the masked attend of a prefill block, as a kernel ----------------------


def _masked_kernel(pos_ref, last_ref, q_ref, k_ref, v_ref, keys_ref, thr_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, kvh, group, hd, tq, tk,
                   nk, scale):
    r, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # key blocks past the block's last query hold no kept key: skipped
    # (their copies too: the index maps repeat the last block's index)
    @pl.when(j * tk <= pos_ref[r] + (i + 1) * tq - 1)
    def _():
        # kept: at or over the query's threshold, and a key at all (the
        # sortable integers as signed ones: the top bit turned)
        top = jnp.int32(-2 ** 31)
        keys = keys_ref[0, 0] ^ top                           # [tq, tk]
        keep = (keys >= (thr_ref[0] ^ top)) & (keys != top)
        # past the call's last position lies what a recycled page left:
        # 0 * NaN is NaN, so select
        row = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
        here = row < last_ref[r]
        for g in range(kvh):
            lanes = pl.ds(g * hd, hd)
            kk = k_ref[0, :, lanes]
            vv = jnp.where(here, v_ref[0, :, lanes], 0)
            for h in range(group):
                n = g * group + h
                s = jax.lax.dot_general(
                    q_ref[0, n], kk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(keep, s * scale, NEG)
                m_prev = m_ref[n]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
                l_ref[n] = alpha * l_ref[n] + p.sum(axis=1, keepdims=True)
                acc_ref[n] = alpha * acc_ref[n] + jnp.dot(
                    p.astype(vv.dtype), vv,
                    preferred_element_type=jnp.float32)
                m_ref[n] = m_new

    @pl.when(j == nk - 1)
    def _():
        for n in range(kvh * group):
            o_ref[0, n] = (acc_ref[n] / l_ref[n]).astype(o_ref.dtype)


def masked_attend_shapes_ok(tc: int, S: int, kb: int, hd: int,
                            dtype) -> bool:
    """Whether ``masked_attend`` takes a block of ``tc`` queries over
    ``S`` keys laid down ``kb`` at a time: whole programs, whole steps,
    whole lane tiles."""
    return (tc % KERNEL_TQ == 0 and kb % KERNEL_TK == 0 and S % kb == 0
            and hd % 128 == 0
            and jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16))


def masked_attend(q, k_row, v_row, keys, thr, pos, last):
    """Attention of ``q`` [b, heads, tc, hd] (heads grouped by key-value
    head) over ``k_row``, ``v_row`` [b, S, kv_heads * hd], each query
    over the keys whose index score is at or over its threshold:
    ``keys`` [S // kb, b, tc, kb] (``sortable`` scores, 0 for no key, as
    ``attend_block`` lays them down) against ``thr`` [b, tc] (no ties at
    a threshold: the caller's case). A running softmax over key steps of
    ``KERNEL_TK``, the mask made in the kernel:
    scores never leave the chip's fast memory (XLA's own attend writes
    them out and reads them back, 0.31 s a layer of a 32 k prefill
    measured, PERF.md). ``pos`` [b]: the first query's position;
    ``last`` [b]: positions past it are not read. Returns ``q``'s shape
    and dtype."""
    as_int = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    return _masked_attend(q, k_row, v_row, as_int(keys),
                          as_int(thr)[..., None], pos, last, _pa.INTERPRET)


@functools.partial(jax.jit, static_argnums=(7,))
def _masked_attend(q, k_row, v_row, keys, thr, pos, last, interpret):
    b, heads, tc, hd = q.shape
    S, width = k_row.shape[1], k_row.shape[2]
    kvh = width // hd
    kb = keys.shape[-1]
    tq, tk = KERNEL_TQ, KERNEL_TK
    nk, per = S // tk, kb // tk

    def upto(r, i, j, pos_ref, _last):
        return jnp.minimum(j, (pos_ref[r] + (i + 1) * tq - 1) // tk)

    kernel = functools.partial(
        _masked_kernel, kvh=kvh, group=heads // kvh, hd=hd, tq=tq, tk=tk,
        nk=nk, scale=1.0 / math.sqrt(hd))
    qo = pl.BlockSpec((1, heads, tq, hd), lambda r, i, j, *_: (r, 0, i, 0))
    kv = pl.BlockSpec((1, tk, width),
                      lambda r, i, j, *s: (r, upto(r, i, j, *s), 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, tc // tq, nk),
            in_specs=[qo, kv, kv,
                      pl.BlockSpec(
                          (1, 1, tq, tk),
                          lambda r, i, j, *s: (upto(r, i, j, *s) // per, r,
                                               i, upto(r, i, j, *s) % per)),
                      pl.BlockSpec((1, tq, 1),
                                   lambda r, i, j, *_: (r, i, 0))],
            out_specs=qo,
            scratch_shapes=[pltpu.VMEM((heads, tq, 1), jnp.float32),
                            pltpu.VMEM((heads, tq, 1), jnp.float32),
                            pltpu.VMEM((heads, tq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="sparse_masked_prefill",
    )(pos.astype(jnp.int32), last.astype(jnp.int32), q, k_row, v_row, keys,
      thr)


# -- the index of a decode step, as a kernel ---------------------------------


def index_pool_shape(pages: int, page: int, d: int) -> tuple:
    """The shape of a layer's pool of index keys: a page's ``page * d``
    values side by side, cut into rows of ``LANES`` where that is whole
    rows (``[pages, 8, 128]`` at 16 keys of 64: a page is one whole tile,
    2 KB in one place, which a kernel may copy alone; of a ``[pages,
    1024]`` array Mosaic slices only aligned groups of 8 rows), else one
    row a page."""
    width = page * d
    lanes = LANES if width % LANES == 0 else width
    return (pages, width // lanes, lanes)


def index_path(qi, ki_pool, page: int, mb: int) -> str:
    """Which implementation ``attend_rows`` takes for the index scores of
    queries ``qi`` [b, t, J, d], from what the code can observe:
    ``INDEX_KERNEL`` (``index_keys_paged``) on a TPU, or under
    ``paged_attention.INTERPRET``, when queries and pool share one of
    float32 and bfloat16, a lane row of the pool holds whole positions
    and the table is whole chunks of ``INDEX_CHUNK`` pages; on a TPU also
    only when a page is whole (8, 128) tiles, a chunk's lane rows whole
    lane tiles and the heads whole sublane tiles. Else ``INDEX_GATHER``:
    the row's table gathered and ``index_scores_paged``."""
    _, sub, lanes = ki_pool.shape
    heads, d = qi.shape[2:]
    ok = (qi.dtype == ki_pool.dtype
          and ki_pool.dtype in (jnp.float32, jnp.bfloat16)
          and lanes % d == 0 and mb % INDEX_CHUNK == 0)
    if not _pa.INTERPRET:
        ok = (ok and _pa._on_tpu() and lanes == LANES and sub % 8 == 0
              and (INDEX_CHUNK * sub) % LANES == 0 and heads % 8 == 0)
    return INDEX_KERNEL if ok else INDEX_GATHER


def _index_kernel(bt_ref, pos_ref, w1_ref, w2_ref, ki_hbm, o_ref, kbuf, sem,
                  *, t, page, d, heads, ppc, mb, slots):
    r = pl.program_id(0)
    sub, lanes = kbuf.shape[2:]
    per = lanes // d            # positions a lane row of the pool holds
    rows = ppc * sub            # lane rows a chunk
    pos0 = pos_ref[r]
    kv_len = jnp.minimum(pos0 + t, mb * page)
    # a row redirected to the null page (inactive slot) reads one page
    kv_len = jnp.where(bt_ref[r * mb] == 0, jnp.minimum(kv_len, page),
                       kv_len)
    n_pages = pl.cdiv(kv_len, page)
    n_chunks = pl.cdiv(n_pages, ppc)
    unroll = math.gcd(ppc, INDEX_UNROLL)

    def copy(phys, slot, p):
        return pltpu.make_async_copy(ki_hbm.at[phys], kbuf.at[slot, p],
                                     sem.at[slot])

    # The scalar core starts a page's copy in some tens of cycles, and
    # that, not the bytes, is what a chunk costs (PERF.md §6, PR 38): a
    # whole chunk's copies are started from an unrolled loop and waited
    # for once, as one copy of the chunk's size; only a row's last chunk
    # takes page-by-page loops over the pages it holds.
    def start(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(bt_ref[r * mb + c * ppc + p], slot, p).start()
            return carry

        def several(g, carry):
            for u in range(unroll):
                one(g * unroll + u, carry)
            return carry

        pl.when(live == ppc)(lambda: jax.lax.fori_loop(
            0, ppc // unroll, several, None))
        pl.when(live < ppc)(lambda: jax.lax.fori_loop(0, live, one, None))

    def wait(c, slot):
        live = jnp.minimum(ppc, n_pages - c * ppc)

        def one(p, carry):
            copy(0, slot, p).wait()
            return carry

        pl.when(live == ppc)(pltpu.make_async_copy(
            ki_hbm.at[pl.ds(0, ppc)], kbuf.at[slot], sem.at[slot]).wait)
        pl.when(live < ppc)(lambda: jax.lax.fori_loop(0, live, one, None))

    o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)
    for i in range(slots - 1):
        pl.when(i < n_chunks)(functools.partial(start, i, i))

    def body(c, carry):
        ahead = c + slots - 1
        pl.when(ahead < n_chunks)(
            lambda: start(ahead, jax.lax.rem(ahead, slots)))
        slot = jax.lax.rem(c, slots)
        wait(c, slot)

        # positions past the row's last share lane rows with resident
        # ones and hold what a recycled page held: 0 * NaN is NaN, so
        # they are cleared before the products (a lane row of a page
        # that was not copied reaches only its own places of the output,
        # which the select below leaves 0)
        @pl.when(c == n_chunks - 1)
        def _():
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
            resident = (kv_len - (c * rows + row) * per) * d
            kbuf[slot] = jnp.where(
                lane < resident, kbuf[slot].reshape(rows, lanes), 0
            ).reshape(ppc, sub, lanes)

        k = kbuf[slot].reshape(rows, lanes)
        # place (h, n) of a chunk's result: position h of lane row n
        at = ((c * rows
               + jax.lax.broadcasted_iota(jnp.int32, (per, rows), 1)) * per
              + jax.lax.broadcasted_iota(jnp.int32, (per, rows), 0))
        for j in range(t):
            # [per * heads, rows]: every head's product with each of a
            # lane row's positions (the queries laid out block-diagonally
            # on the lanes), the lane rows on the lanes of the result
            s = jax.lax.dot_general(w1_ref[0, j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            scores = (jax.nn.relu(s) * w2_ref[0, j]).reshape(
                per, heads, rows).sum(axis=1)
            seen = (at <= pos0 + j) & (at < kv_len)
            o_ref[0, j, :, pl.ds(pl.multiple_of(c * rows, rows), rows)] = (
                jnp.where(seen, _sortable_i32(scores), 0))
        return carry

    jax.lax.fori_loop(0, n_chunks, body, None)


def index_keys_paged(qi, wi, ki_pool, block_table, cache_pos, page: int):
    """What ``attend_rows`` selects from, ``[b, t, S]`` uint32:
    ``sortable(I[t, s])`` of index queries ``qi`` [b, t, J, d] with head
    weights ``wi`` [b, t, J] float32 against the index keys of the pages
    ``block_table`` [b, S // page] names in ``ki_pool``
    (``index_pool_shape``), where ``s`` is at or before the query's own
    position (``cache_pos`` the first's), and 0 elsewhere. The pool is
    walked where it lies: for row ``r`` pages ``block_table[r, 0 ..
    ceil((cache_pos[r] + t) / page) - 1]`` are copied ``INDEX_CHUNK`` at
    a time into VMEM while the chunk before is scored
    (``index_scores_paged``'s arithmetic: products in the operands'
    dtype, float32 sums); nothing past a row's last page is copied or
    scored, an inactive slot costs one page, and of the keys only the
    result leaves VMEM. ``index_path`` says whether the shapes qualify."""
    return _index_keys_paged(qi, wi, ki_pool, block_table, cache_pos,
                             int(page), INDEX_CHUNK, INDEX_SLOTS,
                             _pa.INTERPRET)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _index_keys_paged(qi, wi, ki_pool, block_table, cache_pos, page, ppc,
                      slots, interpret):
    b, t, heads, d = qi.shape
    _, sub, lanes = ki_pool.shape
    mb = block_table.shape[1]
    per = lanes // d
    # w1[(h, j), l] = qi[j, l % d] where lane l holds position h of its
    # lane row, else 0; w2[(h, j)] = wi[j]
    own = (jnp.arange(per)[:, None] == jnp.arange(lanes) // d)[:, None, :]
    w1 = jnp.where(own, jnp.tile(qi, per)[:, :, None], 0).reshape(
        b, t, per * heads, lanes)
    w2 = jnp.tile(wi, per)[..., None]
    kernel = functools.partial(_index_kernel, t=t, page=page, d=d,
                               heads=heads, ppc=ppc, mb=mb, slots=slots)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, t, per * heads, lanes),
                             lambda r, *_: (r, 0, 0, 0)),
                pl.BlockSpec((1, t, per * heads, 1),
                             lambda r, *_: (r, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, t, per, mb * sub),
                                   lambda r, *_: (r, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, ppc, sub, lanes), ki_pool.dtype),
                pltpu.SemaphoreType.DMA((slots,))]),
        out_shape=jax.ShapeDtypeStruct((b, t, per, mb * sub), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="sparse_index_decode",
    )(block_table.reshape(-1).astype(jnp.int32), cache_pos.astype(jnp.int32),
      w1, w2, ki_pool)
    # the kernel's place (h, n) is position n * per + h of the row
    return jax.lax.bitcast_convert_type(
        jnp.swapaxes(out, 2, 3), jnp.uint32).reshape(b, t, mb * page)
