"""Learned sparse attention over the page pool: a query scores every
resident key of its row with a small index, keeps the ``topk`` best and
attends over those alone (DeepSeek Sparse Attention's published form).

A layer keeps three things a position in the engine's pools: the key and
the value (``[pages, page, kv_heads * head_dim]``, as every paged layer)
and ONE index key shared by the index's heads (``[pages, page *
index_dim]``: a page's index keys side by side, whole lane tiles). For
query ``t`` of a row and resident position ``s <= t``::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)
    S_t     = the topk positions of largest I[t, s]; ties to the lower s
    o[t, h] = softmax over S_t of q[t, h] . k[s, h // G] / sqrt(d), times v

``I`` is compared as a real number (``-0.0`` is ``0.0``). A row of at
most ``topk`` positions keeps them all and the result is plain grouped
attention. Nothing approximates: the kept set is the exact one.

Two implementations, by the number of queries a row brings:

* ``attend_rows`` (a decode step, a speculative step, a prefill of a few
  tokens): the row's index keys are gathered through its block table,
  the kept positions are found (``kept_mask``) and listed (``compact``)
  without a sort (``lax.top_k`` of 2,048 from 36,864 is a sort on the
  chip: 4.4 ms a layer measured, PERF.md) and only THEIR keys and values
  are gathered out of the pools, ``topk`` rows of a kilobyte a query,
  whatever the row holds.
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

Device scopes: ``attn.index`` (scores), ``attn.select`` (the selection),
``attn.sparse`` (the attend over the kept keys).
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


def sortable(x):
    """float32 -> uint32 that orders as the numbers do (``-0.0`` with
    ``0.0``); every finite value and both infinities map above 0, which
    is left for "no key here"."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def index_scores(qi, wi, ki):
    """``I`` [b, t, s] float32 of index queries ``qi`` [b, t, J, d] with
    head weights ``wi`` [b, t, J] float32 against index keys ``ki``
    [b, s, d]: products in the operands' dtype, sums in float32."""
    s = jnp.einsum("btjd,bsd->btjs", qi, ki,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * wi[..., None]).sum(axis=2)


def index_scores_paged(qi, wi, ki_pages, page: int):
    """``index_scores`` of a few queries a row against the row's pages
    of index keys as the pool holds them, ``ki_pages`` [b, pages, page *
    d] (a page's keys side by side), without taking the pages apart
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
    with jax.named_scope("attn.index"):
        scores = index_scores_paged(qi, wi, ki_pool[block_table], page)
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
