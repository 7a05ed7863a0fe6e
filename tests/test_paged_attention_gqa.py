"""The grouped paged-attention kernel (``ops/paged_attention.py:
paged_attention_gqa``: several query heads a key-value head, float32 or
bfloat16, a window or none) under the Pallas interpreter against a
gathered window, and its dispatch point. Through the engine against the
model's reference: ``tests/test_cohere2_moe.py``; compiled for the chip at
the served sizes: ``tests/test_chip_compile.py``."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gym_tpu.ops.paged_attention as pa

KVH, G, HD, KPAGE, MB, POOL = 2, 4, 16, 8, 12, 60


def _gathered(q, kp, vp, bt, pos, window):
    b, kvh, t, _g, hd = q.shape
    s = bt.shape[1] * kp.shape[1]
    k = kp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    v = vp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    att = jnp.einsum("bktgd,bskd->bktgs", q.astype(jnp.float32),
                     k) / np.sqrt(hd)
    qpos = pos[:, None] + jnp.arange(t)[None]
    col = jnp.arange(s)[None, None]
    seen = col <= qpos[..., None]
    if window:
        seen = seen & (col > qpos[..., None] - window)
    att = jax.nn.softmax(jnp.where(seen[:, None, :, None], att, -jnp.inf),
                         -1)
    return jnp.einsum("bktgs,bskd->bktgd", att, v)


# a tile of 8 positions (32 rows a head) by chunks of 32 keys in place of
# 256 by 512: a row of 96 positions then has what a 20 k prompt has at the
# served tile, several query tiles a call and several chunks a tile
SMALL = {"_GQA_ROWS": 32, "_GQA_CHUNKS": (16, 32)}


def _set_tile(monkeypatch, tile):
    for name, value in (tile or {}).items():
        monkeypatch.setattr(pa, name, value)


@pytest.mark.parametrize("t,pos,tile", [
    (1, [0, 5, 40, 95], None), (8, [0, 8, 30, 88], None),
    (40, [0, 3, 50, 56], None),
    # three query tiles, the last ragged; a row from 0, one whose tiles
    # all lie in chunk 0, one (70) whose tiles have interior AND edge
    # chunks, one (37) whose tiles straddle a chunk's end
    (20, [0, 5, 70, 37], SMALL),
    # a tile that ends on a chunk's last key and one that starts on a
    # chunk's first; the row's end inside the last tile (75 + 21 = 96)
    (21, [24, 32, 64, 75], SMALL)],
    ids=["decode", "chunk8", "prefill40", "tiles_ragged", "tiles_aligned"])
@pytest.mark.parametrize("window", [0, 20, 72],
                         ids=["full", "window20", "window72"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_grouped_kernel_equals_a_gathered_window(monkeypatch, dtype, tol,
                                                 window, t, pos, tile):
    """Four query heads a key-value head, rows at different depths of
    their tables (the first page, mid-page, past the window, the last
    page), under the Pallas interpreter. With the small tile a window of
    20 cuts a chunk at its lower edge and leaves no chunk unmasked; one
    of 72 leaves a chunk between its lower edge and the diagonal."""
    monkeypatch.setattr(pa, "INTERPRET", True)
    _set_tile(monkeypatch, tile)
    rng = np.random.default_rng(t + window)
    b = len(pos)
    q = jnp.asarray(rng.standard_normal((b, KVH, t, G, HD)), dtype)
    kp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    bt = jnp.asarray(1 + rng.permutation(POOL - 1)[:b * MB].reshape(b, MB),
                     jnp.int32)
    if tile:
        run, unmasked = pa.gqa_chunks(pos, t, G, KVH, KPAGE, MB, window)
        assert run > unmasked and bool(unmasked) == (window != 20)
    pos = jnp.asarray(pos, jnp.int32)
    out = pa.paged_attention_gqa(q, kp, vp, bt, pos, window=window)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _gathered(q, kp, vp, bt, pos, window)
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < tol


@pytest.mark.parametrize("t,pos,window,tile", [
    (1, 90, 20, None),            # keys 71..90
    # tiles of 8 from 64, 72 and 80: to the first, chunk 0 (keys 0..31)
    # is its window's lower edge (pages 0..2 are not copied), chunk 1
    # runs without masks beside it and chunk 2 holds the diagonal
    (20, 64, 40, SMALL)], ids=["decode", "tiles"])
def test_grouped_kernel_never_reads_pages_before_the_window(
        monkeypatch, t, pos, window, tile):
    """A window layer of a long row: poison (NaN) in every page wholly
    older than the window, as a recycled page may hold, and in every
    position past the call's last changes nothing; the same poison inside
    the window does."""
    monkeypatch.setattr(pa, "INTERPRET", True)
    _set_tile(monkeypatch, tile)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, KVH, t, G, HD)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)),
                     jnp.float32)
    bt = jnp.arange(1, MB + 1, dtype=jnp.int32)[None]
    last = pos + t - 1
    want = pa.paged_attention_gqa(q, kp, vp, bt, jnp.asarray([pos]),
                                  window=window)
    # pages wholly before the FIRST query's window, and the positions
    # past the last query in its page and in the pages after it
    old = jnp.arange(1, 1 + (pos - window + 1) // KPAGE)
    kn = kp.at[old].set(jnp.nan).at[2 + last // KPAGE:].set(jnp.nan)
    vn = vp.at[old].set(jnp.nan).at[2 + last // KPAGE:].set(jnp.nan)
    kn = kn.at[1 + last // KPAGE, last % KPAGE + 1:].set(jnp.nan)
    vn = vn.at[1 + last // KPAGE, last % KPAGE + 1:].set(jnp.nan)
    assert len(old) and bool(jnp.isnan(kn[1 + last // KPAGE]).any())
    got = pa.paged_attention_gqa(q, kn, vn, bt, jnp.asarray([pos]),
                                 window=window)
    np.testing.assert_array_equal(got, want)
    inside = pa.paged_attention_gqa(q, kp.at[1 + pos // KPAGE].set(jnp.nan),
                                    vp, bt, jnp.asarray([pos]),
                                    window=window)
    assert np.isnan(np.asarray(inside)).any()


@pytest.mark.parametrize("t,window,tile", [
    (1, 0, None), (1, 20, None), (20, 0, SMALL), (20, 20, SMALL),
    (21, 72, SMALL), (2048, 0, None), (2048, 4096, None)],
    ids=["decode", "decode_window", "tiles", "tiles_window20",
         "tiles_window72", "served_full", "served_window"])
def test_gqa_chunks_equals_a_count_by_a_plain_loop(monkeypatch, t, window,
                                                   tile):
    """``gqa_chunks`` against a loop over rows, query tiles, chunks,
    queries and keys: a chunk is walked if it holds a key some query of
    the tile may see or a page the tile copies, and runs without masks if
    every query of the tile sees every key of it."""
    _set_tile(monkeypatch, tile)
    page, mb = (KPAGE, MB) if tile or t == 1 else (16, 1024)
    tq, ch = pa.gqa_tile(t, G, KVH, page)
    rng = np.random.default_rng(t + window)
    rows = [0, 5] + rng.integers(0, mb * page - t, 4).tolist()
    run = unmasked = 0
    for pos in rows:
        for j0 in range(0, t, tq):
            qs = [pos + j for j in range(j0, min(j0 + tq, t))]
            lo = max(qs[0] - window + 1, 0) // page * page if window else 0
            hi = min(qs[-1] + 1, mb * page)
            for c in range(-(-mb * page // ch)):
                keys = range(c * ch, (c + 1) * ch)
                if c * ch >= hi or (c + 1) * ch <= lo // ch * ch:
                    continue
                run += 1
                # the queries' extremes decide for all of them
                unmasked += t > 1 and all(
                    k <= q and k < hi and (not window or k > q - window)
                    for q in (qs[0], qs[-1]) for k in keys)
    got = pa.gqa_chunks(rows, t, G, KVH, page, mb, window)
    assert got.tolist() == [run, unmasked]
    if t == 2048:
        assert (tq, ch) == (256, 512) and 0 < unmasked < run


def _served_config(model):
    if model == "qwen3-next":
        from gym_tpu.models.qwen3_next import Qwen3NextConfig
        return dataclasses.replace(
            Qwen3NextConfig(num_hidden_layers=8).decode_config(),
            page_size=16, kv_pages=65536)
    from gym_tpu.models.cohere2_moe import Cohere2MoeConfig
    return dataclasses.replace(
        Cohere2MoeConfig(num_hidden_layers=4).decode_config(),
        page_size=16, kv_pages=12288)


@pytest.mark.parametrize("bucket,start,suffix", [
    (16384, 0, 12000), (8192, 0, 4097), (2048, 0, 1500), (16, 4096, 9)],
    ids=["three_passes", "two_passes", "one_call", "a_suffix"])
@pytest.mark.parametrize("model", ["qwen3-next", "command-a-plus"])
def test_a_config_counts_a_prefills_chunks_call_by_call(monkeypatch, model,
                                                        bucket, start,
                                                        suffix):
    """``config.prefill_counted``: for every layer whose attend is the
    grouped kernel, the sum of ``gqa_chunks`` over the passes that ran
    (4,096 positions each, the passes of padding skipped) and the calls
    of 2,048 queries a pass makes; nothing where the gather path runs
    (off the TPU)."""
    cfg = _served_config(model)
    assert cfg.prefill_counted(bucket, start, suffix) == {}
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    got = cfg.prefill_counted(bucket, start, suffix)
    kernel = [i for i, path in enumerate(cfg.attend_paths())
              if path in (pa.KERNEL, pa.KERNEL_WINDOW)]
    assert kernel and sorted(got) == sorted(
        f"layers_{i}/self_attn/gqa_chunks" for i in kernel)
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    step = min(bucket, 4096)
    for i in kernel:
        window = cfg.window(i) if model == "command-a-plus" else 0
        want = np.zeros(2, np.int64)
        for lo in range(0, bucket, step):
            if lo >= suffix:
                continue            # a pass of padding
            tc = min(step, 2048)
            for c in range(0, step, tc):
                want += pa.gqa_chunks(
                    [start + lo + c], tc, group, cfg.num_key_value_heads,
                    16, cfg.block_size // 16, window)
        np.testing.assert_array_equal(
            got[f"layers_{i}/self_attn/gqa_chunks"], want)
        assert 0 <= want[1] < want[0]


@pytest.mark.parametrize("counters,value", [
    ({"layers_3/self_attn/gqa_chunks": [400, 372],
      "layers_7/self_attn/gqa_chunks": [400, 372],
      "layers_3/self_attn/pages": [9000, 0]}, 93.0),
    ({"layers_0/self_attn/gqa_chunks": [90, 30],
      "layers_1/self_attn/gqa_chunks": [300, 270]}, 100 * 300 / 390),
    ({"layers_3/self_attn/pages": [9000, 0]}, None),    # the parent's
    ({"layers_3/self_attn/gqa_chunks": [0, 0]}, None),  # no prefill
    ({}, None)], ids=["full_layers", "window_and_full", "no_counter",
                      "no_prefill", "no_counters"])
def test_serve_gqa_prefill_unmasked_chunks_pct(counters, value):
    """The per-layer metric's reader on ``/stats``' ``model_counters``
    over a window; listed in ``BENCHMARK.json`` for the two cells
    whose models run the grouped kernel."""
    from perfbench import harness
    read = harness.load_reader(os.path.join(harness.ROOT, "perfbench"),
                               "serve_gqa_prefill_unmasked_chunks_pct")
    got = read({"kind": "closed", "model_counters": counters})
    assert got == (pytest.approx(value) if value is not None else None)
    assert read({"kind": "closed"}) is None
    entry, = [m for m in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["per_layer"]
        if m["name"] == "serve_gqa_prefill_unmasked_chunks_pct"]
    assert entry == {
        "name": "serve_gqa_prefill_unmasked_chunks_pct", "unit": "%",
        "better": "higher", "source": "program_counter", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["qwen3-next-80b-a3b.serve-closed-longctx",
                      "command-a-plus.serve-closed-rag"]}


# -- the dispatch point ------------------------------------------------------


@pytest.mark.parametrize("dtype,kv,page,window,want", [
    (jnp.bfloat16, jnp.bfloat16, 16, 0, pa.KERNEL),
    (jnp.bfloat16, jnp.bfloat16, 16, 4096, pa.KERNEL_WINDOW),
    (jnp.float32, jnp.float32, 8, 0, pa.KERNEL),
    (jnp.bfloat16, jnp.bfloat16, 8, 0, pa.GATHER),     # half a bf16 tile
    (jnp.bfloat16, jnp.float32, 16, 0, pa.GATHER),     # mixed dtypes
    (jnp.bfloat16, jnp.int8, 16, 0, pa.GATHER),
    (jnp.bfloat16, jnp.bfloat16, 48, 0, pa.GATHER),    # 256 % 48
], ids=["bf16", "bf16_window", "f32_page8", "bf16_page8", "mixed", "int8",
        "page48"])
def test_dispatch_of_the_grouped_attend(monkeypatch, dtype, kv, page, window,
                                        want):
    """What ``paged_attend_path`` answers for a head dimension of 128 on
    a TPU; off it (all of tier-1) the gather path."""
    assert pa.paged_attend_path(1024, page, dtype, kv, head_dim=128,
                                window=window) == pa.GATHER
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    assert pa.paged_attend_path(1024, page, dtype, kv, head_dim=128,
                                window=window) == want
    # a head that does not fill a lane tile: never this kernel
    assert pa.paged_attend_path(512, page, dtype, kv, head_dim=64,
                                window=window) == pa.GATHER




# -- learned sparse attention (ops/sparse_attention.py) ----------------------

import gym_tpu.ops.sparse_attention as sa  # noqa: E402

J, DI = 3, 8        # index heads and their dimension


def _topk_mask(scores, seen, k):
    """The kept set as ``lax.top_k`` names it (ties to the lower index)."""
    s = jnp.where(seen, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _v, idx = jax.lax.top_k(s, min(k, s.shape[-1]))
    mask = jnp.zeros(s.shape, bool)
    mask = jnp.put_along_axis(mask, idx, True, axis=-1, inplace=False)
    return mask & seen


def _tied_scores(rng, shape):
    """Scores with plateaus of equal values across the threshold: a few
    distinct levels, zeros of both signs, and some distinct values."""
    levels = np.asarray([-2.5, -0.0, 0.0, 0.75, 0.75, 1.5, 3.0], np.float32)
    s = levels[rng.integers(0, len(levels), shape)]
    distinct = rng.random(shape) < 0.3
    return jnp.asarray(np.where(distinct, rng.standard_normal(shape), s),
                       jnp.float32)


@pytest.mark.parametrize("k", [1, 5, 16, 64], ids=lambda k: f"k{k}")
def test_kept_set_equals_top_k_with_planted_ties(k):
    """The threshold search keeps exactly ``lax.top_k``'s set on seeded
    scores with planted ties at the threshold, rows with fewer than ``k``
    keys and ``-0.0`` beside ``0.0``."""
    rng = np.random.default_rng(k)
    scores = _tied_scores(rng, (6, 64))
    n_seen = jnp.asarray([1, 3, 17, 40, 63, 64])
    seen = jnp.arange(64)[None, :] < n_seen[:, None]
    keys = jnp.where(seen, sa.sortable(scores), 0)
    got = sa.kept_mask(keys, k)
    want = _topk_mask(scores, seen, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got).sum(-1)
            == np.minimum(np.asarray(n_seen), k)).all()


def test_sortable_orders_as_the_numbers_do():
    x = jnp.asarray([-jnp.inf, -3.0, -1e-30, -0.0, 0.0, 1e-30, 2.0,
                     jnp.inf], jnp.float32)
    u = np.asarray(sa.sortable(x)).astype(np.int64)
    assert u[3] == u[4] and (np.diff(np.delete(u, 3)) > 0).all()
    assert u.min() > 0


def _sparse_case(rng, b, t, dtype):
    q = jnp.asarray(rng.standard_normal((b, KVH, t, G, HD)), dtype)
    qi = jnp.asarray(rng.standard_normal((b, t, J, DI)), dtype)
    wi = jnp.asarray(rng.standard_normal((b, t, J)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((POOL, KPAGE, KVH * HD)), dtype)
    kip = jnp.asarray(rng.standard_normal(
        sa.index_pool_shape(POOL, KPAGE, DI)), dtype)
    bt = jnp.asarray(1 + rng.permutation(POOL - 1)[:b * MB].reshape(b, MB),
                     jnp.int32)
    return q, qi, wi, kp, vp, kip, bt


def _masked(q, qi, wi, kp, vp, kip, bt, pos, topk):
    """Plain grouped attention over the positions ``lax.top_k`` keeps of
    the index scores, from gathered windows."""
    b, kvh, t, _g, hd = q.shape
    s = bt.shape[1] * kp.shape[1]
    k = kp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    v = vp[bt].reshape(b, s, kvh, hd).astype(jnp.float32)
    ki = kip[bt].reshape(b, s, -1)
    scores = sa.index_scores(qi, wi, ki)
    qpos = pos[:, None] + jnp.arange(t)[None]
    seen = jnp.arange(s)[None, None] <= qpos[..., None]
    keep = _topk_mask(scores, seen, topk)
    att = jnp.einsum("bktgd,bskd->bktgs", q.astype(jnp.float32),
                     k) / np.sqrt(hd)
    att = jax.nn.softmax(jnp.where(keep[:, None, :, None], att, -jnp.inf),
                         -1)
    return jnp.einsum("bktgs,bskd->bktgd", att, v)


@pytest.mark.parametrize("topk", [6, 200], ids=["topk6", "keeps_all"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_sparse_decode_attends_over_the_kept_keys(dtype, tol, topk):
    """A decode step's rows at different depths: the gathered kept keys
    give what masked attention over ``lax.top_k``'s set gives; a row of
    at most ``topk`` positions (every row at ``topk`` 200) is plain
    grouped attention."""
    rng = np.random.default_rng(topk)
    q, qi, wi, kp, vp, kip, bt = _sparse_case(rng, 4, 1, dtype)
    pos = jnp.asarray([0, 5, 40, 95], jnp.int32)
    out = sa.attend_rows(q, qi, wi, kp, vp, kip, bt, pos, topk)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _masked(q, qi, wi, kp, vp, kip, bt, pos, topk)
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < tol
    if topk >= MB * KPAGE:
        plain = _gathered(q, kp, vp, bt, pos, 0)
        assert float(jnp.abs(out.astype(jnp.float32) - plain).max()) < tol


@pytest.mark.parametrize("topk", [6, 200], ids=["topk6", "keeps_all"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_sparse_prefill_block_attends_over_the_kept_keys(topk, ties):
    """A block of 24 queries that starts mid-row, key blocks of 16 of
    which the later ones are never visited: every query keeps its own
    set, the exact one also where index scores tie at the threshold
    (index keys repeated along the row)."""
    rng = np.random.default_rng(topk + ties)
    q, qi, wi, kp, vp, kip, bt = _sparse_case(rng, 2, 24, jnp.float32)
    if ties:
        # each row's index keys take 5 distinct values only
        few = jnp.asarray(rng.standard_normal((5, DI)), jnp.float32)
        pick = jnp.asarray(rng.integers(0, 5, (POOL, KPAGE)))
        kip = few[pick].reshape(POOL, KPAGE * DI)
    pos = jnp.asarray([3, 37], jnp.int32)
    s = MB * KPAGE
    out = sa.attend_block(
        q, qi, wi, kp[bt].reshape(2, s, KVH, HD),
        vp[bt].reshape(2, s, KVH, HD), kip[bt].reshape(2, s, DI), pos,
        topk, key_block=16)
    want = _masked(q, qi, wi, kp, vp, kip, bt, pos, topk)
    assert float(jnp.abs(out - want).max()) < 1e-5
    if topk >= s:
        plain = _gathered(q, kp, vp, bt, pos, 0)
        assert float(jnp.abs(out - plain).max()) < 1e-5


def test_dispatch_of_the_sparse_attend():
    """A layer with ``sparse_topk`` takes the sparse path on every
    backend and dtype; its id holds no ``gather``."""
    for dtype in (jnp.float32, jnp.bfloat16):
        assert pa.paged_attend_path(512, 16, dtype, dtype, head_dim=128,
                                    sparse_topk=2048) == pa.SPARSE
    assert pa.GATHER not in pa.SPARSE


@pytest.mark.parametrize("s,k", [(64, 5), (96, 40), (384, 100)],
                         ids=["one_block", "three_blocks", "blocks_of_128"])
def test_compact_lists_the_marked_positions_in_rising_order(s, k):
    """No sort, scatter or gather: every row's marked positions come out
    as ``nonzero`` gives them, rows with none and with exactly ``k``
    marked among them."""
    rng = np.random.default_rng(s)
    counts = [0, 1, k // 2, k, k - 1]
    mask = np.zeros((len(counts), s), bool)
    for r, n in enumerate(counts):
        mask[r, rng.permutation(s)[:n]] = True
    idx, there = sa.compact(jnp.asarray(mask), k)
    assert idx.shape == there.shape == (len(counts), k)
    for r, n in enumerate(counts):
        assert np.asarray(there[r]).sum() == n
        np.testing.assert_array_equal(np.asarray(idx[r])[:n],
                                      np.nonzero(mask[r])[0])
        assert not np.asarray(idx[r])[n:].any()


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_masked_prefill_kernel_equals_the_plain_walk(monkeypatch, dtype, tol,
                                                     ties):
    """The Pallas kernel of a prefill block's attend (whole tiles: 256
    queries, key steps of 512, head dimension 128) under the interpreter
    against the same block in ``jax.numpy``: two rows that start at
    different depths, the second past the first key step, keys past the
    block's last position never read (NaNs planted there)."""
    kvh, g, hd, di, tc, s, topk = 2, 2, 128, 8, 256, 1536, 100
    rng = np.random.default_rng(int(ties))
    q = jnp.asarray(rng.standard_normal((2, kvh, tc, g, hd)), dtype)
    qi = jnp.asarray(rng.standard_normal((2, tc, J, di)), dtype)
    wi = jnp.asarray(rng.standard_normal((2, tc, J)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, kvh, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((2, s, kvh, hd)), dtype)
    ki = jnp.asarray(rng.standard_normal((2, s, di)), dtype)
    if ties:
        few = jnp.asarray(rng.standard_normal((7, di)), dtype)
        ki = few[jnp.asarray(rng.integers(0, 7, (2, s)))]
    pos = jnp.asarray([40, 700], jnp.int32)
    v = v.at[:, 700 + tc:].set(jnp.nan)
    args = (q, qi, wi, k, v, ki, pos, topk)
    assert not sa.masked_attend_shapes_ok(tc, s, 256, hd, dtype)
    want = sa.attend_block(*args, key_block=512)
    monkeypatch.setattr(pa, "INTERPRET", True)
    assert sa.masked_attend_shapes_ok(tc, s, 512, hd, dtype)
    got = sa.attend_block(*args, key_block=512)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < tol
