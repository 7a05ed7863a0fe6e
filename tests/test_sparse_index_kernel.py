"""The decode step's index as a kernel
(``gym_tpu/ops/sparse_attention.py:index_keys_paged``: a row's live pages
of index keys copied where they lie and scored in VMEM) against the path
it replaces on a TPU, the gather of every row's whole table and
``index_scores_paged``, under the Pallas interpreter on the CPU
(``paged_attention.INTERPRET``).

One batch holds the rows that differ in kind: an inactive slot (its
table the null page), a row of one position (of ``t``, the fewest a call
of ``t`` queries can hold), one that ends on a page boundary and one that
ends one past it, one that ends inside the first chunk of pages, one on a
chunk boundary and one past it, and a full table. Two geometries: the
chip's (pages of 16 keys of 64, whole lane tiles, chunks of 64 pages) and
the rehearsal's (pages of 4 keys of 8 on one row of 32, chunks of 8).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.ops import paged_attention as pa
from gym_tpu.ops import sparse_attention as sa

HEADS = 4
DTYPES = [jnp.float32, jnp.bfloat16]
# page, index dimension, pages a chunk, pages a row's table
GEOMETRIES = {"chip": (16, 64, 64, 128), "rehearsal": (4, 8, 8, 32)}


def _ids(params):
    return [p.__name__ if hasattr(p, "__name__") else str(p)
            for p in params]


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pa, "INTERPRET", True)


def _unsort(keys):
    """The float32 behind ``sortable``'s uint32 (which is never 0)."""
    keys = np.asarray(keys, np.uint32)
    bits = np.where(keys >> 31 == 1, keys & np.uint32(0x7FFFFFFF), ~keys)
    return bits.astype(np.uint32).view(np.float32)


def _rows(t, page, chunk, mb):
    """Resident positions (``cache_pos + t``) of the batch's rows; 0 is
    the inactive slot."""
    ch = chunk * page
    return [0, t, 5 * page, 5 * page + 1, ch - 2 * page - 3, ch, ch + 1,
            mb * page]


def _batch(monkeypatch, geometry, dtype, t, seed=0, spaced=False):
    """``(qi, wi, pool, block_table, cache_pos, page, lens)``: rows of
    ``_rows`` over pages dealt at random from one pool. ``spaced``:
    index keys and queries such that a position's score is a whole
    number of its own times the query's weight, so that no two scores of
    a query lie within rounding of each other."""
    page, d, chunk, mb = GEOMETRIES[geometry]
    monkeypatch.setattr(sa, "INDEX_CHUNK", chunk)
    rng = np.random.default_rng(seed)
    lens = _rows(t, page, chunk, mb)
    b, S = len(lens), mb * page
    n_pages = 1 + sum(-(-n // page) for n in lens) + 7
    deal = rng.permutation(np.arange(1, n_pages))
    bt, pos, at = np.zeros((b, mb), np.int32), np.zeros(b, np.int32), 0
    for r, n in enumerate(lens):
        if n:
            held = -(-n // page)
            bt[r, :held], at = deal[at:at + held], at + held
            pos[r] = n - t
    if spaced:
        # a row's position s holds (hi, lo, 0, ..) with 64 * hi + lo a
        # number of its own in the row; every head asks (64, 1, 0, ..)
        # times a power of two
        ki = np.zeros((n_pages, page, d), np.float32)
        for r, n in enumerate(lens):
            own = rng.permutation(S)[:-(-n // page) * page].reshape(-1, page)
            ki[bt[r, :len(own)], :, 0] = own // 64
            ki[bt[r, :len(own)], :, 1] = own % 64
        qi = np.zeros((b, t, HEADS, d), np.float32)
        scale = 2.0 ** rng.integers(-2, 3, (b, t, HEADS))
        qi[..., 0], qi[..., 1] = 64 * scale, scale
        wi = rng.uniform(0.5, 1.5, (b, t, HEADS))
    else:
        ki = rng.standard_normal((n_pages, page, d))
        qi = rng.standard_normal((b, t, HEADS, d))
        wi = rng.standard_normal((b, t, HEADS))
    ki[0] = 0                                       # the null page
    pool = jnp.asarray(ki, dtype).reshape(
        sa.index_pool_shape(n_pages, page, d))
    return (jnp.asarray(qi, dtype), jnp.asarray(wi, jnp.float32), pool,
            jnp.asarray(bt), jnp.asarray(pos), page, lens)


def _by_gather(qi, wi, pool, bt, pos, page):
    """What ``attend_rows`` builds on the gather path."""
    b, mb = bt.shape
    t = qi.shape[1]
    scores = sa.index_scores_paged(qi, wi, pool[bt].reshape(b, mb, -1),
                                   page)
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    at = jnp.arange(mb * page)[None, None, :]
    # an inactive slot costs the kernel one page; the gather reads the
    # null page again for every entry of the table
    seen = (at <= qpos[:, :, None]) & ((bt[:, :1, None] != 0) | (at < page))
    return jnp.where(seen, sa.sortable(scores), 0)


def _poisoned(pool, bt, lens, page):
    """``pool`` with NaN in every page no live row holds and in every
    position past a row's last (the null page stays what it is)."""
    flat = np.array(pool.astype(jnp.float32)).reshape(pool.shape[0], page,
                                                      -1)
    dirty = np.full(flat.shape, np.nan, np.float32)
    dirty[0] = flat[0]
    for r, n in enumerate(lens):
        for i in range(-(-n // page)):
            upto = min(page, n - i * page)
            dirty[int(bt[r, i]), :upto] = flat[int(bt[r, i]), :upto]
    return jnp.asarray(dirty, pool.dtype).reshape(pool.shape)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("t", [1, 4, 16])
@pytest.mark.parametrize("dtype", DTYPES, ids=_ids(DTYPES))
def test_keys_are_the_gathers(interpret, monkeypatch, geometry, dtype, t):
    """Exact zeros in exactly the gather's places (past each query's own
    position, and all of an inactive slot's but its one position), and
    the scores behind the other keys to float32's rounding of a sum in
    another order."""
    qi, wi, pool, bt, pos, page, _lens = _batch(monkeypatch, geometry,
                                               dtype, t)
    assert sa.index_path(qi, pool, page, bt.shape[1]) == sa.INDEX_KERNEL
    want = np.asarray(_by_gather(qi, wi, pool, bt, pos, page))
    got = np.asarray(sa.index_keys_paged(qi, wi, pool, bt, pos, page))
    assert got.dtype == np.uint32 and got.shape == want.shape
    np.testing.assert_array_equal(got == 0, want == 0)
    a, b = _unsort(want[want != 0]), _unsort(got[want != 0])
    assert np.abs(a).max() > 1.0
    assert np.abs(a - b).max() <= 1e-6 * np.abs(a).max()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("t", [1, 4, 16])
@pytest.mark.parametrize("dtype", DTYPES, ids=_ids(DTYPES))
def test_kept_set_is_the_gathers(interpret, monkeypatch, geometry, dtype,
                                 t):
    """Scores spaced wider than rounding (a whole number of the
    position's own times the query's weight): the two paths keep the
    same positions, as many as the query sees up to ``k``."""
    qi, wi, pool, bt, pos, page, lens = _batch(monkeypatch, geometry, dtype,
                                               t, seed=1, spaced=True)
    want = _by_gather(qi, wi, pool, bt, pos, page)
    got = sa.index_keys_paged(qi, wi, pool, bt, pos, page)
    k = 3 * page
    kept_w, kept_g = sa.kept_mask(want, k), sa.kept_mask(got, k)
    np.testing.assert_array_equal(np.asarray(kept_g), np.asarray(kept_w))
    counts = np.asarray(kept_g).sum(axis=-1)
    for r, n in enumerate(lens):
        for j in range(t):
            sees = n - t + j + 1 if n else min(j + 1, page)
            assert counts[r, j] == min(k, sees)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("t", [1, 4, 16])
@pytest.mark.parametrize("dtype", DTYPES, ids=_ids(DTYPES))
def test_nan_in_pages_and_places_no_row_holds_changes_nothing(
        interpret, monkeypatch, geometry, dtype, t):
    """A recycled page holds what it holds: NaN in every unused page and
    in every position past a row's last gives the keys of the clean pool
    to the bit (0 * NaN is NaN: nothing unseen is ever multiplied into a
    seen place)."""
    qi, wi, pool, bt, pos, page, lens = _batch(monkeypatch, geometry, dtype,
                                               t, seed=2)
    clean = np.asarray(sa.index_keys_paged(qi, wi, pool, bt, pos, page))
    dirty = _poisoned(pool, np.asarray(bt), lens, page)
    assert bool(jnp.isnan(dirty.astype(jnp.float32)).any())
    got = np.asarray(sa.index_keys_paged(qi, wi, dirty, bt, pos, page))
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("t", [1, 4, 16])
@pytest.mark.parametrize("dtype", DTYPES, ids=_ids(DTYPES))
def test_attend_rows_through_the_kernel_equals_the_gather(
        monkeypatch, geometry, dtype, t):
    """The whole of ``attend_rows`` (index, selection, the attend over
    the kept keys) on the same inputs through both paths."""
    qi, wi, pool, bt, pos, page, _lens = _batch(monkeypatch, geometry,
                                               dtype, t, seed=3, spaced=True)
    rng = np.random.default_rng(4)
    kvh, group, hd = 2, 2, 16
    b, pages = qi.shape[0], pool.shape[0]
    q = jnp.asarray(rng.standard_normal((b, kvh, t, group, hd)), dtype)
    null = np.arange(pages)[:, None, None] == 0
    k_pool, v_pool = (
        jnp.asarray(np.where(null, 0, rng.standard_normal(
            (pages, page, kvh * hd))), dtype) for _ in range(2))
    args = (q, qi, wi, k_pool, v_pool, pool, bt, pos, 3 * page)
    assert sa.index_path(qi, pool, page, bt.shape[1]) == sa.INDEX_GATHER
    want = np.asarray(sa.attend_rows(*args).astype(jnp.float32))
    monkeypatch.setattr(pa, "INTERPRET", True)
    assert sa.index_path(qi, pool, page, bt.shape[1]) == sa.INDEX_KERNEL
    got = np.asarray(sa.attend_rows(*args).astype(jnp.float32))
    assert np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


# -- which path runs ---------------------------------------------------------

CELL = dict(pages=32768, page=16, d=64, heads=16, mb=2304, q=jnp.bfloat16,
            pool=jnp.bfloat16)


@pytest.mark.parametrize("change,on_tpu,want", [
    ({}, True, sa.INDEX_KERNEL),                    # the Keye cell
    ({}, False, sa.INDEX_GATHER),                   # every other backend
    ({"q": jnp.float32, "pool": jnp.float32}, True, sa.INDEX_KERNEL),
    ({"q": jnp.float32}, True, sa.INDEX_GATHER),    # dtypes differ
    ({"q": jnp.float16, "pool": jnp.float16}, True, sa.INDEX_GATHER),
    ({"mb": 2304 + 32}, True, sa.INDEX_GATHER),     # not whole chunks
    ({"page": 4, "d": 8, "mb": 64}, True, sa.INDEX_GATHER),   # no lane tile
    ({"page": 8, "mb": 4608}, True, sa.INDEX_GATHER),   # half a tile a page
    ({"heads": 4}, True, sa.INDEX_GATHER),          # half a sublane tile
], ids=["cell", "off_tpu", "float32", "mixed", "float16", "ragged_table",
        "narrow_page", "half_tile", "few_heads"])
def test_the_path_follows_backend_dtype_and_shape(monkeypatch, change,
                                                  on_tpu, want):
    case = {**CELL, **change}
    monkeypatch.setattr(pa, "_on_tpu", lambda: on_tpu)
    pool = jax.ShapeDtypeStruct(
        sa.index_pool_shape(case["pages"], case["page"], case["d"]),
        case["pool"])
    qi = jax.ShapeDtypeStruct((16, 1, case["heads"], case["d"]), case["q"])
    assert sa.index_path(qi, pool, case["page"], case["mb"]) == want


def test_a_page_of_the_pool_is_whole_lane_rows():
    assert sa.index_pool_shape(32768, 16, 64) == (32768, 8, 128)
    assert sa.index_pool_shape(80, 4, 8) == (80, 1, 32)
    assert sa.index_pool_shape(48, 16, 8) == (48, 1, 128)
