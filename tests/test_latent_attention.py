"""The latent attention's two forms (``gym_tpu/ops/latent_attention.py``):
the absorbed attend over pages of latents (what a decode step runs)
against the expanded attend a head (what a prefill runs and what the
reference computes), the Pallas kernels under the interpreter against the
``jax.numpy`` paths, and yarn's frequencies and scale against sums done by
hand."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.models.cohere2_moe import rotate_interleaved
from gym_tpu.ops import latent_attention as la

H, RANK, ROPE, NOPE, DV, PAGE = 4, 32, 8, 16, 16, 8
LANES = la.pool_lanes(RANK, ROPE)
SCALE = la.softmax_scale(NOPE + ROPE, 64.0, 1.0)
# float32 operands on the CPU: the two forms differ by the order of
# float32 additions only (a 32-wide latent, rows of at most 64 positions)
TOL = 2e-5


def _draw(b, t, lens, seed=0):
    """Pages of latents for ``b`` rows whose cursors (the first new
    position) are ``lens - t``, the new positions written already;
    ``(q_nope, q_rope, pool, block_table, cache_pos, rows, w_uk, w_uv)``
    with ``rows`` [b, S, lanes] the same latents laid out a row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    mb = 8
    S = mb * PAGE
    rows = jnp.zeros((b, S, LANES)).at[:, :, :RANK + ROPE].set(
        jax.random.normal(ks[0], (b, S, RANK + ROPE)))
    # physical pages dealt out of order; page 0 is the null page
    perm = np.random.default_rng(seed).permutation(b * mb) + 1
    bt = jnp.asarray(perm.reshape(b, mb), jnp.int32)
    pool = jnp.zeros((b * mb + 1, PAGE, LANES)).at[bt.reshape(-1)].set(
        rows.reshape(b * mb, PAGE, LANES))
    # what lies past a row's cursor is a recycled page's old contents
    q_nope = jax.random.normal(ks[1], (b, t, H, NOPE))
    q_rope = jax.random.normal(ks[2], (b, t, H, ROPE))
    w_uk = jax.random.normal(ks[3], (RANK, H * NOPE)) / math.sqrt(RANK)
    w_uv = jax.random.normal(ks[4], (RANK, H * DV)) / math.sqrt(RANK)
    pos = jnp.asarray(lens, jnp.int32) - t
    return q_nope, q_rope, pool, bt, pos, rows, w_uk, w_uv


def _expanded(q_nope, q_rope, rows, pos, w_uk, w_uv):
    """Attention a head at the head's own sizes, keys and values built
    from the latents: [b, t, H, DV]."""
    b, t = q_nope.shape[:2]
    S = rows.shape[1]
    k_nope = (rows[..., :RANK] @ w_uk).reshape(b, S, H, NOPE)
    v = (rows[..., :RANK] @ w_uv).reshape(b, S, H, DV)
    k_rope = rows[..., RANK:RANK + ROPE]
    s = (jnp.einsum("bthd,bshd->bths", q_nope, k_nope)
         + jnp.einsum("bthd,bsd->bths", q_rope, k_rope)) * SCALE
    qpos = pos[:, None] + jnp.arange(t)[None, :]
    seen = jnp.arange(S)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
    return jnp.einsum("bths,bshd->bthd", jax.nn.softmax(s, -1), v)


def _absorbed(q_nope, q_rope, pool, bt, pos, w_uk, w_uv, path):
    b, t = q_nope.shape[:2]
    q_abs = jnp.einsum("bthd,rhd->bthr", q_nope,
                       w_uk.reshape(RANK, H, NOPE))
    z = la.decode_attend(jnp.concatenate([q_abs, q_rope], -1), pool, bt,
                         pos, RANK, SCALE, path)
    return jnp.einsum("bthr,rhd->bthd", z, w_uv.reshape(RANK, H, DV))


@pytest.mark.parametrize("t,lens", [(1, (1, 9, 40, 64)), (3, (3, 17, 64))],
                         ids=["decode_step", "verify_of_three"])
def test_absorbed_over_pages_equals_expanded_heads(t, lens):
    """``q_nope W_uk^T`` against the cached latent, ``W_uv`` after the
    softmax: the decode step's form gives what attention over expanded
    keys and values gives, for rows of a single position, rows that end
    inside a page and a full table, with the pages dealt out of order."""
    qn, qr, pool, bt, pos, rows, w_uk, w_uv = _draw(len(lens), t, lens)
    want = _expanded(qn, qr, rows, pos, w_uk, w_uv)
    got = _absorbed(qn, qr, pool, bt, pos, w_uk, w_uv, la.LATENT_GATHER)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.fixture()
def interpreter(monkeypatch):
    monkeypatch.setattr(la, "INTERPRET", True)


@pytest.mark.parametrize("t,lens", [(1, (1, 9, 40, 64)), (3, (3, 17, 64)),
                                    (6, (6, 30))],
                         ids=["decode_step", "verify_of_three",
                              "two_query_blocks"])
@pytest.mark.parametrize("chunk", [256, 16], ids=["one_chunk",
                                                   "chunks_of_two_pages"])
def test_decode_kernel_under_the_interpreter_equals_the_gather(
        interpreter, monkeypatch, t, lens, chunk):
    """The Pallas walk of a row's live pages (one key-value head of all
    the query heads, the value the first ``rank`` lanes of the key's own
    tile) against the gather of the row's table; a row whose table is
    the null page reads one page and is finite. With chunks of two pages
    a row is whole chunks (started unrolled, waited for once) and a last
    partial one (page by page)."""
    monkeypatch.setattr(la, "_CHUNK", chunk)
    qn, qr, pool, bt, pos, rows, w_uk, w_uv = _draw(len(lens), t, lens, 1)
    assert la.latent_attend_path(PAGE, jnp.float32, jnp.float32, RANK,
                                 ROPE) == la.LATENT_KERNEL == "latent_paged"
    want = _absorbed(qn, qr, pool, bt, pos, w_uk, w_uv, la.LATENT_GATHER)
    got = _absorbed(qn, qr, pool, bt, pos, w_uk, w_uv, la.LATENT_KERNEL)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    idle = _absorbed(qn, qr, pool, bt.at[0].set(0), pos, w_uk, w_uv,
                     la.LATENT_KERNEL)
    assert np.isfinite(np.asarray(idle)).all()
    np.testing.assert_allclose(idle[1:], want[1:], atol=TOL, rtol=TOL)


def test_stale_positions_past_the_cursor_do_not_reach_a_row(interpreter):
    """A recycled page holds another row's latents, or NaN, past the
    cursor: neither path lets them into a score or a value."""
    qn, qr, pool, bt, pos, rows, w_uk, w_uv = _draw(2, 1, (5, 20), 2)
    want = _absorbed(qn, qr, pool, bt, pos, w_uk, w_uv, la.LATENT_GATHER)
    # row 0 ends at position 4 of its first page: poison 5.. of it, and
    # every page past it
    dirty = pool.at[bt[0, 0], 5:].set(jnp.nan).at[bt[0, 1:]].set(jnp.nan)
    for path in (la.LATENT_GATHER, la.LATENT_KERNEL):
        got = _absorbed(qn, qr, dirty, bt, pos, w_uk, w_uv, path)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def _prefill_inputs(T, S, pos0, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (T, H * NOPE)),
            jax.random.normal(ks[1], (H, T, ROPE)),
            jax.random.normal(ks[2], (S, H * NOPE)),
            jax.random.normal(ks[3], (S, ROPE)),
            jax.random.normal(ks[4], (S, H * DV)), pos0)


def _dense_prefill(qn, qr, kn, kr, v, pos0):
    T, S = qn.shape[0], kn.shape[0]
    s = (jnp.einsum("thd,shd->hts", qn.reshape(T, H, NOPE),
                    kn.reshape(S, H, NOPE))
         + jnp.einsum("htd,sd->hts", qr, kr)) * SCALE
    seen = jnp.arange(S)[None, :] <= pos0 + jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", p, v.reshape(S, H, DV)).reshape(
        T, H * DV)


@pytest.mark.parametrize("T,S,pos0", [(32, 64, 0), (32, 64, 19),
                                      (16, 64, 48)],
                         ids=["from_the_start", "after_a_prefix",
                              "the_rows_last_positions"])
def test_prefill_attend_both_paths_equal_dense_causal_attention(
        monkeypatch, T, S, pos0):
    """The expanded attend of a pass (scores 16 + 8 lanes wide, values
    16) over a past that starts before it: the online softmax over key
    blocks in ``jax.numpy`` and the flash kernel under the interpreter,
    in blocks of 8 x 16 so that a pass is several blocks each way, give
    dense causal attention with the pass's offset."""
    monkeypatch.setattr(la, "_PREFILL_TQ", 8)
    monkeypatch.setattr(la, "_PREFILL_TK", 16)
    args = _prefill_inputs(T, S, pos0)
    want = _dense_prefill(*args)
    got = la.prefill_attend(*args, H, SCALE, la.LATENT_GATHER)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    monkeypatch.setattr(la, "INTERPRET", True)
    got = la.prefill_attend(*args, H, SCALE, la.LATENT_KERNEL)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_expand_builds_only_the_live_positions(monkeypatch):
    """Keys and values a head from the latents, the blocks up to the last
    live position only: what lies past them stays zeros (the causal mask
    never admits it), whatever the latents there hold."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lat = jax.random.normal(ks[0], (64, RANK)).at[40:].set(jnp.nan)
    w_uk = jax.random.normal(ks[1], (RANK, H * NOPE))
    w_uv = jax.random.normal(ks[2], (RANK, H * DV))
    monkeypatch.setattr(la, "_EXPAND", 8)
    k, v = jax.jit(la.expand)(lat, w_uk, w_uv, 19)
    np.testing.assert_allclose(k[:24], lat[:24] @ w_uk, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(v[:24], lat[:24] @ w_uv, atol=TOL, rtol=TOL)
    assert not np.asarray(k[24:]).any() and not np.asarray(v[24:]).any()


def test_yarn_frequencies_and_scale_by_hand():
    """Kimi-K2's rotation: 64 lanes, theta 50,000, factor 64 over 4,096.
    The pair that turns 32 times in 4,096 positions lies at dimension
    8.9 and the one that turns once at 19.2, so pairs 0..8 keep
    ``theta^(-2i/64)``, pairs 20.. take that over 64 and pair 14 is half
    way up the ramp between 8 and 20; ``m = 0.1 ln 64 + 1`` and the
    softmax's scale is ``m^2 / sqrt(192)``."""
    f = la.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:9], base[:9], rtol=1e-6)
    np.testing.assert_allclose(f[20:], base[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(f[14], base[14] * (0.5 + 0.5 / 64),
                               rtol=1e-6)
    assert (np.diff(f) < 0).all()
    m = la.yarn_mscale(64.0, 1.0)
    assert abs(m - 1.4159) < 5e-5
    assert abs(la.softmax_scale(192, 64.0, 1.0)
               - 1.4159 ** 2 / math.sqrt(192)) < 1e-5
    # no scaling: the plain frequencies, a scale of head_width ** -0.5
    np.testing.assert_allclose(
        la.yarn_inv_freq(64, 50000.0, 1.0, 4096, 32.0, 1.0), base,
        rtol=1e-6)
    assert la.softmax_scale(192, 1.0, 1.0) == 1.0 / math.sqrt(192)


def test_the_interleaved_rotation_takes_yarns_frequencies():
    """``cohere2_moe.py``'s interleaved-pair rotation handed the plain
    frequencies is itself, lane for lane; with yarn's it keeps every
    pair's length and a score depends on the distance alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    pos = jnp.arange(5)[None, :, None] + jnp.asarray([0, 7])[:, None, None]
    plain = la.yarn_inv_freq(8, 50000.0, 1.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(
        rotate_interleaved(x, pos, 50000.0, plain),
        rotate_interleaved(x, pos, 50000.0), atol=1e-6)
    yarn = la.yarn_inv_freq(8, 50000.0, 64.0, 4096, 32.0, 1.0)

    def rot(v, at):
        return rotate_interleaved(v, jnp.asarray(at), 50000.0, yarn)

    np.testing.assert_allclose(
        jnp.square(rot(x, 3)).reshape(2, 5, 3, 4, 2).sum(-1),
        jnp.square(x).reshape(2, 5, 3, 4, 2).sum(-1), rtol=1e-5)
    q, k = x[0, 0, 0], x[1, 0, 0]
    near = (rot(q, 9) * rot(k, 4)).sum()
    far = (rot(q, 105) * rot(k, 100)).sum()
    assert abs(float(near - far)) < 1e-4


def test_the_path_follows_what_the_code_can_observe(monkeypatch):
    """Off the TPU the gather; on it the kernel where queries and pool
    share float32 or bfloat16, the latent is whole lane tiles and a page
    whole sublane tiles that divide the walk's chunk."""
    path = la.latent_attend_path
    bf, f32 = jnp.bfloat16, jnp.float32
    assert path(16, bf, bf, 512, 64) == la.LATENT_GATHER == "latent_gather"
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    assert path(16, bf, bf, 512, 64) == la.LATENT_KERNEL
    assert path(8, f32, f32, 512, 64) == la.LATENT_KERNEL
    assert path(8, bf, bf, 512, 64) == la.LATENT_GATHER     # half a tile
    assert path(16, bf, f32, 512, 64) == la.LATENT_GATHER   # two dtypes
    assert path(16, bf, bf, 500, 64) == la.LATENT_GATHER    # ragged latent
    assert path(48, bf, bf, 512, 64) == la.LATENT_GATHER    # chunk % page
    assert la.pool_lanes(512, 64) == 640 and la.pool_lanes(32, 8) == 128
