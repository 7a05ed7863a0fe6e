"""Power retention's three forms (``gym_tpu/ops/power_retention.py``): the
feature map's identity, and the recurrence (what decoding runs), the
chunked form (what a prefill runs) and the attention form agreeing, at
several chunk sizes and with a chunk boundary inside the prompt."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.ops import power_retention as pr

KV, G, HD, EPS = 2, 3, 8, 1e-6
SCALE = 1.0 / math.sqrt(HD)


def _draw(T, seed=0, gate_bias=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (KV, G, T, HD))
    k = jax.random.normal(ks[1], (KV, T, HD))
    v = jax.random.normal(ks[2], (KV, T, HD))
    gam = jax.nn.log_sigmoid(jax.random.normal(ks[3], (KV, T)) + gate_bias)
    return q, k, v, gam


@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_phi_is_the_squared_product(d):
    """``phi(q) . phi(k) == (scale q . k)^2``, on ``(d/2 + 1) d`` places
    that hold the ``d (d + 1) / 2`` symmetric features."""
    ks = jax.random.split(jax.random.PRNGKey(d), 2)
    q = jax.random.normal(ks[0], (5, d))
    k = jax.random.normal(ks[1], (5, d))
    scale = 1.0 / math.sqrt(d)
    fq, fk = pr.phi(q, scale), pr.phi(k, scale)
    assert fq.shape == (5, pr.feature_dim(d)) == (5, (d // 2 + 1) * d)
    assert pr.feature_dim(d) - d // 2 == d * (d + 1) // 2
    np.testing.assert_allclose(
        (fq * fk).sum(-1), jnp.square(scale * (q * k).sum(-1)), rtol=2e-5)


def test_phi_refuses_an_odd_head():
    with pytest.raises(ValueError, match="even"):
        pr.phi(jnp.ones((3,)), 1.0)


def _recurrence(q, k, v, gam):
    """One ``decode_step`` a token on a pool of two blocks (the null
    block and the row's)."""
    D = pr.feature_dim(HD)
    S = jnp.zeros((2, KV, HD, D))
    z = jnp.zeros((2, KV, D))
    bt = jnp.ones((1,), jnp.int32)
    ys = []
    for t in range(q.shape[2]):
        y, S, z = pr.decode_step(
            S, z, bt, q[None, :, :, t], k[None, :, t], v[None, :, t],
            gam[None, :, t], jnp.asarray([t == 0]), EPS, SCALE)
        ys.append(y[0])
    assert not np.asarray(S[0]).any() and not np.asarray(z[0]).any()
    return jnp.stack(ys, axis=2), S[1], z[1]


@pytest.mark.parametrize("chunk", [1, 4, 8, 24])
def test_recurrence_chunked_and_attention_forms_agree(chunk):
    T = 24
    q, k, v, gam = _draw(T)
    want = pr.attend(q, k, v, gam, EPS, SCALE)
    D = pr.feature_dim(HD)
    y, S, z = pr.prefill(jnp.zeros((1, KV, HD, D)), jnp.zeros((1, KV, D)),
                         q[None], k[None], v[None], gam[None],
                         jnp.ones((1, T), bool), EPS, SCALE, chunk)
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-5)
    y_r, S_r, z_r = _recurrence(q, k, v, gam)
    np.testing.assert_allclose(y_r, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S[0], S_r, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(z[0], z_r, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("n", [5, 8, 13])
def test_padding_leaves_the_state_of_the_prompt_alone(n):
    """A run of 16 positions of which ``n`` are the prompt (a chunk
    boundary at 8 falls inside, on or past it): outputs at the prompt's
    positions and the state equal those of the prompt alone, whatever the
    padding holds; then decoding from that state continues the attention
    form."""
    T, more = 16, 4
    q, k, v, gam = _draw(T + more, seed=n)
    D = pr.feature_dim(HD)
    zero = (jnp.zeros((1, KV, HD, D)), jnp.zeros((1, KV, D)))
    valid = (jnp.arange(T) < n)[None]
    junk = 1e3
    y, S, z = pr.prefill(*zero, q[None, :, :, :T],
                         k[None, :, :T].at[:, :, n:].set(junk),
                         v[None, :, :T].at[:, :, n:].set(junk),
                         gam[None, :, :T].at[:, :, n:].set(-5.0), valid,
                         EPS, SCALE, 8)
    y1, S1, z1 = pr.prefill(*zero, q[None, :, :, :n], k[None, :, :n],
                            v[None, :, :n], gam[None, :, :n],
                            jnp.ones((1, n), bool), EPS, SCALE, n)
    np.testing.assert_allclose(y[0, :, :, :n], y1[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S, S1, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(z, z1, rtol=2e-4, atol=2e-5)
    # decode on from the prefill's state: positions n .. n + more of the
    # sequence (prompt, then the next tokens)
    seq = [jnp.concatenate([a[..., :n, :], a[..., T:, :]], axis=-2)
           for a in (q, k, v)]
    g_seq = jnp.concatenate([gam[:, :n], gam[:, T:]], axis=-1)
    want = pr.attend(*seq, g_seq, EPS, SCALE)
    Sp = jnp.concatenate([jnp.zeros_like(S), S])
    zp = jnp.concatenate([jnp.zeros_like(z), z])
    for t in range(n, n + more):
        y_t, Sp, zp = pr.decode_step(
            Sp, zp, jnp.ones((1,), jnp.int32), seq[0][None, :, :, t],
            seq[1][None, :, t], seq[2][None, :, t], g_seq[None, :, t],
            jnp.asarray([False]), EPS, SCALE)
        np.testing.assert_allclose(y_t[0], want[:, :, t], rtol=2e-4,
                                   atol=2e-5)


def test_a_decode_step_touches_only_the_live_rows_blocks():
    """Three rows on a pool of five blocks: a row that is not live (block
    0) changes nothing and reads zeros, a block no row owns (a parked
    row's) keeps its bytes, a fresh row's block is cleared by a select
    (NaNs a quarantined row left do not survive)."""
    D = pr.feature_dim(HD)
    rng = np.random.default_rng(0)
    S = jnp.asarray(rng.normal(size=(5, KV, HD, D)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(5, KV, D)), jnp.float32)
    S = S.at[0].set(0).at[3].set(jnp.nan)
    z = z.at[0].set(0).at[3].set(jnp.nan)
    q, k, v, gam = _draw(3, seed=4)
    bt = jnp.asarray([2, 0, 3], jnp.int32)
    fresh = jnp.asarray([False, False, True])
    y, S1, z1 = pr.decode_step(
        S, z, bt, jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0), gam.T, fresh, EPS, SCALE)
    for blk in (0, 1, 4):
        np.testing.assert_array_equal(S1[blk], S[blk])
        np.testing.assert_array_equal(z1[blk], z[blk])
    assert not np.asarray(y[1]).any()
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(S1[3])).all()
    # the fresh row's block is the update alone
    fk = pr.phi(k[:, 2], SCALE)
    np.testing.assert_allclose(S1[3], v[:, 2][..., None] * fk[:, None, :],
                               rtol=1e-6)
    np.testing.assert_allclose(z1[3], fk, rtol=1e-6)
    # the live row's block: decayed, then updated
    fk0 = pr.phi(k[:, 0], SCALE)
    np.testing.assert_allclose(
        S1[2], jnp.exp(gam[:, 0])[:, None, None] * S[2]
        + v[:, 0][..., None] * fk0[:, None, :], rtol=1e-5, atol=1e-6)


def test_the_state_kernel_is_the_pass_a_row_at_a_time(monkeypatch):
    """``retention_state_decode`` (interpreted on the CPU) at the served
    head: 128 values by 8,320 features, five queries a group. Four rows
    on a pool of six blocks, one not live, one fresh on a block of NaNs,
    one block no row owns: the same outputs and the same pool as the
    ``jax.numpy`` pass, the untouched blocks bit for bit."""
    hd, kv, g = 128, 2, 5
    D = pr.feature_dim(hd)
    assert D == 8320 and D % 128 == 0
    rng = np.random.default_rng(3)
    S = jnp.asarray(rng.normal(size=(6, kv, hd, D)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(6, kv, D)), jnp.float32)
    S = S.at[0].set(0).at[4].set(jnp.nan)
    z = z.at[0].set(0).at[4].set(jnp.nan)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (4, kv, g, hd))
    k = jax.random.normal(ks[1], (4, kv, hd))
    v = jax.random.normal(ks[2], (4, kv, hd))
    gam = jax.nn.log_sigmoid(jax.random.normal(ks[3], (4, kv)) + 3.0)
    bt = jnp.asarray([2, 0, 4, 5], jnp.int32)
    fresh = jnp.asarray([False, True, True, False])
    args = (bt, q, k, v, gam, fresh, 1e-6, 1.0 / math.sqrt(hd))
    assert pr.state_pass_path(S) == "rows"
    want = pr.decode_step(S, z, *args)
    monkeypatch.setattr(pr, "INTERPRET", True)
    assert pr.state_pass_path(S) == "kernel"
    assert pr.state_pass_path(S.astype(jnp.bfloat16)) == "rows"
    got = pr.decode_step(S, z, *args)
    # the pools to rounding; the outputs as far as a quotient of two sums
    # of 8,320 random terms of either sign is conditioned
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-2, atol=1e-2)
    for blk in (0, 1, 3):
        np.testing.assert_array_equal(got[1][blk], S[blk])
    assert np.isfinite(np.asarray(got[1][4])).all()
    assert not np.asarray(got[0][1]).any()


def _draw_served(b, kv, g, T, seed, mm):
    """A prefill's arguments at the served head (128 values by 8,320
    features): queries, keys and values in the products' dtype, gates
    that remember, and for each row a state some past left."""
    hd = 128
    D = pr.feature_dim(hd)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, kv, g, T, hd)).astype(mm)
    k = jax.random.normal(ks[1], (b, kv, T, hd)).astype(mm)
    v = jax.random.normal(ks[2], (b, kv, T, hd)).astype(mm)
    gam = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, kv, T)) + 3.0)
    S0 = jax.random.normal(ks[4], (b, kv, hd, D))
    z0 = 30.0 + jnp.abs(jax.random.normal(ks[5], (b, kv, D)))
    return q, k, v, gam, S0, z0


@pytest.mark.parametrize("tail", [0, 5])
@pytest.mark.parametrize("fresh", [False, True])
@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("group", [1, 5])
def test_the_read_kernel_is_the_read_with_phi_written_out(
        monkeypatch, group, chunk, fresh, tail):
    """``retention_prefill_read`` (interpreted on the CPU) at the served
    head and in the served products' dtype: a chunk's read of a state is
    the einsum over ``phi(Q)`` written out, and a prefill through it
    (two chunks or four, the last ``tail`` positions a bucket's padding,
    the row fresh or with a past) gives the outputs of the prefill
    through the einsum and the SAME state, bit for bit: the kernel reads,
    it does not write."""
    hd, T, mm = 128, 32, jnp.bfloat16
    q, k, v, gam, S0, z0 = _draw_served(1, 2, group, T, 7 * chunk + group,
                                        mm)
    if fresh:
        S0, z0 = jnp.zeros_like(S0), jnp.zeros_like(z0)
    scale = 1.0 / math.sqrt(hd)
    valid = (jnp.arange(T) < T - tail)[None]
    args = (S0, z0, q, k, v, gam, valid, 1e-6, scale, chunk)
    assert pr.prefill_read_path(S0) == "rows"
    want = pr.prefill(*args, mm_dtype=mm)
    S = jnp.concatenate([S0, z0[:, :, None, :]], axis=2)
    q_c = q[:, :, :, :chunk]
    read = jnp.einsum("bkgtd,bkvd->bkgtv", pr.phi(q_c, scale).astype(mm),
                      S.astype(mm), preferred_element_type=jnp.float32)
    monkeypatch.setattr(pr, "INTERPRET", True)
    assert pr.prefill_read_path(S0) == "kernel"
    assert pr.prefill_read_path(S0.astype(jnp.bfloat16)) == "rows"
    got = pr._kernel_read(q_c, S, scale, None)
    assert got.shape[:-1] == read.shape[:-1]
    # sums of 8,320 products of either sign, in another order
    np.testing.assert_allclose(got[..., :hd + 1], read, rtol=1e-4,
                               atol=1e-3 * float(jnp.abs(read).max() + 1))
    got = pr.prefill(*args, mm_dtype=mm)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_a_prefill_through_the_read_kernel_agrees_with_attention(
        monkeypatch, caplog, chunk):
    """``prefill`` whole through ``retention_prefill_read`` (float32
    products, as ``test_recurrence_chunked_and_attention_forms_agree``
    holds the plain form) against the attention form, at the served
    head; the path is logged once a shape on the served kernels'
    logger. The rehearsal's head of 16 (144 features: no whole lane
    tiles) keeps the ``jax.numpy`` read whatever the backend."""
    T, kv, g = 32, 2, 2
    q, k, v, gam, S0, z0 = _draw_served(1, kv, g, T, chunk, jnp.float32)
    scale = 1.0 / math.sqrt(128)
    want = pr.attend(q[0], k[0], v[0], gam[0], EPS, scale)
    monkeypatch.setattr(pr, "INTERPRET", True)
    assert pr.prefill_read_path(jnp.zeros((1, 2, 16, 144))) == "rows"
    with caplog.at_level("INFO", logger="gym_tpu.ops.paged_attention"):
        y, S, z = pr.prefill(jnp.zeros_like(S0), jnp.zeros_like(z0), q, k,
                             v, gam, jnp.ones((1, T), bool), EPS, scale,
                             chunk)
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-5)
    assert (f"attention path retention_read_kernel for q(1, {kv}, {g}, "
            f"{chunk}, 128) float32") in caplog.text
