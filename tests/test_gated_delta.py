"""The gated delta rule (``gym_tpu/ops/gated_delta.py``) against the rule
token by token (``recur``), on the CPU in float32 with seeded inputs: the
decode step's pass over the resident pool (plain and, under the
interpreter, the Pallas kernel), the chunked prefill at lengths that are
and are not whole chunks, state and convolution inputs carried across
passes, a bucket's padding, a row at cursor 0 on a dirty block, a row that
is not live."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.ops import gated_delta as gd

TOL = 2e-5


def _inputs(T, H=4, dk=16, dv=16, seed=0, slow=False):
    """``q``, ``k`` [T, H, dk] normed (``q`` scaled), ``v`` [T, H, dv],
    ``g`` [T, H] log-decays, ``beta`` [T, H]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gd.l2norm(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    rate = 0.01 if slow else 0.3
    g = -rate * jax.nn.softplus(jax.random.normal(ks[3], (T, H)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


def _batched(x):
    """[T, H, ...] -> [1, H, T, ...], one row of a prefill."""
    return jnp.moveaxis(x, 0, 1)[None]


@pytest.mark.parametrize("T,chunk", [(64, 64), (128, 64), (48, 16), (8, 8),
                                     (96, 32)])
@pytest.mark.parametrize("slow", [False, True], ids=["fast", "slow"])
def test_chunked_prefill_equals_the_rule_token_by_token(T, chunk, slow):
    q, k, v, g, beta = _inputs(T, seed=T, slow=slow)
    S0 = jax.random.normal(jax.random.PRNGKey(9), (4, 16, 16))
    want_o, want_S = gd.recur(S0, q, k, v, g, beta)
    o, S = gd.prefill(S0[None], *map(_batched, (q, k, v, g, beta)),
                      jnp.ones((1, T), bool), chunk)
    np.testing.assert_allclose(np.moveaxis(o[0], 0, 1), want_o, atol=TOL)
    np.testing.assert_allclose(S[0], want_S, atol=TOL)


@pytest.mark.parametrize("n_valid", [1, 37, 64, 100, 128])
def test_padding_past_the_prompt_leaves_the_state_as_it_is(n_valid):
    """A bucket of 128 positions of which ``n_valid`` are the prompt's:
    the state after it is the state after ``n_valid`` tokens, whether the
    length is whole chunks or not."""
    T = 128
    q, k, v, g, beta = _inputs(T, seed=3)
    S0 = jnp.zeros((4, 16, 16))
    want_o, want_S = gd.recur(S0, *(x[:n_valid] for x in (q, k, v, g, beta)))
    valid = (jnp.arange(T) < n_valid)[None]
    o, S = gd.prefill(S0[None], *map(_batched, (q, k, v, g, beta)), valid,
                      64)
    np.testing.assert_allclose(np.moveaxis(o[0], 0, 1)[:n_valid], want_o,
                               atol=TOL)
    np.testing.assert_allclose(S[0], want_S, atol=TOL)


def test_the_state_is_carried_from_pass_to_pass():
    """Three passes of 32 positions give the state and the outputs of one
    pass of 96."""
    T = 96
    q, k, v, g, beta = _inputs(T, seed=5)
    whole_o, whole_S = gd.prefill(
        jnp.zeros((1, 4, 16, 16)), *map(_batched, (q, k, v, g, beta)),
        jnp.ones((1, T), bool), 16)
    S, outs = jnp.zeros((1, 4, 16, 16)), []
    for lo in range(0, T, 32):
        o, S = gd.prefill(
            S, *(_batched(x[lo:lo + 32]) for x in (q, k, v, g, beta)),
            jnp.ones((1, 32), bool), 16)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=2), whole_o,
                               atol=TOL)
    np.testing.assert_allclose(S, whole_S, atol=TOL)


def _pool(blocks, H, dk, dv, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (blocks, H, dk, dv))


@pytest.mark.parametrize("kernel", [False, True], ids=["rows", "kernel"])
def test_decode_step_decays_corrects_and_reads_in_place(kernel, monkeypatch):
    """Three rows on a pool of five blocks: a live row with a past, a row
    at cursor 0 on a dirty block (it reads zeros whatever the block
    held) and a row that is not live (block 0: it touches nothing). The
    Pallas kernel under the interpreter at the served head size."""
    H, dk, dv = (8, 128, 128) if kernel else (4, 16, 16)
    monkeypatch.setattr(gd, "INTERPRET", kernel)
    S = _pool(5, H, dk, dv).at[0].set(0.0)
    assert gd.state_pass_path(S) == ("kernel" if kernel else "rows")
    q, k, v, g, beta = _inputs(3, H, dk, dv, seed=2)
    sb = jnp.array([3, 1, 0])
    fresh = jnp.array([False, True, False])
    o, S1 = gd.decode_step(S, sb, q, k, v, g, beta, fresh)
    for row, (block, new) in enumerate([(3, False), (1, True)]):
        S0 = jnp.zeros_like(S[block]) if new else S[block]
        want_o, want_S = gd.recur(S0, *(x[row:row + 1]
                                        for x in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[row], want_o[0], atol=TOL)
        np.testing.assert_allclose(S1[block], want_S, atol=TOL)
    # the row that is not live reads zeros and leaves every block it does
    # not own, the null block too, as it was
    assert not np.asarray(o[2]).any()
    for block in (0, 2, 4):
        np.testing.assert_array_equal(S1[block], S[block])


def test_decode_steps_continue_a_prefill():
    """A prefill of 40 tokens into a block, then 8 decode steps, against
    48 tokens of the rule."""
    q, k, v, g, beta = _inputs(48, seed=11)
    want_o, want_S = gd.recur(jnp.zeros((4, 16, 16)), q, k, v, g, beta)
    _o, S = gd.prefill(jnp.zeros((1, 4, 16, 16)),
                       *(_batched(x[:40]) for x in (q, k, v, g, beta)),
                       jnp.ones((1, 40), bool), 8)
    pool = _pool(3, 4, 16, 16).at[0].set(0.0)
    pool, _ = gd.store_rows(pool, jnp.zeros((3, 3, 8)), jnp.array([2]), S,
                            jnp.zeros((1, 3, 8)))
    for t in range(40, 48):
        o, pool = gd.decode_step(
            pool, jnp.array([2]), *(x[t:t + 1] for x in (q, k, v, g, beta)),
            jnp.array([False]))
        np.testing.assert_allclose(o[0], want_o[t], atol=TOL)
    np.testing.assert_allclose(pool[2], want_S, atol=TOL)


def _conv_reference(x, w):
    """``silu(sum_j w[:, j] x[t - K + 1 + j])`` with zeros before the
    sequence: ``x`` [T, ch], ``w`` [ch, K]."""
    K = w.shape[1]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x])
    return jax.nn.silu(sum(ext[j:j + x.shape[0]] * w[:, j]
                           for j in range(K)))


@pytest.mark.parametrize("n_valid", [0, 2, 20, 32])
def test_conv_run_keeps_the_last_valid_inputs(n_valid):
    """A pass of 32 positions of which ``n_valid`` are the prompt's, after
    inputs kept from before: the outputs are the causal convolution's and
    the kept inputs the last three up to the last valid position."""
    ch, K = 12, 4
    ks = jax.random.split(jax.random.PRNGKey(n_valid), 3)
    before = jax.random.normal(ks[0], (10, ch))
    x = jax.random.normal(ks[1], (32, ch))
    w = jax.random.normal(ks[2], (ch, K))
    y, kept = gd.conv_run(before[None, -3:], x[None], w,
                          jnp.array([n_valid]))
    want = _conv_reference(jnp.concatenate([before, x]), w)[10:]
    np.testing.assert_allclose(y[0, :n_valid], want[:n_valid], atol=TOL)
    seq = jnp.concatenate([before, x[:n_valid]])
    np.testing.assert_allclose(kept[0], seq[-3:], atol=0)


def test_conv_step_shifts_a_rows_inputs_and_spares_the_others():
    """One new position a row: a live row, a row at cursor 0 on a dirty
    block (zeros before it) and a row that is not live."""
    ch, K = 12, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    pool = jax.random.normal(ks[0], (4, K - 1, ch)).at[0].set(0.0)
    x = jax.random.normal(ks[1], (3, ch))
    w = jax.random.normal(ks[2], (ch, K))
    sb = jnp.array([2, 3, 0])
    y, new = gd.conv_step(pool, sb, x, w, jnp.array([False, True, False]))
    past = [pool[2], jnp.zeros((K - 1, ch))]
    for row, block in enumerate((2, 3)):
        win = jnp.concatenate([past[row], x[row:row + 1]])
        np.testing.assert_allclose(
            y[row], jax.nn.silu((win * w.T).sum(0)), atol=TOL)
        np.testing.assert_allclose(new[block], win[1:], atol=0)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1], pool[1])


def test_a_run_that_is_not_whole_chunks_is_refused():
    q, k, v, g, beta = _inputs(40)
    with pytest.raises(ValueError, match="whole chunks"):
        gd.prefill(jnp.zeros((1, 4, 16, 16)),
                   *map(_batched, (q, k, v, g, beta)),
                   jnp.ones((1, 40), bool), 16)
