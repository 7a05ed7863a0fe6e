"""Manifold-constrained hyper-connections (``gym_tpu/ops/hyper_connection
.py``: the coefficients of a sub-layer from the flattened streams, the
Sinkhorn projection of the stream-to-stream matrix, the read and the
write) against the equations written out in NumPy, a token at a time in
float64, at a small size on the CPU.

* the three coefficient groups and the two mixes equal the equations;
* ``H_res``'s rows sum to one and its columns to one within what 20
  iterations reach, so a sub-layer that adds nothing keeps ``sum_i X_i``;
* the clamp holds where ``A_res`` is +-100 (no infinity, no NaN, rows
  that still sum to one);
* a token's coefficients do not depend on the batch it rides in;
* the iterations are unrolled: the lowered program holds no loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gym_tpu.ops import hyper_connection as hc

N, C = 4, 48
K = N * (N + 2)
EPS, HC_EPS, CLAMP, ITERS = 1e-6, 1e-6, (-30.0, 30.0), 20


def _params(seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, scale / np.sqrt(N * C), (N * C, K)),
            rng.normal(1.0, 0.3, (3,)), rng.normal(0, 1.0, (K,)))


def _streams(shape, seed=1):
    return np.random.default_rng(seed).normal(0, 1.5, (N,) + shape + (C,))


def _plain(x, phi, alpha, bias, iters=ITERS):
    """One token's ``(H_pre, H_post, H_res)`` by the description: ``x``
    [n, C] float64."""
    v = x.reshape(-1)
    u = v / np.sqrt(np.mean(v * v) + EPS)
    a = u @ phi
    h_pre = 1 / (1 + np.exp(-(alpha[0] * a[:N] + bias[:N])))
    h_post = 2 / (1 + np.exp(-(alpha[1] * a[N:2 * N] + bias[N:2 * N])))
    a_res = (alpha[2] * a[2 * N:] + bias[2 * N:]).reshape(N, N)
    m = np.exp(np.clip(a_res, *CLAMP))
    for _ in range(iters):
        m = m / (m.sum(0, keepdims=True) + HC_EPS)
        m = m / (m.sum(1, keepdims=True) + HC_EPS)
    return h_pre, h_post, m


def _coefficients(X, phi, alpha, bias):
    return hc.coefficients(
        jnp.asarray(X, jnp.float32), jnp.asarray(phi, jnp.float32),
        jnp.asarray(alpha, jnp.float32), jnp.asarray(bias, jnp.float32),
        eps=EPS, iters=ITERS, hc_eps=HC_EPS, clamp=CLAMP)


@pytest.mark.parametrize("shape", [(5,), (2, 3)], ids=["rows", "b_by_t"])
def test_coefficients_and_mixes_equal_the_equations(shape):
    """Whatever axes the tokens lie on between the streams and the
    hidden: each token's coefficients are the description's, ``read`` is
    ``sum_i H_pre[i] X_i`` and ``write`` ``sum_i H_res[j, i] X_i +
    H_post[j] y``."""
    phi, alpha, bias = _params()
    X = _streams(shape)
    y = np.random.default_rng(2).normal(0, 1, shape + (C,))
    h_pre, h_post, h_res = _coefficients(X, phi, alpha, bias)
    assert h_pre.shape == (N,) + shape and h_res.shape == (N, N) + shape
    got_h = np.asarray(hc.read(jnp.asarray(X, jnp.float32), h_pre))
    got_X = np.asarray(hc.write(jnp.asarray(X, jnp.float32),
                                jnp.asarray(y, jnp.float32), h_res, h_post))
    for at in np.ndindex(*shape):
        x = X[(slice(None),) + at]
        want_pre, want_post, want_res = _plain(x, phi, alpha, bias)
        pick = (Ellipsis,) + at
        np.testing.assert_allclose(h_pre[pick], want_pre, atol=2e-6)
        np.testing.assert_allclose(h_post[pick], want_post, atol=4e-6)
        np.testing.assert_allclose(h_res[pick], want_res, atol=2e-6)
        np.testing.assert_allclose(got_h[at], want_pre @ x, atol=1e-5)
        np.testing.assert_allclose(
            got_X[(slice(None),) + at],
            want_res @ x + want_post[:, None] * y[at],
            atol=2e-5)
    # the coefficients are worth a comparison: none of them constant
    assert np.std(np.asarray(h_pre)) > 0.1 and np.std(
        np.asarray(h_res)) > 0.1


def test_h_res_is_doubly_stochastic_and_an_idle_sub_layer_keeps_the_sum():
    """Rows sum to one to rounding (the last division is the rows'),
    columns within what 20 iterations reach: 1e-5 for the median token
    of these 64, 2e-2 for the slowest (a matrix whose entries spread over
    e^8 converges slowly); after 2 iterations the median is off by 0.14.
    Every entry positive. With ``y = 0`` the new streams' sum is ``sum_i
    colsum_i X_i``: the old streams' sum as nearly as the columns sum to
    one."""
    phi, alpha, bias = _params(3)
    X = _streams((64,), 4)
    _pre, h_post, h_res = _coefficients(X, phi, alpha, bias)
    h_res = np.asarray(h_res)
    assert (h_res > 0).all()
    np.testing.assert_allclose(h_res.sum(1), 1.0, atol=3e-6)
    off = np.abs(h_res.sum(0) - 1.0).max(0)             # a token
    assert np.median(off) < 1e-4 and off.max() < 0.05
    two = np.stack([_plain(X[:, r], phi, alpha, bias, iters=2)[2]
                    for r in range(64)], -1)
    assert np.median(np.abs(two.sum(0) - 1.0).max(0)) > 0.05
    kept = np.asarray(hc.write(
        jnp.asarray(X, jnp.float32), jnp.zeros((64, C), jnp.float32),
        jnp.asarray(h_res), h_post)).sum(0)
    room = off[:, None] * np.abs(X).sum(0) + 1e-4
    assert (np.abs(kept - X.sum(0)) <= room).all()
    assert np.abs(kept - X.sum(0))[off < 1e-5].max() < 2e-4


@pytest.mark.parametrize("sign", [1.0, -1.0], ids=["plus100", "minus100"])
def test_the_clamp_holds_where_a_res_is_far_out(sign):
    """A static ``B_res`` of +-100 on one entry a row (its others 0, no
    dynamic part): ``exp`` sees +-30, nothing overflows, and the matrix
    is what the clamped logits give, rows still summing to one."""
    phi = np.zeros((N * C, K))
    bias = np.zeros(K)
    b_res = np.zeros((N, N))
    b_res[np.arange(N), (np.arange(N) + 1) % N] = sign * 100.0
    bias[2 * N:] = b_res.reshape(-1)
    X = _streams((3,), 5)
    _pre, _post, h_res = _coefficients(X, phi, np.ones(3), bias)
    h_res = np.asarray(h_res)
    assert np.isfinite(h_res).all()
    np.testing.assert_allclose(h_res.sum(1), 1.0, atol=3e-6)
    want = _plain(X[:, 0], phi, np.ones(3), bias)[2]
    np.testing.assert_allclose(h_res[..., 0], want, atol=2e-6)
    # clamped at 30 an entry outweighs its row by e^30; unclamped, exp
    # of 100 is an infinity in float32 and the row a NaN
    assert (h_res[np.arange(N), (np.arange(N) + 1) % N, 0] > 0.99).all() \
        == (sign > 0)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(100.0)))


def test_a_tokens_coefficients_do_not_depend_on_the_batch():
    """Row 3 of a batch of eight, alone, and beside rows scaled a
    thousandfold: the same coefficients (the norm is the token's own,
    and no sum runs over tokens)."""
    phi, alpha, bias = _params(6)
    X = _streams((8,), 7)
    together = _coefficients(X, phi, alpha, bias)
    alone = _coefficients(X[:, 3:4], phi, alpha, bias)
    loud = X * 1000.0
    loud[:, 3] = X[:, 3]
    beside = _coefficients(loud, phi, alpha, bias)
    for a, b, c in zip(together, alone, beside):
        np.testing.assert_allclose(np.asarray(a)[..., 3],
                                   np.asarray(b)[..., 0], atol=1e-6)
        np.testing.assert_allclose(np.asarray(a)[..., 3],
                                   np.asarray(c)[..., 3], atol=1e-6)


def test_the_iterations_are_unrolled():
    """Forty divisions in the lowered program and no loop: a decode
    step's chain of tiny reductions is the compiler's to fuse."""
    phi, alpha, bias = _params()
    text = jax.jit(_coefficients).lower(
        _streams((4,)), phi, alpha, bias).as_text()
    assert "while" not in text
    assert text.count("stablehlo.divide") >= 2 * ITERS
