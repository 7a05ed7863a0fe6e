"""Manifold-constrained hyper-connections: a residual of ``n`` streams a
token, mixed around every sub-layer (mHC, arXiv 2512.24880, over
hyper-connections, arXiv 2409.19606).

A token's residual is ``X`` in R^{n x C}. A sub-layer ``F`` (an attention,
a feed-forward part) has ``Phi`` in R^{nC x n(n+2)} (its columns: ``n`` for
``pre``, ``n`` for ``post``, ``n^2`` for ``res``, row-major ``[j, i]``),
three gains ``alpha`` and a bias a column, all float32::

    u      = vec(X) / sqrt(mean(vec(X)^2) + eps)            no weight
    a      = alpha (u Phi) + bias                           a gain a group
    H_pre  = sigmoid(a_pre)          H_post = 2 sigmoid(a_post)      R^n
    M      = exp(clip(mat(a_res), lo, hi))                           R^{n x n}
    iters times:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    H_res  = M            rows sum to one exactly, columns nearly
    h      = sum_i H_pre[i] X_i                             ``read``
    X'_j   = sum_i H_res[j, i] X_i + H_post[j] F(h)         ``write``

``coefficients`` is the first six lines. The norm is one number a token,
so it is applied to the product and ``u`` is never built. The iterations
are unrolled: forty sums over 4 numbers, no loop in the program.

Layout. The streams lead: ``X`` is ``[n, ..., C]`` and a coefficient
``[n, ...]`` or ``[n, n, ...]``, the token axes minor. A token's 4 x 4
matrix on the minor axes would fill 16 of a tile's 1,024 places; with the
tokens minor a prefill pass of 4,096 positions fills whole tiles, and
sums over streams are sums of whole arrays. Everything is float32 and no
product here is a matrix unit's at reduced precision: ``u Phi`` asks for
``HIGHEST``, and the mixes are multiplies and adds (a float32 ``einsum``
at the default precision rounds its operands to bfloat16 on a TPU).

Scopes: ``hc.coef``, ``hc.sinkhorn``, ``hc.mix``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

COEF, SINKHORN, MIX = "hc.coef", "hc.sinkhorn", "hc.mix"


def sinkhorn(m, iters: int, hc_eps: float):
    """``m`` [n, n, ...] positive, ``m[j, i]``: ``iters`` times columns
    (over ``j``) then rows (over ``i``) divided by their sums."""
    for _ in range(iters):
        m = m / (m.sum(0, keepdims=True) + hc_eps)
        m = m / (m.sum(1, keepdims=True) + hc_eps)
    return m


def coefficients(X, phi, alpha, bias, *, eps: float, iters: int,
                 hc_eps: float, clamp):
    """``(H_pre [n, ...], H_post [n, ...], H_res [n, n, ...])`` of the
    streams ``X`` [n, ..., C] float32 for one sub-layer's ``phi`` [n C,
    n (n + 2)], ``alpha`` [3] and ``bias`` [n (n + 2)]."""
    n, C = X.shape[0], X.shape[-1]
    rest = X.shape[1:-1]
    with jax.named_scope(COEF):
        s = jnp.einsum("i...c,ick->k...", X, phi.reshape(n, C, -1),
                       precision=jax.lax.Precision.HIGHEST)
        a = s * jax.lax.rsqrt(jnp.square(X).mean((0, -1)) + eps)
        bias = bias.reshape((-1,) + (1,) * len(rest))
        h_pre = jax.nn.sigmoid(alpha[0] * a[:n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + bias[n:2 * n])
    with jax.named_scope(SINKHORN):
        a_res = alpha[2] * a[2 * n:] + bias[2 * n:]
        m = jnp.exp(jnp.clip(a_res, clamp[0], clamp[1]))
        h_res = sinkhorn(m.reshape((n, n) + rest), iters, hc_eps)
    return h_pre, h_post, h_res


def read(X, h_pre):
    """The sub-layer's input ``h`` [..., C]: the streams weighted by
    ``H_pre`` and summed."""
    with jax.named_scope(MIX):
        return sum(h_pre[i][..., None] * X[i] for i in range(X.shape[0]))


def write(X, y, h_res, h_post):
    """The streams after the sub-layer, [n, ..., C]: each a mix of the
    old ones by its row of ``H_res`` plus its share ``H_post`` of the
    sub-layer's output ``y`` [..., C]."""
    n = X.shape[0]
    with jax.named_scope(MIX):
        return jnp.stack([
            sum(h_res[j, i][..., None] * X[i] for i in range(n))
            + h_post[j][..., None] * y for j in range(n)])
