"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear
attention whose state is CORRECTED, not only added to, run as a
recurrence over a state of fixed size beside a short causal convolution.

For a value head with key ``k_t`` and query ``q_t`` [dk] (each divided by
its l2 norm; the query scaled by ``dk ** -0.5``), value ``v_t`` [dv],
decay ``alpha_t = exp(g_t)`` in (0, 1] and write strength ``beta_t`` in
(0, 1)::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                                   S [dk, dv] a head

Each token first READS the decayed state (``S^T k``: what the state
already answers to this key) and writes only the correction, so the update
is not a sum of outer products and a run of tokens does not reduce to one
product: a prefill takes the chunked form below, a triangular solve a
chunk.

**The resident cache** of a layer is two arrays indexed by STATE BLOCK on
their first axis, one block a row whatever the row's length
(``serve/engine.py``: a row that holds a state block beside its pages):
``S`` [blocks, Hv, dk, dv] (float32 as served) and ``conv`` [blocks,
width - 1, channels], the last inputs of the causal depthwise convolution
that feeds q, k and v. Block 0 is the null block and stays zeros; a row
whose cursor is 0 has no past, so a block another row left reads as zeros
to it.

* ``decode_step``: one token a row. On a TPU one Pallas kernel
  (``gated_delta_state_decode``) over the live rows' blocks where they lie
  in the donated pool: ``_HEADS`` value heads of a row come in, are
  decayed, corrected, read for their queries and go out in their place
  (all on the vector unit, in float32). Nothing of a block's size is
  gathered, copied or scattered, and a block no live row owns is not
  touched. Elsewhere (the CPU's tests) the same pass in ``jax.numpy``.
* ``conv_step`` / ``conv_run``: the convolution of one new position
  against a row's kept inputs, and of a run of positions, with the kept
  inputs carried from pass to pass.
* ``prefill``: a row's prompt in chunks of ``chunk`` positions. In a
  chunk, with ``G`` the running log-decay::

      A = tril(beta_i (k_i . k_j) exp(G_i - G_j), -1)
      T = (I + A)^-1                      float32; A is nilpotent, so the
                                          inverse is the finite product
                                          (I - A)(I + A^2)(I + A^4)...
      W = T (beta k exp(G))    U = T (beta v)
      v_new = U - W S
      o = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) v_new
      S <- exp(G_last) S + (k exp(G_last - G))^T v_new

  Positions at or past the prompt's length leave the state as it is
  (decay 1, strength 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu  # noqa: F401 — steered here by tests

HIGHEST = jax.lax.Precision.HIGHEST
# the path id (``paged_attention.paged_attend_path``)
GATED_DELTA = "gated_delta"
INTERPRET = False             # tests: run the kernel on the CPU (slow, exact)
_HEADS = 8                    # value heads of a row a program of the kernel
_VMEM = 32 * 1024 * 1024


def l2norm(x, eps: float = 1e-6):
    """``x`` divided by its l2 norm over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + eps)


# -- the convolution ---------------------------------------------------------


def conv_step(conv, sb, x, w, fresh):
    """One new position a row through the causal depthwise convolution.

    ``conv`` [B, K - 1, ch] the pool of kept inputs; ``sb`` [b] each row's
    state block (0: not live, touches nothing); ``x`` [b, ch] the new
    inputs in ``conv``'s dtype; ``w`` [ch, K]; ``fresh`` [b]: the row has
    no past. Returns ``(silu(conv) [b, ch] float32, conv)`` with the rows'
    kept inputs shifted by one."""
    past = jnp.where(fresh[:, None, None], 0, conv[sb])       # [b, K-1, ch]
    win = jnp.concatenate([past, x[:, None]], axis=1)         # [b, K, ch]
    y = jnp.einsum("bkc,ck->bc", win.astype(jnp.float32),
                   w.astype(jnp.float32))
    live = (sb != 0)[:, None, None]
    conv = conv.at[sb].set(jnp.where(live, win[:, 1:], 0).astype(conv.dtype))
    return jax.nn.silu(y), conv


def conv_run(past, x, w, n_valid):
    """A run of ``t`` positions a row: ``past`` [b, K - 1, ch] the inputs
    kept before the run, ``x`` [b, t, ch] (in ``past``'s dtype), ``w``
    [ch, K], ``n_valid`` [b] how many of the run's positions are the
    prompt's (the rest is a bucket's padding). Returns ``(silu(conv) [b,
    t, ch] float32, kept)``: ``kept`` the last ``K - 1`` inputs up to the
    last valid position (``past`` itself where none is)."""
    b, t, ch = x.shape
    K = w.shape[1]
    ext = jnp.concatenate([past.astype(x.dtype), x], axis=1)  # [b,t+K-1,ch]
    wf = w.astype(jnp.float32)
    # a sum of K shifted products: output i reads ext[i .. i + K - 1]
    y = sum(ext[:, j:j + t].astype(jnp.float32) * wf[:, j] for j in range(K))
    off = jnp.clip(n_valid, 0, t)
    kept = jax.vmap(lambda e, o: jax.lax.dynamic_slice_in_dim(
        e, o, K - 1, axis=0))(ext, off)
    return jax.nn.silu(y), kept.astype(past.dtype)


# -- one token a row ---------------------------------------------------------


def _token(S, q, k, v, alpha, beta):
    """One token of the rule in float32: ``S`` [..., dk, dv] decayed, read
    for the key, corrected, read for the query -> ``(o [..., dv], S)``."""
    dec = alpha[..., None, None] * S
    read = jnp.einsum("...kv,...k->...v", dec, k, precision=HIGHEST)
    new = dec + k[..., :, None] * (beta[..., None] * (v - read))[..., None, :]
    return jnp.einsum("...kv,...k->...v", new, q, precision=HIGHEST), new


def _rows_pass(S, sb, q, k, v, alpha, beta, fresh):
    """The state pass as plain ``jax.numpy``: the rows' blocks out,
    decayed, corrected, read, and back in their places."""
    old = jnp.where(fresh[:, None, None, None], 0.0,
                    S[sb].astype(jnp.float32))                # [b,H,dk,dv]
    o, new = _token(old, q, k, v, alpha, beta)
    return o, S.at[sb].set(new.astype(S.dtype))


def _state_kernel(sb_ref, fresh_ref, s_ref, q_ref, k_ref, v_ref, a_ref,
                  b_ref, s_out, o_out, *, heads: int):
    """``heads`` value heads of one row: each head's state ``[dk, dv]``
    in, decayed and corrected, out in its place, and read for its query.
    ``k`` and ``q`` come as rows (lanes) and are needed down the
    sublanes: a row broadcast to a square tile and transposed is the
    column broadcast along the lanes."""
    del sb_ref                      # the index maps read it
    fresh = fresh_ref[pl.program_id(0)] > 0
    dk = s_ref.shape[2]
    for j in range(heads):
        row = lambda ref: ref[0, j:j + 1, :]          # noqa: E731  [1, n]
        col = lambda ref: jnp.broadcast_to(           # noqa: E731
            row(ref), (dk, dk)).T                     # [dk, dk], k down
        old = jnp.where(fresh, 0.0, s_ref[0, j])                 # [dk, dv]
        dec = row(a_ref) * old
        kc = col(k_ref)
        read = (dec * kc).sum(axis=0, keepdims=True)             # [1, dv]
        delta = row(b_ref) * (row(v_ref) - read)
        new = dec + kc * delta
        s_out[0, j] = new
        o_out[0, j:j + 1, :] = (new * col(q_ref)).sum(axis=0, keepdims=True)


def _kernel_pass(S, sb, q, k, v, alpha, beta, fresh):
    """``_rows_pass`` as one Pallas kernel over the live rows' blocks
    where they lie in the donated pool: each is read once and written
    once, and no block a live row does not own is touched but the null
    block (a row that is not live reads and writes its zeros)."""
    b, H, dk = q.shape
    dv = v.shape[-1]
    hb = min(_HEADS, H)
    wide = lambda x: jnp.broadcast_to(            # noqa: E731
        x.astype(jnp.float32)[..., None], x.shape + (dv,))
    at_block = lambda i, h, sb_, fresh_: (sb_[i], h, 0, 0)   # noqa: E731
    at_row = lambda i, h, *_: (i, h, 0)                      # noqa: E731
    vec = lambda n: pl.BlockSpec((1, hb, n), at_row)         # noqa: E731
    S, o = pl.pallas_call(
        functools.partial(_state_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, H // hb),
            in_specs=[pl.BlockSpec((1, hb, dk, dv), at_block),
                      vec(dk), vec(dk), vec(dv), vec(dv), vec(dv)],
            out_specs=[pl.BlockSpec((1, hb, dk, dv), at_block), vec(dv)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((b, H, dv), jnp.float32)],
        # the state (operand 2, after the two prefetched) is output 0
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=INTERPRET,
        name="gated_delta_state_decode",
    )(sb.astype(jnp.int32), fresh.astype(jnp.int32), S,
      q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
      wide(alpha), wide(beta))
    return o, S


def state_pass_path(S) -> str:
    """``"kernel"`` or ``"rows"``: which implementation the decode step's
    pass over the state takes, from what the code can observe (a TPU, or
    ``INTERPRET``; a float32 pool of square heads that are whole tiles,
    in whole groups of ``_HEADS``)."""
    _B, H, dk, dv = S.shape
    ok = (S.dtype == jnp.float32 and dk == dv and dk % 128 == 0
          and H % min(_HEADS, H) == 0)
    return "kernel" if ok and (_on_tpu() or INTERPRET) else "rows"


def decode_step(S, sb, q, k, v, g, beta, fresh):
    """One token a row against the resident pool.

    ``S`` [B, H, dk, dv] (any float dtype; the update is computed in
    float32); ``sb`` [b] each row's state block (0: the row is not live
    and touches nothing); ``q``, ``k`` [b, H, dk] normed (and ``q``
    scaled), ``v`` [b, H, dv]; ``g`` [b, H] float32 log-decays, ``beta``
    [b, H]; ``fresh`` [b]: the row has no past (its block reads as zeros,
    by a select and not by a product: it may hold anything). Returns
    ``(o [b, H, dv] float32, S)``."""
    live = (sb != 0)[:, None]
    # a row that is not live: decay 1, strength 0, which leaves the null
    # block the zeros it is
    alpha = jnp.where(live, jnp.exp(g), 1.0)
    beta = jnp.where(live, beta, 0.0)
    pass_ = _kernel_pass if state_pass_path(S) == "kernel" else _rows_pass
    o, S = pass_(S, sb, q.astype(jnp.float32), k.astype(jnp.float32),
                 v.astype(jnp.float32), alpha, beta, fresh)
    return jnp.where(live[..., None], o, 0.0), S


# -- a run of positions a row ------------------------------------------------


def load_rows(S, conv, sb, fresh):
    """Each row's state and kept inputs before a prefill: its block ``sb``
    [b] out of the pools (the state float32), zeros for a row that has no
    past (``fresh`` [b]: whatever the block's last owner left is not this
    row's)."""
    S0 = jnp.where(fresh[:, None, None, None], 0.0,
                   S[sb].astype(jnp.float32))
    c0 = jnp.where(fresh[:, None, None], 0, conv[sb])
    return S0, c0


def store_rows(S, conv, sb, S1, c1):
    """The rows' new states back into their blocks, in place on the
    pools; a row that is not live (block 0) writes nothing."""
    keep = (sb != 0)[:, None, None]
    S = S.at[sb].set(jnp.where(keep[..., None], S1, S[sb]).astype(S.dtype))
    conv = conv.at[sb].set(jnp.where(keep, c1, conv[sb]).astype(conv.dtype))
    return S, conv


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A`` [..., C, C] strictly lower triangular:
    ``A`` is nilpotent (``A^C = 0``), so with ``N = -A`` the inverse is
    ``sum_i N^i = (I + N)(I + N^2)(I + N^4)...``, a product of ``log2 C``
    factors. What forward substitution gives, row by row, in as many
    products as the chunk has bits. Float32, ``HIGHEST``."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=jnp.float32)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    N = -A
    T = eye + N
    span = 2
    while span < C:
        N = mm(N, N)
        T = mm(T, eye + N)
        span *= 2
    return T


def prefill(S0, q, k, v, g, beta, valid, chunk: int, mm_dtype=jnp.float32):
    """A run of ``T`` positions a row, in chunks of ``chunk``.

    ``S0`` [b, H, dk, dv] float32: each row's state before the run; ``q``,
    ``k`` [b, H, T, dk] normed (``q`` scaled), ``v`` [b, H, T, dv]; ``g``
    [b, H, T] float32 log-decays, ``beta`` [b, H, T]; ``valid`` [b, T]
    bool: a position that is not valid (a bucket's padding) leaves the
    state as it is. Returns ``(o [b, H, T, dv] float32, S)``. The products
    against the state and the values take their operands in ``mm_dtype``
    and sum in float32; decays, the triangular inverse and the state
    itself are float32."""
    b, H, T, dk = q.shape
    dv = v.shape[-1]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"a run of {T} positions is not whole chunks of "
                         f"{C}")
    n = T // C
    mm = jnp.dtype(mm_dtype)
    prec = HIGHEST if mm == jnp.float32 else None
    keep = valid[:, None, :]
    g = jnp.where(keep, g, 0.0)
    beta = jnp.where(keep, beta, 0.0).astype(jnp.float32)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    # chunk-major for the scan: [n, b, H, C, ...]
    cut = lambda x: jnp.moveaxis(                 # noqa: E731
        x.reshape(b, H, n, C, *x.shape[3:]), 2, 0)
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    causal = jnp.tril(jnp.ones((C, C), bool))

    def dot(eq, x, y):
        return jnp.einsum(eq, x.astype(mm), y.astype(mm), precision=prec,
                          preferred_element_type=jnp.float32)

    # what does not wait for the state, for all chunks at once
    G = jnp.cumsum(cut(g), axis=-1)                         # [n,b,H,C]
    qc, kc, vc, bc = cut(q), cut(k), cut(v), cut(beta)
    diff = G[..., :, None] - G[..., None, :]                # G_i - G_j
    kk = jnp.einsum("nbhik,nbhjk->nbhij", kc, kc, precision=HIGHEST)
    A = jnp.where(lower, bc[..., None] * kk * jnp.exp(
        jnp.where(lower, diff, 0.0)), 0.0)
    Tm = _unit_lower_inverse(A)
    W = jnp.matmul(Tm, kc * (bc * jnp.exp(G))[..., None], precision=HIGHEST)
    U = jnp.matmul(Tm, vc * bc[..., None], precision=HIGHEST)
    P = jnp.where(causal, dot("nbhik,nbhjk->nbhij", qc, kc) * jnp.exp(
        jnp.where(causal, diff, 0.0)), 0.0)                 # [n,b,H,C,C]
    qg = qc * jnp.exp(G)[..., None]
    total = G[..., -1]                                      # [n,b,H]
    kd = kc * jnp.exp(total[..., None] - G)[..., None]

    def one_chunk(S, xs):
        W_c, U_c, P_c, qg_c, kd_c, tot = xs
        v_new = U_c - dot("bhck,bhkv->bhcv", W_c, S)
        o = dot("bhck,bhkv->bhcv", qg_c, S) + dot("bhij,bhjv->bhiv", P_c,
                                                  v_new)
        S = jnp.exp(tot)[..., None, None] * S + dot(
            "bhck,bhcv->bhkv", kd_c, v_new)
        return S, o

    S, o = jax.lax.scan(one_chunk, S0.astype(jnp.float32),
                        (W, U, P, qg, kd, total))
    # [n, b, H, C, dv] -> [b, H, T, dv]
    return jnp.moveaxis(o, 0, 2).reshape(b, H, T, dv), S


def recur(S0, q, k, v, g, beta):
    """The rule token by token over a whole sequence, for tests: ``S0``
    [H, dk, dv]; ``q``, ``k`` [T, H, dk], ``v`` [T, H, dv], ``g``,
    ``beta`` [T, H] -> ``(o [T, H, dv], S)``. Float32, ``HIGHEST``."""
    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        o, S = _token(S, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return S, o

    S, o = jax.lax.scan(step, S0.astype(jnp.float32), tuple(
        x.astype(jnp.float32) for x in (q, k, v, g, beta)))
    return o, S
