"""Power retention of degree 2 (arXiv:2507.04239): attention whose score is
``(scale q.k)^2`` under a per-head decay, run as a recurrence over a
state of fixed size in place of a key-value cache.

With ``phi`` the symmetric degree-2 feature map, ``phi(q).phi(k) ==
(scale q.k)^2`` exactly, so for a key-value head with log-gates ``gam_t <=
0``::

    S_t = exp(gam_t) S_{t-1} + phi(k_t) v_t^T       [D, hd]; kept as S^T
    z_t = exp(gam_t) z_{t-1} + phi(k_t)             [D]   the normaliser
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**The feature axis.** ``phi(u)[r, i] = scale c_r u_i u_{(i + r) mod d}``
for shifts ``r = 0 .. d/2`` with ``c_0 = 1`` (the squares), ``c_r =
sqrt(2)`` for ``0 < r < d/2`` (every unordered pair ``{i, i + r}`` once)
and ``c_{d/2} = 1`` (the pairs ``{i, i + d/2}`` come up twice, from either
end: twice 1 is ``sqrt(2)^2``). That is the symmetric embedding's ``d (d +
1) / 2`` features (8,256 at ``d`` 128) on ``(d/2 + 1) d`` places (8,320:
whole lane tiles, 64 of them held twice), made from ``u`` and its lane
rotations alone (one product with a matrix of zeros and ones), with no
gather and no outer product of 16,384.

**The resident state** of a layer is two pool arrays, ``S`` [blocks, KV,
hd, D] (a head's state with the features along the lanes: 65 whole lane
tiles at ``d`` 128, the values along the sublanes) and ``z`` [blocks, KV,
D], one block a row whatever the row's length (``serve/engine.py``: a
page that is a whole row). Block 0 is the null block and stays zeros; a
row whose cursor is 0 has no past, so a block another row left reads as
zeros to it.

* ``decode_step``: one token a row. On a TPU one Pallas kernel
  (``retention_state_decode``) over the live rows' blocks where they lie
  in the donated pool: a block comes in once, is decayed by its row's
  gate, takes the rank-1 update, goes out in its place, and on the way is
  read for the group's queries (all on the vector unit, in float32:
  ``new[v, d] * fq[g, d]`` summed a lane, the last sum over the 128 lanes
  left to XLA). Nothing of a block's size is gathered, copied or
  scattered, and a block no live row owns is not touched. Elsewhere (the
  CPU's tests) the same pass a row at a time in ``jax.numpy``.
* ``prefill``: a row's prompt in chunks: within a chunk the masked scores
  under the gates' differences, across chunks ``phi(Q) S`` (on a TPU the
  kernel ``retention_prefill_read``) and ``S <- decay S + [V; 1]^T
  phi(K)``. Padding leaves the state as it is (gate 1, feature 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu  # noqa: F401 — steered here by tests

HIGHEST = jax.lax.Precision.HIGHEST
# the path id (``paged_attention.paged_attend_path``)
RETENTION = "retention"
INTERPRET = False             # tests: run the kernel on the CPU (slow, exact)
# the kernel's blocks: a head's state in and out, double-buffered (4 x 4.3
# MB at a head of 128), beside a few hundred KB of features
_VMEM = 40 * 1024 * 1024


def feature_dim(d: int) -> int:
    """Places of the resident feature axis for a head dimension ``d``."""
    return (d // 2 + 1) * d


@functools.lru_cache(maxsize=8)
def _shifts(d: int):
    """``(P [d, 2 D], c [D])``: ``u @ P`` lays ``u`` side by side ``d/2 +
    1`` times (the first ``D`` columns) and then its rotations (column
    ``D + r d + i`` picks lane ``(i + r) mod d``); ``c`` holds the
    shifts' weights."""
    shifts, lane = np.arange(d // 2 + 1), np.arange(d)
    D = shifts.size * d
    P = np.zeros((d, 2 * D), np.float32)
    for r in shifts:
        P[lane, r * d + lane] = 1.0
        P[(lane + r) % d, D + r * d + lane] = 1.0
    c = np.where((shifts == 0) | (shifts == d // 2), 1.0, math.sqrt(2.0))
    return P, np.repeat(c, d).astype(np.float32)


def phi(u, scale: float):
    """``u`` [..., d] -> float32 [..., feature_dim(d)] with ``phi(q) .
    phi(k) == (scale q . k)^2``. Lanes and their rotations come from ONE
    product with a matrix of zeros and ones (exact: every sum has one
    term; bfloat16 in, or float32 at ``HIGHEST``). Slices laid side by
    side run on the chip as 65 small updates in place each time, a tiled
    broadcast as a transposing copy of the whole."""
    d = u.shape[-1]
    if d % 2:
        raise ValueError("the feature map pairs lanes: the head dimension "
                         "must be even")
    if u.dtype != jnp.bfloat16:
        u = u.astype(jnp.float32)
    P, c = _shifts(d)
    both = jnp.dot(u, jnp.asarray(P, u.dtype),
                   precision=None if u.dtype == jnp.bfloat16 else HIGHEST,
                   preferred_element_type=jnp.float32)
    D = feature_dim(d)
    return (scale * c) * both[..., :D] * both[..., D:]


def _rows_pass(S, bt, g, fk, v, fq, fresh):
    """The state pass as plain ``jax.numpy``, a row at a time: the rows'
    blocks out, decayed, updated, read, and back in their places. What a
    backend without the kernel runs (the CPU's tests at small sizes)."""
    old = jnp.where(fresh[:, None, None, None], 0.0,
                    S[bt].astype(jnp.float32))                # [b,KV,hd,D]
    new = (g[:, :, None, None] * old
           + v.astype(jnp.float32)[..., None] * fk[:, :, None, :])
    num = jnp.einsum("bkgd,bkvd->bkgv", fq, new, precision=HIGHEST)
    return num, S.at[bt].set(new.astype(S.dtype))


def _state_kernel(bt_ref, fresh_ref, s_ref, g_ref, vb_ref, fk_ref, fq_ref,
                  s_out, acc_out, *, hd: int, lanes: int, group: int,
                  rows: int):
    """One (row, key-value head) of the pass: the head's block of state
    ``[hd, D]`` in, decayed and updated, out in its place, and on the way
    each query head's products with it summed a lane: ``acc[j, v, l] =
    sum over the chunks c of new[v, c * 128 + l] * fq[j, c * 128 + l]``
    (the sum over the 128 lanes is left to the caller). ``rows`` values
    at a time, so that the group's accumulators stay in registers."""
    del bt_ref                      # the index maps read it
    fresh = fresh_ref[pl.program_id(0)] > 0
    g = g_ref[0, 0]                                            # [1, 128]
    for r0 in range(0, hd, rows):
        vb = vb_ref[0, 0, r0:r0 + rows, :]                     # [rows, 128]

        def chunk(c, accs, r0=r0, vb=vb):
            sl = pl.ds(pl.multiple_of(c * 128, 128), 128)
            old = jnp.where(fresh, 0.0, s_ref[0, 0, r0:r0 + rows, sl])
            new = g * old + vb * fk_ref[0, 0, :, sl]
            s_out[0, 0, r0:r0 + rows, sl] = new
            fq = fq_ref[0, 0, :, sl]                           # [G, 128]
            return tuple(a + new * fq[j:j + 1, :]
                         for j, a in enumerate(accs))

        accs = jax.lax.fori_loop(
            0, lanes // 128, chunk,
            tuple(jnp.zeros((rows, 128), jnp.float32)
                  for _ in range(group)))
        for j in range(group):
            acc_out[0, 0, j, r0:r0 + rows, :] = accs[j]


def _kernel_pass(S, bt, g, fk, v, fq, fresh):
    """``_rows_pass`` as one Pallas kernel over the live rows' blocks
    where they lie in the donated pool: each is read once and written
    once, and no block a live row does not own is touched but the null
    block (a row that is not live reads and writes its zeros)."""
    b, KV, G, D = fq.shape
    hd = S.shape[2]
    wide = lambda x: jnp.broadcast_to(            # noqa: E731
        x.astype(jnp.float32)[..., None], x.shape + (128,))
    at_block = lambda i, k, bt_, fresh_: (bt_[i], k, 0, 0)   # noqa: E731
    at_row = lambda i, k, *_: (i, k, 0, 0)                   # noqa: E731
    kernel = functools.partial(_state_kernel, hd=hd, lanes=D, group=G,
                               rows=min(hd, 32))
    S, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, KV),
            in_specs=[pl.BlockSpec((1, 1, hd, D), at_block),
                      pl.BlockSpec((1, 1, 1, 128), at_row),
                      pl.BlockSpec((1, 1, hd, 128), at_row),
                      pl.BlockSpec((1, 1, 1, D), at_row),
                      pl.BlockSpec((1, 1, G, D), at_row)],
            out_specs=[pl.BlockSpec((1, 1, hd, D), at_block),
                       pl.BlockSpec((1, 1, G, hd, 128),
                                    lambda i, k, *_: (i, k, 0, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((b, KV, G, hd, 128), jnp.float32)],
        # the state (operand 2, after the two prefetched) is output 0
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=INTERPRET,
        name="retention_state_decode",
    )(bt.astype(jnp.int32), fresh.astype(jnp.int32), S,
      wide(g)[:, :, None, :], wide(v), fk[:, :, None, :], fq)
    return acc.sum(-1), S


def state_pass_path(S) -> str:
    """``"kernel"`` or ``"rows"``: which implementation the decode step's
    pass over the state takes, from what the code can observe (a TPU, or
    ``INTERPRET``; a float32 pool whose head and feature axes are whole
    tiles)."""
    _P, _KV, hd, D = S.shape
    ok = S.dtype == jnp.float32 and hd % 8 == 0 and D % 128 == 0
    return "kernel" if ok and (_on_tpu() or INTERPRET) else "rows"


def decode_step(S, z, bt, q, k, v, gam, fresh, eps: float, scale: float):
    """One token a row against the resident pools.

    ``S`` [P, KV, hd, D], ``z`` [P, KV, D] (any float dtype; the update
    is computed in float32); ``bt`` [b] each row's block (0: the row is
    not live and touches nothing); ``q`` [b, KV, G, hd], ``k``, ``v``
    [b, KV, hd]; ``gam`` [b, KV] float32 log-gates; ``fresh`` [b]: the
    row has no past (its block reads as zeros, by a select and not by a
    product: it may hold anything). Returns ``(y [b, KV, G, hd] float32,
    S, z)``."""
    live = bt != 0
    with jax.named_scope("attn.retention.gate"):
        # a row that is not live: gate 1, feature 0, which leaves the
        # null block the zeros it is
        g = jnp.where(live[:, None], jnp.exp(gam), 1.0)
        fk = jnp.where(live[:, None, None], phi(k, scale), 0.0)  # [b,KV,D]
        fq = phi(q, scale)                                    # [b,KV,G,D]
    with jax.named_scope("attn.retention.state"):
        new_z = g[..., None] * jnp.where(
            fresh[:, None, None], 0.0, z[bt].astype(jnp.float32)) + fk
        den = jnp.einsum("bkgd,bkd->bkg", fq, new_z, precision=HIGHEST)
        z = z.at[bt].set(new_z.astype(z.dtype))
        pass_ = (_kernel_pass if state_pass_path(S) == "kernel"
                 else _rows_pass)
        num, S = pass_(S, bt, g, fk, v, fq, fresh)
        y = jnp.where(live[:, None, None, None],
                      num / (den[..., None] + eps), 0.0)
    return y, S, z


def load_rows(S, z, bt, fresh):
    """Each row's state before a prefill, float32: its block ``bt`` [b]
    out of the pools, zeros for a row that has no past (``fresh`` [b]:
    whatever the block's last owner left is not this row's)."""
    S0 = jnp.where(fresh[:, None, None, None], 0.0,
                   S[bt].astype(jnp.float32))
    z0 = jnp.where(fresh[:, None, None], 0.0, z[bt].astype(jnp.float32))
    return S0, z0


def store_rows(S, z, bt, S1, z1):
    """The rows' new states back into their blocks, in place on the
    pools; a row that is not live (block 0) writes nothing."""
    with jax.named_scope("attn.retention.state"):
        keep = (bt != 0)[:, None, None, None]
        S = S.at[bt].set(jnp.where(keep, S1, S[bt]).astype(S.dtype))
        z = z.at[bt].set(jnp.where(keep[..., 0], z1, z[bt]).astype(z.dtype))
    return S, z


def prefill(S0, z0, q, k, v, gam, valid, eps: float, scale: float,
            chunk: int, mm_dtype=jnp.float32):
    """A run of ``T`` positions a row, in chunks of ``chunk``.

    ``S0`` [b, KV, hd, D], ``z0`` [b, KV, D] float32: each row's state
    before the run; ``q`` [b, KV, G, T, hd], ``k``, ``v`` [b, KV, T, hd];
    ``gam`` [b, KV, T] float32 log-gates; ``valid`` [b, T] bool: a
    position that is not valid (a bucket's padding) leaves the state as
    it is. Returns ``(y [b, KV, G, T, hd] float32, S, z)``. Products
    take their operands in ``mm_dtype`` and sum in float32; gates, scores
    and the state itself are float32."""
    b, KV, G, T, hd = q.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"a run of {T} positions is not whole chunks of "
                         f"{C}")
    n = T // C
    mm = jnp.dtype(mm_dtype)
    prec = HIGHEST if mm == jnp.float32 else None
    from .paged_attention import report_path   # it imports this module
    path = prefill_read_path(S0)
    report_path(f"retention_read_{path}", (b, KV, G, C, hd), str(mm))
    keep = valid[:, None, :]
    gam = jnp.where(keep, gam, 0.0)
    k = jnp.where(keep[..., None], k, 0).astype(mm)
    # the normaliser rides as the state's last value row, so that ONE
    # product reads numerator and normaliser (as a product of its own the
    # normaliser's read is a reduction over all of phi(Q) once more); it
    # is still summed in float32, beside the product that updates S
    S0 = jnp.concatenate([S0.astype(jnp.float32),
                          z0.astype(jnp.float32)[:, :, None, :]], axis=2)
    q, v = q.astype(mm), v.astype(mm)
    # chunk-major for the scan
    qc = jnp.moveaxis(q.reshape(b, KV, G, n, C, hd), 3, 0)
    kc = jnp.moveaxis(k.reshape(b, KV, n, C, hd), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, KV, n, C, hd), 2, 0)
    gc = jnp.moveaxis(gam.reshape(b, KV, n, C), 2, 0)
    causal = jnp.tril(jnp.ones((C, C), bool))

    def one_chunk(S, xs):
        q_c, k_c, v_c, g_c = xs
        with jax.named_scope("attn.retention.gate"):
            cg = jnp.cumsum(g_c, axis=-1)                     # [b, KV, C]
            total = cg[..., -1]
            into = jnp.exp(cg)                 # the state's weight at t
            out_of = jnp.exp(total[..., None] - cg)   # key s at the end
        with jax.named_scope("attn.retention.chunk"):
            s = jnp.einsum("bkgtd,bksd->bkgts", q_c, k_c, precision=prec,
                           preferred_element_type=jnp.float32) * scale
            diff = cg[..., :, None] - cg[..., None, :]        # [b,KV,C,C]
            w = jnp.square(s) * jnp.exp(
                jnp.where(causal, diff, -jnp.inf))[:, :, None]
            num = jnp.einsum("bkgts,bksv->bkgtv", w.astype(mm), v_c,
                             precision=prec,
                             preferred_element_type=jnp.float32)
            den = w.sum(-1)
        with jax.named_scope("attn.retention.state"):
            if path == "kernel":
                read = into[:, :, None, :, None] * _kernel_read(
                    q_c, S, scale, prec)
            else:
                fq = phi(q_c, scale).astype(mm)           # [b,KV,G,C,D]
                read = into[:, :, None, :, None] * jnp.einsum(
                    "bkgtd,bkvd->bkgtv", fq, S.astype(mm), precision=prec,
                    preferred_element_type=jnp.float32)
            num, den = num + read[..., :hd], den + read[..., hd]
            fk = phi(k_c, scale) * out_of[..., None]        # [b,KV,C,D]
            S = jnp.exp(total)[..., None, None] * S + jnp.concatenate(
                [jnp.einsum("bksv,bksd->bkvd", v_c, fk.astype(mm),
                            precision=prec,
                            preferred_element_type=jnp.float32),
                 fk.sum(2)[:, :, None, :]], axis=2)
        return S, num / (den[..., None] + eps)

    S, y = jax.lax.scan(one_chunk, S0, (qc, kc, vc, gc))
    # [n, b, KV, G, C, hd] -> [b, KV, G, T, hd]
    y = jnp.moveaxis(y, 0, 3).reshape(b, KV, G, T, hd)
    return y, S[:, :, :hd], S[:, :, hd]


def attend(q, k, v, gam, eps: float, scale: float):
    """The attention form over a whole sequence, for tests: ``q`` [KV, G,
    T, hd], ``k``, ``v`` [KV, T, hd], ``gam`` [KV, T] -> [KV, G, T, hd].
    Float32, ``HIGHEST``."""
    T = q.shape[2]
    cg = jnp.cumsum(gam.astype(jnp.float32), axis=-1)
    s = jnp.einsum("kgtd,ksd->kgts", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=HIGHEST) * scale
    diff = cg[:, :, None] - cg[:, None, :]
    w = jnp.square(s) * jnp.exp(jnp.where(
        jnp.tril(jnp.ones((T, T), bool)), diff, -jnp.inf))[:, None]
    num = jnp.einsum("kgts,ksv->kgtv", w, v.astype(jnp.float32),
                     precision=HIGHEST)
    return num / (w.sum(-1)[..., None] + eps)


def prefill_read_path(S0) -> str:
    """``"kernel"`` or ``"rows"``: how a prefill's chunk reads the state,
    by ``state_pass_path``'s test on the rows' states ``S0`` [b, KV, hd,
    D] (a TPU, or ``INTERPRET``; float32, head and feature axes whole
    tiles). ``"rows"`` is ``phi(Q)`` written out and one einsum."""
    return state_pass_path(S0)


def _read_kernel(q_ref, s_ref, out_ref, sb_ref, *, hd: int, scale: float,
                 prec):
    """One (row, key-value head) of a chunk's read: the head's state
    ``[hd + 1, D]`` (the normaliser its last row) against ``phi`` of the
    group's ``G x C`` query rows, which is never whole: for each shift
    ``r`` the rows' lanes rotated by ``r``, times the rows and ``scale
    c_r`` in float32, rounded to the product's dtype as
    ``phi(q).astype(mm)`` rounds, and the state's lanes ``[r hd, (r + 1)
    hd)`` times them, summed in float32. The features are the product's
    stationary side and the state's ``hd + 1`` rows stream past them, so
    the output is ``[values, query rows]`` and the normaliser's row costs
    the matrix unit an eighth more, not a second tile. The state is cast
    to the product's dtype once, into ``sb_ref``, whose rows from ``hd``
    are the normaliser and zeros (a whole tile of the packed dtype)."""
    mm, wide = sb_ref.dtype, sb_ref.shape[0]
    sb_ref[0:hd, :] = s_ref[0, 0, 0:hd, :].astype(mm)
    z = s_ref[0, 0, hd:hd + 1, :]                              # [1, D]
    first = jax.lax.broadcasted_iota(
        jnp.int32, (wide - hd, z.shape[1]), 0) == 0
    sb_ref[hd:wide, :] = jnp.where(first, z, 0.0).astype(mm)
    q = q_ref[0, 0].astype(jnp.float32)                        # [G C, hd]
    acc = jnp.zeros(out_ref.shape[2:], jnp.float32)
    for r in range(hd // 2 + 1):
        c = np.float32(1.0 if r in (0, hd // 2) else math.sqrt(2.0))
        rot = pltpu.roll(q, hd - r, 1) if r else q
        acc = acc + jax.lax.dot_general(
            sb_ref[:, r * hd:(r + 1) * hd],
            ((np.float32(scale) * c) * q * rot).astype(mm),
            (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)
    out_ref[0, 0] = acc


def _kernel_read(q_c, S, scale: float, prec):
    """``einsum("bkgtd,bkvd->bkgtv", phi(q_c, scale).astype(mm),
    S.astype(mm))`` as one Pallas kernel that never writes ``phi(q_c)``
    out: ``q_c`` [b, KV, G, C, hd] in the product's dtype, ``S`` [b, KV,
    hd + 1, D] float32 -> float32 [b, KV, G, C, hd + 16], of which the
    first ``hd + 1`` columns are the read. A key-value head's state
    comes in once for its group's ``G x C`` query rows."""
    b, KV, G, C, hd = q_c.shape
    D, wide = S.shape[-1], hd + 16
    at_head = lambda i, k: (i, k, 0, 0)                     # noqa: E731
    out = pl.pallas_call(
        functools.partial(_read_kernel, hd=hd, scale=scale, prec=prec),
        grid=(b, KV),
        in_specs=[pl.BlockSpec((1, 1, G * C, hd), at_head),
                  pl.BlockSpec((1, 1, hd + 1, D), at_head)],
        out_specs=pl.BlockSpec((1, 1, wide, G * C), at_head),
        out_shape=jax.ShapeDtypeStruct((b, KV, wide, G * C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((wide, D), q_c.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM),
        interpret=INTERPRET,
        name="retention_prefill_read",
    )(q_c.reshape(b, KV, G * C, hd), S)
    return jnp.moveaxis(out.reshape(b, KV, wide, G, C), 2, -1)
