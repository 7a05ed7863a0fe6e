"""Mixture-of-Experts layer + expert parallelism (beyond-reference).

The reference framework has no MoE / expert parallelism anywhere
(SURVEY §2.3: EP row ❌ — its model zoo is dense nanoGPT,
``example/nanogpt/nanogpt.py:104-123`` MLP only). This module closes that
row the TPU way: a GShard/Switch-style token-choice router with **static
capacity** (no data-dependent shapes — XLA requirement), dispatch/combine as
one-hot einsums (MXU-friendly), and expert parallelism as a GSPMD-auto
``'expert'`` mesh axis — expert-stacked params carry
``P('expert', ...)`` sharding constraints and XLA inserts the all-to-alls,
the same recipe as the tensor-parallel path
(``gym_tpu/parallel/tensor_parallel.py``).

Design notes (TPU-first):
- Router math in f32 even under bf16 autocast (softmax/cumsum stability).
- top-k selection is a static K-iteration loop of argmax+mask (K ≤ 2 in
  practice) — no sorts, no dynamic shapes.
- Position-in-expert via cumsum over the flattened token axis; tokens past
  an expert's capacity are *dropped* (their combine weight is 0 and the
  residual connection carries them through) — standard Switch semantics.
- Load-balance aux loss (Switch Transformer eq. 4): ``E · Σ_e f_e · p_e``
  over the top-1 routing fraction f and mean router prob p, plus a router
  z-loss; both are returned from the layer and folded into the training
  loss by the model (weighted by ``GPTConfig.moe_aux_weight`` /
  ``moe_z_weight``).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.axis import EXPERT_AXIS

PyTree = Any


def _init_normal(std: float):
    return nn.initializers.normal(stddev=std)


def _grouped_dot(x, w, sorted_e, chunk_rows: int):
    """``ops.grouped_matmul.grouped_dot`` in row blocks of ``chunk_rows``
    (VERDICT r4 #7: the single whole-array grouped matmul exceeds
    Mosaic's VMEM stack at GPT-base batch 16 — S·K = 32768 rows — while
    batch 12 fit; chunking bounds the kernel's working set regardless of
    batch).

    ``sorted_e`` (the per-row expert id, ascending) is the single source
    of the grouping — every (sub)call histograms its own group sizes from
    it, so no redundant precomputed sizes can silently disagree. A
    contiguous slice of expert-sorted rows is itself expert-sorted, so
    each block is a valid grouped matmul (groups split across a boundary
    just contribute to both blocks). Padding rows carry expert id E−1 —
    the maximum — keeping the sorted invariant; their outputs are sliced
    off. The primitive's flattening batch rule (not ``custom_vmap``,
    which breaks under ``vmap(grad(...))`` — see ops/grouped_matmul.py)
    makes every path here vmap- AND grad-safe, so vnode-folded node
    programs keep ragged-class throughput instead of falling back to the
    E/topk×-FLOPs dense dispatch."""
    from ..ops.grouped_matmul import grouped_dot

    n = x.shape[0]
    n_experts = w.shape[0]

    def sizes(e):
        return jnp.sum(e[:, None] == jnp.arange(n_experts)[None, :],
                       axis=0, dtype=jnp.int32)

    if chunk_rows <= 0 or n <= chunk_rows:
        return grouped_dot(x, w, sizes(sorted_e))
    pad = (-n) % chunk_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        sorted_e = jnp.concatenate(
            [sorted_e, jnp.full((pad,), n_experts - 1, sorted_e.dtype)])
    n_chunks = (n + pad) // chunk_rows
    xc = x.reshape(n_chunks, chunk_rows, x.shape[-1])
    ec = sorted_e.reshape(n_chunks, chunk_rows)

    def one(args):
        x_c, e_c = args
        return grouped_dot(x_c, w, sizes(e_c))

    h = jax.lax.map(one, (xc, ec))
    return h.reshape(-1, w.shape[-1])[:n]


def _constrain(x, spec):
    """``with_sharding_constraint`` that is a no-op under mesh-less tracing
    (unit tests without a mesh context) but fails loudly on a real
    misconfiguration (e.g. an axis name missing from the mesh)."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec)
    )


class MoEMLP(nn.Module):
    """Drop-in replacement for the GPT ``MLP``: E experts, top-k routing.

    ``__call__(x, train) -> (y, aux)`` where ``y`` has ``x``'s shape and
    ``aux`` is the *weighted* auxiliary loss (balance + z), a f32 scalar.
    """

    n_embd: int
    n_layer: int
    n_experts: int
    topk: int = 2
    capacity_factor: float = 1.25
    dropout: float = 0.0
    bias: bool = True
    aux_weight: float = 1e-2
    z_weight: float = 1e-3
    expert_axis: Optional[str] = None  # mesh axis name for EP (GSPMD-auto)
    # Dispatch implementation:
    #   'einsum' — GShard one-hot dispatch/combine tensors [S, E, cap].
    #       Capacity-limited (overflow tokens dropped), EP-shardable, but
    #       costs O(S·E·cap·C) FLOPs/bytes — at GPT-base shapes that
    #       *exceeds* the expert matmuls themselves.
    #   'ragged' — sort tokens by expert, one `jax.lax.ragged_dot` grouped
    #       matmul per projection (the TPU-native MoE kernel path), combine
    #       by segment-sum. No capacity limit (no drops), O(S·K·C·H) only.
    #       Not EP-shardable (row→expert mapping is data-dependent).
    #   'dense' — every expert runs every token; the combine masks to the
    #       selected top-k. Mathematically identical to 'ragged' (same
    #       top-k selection + gate normalization, no drops) at E/K× its
    #       FLOPs, but vmap-safe and static-shaped everywhere.
    #   'auto' — einsum under EP (expert_axis set: the standard GShard
    #       capacity semantics, an explicit *config* choice, not topology);
    #       otherwise ragged everywhere (since r5 the grouped matmul is a
    #       first-class primitive whose flattening batching rule makes it
    #       vmap+grad-safe — ops/grouped_matmul.py — so vnode-folded
    #       programs keep the ragged path too; the objective is identical
    #       however K simulated nodes fold onto devices). 'dense' remains
    #       as the explicit vmap-safe reference implementation.
    moe_impl: str = "auto"
    # Row-block size for the chunked grouped matmul (VERDICT r4 #7): caps
    # the ragged_dot working set so GPT-base batch 16 (S·K = 32768 rows)
    # stays under Mosaic's VMEM stack limit. <= 0 disables chunking.
    chunk_rows: int = 16384

    @nn.compact
    def __call__(self, x, train: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
        E, K = self.n_experts, self.topk
        if not 1 <= K <= E:
            raise ValueError(f"topk={K} must be in [1, n_experts={E}]")
        B, T, C = x.shape
        S = B * T
        hid = 4 * C
        xf = x.reshape(S, C)

        impl = self.moe_impl
        if impl == "auto":
            impl = "einsum" if self.expert_axis else "ragged"
        if impl not in ("einsum", "ragged", "dense"):
            raise ValueError(f"unknown moe_impl {impl!r}")
        if impl == "ragged" and self.expert_axis:
            raise ValueError(
                "ragged MoE dispatch cannot shard experts (use "
                "moe_impl='einsum' for expert parallelism)")

        # -- router (f32) --------------------------------------------------
        logits = nn.Dense(
            E, use_bias=False, kernel_init=_init_normal(0.02), name="router",
        )(xf).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)                    # [S, E]

        # -- expert params (shared by both dispatch impls) -----------------
        w_fc = self.param("fc_kernel", _init_normal(0.02), (E, C, hid))
        w_pr = self.param(
            "proj_kernel", _init_normal(0.02 / math.sqrt(2 * self.n_layer)),
            (E, hid, C),
        )
        b_fc = (self.param("fc_bias", nn.initializers.zeros, (E, hid))
                if self.bias else None)
        b_pr = (self.param("proj_bias", nn.initializers.zeros, (E, C))
                if self.bias else None)
        dtype = x.dtype

        if impl == "ragged":
            try:
                return self._ragged(xf, gates, logits, w_fc, b_fc, w_pr,
                                    b_pr, (B, T, C), train)
            except NotImplementedError:
                # safety net only: the grouped-matmul primitive carries
                # its own batching rule, so vmapped programs normally stay
                # on the ragged path; an exotic transform that still
                # refuses to lower falls back to the dense same-objective
                # dispatch
                impl = "dense"
        if impl == "dense":
            return self._dense(xf, gates, logits, w_fc, b_fc, w_pr, b_pr,
                               (B, T, C), train)

        capacity = min(int(math.ceil(self.capacity_factor * S * K / E)), S)

        # -- static top-k assignment with capacity -------------------------
        remaining = gates
        offset = jnp.zeros((E,), jnp.int32)      # slots used by earlier k
        dispatch = jnp.zeros((S, E, capacity), jnp.float32)
        combine = jnp.zeros((S, E, capacity), jnp.float32)
        gate_sum = jnp.zeros((S,), jnp.float32)
        top1_mask = None
        for k in range(K):
            idx_k = jnp.argmax(remaining, axis=-1)                 # [S]
            mask_k = jax.nn.one_hot(idx_k, E, dtype=jnp.int32)     # [S, E]
            if k == 0:
                top1_mask = mask_k
            gate_k = jnp.sum(gates * mask_k, axis=-1)              # [S]
            # 0-based slot of each token within its chosen expert, counting
            # tokens assigned by earlier k-rounds first (GShard priority)
            pos = jnp.cumsum(mask_k, axis=0) - mask_k + offset[None, :]
            pos_tok = jnp.sum(pos * mask_k, axis=-1)               # [S]
            keep = (pos_tok < capacity).astype(jnp.int32)
            disp_k = (
                (mask_k * keep[:, None])[:, :, None]
                * jax.nn.one_hot(pos_tok, capacity, dtype=jnp.int32)[:, None]
            ).astype(jnp.float32)                                  # [S, E, cap]
            dispatch = dispatch + disp_k
            combine = combine + disp_k * gate_k[:, None, None]
            gate_sum = gate_sum + gate_k * keep.astype(jnp.float32)
            offset = offset + jnp.sum(mask_k * keep[:, None], axis=0)
            remaining = remaining * (1.0 - mask_k.astype(gates.dtype))
        if K > 1:
            # normalize the kept gates to sum to 1 per token (GShard top-2)
            combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]

        # -- expert computation (batched over E; EP shards axis 0) ---------
        xe = jnp.einsum("sec,sm->ecm", dispatch.astype(dtype), xf)
        if self.expert_axis:
            xe = _constrain(xe, (self.expert_axis,))
        h = jnp.einsum("ecm,emh->ech", xe, w_fc.astype(dtype))
        if b_fc is not None:
            h = h + b_fc.astype(dtype)[:, None, :]
        h = nn.gelu(h)
        ye = jnp.einsum("ech,ehm->ecm", h, w_pr.astype(dtype))
        if b_pr is not None:
            ye = ye + b_pr.astype(dtype)[:, None, :]
        if self.expert_axis:
            ye = _constrain(ye, (self.expert_axis,))
        y = jnp.einsum("sec,ecm->sm", combine.astype(dtype), ye)
        y = y.reshape(B, T, C)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return y, self._aux(gates, logits, top1_mask.astype(jnp.float32), E)

    def _dense(self, xf, gates, logits, w_fc, b_fc, w_pr, b_pr, shape,
               train: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Drop-free all-experts dispatch, mathematically identical to
        ``_ragged`` (same ``top_k`` selection, same gate normalization, no
        capacity limit): every expert runs every token and the combine
        weights mask to the selected top-k. Costs E/topk× the ragged FLOPs
        but is vmap-safe (no ``ragged_dot``) and static-shaped, so the
        'auto' fallback under the vnode axis keeps the training objective
        independent of how K simulated nodes fold onto devices."""
        B, T, C = shape
        E, K = self.n_experts, self.topk
        dtype = xf.dtype
        topg, topi = jax.lax.top_k(gates, K)                       # [S, K]
        if K > 1:
            topg = topg / jnp.maximum(topg.sum(-1, keepdims=True), 1e-9)
        # [S, E] combine weights: normalized gate on the selected experts
        w = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32)
                    * topg[..., None], axis=1)
        h = jnp.einsum("sc,ech->esh", xf, w_fc.astype(dtype))
        if b_fc is not None:
            h = h + b_fc.astype(dtype)[:, None, :]
        h = nn.gelu(h)
        ye = jnp.einsum("esh,ehm->esm", h, w_pr.astype(dtype))
        if b_pr is not None:
            ye = ye + b_pr.astype(dtype)[:, None, :]
        y = jnp.einsum("se,esm->sm", w.astype(dtype), ye)
        y = y.reshape(B, T, C)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        top1_mask = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
        return y, self._aux(gates, logits, top1_mask, E)

    def _ragged(self, xf, gates, logits, w_fc, b_fc, w_pr, b_pr, shape,
                train: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Sort-based dispatch: tokens grouped by expert, one
        ``lax.ragged_dot`` per projection, segment-sum combine. No capacity
        limit — no tokens dropped."""
        B, T, C = shape
        E, K = self.n_experts, self.topk
        S = B * T
        dtype = xf.dtype
        topg, topi = jax.lax.top_k(gates, K)                       # [S, K]
        if K > 1:
            topg = topg / jnp.maximum(topg.sum(-1, keepdims=True), 1e-9)
        flat_e = topi.reshape(-1)                                  # [S·K]
        order = jnp.argsort(flat_e)            # stable: ties keep token order
        tok = order // K                       # source token per sorted row
        xs = jnp.take(xf, tok, axis=0)                             # [S·K, C]
        sorted_e = jnp.take(flat_e, order)
        h = _grouped_dot(xs, w_fc.astype(dtype), sorted_e, self.chunk_rows)
        if b_fc is not None:
            h = h + jnp.take(b_fc.astype(dtype), sorted_e, axis=0)
        h = nn.gelu(h)
        ye = _grouped_dot(h, w_pr.astype(dtype), sorted_e, self.chunk_rows)
        if b_pr is not None:
            ye = ye + jnp.take(b_pr.astype(dtype), sorted_e, axis=0)
        gate_rows = jnp.take(topg.reshape(-1), order).astype(dtype)
        y = jax.ops.segment_sum(ye * gate_rows[:, None], tok, num_segments=S)
        y = y.reshape(B, T, C)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        top1_mask = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
        return y, self._aux(gates, logits, top1_mask, E)

    def _aux(self, gates, logits, top1_mask, E) -> jnp.ndarray:
        """Weighted auxiliary losses (f32): Switch load-balance
        ``E · Σ_e f_e · p_e`` + router z-loss."""
        f = jnp.mean(top1_mask, axis=0)                            # [E]
        p = jnp.mean(gates, axis=0)                                # [E]
        balance = E * jnp.sum(f * p)
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return self.aux_weight * balance + self.z_weight * z


def _is_expert_stacked(path) -> bool:
    """True for param-tree leaves with a leading [n_experts] axis (the MoE
    expert weights/biases; the router is not expert-stacked). Single source
    of truth for ``moe_param_specs`` (what to shard over 'expert') and
    ``moe_active_params`` (what to scale by topk/E)."""
    keys = [str(getattr(k, "key", k)) for k in path]
    return any(k == "moe" for k in keys) and keys[-1] in (
        "fc_kernel", "proj_kernel", "fc_bias", "proj_bias")


def moe_active_params(params: PyTree, topk: int, n_experts: int) -> int:
    """Parameter count weighted by activation: expert-stacked leaves count
    at ``topk/n_experts`` of their size (each token runs only its top-k
    experts), everything else fully. The honest ``N`` for MoE MFU — using
    the raw total would credit FLOPs that never execute."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        frac = topk / n_experts if _is_expert_stacked(path) else 1.0
        total += frac * leaf.size
    return int(total)


def moe_param_specs(params: PyTree, base_specs: PyTree = None,
                    leading: int = 0) -> PyTree:
    """PartitionSpec tree sharding expert-stacked MoE params over
    ``'expert'`` (leaves under an ``moe`` module: ``fc_kernel`` [E, C, H],
    ``proj_kernel`` [E, H, C], ``*_bias`` [E, ·]; the router stays
    replicated). Non-MoE leaves take ``base_specs``'s spec (e.g. the
    Megatron TP rules) or replicated ``P()``. ``leading``: extra leading
    axes before the expert axis (2 in the pipeline layout — the stage
    tile + per-stage layer axes, owned by ``'pipe'``/stacking)."""
    from jax.sharding import PartitionSpec as P

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    if base_specs is None:
        base = [P()] * len(flat)
    else:
        base = jax.tree_util.tree_flatten(
            base_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    out = []
    for (path, leaf), b in zip(flat, base):
        if _is_expert_stacked(path):
            out.append(P(*([None] * leading), EXPERT_AXIS,
                         *([None] * (leaf.ndim - 1 - leading))))
        else:
            out.append(b)
    return jax.tree_util.tree_unflatten(treedef, out)


# -- a chip's share of a routed expert layer (serving) -------------


def _gated(x, w_gate, w_up, w_down, dot):
    """``(silu(x Wg) * (x Wu)) Wd`` with ``dot`` as the product; the gate
    in float32, the products' operands in ``x``'s dtype."""
    g = dot(x, w_gate).astype(jnp.float32)
    u = dot(x, w_up).astype(jnp.float32)
    return dot((jax.nn.silu(g) * u).astype(x.dtype), w_down)


_SCORE_FNS = {"sigmoid": jax.nn.sigmoid,
              "softmax": lambda x: jax.nn.softmax(x, axis=-1)}


class HeldExperts(nn.Module):
    """The expert layer of a deployment that shares each layer's experts
    over several chips, as ONE of those chips runs it (inference only).

    Routing runs over all ``n_experts``: scores in float32 (``score_fn``:
    ``sigmoid`` of each router output, or ``softmax`` over all of them),
    the ``topk`` largest, their weights renormalised to sum to one
    (``norm_topk``). With ``select_bias`` a float32 vector
    ``e_score_correction_bias`` [n_experts] is added to the scores for
    the CHOICE only (``noaux_tc``): the chosen experts' weights are their
    own scores, the bias never enters a weight. The chip holds the routed experts ``held = (lo,
    hi)`` and computes their part of the sum alone: token-picks are
    sorted by expert, the picks on held experts run through
    ``ops.grouped_matmul.grouped_dot`` (groups = the held experts, none
    dropped, no capacity), picks on absent experts add nothing. Nothing
    stands in for the other chips or for the exchange with them.

    ``n_shared`` shared experts of the same gated form run on every
    token and are averaged (every chip computes them alike).

    The sorted picks are taken ``chunk_rows`` at a time, and a block
    wholly past the last held pick is skipped: a 16k-token prefill has
    131k picks of which an eighth are held, and only those blocks gather
    rows and run products.

    ``__call__(h [S, C] float32) -> (routed, shared)`` float32. Sows
    into the ``counters`` collection (when mutable): ``picks`` [held]
    token-picks on each held expert from the rows marked ``live``,
    ``hit`` the held experts any row picked, ``tokens`` the live rows.
    """

    hidden: int
    width: int
    n_experts: int
    topk: int
    held: Tuple[int, int]
    n_shared: int
    norm_topk: bool = True
    chunk_rows: int = 8192
    param_dtype: Any = jnp.bfloat16
    score_fn: str = "sigmoid"
    select_bias: bool = False

    @nn.compact
    def __call__(self, h, live=None):
        from ..ops.grouped_matmul import grouped_dot
        C, F, E, K = self.hidden, self.width, self.n_experts, self.topk
        lo, hi = self.held
        nh = hi - lo
        if self.score_fn not in _SCORE_FNS:
            raise ValueError(f"score_fn must be one of "
                             f"{sorted(_SCORE_FNS)}, got {self.score_fn!r}")
        if not 0 <= lo < hi <= E:
            raise ValueError(f"held experts {self.held} not within "
                             f"[0, {E})")
        S = h.shape[0]
        dt = self.param_dtype
        init = _init_normal(0.02)
        router = self.param("router", init, (C, E), dt)
        wg = self.param("gate_proj", init, (nh, C, F), dt)
        wu = self.param("up_proj", init, (nh, C, F), dt)
        wd = self.param("down_proj", init, (nh, F, C), dt)
        hb = h.astype(dt)

        with jax.named_scope("moe.router"):
            scores = _SCORE_FNS[self.score_fn](jnp.dot(
                h, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            if self.select_bias:
                bias = self.param("e_score_correction_bias",
                                  nn.initializers.zeros, (E,), jnp.float32)
                _, top_i = jax.lax.top_k(scores + bias, K)
                top_s = jnp.take_along_axis(scores, top_i, axis=-1)
            else:
                top_s, top_i = jax.lax.top_k(scores, K)          # [S, K]
            w = (top_s / top_s.sum(-1, keepdims=True) if self.norm_topk
                 else top_s)
            local = top_i - lo
            # absent experts sort last, as one key past the held ones
            key = jnp.where((local >= 0) & (local < nh), local,
                            nh).reshape(-1)                      # [S*K]
            order = jnp.argsort(key)       # stable: ties keep token order
            onehot = key[:, None] == jnp.arange(nh)[None, :]
            counts = onehot.sum(0, dtype=jnp.int32)              # [held]
            offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(counts)])
            n_rows = offs[-1]              # picks on held experts
            w_flat = w.reshape(-1)
        if live is None:
            live = jnp.ones((S,), bool)
        self.sow("counters", "picks",
                 (onehot & jnp.repeat(live, K)[:, None]).sum(
                     0, dtype=jnp.int32),
                 reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((nh,),
                                                              jnp.int32))
        self.sow("counters", "hit", (counts > 0).sum(dtype=jnp.int32),
                 reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((),
                                                              jnp.int32))
        self.sow("counters", "tokens", live.sum(dtype=jnp.int32),
                 reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((),
                                                              jnp.int32))

        R = min(S * K, int(self.chunk_rows))
        n_chunks = -(-(S * K) // R)
        order = jnp.pad(order, (0, n_chunks * R - S * K))

        def block(c, out):
            rows = jax.lax.dynamic_slice(order, (c * R,), (R,))
            tok = rows // K
            first = c * R
            gs = (jnp.clip(offs[1:], first, first + R)
                  - jnp.clip(offs[:-1], first, first + R))
            y = _gated(hb[tok], wg, wu, wd,
                       lambda a, b: grouped_dot(a, b, gs))
            # rows past the held picks belong to no group: whatever the
            # grouped product left there is selected away, not scaled
            held_row = (first + jnp.arange(R)) < n_rows
            y = jnp.where(held_row[:, None],
                          y.astype(jnp.float32) * w_flat[rows][:, None],
                          0.0)
            return out.at[tok].add(y)

        with jax.named_scope("moe.routed"):
            routed = jnp.zeros((S, C), jnp.float32)
            if n_chunks == 1:
                routed = block(0, routed)
            else:
                routed = jax.lax.fori_loop(
                    0, n_chunks,
                    lambda c, out: jax.lax.cond(
                        c * R < n_rows, lambda o: block(c, o),
                        lambda o: o, out),
                    routed)

        with jax.named_scope("moe.shared"):
            shared = jnp.zeros((S, C), jnp.float32)
            if self.n_shared:
                sg = self.param("shared_gate_proj", init,
                                (self.n_shared, C, F), dt)
                su = self.param("shared_up_proj", init,
                                (self.n_shared, C, F), dt)
                sd = self.param("shared_down_proj", init,
                                (self.n_shared, F, C), dt)
                dot = lambda a, b: jnp.dot(          # noqa: E731
                    a, b, preferred_element_type=jnp.float32)
                for s in range(self.n_shared):
                    shared = shared + _gated(hb, sg[s], su[s], sd[s], dot)
                shared = shared / self.n_shared
        return routed, shared
