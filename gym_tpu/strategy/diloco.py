"""DiLoCo: two-level optimization (inner per-step, outer Nesterov every H).

Reference (``exogym/strategy/diloco.py``): inner AdamW every step; every H
steps all nodes average params, rank 0 keeps a CPU ``master_model``, sets the
outer pseudo-gradient ``master − averaged``, steps an outer
SGD(lr=0.7, nesterov, momentum=0.9) (``:26-28``, ``:62-71``), then broadcasts
the result from rank 0 (``:73-74``).

TPU-native restatement (SURVEY §7 "hard parts"): there is no cheap
"only rank 0 computes" in SPMD — instead the outer optimizer state (master
params + momentum) is *replicated* and the outer step is computed identically
on every node. The input is the psum-average (bitwise deterministic on TPU),
so replicas remain bit-identical and the reference's rank-0 broadcast
disappears — saving one full model broadcast per outer round
(comm: 2(K−1)/K·|θ| per H steps vs the reference's allreduce+broadcast).

``DiLoCoCommunicator`` is the communication-module form — the missing piece
that makes the SPARTA×DiLoCo combo real (the reference imports a nonexistent
``DiLoCoCommunicator``, ``sparta_diloco.py:6``).
"""

from __future__ import annotations

from typing import List, Optional, Union

import jax
import jax.numpy as jnp
import optax
from jax.flatten_util import ravel_pytree

from .base import (CollectiveEvent, PyTree, StrategyLifecycleError,
                   tree_bytes, tree_num_params)
from .communicate_optimize import (CommunicateOptimizeStrategy,
                                   CommunicationModule)
from .compress import Codec, CompressedLink
from .optim import OptimSpec, ensure_optim_spec
from .sharding import pipe_unwrap, pipe_wrap, take_shard, unshard


class DiLoCoCommunicator(CommunicationModule):
    """Outer-loop model averaging + replicated Nesterov outer step.

    ``shard_outer=True`` stores each node's 1/K slice of the (otherwise
    bit-identical, replicated) master params + outer momentum — ZeRO
    applied to the OUTER optimizer. Valid because the outer step's input
    (the psum average) is identical on every node, so slicing commutes
    with the elementwise Nesterov update. Cuts the outer state from
    2·|θ| per node to 2·|θ|/K (at GPT-2 base × 4 nodes: 4 GB → 1 GB
    total), at the cost of an extra all_gather per outer round
    (3(K−1)/K·|θ| per H steps instead of 2(K−1)/K·|θ|)."""

    def __init__(
        self,
        H: int = 100,
        outer_optim_spec: Optional[Union[str, OptimSpec]] = None,
        shard_outer: bool = False,
        participation: float = 1.0,
        fault_seed: int = 5678,
        codec: Union[str, Codec, None] = None,
        codec_seed: int = 1206,
        error_feedback: Optional[bool] = None,
        **codec_kwargs,
    ):
        if not 0.0 < participation <= 1.0:
            raise ValueError(
                f"participation must be in (0, 1], got {participation}")
        if shard_outer and participation < 1.0:
            # a truly failed node could not serve its exclusive master
            # shard for the all_gather reassembly, so the fault model is
            # physically inconsistent with a node-sharded outer state
            raise ValueError(
                "shard_outer=True cannot be combined with participation<1: "
                "dead nodes would still have to serve their master shard. "
                "Use the replicated outer state for fault simulation."
            )
        self.H = int(H)
        self.shard_outer = bool(shard_outer)
        self.participation = float(participation)
        self.fault_seed = fault_seed
        # codec ORTHOGONAL to the outer loop (ISSUE 12): the outer
        # DELTA (params − master) ships compressed through a
        # CompressedLink with a per-node error-feedback residual carried
        # in the module state. Restricted to the replicated outer state
        # with full participation: a node-sharded master would also have
        # to shard the residual reassembly, and a dead node's residual
        # would silently freeze its error feedback — neither composition
        # is honest enough to ship unverified.
        self.link = CompressedLink(codec, seed=codec_seed,
                                   error_feedback=error_feedback,
                                   **codec_kwargs)
        if self.link.compressed and self.shard_outer:
            raise ValueError(
                "codec cannot be combined with shard_outer=True: the "
                "compressed outer delta needs the replicated outer state")
        if self.link.compressed and self.participation < 1.0:
            raise ValueError(
                "codec cannot be combined with participation<1: a dead "
                "node's error-feedback residual would silently freeze")
        self.outer_optim_spec = ensure_optim_spec(
            outer_optim_spec,
            OptimSpec("sgd", lr=0.7, nesterov=True, momentum=0.9),
        )
        self.outer_tx = self.outer_optim_spec.build()

    def init(self, params: PyTree) -> PyTree:
        if not self.shard_outer:
            return {
                "master": jax.tree.map(jnp.array, params),
                "outer_opt": self.outer_tx.init(params),
                **self.link.init(tree_num_params(params)),
            }
        if self._ctx is None:
            raise StrategyLifecycleError(
                "shard_outer=True needs the mesh: pass ctx to make_init_fn "
                "(the Trainer does) or call strategy.bind_ctx(runtime.ctx)")
        # init runs inside the node program (NodeRuntime.init_state), so
        # the node index is live and each node keeps only its own slice.
        # Dtype follows the params (sharding.take_shard), so the sharded
        # Nesterov arithmetic is comparable with the replicated path for
        # any parameter dtype. Under pipeline parallelism the slice covers
        # THIS STAGE's param view — pipe-varying (sharding.pipe_wrap).
        my, _, _ = take_shard(params, self._ctx.num_nodes,
                              self._ctx.node_index())
        return pipe_wrap({"master": my, "outer_opt": self.outer_tx.init(my)},
                         self._ctx)

    def communicate(self, params, mstate, step, ctx):
        k = ctx.num_nodes
        psize = float(tree_bytes(params))
        if self.shard_outer:
            mstate = pipe_unwrap(mstate, ctx)

        def _avg_and_alive(params):
            """Round average + this node's participation flag. With
            participation < 1 (simulated failures, ``strategy/faults.py``)
            only alive nodes' params enter the outer pseudo-gradient; the
            outer master/momentum update stays replicated-identical on
            EVERY node (the alive mask is shared-PRNG), so dead nodes'
            outer state cannot drift — they just skip the param sync and
            rejoin with stale local params."""
            from .faults import masked_mean, participation_round
            _, me_alive, group = participation_round(
                self.fault_seed, step, self.participation, ctx)
            if self.participation >= 1.0:
                return ctx.pmean(params), me_alive, group
            return (masked_mean(params, me_alive.astype(jnp.float32), ctx),
                    me_alive, group)

        def outer_replicated(params, mstate):
            avg, me_alive, group = _avg_and_alive(params)
            master = mstate["master"]
            # outer pseudo-gradient: master − averaged (reference :43-45)
            pseudo = jax.tree.map(jnp.subtract, master, avg)
            updates, outer_opt = self.outer_tx.update(
                pseudo, mstate["outer_opt"], master
            )
            master = optax.apply_updates(master, updates)
            # all nodes sync to the new master (reference :47-49, :73-74 —
            # but without the broadcast: the computation is replicated);
            # a dead node misses the sync and keeps its local params
            from .faults import ring_bytes, sync_alive
            new_params = sync_alive(master, params, me_alive)
            comm = me_alive * ring_bytes(group, psize)
            return (new_params,
                    {"master": master, "outer_opt": outer_opt}, comm)

        def outer_sharded(params, mstate):
            avg, me_alive, group = _avg_and_alive(params)
            avg_my, unravel, n = take_shard(avg, k, ctx.node_index())
            pseudo = mstate["master"] - avg_my
            updates, outer_opt = self.outer_tx.update(
                pseudo, mstate["outer_opt"], mstate["master"]
            )
            master = optax.apply_updates(mstate["master"], updates)
            # every node's shard is valid regardless of aliveness (the
            # sharded outer state is slices of a replicated-identical
            # master), so the all_gather reassembly is fault-agnostic;
            # only the final param sync respects the alive mask
            assembled = unshard(ctx, master, n, unravel)
            new_params = jax.tree.map(
                lambda m, p: jnp.where(me_alive, m, p), assembled, params
            )
            comm = (me_alive * 3.0 * (group - 1)
                    / jnp.maximum(group, 1) * psize)
            return (new_params,
                    {"master": master, "outer_opt": outer_opt}, comm)

        def outer_compressed(params, mstate):
            """The codec path: each node compresses its OUTER DELTA
            (params − master) through the link — with error feedback,
            the dropped/rounded mass re-enters the next round's delta —
            and the round average is reassembled as
            ``master + mean(deltâ)``. The master is replicated and the
            pmean is a collective, so the reconstruction (and hence the
            outer Nesterov step) stays bit-identical on every node; only
            each node's rounding noise is node-specific (per-node
            ``link_key``, folded from the node index)."""
            flat_p, unravel = ravel_pytree(params)
            flat_m, _ = ravel_pytree(mstate["master"])
            delta = flat_p.astype(jnp.float32) - flat_m.astype(jnp.float32)
            key = self.link.key(step, hop=0, node=ctx.node_index())
            lstate = ({"ef_residual": mstate["ef_residual"]}
                      if self.link.error_feedback else {})
            delta_hat, lstate = self.link.send(delta, lstate, key)
            avg_flat = flat_m.astype(jnp.float32) + ctx.pmean(delta_hat)
            avg = jax.tree.map(lambda a, p: a.astype(p.dtype),
                               unravel(avg_flat), params)
            master = mstate["master"]
            pseudo = jax.tree.map(jnp.subtract, master, avg)
            updates, outer_opt = self.outer_tx.update(
                pseudo, mstate["outer_opt"], master)
            master = optax.apply_updates(master, updates)
            comm = 2.0 * (k - 1) / k * self.link.wire_bytes(delta.size)
            return (master,
                    {"master": master, "outer_opt": outer_opt, **lstate},
                    jnp.asarray(comm, jnp.float32))

        def skip(params, mstate):
            return params, mstate, jnp.zeros(())

        if self.link.compressed:
            outer = outer_compressed
        else:
            outer = outer_sharded if self.shard_outer else outer_replicated
        do = jnp.logical_and(step % self.H == 0, step > 0)
        params, mstate, comm = jax.lax.cond(
            do, jax.named_scope("outer")(outer), skip, params, mstate)
        if self.shard_outer:
            mstate = pipe_wrap(mstate, ctx)
        return params, mstate, comm

    def comm_events(self, step: int, params: PyTree,
                    num_nodes: int) -> List[CollectiveEvent]:
        if num_nodes <= 1 or not (step % self.H == 0 and step > 0):
            return []
        psize = float(tree_bytes(params))
        if self.link.compressed:
            # compressed round average of the outer delta: declared at
            # the codec's honest wire bytes; the emulation pmeans the
            # reconstructed dense f32 delta, bounded by emulated_bytes
            n = tree_num_params(params)
            return [CollectiveEvent(
                "all_reduce", self.link.wire_bytes(n), num_nodes,
                label="outer_delta_compressed",
                emulated_bytes=4.0 * n)]
        if self.shard_outer:
            # round average + the extra all_gather that reassembles the
            # sharded master: 3(K−1)/K·|θ| total (participation<1 is
            # rejected with shard_outer at construction)
            return [
                CollectiveEvent("all_reduce", psize, num_nodes,
                                label="outer_avg"),
                CollectiveEvent("all_gather", psize, num_nodes,
                                label="outer_master"),
            ]
        from .faults import host_participation, mean_ring_tx
        group, frac = host_participation(self.fault_seed, step, num_nodes,
                                         self.participation)
        tx = None if frac >= 1.0 else mean_ring_tx(group, frac, psize)
        return [CollectiveEvent("all_reduce", psize, group,
                                label="outer_avg", tx_bytes=tx)]

    def config(self):
        cfg = {"module": "DiLoCoCommunicator", "H": self.H,
               "outer_optimizer": self.outer_optim_spec.name,
               "outer_lr": self.outer_optim_spec.lr}
        if self.shard_outer:
            cfg["shard_outer"] = True
        if self.participation < 1.0:
            cfg["participation"] = self.participation
        if self.link.compressed:
            cfg.update(self.link.config())
        return cfg


class DiLoCoStrategy(CommunicateOptimizeStrategy):
    """Inner optimizer (default AdamW) + DiLoCo outer loop
    (reference ``diloco.py:14-89``; ``optim_spec`` names the inner optimizer
    for consistency with the reference signature)."""

    def __init__(
        self,
        optim_spec: Optional[Union[str, OptimSpec]] = None,
        outer_optim_spec: Optional[Union[str, OptimSpec]] = None,
        H: int = 100,
        max_norm: Optional[float] = None,
        lr_scheduler=None,
        lr_scheduler_kwargs=None,
        shard_outer: bool = False,
        participation: float = 1.0,
        codec: Union[str, Codec, None] = None,
        error_feedback: Optional[bool] = None,
        **codec_kwargs,
    ):
        self.H = int(H)
        super().__init__(
            communication_modules=[
                DiLoCoCommunicator(H=H, outer_optim_spec=outer_optim_spec,
                                   shard_outer=shard_outer,
                                   participation=participation,
                                   codec=codec,
                                   error_feedback=error_feedback,
                                   **codec_kwargs)
            ],
            inner_optim=ensure_optim_spec(optim_spec, OptimSpec("adamw")),
            max_norm=max_norm,
            lr_scheduler=lr_scheduler,
            lr_scheduler_kwargs=lr_scheduler_kwargs,
        )

    def config(self):
        cfg = super().config()
        cfg["H"] = self.H
        return cfg
