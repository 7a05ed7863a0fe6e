"""Node-axis collective context.

The reference framework (EXO Gym) simulates K training nodes as K OS processes
joined by a ``torch.distributed`` process group, and exposes ``broadcast`` /
``all_reduce`` / ``all_gather`` free functions (reference:
``exogym/strategy/communicate.py:63-75``). Here the K nodes are a *mesh axis*
of one SPMD program: up to ``P`` physical devices carry the ``'node'`` mesh
axis (via ``jax.shard_map``) and the remaining factor ``V = K / P`` is a
vmapped ``'vnode'`` axis, so collectives over the pair ``('node', 'vnode')``
span all K simulated nodes. XLA lowers these to ICI collectives on real
multi-chip meshes; there is no rendezvous, no process group, and no barrier —
lockstep is a property of the compiled program.

``AxisCtx`` is the object strategies receive instead of ``(rank, num_nodes)``:
it knows the axis names and node count, and provides the collective toolkit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


NODE_AXIS = "node"
VNODE_AXIS = "vnode"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Collective context for one simulated node inside the SPMD program.

    Replaces the reference's ``(rank, num_nodes)`` pair plus the
    ``communicate.py`` free functions. All methods must be called from inside
    the node program (under ``shard_map`` + ``vmap``).
    """

    num_nodes: int
    # Axis names spanning the simulated-node dimension, outermost first.
    # ('node', 'vnode') in the standard runtime; a subset in tests.
    axes: tuple = (NODE_AXIS, VNODE_AXIS)
    # Size of each axis, same order as `axes`. prod(sizes) == num_nodes.
    sizes: tuple = (1, 1)
    # Context-parallel (sequence) mesh axes, orthogonal to the node axes.
    # Long sequences are sharded over these inside each node's forward pass
    # (ring attention); gradients must be psum'd over them (train_node.py).
    seq_axes: tuple = ()
    seq_sizes: tuple = ()
    # Tensor-parallel mesh axes (GSPMD-auto inside the node program): each
    # node's network is Megatron-sharded over these. Strategies never see
    # them — the partitioner inserts the collectives.
    tp_axes: tuple = ()
    tp_sizes: tuple = ()
    # Expert-parallel mesh axes (GSPMD-auto, like tp): MoE expert-stacked
    # params are sharded over these and XLA inserts the dispatch/combine
    # all-to-alls (models/moe.py).
    ep_axes: tuple = ()
    ep_sizes: tuple = ()
    # Pipeline-parallel mesh axes (manual, like seq): each node's layer
    # trunk is split into stages over these; microbatch activations stream
    # stage→stage via ppermute (parallel/pipeline.py). Stage-local params
    # are sharded over the axis; replicated ("outer") param gradients must
    # be pp_psum'd (train_node.make_pipeline_train_step).
    pp_axes: tuple = ()
    pp_sizes: tuple = ()

    # -- collectives ------------------------------------------------------

    def psum(self, tree: PyTree) -> PyTree:
        """Sum across all simulated nodes (reference all_reduce SUM)."""
        if self.num_nodes == 1:
            return tree
        return jax.tree.map(lambda x: lax.psum(x, self.axes), tree)

    def pmean(self, tree: PyTree) -> PyTree:
        """Mean across all simulated nodes (all_reduce SUM then /K,
        the idiom at e.g. reference ``exogym/strategy/diloco.py:34-37``)."""
        if self.num_nodes == 1:
            return tree
        return jax.tree.map(lambda x: lax.pmean(x, self.axes), tree)

    def all_gather(self, tree: PyTree) -> PyTree:
        """Gather from all nodes: each leaf gains a leading axis of size K,
        ordered by linear node index (reference ``all_gather`` tensor_list)."""
        if self.num_nodes == 1:
            return jax.tree.map(lambda x: x[None], tree)

        def gather(x):
            # Gather innermost-first so the final leading axis is ordered by
            # the linear index produced by `node_index` (outer*inner + inner).
            for ax in reversed(self.axes):
                x = lax.all_gather(x, ax, tiled=False)
            # x now has one leading axis per name; flatten them into one.
            k = self.num_nodes
            return x.reshape((k,) + x.shape[len(self.axes):])

        return jax.tree.map(gather, tree)

    def reduce_scatter(self, x: jnp.ndarray) -> jnp.ndarray:
        """Summed 1/K chunk of a flat ``[K·shard]`` vector — the canonical
        ZeRO-1 collective (reduce-scatter, (K−1)/K·|x| bytes vs psum's
        2(K−1)/K). Only valid when the simulated-node dimension is a single
        mesh axis (``lax.psum_scatter`` has no batching rule for the
        vmapped vnode factor). Chunk ``i`` lands on axis index ``i``,
        matching ``take_shard``'s linear-index slicing."""
        if len(self.axes) != 1:
            raise ValueError(
                "reduce_scatter needs the pure mesh node axis (n_virt == 1)")
        return lax.psum_scatter(x, self.axes[0], scatter_dimension=0,
                                tiled=True)

    def node_index(self) -> jnp.ndarray:
        """Linear index of this simulated node in [0, K) (reference rank)."""
        idx = jnp.zeros((), jnp.int32)
        for name, size in zip(self.axes, self.sizes):
            idx = idx * size + lax.axis_index(name)
        return idx

    def fold_counter(self, step: jnp.ndarray) -> jnp.ndarray:
        """The step counter as ONE value for the nodes folded on this
        device. Invariant: all nodes of a device block hold the same
        ``step`` (``init_state`` zeroes it, every step program adds 1 to
        all of them, a checkpoint stores what such a program wrote, and
        ``elastic.reshard_state`` refuses rows that differ), so the
        reduction over the vmapped axis loses nothing — and its result is
        unbatched under the fold's ``vmap``. A ``lax.cond`` gated on it
        (every strategy's H-gate) therefore stays an XLA ``conditional``;
        on the batched counter it is a ``select`` that computes both
        branches every step and rewrites every leaf they return."""
        if VNODE_AXIS not in self.axes:
            return step
        return lax.pmax(step, VNODE_AXIS)

    def broadcast_from(self, tree: PyTree, src: int = 0) -> PyTree:
        """Every node receives node `src`'s value (reference ``broadcast``).

        In SPMD this is an all_gather + static index; strategies mostly don't
        need it because rank-asymmetric computation is replaced by replicated
        deterministic computation (see DiLoCo), but it is kept for parity and
        for tests.
        """
        if self.num_nodes == 1:
            return tree
        gathered = self.all_gather(tree)
        return jax.tree.map(lambda g: g[src], gathered)

    def ppermute(self, tree: PyTree, perm: Sequence[tuple]) -> PyTree:
        """Ring-style permute across the *outer* (physical) node axis only."""
        return jax.tree.map(lambda x: lax.ppermute(x, self.axes[0], perm), tree)

    # -- context-parallel (sequence) axis ---------------------------------

    @property
    def cp(self) -> int:
        """Context-parallel group size (1 = no sequence sharding)."""
        n = 1
        for s in self.seq_sizes:
            n *= s
        return n

    def seq_psum(self, tree: PyTree) -> PyTree:
        """Sum over the context-parallel axes (used to combine the per-chunk
        gradient contributions of a sequence-sharded forward pass)."""
        if not self.seq_axes:
            return tree
        return jax.tree.map(lambda x: lax.psum(x, self.seq_axes), tree)

    def seq_index(self) -> jnp.ndarray:
        """Linear index of this device within its context-parallel group."""
        idx = jnp.zeros((), jnp.int32)
        for name, size in zip(self.seq_axes, self.seq_sizes):
            idx = idx * size + lax.axis_index(name)
        return idx

    # -- pipeline-parallel axis -------------------------------------------

    @property
    def pp(self) -> int:
        """Pipeline group size (1 = no stage sharding)."""
        n = 1
        for s in self.pp_sizes:
            n *= s
        return n

    def pp_psum(self, tree: PyTree) -> PyTree:
        """Sum over the pipeline axes — combines the per-stage gradient
        contributions to *replicated* params (embeddings touched by stage
        0, the tied lm head by the last stage)."""
        if not self.pp_axes:
            return tree
        return jax.tree.map(lambda x: lax.psum(x, self.pp_axes), tree)


def single_node_ctx() -> AxisCtx:
    """Ctx for K=1 (all collectives degenerate to identity)."""
    return AxisCtx(num_nodes=1, axes=(), sizes=())
