"""Node-mesh runtime: K simulated nodes as one SPMD program.

Replaces the reference's process-per-node orchestration
(``exogym/trainer.py:221-228`` mp.spawn, ``trainer.py:310-351`` process-group
rendezvous, ``train_node.py:618`` per-step barrier): here the K simulated
nodes are the leading axis of every state array, sharded over up to P physical
devices (mesh axis ``'node'``) with the remaining factor V = K/P vmapped
(axis name ``'vnode'``). One ``jax.jit`` of a ``shard_map`` program *is* the
cluster; collectives ride ICI on real multi-chip meshes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .axis import (EXPERT_AXIS, MODEL_AXIS, NODE_AXIS, PIPE_AXIS, SEQ_AXIS,
                   VNODE_AXIS, AxisCtx)

PyTree = Any

def _largest_divisor_at_most(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


@dataclasses.dataclass
class NodeRuntime:
    """Execution runtime for K simulated nodes on a set of real devices.

    Every "global" array managed by the runtime has leading axis K
    (one slice per simulated node), stored sharded: axis 0 is split into
    [P, V] with P over the ``'node'`` mesh axis.
    """

    num_nodes: int
    mesh: Mesh
    n_phys: int   # P — physical devices carrying the 'node' mesh axis
    n_virt: int   # V — simulated nodes folded per device (vmap)
    ctx: AxisCtx
    cp: int = 1   # context-parallel group size (devices per 'seq' axis)
    tp: int = 1   # tensor-parallel group size (devices per 'model' axis)
    ep: int = 1   # expert-parallel group size (devices per 'expert' axis)
    pp: int = 1   # pipeline-parallel group size (devices per 'pipe' axis)

    @classmethod
    def create(cls, num_nodes: int,
               devices: Sequence[jax.Device] | None = None, cp: int = 1,
               tp: int = 1, ep: int = 1, pp: int = 1):
        """``cp > 1`` adds a ``'seq'`` mesh axis: each simulated node's
        forward pass is context-parallel over ``cp`` devices (ring attention
        over ICI, SURVEY §5.7 resolution). ``tp > 1`` adds a ``'model'``
        mesh axis instead: each node's network is tensor-parallel over
        ``tp`` devices — the axis stays GSPMD-*auto* (the body is manual
        over ``'node'``/``'seq'`` only) so XLA partitions the matmuls from
        ``with_sharding_constraint`` annotations and inserts the Megatron
        collectives itself. ``ep > 1`` likewise adds a GSPMD-auto
        ``'expert'`` axis for MoE expert sharding (``models/moe.py``) —
        XLA inserts the dispatch/combine all-to-alls. ``pp > 1`` adds a
        manual ``'pipe'`` axis: each node's layer trunk is GPipe-split
        into ``pp`` stages (``parallel/pipeline.py``), stage params
        sharded over the axis. Mesh is [P, cp?, tp?, ep?, pp?];
        P·cp·tp·ep·pp ≤ devices."""
        if devices is None:
            devices = jax.devices()
        if len(devices) < cp * tp * ep * pp:
            raise ValueError(
                f"cp={cp}*tp={tp}*ep={ep}*pp={pp} does not fit "
                f"{len(devices)} devices")
        n_phys = _largest_divisor_at_most(
            num_nodes, len(devices) // (cp * tp * ep * pp))
        n_virt = num_nodes // n_phys
        axes = [NODE_AXIS]
        dims = [n_phys]
        if cp > 1:
            axes.append(SEQ_AXIS)
            dims.append(cp)
        if tp > 1:
            axes.append(MODEL_AXIS)
            dims.append(tp)
        if ep > 1:
            axes.append(EXPERT_AXIS)
            dims.append(ep)
        if pp > 1:
            axes.append(PIPE_AXIS)
            dims.append(pp)
        grid = np.asarray(devices[: int(np.prod(dims))]).reshape(dims)
        mesh = Mesh(grid, tuple(axes))
        ctx = AxisCtx(
            num_nodes=num_nodes,
            # drop the size-1 vmapped axis entirely when every node is
            # physical: one transform layer less, and primitives without
            # general batching rules (lax.ragged_dot — the MoE grouped
            # matmul) stay usable inside the node program
            axes=(NODE_AXIS, VNODE_AXIS) if n_virt > 1 else (NODE_AXIS,),
            sizes=(n_phys, n_virt) if n_virt > 1 else (n_phys,),
            seq_axes=(SEQ_AXIS,) if cp > 1 else (),
            seq_sizes=(cp,) if cp > 1 else (),
            tp_axes=(MODEL_AXIS,) if tp > 1 else (),
            tp_sizes=(tp,) if tp > 1 else (),
            ep_axes=(EXPERT_AXIS,) if ep > 1 else (),
            ep_sizes=(ep,) if ep > 1 else (),
            pp_axes=(PIPE_AXIS,) if pp > 1 else (),
            pp_sizes=(pp,) if pp > 1 else (),
        )
        return cls(num_nodes=num_nodes, mesh=mesh, n_phys=n_phys,
                   n_virt=n_virt, ctx=ctx, cp=cp, tp=tp, ep=ep, pp=pp)

    # -- sharding helpers -------------------------------------------------

    @property
    def node_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(NODE_AXIS))

    @property
    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, tree: PyTree) -> PyTree:
        """Put host arrays with leading axis K onto the mesh, node-sharded."""
        return jax.device_put(tree, self.node_sharding)

    def to_host(self, tree: PyTree) -> PyTree:
        return jax.device_get(tree)

    # -- program compilation ---------------------------------------------

    def compile(
        self,
        node_fn: Callable[..., Any],
        *,
        donate_state: bool = True,
        n_state_args: int = 1,
        donate_batch: bool = False,
        in_specs=None,
        out_specs=None,
    ):
        """Compile a per-node function into the K-node SPMD program.

        ``node_fn(*args)`` sees the *single-node* view of each argument
        (leading K axis stripped) and may use ``self.ctx`` collectives.
        Returns a jitted function over global arrays with leading axis K.

        ``in_specs`` / ``out_specs``: optional ``shard_map`` spec overrides
        (pytree prefixes per argument / output). Defaults to
        ``P('node')`` everywhere — override for state whose leaves are
        additionally sharded over another manual axis (the pipeline's
        stage-stacked params, ``P('node', 'pipe')``).

        ``donate_batch``: donate the non-state arguments (the batch). Safe
        only when every batch array is used for exactly one call — the
        Trainer's streaming path qualifies; a benchmark reusing one
        device-resident batch across calls must NOT set this."""
        ctx = self.ctx

        if self.n_virt > 1:
            def block_fn(*args):
                return jax.vmap(node_fn, axis_name=VNODE_AXIS)(*args)
        else:
            # no vmap layer: strip/restore the per-device [V=1] block axis
            # (asarray: metric leaves may be python scalars, which vmap
            # would have broadcast)
            def block_fn(*args):
                sq = jax.tree.map(lambda x: x[0], args)
                out = node_fn(*sq)
                return jax.tree.map(lambda x: jnp.asarray(x)[None], out)

        # manual over node/seq/pipe; 'model'/'expert' axes stay GSPMD-auto
        manual = frozenset(self.mesh.axis_names) - {MODEL_AXIS, EXPERT_AXIS}

        def program(*args):
            n_in = len(args)
            ins = in_specs if in_specs is not None else (P(NODE_AXIS),) * n_in
            return jax.shard_map(
                block_fn,
                mesh=self.mesh,
                in_specs=ins,
                out_specs=(out_specs if out_specs is not None
                           else P(NODE_AXIS)),
                axis_names=manual,
                check_vma=False,
            )(*args)

        donate = tuple(range(n_state_args)) if donate_state else ()
        if donate_batch:
            # batch arrays are single-use in the streaming fit loop: letting
            # XLA alias their buffers trims peak HBM while the prefetcher
            # keeps the next batch already resident
            donate = donate + tuple(range(n_state_args, n_state_args + 1))
        return jax.jit(program, donate_argnums=donate)

    def init_state(self, init_fn: Callable[[jnp.ndarray], PyTree],
                   state_specs=None) -> PyTree:
        """Build per-node initial state: ``init_fn(node_index) -> state``.

        Parameters must be *identical* across nodes when ``init_fn`` ignores
        asymmetry — this replaces the reference's initial parameter broadcast
        from rank 0 (``exogym/train_node.py:101-104``): replicas constructed
        from the same seed are identical by determinism, no collective needed.

        ``state_specs``: output spec override (see ``compile``) for state
        sharded over more than the node axis."""
        ctx = self.ctx

        def node_init(_):
            return init_fn(ctx.node_index())

        program = self.compile(node_init, donate_state=False,
                               out_specs=state_specs)
        dummy = self.shard_batch(np.zeros((self.num_nodes,), np.int32))
        return program(dummy)

    def unshard(self, tree: PyTree) -> PyTree:
        """Host copy of a K-leading global pytree."""
        return jax.device_get(tree)

    def average_over_nodes(self, tree: PyTree) -> PyTree:
        """Uniform average over the node axis (host-side), matching the
        reference's final model averaging (``exogym/trainer.py:95-119``):
        integer leaves are averaged in float and cast back."""
        def avg(x):
            x = np.asarray(x)
            if np.issubdtype(x.dtype, np.integer) or x.dtype == np.bool_:
                return x.astype(np.float64).mean(axis=0).astype(x.dtype)
            return x.mean(axis=0)
        return jax.tree.map(avg, jax.device_get(tree))
