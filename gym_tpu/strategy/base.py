"""Strategy base: "optimizer ∪ communication schedule" as a pure function.

Reference (``exogym/strategy/strategy.py:18-63``): a Strategy owns the
optimizer + scheduler and its ``step()`` performs *all* post-gradient work —
clipping, communication, optimizer step. Here a Strategy is a pair of pure
functions over pytrees:

    state   = strategy.init(params)
    params', state', metrics = strategy.step(grads, params, state, step, ctx)

run inside the jitted SPMD node program; ``ctx`` (AxisCtx) supplies
collectives over the simulated-node axis. ``finalize(max_steps)`` must be
called before ``init`` — it builds the optax transforms and lr schedule (the
reference equivalently injects ``strategy.max_steps`` before training at
``train_node.py:583``).

Communication volume is a first-class metric: every ``step`` returns
``comm_bytes`` — the analytic per-node payload the algorithm would transmit
on a real network (the reference only tracked this for DeMo and never logged
it; SURVEY §5.5).
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.axis import AxisCtx
from .schedule import build_lr_scale

PyTree = Any


class StrategyLifecycleError(RuntimeError):
    """A strategy was used out of order: ``init`` before
    ``finalize(max_steps)``, or a mesh-layout-dependent strategy (ZeRO
    sharding, DiLoCo ``shard_outer``) initialized without
    ``bind_ctx(runtime.ctx)``. Typed so callers and tests can branch on
    the class instead of matching an ``AssertionError`` string."""


def require_finalized(strategy: "Strategy") -> None:
    """Raise ``StrategyLifecycleError`` unless ``finalize`` ran — every
    ``Strategy.init`` calls this first."""
    if not getattr(strategy, "_finalized", False):
        raise StrategyLifecycleError(
            f"{type(strategy).__name__}: call strategy.finalize(max_steps) "
            f"before init")


def tree_bytes(tree: PyTree) -> int:
    """Total payload size of a pytree in bytes (static python int)."""
    return int(
        sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    )


def comm_metric(x) -> jnp.ndarray:
    """Canonical form of the per-step ``comm_bytes`` metric: a float32
    scalar. Every strategy funnels its accounting through this one helper
    so the host logging path sees one dtype/shape whatever the strategy
    (the strategies used to return a mix of Python floats and jnp arrays;
    ``tests/test_strategies.py`` asserts the invariant)."""
    return jnp.asarray(x, jnp.float32).reshape(())


# Collective op kinds a strategy step can schedule; the payload-size
# convention per op (CollectiveEvent.bytes) is:
#   all_reduce      — size of the vector being reduced
#   reduce_scatter  — size of the full input vector (output is bytes/group)
#   all_gather      — size of the assembled output (inputs bytes/group each)
#   broadcast / p2p — size of the message
COLLECTIVE_OPS = ("all_reduce", "all_gather", "reduce_scatter", "broadcast",
                  "p2p")


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective a strategy step performs, described analytically.

    This is the structured upgrade of the scalar ``comm_bytes`` metric
    (ISSUE 3): strategies describe WHAT they communicate (op kind, payload,
    participant group) from the host via ``Strategy.comm_events(step, ...)``
    so the network simulator (``gym_tpu.sim``) can price the same schedule
    on any topology. ``per_node_tx()`` reproduces each strategy's in-step
    ``comm_bytes`` accounting exactly, which is what makes trace totals
    reconcile with the logged ``cum_comm_bytes`` column.
    """

    op: str                 # one of COLLECTIVE_OPS
    bytes: float            # logical payload size (convention above)
    group: int              # number of participating nodes
    label: str = ""         # e.g. "grads", "outer_sync"
    # Per-node transmitted bytes as the strategy's own comm_bytes metric
    # counts them. None = the canonical ring formula for `op`; strategies
    # whose accounting deliberately differs (DeMo counts its payload once,
    # FedAvg islands count one model transmit) pin it explicitly.
    tx_bytes: Optional[float] = None
    # For `p2p` gossip rounds: the (sender, receiver) node pairs of this
    # round's exchange, all concurrent. The cost model then prices the
    # round as the SLOWEST pair's single hop over the actual link each
    # pair crosses (intra- vs inter-host on hierarchical topologies)
    # instead of a serial sum — a gossip round where every node talks to
    # one partner is one network round-trip, not K of them. None for the
    # non-p2p ops (and for p2p messages priced on the bottleneck link).
    pairs: Optional[tuple] = None
    # For events whose declared (wire) bytes deliberately differ from
    # what the SPMD emulation moves (compressed payloads, masked
    # exchanges, p2p-via-gather): the DENSE bytes the emulation is
    # expected to move for this event, in the extracted-site convention
    # (all_reduce/reduce_scatter = full input vector, all_gather =
    # assembled output). The static verifier uses it as an UPPER BOUND
    # on the jaxpr's moved bytes — a strategy that quietly moves more
    # than its declared emulation (e.g. an undeclared residual gather
    # folded into a declared hop) fails reconciliation even though the
    # wire accounting still matches. None = no bound declared (the
    # pre-existing strategies' realized-vs-moved splits are grandfathered
    # by the metric check alone).
    emulated_bytes: Optional[float] = None

    def __post_init__(self):
        if self.op not in COLLECTIVE_OPS:
            raise ValueError(f"unknown collective op {self.op!r}; "
                             f"expected one of {COLLECTIVE_OPS}")

    def per_node_tx(self) -> float:
        """Bytes this event puts on the wire per participating node."""
        if self.tx_bytes is not None:
            return float(self.tx_bytes)
        g = max(int(self.group), 1)
        if self.op == "all_reduce":
            return 2.0 * (g - 1) / g * self.bytes
        if self.op in ("all_gather", "reduce_scatter"):
            return (g - 1) / g * self.bytes
        return float(self.bytes)  # broadcast / p2p


def tree_num_params(tree: PyTree) -> int:
    return int(sum(x.size for x in jax.tree.leaves(tree)))


def clip_by_global_norm(tree: PyTree, max_norm: float) -> PyTree:
    """Global-norm gradient clipping (torch
    ``nn_utils.clip_grad_norm_`` semantics, used at reference
    ``strategy.py:135-138``)."""
    sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda x: x * scale, tree)


class Strategy(abc.ABC):
    """Base strategy. Subclasses implement ``init`` and ``step``.

    Constructor mirrors the reference's kwargs surface
    (``lr_scheduler``, ``lr_scheduler_kwargs``, ``max_norm``) but unknown
    kwargs are rejected by subclasses' explicit signatures rather than
    silently setattr'd (kills the bug class of SURVEY §5.6).
    """

    def __init__(
        self,
        lr_scheduler: Optional[str] = None,
        lr_scheduler_kwargs: Optional[dict] = None,
        max_norm: Optional[float] = None,
    ):
        self.lr_scheduler = lr_scheduler
        self.lr_scheduler_kwargs = lr_scheduler_kwargs
        self.max_norm = max_norm
        self.max_steps = 1
        self._lr_scale = None
        self._lr_scale_host = None
        self._finalized = False
        self._ctx = None

    def bind_ctx(self, ctx) -> "Strategy":
        """Attach the mesh context before ``init`` for strategies whose
        state layout depends on the node count (e.g. ZeRO sharding).
        ``make_init_fn(..., ctx=...)`` calls this; most strategies ignore
        it."""
        self._ctx = ctx
        return self

    # -- lifecycle --------------------------------------------------------

    def finalize(self, max_steps: int) -> "Strategy":
        """Bind ``max_steps`` (needed by the lr schedule) and build
        optimizers. Idempotent."""
        import numpy as np
        self.max_steps = int(max_steps)
        self._lr_scale = build_lr_scale(
            self.lr_scheduler, self.lr_scheduler_kwargs, self.max_steps
        )
        # numpy twin of the schedule for the logging path: evaluating the
        # jnp schedule per logged step from the host loop is a blocking
        # device round-trip per step
        self._lr_scale_host = build_lr_scale(
            self.lr_scheduler, self.lr_scheduler_kwargs, self.max_steps,
            xp=np,
        )
        self._build()
        self._finalized = True
        return self

    def _build(self) -> None:
        """Subclass hook: construct optax transforms using self._lr_scale."""

    # -- pure API ---------------------------------------------------------

    @abc.abstractmethod
    def init(self, params: PyTree) -> PyTree:
        """Per-node strategy state for `params` (single-node view)."""

    @abc.abstractmethod
    def step(
        self,
        grads: PyTree,
        params: PyTree,
        state: PyTree,
        step: jnp.ndarray,
        ctx: AxisCtx,
    ) -> Tuple[PyTree, PyTree, Dict[str, jnp.ndarray]]:
        """One post-gradient step: communicate + optimize.

        Returns (new_params, new_state, metrics). ``metrics`` must include
        ``comm_bytes`` (per-node bytes transmitted this step).
        """

    # -- collective trace (host-side, pure) -------------------------------

    def comm_events(self, step: int, params: PyTree,
                    num_nodes: int) -> List[CollectiveEvent]:
        """The collectives this strategy's ``step`` schedules at host step
        ``step``, described analytically (op kind, payload bytes,
        participant group). Pure host Python — called outside jit with a
        concrete ``step``; ``params`` is a per-node pytree of arrays or
        ``ShapeDtypeStruct``s (only shapes/dtypes are read). Cadence is
        encoded by returning ``[]`` on steps with no communication.

        Contract: summing ``per_node_tx()`` over the returned events must
        equal the mean per-node ``comm_bytes`` metric the jitted step
        reports at the same step (float32 rounding aside) — the simulator
        relies on this to reconcile traces with the logged CSV.
        """
        return []

    def comm_cycle_steps(self) -> List[int]:
        """Host steps forming one full communication cycle — the static
        trace verifier (``gym_tpu.analysis.trace_check``) reconciles the
        jaxpr-extracted collective inventory against ``comm_events`` at
        exactly these steps. Default: one period of the ``H`` gate when
        the strategy has one (plus the gate's step-0 and wraparound
        edges), else three consecutive steps. Strategies with a cadence
        that is not H-shaped (e.g. SPARTA's ``interval``) override."""
        H = int(getattr(self, "H", 1) or 1)
        return list(range(0, max(3, H + 2)))

    # -- logging helpers --------------------------------------------------

    def lr_at(self, step: int) -> float:
        """Host-side lr for logging (replaces the reference's lr_callbacks,
        ``strategy.py:56-58``: the schedule is deterministic, so the logger
        evaluates it instead of receiving callbacks). Pure numpy — zero
        device ops per call."""
        base = getattr(self, "optim_spec", None)
        base_lr = base.lr if base is not None else 0.0
        if self._lr_scale_host is None:
            return base_lr
        return float(base_lr * self._lr_scale_host(step))

    def config(self) -> Dict[str, Any]:
        cfg: Dict[str, Any] = {"strategy": type(self).__name__}
        if self.lr_scheduler:
            cfg["lr_scheduler"] = self.lr_scheduler
            cfg.update(
                {f"lr_{k}": v for k, v in (self.lr_scheduler_kwargs or {}).items()}
            )
        if self.max_norm is not None:
            cfg["max_norm"] = self.max_norm
        spec = getattr(self, "optim_spec", None)
        if spec is not None:
            cfg.update(spec.config())
        return cfg

    def _maybe_clip(self, grads: PyTree, ctx: AxisCtx = None) -> PyTree:
        """Global-norm clip. Under pipeline parallelism (``ctx.pp_axes``
        and the pipeline grad layout ``{"outer", "stages"}``) the true
        global norm counts the replicated outer grads ONCE and sums the
        stage-local parts over the pipe group — a per-device norm would
        give each stage a different clip scale, silently desyncing the
        replicated outer params (embeddings/tied head) across the pipe
        group forever."""
        if not self.max_norm:
            return grads
        if (ctx is not None and ctx.pp_axes and isinstance(grads, dict)
                and set(grads.keys()) == {"outer", "stages"}):
            def sq(t):
                return sum(jnp.sum(jnp.square(x))
                           for x in jax.tree.leaves(t))
            total = sq(grads["outer"]) + jax.lax.psum(
                sq(grads["stages"]), ctx.pp_axes)
            scale = jnp.minimum(
                1.0, self.max_norm / (jnp.sqrt(total) + 1e-6))
            return jax.tree.map(lambda x: x * scale, grads)
        return clip_by_global_norm(grads, self.max_norm)
