"""Composition base: local optimize + pluggable communication modules.

Reference (``exogym/strategy/communicate_optimize_strategy.py``): a strategy
that (1) runs the inner optimizer, then (2) applies a list of
``CommunicationModule``s. Here modules are pure state transformers:

    mstate            = module.init(params)
    params', mstate', bytes = module.communicate(params, mstate, step, ctx)

so the same module composes into any strategy (this is what makes the
SPARTA×DiLoCo combo work — the reference version was broken because its
DiLoCo communicator module never existed, ``sparta_diloco.py:6`` /
``strategy/__init__.py:10``; SURVEY §2.1).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import optax

from .base import (CollectiveEvent, PyTree, Strategy, comm_metric,
                   require_finalized)
from .optim import OptimSpec, ensure_optim_spec


class CommunicationModule(abc.ABC):
    """Pure communication transformer over the node axis."""

    _ctx = None  # mesh context, bound before init for layout decisions

    def bind_ctx(self, ctx) -> "CommunicationModule":
        self._ctx = ctx
        return self

    def init(self, params: PyTree) -> PyTree:
        return {}

    @abc.abstractmethod
    def communicate(self, params, mstate, step, ctx):
        """Returns (new_params, new_mstate, comm_bytes)."""

    def comm_events(self, step: int, params: PyTree,
                    num_nodes: int) -> List[CollectiveEvent]:
        """Host-side analytic trace of the collectives ``communicate``
        runs at ``step`` (see ``Strategy.comm_events``)."""
        return []

    def config(self) -> Dict[str, Any]:
        return {"module": type(self).__name__}


class CommunicateOptimizeStrategy(Strategy):
    """Inner optimizer step, then each communication module in order
    (reference ``communicate_optimize_strategy.py:67-85``)."""

    def __init__(
        self,
        communication_modules: Sequence[CommunicationModule],
        inner_optim: Optional[Union[str, OptimSpec]] = None,
        max_norm: Optional[float] = None,
        lr_scheduler=None,
        lr_scheduler_kwargs=None,
    ):
        super().__init__(lr_scheduler, lr_scheduler_kwargs, max_norm)
        self.optim_spec = ensure_optim_spec(inner_optim, OptimSpec("adamw"))
        self.communication_modules: List[CommunicationModule] = list(
            communication_modules
        )
        self.tx: optax.GradientTransformation | None = None

    def _build(self):
        self.tx = self.optim_spec.build(self._lr_scale)

    def bind_ctx(self, ctx):
        super().bind_ctx(ctx)
        for m in self.communication_modules:
            m.bind_ctx(ctx)
        return self

    def init(self, params: PyTree) -> PyTree:
        require_finalized(self)
        return {
            "opt": self.tx.init(params),
            "modules": [m.init(params) for m in self.communication_modules],
        }

    def _should_communicate(self, step):
        """Gate hook; FedAvg overrides with its H-periodic gate
        (reference ``federated_averaging.py:108-111``)."""
        return None  # None = always

    def _should_communicate_host(self, step: int) -> bool:
        """Pure-Python twin of ``_should_communicate`` for the host-side
        trace path (``comm_events`` runs outside jit, per logged step —
        it must not build jnp scalars). Subclasses overriding the gate
        override both."""
        return True

    def comm_events(self, step: int, params: PyTree,
                    num_nodes: int) -> List[CollectiveEvent]:
        if not self._should_communicate_host(step):
            return []
        events: List[CollectiveEvent] = []
        for m in self.communication_modules:
            events.extend(m.comm_events(step, params, num_nodes))
        return events

    def step(self, grads, params, state, step, ctx):
        with jax.named_scope("optimizer"):
            grads = self._maybe_clip(grads, ctx)
            updates, opt_state = self.tx.update(grads, state["opt"],
                                                params)
            params = optax.apply_updates(params, updates)

        def run(params, mstates):
            total = jnp.zeros(())
            new_mstates = []
            for mod, ms in zip(self.communication_modules, mstates):
                params, ms, nbytes = mod.communicate(params, ms, step, ctx)
                new_mstates.append(ms)
                total = total + nbytes
            return params, new_mstates, total

        gate = self._should_communicate(step)
        if gate is None:
            params, mstates, comm = run(params, state["modules"])
        else:
            params, mstates, comm = jax.lax.cond(
                gate,
                lambda p, m: run(p, m),
                lambda p, m: (p, m, jnp.zeros(())),
                params, state["modules"],
            )
        return (
            params,
            {"opt": opt_state, "modules": mstates},
            {"comm_bytes": comm_metric(comm)},
        )

    def config(self):
        cfg = super().config()
        for i, m in enumerate(self.communication_modules):
            for k, v in m.config().items():
                cfg[f"{k}_{i}" if k in cfg else k] = v
        return cfg
