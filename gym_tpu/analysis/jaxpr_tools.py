"""Jaxpr tracing + walking machinery shared by the static analyzers.

Two capabilities:

1. **Abstract node-axis tracing** (``trace_with_axis_env``): the trainer
   runs the per-node step under ``shard_map`` over a ``'node'`` mesh
   axis, but building that mesh needs K physical devices — which a CI
   host doesn't have (the 2-core container folds K nodes onto one CPU
   device via a vmapped ``'vnode'`` axis, which ERASES the collectives
   from the jaxpr: vmap's batching rules turn a vnode psum into a dense
   sum at trace time). ``jax.make_jaxpr(axis_env=...)`` binds the axis
   names *abstractly* instead, so ``jax.make_jaxpr`` of the raw node
   function stages every ``psum``/``all_gather``/``reduce_scatter`` as a
   first-class equation over the full K-sized axis — the honest
   collective signature of the program, independent of how many devices
   the analysis host happens to have.

2. **Constant-folding jaxpr walk** (``walk_jaxpr``): an abstract
   interpreter over a ClosedJaxpr that (a) collects every collective
   equation over the node axes into a ``CollectiveSite`` inventory,
   descending through ``jit``/``cond``/``scan``/``shard_map``/custom-
   derivative sub-jaxprs; (b) flags host callbacks and f64-producing
   equations; and (c) *partially evaluates* the program: any equation
   whose inputs are all known constants is executed eagerly on the host.
   Because the analyzers close over a CONCRETE step index, the strategy
   gates (``step % H == 0``), the shared-PRNG masks (SPARTA) and the
   ``comm_bytes`` accounting all fold to constants — ``cond`` equations
   resolve to the branch that would actually run at that step, and the
   step's ``comm_bytes`` metric output folds to the exact float32 the
   compiled program would report. That folded metric is what makes the
   static reconciliation byte-exact even for strategies whose wire
   accounting is data-dependent (SPARTA's realized-mask bytes).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.core import DropVar
from jax.extend import core

from ..parallel.axis import AxisCtx

PyTree = Any


class _Unknown:
    """Sentinel for 'value not statically known' (params, grads, ...)."""

    def __repr__(self):
        return "<unknown>"


UNKNOWN = _Unknown()

# Collective primitives over named axes → the CollectiveEvent op
# vocabulary (strategy/base.py). jax 0.4.x names: psum_scatter binds a
# primitive that prints as `reduce_scatter`.
COLLECTIVE_PRIM_OPS = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "ppermute": "p2p",
    "pbroadcast": "broadcast",
    "all_to_all": "all_to_all",
}

# Host-callback primitives: forbidden in hot paths (a device→host round
# trip per dispatch; on TPU it also forces a tuplized transfer that
# breaks async dispatch).
CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
}

# Call-like primitives: one sub-jaxpr, semantics = inline call, so known
# inputs propagate to known outputs.
_CALL_PRIMS = {
    "jit", "closed_call", "core_call", "call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call", "custom_vjp_call_jaxpr",
}

# Payload at or below this is control-plane traffic (clip norms, alive
# counts, masked-mean denominators — all 4-byte f32 scalars), not
# data-plane payload: the strategies' own ``comm_bytes`` accounting
# prices payload only, so the inventory keeps the two separate rather
# than failing reconciliation over a scalar psum.
CONTROL_PLANE_BYTES = 8


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   * np.dtype(aval.dtype).itemsize)
    except Exception:  # abstract tokens etc.
        return 0


@dataclasses.dataclass
class CollectiveSite:
    """One collective equation over the node axes, analytically priced.

    ``bytes`` follows the CollectiveEvent convention (strategy/base.py):
    all_reduce/reduce_scatter = size of the (full) input vector,
    all_gather = size of the assembled output, p2p/broadcast = message
    size. ``times`` multiplies for collectives inside a ``scan`` body.
    """

    op: str
    primitive: str
    axes: Tuple[str, ...]
    group: int
    bytes: float
    times: int = 1
    path: str = ""
    control_plane: bool = False


@dataclasses.dataclass
class WalkReport:
    """Everything one ``walk_jaxpr`` pass learned about a program."""

    collectives: List[CollectiveSite] = dataclasses.field(
        default_factory=list)
    callbacks: List[str] = dataclasses.field(default_factory=list)
    f64_eqns: List[str] = dataclasses.field(default_factory=list)
    # conds whose predicate could not be folded AND whose branches
    # contain node collectives: the static inventory is then ambiguous
    dynamic_collective_conds: int = 0
    out_values: List[Any] = dataclasses.field(default_factory=list)

    def data_collectives(self) -> List[CollectiveSite]:
        return [c for c in self.collectives if not c.control_plane]


def abstract_node_ctx(num_nodes: int, n_virt: int = 1) -> AxisCtx:
    """An ``AxisCtx`` for abstract tracing: the canonical single
    ``'node'`` mesh axis (``n_virt == 1``, the benchmarked topology), or
    the ``('node', 'vnode')`` pair to trace a strategy's vnode-fallback
    schedule (``n_virt > 1``)."""
    if num_nodes % n_virt:
        raise ValueError(f"n_virt={n_virt} does not divide K={num_nodes}")
    if n_virt > 1:
        return AxisCtx(num_nodes=num_nodes, axes=("node", "vnode"),
                       sizes=(num_nodes // n_virt, n_virt))
    return AxisCtx(num_nodes=num_nodes, axes=("node",), sizes=(num_nodes,))


def trace_with_axis_env(fn: Callable, example_args: Sequence[Any],
                        axis_sizes: Optional[Dict[str, int]] = None):
    """``jax.make_jaxpr(fn)(*example_args)`` with the named axes in
    ``axis_sizes`` bound abstractly, so collectives over those axes stage
    as jaxpr equations instead of failing with an unbound-axis error.
    ``example_args`` may be ``ShapeDtypeStruct`` pytrees — nothing is
    materialized or executed."""
    return jax.make_jaxpr(
        fn, axis_env=list((axis_sizes or {}).items()))(*example_args)


def eval_shape_with_axis_env(fn: Callable, example_args: Sequence[Any],
                             axis_sizes: Optional[Dict[str, int]] = None):
    """``jax.eval_shape(fn, *example_args)`` with the named axes bound
    abstractly, as in ``trace_with_axis_env``."""
    _, shapes = jax.make_jaxpr(
        fn, axis_env=list((axis_sizes or {}).items()),
        return_shape=True)(*example_args)
    return shapes


def _eqn_axes(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        return (ax,)
    return tuple(ax) if ax is not None else ()


def _sub_jaxprs(params: dict):
    """Every Jaxpr/ClosedJaxpr nested in an eqn's params (generic
    fallback for primitives the walker has no special case for)."""
    out = []
    for v in params.values():
        if isinstance(v, core.ClosedJaxpr):
            out.append(v)
        elif isinstance(v, core.Jaxpr):
            out.append(core.ClosedJaxpr(v, ()))
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, core.ClosedJaxpr):
                    out.append(x)
                elif isinstance(x, core.Jaxpr):
                    out.append(core.ClosedJaxpr(x, ()))
    return out


class _Walker:
    def __init__(self, node_axes: Sequence[str], axis_sizes: Dict[str, int],
                 control_plane_bytes: int = CONTROL_PLANE_BYTES,
                 fold: bool = True):
        self.node_axes = frozenset(node_axes)
        self.axis_sizes = dict(axis_sizes)
        self.control_plane_bytes = control_plane_bytes
        self.fold = fold
        self.report = WalkReport()
        # all_gather output var → its CollectiveSite, for coalescing the
        # gather-per-axis chain ``AxisCtx.all_gather`` emits over
        # ('node', 'vnode') into ONE logical event whose bytes are the
        # final assembled output (matching the declared convention)
        self._gather_sites: Dict[Any, CollectiveSite] = {}

    # -- value environment helpers ---------------------------------------

    @staticmethod
    def _read(env, atom):
        if isinstance(atom, core.Literal):
            return atom.val
        return env.get(atom, UNKNOWN)

    @staticmethod
    def _write(env, var, val):
        if not isinstance(var, DropVar):
            env[var] = val

    # -- main walk --------------------------------------------------------

    def walk(self, jaxpr: core.Jaxpr, consts: Sequence[Any],
             in_vals: Sequence[Any], path: str = "",
             times: int = 1) -> List[Any]:
        env: Dict[Any, Any] = {}
        for v, c in zip(jaxpr.constvars, consts):
            self._write(env, v, c)
        for v, val in zip(jaxpr.invars, in_vals):
            self._write(env, v, val)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            invals = [self._read(env, a) for a in eqn.invars]
            where = f"{path}/{prim}" if path else prim

            for ov in eqn.outvars:
                dt = getattr(ov.aval, "dtype", None)
                try:
                    wide = dt is not None and np.dtype(dt) in (
                        np.dtype(np.float64), np.dtype(np.complex128))
                except TypeError:
                    wide = False  # extended dtypes (typed PRNG keys)
                if wide:
                    self.report.f64_eqns.append(where)
                    break

            if prim in CALLBACK_PRIMS:
                self.report.callbacks.append(where)
                for ov in eqn.outvars:
                    self._write(env, ov, UNKNOWN)
                continue

            if prim in COLLECTIVE_PRIM_OPS:
                self._record_collective(eqn, prim, where, times)
                for ov in eqn.outvars:
                    self._write(env, ov, UNKNOWN)
                continue

            if prim == "cond":
                self._walk_cond(eqn, env, invals, where, times)
                continue

            if prim == "scan":
                sub = eqn.params["jaxpr"]
                length = int(eqn.params.get("length", 1))
                self.walk(sub.jaxpr, sub.consts,
                          [UNKNOWN] * len(sub.jaxpr.invars),
                          f"{where}", times * max(length, 1))
                for ov in eqn.outvars:
                    self._write(env, ov, UNKNOWN)
                continue

            if prim == "while":
                for sub in _sub_jaxprs(eqn.params):
                    self.walk(sub.jaxpr, sub.consts,
                              [UNKNOWN] * len(sub.jaxpr.invars),
                              f"{where}", times)
                for ov in eqn.outvars:
                    self._write(env, ov, UNKNOWN)
                continue

            if prim in _CALL_PRIMS:
                sub = (eqn.params.get("jaxpr")
                       or eqn.params.get("call_jaxpr")
                       or eqn.params.get("fun_jaxpr"))
                if isinstance(sub, core.Jaxpr):
                    sub = core.ClosedJaxpr(sub, ())
                if sub is not None:
                    outs = self.walk(sub.jaxpr, sub.consts,
                                     list(invals)[:len(sub.jaxpr.invars)],
                                     where, times)
                    for ov, val in zip(eqn.outvars, outs):
                        self._write(env, ov, val)
                    continue

            subs = _sub_jaxprs(eqn.params)
            if subs:
                # unknown higher-order primitive (shard_map, ...): walk
                # the bodies for inventory/callbacks, outputs unknown
                for sub in subs:
                    self.walk(sub.jaxpr, sub.consts,
                              [UNKNOWN] * len(sub.jaxpr.invars),
                              where, times)
                for ov in eqn.outvars:
                    self._write(env, ov, UNKNOWN)
                continue

            self._fold_eqn(eqn, env, invals)

        outs = [self._read(env, a) for a in jaxpr.outvars]
        return outs

    # -- pieces -----------------------------------------------------------

    def _record_collective(self, eqn, prim, where, times):
        axes = _eqn_axes(eqn)
        named = [a for a in axes if a in self.node_axes]
        if not named:
            return  # seq/pipe-axis collective: not node traffic
        group = 1
        for a in named:
            group *= int(self.axis_sizes.get(a, 1))
        op = COLLECTIVE_PRIM_OPS[prim]
        if op == "all_gather":
            nbytes = sum(_aval_bytes(ov.aval) for ov in eqn.outvars)
            prev = None
            for a in eqn.invars:
                if not isinstance(a, core.Literal):
                    prev = self._gather_sites.get(a)
            if prev is not None:
                # second hop of AxisCtx.all_gather's per-axis chain:
                # fold into one logical gather over the combined axes
                prev.axes = tuple(prev.axes) + tuple(named)
                prev.group *= group
                prev.bytes = float(nbytes)
                prev.path = where
                for ov in eqn.outvars:
                    self._gather_sites[ov] = prev
                return
        else:
            nbytes = sum(_aval_bytes(a.aval) for a in eqn.invars)
        site = CollectiveSite(
            op=op, primitive=prim, axes=tuple(named), group=group,
            bytes=float(nbytes), times=times, path=where,
            control_plane=nbytes <= self.control_plane_bytes)
        self.report.collectives.append(site)
        if op == "all_gather":
            for ov in eqn.outvars:
                self._gather_sites[ov] = site

    def _walk_cond(self, eqn, env, invals, where, times):
        pred, ops = invals[0], invals[1:]
        branches = eqn.params["branches"]
        if pred is not UNKNOWN:
            idx = int(np.asarray(pred))
            idx = max(0, min(idx, len(branches) - 1))
            b = branches[idx]
            outs = self.walk(b.jaxpr, b.consts, ops,
                             f"{where}[{idx}]", times)
            for ov, val in zip(eqn.outvars, outs):
                self._write(env, ov, val)
            return
        before = len(self.report.collectives)
        for j, b in enumerate(branches):
            self.walk(b.jaxpr, b.consts,
                      [UNKNOWN] * len(b.jaxpr.invars),
                      f"{where}?[{j}]", times)
        if any(not c.control_plane
               for c in self.report.collectives[before:]):
            self.report.dynamic_collective_conds += 1
        for ov in eqn.outvars:
            self._write(env, ov, UNKNOWN)

    def _fold_eqn(self, eqn, env, invals):
        known = all(v is not UNKNOWN for v in invals)
        if not (self.fold and known):
            for ov in eqn.outvars:
                self._write(env, ov, UNKNOWN)
            return
        try:
            out = eqn.primitive.bind(*invals, **eqn.params)
        except Exception:
            out = None
            ok = False
        else:
            ok = True
        if not ok:
            for ov in eqn.outvars:
                self._write(env, ov, UNKNOWN)
            return
        if eqn.primitive.multiple_results:
            for ov, val in zip(eqn.outvars, out):
                self._write(env, ov, val)
        else:
            self._write(env, eqn.outvars[0], out)


def walk_jaxpr(closed: core.ClosedJaxpr, *,
               node_axes: Sequence[str] = ("node", "vnode"),
               axis_sizes: Optional[Dict[str, int]] = None,
               known_args: Optional[Sequence[Any]] = None,
               control_plane_bytes: int = CONTROL_PLANE_BYTES,
               fold: bool = True) -> WalkReport:
    """Walk a ClosedJaxpr: collect the node-axis collective inventory,
    host callbacks and f64 equations; constant-fold what it can (conds
    with foldable predicates resolve to the live branch). ``known_args``
    optionally pins input values (UNKNOWN where None)."""
    w = _Walker(node_axes, axis_sizes or {}, control_plane_bytes, fold)
    n_in = len(closed.jaxpr.invars)
    ins = list(known_args) if known_args is not None else [UNKNOWN] * n_in
    ins += [UNKNOWN] * (n_in - len(ins))
    outs = w.walk(closed.jaxpr, closed.consts, ins)
    w.report.out_values = outs
    return w.report
