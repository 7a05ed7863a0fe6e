"""Static-analysis subsystem (ISSUE 6): jaxpr auditor, static comm-trace
reconciliation, and the host-concurrency lint.

Everything here is host-side tracing/AST work — no device programs are
compiled or executed, so the whole file is non-slow.
"""

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from gym_tpu.analysis import (ProgramSpec, audit_program,
                              audit_shipped_programs, check_all_strategies,
                              check_strategy, program_key, recompile_guard,
                              trace_with_axis_env, walk_jaxpr)
from gym_tpu.analysis.lint import (apply_suppressions, lint_source,
                                   load_suppressions, run_lint)
from gym_tpu.analysis.trace_check import (DEFAULT_TEMPLATE,
                                          extract_step_inventory)
from gym_tpu.strategy import (DiLoCoStrategy, OptimSpec,
                              SimpleReduceStrategy, SPARTAStrategy)
from gym_tpu.strategy.base import CollectiveEvent, tree_bytes

F32 = np.float32


# -- walker: collective extraction + constant folding ----------------------


def test_walker_extracts_collectives_over_abstract_axis():
    def fn(x):
        s = lax.psum(x, "node")
        g = lax.all_gather(x, "node", tiled=False)
        rs = lax.psum_scatter(x, "node", scatter_dimension=0, tiled=True)
        return s, g, rs

    closed = trace_with_axis_env(
        fn, (jax.ShapeDtypeStruct((8,), F32),), {"node": 4})
    rep = walk_jaxpr(closed, node_axes=("node",), axis_sizes={"node": 4})
    sites = rep.data_collectives()
    by_op = {s.op: s for s in sites}
    assert set(by_op) == {"all_reduce", "all_gather", "reduce_scatter"}
    assert by_op["all_reduce"].bytes == 32          # input vector
    assert by_op["all_gather"].bytes == 4 * 32      # assembled output
    assert by_op["reduce_scatter"].bytes == 32      # full input
    assert all(s.group == 4 for s in sites)


def test_walker_resolves_cond_with_foldable_predicate():
    """The H-gate pattern: with a concrete step the predicate folds and
    only the LIVE branch's collectives are counted."""

    def make(step):
        def fn(x):
            do = jnp.logical_and(jnp.asarray(step) % 5 == 0,
                                 jnp.asarray(step) > 0)
            return lax.cond(do, lambda a: lax.psum(a, "node"),
                            lambda a: a, x)
        return fn

    tpl = (jax.ShapeDtypeStruct((16,), F32),)
    on = walk_jaxpr(trace_with_axis_env(make(5), tpl, {"node": 4}),
                    node_axes=("node",), axis_sizes={"node": 4})
    off = walk_jaxpr(trace_with_axis_env(make(3), tpl, {"node": 4}),
                     node_axes=("node",), axis_sizes={"node": 4})
    assert len(on.data_collectives()) == 1
    assert off.data_collectives() == []
    assert on.dynamic_collective_conds == 0


def test_walker_folds_constant_metric_through_cond():
    def fn(x):
        do = jnp.asarray(10) % 5 == 0
        comm = lax.cond(do, lambda: jnp.float32(123.0),
                        lambda: jnp.float32(0.0))
        return comm, lax.psum(x, "node")

    closed = trace_with_axis_env(
        fn, (jax.ShapeDtypeStruct((4,), F32),), {"node": 2})
    rep = walk_jaxpr(closed, node_axes=("node",), axis_sizes={"node": 2})
    assert float(np.asarray(rep.out_values[0])) == 123.0


def test_walker_gather_chain_coalesces_to_final_output():
    """AxisCtx.all_gather over ('node', 'vnode') emits one gather per
    axis; the inventory must price them as ONE logical gather with the
    final assembled bytes (the declared-event convention)."""
    from gym_tpu.analysis.jaxpr_tools import abstract_node_ctx

    ctx = abstract_node_ctx(4, n_virt=2)

    def fn(x):
        return ctx.all_gather(x)

    closed = trace_with_axis_env(
        fn, (jax.ShapeDtypeStruct((10,), F32),),
        dict(zip(ctx.axes, ctx.sizes)))
    rep = walk_jaxpr(closed, node_axes=ctx.axes,
                     axis_sizes=dict(zip(ctx.axes, ctx.sizes)))
    sites = rep.data_collectives()
    assert len(sites) == 1
    assert sites[0].group == 4
    assert sites[0].bytes == 4 * 10 * 4


def test_walker_counts_scan_multiplicity_and_control_plane():
    def fn(x):
        def body(c, _):
            return c + lax.psum(c, "node"), None
        y, _ = lax.scan(body, x, None, length=3)
        tiny = lax.psum(jnp.float32(1.0), "node")   # control-plane scalar
        return y, tiny

    closed = trace_with_axis_env(
        fn, (jax.ShapeDtypeStruct((8,), F32),), {"node": 2})
    rep = walk_jaxpr(closed, node_axes=("node",), axis_sizes={"node": 2})
    data = rep.data_collectives()
    assert len(data) == 1 and data[0].times == 3
    ctrl = [s for s in rep.collectives if s.control_plane]
    assert len(ctrl) == 1 and ctrl[0].bytes == 4


# -- static trace reconciliation (the acceptance oracle) -------------------


@pytest.mark.parametrize("name", [
    "simple_reduce", "zero_reduce", "zero_reduce_vnode", "diloco",
    "fedavg", "sparta", "demo", "sparta_diloco", "noloco", "dynamiq",
    "dynamiq_vnode", "dynamiq_topk", "diloco_int8", "diloco_topk",
    "noloco_int4", "demo_outer"])
def test_static_reconciliation_all_strategies(name):
    """jaxpr-extracted collective inventory == declared comm_events,
    op-for-op and byte-for-byte (folded comm_bytes metric), over a full
    H cycle, for every shipped strategy configuration."""
    res = check_all_strategies(num_nodes=4)[name]
    assert res.ok, res.summary()
    # the cycle actually exercises both silent and communicating steps
    # for the gated strategies
    txs = [s.declared_tx for s in res.steps]
    if name in ("diloco", "fedavg", "noloco", "diloco_int8",
                "diloco_topk", "noloco_int4", "demo_outer"):
        # the cycle exercises both silent and communicating steps
        assert any(t == 0 for t in txs) and any(t > 0 for t in txs)
    if name in ("diloco_int8", "diloco_topk", "noloco_int4",
                "demo_outer"):
        # the compressed outer rounds talk at well under the dense
        # round's cost (int8 ≈ 1/4, int4 ≈ 1/8, top-k 5% ≈ 1/12 of the
        # respective dense convention)
        psize = tree_bytes(DEFAULT_TEMPLATE)
        dense_round = (psize if name.startswith("noloco")
                       else 2 * 3 / 4 * psize)
        assert all(t < 0.5 * dense_round for t in txs if t > 0), \
            (txs, dense_round)
    if name == "sparta_diloco":
        # gossip every step, outer round only at H: two distinct levels
        assert len(set(round(t) for t in txs)) >= 2
    if name.startswith("dynamiq"):
        # compressed ALL-reduce: every step talks, and at well under the
        # dense 2(K−1)/K·|θ| f32 cost (int8 ≈ 1/4, topk 5% ≈ 1/5)
        psize = tree_bytes(DEFAULT_TEMPLATE)
        dense = 2 * 3 / 4 * psize
        assert all(0 < t < 0.5 * dense for t in txs), (txs, dense)


def test_diloco_h_gate_static_cadence():
    """Off-H steps must extract ZERO node collectives (the skip branch),
    and the H step must extract the outer all_reduce."""
    s = DiLoCoStrategy(H=5)
    s.finalize(32)
    rep_off = extract_step_inventory(s, DEFAULT_TEMPLATE, 4, step=3)
    rep_on = extract_step_inventory(s, DEFAULT_TEMPLATE, 4, step=5)
    assert rep_off.data_collectives() == []
    assert float(np.asarray(rep_off.out_values[0])) == 0.0
    ops = {c.op for c in rep_on.data_collectives()}
    assert ops == {"all_reduce"}


def test_sparta_static_tx_is_realized_mask_bytes_not_expectation():
    """The folded static metric must equal the REALIZED shared-PRNG mask
    bytes (varying per step), not the p·|θ| expectation — the exact
    property the runtime test pinned with a real fit, now proven by
    constant folding alone."""
    s = SPARTAStrategy(inner_optim=OptimSpec("sgd", lr=0.0), p_sparta=0.3)
    s.finalize(16)
    psize = tree_bytes(DEFAULT_TEMPLATE)
    seen = set()
    for t in (0, 1, 2):
        rep = extract_step_inventory(s, DEFAULT_TEMPLATE, 4, step=t)
        static = float(np.asarray(rep.out_values[0]))
        declared = sum(e.per_node_tx()
                       for e in s.comm_events(t, DEFAULT_TEMPLATE, 4))
        assert static == pytest.approx(declared, rel=1e-6)
        expectation = 2 * 3 / 4 * 0.3 * psize
        assert static != pytest.approx(expectation, rel=1e-3)
        seen.add(round(static, 3))
    assert len(seen) == 3   # fresh Bernoulli draw per step


def test_falsified_trace_is_caught():
    """A strategy whose declared trace lies — wrong bytes or wrong op —
    must fail the static reconciliation (the ISSUE 6 acceptance
    fixture)."""

    class LyingBytes(SimpleReduceStrategy):
        def comm_events(self, step, params, num_nodes):
            return [CollectiveEvent(
                "all_reduce", float(tree_bytes(params)) / 2, num_nodes)]

    class LyingOp(SimpleReduceStrategy):
        def comm_events(self, step, params, num_nodes):
            return [CollectiveEvent(
                "all_gather", float(tree_bytes(params)), num_nodes)]

    class SilentExtra(SimpleReduceStrategy):
        def comm_events(self, step, params, num_nodes):
            return []      # claims silence while psumming every step

    for cls, frag in ((LyingBytes, "static comm_bytes"),
                      (LyingOp, "ops mismatch"),
                      (SilentExtra, "ops mismatch")):
        res = check_strategy(cls(), num_nodes=4)
        assert not res.ok, cls.__name__
        assert any(frag in e for s in res.failures() for e in s.errors), \
            (cls.__name__, res.failures()[0].errors)


def test_falsified_low_comm_traces_are_caught():
    """The ISSUE 10 falsification fixtures: byte totals alone cannot
    catch these lies, the structural checks must.

    - WrongPartner: a NoLoCo whose trace declares a rotated partner map
      — every derangement moves the same |θ|, so only the folded
      shared-PRNG draw comparison can refute it.
    - NotAPermutation: declared pairs where one node receives twice.
    - WrongCompressedBytes: a DynamiQ declaring half its codec's honest
      wire bytes — caught by the folded comm_bytes metric.
    - UndeclaredResidualGather: a DynamiQ-topk that all_gathers its
      error-feedback residual every step without declaring it; the wire
      accounting still matches, but the moved bytes exceed the declared
      dense-emulation bound.
    """
    from gym_tpu.strategy import DynamiQStrategy, NoLoCoStrategy
    from gym_tpu.strategy.noloco import NoLoCoCommunicator

    class _WrongPartnerComm(NoLoCoCommunicator):
        def comm_events(self, step, params, num_nodes):
            events = super().comm_events(step, params, num_nodes)
            return [
                CollectiveEvent(
                    e.op, e.bytes, e.group, label=e.label,
                    pairs=tuple((i, (j + 1) % num_nodes)
                                for i, j in e.pairs),
                    emulated_bytes=e.emulated_bytes)
                for e in events]

    class WrongPartner(NoLoCoStrategy):
        def __init__(self):
            super().__init__(H=2)
            self.communication_modules[0].__class__ = _WrongPartnerComm

    class _NotPermComm(NoLoCoCommunicator):
        def comm_events(self, step, params, num_nodes):
            events = super().comm_events(step, params, num_nodes)
            return [
                CollectiveEvent(
                    e.op, e.bytes, e.group, label=e.label,
                    pairs=((0, 1),) * num_nodes,
                    emulated_bytes=e.emulated_bytes)
                for e in events]

    class NotAPermutation(NoLoCoStrategy):
        def __init__(self):
            super().__init__(H=2)
            self.communication_modules[0].__class__ = _NotPermComm

    class WrongCompressedBytes(DynamiQStrategy):
        def comm_events(self, step, params, num_nodes):
            return [
                CollectiveEvent(e.op, e.bytes / 2, e.group, label=e.label,
                                emulated_bytes=e.emulated_bytes)
                for e in super().comm_events(step, params, num_nodes)]

    class UndeclaredResidualGather(DynamiQStrategy):
        def __init__(self):
            super().__init__(codec="topk", frac=0.05)

        def step(self, grads, params, state, step, ctx):
            p, s, m = super().step(grads, params, state, step, ctx)
            # smuggle a dense residual exchange into the declared
            # gather hop; fold a value through so it isn't dead code,
            # but keep the comm_bytes metric (the wire lie) unchanged
            leak = ctx.all_gather(s["residual"])
            s = dict(s, residual=s["residual"] + 0.0 * leak.sum())
            return p, s, m

    for cls, frag in (
            (WrongPartner, "folded shared-PRNG draw"),
            (NotAPermutation, "not a permutation"),
            (WrongCompressedBytes, "static comm_bytes"),
            (UndeclaredResidualGather, "dense-emulation bound")):
        res = check_strategy(cls(), num_nodes=4)
        assert not res.ok, cls.__name__
        assert any(frag in e for s in res.failures() for e in s.errors), \
            (cls.__name__, [s.errors for s in res.failures()])


def test_falsified_compressed_outer_loop_traces_are_caught():
    """The ISSUE 12 falsification fixtures — the codec axis must not
    weaken the gates:

    - WrongWireBytes: a compressed DiLoCo declaring half its link's
      honest wire bytes (codec bytes are far below the dense emulation
      anyway, so only the folded comm_bytes metric can refute it).
    - UndeclaredResidualExchange: a compressed NoLoCo that gossips its
      error-feedback residual alongside the params without declaring it
      — wire accounting still matches, but the gathered dense payload
      exceeds the declared ``emulated_bytes`` bound.
    """
    from gym_tpu.strategy import DiLoCoStrategy, NoLoCoStrategy
    from gym_tpu.strategy.noloco import NoLoCoCommunicator

    class WrongWireBytes(DiLoCoStrategy):
        def __init__(self):
            super().__init__(H=2, codec="int4")

        def comm_events(self, step, params, num_nodes):
            return [
                CollectiveEvent(e.op, e.bytes / 2, e.group, label=e.label,
                                emulated_bytes=e.emulated_bytes)
                for e in super().comm_events(step, params, num_nodes)]

    class _LeakyGossip(NoLoCoCommunicator):
        def communicate(self, params, mstate, step, ctx):
            params, mstate, comm = super().communicate(
                params, mstate, step, ctx)
            # smuggle the residual into an extra gather; fold a value
            # through so it isn't dead code, keep the metric unchanged
            leak = ctx.all_gather(mstate["ef_residual"])
            mstate = dict(mstate,
                          ef_residual=mstate["ef_residual"]
                          + 0.0 * leak.sum())
            return params, mstate, comm

    class UndeclaredResidualExchange(NoLoCoStrategy):
        def __init__(self):
            super().__init__(H=2, codec="int4")
            self.communication_modules[0].__class__ = _LeakyGossip

    for cls, frag in (
            (WrongWireBytes, "static comm_bytes"),
            (UndeclaredResidualExchange, "dense-emulation bound")):
        res = check_strategy(cls(), num_nodes=4)
        assert not res.ok, cls.__name__
        assert any(frag in e for s in res.failures() for e in s.errors), \
            (cls.__name__, [s.errors for s in res.failures()])


# -- jaxpr audit: donation / callbacks / keys ------------------------------


def _spec(fn, args, donate=(), name="toy", axis_sizes=None):
    return ProgramSpec(name=name, fn=fn, args=tuple(args),
                       donate_args=tuple(donate), axis_sizes=axis_sizes)


def test_donation_unaliased_detected():
    """Donating a buffer no output can alias (shape mismatch) is the
    silent copy the audit exists to catch; the aliasable twin passes."""
    big = jax.ShapeDtypeStruct((128,), F32)

    def shrinks(x):
        return x[:4]

    def keeps(x):
        return x + 1

    bad = audit_program(_spec(shrinks, [big], donate=(0,)))
    assert [f.kind for f in bad.findings] == ["donation-unaliased"]
    good = audit_program(_spec(keeps, [big], donate=(0,)))
    assert good.ok


def test_donation_unused_detected():
    def ignores(x, y):
        return y * 2

    audit = audit_program(_spec(
        ignores, [jax.ShapeDtypeStruct((8,), F32)] * 2, donate=(0,)))
    kinds = [f.kind for f in audit.findings]
    assert "donation-unused" in kinds
    # the same program WITHOUT donating the dead arg is silent
    assert audit_program(_spec(
        ignores, [jax.ShapeDtypeStruct((8,), F32)] * 2)).ok


def test_host_callback_detected_in_hot_path_only():
    def with_cb(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct((4,), F32), x)
        return x + y

    def clean(x):
        return x * 2

    tpl = [jax.ShapeDtypeStruct((4,), F32)]
    hot = audit_program(_spec(with_cb, tpl))
    assert [f.kind for f in hot.findings] == ["host-callback"]
    cold = audit_program(dataclasses_replace_hot(_spec(with_cb, tpl)))
    assert cold.ok
    assert audit_program(_spec(clean, tpl)).ok


def dataclasses_replace_hot(spec):
    import dataclasses
    return dataclasses.replace(spec, hot_path=False)


def test_debug_print_counts_as_callback():
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    audit = audit_program(_spec(noisy, [jax.ShapeDtypeStruct((2,), F32)]))
    assert [f.kind for f in audit.findings] == ["host-callback"]


def test_program_key_stability_and_sensitivity():
    tpl = (jax.ShapeDtypeStruct((8,), F32),)
    _, h1 = program_key("p", {"a": 1}, tpl, (0,))
    _, h2 = program_key("p", {"a": 1}, tpl, (0,))
    assert h1 == h2
    # every key component moves the hash
    assert program_key("p", {"a": 2}, tpl, (0,))[1] != h1
    assert program_key("p", {"a": 1}, tpl, ())[1] != h1
    assert program_key("p", {"a": 1},
                       (jax.ShapeDtypeStruct((8,), np.float64),),
                       (0,))[1] != h1


def test_recompile_guard_flags_donation_near_miss():
    tpl = [jax.ShapeDtypeStruct((8,), F32)]

    def f(x):
        return x + 1

    a = audit_program(_spec(f, tpl, donate=(0,), name="fam[x]"))
    b = audit_program(_spec(f, tpl, donate=(), name="fam[y]"))
    for x in (a, b):
        x.family = "fam"
    guard = recompile_guard([a, b])
    assert guard["near_misses"], guard
    assert not guard["collisions"]


@pytest.mark.slow
def test_shipped_programs_audit_clean():
    """The full shipped-program registry: zero unconsumed donations,
    zero hot-path callbacks, zero f64, stable keys. (~10 s of tracing —
    also run by scripts/ci_analyze.sh via the CLI.)"""
    rep = audit_shipped_programs()
    assert rep["violations"] == 0, rep
    names = {p["name"] for p in rep["programs"]}
    assert len(names) == len(rep["programs"]) >= 26
    # ISSUE 29: the engine has one KV cache; no program of the unpaged
    # slot ring is left to audit
    assert not any(n.startswith(("serve.decode", "serve.prefill[",
                                 "serve.admit")) for n in names)
    # ISSUE 7: the paged/speculative serving programs are audited
    assert any(n.startswith("serve.paged_prefill") for n in names)
    assert any(n.startswith("serve.paged_decode") for n in names)
    assert any(n.startswith("serve.spec_decode") for n in names)
    assert any(n.startswith("serve.cow") for n in names)
    # ISSUE 11: the quantized family is audited too — donation-clean
    # int8 pools/scales, distinct names (dtype tag) and distinct keys
    assert any("w=int8" in n and "kv=int8" in n for n in names)
    assert any(n.startswith("serve.paged_decode[") and "w=int8" in n
               for n in names)
    assert rep["recompile_guard"]["n_keys"] == len(rep["programs"])


# -- lint rules, each pinned on a minimal snippet --------------------------


def _lint(src):
    return lint_source(textwrap.dedent(src))


def test_lint_bare_assert():
    vs = _lint("""
        def f(x):
            assert x > 0, "nope"
    """)
    assert [v.rule for v in vs] == ["GT101"]


def test_lint_lock_across_blocking_call():
    vs = _lint("""
        import threading, time, queue

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def bad(self):
                with self._lock:
                    item = self._q.get(timeout=1)
                    time.sleep(0.1)
                    self.fut.result()
                return item

            def good(self):
                with self._lock:
                    n = len(self.items)
                item = self._q.get(timeout=1)
                return n, item
    """)
    assert [v.rule for v in vs] == ["GT102"] * 3


def test_lint_condition_wait_on_held_lock_is_exempt():
    vs = _lint("""
        import threading

        class W:
            def __init__(self):
                self._work = threading.Condition()
                self._stop = threading.Event()

            def ok(self):
                with self._work:
                    while not self.ready:
                        self._work.wait()

            def bad(self):
                with self._work:
                    self._stop.wait(1.0)
    """)
    assert [v.rule for v in vs] == ["GT102"]
    assert "_stop" in vs[0].msg


def test_lint_fsync_under_lock():
    vs = _lint("""
        import threading, os

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def sync(self):
                with self._lock:
                    self._f.flush()
                    os.fsync(self._f.fileno())
    """)
    assert [v.rule for v in vs] == ["GT102"]


def test_lint_condition_alias_self_deadlock():
    vs = _lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._drained = threading.Condition(self._lock)

            def bad(self):
                with self._drained:
                    with self._lock:
                        pass
    """)
    assert [v.rule for v in vs] == ["GT103"]
    assert "same underlying lock" in vs[0].msg


def test_lint_lock_order_cycle():
    vs = _lint("""
        import threading

        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """)
    assert any(v.rule == "GT103" and "cycle" in v.msg for v in vs)


def test_lint_nested_function_does_not_inherit_lock_region():
    vs = _lint("""
        import threading, time

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def ok(self):
                with self._lock:
                    def later():
                        time.sleep(1)   # runs on another stack
                    self.cb = later
    """)
    assert vs == []


def test_lint_untyped_raise_and_wallclock():
    vs = _lint("""
        import time

        def f():
            t0 = time.time()
            raise RuntimeError("boom")
    """)
    assert sorted(v.rule for v in vs) == ["GT104", "GT105"]


def test_lint_str_join_and_dict_get_not_flagged():
    vs = _lint("""
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def ok(self):
                with self._lock:
                    s = ", ".join(self.parts)
                    v = self.cfg.get("key")
                return s, v
    """)
    assert vs == []


def test_suppression_budget_and_ratchet(tmp_path):
    supp = tmp_path / "supp.txt"
    supp.write_text(
        "# comment\n"
        "pkg/a.py:GT101 = 2  # legacy asserts\n"
        "pkg/b.py:GT105 = 3  # over-budgeted\n")
    loaded = load_suppressions(str(supp))
    assert loaded[("pkg/a.py", "GT101")] == (2, "legacy asserts")

    from gym_tpu.analysis.lint import LintViolation
    vs = [LintViolation("pkg/a.py", i, "GT101", "m") for i in (1, 2, 3)]
    vs.append(LintViolation("pkg/b.py", 9, "GT105", "m"))
    unsup, notes = apply_suppressions(vs, loaded)
    assert len(unsup) == 1 and unsup[0].line == 3      # beyond budget
    assert any("pkg/b.py:GT105" in n for n in notes)   # ratchet down

    with pytest.raises(ValueError, match="malformed suppression"):
        supp.write_text("what is this line\n")
        load_suppressions(str(supp))


def test_lint_gate_is_green_on_the_shipped_tree():
    """The ISSUE 6 burn-down pin: the real package has ZERO unsuppressed
    violations — 41 bare asserts became typed exceptions, the RuntimeErrors
    grew classes, and durations use perf_counter."""
    violations = run_lint("gym_tpu")
    unsup, notes = apply_suppressions(violations, load_suppressions())
    assert unsup == [], [v.render() for v in unsup]
    assert notes == [], notes   # budgets must stay ratcheted tight


def test_lock_sites_conformance_pinned():
    """The concurrency-audit satellite: the seven Lock/Condition sites
    (scheduler, supervisor, metrics, checkpoint, resilience ×2, plus the
    scheduler's condition) carry no lock-across-blocking-call or
    lock-order violations. metrics.sync()'s fsync-under-lock was the one
    genuine finding and is fixed — this test is the regression pin."""
    violations = run_lint("gym_tpu")
    conc = [v for v in violations if v.rule in ("GT102", "GT103")]
    assert conc == [], [v.render() for v in conc]


def test_metrics_sync_fsyncs_outside_the_lock(tmp_path, monkeypatch):
    """Behavioral twin of the lint pin: while sync()'s fsync is in
    flight, the metrics lock must be FREE — admission control
    (tokens_per_s_ewma) and request_done must not queue behind a disk
    stall."""
    import os as _os

    from gym_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(str(tmp_path))
    observed = {}
    real_fsync = _os.fsync

    def probing_fsync(fd):
        observed["lock_free"] = m._lock.acquire(timeout=1.0)
        if observed["lock_free"]:
            m._lock.release()
        return real_fsync(fd)

    monkeypatch.setattr(_os, "fsync", probing_fsync)
    m.sync()
    assert observed == {"lock_free": True}
    m.close()
    m.sync()   # straggler sync after close: dropped, not ValueError


# -- CLI -------------------------------------------------------------------


def test_cli_runs_lint_section_and_writes_json(tmp_path):
    from gym_tpu.analysis.__main__ import main

    out = tmp_path / "analysis.json"
    rc = main(["--only", "lint", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["violations"] == 0
    assert report["sections"]["lint"]["total"] >= 1   # suppressed GT105
