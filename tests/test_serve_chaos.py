"""Serving under fire (ISSUE 5): deadlines, load shedding, quarantine,
and the self-healing engine supervisor.

Acceptance oracles pinned here:

- **deadline oracle** — a request whose ``deadline_s`` is shorter than
  the EWMA-estimated service time is rejected AT ADMISSION (typed,
  ``retry_after_s`` hint, never enqueued) while a feasible request
  submitted concurrently still completes; an already-queued request past
  its deadline is shed BEFORE prefill, and a running one is cancelled at
  the chunk boundary with its slot freed.
- **NaN quarantine oracle** — an injected NaN in one slot's KV cache
  fails only that slot's request (typed ``SlotQuarantinedError``); a
  concurrent request in a neighbor slot returns tokens IDENTICAL to an
  uncontended ``generate_fast`` run.
- **supervisor oracle** — with ``serve.decode`` faults injected (raise
  or hang) the supervisor fails in-flight requests typed, rebuilds the
  engine WARM (global program LRUs) and resumes the queue; a wedged
  driver thread that eventually wakes is discarded by the scheduler
  epoch instead of corrupting the new generation (post-recovery tokens
  still match ``generate_fast`` exactly).

The HTTP tests drive the REAL entry point (``create_server`` — the same
stack ``python -m gym_tpu.serve`` runs) in-process on an ephemeral port.
"""

import json
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.metrics import ServeMetrics, read_headline
from gym_tpu.serve.scheduler import (AdmissionRejectedError,
                                     DeadlineExceededError,
                                     EngineFailedError, QueueFullError,
                                     RequestStatus, Scheduler,
                                     SchedulerClosedError,
                                     SlotQuarantinedError)
from gym_tpu.serve.supervisor import Supervisor
from gym_tpu.utils.resilience import FAULT_SITES, InjectedFault, faults


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng}, np.zeros((1, 8), np.int64),
                        train=False)["params"]
    return cfg, model, params


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with an empty fault registry — the
    registry is process-global and a leaked rule would poison neighbors."""
    faults.reset()
    yield
    faults.reset()


def _prompt(n, seed, vocab=48):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, vocab))


def _drain(sched, handles, limit=5000):
    for _ in range(limit):
        if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for h in handles):
            return
        sched.step()
    raise AssertionError("scheduler did not drain")


# -- fault sites ----------------------------------------------------------


def test_serve_fault_sites_registered():
    for site in ("serve.prefill", "serve.decode", "serve.admit",
                 "serve.http"):
        assert site in FAULT_SITES
    faults.configure("serve.decode:hang=5@2,serve.admit:oserror@1-3")
    assert faults.active
    faults.reset()


def test_a_requests_decode_dispatches_are_the_gates_arithmetic(setup):
    """``@N`` in a fault spec counts REAL decode dispatches, and a lone
    request of ``n`` tokens makes ``n`` of them: ``n - 1`` for the tokens
    after the prefill's and the one the round keeps in flight ahead of
    its reads. ``scripts/ci_chaos.sh`` aims its hangs by this count
    (request A of 4 tokens takes dispatches 1-4, so ``hang@5`` and
    ``hang@7`` land in request B); the script failed from the PR that
    put a step in flight until PR 46 read the count again."""
    cfg, model, params = setup
    sched = Scheduler(InferenceEngine(params, cfg, num_slots=2),
                      max_queue=8)
    faults.configure("serve.decode:delay=0")
    for n, hits in ((4, 4), (8, 12)):
        h = sched.submit(np.array([1, 2, 3]),
                         SamplingParams(max_new_tokens=n, top_k=4))
        _drain(sched, [h])
        for _ in range(3):                # idle rounds dispatch nothing
            sched.step()
        assert len(h.result(timeout=1)) == n
        assert faults.hits("serve.decode") == hits


def test_prefill_fault_fails_only_its_request(setup):
    """An injected IO error at the prefill site fails THAT request typed
    and the loop keeps serving — isolation, not collapse."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    sched = Scheduler(eng, max_queue=8)
    faults.install("serve.prefill", "oserror", first=1, last=1)
    bad = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=4))
    good = sched.submit(_prompt(6, 1), SamplingParams(max_new_tokens=4))
    _drain(sched, [bad, good])
    with pytest.raises(InjectedFault):
        bad.result(timeout=1)
    assert len(good.result(timeout=1)) == 4


def test_admit_fault_surfaces_at_submit(setup):
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    sched = Scheduler(eng, max_queue=8)
    faults.install("serve.admit", "oserror", first=1, last=1)
    with pytest.raises(InjectedFault):
        sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=2))
    # the fault window closed — the next submit serves normally
    h = sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=2))
    _drain(sched, [h])
    assert len(h.result(timeout=1)) == 2


# -- scheduler shutdown semantics (satellite) -----------------------------


def test_submit_after_shutdown_typed_and_idempotent(setup):
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng, max_queue=4)
    queued = sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=4))
    sched.shutdown(finish_running=False)
    with pytest.raises(SchedulerClosedError):
        sched.submit(_prompt(4, 1), SamplingParams(max_new_tokens=2))
    # the typed error still satisfies legacy RuntimeError handlers
    with pytest.raises(RuntimeError, match="shutting down"):
        sched.submit(_prompt(4, 1), SamplingParams(max_new_tokens=2))
    with pytest.raises(SchedulerClosedError):
        queued.result(timeout=1)
    # idempotent: a second shutdown returns immediately, no re-drain
    t0 = time.perf_counter()
    sched.shutdown(finish_running=True, deadline_s=60.0)
    assert time.perf_counter() - t0 < 1.0


def test_shutdown_drain_survives_broken_engine(setup):
    """A persistent engine fault racing the graceful drain must not kill
    the drain thread: the step exception breaks the drain loop and the
    remaining in-flight requests are failed typed — shutdown returns."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng, max_queue=4)
    h = sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=10))
    sched.step()                                 # admit into the slot
    assert h.status is RequestStatus.RUNNING
    faults.install("serve.decode", "oserror")    # every dispatch fails
    sched.shutdown(finish_running=True, deadline_s=30.0)  # must not raise
    assert h.status is RequestStatus.FAILED
    with pytest.raises(SchedulerClosedError):
        h.result(timeout=1)


# -- deadlines ------------------------------------------------------------


def test_deadline_sheds_expired_queued_before_prefill(setup):
    """A queued request whose deadline passes is shed BEFORE prefill —
    even while every slot is busy (it must not wait for a free slot just
    to be told it is late)."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng, max_queue=8)
    running = sched.submit(_prompt(4, 0),
                           SamplingParams(max_new_tokens=20))
    sched.step()                       # admit `running` into the one slot
    late = sched.submit(_prompt(4, 1), SamplingParams(max_new_tokens=4),
                        deadline_s=0.01)
    time.sleep(0.05)
    sched.step()                       # the shed sweep runs first
    assert late.status is RequestStatus.FAILED
    with pytest.raises(DeadlineExceededError, match="before prefill"):
        late.result(timeout=1)
    assert eng.stats.prefills == 1     # late never touched the engine
    _drain(sched, [running])
    assert len(running.result(timeout=1)) == 20


def test_deadline_cancels_running_at_chunk_boundary(setup):
    """A running request past deadline is cancelled at the next chunk
    boundary: partial tokens reported, typed error, slot freed for the
    next request."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, decode_chunk=2)
    sched = Scheduler(eng, max_queue=4)
    faults.install("serve.decode", "delay", arg=0.05)   # slow every chunk
    h = sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=40),
                     deadline_s=0.12)
    for _ in range(50):
        sched.step()
        if h.status is RequestStatus.FAILED:
            break
    with pytest.raises(DeadlineExceededError, match="chunk boundary"):
        h.result(timeout=1)
    assert 0 < len(h.tokens) < 40      # partial progress, then the axe
    assert len(eng.free_slots()) == 1  # the slot came back
    faults.reset()
    nxt = sched.submit(_prompt(4, 1), SamplingParams(max_new_tokens=3))
    _drain(sched, [nxt])
    assert len(nxt.result(timeout=1)) == 3


def test_deadline_caps_queue_full_wait(setup):
    """The end-to-end bound includes backpressure: a deadlined submit
    against a full queue must fail typed within ~deadline_s, not sit out
    the full queue-wait timeout and then enqueue with a fresh clock."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng, max_queue=1)
    sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=4))
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        sched.submit(_prompt(4, 1), SamplingParams(max_new_tokens=4),
                     timeout=30.0, deadline_s=0.2)
    assert time.perf_counter() - t0 < 2.0


def test_deadline_validation(setup):
    cfg, model, params = setup
    sched = Scheduler(InferenceEngine(params, cfg, num_slots=1))
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit(_prompt(4, 0), SamplingParams(max_new_tokens=2),
                     deadline_s=0.0)


# -- admission control (the deadline oracle) ------------------------------


def test_admission_rejects_infeasible_deadline(setup, tmp_path):
    """The acceptance oracle: once the tokens/s EWMA is live, a request
    with an impossible deadline is rejected at submit — typed, with a
    retry hint, NEVER enqueued — while a feasible request submitted
    concurrently completes."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    metrics = ServeMetrics(str(tmp_path))
    sched = Scheduler(eng, max_queue=8, metrics=metrics)
    # prime the EWMA the way production does: a driver loop ticking
    # metrics while real requests decode
    warm = [sched.submit(_prompt(5, i), SamplingParams(
        max_new_tokens=8, seed=i)) for i in range(2)]
    while any(h.status in (RequestStatus.QUEUED, RequestStatus.RUNNING)
              for h in warm):
        sched.step()
        metrics.engine_tick(eng.stats, queue_depth=sched.queue_depth())
    assert metrics.tokens_per_s_ewma() is not None
    depth_before = sched.queue_depth()
    with pytest.raises(AdmissionRejectedError, match="shed at admission") \
            as exc_info:
        sched.submit(_prompt(5, 7), SamplingParams(max_new_tokens=40),
                     deadline_s=1e-4)
    assert exc_info.value.retry_after_s > 0
    assert sched.queue_depth() == depth_before          # never enqueued
    assert metrics.headline()["requests_rejected"] == 1
    # a feasible request submitted right after the reject still completes
    ok = sched.submit(_prompt(5, 8), SamplingParams(max_new_tokens=6,
                                                    seed=8),
                      deadline_s=120.0)
    _drain(sched, [ok])
    assert len(ok.result(timeout=1)) == 6


# -- NaN quarantine (the quarantine oracle) -------------------------------


def test_nan_quarantine_isolates_slot(setup, tmp_path):
    """An injected NaN in one slot's KV cache fails ONLY that request
    (typed); the neighbor slot's tokens are IDENTICAL to an uncontended
    run, and the quarantined counter ticks."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    metrics = ServeMetrics(str(tmp_path))
    sched = Scheduler(eng, max_queue=4, metrics=metrics)
    pa, pb = _prompt(6, 20), _prompt(7, 21)
    ref_b = generate_fast(params, cfg, pb[None], 12, temperature=0.9,
                          top_k=7, seed=11)[0, 7:].tolist()
    ha = sched.submit(pa, SamplingParams(max_new_tokens=12,
                                         temperature=0.9, top_k=7,
                                         seed=10))
    hb = sched.submit(pb, SamplingParams(max_new_tokens=12,
                                         temperature=0.9, top_k=7,
                                         seed=11))
    sched.step()                       # both admitted + one decode step
    assert ha.status is RequestStatus.RUNNING
    slot_a = next(s for s, r in sched._by_slot.items() if r is ha)
    # poison slot A's first page (K/V) — the engine-visible shape of a
    # numerical fault confined to one row
    page_a = int(eng._bt[slot_a, 0])
    eng._cache = jax.tree.map(lambda x: x.at[page_a].set(jnp.nan),
                              eng._cache)
    _drain(sched, [ha, hb])
    with pytest.raises(SlotQuarantinedError, match="quarantined"):
        ha.result(timeout=1)
    assert hb.result(timeout=1) == ref_b       # neighbor untouched
    assert eng.stats.quarantined == 1
    head = metrics.headline()
    assert head["requests_quarantined"] == 1
    assert head["requests_done"] == 1
    # the quarantined row's pages were written over before they were
    # freed (a masked NaN still multiplies): no page keeps one
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(eng._cache))
    # the quarantined slot is free and serves cleanly again, on the
    # poisoned page itself: two requests of two pages each take the four
    # pages that were freed, A's among them
    again = [sched.submit(pb, SamplingParams(max_new_tokens=12,
                                             temperature=0.9, top_k=7,
                                             seed=11)) for _ in range(2)]
    sched.step()
    assert page_a in eng._bt
    _drain(sched, again)
    for hc in again:
        assert hc.result(timeout=1) == ref_b


def test_nan_quarantine_catches_slot_finishing_mid_chunk(setup):
    """Regression: with decode_chunk > 1, a poisoned slot that hits
    max-tokens MID-chunk goes inactive before the chunk tail — the
    quarantine check must still catch it (its final-step logits flow
    from the NaN cache rows), not deliver the garbage as a completed
    request. The neighbour row, on pages of its own, is not touched."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=4)
    # max_new=3: one token from prefill, two from the next chunk — the
    # slot deactivates at scanned step 2 of 4, well before the tail
    slot, ev = eng.admit(_prompt(6, 30), SamplingParams(max_new_tokens=3,
                                                        seed=12))
    other, _ = eng.admit(_prompt(7, 31), SamplingParams(max_new_tokens=9,
                                                        seed=13))
    assert not ev.finished
    page = int(eng._bt[slot, 0])
    eng._cache = jax.tree.map(lambda x: x.at[page].set(jnp.nan),
                              eng._cache)
    events = eng.step()
    mine = [e for e in events if e.slot == slot]
    assert mine and all(e.poisoned for e in mine)
    theirs = [e for e in events if e.slot == other]
    assert len(theirs) == 4 and not any(e.poisoned for e in theirs)
    assert eng.stats.quarantined == 1
    assert slot in eng.free_slots() and other not in eng.free_slots()


@pytest.mark.parametrize("readers", [1, 2])
def test_nan_quarantine_forgets_a_poisoned_prefix_page(setup, readers):
    """A poisoned page that holds a full prompt block is in the prefix
    table: when the last row that reads it is quarantined it leaves the
    table and is written over, so the same prompt sent again is
    prefilled anew and served, not handed the NaNs."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    prompt = _prompt(20, 40)                   # block 0 is full: hashed
    sp = SamplingParams(max_new_tokens=6, temperature=0.8, top_k=5, seed=4)
    slots = [eng.admit(prompt, sp)[0] for _ in range(readers)]
    shared = int(eng._bt[slots[0], 0])
    assert all(int(eng._bt[s, 0]) == shared for s in slots)
    assert eng.stats.prefix_hit_blocks == readers - 1
    eng._cache = jax.tree.map(lambda x: x.at[shared].set(jnp.nan),
                              eng._cache)
    assert all(e.poisoned for e in eng.step())
    assert eng.stats.quarantined == readers
    assert eng._alloc.cached() == 0 and eng._alloc.in_use() == 0
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(eng._cache))
    hits = eng.stats.prefix_hit_blocks
    slot, ev = eng.admit(prompt, sp)
    assert eng.stats.prefix_hit_blocks == hits      # prefilled anew
    got = [ev.token]
    while slot not in eng.free_slots():
        got += [e.token for e in eng.step()]
    ref = generate_fast(params, cfg, prompt[None], 6, temperature=0.8,
                        top_k=5, seed=4)
    assert got == ref[0, 20:].tolist()


# -- supervisor -----------------------------------------------------------


def _make_supervised(params, cfg, num_slots=2, metrics=None, **sup_kw):
    def factory():
        return InferenceEngine(params, cfg, num_slots=num_slots)
    sched = Scheduler(factory(), max_queue=16, metrics=metrics)
    sup = Supervisor(sched, factory, metrics=metrics, log=lambda *a, **k:
                     None, **sup_kw)
    return sched, sup


def test_supervisor_recovers_engine_exception(setup):
    """serve.decode raises at dispatch 2: the in-flight request fails
    typed, the engine is rebuilt, the next request completes — and the
    rebuild is WARM: same config, same device-program registry, so the
    failover rebuild + recovery request trigger ZERO new program builds
    (the supervisor-failover zero-recompile seam, ISSUE 9)."""
    from gym_tpu.programs import compile_counter

    cfg, model, params = setup
    sched, sup = _make_supervised(params, cfg, dispatch_timeout_s=30.0,
                                  max_restarts=3)
    faults.install("serve.decode", "oserror", first=2, last=2)
    sup.start()
    try:
        h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=8,
                                                       seed=3))
        with pytest.raises(EngineFailedError, match="InjectedFault"):
            h.result(timeout=60)
        # the failed request built everything this config/bucket needs;
        # everything from here — the supervisor's engine rebuild and the
        # recovery request — must be served by the shared registry
        builds0 = compile_counter()
        assert sup.restarts == 1
        ref = generate_fast(params, cfg, _prompt(5, 1)[None], 6,
                            temperature=0.8, top_k=5, seed=4)
        h2 = sched.submit(_prompt(5, 1), SamplingParams(
            max_new_tokens=6, temperature=0.8, top_k=5, seed=4))
        assert h2.result(timeout=60) == ref[0, 5:].tolist()
        assert sup.failed is None
        assert compile_counter() == builds0   # zero-recompile failover
    finally:
        sup.stop(join_timeout_s=10)


def test_supervisor_recovers_wedged_dispatch(setup):
    """serve.decode hangs at dispatch 2: the watchdog reaps the wedged
    driver, in-flight fails typed WITHIN the watchdog deadline, the
    rebuilt engine serves exact tokens — and when the abandoned thread
    finally wakes, the scheduler epoch discards it (post-wake requests
    still match generate_fast: no cross-generation corruption)."""
    cfg, model, params = setup
    sched, sup = _make_supervised(params, cfg, dispatch_timeout_s=0.4,
                                  max_restarts=3)
    faults.install("serve.decode", "hang", arg=1.5, first=2, last=2)
    sup.start()
    try:
        t0 = time.perf_counter()
        h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=8,
                                                       seed=3))
        with pytest.raises(EngineFailedError, match="wedged"):
            h.result(timeout=60)
        assert time.perf_counter() - t0 < 10.0   # typed failure, fast
        assert sup.restarts == 1
        ref = generate_fast(params, cfg, _prompt(6, 1)[None], 6,
                            temperature=0.8, top_k=5, seed=4)
        h2 = sched.submit(_prompt(6, 1), SamplingParams(
            max_new_tokens=6, temperature=0.8, top_k=5, seed=4))
        assert h2.result(timeout=60) == ref[0, 6:].tolist()
        time.sleep(1.6)              # let the abandoned thread wake up
        h3 = sched.submit(_prompt(6, 1), SamplingParams(
            max_new_tokens=6, temperature=0.8, top_k=5, seed=4))
        assert h3.result(timeout=60) == ref[0, 6:].tolist()
    finally:
        sup.stop(join_timeout_s=10)


def test_supervisor_max_restarts_declares_dead(setup):
    """A permanently-broken engine must not crash-loop forever: past
    max_restarts the supervisor fails queued requests typed and stops;
    submit turns into a typed refusal. The server process survives."""
    cfg, model, params = setup
    sched, sup = _make_supervised(params, cfg, dispatch_timeout_s=30.0,
                                  max_restarts=1)
    faults.install("serve.decode", "oserror")          # every dispatch
    sup.start()
    try:
        h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=8))
        with pytest.raises(EngineFailedError):
            h.result(timeout=60)
        assert sup.restarts == 1
        # the rebuilt engine is just as broken: the next request's first
        # dispatch faults again, which exceeds max_restarts
        h2 = sched.submit(_prompt(5, 1), SamplingParams(max_new_tokens=8))
        with pytest.raises(EngineFailedError):
            h2.result(timeout=60)
        deadline = time.perf_counter() + 30.0
        while sup.failed is None and time.perf_counter() < deadline:
            time.sleep(0.05)
        assert sup.failed is not None
        assert sup.restarts == 2                       # 1 allowed + fatal
        with pytest.raises(SchedulerClosedError):
            sched.submit(_prompt(5, 1), SamplingParams(max_new_tokens=2))
    finally:
        sup.stop(join_timeout_s=10)


def test_failover_fails_request_wedged_in_admission(setup):
    """A request popped from the queue but wedged INSIDE engine.admit is
    in neither _queue nor _by_slot — failover must still resolve its
    future typed instead of leaving the client to its wall-clock
    timeout."""
    cfg, model, params = setup
    sched, sup = _make_supervised(params, cfg, dispatch_timeout_s=0.4,
                                  max_restarts=3)
    faults.install("serve.prefill", "hang", arg=1.5, first=1, last=1)
    sup.start()
    try:
        t0 = time.perf_counter()
        h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=6,
                                                       seed=3))
        with pytest.raises(EngineFailedError, match="wedged"):
            h.result(timeout=60)
        assert time.perf_counter() - t0 < 10.0
        # the rebuilt engine serves; the abandoned thread, when it wakes
        # from the hung prefill, must not resurrect the failed request
        h2 = sched.submit(_prompt(5, 1), SamplingParams(max_new_tokens=4,
                                                        seed=4))
        assert len(h2.result(timeout=60)) == 4
        time.sleep(1.6)              # let the abandoned thread wake
        assert h.status is RequestStatus.FAILED
        h3 = sched.submit(_prompt(5, 2), SamplingParams(max_new_tokens=4,
                                                        seed=5))
        assert len(h3.result(timeout=60)) == 4
    finally:
        sup.stop(join_timeout_s=10)


def test_supervisor_clean_stop_is_not_a_failure(setup):
    cfg, model, params = setup
    sched, sup = _make_supervised(params, cfg, dispatch_timeout_s=30.0)
    sup.start()
    h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=5,
                                                   seed=2))
    assert len(h.result(timeout=60)) == 5
    assert sup.stop(join_timeout_s=10)
    assert sup.restarts == 0 and sup.failed is None


# -- metrics: percentiles + synthetic CSV (satellite) ---------------------


def _fake_req(rid, tokens, ttft, lat, exc=None):
    return types.SimpleNamespace(
        id=rid, prompt=np.zeros(4, np.int32), tokens=list(range(tokens)),
        error=None if exc is None else str(exc), exception=exc,
        ttft_s=ttft, avg_token_latency_s=lat)


def test_metrics_percentiles_in_headline(tmp_path):
    m = ServeMetrics(str(tmp_path))
    for i in range(1, 101):          # ttft 0.01..1.00, lat 0.001..0.100
        m.request_done(_fake_req(i, 4, i / 100.0, i / 1000.0),
                       queue_depth=0, active_slots=1)
    head = m.headline()
    assert head["requests_done"] == 100
    # np.percentile linear interpolation over 0.01..1.00
    assert head["ttft_p50_s"] == 0.505
    assert head["ttft_p95_s"] == 0.9505
    assert head["ttft_p99_s"] == 0.9901
    assert head["token_lat_p50_s"] == 0.0505
    assert head["token_lat_p95_s"] == 0.09505
    assert head["token_lat_p99_s"] == 0.09901
    m.close()


def test_metrics_ewma_and_status_rows(tmp_path):
    m = ServeMetrics(str(tmp_path), engine_log_every=1)
    stats = types.SimpleNamespace(tokens_generated=0, active_slots=1)
    m.engine_tick(stats, queue_depth=0)
    time.sleep(0.02)
    stats.tokens_generated = 100
    m.engine_tick(stats, queue_depth=0)
    ewma = m.tokens_per_s_ewma()
    assert ewma is not None and ewma > 0
    # an engine rebuild resets the token counter; the EWMA must survive
    m.engine_restarted()
    stats.tokens_generated = 3
    m.engine_tick(stats, queue_depth=0)
    assert m.tokens_per_s_ewma() == ewma
    # typed failures land typed in the CSV
    m.request_done(_fake_req(1, 2, 0.1, 0.01,
                             exc=DeadlineExceededError("late")),
                   queue_depth=0, active_slots=1)
    m.request_done(_fake_req(2, 2, 0.1, 0.01,
                             exc=SlotQuarantinedError("nan")),
                   queue_depth=0, active_slots=1)
    m.request_rejected(queue_depth=0, active_slots=1)
    head = m.headline()
    assert head["requests_shed"] == 1
    assert head["requests_quarantined"] == 1
    assert head["requests_rejected"] == 1
    assert head["engine_restarts"] == 1
    m.close()


def test_metrics_ewma_resets_after_idle(tmp_path):
    """A stale-low EWMA must not reject deadline'd requests forever: a
    fully idle engine (no slots, no queue, no flow) past the reset
    window goes COLD (EWMA None → optimistic admission). A busy-but-
    stalled engine keeps its honest low rate."""
    m = ServeMetrics(str(tmp_path), ewma_idle_reset_s=0.05)
    stats = types.SimpleNamespace(tokens_generated=0, active_slots=1)
    m.engine_tick(stats, queue_depth=0)
    time.sleep(0.01)
    stats.tokens_generated = 5           # a slow burst: low rate
    m.engine_tick(stats, queue_depth=0)
    assert m.tokens_per_s_ewma() is not None
    # busy-but-stalled: rate survives (the low estimate is the truth)
    stats.active_slots = 1
    time.sleep(0.06)
    m.engine_tick(stats, queue_depth=0)
    time.sleep(0.06)
    m.engine_tick(stats, queue_depth=0)
    assert m.tokens_per_s_ewma() is not None
    # fully idle past the window: cold again
    stats.active_slots = 0
    m.engine_tick(stats, queue_depth=0)
    time.sleep(0.06)
    m.engine_tick(stats, queue_depth=0)
    assert m.tokens_per_s_ewma() is None
    m.close()


def test_read_headline_synthetic_csv(tmp_path):
    """read_headline recomputes the live headline from serve.csv alone —
    pinned on a synthetic file with known percentiles and counts."""
    path = tmp_path / "serve.csv"
    rows = ["ts_s,kind,request_id,status,queue_depth,active_slots,"
            "prompt_tokens,new_tokens,ttft_s,avg_token_latency_s,"
            "cum_tokens,tokens_per_s"]
    for i in range(1, 101):
        rows.append(f"{i / 10.0:.4f},request,{i},done,0,1,4,3,"
                    f"{i / 100.0:.5f},{i / 1000.0:.5f},{3 * i},1.0")
    rows.append("10.2,request,101,shed,0,1,4,1,0.5,,301,1.0")
    rows.append("10.3,request,102,quarantined,1,1,4,2,0.5,0.1,303,1.0")
    rows.append("10.4,request,,rejected,1,1,,,,,303,1.0")
    rows.append("10.5,engine,,restart,,,,,,,303,1.0")
    rows.append("10.6,engine,,,0,0,,,,,303,1.0")
    path.write_text("\n".join(rows) + "\n")
    head = read_headline(str(path))
    assert head["requests_done"] == 100
    assert head["requests_failed"] == 2
    assert head["requests_shed"] == 1
    assert head["requests_quarantined"] == 1
    assert head["requests_rejected"] == 1
    assert head["engine_restarts"] == 1
    assert head["tokens_out"] == 303
    assert head["wall_s"] == 10.6
    # percentiles over the 100 done + 2 failed ttfts (102 samples)
    assert head["ttft_p99_s"] == pytest.approx(0.9899, abs=1e-4)
    assert head["mean_token_latency_s"] is not None


# -- HTTP entry point -----------------------------------------------------


@pytest.fixture()
def http_server(setup, tmp_path):
    cfg, model, params = setup
    from gym_tpu.serve.__main__ import create_server
    handle = create_server(params, cfg, port=0, num_slots=2,
                           metrics_dir=str(tmp_path),
                           dispatch_timeout=30.0, request_timeout=120.0)
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()
    yield handle
    handle.close()
    t.join(timeout=10)


def _post(port, body_bytes, headers=None, path="/generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", body_bytes,
        {"Content-Type": "application/json", **(headers or {})})
    try:
        r = urllib.request.urlopen(req, timeout=120)
        return r.status, json.loads(r.read()), r.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


def test_http_malformed_json_is_400(http_server):
    code, body, _ = _post(http_server.port, b"{not json")
    assert code == 400
    assert "malformed JSON" in body["error"]
    code, body, _ = _post(http_server.port, b"[1, 2, 3]")
    assert code == 400
    assert "must be an object" in body["error"]


def test_http_oversized_prompt_is_400_typed(http_server):
    payload = json.dumps({"prompt": list(range(40)),
                          "max_new_tokens": 40}).encode()
    code, body, _ = _post(http_server.port, payload)
    assert code == 400
    assert "exceeds the KV cache" in body["error"]
    code, body, _ = _post(http_server.port, json.dumps(
        {"prompt": [1, 2, 999]}).encode())
    assert code == 400
    assert "token ids" in body["error"]


def test_http_roundtrip_and_deadline_reject(http_server):
    """Happy path primes the EWMA; an infeasible deadline (body field or
    X-Deadline-S header) then draws 429 + Retry-After; a feasible request
    still completes — load shedding under deadline pressure."""
    ok = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6,
                     "top_k": 4, "seed": 0}).encode()
    for _ in range(2):
        code, body, _ = _post(http_server.port, ok)
        assert code == 200 and len(body["tokens"]) == 6
    infeasible = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 40,
                             "deadline_s": 1e-4}).encode()
    code, body, headers = _post(http_server.port, infeasible)
    assert code == 429
    assert "shed at admission" in body["error"]
    assert int(headers["Retry-After"]) >= 1
    # header spelling of the same deadline
    code, body, headers = _post(
        http_server.port,
        json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 40}).encode(),
        headers={"X-Deadline-S": "0.0001"})
    assert code == 429 and headers["Retry-After"] is not None
    code, body, _ = _post(http_server.port, ok)
    assert code == 200 and len(body["tokens"]) == 6
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{http_server.port}/stats", timeout=30).read())
    assert stats["requests_rejected"] == 2
    assert stats["engine_restarts"] == 0
    assert stats["ttft_p50_s"] is not None


def test_http_fault_site_is_503_not_traceback(http_server):
    faults.install("serve.http", "oserror", first=1, last=1)
    code, body, headers = _post(http_server.port, json.dumps(
        {"prompt": [1, 2, 3], "max_new_tokens": 2}).encode())
    assert code == 503
    assert "InjectedFault" in body["error"]
    assert headers["Retry-After"] is not None


def test_http_engine_wedge_recovery(setup, tmp_path):
    """The chaos drill in-process: a hung decode dispatch fails the
    in-flight request typed (503, within its deadline) while the server
    stays up; the supervisor rebuilds the engine and the next request
    succeeds."""
    cfg, model, params = setup
    from gym_tpu.serve.__main__ import create_server
    handle = create_server(params, cfg, port=0, num_slots=2,
                           metrics_dir=str(tmp_path),
                           dispatch_timeout=0.5, request_timeout=120.0)
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()
    try:
        faults.install("serve.decode", "hang", arg=1.5, first=2, last=2)
        t0 = time.perf_counter()
        code, body, _ = _post(handle.port, json.dumps(
            {"prompt": [1, 2, 3], "max_new_tokens": 8,
             "deadline_s": 30.0}).encode())
        elapsed = time.perf_counter() - t0
        assert code == 503                    # engine fault ≠ 500
        assert "EngineFailedError" in body["error"]
        assert elapsed < 30.0                 # inside the deadline
        code, body, _ = _post(handle.port, json.dumps(
            {"prompt": [1, 2, 3], "max_new_tokens": 6,
             "top_k": 4, "seed": 1}).encode())
        assert code == 200 and len(body["tokens"]) == 6
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{handle.port}/stats", timeout=30).read())
        assert stats["engine_restarts"] == 1
        assert stats["status"] == "ok"
        # let the abandoned hung thread wake and self-discard BEFORE the
        # interpreter exits — a daemon thread reaped mid-C-call aborts
        # the process ("terminate called without an active exception")
        time.sleep(max(0.0, 1.6 - (time.perf_counter() - t0)))
    finally:
        handle.close()
        t.join(timeout=10)
