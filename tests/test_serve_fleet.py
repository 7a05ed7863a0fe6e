"""Fleet serving (ISSUE 8): the multi-replica router — health-aware
dispatch, replica-kill failover, and zero-downtime weight hot-swap.

Acceptance oracles pinned here:

- **failover oracle** — kill one of 2 replicas mid-decode: the affected
  request completes on the sibling inside its original deadline with a
  token stream IDENTICAL to an uncontended ``generate_fast`` run (no
  duplicate tokens, no gaps — partials from the dead attempt are
  discarded, never concatenated); ``Router.status()`` records the
  failover and the dead replica is excluded from dispatch.
- **hot-swap oracle** — roll new params through a 2-replica fleet under
  sustained concurrent traffic: ZERO failed/dropped requests, ZERO
  recompiles (pinned by the device-program registry's build counter),
  and post-swap generations provably come from the NEW params (exact
  ``generate_fast(params_b)`` match).
- **deadline-forwarding satellite** — a failover retry carries the
  request's REMAINING deadline (anchored at the fleet submit entry), so
  a retried request can never wait two full deadlines; a deadline
  already exhausted at failover time surfaces typed, not retried.
- **fleet shutdown drill** — ``create_server(replicas=2)`` torn down
  with in-flight requests on EVERY replica: in-flight answered (200,
  full tokens), queued failed typed (503), a wedged replica gets its
  thread stacks dumped without its engine ever being stepped.
"""

import concurrent.futures
import json
import os
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.load import CheckpointWatcher, latest_checkpoint_step
from gym_tpu.serve.metrics import ServeMetrics, read_headline
from gym_tpu.serve.router import (FleetReloadError, NoHealthyReplicaError,
                                  Router, build_fleet)
from gym_tpu.serve.scheduler import (DeadlineExceededError,
                                     EngineFailedError, RequestStatus,
                                     SchedulerClosedError)
from gym_tpu.utils.resilience import faults


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params_a = model.init({"params": jax.random.PRNGKey(0)},
                          np.zeros((1, 8), np.int64),
                          train=False)["params"]
    params_b = model.init({"params": jax.random.PRNGKey(7)},
                          np.zeros((1, 8), np.int64),
                          train=False)["params"]
    return cfg, params_a, params_b


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _prompt(n, seed, vocab=48):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, vocab))


def _fleet(params, cfg, tmp_path=None, *, replicas=2, num_slots=2,
           start=True, **kw):
    m = ServeMetrics(str(tmp_path)) if tmp_path is not None else None
    kw.setdefault("dispatch_timeout_s", 30.0)
    r = build_fleet(params, cfg, replicas=replicas, num_slots=num_slots,
                    metrics=m, log=lambda *a, **k: None, **kw)
    if start:
        r.start()
    return r, m


def _close(router, metrics):
    router.close(drain_deadline_s=30.0)
    if metrics is not None:
        metrics.close()


def _program_misses():
    # the device-program registry's shared build counter (ISSUE 9) —
    # a delta of 0 across an operation is the zero-recompile pin
    from gym_tpu.programs import compile_counter
    return compile_counter()


# -- dispatch -------------------------------------------------------------


def test_dispatch_least_loaded_and_tiebreak(setup):
    """An idle fleet ties to replica 0; a replica carrying backlog loses
    the next pick to its empty sibling."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, start=False)
    a = router.submit(_prompt(5, 0), SamplingParams(max_new_tokens=8),
                      block=False)
    assert a.replica_id == 0              # idle tie → lowest id
    b = router.submit(_prompt(5, 1), SamplingParams(max_new_tokens=8),
                      block=False)
    assert b.replica_id == 1              # replica 0 now carries backlog
    c = router.submit(_prompt(5, 2), SamplingParams(max_new_tokens=2),
                      block=False)
    assert c.replica_id in (0, 1)
    _close(router, None)


def test_dispatch_prefix_affinity_sticks_to_warm_replica(setup):
    """Paged fleets: a prompt whose prefix blocks are resident on one
    replica routes BACK to it — the admit_probe bonus beats the idle
    tie-break that would otherwise send it to replica 0."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, page_size=16,
                        kv_pages=64)
    try:
        shared = _prompt(34, 3)           # 2 full pages of shared prefix
        # park a request on replica 0 so the shared prompt lands on 1
        park = router.submit(_prompt(5, 4),
                             SamplingParams(max_new_tokens=24, seed=0))
        assert park.replica_id == 0
        warm = router.submit(shared, SamplingParams(max_new_tokens=4,
                                                    seed=1))
        assert warm.replica_id == 1
        warm.result(timeout=60)
        park.result(timeout=60)
        # both idle again: without the bonus the tie would go to 0 —
        # the resident prefix on 1 must win
        hit = router.submit(shared, SamplingParams(max_new_tokens=4,
                                                   seed=2))
        assert hit.replica_id == 1
        hit.result(timeout=60)
    finally:
        _close(router, None)


# -- failover (the acceptance oracle) -------------------------------------


def test_replica_kill_mid_decode_fails_over_exact_stream(setup, tmp_path):
    """Kill one of 2 replicas mid-decode: the request completes on the
    sibling with the EXACT uncontended token stream (no duplicates, no
    gaps), the failover is recorded, the dead replica leaves dispatch."""
    cfg, params, _ = setup
    router, m = _fleet(params, cfg, tmp_path, max_restarts=0)
    try:
        p = _prompt(6, 10)
        ref = generate_fast(params, cfg, p[None], 24, temperature=0.9,
                            top_k=7, seed=5)[0, 6:].tolist()
        fr = router.submit(p, SamplingParams(max_new_tokens=24,
                                             temperature=0.9, top_k=7,
                                             seed=5), deadline_s=60.0)
        victim = fr.replica_id
        deadline = time.perf_counter() + 30.0
        while len(fr.tokens) < 4:         # mid-decode, provably partial
            assert time.perf_counter() < deadline, "no decode progress"
            time.sleep(0.005)

        def boom(*a, **k):
            raise RuntimeError("test: injected hard engine death")

        router.replicas[victim].scheduler.engine.step = boom
        t0 = time.perf_counter()
        assert fr.result(timeout=60) == ref
        assert time.perf_counter() - t0 < 60.0   # inside the deadline
        assert fr.failovers == 1
        assert fr.replica_id != victim
        # the retry carried the REMAINING deadline, not a fresh one
        assert fr._inner.deadline_s is not None
        assert fr._inner.deadline_s < 60.0
        st = router.status()
        assert st["failovers"] == 1
        assert st["replicas"][victim]["dead"] is True
        assert st["healthy_replicas"] == 1
        # dead replica excluded: every subsequent pick is the sibling
        for i in range(3):
            nxt = router.submit(_prompt(4, 20 + i),
                                SamplingParams(max_new_tokens=2, seed=i))
            assert nxt.replica_id != victim
            assert len(nxt.result(timeout=60)) == 2
    finally:
        _close(router, m)


def test_whole_fleet_dead_degrades_typed(setup):
    """Both replicas broken: the in-flight request exhausts its failover
    budget and surfaces the TYPED engine failure; the next submit draws
    ``NoHealthyReplicaError`` (the HTTP 503), never a bare traceback."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, max_restarts=0)
    try:
        def boom(*a, **k):
            raise RuntimeError("test: injected hard engine death")

        fr = router.submit(_prompt(5, 0),
                           SamplingParams(max_new_tokens=16, seed=0))
        for rep in router.replicas:
            rep.scheduler.engine.step = boom
        # whichever race wins — sibling accepted then died (typed engine
        # failure / closed), or died first (typed 503) — the client gets
        # the fleet's TYPED answer, never a bare traceback
        with pytest.raises((EngineFailedError, SchedulerClosedError,
                            NoHealthyReplicaError)):
            fr.result(timeout=60)
        deadline = time.perf_counter() + 30.0
        while (any(not r.dead for r in router.replicas)
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        with pytest.raises(NoHealthyReplicaError):
            router.submit(_prompt(4, 1), SamplingParams(max_new_tokens=2))
    finally:
        _close(router, None)


def test_dispatch_death_window_is_health_typed_not_queue_full(setup):
    """A replica whose scheduler refuses (closing) BEFORE its supervisor
    sets ``failed`` still counts as alive — a non-blocking dispatch that
    only hit that window must surface the HEALTH signal (typed 503 +
    retry hint), never claim 'queue at capacity'."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, start=False)
    for rep in router.replicas:
        rep.scheduler.shutdown(finish_running=False, deadline_s=0.0)
    assert all(not rep.dead for rep in router.replicas)   # the window
    with pytest.raises(NoHealthyReplicaError,
                       match="shutting down or being declared dead") \
            as ei:
        router.submit(_prompt(4, 0), SamplingParams(max_new_tokens=2),
                      block=False)
    assert ei.value.retry_after_s > 0
    _close(router, None)


def test_failover_deadline_already_exhausted_is_typed(setup):
    """The satellite's hard edge: when the submit-entry-anchored
    deadline has fully elapsed by failover time, the request is NOT
    retried — it fails typed, chained to the replica death."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, start=False)
    fr = router.submit(_prompt(5, 0), SamplingParams(max_new_tokens=4),
                       deadline_s=5.0, block=False)
    fr.submit_t -= 10.0                   # elapsed > deadline_s
    router.replicas[fr.replica_id].scheduler.shutdown(
        finish_running=False, deadline_s=0.0)
    with pytest.raises(DeadlineExceededError,
                       match="during replica failover"):
        fr.result(timeout=5)
    assert fr.failovers == 0
    _close(router, None)


def test_failover_forwards_remaining_deadline(setup):
    """A queued request whose replica dies is re-dispatched with
    ``deadline_s`` minus the time already spent — never a fresh clock."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, start=False)
    fr = router.submit(_prompt(5, 0), SamplingParams(max_new_tokens=4),
                       deadline_s=30.0, block=False)
    first = fr.replica_id
    fr.submit_t -= 3.0                    # 3 s already "spent"
    router.replicas[first].scheduler.shutdown(finish_running=False,
                                              deadline_s=0.0)
    with pytest.raises(TimeoutError):     # re-queued on the sibling,
        fr.result(timeout=0.05)           # which is not running — fine
    assert fr.failovers == 1
    assert fr.replica_id != first
    assert fr._inner.deadline_s == pytest.approx(27.0, abs=1.0)
    _close(router, None)


# -- zero-downtime weight hot-swap (the acceptance oracle) ----------------


def test_rolling_hot_swap_under_traffic(setup, tmp_path):
    """Swap weights across the fleet under sustained concurrent traffic:
    zero failed requests, zero recompiles (registry builds pinned),
    and a post-swap generation that matches ``generate_fast`` under the
    NEW params exactly."""
    cfg, params_a, params_b = setup
    router, m = _fleet(params_a, cfg, tmp_path, weights_tag="v1",
                       max_restarts=2)
    try:
        probe = _prompt(6, 30)
        ref_b = generate_fast(params_b, cfg, probe[None], 8,
                              temperature=0.9, top_k=7,
                              seed=9)[0, 6:].tolist()
        # warm every program before the pinned window: the clients below
        # send prompts of 4..8 tokens, i.e. BOTH the 4- and 8-token
        # prefill buckets (the shared registry means one warm request
        # per bucket covers both replicas)
        router.submit(probe, SamplingParams(max_new_tokens=2,
                                            seed=0)).result(timeout=60)
        router.submit(_prompt(4, 31), SamplingParams(
            max_new_tokens=2, seed=0)).result(timeout=60)
        misses0 = _program_misses()

        def client(i):
            fr = router.submit(
                _prompt(4 + i % 5, 40 + i),
                SamplingParams(max_new_tokens=10, seed=i), timeout=60.0)
            return len(fr.result(timeout=120)) == 10

        reload_result = {}

        def do_reload():
            time.sleep(0.1)               # let traffic occupy the fleet
            reload_result.update(router.reload(params_b,
                                               weights_tag="v2",
                                               drain_timeout_s=60.0))

        swapper = threading.Thread(target=do_reload)
        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            futs = [ex.submit(client, i) for i in range(12)]
            swapper.start()
            results = [f.result() for f in futs]
        swapper.join(timeout=60)
        assert not swapper.is_alive()
        assert all(results), f"hot-swap dropped {results.count(False)}"
        assert sorted(reload_result["swapped"]) == [0, 1]
        assert reload_result["skipped"] == []
        assert _program_misses() == misses0      # zero recompiles
        # post-swap generations provably come from the NEW params
        fr = router.submit(probe, SamplingParams(
            max_new_tokens=8, temperature=0.9, top_k=7, seed=9))
        assert fr.result(timeout=60) == ref_b
        st = router.status()
        assert st["weight_reloads"] == 1
        assert st["weights_tag"] == "v2"
        assert all(r["weights_tag"] == "v2" for r in st["replicas"])
        # the collector's engine_reloads counts per-ENGINE swap events
        # (like engine_restarts): one rollout × two replicas — distinct
        # from the router's rollout-count weight_reloads above
        head = m.headline()
        assert head["engine_reloads"] == 2
        assert all(head["replicas"][rid]["engine_reloads"] == 1
                   for rid in ("0", "1"))
    finally:
        _close(router, m)


def test_replace_engine_bumps_epoch_against_stale_admit(setup):
    """The hot-swap race pin: a driver iteration that snapshotted
    (epoch, engine) BEFORE the swap must not admit a queued request
    into the detached old engine — ``replace_engine`` bumps the epoch,
    so the stale ``_admit_from_queue`` is a no-op and the request
    admits onto the NEW engine instead."""
    cfg, params, _ = setup
    from gym_tpu.serve.scheduler import Scheduler
    old = InferenceEngine(params, cfg, num_slots=2)
    sched = Scheduler(old, max_queue=4)
    h = sched.submit(_prompt(5, 0), SamplingParams(max_new_tokens=3,
                                                   seed=1))
    stale_epoch = sched._epoch
    sched.replace_engine(InferenceEngine(params, cfg, num_slots=2))
    assert sched._admit_from_queue(stale_epoch, old) == 0
    assert h.status is RequestStatus.QUEUED   # still queued, not lost
    assert old.stats.prefills == 0            # old engine never touched
    while h.status in (RequestStatus.QUEUED, RequestStatus.RUNNING):
        sched.step()                          # admits onto the NEW engine
    assert len(h.result(timeout=1)) == 3
    assert sched.engine.stats.prefills == 1


def test_reload_drain_timeout_is_transient_typed(setup):
    """A replica that cannot drain inside the bound aborts the rollout
    with a RETRYABLE typed error (``retry_after_s`` set → HTTP 503),
    distinct from the reload-already-rolling conflict (409)."""
    cfg, params_a, params_b = setup
    router, _m = _fleet(params_a, cfg, num_slots=1)
    try:
        faults.install("serve.decode", "delay", arg=0.05)
        fr = router.submit(_prompt(5, 0),
                           SamplingParams(max_new_tokens=40, seed=0))
        deadline = time.perf_counter() + 30.0
        while router.replicas[fr.replica_id].scheduler.inflight() == 0:
            assert time.perf_counter() < deadline, "never admitted"
            time.sleep(0.005)
        with pytest.raises(FleetReloadError, match="did not drain") as ei:
            router.reload(params_b, weights_tag="v2",
                          drain_timeout_s=0.01)
        assert ei.value.retry_after_s is not None   # transient → 503
        faults.reset()
        assert len(fr.result(timeout=60)) == 40     # request unharmed
        # the aborted rollout released the serialization flag
        res = router.reload(params_b, weights_tag="v2",
                            drain_timeout_s=60.0)
        assert sorted(res["swapped"]) == [0, 1]
    finally:
        _close(router, None)


def test_reload_skips_dead_replica_and_serializes(setup):
    """A dead replica is skipped (its eventual rebuild reads the updated
    params box anyway); a second concurrent reload is refused typed."""
    cfg, params_a, params_b = setup
    router, _m = _fleet(params_a, cfg, start=False, weights_tag="v1")
    router.replicas[0].supervisor.failed = RuntimeError("test: dead")
    res = router.reload(params_b, weights_tag="v2")
    assert res["swapped"] == [1] and res["skipped"] == [0]
    assert router.params_box["params"] is params_b
    assert router.replicas[1].scheduler.engine.weights_tag == "v2"
    router._reloading = True              # a rollout mid-flight
    with pytest.raises(FleetReloadError, match="already in progress"):
        router.reload(params_b, weights_tag="v3")
    router._reloading = False
    _close(router, None)


# -- fleet shutdown (satellite drill) -------------------------------------


def test_fleet_shutdown_inflight_answered_queued_typed(setup, tmp_path):
    """``create_server(replicas=2)`` torn down with a running request on
    EVERY replica and more queued behind them: the running ones are
    answered 200 with full tokens (one per replica — the fleet really
    was draining both), the queued ones fail typed 503."""
    cfg, params, _ = setup
    from gym_tpu.serve.__main__ import create_server
    handle = create_server(params, cfg, port=0, num_slots=1, replicas=2,
                           metrics_dir=str(tmp_path),
                           dispatch_timeout=30.0, request_timeout=120.0)
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()
    try:
        faults.install("serve.decode", "delay", arg=0.05)

        def post(i):
            body = json.dumps({"prompt": [1, 2, 3 + i],
                               "max_new_tokens": 12, "seed": i}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{handle.port}/generate", body,
                {"Content-Type": "application/json"})
            try:
                r = urllib.request.urlopen(req, timeout=120)
                return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            futs = [ex.submit(post, i) for i in range(4)]
            # both single-slot replicas running, the rest queued
            deadline = time.perf_counter() + 30.0
            while (sum(r.scheduler.active_requests()
                       for r in handle.router.replicas) < 2):
                assert time.perf_counter() < deadline, "slots never filled"
                time.sleep(0.01)
            # close drains replicas SEQUENTIALLY: freeze admission
            # fleet-wide first so a queued request cannot slip into a
            # slot replica 1 frees while replica 0 is still draining —
            # the drill pins "running answered, queued failed", not the
            # race of which queued request got lucky
            for rep in handle.router.replicas:
                rep.scheduler.pause_admission()
            handle.close(drain_deadline_s=60.0)
            results = [f.result() for f in futs]
        oks = [(c, b) for c, b in results if c == 200]
        fails = [(c, b) for c, b in results if c != 200]
        assert len(oks) == 2 and len(fails) == 2, results
        assert all(len(b["tokens"]) == 12 for _, b in oks)
        assert {b["replica"] for _, b in oks} == {0, 1}
        for code, body in fails:
            assert code == 503
            assert "shutting down" in body["error"]
    finally:
        faults.reset()
        t.join(timeout=10)


def test_fleet_close_dumps_stacks_for_wedged_replica(setup, capsys):
    """A replica whose driver never exits the drain gets its thread
    stacks dumped (per-replica evidence) and its requests failed typed
    WITHOUT its engine being stepped; siblings still drain clean."""
    cfg, params, _ = setup
    router, _m = _fleet(params, cfg, start=False)
    router.replicas[0].supervisor.stop = lambda **k: False
    q = router.submit(_prompt(4, 0), SamplingParams(max_new_tokens=4),
                      block=False)
    assert q.replica_id == 0
    assert router.close(drain_deadline_s=0.5) is False
    assert "replica 0 driver wedged" in capsys.readouterr().err
    assert q.status is RequestStatus.FAILED
    with pytest.raises(SchedulerClosedError):
        q.result(timeout=1)


# -- HTTP fleet surface ----------------------------------------------------


def test_http_fleet_stats_and_reload(setup, tmp_path):
    """The wire-level fleet story: /generate reports its replica,
    /stats carries the per-replica section, POST /reload hot-swaps the
    weights and the very next generation comes from the new params."""
    cfg, params_a, params_b = setup
    from gym_tpu.serve.__main__ import create_server
    handle = create_server(
        params_a, cfg, port=0, num_slots=2, replicas=2,
        metrics_dir=str(tmp_path), dispatch_timeout=30.0,
        request_timeout=120.0,
        reload_source=lambda body: (params_b,
                                    body.get("tag", "step-9")))
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()

    def post(path, payload):
        body = json.dumps(payload).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{handle.port}{path}", body,
            {"Content-Type": "application/json"})
        try:
            r = urllib.request.urlopen(req, timeout=120)
            return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        ref_b = generate_fast(params_b, cfg,
                              np.asarray([[1, 2, 3]]), 6,
                              temperature=1.0, top_k=4,
                              seed=0)[0, 3:].tolist()
        code, body = post("/generate", {"prompt": [1, 2, 3],
                                        "max_new_tokens": 6,
                                        "top_k": 4, "seed": 0})
        assert code == 200 and len(body["tokens"]) == 6
        assert body["replica"] in (0, 1) and body["failovers"] == 0
        assert body["tokens"] != ref_b    # still the old params
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{handle.port}/stats", timeout=30).read())
        assert stats["healthy_replicas"] == 2
        assert stats["failovers"] == 0 and stats["weight_reloads"] == 0
        assert [r["id"] for r in stats["replicas"]] == [0, 1]
        assert all(r["healthy"] for r in stats["replicas"])
        code, body = post("/reload", {"tag": "step-9"})
        assert code == 200, body
        assert sorted(body["swapped"]) == [0, 1]
        assert body["weights_tag"] == "step-9"
        code, body = post("/generate", {"prompt": [1, 2, 3],
                                        "max_new_tokens": 6,
                                        "top_k": 4, "seed": 0})
        assert code == 200 and body["tokens"] == ref_b
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{handle.port}/stats", timeout=30).read())
        assert stats["weight_reloads"] == 1
        assert stats["weights_tag"] == "step-9"
        assert stats["step"] == 9         # "step" tracks the live weights
    finally:
        handle.close()
        t.join(timeout=10)


def test_http_reload_bad_bodies_are_400_typed(setup, tmp_path):
    """Every malformed /reload body — no source configured, non-object
    JSON, non-numeric drain_timeout_s — draws a typed 400 JSON reply,
    never a handler traceback with a dropped connection."""
    cfg, params, params_b = setup
    from gym_tpu.serve.__main__ import create_server
    handle = create_server(params, cfg, port=0, num_slots=1,
                           metrics_dir=str(tmp_path),
                           dispatch_timeout=30.0)
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()

    def post_reload(raw):
        req = urllib.request.Request(
            f"http://127.0.0.1:{handle.port}/reload", raw,
            {"Content-Type": "application/json"})
        try:
            r = urllib.request.urlopen(req, timeout=30)
            return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        code, body = post_reload(b"{}")
        assert code == 400 and "no reload source" in body["error"]
    finally:
        handle.close()
        t.join(timeout=10)
    handle = create_server(
        params, cfg, port=0, num_slots=1,
        metrics_dir=str(tmp_path / "b"), dispatch_timeout=30.0,
        reload_source=lambda body: (params_b, "v2"))
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()
    try:
        for raw in (b"[1, 2]",
                    json.dumps({"drain_timeout_s": "fast"}).encode(),
                    json.dumps({"drain_timeout_s": [1]}).encode()):
            code, body = post_reload(raw)
            assert code == 400, (raw, code, body)
            assert "malformed reload body" in body["error"], body
        code, body = post_reload(b"{}")   # a good body still works
        assert code == 200 and sorted(body["swapped"]) == [0]
    finally:
        handle.close()
        t.join(timeout=10)


# -- per-replica metrics (satellite) --------------------------------------


def _fake_req(rid, tokens, ttft, lat, exc=None):
    return types.SimpleNamespace(
        id=rid, prompt=np.zeros(4, np.int32), tokens=list(range(tokens)),
        error=None if exc is None else str(exc), exception=exc,
        ttft_s=ttft, avg_token_latency_s=lat)


def test_metrics_replica_views_isolate_ewma_and_counters(tmp_path):
    """Two replicas' interleaved engine ticks must never be differenced
    against each other: each view keeps its own EWMA, and the headline
    grows a per-replica section plus fleet-aggregate rates."""
    m = ServeMetrics(str(tmp_path), engine_log_every=1)
    v0, v1 = m.replica_view(0), m.replica_view(1)
    s0 = types.SimpleNamespace(tokens_generated=0, active_slots=1)
    s1 = types.SimpleNamespace(tokens_generated=0, active_slots=1)
    v0.engine_tick(s0, queue_depth=0)
    v1.engine_tick(s1, queue_depth=0)
    time.sleep(0.02)
    s0.tokens_generated, s1.tokens_generated = 100, 10
    v0.engine_tick(s0, queue_depth=0)     # interleaved, per-replica safe
    v1.engine_tick(s1, queue_depth=0)
    e0, e1 = v0.tokens_per_s_ewma(), v1.tokens_per_s_ewma()
    assert e0 is not None and e1 is not None and e0 > e1
    assert m.tokens_per_s_ewma() == pytest.approx(e0 + e1)
    v0.request_done(_fake_req(1, 5, 0.1, 0.01), queue_depth=0,
                    active_slots=1)
    v1.request_done(_fake_req(2, 3, 0.1, 0.01,
                              exc=DeadlineExceededError("late")),
                    queue_depth=0, active_slots=1)
    v0.engine_restarted()
    v1.engine_reloaded()
    head = m.headline()
    assert head["requests_done"] == 1 and head["requests_failed"] == 1
    assert head["engine_restarts"] == 1 and head["engine_reloads"] == 1
    reps = head["replicas"]
    assert reps["0"]["requests_done"] == 1
    assert reps["0"]["engine_restarts"] == 1
    assert reps["1"]["requests_failed"] == 1
    assert reps["1"]["engine_reloads"] == 1
    assert reps["0"]["tokens_per_s_ewma"] > reps["1"]["tokens_per_s_ewma"]
    m.close()
    # the CSV round-trips the same per-replica story
    head2 = read_headline(os.path.join(str(tmp_path), "serve.csv"))
    assert head2["requests_done"] == 1
    assert head2["engine_restarts"] == 1
    assert head2["engine_reloads"] == 1
    assert head2["replicas"]["0"]["requests_done"] == 1
    assert head2["replicas"]["1"]["engine_reloads"] == 1


def test_read_headline_tolerates_pre_fleet_csv(tmp_path):
    """A pre-fleet CSV (no ``replica_id`` column — like pre-paging CSVs
    lack the KV columns) still aggregates, with NO replicas section."""
    path = tmp_path / "serve.csv"
    rows = ["ts_s,kind,request_id,status,queue_depth,active_slots,"
            "prompt_tokens,new_tokens,ttft_s,avg_token_latency_s,"
            "cum_tokens,tokens_per_s",
            "0.5,request,1,done,0,1,4,3,0.10000,0.01000,3,1.0",
            "0.9,engine,,restart,,,,,,,3,1.0"]
    path.write_text("\n".join(rows) + "\n")
    head = read_headline(str(path))
    assert head["requests_done"] == 1
    assert head["engine_restarts"] == 1
    assert head["engine_reloads"] == 0
    assert "replicas" not in head


# -- checkpoint watcher (hot-swap push half) ------------------------------


def test_checkpoint_watcher_fires_only_on_newer_committed(tmp_path):
    """Committed = the dir name is a bare integer (Orbax renames on
    commit; quarantined dirs carry a suffix). Only strictly newer steps
    fire, and a failing callback must not kill the watcher."""
    run = tmp_path / "run"
    run.mkdir()
    assert latest_checkpoint_step(str(run)) is None
    (run / "100").mkdir()
    (run / "150.corrupt-1").mkdir()
    (run / "200.tmp-orbax").mkdir()
    assert latest_checkpoint_step(str(run)) == 100
    fired = []
    w = CheckpointWatcher(str(run), fired.append, poll_s=3600.0,
                          initial_step=100)
    assert w.poll_once() is None          # nothing newer than 100
    (run / "200").mkdir()
    assert w.poll_once() == 200
    assert w.poll_once() is None          # 200 already seen
    assert fired == [200]

    def explode(step):
        fired.append(step)
        raise RuntimeError("test: reload blew up")

    w2 = CheckpointWatcher(str(run), explode, poll_s=3600.0,
                           initial_step=100)
    assert w2.poll_once() == 200          # callback error swallowed
    (run / "300").mkdir()
    assert w2.poll_once() == 300          # watcher survived, fired again
    assert fired == [200, 200, 300]


def test_checkpoint_watcher_drives_router_reload(setup, tmp_path):
    """End to end: a trainer committing a newer checkpoint dir rolls the
    new weights through the fleet via the watcher callback."""
    cfg, params_a, params_b = setup
    router, _m = _fleet(params_a, cfg, start=False, weights_tag="step-1")
    run = tmp_path / "run"
    run.mkdir()
    (run / "1").mkdir()

    def on_new_step(step):
        router.reload(params_b, weights_tag=f"step-{step}")

    w = CheckpointWatcher(str(run), on_new_step, poll_s=3600.0,
                          initial_step=1)
    assert w.poll_once() is None
    (run / "2").mkdir()
    assert w.poll_once() == 2
    st = router.status()
    assert st["weights_tag"] == "step-2"
    assert st["weight_reloads"] == 1
    _close(router, None)
