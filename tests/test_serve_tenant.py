"""Multi-tenant serving (ISSUE 17): SLO classes, token quotas,
weighted-fair scheduling and preemptible decode.

Oracles:
- QUOTA DETERMINISM: the refill bucket under an injected fake clock
  admits/rejects on exact token arithmetic — typed
  ``QuotaExceededError`` (an ``AdmissionRejectedError`` subclass, so
  the whole 429 + Retry-After surface applies unchanged).
- WFQ: with multiple tenants queued, an interactive tenant's head
  beats a batch flood to the slot; the queue HEAD is still admitted
  within ``starvation_rounds`` passes (the PR-7 anti-starvation
  contract, now covering fair-queuing skips too); a single tenant
  keeps exact FCFS.
- PREEMPT-RESUME EXACTNESS: a batch request parked mid-decode for an
  interactive one resumes and finishes BYTE-IDENTICAL to an
  uncontended run — the per-token ``fold_in(base, gen_idx)`` key
  schedule makes this an equality oracle, not a tolerance.
- TYPED, NEVER SILENT: a parked request caught in an engine failover
  resolves with ``EngineFailedError`` — its future never hangs.
- WIRE/WORKER HYGIENE: ``QuotaExceededError`` survives the socket hop
  typed with its retry hint; a submit frame carrying UNKNOWN fields is
  served with a stderr note, never rejected (mixed-version fleets
  degrade soft).
"""

import threading
import time
import types

import numpy as np
import pytest

import jax

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.serve import wire
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import (CLASS_PRIORITY, ClassQuota,
                                     EngineFailedError,
                                     QuotaExceededError,
                                     AdmissionRejectedError,
                                     RequestStatus, Scheduler)
from gym_tpu.serve.worker import _SUBMIT_FIELDS, WorkerServer
from gym_tpu.servesim import cost_model


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64),
                        train=False)["params"]
    return cfg, model, params


def _prompt(n, seed, vocab=48):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n,), 0, vocab))


def _drain(sched, handles, limit=5000):
    for _ in range(limit):
        if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for h in handles):
            return
        sched.step()
    raise AssertionError("scheduler did not drain")


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# -- quotas ---------------------------------------------------------------


def test_class_priority_mirrors_cost_model():
    """The sweep's jax-free cost model duplicates the scheduler's
    priority table (importing the scheduler would drag jax into the
    fast path) — this pin is what allows the duplication."""
    assert cost_model._CLASS_PRIORITY == CLASS_PRIORITY


def test_quota_refill_determinism_fake_clock(setup):
    """Exact bucket arithmetic under a stepped clock: cap = rate ×
    burst_s tokens, a dry class rejects typed with a computable
    Retry-After, and the advertised retry interval is precisely what
    refills enough budget."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    clock = FakeClock()
    sched = Scheduler(
        eng, quotas={"batch": ClassQuota(tokens_per_s=10.0,
                                         burst_s=1.0)},
        quota_clock=clock)
    sp = SamplingParams(max_new_tokens=8, seed=1)
    # cap = 10; first take: 10 -> 2
    r1 = sched.submit(_prompt(8, 1), sp, slo_class="batch")
    # second take needs 8 > 2 -> typed reject, retry = (8-2)/10
    with pytest.raises(QuotaExceededError) as ei:
        sched.submit(_prompt(8, 2), sp, slo_class="batch")
    assert isinstance(ei.value, AdmissionRejectedError)
    assert ei.value.retry_after_s == pytest.approx(0.6)
    assert sched.quota_rejections == {"batch": 1}
    # other classes are not rate-limited by batch's bucket
    r3 = sched.submit(_prompt(8, 3), sp, slo_class="interactive")
    # advancing the clock past the advertised retry refills the bucket
    # (an epsilon over: the refill itself is float arithmetic)
    clock.t += ei.value.retry_after_s + 1e-3
    r4 = sched.submit(_prompt(8, 4), sp, slo_class="batch")
    _drain(sched, [r1, r3, r4])
    assert [len(r.tokens) for r in (r1, r3, r4)] == [8, 8, 8]
    snap = sched.tenant_snapshot()
    assert snap["quota_rejections"] == {"batch": 1}
    assert snap["quota_fill"]["batch"] < 0.05


def test_quota_oversize_request_passes_at_full_bucket(setup):
    """A request larger than the whole bucket is admitted when the
    bucket is FULL (level goes negative — long-run rate enforcement),
    instead of starving forever behind an unpassable bar."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    clock = FakeClock()
    sched = Scheduler(
        eng, quotas={"batch": ClassQuota(tokens_per_s=4.0,
                                         burst_s=1.0)},
        quota_clock=clock)
    big = SamplingParams(max_new_tokens=16, seed=1)   # 4x the cap
    r1 = sched.submit(_prompt(8, 1), big, slo_class="batch")
    with pytest.raises(QuotaExceededError):
        sched.submit(_prompt(8, 2), big, slo_class="batch")
    _drain(sched, [r1])
    assert len(r1.tokens) == 16


def test_unknown_slo_class_rejected_typed(setup):
    """A typo'd class must fail loudly (HTTP 400), not silently map to
    some default priority — that would be an isolation hole."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="unknown slo_class"):
        sched.submit(_prompt(8, 1), SamplingParams(max_new_tokens=4),
                     slo_class="premium")


# -- weighted-fair queuing ------------------------------------------------


def test_single_tenant_keeps_fcfs_order(setup):
    """The default deployment (one tenant, no shared prefix) must keep
    the exact pre-tenant admission order: FCFS."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng)
    sp = SamplingParams(max_new_tokens=4, seed=0)
    reqs = [sched.submit(_prompt(8, i), sp) for i in range(4)]
    _drain(sched, reqs)
    firsts = [r.first_token_t for r in reqs]
    assert firsts == sorted(firsts)


def test_wfq_interactive_head_beats_batch_flood(setup):
    """Two tenants queued: the interactive tenant's head (weight 8)
    carries the earliest virtual finish tag and wins the first free
    slot even though the batch flood (weight 1) queued first."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng)
    flood = [sched.submit(_prompt(8, i),
                          SamplingParams(max_new_tokens=8, seed=i),
                          tenant="tenant_b", slo_class="batch")
             for i in range(6)]
    victim = sched.submit(_prompt(8, 99),
                          SamplingParams(max_new_tokens=4, seed=99),
                          tenant="tenant_a", slo_class="interactive")
    _drain(sched, flood + [victim])
    assert victim.done_t < min(b.done_t for b in flood)


def test_wfq_starvation_bound_admits_head(setup):
    """A batch head passed over by fair-queuing skips must still admit
    within ``starvation_rounds`` passes — the PR-7 anti-starvation
    contract extended to WFQ: interactive pressure cannot starve batch
    unboundedly."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1)
    sched = Scheduler(eng, starvation_rounds=2)
    head = sched.submit(_prompt(8, 0),
                        SamplingParams(max_new_tokens=4, seed=0),
                        tenant="tenant_b", slo_class="batch")
    inter = [sched.submit(_prompt(8, 1 + i),
                          SamplingParams(max_new_tokens=4, seed=1 + i),
                          tenant="tenant_a", slo_class="interactive")
             for i in range(6)]
    _drain(sched, [head] + inter)
    # the head may lose at most starvation_rounds + 1 admissions
    later = sorted(r.done_t for r in inter)
    assert head.done_t < later[3], \
        "batch head starved past the starvation_rounds bound"


# -- preemptible decode ---------------------------------------------------


def _uncontended(params, cfg, prompt, sp, **engine_kw):
    eng = InferenceEngine(params, cfg, **engine_kw)
    slot, ev = eng.admit(prompt, sp)
    toks = [ev.token]
    while not ev.finished:
        evs = [e for e in eng.step() if e.slot == slot]
        assert evs
        ev = evs[-1]
        toks.extend(e.token for e in evs)
    return toks


def test_preempt_parks_batch_resumes_byte_identical(setup):
    """The tentpole oracle: a batch request parked mid-decode for an
    interactive arrival finishes with EXACTLY the token stream of an
    uncontended run — equality, not tolerance (the per-token
    ``fold_in(base, gen_idx)`` key schedule is position-keyed, so the
    park/resume round-trip through host memory must be invisible)."""
    cfg, model, params = setup
    kw = dict(num_slots=1, paged=True, page_size=8, kv_pages=64)
    batch_prompt = _prompt(8, 7)
    batch_sp = SamplingParams(max_new_tokens=24, temperature=0.9,
                              top_k=7, seed=7)
    ref = _uncontended(params, cfg, batch_prompt, batch_sp, **kw)

    eng = InferenceEngine(params, cfg, **kw)
    sched = Scheduler(eng, preempt=True)
    batch = sched.submit(batch_prompt, batch_sp,
                         tenant="tenant_b", slo_class="batch")
    for _ in range(200):
        sched.step()
        if len(batch.tokens) >= 4:
            break
    assert len(batch.tokens) >= 4 and batch.status is \
        RequestStatus.RUNNING
    inter = sched.submit(_prompt(8, 42),
                         SamplingParams(max_new_tokens=6, seed=42),
                         tenant="tenant_a", slo_class="interactive")
    _drain(sched, [inter, batch])
    assert sched.preemptions >= 1 and sched.resumes >= 1
    assert batch.preemptions >= 1
    # the interactive request got the slot while batch was parked
    assert inter.done_t < batch.done_t
    # byte-identical resume: the oracle
    assert batch.tokens == ref
    # and the interactive stream equals ITS uncontended run too
    assert inter.tokens == _uncontended(
        params, cfg, _prompt(8, 42),
        SamplingParams(max_new_tokens=6, seed=42), **kw)


def test_preempt_never_within_same_class(setup):
    """Preemption runs only in favor of a STRICTLY more urgent class —
    same-class traffic must never thrash slots back and forth."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, paged=True,
                          page_size=8, kv_pages=64)
    sched = Scheduler(eng, preempt=True)
    sp = SamplingParams(max_new_tokens=8, seed=1)
    a = sched.submit(_prompt(8, 1), sp, slo_class="batch")
    b = sched.submit(_prompt(8, 2), sp, slo_class="batch")
    _drain(sched, [a, b])
    assert sched.preemptions == 0


def test_parked_request_fails_typed_on_engine_death(setup):
    """A replica dying while holding a PARKED request (its pinned pages
    died with the engine's pool) must resolve that request's future
    typed — never a silent drop, never a hang."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=1, paged=True,
                          page_size=8, kv_pages=64)
    sched = Scheduler(eng, preempt=True)
    batch = sched.submit(_prompt(8, 7),
                         SamplingParams(max_new_tokens=24, seed=7),
                         tenant="tenant_b", slo_class="batch")
    for _ in range(200):
        sched.step()
        if len(batch.tokens) >= 2:
            break
    sched.submit(_prompt(8, 42),
                 SamplingParams(max_new_tokens=6, seed=42),
                 tenant="tenant_a", slo_class="interactive")
    for _ in range(50):
        sched.step()
        if sched.preemptions:
            break
    assert sched.preemptions >= 1
    snap = sched.tenant_snapshot()
    assert snap["parked"] == 1
    victims = sched.fail_inflight(
        EngineFailedError("engine died under chaos"))
    assert batch in victims
    with pytest.raises(EngineFailedError):
        batch.result(timeout=1.0)
    assert sched.tenant_snapshot()["parked"] == 0


# -- wire + worker hygiene ------------------------------------------------


def test_quota_error_survives_the_socket_typed():
    exc = QuotaExceededError("slo_class=batch token quota exhausted",
                             retry_after_s=2.5)
    frame = wire.exception_to_frame(7, exc)
    back = wire.frame_to_exception(
        wire.decode_payload(wire.encode_frame(frame)[4:]))
    assert type(back) is QuotaExceededError
    assert back.retry_after_s == pytest.approx(2.5)
    assert isinstance(back, AdmissionRejectedError)


def test_submit_fields_pin():
    """The worker's known-field set must cover everything the router
    sends today — adding a field to the ROUTER without teaching the
    worker produces a stderr note on every request, which this pin
    turns into a test failure instead of silent log spam."""
    assert {"type", "id", "prompt", "sampling", "prefix",
            "deadline_s", "stream", "submit_timeout", "coalesce_s",
            "tenant", "slo_class"} <= _SUBMIT_FIELDS


def test_unknown_submit_field_served_with_note(setup, capsys):
    """A submit frame carrying a field this worker has never heard of
    is served normally (ignored-with-note) — the mixed-version-fleet
    contract: an old worker behind a new router degrades soft, it does
    not reject traffic."""
    cfg, model, params = setup
    eng = InferenceEngine(params, cfg, num_slots=2)
    sched = Scheduler(eng)
    stop = threading.Event()
    driver = threading.Thread(target=sched.run, args=(stop,),
                              daemon=True)
    driver.start()
    stub = types.SimpleNamespace(scheduler=sched)
    sent = []

    def send(frame):
        sent.append(frame)
        return True

    frame = {"type": "submit", "id": "r1",
             "prompt": _prompt(8, 3).tolist(),
             "sampling": {"max_new_tokens": 5, "seed": 3},
             "tenant": "tenant_a", "slo_class": "interactive",
             "qos_hint": "gold-plated"}         # the unknown field
    try:
        WorkerServer._stream_request(stub, frame, send, {}, set(),
                                     threading.Lock())
    finally:
        stop.set()
        driver.join(timeout=10)
    err = capsys.readouterr().err
    assert "unknown fields ['qos_hint']" in err
    assert sent[0] == {"type": "accepted", "id": "r1"}
    done = [f for f in sent if f["type"] == "done"]
    assert done and done[0]["tokens_total"] == 5
    # and the stream is still exact: tenant plumbing changed nothing
    chunks = [t for f in sent if f["type"] == "chunk"
              for t in f["tokens"]]
    ref = generate_fast(params, cfg, _prompt(8, 3)[None], 5,
                        seed=3)[0, 8:].tolist()
    assert chunks == ref


# -- per-class metrics ----------------------------------------------------


def _fake_req(rid, tokens, ttft, lat, tenant=None, slo_class=None):
    return types.SimpleNamespace(
        id=rid, prompt=np.zeros(4, np.int32),
        tokens=list(range(tokens)), error=None, exception=None,
        ttft_s=ttft, avg_token_latency_s=lat,
        tenant=tenant, slo_class=slo_class)


def test_metrics_per_class_headline_and_csv_roundtrip(tmp_path):
    """``headline()`` and ``read_headline`` agree on the per-class
    breakdown: TTFT tails split by slo_class, preempt/resume event
    rows counted WITHOUT double-counting tokens (events carry a blank
    new_tokens cell; tokens land once, on the completion row)."""
    from gym_tpu.serve.metrics import ServeMetrics, read_headline
    m = ServeMetrics(str(tmp_path))
    for i in range(1, 11):
        m.request_done(
            _fake_req(i, 4, i / 100.0, 0.01,
                      tenant="tenant_a", slo_class="interactive"),
            queue_depth=0, active_slots=1)
    batch = _fake_req(99, 8, 0.5, 0.01, tenant="tenant_b",
                      slo_class="batch")
    m.request_preempted(batch, queue_depth=1, active_slots=1)
    m.request_resumed(batch, queue_depth=0, active_slots=1)
    m.request_done(batch, queue_depth=0, active_slots=1)
    m.request_rejected(queue_depth=0, active_slots=1,
                       tenant="tenant_b", slo_class="batch")
    head = m.headline()
    assert head["requests_done"] == 11
    assert head["requests_preempted"] == 1
    assert head["requests_resumed"] == 1
    cls = head["classes"]
    assert cls["interactive"]["requests_done"] == 10
    assert cls["interactive"]["ttft_p99_s"] == pytest.approx(0.0991)
    assert cls["batch"]["preemptions"] == 1
    assert cls["batch"]["resumes"] == 1
    assert cls["batch"]["requests_rejected"] == 1
    m.close()
    disk = read_headline(str(tmp_path / "serve.csv"))
    assert disk["requests_done"] == 11
    assert disk["requests_preempted"] == 1
    assert disk["requests_resumed"] == 1
    # tokens counted once: 10x4 interactive + 8 batch
    assert disk["tokens_out"] == 48
    dcls = disk["classes"]
    assert dcls["interactive"]["requests_done"] == 10
    assert dcls["interactive"]["ttft_p99_s"] == pytest.approx(0.0991)
    assert dcls["batch"]["preemptions"] == 1
    assert dcls["batch"]["requests_rejected"] == 1


def test_metrics_single_tenant_headline_has_no_classes_block(tmp_path):
    """The single-tenant default emits NO classes block — dashboards
    reading the pre-tenant headline see the pre-tenant shape."""
    from gym_tpu.serve.metrics import ServeMetrics, read_headline
    m = ServeMetrics(str(tmp_path))
    m.request_done(_fake_req(1, 4, 0.1, 0.01), queue_depth=0,
                   active_slots=1)
    head = m.headline()
    assert "classes" not in head
    m.close()
    assert "classes" not in read_headline(str(tmp_path / "serve.csv"))
