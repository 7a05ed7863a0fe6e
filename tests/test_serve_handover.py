"""A round hands its tokens over in time linear in them (ISSUE 49).

``Scheduler._deliver`` applies a read's events to their requests and
wakes the streamers of the requests that progressed: it compares no two
requests, keeps one entry a request whatever a read holds for it, and
wakes every request once (a request that resolved by its resolution, the
others under the span ``serve.wake``). ``wakes`` counts them, and the
per-layer reader ``serve_deliver_cpu_ms_per_round`` reads ``serve.deliver``.
ISSUE 49's third part, the wake-ups behind the next round's dispatch, was
built, measured and left out (``PERF.md`` §6).
"""

import os
import types

import numpy as np
import pytest

from gym_tpu.serve.engine import SamplingParams, TokenEvent
from gym_tpu.serve.scheduler import Request, RequestStatus, Scheduler
from gym_tpu.utils import trace
from perfbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 128


class EngineDouble:
    """What the scheduler asks of an engine, with every call logged. A
    round's read is scripted: ``reads`` holds the events of the next
    ``step`` calls, oldest first."""

    def __init__(self, log, num_slots=ROWS):
        self.log, self.num_slots = log, num_slots
        self.reads = []
        self.stats = types.SimpleNamespace(active_slots=0, prefill_tokens=0)

    def free_slots(self):
        return []

    def validate(self, prompt, sampling):
        pass

    def release(self, slot):
        self.log.append(("release", slot))

    def drain(self):
        self.log.append(("drain",))
        return []

    def step(self, ahead=False):
        assert ahead
        self.log.append(("launch",))
        self.log.append(("readback",))
        return self.reads.pop(0) if self.reads else []


class Metrics:
    def __init__(self):
        self.done = []

    def request_done(self, req, queue_depth, active_slots):
        self.done.append(req.id)

    def request_preempted(self, req, queue_depth, active_slots):
        pass

    def tokens_per_s_ewma(self):
        return None


@pytest.fixture()
def rig(monkeypatch):
    """A scheduler over the double with ``ROWS`` running requests, the
    wake-ups and comparisons of ``Request`` logged."""
    log = []
    eng = EngineDouble(log)
    sched = Scheduler(eng, metrics=Metrics())
    reqs = []
    for slot in range(ROWS):
        req = Request(id=slot, prompt=np.arange(3, dtype=np.int32),
                      sampling=SamplingParams(max_new_tokens=64),
                      status=RequestStatus.RUNNING)
        sched._by_slot[slot] = req
        reqs.append(req)
    compared = []
    monkeypatch.setattr(Request, "__eq__",
                        lambda a, b: compared.append((a.id, b.id)) or a is b,
                        raising=False)
    monkeypatch.setattr(Request, "_notify_progress",
                        lambda r: log.append(("wake", r.id)))
    return types.SimpleNamespace(sched=sched, eng=eng, reqs=reqs, log=log,
                                 compared=compared)


def _read(per_request, finishing=()):
    """A read's events in the engine's order (iteration-major, then slot,
    then the tokens an iteration emitted): ``per_request[slot]`` is a list
    of lists of tokens, one list an iteration of the chunk."""
    events = []
    iterations = max(len(its) for its in per_request.values())
    for k in range(iterations):
        for slot, its in per_request.items():
            for j, tok in enumerate(its[k] if k < len(its) else ()):
                last = (k == len(its) - 1 and j == len(its[k]) - 1)
                events.append(TokenEvent(slot, tok,
                                         last and slot in finishing))
    return events


SHAPES = {
    # one token a row a round: the benchmark's served cells
    "one_token": lambda slot: [[1000 + slot]],
    # decode_chunk 4: four iterations of one token
    "chunk_of_4": lambda slot: [[4 * slot + k] for k in range(4)],
    # a speculative round: the drafts accepted differ from row to row
    "speculative": lambda slot: [list(range(slot, slot + 1 + slot % 5))],
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_deliver_compares_no_requests_and_wakes_each_once(rig, shape):
    sched, reqs = rig.sched, rig.reqs
    per_request = {slot: SHAPES[shape](slot) for slot in range(ROWS)}
    finishing = set(range(0, ROWS, 16))
    events = _read(per_request, finishing)
    produced = sched._deliver(events, sched._epoch, rig.eng)
    assert rig.compared == []                # no Request.__eq__, at any size
    want = {slot: [t for it in its for t in it]
            for slot, its in per_request.items()}
    assert produced == len(events) == sum(map(len, want.values()))
    for slot, req in enumerate(reqs):
        assert req.tokens == want[slot]      # in order, none doubled
    # a finished request is completed once, woken by its completion and
    # off the slot map; the others are woken as the read's hand-over ends
    assert sorted(sched.metrics.done) == sorted(finishing)
    assert all(reqs[s].status is RequestStatus.DONE for s in finishing)
    assert set(sched._by_slot) == set(range(ROWS)) - finishing
    woken = [entry[1] for entry in rig.log if entry[0] == "wake"]
    assert sorted(woken) == list(range(ROWS))        # each exactly once
    assert woken[:len(finishing)] == sorted(finishing)   # resolutions first
    assert sched.wakes == ROWS
    assert trace.records("serve.wake")[-1].ids["requests"] == (
        ROWS - len(finishing))
    assert rig.compared == []


def test_requests_compare_by_identity():
    """Two requests with the same id are not equal, and comparing them
    does not touch their prompt arrays (the generated ``__eq__`` raised
    on arrays of more than one element)."""
    a, b = (Request(id=7, prompt=np.arange(5, dtype=np.int32),
                    sampling=SamplingParams()) for _ in range(2))
    assert a != b and a == a
    assert a in [b, a] and [b, a].index(a) == 1
    assert len({a, b}) == 2


def _round(rig, events=()):
    rig.eng.reads.append(list(events))
    return rig.sched.step()


def _names(log):
    return [entry[0] for entry in log]


def test_a_rounds_wakes_follow_its_read_and_its_resolutions(rig):
    read = [TokenEvent(0, 5, True)] + [TokenEvent(slot, 6, False)
                                       for slot in range(1, 4)]
    assert _round(rig, read) == 4
    assert rig.log == [("launch",), ("readback",)] + [
        ("wake", slot) for slot in range(4)]
    assert rig.reqs[0]._event.is_set()
    assert rig.sched.wakes == 4
    rec = trace.records("serve.wake")[-1]
    assert rec.ids["requests"] == 3 and rec.ids["round"] == rig.sched.round
    # a stale driver's read is discarded: nobody progressed, nobody woken
    del rig.log[:]
    assert rig.sched._deliver(read, rig.sched._epoch - 1, rig.eng) == 0
    assert rig.log == [] and rig.sched.wakes == 4


def test_no_wake_up_is_lost_between_the_driver_and_32_streamers():
    """More streamers than cores, each blocked in ``wait_progress`` with a
    time-out far beyond the test's: after round r's step every streamer
    has round r's token, or a wake-up was lost. The driver's side is the
    scheduler's own over the double; the interpreter switches threads
    every 10 us."""
    import sys
    import threading
    import time
    n, rounds = 32, 60
    eng = EngineDouble([], num_slots=n)
    sched = Scheduler(eng)
    reqs = []
    for slot in range(n):
        req = Request(id=slot, prompt=np.arange(3, dtype=np.int32),
                      sampling=SamplingParams(max_new_tokens=rounds),
                      status=RequestStatus.RUNNING)
        sched._by_slot[slot] = req
        reqs.append(req)
    got = [[] for _ in range(n)]

    def streamer(k):
        terminal = False
        while not terminal:
            new, terminal = reqs[k].wait_progress(len(got[k]), timeout=120)
            got[k].extend(new)

    def wait_for(tokens):
        deadline = time.perf_counter() + 30
        while any(len(g) < tokens for g in got):
            assert time.perf_counter() < deadline, (
                f"a streamer still lacks token {tokens}: a wake-up was lost")
            time.sleep(0.0005)

    threads = [threading.Thread(target=streamer, args=(k,), daemon=True)
               for k in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for r in range(rounds):
            eng.reads.append([TokenEvent(slot, 100 * r + slot,
                                         r == rounds - 1)
                              for slot in range(n)])
            assert sched.step() == n
            wait_for(r + 1)          # this round's wake-ups left in its step
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for slot in range(n):
        assert got[slot] == [100 * r + slot for r in range(rounds)]
    assert sched.wakes == n * rounds


# -- over the real engine ------------------------------------------------------


@pytest.mark.parametrize("kind", ["plain", "chunk4", "spec4"])
def test_every_progressed_request_is_woken_once_a_read(kind, monkeypatch):
    """The scheduler's rounds over a real engine, one token a read, a
    chunk of four, a speculative round: every (request, read) pair that
    progressed is woken exactly once, after its tokens were appended, and
    ``wakes`` counts the pairs."""
    import jax
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.serve.engine import InferenceEngine
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64), train=False)["params"]
    kw = {"plain": {}, "chunk4": dict(decode_chunk=4),
          "spec4": dict(spec_tokens=4)}[kind]
    sched = Scheduler(InferenceEngine(params, cfg, num_slots=3, page_size=8,
                                      **kw))
    woken = []
    monkeypatch.setattr(Request, "_notify_progress",
                        lambda r: woken.append((r.id, len(r.tokens))))
    reqs = [sched.submit(np.random.default_rng(i).integers(0, 48, 5 + 3 * i),
                         SamplingParams(max_new_tokens=9 + 4 * i, seed=i,
                                        temperature=0.9, top_k=7))
            for i in range(3)]
    pairs = []                           # (request, tokens after the read)
    for _ in range(200):
        if all(r.status is RequestStatus.DONE for r in reqs):
            break
        before = [len(r.tokens) for r in reqs]
        sched.step()
        pairs += [(r.id, len(r.tokens)) for r, n in zip(reqs, before)
                  if len(r.tokens) > n]
    else:
        raise AssertionError("the scheduler did not finish")
    assert sorted(woken) == sorted(pairs) and len(set(pairs)) == len(pairs)
    assert sched.wakes == len(pairs)
    assert [len(r.tokens) for r in reqs] == [9, 13, 17]


# -- the reader ----------------------------------------------------------


def _reader(name):
    return harness.load_reader(os.path.join(ROOT, "perfbench"), name)


def _sample(rounds, deliver_cpu, wake_cpu):
    spans = {"serve.round": [rounds, 0.04 * rounds, 0.1, 0.02 * rounds],
             "serve.deliver": [rounds, 0.004 * rounds, 0.01, deliver_cpu]}
    if wake_cpu is not None:
        spans["serve.wake"] = [rounds, 0.001 * rounds, 0.01, wake_cpu]
    return {"spans": spans, "decode_steps": rounds}


READER = "serve_deliver_cpu_ms_per_round"


def test_reader_reads_the_span_once():
    facts = {"stats_samples": [
        _sample(10, 0.010, 0.002),
        _sample(20, 0.030, 0.006),       # the first whole round
        _sample(120, 0.230, 0.056)]}
    # (0.230 - 0.030) s over 100 rounds, in ms: ``serve.wake`` is a leaf
    # of ``serve.deliver``, whose thread time holds it already
    assert _reader(READER)(facts) == pytest.approx(2.0)


def test_the_benchmark_lists_the_metric_for_every_served_cell():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    served = [w["name"] for w in bench["workloads"]
              if w["traffic"].startswith("serve-")]
    assert len(served) == 7
    entry, = [m for m in bench["per_layer"] if m["name"] == READER]
    assert entry == {
        "name": READER, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Scheduler and HTTP",
        "moves": "serve_tokens_per_s", "workloads": served}


def test_reader_on_a_program_without_the_span_or_the_second_clock():
    """The parent of this PR has no ``serve.wake`` span: it wakes inside
    ``serve.deliver``, which is then the whole hand-over. A program from
    before the spans' second clock gives nothing. No raise."""
    parent = {"stats_samples": [_sample(n, 0.01 * n, None)
                                for n in (10, 20, 120)]}
    assert _reader(READER)(parent) == pytest.approx(10.0)
    three = {"stats_samples": [
        {"spans": {"serve.round": [n, 0.04 * n, 0.1],
                   "serve.deliver": [n, 0.004 * n, 0.01]}}
        for n in (10, 20, 120)]}
    assert _reader(READER)(three) is None
    assert _reader(READER)({}) is None
    assert _reader(READER)({"kind": "fit"}) is None
