"""The span recorder (``gym_tpu/utils/trace.py``) and its call sites: what a
span records, what reaches the profiler, what ``fit`` and the serving round
leave behind, and the names the step program carries to a device trace.

The recorder is process-wide, so every test reads only what it wrote: by
an id of its own (``run``, ``request``) or by ``seq`` past a mark.
"""

import glob
import json
import os
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from gym_tpu import Trainer
from gym_tpu.models.base import LossModel
from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.ops import flash_attention, fused_attention
from gym_tpu.parallel.mesh import NodeRuntime
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler
from gym_tpu.strategy import DiLoCoStrategy, OptimSpec
from gym_tpu.train_node import make_init_fn, make_train_step
from gym_tpu.utils import trace
from test_trainer_e2e import TinyLossModel, blobs


def _mark() -> int:
    """A ``seq`` no record written so far has reached."""
    with trace.span("mark"):
        pass
    return trace.records("mark")[-1].seq


def _since(mark, name=None, **match):
    return [r for r in trace.records(name, **match) if r.seq > mark]


# -- the recorder ------------------------------------------------------------


def _nested():
    with trace.span("t.outer", request=7, round=3) as outer:
        with trace.span("t.inner", bytes=10):
            pass
    inner, = trace.records("t.inner", request=7)[-1:]
    rec, = trace.records("t.outer", request=7)[-1:]
    assert rec.seq == outer.seq and rec.parent is None
    assert inner.parent == outer.seq
    # the parent's ids reach the child; its own join them
    assert inner.ids == {"request": 7, "round": 3, "bytes": 10}
    assert rec.t0 <= inner.t0 <= inner.t1 <= rec.t1


def _per_thread():
    seen = {}

    def other():
        with trace.span("t.thread") as sp:
            seen["seq"] = sp.seq
    with trace.span("t.main"):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    rec, = [r for r in trace.records("t.thread") if r.seq == seen["seq"]]
    assert rec.parent is None       # an open span elsewhere is no parent


def _bounded():
    before = trace.totals().get("t.flood", (0,))[0]
    for _ in range(trace.CAPACITY + 50):
        with trace.span("t.flood"):
            pass
    assert len(trace.records()) == trace.CAPACITY
    assert trace.totals()["t.flood"][0] == before + trace.CAPACITY + 50


def _raises():
    mark = _mark()
    with pytest.raises(KeyError):
        with trace.span("t.raises", run="r"):
            raise KeyError("x")
    rec, = _since(mark, "t.raises")
    assert rec.t1 >= rec.t0 and rec.ids == {"run": "r"}
    with trace.span("t.after"):
        pass
    assert _since(mark, "t.after")[0].parent is None    # the stack unwound


def _held():
    mark = _mark()
    for keep in (True, False):
        with trace.span("t.round", annotate=False, hold=True,
                        round=int(keep)) as rnd:
            with trace.span("t.leaf"):
                pass
            trace.record("t.queued", 1.0, 2.5, request=1)
            assert not _since(mark, round=int(keep))    # held back
            rnd.keep = keep
    kept = _since(mark, round=1)
    assert [r.name for r in kept] == ["t.leaf", "t.queued", "t.round"]
    assert not _since(mark, round=0)                    # dropped whole
    queued = kept[1]
    assert queued.seconds == pytest.approx(1.5)
    assert queued.parent == kept[2].seq


def _late_ids():
    mark = _mark()
    with trace.span("t.late") as sp:
        sp.ids["request"] = 41
    assert _since(mark, "t.late", request=41)


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _cpu_of(name, work, **kw):
    """The record and the totals row's growth of one span around ``work``."""
    before = trace.totals().get(name, (0, 0.0, 0.0, 0.0))
    with trace.span(name, **kw) as sp:
        work()
    rec, = [r for r in trace.records(name) if r.seq == sp.seq]
    after = trace.totals()[name]
    return rec, [a - b for a, b in zip(after, before)]


def _cpu_sleeps():
    rec, grew = _cpu_of("t.cpu.sleeps", lambda: time.sleep(0.05))
    assert rec.seconds >= 0.05 and 0 <= rec.cpu * 1e-9 < 0.01
    assert grew[3] == pytest.approx(rec.cpu * 1e-9)


def _cpu_spins():
    # a machine that runs six workers of this suite may take the core
    # away for a while: the best of a few tries
    for _ in range(5):
        rec, grew = _cpu_of("t.cpu.spins", lambda: _spin(0.05))
        # the two clocks are read at the same moments, to REUSE_NS
        assert 0 < rec.cpu <= rec.t1 - rec.t0 + trace.REUSE_NS
        if rec.cpu * 1e-9 > 0.8 * rec.seconds:
            break
    assert rec.cpu * 1e-9 > 0.8 * rec.seconds
    assert grew[3] == pytest.approx(rec.cpu * 1e-9)


def _cpu_contended():
    """One interpreter lock: beside four threads that spin, the span's
    thread runs about a fifth of its wall time."""
    stop = threading.Event()

    def other():
        while not stop.is_set():
            pass
    threads = [threading.Thread(target=other, daemon=True)
               for _ in range(4)]
    for th in threads:
        th.start()
    try:
        rec, _ = _cpu_of("t.cpu.contended", lambda: _spin(0.3))
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
    assert not any(th.is_alive() for th in threads)
    assert rec.cpu * 1e-9 < 0.6 * rec.seconds


def _cpu_reading_shared():
    """The thread clock is a system call: where one span closes and the
    next opens both take one reading, at most one every ``REUSE_NS``."""
    real, calls = time.thread_time_ns, []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(time, "thread_time_ns",
                      lambda: calls.append(1) or real())
        t0 = time.perf_counter_ns()
        for _ in range(200):
            with trace.span("t.cpu.shared"):
                pass
        wall = time.perf_counter_ns() - t0
    assert 1 <= len(calls) <= wall // trace.REUSE_NS + 2
    assert len(calls) < 200                 # of 400 boundaries


def _totals_rows():
    name = f"t.rows.{_mark()}"              # a row nobody else wrote
    recs = []
    for nap in (0.01, 0.03):
        with trace.span(name) as sp:
            time.sleep(nap)
            _spin(0.002)
        recs += [r for r in trace.records(name) if r.seq == sp.seq]
    trace.record(name, 1.0, 1.5)            # after the fact: no CPU stamp
    row = trace.totals()[name]
    assert len(row) == 4
    assert row[0] == 3
    assert row[1] == pytest.approx(sum(r.seconds for r in recs) + 0.5)
    assert row[2] == pytest.approx(0.5)
    assert row[3] == pytest.approx(sum(r.cpu for r in recs) * 1e-9)
    assert trace.records(name)[-1].cpu == 0
    # a record made by hand, as the benchmark's tests make them
    assert trace.Record(1, "x", 0, 1, None, {}).cpu == 0


@pytest.mark.parametrize("case", [_nested, _per_thread, _bounded, _raises,
                                  _held, _late_ids, _cpu_sleeps, _cpu_spins,
                                  _cpu_contended, _cpu_reading_shared,
                                  _totals_rows],
                         ids=lambda f: f.__name__.strip("_"))
def test_recorder(case):
    case()


# -- the profiler's side -----------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("session", [True, False],
                         ids=["in_session", "no_session"])
def test_span_reaches_the_profiler_only_in_a_session(tmp_path, session):
    def spans():
        with trace.span("t.prof", request=9) as sp:
            with trace.span("t.prof.quiet", annotate=False):
                pass
        return sp.seq

    if session:
        with jax.profiler.trace(str(tmp_path)):
            seq = spans()
        events = _host_events(str(tmp_path))
        stats, = [s for n, s in events if n == "t.prof"]
        assert stats["seq"] == seq and stats["request"] == 9
        assert not [n for n, _ in events if n == "t.prof.quiet"]
    else:
        assert not trace.TraceAnnotation.is_enabled()
        seq = spans()
        assert not os.listdir(tmp_path)                 # nothing written
    assert trace.records("t.prof", request=9)[-1].seq == seq


# -- fit ----------------------------------------------------------------------

FIT_STEPS = 6


@pytest.fixture(scope="module")
def tiny_fit(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace_fit")
    res = Trainer(TinyLossModel(), blobs(256, seed=8), blobs(64, seed=9)).fit(
        strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3), H=3),
        num_nodes=2, max_steps=FIT_STEPS, batch_size=16, val_size=16,
        val_interval=3, show_progress=False, seed=13,
        run_name="trace-test", log_dir=str(tmp / "logs"),
        save_dir=str(tmp / "ckpt"), checkpoint_interval=3)
    return res, trace.records(run="trace-test")


@pytest.mark.parametrize("name", ["fit.data_wait", "fit.dispatch",
                                  "fit.retire.wait", "fit.retire.log"])
def test_fit_leaves_one_span_a_step(tiny_fit, name):
    _res, recs = tiny_fit
    mine = [r for r in recs if r.name == name]
    assert [r.ids["step"] for r in mine] == list(range(FIT_STEPS))
    # a checkpoint retires the step in flight first: that drain's spans
    # lie under ``fit.checkpoint``; every other span is the loop's own
    saves = {r.seq for r in recs if r.name == "fit.checkpoint"}
    assert all(r.ids["run"] == "trace-test"
               and (r.parent is None or r.parent in saves) for r in mine)


@pytest.mark.parametrize("name,steps", [("fit.eval", [0, 3, 6]),
                                        ("fit.checkpoint", [3, 6])])
def test_fit_marks_eval_and_checkpoint(tiny_fit, name, steps):
    _res, recs = tiny_fit
    assert [r.ids["step"] for r in recs if r.name == name] == steps


def test_fit_history_has_one_retire_stamp_a_step(tiny_fit):
    res, recs = tiny_fit
    stamps = res.history["retire_t"]
    assert [s for s, _ in stamps] == list(range(FIT_STEPS))
    times = [t for _, t in stamps]
    assert times == sorted(times)
    # the stamp is taken as the read-back returns, on the recorder's clock
    waits = [r for r in recs if r.name == "fit.retire.wait"]
    for (_, t), w in zip(stamps, waits):
        assert w.t1 * 1e-9 <= t <= w.t1 * 1e-9 + 0.05


@pytest.mark.parametrize("steps,first_traced", [(40, 17), (6, 3)],
                         ids=["long_fit", "short_fit"])
def test_profile_dir_skips_the_fast_first_steps(monkeypatch, tmp_path, steps,
                                                first_traced):
    """``fit(profile_dir=)`` starts 16 steps after the first dispatch
    retired (at step 1 here), or at the half of a shorter fit."""
    started = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d, **kw: started.append(
            trace.records("fit.dispatch", run="prof")[-1].ids["step"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    Trainer(TinyLossModel(), blobs(256, seed=8), None).fit(
        strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3), H=3),
        num_nodes=2, max_steps=steps, batch_size=16, val_interval=0,
        show_progress=False, seed=13, run_name="prof",
        log_dir=str(tmp_path / "logs"), profile_dir=str(tmp_path / "p"))
    assert started == [first_traced]


def test_mfu_is_over_the_steady_window(monkeypatch, tmp_path):
    from gym_tpu.models import nanogpt
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(nanogpt.PEAK_BF16_FLOPS, kind, 1e12)
    cfg = GPTConfig(block_size=16, vocab_size=32, n_layer=1, n_head=2,
                    n_embd=16, dropout=0.0)
    rows = np.random.default_rng(0).integers(0, 32, (64, 17))

    class Rows:
        def __len__(self):
            return len(rows)

        def take(self, idx):
            r = rows[np.asarray(idx) % len(rows)]
            return r[:, :-1], r[:, 1:]

    res = Trainer(GPT(cfg), Rows(), None).fit(
        strategy=DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3), H=3),
        num_nodes=1, max_steps=5, batch_size=4, val_interval=0,
        show_progress=False, seed=1, log_dir=str(tmp_path / "logs"))
    # the first dispatch holds the compile: the whole-fit rate is far
    # under the steady one, and mfu follows the steady one
    assert res.steps_per_second_steady > 2 * res.steps_per_second
    whole = nanogpt.node_mfu(cfg, res.node_state.params, 4,
                             1.0 / res.steps_per_second, peak_flops=1e12)
    assert res.mfu == pytest.approx(
        whole * res.steps_per_second_steady / res.steps_per_second)


# -- the serving round --------------------------------------------------------

ROUND_LEAVES = {"serve.shed", "serve.pick", "serve.admit",
                "serve.decode.args", "serve.decode.dispatch",
                "serve.decode.readback", "serve.decode.events",
                "serve.deliver"}
PREFILL_LEAVES = ["serve.prefill.args", "serve.prefill.plan",
                  "serve.prefill.dispatch", "request.queue"]


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64), train=False)["params"]
    return cfg, params


@pytest.fixture(scope="module")
def served(tiny_gpt):
    """Three requests through a paged engine under ``Scheduler.step``,
    then two idle rounds: ``(requests, records, engine)``."""
    cfg, params = tiny_gpt
    eng = InferenceEngine(params, cfg, num_slots=2, page_size=8)
    sched = Scheduler(eng)
    mark = _mark()
    # no two prompts share a first page: every prefill is of the whole
    # prompt (the engine was the unpaged ring here until PR 29)
    reqs = [sched.submit((np.arange(3 + 5 * i) + 7 * i) % 48,
                         SamplingParams(max_new_tokens=4 + i, seed=i))
            for i in range(3)]
    for _ in range(200):
        if all(r.status is RequestStatus.DONE for r in reqs):
            break
        sched.step()
    assert all(r.status is RequestStatus.DONE for r in reqs)
    busy = _since(mark)
    assert sched.step() == 0 and sched.step() == 0
    assert len(_since(mark)) == len(busy)       # idle rounds leave nothing
    return reqs, busy, eng


def _serve_leaves_tile_the_round(reqs, recs, eng):
    rounds = [r for r in recs if r.name == "serve.round"]
    assert rounds and all(r.parent is None for r in rounds)
    assert [r.ids["round"] for r in rounds] == sorted(
        {r.ids["round"] for r in rounds})
    for rnd in rounds:
        leaves = sorted((r for r in recs if r.parent == rnd.seq),
                        key=lambda r: r.t0)
        assert {r.name for r in leaves} <= ROUND_LEAVES
        assert leaves[0].name == "serve.shed"
        assert leaves[-1].name == "serve.deliver"
        # the wake-ups (ISSUE 49) are a leaf of ``serve.deliver``'s own
        assert not any(r.name == "serve.wake" for r in leaves)
        assert all(r.parent == leaves[-1].seq for r in recs
                   if r.name == "serve.wake"
                   and r.ids["round"] == rnd.ids["round"])
        assert all(r.ids["round"] == rnd.ids["round"] for r in leaves)
        edges = [rnd.t0] + [t for r in leaves for t in (r.t0, r.t1)] \
            + [rnd.t1]
        assert edges == sorted(edges)           # inside it, none overlap


def _serve_one_admit_and_queue_a_request(reqs, recs, eng):
    for req in reqs:
        admit, = [r for r in recs if r.name == "serve.admit"
                  and r.ids["request"] == req.id]
        assert admit.ids["prompt_tokens"] == req.prompt.size
        assert admit.ids["bucket"] >= req.prompt.size
        below = [r for r in recs if r.parent == admit.seq]
        assert [r.name for r in below] == PREFILL_LEAVES
        assert all(r.ids["request"] == req.id
                   and r.ids["round"] == admit.ids["round"] for r in below)
        queued = below[-1]
        assert queued.t0 == int(req.submit_t * 1e9)
        assert queued.t1 == int(req.admit_t * 1e9)
        assert req.submit_t <= req.admit_t <= req.first_token_t


def _serve_readback_carries_its_bytes(reqs, recs, eng):
    reads = [r for r in recs if r.name == "serve.decode.readback"]
    assert len(reads) == eng.stats.decode_steps
    assert sum(r.ids["bytes"] for r in reads) == eng.stats.readback_bytes
    # tokens and flags, not the [slots, vocab] f32 logits (ISSUE 28)
    assert all(0 < r.ids["bytes"] < 2 * 48 * 4 for r in reads)
    # and how many host arrays each decode dispatch was handed
    args = [r for r in recs if r.name == "serve.decode.args"]
    assert sum(r.ids["uploads"] for r in args) == eng.stats.upload_arrays


def _serve_prefill_readback_where_it_waits(reqs, recs, eng):
    """A prefill's token comes down with the next step's read; only where
    no step is in flight to read it with (the first round here) does a
    ``serve.prefill.readback`` wait for it, inside ``.events``."""
    reads = [r for r in recs if r.name == "serve.prefill.readback"]
    events = {r.seq for r in recs if r.name == "serve.decode.events"}
    assert reads and all(r.parent in events for r in reads)
    assert "request" not in reads[0].ids
    assert len(reads) < len(reqs)


@pytest.mark.parametrize("check", [_serve_leaves_tile_the_round,
                                   _serve_one_admit_and_queue_a_request,
                                   _serve_readback_carries_its_bytes,
                                   _serve_prefill_readback_where_it_waits],
                         ids=lambda f: f.__name__.strip("_"))
def test_serving_round(served, check):
    check(*served)


def _post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as reply:
        return reply.read()


@pytest.mark.parametrize("stream", [False, True], ids=["reply", "stream"])
def test_request_id_runs_from_http_to_prefill(tiny_gpt, tmp_path, stream):
    from gym_tpu.serve.__main__ import create_server
    cfg, params = tiny_gpt
    handle = create_server(params, cfg, port=0, num_slots=2, replicas=1,
                           page_size=8, warmup=False,
                           metrics_dir=str(tmp_path))
    th = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    th.start()
    try:
        mark = _mark()
        _post(handle.port, {"prompt": [1, 2, 3, 4, 5], "max_new_tokens": 5,
                            "seed": 2, "stream": stream})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/stats", timeout=60) as r:
            stats = json.loads(r.read())
    finally:
        handle.close()
        th.join(timeout=60)
    http, = _since(mark, "http.generate")
    rid = http.ids["request"]
    for name in ("request.queue", "serve.admit", "serve.prefill.plan",
                 "serve.prefill.dispatch", "http.submit"):
        assert len(_since(mark, name, request=rid)) == 1, name
    admit, = _since(mark, "serve.admit", request=rid)
    assert http.t0 <= admit.t0 and admit.t1 <= http.t1
    # the handler's leaves lie under the request's span, in order; the
    # body is parsed before any id is known
    parse, = _since(mark, "http.parse")
    submit, = _since(mark, "http.submit")
    assert parse.parent == submit.parent == http.seq
    assert http.t0 <= parse.t0 <= parse.t1 <= submit.t0 <= admit.t0
    # /stats serves the totals, and the bytes the decode steps read back
    for name in ("serve.round", "serve.tick", "serve.admit",
                 "serve.decode.readback", "request.queue", "http.parse"):
        count, total_s, max_s, cpu_s = stats["spans"][name]
        assert count >= 1 and total_s >= max_s >= 0
        assert 0 <= cpu_s <= total_s + count * trace.REUSE_NS * 1e-9
    assert stats["spans"]["request.queue"][3] == 0      # never stamped
    assert stats["readback_bytes"] > 0


# -- who annotates, and what the handlers leave -----------------------------


class _CountingAnnotation:
    """Stands where ``TraceAnnotation`` does, says a profiler session is
    on, and lists what entered it."""
    entered = []

    def __init__(self, name, **ids):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def streamed(tiny_gpt, tmp_path_factory):
    """One streamed ``/generate`` against the tiny in-process server with
    every annotation counted: ``(names annotated, /stats after,
    records)``."""
    from gym_tpu.serve.__main__ import create_server
    cfg, params = tiny_gpt
    handle = create_server(params, cfg, port=0, num_slots=2, replicas=1,
                           page_size=8, warmup=False,
                           metrics_dir=str(tmp_path_factory.mktemp("m")))
    th = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    th.start()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace, "TraceAnnotation", _CountingAnnotation)
        _CountingAnnotation.entered.clear()
        try:
            mark = _mark()
            body = _post(handle.port, {"prompt": [3, 1, 4, 1, 5],
                                       "max_new_tokens": 6, "seed": 4,
                                       "stream": True})
            assert body.count(b"data: ") >= 2   # token chunks, the summary
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/stats",
                    timeout=60) as r:
                stats = json.loads(r.read())
        finally:
            handle.close()
            th.join(timeout=60)
        assert not th.is_alive()
        return list(_CountingAnnotation.entered), stats, _since(mark)


@pytest.mark.parametrize("name,annotated", [
    ("serve.tick", True), ("serve.decode.dispatch", True),
    ("serve.round", False), ("http.generate", False), ("http.parse", False),
    ("http.submit", False)])
def test_only_the_thread_that_feeds_the_device_annotates(streamed, name,
                                                         annotated):
    entered, stats, _recs = streamed
    assert stats["spans"][name][0] >= 1         # it did fire
    assert (name in entered) == annotated


def test_handler_cpu_is_the_requests_and_stats_has_the_processes(streamed):
    _entered, stats, recs = streamed
    http, = [r for r in recs if r.name == "http.generate"]
    leaves = [r for r in recs if r.parent == http.seq]
    assert [r.name for r in leaves] == ["http.parse", "http.submit"]
    # all the handler thread ran for the request, its leaves and every
    # event it wrote included; there is no span an event (the thread
    # clock is a system call)
    assert sum(r.cpu for r in leaves) < http.cpu < (http.t1 - http.t0)
    assert set(stats["spans"]) >= {"http.generate", "http.parse",
                                   "http.submit"}
    assert not [n for n in stats["spans"] if n.startswith("http.write")]
    assert 0 < stats["process_cpu_s"] <= time.process_time()


def test_tick_follows_its_round_on_the_driver_thread(streamed):
    _entered, _stats, recs = streamed
    rounds = {r.ids["round"]: r for r in recs if r.name == "serve.round"}
    ticks = [r for r in recs if r.name == "serve.tick"]
    assert ticks and all(r.parent is None for r in ticks)
    for tick in ticks:                          # kept with its round
        assert rounds[tick.ids["round"]].t1 <= tick.t0 <= tick.t1


# -- names in the step program ------------------------------------------------


@pytest.fixture()
def flash_on_cpu(monkeypatch):
    """The Pallas kernels in the interpreter, behind the TPU's path."""
    monkeypatch.setattr(fused_attention, "INTERPRET", True)
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)


@pytest.fixture()
def step_hlo(flash_on_cpu):
    cfg = GPTConfig(block_size=128, vocab_size=64, n_layer=1, n_head=2,
                    n_embd=32, dropout=0.0, attn_impl="flash")
    model = LossModel(GPT(cfg), None)
    strategy = DiLoCoStrategy(optim_spec=OptimSpec("adamw", lr=1e-3), H=2)
    strategy.finalize(4)
    runtime = NodeRuntime.create(2, jax.devices()[:2])
    x = np.zeros((2, 128), np.int32)
    state = runtime.init_state(make_init_fn(model, strategy, (x, x), 0,
                                            ctx=runtime.ctx))
    step = runtime.compile(make_train_step(model, strategy, runtime.ctx))
    batch = runtime.shard_batch(
        jax.tree.map(lambda a: np.broadcast_to(a, (2, 1) + a.shape), (x, x)))
    lowered = step.lower(state, batch).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', lowered))


@pytest.mark.parametrize("name", ["fwd_bwd", "strategy", "optimizer", "outer",
                                  "attn_fwd", "attn_bwd"])
def test_step_program_names_its_parts(step_hlo, name):
    """Scopes and kernel names ride on the operations' locations (the
    compiled program's ``op_name``): a device trace can book each
    operation to forward and backward, the strategy, its inner optimizer
    or DiLoCo's outer step, and tell the forward attention kernel from
    the backward one."""
    mine = [o for o in step_hlo
            if re.search(rf"(^|[/(]){name}(_blk)?([/)]|$)", o)]
    assert mine, f"no operation under {name!r}"
    if name == "optimizer":
        assert any(o.startswith("strategy/optimizer/") for o in mine)
    if name == "outer":     # the taken branch of the H-gate, not the inner
        assert any(re.match(r"strategy/cond/.*outer/", o) for o in mine)
        assert not any("optimizer" in o for o in mine)
