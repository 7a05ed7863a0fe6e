"""Token streaming (ISSUE 13): the in-process half of the streaming
fleet — chunk-granular streams exactly equal to ``generate_fast``,
mid-stream failover SPLICE (the PR-8 exact-stream oracle upgraded to
streaming), client-disconnect cancellation at the chunk boundary, and
the metrics schema riders (``pid`` column, ``status=disconnected``,
``streams_active``, old-header tolerance)."""

import csv
import json
import os
import socket
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.metrics import HEADER, ServeMetrics, read_headline
from gym_tpu.serve.router import FleetRequest, build_fleet
from gym_tpu.serve.scheduler import (EngineFailedError, Request,
                                     RequestCancelledError,
                                     RequestStatus, Scheduler)
from gym_tpu.serve.worker import WorkerServer


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64),
                        train=False)["params"]
    return cfg, params


def _ref(params, cfg, prompt, n, **kw):
    return generate_fast(params, cfg, np.asarray(prompt)[None], n,
                         **kw)[0, len(prompt):].tolist()


# -- FleetRequest.stream --------------------------------------------------


def test_stream_chunks_concatenate_to_exact_generate_fast(setup):
    """Streamed chunks, concatenated, are byte-identical to the
    buffered result AND to ``generate_fast`` — and more than one chunk
    arrives (it is a stream, not a buffer)."""
    cfg, params = setup
    router = build_fleet(params, cfg, replicas=1, num_slots=2,
                         log=lambda *a, **k: None).start()
    try:
        prompt = [1, 2, 3, 4, 5, 6]
        ref = _ref(params, cfg, prompt, 16, temperature=0.9, top_k=7,
                   seed=3)
        fr = router.submit(prompt, SamplingParams(
            max_new_tokens=16, temperature=0.9, top_k=7, seed=3))
        got, chunks = [], 0
        for chunk in fr.stream(timeout=60):
            got.extend(chunk)
            chunks += 1
        assert got == ref
        assert chunks > 1
        assert fr.ttft_s is not None and fr.done_t is not None
    finally:
        router.close(drain_deadline_s=30)


def test_mid_stream_replica_kill_splices_exact(setup, tmp_path):
    """THE streaming splice oracle (in-process half): kill the serving
    replica after >= 4 tokens have been streamed — the concatenated
    stream the client saw is byte-identical to an uncontended run, the
    failover is recorded, and it fits the original deadline."""
    cfg, params = setup
    m = ServeMetrics(str(tmp_path))
    router = build_fleet(params, cfg, replicas=2, num_slots=2,
                         metrics=m, max_restarts=0,
                         log=lambda *a, **k: None).start()
    try:
        prompt = [1, 2, 3, 4, 5, 6]
        ref = _ref(params, cfg, prompt, 24, temperature=0.9, top_k=7,
                   seed=5)
        fr = router.submit(prompt, SamplingParams(
            max_new_tokens=24, temperature=0.9, top_k=7, seed=5),
            deadline_s=60.0)
        victim = fr.replica_id
        got, killed = [], False
        t0 = time.perf_counter()
        for chunk in fr.stream(timeout=60):
            got.extend(chunk)
            if not killed and len(got) >= 4:
                def boom(*a, **k):
                    raise RuntimeError("test: injected hard death")
                router.replicas[victim].scheduler.engine.step = boom
                killed = True
        assert killed, "stream finished before the kill landed"
        assert got == ref                       # no dupes, no gaps
        assert time.perf_counter() - t0 < 60.0  # inside the deadline
        assert fr.failovers == 1
        assert fr.replica_id != victim
        assert router.status()["failovers"] == 1
    finally:
        router.close(drain_deadline_s=30)
        m.close()


# -- scheduler.cancel (the disconnect primitive) --------------------------


def test_cancel_running_frees_slot_at_chunk_boundary(setup, tmp_path):
    cfg, params = setup
    m = ServeMetrics(str(tmp_path))
    sched = Scheduler(InferenceEngine(params, cfg, num_slots=1),
                      metrics=m.replica_view(0))
    stop = threading.Event()
    t = threading.Thread(target=sched.run, args=(stop,), daemon=True)
    t.start()
    try:
        req = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=48,
                                                     seed=0))
        toks, _ = req.wait_progress(0, timeout=30)
        assert toks, "no progress before cancel"
        assert sched.cancel(req) is True
        with pytest.raises(RequestCancelledError):
            req.result(timeout=30)
        assert req.status is RequestStatus.FAILED
        assert len(req.tokens) < 48
        # the slot is FREE: the next request runs to completion
        nxt = sched.submit([4, 5], SamplingParams(max_new_tokens=4,
                                                  seed=1))
        assert len(nxt.result(timeout=60)) == 4
        # a second cancel is a no-op on a resolved request
        assert sched.cancel(req) is False
    finally:
        stop.set()
        t.join(timeout=30)
        sched.shutdown(finish_running=False, deadline_s=0.0)
        m.close()
    head = read_headline(os.path.join(str(tmp_path), "serve.csv"))
    assert head["requests_disconnected"] == 1
    assert head["requests_failed"] == 0      # a disconnect is not a
    #                                          server failure
    assert head["requests_done"] == 1


def test_cancel_queued_fails_immediately(setup):
    cfg, params = setup
    sched = Scheduler(InferenceEngine(params, cfg, num_slots=1))
    # no driver running: the request stays queued
    req = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    assert sched.cancel(req) is True
    with pytest.raises(RequestCancelledError):
        req.result(timeout=5)
    assert sched.queue_depth() == 0
    sched.shutdown(finish_running=False, deadline_s=0.0)


# -- HTTP streaming + disconnect regression -------------------------------


@pytest.fixture()
def http_server(setup):
    from gym_tpu.serve.__main__ import create_server
    cfg, params = setup
    handle = create_server(
        params, cfg, port=0, num_slots=2, replicas=1, warmup=False,
        metrics_dir=tempfile.mkdtemp(prefix="gym_tpu_stream_"))
    t = threading.Thread(target=handle.httpd.serve_forever, daemon=True)
    t.start()
    yield handle
    handle.close()


def _sse_events(port, payload, timeout=120):
    import urllib.request
    body = json.dumps(payload).encode()
    r = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", body,
        {"Content-Type": "application/json"}), timeout=timeout)
    assert r.headers["Content-Type"] == "text/event-stream"
    return [json.loads(line[6:]) for line in r
            if line.strip().startswith(b"data: ")]


def test_http_stream_true_is_chunked_and_exact(setup, http_server):
    cfg, params = setup
    ref = _ref(params, cfg, [1, 2, 3, 4, 5, 6], 16, temperature=0.9,
               top_k=7, seed=3)
    evs = _sse_events(http_server.port, {
        "prompt": [1, 2, 3, 4, 5, 6], "max_new_tokens": 16,
        "temperature": 0.9, "top_k": 7, "seed": 3, "stream": True})
    toks = [t for e in evs if not e.get("done")
            for t in e.get("tokens", [])]
    fin = evs[-1]
    assert fin.get("done") is True
    assert toks == ref
    assert fin["tokens_total"] == 16
    assert len(evs) > 2                      # chunked, not buffered
    # streamed TTFB ≡ first token: the reported ttft is a real number
    # well under the full latency
    assert fin["ttft_s"] is not None
    assert fin["latency_s"] > fin["ttft_s"]


def test_client_disconnect_after_two_chunks_is_recorded(http_server):
    """THE disconnect regression (ISSUE 13 satellite): a client that
    closes after 2 chunks → the request is cancelled at the next
    decode-chunk boundary, the slot freed, ``status=disconnected``
    lands in serve.csv (no traceback, not a failure), and the next
    request is served normally."""
    port = http_server.port
    s = socket.create_connection(("127.0.0.1", port))
    body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 48,
                       "top_k": 4, "seed": 1, "stream": True}).encode()
    s.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/json\r\n"
              + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    buf = b""
    while buf.count(b"data: ") < 2:
        chunk = s.recv(4096)
        assert chunk, "server closed before 2 chunks"
        buf += chunk
    s.close()                                # EPIPE on the next write
    deadline = time.monotonic() + 30
    head = {}
    while time.monotonic() < deadline:
        head = http_server.metrics.headline()
        if head.get("requests_disconnected", 0) >= 1:
            break
        time.sleep(0.1)
    assert head["requests_disconnected"] == 1, head
    assert head["streams_active"] == 0, head
    # slot freed: a fresh streamed request completes
    evs = _sse_events(port, {"prompt": [1, 2, 3], "max_new_tokens": 4,
                             "top_k": 4, "seed": 2, "stream": True})
    assert evs[-1].get("done") is True
    csv_path = os.path.join(http_server.metrics.path)
    with open(csv_path) as f:
        stats = [row["status"] for row in csv.DictReader(f)
                 if row["kind"] == "request"]
    assert "disconnected" in stats


# -- metrics schema riders ------------------------------------------------


def test_serve_csv_rows_carry_pid_and_headline_counts(tmp_path):
    m = ServeMetrics(str(tmp_path))
    view = m.replica_view(0, pid=4242)
    req = type("R", (), {
        "id": 1, "prompt": np.zeros(3, np.int32), "tokens": [1, 2, 3],
        "error": None, "exception": None, "ttft_s": 0.1,
        "avg_token_latency_s": 0.01})()
    view.request_done(req, queue_depth=0, active_slots=1)
    m.replica_spawned(replica_id=1, pid=4343)
    m.replica_retired(replica_id=1, pid=4343)
    m.stream_started()
    head = m.headline()
    assert head["replicas_spawned"] == 1
    assert head["replicas_retired"] == 1
    assert head["streams_active"] == 1
    m.stream_ended()
    assert m.headline()["streams_active"] == 0
    assert m.headline()["replicas"]["0"]["pid"] == 4242
    m.close()
    with open(os.path.join(str(tmp_path), "serve.csv")) as f:
        rows = list(csv.DictReader(f))
    assert rows[0]["pid"] == "4242"


def test_read_headline_tolerates_pre_pid_csv(tmp_path):
    """Pinned per repo convention: serve.csv files written BEFORE the
    pid/disconnect schema bump still aggregate — and new-schema files
    read back their disconnect counts."""
    old_header = [c for c in HEADER if c != "pid"]
    path = os.path.join(str(tmp_path), "serve.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(old_header)
        w.writerow(["0.5", "request", "0", "done", "0", "1", "3", "4",
                    "0.1", "0.01", "4", "8.0", "", "", "", "0",
                    "", "", "", "", ""])
        w.writerow(["0.9", "request", "1", "disconnected", "0", "1",
                    "3", "2", "0.1", "0.01", "6", "6.6", "", "", "",
                    "0", "", "", "", "", ""])
    head = read_headline(path)
    assert head["requests_done"] == 1
    assert head["requests_disconnected"] == 1
    assert head["requests_failed"] == 0
    assert head["replicas"]["0"]["requests_done"] == 1


# -- the streamer reads only its new tokens (ISSUE 49) ----------------------


class CountingList(list):
    """A token list that counts the elements its readers copy out of it:
    a slice's length, a whole iteration's (``list(tokens)``)."""

    copied = 0

    def __getitem__(self, key):
        got = super().__getitem__(key)
        if isinstance(key, slice):
            CountingList.copied += len(got)
        return got

    def __iter__(self):
        CountingList.copied += len(self)
        return super().__iter__()


def _request(rid=0, tokens=()):
    req = Request(id=rid, prompt=np.arange(4, dtype=np.int32),
                  sampling=SamplingParams(max_new_tokens=8192),
                  status=RequestStatus.RUNNING)
    req.tokens = CountingList(tokens)
    return req


def _resolve(req, exc=None):
    """What ``Scheduler._complete`` / ``_fail`` do to a request."""
    if exc is None:
        req.status = RequestStatus.DONE
    else:
        req.status, req.exception = RequestStatus.FAILED, exc
    req._event.set()
    req._notify_progress()


def _feed(req, tokens, exc=None, pause=0.0):
    """The driver's side: append and wake, a token an event."""
    for tok in tokens:
        req.tokens.append(tok)
        req._notify_progress()
        if pause:
            time.sleep(pause)
    _resolve(req, exc)


class _Attempts:
    """A router for ``FleetRequest.stream``: its failover installs the
    next scripted attempt."""

    replicas = ()

    def __init__(self, attempts):
        self.attempts = list(attempts)

    def _failover_redispatch(self, fr, exc, wait_deadline):
        fr._inner = self.attempts.pop(0)
        fr.failovers += 1


def _fleet_request(router, inner):
    fr = FleetRequest(router, inner.prompt, inner.sampling, None,
                      time.perf_counter())
    fr._inner = inner
    return fr


def test_wait_progress_returns_the_tail_and_sums_to_the_result():
    req = _request()
    assert req.wait_progress(0, timeout=0.0) == ([], False)
    got, seen = [], 0
    for burst in ([5], [6, 7, 8], [9], [10, 11]):    # a chunk of 3, of 2
        req.tokens.extend(burst)
        new, terminal = req.wait_progress(seen, timeout=0.0)
        assert new == burst and not terminal         # only what is new
        got += new
        seen += len(new)
    assert req.wait_progress(seen, timeout=0.0) == ([], False)
    _resolve(req)
    assert req.wait_progress(seen, timeout=0.0) == ([], True)
    assert got == req.result(timeout=0) == [5, 6, 7, 8, 9, 10, 11]
    # a cursor of 0 reads everything: what a caller without one expects
    assert req.wait_progress(0, timeout=0.0) == (got, True)


def test_a_stream_of_2000_tokens_copies_linear_elements():
    """One event a token, 2,000 of them: the wait surface and the
    router's stream copy a few elements an event, not the tokens so far
    (that was 2,000 x 2,001 / 2 = 2 million twice over)."""
    n = 2000
    req = _request()
    stream = _fleet_request(_Attempts([]), req).stream(timeout=60)
    CountingList.copied = 0
    got = []
    for tok in range(n):
        req.tokens.append(tok)
        got.extend(next(stream))         # returns at once: a token waits
    _resolve(req)
    assert list(stream) == []
    assert got == list(range(n))
    assert CountingList.copied <= 2 * n
    CountingList.copied = 0
    assert req.result(timeout=0) == got  # the one whole copy, at the end
    assert CountingList.copied == n


REPLAY = list(range(100, 112))           # what the first attempt streamed


@pytest.mark.parametrize("fed", ["at_once", "token_by_token"])
@pytest.mark.parametrize("diverges_at", [None, 0, 5, len(REPLAY) - 1])
def test_splice_guard_compares_a_new_attempts_replay(diverges_at, fed):
    """A failover's replay is held to what was yielded, position by
    position: an honest one is suppressed and the stream goes on
    byte-identical; one that diverges anywhere raises typed."""
    first = _request(0, REPLAY)
    _resolve(first, EngineFailedError("test: the replica died"))
    replay = list(REPLAY)
    if diverges_at is not None:
        replay[diverges_at] += 1
    rest = [7, 8, 9]
    second = _request(1)
    fr = _fleet_request(_Attempts([second]), first)
    feeder = threading.Thread(
        target=_feed, args=(second, replay + rest),
        kwargs={"pause": 0.0 if fed == "at_once" else 0.002})
    if fed == "at_once":
        feeder.start()
        feeder.join()
    CountingList.copied = 0
    got = []
    try:
        stream = fr.stream(timeout=60)
        got.extend(next(stream))         # the first attempt's tokens
        assert got == REPLAY
        if fed != "at_once":
            feeder.start()
        if diverges_at is None:
            for chunk in stream:
                got.extend(chunk)
            assert got == REPLAY + rest  # no dupes, no gaps
            assert fr.failovers == 1
        else:
            with pytest.raises(EngineFailedError, match="splice mismatch"):
                for chunk in stream:
                    got.extend(chunk)
            assert got == REPLAY         # nothing of the bad attempt
    finally:
        feeder.join()
    # the guard reads each replayed position once, not the prefix an event
    assert CountingList.copied <= 4 * (len(REPLAY) + len(rest))


class _OneRequestScheduler:
    def __init__(self, req):
        self.req, self.cancelled = req, []

    def submit(self, prompt, sp, **kw):
        return self.req

    def cancel(self, req, reason=""):
        self.cancelled.append(reason)
        return True


@pytest.mark.parametrize("fed", ["at_once", "token_by_token"])
@pytest.mark.parametrize("diverges_at", [None, 0, 5, len(REPLAY) - 1])
def test_worker_frame_splice_guard(diverges_at, fed):
    """The process fleet's half: a submit frame's ``prefix`` is verified
    against the replay token by token and only what follows is shipped;
    a divergence cancels the request and sends the typed error frame."""
    rest = [7, 8, 9]
    replay = list(REPLAY)
    if diverges_at is not None:
        replay[diverges_at] += 1
    req = _request(3)
    feeder = threading.Thread(
        target=_feed, args=(req, replay + rest),
        kwargs={"pause": 0.0 if fed == "at_once" else 0.002})
    worker = WorkerServer.__new__(WorkerServer)
    worker.scheduler = _OneRequestScheduler(req)
    frames = []
    if fed == "at_once":
        feeder.start()
        feeder.join()
    else:
        feeder.start()
    CountingList.copied = 0
    try:
        worker._stream_request(
            {"type": "submit", "id": "r1", "prompt": [1, 2, 3],
             "prefix": REPLAY, "coalesce_s": 0.0},
            lambda frame: frames.append(frame) or True,
            {}, set(), threading.Lock())
    finally:
        feeder.join()
    assert frames[0] == {"type": "accepted", "id": "r1"}
    shipped = [t for f in frames if f["type"] == "chunk"
               for t in f["tokens"]]
    if diverges_at is None:
        assert shipped == rest
        assert frames[-1]["type"] == "done"
        assert frames[-1]["new_tokens"] == len(rest)
        assert not worker.scheduler.cancelled
    else:
        assert shipped == []
        assert frames[-1]["type"] == "error"
        assert frames[-1]["error_type"] == "EngineFailedError"
        assert f"replayed token {diverges_at} is" in frames[-1]["message"]
        assert worker.scheduler.cancelled == ["splice mismatch"]
    assert CountingList.copied <= 4 * (len(REPLAY) + len(rest))
