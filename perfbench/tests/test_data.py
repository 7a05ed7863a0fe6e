"""The request list is a pure function of the seed, and the closed loop
never has more than ``clients`` requests in flight."""

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from perfbench import data
from perfbench.harness import load_json
from perfbench.kinds import closed

TRAFFIC = load_json(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "serve-closed.json"))


def test_request_list_is_a_pure_function_of_the_seed():
    a = data.closed_requests(TRAFFIC, 50304, 3_000_000_017, 300)
    b = data.closed_requests(TRAFFIC, 50304, 3_000_000_017, 300)
    c = data.closed_requests(TRAFFIC, 50304, 18, 300)
    assert a == b and a != c
    p, o = TRAFFIC["prompt_tokens"], TRAFFIC["output_tokens"]
    for r in a:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o["min"] <= r["max_new_tokens"] <= o["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= 1024
    assert sum(r["greedy"] for r in a) == 300 // TRAFFIC["greedy_every"]
    cut = data.first_round_cut(TRAFFIC, 5, 128)
    assert (cut == data.first_round_cut(TRAFFIC, 5, 128)).all()
    assert 0.1 <= cut.min() and cut.max() <= 1.0


def test_token_stream_is_seeded_and_records_what_it_fed():
    a = data.TokenStream(9, 1, 256, 16, 4096)
    b = data.TokenStream(9, 1, 256, 16, 4096)
    assert (a.data == b.data).all()
    assert (a.data != data.TokenStream(9, 2, 256, 16, 4096).data).any()
    a.take([0, 0])                  # fit's example batch: left out
    x, y = a.take([3, 77])
    assert (x[0, 1:] == y[0, :-1]).all() and x.shape == (2, 16)
    (fx, fy), = a.step_batches(1)
    assert (fx == x).all() and (fy == y).all()


class _Fake(BaseHTTPRequestHandler):
    """Streams ``max_new_tokens`` tokens and counts who is in flight."""
    lock = threading.Lock()
    now = 0
    most = 0

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        cls = type(self)
        with cls.lock:
            cls.now += 1
            cls.most = max(cls.most, cls.now)
        try:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Connection", "close")
            self.end_headers()
            for i in range(body["max_new_tokens"]):
                self.wfile.write(b"data: " + json.dumps(
                    {"tokens": [i]}).encode() + b"\n\n")
                self.wfile.flush()
                time.sleep(0.001)
        finally:
            # before the last event: the client sends its next request
            # the moment it reads this one's end
            with cls.lock:
                cls.now -= 1
        self.wfile.write(b'data: {"done": true}\n\n')


def test_closed_loop_never_exceeds_its_clients():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Fake)
    th = threading.Thread(target=httpd.serve_forever)
    th.start()
    stop = threading.Event()
    reqs = data.closed_requests(TRAFFIC, 100, 1, 400)
    cursor, lock = [0], threading.Lock()

    def feed(k):
        with lock:
            i = cursor[0]
            cursor[0] += 1
        return (i, closed.request_body(reqs[i], 5)) if i < len(reqs) else None

    clients = [closed.Client(k, httpd.server_address[1], feed, stop)
               for k in range(6)]
    for c in clients:
        c.start()
    time.sleep(1.0)
    stop.set()
    for c in clients:
        c.join(timeout=30)
    httpd.shutdown()
    httpd.server_close()
    th.join()
    done = sum(rec["done"] for c in clients for rec in c.log)
    assert done > 12 and _Fake.most <= 6
    assert not any(c.failed for c in clients)
    # requests were taken in order from the one list
    sent = sorted(rec["i"] for c in clients for rec in c.log)
    assert sent == list(range(len(sent)))


def _bursts(rounds, slots=8, period=0.4):
    """One record a slot: a token every ``period`` s in each of
    ``rounds``, sent at -1 and done with its last token."""
    return [{"i": k, "sent": -1.0, "done": True,
             "stamps": [r * period + k * 0.002 for r in rounds],
             "tokens": [7] * len(rounds)} for k in range(slots)]


def test_the_window_counts_every_token_inside_it_and_nothing_else():
    requests = [{"prompt": [1, 2], "greedy": k % 2 == 1} for k in range(8)]
    got = closed.reduce_window(_bursts(range(10)), requests, 0.5, 3.5)
    # bursts at 0.8 ... 3.2 arrive inside: seven rounds of eight tokens
    assert got["arrived"] == 7 * 8
    # the edges cut the rounds 0.4-0.8 and 3.2-3.6 at three quarters and
    # one quarter, of which the window takes its share: 7.5 rounds in 3 s
    assert got["tokens"] == pytest.approx(7.5 * 8)
    assert len(got["gaps"]) == 7 * 8 and got["sent"] == 0
    assert got["finished"] == []         # the last token came at 3.6
    got = closed.reduce_window(_bursts(range(10)), requests, 0.5, 3.7)
    assert len(got["finished"]) == 8
    assert sum(f["greedy"] for f in got["finished"]) == 4


def test_an_edge_moved_a_little_moves_the_count_a_little():
    """The plain count of arrivals jumps by a burst when an edge passes
    one; the count by shares does not."""
    requests = [{"prompt": [1], "greedy": False} for _ in range(8)]
    recs = _bursts(range(10))
    before = closed.reduce_window(recs, requests, 0.5, 3.19)
    after = closed.reduce_window(recs, requests, 0.5, 3.23)
    assert after["arrived"] - before["arrived"] == 8
    assert after["tokens"] - before["tokens"] == pytest.approx(
        8 * 0.04 / 0.4)
    # a first token's interval runs from the send
    one = [{"i": 0, "sent": 1.0, "done": False, "stamps": [3.0],
            "tokens": [7]}]
    assert closed.reduce_window(one, requests, 2.0, 4.0)["tokens"] == 0.5
    # a token that never arrived counts nothing
    none = [{"i": 0, "sent": 1.0, "done": False, "stamps": [],
             "tokens": []}]
    assert closed.reduce_window(none, requests, 0.0, 4.0)["tokens"] == 0


@pytest.mark.parametrize("stalled", [range(7, 10), range(0, 3)])
def test_a_stall_at_either_edge_of_the_window_lowers_the_rate(stalled):
    """Nothing re-cuts the window after the fact: rounds that never came,
    at its end or at its start, are missing from the count while the
    seconds stay."""
    requests = [{"prompt": [1], "greedy": False} for _ in range(8)]
    whole = closed.reduce_window(_bursts(range(10)), requests, -0.1, 3.9)
    rounds = [r for r in range(10) if r not in stalled]
    cut = closed.reduce_window(_bursts(rounds), requests, -0.1, 3.9)
    assert whole["arrived"] == 80 and cut["arrived"] == 56
    # the stretched intervals hold the stall: at the end the three rounds
    # are lost outright, at the start the first burst to come is spread
    # over the 2.2 s since the send, 1.3 of them inside
    assert cut["tokens"] < 0.8 * whole["tokens"]
