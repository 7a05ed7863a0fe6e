"""Traffic kind ``closed``: a closed loop of as many clients as the server
has slots, over loopback HTTP.

The whole stack (``create_server``: engine, scheduler, HTTP) runs on
threads of this one process with weights the benchmark made on the device.
Each client streams one ``/generate`` request and sends its next the
moment the last token arrives; requests come in order from one seeded
list, so load is pinned and not drawn. Set-up sends one request per
prefill bucket the list uses, then starts the clients a few at a time; the
window opens once every client has received a token and lasts
``--seconds``. Tokens are counted by their arrival time at the client
(``reduce_window``).

After the window the clients hang up, the server is shut down and freed,
and a seeded sample of the finished greedy requests (the longest among
them) is judged against the plain reference.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import shutil
import threading
import time
import urllib.request

import numpy as np

from perfbench import data, harness, reference, weights, xplane


class Client(threading.Thread):
    """One closed-loop client. ``log`` holds a record per request sent:
    ``{"i", "sent", "stamps": [arrival per token], "tokens", "done"}``."""

    def __init__(self, k: int, port: int, feed, stop: threading.Event):
        super().__init__(name=f"perfbench-client-{k}", daemon=True)
        self.k, self.port, self.feed, self.stop_ev = k, port, feed, stop
        self.log: list = []
        self.failed = 0
        self.sock = None

    def run(self) -> None:
        while not self.stop_ev.is_set():
            job = self.feed(self.k)
            if job is None:
                return
            i, body = job
            rec = {"i": i, "sent": time.monotonic(), "stamps": [],
                   "tokens": [], "done": False}
            self.log.append(rec)
            try:
                self.stream(body, rec)
            except (OSError, http.client.HTTPException, ValueError):
                if not self.stop_ev.is_set():
                    self.failed += 1
            if not rec["done"] and not self.stop_ev.is_set():
                self.failed += 1

    def stream(self, body: dict, rec: dict) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=600)
        try:
            conn.request("POST", "/generate", json.dumps(body),
                         {"Content-Type": "application/json"})
            # the reply takes the socket over and the connection forgets
            # it: hang_up needs a handle of its own
            self.sock = conn.sock
            reply = conn.getresponse()
            if reply.status != 200:
                raise ValueError(f"status {reply.status}")
            for line in reply:
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                event = json.loads(line[6:])
                if "error" in event:
                    raise ValueError(event["error"])
                if event.get("done"):
                    rec["done"] = True
                    return
                for tok in event.get("tokens", ()):
                    rec["tokens"].append(tok)
                    rec["stamps"].append(now)
        finally:
            self.sock = None
            conn.close()

    def hang_up(self) -> None:
        sock = self.sock
        if sock is not None:
            try:
                sock.shutdown(2)
            except OSError:
                pass


def share_inside(lo: float, hi: float, t_open: float, t_close: float):
    """The share of the interval ``[lo, hi)`` that lies inside the
    window."""
    if hi <= lo:
        return 1.0 if t_open <= hi < t_close else 0.0
    return max(0.0, min(hi, t_close) - max(lo, t_open)) / (hi - lo)


def reduce_window(records: list, requests: list, t_open: float,
                  t_close: float, trace_span=None) -> dict:
    """The clients' logs reduced over the window ``[t_open, t_close)`` and
    nothing but it.

    ``tokens``: every token counts by the share of its interval (from the
    stream's previous token, or from the send for a first token, to its
    arrival at the client) that lies inside the window: 1 for a token
    made wholly inside, a fraction for one whose interval straddles an
    edge. A decode step's tokens arrive in one burst of as many as there
    are slots, some hundreds of ms apart, so a plain count of arrivals
    (``arrived``, kept beside it) moves by a whole burst, 1.2% of a
    window of 84, with the edge's phase. Nothing is re-cut: a stall
    anywhere in the window, its edges included, stretches the intervals
    that hold it and lowers the count, and a token that never arrives
    counts nothing.

    Also: the gaps between a stream's tokens that end inside the window,
    send-to-first-token of the requests sent inside it, and the requests
    that finished inside it."""
    out = {"tokens": 0.0, "arrived": 0, "tokens_in_trace": 0, "gaps": [],
           "ttft": [], "finished": [], "sent": 0}
    for rec in records:
        st = rec["stamps"]
        for lo, hi in zip([rec["sent"]] + st, st):
            out["tokens"] += share_inside(lo, hi, t_open, t_close)
        out["arrived"] += sum(1 for s in st if t_open <= s < t_close)
        if trace_span:
            out["tokens_in_trace"] += sum(
                1 for s in st if trace_span[0] <= s < trace_span[1])
        out["gaps"].extend(b - a for a, b in zip(st, st[1:])
                           if t_open <= b < t_close)
        if t_open <= rec["sent"] < t_close:
            out["sent"] += 1
            if st:
                out["ttft"].append(st[0] - rec["sent"])
        if rec["done"] and st and t_open <= st[-1] < t_close:
            req = requests[rec["i"]]
            out["finished"].append({"prompt": req["prompt"],
                                    "greedy": req["greedy"],
                                    "tokens": rec["tokens"]})
    return out


def request_body(req: dict, max_new: int = None) -> dict:
    body = {"prompt": req["prompt"], "stream": True, "seed": req["seed"],
            "max_new_tokens": max_new or req["max_new_tokens"]}
    if req["greedy"]:
        body["top_k"] = 1
    return body


def get_stats(port: int) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats",
                                timeout=60) as reply:
        return json.loads(reply.read())


def pick_judged(ctx, finished: list) -> list:
    """A sample, drawn from the seed, of the greedy requests the window
    finished, with the longest in it."""
    rng = np.random.default_rng([int(ctx["args"].seed), 0x5a3b1e])
    greedy = [r for r in finished if r["greedy"]]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in greedy if r is not longest]
    n = min(int(ctx["traffic"]["judged_requests"]) - 1, len(rest))
    return [longest] + [rest[j] for j in
                        rng.choice(len(rest), n, replace=False)]


def judge(ctx, picked: list, mode: str = "f32") -> dict:
    """Over the picked requests: how far, at each served position, the
    served token's reference logit lies below the reference's best; the
    widest such gap and their mean. (``mode`` other than ``"f32"``: the
    control, see ``reference.served_gaps``.)"""
    t, sizes = ctx["traffic"], ctx["sizes"]
    if not picked:
        return {"requests": 0, "tokens": 0, "widest": float("inf"),
                "mean": float("inf")}
    params = weights.make_params(
        sizes, ctx["args"].seed, device=ctx["devices"][0],
        block_kernel_scale=float(t["block_kernel_scale"]))
    gaps = np.concatenate([
        reference.served_gaps(
            params, r["prompt"], r["tokens"], sizes["n_head"],
            pad_to=sizes["n_positions"],
            n_pos=int(t["output_tokens"]["max"]), mode=mode)
        for r in picked])
    return {"requests": len(picked), "tokens": int(gaps.size),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "not_best": int((gaps > 0).sum())}


def gap_ratio(mean: float, mean_lower: float) -> float:
    """The served tokens' mean logit gap against the lower precision's at
    the same positions: 1.0 for a program that serves in that precision,
    0 for one whose every token is the reference's best."""
    if mean_lower > 0:
        return mean / mean_lower
    return 0.0 if mean == 0 else float("inf")


def run(ctx) -> dict:
    from gym_tpu import programs
    from gym_tpu.models.nanogpt import GPTConfig
    from gym_tpu.serve.__main__ import create_server

    t, sizes, log = ctx["traffic"], ctx["sizes"], ctx["log"]
    args, devices, seconds = ctx["args"], ctx["devices"], ctx["seconds"]
    slots = int(t["num_slots"])
    block = sizes["n_positions"]
    for sub in ("serve", "trace"):
        shutil.rmtree(os.path.join(ctx["out_dir"], sub), ignore_errors=True)

    requests = data.closed_requests(t, sizes["vocab_size"], args.seed,
                                    int(t["request_count"]))
    first_cut = data.first_round_cut(t, args.seed, slots)
    params = weights.make_params(
        sizes, args.seed, device=devices[0],
        block_kernel_scale=float(t["block_kernel_scale"]))
    cfg = GPTConfig(block_size=block, vocab_size=sizes["vocab_size"],
                    n_layer=sizes["n_layer"], n_head=sizes["n_head"],
                    n_embd=sizes["n_embd"],
                    dropout=weights.dropout_rate(sizes))
    handle = create_server(
        params, cfg, port=0, num_slots=slots,
        decode_chunk=int(t["decode_chunk"]), page_size=int(t["page_size"]),
        max_queue=max(64, 2 * slots), warmup=False,
        metrics_dir=os.path.join(ctx["out_dir"], "serve"))
    http_thread = threading.Thread(target=handle.httpd.serve_forever,
                                   name="perfbench-http")
    http_thread.start()
    port = handle.port
    stop = threading.Event()
    clients: list = []
    reg = programs.default_registry()
    try:
        # -- set-up: one request per prefill bucket the list uses ---------
        buckets = sorted({data.prompt_bucket(len(r["prompt"]), block)
                          for r in requests})
        rng = np.random.default_rng([int(args.seed), 0xb0c4])
        for b in buckets:
            # the shortest prompt of the bucket: it leaves room for output
            warm = {"prompt": rng.integers(0, sizes["vocab_size"],
                                           b // 2 + 1).tolist(),
                    "max_new_tokens": 2, "seed": 0, "greedy": False}
            c = Client(-1, port, lambda k: None, stop)
            rec = {"tokens": [], "stamps": [], "done": False}
            c.stream(request_body(warm), rec)
            if not rec["done"]:
                raise RuntimeError(f"warm-up request of bucket {b} failed")
        log({"warmed_buckets": buckets, "registry": reg.counters()})

        # -- the clients, a few at a time ---------------------------------
        lock = threading.Lock()
        cursor = [0]
        first_done = set()

        def feed(k: int):
            with lock:
                i = cursor[0]
                if i >= len(requests):
                    return None
                cursor[0] += 1
                req = requests[i]
                n_new = req["max_new_tokens"]
                if k not in first_done:
                    first_done.add(k)
                    n_new = max(1, int(round(n_new * first_cut[k])))
                return i, request_body(req, n_new)

        clients = [Client(k, port, feed, stop) for k in range(slots)]
        for lo in range(0, slots, int(t["connect_batch"])):
            for c in clients[lo:lo + int(t["connect_batch"])]:
                c.start()
            time.sleep(float(t["connect_pause_s"]))
        deadline = time.monotonic() + 600
        while not all(c.log and c.log[0]["stamps"] for c in clients):
            if time.monotonic() > deadline or any(c.failed for c in clients):
                raise RuntimeError("the clients did not all receive a "
                                   "first token during set-up")
            time.sleep(0.05)

        # -- the window ---------------------------------------------------
        stats0 = get_stats(port)
        t_open = time.monotonic()
        t_close = t_open + seconds
        samples, tracer = [], None
        if args.trace:
            span = min(float(t["trace_seconds"]), seconds / 4)
            trace_at = t_open + (seconds - span) / 2
            tracer = harness.MidRunTrace(
                os.path.join(ctx["out_dir"], "trace"), span,
                lambda: time.monotonic() >= trace_at)
            tracer.start()
        gc_clock = harness.GcClock()
        while time.monotonic() < t_close:
            samples.append(get_stats(port))
            time.sleep(min(float(t["stats_every_s"]),
                           max(0.0, t_close - time.monotonic())))
        stats1 = get_stats(port)
        gc_pauses = gc_clock.close(t_open, t_close)
        if cursor[0] >= len(requests):
            raise RuntimeError("the request list ran out inside the window: "
                               "the mix's request_count is too small")
        trace_span = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes(devices)
        in_window = ctx["compiles"].between(t_open, t_close)
        counters = reg.counters()
        # every stream's next token closes the interval that straddles
        # the window's end; one that does not come in time counts nothing
        grace = time.monotonic() + float(t["edge_grace_s"])
        while time.monotonic() < grace and not all(
                c.log[-1]["stamps"] and c.log[-1]["stamps"][-1] >= t_close
                for c in clients):
            time.sleep(0.02)
    finally:
        # -- hang up, shut down, free ------------------------------------
        stop.set()
        for c in clients:
            c.hang_up()
        handle.close(drain_deadline_s=30.0)
        http_thread.join(timeout=60)
        for c in clients:
            c.join(timeout=30)
    left = [th.name for th in threading.enumerate()
            if th.name.startswith(("perfbench-", "gym-tpu"))
            and th.is_alive()]
    del handle, params
    gc.collect()

    records = [rec for c in clients for rec in c.log]
    got = reduce_window(records, requests, t_open, t_close, trace_span)
    window_tokens, gaps, ttft = got["tokens"], got["gaps"], got["ttft"]
    closed_late = max((rec["stamps"][-1] for rec in records
                       if rec["stamps"]), default=t_close) - t_close
    finished, sent_in = got["finished"], got["sent"]
    tokens_in_trace = got["tokens_in_trace"]
    failed = sum(c.failed for c in clients)
    tokens_per_s = window_tokens / seconds
    setup_s = t_open - ctx["t0"]
    delta = {k: stats1[k] - stats0[k]
             for k in ("tokens_generated", "decode_steps", "prefills")}
    delta["num_slots"] = stats1["num_slots"]
    trace = xplane.summarize(tracer.trace_dir) if trace_span else None

    # -- after the window: the reference on what was served --------------
    t_ref0 = time.monotonic()
    picked = pick_judged(ctx, finished)
    with open(os.path.join(ctx["out_dir"], f"judged-{args.seed}.json"),
              "w") as f:
        json.dump({"seed": args.seed, "picked": picked}, f)
    with open(os.path.join(ctx["out_dir"], f"arrivals-{args.seed}.json"),
              "w") as f:
        # every stream's send and arrivals against the window's opening:
        # what a window of another length would have counted
        json.dump({"seed": args.seed, "seconds": seconds, "streams": [
            {"sent": rec["sent"] - t_open,
             "stamps": [s - t_open for s in rec["stamps"]]}
            for rec in records]}, f)
    verdict = judge(ctx, picked)
    # the yardstick: the same positions judged by the token that the next
    # lower precision puts first. How often two logits lie close enough to
    # swap differs from seed to seed by a factor of two, for the served
    # tokens and for these alike, and leaves their ratio
    lower = t["control_mode"]
    verdict["lower"] = judge(ctx, picked, lower)
    vs_lower = gap_ratio(verdict["mean"], verdict["lower"]["mean"])
    ref_s = time.monotonic() - t_ref0
    limits = ctx["limits"]
    rows = [
        {"name": "served_logit_gap_widest", "value": verdict["widest"],
         "limit": limits["served_logit_gap_widest"],
         "ok": bool(verdict["widest"] <= limits["served_logit_gap_widest"])},
        {"name": "served_logit_gap_vs_" + lower, "value": vs_lower,
         "limit": limits["served_logit_gap_vs_" + lower],
         "ok": bool(vs_lower <= limits["served_logit_gap_vs_" + lower]),
         "mean": verdict["mean"], "mean_" + lower: verdict["lower"]["mean"]},
        {"name": "requests_failed", "value": failed, "limit": 0,
         "ok": failed == 0},
        {"name": "threads_left", "value": len(left), "limit": 0,
         "ok": not left},
    ]
    log({"window": {
        "seconds": seconds, "tokens": window_tokens,
        "tokens_arrived": got["arrived"], "last_arrival_after_close_s":
        closed_late, "gc_pauses": gc_pauses,
        "requests_sent": sent_in, "requests_finished": len(finished),
        "gaps_counted": len(gaps), "rounds": delta["decode_steps"],
        "prefills": delta["prefills"],
        "prefill_buckets": stats1.get("prefill_buckets"),
        "stats_samples": len(samples), "judged": verdict,
        "reference_s": ref_s, "threads_left": left,
        "kv_pages": stats1.get("kv_pages"), "registry": counters}})
    facts = {
        "kind": "closed", "trace": trace, "sizes": sizes, "traffic": t,
        "chips": ctx["chips"], "device_kind": devices[0].device_kind,
        "memory_peak_bytes": peak, "token_gaps_s": gaps, "ttft_s": ttft,
        "stats_samples": samples, "stats_delta": delta,
        "tokens_in_trace": tokens_in_trace,
        "compile_s": counters["compile_seconds"],
        "xla_compiles_in_window": in_window,
    }
    return {"correct": all(r["ok"] for r in rows),
            "attempted": sent_in, "failed": failed, "compared": rows,
            "end_to_end": {"serve_tokens_per_s": tokens_per_s,
                           "setup_s": setup_s},
            "facts": facts}
