"""Traffic kind ``closed_model``: the closed loop of ``closed.py`` for a
model that the configuration file names (``model_type``), not GPT-2.

The loop is ``closed.py``'s, imported: its clients, its window arithmetic
(``reduce_window``), its choice of judged requests and its verdict's
numbers. What this file brings is what ``closed.py:run`` builds by hand
for GPT-2: the model's config for the program, its weights from the seed,
its plain reference. The ``facts`` have ``closed.py``'s shape (``kind`` is
``"closed"``: the ``serve_*`` readers read them unchanged) plus what the
model's own per-layer metrics read: the sums of what the model counted
in the window's decode steps (``model_counters``, from ``/stats``), the
``serve.admit`` spans' seconds and prompt tokens, and the byte and
operation counts of ``perfbench/flops_moe.py``.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time

import numpy as np

from perfbench import data, harness, weights_moe, xplane
from perfbench.kinds import closed
from perfbench.references import command_a_plus as reference


def model_config(sizes: dict):
    """The program's config for the configuration file's sizes."""
    from gym_tpu.models.cohere2_moe import Cohere2MoeConfig
    layers = int(sizes["num_hidden_layers"])
    dtype = {"bfloat16": "bf16", "float32": "f32"}[sizes["dtype"]]
    return Cohere2MoeConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        layer_types=tuple(sizes["layer_types"][:layers]),
        sliding_window=sizes["sliding_window"],
        rope_theta=float(sizes["rope_theta"]),
        layer_norm_eps=sizes["layer_norm_eps"],
        logit_scale=float(sizes["logit_scale"]),
        num_experts=sizes["num_experts_routed"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        num_shared_experts=sizes["num_shared_experts"],
        norm_topk_prob=sizes["norm_topk_prob"],
        held_experts=tuple(sizes["held_experts"]),
        block_size=sizes["max_position_embeddings"],
        weights_dtype=dtype, kv_dtype=dtype)


def paired(requests: list, traffic: dict) -> list:
    """``data.closed_requests``' list with each block's output lengths
    dealt to its prompts by ONE pairing for every seed:
    ``output_rank_of_prompt_rank[r]`` is the rank, among the block's
    output lengths, that the prompt of rank ``r`` gets. The seed still
    orders the block. A decode round's time grows with the positions its
    rows hold, so a seed that deals long outputs to long prompts keeps
    long rows resident and runs 4% slower than one that does not (five
    seeds, PERF.md section 6); with the pairs fixed, every block holds
    the same requests and the sum of prompt x output is what independent
    draws expect (1.001 of it). A last, partial block stays as dealt."""
    n, pair = int(traffic["block_of"]), traffic["output_rank_of_prompt_rank"]
    if sorted(pair) != list(range(n)):
        raise ValueError("output_rank_of_prompt_rank must be a permutation "
                         f"of range(block_of = {n})")
    out = [dict(r) for r in requests]
    for lo in range(0, len(out) - n + 1, n):
        block = out[lo:lo + n]
        outputs = sorted(r["max_new_tokens"] for r in block)
        by_prompt = np.argsort([len(r["prompt"]) for r in block],
                               kind="stable")
        for rank, i in enumerate(by_prompt):
            block[i]["max_new_tokens"] = outputs[pair[rank]]
    return out


def judge(ctx, picked: list, mode: str = "f32", faults=()) -> dict:
    """``closed.judge`` against this model's reference: over the picked
    requests, how far, at each served position, the served token's
    reference logit lies below the reference's best. ``mode`` other than
    ``"f32"`` (or ``faults``, a wrong reading of the description planted
    in the reference): the token that arithmetic puts first takes the
    served token's place. The weights (``run`` leaves the program's own
    in ``ctx``; made from the seed where it has not) and each request's
    float32 logits (on the host: 128 KiB a served token) are kept in
    ``ctx`` for the calls that follow."""
    t, sizes = ctx["traffic"], ctx["sizes"]
    if not picked:
        return {"requests": 0, "tokens": 0, "widest": float("inf"),
                "mean": float("inf")}
    if "reference_params" not in ctx:
        ctx["reference_params"] = weights_moe.make_params(
            sizes, ctx["args"].seed, device=ctx["devices"][0])
    params, pad = ctx["reference_params"], int(t["reference_pad_multiple"])
    refs = ctx.setdefault("reference_logits", {})
    gaps = []
    for i, r in enumerate(picked):
        key = (i, len(r["prompt"]), tuple(r["tokens"]))
        if key not in refs:
            refs[key] = np.asarray(reference.served_logits(
                params, sizes, r["prompt"], r["tokens"], pad_multiple=pad))
        gaps.append(reference.served_gaps(
            params, sizes, r["prompt"], r["tokens"], pad_multiple=pad,
            mode=mode, faults=faults, ref=refs[key]))
    gaps = np.concatenate(gaps)
    return {"requests": len(picked), "tokens": int(gaps.size),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "not_best": int((gaps > 0).sum())}


def verdict_rows(ctx, verdict: dict, failed: int, left: list) -> list:
    """The rows that decide ``correct``, under ``closed.py``'s names."""
    limits, lower = ctx["limits"], ctx["traffic"]["control_mode"]
    vs_lower = closed.gap_ratio(verdict["mean"], verdict["lower"]["mean"])
    return [
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


def counters_delta(stats0: dict, stats1: dict) -> dict:
    """What the model counted between two ``/stats`` samples; empty where
    the program serves no such counters."""
    c0, c1 = stats0.get("model_counters"), stats1.get("model_counters")
    if not c1:
        return {}
    return {k: (np.asarray(v, np.int64)
                - np.asarray((c0 or {}).get(k, 0), np.int64)).tolist()
            for k, v in c1.items()}


def admit_spans(t_open: float, t_close: float) -> dict:
    """Seconds and prompt tokens of the ``serve.admit`` spans that closed
    inside the window (the recorder's clock is ``perf_counter``, the
    window's ``monotonic``: the offset between them is taken now)."""
    try:
        from gym_tpu.utils import trace
    except ImportError:
        return {}
    shift = time.monotonic() - time.perf_counter()
    recs = [r for r in trace.records("serve.admit")
            if "prompt_tokens" in r.ids
            and t_open <= r.t1 * 1e-9 + shift < t_close]
    return {"count": len(recs), "seconds": sum(r.seconds for r in recs),
            "prompt_tokens": sum(int(r.ids["prompt_tokens"])
                                 for r in recs)}


def run(ctx) -> dict:
    from gym_tpu import programs
    from gym_tpu.serve.__main__ import create_server

    t, sizes, log = ctx["traffic"], ctx["sizes"], ctx["log"]
    args, devices, seconds = ctx["args"], ctx["devices"], ctx["seconds"]
    slots = int(t["num_slots"])
    block = sizes["max_position_embeddings"]
    for sub in ("serve", "trace"):
        shutil.rmtree(os.path.join(ctx["out_dir"], sub), ignore_errors=True)

    requests = paired(data.closed_requests(t, sizes["vocab_size"], args.seed,
                                           int(t["request_count"])), t)
    first_cut = data.first_round_cut(t, args.seed, slots)
    # the config first: a program without this model fails here, in
    # seconds, before 9.5 GB of weights are made
    cfg = model_config(sizes)
    params = weights_moe.make_params(sizes, args.seed, device=devices[0])
    handle = create_server(
        params, cfg, port=0, num_slots=slots,
        decode_chunk=int(t["decode_chunk"]), page_size=int(t["page_size"]),
        kv_pages=int(t["kv_pages"]), max_queue=max(64, 2 * slots),
        warmup=False, dispatch_timeout=float(t["dispatch_timeout_s"]),
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
            warm = {"prompt": rng.integers(0, sizes["vocab_size"],
                                           b // 2 + 1).tolist(),
                    "max_new_tokens": 2, "seed": 0, "greedy": False}
            c = closed.Client(-1, port, lambda k: None, stop)
            rec = {"tokens": [], "stamps": [], "done": False}
            c.stream(closed.request_body(warm), rec)
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
                return i, closed.request_body(req, n_new)

        clients = [closed.Client(k, port, feed, stop) for k in range(slots)]
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
        stats0 = closed.get_stats(port)
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
            samples.append(closed.get_stats(port))
            time.sleep(min(float(t["stats_every_s"]),
                           max(0.0, t_close - time.monotonic())))
        stats1 = closed.get_stats(port)
        gc_pauses = gc_clock.close(t_open, t_close)
        if cursor[0] >= len(requests):
            raise RuntimeError("the request list ran out inside the window: "
                               "the mix's request_count is too small")
        trace_span = tracer.finish() if tracer else None
        peak = harness.memory_peak_bytes(devices)
        in_window = ctx["compiles"].between(t_open, t_close)
        counters = reg.counters()
        admits = admit_spans(t_open, t_close)
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
    # the server's pool and programs go; the weights stay for the
    # reference, which reads the values the program was given
    ctx["reference_params"] = params
    del handle, params
    gc.collect()

    records = [rec for c in clients for rec in c.log]
    got = closed.reduce_window(records, requests, t_open, t_close,
                               trace_span)
    finished, sent_in = got["finished"], got["sent"]
    failed = sum(c.failed for c in clients)
    delta = {k: stats1[k] - stats0[k]
             for k in ("tokens_generated", "decode_steps", "prefills")}
    delta["num_slots"] = stats1["num_slots"]
    trace = xplane.summarize(tracer.trace_dir) if trace_span else None

    # -- after the window: the reference on what was served --------------
    t_ref0 = time.monotonic()
    picked = closed.pick_judged(ctx, finished)
    with open(os.path.join(ctx["out_dir"], f"judged-{args.seed}.json"),
              "w") as f:
        json.dump({"seed": args.seed, "picked": picked}, f)
    verdict = judge(ctx, picked)
    verdict["lower"] = judge(ctx, picked, t["control_mode"])
    rows = verdict_rows(ctx, verdict, failed, left)
    log({"window": {
        "seconds": seconds, "tokens": got["tokens"],
        "tokens_arrived": got["arrived"], "gc_pauses": gc_pauses,
        "requests_sent": sent_in, "requests_finished": len(finished),
        "rounds": delta["decode_steps"], "prefills": delta["prefills"],
        "prefill_buckets": stats1.get("prefill_buckets"),
        "admits": admits, "stats_samples": len(samples),
        "judged": verdict, "reference_s": time.monotonic() - t_ref0,
        "threads_left": left, "kv_pages": stats1.get("kv_pages"),
        "paged_kernel_dispatches": stats1.get("paged_kernel_dispatches"),
        "registry": counters}})
    facts = {
        "kind": "closed", "trace": trace, "sizes": sizes, "traffic": t,
        "chips": ctx["chips"], "device_kind": devices[0].device_kind,
        "memory_peak_bytes": peak, "token_gaps_s": got["gaps"],
        "ttft_s": got["ttft"], "stats_samples": samples,
        "stats_delta": delta, "tokens_in_trace": got["tokens_in_trace"],
        "compile_s": counters["compile_seconds"],
        "xla_compiles_in_window": in_window,
        "model_counters": counters_delta(stats0, stats1),
        "admit_spans": admits,
    }
    return {"correct": all(r["ok"] for r in rows),
            "attempted": sent_in, "failed": failed, "compared": rows,
            "end_to_end": {"serve_tokens_per_s": got["tokens"] / seconds,
                           "setup_s": t_open - ctx["t0"]},
            "facts": facts}
