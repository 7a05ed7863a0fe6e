"""Traffic kind ``closed_xing4``: the closed loop of ``closed.py`` for the
``xing4_0`` configuration (four residual streams a token mixed around
every sub-layer by a manifold-constrained hyper-connection; multi-head
latent attention over pages that hold the latent; a leading dense layer,
then 64 sigmoid-routed experts with a selection bias, ALL held, beside a
shared one; untied head).

The loop is ``closed.py``'s and the model-neutral pieces are imported from
the kinds that have them: clients and window arithmetic (``closed.py``),
the fixed pairing of a block's lengths, the verdict's rows, the counters'
delta and the admissions' spans (``closed_model.py``), one schedule of
sizes for every seed (``closed_keye.py``: ``steadied``, ``dealt_cuts``),
the ``serve.admit`` records as the window goes (``closed_brumby.py``:
``note_admits``), the admissions a traced stretch held with their
lengths' squares (``closed_kimi.py``: ``admits_held``; the expanded
attend is that cell's too). What this file brings is what those bind to
their own model at import: the program's config for this model, its
weights from the seed (``perfbench/weights_xing4.py``) and its plain
reference (``perfbench/references/xing4.py``); the ``facts`` are of
``closed_model.py``'s shape, which every ``serve_*`` reader reads
unchanged.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time

import numpy as np

from perfbench import data, harness, weights_xing4, xplane
from perfbench.kinds import closed
from perfbench.kinds.closed_brumby import note_admits
from perfbench.kinds.closed_keye import dealt_cuts, steadied
from perfbench.kinds.closed_kimi import admits_held
from perfbench.kinds.closed_model import (admit_spans, counters_delta,
                                          paired, verdict_rows)
from perfbench.references import xing4 as reference


def model_config(sizes: dict):
    """The program's config for the configuration file's sizes
    (``prefill_rows``: the positions a pass of a prefill takes)."""
    from gym_tpu.models.xing4 import Xing4Config
    dtype = {"bfloat16": "bf16", "float32": "f32"}[sizes["dtype"]]
    rs = sizes["rope_scaling"]
    return Xing4Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_hidden_layers=int(sizes["num_hidden_layers"]),
        num_attention_heads=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        first_k_dense_replace=int(sizes["first_k_dense_replace"]),
        n_routed_experts=sizes["n_routed_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        n_shared_experts=sizes["n_shared_experts"],
        norm_topk_prob=sizes["norm_topk_prob"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        rms_norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        held_experts=tuple(sizes["held_experts"]),
        block_size=sizes["max_position_embeddings"],
        prefill_rows=int(sizes["prefill_rows"]),
        hc_mult=int(sizes["hc_mult"]),
        hc_sinkhorn_iters=int(sizes["hc_sinkhorn_iters"]),
        hc_eps=float(sizes["hc_eps"]),
        mhc_h_res_clamp_min=float(sizes["mhc_h_res_clamp_min"]),
        mhc_h_res_clamp_max=float(sizes["mhc_h_res_clamp_max"]),
        weights_dtype=dtype, kv_dtype=dtype)


def judge(ctx, picked: list, mode: str = "f32", faults=()) -> dict:
    """``closed_model.judge`` against this model's reference: over the
    picked requests, how far, at each served position, the served token's
    reference logit lies below the reference's best (``mode`` other than
    ``"f32"``, or ``faults``: the token that arithmetic puts first takes
    the served token's place). Weights and each request's float32 logits
    are kept in ``ctx`` for the calls that follow; ``seconds`` says what
    each request took (a run has a time limit, and the reference is most
    of what follows the window)."""
    t, sizes = ctx["traffic"], ctx["sizes"]
    if not picked:
        return {"requests": 0, "tokens": 0, "widest": float("inf"),
                "mean": float("inf")}
    if "reference_params" not in ctx:
        ctx["reference_params"] = weights_xing4.make_params(
            sizes, ctx["args"].seed, device=ctx["devices"][0])
    params, pad = ctx["reference_params"], int(t["reference_pad_multiple"])
    refs = ctx.setdefault("reference_logits", {})
    gaps, took = [], []
    for i, r in enumerate(picked):
        t_req = time.monotonic()
        key = (i, len(r["prompt"]), tuple(r["tokens"]))
        if key not in refs:
            refs[key] = np.asarray(reference.served_logits(
                params, sizes, r["prompt"], r["tokens"], pad_multiple=pad))
        gaps.append(reference.served_gaps(
            params, sizes, r["prompt"], r["tokens"], pad_multiple=pad,
            mode=mode, faults=faults, ref=refs[key]))
        took.append(round(time.monotonic() - t_req, 2))
    gaps = np.concatenate(gaps)
    return {"requests": len(picked), "tokens": int(gaps.size),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "not_best": int((gaps > 0).sum()), "seconds": took}


def run(ctx) -> dict:
    t, sizes, log = ctx["traffic"], ctx["sizes"], ctx["log"]
    args, devices, seconds = ctx["args"], ctx["devices"], ctx["seconds"]
    # the config first: a program without this model fails here, in
    # seconds, before any weight is made
    cfg = model_config(sizes)
    from gym_tpu import programs
    from gym_tpu.serve.__main__ import create_server

    slots = int(t["num_slots"])
    block = sizes["max_position_embeddings"]
    for sub in ("serve", "trace"):
        shutil.rmtree(os.path.join(ctx["out_dir"], sub), ignore_errors=True)

    requests = steadied(paired(data.closed_requests(
        t, sizes["vocab_size"], args.seed, int(t["request_count"])), t), t)
    first_cut = dealt_cuts(t, slots)
    params = weights_xing4.make_params(sizes, args.seed, device=devices[0])
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
        # client k's FIRST request is the list's k-th with the k-th cut,
        # whichever thread asks first: dealt by arrival, the pairing of
        # sizes and cuts was a race between the threads of a connect
        # batch, and a window then held 36, 38 or 39 admissions on the
        # same list (576-626 tokens/s on eight seeds, PERF.md section 6)
        lock = threading.Lock()
        cursor = [slots]
        first_done = set()

        def feed(k: int):
            with lock:
                if k not in first_done:
                    first_done.add(k)
                    req = requests[k]
                    return k, closed.request_body(req, max(1, int(round(
                        req["max_new_tokens"] * first_cut[k]))))
                i = cursor[0]
                if i >= len(requests):
                    return None
                cursor[0] += 1
                return i, closed.request_body(requests[i])

        clients = [closed.Client(k, port, feed, stop) for k in range(slots)]
        for lo in range(0, slots, int(t["connect_batch"])):
            for c in clients[lo:lo + int(t["connect_batch"])]:
                c.start()
            time.sleep(float(t["connect_pause_s"]))
        deadline = time.monotonic() + float(t["fill_deadline_s"])
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
        admit_records: dict = {}
        while time.monotonic() < t_close:
            samples.append(closed.get_stats(port))
            note_admits(admit_records)
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
        admits_traced = (admits_held(admit_records, *trace_span)
                         if trace_span else {})
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
    prompts = sorted(len(r["prompt"]) for r in requests)
    log({"window": {
        "seconds": seconds, "tokens": got["tokens"],
        "tokens_arrived": got["arrived"], "gc_pauses": gc_pauses,
        "requests_sent": sent_in, "requests_finished": len(finished),
        "rounds": delta["decode_steps"], "prefills": delta["prefills"],
        "prefill_buckets": stats1.get("prefill_buckets"),
        "admits": admits, "stats_samples": len(samples),
        "judged": verdict, "reference_s": time.monotonic() - t_ref0,
        "judged_prompts": [len(r["prompt"]) for r in picked],
        "longest_quarter_from": prompts[(3 * len(prompts)) // 4],
        "threads_left": left, "kv_pages": stats1.get("kv_pages"),
        "kv_blocks_peak": max((s.get("kv_blocks_in_use", 0)
                               for s in samples), default=None),
        "admission_waits": {k: stats1.get(k) for k in
                            ("preemptions", "kv_admission_blocked")
                            if k in stats1},
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
        "admit_spans": admits, "admit_spans_traced": admits_traced,
    }
    return {"correct": all(r["ok"] for r in rows),
            "attempted": sent_in, "failed": failed, "compared": rows,
            "end_to_end": {"serve_tokens_per_s": got["tokens"] / seconds,
                           "setup_s": t_open - ctx["t0"]},
            "facts": facts}
