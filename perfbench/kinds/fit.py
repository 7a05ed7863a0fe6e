"""Traffic kind ``fit``: tokens through ``Trainer.fit``.

``fit`` has no stop by time and hands out no state before its end, so a run
is two fits of the same arguments and seed:

1. the **check fit**, ``check_steps`` steps (3). It compiles or loads the
   step program, gives a first rate, and its end state is what the
   reference is compared with: every step's loss, and per leaf the norm of
   the parameters' change and of Adam's first moment after those steps.
2. the **timed fit**: ``1 + N`` steps, ``N`` the multiple of
   ``steps_multiple`` nearest to ``--seconds`` at the check fit's rate.
   Its rate after the first dispatch is the end-to-end metric; its first
   ``check_steps`` losses must equal the check fit's, which ties the
   object that was checked to the one that was timed.

The reference runs last, when the program's state is freed, on the rows the
program was fed (the datasets record them); its time is not set-up.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import shutil
import time

import numpy as np

from perfbench import data, harness, reference, weights, xplane


def _import(dotted: str):
    mod, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def make_strategy(spec: dict):
    """The strategy a traffic file names: ``class`` by dotted path, its
    ``kwargs``, and the inner optimizer."""
    from gym_tpu.strategy.optim import OptimSpec
    optim = dict(spec["optim"])
    return _import(spec["class"])(OptimSpec(optim.pop("name"), **optim),
                                  **spec.get("kwargs", {}))


def gpt_config(sizes: dict, traffic: dict):
    from gym_tpu.models.nanogpt import GPTConfig
    return GPTConfig(
        block_size=sizes["n_positions"], vocab_size=sizes["vocab_size"],
        n_layer=sizes["n_layer"], n_head=sizes["n_head"],
        n_embd=sizes["n_embd"], dropout=weights.dropout_rate(sizes),
        attn_impl=traffic["attn_impl"], remat=bool(traffic["remat"]))


def _adam_state(strategy_state, like):
    """The Adam moments inside the program's strategy state: the first
    node with ``mu`` and ``nu`` shaped like the parameters."""
    import jax
    want = jax.tree.structure(like)
    for node in jax.tree.leaves(
            strategy_state,
            is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu")):
        if hasattr(node, "mu") and jax.tree.structure(node.mu) == want:
            return node
    raise RuntimeError("no Adam state shaped like the parameters in the "
                       "strategy's state")


def program_norms(node_state, sizes: dict, seed: int) -> list:
    """Per node: ``{"dparam": {leaf: norm}, "mu": {leaf: norm}, "mu_proj":
    {leaf: projection}}`` of the program's state, reduced on the device. The initial weights are drawn
    again from the seed inside the same call."""
    import jax
    import jax.numpy as jnp

    adam = _adam_state(node_state.strategy_state, node_state.params)

    def norms(params, mu, key):
        p0 = weights.make_params_traced(sizes, key)

        def per_node(a):
            a = a.astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(a),
                                    axis=tuple(range(1, a.ndim))))
        return {"dparam": jax.tree.map(
                    lambda p, z: per_node(p - z[None]), params, p0),
                "mu": jax.tree.map(per_node, mu),
                "mu_proj": reference.leaf_projections(mu, lead=1)}

    got = jax.jit(norms)(node_state.params, adam.mu, weights.seed_key(seed))
    got = {name: {k: np.asarray(v) for k, v in reference.by_path(tree).items()}
           for name, tree in got.items()}
    k_nodes = len(next(iter(got["mu"].values())))
    return [{name: {k: float(v[i]) for k, v in leaves.items()}
             for name, leaves in got.items()} for i in range(k_nodes)]


def worst_leaf_gap(prog: dict, ref: dict):
    """The gap between the program's norm and the reference's, by the
    worst leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for leaf, r in ref.items():
        gap = abs(prog[leaf] - r) / max(r, median, 1e-30)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def projection_gaps(prog: dict, ref: dict):
    """Per leaf, the gap between the two sides' projections of Adam's
    first moment, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns the worst leaf's gap, that
    leaf, and the root mean square over leaves."""
    median = float(np.median(list(ref["mu"].values())))
    gaps = {leaf: abs(prog["mu_proj"][leaf] - r)
            / max(ref["mu"][leaf], median, 1e-30)
            for leaf, r in ref["mu_proj"].items()}
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.sqrt(np.mean(
        np.square(list(gaps.values())))))


def compare(prog_losses, prog_nodes, ref, timed_losses, limits):
    """Every number compared, beside its limit."""
    rows = []

    def row(name, value, limit, **extra):
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(value <= limit), **extra})

    for s, per_node in enumerate(ref["losses"]):
        # fit's history holds node 0's loss
        row(f"loss_gap_step{s}",
            abs(prog_losses[s] - per_node[0]) / abs(per_node[0]),
            limits["loss_gap"], program=prog_losses[s],
            reference=per_node[0])
    mu = [worst_leaf_gap(p["mu"], r["mu"])
          for p, r in zip(prog_nodes, ref["nodes"])]
    dp = [worst_leaf_gap(p["dparam"], r["dparam"])
          for p, r in zip(prog_nodes, ref["nodes"])]
    k = int(np.argmax([m[0] for m in mu]))
    row("grad_moment_norm_gap", mu[k][0], limits["grad_moment_norm_gap"],
        leaf=mu[k][1], node=k)
    pj = [projection_gaps(p, r) for p, r in zip(prog_nodes, ref["nodes"])]
    k = int(np.argmax([g[2] for g in pj]))
    row("grad_moment_proj_gap_rms", pj[k][2],
        limits["grad_moment_proj_gap_rms"], node=k,
        worst_leaf=pj[k][1], worst_leaf_gap=pj[k][0])
    k = int(np.argmax([d[0] for d in dp]))
    row("param_change_norm_gap", dp[k][0], limits["param_change_norm_gap"],
        leaf=dp[k][1], node=k)
    row("timed_fit_first_losses_gap",
        max(abs(a - b) / abs(b)
            for a, b in zip(timed_losses, prog_losses)),
        limits["timed_fit_first_losses_gap"])
    return rows


def reference_run(ctx, node_batches, mode="f32"):
    import jax.numpy as jnp
    t, sizes = ctx["traffic"], ctx["sizes"]
    params0 = weights.make_params(sizes, ctx["args"].seed,
                                  device=ctx["devices"][0])
    batches = [[(jnp.asarray(x), jnp.asarray(y)) for x, y in per_node]
               for per_node in node_batches]
    hyper = dict(t["reference"]["adamw"])
    return reference.follow_training(
        params0, batches, n_head=sizes["n_head"], hyper=hyper,
        reduce=t["reference"]["reduce"], mode=mode,
        rows_block=int(t["reference"]["rows_block"]))


def one_fit(ctx, name, max_steps, streams):
    from gym_tpu import Trainer
    from gym_tpu.models.nanogpt import GPT
    t, sizes = ctx["traffic"], ctx["sizes"]
    trainer = Trainer(GPT(gpt_config(sizes, t)),
                      lambda n, k, is_val: streams[n])
    return trainer.fit(
        strategy=make_strategy(t["strategy"]), num_nodes=t["num_nodes"],
        devices=([0] if t["placement"] == "fold" else None),
        max_steps=max_steps, batch_size=t["batch_size"],
        autocast=bool(t["autocast"]), val_size=0, val_interval=0,
        show_progress=False, seed=weights.seed32(ctx["args"].seed),
        run_name=name, log_dir=os.path.join(ctx["out_dir"], "logs"))


def make_streams(ctx):
    t, sizes = ctx["traffic"], ctx["sizes"]
    return [data.TokenStream(ctx["args"].seed, n, sizes["vocab_size"],
                             sizes["n_positions"], t["stream_tokens"])
            for n in range(t["num_nodes"])]


def pace(take_times, multiple: int) -> dict:
    """How evenly the timed fit drew its batches: the prefetch thread
    draws one a step and waits for the loop to take it, so a gap between
    two draws far over the median is a pause of the host or a stall of
    the device. A pause shorter than the one step the loop keeps in
    flight costs nothing; the seconds each block of ``multiple`` steps
    took show what was lost, and a rate that drifts. Logged beside the
    rate; judged by nothing."""
    t = np.asarray(take_times[8:], float)
    if len(t) < 3:
        return {}
    gaps = np.diff(t)
    med = float(np.median(gaps))
    late = np.flatnonzero(gaps > 1.5 * med)
    return {"gap_ms_p50": med * 1e3, "gap_ms_max": float(gaps.max()) * 1e3,
            "gaps_late": int(late.size),
            "late_excess_s": float((gaps[late] - med).sum()),
            # [draw, seconds after the first draw counted, gap in ms]
            "late": [[int(i) + 8, round(float(t[i] - t[0]), 3),
                      round(float(gaps[i]) * 1e3, 1)] for i in late[:12]],
            "block_s": [round(float(b), 4)
                        for b in np.diff(t[::multiple])]}


def run(ctx) -> dict:
    from gym_tpu import programs

    t, sizes, log = ctx["traffic"], ctx["sizes"], ctx["log"]
    args, devices = ctx["args"], ctx["devices"]
    tokens_per_step = t["num_nodes"] * t["batch_size"] * sizes["n_positions"]
    check_steps = int(t["check_steps"])
    for sub in ("logs", "trace"):
        shutil.rmtree(os.path.join(ctx["out_dir"], sub), ignore_errors=True)
    reg = programs.default_registry()

    # -- set-up: the check fit ------------------------------------------
    streams = make_streams(ctx)
    res = one_fit(ctx, "check", check_steps, streams)
    node_batches = [s.step_batches(check_steps) for s in streams]
    prog_losses = [loss for _, loss in res.history["train_loss"]]
    prog_nodes = program_norms(res.node_state, sizes, args.seed)
    rate = res.steps_per_second_steady
    if not rate:
        raise RuntimeError("the check fit gave no rate after its first "
                           "dispatch")
    del res
    gc.collect()
    multiple = int(t["steps_multiple"])
    # The nearest multiple, so that the same count comes out of every
    # run: the check fit's rate is read over two steps and wanders by a
    # few percent, and rounded down it fell on both sides of a whole
    # multiple (400 and 500 steps for one cell at 51 s). A fit's first
    # steps also run faster than the rest (96 ms against 118 ms on the
    # v5e), so the window comes out up to a fifth longer than --seconds.
    n_steps = max(int(t["min_multiples"]),
                  round(rate * ctx["seconds"] / multiple)) * multiple
    log({"check_fit": {"losses": prog_losses, "steps_per_s": rate,
                       "timed_steps": n_steps,
                       "memory_stats": devices[0].memory_stats()}})

    # -- the timed fit; its window opens when its first dispatch retires --
    before = reg.counters()
    streams = make_streams(ctx)
    tracer = None
    if args.trace:
        # a steady stretch in the middle of the window: fit's own
        # profile_dir records its first ten steps, which run faster than
        # the rest
        tracer = harness.MidRunTrace(
            os.path.join(ctx["out_dir"], "trace"),
            min(float(t["trace_seconds"]), ctx["seconds"] / 4),
            lambda: len(streams[0].take_times) > n_steps // 2)
        tracer.start()
    gc_clock = harness.GcClock()
    heart = harness.Heartbeat()
    heart.start()
    t_fit0 = time.monotonic()
    try:
        res = one_fit(ctx, "timed", 1 + n_steps, streams)
    finally:
        heart.stop.set()
        span = tracer.finish() if tracer else None
    t_fit1 = time.monotonic()
    after = reg.counters()
    sps = res.steps_per_second_steady
    window_s = n_steps / sps
    losses = [loss for _, loss in res.history["train_loss"]]
    peak = harness.memory_peak_bytes(devices)
    mem_stats = devices[0].memory_stats()
    del res
    gc.collect()
    # Nothing may compile inside the window. fit does not say when its
    # window opened, but the prefetch thread draws batch i+2 at the latest
    # when step i is dispatched: from the eighth draw to the last one the
    # window is open, and that span misses a handful of its steps.
    draws = streams[0].take_times
    opened = draws[min(8, len(draws) - 1)]
    in_window = ctx["compiles"].between(opened, draws[-1])
    gc_pauses = gc_clock.close(opened, draws[-1])
    heartbeat = heart.close(opened, draws[-1])
    setup_s = (t_fit1 - ctx["t0"]) - window_s

    # -- after the window: the reference, on what the program was fed ----
    t_ref0 = time.monotonic()
    ref = reference_run(ctx, node_batches)
    ref_s = time.monotonic() - t_ref0
    limits = ctx["limits"]
    rows = compare(prog_losses, prog_nodes, ref, losses[:check_steps],
                   limits)
    tail = float(np.mean(losses[-10:]))
    rows.append({"name": "final_loss_over_first", "value": tail / losses[0],
                 "limit": limits["final_loss_over_first"],
                 "ok": bool(math.isfinite(tail)
                            and tail / losses[0]
                            <= limits["final_loss_over_first"])})
    failed = sum(1 for x in losses if not math.isfinite(x))
    tokens_per_s = sps * tokens_per_step
    trace = xplane.summarize(tracer.trace_dir) if span else None
    log({"timed_fit": {
        "steps": n_steps, "steps_per_s": sps, "window_s": window_s,
        "fit_call_s": t_fit1 - t_fit0, "reference_s": ref_s,
        "first_loss": losses[0], "last10_loss": tail,
        "pace": pace(draws, multiple), "gc_pauses": gc_pauses,
        "heartbeat": heartbeat,
        "registry": {k: after[k] - before[k] for k in after
                     if isinstance(after[k], (int, float))},
        "memory_stats": mem_stats,
        "traced_step": trace.main_module_step() if trace else None}})
    facts = {
        "kind": "fit", "trace": trace, "sizes": sizes, "traffic": t,
        "chips": ctx["chips"], "device_kind": devices[0].device_kind,
        "tokens_per_s": tokens_per_s, "tokens_per_step": tokens_per_step,
        "rows_per_step_per_chip": (t["num_nodes"] * t["batch_size"]
                                   // ctx["chips"]),
        "memory_peak_bytes": peak, "memory_stats": mem_stats,
        "compile_s": after["compile_seconds"],
        "xla_compiles_in_window": in_window,
    }
    return {"correct": all(r["ok"] for r in rows) and failed == 0,
            "attempted": len(losses), "failed": failed, "compared": rows,
            "end_to_end": {"train_tokens_per_s": tokens_per_s,
                           "setup_s": setup_s},
            "facts": facts}
