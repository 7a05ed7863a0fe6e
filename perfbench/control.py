#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip, many
seeds in one process (one set-up for all):

    python3 perfbench/control.py --workload <name> --seeds 1,2,3
                                 [--no-program] [--judged-dir <dir>]

For every seed it prints one JSON line with the numbers a run compares,
twice: ``program`` (the program against the float32 reference) and
``control`` (the reference in the nearest precision below the one the
configuration states, in the program's place: fp8 operands where the cell
trains under bf16 autocast, bfloat16 throughout where it serves float32).
A limit belongs above the largest ``program`` value and below the smallest
``control`` value. The benchmark's own runs never call this.

A served cell needs no server here: its runs leave the requests they
judged on disk, and ``closed_seed`` judges those again.

``--no-program`` reads only the control against the reference (both run on
one chip, whatever the cell asks for); the rows fed are then drawn straight
from the cell's token streams.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fit_seed(ctx, with_program: bool) -> dict:
    import numpy as np
    from perfbench.kinds import fit
    t, sizes = ctx["traffic"], ctx["sizes"]
    steps = int(t["check_steps"])
    limits = ctx["limits"]
    out = {}
    if with_program:
        streams = fit.make_streams(ctx)
        res = fit.one_fit(ctx, "control", steps, streams)
        batches = [s.step_batches(steps) for s in streams]
        losses = [loss for _, loss in res.history["train_loss"]]
        nodes = fit.program_norms(res.node_state, sizes, ctx["args"].seed)
        del res
        gc.collect()
    else:
        rng = np.random.default_rng([int(ctx["args"].seed), 0xfeed])
        streams = fit.make_streams(ctx)
        batches = [[s.take(rng.integers(0, len(s), t["batch_size"]))
                    for _ in range(steps)] for s in streams]
    ref = fit.reference_run(ctx, batches, "f32")
    if with_program:
        out["program"] = {r["name"]: r["value"] for r in fit.compare(
            losses, nodes, ref, losses, limits)}
    ctl = fit.reference_run(ctx, batches, t["reference"]["control_mode"])
    ctl_losses = [per_node[0] for per_node in ctl["losses"]]
    out["control"] = {r["name"]: r["value"] for r in fit.compare(
        ctl_losses, ctl["nodes"], ref, ctl_losses, limits)}
    return out


def closed_seed(ctx, judged_dir: str) -> dict:
    """The served cell's readings come from what its runs served: every
    run leaves ``judged-<seed>.json`` (the sampled greedy requests with
    their served tokens); here the reference judges them again, and the
    control beside it, with no server."""
    from perfbench.kinds import closed
    path = os.path.join(judged_dir, f"judged-{ctx['args'].seed}.json")
    with open(path) as f:
        picked = json.load(f)["picked"]
    program = closed.judge(ctx, picked)
    control = closed.judge(ctx, picked, ctx["traffic"]["control_mode"])
    # the number a run compares: the control reads 1.0 by construction
    program["vs_control"] = closed.gap_ratio(program["mean"],
                                             control["mean"])
    return {"program": program, "control": control}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--judged-dir", default=None,
                    help="served cells: where the runs left their "
                         "judged-<seed>.json files")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    from gym_tpu import programs
    from perfbench import harness
    spec = harness.load_cell(a.workload)
    devices = jax.devices()
    chips = 1 if a.no_program else int(spec["cell"]["chips"])
    if not a.rehearse and devices[0].platform != "tpu":
        print("no accelerator", file=sys.stderr)
        return 2
    programs.enable_disk_tier(min_compile_time_secs=0.0)
    compiles = harness.CompileClock()
    traffic = {**spec["traffic"],
               **(spec["traffic"].get("rehearse", {}) if a.rehearse else {})}
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(seed=seed, trace=0, workload=a.workload)
        ctx = {"t0": time.monotonic(), "args": args, "spec": spec,
               "seconds": float(spec["bench"]["run_seconds"]),
               "devices": devices[:chips], "chips": chips,
               "log": lambda obj: None, "compiles": compiles,
               "out_dir": os.path.join(harness.OUT_ROOT, "control"),
               "rehearse": a.rehearse, "traffic": traffic,
               "limits": spec["limits"]["rehearse" if a.rehearse
                                        else "limits"],
               "sizes": ({**spec["config"], **spec["config"]["rehearse"]}
                         if a.rehearse else spec["config"])}
        os.makedirs(ctx["out_dir"], exist_ok=True)
        t_seed = time.monotonic()
        if traffic["kind"] == "fit":
            row = fit_seed(ctx, not a.no_program)
        else:
            row = closed_seed(ctx, a.judged_dir or os.path.join(
                harness.OUT_ROOT, a.workload))
        print(json.dumps({"seed": seed, "workload": a.workload,
                          "device": devices[0].device_kind,
                          "seconds": round(time.monotonic() - t_seed, 1),
                          **row}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
