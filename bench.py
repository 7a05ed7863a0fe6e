"""Benchmark: nanoGPT DiLoCo at 64 simulated nodes (the BASELINE.json
north-star config — ``example/nanogpt.py`` with ``--strategy diloco``,
64 nodes) on the current accelerator.

Prints ONE JSON line:
    {"metric": ..., "value": it/s, "unit": "it/s", "vs_baseline": ...}

``vs_baseline`` is measured it/s divided by the CPU it/s of the *same*
workload (the north star is ">=10x CPU iterations/sec"). The CPU number is
re-measurable with ``python bench.py --cpu`` and overridable via
``GYM_TPU_BENCH_BASELINE``.

A device mode that finds no accelerator fails: it exits non-zero and
prints no result (a CPU run is asked for with ``--cpu`` and says so in
its JSON). A rider that fails fails the run. One process does the work —
a chip belongs to one process at a time.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _artifact_status(obj) -> tuple:
    """Classify one bench artifact as (result_dict_or_None, status).
    Accepts both the raw one-JSON-line result and the baseline runner's
    wrapper (``BENCH_rNN.json``: ``{"n", "cmd", "rc", "tail",
    "parsed"}``). Artifacts predating the explicit ``status`` field
    (r01–r03) are grandfathered: a parsed result carrying ``value`` and
    no ``error`` was a measurement; anything else is ``not_measured``."""
    if isinstance(obj, dict) and "parsed" in obj:
        obj = obj["parsed"]
    if (isinstance(obj, dict) and len(obj) == 1
            and isinstance(next(iter(obj.values())), dict)
            and "metric" in next(iter(obj.values()))):
        # an --X-only arm wrapper ({"coldstart": {...}}, {"serving":
        # {...}}): the inner object is the artifact
        obj = next(iter(obj.values()))
    if not isinstance(obj, dict):
        return None, "not_measured"
    if obj.get("status"):
        return obj, obj["status"]
    if obj.get("error"):
        return obj, "not_measured"
    if "value" in obj:
        return obj, "measured"
    return obj, "not_measured"


def compare_runs(path_a: str, path_b: str) -> dict:
    """``bench.py --compare A.json B.json`` — the ONLY sanctioned way to
    turn two bench artifacts into a speedup. Refuses (one-line
    ``not_comparable`` note, exit 0) when EITHER arm's status is not
    ``measured``: dividing a not-measured marker by a measurement is
    how a missing chip gets reported as a 100% regression."""
    arms = {}
    for name, path in (("a", path_a), ("b", path_b)):
        try:
            with open(path) as f:
                raw = json.load(f)
            obj, status = _artifact_status(raw)
        except (OSError, json.JSONDecodeError) as e:
            obj, status = None, "not_measured"
            arms[name] = {"path": path, "status": status,
                          "error": f"{type(e).__name__}: {e}"}
            continue
        arms[name] = {"path": path, "status": status,
                      "value": (obj or {}).get("value"),
                      "metric": (obj or {}).get("metric"),
                      "error": (obj or {}).get("error")}
    a, b = arms["a"], arms["b"]
    out = {"mode": "compare", "a": a, "b": b}

    def numeric(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    bad = [n for n in ("a", "b") if arms[n]["status"] != "measured"
           or not numeric(arms[n].get("value"))
           or arms[n]["value"] == 0]
    if bad:
        out["comparable"] = False
        out["note"] = "not_comparable"
        out["reason"] = "; ".join(
            f"arm {n} ({arms[n]['path']}): status="
            f"{arms[n]['status']}"
            + ("" if numeric(arms[n].get("value"))
               else f", value={arms[n].get('value')!r}")
            + (f", error={arms[n]['error']}" if arms[n].get("error")
               else "")
            for n in bad)
        return out
    if (a.get("metric") and b.get("metric")
            and a["metric"] != b["metric"]):
        # dividing steps/s by, say, sim-seconds is a confidently wrong
        # number (and inverted for lower-is-better metrics)
        out["comparable"] = False
        out["note"] = "not_comparable"
        out["reason"] = (f"metric mismatch: a={a['metric']!r} "
                         f"b={b['metric']!r}")
        return out
    out["comparable"] = True
    out["speedup"] = round(b["value"] / a["value"], 3)
    return out


CPU_BASELINE_IT_S = 0.008  # measured on this host: `python bench.py --cpu`
# (64-node nanoGPT DiLoCo on 8 virtual CPU devices: ~125 s/step)
CPU_BASELINE_MEASURED_AT = "2026-07-29"  # provenance of the constant above
# (VERDICT r2 weak #8: vs_baseline must not silently trust an undated
# constant — the date is stamped into the JSON; re-measure with --cpu
# and override via GYM_TPU_BENCH_BASELINE, which stamps "env-override")

NUM_NODES = 64
BLOCK_SIZE = 256
VOCAB = 65          # shakespeare char vocab (reference build_dataset.py:8-21)
BATCH_PER_NODE = 16
WARMUP = int(os.environ.get("GYM_TPU_BENCH_WARMUP", 3))
TIMED = int(os.environ.get("GYM_TPU_BENCH_STEPS", 20))


def _interleaved_ab(run, steps: int, windows: int):
    """Median-of-windows A/B with arm order ALTERNATED window to window:
    shared-machine throughput drifts by more than the effect size, so a
    fixed A-then-B order would systematically bias whichever arm runs
    later in each pair, and a max-statistic just samples the drift.
    ``run(arm: bool, steps)`` returns a FitResult; the steady-state rate
    is compared (falls back to the full-run rate for 1-dispatch runs).
    Returns ``(off_median_its, on_median_its, losses_bit_identical)``.
    Shared by the host-overlap and resilience ablations so the two
    measurement protocols cannot drift apart."""
    offs, ons = [], []
    losses_off = losses_on = None
    for w in range(windows):
        order = (False, True) if w % 2 == 0 else (True, False)
        for arm in order:
            res = run(arm, steps)
            its = res.steps_per_second_steady or res.steps_per_second
            (ons if arm else offs).append(its)
            losses = [l for _, l in res.history["train_loss"]]
            if arm:
                losses_on = losses
            else:
                losses_off = losses
    return (sorted(offs)[len(offs) // 2], sorted(ons)[len(ons) // 2],
            losses_off == losses_on)


def measure_host_overlap() -> dict:
    """A/B the Trainer's host-overlap pipeline: the SAME seeded fit run
    with ``prefetch=False`` (every batch assembled + device_put on the
    dispatch critical path) vs ``prefetch=True`` (background double-
    buffered prefetch, deferred metric drains). Reports steady-state
    steps/sec for both and verifies the two loss trajectories are
    bit-identical — the prefetcher's determinism contract.

    The workload exercises the WHOLE host pipeline the overlap layer
    covers: a small dense model fed by a map-style
    (torch-``__getitem__``-like) dataset — the reference framework's
    DataLoader regime — with periodic checkpoint saves. Overlap-off runs
    every piece of host work serially on the dispatch critical path
    (inline assembly, blocking device_get + Orbax write per save);
    overlap-on is the Trainer's default pipeline (background prefetch,
    deferred drains, checkpoint writer thread). Compile cost is kept out
    of the A/B twice over: a warmup fit primes JAX's persistent
    compilation cache, and the comparison uses
    ``steps_per_second_steady`` (clock starts after the first dispatch
    retires).
    """
    import shutil
    import tempfile

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax

    from gym_tpu import Trainer
    from gym_tpu.data.sampler import IndexedDataset
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(min_compile_time_secs=0)

    nodes = int(os.environ.get("GYM_TPU_BENCH_OVERLAP_NODES", 8))
    steps = int(os.environ.get("GYM_TPU_BENCH_OVERLAP_STEPS", 192))
    spc = int(os.environ.get("GYM_TPU_BENCH_OVERLAP_SPC", 8))
    ckpt_every = int(os.environ.get("GYM_TPU_BENCH_OVERLAP_CKPT", 24))
    hid = 256  # wide enough that each save moves real bytes (~25 MB of
    # state per node set): the serial arm's device_get + write stall is
    # then signal, not noise, on a loaded shared machine

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, batch, train=True):
            x, y = batch
            x = x.reshape((x.shape[0], -1))
            h = nn.relu(nn.Dense(hid)(x))
            logits = nn.Dense(10)(h)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y).mean()

    rng = np.random.default_rng(0)
    n = 8192
    xs = rng.normal(0, 1, size=(n, 32, 32)).astype(np.float32)
    ys = rng.integers(0, 10, n).astype(np.int32)

    class PairDataset:  # map-style: per-item host work, like a DataLoader
        def __len__(self):
            return n

        def __getitem__(self, i):
            return xs[i], ys[i]

    ds = IndexedDataset(PairDataset())

    def run(overlap: bool, max_steps: int, ckpt: bool = True):
        save_dir = tempfile.mkdtemp(prefix="gym_tpu_overlap_ckpt_")
        try:
            res = Trainer(MLP(), ds).fit(
                strategy=DiLoCoStrategy(
                    optim_spec=OptimSpec("adamw", lr=1e-3), H=100),
                num_nodes=nodes, max_steps=max_steps, batch_size=64,
                minibatch_size=64, steps_per_call=spc, val_size=0,
                val_interval=0, show_progress=False, seed=7,
                prefetch=overlap, async_checkpoint=overlap,
                checkpoint_interval=ckpt_every if ckpt else None,
                save_dir=save_dir if ckpt else None,
                log_dir=os.environ.get("GYM_TPU_BENCH_LOGDIR",
                                       "/tmp/gym_tpu_bench_logs"))
            if res.preempted:
                # Ctrl-C now returns a normal-looking partial FitResult;
                # a truncated sample must abort the A/B, not pollute it
                raise KeyboardInterrupt("fit preempted mid-benchmark")
            return res
        finally:
            # fresh dir per run: a leftover checkpoint would RESUME the
            # next fit instead of starting it from scratch
            shutil.rmtree(save_dir, ignore_errors=True)

    run(False, 2 * spc, ckpt=False)  # primes the persistent compile cache
    windows = max(1, int(os.environ.get("GYM_TPU_BENCH_OVERLAP_WINDOWS",
                                        5)))
    off_its, on_its, bit_identical = _interleaved_ab(run, steps, windows)
    return {
        "metric": "host_overlap_ablation_steps_per_sec",
        "workload": (f"mlp(1024-{hid}-10) map-style dataset, diloco {nodes}n "
                     f"bs64 spc{spc} x{steps} steps, ckpt every "
                     f"{ckpt_every}"),
        "timing": f"median_of_{windows}_interleaved",
        "overlap_off_it_s": round(off_its, 3),
        "overlap_on_it_s": round(on_its, 3),
        "speedup": round(on_its / off_its, 3) if off_its else None,
        "loss_bit_identical": bit_identical,
    }


def measure_resilience_overhead() -> dict:
    """A/B the ISSUE 2 resilience layer's steady-state cost: the SAME
    seeded fit with the watchdog armed (deadline contexts around every
    drain/prefetch-get/checkpoint region) vs off. The fault-injection
    registry (empty: one attribute read per site) and the retry wrappers
    (no-op on the success path) are active in BOTH arms — they are
    always-on in production too; the watchdog thread + context managers
    are the only toggleable cost. Expected: noise.
    """
    import shutil
    import tempfile

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax

    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(min_compile_time_secs=0)

    steps = int(os.environ.get("GYM_TPU_BENCH_RESIL_STEPS", 192))
    spc = int(os.environ.get("GYM_TPU_BENCH_RESIL_SPC", 8))

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, batch, train=True):
            x, y = batch
            x = x.reshape((x.shape[0], -1))
            h = nn.relu(nn.Dense(256)(x))
            logits = nn.Dense(10)(h)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y).mean()

    rng = np.random.default_rng(0)
    ds = ArrayDataset(
        rng.normal(0, 1, size=(8192, 32, 32)).astype(np.float32),
        rng.integers(0, 10, 8192).astype(np.int32))

    def run(watchdog: bool, max_steps: int):
        save_dir = tempfile.mkdtemp(prefix="gym_tpu_resil_ckpt_")
        try:
            res = Trainer(MLP(), ds).fit(
                strategy=DiLoCoStrategy(
                    optim_spec=OptimSpec("adamw", lr=1e-3), H=100),
                num_nodes=8, max_steps=max_steps, batch_size=64,
                minibatch_size=64, steps_per_call=spc, val_size=0,
                val_interval=0, show_progress=False, seed=7,
                checkpoint_interval=24, save_dir=save_dir,
                # 0.0, not None: None falls back to GYM_TPU_WATCHDOG_S,
                # which would arm the watchdog in the OFF arm too
                watchdog_timeout=300.0 if watchdog else 0.0,
                log_dir=os.environ.get("GYM_TPU_BENCH_LOGDIR",
                                       "/tmp/gym_tpu_bench_logs"))
            if res.preempted:
                raise KeyboardInterrupt("fit preempted mid-benchmark")
            return res
        finally:
            shutil.rmtree(save_dir, ignore_errors=True)

    run(False, 2 * spc)  # primes the persistent compile cache
    windows = max(1, int(os.environ.get("GYM_TPU_BENCH_RESIL_WINDOWS", 5)))
    off_its, on_its, bit_identical = _interleaved_ab(run, steps, windows)
    return {
        "metric": "resilience_overhead_steps_per_sec",
        "workload": (f"mlp(1024-256-10), diloco 8n bs64 spc{spc} "
                     f"x{steps} steps, ckpt every 24"),
        "timing": f"median_of_{windows}_interleaved",
        "watchdog_off_it_s": round(off_its, 3),
        "watchdog_on_it_s": round(on_its, 3),
        "overhead_pct": round(100.0 * (off_its - on_its) / off_its, 2)
        if off_its else None,
        "loss_bit_identical": bit_identical,
    }


def measure_sdc_guard() -> dict:
    """A/B the ISSUE 20 training guard's steady-state cost: the SAME
    seeded fit with ``fit(guard=Guard(...))`` (per-drained-step
    finiteness + worst-node EWMA spike checks, plus the on-device
    state-fingerprint probe at the checkpoint cadence) vs no guard.
    The guard is pure observation — the loss trajectories must stay
    bit-identical — and its host cost is a few float compares per
    drained step, so the budget is < 2% steps/sec. Both arms
    ``status=measured``; the checkpoint sidecar writes are active in
    BOTH arms (always-on, like the fault registry in the resilience
    ablation) — the guard observation layer is the only toggle."""
    import shutil
    import tempfile

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax

    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.utils.compile_cache import enable_compilation_cache
    from gym_tpu.utils.integrity import Guard

    enable_compilation_cache(min_compile_time_secs=0)

    steps = int(os.environ.get("GYM_TPU_BENCH_SDC_STEPS", 192))
    spc = int(os.environ.get("GYM_TPU_BENCH_SDC_SPC", 8))

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, batch, train=True):
            x, y = batch
            x = x.reshape((x.shape[0], -1))
            h = nn.relu(nn.Dense(256)(x))
            logits = nn.Dense(10)(h)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y).mean()

    rng = np.random.default_rng(0)
    ds = ArrayDataset(
        rng.normal(0, 1, size=(8192, 32, 32)).astype(np.float32),
        rng.integers(0, 10, 8192).astype(np.int32))

    def run(guard_on: bool, max_steps: int):
        save_dir = tempfile.mkdtemp(prefix="gym_tpu_sdc_ckpt_")
        try:
            res = Trainer(MLP(), ds).fit(
                strategy=DiLoCoStrategy(
                    optim_spec=OptimSpec("adamw", lr=1e-3), H=100),
                num_nodes=8, max_steps=max_steps, batch_size=64,
                minibatch_size=64, steps_per_call=spc, val_size=0,
                val_interval=0, show_progress=False, seed=7,
                checkpoint_interval=24, save_dir=save_dir,
                # fingerprint probe at the checkpoint cadence: the full
                # defense a production run would arm
                guard=Guard(fingerprint_interval=24) if guard_on
                else None,
                watchdog_timeout=0.0,
                log_dir=os.environ.get("GYM_TPU_BENCH_LOGDIR",
                                       "/tmp/gym_tpu_bench_logs"))
            if res.preempted:
                raise KeyboardInterrupt("fit preempted mid-benchmark")
            return res
        finally:
            shutil.rmtree(save_dir, ignore_errors=True)

    run(False, 2 * spc)  # primes the persistent compile cache
    windows = max(1, int(os.environ.get("GYM_TPU_BENCH_SDC_WINDOWS", 5)))
    off_its, on_its, bit_identical = _interleaved_ab(run, steps, windows)
    return {
        "metric": "sdc_guard_overhead_steps_per_sec",
        "status": "measured",
        "measured": True,
        "workload": (f"mlp(1024-256-10), diloco 8n bs64 spc{spc} "
                     f"x{steps} steps, ckpt every 24, fingerprint "
                     f"probe every 24"),
        "timing": f"median_of_{windows}_interleaved",
        "guard_off_it_s": round(off_its, 3),
        "guard_on_it_s": round(on_its, 3),
        "overhead_pct": round(100.0 * (off_its - on_its) / off_its, 2)
        if off_its else None,
        "loss_bit_identical": bit_identical,
    }


def _overlap_subprocess(timeout_s: int = 1800):
    """Run the host-overlap ablation in a fresh CPU subprocess (its
    ``main`` forces the test harness's 16-virtual-device layout before
    jax initializes). Only a CPU run calls this: a parent that holds
    the chip measures in-process. Raises when the child gives no
    result — a rider that fails fails the run."""
    cmd = [sys.executable, os.path.abspath(__file__), "--overlap-only",
           "--cpu"]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout_s)
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)["host_overlap"]
        except (json.JSONDecodeError, KeyError):
            continue
    raise RuntimeError("host-overlap ablation child printed no result: "
                       + (proc.stdout + proc.stderr)[-500:])


def measure_network_sim() -> dict:
    """The ISSUE 3 rider, grown by ISSUE 10 and ISSUE 12: the
    low-communication strategy family — now codec × outer loop — vs
    AllReduce in simulated wall-clock on the WAN, datacenter and
    federated presets, via a tiny real sweep (measured compute, modeled
    comm) through ``gym_tpu.sim.sweep``. Per preset, each cell's
    simulated speedup over AllReduce plus whether every cell's declared
    trace reconciled with its logged ``cum_comm_bytes``; the federated
    preset carries the ISSUE 12 headline key
    ``compressed_gossip_speedup`` (best NoLoCo × non-dense-codec
    cell)."""
    import contextlib
    import tempfile

    from gym_tpu.sim.sweep import SweepConfig, run_sweep

    out = (os.environ.get("GYM_TPU_BENCH_SIM_DIR")
           or tempfile.mkdtemp(prefix="gym_tpu_sim_bench_"))
    cfg = SweepConfig(
        strategies=["diloco", "noloco", "demo_outer", "dynamiq_int8",
                    "simple_reduce"],
        presets=["wan", "datacenter", "federated"],
        codecs=["dense", "int8", "int4"],
        nodes=[int(os.environ.get("GYM_TPU_BENCH_SIM_NODES", 4))],
        H=[int(os.environ.get("GYM_TPU_BENCH_SIM_H", 10))],
        steps=int(os.environ.get("GYM_TPU_BENCH_SIM_STEPS", 30)),
        out=out,
    )
    with contextlib.redirect_stdout(sys.stderr):  # keep stdout one JSON line
        rows = run_sweep(cfg)

    def cell(strategy, preset, codec=None):
        return next(r for r in rows if r["strategy"] == strategy
                    and r["topology"] == preset
                    and r.get("codec") == codec)

    result = {"metric": "network_sim_low_comm_vs_allreduce",
              "status": "measured",
              "measured": True,
              "workload": (f"2-layer GPT, {cfg.nodes[0]} nodes, "
                           f"{cfg.steps} steps, H={cfg.H[0]}, "
                           f"codecs {'+'.join(cfg.codecs)}"),
              "out_dir": out}
    for preset in cfg.presets:
        a = cell("simple_reduce", preset)
        entry = {"allreduce_sim_s": round(a["sim_total_s"], 3),
                 "traces_reconcile": bool(a["reconciled"])}
        # every (strategy, codec) cell the grid runs is reported — a
        # trained-but-unreported cell would be wasted fit time
        for name, key, codec in (
                ("diloco", "diloco", None),
                ("diloco", "diloco_int8", "int8"),
                ("diloco", "diloco_int4", "int4"),
                ("noloco", "noloco", None),
                ("noloco", "noloco_int8", "int8"),
                ("noloco", "noloco_int4", "int4"),
                ("demo_outer", "demo_outer", None),
                ("demo_outer", "demo_outer_int8", "int8"),
                ("demo_outer", "demo_outer_int4", "int4"),
                ("dynamiq", "dynamiq_int8", "int8")):
            r = cell(name, preset, codec)
            entry[f"{key}_sim_s"] = round(r["sim_total_s"], 3)
            entry[f"{key}_speedup"] = (
                round(a["sim_total_s"] / r["sim_total_s"], 2)
                if r["sim_total_s"] else None)
            entry[f"{key}_final_loss"] = round(r["final_train_loss"], 4)
            entry["traces_reconcile"] &= bool(r["reconciled"])
        # back-compat key: r03-era artifacts called this "speedup"
        entry["speedup"] = entry["diloco_speedup"]
        result[preset] = entry
    # the ISSUE 12 headline: best compressed-gossip cell on the
    # federated preset, end to end vs AllReduce
    fed = result.get("federated", {})
    result["compressed_gossip_speedup"] = max(
        (fed[k] for k in ("noloco_int8_speedup", "noloco_int4_speedup")
         if fed.get(k)), default=None)
    return result


def measure_serving() -> dict:
    """The ISSUE 4 headline: aggregate tokens/s of the continuous-batching
    engine (``gym_tpu.serve``) vs sequentially looping ``generate_fast``
    over the SAME mixed prompt/output-length request set.

    The workload is genuinely mixed — every request draws a DISTINCT
    ``(prompt_len, max_new_tokens)`` signature, which is what live
    traffic looks like. That regime is exactly what the engine exists
    for: ``generate_fast`` compiles one program per signature (N
    requests → N multi-second XLA compiles; its lru cache never
    saturates under live traffic), while the engine's compile set is
    BOUNDED — one decode program plus at most ``⌈log2(block_size)⌉ + 1``
    prefill buckets — so the headline times each arm END TO END from a
    cold program cache, compiles included, the way a serving process
    actually experiences the workload. (The JAX persistent compile cache
    is disabled for this measurement; see main().)

    A second, warm pass of each arm is reported alongside
    (``*_warm_tok_s``): it isolates steady-state decode mechanics with
    every program already compiled. On this 2-core CPU the warm arms are
    within ~1.25x of each other — a b=8 decode step costs ~5x a b=1 step
    here (per-row attention over the static cache dominates; there is no
    under-utilized MXU to fill), so batching pays modestly; on an
    accelerator the batch dimension is where the win scales."""
    import math

    import numpy as np

    from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from gym_tpu.serve.scheduler import Scheduler

    num_slots = int(os.environ.get("GYM_TPU_BENCH_SERVE_SLOTS", 8))
    n_req = int(os.environ.get("GYM_TPU_BENCH_SERVE_REQUESTS", 12))
    chunk = int(os.environ.get("GYM_TPU_BENCH_SERVE_CHUNK", 8))
    cfg = GPTConfig(block_size=256, vocab_size=65, n_layer=4, n_head=4,
                    n_embd=128, dropout=0.0, bias=True)
    model = GPT(cfg)
    import jax
    import jax.numpy as jnp
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]

    # distinct (prompt_len, max_new) per request — live-traffic shape mix
    rng = np.random.default_rng(0)
    sigs = set()
    while len(sigs) < n_req:
        sigs.add((int(rng.integers(4, 48)), int(rng.integers(8, 40))))
    workload = [
        (rng.integers(0, cfg.vocab_size, plen), SamplingParams(
            max_new_tokens=mnew, temperature=0.9, top_k=16, seed=i))
        for i, (plen, mnew) in enumerate(sorted(sigs))
    ]
    total_new = sum(sp.max_new_tokens for _, sp in workload)

    def run_sequential():
        for prompt, sp in workload:
            out = generate_fast(params, cfg, prompt[None],
                                sp.max_new_tokens,
                                temperature=sp.temperature,
                                top_k=sp.top_k, seed=sp.seed)
            assert out.shape[1] == len(prompt) + sp.max_new_tokens

    engine = InferenceEngine(params, cfg, num_slots=num_slots,
                             decode_chunk=chunk)

    def run_engine():
        sched = Scheduler(engine, max_queue=len(workload))
        handles = [sched.submit(p, sp) for p, sp in workload]
        while any(h.status.value in ("queued", "running")
                  for h in handles):
            sched.step()
        for h in handles:
            assert len(h.result()) == h.sampling.max_new_tokens

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    # cold pass per arm (the headline: serve the workload end to end,
    # compiles included), then a warm pass (steady-state mechanics)
    seq_cold = timed(run_sequential)
    eng_cold = timed(run_engine)
    seq_warm = timed(run_sequential)
    eng_warm = timed(run_engine)

    # ---- shared-prefix workload (ISSUE 7): prefix-shared KV on the
    # realistic chatbot/agent shape — N requests dominated by one long
    # common system prompt. The engine prefills the shared blocks ONCE
    # and admits the rest through the prefix cache. The structural
    # assert is that prefill WORK (padded tokens dispatched) is less
    # than every prompt prefilled whole (what the unpaged PR-4 engine,
    # gone since ISSUE 29, dispatched).
    n_shared = int(os.environ.get("GYM_TPU_BENCH_SERVE_SHARED_REQS", 12))
    sys_len, tail_len, shared_mnew = 224, 8, 8
    shared_sys = rng.integers(0, cfg.vocab_size, sys_len)
    shared_workload = [
        (np.concatenate([shared_sys,
                         rng.integers(0, cfg.vocab_size, tail_len)]),
         SamplingParams(max_new_tokens=shared_mnew, temperature=0.9,
                        top_k=16, seed=500 + i))
        for i in range(n_shared)]
    shared_new = sum(sp.max_new_tokens for _, sp in shared_workload)

    def shared_arm(spec: int = 0, arm_cfg=None,
                   arm_params=None) -> dict:
        arm_cfg = cfg if arm_cfg is None else arm_cfg
        arm_params = params if arm_params is None else arm_params

        def mk():
            return InferenceEngine(arm_params, arm_cfg,
                                   num_slots=num_slots,
                                   decode_chunk=chunk,
                                   page_size=16, spec_tokens=spec)

        def serve(sched, wl):
            handles = [sched.submit(p, sp) for p, sp in wl]
            while any(h.status.value in ("queued", "running")
                      for h in handles):
                sched.step()
            for h in handles:
                assert len(h.result()) == h.sampling.max_new_tokens
            return handles

        # compile pass on a THROWAWAY engine: the measured burst must
        # meet a COLD prefix cache (first request pays the full
        # prefill) but warm programs — the global LRUs carry them over
        serve(Scheduler(mk(), max_queue=n_shared), shared_workload[:2])
        eng = mk()
        sched = Scheduler(eng, max_queue=n_shared)
        t0 = time.perf_counter()
        handles = serve(sched, shared_workload)
        wall = time.perf_counter() - t0
        ttfts = [h.ttft_s for h in handles]
        out = {
            "tok_s": round(shared_new / wall, 1),
            "p50_ttft_s": round(float(np.percentile(ttfts, 50)), 4),
            "p99_ttft_s": round(float(np.percentile(ttfts, 99)), 4),
            "prefills": eng.stats.prefills,
            "prefill_tokens": eng.stats.prefill_tokens,
            "prefix_hit_blocks": eng.stats.prefix_hit_blocks,
        }
        if spec:
            out["spec_accept_rate"] = eng.stats.spec_accept_rate()
        return out

    from gym_tpu.serve.engine import prompt_bucket
    whole_prompts = sum(prompt_bucket(len(p), cfg.block_size)
                        for p, _ in shared_workload)
    paged_arm = shared_arm()
    spec_arm = shared_arm(spec=4)
    # structural acceptance (ISSUE 7): the shared blocks are measurably
    # ELIDED from prefill dispatch work, not just faster by luck
    assert paged_arm["prefill_tokens"] < whole_prompts, (
        paged_arm, whole_prompts)
    assert paged_arm["prefix_hit_blocks"] > 0, paged_arm

    # ---- quantized serving (ISSUE 11): int8 weights + int8 paged KV.
    # The HEADLINE here is the deterministic capacity metric — resident
    # shared prefix blocks at a fixed KV payload byte budget — plus the
    # prefill-work elision it buys; tok/s is reported next to it but on
    # this 2-core CPU box it is noise-prone (±10%, see BENCH_r06) and
    # carries its own status field.
    import dataclasses as _dc

    from gym_tpu.serve.load import quantize_params

    qcfg = _dc.replace(cfg, weights_dtype="int8", kv_dtype="int8")
    qparams = quantize_params(params, qcfg)
    f32_param_bytes = sum(int(x.size * x.dtype.itemsize)
                          for x in jax.tree.leaves(params))
    q_param_bytes = sum(int(np.asarray(x).nbytes)
                        for x in jax.tree.leaves(qparams))

    def capacity_arm(arm_cfg, arm_params, kv_pages: int):
        """Sequential distinct one-block prompts through a small pool:
        every request content-registers its prompt block; the resident
        (refcount-0 cached) block count at the end IS the pool's
        prefix-holding capacity — deterministic, no timing anywhere."""
        eng = InferenceEngine(arm_params, arm_cfg, num_slots=2,
                              paged=True, page_size=16,
                              kv_pages=kv_pages)
        for i in range(80):
            slot, ev = eng.admit(
                rng.integers(0, cfg.vocab_size, 16),
                SamplingParams(max_new_tokens=2, seed=900 + i))
            while not ev.finished:
                evs = [e for e in eng.step() if e.slot == slot]
                ev = evs[-1]
        return eng

    # smallest legal f32 pool (null + one full window + CoW headroom);
    # the int8 arm gets exactly the same PAYLOAD byte budget — 4 pages
    # per f32 page — and must hold >= 4x the resident prefixes
    f32_kv_pages = 2 + cfg.block_size // 16           # 18 → 17 usable
    int8_kv_pages = 1 + (f32_kv_pages - 1) * 4        # 69: equal payload
    cap_f32 = capacity_arm(cfg, params, f32_kv_pages)
    cap_int8 = capacity_arm(qcfg, qparams, int8_kv_pages)
    # structural acceptance (ISSUE 11): the int8 pool's PAYLOAD fits the
    # f32 byte budget (scale sidecar reported, not hidden) and holds
    # >= 4x the resident prefix blocks
    assert (cap_int8.kv_pool_bytes()["payload"]
            <= cap_f32.kv_pool_bytes()["payload"]), (
        cap_int8.kv_pool_bytes(), cap_f32.kv_pool_bytes())
    assert (cap_int8.stats.kv_blocks_cached
            >= 4 * cap_f32.stats.kv_blocks_cached), (
        cap_int8.stats.kv_blocks_cached, cap_f32.stats.kv_blocks_cached)

    # token-stream divergence vs f32, per sampling config (int8 streams
    # are exact vs their own quantized reference — pinned in
    # tests/test_serve_paged.py — so what is measured here is the honest
    # f32-vs-int8 QUALITY delta, not a correctness bug)
    div_prompt = rng.integers(0, cfg.vocab_size, 24)
    div_new = 32
    divergence = {}
    for name, kw in (("greedy", dict(top_k=1)),
                     ("temp0.9_topk16", dict(temperature=0.9, top_k=16)),
                     ("topp0.9", dict(top_p=0.9))):
        ref = generate_fast(params, cfg, div_prompt[None], div_new,
                            seed=7, **kw)[0, 24:]
        got = generate_fast(qparams, qcfg, div_prompt[None], div_new,
                            seed=7, **kw)[0, 24:]
        diff = np.asarray(ref) != np.asarray(got)
        first = int(np.argmax(diff)) if diff.any() else None
        divergence[name] = {
            "tokens": div_new,
            "diverged_frac": round(float(diff.mean()), 4),
            "first_divergence_index": first,
        }

    # perplexity delta: mean CE of the SAME forward under f32 vs
    # quantized weights (eval mode; random-init model, so the absolute
    # level is meaningless — the DELTA is the codec's quality cost)
    ev = rng.integers(0, cfg.vocab_size, (4, 65))
    ev_batch = (jnp.asarray(ev[:, :-1]), jnp.asarray(ev[:, 1:]))
    loss_f32 = float(GPT(cfg).apply({"params": params}, ev_batch,
                                    train=False))
    loss_q = float(GPT(qcfg).apply({"params": qparams}, ev_batch,
                                   train=False))

    # tok/s: the shared-prefix workload on the quantized engine (weights
    # dequant fused into the matmuls + int8 KV), vs the f32 paged arm
    quant_arm = shared_arm(arm_cfg=qcfg, arm_params=qparams)

    capacity_ratio = round(cap_int8.stats.kv_blocks_cached
                           / max(cap_f32.stats.kv_blocks_cached, 1), 2)
    quantized = {
        # self-describing artifact: --compare'able on the DETERMINISTIC
        # capacity ratio (write {"parsed": {"quantized": ...}} wrappers
        # and two rounds compare cleanly; tok/s stays a side column)
        "metric": "quantized_serving_capacity_ratio_int8_vs_f32",
        "value": capacity_ratio,
        "status": "measured",
        "measured": True,
        "config": "weights int8 (per-tile codec, dequant fused) + "
                  "kv int8 (per-(page-slot, head) scales); embedding "
                  "f32",
        "weights_bytes_f32": f32_param_bytes,
        "weights_bytes_int8": q_param_bytes,
        "weights_bytes_ratio": round(f32_param_bytes
                                     / max(q_param_bytes, 1), 2),
        "capacity": {
            # the deterministic headline: resident shared prefixes at a
            # FIXED KV payload byte budget (18-page f32 pool vs 69-page
            # int8 pool — equal payload bytes; no timing anywhere)
            "workload": "80 distinct 1-block prompts, page 16, "
                        "sequential",
            "f32_kv_pages": f32_kv_pages,
            "int8_kv_pages": int8_kv_pages,
            "f32_pool_bytes": cap_f32.kv_pool_bytes(),
            "int8_pool_bytes": cap_int8.kv_pool_bytes(),
            "f32_resident_prefix_blocks":
                int(cap_f32.stats.kv_blocks_cached),
            "int8_resident_prefix_blocks":
                int(cap_int8.stats.kv_blocks_cached),
            "capacity_ratio": capacity_ratio,
            "prefill_tokens_f32_arm": int(cap_f32.stats.prefill_tokens),
            "prefill_tokens_int8_arm":
                int(cap_int8.stats.prefill_tokens),
        },
        "shared_prefix_quantized": quant_arm,
        "tok_s_vs_f32_paged": round(
            quant_arm["tok_s"] / max(paged_arm["tok_s"], 1e-9), 2),
        "tok_s_note": "2-core CPU box: tok/s drifts +-10% — the "
                      "capacity metric above is the headline; on an "
                      "accelerator the int8 weight traffic is where "
                      "dequant-fused matmuls win",
        "divergence_vs_f32": divergence,
        "quality": {
            "eval_loss_f32": round(loss_f32, 6),
            "eval_loss_int8": round(loss_q, 6),
            "loss_delta": round(loss_q - loss_f32, 6),
            "perplexity_f32": round(math.exp(loss_f32), 4),
            "perplexity_int8": round(math.exp(loss_q), 4),
            "perplexity_delta": round(math.exp(loss_q)
                                      - math.exp(loss_f32), 4),
        },
    }

    return {
        "metric": "serving_continuous_batching_vs_sequential_tokens_per_s",
        "status": "measured",
        "measured": True,
        "workload": (f"{n_req} requests, distinct (prompt_len in [4,48), "
                     f"max_new in [8,40)) signatures, gpt "
                     f"{cfg.n_layer}L/{cfg.n_embd}d block "
                     f"{cfg.block_size}, {num_slots} slots, "
                     f"chunk {chunk}"),
        "timing": "cold_process_compiles_included; warm = second pass",
        "sequential_tok_s": round(total_new / seq_cold, 1),
        "engine_tok_s": round(total_new / eng_cold, 1),
        "speedup": round(seq_cold / eng_cold, 2),
        "sequential_warm_tok_s": round(total_new / seq_warm, 1),
        "engine_warm_tok_s": round(total_new / eng_warm, 1),
        "warm_speedup": round(seq_warm / eng_warm, 2),
        "sequential_programs_compiled": len(workload),
        "engine_prefill_compiles": engine.stats.prefill_compiles,
        "prefill_bound": (cfg.block_size - 1).bit_length() + 1,
        "shared_prefix": {
            "workload": (f"{n_shared} requests = {sys_len}-token shared "
                         f"system prompt + {tail_len}-token distinct "
                         f"tail, max_new {shared_mnew}, page 16, "
                         f"{num_slots} slots, chunk {chunk}; programs "
                         f"warm, prefix cache cold"),
            "paged_engine": paged_arm,
            "paged_spec_engine": spec_arm,
            "prefill_tokens_elided": (whole_prompts
                                      - paged_arm["prefill_tokens"]),
        },
        "quantized": quantized,
    }


def _coldstart_worker() -> None:
    """Child process for ``measure_coldstart`` — one genuinely fresh
    process per regime (a cold start is a PROCESS property: registry,
    jit caches and the XLA client all start empty).

    argv: ``--coldstart-worker <cache_dir|-> <warmup 0|1>``.  Builds the
    serving engine, optionally enables the registry's persistent
    executable tier and/or runs background warmup TO COMPLETION, then
    serves one burst of bucket-spanning prompts submitted all at t=0 —
    the worst-case cold arrival — and prints per-request TTFTs plus the
    registry counters as one JSON line."""
    i = sys.argv.index("--coldstart-worker")
    cache_dir, warmup = sys.argv[i + 1], sys.argv[i + 2] == "1"

    import numpy as np

    import jax

    from gym_tpu import programs
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from gym_tpu.serve.scheduler import Scheduler

    if cache_dir != "-":
        programs.enable_disk_tier(cache_dir)

    cfg = GPTConfig(block_size=256, vocab_size=65, n_layer=4, n_head=4,
                    n_embd=128, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64),
                           train=False)["params"]
    eng = InferenceEngine(params, cfg, num_slots=4, decode_chunk=8)

    warm_s = 0.0
    if warmup:
        w = programs.warm_engine_programs(eng, start=True)
        assert w.wait(timeout=1800), "warmup did not finish"
        assert w.stats()["warmed"] == w.stats()["total"], w.stats()
        warm_s = w.seconds

    builds0 = programs.default_registry().counters()["builds"]
    # one prompt per power-of-two prefill bucket (4..256 at block 256):
    # a cold engine pays one compile per bucket ON the request path
    rng = np.random.default_rng(0)
    burst = [(rng.integers(0, cfg.vocab_size, n),
              SamplingParams(max_new_tokens=8, temperature=0.9,
                             top_k=16, seed=i))
             for i, n in enumerate((3, 6, 12, 24, 48, 96, 190))]
    sched = Scheduler(eng, max_queue=len(burst))
    t0 = time.perf_counter()
    handles = [sched.submit(p, sp) for p, sp in burst]
    while any(h.status.value in ("queued", "running") for h in handles):
        sched.step()
    wall = time.perf_counter() - t0
    for h in handles:
        assert len(h.result(timeout=30)) == h.sampling.max_new_tokens

    ttfts = sorted(h.ttft_s for h in handles)
    c = programs.default_registry().counters()
    print(json.dumps({
        "ttfts_s": [round(t, 4) for t in ttfts],
        "p50_ttft_s": round(ttfts[len(ttfts) // 2], 4),
        "p99_ttft_s": round(ttfts[-1], 4),     # 7 samples: p99 == max
        "burst_wall_s": round(wall, 3),
        "on_path_builds": c["builds"] - builds0,
        "counters": c,
        "xla_compiles": programs.xla_compile_counter(),
        "warmup_s": round(warm_s, 3),
    }))


def measure_coldstart() -> dict:
    """The ISSUE 9 headline: first-burst TTFT of a fresh serving process
    under the device-program registry's three cold-start regimes —

    - ``cold_disk``     — empty persistent tier, no warmup: every
      program XLA-compiles ON the request path (the pre-registry cold
      start, and this run seeds the disk tier for the next two);
    - ``warm_disk``     — process restart against the seeded tier, no
      warmup: builds deserialize instead of compiling, still on-path;
    - ``warmed``        — restart + background AOT warmup completed
      before traffic: zero on-path builds (the shipped server default).

    Each regime is a fresh subprocess (cold starts are process
    properties).  Structural pins ride along with the timings: the
    warm-disk restart reports ``xla_compiles == 0`` and the warmed
    server's burst triggers ``on_path_builds == 0``."""
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix="gym_tpu_coldstart_")
    cache = os.path.join(tmp, "progcache")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)                 # plain 1-device children
    # regime = argv, not env: the variable would beat the argument
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(cache_dir: str, warmup: bool) -> dict:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--coldstart-worker", cache_dir, "1" if warmup else "0"],
            env=env, capture_output=True, text=True, timeout=1800)
        assert p.returncode == 0, (p.stdout + p.stderr)[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    try:
        cold = run(cache, warmup=False)
        warm_disk = run(cache, warmup=False)
        warmed = run(cache, warmup=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # structural acceptance — the timings above must come from the
    # mechanism claimed, not from noise on a shared 2-core host
    assert cold["xla_compiles"] == cold["on_path_builds"] > 0, cold
    assert warm_disk["xla_compiles"] == 0, warm_disk
    assert warm_disk["on_path_builds"] > 0, warm_disk
    assert warmed["on_path_builds"] == 0, warmed

    return {
        "metric": "serving_coldstart_first_burst_ttft_s",
        "status": "measured",
        "measured": True,
        # the comparable headline (bench.py --compare): p99 TTFT of the
        # shipped default — restart, warm disk, warmup done. LOWER is
        # better; --compare reports b/a, so read speedup as a ratio of
        # TTFTs, not a rate
        "value": warmed["p99_ttft_s"],
        "unit": "s_p99_ttft_warmed_lower_is_better",
        "workload": ("7-request burst at t=0, one per prefill bucket "
                     "(prompt 3..190), max_new 8, gpt 4L/128d block "
                     "256, 4 slots, chunk 8; fresh process per regime"),
        "cold_disk": cold,
        "warm_disk": warm_disk,
        "warmed": warmed,
        "p99_ttft_speedup_warm_disk": round(
            cold["p99_ttft_s"] / warm_disk["p99_ttft_s"], 2),
        "p99_ttft_speedup_warmed": round(
            cold["p99_ttft_s"] / warmed["p99_ttft_s"], 2),
        "warmup_cost_s": warmed["warmup_s"],
    }


def measure_chaos() -> dict:
    """The ISSUE 5 rider: the serving stack under injected faults — the
    SAME mixed-request workload served (a) clean and (b) with a delay
    fault on every decode dispatch plus one injected HANG mid-run (the
    supervisor recovery drill) and a burst of infeasible-deadline
    submissions (the admission-control shed). Reports tail latencies
    (p50/p95/p99 TTFT + per-token) for both arms and the shed /
    quarantined / restart counters — the "serving under fire" headline.

    Host-side by construction (the faults are host faults); always
    CPU-forced like --sim-only. Both arms run warm (a warmup request
    precedes them) so the deltas are fault cost, not compile cost."""
    import tempfile

    import numpy as np

    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from gym_tpu.serve.metrics import ServeMetrics
    from gym_tpu.serve.scheduler import (AdmissionRejectedError,
                                         Scheduler)
    from gym_tpu.serve.supervisor import Supervisor
    from gym_tpu.utils.resilience import faults

    import jax

    num_slots = int(os.environ.get("GYM_TPU_BENCH_CHAOS_SLOTS", 4))
    n_req = int(os.environ.get("GYM_TPU_BENCH_CHAOS_REQUESTS", 16))
    cfg = GPTConfig(block_size=128, vocab_size=65, n_layer=2, n_head=2,
                    n_embd=64, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]

    rng = np.random.default_rng(0)
    sigs = set()
    while len(sigs) < n_req:
        sigs.add((int(rng.integers(4, 32)), int(rng.integers(8, 24))))
    workload = [
        (rng.integers(0, cfg.vocab_size, plen), SamplingParams(
            max_new_tokens=mnew, temperature=0.9, top_k=16, seed=i))
        for i, (plen, mnew) in enumerate(sorted(sigs))
    ]

    def engine_factory():
        return InferenceEngine(params, cfg, num_slots=num_slots,
                               decode_chunk=2)

    def run_arm(fault_spec: str) -> dict:
        faults.reset()
        if fault_spec:
            faults.configure(fault_spec)
        out = tempfile.mkdtemp(prefix="gym_tpu_chaos_")
        metrics = ServeMetrics(out, engine_log_every=10)
        sched = Scheduler(engine_factory(), max_queue=64, metrics=metrics)
        sup = Supervisor(sched, engine_factory, dispatch_timeout_s=1.0,
                         max_restarts=4, metrics=metrics,
                         log=lambda *a, **k: None)
        sup.start()
        handles = [sched.submit(p, sp, deadline_s=120.0)
                   for p, sp in workload]
        # wait out half the workload so the tokens/s EWMA is live, then
        # fire the admission-control shed: deliberately infeasible
        # deadlines must be rejected up front, not queued to die
        for h in handles[:n_req // 2]:
            try:
                h.result(timeout=300)
            except (RuntimeError, OSError):   # OSError covers
                pass                          # TimeoutError + IO faults
        rejected = 0
        for k in range(3):
            try:
                sched.submit(workload[0][0], SamplingParams(
                    max_new_tokens=48, seed=100 + k), deadline_s=1e-4)
            except AdmissionRejectedError:
                rejected += 1
        outcomes = {"ok": 0, "failed": 0}
        for h in handles:
            try:
                h.result(timeout=300)
                outcomes["ok"] += 1
            except (RuntimeError, OSError):
                outcomes["failed"] += 1
        # post-chaos probe: faults off, the engine must serve cleanly
        faults.reset()
        post_ok = False
        try:
            post = sched.submit(workload[0][0], SamplingParams(
                max_new_tokens=8, seed=999), deadline_s=60.0)
            post_ok = len(post.result(timeout=60)) == 8
        except (RuntimeError, OSError):
            post_ok = False
        sup.stop(join_timeout_s=30)
        sched.shutdown(finish_running=False)
        head = metrics.headline()
        metrics.close()
        return {
            "requests_ok": outcomes["ok"],
            "requests_failed_typed": outcomes["failed"],
            "shed_at_admission": rejected,
            "requests_shed": head["requests_shed"],
            "requests_quarantined": head["requests_quarantined"],
            "engine_restarts": sup.restarts,
            "post_chaos_request_ok": post_ok,
            "tokens_per_s": head["tokens_per_s"],
            "ttft_p50_s": head["ttft_p50_s"],
            "ttft_p95_s": head["ttft_p95_s"],
            "ttft_p99_s": head["ttft_p99_s"],
            "token_lat_p50_s": head["token_lat_p50_s"],
            "token_lat_p95_s": head["token_lat_p95_s"],
            "token_lat_p99_s": head["token_lat_p99_s"],
        }

    # warm the global program LRUs — one request PER PREFILL BUCKET the
    # workload can hit, so neither arm's tail latency absorbs a compile
    warm_sched = Scheduler(engine_factory(), max_queue=8)
    warm = [warm_sched.submit(np.ones(n, np.int32),
                              SamplingParams(max_new_tokens=4))
            for n in (4, 8, 16, 31)]
    while any(w.status.value in ("queued", "running") for w in warm):
        warm_sched.step()

    clean = run_arm("")
    # delay every decode dispatch 20 ms + one 4 s hang mid-run (the 1 s
    # watchdog reaps it; the abandoned thread wakes while the arm is
    # still running and is discarded by the scheduler epoch)
    faulted = run_arm("serve.decode:delay=0.02,serve.decode:hang=4@9")
    return {
        "metric": "serving_under_faults_tail_latency",
        "workload": (f"{n_req} requests, distinct (prompt_len in [4,32), "
                     f"max_new in [8,24)) signatures, gpt "
                     f"{cfg.n_layer}L/{cfg.n_embd}d block "
                     f"{cfg.block_size}, {num_slots} slots, chunk 2, "
                     f"watchdog 1s"),
        "fault_spec": "serve.decode:delay=0.02 + serve.decode:hang=4@9",
        "clean": clean,
        "faulted": faulted,
        "recovered": bool(faulted["engine_restarts"] >= 1
                          and faulted["post_chaos_request_ok"]),
    }


def measure_fleet() -> dict:
    """The ISSUE 8 rider: the 2-replica fleet under fire — (a) a
    replica KILLED mid-stream under concurrent traffic (hard engine
    death: every dispatch raises, restart budget 0) with every client
    request still answered via sibling failover, and (b) a rolling
    weight HOT-SWAP under sustained traffic with zero failed requests,
    zero XLA recompiles (pinned by the device-program registry's build
    counter) and post-swap generations provably from the new params.
    Host-side by construction; always CPU-forced like --chaos-only."""
    import concurrent.futures
    import tempfile
    import threading

    import numpy as np

    from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
    from gym_tpu.programs import compile_counter
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from gym_tpu.serve.metrics import ServeMetrics
    from gym_tpu.serve.router import build_fleet

    import jax

    n_req = int(os.environ.get("GYM_TPU_BENCH_FLEET_REQUESTS", 16))
    cfg = GPTConfig(block_size=128, vocab_size=65, n_layer=2, n_head=2,
                    n_embd=64, dropout=0.0, bias=True)
    model = GPT(cfg)
    params_a = model.init({"params": jax.random.PRNGKey(0)},
                          np.zeros((1, 8), np.int64), train=False)["params"]
    params_b = model.init({"params": jax.random.PRNGKey(7)},
                          np.zeros((1, 8), np.int64), train=False)["params"]

    rng = np.random.default_rng(0)
    workload = [
        (rng.integers(0, cfg.vocab_size, int(rng.integers(4, 24))),
         SamplingParams(max_new_tokens=int(rng.integers(12, 28)),
                        temperature=0.9, top_k=16, seed=i))
        for i in range(n_req)]

    def serve_all(router, wl, kill_after=None):
        """Drive the workload through handler-thread-style clients;
        optionally hard-kill the busiest replica once `kill_after`
        requests have completed. Returns (ok, failed, wall_s)."""
        done = {"n": 0}

        def client(arg):
            prompt, sp = arg
            try:
                fr = router.submit(prompt, sp, timeout=60.0,
                                   deadline_s=120.0)
                toks = fr.result(timeout=120.0)
                done["n"] += 1
                return len(toks) == sp.max_new_tokens
            except (RuntimeError, OSError):
                return False

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            futs = [ex.submit(client, w) for w in wl]
            if kill_after is not None:
                while done["n"] < kill_after:
                    time.sleep(0.01)
                victim = max(router.replicas,
                             key=lambda r: r.scheduler.backlog_tokens())

                def boom(*a, **k):
                    raise RuntimeError(
                        "bench: injected hard engine death")

                victim.scheduler.engine.step = boom
            results = [f.result() for f in futs]
        ok = sum(results)
        return ok, len(results) - ok, time.perf_counter() - t0

    def fresh_router(max_restarts):
        m = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_fleet_"),
                         engine_log_every=10)
        r = build_fleet(params_a, cfg, replicas=2, num_slots=4,
                        decode_chunk=2, max_restarts=max_restarts,
                        dispatch_timeout_s=5.0, metrics=m,
                        weights_tag="v1",
                        log=lambda *a, **k: None).start()
        return r, m

    # warm the programs once so neither arm absorbs a compile
    warm, wm = fresh_router(max_restarts=2)
    serve_all(warm, workload[:4])
    warm.close(drain_deadline_s=30)
    wm.close()

    # arm (a): replica kill mid-traffic, restart budget exhausted
    router, m = fresh_router(max_restarts=0)
    ok, failed, wall = serve_all(router, workload, kill_after=2)
    kill_status = router.status()
    assert kill_status["failovers"] >= 1, kill_status
    assert sum(r["dead"] for r in kill_status["replicas"]) == 1, \
        kill_status
    kill_arm = {
        "requests_ok": ok,
        "requests_failed": failed,
        "failovers": kill_status["failovers"],
        "dead_replicas": sum(r["dead"]
                             for r in kill_status["replicas"]),
        "tok_s": round(sum(sp.max_new_tokens
                           for _, sp in workload) / wall, 1),
    }
    router.close(drain_deadline_s=30)
    m.close()

    # arm (b): rolling hot-swap under sustained traffic
    router, m = fresh_router(max_restarts=2)
    probe = workload[0]
    ref_b = generate_fast(params_b, cfg, probe[0][None],
                          probe[1].max_new_tokens, temperature=0.9,
                          top_k=16, seed=probe[1].seed
                          )[0, len(probe[0]):].tolist()
    compiles_before = compile_counter()
    reload_result = {}

    def do_reload():
        time.sleep(0.15)      # let traffic occupy both replicas first
        reload_result.update(router.reload(params_b, weights_tag="v2",
                                           drain_timeout_s=60.0))

    swapper = threading.Thread(target=do_reload)
    swapper.start()
    ok, failed, wall = serve_all(router, workload * 2)
    swapper.join(timeout=120)
    compiles_after = compile_counter()
    fr = router.submit(probe[0], probe[1], timeout=60.0)
    post_tokens = fr.result(timeout=120.0)
    assert failed == 0, f"hot-swap dropped {failed} requests"
    assert sorted(reload_result.get("swapped", [])) == [0, 1], \
        reload_result
    assert compiles_after == compiles_before, (
        f"hot-swap recompiled: {compiles_after - compiles_before} "
        f"new program(s)")
    assert post_tokens == ref_b, "post-swap tokens not from new params"
    swap_arm = {
        "requests_ok": ok,
        "requests_failed": failed,
        "reload_wall_s": reload_result.get("wall_s"),
        "swapped_replicas": reload_result.get("swapped"),
        "recompiles_during_swap": compiles_after - compiles_before,
        "post_swap_params_verified": post_tokens == ref_b,
        "tok_s": round(sum(sp.max_new_tokens
                           for _, sp in workload * 2) / wall, 1),
    }
    router.close(drain_deadline_s=30)
    m.close()

    # arm (c): the OUT-OF-PROCESS A/B (ISSUE 13) — aggregate tok/s for
    # 2 in-process thread replicas vs 2 worker SUBPROCESSES, streamed
    # end to end, on the paged 2-slot config where per-token host work
    # (paged block bookkeeping, stream fan-out, scheduler loops) is a
    # first-order cost: that host work shares ONE GIL in the thread
    # fleet and parallelizes across processes in the subprocess fleet —
    # the honest 2-core parallelism win. Protocol per the perf-noise
    # convention: both arms fully warmed (the thread arm seeds the
    # persistent program tier, so workers spawn at programs_compiled=0),
    # 5 interleaved passes with alternating order, MEDIANS reported.
    # The streamed passes also yield the TTFB observable: p99 time to
    # FIRST BYTE (first chunk at the client) sits next to p99 TTFT
    # (first token in the engine) and must track it — NOT completion
    # time, which is what `/generate` cost before streaming.
    import statistics

    from gym_tpu import programs as programs_mod
    from gym_tpu.serve.router import build_process_fleet

    cache_dir = tempfile.mkdtemp(prefix="gym_tpu_fleet_cache_")
    programs_mod.enable_disk_tier(cache_dir)
    ab_rng = np.random.default_rng(1)
    ab_wl = [
        (ab_rng.integers(0, cfg.vocab_size,
                         int(ab_rng.integers(16, 48))),
         SamplingParams(max_new_tokens=int(ab_rng.integers(12, 28)),
                        temperature=0.9, top_k=16, seed=100 + i))
        for i in range(48)]
    ab_tokens = sum(sp.max_new_tokens for _, sp in ab_wl)
    ab_kw = dict(replicas=2, num_slots=2, decode_chunk=1, max_queue=64,
                 page_size=16, kv_pages=64, dispatch_timeout_s=60.0)

    def run_streamed(router, wl, collect=None):
        """Drive the workload through streaming clients; optionally
        collect (ttfb, ttft, completion) triples. Returns wall_s."""

        def client(arg):
            prompt, sp = arg
            fr = router.submit(prompt, sp, timeout=120.0)
            got = 0
            for chunk in fr.stream(timeout=180.0):
                got += len(chunk)
            if collect is not None and fr.ttft_s is not None:
                done = getattr(fr, "done_frame", None) or {}
                ttft = done.get("ttft_s") or fr.ttft_s
                collect.append((fr.ttft_s, ttft,
                                fr.done_t - fr.submit_t))
            return got == sp.max_new_tokens

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(6) as ex:
            oks = list(ex.map(client, wl))
        assert all(oks), "process-fleet A/B dropped a stream"
        return time.perf_counter() - t0

    tm = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_abt_"),
                      engine_log_every=10)
    thread_router = build_fleet(
        params_a, cfg, metrics=tm,
        log=lambda *a, **k: None, **ab_kw).start()
    run_streamed(thread_router, ab_wl)     # warm + seed the disk tier
    run_streamed(thread_router, ab_wl)
    pm = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_abp_"),
                      engine_log_every=10)
    proc_router = build_process_fleet(
        params_a, cfg, tempfile.mkdtemp(prefix="gym_tpu_abf_"),
        metrics=pm, program_cache_dir=cache_dir, no_warmup=True,
        log=lambda *a, **k: None, **ab_kw)
    proc_router.start()
    proc_router.wait_ready(timeout_s=240)
    run_streamed(proc_router, ab_wl)       # warm the wire path
    run_streamed(proc_router, ab_wl)
    lat = []       # (ttfb, ttft, completion) from proc streamed passes
    t_rates, p_rates = [], []
    for i in range(5):
        arms = ([("p", proc_router), ("t", thread_router)]
                if i % 2 == 0 else
                [("t", thread_router), ("p", proc_router)])
        for tag, r in arms:
            wall = run_streamed(r, ab_wl,
                                collect=lat if tag == "p" else None)
            (p_rates if tag == "p" else t_rates).append(
                ab_tokens / wall)
    thread_tok_s = statistics.median(t_rates)
    proc_tok_s = statistics.median(p_rates)
    ttfbs = np.asarray([x[0] for x in lat])
    ttfts = np.asarray([x[1] for x in lat])
    comps = np.asarray([x[2] for x in lat])
    p99_ttfb = float(np.percentile(ttfbs, 99))
    p99_ttft = float(np.percentile(ttfts, 99))
    p99_completion = float(np.percentile(comps, 99))
    p50_completion = float(np.percentile(comps, 50))
    # PER-REQUEST delta between first byte at the client and first
    # token in the engine: wire + dispatch overhead only. Tail-vs-tail
    # comparisons use the same request population on both sides.
    delta_med = float(np.median(ttfbs - ttfts))
    # structural: streamed TTFB is FIRST-TOKEN time, not completion
    # time — the whole point of streaming. It must track TTFT (a small
    # per-request wire/dispatch delta; tails aligned) and precede the
    # completion tail.
    assert delta_med <= 0.1, (
        f"median TTFB-TTFT delta {delta_med:.3f}s — chunk delivery is "
        f"lagging the engine")
    assert p99_ttfb <= p99_ttft * 1.5 + 0.2, (
        f"p99 TTFB {p99_ttfb:.3f}s does not track p99 TTFT "
        f"{p99_ttft:.3f}s")
    assert p99_ttfb < p99_completion, (
        f"p99 TTFB {p99_ttfb:.3f}s not under p99 completion "
        f"{p99_completion:.3f}s — streaming is buffering")
    proc_status = proc_router.status()
    worker_compiles = [r.get("programs_compiled")
                       for r in proc_status["replicas"]
                       if not r["retired"]]
    thread_router.close(drain_deadline_s=30)
    proc_router.close(drain_deadline_s=30)
    tm.close()
    pm.close()
    process_ab = {
        "status": "measured",
        "measured": True,
        "workload": ("48 streamed requests (prompt_len in [16,48), "
                     "max_new in [12,28)), paged page 16, 2 replicas "
                     "x 2 slots, chunk 1, 6 client threads; medians "
                     "of 5 interleaved passes after 2 warm passes "
                     "per arm"),
        "thread_fleet_tok_s": round(thread_tok_s, 1),
        "process_fleet_tok_s": round(proc_tok_s, 1),
        "process_over_thread": round(proc_tok_s / thread_tok_s, 3),
        "p99_ttfb_s": round(p99_ttfb, 5),
        "p99_ttft_s": round(p99_ttft, 5),
        "ttfb_minus_ttft_median_s": round(delta_med, 5),
        "p99_completion_s": round(p99_completion, 5),
        "p50_completion_s": round(p50_completion, 5),
        "worker_programs_compiled": worker_compiles,
        "streams_spliced_failovers": proc_status["failovers"],
    }

    return {
        "metric": "fleet_failover_and_hot_swap",
        "status": "measured",
        "measured": True,
        "workload": (f"{n_req} requests (prompt_len in [4,24), max_new "
                     f"in [12,28)), gpt {cfg.n_layer}L/{cfg.n_embd}d "
                     f"block {cfg.block_size}, 2 replicas x 4 slots, "
                     f"chunk 2"),
        "replica_kill": kill_arm,
        "hot_swap": swap_arm,
        "process_ab": process_ab,
    }


def measure_tracesim() -> dict:
    """The ISSUE 15 acceptance bench: sim-vs-live agreement on one
    trace × policy point. The SAME seeded flash-crowd trace (deep
    overload: the flash offers ~2× the replica's capacity, every
    request deadlined — admission control and queue sheds both fire)
    runs through (a) a REAL single-replica fleet via the open-loop
    replayer and (b) the discrete-event cost model over a calibrated
    ``ServiceProfile`` (two-point slope/intercept + saturated-burst
    aggregate). Gate: the model's p99 TTFT within [0.5×, 2×] of live
    (or 0.3 s absolute) and shed rate within 0.15 absolute — the
    agreement contract that makes ``servesim/sweep.py``'s policy
    frontier trustworthy. Both arms ``status=measured``; host-side by
    construction (CPU-forced like --chaos-only)."""
    import tempfile

    import numpy as np

    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.serve.engine import SamplingParams
    from gym_tpu.serve.metrics import ServeMetrics
    from gym_tpu.serve.router import build_fleet
    from gym_tpu.servesim import (FleetCostModel, calibrate_router,
                                  flash_crowd_trace, replay_router)

    import jax

    cfg = GPTConfig(block_size=128, vocab_size=48, n_layer=4, n_head=4,
                    n_embd=128, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64),
                           train=False)["params"]
    metrics = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_tsim_"),
                           engine_log_every=10)
    router = build_fleet(params, cfg, replicas=1, num_slots=1,
                         decode_chunk=1, metrics=metrics,
                         log=lambda *a, **k: None).start()
    # warm every prefill bucket the trace can hit (8/16/32) — a compile
    # inside the replay would poison BOTH the live tail and the
    # calibration the model is anchored to
    for n in (8, 16, 32):
        router.submit(np.arange(1, n + 1, dtype=np.int32) % 48,
                      SamplingParams(max_new_tokens=8, seed=n)
                      ).result(timeout=300)
    profile = calibrate_router(router, 48, num_slots=1,
                               saturate_burst=8)

    trace = flash_crowd_trace(
        duration_s=24, base_rps=1.5, flash_at_s=6, flash_mult=24,
        flash_len_s=6, seed=5, prompt_lens=(8, 32), max_news=(24, 56),
        deadline_s=1.5, deadline_frac=1.0)
    live = replay_router(router, trace, vocab_size=48,
                         time_scale=1.0)["report"]
    router.close(drain_deadline_s=60)
    metrics.close()

    model = FleetCostModel(profile, initial_replicas=1,
                           autoscale=False).run(trace).report()

    # the stated tolerances (the ci_deploy gate):
    p99_l, p99_m = live["ttft_p99_s"], model["ttft_p99_s"]
    shed_l, shed_m = live["shed_rate"], model["shed_rate"]
    ttft_ok = (p99_l is not None and p99_m is not None
               and (abs(p99_m - p99_l) <= 0.3
                    or 0.5 <= p99_m / p99_l <= 2.0))
    shed_ok = abs(shed_m - shed_l) <= 0.15
    agreement = {
        "ok": bool(ttft_ok and shed_ok),
        "ttft_ok": bool(ttft_ok),
        "shed_ok": bool(shed_ok),
        "tolerance": ("model p99 TTFT within [0.5x, 2x] of live or "
                      "0.3s abs; shed rate within 0.15 abs"),
        "p99_ttft_ratio": (round(p99_m / p99_l, 3)
                           if p99_l and p99_m else None),
        "shed_rate_delta": round(abs(shed_m - shed_l), 4),
    }
    assert agreement["ok"], {"agreement": agreement,
                             "live": live, "model": model}
    return {
        "metric": "tracesim_live_p99_ttft_s",
        "status": "measured",
        "measured": True,
        # the --compare headline: LIVE p99 TTFT under the overload
        # trace (lower is better, like the coldstart metric)
        "value": p99_l,
        "unit": "s_p99_ttft_live_lower_is_better",
        "workload": ("flash-crowd trace: 24s, base 1.5 rps, 24x flash "
                     "for 6s, prompt [8,32), max_new [24,56), 1.5s "
                     "deadline on all; 1 replica x 1 slot chunk 1, "
                     "gpt 4L/128d block 128; open-loop replay vs "
                     "cost model on the calibrated profile"),
        "requests": live["requests"],
        "profile": {
            "tokens_per_s": round(profile.tokens_per_s, 1),
            "request_overhead_s": round(profile.request_overhead_s, 5),
        },
        "live": live,
        "model": model,
        "agreement": agreement,
    }


def measure_analysis() -> dict:
    """Static-analysis summary (ISSUE 6): the full suite — lint, static
    trace reconciliation, jaxpr audit — as one JSON line, the
    machine-readable twin of `python -m gym_tpu.analysis`. Pure host
    tracing; 'violations' == 0 is the shipped-tree invariant."""
    from gym_tpu.analysis.__main__ import run_all

    report = run_all()
    sections = report["sections"]
    trace = sections["trace"]["strategies"]
    return {
        "violations": report["violations"],
        "lint_total": sections["lint"]["total"],
        "lint_suppressed": sections["lint"]["suppressed"],
        "strategies_reconciled": sum(1 for s in trace.values() if s["ok"]),
        "strategies_checked": len(trace),
        "programs_audited": len(sections["audit"]["programs"]),
        "program_keys": sections["audit"]["recompile_guard"]["n_keys"],
        "seconds": round(sum(s.get("seconds", 0)
                             for s in sections.values()), 2),
    }


def measure_elastic() -> dict:
    """The Elastic ZeRO acceptance bench (ROADMAP: Elastic ZeRO): the
    sweep's 2-layer GPT workload trained for real, measured three ways —
    (a) live per-node optimizer-state bytes, ZeRO-sharded vs replicated
    AdamW at K nodes (the ÷K headline, read off the final device
    state); (b) on-disk checkpoint bytes, the ZeRO-2 sharded layout vs
    the stacked replicated layout (one K-node fit each, same steps);
    (c) the membership change itself: ``fit(resume="auto",
    num_nodes=K-1)`` over the K-sharded checkpoint (restore → collective
    reshard → finish the last step) vs a cold restart replaying every
    step from 0. Both timing arms run twice; the warm pass — persistent
    compile cache hit, registry hot — is the steady-state number an
    autoscale-driven membership change sees. Host-side by construction
    (vnode-folded CPU mesh, like --sim-only); every arm is a real fit,
    status=measured."""
    import contextlib
    import tempfile
    import time

    import numpy as np

    from gym_tpu.data import ArrayDataset
    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.strategy import (OptimSpec, SimpleReduceStrategy,
                                  ZeroReduceStrategy)
    from gym_tpu.trainer import Trainer

    import jax

    k = int(os.environ.get("GYM_TPU_BENCH_ELASTIC_NODES", 4))
    k_new = k - 1
    steps = int(os.environ.get("GYM_TPU_BENCH_ELASTIC_STEPS", 30))
    interval = 10
    cfg_m = GPTConfig(block_size=64, vocab_size=65, n_layer=2, n_head=2,
                      n_embd=64, dropout=0.0, bias=True, attn_impl="dense")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 65, (2048, 65), dtype=np.int64)
    ds = ArrayDataset(np.ascontiguousarray(toks[:, :-1]),
                      np.ascontiguousarray(toks[:, 1:]))

    root = (os.environ.get("GYM_TPU_BENCH_ELASTIC_DIR")
            or tempfile.mkdtemp(prefix="gym_tpu_elastic_bench_"))
    common = dict(batch_size=16, minibatch_size=16, val_interval=0,
                  show_progress=False, seed=3, checkpoint_interval=interval,
                  async_checkpoint=False, devices=[0, 1],
                  log_dir=os.path.join(root, "logs"))

    def leaf_bytes(tree):
        return int(sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree)))

    def du(path):
        return sum(os.path.getsize(os.path.join(d, f))
                   for d, _, files in os.walk(path) for f in files)

    def fit(**kw):
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(sys.stderr):  # stdout: 1 JSON line
            res = Trainer(GPT(cfg_m), ds).fit(**kw)
        return res, round(time.perf_counter() - t0, 3)

    adamw = lambda: OptimSpec("adamw", lr=1e-3)
    # (a)+(b): one K-node fit per layout — live opt-state bytes off the
    # final device state, checkpoint bytes off the written tree
    res_z, _ = fit(strategy=ZeroReduceStrategy(adamw()), num_nodes=k,
                   max_steps=steps, run_name="el",
                   save_dir=os.path.join(root, "zero"), **common)
    res_r, _ = fit(strategy=SimpleReduceStrategy(adamw()), num_nodes=k,
                   max_steps=steps, run_name="el_repl",
                   save_dir=os.path.join(root, "repl"), **common)
    n_params = int(sum(x.size for x in jax.tree.leaves(res_z.params)))
    opt_z = leaf_bytes(res_z.node_state.strategy_state) // k
    opt_r = leaf_bytes(res_r.node_state.strategy_state) // k
    ckpt_z, ckpt_r = du(os.path.join(root, "zero")), du(
        os.path.join(root, "repl"))
    # the O(model/K) invariant, asserted on the measured bytes (padding
    # and the scalar count leave a little slack below the ideal ÷K; the
    # on-disk ratio additionally absorbs fixed per-checkpoint metadata
    # a 108K-param payload does not amortize)
    assert opt_r / opt_z > k - 1, (opt_r, opt_z, k)
    assert ckpt_r / ckpt_z > 1.5, (ckpt_r, ckpt_z, k)

    # (c) membership change: resume the ZeRO-2 checkpoint at K-1 (1 step
    # past the durable save) vs retraining those steps from scratch.
    # Twice each — on a fresh COPY of the sharded tree per resume, since
    # a finished resume writes its own final K'-shaped checkpoint; the
    # warm pass is the autoscaler's steady state. The cold arm
    # checkpoints at the same interval (a real restart re-saves too).
    import shutil

    times = {}
    for arm in ("cold_first", "cold_warm"):
        res_c, times[arm] = fit(strategy=ZeroReduceStrategy(adamw()),
                                num_nodes=k_new, max_steps=steps + 1,
                                run_name=arm,
                                save_dir=os.path.join(root, arm), **common)
        assert res_c.steps == steps + 1
    for arm in ("reshard_first", "reshard_warm"):
        arm_dir = os.path.join(root, arm)
        shutil.copytree(os.path.join(root, "zero"), arm_dir)
        res_e, times[arm] = fit(strategy=ZeroReduceStrategy(adamw()),
                                num_nodes=k_new, max_steps=steps + 1,
                                resume="auto", run_name="el",
                                save_dir=arm_dir, **common)
        assert res_e.steps == steps + 1
        # resumed at the durable step-6 save, did not replay from 0
        assert res_e.history["train_loss"][0][0] == steps, (
            res_e.history["train_loss"])
    # the acceptance claim, on the measured clocks: resharding beats
    # replaying the lost steps
    assert times["reshard_warm"] < times["cold_warm"], times
    speedup = round(times["cold_warm"] / times["reshard_warm"], 2)
    return {
        "metric": "elastic_zero_reshard_vs_cold_restart_speedup",
        "status": "measured",
        "measured": True,
        "value": speedup,
        "unit": "x_warm_wall_clock_higher_is_better",
        "workload": (f"2-layer GPT (n_embd=64, block 64, {n_params} "
                     f"params), {k} nodes vnode-folded on 2 CPU "
                     f"devices, {steps} steps, ckpt interval "
                     f"{interval}; membership change {k}->{k_new}"),
        "nodes": k,
        "nodes_after": k_new,
        "n_params": n_params,
        "opt_state_bytes_per_node": {
            "replicated_adamw": opt_r,
            "zero_sharded": opt_z,
            "reduction": round(opt_r / opt_z, 2),
        },
        "ckpt_bytes": {
            "stacked_replicated": ckpt_r,
            "zero2_sharded": ckpt_z,
            "reduction": round(ckpt_r / ckpt_z, 2),
        },
        "membership_change": {
            "reshard_resume_s": times["reshard_warm"],
            "reshard_resume_first_s": times["reshard_first"],
            "cold_restart_s": times["cold_warm"],
            "cold_restart_first_s": times["cold_first"],
            "steps_replayed_cold": steps,
            "steps_replayed_reshard": 0,
            "speedup": speedup,
        },
        "out_dir": root,
    }


def measure_tenant() -> dict:
    """The ISSUE 17 rider: tenant isolation, measured — the SAME
    noisy-neighbor workload (tenant B's batch flood already decoding
    when tenant A's interactive requests arrive) served twice:

    - ``baseline``: isolation OFF (no quotas, no preemption) — the
      victim's TTFT is whatever slot the flood deigns to free;
    - ``isolated``: isolation ON (batch token quota + preemptible
      decode) — arrivals park a flood slot at a chunk boundary and the
      quota sheds the flood's tail typed (429 + Retry-After).

    Reports the victim's TTFT tail in both arms plus preempt / shed
    counters. Two structural asserts ride in the bench itself: (1) the
    victim's p99 TTFT under isolation stays within 5% of the baseline
    (in practice it collapses — the improvement factor is the
    headline), and (2) EVERY completed stream — including every
    preempted-then-resumed batch request — equals its solo
    ``generate_fast`` run token-for-token, so the park/resume
    round-trip is provably invisible. Host-side by construction;
    always CPU-forced like --chaos-only."""
    import numpy as np

    from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from gym_tpu.serve.scheduler import (ClassQuota, QuotaExceededError,
                                         RequestStatus, Scheduler)

    import jax

    n_flood = int(os.environ.get("GYM_TPU_BENCH_TENANT_FLOOD", 6))
    n_victims = int(os.environ.get("GYM_TPU_BENCH_TENANT_VICTIMS", 6))
    flood_new, victim_new = 48, 8
    cfg = GPTConfig(block_size=128, vocab_size=65, n_layer=2, n_head=2,
                    n_embd=64, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]
    engine_kw = dict(num_slots=2, paged=True, page_size=16, kv_pages=64)

    rng = np.random.default_rng(17)
    flood_wl = [(rng.integers(0, cfg.vocab_size, int(rng.integers(16, 32))),
                 SamplingParams(max_new_tokens=flood_new, temperature=0.9,
                                top_k=16, seed=i))
                for i in range(n_flood)]
    victim_wl = [(rng.integers(0, cfg.vocab_size, 8),
                  SamplingParams(max_new_tokens=victim_new,
                                 temperature=0.9, top_k=16, seed=100 + i))
                 for i in range(n_victims)]
    # the exactness oracle: every request's solo generate_fast stream
    refs = {id(sp): generate_fast(params, cfg, p[None],
                                  sp.max_new_tokens, temperature=0.9,
                                  top_k=16, seed=sp.seed)[0, len(p):]
            .tolist() for p, sp in flood_wl + victim_wl}

    def run_arm(isolated: bool) -> dict:
        eng = InferenceEngine(params, cfg, **engine_kw)
        # quota: cap = 48 tok/s x 4 s burst = 192 tokens — admits 4 of
        # the 6 flood submissions back-to-back, sheds the tail typed
        sched = Scheduler(
            eng, max_queue=64,
            quotas=({"batch": ClassQuota(tokens_per_s=48.0, burst_s=4.0)}
                    if isolated else None),
            preempt=isolated)
        flood, shed = [], 0
        for p, sp in flood_wl:
            try:
                flood.append(sched.submit(p, sp, tenant="tenant_b",
                                          slo_class="batch"))
            except QuotaExceededError:
                shed += 1
        for _ in range(2000):
            sched.step()
            if flood and len(flood[0].tokens) >= 4:
                break
        victims = []
        for p, sp in victim_wl:
            victims.append(sched.submit(p, sp, tenant="tenant_a",
                                        slo_class="interactive"))
            for _ in range(4):
                sched.step()
        for _ in range(20000):
            if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
                   for h in flood + victims):
                break
            sched.step()
        # quota sheds strictly from the tail, so the admitted handles
        # line up with the workload prefix
        pairs = list(zip(flood, flood_wl)) + list(zip(victims, victim_wl))
        exact = all(h.result(timeout=1) == refs[id(sp)]
                    for h, (p, sp) in pairs)
        ttfts = sorted(h.ttft_s for h in victims)
        sched.shutdown(finish_running=False)
        return {
            "victim_ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
            "victim_ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
            "flood_shed_typed": shed,
            "flood_tokens_out": sum(len(h.tokens) for h in flood),
            "preemptions": sched.preemptions,
            "resumes": sched.resumes,
            "all_streams_exact": exact,
        }

    baseline = run_arm(isolated=False)
    isolated = run_arm(isolated=True)
    # structural asserts — an isolation bench that lets these slide is
    # measuring noise, not isolation
    assert isolated["all_streams_exact"] and baseline["all_streams_exact"], \
        "a served stream diverged from its solo generate_fast run"
    assert isolated["preemptions"] >= 1 and isolated["resumes"] >= 1, \
        "isolated arm never exercised preemptible decode"
    assert (isolated["victim_ttft_p99_s"]
            <= baseline["victim_ttft_p99_s"] * 1.05), \
        "isolation made the victim's p99 TTFT worse"
    assert isolated["flood_shed_typed"] == 2, \
        "quota admitted the wrong number of flood requests"
    return {
        "metric": "tenant_isolation_noisy_neighbor_victim_ttft_p99",
        "status": "measured",
        "measured": True,
        "workload": (f"{n_flood} batch flood (max_new {flood_new}) vs "
                     f"{n_victims} interactive victims (max_new "
                     f"{victim_new}), gpt {cfg.n_layer}L/{cfg.n_embd}d, "
                     f"2 paged slots, quota 48 tok/s x 4 s burst"),
        "baseline": baseline,
        "isolated": isolated,
        "victim_p99_improvement": round(
            baseline["victim_ttft_p99_s"]
            / max(isolated["victim_ttft_p99_s"], 1e-9), 2),
        "preempted_resume_exact": isolated["all_streams_exact"],
    }


def main() -> None:
    force_cpu = ("--cpu" in sys.argv or "--sim-only" in sys.argv
                 or "--chaos-only" in sys.argv
                 or "--fleet-only" in sys.argv
                 or "--analyze-only" in sys.argv
                 or "--coldstart-only" in sys.argv
                 or "--tracesim-only" in sys.argv
                 or "--elastic-only" in sys.argv
                 or "--tenant-only" in sys.argv
                 or "--sdc-only" in sys.argv)
    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if any(m in sys.argv for m in (
                "--overlap-only", "--resilience-only", "--sim-only",
                "--elastic-only", "--sdc-only")):
            # ablation-only CPU run: the 16-virtual-device layout of the
            # test harness (the flag must precede jax's initialization)
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=16 "
                + os.environ.get("XLA_FLAGS", ""))

    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform == "cpu":
        sys.exit("bench.py: no accelerator (jax.devices()[0] is the CPU) — "
                 "a device mode measures nothing here; ask for the CPU "
                 "arms with --cpu")

    # Persistent XLA compile cache: a repeated bench invocation of the
    # same program skips the ~40 s warmup compile entirely. Opt out with
    # GYM_TPU_BENCH_COMPILE_CACHE=0 (e.g. to measure cold compiles).
    # --serve-only NEVER uses it: its headline measures exactly the
    # compile behavior a serving process sees (a warm persistent cache
    # would quietly turn the cold arms warm on the second invocation).
    if (os.environ.get("GYM_TPU_BENCH_COMPILE_CACHE", "1") == "1"
            and "--serve-only" not in sys.argv
            and "--coldstart-only" not in sys.argv):
        from gym_tpu.utils.compile_cache import enable_compilation_cache
        enable_compilation_cache()

    if "--overlap-only" in sys.argv:
        print(json.dumps({"host_overlap": measure_host_overlap()}))
        return

    if "--resilience-only" in sys.argv:
        print(json.dumps(
            {"resilience_overhead": measure_resilience_overhead()}))
        return

    if "--sdc-only" in sys.argv:
        print(json.dumps({"sdc_guard": measure_sdc_guard()}))
        return

    if "--sim-only" in sys.argv:
        print(json.dumps({"network_sim": measure_network_sim()}))
        return

    if "--serve-only" in sys.argv:
        print(json.dumps({"serving": measure_serving()}))
        return

    if "--coldstart-only" in sys.argv:
        print(json.dumps({"coldstart": measure_coldstart()}))
        return

    if "--chaos-only" in sys.argv:
        print(json.dumps({"chaos": measure_chaos()}))
        return

    if "--fleet-only" in sys.argv:
        print(json.dumps({"fleet": measure_fleet()}))
        return

    if "--tracesim-only" in sys.argv:
        print(json.dumps({"tracesim": measure_tracesim()}))
        return

    if "--analyze-only" in sys.argv:
        print(json.dumps({"analysis": measure_analysis()}))
        return

    if "--elastic-only" in sys.argv:
        print(json.dumps({"elastic": measure_elastic()}))
        return

    if "--tenant-only" in sys.argv:
        print(json.dumps({"tenant": measure_tenant()}))
        return

    import numpy as np

    from gym_tpu.models.base import LossModel
    from gym_tpu.models.nanogpt import (GPT, GPTConfig, PEAK_BF16_FLOPS,
                                        node_mfu)
    from gym_tpu.parallel.mesh import NodeRuntime
    from gym_tpu.strategy.diloco import DiLoCoStrategy
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.train_node import make_init_fn, make_multi_train_step

    import jax.numpy as jnp

    attn = os.environ.get("GYM_TPU_BENCH_ATTN",
                          "dense" if force_cpu else "flash")
    cfg = GPTConfig(block_size=BLOCK_SIZE, vocab_size=VOCAB, n_layer=4,
                    n_head=4, n_embd=128, dropout=0.0, bias=True,
                    attn_impl=attn)
    # bf16 forward (params stay f32; loss/softmax accumulate f32) — the
    # TPU-native analog of the reference's autocast, default ON for the
    # benchmark since MXU bf16 is the intended number format.
    bf16 = os.environ.get("GYM_TPU_BENCH_BF16", "1") == "1"
    loss_model = LossModel(GPT(cfg), jnp.bfloat16 if bf16 else None)

    spc = int(os.environ.get("GYM_TPU_BENCH_SPC", 20))
    warm_calls = max(1, WARMUP // spc)
    timed_calls = max(1, TIMED // spc)

    strategy = DiLoCoStrategy(
        optim_spec=OptimSpec("adamw", lr=3e-4), H=100,
        lr_scheduler="lambda_cosine",
        lr_scheduler_kwargs={"warmup_steps": 100},
    )
    strategy.finalize(max_steps=(warm_calls + timed_calls) * spc)

    runtime = NodeRuntime.create(NUM_NODES, jax.devices())

    # S steps per dispatch: amortizes host→device dispatch latency
    # across a lax.scan of compiled steps.
    rng = np.random.default_rng(0)
    idx = rng.integers(
        0, VOCAB, (NUM_NODES, spc, 1, BATCH_PER_NODE, BLOCK_SIZE),
        dtype=np.int64,
    )
    batches = runtime.shard_batch((idx, np.roll(idx, -1, axis=-1)))

    init_fn = make_init_fn(loss_model, strategy,
                           (idx[0, 0, 0], idx[0, 0, 0]), seed=42)
    state = runtime.init_state(init_fn)
    multi_step = runtime.compile(
        make_multi_train_step(loss_model, strategy, runtime.ctx)
    )

    for _ in range(warm_calls):
        state, metrics = multi_step(state, batches)
    # fence: fetching the loss waits for the whole step chain
    float(np.asarray(metrics["loss"]).sum())

    # best-of-N windows, each fenced by a value fetch. CPU runs skip the
    # extra window: a CPU window takes ~40 min, so the 0.008 it/s
    # baseline stays measured the way it always was.
    default_windows = "1" if force_cpu else "2"
    windows = max(1, int(os.environ.get("GYM_TPU_BENCH_WINDOWS",
                                        default_windows)))
    best_dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(timed_calls):
            state, metrics = multi_step(state, batches)
        loss = float(np.asarray(metrics["loss"]).mean())
        best_dt = min(best_dt, time.perf_counter() - t0)

    it_s = timed_calls * spc / best_dt
    assert np.isfinite(loss), f"non-finite loss {loss}"

    baseline_env = os.environ.get("GYM_TPU_BENCH_BASELINE")
    baseline = float(baseline_env) if baseline_env else CPU_BASELINE_IT_S
    baseline_prov = ("env-override" if baseline_env
                     else CPU_BASELINE_MEASURED_AT)
    # MFU of the whole 64-node workload (seqs/iter = nodes × per-node batch)
    # (None on a device with no entry in the peaks table, e.g. --cpu)
    peak = PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)
    mfu = (node_mfu(cfg, state.params, NUM_NODES * BATCH_PER_NODE,
                    1.0 / it_s, peak_flops=peak * len(jax.devices()))
           if peak else None)
    result = {
        "metric": "nanogpt_diloco_64node_iterations_per_sec",
        "status": "measured",
        "measured": True,
        "value": round(it_s, 3),
        "unit": "it/s",
        "vs_baseline": round(it_s / baseline, 2),
        "cpu_baseline_it_s": baseline,
        "cpu_baseline_measured_at": baseline_prov,
        "mfu": None if mfu is None else round(mfu, 4),
        # timing method is part of the metric's identity
        "timing": f"best_of_{windows}",
    }

    # Realistic-scale rider: GPT-2 base (124M) single-replica MFU,
    # measured by the same code path as benchmarks/bench_gpt2_base.py.
    # Skipped on CPU (a base-model step takes minutes there). Disable
    # with GYM_TPU_BENCH_BASE=0. A rider that fails fails the run.
    if not force_cpu and os.environ.get("GYM_TPU_BENCH_BASE", "1") == "1":
        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks"))
        from bench_gpt2_base import measure

        base = measure(size="base", nodes=1, batch=16, attn="flash",
                       remat=False, strategy="diloco",
                       steps=15, warmup=5, spc=5)
        result["gpt2_base_it_per_sec"] = base["value"]
        result["gpt2_base_mfu"] = base["mfu"]
        result["gpt2_base_tokens_per_sec"] = base["tokens_per_sec"]

    # Host-overlap ablation rider (ISSUE 1): prefetch on/off A/B. On an
    # accelerator it runs in-process (a chip belongs to one process); on
    # CPU it runs in a fresh subprocess pinned to the 16-virtual-device
    # harness layout.
    if os.environ.get("GYM_TPU_BENCH_OVERLAP", "1") == "1":
        result["host_overlap"] = (_overlap_subprocess() if force_cpu
                                  else measure_host_overlap())

    print(json.dumps(result))


if __name__ == "__main__":
    if "--compare" in sys.argv:
        # artifact comparison is pure host-side JSON work: no jax
        i = sys.argv.index("--compare")
        if len(sys.argv) < i + 3:
            print(json.dumps({"mode": "compare", "comparable": False,
                              "note": "not_comparable",
                              "reason": "--compare needs two artifact "
                                        "paths"}))
            sys.exit(1)
        print(json.dumps(compare_runs(sys.argv[i + 1], sys.argv[i + 2])))
        sys.exit(0)
    if "--coldstart-worker" in sys.argv:
        # measure_coldstart's child (env is prepared by measure_coldstart)
        _coldstart_worker()
        sys.exit(0)
    main()
