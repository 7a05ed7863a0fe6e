"""The harness: finds a cell's files by name, runs its traffic kind, reads
the per-layer metrics, and ends on the contract's one line.

Driven by data. ``BENCHMARK.json`` names cells, configurations, traffic
mixes and metrics; each is a file of its own found by that name:

* ``perfbench/configs/<config>.json``      — sizes as run, source, cuts
* ``perfbench/traffic/<traffic>.json``     — ``kind`` and its parameters
* ``perfbench/limits/<cell>.json``         — the limits of ``correct``
* ``perfbench/kinds/<kind>.py``            — the one generator of a kind
* ``perfbench/layer_metrics/<metric>.py``  — ``read(facts)`` -> number|None

so a later PR adds cells, mixes and metrics by adding files and entries.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_ROOT = os.path.join(ROOT, ".perfbench_out")


class Refuse(Exception):
    """The run cannot be a measurement (no chip, too few chips, a file
    missing): exit non-zero and print no result."""


def claim_stdout():
    """Point fd 1 at stderr and return the real stdout as a private file
    (``chip_smoke.py``'s discipline): nothing a library, a thread or an
    exit hook prints can land on stdout, before or after the last line."""
    sys.stdout.flush()
    real = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    return real


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything the run needs to know about one cell, from
    ``BENCHMARK.json`` and the files it names."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refuse(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", workload + ".json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits, "bench_dir": bench_dir,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def load_reader(bench_dir: str, name: str):
    """The per-layer metric's own reader, found by the metric's name."""
    path = os.path.join(bench_dir, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        raise Refuse(f"per-layer metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(spec: dict, facts: dict) -> dict:
    """Every per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and its metric is left out of
    the line; a reader that raises fails the traced run."""
    out = {}
    for metric in spec["per_layer"]:
        value = load_reader(spec["bench_dir"], metric["name"])(facts)
        if value is None or not math.isfinite(value):
            continue        # nothing to read: left out of the line
        out[metric["name"]] = {"value": float(value),
                               "unit": metric["unit"]}
    return out


def device_block(devices, chips: int, peak_bytes=None, trace=None) -> dict:
    first = devices[0] if devices else None
    block = {"platform": getattr(first, "platform", None),
             "kind": getattr(first, "device_kind", None),
             "count": chips if devices else 0,
             "memory_peak_bytes": peak_bytes}
    if trace is not None:
        block["busy_s"] = trace.busy_s
        block["window_s"] = trace.window_s
    return block


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, as the allocator reports: the
    buffers in use at their peak plus, where the backend keeps them apart
    (the TPU does), the peak of what compiled programs reserve for their
    temporaries. The two peaks fall together in a step: the state is live
    while the step's scratch is."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved", 0))
    return int(max(peaks)) if peaks else 0


class MidRunTrace(threading.Thread):
    """Records a profiler trace of ``span_s`` seconds from another thread
    once ``ready()`` says the run is far enough in: a steady stretch in
    the middle of the window, not its first steps. The Python tracer is
    off: it costs host time and the reduction does not read it."""

    def __init__(self, trace_dir: str, span_s: float, ready):
        super().__init__(name="perfbench-trace", daemon=True)
        self.trace_dir, self.span_s, self.ready = trace_dir, span_s, ready
        self.span = None            # (start, stop) on the host's clock
        self.cancel = threading.Event()

    def run(self) -> None:
        import jax
        while not self.ready():
            if self.cancel.wait(0.05):
                return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        start = time.monotonic()
        self.cancel.wait(self.span_s)
        stop = time.monotonic()     # collecting the trace takes seconds
        jax.profiler.stop_trace()
        self.span = (start, stop)

    def finish(self):
        self.cancel.set()
        self.join(timeout=120)
        return self.span


class CompileClock:
    """When XLA was asked to compile in this process: one time stamp per
    request, whether the persistent cache answered it or not. A program
    built inside the measured window shows as a stamp inside it."""

    EVENTS = ("/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax.monitoring
        self.stamps = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.EVENTS:
            self.stamps.append(time.monotonic())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in list(self.stamps) if lo <= t <= hi)


class Heartbeat(threading.Thread):
    """A witness to stalls: a thread that sleeps ``every`` seconds and
    notes each time it woke more than ``late_s`` late. When the loop that
    is measured stands still and this thread does too, the process (or
    the machine) stood still; when it beats on, the loop was waiting for
    the device. Logged; judged by nothing."""

    def __init__(self, every: float = 0.05, late_s: float = 0.1):
        super().__init__(name="perfbench-heartbeat", daemon=True)
        self.every, self.late_s = every, late_s
        self.late, self.beats = [], 0
        self.stop = threading.Event()

    def run(self) -> None:
        while not self.stop.is_set():
            t = time.monotonic()
            self.stop.wait(self.every)
            over = time.monotonic() - t - self.every
            self.beats += 1
            if over > self.late_s:
                self.late.append((t, over))

    def close(self, lo: float, hi: float) -> dict:
        self.stop.set()
        self.join(timeout=5)
        inside = [(t, d) for t, d in self.late if lo <= t < hi]
        return {"beats": self.beats,
                "late_max_s": max((d for _, d in inside), default=0.0),
                "late": [[round(t - lo, 3), round(d, 3)]
                         for t, d in inside[:8]]}


class GcClock:
    """When, for how long and in which generation Python's collector
    stopped this process: a pause stops server and clients alike, and a
    stall in the arrivals is either one of these or it is not."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.pauses.append((self._t, now - self._t, info["generation"]))

    def close(self, lo: float, hi: float) -> dict:
        gc.callbacks.remove(self._on_gc)
        inside = [(d, g) for t, d, g in self.pauses if lo <= t < hi]
        return {"count": len(inside),
                "total_s": sum(d for d, _ in inside),
                "longest_s": max((d for d, _ in inside), default=0.0),
                "longest_generation": max(inside, default=(0, None))[1]}


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the machine this "
                    "is started on and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is (the "
                         "CPU here); says so and prints no device metric")
    return ap.parse_args(argv)


def main(t0: float, argv=None) -> int:
    args = parse_args(argv)
    out = claim_stdout()

    def log(obj: dict) -> None:
        out.write(json.dumps({"t_s": round(time.monotonic() - t0, 2), **obj},
                             default=str) + "\n")
        out.flush()

    line, code = None, 1
    devices, chips = [], 0
    try:
        spec = load_cell(args.workload)
        chips = int(spec["cell"]["chips"])
        seconds = (args.seconds if args.seconds is not None
                   else float(spec["bench"]["run_seconds"]))
        # the program under test, its compile cache inside the checkout
        # (or where JAX_COMPILATION_CACHE_DIR says), nothing under /tmp
        try:
            import jax
            from gym_tpu import programs
        except ImportError as e:
            raise Refuse(f"the program under test is not here: {e}")
        devices = jax.devices()
        if args.rehearse:
            log({"rehearsal": True, "note":
                 "tiny sizes on " + devices[0].platform + ": no "
                 "measurement, no device metric is printed"})
        else:
            if devices[0].platform != "tpu":
                raise Refuse(f"no accelerator: JAX found "
                             f"{devices[0].platform}")
        if len(devices) < chips:
            raise Refuse(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
        devices = devices[:chips]
        cache_dir = programs.enable_disk_tier(min_compile_time_secs=0.0)
        compiles = CompileClock()
        out_dir = os.path.join(OUT_ROOT, args.workload)
        os.makedirs(out_dir, exist_ok=True)
        ctx = {"t0": t0, "args": args, "spec": spec, "seconds": seconds,
               "devices": devices, "chips": chips, "log": log,
               "out_dir": out_dir, "rehearse": args.rehearse,
               "compiles": compiles,
               "limits": spec["limits"]["rehearse" if args.rehearse
                                        else "limits"],
               # a rehearsal overlays the files' own tiny presets
               "traffic": {**spec["traffic"],
                           **(spec["traffic"].get("rehearse", {})
                              if args.rehearse else {})},
               "sizes": ({**spec["config"], **spec["config"]["rehearse"]}
                         if args.rehearse else spec["config"])}
        log({"workload": args.workload, "seed": args.seed,
             "seconds": seconds, "trace": args.trace,
             "cache_dir": cache_dir,
             "device": device_block(devices, chips)})
        kind = importlib.import_module(
            "perfbench.kinds." + spec["traffic"]["kind"])
        result = kind.run(ctx)
        facts = result["facts"]
        trace = facts.get("trace")
        if args.trace:
            if trace is None and not args.rehearse:
                raise RuntimeError("the traced run recorded no operation "
                                   "on the device")
            metrics = read_layer_metrics(spec, facts)
        else:
            metrics = {m["name"]: {"value": float(
                result["end_to_end"][m["name"]]), "unit": m["unit"]}
                for m in spec["end_to_end"]}
        for row in result["compared"]:
            log({"compared": row["name"], **{k: v for k, v in row.items()
                                             if k != "name"}})
        line = {"correct": bool(result["correct"]),
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]),
                "metrics": {} if args.rehearse else metrics,
                "device": device_block(
                    devices, chips, facts.get("memory_peak_bytes"),
                    trace if args.trace else None)}
        if args.trace and trace is not None and not args.rehearse:
            line["breakdown"] = {
                "device_ops": [[k, v] for k, v in trace.top_ops(10)],
                "idle_gaps": [[k, v] for k, v in trace.top_gaps(10)]}
        if args.rehearse:
            line["rehearsal"] = True
            line["metric_names"] = sorted(metrics)
        code = 0
    except Refuse as e:
        sys.stderr.write(f"perfbench: refused: {e}\n")
        line, code = None, 2
    except Exception as e:  # noqa: BLE001 — boundary: say so, exit != 0
        traceback.print_exc(file=sys.stderr)
        log({"error": f"{type(e).__name__}: {e}"[:500]})
        line = {"correct": False, "attempted": 0, "failed": 0,
                "metrics": {}, "device": device_block(devices, chips)}
        code = 1
    finally:
        if line is not None:
            out.write(json.dumps(line) + "\n")
        out.close()
    return code
