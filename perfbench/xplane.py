"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time, time by operation,
collective time and the part of it that nothing hides, the idle gaps by
what the host was doing.

Only ``jax.profiler.ProfileData`` is needed to read the file. The
reduction itself (``reduce_events``) works on plain tuples, so it is
tested on a small trace recorded on the chip and kept beside the tests.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
MIN_GAP_NS = 20_000     # gaps shorter than this are the ops' own spacing

Event = Tuple[str, float, float]        # name, start_ns, duration_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, duration_ns), ...]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return out


def op_kind(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: an operation's name without its
    instance number, the key operations are summed under."""
    head = name.lstrip("%").split(" ")[0].split("=")[0]
    return re.sub(r"[.\d]+$", "", head) or head


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _subtract(a, b) -> float:
    """Length of the part of interval list ``a`` (merged) that interval
    list ``b`` (merged) does not cover."""
    total, j = 0.0, 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            total += hi - cur
    return total


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float                 # first device event to the last
    busy_s: float                   # union of op intervals, mean over chips
    op_seconds: Dict[str, float]    # by op_kind, mean over chips
    collective_s: float             # mean over chips
    collective_exposed_s: float     # ... while no other op ran on the chip
    idle_gaps: Dict[str, float]     # host activity -> idle seconds (chip 0)
    op_names: Dict[str, float]      # full names -> seconds (chip 0)
    module_runs: Dict[str, Tuple[int, float]]   # program -> runs, s (chip 0)
    module_events: Dict[str, List[Tuple[float, float]]]  # -> (start, dur) ns

    def top_ops(self, n: int = 10):
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10):
        return sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]

    def main_module(self):
        """``(name, runs, seconds)`` of the program that took most device
        time: the training step, the decode step."""
        if not self.module_runs:
            return None
        name = max(self.module_runs, key=lambda k: self.module_runs[k][1])
        return (name, *self.module_runs[name])

    def main_module_step(self):
        """Of the program that took most device time: ``(runs, seconds a
        run, seconds from one run's start to the next)``, medians over
        the runs the trace holds whole. The first is the device's own
        time for a step, the second the step's period, waits included."""
        main = self.main_module()
        if not main:
            return None
        events = sorted(self.module_events[main[0]])
        if len(events) < 3:
            return None
        import statistics
        durations = [d for _s, d in events]
        periods = [b[0] - a[0] for a, b in zip(events, events[1:])]
        return (len(events), statistics.median(durations) * 1e-9,
                statistics.median(periods) * 1e-9)

    def custom_call_seconds(self, target: str = "tpu_custom_call",
                            head: str = "") -> float:
        """Device seconds (chip 0) of the custom calls to ``target`` (the
        Pallas kernels) whose own name, the part before ``=``, matches
        ``head``."""
        rx = re.compile(head)
        return sum(s for name, s in self.op_names.items()
                   if f'custom_call_target="{target}"' in name
                   and rx.search(name.split(" = ")[0]))


def _host_label(name: str) -> str:
    label = re.sub(r"[^A-Za-z0-9]+", "_", name.lstrip("$")).strip("_")
    return label[:48] or "unnamed"


def reduce_events(planes: Dict[str, Dict[str, List[Event]]],
                  window_s: Optional[float] = None) -> Optional[Summary]:
    """The reduction. ``None`` when no operation ran on any device."""
    device = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and lines.get(OPS_LINE):
            device[int(m.group(1))] = [e for e in lines[OPS_LINE]
                                       if e[2] > 0]
    device = {k: v for k, v in device.items() if v}
    if not device:
        return None
    n = len(device)
    busy = coll = exposed = 0.0
    ops: Dict[str, float] = {}
    t_lo = min(e[1] for evs in device.values() for e in evs)
    t_hi = max(e[1] + e[2] for evs in device.values() for e in evs)
    for evs in device.values():
        busy += _length(_union([(s, s + d) for _n, s, d in evs]))
        c_iv = _union([(s, s + d) for nm, s, d in evs
                       if COLLECTIVE.match(op_kind(nm))])
        o_iv = _union([(s, s + d) for nm, s, d in evs
                       if not COLLECTIVE.match(op_kind(nm))])
        coll += _length(c_iv)
        exposed += _subtract(c_iv, o_iv)
        for nm, _s, d in evs:
            k = op_kind(nm)
            ops[k] = ops.get(k, 0.0) + d
    first = device[min(device)]
    names: Dict[str, float] = {}
    for nm, _s, d in first:
        names[nm] = names.get(nm, 0.0) + d * 1e-9
    modules: Dict[str, Tuple[int, float]] = {}
    module_events: Dict[str, List[Tuple[float, float]]] = {}
    for nm, _s, d in planes[f"/device:TPU:{min(device)}"].get(MODULES_LINE,
                                                              []):
        key = re.sub(r"\(\d+\)$", "", nm)
        runs, secs = modules.get(key, (0, 0.0))
        modules[key] = (runs + 1, secs + d * 1e-9)
        module_events.setdefault(key, []).append((_s, d))
    return Summary(
        devices=n, module_runs=modules, module_events=module_events,
        window_s=window_s if window_s else (t_hi - t_lo) * 1e-9,
        busy_s=busy / n * 1e-9,
        op_seconds={k: v / n * 1e-9 for k, v in ops.items()},
        collective_s=coll / n * 1e-9,
        collective_exposed_s=exposed / n * 1e-9,
        idle_gaps=_gaps_by_host(first, planes),
        op_names=names)


def _gaps_by_host(dev_events: List[Event], planes) -> Dict[str, float]:
    """Idle gaps of one chip, each booked to the host event that overlaps
    it most (the shorter event wins a tie: the innermost frame)."""
    busy = _union([(s, s + d) for _n, s, d in dev_events])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    out: Dict[str, float] = {}
    short = sum(hi - lo for lo, hi in gaps if hi - lo < MIN_GAP_NS)
    if short:
        out["between_ops_under_20us_each"] = short * 1e-9
    gaps = [g for g in gaps if g[1] - g[0] >= MIN_GAP_NS]
    if not gaps:
        return out
    host = sorted(
        (e for name, lines in planes.items() if name.startswith("/host:")
         for evs in lines.values() for e in evs
         # "$..." are the Python tracer's frames, one per call and nested
         # to the thread's root: the runtime's own annotations say more
         if e[2] > 0 and not e[0].startswith("$")),
        key=lambda e: e[1])
    starts = [e[1] for e in host]
    # look back no further than the longest host event, two seconds at most
    longest = min(max((e[2] for e in host), default=0.0), 2e9)
    for lo, hi in gaps:
        best, best_key = "unattributed", (0.0, 0.0)
        i = bisect.bisect_left(starts, lo - longest)
        while i < len(host) and host[i][1] < hi:
            nm, s, d = host[i]
            ov = min(hi, s + d) - max(lo, s)
            if ov > 0 and (ov, -d) > best_key:
                best, best_key = _host_label(nm), (ov, -d)
            i += 1
        out[best] = out.get(best, 0.0) + (hi - lo) * 1e-9
    return out


def summarize(trace_dir: str, window_s: Optional[float] = None
              ) -> Optional[Summary]:
    path = find_xplane(trace_dir)
    return reduce_events(read_planes(path), window_s) if path else None
