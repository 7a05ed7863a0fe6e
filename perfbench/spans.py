"""What the program's own names add to what the benchmark can read: its
spans (``gym_tpu/utils/trace.py`` keeps them in a recorder, serves their
totals under ``/stats`` and, in a profiler session, writes them on the host
plane of the ``xplane.pb`` the device's operations are in, each with its
``seq``), its scopes in the step program and its kernels' names.

* ``idle_by_span``: chip 0's idle intervals intersected with the spans'
  intervals on the trace's own clock, so that every idle second is booked
  to what the program was doing (the innermost span that covers it);
* ``op_scopes``: each device operation's scope (``fwd_bwd``, ``strategy``,
  ...), which only the operation's metadata carries. ``ProfileData`` does
  not hand out event metadata, so a reader of the few protobuf fields
  needed is here (``xplane.proto``: XSpace.planes=1; XPlane.name=2,
  .event_metadata=4, .stat_metadata=5; XEventMetadata.name=2,
  .display_name=4, .stats=5; XStat.metadata_id=1, .str_value=5,
  .ref_value=7; XStatMetadata.name=2).

    python3 -m perfbench.spans <trace-dir>     # the table of PERF.md §5

A parent without spans, or a trace without scopes, gives empty results.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from perfbench import harness, xplane

Span = Tuple[str, float, float, dict]       # name, start_ns, end_ns, stats


def stats_span_deltas(facts) -> Optional[Dict[str, Tuple[int, float]]]:
    """``{span: (count, seconds)}`` between two of the window's ``/stats``
    samples, which hold the program's span totals under ``spans``; None
    where the program serves none (the parent of the PR that added them).

    The scheduler commits a round's spans together when the round ends,
    so the totals always hold whole rounds and their ratios are exact.
    The window opens inside the round that filled the slots (one prefill
    a slot, 20 s of them), which is committed a moment later: the first
    sample taken is therefore the first one after the round count moved,
    and the last is the window's last."""
    samples = [s for s in facts.get("stats_samples") or [] if "spans" in s]
    if len(samples) < 3:
        return None

    def rounds(sample):
        return sample["spans"].get("serve.round", (0,))[0]

    first = next((s for s in samples if rounds(s) > rounds(samples[0])),
                 None)
    if first is None or first is samples[-1]:
        return None
    first, last = first["spans"], samples[-1]["spans"]
    return {name: (row[0] - first.get(name, (0, 0.0))[0],
                   row[1] - first.get(name, (0, 0.0))[1])
            for name, row in last.items()}


def fit_records(run: str = "timed") -> list:
    """The span records the fit named ``run`` left in the program's
    recorder (``gym_tpu.utils.trace``; the fit kind's two fits are
    ``check`` and ``timed``), oldest first; empty where the program has
    no recorder."""
    try:
        from gym_tpu.utils import trace
    except ImportError:
        return []
    return trace.records(run=run)


def retire_periods(records: list) -> List[Tuple[int, float, float, float]]:
    """Per retired step of a fit, from its ``fit.retire.wait`` spans (the
    read-back that blocks until the step's dispatch retired) and its
    ``fit.data_wait`` spans: ``(step, period_s, waited_s, data_wait_s)``,
    the period from the previous step's retirement to this one's and the
    two waits inside it. The first step, which holds the compile, and
    the one after it have no full period and are left out."""
    waits = sorted((r for r in records if r.name == "fit.retire.wait"),
                   key=lambda r: r.t1)
    data = sorted((r for r in records if r.name == "fit.data_wait"),
                  key=lambda r: r.t0)
    starts = [r.t0 for r in data]
    out = []
    for prev, cur in zip(waits[1:], waits[2:]):
        i = bisect.bisect_left(starts, prev.t1)
        fed = 0.0
        while i < len(data) and data[i].t1 <= cur.t1:
            fed += data[i].seconds
            i += 1
        out.append((cur.ids["step"], (cur.t1 - prev.t1) * 1e-9,
                    cur.seconds, fed))
    return out


def newest_xplane(root: Optional[str] = None) -> Optional[str]:
    """The ``xplane.pb`` written last under the harness's output root:
    the one the run that is being reduced has just recorded."""
    found = glob.glob(os.path.join(root or harness.OUT_ROOT, "*", "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def program_spans(path: str) -> List[Span]:
    """The host events that are the program's spans: those that carry a
    ``seq``. Sorted by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "seq" in stats:
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns),
                                stats))
    return sorted(out, key=lambda s: s[1])


def idle_intervals(path: str) -> Tuple[List[Tuple[float, float]], float]:
    """Chip 0's idle intervals between its first and last operation, and
    the length of that stretch, in ns."""
    planes = xplane.read_planes(path)
    chips = sorted(int(m.group(1)) for m in map(xplane.DEVICE_PLANE.match,
                                                planes) if m)
    for chip in chips:
        ops = [e for e in planes[f"/device:TPU:{chip}"].get(
            xplane.OPS_LINE, []) if e[2] > 0]
        if ops:
            busy = xplane._union([(s, s + d) for _n, s, d in ops])
            return ([(a[1], b[0]) for a, b in zip(busy, busy[1:])],
                    busy[-1][1] - busy[0][0])
    return [], 0.0


def idle_by_span(path: str, skip=("http.generate",)) -> Dict[str, float]:
    """``{span name: idle seconds of chip 0 inside it}``: every idle
    interval is cut at the spans' edges and each piece goes to the
    innermost span that covers it (the one that started last), or to
    ``outside_spans``. ``skip``: spans of other threads that lie over
    everything (a request's whole life in its HTTP handler)."""
    spans = [s for s in program_spans(path) if s[0] not in skip]
    gaps, _ = idle_intervals(path)
    out: Dict[str, float] = {}
    if not gaps:
        return out
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    for lo, hi in gaps:
        i = bisect.bisect_left(starts, lo - longest)
        over = []
        while i < len(spans) and spans[i][1] < hi:
            if spans[i][2] > lo:
                over.append(spans[i])
            i += 1
        cuts = sorted({lo, hi, *(t for s in over for t in (s[1], s[2])
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[1] <= a and s[2] >= b]
            name = (max(cover, key=lambda s: s[1])[0] if cover
                    else "outside_spans")
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


# -- the operations' scopes: a reader of the protobuf fields needed ----------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: varints as
    ints, length-delimited fields as bytes; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for field, _w, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            val = v
    return key, val


def op_scopes(path: str, plane_rx=xplane.DEVICE_PLANE,
              stat: str = "tf_op") -> Dict[str, str]:
    """``{operation's name as the trace prints it: its op_name}`` for the
    first device plane: the path of scopes the operation was traced
    under, ``jit(step)/.../fwd_bwd/.../dot_general``."""
    with open(path, "rb") as f:
        space = f.read()
    for field, _w, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for pf, _pw, pv in _fields(plane):
            if pf == 2:
                name = pv.decode("utf-8", "replace")
            elif pf == 4:
                events.append(_map_entry(pv)[1])
            elif pf == 5:
                sid, meta = _map_entry(pv)
                stats[sid] = next((v.decode("utf-8", "replace")
                                   for f2, _w2, v in _fields(meta)
                                   if f2 == 2), "")
        if not plane_rx.match(name):
            continue
        want = {sid for sid, sname in stats.items() if sname == stat}
        out: Dict[str, str] = {}
        for meta in events:
            names, scope = [], None
            for mf, _mw, mv in _fields(meta):
                if mf in (2, 4):
                    names.append(mv.decode("utf-8", "replace"))
                elif mf == 5:
                    sid, text, ref = None, None, None
                    for sf, _sw, sv in _fields(mv):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            text = sv.decode("utf-8", "replace")
                        elif sf == 7:
                            ref = sv
                    if sid in want:
                        scope = text if text is not None else stats.get(ref)
            if scope:
                for nm in names:
                    out[nm] = scope
        return out
    return {}


def scope_seconds(trace, scopes: Dict[str, str], scope: str) -> float:
    """Device seconds (chip 0, the traced window) of the operations whose
    op_name has ``scope`` as one of its parts; a transform may wrap it
    (``vmap(fwd_bwd)``, ``transpose(jvp(...))``)."""
    rx = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
    return sum(s for name, s in trace.op_names.items()
               if rx.search(scopes.get(name, "")))


STEP_SCOPES = ("fwd_bwd", "strategy", "optimizer", "outer")


def device_seconds_by_scope(path: str,
                            scopes=STEP_SCOPES) -> Dict[str, float]:
    """Chip 0's device seconds under each of the step program's scopes
    (``optimizer`` and ``outer`` lie inside ``strategy``), under none,
    and in all operations."""
    summary = xplane.reduce_events(xplane.read_planes(path))
    if summary is None:
        return {}
    names = op_scopes(path)
    out = {sc: scope_seconds(summary, names, sc) for sc in scopes}
    out["all_ops"] = sum(summary.op_names.values())
    out["no_scope"] = sum(s for n, s in summary.op_names.items()
                          if not names.get(n))
    return out


def attention_kernel_roofline(facts, head: str, backward: bool):
    """Share of its roofline, in percent, of the attention kernel whose
    custom calls' names hold ``head`` (``attn_fwd``, ``attn_bwd``: the
    program names its Pallas kernels): the least time the chip could take
    for the traced steps' forward (or, ``backward``, backward: all of
    attention's operations and bytes less the forward's) over those
    calls' device time. None where the trace has no such name."""
    from perfbench import flops
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step = trace.main_module_step()
    seconds = trace.custom_call_seconds(head=head)
    if not step or not seconds:
        return None
    sizes, seq = facts["sizes"], facts["sizes"]["n_positions"]
    # steps in the traced window: its length over the step's period
    rows = facts["rows_per_step_per_chip"] * trace.window_s / step[2]
    work = flops.attention_flops(sizes, rows, seq, backward=False)
    moved = flops.attention_bytes(sizes, rows, seq, backward=False)
    if backward:
        work = flops.attention_flops(sizes, rows, seq) - work
        moved = flops.attention_bytes(sizes, rows, seq) - moved
    least, _bound = flops.roofline_seconds(
        work, moved, flops.peaks(facts["device_kind"]))
    return 100.0 * least / seconds


def scope_ms_per_step(facts, scope: str):
    """Device ms a step (chip 0) of the operations traced under
    ``scope``, from the trace the run has just written. None where the
    operations carry none of the step program's scopes."""
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step, path = trace.main_module_step(), newest_xplane()
    if not step or not path:
        return None
    names = op_scopes(path)
    # a step program without the scopes (the parent's) has nothing to
    # read; one with them reads 0 where nothing runs under this scope
    if not any(scope_seconds(trace, names, sc) for sc in STEP_SCOPES):
        return None
    return (1e3 * scope_seconds(trace, names, scope) * step[2]
            / trace.window_s)


def main(argv) -> int:
    path = xplane.find_xplane(argv[0]) if argv else newest_xplane()
    if not path:
        print("no xplane.pb found", file=sys.stderr)
        return 1
    gaps, stretch = idle_intervals(path)
    by_span = idle_by_span(path)
    idle = sum(hi - lo for lo, hi in gaps) * 1e-9
    print(json.dumps({
        "xplane": path, "stretch_s": stretch * 1e-9, "idle_s": idle,
        "idle_in_spans_share": (1 - by_span.get("outside_spans", 0.0) / idle
                                if idle else None),
        "idle_by_span": dict(sorted(by_span.items(),
                                    key=lambda kv: -kv[1])),
        "spans_in_trace": len(program_spans(path)),
        "device_s_by_scope": device_seconds_by_scope(path)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
