"""What the per-layer metrics of a served model read from a traced run:
the device seconds of the decode program's operations under the model's
scopes (``attn.window``, ``attn.full``, ``moe.router``, ``moe.routed``,
``moe.shared``, ``head``) and kernels, a decode step, and the sums of
what the model counted.

An operation belongs to a scope by its ``op_name`` in the trace's
metadata (``perfbench/spans.py:op_scopes``), to the decode program by
that path's head ``jit(decode)``. Two kinds of operation carry no scope:
XLA's own grouped-matmul kernel for ``ragged_dot`` (named
``ragged-dot-*``, its metadata rewritten by the compiler) and, on some
versions, a Pallas kernel; both are found by their names. A grouped
product is the decode step's where its first dimension is the step's
``num_slots * num_experts_per_tok`` token-picks (a prefill's is its
block of ``moe_chunk_rows``).

A program without the scopes or counters (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

import numpy as np

from perfbench import spans

MOE_SCOPES = ("moe.router", "moe.routed", "moe.shared")
ATTN_SCOPES = ("attn.window", "attn.full")
ATTN_KERNELS = ("paged_gqa_decode_window", "paged_gqa_decode_full")
_FIRST_DIM = re.compile(r" = \(?\w+\[(\d+)[,\]]")


def _scope_rx(scope: str):
    return re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")


def decode_runs(trace) -> int:
    """Decode steps the trace holds: runs of the program named
    ``*decode*``."""
    return sum(runs for name, (runs, _s) in trace.module_runs.items()
               if "decode" in name)


def decode_op_seconds(facts) -> Optional[Dict[str, float]]:
    """``{"moe.routed": seconds, ..., "kernel:<name>": seconds}`` of chip
    0's decode-program operations in the traced window, or None where
    the trace names none of the model's scopes."""
    trace = facts.get("trace")
    path = spans.newest_xplane() if trace is not None else None
    if facts.get("kind") != "closed" or not path:
        return None
    names = spans.op_scopes(path)
    pick_rows = (int(facts["traffic"]["num_slots"])
                 * int(facts["sizes"].get("num_experts_per_tok", 0)))
    out: Dict[str, float] = {}
    for op, seconds in trace.op_names.items():
        head = op.split(" = ")[0].lstrip("%")
        scope_path = names.get(op, "")
        for kernel in ATTN_KERNELS:
            if head.startswith(kernel):
                key = "kernel:" + kernel
                out[key] = out.get(key, 0.0) + seconds
        if head.startswith("ragged-dot"):
            m = _FIRST_DIM.search(op)
            if m and pick_rows and int(m.group(1)) == pick_rows:
                out["moe.routed"] = out.get("moe.routed", 0.0) + seconds
            continue
        if not scope_path.startswith("jit(decode)"):
            continue
        for scope in MOE_SCOPES + ATTN_SCOPES + ("head",):
            if _scope_rx(scope).search(scope_path):
                out[scope] = out.get(scope, 0.0) + seconds
                break
    found = [k for k in out if not k.startswith("kernel:")]
    return out if found else None


def decode_step_seconds(facts):
    """``(decode_op_seconds, decode steps in the trace)``, or ``(None,
    0)`` where there is nothing to read."""
    by_scope = decode_op_seconds(facts)
    return by_scope, (decode_runs(facts["trace"]) if by_scope else 0)


def kernel_seconds(by_scope) -> float:
    return sum(v for k, v in by_scope.items() if k.startswith("kernel:"))


def scopes_ms_per_step(facts, scopes) -> Optional[float]:
    """Device ms a decode step of the operations under ``scopes``."""
    by_scope, steps = decode_step_seconds(facts)
    if not steps:
        return None
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / steps


def attn_ms_per_step(facts) -> Optional[float]:
    """The two ``attn.*`` scopes a decode step; the kernels' own time
    where their operations carry no scope."""
    by_scope, steps = decode_step_seconds(facts)
    if not steps:
        return None
    scoped = sum(by_scope.get(s, 0.0) for s in ATTN_SCOPES)
    return 1e3 * max(scoped, kernel_seconds(by_scope)) / steps


def counted(facts) -> Optional[Dict[str, np.ndarray]]:
    """``{"picks": [layers, held], "hit": [layers], "tokens": [layers],
    "pages": [layers, 2], "steps": n}``: the window's sums of what the
    model counted in its decode steps, layer by layer."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = int(facts["sizes"]["num_hidden_layers"])
    try:
        return {
            "picks": np.asarray([raw[f"layers_{i}/mlp/picks"]
                                 for i in range(layers)], np.float64),
            "hit": np.asarray([raw[f"layers_{i}/mlp/hit"]
                               for i in range(layers)], np.float64),
            "tokens": np.asarray([raw[f"layers_{i}/mlp/tokens"]
                                  for i in range(layers)], np.float64),
            "pages": np.asarray([raw[f"layers_{i}/self_attn/pages"]
                                 for i in range(layers)], np.float64),
            "steps": float(steps)}
    except KeyError:
        return None


def main(argv) -> int:
    """``python3 -m perfbench.model_spans [trace-dir]``: the newest
    trace's operations by seconds with their scopes, and its programs:
    what the readers above match against."""
    import json
    import sys

    from perfbench import xplane
    path = xplane.find_xplane(argv[0]) if argv else spans.newest_xplane()
    if not path:
        print("no xplane.pb found", file=sys.stderr)
        return 1
    trace = xplane.reduce_events(xplane.read_planes(path))
    names = spans.op_scopes(path)
    top = sorted(trace.op_names.items(), key=lambda kv: -kv[1])[:60]
    print(json.dumps({
        "xplane": path, "window_s": trace.window_s, "busy_s": trace.busy_s,
        "modules": {k: list(v) for k, v in trace.module_runs.items()},
        "ops": [{"s": round(sec, 6), "op": op[:160],
                 "scope": names.get(op, "")[-160:]} for op, sec in top]},
        indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
