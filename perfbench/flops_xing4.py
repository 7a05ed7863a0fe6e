"""Bytes and operations the residual path (hyper-connections) and the
whole expert layers of the ``xing4_0`` decoder need, from the
configuration's sizes and from what the program counted, and the device
seconds of the residual path's operations from a traced run: what
``serve_hc_*`` and ``serve_expert_weights_roofline_pct`` are computed
from.

The counts hold only what ANY implementation must do. A sub-layer's
hyper-connection reads the live rows' streams once and writes them once
(``2 x rows x hc_mult x hidden x 4 B``: the streams are float32; the
program's counter ``layers_<i>/hc/rows`` = [rows mixed, sub-layers],
summed over decode steps, the rows already summed over the layer's
sub-layers) and reads the sub-layer's ``Phi`` once. The sub-layer's input
``h`` out and its output ``y`` in, a second pass over the streams for the
norm or the products, the coefficients themselves: none of it is
counted, so a share cannot pass 100. An expert layer reads, whole, every
routed expert that a pick of the step hit (``layers_<i>/mlp/hit``), the
shared expert and the router; a layer that counts no picks (a leading
dense layer) has no expert branch and adds nothing.

A program without the scopes or the counters (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from perfbench import flops_moe, model_spans, spans

HC_SCOPES = ("hc.coef", "hc.sinkhorn", "hc.mix")
# a kernel for the residual path, should one be written, carries no scope
# on some versions: found by its name
HC_KERNEL = "hyper_connection"
DECODE, PREFILL = "jit(decode)", "jit(prefill)"
STREAM_BYTES = 4        # the streams and Phi are float32


def hc_scope_seconds(op_names: Dict[str, float], names: Dict[str, str],
                     program: str) -> Dict[str, float]:
    """``{"hc.coef": seconds, ...}`` of ``program``'s operations
    (``op_names``: seconds by operation, ``names``: its scope path) under
    each of ``HC_SCOPES``; a kernel, by its name, is booked to ``hc.mix``."""
    rx = {scope: model_spans._scope_rx(scope) for scope in HC_SCOPES}
    out: Dict[str, float] = {}
    for op, seconds in op_names.items():
        path = names.get(op, "")
        if not path.startswith(program):
            continue
        if op.split(" = ")[0].lstrip("%").startswith(HC_KERNEL):
            hit = HC_SCOPES[-1]
        else:
            hit = next((s for s, r in rx.items() if r.search(path)), None)
        if hit is not None:
            out[hit] = out.get(hit, 0.0) + seconds
    return out


def hc_seconds(facts, program: str) -> Optional[float]:
    """Device seconds of chip 0's operations under ``hc.*`` in
    ``program`` (``DECODE`` or ``PREFILL``) over the traced window, or
    None where the trace names none."""
    trace = facts.get("trace")
    path = spans.newest_xplane() if trace is not None else None
    if facts.get("kind") != "closed" or not path:
        return None
    found = hc_scope_seconds(trace.op_names, spans.op_scopes(path), program)
    return sum(found.values()) if found else None


def hc_decode_ms_per_step(facts) -> Optional[float]:
    seconds = hc_seconds(facts, DECODE)
    steps = model_spans.decode_runs(facts["trace"]) if seconds else 0
    return 1e3 * seconds / steps if steps else None


def hc_counted(facts) -> Optional[Dict[str, float]]:
    """``{"rows", "sub_layers", "steps"}``: the window's sums over layers
    of what the hyper-connections counted in decode steps."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = int(facts["sizes"]["num_hidden_layers"])
    try:
        rows = np.asarray([raw[f"layers_{i}/hc/rows"]
                           for i in range(layers)], np.float64)
    except KeyError:
        return None
    return {"rows": float(rows[:, 0].sum()),
            "sub_layers": float(rows[:, 1].sum()), "steps": float(steps)}


def hc_bytes(sizes: dict, rows: float, sub_layers: float) -> float:
    """The least bytes ``sub_layers`` hyper-connections move for ``rows``
    rows mixed in all: the streams in and out, ``Phi`` once each."""
    n, c = int(sizes["hc_mult"]), int(sizes["hidden_size"])
    return STREAM_BYTES * (2.0 * rows * n * c
                           + sub_layers * n * c * n * (n + 2))


def hc_flops(sizes: dict, rows: float) -> float:
    """``u Phi`` and the two mixes, 2 a multiply-add."""
    n, c = int(sizes["hc_mult"]), int(sizes["hidden_size"])
    return 2.0 * rows * n * c * (n * (n + 2) + 1 + n + 1)


def expert_params(sizes: dict) -> int:
    """One gated expert of ``moe_intermediate_size``."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_layers_counted(facts) -> Optional[Dict[str, float]]:
    """``{"hit", "picks", "tokens", "layers", "steps"}``: the window's
    sums over the layers that count picks (a leading dense layer counts
    none) of the held experts a step's picks hit, the picks on held
    experts and the live rows."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    held = [i for i in range(int(facts["sizes"]["num_hidden_layers"]))
            if f"layers_{i}/mlp/hit" in raw]
    if not held:
        return None
    return {"hit": float(sum(raw[f"layers_{i}/mlp/hit"] for i in held)),
            "picks": float(sum(np.sum(raw[f"layers_{i}/mlp/picks"])
                               for i in held)),
            "tokens": float(sum(raw[f"layers_{i}/mlp/tokens"]
                                for i in held)),
            "layers": float(len(held)), "steps": float(steps)}


def _every_token_params(sizes: dict) -> int:
    """What an expert layer runs for every row: the shared experts and
    the router."""
    return (sizes["n_shared_experts"] * expert_params(sizes)
            + sizes["hidden_size"] * sizes["n_routed_experts_published"])


def expert_bytes(sizes: dict, hit: float, layers: float) -> float:
    """The least weight bytes the expert branches of ``layers`` layers
    read in a step whose picks hit ``hit`` experts over those layers:
    each of them whole, and a layer the shared experts and the router."""
    return flops_moe.elem_bytes(sizes) * (
        hit * expert_params(sizes) + layers * _every_token_params(sizes))


def expert_flops(sizes: dict, picks: float, tokens: float) -> float:
    """Their operations: ``picks`` token-picks on held experts, and every
    one of ``tokens`` rows (summed over the layers) through the shared
    experts and the router, 2 a multiply-add."""
    return 2.0 * (picks * expert_params(sizes)
                  + tokens * _every_token_params(sizes))


def main(argv) -> int:
    """``python3 -m perfbench.flops_xing4 [trace-dir]``: the newest
    trace's device seconds under each ``hc.*`` scope, in the decode and in
    the prefill programs, and those programs' runs (``perfbench
    .model_spans`` prints a trace's sixty largest operations, and a
    hyper-connection's are among its smallest)."""
    import json
    import sys

    from perfbench import xplane
    path = xplane.find_xplane(argv[0]) if argv else spans.newest_xplane()
    if not path:
        print("no xplane.pb found", file=sys.stderr)
        return 1
    trace = xplane.reduce_events(xplane.read_planes(path))
    names = spans.op_scopes(path)
    print(json.dumps({
        "xplane": path,
        "modules": {k: list(v) for k, v in trace.module_runs.items()},
        "decode": hc_scope_seconds(trace.op_names, names, DECODE),
        "prefill": hc_scope_seconds(trace.op_names, names, PREFILL)},
        indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
