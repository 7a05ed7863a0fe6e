"""Bytes and operations a decode step's power retention needs (the pass
over the live rows' recurrent state), from the configuration's sizes and
from what the program counted (``layers_<i>/self_attn/state`` = [live
rows, KiB of state they hold in this layer], summed over decode steps),
and the device seconds of the retention's operations from a traced run:
what ``serve_retention_*`` and ``serve_state_mib_per_row`` are computed
from.

The counts hold only what ANY implementation must do with a state that
is resident: read each live row's state once and write it once (the
decay and the rank-1 update leave no entry as it was), take each row's
q, k, v in and its y out, and the update's and the read's
multiply-adds. A second read of the state for the queries, a pass over
blocks no live row owns, feature maps written out and read back: none of
it is counted, so the share cannot pass 100. The bytes of state are the
program's own counter, so a program that keeps the state on fewer places
or in another dtype is measured against what IT holds.

A program without the scopes or the counter (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from perfbench import model_spans, spans

SCOPES = ("attn.retention.gate", "attn.retention.state",
          "attn.retention.chunk")
# a custom call (a Pallas kernel) for the state pass carries no scope on
# some versions: found by its name
KERNELS = ("retention_state",)
DECODE, PREFILL = "jit(decode)", "jit(prefill)"


def qkvy_bytes(sizes: dict, rows: float) -> float:
    """A live row's queries, key, value and gate in and output out, one
    layer, in the configuration's dtype (``rows``: live rows summed over
    layers and steps)."""
    elem = 2 if sizes.get("dtype") == "bfloat16" else 4
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return rows * ((2 * heads + 2 * kv) * sizes["head_dim"] * elem + kv * 4)


def state_pass_bytes(sizes: dict, state_bytes: float, rows: float) -> float:
    """The least bytes the pass moves: the live rows' state read once
    and written once, their q, k, v in and y out."""
    return 2.0 * state_bytes + qkvy_bytes(sizes, rows)


def state_pass_flops(sizes: dict, state_bytes: float,
                     state_elem_bytes: int = 4) -> float:
    """Its operations: for every entry of a live row's state the decay
    and the rank-1 update (a multiply and a multiply-add: 3) and one
    multiply-add a query head of the group that reads it (2 each)."""
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    return (state_bytes / state_elem_bytes) * (3.0 + 2.0 * group)


def counted(facts) -> Optional[Dict[str, float]]:
    """``{"rows", "state_bytes", "steps", "layers"}``: the window's sums
    over layers of what the retention layers counted in decode steps
    (live rows; bytes of state they held), and the decode steps."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = int(facts["sizes"]["num_hidden_layers"])
    try:
        state = np.asarray([raw[f"layers_{i}/self_attn/state"]
                            for i in range(layers)], np.float64)
    except KeyError:
        return None
    return {"rows": float(state[:, 0].sum()),
            "state_bytes": 1024.0 * float(state[:, 1].sum()),
            "steps": float(steps), "layers": float(layers)}


def scope_seconds(facts, program: str) -> Optional[Dict[str, float]]:
    """``{"attn.retention.state": s, ...}`` of chip 0's operations under
    the retention's scopes in ``program`` (``DECODE`` or ``PREFILL``)
    over the traced window, or None where the trace names none. A custom
    call named for the state pass is the decode program's (a prefill
    carries the state in float32 through ``jax.numpy``)."""
    trace = facts.get("trace")
    path = spans.newest_xplane() if trace is not None else None
    if facts.get("kind") != "closed" or not path:
        return None
    names = spans.op_scopes(path)
    rx = {s: model_spans._scope_rx(s) for s in SCOPES}
    out: Dict[str, float] = {}
    for op, seconds in trace.op_names.items():
        scope_path = names.get(op, "")
        head = op.split(" = ")[0].lstrip("%")
        hit = next((s for s in SCOPES if rx[s].search(scope_path)), None)
        if hit is None and head.startswith(KERNELS) and program == DECODE \
                and not scope_path.startswith(PREFILL):
            hit = "attn.retention.state"
        elif hit is not None and not scope_path.startswith(program):
            hit = None
        if hit is not None:
            out[hit] = out.get(hit, 0.0) + seconds
    return out or None


def ms_per_step(facts) -> Optional[float]:
    """Device ms a decode step of the operations under the retention's
    scopes."""
    by_scope = scope_seconds(facts, DECODE)
    steps = model_spans.decode_runs(facts["trace"]) if by_scope else 0
    if not steps:
        return None
    return 1e3 * sum(by_scope.values()) / steps


def prefill_ms_per_ktoken(facts) -> Optional[float]:
    """Device ms of the prefill programs' operations under the
    retention's scopes a thousand prompt tokens of the admissions the
    traced stretch held."""
    by_scope = scope_seconds(facts, PREFILL)
    tokens = (facts.get("admit_spans_traced") or {}).get("prompt_tokens")
    if not by_scope or not tokens:
        return None
    return 1e6 * sum(by_scope.values()) / tokens
