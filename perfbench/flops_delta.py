"""Bytes and operations a decode step's gated delta rule needs (the pass
over the live rows' recurrent state and their kept convolution inputs) and
a prefill chunk's operations, from the configuration's sizes and from what
the program counted (``layers_<i>/linear_attn/state`` = [live rows, bytes
of state and convolution inputs they hold in this layer], summed over
decode steps), and the device seconds of the delta layers' operations
from a traced run: what ``serve_delta_*`` are computed from.

The counts hold only what ANY implementation must do with a state that
is resident: read each live row's state and kept inputs once and write
them once (the decay and the correction leave no entry as it was), take
each row's q, k, v and gates in and its output out, and the decay's, the
two reads' and the correction's multiply-adds. A second read of the state
for the query, a pass over blocks no live row owns, a layout change: none
of it is counted, so the share cannot pass 100. The bytes of state are
the program's own counter, so a program that keeps the state in another
dtype is measured against what IT holds.

A program without the scopes or the counter (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from perfbench import model_spans, spans

SCOPES = ("attn.delta.proj", "attn.delta.conv", "attn.delta.state",
          "attn.delta.chunks", "attn.delta.out")
# the scopes under which a decode step touches the bytes the counter
# counts: the state (decayed, corrected, read) and the kept inputs
# (shifted by one)
STATE_SCOPES = ("attn.delta.state", "attn.delta.conv")
# a custom call (a Pallas kernel) for the state pass carries no scope on
# some versions: found by its name
KERNELS = ("gated_delta_state",)
DECODE, PREFILL = "jit(decode)", "jit(prefill)"


def delta_layers(sizes: dict) -> list:
    every = int(sizes["full_attention_interval"])
    return [i for i in range(int(sizes["num_hidden_layers"]))
            if (i + 1) % every]


def qkvo_bytes(sizes: dict, rows: float) -> float:
    """A live row's queries, keys, values, the two gates in and the
    output out, one layer, float32 (``rows``: live rows summed over
    layers and steps)."""
    hv = sizes["linear_num_value_heads"]
    return rows * 4 * hv * (2 * sizes["linear_key_head_dim"]
                            + 2 * sizes["linear_value_head_dim"] + 2)


def state_pass_bytes(sizes: dict, state_bytes: float, rows: float) -> float:
    """The least bytes the pass moves: the live rows' state and kept
    inputs read once and written once, their q, k, v in and o out."""
    return 2.0 * state_bytes + qkvo_bytes(sizes, rows)


def state_pass_flops(state_bytes: float, state_elem_bytes: int = 4) -> float:
    """Its operations: for every entry of a live row's state the decay (a
    multiply), the read for the key and the read for the query (a
    multiply-add each) and the correction (a multiply-add): 7."""
    return (state_bytes / state_elem_bytes) * 7.0


def prefill_chunk_flops(sizes: dict, chunk: int = 64) -> float:
    """Operations of one chunk of ``chunk`` positions, one value head, in
    the chunked form (2 a multiply-add): the chunk's k k^T and q k^T, the
    triangular inverse as ``2 log2(chunk) - 2`` products of the chunk's
    size, W and U, the two products against the state, the product with
    ``v_new`` and the state's update."""
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    c = float(chunk)
    inverse = (2 * np.log2(c) - 2) * c ** 3
    return 2.0 * (2 * c * c * dk + inverse + c * c * (dk + dv)
                  + 2 * c * dk * dv + c * c * dv + c * dk * dv)


def counted(facts) -> Optional[Dict[str, float]]:
    """``{"rows", "state_bytes", "steps", "layers"}``: the window's sums
    over the delta layers of what they counted in decode steps (live
    rows; bytes of state and kept inputs they held), and the decode
    steps."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = delta_layers(facts["sizes"])
    try:
        state = np.asarray([raw[f"layers_{i}/linear_attn/state"]
                            for i in layers], np.float64)
    except KeyError:
        return None
    return {"rows": float(state[:, 0].sum()),
            "state_bytes": float(state[:, 1].sum()),
            "steps": float(steps), "layers": float(len(layers))}


def scope_seconds(facts, program: str) -> Optional[Dict[str, float]]:
    """``{"attn.delta.state": s, ...}`` of chip 0's operations under the
    delta layers' scopes in ``program`` (``DECODE`` or ``PREFILL``) over
    the traced window, or None where the trace names none. A custom call
    named for the state pass is the decode program's."""
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
            hit = "attn.delta.state"
        elif hit is not None and not scope_path.startswith(program):
            hit = None
        if hit is not None:
            out[hit] = out.get(hit, 0.0) + seconds
    return out or None


def ms_per_step(facts, scopes=SCOPES) -> Optional[float]:
    """Device ms a decode step of the operations under ``scopes`` of the
    delta layers'."""
    by_scope = scope_seconds(facts, DECODE)
    steps = model_spans.decode_runs(facts["trace"]) if by_scope else 0
    if not steps:
        return None
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / steps


def prefill_ms_per_ktoken(facts) -> Optional[float]:
    """Device ms of the prefill programs' operations under the delta
    layers' scopes a thousand prompt tokens of the admissions the traced
    stretch held."""
    by_scope = scope_seconds(facts, PREFILL)
    tokens = (facts.get("admit_spans_traced") or {}).get("prompt_tokens")
    if not by_scope or not tokens:
        return None
    return 1e6 * sum(by_scope.values()) / tokens
