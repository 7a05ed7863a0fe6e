"""Bytes and operations the latent attention needs (a decode step's
absorbed attend over the live rows' pages of latents; a prefill's expanded
causal attend), from the configuration's sizes and from what the program
counted (``layers_<i>/self_attn/latent`` = [live positions, bytes of cache
they hold in this layer], summed over decode steps), and the device
seconds of the latent layer's operations from a traced run: what
``serve_latent_*`` are computed from.

The counts hold only what ANY implementation must do with latents that
are resident. A decode step reads each live position's cached row once
(the bytes are the program's own counter, so a program that keeps the
latent on fewer lanes or in another dtype is measured against what IT
holds) and takes, a head, one multiply-add a cached number for the score
(``kv_lora_rank + qk_rope_head_dim``) and one a latent number for the
weighted sum (``kv_lora_rank``). A prefill takes, a head and a pair of
positions of the causal half, one multiply-add a score lane
(``qk_nope_head_dim + qk_rope_head_dim``) and one a value lane
(``v_head_dim``). The queries in and the outputs out, the softmax, the
expansion of keys and values, a second read of a page: none of it is
counted, so a share cannot pass 100.

A program without the scopes or the counter (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from perfbench import model_spans, spans

DECODE_SCOPES = ("attn.latent.q", "attn.latent.kv", "attn.latent.attend",
                 "attn.latent.out")
ATTEND = "attn.latent.attend"
# a custom call (a Pallas kernel) carries no scope on some versions:
# found by its name, the decode step's and the prefill's
KERNELS = {"jit(decode)": "latent_paged_decode",
           "jit(prefill)": "latent_prefill"}
DECODE, PREFILL = "jit(decode)", "jit(prefill)"


def decode_attend_flops(sizes: dict, positions: float) -> float:
    """The absorbed attend's operations over ``positions`` live cached
    positions (summed over layers): a head, 2 a cached number scored and
    2 a latent number summed."""
    rank, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    return positions * 2.0 * sizes["num_attention_heads"] * (
        (rank + rope) + rank)


def prefill_attend_flops(sizes: dict, prompt_tokens_sq: float) -> float:
    """The expanded attend's operations for prompts whose squared lengths
    sum to ``prompt_tokens_sq``, over all layers: the causal half of the
    pairs, a head 2 a score lane and 2 a value lane."""
    lanes = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
             + sizes["v_head_dim"])
    return (int(sizes["num_hidden_layers"]) * 0.5 * prompt_tokens_sq
            * 2.0 * sizes["num_attention_heads"] * lanes)


def counted(facts) -> Optional[Dict[str, float]]:
    """``{"positions", "bytes", "steps", "layers"}``: the window's sums
    over layers of what the latent layers counted in decode steps (live
    cached positions; bytes of cache they held), and the decode steps."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = int(facts["sizes"]["num_hidden_layers"])
    try:
        latent = np.asarray([raw[f"layers_{i}/self_attn/latent"]
                             for i in range(layers)], np.float64)
    except KeyError:
        return None
    return {"positions": float(latent[:, 0].sum()),
            "bytes": float(latent[:, 1].sum()),
            "steps": float(steps), "layers": float(layers)}


def scope_seconds(facts, program: str, scopes) -> Optional[Dict[str, float]]:
    """``{"attn.latent.attend": s, ...}`` of chip 0's operations under
    ``scopes`` in ``program`` (``DECODE`` or ``PREFILL``) over the traced
    window, or None where the trace names none. The program's kernel, by
    its name, is booked to ``ATTEND``."""
    trace = facts.get("trace")
    path = spans.newest_xplane() if trace is not None else None
    if facts.get("kind") != "closed" or not path:
        return None
    names = spans.op_scopes(path)
    rx = {s: model_spans._scope_rx(s) for s in scopes}
    out: Dict[str, float] = {}
    for op, seconds in trace.op_names.items():
        scope_path = names.get(op, "")
        head = op.split(" = ")[0].lstrip("%")
        if head.startswith(KERNELS[program]):
            hit = ATTEND if ATTEND in scopes else None
        elif scope_path.startswith(program):
            hit = next((s for s in scopes if rx[s].search(scope_path)), None)
        else:
            hit = None
        if hit is not None:
            out[hit] = out.get(hit, 0.0) + seconds
    return out or None


def decode_ms_per_step(facts, scopes=DECODE_SCOPES) -> Optional[float]:
    """Device ms a decode step of the operations under ``scopes``."""
    by_scope = scope_seconds(facts, DECODE, scopes)
    steps = model_spans.decode_runs(facts["trace"]) if by_scope else 0
    if not steps:
        return None
    return 1e3 * sum(by_scope.values()) / steps


def prefill_attend_seconds(facts) -> Optional[float]:
    """Device seconds of the prefill programs' expanded attend in the
    traced stretch."""
    by_scope = scope_seconds(facts, PREFILL, (ATTEND,))
    return sum(by_scope.values()) if by_scope else None
