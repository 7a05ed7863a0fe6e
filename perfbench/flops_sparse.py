"""Bytes and operations a decode step's learned sparse attention needs
(index scores, selection, the attend over the kept keys), from the
configuration's sizes and from what the program counted
(``layers_<i>/self_attn/keys`` = [kept, resident] over live rows), and
the device seconds of those parts from a traced run: what
``serve_index_select_*`` and ``serve_sparse_attn_*`` are computed from.

Both counts hold only what ANY implementation must do: read each resident
index key once and multiply it with the row's index queries; read each
kept key and value once and take the two products. Gathering a row's
whole table, writing scores out and reading them back, sorting: none of
it is counted, so neither roofline share can pass 100.

A program without the scopes or the counter (the parent of the PR that
brought them) gives ``None`` everywhere.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

import numpy as np

from perfbench import flops_moe, model_spans, spans

INDEX_SCOPES = ("attn.index", "attn.select")
SPARSE_SCOPES = ("attn.sparse",)
_SORTS = ("sort", "topk", "top-k", "top_k", "TopK")


def index_key_bytes(sizes: dict) -> int:
    """One position of one layer's index key in the pool."""
    return (sizes["sa_config"]["indexer_head_dim"]
            * flops_moe.elem_bytes(sizes))


def index_bytes(sizes: dict, resident: float) -> float:
    """The least bytes the index moves for ``resident`` (row, position)
    pairs summed over rows and layers: each resident index key once. The
    index queries and head weights (a few KB a row) are left out, which
    keeps the share on the low side."""
    return resident * index_key_bytes(sizes)


def index_flops(sizes: dict, resident: float) -> float:
    """``I[t, s]`` for those pairs: every index head's product with the
    key, 2 a multiply-add."""
    sa = sizes["sa_config"]
    return 2.0 * resident * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def sparse_attn_bytes(sizes: dict, kept: float, rows: float) -> float:
    """The least bytes the attend over the kept keys moves: each kept key
    and value once, each row's queries in and output out (``rows``: live
    rows summed over layers)."""
    qo = (2 * rows * sizes["num_attention_heads"] * sizes["head_dim"]
          * flops_moe.elem_bytes(sizes))
    return 2.0 * kept * flops_moe.kv_row_bytes(sizes) + qo


def sparse_attn_flops(sizes: dict, kept: float) -> float:
    """Its two products over the kept positions, every query head against
    its key-value head, 2 a multiply-add."""
    return (2.0 * 2.0 * kept * sizes["num_attention_heads"]
            * sizes["head_dim"])


def counted(facts) -> Optional[Dict[str, float]]:
    """``{"kept", "resident", "rows", "sparse_rows", "steps"}``: the
    window's sums over layers of what the attention layers counted in
    decode steps, and the decode steps."""
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    layers = int(facts["sizes"]["num_hidden_layers"])
    try:
        keys = np.asarray([raw[f"layers_{i}/self_attn/keys"]
                           for i in range(layers)], np.float64)
        past = np.asarray([raw[f"layers_{i}/self_attn/sparse_rows"]
                           for i in range(layers)], np.float64)
        rows = np.asarray([raw[f"layers_{i}/mlp/tokens"]
                           for i in range(layers)], np.float64)
    except KeyError:
        return None
    return {"kept": float(keys[:, 0].sum()),
            "resident": float(keys[:, 1].sum()), "rows": float(rows.sum()),
            "sparse_rows": float(past.sum()), "steps": float(steps)}


def _has_extent(op: str, extent: int) -> bool:
    return re.search(rf"[\[,]{extent}[\],]", op) is not None


def decode_scope_seconds(facts) -> Optional[Dict[str, float]]:
    """``{"attn.index": s, "attn.select": s, "attn.sparse": s}`` of chip
    0's decode-program operations in the traced window, or None where the
    trace names none of them. A sort or top-k operation that carries no
    scope is the selection's where one of its dimensions is the row's
    extent (``max_position_embeddings``; the sampler's sorts are as long
    as the vocabulary)."""
    trace = facts.get("trace")
    path = spans.newest_xplane() if trace is not None else None
    if facts.get("kind") != "closed" or not path:
        return None
    names = spans.op_scopes(path)
    extent = int(facts["sizes"].get("max_position_embeddings", 0))
    scopes = INDEX_SCOPES + SPARSE_SCOPES
    rx = {s: model_spans._scope_rx(s) for s in scopes}
    out: Dict[str, float] = {}
    for op, seconds in trace.op_names.items():
        scope_path = names.get(op, "")
        hit = next((s for s in scopes if rx[s].search(scope_path)), None)
        if hit is not None:
            if scope_path.startswith("jit(decode)"):
                out[hit] = out.get(hit, 0.0) + seconds
            continue
        head = op.split(" = ")[0].lstrip("%")
        if (head.startswith(_SORTS) and extent and _has_extent(op, extent)
                and not scope_path.startswith("jit(prefill)")):
            out["attn.select"] = out.get("attn.select", 0.0) + seconds
    return out or None


def ms_per_step(facts, scopes) -> Optional[float]:
    """Device ms a decode step of the operations under ``scopes``."""
    by_scope = decode_scope_seconds(facts)
    steps = model_spans.decode_runs(facts["trace"]) if by_scope else 0
    if not steps:
        return None
    return 1e3 * sum(by_scope.get(s, 0.0) for s in scopes) / steps
