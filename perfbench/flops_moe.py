"""Bytes and operations a decode step of the Cohere2-MoE decoder needs,
from its shapes and from what the program counted: what the two serving
rooflines of this model are computed from (``serve_moe_weight_roofline_pct``,
``serve_paged_attn_roofline_pct``).

``sizes`` is the configuration file (``config.json``'s keys as run).
Weights and cache are ``elem`` bytes an element (2: bfloat16).
"""

from __future__ import annotations

ELEM = {"bfloat16": 2, "float32": 4}


def elem_bytes(sizes: dict) -> int:
    return ELEM[sizes["dtype"]]


def expert_params(sizes: dict) -> int:
    """One gated expert: gate, up and down projections."""
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def moe_layer_bytes(sizes: dict, experts_hit: float) -> float:
    """The least weight bytes one layer's expert branch reads in a step:
    every held expert that any token-pick of the step hit, whole (a
    decode step has one or two picks an expert: each weight is read once
    for them all), the shared experts and the router. Activations (a few
    hundred rows of ``hidden_size``) are a thousandth of that and are
    left out, which keeps the share on the low side."""
    e = elem_bytes(sizes)
    return e * (experts_hit * expert_params(sizes)
                + sizes["num_shared_experts"] * expert_params(sizes)
                + sizes["hidden_size"] * sizes["num_experts_routed"])


def moe_layer_flops(sizes: dict, tokens: float, held_picks: float) -> float:
    """Operations of one layer's expert branch for ``tokens`` rows of
    which ``held_picks`` token-picks fell on held experts: 2 a
    multiply-add."""
    return 2.0 * (held_picks * expert_params(sizes)
                  + tokens * sizes["num_shared_experts"]
                  * expert_params(sizes)
                  + tokens * sizes["hidden_size"]
                  * sizes["num_experts_routed"])


def kv_row_bytes(sizes: dict) -> int:
    """One position of one layer's keys OR values in the pool."""
    return (sizes["num_key_value_heads"] * sizes["head_dim"]
            * elem_bytes(sizes))


def paged_attn_bytes(sizes: dict, pages: float, page_size: int,
                     rows: float) -> float:
    """The least bytes the paged attend moves for ``pages`` pages (keys
    and values, inside the window on window layers): the pages, and each
    row's queries in and output out."""
    qo = (2 * rows * sizes["num_attention_heads"] * sizes["head_dim"]
          * elem_bytes(sizes))
    return 2.0 * pages * page_size * kv_row_bytes(sizes) + qo


def paged_attn_flops(sizes: dict, pages: float, page_size: int) -> float:
    """Its two products (QK^T and PV) over the positions of those pages,
    every query head against its key-value head, 2 a multiply-add. A
    page's unseen tail (past the cursor, before the window) is counted:
    at most a page a row and layer of a few hundred."""
    return (2.0 * 2.0 * pages * page_size * sizes["num_attention_heads"]
            * sizes["head_dim"])
