"""Operations and bytes from shapes, and the table of peaks. Kept with the
benchmark so that no PR that claims a gain can change what a utilisation
or a roofline share is measured against.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in perfbench/"
            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    four block projections of each layer and the tied output head. The
    embedding lookups and the position table multiply nothing."""
    c, layers, vocab = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    per_layer = c * 3 * c + c * c + c * 4 * c + 4 * c * c      # 12 c^2
    return layers * per_layer + vocab * c


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward plus backward floating-point operations per trained token at
    sequence length ``seq``: 6 per matrix-product parameter (2 forward, 4
    backward) and causal attention's two products (QK^T and PV), 2 flops a
    multiply-add, over the causal half of the square: forward
    ``2 * 2 * seq/2 * c`` per layer and token, backward twice that.
    Recomputation under remat is not counted."""
    c, layers = sizes["n_embd"], sizes["n_layer"]
    attn_fwd = layers * 2 * 2 * (seq / 2) * c
    return 6.0 * matmul_params(sizes) + 3.0 * attn_fwd


def attention_flops(sizes: dict, rows: int, seq: int,
                    backward: bool = True) -> float:
    """Causal attention's own operations for ``rows`` sequences of ``seq``
    tokens over all layers (QK^T and PV; backward: dQ, dK, dV, dP and the
    recomputed scores, 2.5 times forward as flash attention does it)."""
    c, layers = sizes["n_embd"], sizes["n_layer"]
    fwd = rows * layers * 2 * 2 * seq * (seq / 2) * c
    return fwd * (1 + 2.5) if backward else fwd


def attention_bytes(sizes: dict, rows: int, seq: int, elem_bytes: int = 2,
                    backward: bool = True) -> float:
    """The least bytes attention must move through HBM for ``rows``
    sequences over all layers: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV. Scores never
    leave the chip in a fused kernel; the per-row softmax statistics
    (4 bytes a head and token) are counted once each way."""
    c, layers, heads = sizes["n_embd"], sizes["n_layer"], sizes["n_head"]
    tensor = rows * seq * c * elem_bytes
    stats = rows * seq * heads * 4
    fwd = 4 * tensor + stats
    bwd = 8 * tensor + stats
    return layers * (fwd + (bwd if backward else 0))


def roofline_seconds(flops: float, bytes_: float, peak: dict):
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
