"""Flash attention on TPU via Pallas.

The reference gets flash attention from torch
``F.scaled_dot_product_attention`` when available
(``example/nanogpt/nanogpt.py:78-87``). The TPU-native equivalent is a
Pallas kernel: blockwise online-softmax attention that never materializes
the [T, T] score matrix in HBM. We use JAX's bundled Pallas TPU kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``, fwd+bwd defined) and
fall back to the dense XLA path on CPU/GPU or for shapes the kernel does not
tile well (T < 128, unaligned head dims).

Attention dropout is not supported by the kernel (same situation as torch's
flash backend, which silently picks a different kernel when dropout > 0) —
we fall back to dense in that case too.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import dense_causal_attention

_log = logging.getLogger(__name__)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.cache
def _report(path: str, shape: tuple, dtype: str) -> None:
    """Log which implementation ``attn_impl='flash'`` resolved to, once
    per (path, shape, dtype) per process: off-TPU and untileable shapes
    take the dense XLA path by design, and that choice must be visible
    (``chip_smoke.py`` reads these records to assert the kernel ran)."""
    _log.info("attention path %s for q%s %s", path, shape, dtype)


def _flash_ok(q: jnp.ndarray) -> bool:
    t, d = q.shape[-2], q.shape[-1]
    # kernel tiles: sequence in ≥128 blocks, head_dim on 128 lanes
    return t >= 128 and t % 128 == 0 and d <= 256


def flash_causal_attention(
    q: jnp.ndarray,  # [B, H, T, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jnp.ndarray:
    use_dropout = dropout_rate > 0.0 and not deterministic
    if not _on_tpu() or use_dropout or not _flash_ok(q):
        _report("dense", q.shape, str(q.dtype))
        return dense_causal_attention(
            q, k, v, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            deterministic=deterministic,
        )
    from .fused_attention import fused_causal_attention, fused_supported
    if fused_supported(q):
        # whole-context fused kernel: fastest at the reference's shapes
        # (T ≤ 1024), probs never touch HBM in fwd or bwd
        _report("pallas_fused", q.shape, str(q.dtype))
        return fused_causal_attention(q, k, v)
    _report("pallas_flash", q.shape, str(q.dtype))
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention,
    )
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    t = q.shape[-2]
    # The kernel's default block sizes leave large factors on the table at
    # long context. Swept on v5e (B·H=24, D=64, fwd+bwd): bq=1024/bkv=2048
    # beats the defaults at every T — 11.7→8.0 ms (T=2048), 19.0→9.8
    # (4096), 27.5→9.3 (8192), 69.3→14.3 (16384), i.e. up to 4.8×.
    bq, bkv = min(1024, t), min(2048, t)
    bqb, bkb = min(512, t), min(1024, t)  # bwd kernels: tighter VMEM stack
    if q.shape[-1] > 64 or t % bq or t % bkv or t % bqb or t % bkb:
        # swept at head_dim 64 only; larger D scales the kernel's VMEM
        # tiles proportionally and could blow the scoped-VMEM stack where
        # the defaults compiled — don't extrapolate the tuning
        return flash_attention(q, k, v, causal=True, sm_scale=scale)
    bs = BlockSizes(
        block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
        block_q_major_dkv=bqb, block_k_major_dkv=bkb,
        block_q_dkv=bqb, block_k_dkv=bkb,
        block_q_dq=bqb, block_k_dq=bkb, block_k_major_dq=bkb,
    )
    return flash_attention(q, k, v, causal=True, sm_scale=scale,
                           block_sizes=bs)


def packed_flash_attention_or_none(q, k, v, n_head: int):
    """Packed-layout fast path: q/k/v [B, T, C] → output [B, T, C] with NO
    head transposes, via a fused Pallas kernel. Returns None when neither
    packed kernel is eligible (off-TPU, untileable T, dropout handled by
    the caller) so the caller can take the standard [B, H, T, D] path.
    This is THE dispatch point for packed eligibility — models must not
    re-implement the platform/shape checks.

    Measured alternative (rejected): a blocked-causal FA2 packed kernel
    (q in bq-row blocks, k-loop bounded by the diagonal) that skips ~45%
    of the score work. On the chip at GPT-2-base (T=1024, C=768) it loses
    to the per-head whole-context kernel — 6.4 it/s (bq=256) / 7.2 (512)
    vs 7.5 — because slicing 64-lane heads out of a 768-lane packed block
    costs more than the causal skip saves. The [B, H, T, D] fallback path
    below therefore stays the dispatch for shapes this packed kernel's
    VMEM gate rejects."""
    from .fused_attention import (fused_causal_attention_packed,
                                  packed_supported)
    if not _on_tpu() or not packed_supported(q, n_head):
        return None
    _report("pallas_packed", q.shape, str(q.dtype))
    return fused_causal_attention_packed(q, k, v, n_head)
