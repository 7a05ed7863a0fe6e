"""Layer: Kernels (``ops/fused_attention.py``). The backward attention
kernel's share of its roofline: the least time the chip could take for
the traced steps' backward attention (dQ, dK, dV, dP and the recomputed
scores: 2.5 times the forward's operations, ``perfbench/flops.py``) over
the device time of the custom calls named ``attn_bwd*`` in the trace.
Moves ``train_tokens_per_s``."""
from perfbench import spans


def read(facts):
    return spans.attention_kernel_roofline(facts, "attn_bwd", backward=True)
