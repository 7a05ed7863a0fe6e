"""Layer: Kernels (``ops/fused_attention.py``). The forward attention
kernel's share of its roofline: the least time the chip could take for
the traced steps' forward attention (``perfbench/flops.py``) over the
device time of the custom calls named ``attn_fwd*`` in the trace. Under
remat the forward kernel runs twice a step and the needed work is counted
once, so the share reads at most half there. Moves
``train_tokens_per_s``."""
from perfbench import spans


def read(facts):
    return spans.attention_kernel_roofline(facts, "attn_fwd", backward=False)
