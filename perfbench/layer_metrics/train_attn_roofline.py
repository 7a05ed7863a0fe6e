"""Layer: Kernels (``ops/fused_attention.py``). The Pallas attention
kernels' share of their roofline: the least time the chip could take for
the traced steps' attention (the larger of operations over the bf16 peak
and least bytes over the HBM peak, both from ``perfbench/flops.py``) over
the device time of the attention custom calls in the trace. Which bound
sets it is logged by the harness. Moves ``train_tokens_per_s``."""
from perfbench import flops

# every Pallas call in the training step is an attention kernel (forward
# and backward); the trace names them by their custom-call target


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step = trace.main_module_step()
    seconds = trace.custom_call_seconds()
    if not step or not seconds:
        return None
    sizes, seq = facts["sizes"], facts["sizes"]["n_positions"]
    # steps in the traced window: its length over the step's period
    rows = facts["rows_per_step_per_chip"] * trace.window_s / step[2]
    least, _bound = flops.roofline_seconds(
        flops.attention_flops(sizes, rows, seq),
        flops.attention_bytes(sizes, rows, seq),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / seconds
