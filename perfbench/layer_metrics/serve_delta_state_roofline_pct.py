"""Layer: Kernels. The delta layers' state pass as a share of its
roofline in decode steps: the least time the chip could take to read and
to write once the state and the kept convolution inputs the program's
counter says the live rows held, with their q, k, v and gates in and o
out, and to take the decay's, the reads' and the correction's
multiply-adds (``perfbench/flops_delta.py``), the larger of the two, over
the device time a step under ``attn.delta.state`` and
``attn.delta.conv`` (the operations that touch those bytes; it counts the
same bytes whatever implements the pass). Moves ``serve_tokens_per_s``."""
from perfbench import flops, flops_delta


def read(facts):
    ms = flops_delta.ms_per_step(facts, flops_delta.STATE_SCOPES)
    c = flops_delta.counted(facts)
    if not ms or c is None or not c["state_bytes"]:
        return None
    state, rows = c["state_bytes"] / c["steps"], c["rows"] / c["steps"]
    least, _bound = flops.roofline_seconds(
        flops_delta.state_pass_flops(state),
        flops_delta.state_pass_bytes(facts["sizes"], state, rows),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
