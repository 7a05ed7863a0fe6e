"""Layer: Kernels. The retention's share of its roofline in decode
steps: the least time the chip could take to read and to write once the
state the program's counter says the live rows held, with their q, k, v
in and y out, and to take the update's and the read's multiply-adds
(``perfbench/flops_retention.py``), the larger of the two, over the
device time a step under ``attn.retention.*``. Moves
``serve_tokens_per_s``."""
from perfbench import flops, flops_retention


def read(facts):
    ms = flops_retention.ms_per_step(facts)
    c = flops_retention.counted(facts)
    if not ms or c is None or not c["state_bytes"]:
        return None
    sizes = facts["sizes"]
    state, rows = c["state_bytes"] / c["steps"], c["rows"] / c["steps"]
    least, _bound = flops.roofline_seconds(
        flops_retention.state_pass_flops(sizes, state),
        flops_retention.state_pass_bytes(sizes, state, rows),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
