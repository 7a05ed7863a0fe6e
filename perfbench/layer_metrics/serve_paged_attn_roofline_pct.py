"""Layer: Kernels. The grouped paged-attention kernel's share of its
roofline in decode steps: the least time the chip could take for the
pages a step had to read (keys and values, inside the window on window
layers: the program's counter) and for its two products
(``perfbench/flops_moe.py``), the larger of the two, over the device time
a step of the custom calls named ``paged_gqa_decode_*``. Moves
``serve_tokens_per_s``."""
from perfbench import flops, flops_moe, model_spans


def read(facts):
    by_scope, steps = model_spans.decode_step_seconds(facts)
    c = model_spans.counted(facts)
    seconds = model_spans.kernel_seconds(by_scope) if steps else 0.0
    if not seconds or c is None:
        return None
    sizes, page = facts["sizes"], int(facts["traffic"]["page_size"])
    pages = c["pages"][:, 0].sum() / c["steps"]
    rows = c["tokens"].sum() / c["steps"]
    least, _bound = flops.roofline_seconds(
        flops_moe.paged_attn_flops(sizes, pages, page),
        flops_moe.paged_attn_bytes(sizes, pages, page, rows),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (seconds / steps)
