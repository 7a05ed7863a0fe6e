"""Layer: Kernels. The learned index's share of its roofline in decode
steps: the least time the chip could take to read the resident index
keys once (the program's counter, 128 B a key) and take the index heads'
products with them (``perfbench/flops_sparse.py``), the larger of the
two, over the device time a step under ``attn.index`` and
``attn.select``. The selection itself needs no bytes of its own and is
not in the numerator: what it costs lowers the share. Moves
``serve_tokens_per_s``."""
from perfbench import flops, flops_sparse


def read(facts):
    ms = flops_sparse.ms_per_step(facts, flops_sparse.INDEX_SCOPES)
    c = flops_sparse.counted(facts)
    if not ms or c is None:
        return None
    sizes, resident = facts["sizes"], c["resident"] / c["steps"]
    least, _bound = flops.roofline_seconds(
        flops_sparse.index_flops(sizes, resident),
        flops_sparse.index_bytes(sizes, resident),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
