"""Layer: Kernels. The sparse attend's share of its roofline in decode
steps: the least time the chip could take to read the kept keys and
values once (the program's counter, 2,048 B a kept position), the rows'
queries in and outputs out, and to take the two products
(``perfbench/flops_sparse.py``), the larger of the two, over the device
time a step under ``attn.sparse``. Moves ``serve_tokens_per_s``."""
from perfbench import flops, flops_sparse


def read(facts):
    ms = flops_sparse.ms_per_step(facts, flops_sparse.SPARSE_SCOPES)
    c = flops_sparse.counted(facts)
    if not ms or c is None:
        return None
    sizes = facts["sizes"]
    kept, rows = c["kept"] / c["steps"], c["rows"] / c["steps"]
    least, _bound = flops.roofline_seconds(
        flops_sparse.sparse_attn_flops(sizes, kept),
        flops_sparse.sparse_attn_bytes(sizes, kept, rows),
        flops.peaks(facts["device_kind"]))
    return 100.0 * least / (ms * 1e-3)
