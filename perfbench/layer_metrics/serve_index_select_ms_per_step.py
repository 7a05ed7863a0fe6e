"""Layer: Kernels. Device ms a decode step of the learned index: the
decode program's operations under ``attn.index`` (the index scores of
every row against its resident index keys) and ``attn.select`` (the exact
selection of the keys kept), with a sort or top-k operation that carries
no scope booked to the selection by its shape
(``perfbench/flops_sparse.py``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_sparse


def read(facts):
    return flops_sparse.ms_per_step(facts, flops_sparse.INDEX_SCOPES)
