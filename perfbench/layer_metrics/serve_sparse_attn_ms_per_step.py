"""Layer: Kernels. Device ms a decode step of the attend over the kept
keys: the decode program's operations under ``attn.sparse`` (the gather
of the kept keys and values where they lie, the two products, the
softmax), from the trace (``perfbench/flops_sparse.py``). Moves
``serve_tokens_per_s``."""
from perfbench import flops_sparse


def read(facts):
    return flops_sparse.ms_per_step(facts, flops_sparse.SPARSE_SCOPES)
