"""Layer: Step program. Device ms a step (chip 0) in the operations
traced under the ``fwd_bwd`` scope: the forward and backward passes over
the microbatches, attention kernels and recomputation under remat
included. A fusion counts under the scope of its root operation. Moves
``train_tokens_per_s``."""
from perfbench import spans


def read(facts):
    return spans.scope_ms_per_step(facts, "fwd_bwd")
