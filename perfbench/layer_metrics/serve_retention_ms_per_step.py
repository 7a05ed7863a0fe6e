"""Layer: Kernels. Device ms a decode step of the power retention: the
decode program's operations under ``attn.retention.*`` (the gates and
feature maps, the pass over the resident state: decay, rank-1 update and
the read for the group's queries) and any custom call named for the
state pass, from the trace (``perfbench/flops_retention.py``). Moves
``serve_tokens_per_s``."""
from perfbench import flops_retention


def read(facts):
    return flops_retention.ms_per_step(facts)
