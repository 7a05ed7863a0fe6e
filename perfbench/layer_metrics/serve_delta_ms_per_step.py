"""Layer: Kernels. Device ms a decode step of the gated delta-rule
layers: the decode program's operations under ``attn.delta.*`` (the
projections, the convolution's step, the pass over the resident state:
decay, correction and the read for the query, the gated norm and the
output projection) and any custom call named for the state pass, from the
trace (``perfbench/flops_delta.py``). Moves ``serve_tokens_per_s``."""
from perfbench import flops_delta


def read(facts):
    return flops_delta.ms_per_step(facts)
