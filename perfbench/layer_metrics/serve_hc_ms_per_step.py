"""Layer: Residual path. Device ms a decode step of the hyper-connections:
the decode program's operations under ``hc.coef`` (the norm of the
flattened streams, ``u Phi``, the two sigmoids), ``hc.sinkhorn`` (clip,
``exp``, the 20 iterations) and ``hc.mix`` (the read ``h``, the write
``X'``), twelve sub-layers a step, from the trace
(``perfbench/flops_xing4.py``; a kernel by its name). Lower is better.
Moves ``serve_tokens_per_s``."""
from perfbench import flops_xing4


def read(facts):
    return flops_xing4.hc_decode_ms_per_step(facts)
