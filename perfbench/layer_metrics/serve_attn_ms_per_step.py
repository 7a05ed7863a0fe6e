"""Layer: Kernels (``ops/paged_attention.py``). Device ms a decode step
under the two ``attn.*`` scopes (window and full layers' paged attends)
of the decode program, from the trace (``perfbench/model_spans.py``).
Moves ``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    return model_spans.attn_ms_per_step(facts)
