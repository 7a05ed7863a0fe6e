"""Layer: Expert layer (``models/moe.py:HeldExperts``). Device ms a decode
step under the three ``moe.*`` scopes (router, routed, shared) of the
decode program, from the trace (``perfbench/model_spans.py``). Moves
``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    return model_spans.scopes_ms_per_step(facts, model_spans.MOE_SCOPES)
