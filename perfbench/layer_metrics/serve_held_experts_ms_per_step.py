"""Layer: Expert layer (``models/moe.py:HeldExperts``). Device ms a decode
step of the expert layers: the decode program's operations under
``moe.router``, ``moe.routed`` and ``moe.shared`` and XLA's ``ragged-dot``
kernels of the step's token-picks, from the trace
(``perfbench/model_spans.py``): what ``serve_moe_ms_per_step`` reads, for
a cell whose leading layers are dense (they count nothing here) and
which that metric's list, held to the cell it came with, cannot take.
Moves ``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    return model_spans.scopes_ms_per_step(facts, model_spans.MOE_SCOPES)
