"""Layer: Expert layer. The fullest held expert's token-picks over the
held experts' mean, a layer, mean over the layers: 1.0 when the picks
spread evenly. From the program's counters. Moves
``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    c = model_spans.counted(facts)
    if c is None or not c["picks"].sum(axis=1).all():
        return None
    return float((c["picks"].max(axis=1) / c["picks"].mean(axis=1)).mean())
