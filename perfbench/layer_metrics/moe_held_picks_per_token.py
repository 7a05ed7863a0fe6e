"""Layer: Expert layer. Token-picks that fell on the experts this chip
holds, a token and layer, over what uniform routing over ALL the
router's experts would give (``topk * held / routed``): 1.0 at uniform
routing, ``routed / held`` if the router only chose among the held.
From the program's counters. Moves ``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    c = model_spans.counted(facts)
    if c is None or not c["tokens"].sum():
        return None
    sizes = facts["sizes"]
    lo, hi = sizes["held_experts"]
    uniform = (sizes["num_experts_per_tok"] * (hi - lo)
               / sizes["num_experts_routed"])
    return c["picks"].sum() / c["tokens"].sum() / uniform
