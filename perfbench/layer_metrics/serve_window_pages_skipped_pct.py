"""Layer: Kernels. Of the pages the running rows hold on the window
layers, the share the kernel neither copied nor scored because they lie
wholly before the window, over the window's decode steps. From the
program's counters. Moves ``serve_tokens_per_s``."""
from perfbench import model_spans


def read(facts):
    c = model_spans.counted(facts)
    if c is None:
        return None
    kinds = facts["sizes"]["layer_types"][:len(c["pages"])]
    window = [i for i, k in enumerate(kinds) if k == "sliding_attention"]
    held = c["pages"][window].sum()
    return 100.0 * c["pages"][window, 1].sum() / held if held else None
