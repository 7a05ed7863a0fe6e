"""Layer: Kernels. Of the chunks of keys the grouped paged-attention
kernel walked in the window's prefills (``paged_gqa_prefill_*``), the
share that took the body without masks: chunks wholly at or below a
query block's first position, wholly inside its last position's window
and wholly in copied pages. The rest are a block's edges (the diagonal,
a window's lower edge, the chunk that holds the row's end). From the
program's counters (``gqa_chunks``, which the engine adds a dispatched
prefill); nothing to read on a program without them, or in a window
without a prefill. A count: it repeats exactly for the same admissions.
Moves ``serve_tokens_per_s``."""


def read(facts):
    raw = facts.get("model_counters") or {}
    run = unmasked = 0
    for name, value in raw.items():
        if name.endswith("/gqa_chunks"):
            run, unmasked = run + value[0], unmasked + value[1]
    return 100.0 * unmasked / run if run else None
