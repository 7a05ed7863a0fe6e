"""Layer: Serving engine. Share of the scheduler's rounds spent admitting
requests (``engine.admit``: the prefill and the read-back of its first
token, one request at a time between decode steps): the ``serve.admit``
spans' seconds over the ``serve.round`` spans', from the program's span
totals in the window's ``/stats`` samples (``spans.stats_span_deltas``). Moves
``serve_tokens_per_s``."""
from perfbench import spans


def read(facts):
    d = spans.stats_span_deltas(facts)
    if not d or not d.get("serve.round", (0, 0.0))[1]:
        return None
    return 100.0 * d.get("serve.admit", (0, 0.0))[1] / d["serve.round"][1]
