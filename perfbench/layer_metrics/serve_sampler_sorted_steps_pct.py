"""Layer: Serving engine. The share of decode steps whose sampler sorted
the vocabulary: ``sample_rows`` takes its two full-vocabulary sorts only
in a step in which a live row filters (``1 < top_k < V`` or
``top_p < 1``), one decision a step for the whole batch.
``EngineStats.sampler_sorted_steps`` over ``decode_steps``, between the
window's first and last ``/stats`` samples. Nothing to read on a program
without the counter (every step sorted there). A count: it repeats
exactly. Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples") or []
    if len(samples) < 2 or "sampler_sorted_steps" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    steps = last["decode_steps"] - first["decode_steps"]
    if not steps:
        return None
    return 100.0 * (last["sampler_sorted_steps"]
                    - first["sampler_sorted_steps"]) / steps
