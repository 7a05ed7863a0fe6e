"""Layer: Serving engine. Pages referenced by running requests over the
pool's pages, mean of the ``/stats`` samples taken through the window.
Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples")
    if not samples:
        return None
    fill = [s["kv_blocks_in_use"] / s["kv_pages"] for s in samples
            if s.get("kv_pages")]
    return 100.0 * sum(fill) / len(fill) if fill else None
