"""Layer: Serving engine. Median of all gaps between consecutive tokens
of a stream, all streams pooled, by arrival at the client: the length of
a scheduler round. Moves ``serve_tokens_per_s``."""
import statistics


def read(facts):
    gaps = facts.get("token_gaps_s")
    return 1e3 * statistics.median(gaps) if gaps else None
