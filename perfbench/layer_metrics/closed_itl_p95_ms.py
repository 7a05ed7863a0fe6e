"""Layer: Scheduler and HTTP. 95th percentile of the pooled gaps between
consecutive tokens of a stream at the client. A closed loop's reading:
recorded, never judged. Moves ``serve_tokens_per_s``."""
import statistics


def read(facts):
    gaps = facts.get("token_gaps_s")
    if not gaps or len(gaps) < 20:
        return None
    return 1e3 * statistics.quantiles(gaps, n=20)[-1]
