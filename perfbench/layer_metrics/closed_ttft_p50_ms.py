"""Layer: Scheduler and HTTP. Median time from sending a request to its
first token, by the client's clock, over the requests sent inside the
window. A closed loop's reading: recorded, never judged. Moves
``serve_tokens_per_s``."""
import statistics


def read(facts):
    ttft = facts.get("ttft_s")
    return 1e3 * statistics.median(ttft) if ttft else None
