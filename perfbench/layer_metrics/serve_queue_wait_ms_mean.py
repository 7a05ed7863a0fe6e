"""Layer: Scheduler and HTTP. Mean time a request waited in the
scheduler's queue, from ``submit`` to the pop that hands it to the
prefill (``request.queue`` spans, written at admission), over the
requests admitted inside the window; from the span totals in the
window's ``/stats`` samples (``spans.stats_span_deltas``). In a closed loop of as many
clients as slots it is the wait for the round in flight to end. Moves
``serve_tokens_per_s``."""
from perfbench import spans


def read(facts):
    d = spans.stats_span_deltas(facts)
    if not d or not d.get("request.queue", (0, 0.0))[0]:
        return None
    count, seconds = d["request.queue"]
    return 1e3 * seconds / count
