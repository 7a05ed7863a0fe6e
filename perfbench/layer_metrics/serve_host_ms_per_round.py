"""Layer: Scheduler and HTTP. The host's own time a scheduler round: the
``serve.round`` spans' seconds less the two read-back spans', in which
the host waits for the device, over the rounds, from the span totals in
the window's ``/stats`` samples (``spans.stats_span_deltas``). A dispatch
returns once the program is enqueued, so it counts as the host's. Moves
``serve_tokens_per_s``."""
from perfbench import spans


def read(facts):
    d = spans.stats_span_deltas(facts)
    if not d or not d.get("serve.round", (0, 0.0))[0]:
        return None
    rounds, seconds = d["serve.round"]
    waited = sum(d.get(name, (0, 0.0))[1] for name in (
        "serve.decode.readback", "serve.prefill.readback"))
    return 1e3 * (seconds - waited) / rounds
