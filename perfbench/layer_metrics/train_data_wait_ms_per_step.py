"""Layer: Step program (host side). Mean time the fit loop waited for a
step's batch (``fit.data_wait`` spans: ``prefetcher.get()``, or drawing
and placing the batch where there is no prefetch thread), over the timed
fit's steps after the first; from the program's span recorder. Moves
``train_tokens_per_s``."""
from perfbench import spans


def read(facts):
    if facts.get("kind") != "fit":
        return None
    waits = [r.seconds for r in spans.fit_records("timed")
             if r.name == "fit.data_wait" and r.ids.get("step", 0) > 0]
    return 1e3 * sum(waits) / len(waits) if waits else None
