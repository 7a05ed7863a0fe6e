"""Layer: Scheduler and HTTP. Rows of the decode batch that carried a
request: tokens the engine emitted in decode steps over decode steps
times slots, from ``EngineStats`` deltas across the window (a prefill
emits its request's first token, which is taken off). Moves
``serve_tokens_per_s``."""


def read(facts):
    d = facts.get("stats_delta")
    if not d or not d.get("decode_steps") or not d.get("num_slots"):
        return None
    decoded = d["tokens_generated"] - d["prefills"]
    return 100.0 * decoded / (d["decode_steps"] * d["num_slots"])
