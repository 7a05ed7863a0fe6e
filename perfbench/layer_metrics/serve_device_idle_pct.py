"""Layer: Device. Share of the traced window in which no operation ran
on the chip. Moves ``serve_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "closed" or trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
