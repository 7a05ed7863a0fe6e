"""Layer: Serving engine. Device busy time in the trace over the tokens
that reached the clients while it was recorded. Moves
``serve_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "closed" or trace is None:
        return None
    tokens = facts.get("tokens_in_trace")
    return 1e3 * trace.busy_s / tokens if tokens else None
