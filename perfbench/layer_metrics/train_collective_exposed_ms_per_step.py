"""Layer: Strategy collectives. The part of a step's collective time
during which no other operation ran on that chip: what overlap would
buy. Moves ``train_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step = trace.main_module_step()
    if not step:
        return None
    return 1e3 * trace.collective_exposed_s * step[2] / trace.window_s
