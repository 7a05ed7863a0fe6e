"""Layer: Strategy collectives. Device time in collective operations a
step (all-reduce, all-gather, reduce-scatter, permute), mean over chips.
Moves ``train_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step = trace.main_module_step()
    if not step:
        return None
    return 1e3 * trace.collective_s * step[2] / trace.window_s
