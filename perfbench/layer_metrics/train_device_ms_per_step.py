"""Layer: Step program. Device time of one run of the step program (the
program that took most device time in the trace), median over the runs
traced. Moves ``train_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "fit" or trace is None:
        return None
    step = trace.main_module_step()
    return 1e3 * step[1] if step else None
