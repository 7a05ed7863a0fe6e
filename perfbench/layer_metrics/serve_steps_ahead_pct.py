"""Layer: Serving engine. The share of decode steps the engine dispatched
while the step before had not yet been read (the driver thread gave the
device its next step before it waited for the last one):
``EngineStats.steps_ahead`` over ``decode_steps``, between the window's
first and last ``/stats`` samples. Nothing to read on a program without
the counter. A count: it repeats exactly. Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples") or []
    if len(samples) < 2 or "steps_ahead" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    steps = last["decode_steps"] - first["decode_steps"]
    if not steps:
        return None
    return 100.0 * (last["steps_ahead"] - first["steps_ahead"]) / steps
