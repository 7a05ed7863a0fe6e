"""Layer: Serving engine. Host arrays the engine handed to a decode
dispatch (a mirror the host wrote since the step before: an admission, a
release, a quarantine; the state itself stays on the device):
``EngineStats.upload_arrays`` over ``decode_steps``, between the window's
first and last ``/stats`` samples. Nothing to read on a program without
the counter. A count: it repeats exactly. Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples") or []
    if len(samples) < 2 or "upload_arrays" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    steps = last["decode_steps"] - first["decode_steps"]
    if not steps:
        return None
    return (last["upload_arrays"] - first["upload_arrays"]) / steps
