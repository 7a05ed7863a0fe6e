"""Layer: Serving engine. MiB the engine read back from the device a
decode step (the ``[slots, vocabulary]`` f32 logits and the small arrays
beside them): ``EngineStats.readback_bytes`` over ``decode_steps``,
between the window's first and last ``/stats`` samples. A count: it
repeats exactly. Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples") or []
    if len(samples) < 2 or "readback_bytes" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    steps = last["decode_steps"] - first["decode_steps"]
    if not steps:
        return None
    return (last["readback_bytes"] - first["readback_bytes"]) / steps / 2 ** 20
