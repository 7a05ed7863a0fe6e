"""Layer: Serving engine. Of the positions the engine dispatched through
prefills (each suffix padded to its power-of-two bucket), the share the
prefill programs ran: a model that takes a bucket in passes through all
its layers skips the passes that hold only padding.
``EngineStats.prefill_tokens_run`` over ``prefill_tokens``, between the
window's first and last ``/stats`` samples; 100 for a model that runs its
buckets whole. Nothing to read on a program without the counter, or in a
window without a prefill. A count: it repeats exactly for the same
admissions. Moves ``serve_tokens_per_s``."""


def read(facts):
    samples = facts.get("stats_samples") or []
    if len(samples) < 2 or "prefill_tokens_run" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    padded = last["prefill_tokens"] - first["prefill_tokens"]
    if not padded:
        return None
    return 100.0 * (last["prefill_tokens_run"]
                    - first["prefill_tokens_run"]) / padded
