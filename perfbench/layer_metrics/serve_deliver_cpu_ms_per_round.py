"""Layer: Scheduler and HTTP. The Python the driver thread runs a round to
hand the round's tokens over to the threads that stream them: the
thread-CPU time (``cpu_s``, the fourth entry of a span-totals row) of
``serve.deliver`` (the read's events onto their requests, the sweep, the
resolutions and, under its leaf ``serve.wake``, the notifications), over
the rounds, between the window's ``/stats`` samples
(``span_cpu.span_cpu_deltas``). None on a program whose rows have three
entries. Moves ``serve_tokens_per_s``."""
from perfbench import span_cpu


def read(facts):
    d = span_cpu.span_cpu_deltas(facts)
    if not d or not d.get("serve.round", (0,))[0] or "serve.deliver" not in d:
        return None
    return 1e3 * d["serve.deliver"][2] / d["serve.round"][0]
