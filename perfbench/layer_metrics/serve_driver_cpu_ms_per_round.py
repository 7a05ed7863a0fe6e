"""Layer: Scheduler and HTTP. The Python the driver thread really runs a
scheduler round: the thread-CPU time (``cpu_s``, the fourth entry of a
span-totals row) of ``serve.round`` and ``serve.tick``, which cover the
driver's loop from one round's start to the next's, over the rounds, from
the window's ``/stats`` samples (``span_cpu.per_round``). What
``serve_host_ms_per_round`` was meant to say before the read-back stopped
waiting. None on a program whose rows have three entries. Moves
``serve_tokens_per_s``."""
from perfbench import span_cpu


def read(facts):
    r = span_cpu.per_round(facts)
    return None if r is None else 1e3 * r["driver_cpu"]
