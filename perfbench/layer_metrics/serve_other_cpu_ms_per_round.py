"""Layer: Scheduler and HTTP. CPU time a round of every other thread of the
server's process: the delta of ``/stats``' ``process_cpu_s``
(``time.process_time()``) less the driver's and the handlers' CPU
(``serve_driver_cpu_ms_per_round``, ``serve_handler_cpu_ms_per_round``),
over the rounds (``span_cpu.per_round``). In the benchmark that is the
closed kind's client threads, which live in the server's process, the
``/stats`` poll and the runtime's own threads. None on a program without
the counter. Moves ``serve_tokens_per_s``."""
from perfbench import span_cpu


def read(facts):
    r = span_cpu.per_round(facts)
    if r is None or r["process_cpu"] is None:
        return None
    return 1e3 * (r["process_cpu"] - r["driver_cpu"] - r["handler_cpu"])
