"""Layer: Scheduler and HTTP. Time a round the driver thread stood off the
CPU while the device was not what it waited for: wall less thread-CPU time
of ``serve.round`` and ``serve.tick``, less the same difference of
``serve.decode.readback`` and ``serve.prefill.readback`` (there it waits
for the device by design), over the rounds (``span_cpu.per_round``). It
holds the interpreter lock's hand-overs, the scheduler's own locks, the
OS's preemption and a dispatch blocked inside the runtime, which one clock
cannot tell apart. None on a program whose rows have three entries. Moves
``serve_tokens_per_s``."""
from perfbench import span_cpu


def read(facts):
    r = span_cpu.per_round(facts)
    return None if r is None else 1e3 * r["driver_blocked"]
