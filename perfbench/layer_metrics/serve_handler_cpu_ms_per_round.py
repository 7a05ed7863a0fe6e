"""Layer: Scheduler and HTTP. What the HTTP handler threads run, under the
same interpreter lock as the driver thread, while a round lasts: the
thread-CPU time of ``http.generate`` (all that a handler thread runs for a
request: parsing, submitting, a server-sent event a token, the wake-ups in
``req.stream``) of the requests that ended in the window, over the rounds
(``span_cpu.per_round``). None on a program whose rows have three entries.
Moves ``serve_tokens_per_s``."""
from perfbench import span_cpu


def read(facts):
    r = span_cpu.per_round(facts)
    return None if r is None else 1e3 * r["handler_cpu"]
