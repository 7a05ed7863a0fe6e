"""The second clock of the program's spans: since PR 37 a row of the span
totals on ``/stats`` is ``[count, total_s, max_s, cpu_s]`` (``cpu_s``: what
the span's thread ran inside it, by ``time.thread_time_ns``) and ``/stats``
carries ``process_cpu_s`` (``time.process_time()``: every thread of the
server's process). This file turns two of the window's samples into the
four quantities the ``serve_*_cpu_ms_per_round`` and
``serve_driver_blocked_ms_per_round`` readers divide by the rounds:

* the driver thread's CPU: ``cpu_s`` of ``serve.round`` and ``serve.tick``,
  which cover its loop from one round's start to the next's;
* the driver thread's blocked time: wall less CPU of those two, less the
  same difference of the two read-back spans, in which it waits for the
  device by design. What is left it stood off the CPU for something else:
  the interpreter lock, the scheduler's locks, the OS, a dispatch held
  inside the runtime. One clock cannot tell these apart;
* the handler threads' CPU: ``cpu_s`` of ``http.generate``, all that a
  handler thread ran for a request (parsing, submitting, every
  server-sent event, every wake-up in between). The span closes with the
  request, so a window's delta holds the requests that ended in it: in a
  closed loop what straddles one edge stands for what straddles the other;
* the process's CPU, for everything else by subtraction.

The samples are chosen by ``spans.stats_span_deltas``'s rule (the first one
after the round count moved, and the window's last). A program whose rows
have three entries (the parent of PR 37) gives None.
"""

from __future__ import annotations

from typing import Dict, Optional

DRIVER = ("serve.round", "serve.tick")
DEVICE_WAITS = ("serve.decode.readback", "serve.prefill.readback")
HANDLERS = ("http.generate",)


def window_samples(facts):
    """``(first, last)`` of the window's ``/stats`` samples that hold whole
    rounds, or None: ``spans.stats_span_deltas`` says why these two."""
    samples = [s for s in facts.get("stats_samples") or [] if "spans" in s]
    if len(samples) < 3:
        return None

    def rounds(sample):
        return sample["spans"].get("serve.round", (0,))[0]

    first = next((s for s in samples if rounds(s) > rounds(samples[0])),
                 None)
    if first is None or first is samples[-1]:
        return None
    return first, samples[-1]


def _deltas(first, last) -> Optional[Dict[str, tuple]]:
    first, last = first["spans"], last["spans"]
    if any(len(row) < 4 for row in last.values()):
        return None
    zero = (0, 0.0, 0.0, 0.0)
    return {name: (row[0] - first.get(name, zero)[0],
                   row[1] - first.get(name, zero)[1],
                   row[3] - first.get(name, zero)[3])
            for name, row in last.items()}


def span_cpu_deltas(facts) -> Optional[Dict[str, tuple]]:
    """``{span: (count, wall_s, cpu_s)}`` between the two samples; None
    where there are none or a row has no fourth entry."""
    pair = window_samples(facts)
    return None if pair is None else _deltas(*pair)


def per_round(facts) -> Optional[Dict[str, Optional[float]]]:
    """Seconds a round of ``driver_cpu``, ``driver_wall``,
    ``driver_device_wait``, ``driver_blocked``, ``handler_cpu`` and, where
    ``/stats`` has ``process_cpu_s``, ``process_cpu`` (else None)."""
    pair = window_samples(facts)
    d = None if pair is None else _deltas(*pair)
    if not d or not d.get("serve.round", (0,))[0]:
        return None
    rounds = d["serve.round"][0]

    def total(names, col):
        return sum(d.get(n, (0, 0.0, 0.0))[col] for n in names)

    wall, cpu = total(DRIVER, 1), total(DRIVER, 2)
    waited = total(DEVICE_WAITS, 1) - total(DEVICE_WAITS, 2)
    first, last = pair
    process = (last["process_cpu_s"] - first["process_cpu_s"]
               if "process_cpu_s" in first and "process_cpu_s" in last
               else None)
    return {"driver_cpu": cpu / rounds, "driver_wall": wall / rounds,
            "driver_device_wait": waited / rounds,
            "driver_blocked": (wall - cpu - waited) / rounds,
            "handler_cpu": total(HANDLERS, 2) / rounds,
            "process_cpu": None if process is None else process / rounds}
