"""Background AOT warmup: pay every compile OFF the request path.

The serving engine's compile set is bounded — the full power-of-two
prefill-bucket family (≤ ⌈log2(block_size)⌉ + 1 programs) plus the
paged-decode, copy-on-write and speculative programs — but a
cold server still pays each of those compiles on the first request that
needs it, which is exactly where p99 TTFT lives.  ``WarmupThread`` walks
the engine's complete ``ProgramDef`` family through the registry in a
low-priority daemon thread at server construction, so by the time
traffic arrives every program is already an executable (from the disk
tier, a deserialization; cold, a real compile — either way off-path).

Single-flight makes the race benign: a request that needs a program the
warmup hasn't reached yet builds it itself (or joins the in-progress
build); nothing is ever compiled twice.  Order is chosen for traffic:
decode family first (needed immediately after the first admit), then
prefill buckets smallest-first (short prompts are the common cold-start
case and small buckets compile fastest).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from .registry import ProgramDef, ProgramRegistry, default_registry


class WarmupThread(threading.Thread):
    """Daemon thread precompiling ``defs`` through ``registry``.  Query
    ``stats()`` for progress (``/stats`` exports it) or ``wait()`` to
    block until done (tests, a server that warms before it listens)."""

    def __init__(self, defs: List[ProgramDef],
                 registry: Optional[ProgramRegistry] = None,
                 log=None):
        super().__init__(daemon=True, name="gym-tpu-program-warmup")
        self._defs = list(defs)
        # NOT `registry or ...`: ProgramRegistry defines __len__, so an
        # EMPTY registry is falsy and would silently be swapped for the
        # process default
        self._registry = (registry if registry is not None
                          else default_registry())
        self._log = log
        # NOT named _stop: threading.Thread.join() calls self._stop()
        # as a METHOD internally (CPython _wait_for_tstate_lock), so
        # shadowing it with an Event breaks join with a TypeError
        self._stop_evt = threading.Event()
        self._done = threading.Event()
        self.warmed = 0
        self.seconds = 0.0

    def run(self) -> None:
        t0 = time.perf_counter()
        try:
            for d in self._defs:
                if self._stop_evt.is_set():
                    break
                self._registry.acquire(d, eager=True)
                self.warmed += 1
                # yield between compiles: warmup is the lowest-priority
                # work in the process — a request-path build waiting on
                # the compile lock should win the next slot
                time.sleep(0)
        except Exception as e:  # noqa: BLE001 — warmup must never kill
            if self._log is not None:  # the server it is warming
                self._log(f"gym_tpu.programs: warmup aborted after "
                          f"{self.warmed}/{len(self._defs)} programs "
                          f"({type(e).__name__}: {e})\n")
        finally:
            self.seconds = time.perf_counter() - t0
            self._done.set()
            if self._log is not None and not self._stop_evt.is_set():
                self._log(f"gym_tpu.programs: warmup — {self.warmed}/"
                          f"{len(self._defs)} programs ready in "
                          f"{self.seconds:.2f}s\n")

    def stop(self) -> None:
        self._stop_evt.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def stats(self) -> Dict[str, object]:
        return {"total": len(self._defs), "warmed": self.warmed,
                "done": self._done.is_set(),
                "seconds": round(self.seconds, 3)}


def warm_engine_programs(engine, registry: Optional[ProgramRegistry]
                         = None, *, start: bool = True,
                         log=None) -> WarmupThread:
    """Warmup thread over ``engine``'s full program family
    (``InferenceEngine.warmup_defs``) — the fleet/server construction
    hook."""
    t = WarmupThread(engine.warmup_defs(), registry=registry, log=log)
    if start:
        t.start()
    return t
