"""gym_tpu.serve — continuous-batching inference over the KV-cache decode
path (the fifth subsystem, alongside ``data/``, ``strategy/``, ``sim/``
and ``utils/``).

``generate_fast`` (``models/nanogpt.py``) made single-request decode fast
but left it fixed-shape (one compile per exact ``(batch, prompt_len,
max_new_tokens)`` signature) with no request layer. This package is the
path from a trained ``fit()`` run dir to tokens-per-second under
concurrent load:

- ``engine``: fixed-capacity slot batch over one paged KV cache, ONE
  jitted decode step shared by every request (per-slot cursors/masks
  and vectorized per-slot sampling params), and prefill
  bucketed to powers of two so total compilations are bounded by
  ``O(log block_size)`` instead of one per prompt length. Requests enter
  free slots and leave on EOS/max-tokens BETWEEN decode steps —
  continuous batching, no drain-the-batch barrier — and one decode step
  is always in flight: the scheduler's round queues step K before it
  reads step K-1, and admissions ride the device's queue. The KV cache is a
  shared PAGE POOL with per-slot block tables, a
  ref-counted allocator and a prefix hash table: block-aligned shared
  prompt prefixes are prefilled once and reused copy-free across
  requests, and ``spec_tokens=γ`` adds self-drafting speculative
  decoding whose token streams are EXACTLY the non-speculative ones.
- ``scheduler``: FCFS request queue, slot assignment, and a
  backpressure-bounded submit/poll API — with per-request deadlines
  (queued requests past deadline shed before prefill, running ones
  cancelled at chunk boundaries), EWMA-based admission control
  (infeasible deadlines rejected typed before they are enqueued) and
  prefix-aware admit ordering over a bounded lookahead window.
- ``supervisor``: self-healing driver loop — every dispatch runs under a
  watchdog; an engine crash or wedge fails in-flight requests typed,
  rebuilds the engine warm (global program LRUs) and resumes the queue.
- ``router``: the FLEET tier — N replica stacks behind health-aware
  least-loaded + prefix-cache-affine dispatch, transparent failover of
  in-flight requests onto a sibling under their remaining deadline when
  a replica dies, and rolling zero-downtime weight hot-swap
  (``Router.reload``) so a trainer's newest checkpoint enters the fleet
  without dropping a request or recompiling a program.
- ``load``: params-only checkpoint restore — a ``fit(save_dir=...)`` run
  dir serves directly, no optimizer-state template needed.
- ``metrics``: per-request TTFT / per-token latency and engine
  tokens/s / queue depth / slot occupancy, logged CSVLogger-style to
  ``serve.csv``.
- ``wire`` / ``worker`` / ``autoscale``: the OUT-OF-PROCESS fleet tier
  (ISSUE 13) — each replica a real subprocess (its own GIL, its own
  failure domain) speaking a length-prefixed JSON frame protocol over a
  local socket (submit / streamed chunk / health / reload / stop), the
  router's ``ProcessRouter`` as a thin async dispatcher with the SAME
  failover semantics upgraded to streaming (mid-stream replica death
  splices the re-derived token stream byte-identically), and a
  load-adaptive autoscaler spawning/retiring replica processes from the
  per-replica tokens/s EWMAs and backlog.
- ``__main__``: ``python -m gym_tpu.serve --ckpt <run_dir>`` — a
  stdlib-HTTP entrypoint with graceful SIGTERM drain, token streaming
  (``"stream": true`` → chunked SSE, TTFB = first-token time), and
  ``--out-of-process`` / ``--autoscale`` for the process fleet.
"""

from .autoscale import (AutoscaleController, AutoscalePolicy,
                        Autoscaler)
from .engine import (BlockAllocator, EngineStats, InferenceEngine,
                     NoFreeBlocksError, SamplingParams)
from .load import CheckpointWatcher, load_for_serving
from .metrics import ReplicaMetrics, ServeMetrics
from .router import (FleetReloadError, FleetRequest,
                     NoHealthyReplicaError, ProcessReplica,
                     ProcessRouter, ProcRequest, Replica, Router,
                     WorkerSpawner, build_fleet, build_process_fleet)
from .scheduler import (AdmissionRejectedError, DeadlineExceededError,
                        EngineFailedError, QueueFullError, Request,
                        RequestCancelledError, RequestStatus, Scheduler,
                        SchedulerClosedError, SlotQuarantinedError)
from .supervisor import Supervisor

__all__ = [
    "InferenceEngine", "SamplingParams", "EngineStats",
    "BlockAllocator", "NoFreeBlocksError",
    "Scheduler", "Request", "RequestStatus", "QueueFullError",
    "SchedulerClosedError", "DeadlineExceededError",
    "AdmissionRejectedError", "EngineFailedError",
    "SlotQuarantinedError", "RequestCancelledError", "Supervisor",
    "Router", "Replica", "FleetRequest", "build_fleet",
    "NoHealthyReplicaError", "FleetReloadError",
    "ProcessRouter", "ProcessReplica", "ProcRequest", "WorkerSpawner",
    "build_process_fleet",
    "AutoscalePolicy", "AutoscaleController", "Autoscaler",
    "load_for_serving", "CheckpointWatcher",
    "ServeMetrics", "ReplicaMetrics",
]
