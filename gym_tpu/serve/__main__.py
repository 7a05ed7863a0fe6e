"""``python -m gym_tpu.serve --ckpt <run_dir>`` — stdlib-HTTP serving.

No framework: ``http.server.ThreadingHTTPServer`` + the scheduler under
an engine ``Supervisor``. One driver thread runs the engine loop inside
a watchdog; handler threads submit and block on the request future.
Endpoints:

- ``POST /generate`` — JSON body with either ``prompt`` (a list of token
  ids) or ``text`` (char-level corpora only: encoded via the shakespeare
  ``CHAR_VOCAB``), plus optional ``max_new_tokens`` / ``temperature`` /
  ``top_k`` / ``top_p`` / ``eos_token`` / ``seed`` / ``deadline_s``.
  ``deadline_s`` (also settable per request via the ``X-Deadline-S``
  header; the body field wins) bounds the request end to end: admission
  control rejects it up front (HTTP 429 + ``Retry-After``) when the
  live tokens/s EWMA says the backlog cannot meet it; a queued request
  past deadline is shed before prefill and a running one cancelled at
  the next chunk boundary (HTTP 504, typed). Replies with the new
  ``tokens`` (and ``text`` when the vocab is char-level), TTFT and
  per-token latency.
- ``GET /stats`` (alias ``/healthz``) — engine + metrics headline JSON,
  including supervisor state (engine generation / restarts) and, with
  ``--replicas N``, the fleet view: per-replica health/EWMA/weights
  sections, ``failovers``, ``healthy_replicas``, ``weight_reloads``
  (rolling ROLLOUTS; the collector's ``engine_reloads`` counts
  per-replica engine swaps — one rollout × N replicas).
- ``POST /reload`` — zero-downtime weight hot-swap: re-reads the
  checkpoint run dir (optionally ``{"ckpt": ..., "step": ...}``) and
  rolls the new params through the replicas one at a time (drain →
  warm rebuild through the global program LRUs → re-admit) without
  dropping an in-flight request. ``--reload-watch S`` does the same
  automatically whenever the trainer commits a newer checkpoint.

``--replicas N`` runs N in-process engine+scheduler+supervisor stacks
behind the health-aware router (``serve/router.py``): least-loaded +
prefix-cache-affine dispatch, and a replica that dies mid-request has
the request transparently retried on a sibling under its remaining
deadline — the client sees 200, ``/stats`` sees ``failovers``.

Typed failure → status mapping (never a traceback-500 for a fault the
serving stack understands):

====================== ======================================
400                     malformed JSON / bad params / prompt
                        too long (typed ``ValueError`` body)
429 + ``Retry-After``   queue full, admission-control reject
503 + ``Retry-After``   shutting down, engine failed/rebuilt,
                        slot quarantined (NaN), injected IO
504                     deadline exceeded (shed or cancelled)
====================== ======================================

Shutdown drill (ISSUE 4 acceptance): SIGTERM/SIGINT triggers a graceful
drain — stop accepting, FAIL queued requests (typed, reported to their
waiting handlers, never dropped), ANSWER in-flight requests (the engine
keeps stepping until the running slots finish, bounded by
``--drain-deadline``), close the listener, flush ``serve.csv``, print a
final ``tokens_per_s`` headline, exit 0. A wedged drain dumps every
thread's stack (``utils.resilience.dump_thread_stacks``) instead of
hanging silently.

Chaos drill (ISSUE 5 acceptance, ``scripts/ci_chaos.sh``): with
``GYM_TPU_FAULTS=serve.decode:hang@…`` injected the supervisor abandons
the wedged driver, fails in-flight requests typed (503, inside their
deadline), rebuilds the engine warm and keeps serving — the HTTP server
never dies with its engine.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m gym_tpu.serve",
        description="Serve a trained gym_tpu checkpoint over HTTP "
                    "(continuous-batching KV-cache decode).")
    p.add_argument("--ckpt", required=True, metavar="RUN_DIR",
                   help="checkpoint run dir: fit(save_dir=...)/<run_name>")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest valid)")
    p.add_argument("--config", default=None, metavar="CONFIG_JSON",
                   help="explicit config.json (for run dirs predating the "
                        "in-dir snapshot: logs/<run_name>/config.json)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--num_slots", type=int, default=4,
                   help="concurrent decode slots (the batch width)")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the health-aware router "
                        "(fleet serving: failover + rolling weight "
                        "hot-swap need >= 2)")
    p.add_argument("--out-of-process", action="store_true",
                   help="run each replica as a worker SUBPROCESS over a "
                        "local socket (its own GIL, its own failure "
                        "domain) instead of an in-process thread stack; "
                        "responses can stream and a killed replica "
                        "process splices mid-stream onto a sibling")
    p.add_argument("--autoscale", action="store_true",
                   help="with --out-of-process: spawn/retire replica "
                        "processes from the live per-replica tokens/s "
                        "EWMAs and backlog (bounds: --min-replicas/"
                        "--max-replicas); also respawns killed workers")
    p.add_argument("--min-replicas", type=int, default=None,
                   help="autoscaler floor (default: --replicas)")
    p.add_argument("--max-replicas", type=int, default=None,
                   help="autoscaler ceiling (default: "
                        "max(--replicas, 4))")
    p.add_argument("--autoscale-interval", type=float, default=1.0,
                   help="autoscaler tick interval in seconds")
    p.add_argument("--worker-startup-timeout", type=float, default=240.0,
                   help="seconds to wait for spawned worker processes "
                        "to come healthy at startup")
    p.add_argument("--failover-retries", type=int, default=None,
                   help="per-request failover re-dispatch budget "
                        "(default: min(2, replicas-1) — a single "
                        "replica keeps the PR-5 typed-503 behavior)")
    p.add_argument("--reload-watch", type=float, default=0.0,
                   help="poll the checkpoint run dir every S seconds "
                        "and hot-swap newer checkpoints into the fleet "
                        "(0 = off; POST /reload always works)")
    p.add_argument("--decode_chunk", type=int, default=1,
                   help="decode steps fused per dispatch (chunk boundary "
                        "= deadline-cancellation granularity)")
    p.add_argument("--page_size", type=int, default=16,
                   help="KV cache page size in tokens, >= 1 (the page "
                        "pool is the only KV cache). A size that does "
                        "not divide the checkpoint's block_size falls "
                        "to its largest divisor not above it, and a "
                        "given --kv_pages is scaled to as many tokens")
    p.add_argument("--kv_pages", type=int, default=None,
                   help="physical pages in the paged KV pool (default: "
                        "null page + num_slots full windows; smaller "
                        "pools admit lazily as blocks free)")
    p.add_argument("--spec_tokens", type=int, default=0,
                   help="speculative decoding draft length γ (0 = "
                        "off). Token streams stay exactly equal to "
                        "non-speculative decoding")
    p.add_argument("--quant", choices=("int8", "int4"), default=None,
                   help="quantize the restored params at load: per-tile "
                        "int8/int4 + f32 scales (QuantizeCodec tiling), "
                        "dequant fused into the consuming matmuls. "
                        "Embedding/lm_head stay f32 unless "
                        "--quant-embed. Default: f32 (no quantization)")
    p.add_argument("--quant-embed", action="store_true",
                   help="with --quant: also quantize the tied "
                        "embedding/lm_head (they dominate quality — "
                        "gated separately)")
    p.add_argument("--kv-quant", choices=("int8",), default=None,
                   help="store the decode KV cache/page pools int8 with "
                        "per-(page-slot, head) f32 scales — the same "
                        "kv_pages budget holds 4x the resident KV "
                        "payload. Default: f32")
    p.add_argument("--max_queue", type=int, default=64,
                   help="FCFS queue bound (backpressure: submits beyond "
                        "it wait, then 429)")
    p.add_argument("--quotas", default=None, metavar="JSON",
                   help="per-SLO-class token-rate quotas as JSON, e.g. "
                        "'{\"batch\": {\"share\": 0.5}}' or "
                        "'{\"interactive\": {\"tokens_per_s\": 500}}' "
                        "(share = fraction of the live tokens/s EWMA; "
                        "exceeding the refill bucket -> 429 + "
                        "Retry-After). Default: no quotas — the "
                        "single-tenant behavior")
    p.add_argument("--preempt", action="store_true",
                   help="preemptible decode: park a low-priority "
                        "running request at a chunk boundary when a "
                        "strictly more urgent one is queued and no slot "
                        "is free; the parked stream resumes "
                        "byte-identical")
    p.add_argument("--request_timeout", type=float, default=600.0,
                   help="per-request wall-clock bound inside a handler")
    p.add_argument("--default-deadline", type=float, default=None,
                   help="deadline_s applied to requests that don't set "
                        "one (default: none)")
    p.add_argument("--dispatch-timeout", type=float,
                   default=float(os.environ.get(
                       "GYM_TPU_SERVE_WATCHDOG_S", 120.0)),
                   help="supervisor watchdog: a dispatch wedged past this "
                        "triggers engine failover (env "
                        "GYM_TPU_SERVE_WATCHDOG_S)")
    p.add_argument("--max-restarts", type=int, default=5,
                   help="engine rebuilds before the supervisor declares "
                        "the engine unrecoverable")
    p.add_argument("--drain-deadline", type=float, default=300.0,
                   help="SIGTERM: max seconds to finish in-flight "
                        "requests before failing them")
    p.add_argument("--metrics_dir", default=None,
                   help="serve.csv location (default: <RUN_DIR>/serve)")
    p.add_argument("--program-cache-dir", default=None,
                   help="place the device-program registry's persistent "
                        "executable tier (default: .jax_cache in the "
                        "checkout; JAX_COMPILATION_CACHE_DIR, where "
                        "set, wins): a restart against the same "
                        "config deserializes every program instead of "
                        "compiling — /stats programs_compiled stays 0")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the background AOT program warmup at "
                        "startup (cold requests then pay compiles "
                        "on-path — the pre-registry behavior)")
    p.add_argument("--device", default=None,
                   help="'cpu' pins the CPU backend")
    return p


@dataclasses.dataclass
class ServerHandle:
    """Everything a caller (main() or an in-process test) needs to drive
    and tear down one serving stack. ``scheduler``/``supervisor``/
    ``engine_factory`` are replica 0's (the pre-fleet surface, kept so
    single-replica callers and tests read exactly what they always
    did); ``router`` is the fleet."""

    httpd: ThreadingHTTPServer
    scheduler: Any
    supervisor: Any
    metrics: Any
    engine_factory: Any
    info: Dict[str, Any]
    router: Any = None
    warmup: Any = None
    autoscaler: Any = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def stop_warmup(self) -> None:
        """Stop AND join the background warmup before teardown: the
        warmup daemon thread may be inside an XLA compile/deserialize —
        interpreter teardown while C++ holds that thread aborts the
        process (SIGABRT after the clean-shutdown line; the ci_serve
        restart drill caught it). stop() bounds the wait to the one
        in-flight build. Shared by close() and main()'s SIGTERM drain
        so the invariant cannot drift between the two paths."""
        if self.warmup is not None:
            self.warmup.stop()
            self.warmup.join(timeout=120.0)

    def close(self, drain_deadline_s: float = 30.0) -> None:
        """Test-path teardown: stop every replica's driver, drain it
        (wedged replicas get their stacks dumped and their requests
        failed typed — handler threads blocked in result() must not pin
        server_close open), close sockets. Process fleets additionally
        stop the autoscaler first (no respawns during teardown) and
        reap every worker child."""
        self.stop_warmup()
        if self.autoscaler is not None:
            self.autoscaler.stop()
        self.router.close(drain_deadline_s=drain_deadline_s)
        self.httpd.shutdown()
        self.httpd.server_close()
        self.metrics.close()


def create_server(params, cfg, *, host: str = "127.0.0.1", port: int = 0,
                  num_slots: int = 4, decode_chunk: int = 1,
                  max_queue: int = 64, request_timeout: float = 600.0,
                  default_deadline: Optional[float] = None,
                  dispatch_timeout: float = 120.0, max_restarts: int = 5,
                  metrics_dir: Optional[str] = None,
                  info: Optional[Dict[str, Any]] = None,
                  stop_event: Optional[threading.Event] = None,
                  page_size: int = 16, kv_pages: Optional[int] = None,
                  spec_tokens: int = 0, replicas: int = 1,
                  failover_retries: Optional[int] = None,
                  reload_source: Optional[Any] = None,
                  warmup: bool = True,
                  program_cache_dir: Optional[str] = None,
                  out_of_process: bool = False,
                  autoscale: bool = False,
                  min_replicas: Optional[int] = None,
                  max_replicas: Optional[int] = None,
                  autoscale_policy: Optional[Any] = None,
                  autoscale_interval_s: float = 1.0,
                  fleet_dir: Optional[str] = None,
                  worker_startup_timeout_s: float = 240.0,
                  worker_env: Optional[Dict[str, str]] = None,
                  quotas: Optional[Dict[str, Any]] = None,
                  preempt: bool = False
                  ) -> ServerHandle:
    """Build the full serving stack — replica fleet (engines, schedulers,
    supervisors, router), metrics, HTTP server — WITHOUT entering
    ``serve_forever``. ``main`` and the in-process chaos tests share
    this path, so what the tests exercise is exactly what
    ``python -m gym_tpu.serve`` runs. ``port=0`` binds an ephemeral
    port (``handle.port`` reports it). ``reload_source(body) ->
    (params, weights_tag)`` supplies ``POST /reload``'s checkpoint
    re-read (absent: /reload answers 400; ``Router.reload`` still works
    programmatically).

    ``warmup=True`` starts a background thread precompiling the fleet's
    COMPLETE program family (all power-of-two prefill buckets + the
    decode, copy-on-write and speculative programs) through the device-program
    registry before traffic needs them — cold-start p99 TTFT pays no
    compiles.  The registry's persistent executable tier is always on
    (``program_cache_dir`` places it; ``programs.resolve_cache_dir``
    decides): a restart against the same config
    deserializes every program instead of compiling (``/stats`` →
    ``programs_compiled`` stays 0, pinned by the ``scripts/ci_serve.sh``
    restart drill)."""
    from ..data.build_dataset import CHAR_VOCAB
    from ..utils.checkpoint import CheckpointNotFoundError
    from ..utils import trace
    from ..utils.resilience import fault_point
    from .autoscale import AutoscalePolicy, Autoscaler
    from .engine import SamplingParams, fit_pool, row_cache
    from .metrics import ServeMetrics
    from .router import (FleetReloadError, NoHealthyReplicaError,
                         build_fleet, build_process_fleet)
    from .scheduler import (AdmissionRejectedError, DeadlineExceededError,
                            EngineFailedError, QueueFullError,
                            RequestCancelledError, SchedulerClosedError,
                            SlotQuarantinedError)

    info = dict(info or {"step": None, "num_nodes": None})
    stop = stop_event or threading.Event()
    if metrics_dir is None:
        # per-instance dir: a fixed shared default would interleave two
        # servers' rows in one append-mode serve.csv
        import tempfile
        metrics_dir = tempfile.mkdtemp(prefix="gym_tpu_serve_")

    asked = page_size
    page_size, kv_pages = fit_pool(page_size, cfg.block_size, kv_pages,
                                   config=cfg)
    if page_size != asked and not row_cache(cfg):
        sys.stderr.write(
            f"gym_tpu.serve: page_size {asked} does not divide "
            f"block_size {cfg.block_size} — serving with page_size "
            f"{page_size}"
            + (f" and kv_pages {kv_pages} (as many tokens)"
               if kv_pages is not None else "") + "\n")

    from .. import programs as programs_mod
    resolved = programs_mod.enable_disk_tier(program_cache_dir)
    sys.stderr.write(
        f"gym_tpu.serve: program registry disk tier at {resolved}\n")

    metrics = ServeMetrics(metrics_dir)
    weights_tag = (f"step-{info['step']}"
                   if info.get("step") is not None else None)
    autoscaler = None
    warm_thread = None
    if out_of_process:
        # process fleet: each replica is a worker SUBPROCESS speaking
        # the wire protocol over a unix socket in a private runtime
        # dir; the parent materializes the params snapshot once and
        # every worker loads it (and warms ITSELF — with a persistent
        # --program-cache-dir a spawned worker deserializes its whole
        # program family: programs_compiled=0)
        import tempfile
        base = fleet_dir or tempfile.mkdtemp(prefix="gym_tpu_fleet_")
        router = build_process_fleet(
            params, cfg, base, replicas=replicas, num_slots=num_slots,
            decode_chunk=decode_chunk, page_size=page_size,
            kv_pages=kv_pages, spec_tokens=spec_tokens,
            max_queue=max_queue, metrics=metrics,
            dispatch_timeout_s=dispatch_timeout,
            max_restarts=max_restarts, max_failovers=failover_retries,
            weights_tag=weights_tag,
            program_cache_dir=program_cache_dir,
            no_warmup=not warmup, device=None, env=worker_env,
            quotas=quotas, preempt=preempt,
            log=lambda *a, **k: print(*a, file=sys.stderr, flush=True))
        router.start()
        router.wait_ready(n=replicas,
                          timeout_s=worker_startup_timeout_s)
        if autoscale:
            lo = replicas if min_replicas is None else int(min_replicas)
            hi = (max(replicas, 4) if max_replicas is None
                  else int(max_replicas))
            if autoscale_policy is not None:
                # an explicit policy supplies the watermark/patience
                # knobs; EXPLICIT replica-bound arguments still win (a
                # caller asking for min_replicas=2 must never scale
                # below 2 because the policy object defaulted to 1)
                policy = dataclasses.replace(
                    autoscale_policy,
                    min_replicas=(int(min_replicas)
                                  if min_replicas is not None
                                  else autoscale_policy.min_replicas),
                    max_replicas=(int(max_replicas)
                                  if max_replicas is not None
                                  else autoscale_policy.max_replicas))
            else:
                policy = AutoscalePolicy(min_replicas=lo,
                                         max_replicas=hi)
            autoscaler = Autoscaler(
                router, policy,
                interval_s=autoscale_interval_s,
                metrics=metrics,   # ISSUE 15: per-tick audit rows
                log=lambda *a, **k: print(*a, file=sys.stderr,
                                          flush=True)).start()
        sched = sup = None
    else:
        # the params live in memory (restored from the checkpoint at
        # startup); the process-wide device-program registry makes every
        # replica's engine — and any failover/hot-swap rebuild — warm:
        # same config, no recompiles
        router = build_fleet(
            params, cfg, replicas=replicas, num_slots=num_slots,
            decode_chunk=decode_chunk, page_size=page_size,
            kv_pages=kv_pages, spec_tokens=spec_tokens,
            max_queue=max_queue,
            metrics=metrics, dispatch_timeout_s=dispatch_timeout,
            max_restarts=max_restarts, max_failovers=failover_retries,
            weights_tag=weights_tag, quotas=quotas, preempt=preempt)
        rep0 = router.replicas[0]
        sched, sup = rep0.scheduler, rep0.supervisor
        if warmup:
            # background AOT warmup over ONE replica's program family —
            # all replicas share config, so one pass warms the whole
            # fleet (and any future failover rebuild / hot-swap
            # generation) through the shared registry; a request
            # arriving mid-warmup single-flights into the same build
            # instead of compiling twice
            warm_thread = programs_mod.warm_engine_programs(
                rep0.scheduler.engine, log=sys.stderr.write)
    char_level = cfg.vocab_size <= len(CHAR_VOCAB) + 1

    def agg_tenant_snapshots(snaps):
        """Fold per-replica ``tenant_snapshot``s into one /stats
        ``tenants`` block: counters sum; per-class quota fill reports
        the MOST CONSTRAINED replica (min — the fill a client's next
        request actually prices against on the worst-placed replica)."""
        agg: Dict[str, Any] = {"preemptions": 0, "resumes": 0,
                               "parked": 0, "quota_rejections": {},
                               "quota_fill": {}, "backlog_by_class": {}}
        for s in snaps:
            if not s:
                continue
            agg["preemptions"] += int(s.get("preemptions", 0) or 0)
            agg["resumes"] += int(s.get("resumes", 0) or 0)
            agg["parked"] += int(s.get("parked", 0) or 0)
            for k, v in (s.get("quota_rejections") or {}).items():
                agg["quota_rejections"][k] = (
                    agg["quota_rejections"].get(k, 0) + int(v or 0))
            for k, v in (s.get("backlog_by_class") or {}).items():
                agg["backlog_by_class"][k] = (
                    agg["backlog_by_class"].get(k, 0) + int(v or 0))
            for k, v in (s.get("quota_fill") or {}).items():
                if v is None:
                    agg["quota_fill"].setdefault(k, None)
                else:
                    prev = agg["quota_fill"].get(k)
                    agg["quota_fill"][k] = (float(v) if prev is None
                                            else min(prev, float(v)))
        return agg

    def encode_text(text: str):
        table = {c: i for i, c in enumerate(CHAR_VOCAB)}
        toks = [table[c] for c in text if c in table]
        if not toks:
            raise ValueError("text encodes to an empty prompt under the "
                             "char vocab")
        return np.asarray(toks, np.int32)

    def decode_text(tokens):
        return "".join(CHAR_VOCAB[t] for t in tokens
                       if 0 <= t < len(CHAR_VOCAB))

    class Handler(BaseHTTPRequestHandler):
        # quiet structured access log — one line per request on stderr
        def log_message(self, fmt, *a):
            sys.stderr.write("gym_tpu.serve: " + fmt % a + "\n")

        def _reply(self, code: int, payload: dict,
                   retry_after_s: Optional[float] = None):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after_s is not None:
                self.send_header("Retry-After",
                                 str(max(1, math.ceil(retry_after_s))))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path not in ("/stats", "/healthz"):
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            if getattr(router, "kind", "thread") == "process":
                self._stats_process()
                return
            fleet = router.status()
            engines = [rep.scheduler.engine for rep in router.replicas]
            stats = [e.stats for e in engines]
            eng0 = engines[0]
            buckets = sorted({b for s in stats for b in s.prefill_buckets})
            drafted = sum(s.spec_drafted for s in stats)
            accepted = sum(s.spec_accepted for s in stats)
            head = metrics.headline()
            rep_counters = head.pop("replicas", {})
            # ONE per-replica section: live engine samples + the
            # metrics collector's per-replica counters folded into the
            # router's health entries
            for entry, s in zip(fleet["replicas"], stats):
                entry.update(active_slots=s.active_slots,
                             tokens_generated=s.tokens_generated,
                             quarantined=s.quarantined)
                entry.update(rep_counters.get(str(entry["id"]), {}))
            dead = sum(1 for rep in router.replicas if rep.dead)
            self._reply(200, {
                **head,                 # first: the LIVE engine stats
                #                         below win over its tick samples
                "status": ("draining" if stop.is_set() else
                           "degraded" if dead else "ok"),
                "step": info["step"],
                "num_slots": sum(s.num_slots for s in stats),
                "active_slots": sum(s.active_slots for s in stats),
                "queue_depth": sum(rep.scheduler.queue_depth()
                                   for rep in router.replicas),
                "tokens_generated": sum(s.tokens_generated
                                        for s in stats),
                "decode_steps": sum(s.decode_steps for s in stats),
                "prefills": sum(s.prefills for s in stats),
                "prefill_buckets": buckets,
                "prefill_tokens": sum(s.prefill_tokens for s in stats),
                "prefill_tokens_run": sum(s.prefill_tokens_run
                                          for s in stats),
                "paged": True,          # the only cache there is
                "page_size": int(eng0.page_size),
                "kv_pages": int(eng0.kv_pages),
                "spec_tokens": int(eng0.spec_tokens),
                # quantized serving (ISSUE 11): config echo + the
                # f32-normalized pool capacity and actual byte
                # footprints (honest accounting — scale sidecars
                # reported, not hidden)
                "weights_dtype": getattr(eng0, "weights_dtype", "f32"),
                "kv_dtype": getattr(eng0, "kv_dtype", "f32"),
                "kv_blocks_capacity_effective": sum(
                    int(getattr(e, "kv_blocks_capacity_effective", 0))
                    for e in engines),
                "weights_bytes": int(getattr(eng0, "weights_bytes", 0)),
                "kv_blocks_in_use": sum(s.kv_blocks_in_use
                                        for s in stats),
                "kv_blocks_cached": sum(s.kv_blocks_cached
                                        for s in stats),
                "prefix_hit_blocks": sum(s.prefix_hit_blocks
                                         for s in stats),
                # pages evicted from the prefix cache (a full pool's
                # admissions take their pages there), and the entries of
                # the allocator's recency heap those evictions looked at
                "kv_evictions": sum(s.kv_evictions for s in stats),
                "kv_evict_visits": sum(s.kv_evict_visits for s in stats),
                # a model whose rows hold one state block beside their
                # pages: how many there are (0: no such model), and how
                # many live and parked rows hold
                "state_blocks": sum(s.state_blocks for s in stats),
                "state_blocks_in_use": sum(s.state_blocks_in_use
                                           for s in stats),
                "spec_accept_rate": (accepted / drafted
                                     if drafted else None),
                # device-program registry: XLA compiles this process has
                # actually run (disk-tier deserializations excluded) —
                # THE restart-drill observable (0 across a restart with
                # a warm disk tier) — plus background-warmup progress
                "programs_compiled": programs_mod.xla_compile_counter(),
                # the program's spans so far: {name: [count, total_s,
                # max_s, cpu_s]} (utils/trace.py; names in PERF.md §3)
                "spans": trace.totals(),
                # CPU seconds of every thread of this process so far:
                # beside the spans' cpu_s it says what the threads
                # outside any span ran
                "process_cpu_s": time.process_time(),
                "readback_bytes": sum(s.readback_bytes for s in stats),
                # host arrays of the decode state handed to dispatches,
                # and decode dispatches that were handed none (all
                # state resident)
                "upload_arrays": sum(s.upload_arrays for s in stats),
                "resident_steps": sum(s.resident_steps for s in stats),
                # decode steps dispatched while the step before was
                # unread, and times a host write or an idle round waited
                # out what was in flight
                "steps_ahead": sum(s.steps_ahead for s in stats),
                "drains": sum(s.drains for s in stats),
                # requests that progressed in a read of the scheduler's,
                # each woken once
                "wakes": sum(rep.scheduler.wakes
                             for rep in router.replicas),
                # decode steps whose sampler sorted the vocabulary (a
                # live row filtered by top_k or top_p)
                "sampler_sorted_steps": sum(s.sampler_sorted_steps
                                            for s in stats),
                # decode + prefill dispatches whose attend ran the Pallas
                # page walk: equals decode dispatches + prefills on a TPU
                # with an f32 pool, 0 on the gather path
                "paged_kernel_dispatches": sum(
                    s.paged_kernel_dispatches for s in stats),
                # what the model counted in its decode steps (expert
                # picks, pages read and skipped, ...): engine 0's sums
                "model_counters": {
                    k: np.asarray(v).tolist()
                    for k, v in stats[0].model_counters.items()},
                "warmup": (warm_thread.stats()
                           if warm_thread is not None else None),
                # multi-tenant serving (ISSUE 17): live quota fill,
                # preemption/park counters and per-class backlog
                "tenants": agg_tenant_snapshots(
                    [rep.scheduler.tenant_snapshot()
                     for rep in router.replicas if not rep.dead]),
                # pre-fleet surface: replica 0's supervisor state (the
                # keys every existing dashboard/drill greps)
                **sup.status(),
                # the fleet view: per-replica health/load/weights,
                # failovers, reloads — wins over the aggregates above
                # where keys collide (replicas, failovers, …)
                **fleet,
            })

        def _stats_process(self):
            """/stats for the OUT-OF-PROCESS fleet: the router process
            holds no engines — per-replica engine samples come from the
            workers' health frames (cached by the dispatcher's reader
            loop), each entry carrying the worker ``pid`` and its OWN
            ``programs_compiled`` (the spawn-cheapness observable the
            ci_serve drill pins at 0 against a warm cache dir)."""
            fleet = router.status()
            live = [r for r in fleet["replicas"] if not r["retired"]]
            head = metrics.headline()
            head.pop("replicas", None)
            # degraded = fewer healthy workers than the fleet's floor
            # (dead replicas stay listed for the post-mortem, but a
            # respawned fleet is OK again — alerts must clear)
            floor = (autoscaler.policy.min_replicas
                     if autoscaler is not None else replicas)
            self._reply(200, {
                **head,
                "status": ("draining" if stop.is_set() else
                           "degraded"
                           if fleet["healthy_replicas"] < floor
                           else "ok"),
                "step": info["step"],
                "num_slots": sum(r.get("num_slots") or 0
                                 for r in live if r["healthy"]),
                "active_slots": sum(r.get("active_slots") or 0
                                    for r in live),
                "queue_depth": sum(r.get("queue_depth") or 0
                                   for r in live),
                "tokens_generated": sum(r.get("tokens_generated") or 0
                                        for r in live),
                # the ROUTER process's own compile counter (should stay
                # ~0: it dispatches, it does not decode); per-replica
                # programs_compiled lives in each replicas[] entry
                "programs_compiled": programs_mod.xla_compile_counter(),
                "autoscaler": (autoscaler.status()
                               if autoscaler is not None else None),
                # multi-tenant block off the workers' health frames
                "tenants": agg_tenant_snapshots(
                    [r.get("tenants") for r in live]),
                **fleet,
            })

        def do_POST(self):
            if self.path == "/reload":
                self._do_reload()
                return
            if self.path != "/generate":
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            # to the last byte written; `request` joins it once known.
            # Recorder only, like every span of a handler thread: these
            # threads do not feed the device (utils/trace.py). Its cpu
            # is all this thread ran for the request; there is no span a
            # server-sent event: the thread clock is a system call
            with trace.span("http.generate", annotate=False) as http_span:
                self._generate(http_span)

        def _generate(self, http_span):
            try:
                with trace.span("http.parse", annotate=False):
                    fault_point("serve.http")
                    n = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(n) or b"{}"
                    try:
                        body = json.loads(raw)
                    except json.JSONDecodeError as e:
                        raise ValueError(f"malformed JSON body: {e}")
                    if not isinstance(body, dict):
                        raise ValueError(
                            f"JSON body must be an object, got "
                            f"{type(body).__name__}")
                    if "prompt" in body:
                        prompt = np.asarray(body["prompt"], np.int32)
                    elif "text" in body and char_level:
                        prompt = encode_text(body["text"])
                    elif "text" in body:
                        raise ValueError(
                            "text prompts need a char-level vocab; this model "
                            f"has vocab_size={cfg.vocab_size} — send token "
                            "ids as 'prompt'")
                    else:
                        raise ValueError("body needs 'prompt' (token ids) "
                                         "or 'text'")
                    sp = SamplingParams(
                        max_new_tokens=int(body.get("max_new_tokens", 64)),
                        temperature=float(body.get("temperature", 1.0)),
                        top_k=(None if body.get("top_k") is None
                               else int(body["top_k"])),
                        top_p=(None if body.get("top_p") is None
                               else float(body["top_p"])),
                        eos_token=(None if body.get("eos_token") is None
                                   else int(body["eos_token"])),
                        seed=int(body.get("seed", 0)))
                    # body field wins over the X-Deadline-S header; both win
                    # over the server-wide default
                    deadline = body.get("deadline_s",
                                        self.headers.get("X-Deadline-S"))
                    deadline = (default_deadline if deadline is None
                                else float(deadline))
                    stream = bool(body.get("stream", False))
                    # multi-tenant tags (ISSUE 17): body field wins over
                    # the header; both optional — absent = the default
                    # tenant/class (single-tenant behavior)
                    tenant = body.get("tenant",
                                      self.headers.get("X-Tenant"))
                    slo_class = body.get("slo_class",
                                         self.headers.get("X-SLO-Class"))
                    if tenant is not None:
                        tenant = str(tenant)
                    if slo_class is not None:
                        slo_class = str(slo_class)
            except (ValueError, KeyError, TypeError) as e:
                self._reply(400, {"error": str(e)})
                return
            except OSError as e:      # serve.http injected IO fault
                self._reply(503, {"error": f"{type(e).__name__}: {e}"},
                            retry_after_s=1.0)
                return
            try:
                # the process router skips per-chunk wire frames for
                # result-only requests; the in-process router has no
                # such knob (tokens are already shared memory)
                submit_kw = ({"stream": stream}
                             if getattr(router, "kind", "") == "process"
                             else {})
                with trace.span("http.submit", annotate=False) as sub:
                    req = router.submit(prompt, sp, timeout=30.0,
                                        deadline_s=deadline, tenant=tenant,
                                        slo_class=slo_class, **submit_kw)
                    sub.ids["request"] = req.id
            except AdmissionRejectedError as e:
                self._reply(429, {"error": str(e)},
                            retry_after_s=e.retry_after_s)
                return
            except QueueFullError as e:
                self._reply(429, {"error": str(e)}, retry_after_s=2.0)
                return
            except NoHealthyReplicaError as e:
                self._reply(503, {"error": str(e)},
                            retry_after_s=e.retry_after_s)
                return
            except SchedulerClosedError as e:
                self._reply(503, {"error": str(e)}, retry_after_s=10.0)
                return
            except ValueError as e:
                # a prompt the KV cache can't fit, bad sampling params
                self._reply(400, {"error": str(e)})
                return
            except OSError as e:      # serve.admit injected IO fault
                self._reply(503, {"error": f"{type(e).__name__}: {e}"},
                            retry_after_s=1.0)
                return
            http_span.ids["request"] = req.id
            # the handler's own wait honors the request deadline: even if
            # the driver is wedged (the watchdog will reap it), the
            # client gets its typed answer within deadline + grace
            wait_s = request_timeout
            if deadline is not None:
                wait_s = min(wait_s, deadline + 5.0)
            if stream:
                self._stream_reply(req, prompt, wait_s)
                return
            try:
                tokens = req.result(timeout=wait_s)
            except DeadlineExceededError as e:
                self._reply(504, {"error": str(e),
                                  "tokens_before_deadline":
                                  len(req.tokens)})
                return
            except TimeoutError as e:
                self._reply(504, {"error": str(e)})
                return
            except (EngineFailedError, SlotQuarantinedError,
                    SchedulerClosedError) as e:
                self._reply(503, {"error": f"{type(e).__name__}: {e}"},
                            retry_after_s=2.0)
                return
            except AdmissionRejectedError as e:
                # a failover retry shed at the SIBLING's admission (the
                # remaining deadline is infeasible there): same 429 +
                # Retry-After contract as a front-door shed
                self._reply(429, {"error": str(e)},
                            retry_after_s=e.retry_after_s)
                return
            except QueueFullError as e:
                self._reply(429, {"error": str(e)}, retry_after_s=2.0)
                return
            except NoHealthyReplicaError as e:
                self._reply(503, {"error": str(e)},
                            retry_after_s=e.retry_after_s)
                return
            except OSError as e:
                # a request failed by an IO fault (e.g. serve.prefill
                # oserror) stores that exception; it must surface as a
                # typed 503, not escape the handler as a traceback
                self._reply(503, {"error": f"{type(e).__name__}: {e}"},
                            retry_after_s=1.0)
                return
            except RuntimeError as e:
                self._reply(503, {"error": str(e)})
                return
            out = {"tokens": tokens,
                   "prompt_tokens": int(prompt.size),
                   "ttft_s": round(req.ttft_s, 5),
                   "latency_s": round(req.done_t - req.submit_t, 5),
                   "replica": req.replica_id,
                   "failovers": req.failovers}
            if char_level:
                out["text"] = decode_text(tokens)
            self._reply(200, out)

        def _sse(self, obj: dict) -> None:
            self.wfile.write(b"data: " + json.dumps(obj).encode()
                             + b"\n\n")
            self.wfile.flush()

        def _stream_reply(self, req, prompt, wait_s: float) -> None:
            """``"stream": true`` — chunked SSE: one ``data:`` event per
            decode chunk, then a final summary event. TTFB collapses
            from completion time to FIRST-token time; a mid-stream
            replica death is spliced by the router (the concatenated
            events are byte-identical to an uncontended run); a client
            that disconnects (EPIPE on the chunked write) has its
            request cancelled at the next decode-chunk boundary and
            recorded ``status=disconnected`` — never a traceback."""
            metrics.stream_started()
            tokens = []
            try:
                try:
                    # header writes can ALREADY raise EPIPE (client
                    # gone before the first byte) — they must sit
                    # inside the disconnect guard or the generation
                    # runs for nobody and the handler tracebacks
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.send_header("Connection", "close")
                    self.end_headers()
                    for chunk in req.stream(timeout=wait_s):
                        tokens.extend(chunk)
                        self._sse({"tokens": chunk,
                                   "replica": req.replica_id})
                    out = {"done": True,
                           "tokens_total": len(tokens),
                           "prompt_tokens": int(prompt.size),
                           "ttft_s": (round(req.ttft_s, 5)
                                      if req.ttft_s is not None
                                      else None),
                           "latency_s": (round(req.done_t - req.submit_t,
                                               5)
                                         if req.done_t is not None
                                         else None),
                           "replica": req.replica_id,
                           "failovers": req.failovers}
                    if char_level:
                        out["text"] = decode_text(tokens)
                    self._sse(out)
                except (BrokenPipeError, ConnectionResetError):
                    # the client went away mid-stream: cancel at the
                    # next chunk boundary, free the slot; metrics land
                    # as status=disconnected via RequestCancelledError
                    req.cancel(reason="client disconnected mid-stream")
                    self.close_connection = True
                except (DeadlineExceededError, TimeoutError,
                        AdmissionRejectedError, QueueFullError,
                        EngineFailedError, SlotQuarantinedError,
                        SchedulerClosedError, NoHealthyReplicaError,
                        RequestCancelledError, OSError,
                        RuntimeError) as e:
                    # headers are gone — the typed failure travels as a
                    # terminal SSE event instead of a status code
                    try:
                        self._sse({"error": str(e),
                                   "error_type": type(e).__name__,
                                   "tokens_total": len(tokens)})
                    except (BrokenPipeError, ConnectionResetError):
                        req.cancel(reason="client disconnected")
                        self.close_connection = True
            finally:
                metrics.stream_ended()

        def _do_reload(self):
            """Zero-downtime weight hot-swap over HTTP: re-read the
            checkpoint (body: optional ``ckpt``/``step``), roll it
            through the fleet. 400 bad body/source, 409 when a reload
            is already rolling, 503 when a replica failed to drain."""
            if reload_source is None:
                self._reply(400, {
                    "error": "no reload source configured — start the "
                             "server via `python -m gym_tpu.serve "
                             "--ckpt ...` to enable /reload"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n) or b"{}"
                body = json.loads(raw)
                if not isinstance(body, dict):
                    raise ValueError(
                        f"JSON body must be an object, got "
                        f"{type(body).__name__}")
                drain_s = float(body.get("drain_timeout_s", 300.0))
            except (json.JSONDecodeError, ValueError, TypeError) as e:
                self._reply(400, {"error": f"malformed reload body: {e}"})
                return
            try:
                new_params, tag = reload_source(body)
            except (CheckpointNotFoundError, FileNotFoundError,
                    ValueError) as e:
                self._reply(400, {"error": f"{type(e).__name__}: {e}"})
                return
            except OSError as e:
                self._reply(503, {"error": f"{type(e).__name__}: {e}"},
                            retry_after_s=5.0)
                return
            try:
                result = router.reload(
                    new_params, weights_tag=tag, drain_timeout_s=drain_s)
            except FleetReloadError as e:
                if e.retry_after_s is not None:
                    # a replica failed to drain in time — transient
                    self._reply(503, {"error": str(e)},
                                retry_after_s=e.retry_after_s)
                else:       # another rollout already in flight
                    self._reply(409, {"error": str(e)})
                return
            except SchedulerClosedError as e:
                self._reply(503, {"error": str(e)}, retry_after_s=10.0)
                return
            if tag and tag.startswith("step-"):
                # /stats "step" tracks the weights actually serving
                try:
                    info["step"] = int(tag[5:])
                except ValueError:
                    pass
            self._reply(200, result)

    httpd = ThreadingHTTPServer((host, port), Handler)
    # answered-before-closed: server_close waits for handler threads, so
    # every accepted request gets its JSON reply before the process exits
    httpd.daemon_threads = False
    httpd.block_on_close = True
    if not out_of_process:
        router.start()        # process fleets started above (their
        #                       workers need the pre-listen wait)
    return ServerHandle(httpd=httpd, scheduler=sched, supervisor=sup,
                        metrics=metrics,
                        engine_factory=(None if out_of_process
                                        else router.replicas[0]
                                        .engine_factory),
                        info=info, router=router, warmup=warm_thread,
                        autoscaler=autoscaler)


def main(argv=None) -> int:
    parser = _build_parser()
    args = parser.parse_args(argv)
    if args.page_size < 1:
        parser.error(f"--page_size must be >= 1, got {args.page_size}: "
                     f"the page pool is the only KV cache")
    if getattr(args, "quant_embed") and not args.quant:
        # refuse, don't silently no-op: quant_embed only has meaning on
        # a quantized weight tree
        parser.error("--quant-embed requires --quant {int8,int4}")
    if args.device == "cpu":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        jax.config.update("jax_platforms", "cpu")

    from ..utils.checkpoint import CheckpointNotFoundError
    from .load import CheckpointWatcher, load_for_serving

    quant_kw = dict(weights_dtype=args.quant,
                    kv_dtype=getattr(args, "kv_quant"),
                    quant_embed=getattr(args, "quant_embed"))
    try:
        params, cfg, info = load_for_serving(
            args.ckpt, step=args.step, config_path=args.config,
            **quant_kw)
    except (CheckpointNotFoundError, FileNotFoundError, ValueError) as e:
        print(f"gym_tpu.serve: cannot load {args.ckpt}: {e}",
              file=sys.stderr)
        return 1
    quant_note = ""
    if args.quant or getattr(args, "kv_quant"):
        quant_note = (f", quantized (weights {cfg.weights_dtype}"
                      + (", embed" if cfg.quant_embed else "")
                      + f", kv {cfg.kv_dtype})")
    print(f"gym_tpu.serve: restored step {info['step']} "
          f"({info['num_nodes']}-node average) from {args.ckpt}"
          f"{quant_note}", flush=True)

    def reload_source(body):
        """POST /reload + the checkpoint watcher: re-read the run dir
        (newest valid step unless pinned) and hand back the node-
        averaged params with a ``step-N`` weights tag — quantized
        through the same load-time step as startup, so a hot-swap never
        silently changes serving dtype. The architecture must match —
        the fleet's compiled programs are config-keyed."""
        ckpt = body.get("ckpt") or args.ckpt
        new_params, new_cfg, new_info = load_for_serving(
            ckpt, step=body.get("step"), config_path=args.config,
            **quant_kw)
        if new_cfg != cfg:
            raise ValueError(
                f"checkpoint {ckpt} carries a different model config — "
                f"a hot-swap cannot change architecture; restart the "
                f"server")
        return new_params, f"step-{new_info['step']}"

    quotas = None
    if getattr(args, "quotas"):
        from .scheduler import ClassQuota
        try:
            quotas = {cls: ClassQuota(**spec)
                      for cls, spec in json.loads(args.quotas).items()}
        except (json.JSONDecodeError, TypeError, ValueError) as e:
            print(f"gym_tpu.serve: bad --quotas JSON: {e}",
                  file=sys.stderr)
            return 1

    from .router import ChipHeldByParentError
    stop = threading.Event()
    try:
        handle = create_server(
            params, cfg, host=args.host, port=args.port,
            num_slots=args.num_slots, decode_chunk=args.decode_chunk,
            max_queue=args.max_queue,
            request_timeout=args.request_timeout,
            default_deadline=getattr(args, "default_deadline"),
            dispatch_timeout=getattr(args, "dispatch_timeout"),
            max_restarts=getattr(args, "max_restarts"),
            metrics_dir=(args.metrics_dir
                         or os.path.join(args.ckpt, "serve")),
            info=info, stop_event=stop, page_size=args.page_size,
            kv_pages=args.kv_pages, spec_tokens=args.spec_tokens,
            replicas=args.replicas,
            failover_retries=getattr(args, "failover_retries"),
            reload_source=reload_source,
            warmup=not getattr(args, "no_warmup"),
            program_cache_dir=getattr(args, "program_cache_dir"),
            out_of_process=getattr(args, "out_of_process"),
            autoscale=getattr(args, "autoscale"),
            min_replicas=getattr(args, "min_replicas"),
            max_replicas=getattr(args, "max_replicas"),
            autoscale_interval_s=getattr(args, "autoscale_interval"),
            worker_startup_timeout_s=getattr(args,
                                             "worker_startup_timeout"),
            quotas=quotas, preempt=getattr(args, "preempt"))
    except ChipHeldByParentError as e:
        # --out-of-process from a parent that holds the TPU: refuse in
        # one line instead of spawning workers that wait for the chip
        print(f"gym_tpu.serve: {e}", file=sys.stderr)
        return 1
    httpd, metrics, router = handle.httpd, handle.metrics, handle.router

    watcher = None
    if getattr(args, "reload_watch") > 0:

        def on_new_step(step):
            new_params, tag = reload_source({"step": step})
            res = router.reload(new_params, weights_tag=tag)
            # /stats "step" tracks the live weights — mutate the
            # handler's copy (create_server dict()s the info it is given)
            handle.info["step"] = step
            print(f"gym_tpu.serve: checkpoint watcher — hot-swapped "
                  f"{tag} into replicas {res['swapped']} "
                  f"in {res['wall_s']}s", flush=True)

        watcher = CheckpointWatcher(
            args.ckpt, on_new_step,
            poll_s=getattr(args, "reload_watch"),
            initial_step=info["step"]).start()

    def graceful(signum):
        name = signal.Signals(signum).name
        print(f"gym_tpu.serve: {name} — draining "
              f"(answer in-flight, fail queued)", flush=True)
        deadline = getattr(args, "drain_deadline")
        stop.set()
        if watcher is not None:
            watcher.stop()
        if handle.autoscaler is not None:
            handle.autoscaler.stop()   # no respawns during the drain
        handle.stop_warmup()
        # per-replica drain: answer in-flight, fail queued typed; a
        # WEDGED replica gets its thread stacks dumped and its requests
        # failed typed without its engine ever being stepped from this
        # thread (single-driver contract) — Router.close does both
        if not router.close(drain_deadline_s=deadline):
            print("gym_tpu.serve: one or more replica drivers wedged "
                  "through the drain (stacks dumped above)",
                  file=sys.stderr, flush=True)
        httpd.shutdown()

    def _on_signal(signum, frame):
        # serve_forever blocks the main thread; drain from a helper so the
        # handler returns immediately (a second signal takes default
        # action — grace, not imprisonment)
        threading.Thread(target=graceful, args=(signum,),
                         daemon=True).start()
        signal.signal(signum, signal.SIG_DFL)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)

    if handle.scheduler is not None:
        eng = handle.scheduler.engine
        kv = (f"paged kv: page {eng.page_size} x {eng.kv_pages} pages"
              + (f", spec {eng.spec_tokens}" if eng.spec_tokens else ""))
        if eng.weights_dtype != "f32" or eng.kv_dtype != "f32":
            kv += f", quant w={eng.weights_dtype} kv={eng.kv_dtype}"
        fleet_note = f"{args.replicas} replica(s)"
    else:
        kv = "worker-side kv"
        fleet_note = (f"{args.replicas} worker process(es)"
                      + (", autoscaling" if handle.autoscaler is not None
                         else ""))
    print(f"gym_tpu.serve: listening on http://{args.host}:{handle.port} "
          f"({fleet_note} x {args.num_slots} slots, "
          f"queue {args.max_queue}, {kv}, "
          f"watchdog {getattr(args, 'dispatch_timeout'):.0f}s)", flush=True)
    try:
        httpd.serve_forever()
    finally:
        httpd.server_close()
        if watcher is not None:
            watcher.stop()
        metrics.sync()
        head = metrics.headline()
        fleet = router.status()
        print(f"gym_tpu.serve: shut down cleanly — "
              f"{head['requests_done']} done, "
              f"{head['requests_failed']} failed "
              f"({head['requests_shed']} shed, "
              f"{head['requests_quarantined']} quarantined), "
              f"{head['engine_restarts']} engine restart(s), "
              f"{fleet['failovers']} failover(s), "
              f"{fleet['weight_reloads']} weight reload(s), "
              f"tokens_per_s={head['tokens_per_s']}", flush=True)
        metrics.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
