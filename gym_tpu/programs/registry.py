"""Unified device-program registry: one owner for every compiled program.

Before this module the repo compiled XLA programs in four unrelated
places — the trainer's per-fit ``jax.jit``, six module-global
``functools.lru_cache`` stores in ``serve/engine.py``, the persistent
compile cache wired by ``utils/compile_cache.py``, and the fleet
hot-swap's "warm global LRUs".  The registry collapses them into one
keyed store with three perf layers:

1. **Single-flight in-memory store.**  Programs are keyed by the
   canonical sha256 key from ``programs.keys`` (the same key the jaxpr
   auditor reports).  Two threads — two replicas, a warmup thread and a
   request, trainer and server — requesting the same key trigger
   exactly ONE build: the first holds the per-key build lock, the rest
   block on it and share the result.  Hits, builds, XLA compiles, disk
   hits and compile-seconds are counted and exported (``/stats``,
   ``serve.csv``, the benchmark's ``window`` line).

2. **Persistent executable tier.**  ``enable_disk_tier`` points JAX's
   persistent compilation cache at a directory (owning what
   ``utils/compile_cache.py`` used to wire ad hoc) and installs a
   ``jax.monitoring`` listener for the cache's hit/miss events.  A
   registry build AOT-compiles the program (``jit(...).lower(*avals)
   .compile()``); with the disk tier enabled that compile deserializes
   a previously-persisted executable instead of running XLA, so a
   server process restart against the same config performs ZERO XLA
   compiles on its hot path — ``xla_compiles`` stays 0 and the restart
   drill in ``scripts/ci_serve.sh`` pins it.  A corrupt or stale disk
   entry is survivable twice over: JAX itself warns and recompiles on a
   deserialization error, and the registry additionally retries a
   failed build once with the cache bypassed.

3. **AOT compile + direct executable dispatch.**  Built entries store
   the ``jax.stages.Compiled`` executable and ``Program.__call__``
   invokes it directly — measured ~15x less per-dispatch host overhead
   than re-entering the ``jax.jit`` wrapper on this CPU backend, and it
   guarantees the executable used is exactly the one the registry
   compiled/warmed (the jit wrapper's own dispatch cache is a separate,
   unwarmed cache).  Programs whose call-site avals are not statically
   known (the trainer step) register through ``track_jit`` instead:
   same key space and counters, compile measured at first dispatch.

Capacity is bounded (LRU eviction of UNPINNED entries only): an engine
pins the programs it holds — via a weakref finalizer, so a dead engine
releases its pins — and eviction can therefore never drop a program a
live engine is dispatching through.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from .keys import program_key

PyTree = Any

# -- disk tier (persistent XLA executable cache) ---------------------------

#: where the cache lives when nothing places it: ONE fixed path inside
#: the checkout (the path is part of JAX's cache key — a directory that
#: moves with the home, the pid, the time or an output dir never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: global persistent-cache event counters, fed by jax.monitoring. The
#: events are process-global (jax has one compilation cache), so the
#: listener and counters are module-level; registries read deltas under
#: the compile lock for exact attribution.
_DISK_EVENTS = {"hits": 0, "misses": 0}
_EVENTS_LOCK = threading.Lock()
_LISTENER_INSTALLED = False

#: serializes actual builds (lower+compile) across the process so a
#: build's persistent-cache hit/miss event delta is attributable to THAT
#: build — and because concurrent XLA compiles on a 2-core host contend
#: anyway. Single-flight already dedupes same-key builds; this only
#: orders different-key ones.
_COMPILE_LOCK = threading.Lock()


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    import jax.monitoring

    def _on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with _EVENTS_LOCK:
                _DISK_EVENTS["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with _EVENTS_LOCK:
                _DISK_EVENTS["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


def _disk_events() -> Tuple[int, int]:
    with _EVENTS_LOCK:
        return _DISK_EVENTS["hits"], _DISK_EVENTS["misses"]


def disk_event_counters() -> Dict[str, int]:
    """Process-global persistent-cache hit/miss event counts (every XLA
    compile in the process, registry-owned or not). 0/0 until
    ``enable_disk_tier`` has installed the listener."""
    h, m = _disk_events()
    return {"xla_cache_hits": h, "xla_cache_misses": m}


def _enabled_cache_dir() -> Optional[str]:
    """The directory an earlier ``enable_disk_tier`` call turned on."""
    import jax
    return (jax.config.jax_compilation_cache_dir
            if _LISTENER_INSTALLED else None)


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Where the persistent compile cache goes: ``JAX_COMPILATION_CACHE_DIR``
    (where it is set, that directory is used and nothing in this repo
    sets another) > explicit argument > the directory an earlier call
    already enabled > ``DEFAULT_CACHE_DIR`` inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
            or _enabled_cache_dir() or DEFAULT_CACHE_DIR)


def enable_disk_tier(cache_dir: Optional[str] = None, *,
                     min_compile_time_secs: Optional[float] = 0.0) -> str:
    """Point JAX's persistent compilation cache at
    ``resolve_cache_dir(cache_dir)`` and install the hit/miss listener
    the registry's compile counters read.
    ``min_compile_time_secs`` defaults to 0 (persist even
    sub-second compiles — the serving programs on small models compile
    fast but a cold start pays all of them at once; ``None`` leaves
    JAX's own ~1 s threshold untouched, the trainer-path default).
    Idempotent; returns the resolved directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = resolve_cache_dir(cache_dir)
    if min_compile_time_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
    if cache_dir != _enabled_cache_dir():
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_enable_compilation_cache", True)
        # JAX binds its cache object to a directory once per process;
        # the reset makes the next compile re-initialize against
        # ``cache_dir`` (a server restores its checkpoint, which
        # compiles, before this function runs)
        compilation_cache.reset_cache()
        _install_listener()
    return cache_dir


# -- program definitions ---------------------------------------------------


@dataclasses.dataclass
class ProgramDef:
    """One registrable device program: enough to (a) compute its
    canonical key without building anything and (b) build + AOT-compile
    it on demand.  ``args`` are pytrees of ``jax.ShapeDtypeStruct``
    templates — the exact avals every call site dispatches with (the
    registry stores the AOT executable, so call-site avals MUST match).
    ``builder()`` returns the jitted callable, donation already
    attached."""

    name: str
    family: str
    config: Dict[str, Any]
    args: Tuple[Any, ...]
    donate_args: Tuple[int, ...]
    builder: Callable[[], Callable]
    #: False skips the AOT ``lower().compile()`` and stores the raw
    #: builder result (programs that must trace lazily, e.g. under a
    #: mesh context the registry doesn't own)
    aot: bool = True

    def key(self) -> Tuple[str, str]:
        return program_key(self.name, self.config, self.args,
                           self.donate_args)


class Program:
    """Callable handle to a registry entry.  ``ensure()`` builds (or
    joins the single-flight build of) the executable; ``__call__``
    ensures then dispatches.  After the first ensure the executable is
    cached on the handle — the hot path never re-enters the registry."""

    __slots__ = ("_registry", "_key_hash", "_fn", "name")

    def __init__(self, registry: "ProgramRegistry", key_hash: str,
                 name: str):
        self._registry = registry
        self._key_hash = key_hash
        self._fn: Optional[Callable] = None
        self.name = name

    @property
    def key_hash(self) -> str:
        return self._key_hash

    @property
    def built(self) -> bool:
        return (self._fn is not None
                or self._registry._is_built(self._key_hash))

    def ensure(self) -> Callable:
        if self._fn is None:
            self._fn, _ = self._registry._ensure_built(self._key_hash)
        return self._fn

    def ensure_reporting(self) -> bool:
        """Ensure built; True iff THIS call ran the build.  The exact
        per-key compile observable — diffing a global counter around
        ``ensure()`` misattributes concurrent builds (warmup thread,
        sibling replicas) to this call site."""
        if self._fn is not None:
            return False
        self._fn, built_now = self._registry._ensure_built(self._key_hash)
        return built_now

    def __call__(self, *args):
        fn = self._fn
        if fn is None:
            fn = self.ensure()
        tid = threading.get_ident()
        _INFLIGHT[tid] = f"{self.name} [{self._key_hash[:12]}]"
        try:
            return fn(*args)
        finally:
            _INFLIGHT.pop(tid, None)


@dataclasses.dataclass
class _Entry:
    pdef: Optional[ProgramDef]
    name: str
    family: str
    fn: Optional[Callable] = None
    pins: int = 0
    build_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock)


# -- the registry ----------------------------------------------------------


class ProgramRegistry:
    """Keyed, bounded, single-flight store of compiled device programs.

    Thread-safe.  ``acquire`` registers a key (and returns a handle)
    without compiling; the build happens at ``ensure``/first call, or
    eagerly (``eager=True`` — what the warmup thread uses).  Counters:

    - ``hits``   — acquires/ensures answered by an already-built entry
    - ``builds`` — in-memory misses that ran a builder (the analogue of
      the retired ``lru_cache`` miss probes; ``compile_counter()``)
    - ``xla_compiles`` — builds whose compile actually ran XLA (with
      the disk tier warm this stays 0 across a process restart)
    - ``disk_hits`` — builds served by deserializing a persisted
      executable
    - ``compile_seconds`` — wall time inside builds (trace + compile
      or deserialize)
    """

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.RLock()
        self._store: "OrderedDict[str, _Entry]" = OrderedDict()
        self._hits = 0
        self._builds = 0
        self._xla_compiles = 0
        self._disk_hits = 0
        self._compile_seconds = 0.0
        self._evictions = 0

    # -- introspection ----------------------------------------------------

    def counters(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "hits": self._hits,
                "builds": self._builds,
                "xla_compiles": self._xla_compiles,
                "disk_hits": self._disk_hits,
                "compile_seconds": round(self._compile_seconds, 4),
                "evictions": self._evictions,
                "programs": len(self._store),
            }

    def keys(self) -> Dict[str, str]:
        """``{key_hash: program name}`` for every registered program."""
        with self._lock:
            return {k: e.name for k, e in self._store.items()}

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)

    def lowered_text(self, name: str) -> str:
        """StableHLO of the newest registered program called ``name``,
        lowered again from the argument templates it was registered
        with.  Lets a caller that did not build the program read what
        went to the compiler: whether the Pallas kernel is in the
        trainer step (``tpu_custom_call``), whether the collectives are
        (``all_reduce``)."""
        with self._lock:
            pdef = next((e.pdef for e in reversed(self._store.values())
                         if e.name == name and e.pdef is not None), None)
        if pdef is None:
            raise KeyError(f"no program named {name!r} is registered "
                           f"with a definition")
        return pdef.builder().lower(*pdef.args).as_text()

    # -- registration / acquisition ---------------------------------------

    def register(self, pdef: ProgramDef) -> str:
        """Record ``pdef``'s key without building; returns the key hash.
        The audit gate uses this to reconcile the auditor's key set with
        the registry's without compiling anything."""
        _canon, key_hash = pdef.key()
        with self._lock:
            self._register_locked(key_hash, pdef)
        return key_hash

    def _register_locked(self, key_hash: str, pdef: ProgramDef) -> None:
        ent = self._store.get(key_hash)
        if ent is None:
            self._store[key_hash] = _Entry(pdef=pdef, name=pdef.name,
                                           family=pdef.family)
            self._evict_over_capacity_locked(protect=key_hash)
        elif ent.pdef is None:
            ent.pdef = pdef

    def acquire(self, pdef: ProgramDef, *, eager: bool = False,
                pin_owner: Optional[object] = None) -> Program:
        """Handle for ``pdef``'s program.  ``eager=True`` builds before
        returning (single-flight).  ``pin_owner`` pins the entry against
        capacity eviction for the owner's lifetime (released by a
        weakref finalizer when the owner is collected).  Registration,
        pin and eviction happen atomically, so a pinned acquire into a
        fully-pinned store runs the store over capacity instead of
        evicting the program it is about to hand out."""
        _canon, key_hash = pdef.key()
        with self._lock:
            self._register_locked(key_hash, pdef)
            self._store.move_to_end(key_hash)
            if pin_owner is not None:
                self._pin_locked(key_hash, pin_owner)
            self._evict_over_capacity_locked(protect=key_hash)
        h = Program(self, key_hash, pdef.name)
        if eager:
            h.ensure()
        return h

    def pin(self, key_hash: str, owner: Optional[object] = None) -> None:
        with self._lock:
            self._pin_locked(key_hash, owner)

    def unpin(self, key_hash: str) -> None:
        with self._lock:
            ent = self._store.get(key_hash)
            if ent is not None and ent.pins > 0:
                ent.pins -= 1

    def _pin_locked(self, key_hash: str, owner: Optional[object]) -> None:
        ent = self._store[key_hash]
        ent.pins += 1
        if owner is not None:
            import weakref
            weakref.finalize(owner, self.unpin, key_hash)

    # -- build path --------------------------------------------------------

    def _is_built(self, key_hash: str) -> bool:
        with self._lock:
            ent = self._store.get(key_hash)
            return ent is not None and ent.fn is not None

    def _ensure_built(self, key_hash: str) -> Tuple[Callable, bool]:
        """Returns ``(callable, built_now)`` — ``built_now`` is True
        only for the one caller whose invocation actually ran the
        build (joiners and hits get False)."""
        with self._lock:
            ent = self._store.get(key_hash)
            if ent is None:
                raise KeyError(
                    f"program {key_hash} was evicted before it was "
                    f"built — re-acquire it from its ProgramDef")
            if ent.fn is not None:
                self._hits += 1
                self._store.move_to_end(key_hash)
                return ent.fn, False
            if ent.pdef is None:
                raise KeyError(
                    f"program {key_hash} ({ent.name}) was registered "
                    f"key-only — acquire it with a full ProgramDef")
            build_lock, pdef = ent.build_lock, ent.pdef
        with build_lock:                       # single flight
            with self._lock:
                if ent.fn is not None:
                    self._hits += 1
                    return ent.fn, False
            fn, compiled, disk_hit, dt = self._build(pdef)
            with self._lock:
                ent.fn = fn
                self._builds += 1
                self._xla_compiles += int(compiled)
                self._disk_hits += int(disk_hit)
                self._compile_seconds += dt
            return fn, True

    def _build(self, pdef: ProgramDef
               ) -> Tuple[Callable, bool, bool, float]:
        """Build + (optionally) AOT-compile one program under the global
        compile lock.  Returns ``(callable, ran_xla, disk_hit,
        seconds)``.  A failed AOT compile with the disk tier enabled is
        retried once with the persistent cache bypassed — a corrupt or
        stale disk entry must degrade to a fresh compile with a warning,
        never a crash."""
        with _COMPILE_LOCK:
            h0, m0 = _disk_events()
            t0 = time.perf_counter()
            fn = pdef.builder()
            if pdef.aot and hasattr(fn, "lower"):
                try:
                    fn = fn.lower(*pdef.args).compile()
                except Exception as e:  # noqa: BLE001 — see docstring
                    if not _LISTENER_INSTALLED:
                        raise
                    warnings.warn(
                        f"program registry: AOT compile of {pdef.name} "
                        f"failed ({type(e).__name__}: {e}); retrying "
                        f"with the persistent compile cache bypassed")
                    import jax
                    jax.config.update("jax_enable_compilation_cache",
                                      False)
                    try:
                        fn = pdef.builder().lower(*pdef.args).compile()
                    finally:
                        jax.config.update("jax_enable_compilation_cache",
                                          True)
            dt = time.perf_counter() - t0
            h1, m1 = _disk_events()
        if h1 == h0 and m1 == m0:
            # no persistent cache consulted (disk tier off, or aot=False
            # deferring the compile to first dispatch): count the build
            # as a compile — without a disk tier every build is one
            return fn, True, False, dt
        disk_hit = h1 > h0 and m1 == m0
        return fn, not disk_hit, disk_hit, dt

    # -- tracked (non-owned) programs --------------------------------------

    def track_jit(self, name: str, config: Dict[str, Any],
                  donate_args: Tuple[int, ...], fn: Callable,
                  family: str = "") -> Callable:
        """Register a jitted callable the registry cannot AOT-compile
        (the trainer step: its avals exist only at the first dispatch
        and it must trace under the runtime's mesh context).  The
        wrapper computes the canonical key from the FIRST call's live
        avals — so the key matches what the auditor computes from
        templates — and attributes that call's compile to the registry
        counters (build + xla-compile-or-disk-hit + seconds)."""
        state: Dict[str, Any] = {"first": True}
        tracker_lock = threading.Lock()

        def wrapped(*args):
            if not state["first"]:
                tid = threading.get_ident()
                _INFLIGHT[tid] = name
                try:
                    return fn(*args)
                finally:
                    _INFLIGHT.pop(tid, None)
            with tracker_lock:
                if not state["first"]:
                    tid = threading.get_ident()
                    _INFLIGHT[tid] = name
                    try:
                        return fn(*args)
                    finally:
                        _INFLIGHT.pop(tid, None)
                # key from aval TEMPLATES, not the live arrays: the
                # registry holds the ProgramDef for its lifetime, and
                # storing the first call's arguments would pin a full
                # copy of the training state (GBs at real sizes) in the
                # process-global registry forever. program_key reads
                # only shape/dtype, so templates key identically.
                import jax
                import numpy as _np
                args_tpl = tuple(
                    jax.tree.map(
                        lambda l: jax.ShapeDtypeStruct(
                            tuple(getattr(l, "shape", ())),
                            _np.dtype(getattr(l, "dtype", _np.float32))),
                        a) for a in args)
                pdef = ProgramDef(
                    name=name, family=family or name.split("[")[0],
                    config=config, args=args_tpl,
                    donate_args=donate_args,
                    builder=lambda: fn, aot=False)
                key_hash = self.register(pdef)
                with _COMPILE_LOCK:
                    h0, m0 = _disk_events()
                    t0 = time.perf_counter()
                    tid = threading.get_ident()
                    _INFLIGHT[tid] = name
                    try:
                        out = fn(*args)
                    finally:
                        _INFLIGHT.pop(tid, None)
                    dt = time.perf_counter() - t0
                    h1, m1 = _disk_events()
                with self._lock:
                    ent = self._store.get(key_hash)
                    if ent is not None:
                        ent.fn = fn
                    self._builds += 1
                    disk_hit = h1 > h0 and m1 == m0
                    self._disk_hits += int(disk_hit)
                    self._xla_compiles += int(not disk_hit)
                    self._compile_seconds += dt
                state["first"] = False
                return out

        wrapped.lower = getattr(fn, "lower", None)  # HLO-inspection tests
        return wrapped

    # -- eviction ----------------------------------------------------------

    def _evict_over_capacity_locked(self,
                                    protect: Optional[str] = None) -> None:
        """LRU-evict UNPINNED entries past capacity.  Pinned (in-use)
        programs, the key being registered right now (``protect``) and
        entries whose build is IN FLIGHT (build_lock held — evicting
        one would detach the building thread's _Entry and hand a second
        acquirer a fresh entry, duplicating the compile and crashing
        joiners with KeyError) are never evicted — if everything is
        held the store runs over capacity rather than dropping a live
        program."""
        while len(self._store) > self.capacity:
            victim = None
            for k, e in self._store.items():          # oldest first
                if (e.pins == 0 and k != protect
                        and not e.build_lock.locked()):
                    victim = k
                    break
            if victim is None:
                return
            del self._store[victim]
            self._evictions += 1


# -- in-flight dispatch tracking -------------------------------------------

#: thread ident -> program name for every registry-dispatched program
#: currently executing. Single dict ops (GIL-atomic) on the hot path —
#: no lock. Read by the watchdog's stack dump so a wedged dispatch
#: names the SPECIFIC compiled program, not just "inside jax".
_INFLIGHT: Dict[int, str] = {}


def inflight_programs() -> Dict[int, str]:
    """Snapshot of registry programs currently executing, keyed by
    thread ident. Empty when nothing is dispatching."""
    return dict(_INFLIGHT)


# -- module-level default registry ----------------------------------------

_DEFAULT = ProgramRegistry()


def default_registry() -> ProgramRegistry:
    """The process-wide registry every engine/trainer/server shares —
    program reuse across replicas, rebuilds and hot-swaps depends on
    them all resolving the same store."""
    return _DEFAULT


def compile_counter() -> int:
    """Monotonic count of in-memory program BUILDS in the default
    registry — the shared instrumentation probe replacing the old
    per-builder ``lru_cache.cache_info().misses`` sums.  A delta of 0
    across an operation means it was served entirely by already-built
    programs (the zero-recompile seams: supervisor failover, fleet
    hot-swap, trainer→server handoff)."""
    return _DEFAULT.counters()["builds"]


def xla_compile_counter() -> int:
    """Monotonic count of builds that actually ran XLA (disk-tier hits
    excluded) — the restart drill's ``programs_compiled`` observable."""
    return _DEFAULT.counters()["xla_compiles"]
