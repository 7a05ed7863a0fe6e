"""The unified device-program registry (ISSUE 9).

Pins the registry's three perf layers and the zero-recompile seams:

- **single-flight** — N threads requesting one key run exactly ONE
  build; the rest block on the per-key build lock and share the result.
- **bounded capacity, pinned programs safe** — LRU eviction only ever
  takes UNPINNED entries; an engine's pins are released by weakref when
  the engine dies, never while it could still dispatch.
- **corrupt/stale disk tier degrades, never crashes** — a failed AOT
  compile with the persistent cache enabled is retried once with the
  cache bypassed, surfacing a warning and a fresh executable.
- **one key function** — the jaxpr auditor's serve key set and the
  registry's key set are the same set (the CI gate
  ``registry_key_reconciliation`` asserts in ``python -m
  gym_tpu.analysis``).
- **zero-recompile seams** — trainer→server handoff in-process (the
  supervisor-failover and fleet hot-swap seams live in
  ``test_serve_chaos.py`` / ``test_serve_fleet.py``) and the
  process-restart cold start with a warm disk tier (subprocess:
  ``xla_compiles == 0`` on the second run).
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.programs import (ProgramDef, ProgramRegistry, WarmupThread,
                              compile_counter, default_registry,
                              program_key, warm_engine_programs)
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import RequestStatus, Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESTART_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "_programs_restart_worker.py")


def _fake_def(name, calls, config=None, fail_first=False):
    """A ProgramDef whose builder is pure host python (no XLA): builds
    are observable via ``calls`` and run in microseconds."""
    def builder():
        calls.append(name)
        time.sleep(0.005)        # widen the race window for the threads
        return lambda *a: (name, len(calls))

    return ProgramDef(name=name, family=name.split("[")[0],
                      config=config or {"n": name}, args=(),
                      donate_args=(), builder=builder)


# -- keys ------------------------------------------------------------------


def test_program_key_deterministic_and_donation_sensitive():
    tpl = jax.ShapeDtypeStruct((4, 8), np.float32)
    canon_a, ha = program_key("p", {"k": 1}, (tpl,), (0,))
    canon_b, hb = program_key("p", {"k": 1}, (tpl,), (0,))
    assert (canon_a, ha) == (canon_b, hb)
    # donation mask, config and avals each change the key — these are
    # exactly the silent-recompile axes the registry keys on
    assert program_key("p", {"k": 1}, (tpl,), ())[1] != ha
    assert program_key("p", {"k": 2}, (tpl,), (0,))[1] != ha
    tpl16 = jax.ShapeDtypeStruct((4, 8), np.float16)
    assert program_key("p", {"k": 1}, (tpl16,), (0,))[1] != ha


# -- single flight ---------------------------------------------------------


def test_n_threads_one_key_exactly_one_build():
    reg = ProgramRegistry()
    calls = []
    pdef = _fake_def("t.sf", calls)
    n = 8
    barrier = threading.Barrier(n)
    results = []

    def worker():
        barrier.wait()
        h = reg.acquire(pdef)
        results.append(h.ensure()())

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert calls == ["t.sf"]                  # exactly one build
    assert len(set(results)) == 1             # everyone shares it
    c = reg.counters()
    assert c["builds"] == 1
    assert c["hits"] == n - 1                 # the other N-1 joined


def test_eager_acquire_and_handle_caching():
    reg = ProgramRegistry()
    calls = []
    h = reg.acquire(_fake_def("t.eager", calls), eager=True)
    assert calls == ["t.eager"] and h.built
    h()                                       # hot path: no registry hit
    hits0 = reg.counters()["hits"]
    h()
    assert reg.counters()["hits"] == hits0


# -- eviction / pinning ----------------------------------------------------


class _Owner:
    """weakref-able stand-in for the engine that pins its programs."""


def test_eviction_never_evicts_pinned_in_use():
    reg = ProgramRegistry(capacity=2)
    calls = []
    owner = _Owner()
    ha = reg.acquire(_fake_def("t.a", calls), eager=True,
                     pin_owner=owner)
    reg.acquire(_fake_def("t.b", calls), eager=True)
    reg.acquire(_fake_def("t.c", calls), eager=True)   # over capacity
    names = set(reg.keys().values())
    assert "t.a" in names                     # pinned survived
    assert "t.b" not in names                 # oldest unpinned evicted
    assert reg.counters()["evictions"] == 1
    assert ha()[0] == "t.a"                   # still dispatchable

    # everything pinned: the store runs OVER capacity rather than
    # dropping a live program
    o2, o3 = _Owner(), _Owner()
    reg.acquire(_fake_def("t.c", calls), pin_owner=o2)
    reg.acquire(_fake_def("t.d", calls), eager=True, pin_owner=o3)
    assert len(reg) == 3 and reg.counters()["evictions"] == 1

    # a dead owner releases its pin (weakref finalizer) — the entry
    # becomes evictable again
    del o3
    import gc
    gc.collect()
    reg.acquire(_fake_def("t.e", calls), eager=True)
    assert "t.d" not in set(reg.keys().values())


def test_evicted_unbuilt_handle_raises_keyerror():
    reg = ProgramRegistry(capacity=1)
    calls = []
    h = reg.acquire(_fake_def("t.x", calls))          # registered, unbuilt
    reg.acquire(_fake_def("t.y", calls), eager=True)  # evicts t.x
    with pytest.raises(KeyError, match="evicted"):
        h.ensure()


# -- corrupt / stale disk tier ---------------------------------------------


def test_corrupt_disk_entry_falls_back_with_warning(monkeypatch):
    """A persisted executable that fails to deserialize (corrupt/stale
    cache entry → the AOT compile raises) degrades to ONE retry with
    the persistent cache bypassed — a warning and a fresh compile,
    never a crash."""
    from gym_tpu.programs import registry as regmod
    monkeypatch.setattr(regmod, "_LISTENER_INSTALLED", True)

    calls = {"n": 0}

    class _CorruptLowered:
        def lower(self, *a):
            raise RuntimeError("deserialization failed: corrupt entry")

    def builder():
        calls["n"] += 1
        if calls["n"] == 1:
            return _CorruptLowered()
        return jax.jit(lambda x: x + 1)

    pdef = ProgramDef(
        name="t.corrupt", family="t", config={},
        args=(jax.ShapeDtypeStruct((2,), np.float32),),
        donate_args=(), builder=builder)
    reg = ProgramRegistry()
    with pytest.warns(UserWarning, match="persistent compile cache "
                                         "bypassed"):
        h = reg.acquire(pdef, eager=True)
    assert calls["n"] == 2                    # original + bypass retry
    np.testing.assert_allclose(
        np.asarray(h(jnp.ones((2,), jnp.float32))), 2.0)
    # the bypass retry must re-enable the persistent cache afterwards
    assert jax.config.jax_enable_compilation_cache


def test_corrupt_entry_without_disk_tier_raises(monkeypatch):
    """Without the disk tier there is nothing to bypass: a failing
    build surfaces (a broken builder must not be silently retried)."""
    from gym_tpu.programs import registry as regmod
    monkeypatch.setattr(regmod, "_LISTENER_INSTALLED", False)

    class _Broken:
        def lower(self, *a):
            raise RuntimeError("boom")

    pdef = ProgramDef(name="t.broken", family="t", config={},
                      args=(jax.ShapeDtypeStruct((2,), np.float32),),
                      donate_args=(), builder=lambda: _Broken())
    with pytest.raises(RuntimeError, match="boom"):
        ProgramRegistry().acquire(pdef, eager=True)


# -- track_jit (trainer-path programs) -------------------------------------


def test_track_jit_registers_and_attributes_first_call():
    reg = ProgramRegistry()
    fn = jax.jit(lambda x: x * 2)
    wrapped = reg.track_jit("t.step[x2]", {"lr": 0.1}, (0,), fn,
                            family="t.step")
    out = wrapped(jnp.arange(3.0))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0])
    c = reg.counters()
    assert c["builds"] == 1 and c["compile_seconds"] > 0
    assert "t.step[x2]" in set(reg.keys().values())
    wrapped(jnp.arange(3.0))                  # steady state: no tracking
    assert reg.counters()["builds"] == 1


# -- warmup ----------------------------------------------------------------


def test_warmup_thread_builds_all_and_single_flights_with_requests():
    reg = ProgramRegistry()
    calls = []
    defs = [_fake_def(f"t.w[{i}]", calls) for i in range(6)]
    t = WarmupThread(defs, registry=reg)
    t.start()
    # a "request" racing the warmup joins the build instead of doubling
    reg.acquire(defs[3]).ensure()
    assert t.wait(timeout=30)
    assert t.stats()["warmed"] == 6 and t.stats()["done"]
    assert sorted(calls) == sorted(f"t.w[{i}]" for i in range(6))
    assert reg.counters()["builds"] == 6      # nothing compiled twice


def test_warmup_survives_builder_failure():
    reg = ProgramRegistry()
    calls = []
    bad = ProgramDef(name="t.bad", family="t", config={}, args=(),
                     donate_args=(),
                     builder=lambda: (_ for _ in ()).throw(
                         RuntimeError("builder exploded")))
    logs = []
    t = WarmupThread([_fake_def("t.ok", calls), bad],
                     registry=reg, log=logs.append)
    t.start()
    assert t.wait(timeout=30)
    assert t.stats()["warmed"] == 1
    assert any("aborted" in line for line in logs)


# -- engine warmup covers the full traffic path ----------------------------


@pytest.fixture(scope="module")
def tiny_serving():
    cfg = GPTConfig(block_size=32, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]
    return cfg, params


def _serve(eng, workload):
    sched = Scheduler(eng, max_queue=len(workload))
    handles = [sched.submit(p, sp) for p, sp in workload]
    for _ in range(5000):
        if all(h.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for h in handles):
            break
        sched.step()
    for h in handles:
        assert len(h.result(timeout=5)) == h.sampling.max_new_tokens
    return handles


def test_warmed_engine_serves_with_zero_builds(tiny_serving):
    """After background warmup finishes, NO request — any prompt
    length, any sampling — triggers a build: the ≤⌈log2(block)⌉+1
    compile bound is paid entirely off the request path (the cold-p99
    TTFT mechanism, pinned here structurally; its seconds on the chip
    are a cell's ``setup_s`` and ``compile_s``, PERF.md)."""
    cfg, params = tiny_serving
    eng = InferenceEngine(params, cfg, num_slots=2, decode_chunk=2)
    warm = warm_engine_programs(eng, start=True)
    assert warm.wait(timeout=600)
    st = warm.stats()
    bound = (cfg.block_size - 1).bit_length() + 1
    # prefill buckets + decode + admit + the chunk-1 decode twin
    assert st["warmed"] == st["total"] == bound + 3
    builds0 = compile_counter()
    rng = np.random.default_rng(0)
    workload = [
        (rng.integers(0, cfg.vocab_size, n),
         SamplingParams(max_new_tokens=3, temperature=0.9, top_k=8,
                        seed=n))
        for n in (1, 2, 5, 9, 17, 29)]                # every bucket
    # (29 + 3 new tokens fills block_size exactly; 29 still buckets
    # to the top power-of-two prefill program)
    _serve(eng, workload)
    assert compile_counter() == builds0
    assert eng.stats.prefill_compiles == 0


# -- seam 1: trainer→server handoff (in-process) ---------------------------


@pytest.mark.slow
def test_trainer_to_server_handoff_zero_recompile(tmp_path):
    """One process, one registry: a tiny ``fit`` registers its step
    programs next to the serving programs; the server stack built from
    the trained params serves, and REBUILDING it (the restore/handoff
    path) triggers zero new builds — the warm handoff ROADMAP item 3
    promises, pinned on the shared counter."""
    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.strategy import OptimSpec, SimpleReduceStrategy

    cfg = GPTConfig(block_size=32, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 48, (32, 33))
    ds = ArrayDataset(toks[:, :-1].astype(np.int64),
                      toks[:, 1:].astype(np.int64))
    res = Trainer(GPT(cfg), ds).fit(
        strategy=SimpleReduceStrategy(OptimSpec("adamw", lr=1e-3)),
        num_nodes=1, max_steps=2, batch_size=4, val_size=0,
        val_interval=0, show_progress=False, seed=1)
    names = set(default_registry().keys().values())
    assert any(n.startswith("trainer.step[") for n in names)

    workload = [(np.arange(1, 6), SamplingParams(max_new_tokens=4,
                                                 seed=7))]
    eng = InferenceEngine(res.params, cfg, num_slots=2)
    first = _serve(eng, workload)[0].result(timeout=5)
    builds0 = compile_counter()
    # the handoff/restore rebuild: same config, fresh engine
    eng2 = InferenceEngine(res.params, cfg, num_slots=2)
    second = _serve(eng2, workload)[0].result(timeout=5)
    assert compile_counter() == builds0       # zero-recompile handoff
    assert second == first                    # same params, same stream
    names = set(default_registry().keys().values())
    assert any(n.startswith("serve.prefill[") for n in names)


# -- seam 4: process restart with a warm disk tier -------------------------


@pytest.mark.slow
@pytest.mark.parametrize("warmup", [True, False],
                         ids=["warmed", "on_request_path"])
def test_process_restart_zero_xla_compiles(tmp_path, warmup):
    """The restart drill's pin, at the python level: two processes, same
    config, same program-cache dir. The first compiles and persists;
    the second — a server restart — reports ``xla_compiles == 0``:
    every program deserialized, zero XLA on the hot path. Warmed, the
    request meets no build at all; un-warmed it pays every build of its
    own, each a compile in the first process and a read in the second."""
    cache_dir = str(tmp_path / "progcache")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)               # plain 1-device subprocess
    # the worker's argument names the directory: the variable would win
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def run():
        p = subprocess.run([sys.executable, RESTART_WORKER, cache_dir]
                           + ([] if warmup else ["nowarm"]),
                           env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["xla_compiles"] == cold["counters"]["builds"] > 0
    assert cold["counters"]["disk_hits"] == 0
    warm = run()
    assert warm["xla_compiles"] == 0          # the acceptance pin
    assert warm["counters"]["disk_hits"] == warm["counters"]["builds"] \
        == cold["counters"]["builds"]
    assert warm["tokens"] == cold["tokens"]   # same executables, bitwise
    # the deserializing restart is also measurably cheaper
    assert (warm["counters"]["compile_seconds"]
            < cold["counters"]["compile_seconds"])
    for regime in (cold, warm):
        assert regime["on_path_builds"] == (
            0 if warmup else regime["counters"]["builds"])


# -- satellite: generate_fast cache collision audit ------------------------


def test_generate_fast_cache_distinguishes_configs():
    """Two configs with IDENTICAL param trees and arg shapes (only
    ``n_head`` differs — the pure-static knob) must occupy two distinct
    ``_cached_decode_program`` entries: the maxsize=32 cache keys on
    the full config astuple, so a cross-config collision — the one
    failure its lru key could silently produce — is impossible."""
    from gym_tpu.models.nanogpt import _cached_decode_program, \
        generate_fast

    cfg_a = GPTConfig(block_size=16, vocab_size=32, n_layer=1, n_head=2,
                      n_embd=16, dropout=0.0)
    cfg_b = dataclasses.replace(cfg_a, n_head=4)   # same param shapes
    model = GPT(cfg_a)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 4), np.int64),
                        train=False)["params"]
    prompt = np.arange(1, 5)[None]
    misses0 = _cached_decode_program.cache_info().misses
    out_a = generate_fast(params, cfg_a, prompt, 3, seed=0)
    out_b = generate_fast(params, cfg_b, prompt, 3, seed=0)
    assert _cached_decode_program.cache_info().misses == misses0 + 2
    assert out_a.shape == out_b.shape == (1, 7)
    # and a same-config repeat is a hit, not a third entry
    generate_fast(params, cfg_a, prompt, 3, seed=0)
    assert _cached_decode_program.cache_info().misses == misses0 + 2
