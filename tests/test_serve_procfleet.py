"""Out-of-process fleet (ISSUE 13): REAL worker subprocesses behind the
socket-speaking ``ProcessRouter``.

Acceptance oracles pinned here:

- **streaming exact-stream** — a streamed request through a worker
  subprocess concatenates byte-identical to ``generate_fast``.
- **kill -9 splice oracle** — SIGKILL the worker process serving a
  stream after >= 4 tokens reached the client: the router re-dispatches
  with the delivered prefix, the sibling re-derives + suppresses it,
  and the CONCATENATED client stream is byte-identical to an
  uncontended run, inside the original deadline. ``scale_up`` (the
  autoscaler's respawn) restores the fleet and the dead worker leaves
  no zombie.
- **one shared fleet fixture** — workers cost a jax import each; the
  module spawns exactly one 2-worker fleet and the kill test runs LAST
  (ordering matters: ``-p no:randomly``, the repo-wide convention).
"""

import os
import signal
import tempfile
import time

import numpy as np
import pytest

import jax

from gym_tpu.models.nanogpt import GPT, GPTConfig, generate_fast
from gym_tpu.serve.engine import SamplingParams
from gym_tpu.serve.metrics import ServeMetrics
from gym_tpu.serve.router import build_process_fleet


@pytest.fixture(scope="module")
def fleet():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64),
                        train=False)["params"]
    metrics = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_pfm_"))
    router = build_process_fleet(
        params, cfg, tempfile.mkdtemp(prefix="gym_tpu_pf_"),
        replicas=2, num_slots=2, metrics=metrics, no_warmup=True,
        max_restarts=0, log=lambda *a, **k: None)
    router.start()
    router.wait_ready(timeout_s=240)
    yield cfg, params, router, metrics
    assert router.close(drain_deadline_s=60) is True
    metrics.close()
    # no zombies: every spawned worker pid is gone (or reaped)
    for rep in router.replicas:
        if rep.proc is not None:
            assert rep.proc.poll() is not None, \
                f"worker {rep.id} (pid {rep.pid}) still running"


def _ref(params, cfg, prompt, n, **kw):
    return generate_fast(params, cfg, np.asarray(prompt)[None], n,
                         **kw)[0, len(prompt):].tolist()


def test_proc_stream_exact_and_result_surface(fleet):
    cfg, params, router, _m = fleet
    prompt = [1, 2, 3, 4, 5, 6]
    ref = _ref(params, cfg, prompt, 16, temperature=0.9, top_k=7,
               seed=3)
    pr = router.submit(prompt, SamplingParams(
        max_new_tokens=16, temperature=0.9, top_k=7, seed=3))
    got, chunks = [], 0
    for chunk in pr.stream(timeout=120):
        got.extend(chunk)
        chunks += 1
    assert got == ref
    assert chunks > 1
    assert pr.tokens == ref
    assert pr.ttft_s is not None and pr.ttft_s > 0
    assert pr.done_t is not None
    # buffered surface too (a second request; results are one-shot)
    pr2 = router.submit(prompt, SamplingParams(
        max_new_tokens=16, temperature=0.9, top_k=7, seed=3))
    assert pr2.result(timeout=120) == ref


def test_health_reports_pids_and_load_observables(fleet):
    _cfg, _params, router, _m = fleet
    deadline = time.monotonic() + 30
    st = router.status()
    while time.monotonic() < deadline:
        st = router.status()
        live = [r for r in st["replicas"] if not r["retired"]]
        if all(r.get("pid") and "backlog_tokens" in r for r in live):
            break
        time.sleep(0.2)
    live = [r for r in st["replicas"] if not r["retired"]]
    assert st["healthy_replicas"] >= 2
    pids = {r["pid"] for r in live}
    assert len(pids) == len(live)            # distinct real processes
    assert os.getpid() not in pids           # none of them is us
    for r in live:
        assert r["programs_compiled"] is not None
        assert "tokens_per_s_ewma" in r
    snap = router.autoscale_snapshot()
    assert snap["healthy"] >= 2
    assert "backlog_tokens" in snap and "tokens_per_s" in snap


def test_health_carries_the_samplers_sorted_steps(fleet):
    """The worker's health frame has ``sampler_sorted_steps`` beside
    ``decode_steps``: a ``top_k = 7`` request of 8 tokens is 7 decode
    steps whose sampler sorted, on the worker that served it."""
    cfg, params, router, _m = fleet

    def counted():
        live = [r for r in router.status()["replicas"] if not r["retired"]]
        assert all(0 <= r["sampler_sorted_steps"] <= r["decode_steps"]
                   for r in live)
        return sum(r["sampler_sorted_steps"] for r in live)

    # nothing is running: let the frames of earlier requests come in (one
    # a worker every half second)
    before = counted()
    for _ in range(3):
        time.sleep(1.2)
        before, was = counted(), before
        if before == was:
            break
    prompt = [2, 4, 6, 8]
    pr = router.submit(prompt, SamplingParams(
        max_new_tokens=8, temperature=0.9, top_k=7, seed=4))
    assert pr.result(timeout=120) == _ref(params, cfg, prompt, 8,
                                          temperature=0.9, top_k=7, seed=4)
    deadline = time.monotonic() + 30
    while counted() < before + 7 and time.monotonic() < deadline:
        time.sleep(0.2)
    assert counted() == before + 7


def test_proc_reload_rolls_through_workers(fleet):
    """Rolling hot-swap across the process boundary: both workers
    drain, rebuild from the new snapshot, and post-swap generations
    come from the NEW params exactly."""
    cfg, params, router, _m = fleet
    model = GPT(cfg)
    params_b = model.init({"params": jax.random.PRNGKey(7)},
                          np.zeros((1, 8), np.int64),
                          train=False)["params"]
    prompt = [1, 2, 3, 4]
    ref_b = _ref(params_b, cfg, prompt, 8, temperature=0.9, top_k=7,
                 seed=2)
    res = router.reload(params_b, weights_tag="v2",
                        drain_timeout_s=120.0)
    assert sorted(res["swapped"]) == sorted(
        r.id for r in router.replicas if r.healthy)
    # both replicas serve the new params (pin each one via dispatch)
    outs = []
    for seed_probe in range(4):
        pr = router.submit(prompt, SamplingParams(
            max_new_tokens=8, temperature=0.9, top_k=7, seed=2))
        outs.append((pr.replica_id, pr.result(timeout=120)))
    assert {rid for rid, _ in outs} == {
        r.id for r in router.replicas if r.healthy}
    for _rid, toks in outs:
        assert toks == ref_b
    assert router.status()["weight_reloads"] == 1


def test_kill9_mid_stream_splices_exact_and_respawns(fleet):
    """THE ISSUE-13 acceptance oracle, process edition: SIGKILL the
    worker subprocess serving a stream once >= 4 tokens have reached
    the client — the concatenated stream is byte-identical to an
    uncontended run, delivered inside the original deadline; the dead
    process leaves dispatch (and no zombie), and a ``scale_up``
    respawn (the autoscaler's move) restores the fleet."""
    cfg, params, router, metrics = fleet
    prompt = [1, 2, 3, 4, 5, 6]
    # 48 tokens, UNcoalesced chunks (one frame per decode step), kill
    # on the FIRST chunk: the worker dies with ~47 tokens ungenerated —
    # a warm worker can never outrun the kill into a no-op splice
    sp = SamplingParams(max_new_tokens=48, temperature=0.9, top_k=7,
                        seed=5)
    # reload (previous test) swapped to params_b — regenerate the
    # reference from what the fleet NOW serves: what matters is the
    # splice, not which weights
    model = GPT(cfg)
    params_b = model.init({"params": jax.random.PRNGKey(7)},
                          np.zeros((1, 8), np.int64),
                          train=False)["params"]
    ref = _ref(params_b, cfg, prompt, 48, temperature=0.9, top_k=7,
               seed=5)
    pr = router.submit(prompt, sp, deadline_s=120.0, coalesce_s=0.0)
    victim_pid, victim_rid = pr.pid, pr.replica_id
    got, killed = [], False
    t0 = time.perf_counter()
    for chunk in pr.stream(timeout=120):
        got.extend(chunk)
        if not killed:
            os.kill(victim_pid, signal.SIGKILL)
            killed = True
    wall = time.perf_counter() - t0
    assert killed, "stream finished before the kill landed"
    assert got == ref                       # byte-identical splice
    assert wall < 120.0                     # inside the deadline
    assert pr.failovers == 1
    assert pr.replica_id != victim_rid
    st = router.status()
    assert st["failovers"] >= 1
    victim = next(r for r in st["replicas"] if r["id"] == victim_rid)
    assert victim["dead"] is True and victim["healthy"] is False
    # the corpse is reaped (no zombie) once the router notices
    deadline = time.monotonic() + 30
    vrep = next(r for r in router.replicas if r.id == victim_rid)
    while time.monotonic() < deadline and vrep.proc.poll() is None:
        time.sleep(0.2)
    assert vrep.proc.poll() is not None
    # respawn — exactly what the autoscaler's floor rule does
    router.scale_up()
    router.wait_ready(n=2, timeout_s=240)
    st = router.status()
    assert st["healthy_replicas"] == 2
    assert st["replicas_spawned"] == 3      # 2 initial + 1 respawn
    assert metrics.headline()["replicas_spawned"] == 3
    # and the respawned fleet still serves exact streams
    pr = router.submit(prompt, sp)
    assert pr.result(timeout=120) == ref
