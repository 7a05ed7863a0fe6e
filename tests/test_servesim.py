"""Trace-driven serving simulator (ISSUE 15): traces, replay,
cost-model policy invariants, the serve.csv schema satellites, and a
sim-vs-live agreement smoke.

Acceptance oracles pinned here:

- trace generators are SEEDED and bit-reproducible; the on-disk format
  roundtrips exactly (a trace is an artifact both simulator arms must
  agree on).
- the replayer is OPEN-LOOP: a slow server does not slow the offered
  arrival process (non-coordinated omission).
- the cost model runs the REAL ``AutoscaleController`` and honors its
  contract under generated traffic: scale-up latency bounded by
  patience × interval, never below the floor, cooldown respected.
- ``serve.csv`` satellites: request rows carry ``t_submit`` (arrival
  process reconstructible from disk), autoscale ticks persist as audit
  rows, and ``read_headline`` stays tolerant of OLD headers — pinned
  against a hand-written pre-servesim CSV.
- sim-vs-live agreement: the cost model's report against a real
  single-replica fleet replay of the same trace, on one small feasible
  trace and (``slow``: ``scripts/ci_deploy.sh`` selects it by name) on
  the flash-crowd overload trace with the gate's own tolerances.
"""

import csv
import os
import tempfile
import time

import numpy as np
import pytest

from gym_tpu.serve.autoscale import AutoscaleController, AutoscalePolicy
from gym_tpu.serve.metrics import ServeMetrics, read_headline
from gym_tpu.servesim import (FleetCostModel, Outcome, RequestEvent,
                              ServiceProfile, bursty_trace,
                              diurnal_trace, flash_crowd_trace,
                              load_trace, make_trace, prompt_tokens,
                              replay, replay_from_serve_csv, save_trace,
                              slo_report, trace_stats)

# ---------------------------------------------------------------------------
# traces


def test_traces_seeded_and_roundtrip(tmp_path):
    a = diurnal_trace(duration_s=20, base_rps=3.0, seed=7)
    b = diurnal_trace(duration_s=20, base_rps=3.0, seed=7)
    c = diurnal_trace(duration_s=20, base_rps=3.0, seed=8)
    assert a == b                      # same seed, same trace, exactly
    assert a != c
    path = str(tmp_path / "t.csv")
    assert load_trace(save_trace(path, a)) == a     # exact roundtrip
    # a non-trace CSV is refused, not misparsed
    bad = str(tmp_path / "bad.csv")
    with open(bad, "w") as f:
        f.write("x,y\n1,2\n")
    with pytest.raises(ValueError, match="not a gym_tpu trace"):
        load_trace(bad)


def test_trace_families_shape():
    for family in ("diurnal", "bursty", "flash_crowd"):
        ev = make_trace(family, seed=1, duration_s=30,
                        deadline_s=5.0, deadline_frac=0.5,
                        prefix_groups=3)
        st = trace_stats(ev)
        assert st["requests"] > 10, (family, st)
        assert 0 < st["with_deadline"] < st["requests"]
        assert st["prefix_grouped"] > 0
        assert all(e.arrival_s >= 0 for e in ev)
        assert ev == sorted(ev, key=lambda e: e.arrival_s)
    # the flash visibly lifts the rate inside its window
    fl = flash_crowd_trace(duration_s=40, base_rps=1.0, flash_at_s=10,
                           flash_mult=10, flash_len_s=10, seed=2)
    inside = sum(1 for e in fl if 10 <= e.arrival_s < 20)
    outside = sum(1 for e in fl if e.arrival_s < 10)
    assert inside > 3 * max(1, outside)


def test_prefix_groups_share_prompt_prefix():
    e1 = RequestEvent(0.0, prompt_len=20, max_new=8, prefix_group=4,
                      seed=1)
    e2 = RequestEvent(1.0, prompt_len=16, max_new=8, prefix_group=4,
                      seed=2)
    e3 = RequestEvent(2.0, prompt_len=20, max_new=8, prefix_group=5,
                      seed=3)
    p1 = prompt_tokens(e1, 48)
    p2 = prompt_tokens(e2, 48)
    p3 = prompt_tokens(e3, 48)
    n = min(int(20 * 0.5), int(16 * 0.5))
    assert p1[:n].tolist() == p2[:n].tolist()     # same group: shared
    assert p1[:n].tolist() != p3[:n].tolist()     # different group
    # deterministic: the prompt is a pure function of the event
    assert prompt_tokens(e1, 48).tolist() == p1.tolist()


# ---------------------------------------------------------------------------
# replay


def test_replay_is_open_loop():
    """A slow client must not slow the arrival process: with 0.3s
    service and arrivals every 50ms, submits still land near their
    scheduled times (closed-loop would serialize to ~0.3s apart)."""
    events = [RequestEvent(i * 0.05, 4, 4, seed=i) for i in range(5)]
    t_subs = {}

    def client(ev, t0):
        t_subs[ev.seed] = time.perf_counter() - t0
        time.sleep(0.3)
        return Outcome(index=ev.seed, arrival_s=ev.arrival_s,
                       t_submit=t_subs[ev.seed], status="done",
                       tokens=ev.max_new, max_new=ev.max_new)

    outs = replay(events, client, time_scale=1.0)
    assert len(outs) == 5 and all(o.status == "done" for o in outs)
    # last arrival scheduled 0.2s in; open loop keeps it under ~0.5s
    # (closed loop would be >= 4 * 0.3 = 1.2s)
    assert t_subs[4] < 0.6, t_subs


def test_slo_report_counts_and_attainment():
    outs = [
        Outcome(0, 0.0, 0.0, "done", ttft_s=0.1, latency_s=0.5,
                tokens=8, max_new=8),
        Outcome(1, 0.1, 0.1, "done", ttft_s=2.0, latency_s=3.0,
                tokens=8, max_new=8),
        Outcome(2, 0.2, 0.2, "rejected", max_new=8),
        Outcome(3, 0.3, 0.3, "shed", tokens=2, max_new=8),
    ]
    rep = slo_report(outs, slo_ttft_s=1.0, replica_seconds=12.0,
                     wall_s=4.0)
    assert rep["requests"] == 4 and rep["done"] == 2
    assert rep["shed_rate"] == 0.5          # rejected + shed over 4
    assert rep["slo_attainment"] == 0.25    # only the 0.1s TTFT one
    assert rep["replica_seconds"] == 12.0
    assert rep["tokens_out"] == 18


# ---------------------------------------------------------------------------
# serve.csv satellites: t_submit + autoscale audit rows


class _FakeReq:
    def __init__(self, submit_t, tokens=4, prompt=4):
        self.id = 1
        self.error = None
        self.exception = None
        self.tokens = list(range(tokens))
        self.prompt = np.zeros(prompt, np.int32)
        self.submit_t = submit_t
        self.ttft_s = 0.05
        self.avg_token_latency_s = 0.01


def test_serve_csv_t_submit_and_autoscale_rows(tmp_path):
    m = ServeMetrics(str(tmp_path))
    # three requests submitted at known offsets from the collector's t0
    for dt in (0.5, 1.25, 2.0):
        m.request_done(_FakeReq(m._t0 + dt), queue_depth=0,
                       active_slots=1)
    m.autoscale_tick(healthy=1, starting=0, backlog_tokens=512.0,
                     tokens_per_s=100.0, decision=+1,
                     reason="up: drain_s=5.12 over for 2 tick(s)")
    m.autoscale_tick(healthy=2, starting=0, backlog_tokens=0.0,
                     tokens_per_s=200.0, decision=0,
                     reason="hold: drain_s=0.00 over=0/2 under=1/8")
    head_live = m.headline()
    m.close()
    assert head_live["autoscale"] == {"ticks": 2, "ups": 1, "downs": 0}

    path = os.path.join(str(tmp_path), "serve.csv")
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    req_rows = [r for r in rows if r["kind"] == "request"]
    subs = [float(r["t_submit"]) for r in req_rows]
    assert subs == pytest.approx([0.5, 1.25, 2.0], abs=0.01)
    as_rows = [r for r in rows if r["kind"] == "autoscale"]
    assert [r["status"] for r in as_rows] == ["up", "hold"]
    assert as_rows[0]["as_healthy"] == "1"
    assert as_rows[0]["as_backlog_tokens"] == "512.0"
    assert as_rows[0]["as_reason"].startswith("up:")
    assert as_rows[0]["tokens_per_s"] == "100.00"

    # read_headline folds the audit rows + ignores them as requests
    head = read_headline(path)
    assert head["requests_done"] == 3
    assert head["autoscale"] == {"ticks": 2, "ups": 1, "downs": 0}

    # and the trace satellite: arrivals reconstruct EXACTLY from
    # t_submit (normalized to the first arrival)
    tr = replay_from_serve_csv(path)
    assert [e.arrival_s for e in tr] == pytest.approx([0.0, 0.75, 1.5],
                                                      abs=0.01)
    assert all(e.max_new == 4 for e in tr)


def test_read_headline_tolerates_pre_servesim_header(tmp_path):
    """The schema-bump contract, pinned: a serve.csv written BEFORE the
    t_submit/autoscale columns existed still aggregates — and the trace
    replayer falls back to the completion stamp."""
    path = str(tmp_path / "old.csv")
    old_header = ["ts_s", "kind", "request_id", "status", "queue_depth",
                  "active_slots", "prompt_tokens", "new_tokens",
                  "ttft_s", "avg_token_latency_s", "cum_tokens",
                  "tokens_per_s", "kv_blocks_in_use",
                  "prefix_hit_blocks", "spec_accept_rate", "replica_id",
                  "programs_built", "programs_compiled",
                  "program_compile_s", "weights_dtype", "kv_dtype",
                  "pid"]
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(old_header)
        w.writerow(["1.0", "request", "0", "done", "0", "1", "4", "8",
                    "0.05", "0.01", "8", "8.0", "", "", "", "", "", "",
                    "", "", "", ""])
        w.writerow(["2.0", "request", "1", "shed", "0", "1", "4", "0",
                    "", "", "8", "4.0", "", "", "", "", "", "", "", "",
                    "", ""])
    head = read_headline(path)
    assert head["requests_done"] == 1
    assert head["requests_shed"] == 1
    assert "autoscale" not in head
    tr = replay_from_serve_csv(path)
    assert len(tr) == 2 and tr[1].arrival_s == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# controller reasons + cost-model policy invariants


def test_controller_reasons():
    c = AutoscaleController(AutoscalePolicy(
        min_replicas=1, max_replicas=4, up_patience=2, cooldown=3))
    assert c.tick(0, 0, 0.0, None) == 1
    assert c.last_reason.startswith("floor")
    assert c.tick(1, 0, 0.0, None) == 0
    assert c.last_reason.startswith("cooldown")
    c2 = AutoscaleController(AutoscalePolicy(
        min_replicas=1, max_replicas=4, up_patience=2, cooldown=0,
        up_drain_s=2.0, down_drain_s=0.5))
    assert c2.tick(1, 0, 1000.0, 100.0) == 0
    assert c2.last_reason.startswith("hold: drain_s=10.00")
    assert c2.tick(1, 0, 1000.0, 100.0) == 1
    assert c2.last_reason.startswith("up:")


_PROFILE = ServiceProfile(tokens_per_s=120.0, num_slots=4,
                          request_overhead_s=0.05, startup_s=4.0)


def _flash():
    return flash_crowd_trace(duration_s=60, base_rps=2.0,
                             flash_at_s=20, flash_mult=8,
                             flash_len_s=10, seed=3,
                             prompt_lens=(8, 32), max_news=(12, 32))


def test_cost_model_scale_up_latency_bounded():
    """Under a flash crowd the modeled controller must spawn within
    (up_patience + 1) ticks of the backlog crossing the watermark —
    the scale-up-latency contract the policy advertises."""
    pol = AutoscalePolicy(min_replicas=1, max_replicas=4,
                          up_drain_s=2.0, down_drain_s=0.25,
                          up_patience=2, down_patience=8, cooldown=4)
    res = FleetCostModel(_PROFILE, pol, initial_replicas=1).run(_flash())
    ups = [e for e in res.autoscale_log if e["decision"] > 0]
    assert ups, "flash crowd never triggered a scale-up"
    first_over = next(e["t"] for e in res.autoscale_log
                      if e["tokens_per_s"]
                      and e["backlog_tokens"] / e["tokens_per_s"] > 2.0)
    # patience consecutive over-ticks + the decision tick itself
    assert ups[0]["t"] - first_over <= (pol.up_patience + 1) * 1.0
    assert res.max_replicas_seen > 1


def test_cost_model_never_below_floor_and_cooldown():
    pol = AutoscalePolicy(min_replicas=2, max_replicas=4,
                          up_drain_s=2.0, down_drain_s=0.25,
                          up_patience=1, down_patience=4, cooldown=3)
    res = FleetCostModel(_PROFILE, pol, initial_replicas=2).run(_flash())
    assert all(e["healthy"] + e["starting"] >= 2
               for e in res.autoscale_log), "went below the floor"
    # cooldown: non-hold decisions at least `cooldown` ticks apart
    acts = [e["t"] for e in res.autoscale_log if e["decision"] != 0]
    gaps = [b - a for a, b in zip(acts, acts[1:])]
    assert all(g >= pol.cooldown for g in gaps), (acts, gaps)


def test_cost_model_diurnal_scales_down_after_peak():
    pol = AutoscalePolicy(min_replicas=1, max_replicas=4,
                          up_drain_s=2.0, down_drain_s=0.5,
                          up_patience=1, down_patience=4, cooldown=2)
    tr = diurnal_trace(duration_s=90, base_rps=6.0, amplitude=0.9,
                       seed=4, prompt_lens=(8, 32), max_news=(12, 32))
    res = FleetCostModel(_PROFILE, pol, initial_replicas=1).run(tr)
    assert res.spawns >= 1
    assert res.retires >= 1, "never scaled back down after the trough"
    # conservation: every offered request has exactly one outcome
    rep = res.report()
    assert (rep["done"] + rep["rejected"] + rep["shed"]
            + rep["failed"]) == rep["requests"] == len(tr)


def test_cost_model_more_replicas_better_tail():
    """Monotonicity sanity: a 4-replica fixed fleet cannot have a worse
    p99 TTFT than a 1-replica fixed fleet on the same overload trace."""
    tr = bursty_trace(duration_s=60, calm_rps=2.0, burst_rps=16.0,
                      seed=5, prompt_lens=(8, 32), max_news=(12, 32))
    r1 = FleetCostModel(_PROFILE, initial_replicas=1,
                        autoscale=False).run(tr).report()
    r4 = FleetCostModel(_PROFILE, initial_replicas=4,
                        autoscale=False).run(tr).report()
    assert r4["ttft_p99_s"] <= r1["ttft_p99_s"]
    assert r4["replica_seconds"] > r1["replica_seconds"]


def test_cost_model_deadline_sheds():
    """Deadlined requests under deep overload shed (admission or
    queue-sweep), and a shed request never reports full tokens."""
    tr = flash_crowd_trace(duration_s=30, base_rps=2.0, flash_at_s=5,
                           flash_mult=20, flash_len_s=8, seed=6,
                           prompt_lens=(8, 32), max_news=(16, 48),
                           deadline_s=1.0, deadline_frac=1.0)
    rep = FleetCostModel(_PROFILE, initial_replicas=1,
                         autoscale=False).run(tr).report()
    assert rep["shed_rate"] > 0.2, rep
    assert rep["done"] + rep["rejected"] + rep["shed"] == rep["requests"]


# ---------------------------------------------------------------------------
# sweep + gate (tiny grid, resumable)


def test_serve_sweep_resumable_and_frontier(tmp_path):
    from gym_tpu.servesim.sweep import (ServeSweepConfig, grid,
                                        run_sweep)
    cfg = ServeSweepConfig(
        traces=["flash_crowd"], up_drain_s=[2.0], down_drain_s=[0.5],
        up_patience=[1, 2], cooldown=[2], bounds=[(1, 2), (1, 4)],
        duration_s=40.0, out=str(tmp_path / "sweep"))
    rows = run_sweep(cfg)
    assert len(rows) == len(grid(cfg)) == 4
    out = str(tmp_path / "sweep")
    assert os.path.exists(os.path.join(out, "frontier.csv"))
    assert os.path.exists(os.path.join(out, "report.md"))
    with open(os.path.join(out, "frontier.csv"), newline="") as f:
        frows = list(csv.DictReader(f))
    assert len(frows) == 4
    assert any(r["on_frontier"] == "True" for r in frows)
    # resumability: a rerun serves every cell from its marker
    rows2 = run_sweep(cfg)
    assert rows2 == rows
    # a changed workload invalidates the cache: cells re-measure under
    # the new trace (more seconds -> more offered requests)
    import dataclasses
    cfg3 = dataclasses.replace(cfg, duration_s=60.0)
    rows3 = run_sweep(cfg3)
    assert all(r3["requests"] > r["requests"]
               for r, r3 in zip(rows, rows3))


def test_frontier_gate_record_and_check(tmp_path, monkeypatch):
    """The committed-baseline contract: the gate's COMPARISON path
    passes on an unchanged frontier, fails when the cheapest
    SLO-meeting cost drifts past the ceiling or a family stops meeting
    the SLO at all — exercised via a canned frontier so the grid's
    size doesn't gate the gate's own logic."""
    import copy
    import json as _json

    from gym_tpu.servesim import frontier_gate as fg
    from gym_tpu.servesim.sweep import ServeSweepConfig
    small = ServeSweepConfig(
        traces=["flash_crowd"], up_drain_s=[2.0], down_drain_s=[0.5],
        up_patience=[1], cooldown=[2], bounds=[(1, 4)],
        duration_s=40.0, slo_attainment_target=0.5,
        out=str(tmp_path / "unused"))
    cur = fg.fast_frontier(small)
    best = cur["families"]["flash_crowd"]
    assert best is not None and best["replica_seconds"] > 0
    # determinism: the gate's whole premise
    assert fg.fast_frontier(small) == cur

    monkeypatch.setattr(fg, "fast_frontier", lambda cfg=None: cur)
    base = str(tmp_path / "base.json")
    assert fg.main(["--record", base]) == 0
    assert fg.main(["--baseline", base]) == 0          # unchanged: OK
    assert fg.main(["--baseline",
                    str(tmp_path / "missing.json")]) == 2
    # poisoned baseline: cheaper than reachable -> regression
    poisoned = copy.deepcopy(cur)
    poisoned["families"]["flash_crowd"]["replica_seconds"] *= 0.5
    with open(base, "w") as f:
        _json.dump(poisoned, f)
    assert fg.main(["--baseline", base]) == 1
    # baseline met the SLO but the current frontier no longer does
    with open(base, "w") as f:
        _json.dump(cur, f)
    broken = copy.deepcopy(cur)
    broken["families"]["flash_crowd"] = None
    monkeypatch.setattr(fg, "fast_frontier", lambda cfg=None: broken)
    assert fg.main(["--baseline", base]) == 1


# ---------------------------------------------------------------------------
# sim-vs-live agreement (one trace against a REAL fleet)


def _agrees_feasible(live, model, n):
    assert live["requests"] == model["requests"] == n
    assert live["done"] == model["done"] == n
    assert live["shed_rate"] == model["shed_rate"] == 0.0
    # same regime: a feasible trace stays sub-second in both arms
    assert live["ttft_p99_s"] < 1.0, live
    assert model["ttft_p99_s"] < 1.0, model
    assert abs(model["ttft_p99_s"] - live["ttft_p99_s"]) < 0.75


def _agrees_in_overload(live, model, n):
    """The stated tolerances of the ``scripts/ci_deploy.sh`` gate: the
    model's p99 TTFT within [0.5x, 2x] of live (or 0.3 s absolute) and
    its shed rate within 0.15 absolute: the contract that makes
    ``servesim/sweep.py``'s policy frontier worth reading."""
    both = {"live": live, "model": model}
    assert live["requests"] == model["requests"] == n
    assert live["shed_rate"] > 0.0, both           # the overload regime
    p99_l, p99_m = live["ttft_p99_s"], model["ttft_p99_s"]
    assert p99_l is not None and p99_m is not None, both
    assert abs(p99_m - p99_l) <= 0.3 or 0.5 <= p99_m / p99_l <= 2.0, both
    assert abs(model["shed_rate"] - live["shed_rate"]) <= 0.15, both


_SIM_VS_LIVE = {
    # one tiny feasible trace, replayed four times faster than drawn
    "feasible": dict(
        model=dict(block_size=64, n_layer=2, n_head=2, n_embd=32),
        fleet=dict(num_slots=2, decode_chunk=2), warm=(4, 8, 16),
        calibrate=dict(probes=1), time_scale=4.0,
        trace=lambda: diurnal_trace(
            duration_s=16, base_rps=1.5, seed=9, prompt_lens=(4, 16),
            max_news=(8, 16)),
        agrees=_agrees_feasible),
    # deep overload: the flash offers about twice the replica's
    # capacity and every request is deadlined, so admission control
    # and queue sheds both fire; 24 s in real time
    "overload": dict(
        model=dict(block_size=128, n_layer=4, n_head=4, n_embd=128),
        fleet=dict(num_slots=1, decode_chunk=1), warm=(8, 16, 32),
        calibrate=dict(saturate_burst=8), time_scale=1.0,
        trace=lambda: flash_crowd_trace(
            duration_s=24, base_rps=1.5, flash_at_s=6, flash_mult=24,
            flash_len_s=6, seed=5, prompt_lens=(8, 32),
            max_news=(24, 56), deadline_s=1.5, deadline_frac=1.0),
        agrees=_agrees_in_overload),
}


@pytest.mark.parametrize("regime", [
    "feasible", pytest.param("overload", marks=pytest.mark.slow)])
def test_sim_vs_live_smoke(regime):
    """The cost model over a calibrated profile against a real
    single-replica fleet's replay of the same seeded trace: the same
    outcome counts and a p99 TTFT in the same regime on a feasible
    trace, and the deploy gate's tolerances in overload (``slow``: its
    seconds are the host's, which no other worker should share;
    ``scripts/ci_deploy.sh`` selects it by name)."""
    import dataclasses as _dc

    import jax

    from gym_tpu.models.nanogpt import GPT, GPTConfig
    from gym_tpu.serve.engine import SamplingParams
    from gym_tpu.serve.router import build_fleet
    from gym_tpu.servesim import calibrate_router, replay_router

    point = _SIM_VS_LIVE[regime]
    cfg = GPTConfig(vocab_size=48, dropout=0.0, bias=True,
                    **point["model"])
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64),
                           train=False)["params"]
    m = ServeMetrics(tempfile.mkdtemp(prefix="gym_tpu_svsmoke_"),
                     engine_log_every=10)
    router = build_fleet(params, cfg, replicas=1, metrics=m,
                         log=lambda *a, **k: None,
                         **point["fleet"]).start()
    tr, time_scale = point["trace"](), point["time_scale"]
    try:
        # warm every prefill bucket the trace hits: a compile inside
        # the replay would poison the live tail AND the calibration
        for n in point["warm"]:
            router.submit(np.arange(1, n + 1, dtype=np.int32) % 48,
                          SamplingParams(max_new_tokens=8, seed=n)
                          ).result(timeout=300)
        profile = calibrate_router(
            router, 48, num_slots=point["fleet"]["num_slots"],
            **point["calibrate"])
        live = replay_router(router, tr, vocab_size=48,
                             time_scale=time_scale)["report"]
    finally:
        router.close(drain_deadline_s=60)
        m.close()
    scaled = [_dc.replace(e, arrival_s=e.arrival_s / time_scale)
              for e in tr]
    model = FleetCostModel(profile, initial_replicas=1,
                           autoscale=False).run(scaled).report()
    point["agrees"](live, model, len(tr))


# ---------------------------------------------------------------------------
# the closed-loop drill (in-process flavor; the out-of-process one is
# scripts/ci_deploy.sh)


@pytest.mark.slow
def test_drill_in_process(tmp_path):
    from gym_tpu.servesim.drill import run_drill
    result = run_drill(str(tmp_path / "drill"), replicas=2,
                       out_of_process=False, kill_trainer=False,
                       final_steps=8, trace_duration_s=12.0)
    assert result["ok"], result["failures"]
    assert result["replay"]["done"] == result["replay"]["requests"]
    assert result["post_swap_stream_exact"]
    assert result["compiles_before"] == result["compiles_after"]


# ---------------------------------------------------------------------------
# multi-tenant traces + modeled isolation (ISSUE 17)


def test_tenant_trace_families_deterministic_and_roundtrip(tmp_path):
    """The tenant families are seeded (same seed → identical trace),
    stamp every event with tenant/slo_class, and survive the CSV
    round-trip exactly; a pre-tenant trace file still loads (the
    tolerant-header satellite)."""
    from gym_tpu.servesim.traces import (TRACE_HEADER, load_trace,
                                         make_trace, save_trace,
                                         trace_stats)
    for fam in ("noisy_neighbor", "tenant_flash", "mixed_slo"):
        ev = make_trace(fam, seed=3, duration_s=20.0)
        assert ev == make_trace(fam, seed=3, duration_s=20.0)
        assert all(e.tenant and e.slo_class for e in ev)
        # unique seeds across the merged population: Outcome.index and
        # the per-request sampling keys both key off them
        assert sorted(e.seed for e in ev) == list(range(len(ev)))
        p = str(tmp_path / f"{fam}.csv")
        save_trace(p, ev)
        assert load_trace(p) == ev
        st = trace_stats(ev)
        assert sum(st["tenants"].values()) == len(ev)
    # noisy_neighbor is the headline shape: an interactive victim and
    # a batch flooder
    st = trace_stats(make_trace("noisy_neighbor", seed=0,
                                duration_s=30.0))
    assert set(st["by_class"]) == {"interactive", "batch"}
    # a single-tenant trace still writes (and reloads through) the
    # original 6-column header — old readers keep working
    old = make_trace("diurnal", seed=0, duration_s=10.0)
    p = str(tmp_path / "old.csv")
    save_trace(p, old)
    with open(p) as f:
        assert next(csv.reader(f)) == TRACE_HEADER
    assert load_trace(p) == old


def test_cost_model_isolation_invariant():
    """The modeled twin of the chaos drill: under the noisy-neighbor
    trace, quotas + preemption must STRICTLY improve the interactive
    victim's SLO attainment over no isolation, pay for it in batch
    goodput (quota rejections exist), and stay deterministic."""
    from gym_tpu.servesim.cost_model import class_reports
    from gym_tpu.servesim.traces import make_trace
    prof = ServiceProfile(tokens_per_s=120.0, num_slots=4,
                          max_queue=64, request_overhead_s=0.05)
    events = make_trace("noisy_neighbor", seed=0, duration_s=60.0)

    def run(quotas, preempt):
        res = FleetCostModel(prof, initial_replicas=2,
                             autoscale=False, quotas=quotas,
                             preempt=preempt).run(events)
        return res, class_reports(events, res.outcomes,
                                  slo_ttft_s=2.0)

    res_off, per_off = run(None, False)
    res_on, per_on = run({"batch": {"share": 0.5}}, True)
    att_off = per_off["interactive"]["slo_attainment"]
    att_on = per_on["interactive"]["slo_attainment"]
    assert att_on > att_off
    assert res_on.preemptions >= 1
    assert res_on.quota_rejected.get("batch", 0) > 0
    # isolation off: no tenant machinery fires (single-tenant parity)
    assert res_off.preemptions == 0 and not res_off.quota_rejected
    # determinism: the regression gate depends on it
    res_on2, per_on2 = run({"batch": {"share": 0.5}}, True)
    assert per_on2 == per_on


def test_tenant_gate_record_and_check(tmp_path):
    """The tenant frontier gate's full lifecycle on a scaled-down
    config: record a baseline, re-check clean, then verify a doctored
    baseline (more batch goodput than achievable) trips REGRESSION."""
    import json
    from gym_tpu.servesim.sweep import (TenantSweepConfig,
                                        best_isolation_policy,
                                        run_tenant_cell, tenant_grid)
    from gym_tpu.servesim.tenant_gate import (fast_tenant_frontier,
                                              structural_check)
    cfg = TenantSweepConfig(traces=["noisy_neighbor"],
                            interactive_fracs=[0.5], duration_s=40.0)
    cur = fast_tenant_frontier(cfg)
    assert structural_check(cur)
    assert cur["cells"] == len(tenant_grid(cfg)) == 4
    grp = "noisy_neighbor"
    best = best_isolation_policy(cur["rows"], grp,
                                 cfg.slo_attainment_target)
    assert best is not None, "no policy meets the interactive SLO"
    assert cur["groups"][grp]["policy"] == best["policy"]
    # determinism across runs — the gate's entire premise
    assert fast_tenant_frontier(cfg)["groups"] == cur["groups"]
