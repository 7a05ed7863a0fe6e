"""The cell ``brumby-14b-base.serve-closed-longgen`` on the CPU: its
rehearsal (traced and untraced) ends ``correct: true`` and names its
metrics; the configuration against the catalog's entry; the traffic
file's sizes; the byte and operation counts behind the retention's
roofline; its readers on hand-made facts and on a program without the
scopes and counters. The planted wrong readings of the description and
the planted faults of the cache manager against the kind's ``judge``:
``tests/test_brumby.py`` (tier-1)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_retention, harness, spans
from perfbench.harness import load_json

CELL = "brumby-14b-base.serve-closed-longgen"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs",
                                "brumby-14b-base.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-longgen.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["serve_state_mib_per_row"]
TRACED = ["serve_retention_ms_per_step",
          "serve_retention_state_roofline_pct",
          "serve_retention_prefill_ms_per_ktoken"]
JOINED = ["kv_pool_fill_pct", "decode_batch_occupancy_pct", "compile_s",
          "xla_compiles_in_window", "serve_round_ms_p50",
          "serve_device_ms_per_token", "serve_device_idle_pct",
          "serve_peak_hbm_gib", "closed_ttft_p50_ms", "closed_itl_p95_ms",
          "serve_prefill_share_pct", "serve_host_ms_per_round",
          "serve_queue_wait_ms_mean", "serve_readback_mib_per_round",
          "serve_uploads_per_step", "serve_steps_ahead_pct"]
# pinned to the one cell they came with by
# perfbench/tests/test_command_a_plus.py, or another model's kernels
LEFT_OUT = ["serve_moe_ms_per_step", "serve_attn_ms_per_step",
            "serve_paged_attn_roofline_pct", "serve_prefill_ms_per_ktoken",
            "serve_sparse_attn_ms_per_step", "sparse_keys_kept_pct"]
STEPS, SLOTS, LAYERS = 100, 16, 8
# a row's state in one layer: 8 key-value heads x 8,320 places x (128
# values + the normaliser) x 4 B
ROW_LAYER_BYTES = 8 * 8320 * 129 * 4


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000033",
                            "--seconds", "3", "--trace", str(trace),
                            "--rehearse"])
    assert code == 0, err[-2000:]
    line = last_json(lines)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["metrics"] == {}
    if not trace:       # a traced run prints the per-layer metrics
        assert {"setup_s", "serve_tokens_per_s"} <= set(
            line["metric_names"])
    compared = {json.loads(ln)["compared"] for ln in lines
                if '"compared"' in ln}
    assert compared == {"served_logit_gap_widest", "served_logit_gap_vs_fp8",
                        "requests_failed", "threads_left"}
    if trace:
        names = set(line["metric_names"])
        assert set(COUNTED) <= names
        assert {"kv_pool_fill_pct", "decode_batch_occupancy_pct",
                "compile_s", "xla_compiles_in_window"} <= names
        assert not names & set(LEFT_OUT)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "serve-closed-longgen"
    names = {m["name"] for m in spec["per_layer"]}
    assert set(COUNTED + TRACED + JOINED) <= names
    assert not names & set(LEFT_OUT)
    for name in COUNTED + TRACED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == "Kernels"
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    bench = spec["bench"]
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    entry = next(c for c in bench["configs"]
                 if c["name"] == "brumby-14b-base")
    assert entry["source"] == ("https://huggingface.co/manifestai/"
                               "Brumby-14B-Base/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["file"] == "perfbench/configs/brumby-14b-base.json"


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's config as published except the two
    under ``reduced`` (depth and the row's length); no width is cut."""
    assert set(CONFIG["reduced"]) == {"num_hidden_layers",
                                      "max_position_embeddings"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Brumby-14B-Base")
        for key, value in entry["config"].items():
            assert key in CONFIG, key
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["source"] == entry["source_url"]
    for key, value in (("hidden_size", 5120), ("intermediate_size", 17408),
                       ("head_dim", 128), ("num_attention_heads", 40),
                       ("num_key_value_heads", 8), ("vocab_size", 151936),
                       ("num_hidden_layers", 8),
                       ("max_position_embeddings", 20480)):
        assert CONFIG[key] == value
    assert (CONFIG["dtype"], CONFIG["state_dtype"]) == ("bfloat16",
                                                        "float32")
    for key in ("assumed", "deployment", "rehearse"):
        assert CONFIG[key]
    for key in ("degree", "gate", "gate_timing", "normaliser", "scale",
                "feature_map", "qk_norm", "rope", "state_dtype", "weights"):
        assert CONFIG["assumed"][key]
    assert "five chips" in CONFIG["deployment"]
    # the gate's draw: a key still weighs a third after 4,000 tokens
    gate = 1.0 / (1.0 + np.exp(-CONFIG["gate_bias"]))
    assert 0.3 < gate ** 4000 < 0.45
    # this chip's parameters: eight layers, the embedding and the head
    c, f = CONFIG["hidden_size"], CONFIG["intermediate_size"]
    layer = c * (5120 + 1024 + 1024) + 5120 * c + c * 8 + 8 + 3 * c * f
    assert layer == pytest.approx(330.3e6, rel=1e-3)
    assert 8 * layer + 2 * 151936 * c == pytest.approx(4.20e9, rel=2e-3)
    # a row's state over the eight layers, and the pool of 18 blocks
    assert LAYERS * ROW_LAYER_BYTES == pytest.approx(274.8e6, rel=1e-3)
    assert (TRAFFIC["kv_pages"] * LAYERS * ROW_LAYER_BYTES
            == pytest.approx(4.95e9, rel=2e-3))


def test_the_traffic_file_has_the_issues_parameters():
    t = TRAFFIC
    assert (t["num_slots"], t["decode_chunk"], t["greedy_every"],
            t["block_of"], t["judged_requests"],
            t["first_request_min_share"]) == (16, 1, 2, 8, 8, 0.1)
    assert {k: t["prompt_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 6144, "sigma": 0.6, "min": 2048, "max": 16384}
    assert {k: t["output_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 1024, "sigma": 0.5, "min": 384, "max": 3072}
    assert t["kind"] == "closed_brumby" and t["control_mode"] == "fp8"
    assert "page_size" not in t      # a page is the whole row
    # a block a slot, the null block and one spare
    assert t["kv_pages"] == t["num_slots"] + 2
    assert (t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= CONFIG["max_position_embeddings"])
    pair = t["output_rank_of_prompt_rank"]
    assert sorted(pair) == list(range(t["block_of"]))
    assert np.corrcoef(np.arange(8), pair)[0, 1] == pytest.approx(
        0.0, abs=1e-12)
    assert sorted(t["prompt_rank_at_place"]) == list(range(8))


def test_the_list_has_one_schedule_of_sizes_for_every_seed():
    from perfbench import data
    from perfbench.kinds import closed_keye, closed_model
    lists = []
    for seed in (1, 2, 3000000001):
        reqs = closed_keye.steadied(closed_model.paired(
            data.closed_requests(TRAFFIC, CONFIG["vocab_size"], seed, 24),
            TRAFFIC), TRAFFIC)
        sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        assert sizes[:8] == sizes[8:16] == sizes[16:24]
        lists.append(reqs)
    p, o = np.array([(len(r["prompt"]), r["max_new_tokens"])
                     for r in lists[0][:8]]).T
    assert p.min() >= 2048 and p.max() <= 16384
    assert o.min() >= 384 and o.max() <= 3072
    assert lists[0][0]["prompt"] != lists[1][0]["prompt"]
    cuts = closed_keye.dealt_cuts(TRAFFIC, 16)
    assert min(cuts) >= 0.1 and len(set(cuts)) == 16


# -- bytes and operations behind the roofline -------------------------------


def test_state_pass_bytes_and_operations():
    # a live row's state in one layer: read once, written once; its 40
    # queries and outputs, 8 keys and values of 128 in bfloat16, 8 gates
    qkvy = (2 * 40 + 2 * 8) * 128 * 2 + 8 * 4
    assert flops_retention.qkvy_bytes(CONFIG, 1) == qkvy
    assert flops_retention.state_pass_bytes(CONFIG, ROW_LAYER_BYTES, 1) == (
        2 * ROW_LAYER_BYTES + qkvy)
    # every entry: decay, the rank-1 update, five queries' multiply-adds
    assert flops_retention.state_pass_flops(CONFIG, ROW_LAYER_BYTES) == (
        ROW_LAYER_BYTES / 4 * 13)
    # bound by bytes on a v5e: 1.6 operations a byte against 240
    assert (flops_retention.state_pass_flops(CONFIG, ROW_LAYER_BYTES)
            / flops_retention.state_pass_bytes(CONFIG, ROW_LAYER_BYTES, 1)
            ) < 2


# -- the readers on hand-made facts -----------------------------------------


def counters(rows=SLOTS):
    """``/stats``' ``model_counters`` over 100 decode steps of ``rows``
    live rows."""
    out = {}
    for i in range(LAYERS):
        out[f"layers_{i}/self_attn/state"] = [
            STEPS * rows, STEPS * rows * (ROW_LAYER_BYTES // 1024)]
        out[f"layers_{i}/self_attn/pages"] = [STEPS * rows, 0]
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters(),
            "admit_spans_traced": {"count": 2, "prompt_tokens": 12000.0}}
    return {**base, **over}


def traced(monkeypatch, ops, scopes, decode_runs=10, **over):
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.4),
                                   "jit_prefill(5)": (3, 0.6)})
    return facts(trace=trace, **over)


def retention_trace(monkeypatch, **over):
    d = "jit(decode)/jit(main)/while/body/closed_call/Brumby/layers_0/" \
        "self_attn/"
    p = "jit(prefill)/jit(main)/while/body/cond/branch_1_fun/Block/" \
        "self_attn/while/body/"
    ops = {
        "%fusion.1 = f32[18,8,8320] fusion(...)": 0.010,
        "%fusion.2 = f32[18,8,8320,128] fusion(...)": 0.150,
        "%fusion.3 = f32[18,8,5,128] fusion(...)": 0.080,
        # a kernel for the state pass that carries no scope
        "%retention_state_decode.4 = f32[18,8,8320,128] custom-call(...)":
            0.020,
        "%fusion.5 = bf16[16,17408] fusion(...)": 0.3,        # the SwiGLU
        "%fusion.6 = f32[1,8,5,256,256] fusion(...)": 0.24,
        "%fusion.7 = f32[1,8,8320,128] fusion(...)": 0.36,
        "%fusion.8 = f32[1,8,256] fusion(...)": 0.12,
        "%fusion.9 = bf16[2048,17408] fusion(...)": 2.0,      # its SwiGLU
    }
    names = list(ops)
    scopes = {names[0]: d + "attn.retention.gate/mul",
              names[1]: d + "attn.retention.state/add",
              names[2]: d + "attn.retention.state/dot_general",
              names[4]: "jit(decode)/jit(main)/while/body/closed_call/"
                        "Brumby/layers_0/mlp/dot_general",
              names[5]: p + "attn.retention.chunk/dot_general",
              names[6]: p + "attn.retention.state/dot_general",
              names[7]: p + "attn.retention.gate/cumsum",
              names[8]: "jit(prefill)/jit(main)/while/body/cond/"
                        "branch_1_fun/Block/mlp/dot_general"}
    return traced(monkeypatch, ops, scopes, **over)


def test_counter_reader():
    # eight layers of 34.3 MB a row, whatever the rows
    want = LAYERS * (ROW_LAYER_BYTES // 1024) * 1024 / 2 ** 20
    assert want == pytest.approx(262.0, rel=1e-3)
    assert reader("serve_state_mib_per_row")(facts()) == pytest.approx(want)
    assert reader("serve_state_mib_per_row")(
        facts(model_counters=counters(rows=3))) == pytest.approx(want)


def test_trace_readers_split_the_decode_and_the_prefill_programs(
        monkeypatch):
    f = retention_trace(monkeypatch)
    dec = flops_retention.scope_seconds(f, flops_retention.DECODE)
    assert dec["attn.retention.gate"] == pytest.approx(0.010)
    assert dec["attn.retention.state"] == pytest.approx(0.250)
    assert "attn.retention.chunk" not in dec
    pre = flops_retention.scope_seconds(f, flops_retention.PREFILL)
    assert pre == pytest.approx({"attn.retention.chunk": 0.24,
                                 "attn.retention.state": 0.36,
                                 "attn.retention.gate": 0.12})
    # (10 + 150 + 80 + 20) ms over 10 steps
    assert reader("serve_retention_ms_per_step")(f) == pytest.approx(26.0)
    # 720 ms over 12,000 prompt tokens
    assert reader("serve_retention_prefill_ms_per_ktoken")(f) == \
        pytest.approx(60.0)


def test_roofline_from_counted_state_over_traced_time(monkeypatch):
    f = retention_trace(monkeypatch)
    state = SLOTS * LAYERS * (ROW_LAYER_BYTES // 1024) * 1024
    moved = 2 * state + SLOTS * LAYERS * ((2 * 40 + 16) * 256 + 32)
    least = max(moved / 819e9, state / 4 * 13 / 197e12)
    assert least == pytest.approx(10.7e-3, rel=0.01)
    got = reader("serve_retention_state_roofline_pct")(f)
    assert got == pytest.approx(100 * least / 26.0e-3)
    assert 0 < got < 100


@pytest.mark.parametrize("name", COUNTED + TRACED)
def test_nothing_to_read_on_a_program_without_scopes_and_counters(
        monkeypatch, name):
    """A program without the scopes and the counter: None, and no raise."""
    bare = traced(monkeypatch,
                  {"%fusion.1 = f32[128,768] fusion(...)": 0.2,
                   "%sort.2 = (f32[128,50304]) sort(...)": 0.1},
                  {"%fusion.1 = f32[128,768] fusion(...)":
                   "jit(decode)/jit(main)/while/body/h_0/attn/dot_general"})
    for f in (dict(bare, model_counters={}, admit_spans_traced={}),
              dict(facts(), model_counters={}),
              {"kind": "closed", "trace": None},
              {"kind": "fit", "trace": None}):
        assert reader(name)(f) is None


def test_admissions_of_the_traced_stretch_outlive_the_recorders_ring(
        monkeypatch):
    """The kind notes the ``serve.admit`` records at every ``/stats``
    sample (the ring drops them within seconds) and counts those
    dispatched inside the traced stretch."""
    import time
    from gym_tpu.utils import trace
    from perfbench.kinds import closed_brumby
    shift = time.monotonic() - time.perf_counter()
    at = lambda s: int((s - shift) * 1e9)       # noqa: E731
    rec = lambda seq, t0, n: trace.Record(      # noqa: E731
        seq, "serve.admit", at(t0), at(t0 + 0.01), None,
        {"prompt_tokens": n})
    seen: dict = {}
    ring = [rec(1, 90.0, 5000), rec(2, 100.6, 8000)]
    monkeypatch.setattr(trace, "records", lambda name: ring)
    closed_brumby.note_admits(seen)
    ring[:] = [rec(2, 100.6, 8000), rec(3, 103.9, 3000),
               rec(4, 104.0, 4000),
               trace.Record(5, "serve.admit", at(102.0), at(102.1), None,
                            {})]           # shed before its prefill
    closed_brumby.note_admits(seen)
    assert sorted(seen) == [1, 2, 3, 4]
    got = closed_brumby.admits_held(seen, 100.5, 104.0)
    assert got == {"count": 2, "prompt_tokens": 11000}
