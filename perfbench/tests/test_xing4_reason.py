"""The cell ``xing4.0-29b-a4b.serve-closed-reason`` on the CPU: its
rehearsal (traced and untraced) ends ``correct: true`` and names its
metrics; the configuration against the catalog's entry and the cut's
arithmetic; the traffic file's sizes; the byte counts behind the two new
rooflines (``perfbench/flops_xing4.py``) against sums done by hand; the
four new readers and the appended ones on hand-made facts, and on a
program without the scopes and counters (the parent's). The planted wrong
readings of the description against the kind's ``judge``, each of which
fails the rehearsal's limits: ``tests/test_xing4.py`` (tier-1)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_xing4, harness, spans
from perfbench.harness import load_json

CELL = "xing4.0-29b-a4b.serve-closed-reason"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs",
                                "xing4.0-29b-a4b.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-reason.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["serve_hc_ms_per_step", "serve_hc_roofline_pct",
       "serve_hc_prefill_ms_per_ktoken", "serve_expert_weights_roofline_pct"]
# readers that came with other cells and read this one from the shared
# scopes, counters and ``facts["sizes"]``
APPENDED = ["serve_latent_attn_ms_per_step",
            "serve_latent_attend_roofline_pct",
            "serve_latent_prefill_roofline_pct",
            "serve_latent_kib_per_position",
            "serve_held_experts_ms_per_step",
            "serve_held_expert_picks_per_step",
            "serve_prefill_device_ms_per_ktoken",
            "serve_prefill_positions_run_pct"]
COUNTED = ["serve_latent_kib_per_position",
           "serve_held_expert_picks_per_step",
           "serve_prefill_positions_run_pct"]
STEPS, SLOTS, LAYERS = 100, 32, 6
ROW_BYTES = 640 * 2         # a cached position in one layer, as it lies
FILL = 4000                 # positions a live row holds, about
HIT = 55                    # experts a step's 128 picks hit in a layer
N, C = 4, 3584


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000047",
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


def test_the_benchmark_lists_the_cell_and_its_metrics():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "serve-closed-reason"
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW + APPENDED) <= names
    for name in NEW:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["source"] == "device_trace"
        assert entry["layer"] == ("Expert layer" if "expert" in name
                                  else "Residual path")
        assert entry["unit"] == ("ms" if "_ms_" in name else "%")
        assert entry["better"] == ("lower" if "_ms_" in name else "higher")
    for name in APPENDED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL       # appended, last
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    bench = spec["bench"]
    assert bench["workloads"][-1]["name"] == CELL      # added at the end
    assert bench["configs"][-1]["name"] == "xing4.0-29b-a4b"
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    entry = bench["configs"][-1]
    assert entry["source"] == ("https://huggingface.co/XingChen-AGI/"
                               "Xing4.0-29B-A4B/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["file"] == "perfbench/configs/xing4.0-29b-a4b.json"


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's config as published except the four
    under ``reduced`` (depth, the leading dense layers kept, the row's
    length, the prediction module); no width, expert count or vocabulary
    is cut; the deployment's arithmetic."""
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "max_position_embeddings", "num_nextn_predict_layers"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Xing4.0-29B-A4B")
        for key, value in entry["config"].items():
            assert key in CONFIG, key
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["source"] == entry["source_url"]
    for key, value in (
            ("hidden_size", 3584), ("num_attention_heads", 32),
            ("q_lora_rank", 768), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("intermediate_size", 9216),
            ("moe_intermediate_size", 1024), ("num_experts_per_tok", 4),
            ("n_routed_experts", 64), ("n_routed_experts_published", 64),
            ("held_experts", [0, 64]), ("first_k_dense_replace", 1),
            ("routed_scaling_factor", 2), ("num_hidden_layers", 6),
            ("vocab_size", 131072), ("max_position_embeddings", 12288),
            ("hc_mult", 4), ("hc_sinkhorn_iters", 20), ("hc_eps", 1e-6),
            ("mhc_h_res_clamp_min", -30), ("mhc_h_res_clamp_max", 30),
            ("num_nextn_predict_layers", 0),
            ("num_nextn_predict_layers_published", 1)):
        assert CONFIG[key] == value, key
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert CONFIG["dtype"] == "bfloat16"
    assert CONFIG["rehearse"]["hc_mult"] == 4
    for key in ("assumed", "deployment", "rehearse"):
        assert CONFIG[key]
    for key in ("mhc", "hc_norm_eps", "hc_eps", "sinkhorn_order", "clamp",
                "mat", "streams", "hc_per_sublayer", "hc_names", "block",
                "router", "weights"):
        assert CONFIG["assumed"][key]
    assert "ep_size 1" in CONFIG["deployment"]
    # this chip's parameters, by hand
    c, f, fd = 3584, 1024, 9216
    attn = c * 768 + 768 * 32 * 192 + c * 576 + 512 * 32 * 256 \
        + 32 * 128 * c
    assert attn == pytest.approx(28.4e6, rel=1e-3)
    hc = 2 * N * c * N * (N + 2)
    assert hc == 688128
    dense = attn + 3 * c * fd + hc
    expert_layer = attn + 64 * 3 * c * f + 3 * c * f + c * 64 + hc
    assert dense == pytest.approx(128.2e6, rel=1e-3)
    assert expert_layer == pytest.approx(744.9e6, rel=1e-3)
    total = dense + 5 * expert_layer + 2 * 131072 * c
    assert total == pytest.approx(4.79e9, rel=2e-3)
    assert 2 * total == pytest.approx(9.58e9, rel=2e-3)
    # the pool of pages: 640 lanes of bfloat16 a position and layer
    assert LAYERS * ROW_BYTES == 7680
    assert (16384 * TRAFFIC["page_size"] * LAYERS * ROW_BYTES
            == pytest.approx(2.01e9, rel=2e-3))


def test_the_traffic_file_has_the_issues_parameters():
    t = TRAFFIC
    assert (t["num_slots"], t["decode_chunk"], t["page_size"],
            t["greedy_every"], t["block_of"], t["judged_requests"],
            t["first_request_min_share"], t["request_count"],
            t["first_cut_stride"]) == (32, 1, 16, 2, 8, 8, 0.1, 256, 7)
    assert {k: t["prompt_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 1536, "sigma": 0.6, "min": 512, "max": 4096}
    assert {k: t["output_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 3072, "sigma": 0.6, "min": 1024, "max": 8192}
    assert t["kind"] == "closed_xing4" and t["control_mode"] == "fp8"
    assert (t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= CONFIG["max_position_embeddings"])
    assert CONFIG["max_position_embeddings"] % t["page_size"] == 0
    kimi = load_json(os.path.join(BENCH_DIR, "traffic",
                                  "serve-closed-repo.json"))
    for key in ("output_rank_of_prompt_rank", "prompt_rank_at_place"):
        assert t[key] == kimi[key]


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
    assert p.min() >= 512 and p.max() <= 4096
    assert o.min() >= 1024 and o.max() <= 8192
    # decode-heavy: twice the output tokens of prompt tokens
    assert o.sum() == pytest.approx(2.0 * p.sum(), rel=0.05)
    assert (p + o).max() <= CONFIG["max_position_embeddings"]
    assert lists[0][0]["prompt"] != lists[1][0]["prompt"]
    cuts = closed_keye.dealt_cuts(TRAFFIC, 32)
    assert min(cuts) >= 0.1 and len(set(cuts)) == 32


# -- bytes behind the rooflines ---------------------------------------------


def test_hyper_connection_bytes_by_hand():
    # a sub-layer of 32 rows: four float32 streams of 3,584 in and out,
    # and its Phi [14,336, 24] once
    streams = 2 * 32 * N * C * 4
    phi = N * C * 24 * 4
    assert flops_xing4.hc_bytes(CONFIG, 32, 1) == streams + phi
    assert (streams, phi) == (3670016, 1376256)
    # a decode step: twelve of them, 60.6 MB, 74 us at 819 GB/s
    step = flops_xing4.hc_bytes(CONFIG, 12 * 32, 12)
    assert step == 12 * (streams + phi)
    assert step / 819e9 == pytest.approx(74e-6, rel=0.02)
    # a prefill pass of 4,096 positions: 470 MB a sub-layer
    assert flops_xing4.hc_bytes(CONFIG, 4096, 1) == pytest.approx(
        471e6, rel=0.01)
    # the operations are far under the bytes' time
    assert flops_xing4.hc_flops(CONFIG, 12 * 32) / 197e12 < 2e-6


def test_expert_bytes_by_hand():
    one = 3 * C * 1024                      # a gated expert of 1,024
    assert flops_xing4.expert_params(CONFIG) == one == 11010048
    # five expert layers whose picks hit 55 of 64: 55 experts, the
    # shared one and the router, a layer, in bfloat16
    got = flops_xing4.expert_bytes(CONFIG, 5 * HIT, 5)
    assert got == 2 * 5 * (HIT * one + one + C * 64)
    assert got == pytest.approx(6.17e9, rel=0.01)
    # every expert hit: the 6.8 GB + 0.11 GB of ISSUE 47's reckoning
    assert flops_xing4.expert_bytes(CONFIG, 5 * 64, 5) == pytest.approx(
        7.16e9, rel=0.01)


# -- the readers on hand-made facts -----------------------------------------


def counters(rows=SLOTS):
    """``/stats``' ``model_counters`` over 100 decode steps of ``rows``
    live rows of ``FILL`` positions each."""
    out = {}
    for i in range(LAYERS):
        out[f"layers_{i}/hc/rows"] = [STEPS * rows * 2, STEPS * 2]
        out[f"layers_{i}/self_attn/latent"] = [
            STEPS * rows * FILL, STEPS * rows * FILL * ROW_BYTES]
        out[f"layers_{i}/self_attn/pages"] = [
            STEPS * rows * -(-FILL // 16), 0]
        if i:       # layer 0 is dense: it counts no picks
            out[f"layers_{i}/mlp/picks"] = [STEPS * rows * 4 / 64] * 64
            out[f"layers_{i}/mlp/hit"] = STEPS * HIT
            out[f"layers_{i}/mlp/tokens"] = STEPS * rows
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters(),
            "stats_samples": [
                {"prefill_tokens": 1000, "prefill_tokens_run": 1000},
                {"prefill_tokens": 21480, "prefill_tokens_run": 19432}],
            "admit_spans_traced": {"count": 2, "prompt_tokens": 4000,
                                   "prompt_tokens_sq": 1500 ** 2
                                   + 2500 ** 2}}
    return {**base, **over}


def traced(monkeypatch, ops, scopes, decode_runs=10, **over):
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.2),
                                   "jit_prefill(5)": (3, 0.12)})
    return facts(trace=trace, **over)


def xing4_trace(monkeypatch, **over):
    d = "jit(decode)/jit(main)/while/body/closed_call/Xing4/layers_1/"
    p = "jit(prefill)/jit(main)/while/body/cond/branch_1_fun/Block/"
    ops = {
        "%fusion.1 = f32[24,32,1] fusion(...)": 0.001,
        "%fusion.2 = f32[4,32] fusion(...)": 0.008,
        "%fusion.3 = f32[4,32,1,3584] fusion(...)": 0.003,
        "%fusion.4 = f32[24,1,4096] fusion(...)": 0.004,
        "%fusion.5 = f32[4,1,4096,3584] fusion(...)": 0.012,
        "%fusion.6 = bf16[32,1024] fusion(...)": 0.08,        # an expert
        "%ragged-dot.7 = bf16[128,3584] custom-call(...)": 0.02,
        "%ragged-dot.8 = bf16[8192,3584] custom-call(...)": 0.03,
        "%fusion.9 = f32[32,64] fusion(...)": 0.002,          # the router
        "%latent_paged_decode.10 = bf16[32,32,512] custom-call(...)": 0.04,
        # a kernel for the residual path, should one come, by its name
        "%hyper_connection_mix.11 = f32[4,32,3584] custom-call(...)": 0.0,
        "%fusion.12 = f32[32,3584] fusion(...)": 0.005,       # a norm
    }
    names = list(ops)
    scopes = {names[0]: d + "hc/hc.coef/dot_general",
              names[1]: d + "hc/hc.sinkhorn/reduce_sum",
              names[2]: d + "hc.mix/add",
              names[3]: p + "hc/hc.coef/dot_general",
              names[4]: p + "hc.mix/add",
              names[5]: d + "mlp/moe.routed/dot_general",
              names[8]: d + "mlp/moe.router/dot_general",
              names[10]: d + "hyper_connection_mix",
              names[11]: d + "input_layernorm/mul"}
    return traced(monkeypatch, ops, scopes, **over)


def test_the_residual_paths_time_a_step_and_a_thousand_prompt_tokens(
        monkeypatch):
    f = xing4_trace(monkeypatch)
    assert flops_xing4.hc_seconds(f, flops_xing4.DECODE) == \
        pytest.approx(0.012)
    # by scope (``python3 -m perfbench.flops_xing4`` prints these); the
    # kernel, by its name, is the mix's
    assert flops_xing4.hc_scope_seconds(
        f["trace"].op_names, spans.op_scopes("x.pb"),
        flops_xing4.DECODE) == pytest.approx(
            {"hc.coef": 0.001, "hc.sinkhorn": 0.008, "hc.mix": 0.003})
    assert flops_xing4.hc_seconds(f, flops_xing4.PREFILL) == \
        pytest.approx(0.016)
    # (1 + 8 + 3) ms over 10 steps; the norm beside it is not the path's
    assert reader("serve_hc_ms_per_step")(f) == pytest.approx(1.2)
    # 16 ms of the prefill programs over 4,000 prompt tokens
    assert reader("serve_hc_prefill_ms_per_ktoken")(f) == pytest.approx(4.0)


def test_rooflines_from_counted_work_over_traced_time(monkeypatch):
    f = xing4_trace(monkeypatch)
    # a step: 32 rows through 12 sub-layers, streams in and out, 12 Phi
    least = 12 * (2 * 32 * N * C * 4 + N * C * 24 * 4) / 819e9
    got = reader("serve_hc_roofline_pct")(f)
    assert got == pytest.approx(100 * least / 1.2e-3)
    assert 0 < got < 100 and got == pytest.approx(6.2, rel=0.02)
    # five layers' experts: (80 routed + 20 the step's grouped products +
    # 2 the router) ms over 10 steps = 10.2 ms; the bytes of 55 experts
    # hit, the shared one and the router, a layer
    assert reader("serve_held_experts_ms_per_step")(f) == \
        pytest.approx(10.2)
    least = 2 * 5 * (HIT * 11010048 + 11010048 + C * 64) / 819e9
    got = reader("serve_expert_weights_roofline_pct")(f)
    assert got == pytest.approx(100 * least / 10.2e-3)
    assert 0 < got < 100
    # every one of a step's rows picks 4 of 64: two picks an expert
    assert reader("serve_held_expert_picks_per_step")(f) == \
        pytest.approx(32 * 4 / 64)
    # six layers of 1,280 B a position: 7.5 KiB
    assert reader("serve_latent_kib_per_position")(f) == pytest.approx(7.5)
    assert reader("serve_prefill_positions_run_pct")(f) == \
        pytest.approx(90.0)
    # the walk, by its name: 40 ms over 10 steps
    assert reader("serve_latent_attn_ms_per_step")(f) == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_on_a_program_without_scopes_and_counters(
        monkeypatch, name):
    """The parent's program (no ``hc.*`` scope, no ``hc/rows``, in some
    cells no ``mlp/hit``): None, and no raise."""
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
