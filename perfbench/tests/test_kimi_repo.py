"""The cell ``kimi-k2.7-code.serve-closed-repo`` on the CPU: its rehearsal
(traced and untraced) ends ``correct: true`` and names its metrics; the
configuration against the catalog's entry and the cut's arithmetic; the
traffic file's sizes; the byte and operation counts behind the two
rooflines (``perfbench/flops_latent.py``) against sums done by hand; the
seven readers on hand-made facts and on a program without the scopes and
counters. The planted wrong readings of the description against the
kind's ``judge``: ``tests/test_kimi_k2.py`` (tier-1)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_latent, harness, spans
from perfbench.harness import load_json

CELL = "kimi-k2.7-code.serve-closed-repo"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs",
                                "kimi-k2.7-code.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-repo.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["serve_latent_kib_per_position",
           "serve_held_expert_picks_per_step"]
TRACED = ["serve_latent_attn_ms_per_step",
          "serve_latent_attend_roofline_pct",
          "serve_latent_prefill_roofline_pct",
          "serve_held_experts_ms_per_step",
          "serve_prefill_device_ms_per_ktoken"]
# the cell's own readers of the expert layers and of a prefill's device
# time: the accepted metrics of those are held to the cell they came with
LAYER = {"serve_held_experts_ms_per_step": "Expert layer",
         "serve_held_expert_picks_per_step": "Expert layer",
         "serve_prefill_device_ms_per_ktoken": "Serving engine"}
JOINED = ["kv_pool_fill_pct", "decode_batch_occupancy_pct", "compile_s",
          "xla_compiles_in_window", "serve_round_ms_p50",
          "serve_device_ms_per_token", "serve_device_idle_pct",
          "serve_peak_hbm_gib", "closed_ttft_p50_ms", "closed_itl_p95_ms",
          "serve_prefill_share_pct", "serve_host_ms_per_round",
          "serve_queue_wait_ms_mean", "serve_readback_mib_per_round",
          "serve_uploads_per_step", "serve_steps_ahead_pct",
          "serve_sampler_sorted_steps_pct", "serve_driver_cpu_ms_per_round",
          "serve_driver_blocked_ms_per_round",
          "serve_handler_cpu_ms_per_round", "serve_other_cpu_ms_per_round"]
# pinned to the one cell they came with by
# perfbench/tests/test_command_a_plus.py, or another model's kernels
LEFT_OUT = ["serve_moe_ms_per_step", "serve_attn_ms_per_step",
            "serve_moe_weight_roofline_pct", "moe_held_picks_per_token",
            "serve_paged_attn_roofline_pct", "serve_prefill_ms_per_ktoken",
            "serve_sparse_attn_ms_per_step", "serve_retention_ms_per_step"]
STEPS, SLOTS, LAYERS = 100, 32, 5
ROW_BYTES = 640 * 2         # a cached position in one layer, as it lies
FILL = 14600                # positions a live row holds, about


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000039",
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
    assert spec["cell"]["traffic"] == "serve-closed-repo"
    names = {m["name"] for m in spec["per_layer"]}
    assert set(COUNTED + TRACED + JOINED) <= names
    assert not names & set(LEFT_OUT)
    for name in COUNTED + TRACED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == LAYER.get(name, "Kernels")
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    bench = spec["bench"]
    assert bench["workloads"][-1]["name"] == CELL      # added at the end
    assert bench["configs"][-1]["name"] == "kimi-k2.7-code"
    assert [m["name"] for m in bench["per_layer"][-7:]] == [
        TRACED[0], TRACED[1], TRACED[2], COUNTED[0], TRACED[3], COUNTED[1],
        TRACED[4]]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    entry = bench["configs"][-1]
    assert entry["source"] == ("https://huggingface.co/moonshotai/"
                               "Kimi-K2.7-Code/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["file"] == "perfbench/configs/kimi-k2.7-code.json"


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's config as published except the four
    under ``reduced`` (depth, experts held, vocabulary rows, the row's
    length); no width is cut; the deployment's arithmetic."""
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Kimi-K2.7-Code")
        for key, value in entry["config"].items():
            assert key in CONFIG, key
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["source"].startswith(entry["source_url"])
    for key, value in (
            ("hidden_size", 7168), ("num_attention_heads", 64),
            ("q_lora_rank", 1536), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("intermediate_size", 18432),
            ("moe_intermediate_size", 2048), ("num_experts_per_tok", 8),
            ("n_routed_experts_published", 384), ("n_routed_experts", 12),
            ("held_experts", [0, 12]), ("first_k_dense_replace", 1),
            ("routed_scaling_factor", 2.827), ("num_hidden_layers", 5),
            ("vocab_size", 20480), ("max_position_embeddings", 36864)):
        assert CONFIG[key] == value, key
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert CONFIG["dtype"] == "bfloat16"
    for key in ("assumed", "deployment", "rehearse"):
        assert CONFIG[key]
    for key in ("block", "latent_norms", "no_bias", "rotation",
                "softmax_scale", "router", "shared_expert", "dense_layer",
                "weights", "vision_tower"):
        assert CONFIG["assumed"][key]
    assert "32 chips share each layer" in CONFIG["deployment"]
    # this chip's parameters, by hand
    c, f, fd = 7168, 2048, 18432
    attn = c * 1536 + 1536 * 64 * 192 + c * 576 + 512 * 64 * 256 \
        + 64 * 128 * c
    assert attn == pytest.approx(101.1e6, rel=1e-3)
    dense = attn + 3 * c * fd
    expert_layer = attn + 12 * 3 * c * f + 3 * c * f + c * 384
    assert dense == pytest.approx(497.5e6, rel=1e-3)
    assert expert_layer == pytest.approx(676.4e6, rel=1e-3)
    total = dense + 4 * expert_layer + 2 * 20480 * c
    assert total == pytest.approx(3.50e9, rel=2e-3)
    # a whole expert layer is 17.1 B parameters: no chip holds one
    assert attn + 384 * 3 * c * f + 3 * c * f + c * 384 == pytest.approx(
        17.1e9, rel=5e-3)
    # the pool of pages: 640 lanes of bfloat16 a position and layer
    assert (TRAFFIC["kv_pages"] * TRAFFIC["page_size"] * LAYERS * ROW_BYTES
            == pytest.approx(5.03e9, rel=2e-3))


def test_the_traffic_file_has_the_issues_parameters():
    t = TRAFFIC
    assert (t["num_slots"], t["decode_chunk"], t["page_size"],
            t["greedy_every"], t["block_of"], t["judged_requests"],
            t["first_request_min_share"]) == (32, 1, 16, 2, 8, 8, 0.1)
    assert {k: t["prompt_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 12288, "sigma": 0.55, "min": 4096, "max": 32768}
    assert {k: t["output_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert t["kind"] == "closed_kimi" and t["control_mode"] == "fp8"
    assert (t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= CONFIG["max_position_embeddings"])
    assert CONFIG["max_position_embeddings"] % t["page_size"] == 0
    pair = t["output_rank_of_prompt_rank"]
    assert sorted(pair) == list(range(t["block_of"]))
    assert np.corrcoef(np.arange(8), pair)[0, 1] == pytest.approx(
        0.0, abs=1e-12)
    assert sorted(t["prompt_rank_at_place"]) == list(range(8))
    import math
    assert math.gcd(t["first_cut_stride"], t["num_slots"]) == 1


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
    assert p.min() >= 4096 and p.max() <= 32768
    assert o.min() >= 256 and o.max() <= 2048
    # the tokens come from the held slice of the vocabulary
    assert max(max(r["prompt"]) for r in lists[0]) < CONFIG["vocab_size"]
    assert lists[0][0]["prompt"] != lists[1][0]["prompt"]
    cuts = closed_keye.dealt_cuts(TRAFFIC, 32)
    assert min(cuts) >= 0.1 and len(set(cuts)) == 32


# -- bytes and operations behind the rooflines ------------------------------


def test_attend_operations_by_hand():
    # a live cached position, one layer: 64 heads score 576 numbers and
    # sum 512, a multiply-add each
    assert flops_latent.decode_attend_flops(CONFIG, 1) == \
        2 * 64 * (576 + 512) == 139264
    # against the 1,152 B the latent itself is: 121 operations a byte,
    # half the v5e's ridge of 240 (109 against the 1,280 B the row takes)
    assert 139264 / 1152 == pytest.approx(120.9, rel=1e-3)
    assert 139264 / ROW_BYTES < 197e12 / 819e9
    # a prefill: the causal half of the pairs of a 12,288-token prompt,
    # 64 heads, 192 score lanes and 128 value lanes, five layers
    n = 12288
    want = 5 * (n * n / 2) * 2 * 64 * (192 + 128)
    assert flops_latent.prefill_attend_flops(CONFIG, n * n) == want
    assert want == pytest.approx(15.5e12, rel=5e-3)   # 36% of 43 TFLOP


# -- the readers on hand-made facts -----------------------------------------


def counters(rows=SLOTS):
    """``/stats``' ``model_counters`` over 100 decode steps of ``rows``
    live rows of ``FILL`` positions each."""
    out = {}
    for i in range(LAYERS):
        out[f"layers_{i}/self_attn/latent"] = [
            STEPS * rows * FILL, STEPS * rows * FILL * ROW_BYTES]
        out[f"layers_{i}/self_attn/pages"] = [
            STEPS * rows * -(-FILL // 16), 0]
        if i:       # layer 0 is dense: it counts no picks
            # uniform routing: 8 picks a row over 384 experts, 12 held
            out[f"layers_{i}/mlp/picks"] = [STEPS * rows * 8 / 384] * 12
            out[f"layers_{i}/mlp/tokens"] = STEPS * rows
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters(),
            "admit_spans_traced": {"count": 2, "prompt_tokens": 20000,
                                   "prompt_tokens_sq": 8000 ** 2
                                   + 12000 ** 2}}
    return {**base, **over}


def traced(monkeypatch, ops, scopes, decode_runs=10, **over):
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.4),
                                   "jit_prefill(5)": (3, 0.6)})
    return facts(trace=trace, **over)


def latent_trace(monkeypatch, **over):
    d = "jit(decode)/jit(main)/while/body/closed_call/KimiK2/layers_1/" \
        "self_attn/"
    p = "jit(prefill)/jit(main)/while/body/cond/branch_1_fun/Block/" \
        "self_attn/"
    ops = {
        "%fusion.1 = bf16[32,1,12288] fusion(...)": 0.006,
        "%fusion.2 = bf16[49152,16,640] fusion(...)": 0.002,
        # the kernels carry no scope: found by their names
        "%latent_paged_decode.3 = bf16[32,64,512] custom-call(...)": 0.050,
        "%fusion.4 = f32[32,1,7168] fusion(...)": 0.004,
        "%fusion.5 = bf16[32,2048] fusion(...)": 0.03,        # an expert
        "%fusion.6 = bf16[36864,8192] fusion(...)": 0.05,
        "%latent_prefill.7 = bf16[4096,8192] custom-call(...)": 0.2,
        "%fusion.8 = bf16[4096,12288] fusion(...)": 0.08,
        # the gather path's attend, scoped, in another program's decode
        "%fusion.9 = f32[32,1,64,36864] fusion(...)": 0.0,
        # grouped products carry no scope: the step's 256 token-picks,
        # and a prefill's block of them
        "%ragged-dot.10 = bf16[256,4096] custom-call(...)": 0.01,
        "%ragged-dot.11 = bf16[8192,4096] custom-call(...)": 0.07,
        "%fusion.12 = f32[32,384] fusion(...)": 0.002,        # the router
    }
    names = list(ops)
    scopes = {names[0]: d + "attn.latent.q/dot_general",
              names[1]: d + "attn.latent.kv/scatter",
              names[3]: d + "attn.latent.out/dot_general",
              names[4]: "jit(decode)/jit(main)/while/body/closed_call/"
                        "KimiK2/layers_1/mlp/moe.routed/dot_general",
              names[5]: p + "attn.latent.expand/while/body/dot_general",
              names[7]: p + "attn.latent.q/dot_general",
              names[8]: d + "attn.latent.attend/dot_general",
              names[11]: "jit(decode)/jit(main)/while/body/closed_call/"
                         "KimiK2/layers_2/mlp/moe.router/dot_general"}
    return traced(monkeypatch, ops, scopes, **over)


def test_counter_reader():
    # five layers of 1,280 B a position, whatever the rows: 6.25 KiB
    # (the latent itself is 1,152 B: 5.625; expanded heads would be 200)
    assert reader("serve_latent_kib_per_position")(facts()) == \
        pytest.approx(6.25)
    assert reader("serve_latent_kib_per_position")(
        facts(model_counters=counters(rows=3))) == pytest.approx(6.25)
    assert 5 * 64 * 320 * 2 / 1024 == 200.0


def test_trace_readers_split_the_decode_and_the_prefill_programs(
        monkeypatch):
    f = latent_trace(monkeypatch)
    dec = flops_latent.scope_seconds(f, flops_latent.DECODE,
                                     flops_latent.DECODE_SCOPES)
    assert dec == pytest.approx({"attn.latent.q": 0.006,
                                 "attn.latent.kv": 0.002,
                                 "attn.latent.attend": 0.050,
                                 "attn.latent.out": 0.004})
    pre = flops_latent.scope_seconds(f, flops_latent.PREFILL,
                                     (flops_latent.ATTEND,))
    assert pre == pytest.approx({"attn.latent.attend": 0.2})
    # (6 + 2 + 50 + 4) ms over 10 steps
    assert reader("serve_latent_attn_ms_per_step")(f) == pytest.approx(6.2)


def test_rooflines_from_counted_work_over_traced_time(monkeypatch):
    f = latent_trace(monkeypatch)
    # a step: 32 rows x 14,600 positions x 5 layers, 1,280 B each
    positions = SLOTS * FILL * LAYERS
    least = max(positions * ROW_BYTES / 819e9,
                positions * 139264 / 197e12)
    assert least == pytest.approx(3.65e-3, rel=0.01)      # bytes-bound
    got = reader("serve_latent_attend_roofline_pct")(f)
    assert got == pytest.approx(100 * least / 5.0e-3)
    assert 0 < got < 100
    # the stretch's two prompts of 8,000 and 12,000 tokens
    flops = 5 * 0.5 * (8000 ** 2 + 12000 ** 2) * 2 * 64 * 320
    got = reader("serve_latent_prefill_roofline_pct")(f)
    assert got == pytest.approx(100 * (flops / 197e12) / 0.2)
    assert 0 < got < 100


def test_the_expert_layers_and_the_prefills_device_time(monkeypatch):
    f = latent_trace(monkeypatch)
    # (30 routed + 10 the step's grouped products + 2 the router) ms over
    # 10 steps; the prefill's grouped product is not the step's
    assert reader("serve_held_experts_ms_per_step")(f) == pytest.approx(4.2)
    # 32 rows x 8 picks over 384 experts: two thirds of a pick an expert
    assert reader("serve_held_expert_picks_per_step")(f) == \
        pytest.approx(32 * 8 / 384)
    assert reader("serve_held_expert_picks_per_step")(
        facts(model_counters=counters(rows=3))) == pytest.approx(3 * 8 / 384)
    # three runs of the prefill program, 0.6 s, over 20,000 prompt tokens
    assert reader("serve_prefill_device_ms_per_ktoken")(f) == \
        pytest.approx(30.0)


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


def test_admissions_of_the_traced_stretch_carry_their_squares(monkeypatch):
    """The kind counts the admissions dispatched inside the traced
    stretch with their prompt tokens and the squares' sum (a causal
    attend's operations grow with the square)."""
    import time
    from gym_tpu.utils import trace
    from perfbench.kinds import closed_brumby, closed_kimi
    shift = time.monotonic() - time.perf_counter()
    at = lambda s: int((s - shift) * 1e9)       # noqa: E731
    rec = lambda seq, t0, n: trace.Record(      # noqa: E731
        seq, "serve.admit", at(t0), at(t0 + 0.01), None,
        {"prompt_tokens": n})
    seen: dict = {}
    monkeypatch.setattr(trace, "records", lambda name: [
        rec(1, 90.0, 5000), rec(2, 100.6, 8000), rec(3, 103.9, 3000),
        rec(4, 104.0, 4000)])
    closed_brumby.note_admits(seen)
    got = closed_kimi.admits_held(seen, 100.5, 104.0)
    assert got == {"count": 2, "prompt_tokens": 11000,
                   "prompt_tokens_sq": 8000 ** 2 + 3000 ** 2}
