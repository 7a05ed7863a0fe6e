"""The cell ``qwen3-next-80b-a3b.serve-closed-longctx`` on the CPU: its
rehearsal (traced and untraced) ends ``correct: true`` and names its
metrics; the configuration against the catalog's entry and the cut's
arithmetic; the traffic file's sizes; the byte and operation counts behind
the state pass's roofline (``perfbench/flops_delta.py``) against sums done
by hand; the four readers on hand-made facts and on a program without the
scopes and counters. The cell and its metrics are found by name, never by
place or count. The planted wrong readings of the description against the
kind's ``judge``: ``tests/test_qwen3_next.py`` (tier-1)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_delta, harness, spans
from perfbench.harness import load_json

CELL = "qwen3-next-80b-a3b.serve-closed-longctx"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs",
                                "qwen3-next-80b-a3b.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-longctx.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["serve_delta_state_mib_per_row"]
TRACED = ["serve_delta_ms_per_step", "serve_delta_state_roofline_pct",
          "serve_delta_prefill_ms_per_ktoken"]
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
# held to the one cell they came with by other cells' tests, or another
# model's kernels
LEFT_OUT = ["serve_moe_ms_per_step", "serve_attn_ms_per_step",
            "serve_held_experts_ms_per_step",
            "serve_held_expert_picks_per_step",
            "serve_prefill_device_ms_per_ktoken",
            "serve_retention_ms_per_step", "serve_latent_attn_ms_per_step"]
STEPS, SLOTS = 100, 32
DELTA = [0, 1, 2, 4, 5, 6]          # the delta layers of the eight
STATE = 32 * 128 * 128 * 4          # a row's state in one layer, bytes
KEPT = 3 * 8192 * 2                 # its three kept convolution inputs


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000043",
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
    window = next(json.loads(ln)["window"] for ln in lines
                  if '"window"' in ln)
    # four slots: the null block, one a row and a spare; never more rows
    # than slots hold a block
    assert window["state_blocks"] == 6
    assert 0 < window["state_blocks_peak"] <= 4
    if trace:
        names = set(line["metric_names"])
        assert set(COUNTED) <= names
        assert {"kv_pool_fill_pct", "decode_batch_occupancy_pct",
                "compile_s", "xla_compiles_in_window"} <= names
        assert not names & set(LEFT_OUT)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "serve-closed-longctx"
    assert spec["cell"]["config"] == "qwen3-next-80b-a3b"
    names = {m["name"] for m in spec["per_layer"]}
    assert set(COUNTED + TRACED + JOINED) <= names
    assert not names & set(LEFT_OUT)
    for name in COUNTED + TRACED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == "Kernels"
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           name + ".py"))
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    bench = spec["bench"]
    # found by name: what a later PR appends moves nothing here
    assert CELL in [w["name"] for w in bench["workloads"]]
    for name in JOINED + ["serve_tokens_per_s"]:
        entry = next(m for m in bench["per_layer"] + bench["end_to_end"]
                     if m["name"] == name)
        assert CELL in entry["workloads"]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == ("https://huggingface.co/Qwen/"
                               "Qwen3-Next-80B-A3B-Instruct/blob/main/"
                               "config.json")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["file"] == "perfbench/configs/qwen3-next-80b-a3b.json"


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's config as published except the four
    under ``reduced`` (depth, experts held, vocabulary rows, the row's
    length); no width is cut; the deployment's arithmetic."""
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Qwen3-Next-80B-A3B-Instruct")
        for key, value in entry["config"].items():
            assert key in CONFIG, key
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["source"].startswith(entry["source_url"])
    for key, value in (
            ("hidden_size", 2048), ("num_attention_heads", 16),
            ("num_key_value_heads", 2), ("head_dim", 256),
            ("partial_rotary_factor", 0.25), ("full_attention_interval", 4),
            ("linear_num_key_heads", 16), ("linear_num_value_heads", 32),
            ("linear_key_head_dim", 128), ("linear_value_head_dim", 128),
            ("linear_conv_kernel_dim", 4), ("moe_intermediate_size", 512),
            ("shared_expert_intermediate_size", 512),
            ("num_experts_per_tok", 10), ("num_experts_published", 512),
            ("num_experts", 128), ("held_experts", [0, 128]),
            ("num_hidden_layers", 8), ("vocab_size", 37984),
            ("max_position_embeddings", 50688)):
        assert CONFIG[key] == value, key
    assert CONFIG["dtype"] == "bfloat16"
    assert CONFIG["state_dtype"] == "float32"
    for key in ("assumed", "deployment", "rehearse"):
        assert CONFIG[key]
    for key in ("block", "layer_types", "zero_centred_norm", "output_gate",
                "rotary", "qkvz_layout", "convolution", "delta_rule",
                "router", "state_dtype", "mtp_head", "weights"):
        assert CONFIG["assumed"][key]
    assert "Four chips" in CONFIG["deployment"]
    # this chip's parameters, by hand
    c, f = 2048, 512
    delta = c * 12288 + c * 64 + 8192 * 4 + 4096 * c
    full = c * 8192 + 2 * c * 512 + 4096 * c
    assert delta == pytest.approx(33.7e6, rel=2e-3)
    assert full == pytest.approx(27.3e6, rel=2e-3)
    experts = 128 * 3 * c * f + 3 * c * f + c * 512
    assert 128 * 3 * c * f == pytest.approx(402.7e6, rel=1e-3)
    total = 6 * (delta + experts) + 2 * (full + experts) + 2 * 37984 * c
    assert total == pytest.approx(3.67e9, rel=3e-3)
    # all 512 experts of one layer are 1.61 B parameters: two whole
    # layers would not fit a chip
    assert 512 * 3 * c * f == pytest.approx(1.61e9, rel=2e-3)
    # the caches: pages 4,096 B a position over the two full layers, a
    # row's state 12.28 MiB over the six delta layers
    assert 2 * (2 * 256 * 2 * 2) == 4096
    assert (TRAFFIC["kv_pages"] * TRAFFIC["page_size"] * 4096
            == pytest.approx(4.29e9, rel=2e-3))
    assert 6 * (STATE + KEPT) / 2 ** 20 == pytest.approx(12.28, abs=0.01)
    assert 34 * 6 * (STATE + KEPT) == pytest.approx(0.44e9, rel=0.02)


def test_the_traffic_file_has_the_issues_parameters():
    t = TRAFFIC
    assert (t["num_slots"], t["decode_chunk"], t["page_size"],
            t["greedy_every"], t["block_of"], t["judged_requests"],
            t["connect_batch"], t["kv_pages"]) == (32, 1, 16, 2, 8, 8, 1,
                                                   65536)
    assert {k: t["prompt_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 20480, "sigma": 0.5, "min": 8192, "max": 49152}
    assert {k: t["output_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 512, "sigma": 0.5, "min": 192, "max": 1536}
    assert t["kind"] == "closed_qwen3_next" and t["control_mode"] == "fp8"
    assert (t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            == CONFIG["max_position_embeddings"])
    assert CONFIG["max_position_embeddings"] % t["page_size"] == 0
    repo = load_json(os.path.join(BENCH_DIR, "traffic",
                                  "serve-closed-repo.json"))
    for key in ("output_rank_of_prompt_rank", "prompt_rank_at_place",
                "first_cut_stride", "first_request_min_share"):
        assert t[key] == repo[key], key
    assert np.corrcoef(np.arange(8), t["output_rank_of_prompt_rank"])[
        0, 1] == pytest.approx(0.0, abs=1e-12)
    assert t["reference_pad_multiple"] >= CONFIG["max_position_embeddings"]
    assert t["reference_pad_multiple"] % 4096 == 0


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
    assert p.min() >= 8192 and p.max() <= 49152
    assert o.min() >= 192 and o.max() <= 1536
    # the tokens come from the held slice of the vocabulary
    assert max(max(r["prompt"]) for r in lists[0]) < CONFIG["vocab_size"]
    assert lists[0][0]["prompt"] != lists[1][0]["prompt"]
    # an admission reserves its prompt's whole bucket and its output: a
    # block's eight rows reserve 14,432 pages, and the pool holds four
    # blocks (32 rows) with an eighth to spare
    pages = sum(-(-max(a + b, data.prompt_bucket(int(a), 50688)) // 16)
                for a, b in zip(p, o))
    assert pages == 14432
    assert 4 * pages < 0.9 * TRAFFIC["kv_pages"]


# -- bytes and operations behind the roofline -------------------------------


def test_state_pass_bytes_and_operations_by_hand():
    # a live row, one layer: 524,288 entries of state decayed, read for
    # the key, corrected, read for the query: 7 operations an entry,
    # against 8 bytes an entry moved (in and out): memory-bound by far
    assert flops_delta.state_pass_flops(STATE) == 7 * 32 * 128 * 128
    moved = flops_delta.state_pass_bytes(CONFIG, STATE + KEPT, 1)
    assert moved == 2 * (STATE + KEPT) + 4 * 32 * (4 * 128 + 2)
    assert 7 * 32 * 128 * 128 / moved < 1.0 < 197e12 / 819e9
    # a step of 32 rows over the six delta layers: 0.83 GB, 1.0 ms at the
    # HBM peak
    step = flops_delta.state_pass_bytes(CONFIG, 6 * 32 * (STATE + KEPT),
                                        6 * 32)
    assert step == pytest.approx(0.83e9, rel=0.01)
    assert step / 819e9 == pytest.approx(1.02e-3, rel=0.01)
    assert flops_delta.delta_layers(CONFIG) == DELTA
    # a chunk of 64 positions, one head: 11.3 M operations
    assert flops_delta.prefill_chunk_flops(CONFIG) == pytest.approx(
        2 * (2 * 64 * 64 * 128 + 10 * 64 ** 3 + 64 * 64 * 256
             + 2 * 64 * 128 * 128 + 64 * 64 * 128 + 64 * 128 * 128))


# -- the readers on hand-made facts -----------------------------------------


def counters(rows=SLOTS):
    """``/stats``' ``model_counters`` over 100 decode steps of ``rows``
    live rows."""
    out = {}
    for i in range(8):
        if i in DELTA:
            out[f"layers_{i}/linear_attn/state"] = [
                STEPS * rows, STEPS * rows * (STATE + KEPT)]
        else:
            out[f"layers_{i}/self_attn/pages"] = [STEPS * rows * 1300, 0]
        out[f"layers_{i}/mlp/picks"] = [STEPS * rows * 10 / 512] * 128
        out[f"layers_{i}/mlp/tokens"] = STEPS * rows
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters(),
            "admit_spans_traced": {"count": 2, "prompt_tokens": 40000}}
    return {**base, **over}


def traced(monkeypatch, ops, scopes, decode_runs=10, **over):
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.4),
                                   "jit_prefill(5)": (3, 0.6)})
    return facts(trace=trace, **over)


def delta_trace(monkeypatch, **over):
    d = "jit(decode)/jit(main)/while/body/closed_call/Qwen3Next/layers_1/" \
        "linear_attn/"
    p = "jit(prefill)/jit(main)/while/body/cond/branch_1_fun/Block/" \
        "linear_attn/"
    ops = {
        "%fusion.1 = f32[32,1,12288] fusion(...)": 0.004,
        "%fusion.2 = bf16[34,3,8192] fusion(...)": 0.002,
        # the kernel carries no scope: found by its name
        "%gated_delta_state_decode.3 = f32[34,32,128,128] custom-call(...)":
            0.015,
        "%fusion.4 = f32[32,1,2048] fusion(...)": 0.003,
        "%fusion.5 = bf16[32,512] fusion(...)": 0.03,         # an expert
        "%fusion.6 = f32[44,1,32,64,64] fusion(...)": 0.3,    # the inverse
        "%fusion.7 = f32[1,32,128,128] fusion(...)": 0.2,     # the scan
        "%fusion.8 = f32[1,2816,12288] fusion(...)": 0.3,
        # the full layer's attend, in the decode program
        "%paged_gqa_decode_full.9 = bf16[32,2,8,256] custom-call(...)": 0.05,
        "%fusion.10 = f32[1,2816,2048] fusion(...)": 0.1,
    }
    names = list(ops)
    scopes = {names[0]: d + "attn.delta.proj/dot_general",
              names[1]: d + "attn.delta.conv/scatter",
              names[3]: d + "attn.delta.out/dot_general",
              names[4]: "jit(decode)/jit(main)/while/body/closed_call/"
                        "Qwen3Next/layers_1/mlp/moe.routed/dot_general",
              names[5]: p + "attn.delta.chunks/dot_general",
              names[6]: p + "attn.delta.chunks/while/body/dot_general",
              names[7]: p + "attn.delta.proj/dot_general",
              names[8]: "jit(decode)/jit(main)/while/body/closed_call/"
                        "Qwen3Next/layers_3/self_attn/attn.gated.attend/"
                        "pallas_call",
              names[9]: p + "attn.delta.out/dot_general"}
    return traced(monkeypatch, ops, scopes, **over)


def test_counter_reader():
    # six layers of 2 MiB of state and 48 KiB of kept inputs a row,
    # whatever the rows
    assert reader("serve_delta_state_mib_per_row")(facts()) == \
        pytest.approx(6 * (STATE + KEPT) / 2 ** 20)
    assert reader("serve_delta_state_mib_per_row")(
        facts(model_counters=counters(rows=3))) == pytest.approx(12.28,
                                                                 abs=0.01)


def test_trace_readers_split_the_decode_and_the_prefill_programs(
        monkeypatch):
    f = delta_trace(monkeypatch)
    dec = flops_delta.scope_seconds(f, flops_delta.DECODE)
    assert dec == pytest.approx({"attn.delta.proj": 0.004,
                                 "attn.delta.conv": 0.002,
                                 "attn.delta.state": 0.015,
                                 "attn.delta.out": 0.003})
    pre = flops_delta.scope_seconds(f, flops_delta.PREFILL)
    assert pre == pytest.approx({"attn.delta.chunks": 0.5,
                                 "attn.delta.proj": 0.3,
                                 "attn.delta.out": 0.1})
    # (4 + 2 + 15 + 3) ms over 10 steps
    assert reader("serve_delta_ms_per_step")(f) == pytest.approx(2.4)
    # 0.9 s over 40,000 prompt tokens
    assert reader("serve_delta_prefill_ms_per_ktoken")(f) == \
        pytest.approx(22.5)


def test_the_roofline_from_counted_bytes_over_traced_time(monkeypatch):
    f = delta_trace(monkeypatch)
    # a step: 32 rows x 6 layers of state and kept inputs, in and out,
    # over the 1.7 ms the state pass and the convolution's step took
    least = (2 * 6 * 32 * (STATE + KEPT)
             + 6 * 32 * 4 * 32 * (4 * 128 + 2)) / 819e9
    got = reader("serve_delta_state_roofline_pct")(f)
    assert got == pytest.approx(100 * least / 1.7e-3)
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
