"""The cell ``keye-vl2-30b-a3b.serve-closed-longdoc`` on the CPU: its
rehearsal (traced and untraced) ends ``correct: true`` and names its
metrics; the configuration against the catalog's entry; the traffic
file's sizes and pairing; the byte and operation counts behind its two
rooflines; its readers on hand-made facts and on a program without the
scopes and counters. The planted wrong readings of the description
against the kind's ``judge``: ``tests/test_keye_vl2.py`` (tier-1)."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_sparse, harness, spans
from perfbench.harness import load_json

CELL = "keye-vl2-30b-a3b.serve-closed-longdoc"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs",
                                "keye-vl2-30b-a3b.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-longdoc.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = ["sparse_keys_kept_pct"]
TRACED = ["serve_index_select_ms_per_step", "serve_sparse_attn_ms_per_step",
          "serve_index_select_roofline_pct",
          "serve_sparse_attn_roofline_pct"]
JOINED = ["kv_pool_fill_pct", "decode_batch_occupancy_pct", "compile_s",
          "xla_compiles_in_window", "serve_round_ms_p50",
          "serve_device_ms_per_token", "serve_device_idle_pct",
          "serve_uploads_per_step", "serve_steps_ahead_pct"]
# wrong for this model (the issue says why) ...
LEFT_OUT = ["serve_moe_weight_roofline_pct", "serve_paged_attn_roofline_pct",
            "serve_attn_ms_per_step", "serve_window_pages_skipped_pct"]
# ... and right for it (the traced chip runs of PR 31 read them), but
# perfbench/tests/test_command_a_plus.py holds their `workloads` to the
# one cell they came with, and no PR but a `benchmark` one may edit it
HELD_BACK = ["serve_moe_ms_per_step", "moe_held_picks_per_token",
             "moe_expert_load_max_over_mean", "serve_prefill_ms_per_ktoken"]
STEPS, SLOTS, LAYERS = 100, 16, 8


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000031",
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
        assert not names & set(LEFT_OUT + HELD_BACK)


def test_the_benchmark_lists_the_cell_and_its_metrics():
    spec = harness.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert set(COUNTED + TRACED + JOINED) <= names
    assert not names & set(LEFT_OUT + HELD_BACK)
    for name in COUNTED + TRACED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == "Kernels"
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    bench = spec["bench"]
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl2-30b-a3b")
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])


def test_the_configuration_keeps_the_published_widths():
    """Every key of the catalog's config as published except the four
    under ``reduced``; what is cut is the depth, the experts held, the
    vocabulary and the positions, and the file says so."""
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size",
                                      "max_position_embeddings"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Keye-VL-2.0-30B-A3B")
        for key, value in entry["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["source"].startswith(entry["source_url"])
    for key, value in (("hidden_size", 2048), ("moe_intermediate_size", 768),
                       ("head_dim", 128), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4),
                       ("num_experts_per_tok", 8),
                       ("num_experts_routed", 128),
                       ("intermediate_size", 6144)):
        assert CONFIG[key] == value
    assert CONFIG["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    lo, hi = CONFIG["held_experts"]
    assert hi - lo == CONFIG["num_experts"] == 16
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["max_position_embeddings"]) == (8, 18992, 36864)
    assert CONFIG["dtype"] == "bfloat16"
    for key in ("assumed", "deployment", "rehearse"):
        assert CONFIG[key]
    # the cache a position over the eight layers: keys and values 2 x
    # 1,024 B, the index key 128 B, eight times: 17,408 B; the pool
    row = 8 * (2 * 1024 + flops_sparse.index_key_bytes(CONFIG))
    assert row == 17408
    assert (row * TRAFFIC["kv_pages"] * TRAFFIC["page_size"]
            == pytest.approx(9.13e9, rel=2e-3))
    # a chip's layer, the eight of them and the vocabulary slice
    c, f = CONFIG["hidden_size"], CONFIG["moe_intermediate_size"]
    layer = (2 * c * 4096 + 2 * c * 512 + c * (16 * 64 + 64 + 16)
             + c * 128 + 16 * 3 * c * f)
    assert layer == pytest.approx(96.9e6, rel=2e-3)
    assert 8 * layer + 2 * 18992 * c == pytest.approx(853e6, rel=2e-3)


def test_the_traffic_file_has_the_issues_parameters():
    t = TRAFFIC
    assert (t["num_slots"], t["decode_chunk"], t["greedy_every"],
            t["page_size"], t["block_of"], t["judged_requests"]) == (
                16, 1, 2, 16, 8, 8)
    assert {k: t["prompt_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 16384, "sigma": 0.5, "min": 6144, "max": 32768}
    assert {k: t["output_tokens"][k] for k in
            ("median", "sigma", "min", "max")} == {
                "median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert t["output_rank_of_prompt_rank"] == [3, 6, 0, 5, 2, 7, 1, 4]
    assert t["kind"] == "closed_keye" and t["control_mode"] == "fp8"
    # a row holds the longest prompt and the longest output
    assert (t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= CONFIG["max_position_embeddings"])
    assert CONFIG["max_position_embeddings"] % t["page_size"] == 0
    # every resident row is past topk: the shortest prompt is three times it
    assert t["prompt_tokens"]["min"] == 3 * CONFIG["sa_config"]["topk"]


def test_the_pairing_is_a_permutation_of_rank_correlation_zero():
    """Each block of 8 holds the same (prompt, output) lengths whatever
    the seed; the one pairing deals outputs to prompts with rank
    correlation 0."""
    from perfbench import data
    from perfbench.kinds import closed_model
    pair = TRAFFIC["output_rank_of_prompt_rank"]
    n = TRAFFIC["block_of"]
    assert sorted(pair) == list(range(n))
    ranks = np.arange(n)
    assert np.corrcoef(ranks, pair)[0, 1] == pytest.approx(0.0, abs=1e-12)
    blocks, orders = [], []
    for seed in (1, 2, 3000000001):
        reqs = closed_model.paired(
            data.closed_requests(TRAFFIC, 18992, seed, 24), TRAFFIC)
        sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        blocks += [sorted(sizes[:8]), sorted(sizes[8:16])]
        orders.append(sizes[:8])
        assert [r["greedy"] for r in reqs[:4]] == [False, True, False, True]
    assert all(b == blocks[0] for b in blocks)
    assert orders[0] != orders[1] != orders[2]
    p, o = np.array(blocks[0]).T
    assert p.min() >= 6144 and p.max() <= 32768
    assert o.min() >= 256 and o.max() <= 2048
    # the prompt of rank r gets the output of rank pair[r]
    assert [int(np.searchsorted(np.sort(o), x)) for x in o] == pair
    # two of eight prompts lie in the longest quarter
    assert (p > np.quantile(p, 0.74)).sum() == 2


# -- bytes and operations behind the rooflines ------------------------------


def test_index_and_sparse_attend_bytes_and_operations():
    # one resident key: 64 x 2 B read, 16 heads x 64 multiply-adds
    assert flops_sparse.index_bytes(CONFIG, 1) == 128
    assert flops_sparse.index_flops(CONFIG, 1) == 2 * 16 * 64
    # one kept position: key and value rows of 4 x 128 x 2 B; 32 heads'
    # two products over 128; a row's queries in and output out
    assert flops_sparse.sparse_attn_bytes(CONFIG, 1, 0) == 2048
    assert flops_sparse.sparse_attn_bytes(CONFIG, 0, 1) == 2 * 32 * 128 * 2
    assert flops_sparse.sparse_attn_flops(CONFIG, 1) == 4 * 32 * 128
    # both are bound by bytes on a v5e: 16 and 8 operations a byte
    assert (flops_sparse.index_flops(CONFIG, 1)
            / flops_sparse.index_bytes(CONFIG, 1)) == 16 < 240


# -- the readers on hand-made facts -----------------------------------------


def counters(resident=18000, topk=2048):
    """``/stats``' ``model_counters`` over 100 decode steps of 16 live
    rows of ``resident`` positions each."""
    out = {}
    for i in range(LAYERS):
        out[f"layers_{i}/self_attn/keys"] = [
            STEPS * SLOTS * min(resident, topk), STEPS * SLOTS * resident]
        out[f"layers_{i}/self_attn/sparse_rows"] = (
            STEPS * SLOTS * int(resident > topk))
        out[f"layers_{i}/mlp/tokens"] = STEPS * SLOTS
        out[f"layers_{i}/mlp/picks"] = [STEPS * SLOTS // 16] * 16
        out[f"layers_{i}/mlp/hit"] = 10 * STEPS
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters()}
    return {**base, **over}


def traced(monkeypatch, ops, scopes, decode_runs=10, **over):
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.4),
                                   "jit_prefill(5)": (3, 0.6)})
    return facts(trace=trace, **over)


def decode_trace(monkeypatch, **over):
    d = "jit(decode)/jit(main)/while/body/layers_0/self_attn/"
    ops = {
        "%gather.1 = bf16[16,2304,1024] gather(...)": 0.020,
        "%fusion.2 = f32[16,1,36864] fusion(...)": 0.030,
        "%sort.3 = (f32[16,1,36864], s32[16,1,36864]) sort(...)": 0.040,
        # a sort without a scope: the selection's by the row's extent,
        # the sampler's (as long as the vocabulary) is not
        "%sort.4 = (f32[16,36864], s32[16,36864]) sort(...)": 0.010,
        "%sort.5 = (f32[16,18992], s32[16,18992]) sort(...)": 0.015,
        "%gather.6 = bf16[16,1,2048,4,128] gather(...)": 0.025,
        "%fusion.7 = f32[16,4,1,8,2048] fusion(...)": 0.005,
        "%fusion.8 = f32[1,1024,36864] fusion(...)": 0.9,   # a prefill's
        "%fusion.9 = bf16[16,2048] fusion(...)": 0.05,      # the experts
    }
    names = list(ops)
    scopes = {names[0]: d + "attn.index/gather",
              names[1]: d + "attn.index/dot_general",
              names[2]: d + "attn.select/top_k",
              names[5]: d + "attn.sparse/gather",
              names[6]: d + "attn.sparse/dot_general",
              names[7]: "jit(prefill)/jit(main)/layers_0/self_attn/"
                        "attn.index/dot_general",
              names[8]: "jit(decode)/jit(main)/while/body/layers_0/mlp/"
                        "moe.routed/dot"}
    return traced(monkeypatch, ops, scopes, **over)


def test_counter_reader():
    # sixteen rows of 18,000 positions keep 2,048 each
    assert reader("sparse_keys_kept_pct")(facts()) == pytest.approx(
        100 * 2048 / 18000)
    short = facts(model_counters=counters(resident=1500))
    assert reader("sparse_keys_kept_pct")(short) == pytest.approx(100.0)


def test_trace_readers_take_the_decode_programs_operations(monkeypatch):
    f = decode_trace(monkeypatch)
    by = flops_sparse.decode_scope_seconds(f)
    assert by["attn.index"] == pytest.approx(0.050)
    assert by["attn.select"] == pytest.approx(0.050)
    assert by["attn.sparse"] == pytest.approx(0.030)
    # (20 + 30 + 40 + 10) ms over 10 steps; (25 + 5) ms over 10 steps
    assert reader("serve_index_select_ms_per_step")(f) == pytest.approx(10.0)
    assert reader("serve_sparse_attn_ms_per_step")(f) == pytest.approx(3.0)


def test_rooflines_from_counted_bytes_over_traced_time(monkeypatch):
    f = decode_trace(monkeypatch)
    # eight layers x 16 rows x 18,000 resident index keys of 128 B a step
    resident = 8 * 16 * 18000
    least = max(resident * 128 / 819e9, resident * 2048 / 197e12)
    assert reader("serve_index_select_roofline_pct")(f) == pytest.approx(
        100 * least / 10.0e-3)
    kept, rows = 8 * 16 * 2048, 8 * 16
    moved = kept * 2048 + 2 * rows * 32 * 128 * 2
    least = max(moved / 819e9, 4 * kept * 32 * 128 / 197e12)
    assert reader("serve_sparse_attn_roofline_pct")(f) == pytest.approx(
        100 * least / 3.0e-3)
    for name in ("serve_index_select_roofline_pct",
                 "serve_sparse_attn_roofline_pct"):
        assert 0 < reader(name)(f) < 100


@pytest.mark.parametrize("name", COUNTED + TRACED)
def test_nothing_to_read_on_a_program_without_scopes_and_counters(
        monkeypatch, name):
    """A program without the scopes and the counter: None, and no raise."""
    bare = traced(monkeypatch,
                  {"%fusion.1 = f32[128,768] fusion(...)": 0.2,
                   "%sort.2 = (f32[128,50304]) sort(...)": 0.1},
                  {"%fusion.1 = f32[128,768] fusion(...)":
                   "jit(decode)/jit(main)/while/body/h_0/attn/dot_general"})
    for f in (dict(bare, model_counters={}),
              dict(facts(), model_counters={}),
              {"kind": "closed", "trace": None},
              {"kind": "fit", "trace": None}):
        assert reader(name)(f) is None


def test_the_steadied_list_has_one_order_of_sizes_for_every_seed():
    """The kind's own step after ``paired``: every block in one order of
    sizes whatever the seed, short and long prompts alternating, so that
    any 8 consecutive requests are the same set; the seed still draws
    the tokens and the sampling seeds; the greedy places hold both
    prompts of the longest quarter."""
    from perfbench import data
    from perfbench.kinds import closed_keye, closed_model
    order = TRAFFIC["prompt_rank_at_place"]
    assert sorted(order) == list(range(8))
    lists = []
    for seed in (1, 2, 3000000001):
        reqs = closed_keye.steadied(closed_model.paired(
            data.closed_requests(TRAFFIC, 18992, seed, 28), TRAFFIC), TRAFFIC)
        lists.append(reqs)
        sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        assert sizes[:8] == sizes[8:16] == sizes[16:24]
        for lo in range(1, 9):          # any 8 in a row: the same set
            assert sorted(sizes[lo:lo + 8]) == sorted(sizes[:8])
        ranks = np.argsort(np.argsort([p for p, _o in sizes[:8]])).tolist()
        assert ranks == order
        # neighbours' prompts add up alike: short and long alternate
        assert {ranks[i] + ranks[i + 1] for i in range(0, 8, 2)} == {7}
        assert [r["greedy"] for r in reqs[:4]] == [False, True, False, True]
        assert sorted(ranks[1::2], reverse=True)[:2] == [7, 6]
    a, b = lists[0], lists[1]
    # (the last, partial block of 4 stays as the seed dealt it)
    assert ([len(r["prompt"]) for r in a[:24]]
            == [len(r["prompt"]) for r in b[:24]])
    assert a[0]["prompt"] != b[0]["prompt"] and a[0]["seed"] != b[0]["seed"]
    # the output dealt to a prompt stays the pairing's
    o = sorted(r["max_new_tokens"] for r in a[:8])
    for r, rank in zip(a[:8], order):
        assert o.index(r["max_new_tokens"]) == TRAFFIC[
            "output_rank_of_prompt_rank"][rank]
    with pytest.raises(ValueError, match="permutation"):
        closed_keye.steadied([], {"block_of": 4,
                                  "prompt_rank_at_place": [0, 1, 1, 3]})


def test_first_cuts_are_dealt_by_a_stride_and_not_by_the_seed():
    from perfbench.kinds import closed_keye
    cuts = closed_keye.dealt_cuts(TRAFFIC, 16)
    grid = 0.1 + 0.9 * (np.arange(16) + 0.5) / 16
    assert sorted(cuts) == pytest.approx(sorted(grid))
    assert cuts[1] == pytest.approx(grid[7]) and cuts[0] == grid[0]
    with pytest.raises(ValueError, match="coprime"):
        closed_keye.dealt_cuts({**TRAFFIC, "first_cut_stride": 4}, 16)
    rehearse = {**TRAFFIC, **TRAFFIC["rehearse"]}
    assert len(set(closed_keye.dealt_cuts(rehearse, 4))) == 4


def test_the_held_back_readers_read_this_cell(monkeypatch):
    """The expert layer's readers on this cell's facts: its counters
    have the shape ``model_spans.counted`` asks (``pages`` included)."""
    f = decode_trace(monkeypatch)
    for i in range(LAYERS):
        f["model_counters"][f"layers_{i}/self_attn/pages"] = [100, 0]
    # uniform routing over all 128: 8 x 16 / 128 = one held pick a token
    assert reader("moe_held_picks_per_token")(f) == pytest.approx(1.0)
    assert reader("moe_expert_load_max_over_mean")(f) == pytest.approx(1.0)
    # the one operation under moe.routed: 50 ms over 10 steps
    assert reader("serve_moe_ms_per_step")(f) == pytest.approx(5.0)
