"""The cell ``command-a-plus.serve-closed-rag`` on the CPU: its rehearsal
(traced and untraced) ends ``correct: true`` and names its metrics; the
byte and operation counts behind its two rooflines; its readers on
hand-made facts and on a program without the scopes and counters (the
parent); and the planted wrong readings of the description against the
kind's ``judge``."""

import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT, last_json
from perfbench import flops_moe, harness, model_spans, spans
from perfbench.harness import load_json

CELL = "command-a-plus.serve-closed-rag"
BENCH_DIR = os.path.join(ROOT, "perfbench")
CONFIG = load_json(os.path.join(BENCH_DIR, "configs", "command-a-plus.json"))
TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic",
                                 "serve-closed-rag.json"))
LIMITS = load_json(os.path.join(BENCH_DIR, "limits", CELL + ".json"))
# read from the program's counters and spans: a CPU rehearsal has them
COUNTED = ["moe_held_picks_per_token", "moe_expert_load_max_over_mean",
           "serve_window_pages_skipped_pct", "serve_prefill_ms_per_ktoken"]
# read from the device's trace: nothing to read on the CPU
TRACED = ["serve_moe_ms_per_step", "serve_attn_ms_per_step",
          "serve_moe_weight_roofline_pct", "serve_paged_attn_roofline_pct"]
EXPERT = 3 * 4096 * 4096            # gate, up and down of one expert


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


# -- the rehearsal ----------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_correct_and_names_the_metrics(run, trace):
    code, lines, err = run(["--workload", CELL, "--seed", "3000000027",
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
        assert set(COUNTED) <= set(line["metric_names"])
        for name in ("serve_round_ms_p50", "kv_pool_fill_pct",
                     "decode_batch_occupancy_pct", "compile_s",
                     "xla_compiles_in_window"):
            assert name in line["metric_names"]


def test_the_benchmark_lists_every_new_metric_for_the_cell():
    spec = harness.load_cell(CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert set(COUNTED + TRACED) <= names
    for name in COUNTED + TRACED:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}


def test_the_configuration_keeps_the_published_widths():
    """Every width as published; what is cut is the depth, the experts
    held, the vocabulary and the positions, and the file says so."""
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size",
                                      "max_position_embeddings"}
    for key, value in (("hidden_size", 4096), ("intermediate_size", 4096),
                       ("head_dim", 128), ("num_attention_heads", 128),
                       ("num_key_value_heads", 8), ("sliding_window", 4096),
                       ("num_experts_per_tok", 8),
                       ("num_shared_experts", 4),
                       ("num_experts_routed", 128)):
        assert CONFIG[key] == value
    lo, hi = CONFIG["held_experts"]
    assert hi - lo == CONFIG["num_experts"] == 16
    assert CONFIG["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    # the cache pool the traffic asks for: 16 KiB a position, 3 GiB
    row = 4 * 2 * flops_moe.kv_row_bytes(CONFIG)
    assert row == 16 * 1024
    assert row * TRAFFIC["kv_pages"] * TRAFFIC["page_size"] == 3 * 2 ** 30


# -- bytes and operations behind the rooflines ------------------------------


def test_expert_layer_bytes_and_operations():
    assert flops_moe.expert_params(CONFIG) == EXPERT == 50_331_648
    # 14 held experts hit, the 4 shared, the router: bfloat16
    assert flops_moe.moe_layer_bytes(CONFIG, 14) == 2 * (
        18 * EXPERT + 4096 * 128)
    # a fraction of an expert: the mean over the window's steps
    assert flops_moe.moe_layer_bytes(CONFIG, 13.5) == pytest.approx(
        2 * (17.5 * EXPERT + 4096 * 128))
    # 32 rows, 30 of their 256 picks on held experts
    assert flops_moe.moe_layer_flops(CONFIG, 32, 30) == 2.0 * (
        30 * EXPERT + 32 * 4 * EXPERT + 32 * 4096 * 128)
    # a decode step is far under the ridge (240 FLOP/B): bytes bound it
    bytes_, work = (flops_moe.moe_layer_bytes(CONFIG, 14),
                    flops_moe.moe_layer_flops(CONFIG, 32, 32))
    assert work / bytes_ < 10


def test_paged_attention_bytes_and_operations():
    assert flops_moe.kv_row_bytes(CONFIG) == 8 * 128 * 2
    # 1,000 pages of 16 positions, keys and values, and 32 rows' queries
    # in and outputs out (128 heads of 128, bfloat16)
    assert flops_moe.paged_attn_bytes(CONFIG, 1000, 16, 32) == (
        2 * 1000 * 16 * 2048 + 2 * 32 * 128 * 128 * 2)
    # QK^T and PV over those positions for every query head
    assert flops_moe.paged_attn_flops(CONFIG, 1000, 16) == (
        2 * 2 * 1000 * 16 * 128 * 128)
    # 16 query heads a key-value head: 16 multiply-adds a key byte pair,
    # still under the ridge
    assert (flops_moe.paged_attn_flops(CONFIG, 1000, 16)
            / flops_moe.paged_attn_bytes(CONFIG, 1000, 16, 32)) < 20


# -- the readers on hand-made facts -----------------------------------------

STEPS, SLOTS = 100, 32


def counters(picks_per_token=1.0, hit=14, skipped=300):
    """``/stats``' ``model_counters`` over 100 decode steps of 32 live
    rows: every held expert alike, ``hit`` held experts a step."""
    tokens = STEPS * SLOTS
    each = int(tokens * picks_per_token / 16)   # on one held expert
    out = {}
    for i in range(4):
        out[f"layers_{i}/mlp/picks"] = [each] * 16
        out[f"layers_{i}/mlp/hit"] = hit * STEPS
        out[f"layers_{i}/mlp/tokens"] = tokens
        out[f"layers_{i}/self_attn/pages"] = (
            [1000 * STEPS, 0] if i == 3
            else [(1000 - skipped) * STEPS, skipped * STEPS])
    return out


def facts(**over):
    base = {"kind": "closed", "sizes": CONFIG, "traffic": TRAFFIC,
            "device_kind": "TPU v5 lite", "trace": None,
            "stats_delta": {"decode_steps": STEPS, "num_slots": SLOTS},
            "model_counters": counters(),
            "admit_spans": {"count": 10, "seconds": 3.0,
                            "prompt_tokens": 30000}}
    return {**base, **over}


def test_counter_readers():
    f = facts()
    # uniform routing over all 128: 8 x 16 / 128 = one held pick a token
    assert reader("moe_held_picks_per_token")(f) == pytest.approx(1.0)
    assert reader("moe_expert_load_max_over_mean")(f) == pytest.approx(1.0)
    # 3 window layers skip 300 of the 1,000 pages their rows hold
    assert reader("serve_window_pages_skipped_pct")(f) == pytest.approx(30.0)
    assert reader("serve_prefill_ms_per_ktoken")(f) == pytest.approx(100.0)
    # a router that chose among the held 16 only: 8 picks a token
    held_only = facts(model_counters=counters(picks_per_token=8.0))
    assert reader("moe_held_picks_per_token")(held_only) == pytest.approx(
        128 / 16)
    uneven = counters()
    uneven["layers_0/mlp/picks"] = [400] + [0] * 15
    assert reader("moe_expert_load_max_over_mean")(
        facts(model_counters=uneven)) == pytest.approx((16 + 3) / 4)


def traced(monkeypatch, ops, scopes, decode_runs=10):
    """Facts whose trace holds ``ops`` ({operation: seconds}) with the
    scope paths ``scopes`` and ``decode_runs`` runs of the decode
    program."""
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: "x.pb")
    monkeypatch.setattr(spans, "op_scopes", lambda path: scopes)
    trace = types.SimpleNamespace(
        op_names=ops, module_runs={"jit_decode(123)": (decode_runs, 0.4),
                                   "jit_prefill(5)": (3, 0.6)})
    return facts(trace=trace)


def decode_trace(monkeypatch):
    d = "jit(decode)/jit(main)/while/body/layers_0/"
    ops = {
        "%fusion.1 = bf16[32,4096] fusion(...)": 0.010,
        "%fusion.2 = f32[32,128] fusion(...)": 0.002,
        "%fusion.3 = bf16[32,4096] fusion(...)": 0.030,
        # XLA's grouped-matmul kernel carries no scope: the decode step's
        # by its 32 x 8 token-picks, a prefill's block is left out
        "%ragged-dot.7 = bf16[256,4096] custom-call(...)": 0.050,
        "%ragged-dot.9 = bf16[8192,4096] custom-call(...)": 0.700,
        "%paged_gqa_decode_window.3 = bf16[32,8,16,128] custom-call()": 0.012,
        "%paged_gqa_decode_full.1 = bf16[32,8,16,128] custom-call()": 0.008,
        "%paged_gqa_prefill_full.1 = bf16[1,8,512,128] custom-call()": 0.3,
        "%fusion.8 = f32[32,32768] fusion(...)": 0.004,
        "%fusion.9 = bf16[4096,4096] fusion(...)": 0.5,     # a prefill's
    }
    names = list(ops)
    scopes = {names[0]: d + "mlp/moe.shared/dot_general",
              names[1]: d + "mlp/moe.router/dot_general",
              names[2]: d + "mlp/moe.routed/gather",
              names[5]: d + "self_attn/attn.window/pallas_call",
              names[6]: d + "self_attn/attn.full/pallas_call",
              names[8]: "jit(decode)/jit(main)/while/body/head/dot_general",
              names[9]: "jit(prefill)/jit(main)/layers_0/mlp/moe.shared/dot"}
    return traced(monkeypatch, ops, scopes)


def test_trace_readers_take_the_decode_programs_operations(monkeypatch):
    f = decode_trace(monkeypatch)
    by = model_spans.decode_op_seconds(f)
    assert by["moe.shared"] == pytest.approx(0.010)
    assert by["moe.router"] == pytest.approx(0.002)
    assert by["moe.routed"] == pytest.approx(0.080)
    assert by["head"] == pytest.approx(0.004)
    assert by["kernel:paged_gqa_decode_window"] == pytest.approx(0.012)
    # (10 + 2 + 30 + 50) ms over 10 steps; (12 + 8) ms over 10 steps
    assert reader("serve_moe_ms_per_step")(f) == pytest.approx(9.2)
    assert reader("serve_attn_ms_per_step")(f) == pytest.approx(2.0)


def test_rooflines_from_counted_bytes_over_traced_time(monkeypatch):
    f = decode_trace(monkeypatch)
    # four layers, each 14 held experts hit + 4 shared + router, bf16,
    # over 819 GB/s, against 9.2 ms of moe.* a step
    least = 4 * 2 * (18 * EXPERT + 4096 * 128) / 819e9
    assert reader("serve_moe_weight_roofline_pct")(f) == pytest.approx(
        100 * least / 9.2e-3)
    # pages read a step over the four layers: 3 x 700 + 1,000
    pages = 3 * 700 + 1000
    moved = 2 * pages * 16 * 2048 + 2 * (4 * 32) * 128 * 128 * 2
    work = 4 * pages * 16 * 128 * 128
    least = max(moved / 819e9, work / 197e12)
    assert reader("serve_paged_attn_roofline_pct")(f) == pytest.approx(
        100 * least / 2.0e-3)
    for name in ("serve_moe_weight_roofline_pct",
                 "serve_paged_attn_roofline_pct"):
        assert 0 < reader(name)(f) < 100


@pytest.mark.parametrize("name", COUNTED + TRACED)
def test_nothing_to_read_on_a_program_without_scopes_and_counters(
        monkeypatch, name):
    """The parent of the PR that brought them, laid under these files: no
    ``model_counters`` on ``/stats``, no ``prompt_tokens`` total, a trace
    whose operations carry none of the scopes. None, and no raise."""
    bare = traced(monkeypatch,
                  {"%fusion.1 = f32[128,768] fusion(...)": 0.2,
                   "%paged_attn_decode.1 = f32[128,16,768] custom-call()":
                       0.1},
                  {"%fusion.1 = f32[128,768] fusion(...)":
                   "jit(decode)/jit(main)/while/body/h_0/attn/dot_general"})
    for f in (dict(bare, model_counters={}, admit_spans={}),
              dict(facts(), model_counters={}, admit_spans={}),
              {"kind": "closed", "trace": None},
              {"kind": "fit", "trace": None}):
        assert reader(name)(f) is None


# -- the comparison catches what it must --------------------------------------


@pytest.fixture(scope="module")
def served():
    """Six greedy requests through the program's engine at the rehearsal's
    sizes (bfloat16), and the context ``judge`` reads."""
    import jax
    from gym_tpu.serve.engine import InferenceEngine, SamplingParams
    from perfbench import weights_moe
    from perfbench.kinds import closed_model
    sizes = {**CONFIG, **CONFIG["rehearse"]}
    ctx = {"traffic": {**TRAFFIC, **TRAFFIC["rehearse"]}, "sizes": sizes,
           "args": types.SimpleNamespace(seed=5), "devices": jax.devices(),
           "limits": LIMITS["rehearse"]}
    eng = InferenceEngine(weights_moe.make_params(sizes, 5),
                          closed_model.model_config(sizes), num_slots=2,
                          paged=True, page_size=16, kv_pages=48)
    rng, picked = np.random.default_rng(5), []
    for n in (9, 20, 33, 14, 40, 26):
        prompt = rng.integers(0, sizes["vocab_size"], n)
        slot, ev = eng.admit(prompt, SamplingParams(max_new_tokens=16,
                                                    top_k=1))
        tokens = [ev.token]
        while not ev.finished:
            ev = [e for e in eng.step() if e.slot == slot][-1]
            tokens.append(ev.token)
        picked.append({"prompt": prompt.tolist(), "tokens": tokens})
    sound = closed_model.judge(ctx, picked)
    sound["lower"] = closed_model.judge(ctx, picked, "fp8")
    return ctx, picked, sound


def test_sound_tokens_pass_and_the_fp8_control_fails(served):
    from perfbench.kinds import closed_model
    ctx, picked, sound = served
    rows = closed_model.verdict_rows(ctx, sound, 0, [])
    assert all(r["ok"] for r in rows), rows
    assert sound["tokens"] == 6 * 16 and sound["lower"]["mean"] > 0
    # the control in the program's place reads 1.0 against itself
    control = dict(sound["lower"], lower=sound["lower"])
    rows = closed_model.verdict_rows(ctx, control, 0, [])
    assert rows[1]["name"] == "served_logit_gap_vs_fp8"
    assert rows[1]["value"] == 1.0 and not rows[1]["ok"]
    # a failed request or a thread left fails the run whatever the gaps
    assert not all(r["ok"] for r in
                   closed_model.verdict_rows(ctx, sound, 1, []))
    assert not all(r["ok"] for r in
                   closed_model.verdict_rows(ctx, sound, 0, ["gym-tpu-x"]))


@pytest.mark.parametrize("fault", ["shared_summed", "route_held_only",
                                   "window_off", "rotary_on_full",
                                   "topk_not_renormalised"])
def test_a_wrong_reading_fails_the_kinds_judge(served, fault):
    """A program with one wrong reading of the description would serve
    the tokens that reading puts first: at least one limit refuses
    them."""
    from perfbench.kinds import closed_model
    ctx, picked, sound = served
    wrong = closed_model.judge(ctx, picked, faults=(fault,))
    wrong["lower"] = sound["lower"]
    rows = closed_model.verdict_rows(ctx, wrong, 0, [])
    assert not all(r["ok"] for r in rows), rows


# -- the request list ---------------------------------------------------------


def test_every_seed_sends_the_same_pairs_in_another_order():
    """Each block of 16 holds the same (prompt, output) lengths whatever
    the seed: the seed orders them and draws the tokens."""
    from perfbench import data
    from perfbench.kinds import closed_model
    blocks, orders = [], []
    for seed in (1, 2, 3000000001):
        reqs = closed_model.paired(
            data.closed_requests(TRAFFIC, 32768, seed, 40), TRAFFIC)
        sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        blocks += [sorted(sizes[:16]), sorted(sizes[16:32])]
        orders.append(sizes[:16])
        assert [r["greedy"] for r in reqs[:4]] == [False, True, False, True]
    assert all(b == blocks[0] for b in blocks)
    assert orders[0] != orders[1] != orders[2]
    # the shortest prompt gets the shortest output, the longest the 13th
    assert blocks[0][0] == (1024, 96) and blocks[0][-1] == (11316, 408)
    # prompt x output as independent draws expect, to a fifth of a percent
    p, o = np.array(blocks[0]).T
    assert (p * o).sum() / (16 * p.mean() * o.mean()) == pytest.approx(
        1.0, abs=2e-3)
    with pytest.raises(ValueError, match="permutation"):
        closed_model.paired([], {"block_of": 4,
                                 "output_rank_of_prompt_rank": [0, 1, 1, 3]})


ALTERED_TOKEN = """
import gym_tpu.programs.serve_defs as d
_sample = d.sample_logits
def altered(logits, *a, **k):
    return (_sample(logits, *a, **k) + 1) % logits.shape[-1]
d.sample_logits = altered
"""


def test_a_token_altered_where_it_is_produced_ends_correct_false(run):
    """What the widest-gap limit is held against (the fp8 control does
    not separate from sound runs by it)."""
    code, lines, err = run(["--workload", CELL, "--seed", "11", "--seconds",
                            "2", "--trace", "0", "--rehearse"],
                           patch=ALTERED_TOKEN)
    assert code == 0, err[-2000:]
    assert last_json(lines)["correct"] is False
    compared = {json.loads(ln)["compared"]: json.loads(ln)
                for ln in lines if '"compared"' in ln}
    assert compared["served_logit_gap_widest"]["ok"] is False
