"""``serve_prefill_positions_run_pct`` on recorded ``/stats`` samples: the
counter present, absent (the parent's program), and a window without a
prefill."""

import os

import pytest

from perfbench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sample(padded, run=None):
    out = {"decode_steps": 40, "prefills": 3, "prefill_tokens": padded}
    if run is not None:
        out["prefill_tokens_run"] = run
    return out


@pytest.mark.parametrize("samples,value", [
    # one block of serve-closed-longdoc's eight prompts after the fill:
    # buckets 8,192 / 16,384 x3 / 32,768 x4, passes of 2,048
    ([sample(400000, 330000), sample(450000, 371000),
      sample(588416, 481552)], 100 * 151552 / 188416),
    ([sample(400000, 400000), sample(588416, 588416)], 100.0),
    ([sample(400000), sample(588416)], None),           # no such counter
    ([sample(400000, 330000), sample(400000, 330000)], None),  # no prefill
    ([sample(400000, 330000)], None),
    ([], None),
], ids=["passes", "whole_buckets", "absent", "no_prefill", "one_sample",
        "no_samples"])
def test_serve_prefill_positions_run_pct(samples, value):
    read = harness.load_reader(BENCH_DIR, "serve_prefill_positions_run_pct")
    got = read({"kind": "closed", "stats_samples": samples})
    assert got == (pytest.approx(value) if value is not None else None)


def test_benchmark_lists_it_for_the_two_cells_whose_models_run_in_passes():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry = bench["per_layer"][-1]
    assert entry["name"] == "serve_prefill_positions_run_pct"
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Serving engine"
    assert entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["workloads"] == ["command-a-plus.serve-closed-rag",
                                  "keye-vl2-30b-a3b.serve-closed-longdoc"]
