"""``serve_steps_ahead_pct`` on hand-made ``/stats`` samples: the
counter present, absent (the parent's program), and a window without a
decode step."""

import os

import pytest

from perfbench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sample(steps, ahead=None):
    out = {"decode_steps": steps, "upload_arrays": 0}
    if ahead is not None:
        out["steps_ahead"] = ahead
    return out


@pytest.mark.parametrize("samples,value", [
    # 625 steps of which three followed a drain (a cancel, two parks)
    ([sample(40, 39), sample(300, 298), sample(665, 661)], 100 * 622 / 625),
    ([sample(40, 39), sample(665, 664)], 100.0),
    ([sample(40, 0), sample(665, 0)], 0.0),         # every step waited for
    ([sample(40), sample(665)], None),              # no such counter
    ([sample(40, 39), sample(40, 39)], None),       # no decode step
    ([sample(40, 39)], None),
    ([], None),
], ids=["present", "all_ahead", "none_ahead", "absent", "zero_steps",
        "one_sample", "no_samples"])
def test_serve_steps_ahead_pct(samples, value):
    read = harness.load_reader(BENCH_DIR, "serve_steps_ahead_pct")
    got = read({"kind": "closed", "stats_samples": samples})
    assert got == (pytest.approx(value) if value is not None else None)


def test_benchmark_lists_it_for_both_served_cells():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "serve_steps_ahead_pct"]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Serving engine"
    assert entry["workloads"] == ["gpt2-base.serve-closed",
                                  "command-a-plus.serve-closed-rag"]
