"""``serve_uploads_per_step`` on hand-made ``/stats`` samples: the
counter present, absent (the parent's program), and a window without a
decode step."""

import os

import pytest

from perfbench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sample(steps, uploads=None):
    out = {"decode_steps": steps, "readback_bytes": 0}
    if uploads is not None:
        out["upload_arrays"] = uploads
    return out


@pytest.mark.parametrize("samples,value", [
    # 70 admissions of 11 arrays and 70 block tables over 625 steps
    ([sample(40, 500), sample(300, 800), sample(665, 1340)], 840 / 625),
    ([sample(40, 500), sample(665, 500)], 0.0),
    ([sample(40), sample(665)], None),              # no such counter
    ([sample(40, 500), sample(40, 500)], None),     # no decode step
    ([sample(40, 500)], None),
    ([], None),
], ids=["present", "resident", "absent", "zero_steps", "one_sample",
        "no_samples"])
def test_serve_uploads_per_step(samples, value):
    read = harness.load_reader(BENCH_DIR, "serve_uploads_per_step")
    got = read({"kind": "closed", "stats_samples": samples})
    assert got == (pytest.approx(value) if value is not None else None)


def test_benchmark_lists_it_for_both_served_cells():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "serve_uploads_per_step"]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["workloads"] == ["gpt2-base.serve-closed",
                                  "command-a-plus.serve-closed-rag"]
