"""``serve_sampler_sorted_steps_pct`` on hand-made ``/stats`` samples: the
counter present, absent (the parent's program), and a window without a
decode step."""

import os

import pytest

from perfbench import harness

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVED = ["gpt2-base.serve-closed", "command-a-plus.serve-closed-rag",
          "keye-vl2-30b-a3b.serve-closed-longdoc",
          "brumby-14b-base.serve-closed-longgen"]


def sample(steps, sorted_steps=None):
    out = {"decode_steps": steps, "steps_ahead": max(steps - 1, 0)}
    if sorted_steps is not None:
        out["sampler_sorted_steps"] = sorted_steps
    return out


@pytest.mark.parametrize("samples,value", [
    # defaults and greedy rows only: no step of the window sorted
    ([sample(40, 0), sample(300, 0), sample(665, 0)], 0.0),
    # a filtering request was live before the window, none inside it
    ([sample(40, 25), sample(665, 25)], 0.0),
    # one top_p row live all through the window
    ([sample(40, 40), sample(665, 665)], 100.0),
    # a filtering row live for 125 of the window's 625 steps
    ([sample(40, 0), sample(300, 90), sample(665, 125)], 20.0),
    ([sample(40), sample(665)], None),              # no such counter
    ([sample(40, 0), sample(40, 0)], None),         # no decode step
    ([sample(40, 0)], None),
    ([], None),
], ids=["none_sorted", "sorted_before_the_window", "all_sorted", "a_fifth",
        "absent", "zero_steps", "one_sample", "no_samples"])
def test_serve_sampler_sorted_steps_pct(samples, value):
    read = harness.load_reader(BENCH_DIR, "serve_sampler_sorted_steps_pct")
    got = read({"kind": "closed", "stats_samples": samples})
    assert got == (pytest.approx(value) if value is not None else None)


def test_benchmark_lists_it_for_the_four_served_cells():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "serve_sampler_sorted_steps_pct"]
    # a later served cell is appended to the list
    assert entry.pop("workloads")[:4] == SERVED
    assert entry == {"name": "serve_sampler_sorted_steps_pct", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "Serving engine",
                     "moves": "serve_tokens_per_s"}
