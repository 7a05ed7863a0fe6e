"""The four readers of the spans' second clock (``perfbench/span_cpu.py``:
``serve_driver_cpu_ms_per_round``, ``serve_driver_blocked_ms_per_round``,
``serve_handler_cpu_ms_per_round``, ``serve_other_cpu_ms_per_round``) on
hand-made ``/stats`` samples: the first-sample rule, nothing to read on
three-entry rows (the parent's program), the read-back subtraction, and
``other`` = process less driver less handlers."""

import os

import pytest

from perfbench import harness, span_cpu

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("serve_driver_cpu_ms_per_round", "serve_driver_blocked_ms_per_round",
         "serve_handler_cpu_ms_per_round", "serve_other_cpu_ms_per_round")
SERVED = ["gpt2-base.serve-closed", "command-a-plus.serve-closed-rag",
          "keye-vl2-30b-a3b.serve-closed-longdoc",
          "brumby-14b-base.serve-closed-longgen"]


def stats(rounds, round_s, round_cpu, tick_s, tick_cpu, rb_s, rb_cpu,
          prb_s, prb_cpu, requests, http_cpu, process=None, entries=4):
    """One ``/stats`` sample: rows ``[count, total_s, max_s, cpu_s]``."""
    rows = {"serve.round": [rounds, round_s, 1.0, round_cpu],
            "serve.tick": [rounds, tick_s, 0.1, tick_cpu],
            "serve.decode.readback": [rounds, rb_s, 0.5, rb_cpu],
            "serve.prefill.readback": [1, prb_s, 0.5, prb_cpu],
            "http.generate": [requests, 9.0 * requests, 9.0, http_cpu],
            # leaves of the two above: no reader may count them again
            "http.parse": [requests, 0.5, 0.1, http_cpu / 10],
            "http.submit": [requests, 0.5, 0.1, http_cpu / 10],
            "serve.deliver": [rounds, round_s / 5, 0.1, round_cpu / 4]}
    out = {"spans": {k: v[:entries] for k, v in rows.items()}}
    if process is not None:
        out["process_cpu_s"] = process
    return out


# The window opens inside the round that filled the slots: the first sample
# does not hold it yet, the second does, and a hundred rounds follow. In
# them: round + tick wall 6.0 s, CPU 2.5 s; the read-backs wall 1.2 s, CPU
# 0.2 s (1.0 s of waiting for the device); handlers' CPU 1.0 s; process 9 s.
ROWS = ((10, 1.0, 0.5, 0.1, 0.05, 0.3, 0.01, 0.2, 0.0, 1000, 0.5),
        (11, 23.0, 2.5, 0.2, 0.1, 0.5, 0.02, 0.2, 0.0, 1200, 1.0),
        (111, 28.5, 4.8, 0.7, 0.3, 1.6, 0.22, 0.3, 0.0, 13200, 2.0))


def facts(process=(5.0, 30.0, 39.0), entries=4):
    return {"kind": "closed", "stats_samples": [
        stats(*row, process=p, entries=entries)
        for row, p in zip(ROWS, process)]}


WHOLE = facts()
FIRST, _SECOND, LAST = WHOLE["stats_samples"]


def read(name, f):
    return harness.load_reader(BENCH_DIR, name)(f)


@pytest.mark.parametrize("name,value", [
    # (4.8 - 2.5) + (0.3 - 0.1) s of CPU over 100 rounds
    ("serve_driver_cpu_ms_per_round", 25.0),
    # wall 5.5 + 0.5, less CPU 2.5, less the read-backs' (1.1 - 0.2) +
    # (0.1 - 0.0) of waiting for the device
    ("serve_driver_blocked_ms_per_round", 25.0),
    # http.generate alone: its leaves are inside it
    ("serve_handler_cpu_ms_per_round", 10.0),
    # process 9.0 s less driver 2.5 less handlers 1.0
    ("serve_other_cpu_ms_per_round", 55.0),
])
def test_readers_count_whole_rounds_of_the_window(name, value):
    assert read(name, WHOLE) == pytest.approx(value)


def test_cpu_blocked_and_device_wait_add_up_to_the_wall():
    r = span_cpu.per_round(WHOLE)
    assert r["driver_cpu"] + r["driver_blocked"] + r["driver_device_wait"] \
        == pytest.approx(r["driver_wall"])
    assert r["driver_device_wait"] == pytest.approx(0.010)
    assert r["driver_wall"] == pytest.approx(0.060)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_on_the_parents_three_entry_rows(name):
    assert read(name, facts(entries=3)) is None


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("samples", [
    [], [FIRST], [FIRST, LAST],             # under three samples
    [FIRST, FIRST, FIRST],                  # the round count never moved
    [FIRST, FIRST, LAST],                   # ... until the last sample
    [{"decode_steps": 1}] * 3,              # a program without spans
], ids=["none", "one", "two", "no_round", "last_only", "no_spans"])
def test_nothing_to_read_without_whole_rounds(name, samples):
    assert read(name, {"kind": "closed", "stats_samples": samples}) is None


def test_other_needs_the_process_counter_and_the_rest_do_not():
    f = facts(process=(None,) * 3)          # four entries, no process_cpu_s
    assert read("serve_other_cpu_ms_per_round", f) is None
    assert read("serve_driver_cpu_ms_per_round", f) == pytest.approx(25.0)
    assert read("serve_handler_cpu_ms_per_round", f) == pytest.approx(10.0)


def test_the_deltas_follow_the_first_sample_rule_of_spans():
    from perfbench import spans
    theirs = spans.stats_span_deltas(WHOLE)
    mine = span_cpu.span_cpu_deltas(WHOLE)
    assert {k: v[:2] for k, v in mine.items()} == pytest.approx(theirs)


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_lists_it_for_the_four_served_cells(name):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "Scheduler and HTTP",
                     "moves": "serve_tokens_per_s", "workloads": SERVED}
    assert bench["per_layer"][-4:] == [
        m for m in bench["per_layer"] if m["name"] in NAMES]
