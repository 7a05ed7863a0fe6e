"""The per-layer metrics that read the program's own spans, scopes and
kernel names (``perfbench/spans.py`` and the readers that use it): each
reader on hand-made facts, nothing to read where its input is missing (as
on a program without spans), and the traced rehearsals listing them."""

import os
import types

import pytest

from conftest import last_json

from perfbench import harness, spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "recorded", "small.xplane.pb")


def reader(name):
    return harness.load_reader(BENCH_DIR, name)


def stats(rounds, round_s, admit_s, decode_rb, prefill_rb, queued, queue_s,
          steps, read):
    return {"decode_steps": steps, "readback_bytes": read, "spans": {
        "serve.round": [rounds, round_s, 1.0],
        "serve.admit": [queued, admit_s, 0.5],
        "serve.decode.readback": [rounds, decode_rb, 0.5],
        "serve.prefill.readback": [queued, prefill_rb, 0.5],
        "request.queue": [queued, queue_s, 0.5]}}


# The window opens inside the round that filled the slots (128 prefills,
# 22 s): the first sample does not hold it yet, the second does, and a
# hundred rounds follow.
SERVED = {"kind": "closed", "stats_samples": [
    stats(10, 6.0, 2.0, 3.0, 0.5, 12, 1.2, 10, 10 * 2 ** 20),
    stats(11, 28.0, 23.0, 3.4, 20.5, 140, 1281.2, 11, 36 * 2 ** 20),
    stats(111, 88.0, 43.0, 33.4, 24.5, 240, 1311.2, 110, 2610 * 2 ** 20)]}


@pytest.mark.parametrize("name,value", [
    ("serve_prefill_share_pct", 100.0 * 20.0 / 60.0),
    # (60 - 30 - 4) s of the host's own over 100 rounds
    ("serve_host_ms_per_round", 260.0),
    ("serve_queue_wait_ms_mean", 300.0),
    ("serve_readback_mib_per_round", 26.0),
])
def test_serving_readers_count_whole_rounds_of_the_window(name, value):
    assert reader(name)(SERVED) == pytest.approx(value)


class Rec(types.SimpleNamespace):
    @property
    def seconds(self):
        return (self.t1 - self.t0) * 1e-9


def fit_records(steps=12, h=4, period_ms=100, outer_ms=30, data_ms=0.5,
                host_ms=3.0):
    """What a fit of ``steps`` steps leaves: step ``s`` retires at the
    end of a ``fit.retire.wait`` that the loop enters ``host_ms`` and a
    ``fit.data_wait`` of ``data_ms`` after the retirement before."""
    recs, t = [], 0
    for s in range(steps):
        dur = period_ms + (outer_ms if s and s % h == 0 else 0)
        start, t = t, t + int(dur * 1e6)
        d0 = start + int(1e6)
        recs.append(Rec(name="fit.data_wait", t0=d0,
                        t1=d0 + int(data_ms * 1e6), ids={"step": s + 1}))
        recs.append(Rec(name="fit.retire.wait",
                        t0=start + int((host_ms + data_ms) * 1e6), t1=t,
                        ids={"step": s}))
    return recs


@pytest.mark.parametrize("name,value", [
    ("train_data_wait_ms_per_step", 0.5),
    ("train_host_ms_per_step", 3.0),
    ("train_outer_ms_per_round", 30.0),
])
def test_training_readers_take_the_timed_fits_spans(monkeypatch, name, value):
    monkeypatch.setattr(spans, "fit_records", lambda run="timed": (
        fit_records() if run == "timed" else []))
    facts = {"kind": "fit", "traffic": {"strategy": {"kwargs": {"H": 4}}}}
    assert reader(name)(facts) == pytest.approx(value)


def test_retire_periods_leave_out_the_steps_without_a_full_period():
    periods = spans.retire_periods(fit_records(steps=6, h=4))
    assert [s for s, *_ in periods] == [2, 3, 4, 5]
    assert periods[2][1] == pytest.approx(0.130)        # the outer step
    assert all(w == pytest.approx(p - 0.0035) for _s, p, w, _d in periods)


def traced_fit(op_names, window_s=1.0, step_s=0.1):
    trace = xplane.Summary(
        devices=1, window_s=window_s, busy_s=window_s, op_seconds={},
        collective_s=0.0, collective_exposed_s=0.0, idle_gaps={},
        op_names=op_names, module_runs={"jit_step": (10, window_s)},
        module_events={"jit_step": [(i * step_s * 1e9, step_s * 1e9)
                                    for i in range(10)]})
    return {"kind": "fit", "trace": trace, "device_kind": "TPU v5 lite",
            "rows_per_step_per_chip": 16,
            "sizes": {"n_embd": 768, "n_layer": 12, "n_head": 12,
                      "n_positions": 1024}}


def custom_call(name):
    return (f'%{name} = (bf16[16,12,1024,64]) custom-call(%a, %b), '
            f'custom_call_target="tpu_custom_call"')


def test_kernel_rooflines_tell_forward_from_backward():
    from perfbench import flops
    facts = traced_fit({custom_call("attn_fwd_blk.3"): 0.05,
                        custom_call("transpose_jvp_attn_bwd_blk__.7"): 0.15,
                        "%fusion.1 = f32[8] fusion(%x)": 0.5})
    sizes, peak = facts["sizes"], flops.peaks("TPU v5 lite")
    fwd_flops = flops.attention_flops(sizes, 160, 1024, backward=False)
    assert reader("train_attn_fwd_roofline")(facts) == pytest.approx(
        100.0 * fwd_flops / peak["bf16_flops"] / 0.05)
    assert reader("train_attn_bwd_roofline")(facts) == pytest.approx(
        100.0 * 2.5 * fwd_flops / peak["bf16_flops"] / 0.15)
    # together they are the accepted metric's kernel time
    assert facts["trace"].custom_call_seconds() == pytest.approx(0.2)


def test_op_scopes_reads_the_recorded_traces_metadata():
    scopes = spans.op_scopes(RECORDED)
    dots = [v for k, v in scopes.items() if k.startswith("%convolution")]
    assert dots and all(v.startswith("jit(small_step)/dot_general")
                        for v in dots)
    assert any(v.startswith("jit(small_step)/jit(sort)/sort")
               for v in scopes.values())


def test_scope_seconds_matches_a_scope_under_any_transform():
    trace = types.SimpleNamespace(op_names={"a": 1.0, "b": 2.0, "c": 4.0,
                                            "d": 8.0, "e": 16.0})
    scopes = {"a": "jit(step)/fwd_bwd/while/body/dot_general:",
              "b": "jit(step)/vmap(fwd_bwd)/transpose(jvp(GPT))/mul:",
              "c": "jit(step)/strategy/optimizer/add:",
              "d": "jit(step)/strategy/cond/branch_1_fun/outer/psum:",
              "e": "jit(step)/not_fwd_bwd_at_all/add:"}
    assert spans.scope_seconds(trace, scopes, "fwd_bwd") == 3.0
    assert spans.scope_seconds(trace, scopes, "strategy") == 12.0
    assert spans.scope_seconds(trace, scopes, "outer") == 8.0


def test_scope_readers_read_the_trace_the_run_wrote(monkeypatch):
    facts = traced_fit({"%fusion.1": 0.3, "%fusion.2": 0.2, "%copy.3": 0.1})
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: RECORDED)
    monkeypatch.setattr(spans, "op_scopes", lambda path: {
        "%fusion.1": "jit(step)/fwd_bwd/mul:",
        "%fusion.2": "jit(step)/strategy/optimizer/add:"})
    assert reader("train_fwd_bwd_ms_per_step")(facts) == pytest.approx(30.0)
    assert reader("train_optimizer_ms_per_step")(facts) == pytest.approx(20.0)
    # scopes in the trace and nothing under this one (an update fused
    # into the backward pass) is a reading of 0, not a missing metric
    monkeypatch.setattr(spans, "op_scopes", lambda path: {
        "%fusion.1": "jit(step)/fwd_bwd/mul:"})
    assert reader("train_optimizer_ms_per_step")(facts) == 0.0


def test_idle_by_span_books_each_piece_to_the_innermost_span(monkeypatch):
    ops = [("%a", 0.0, 100.0), ("%b", 1100.0, 100.0), ("%c", 3200.0, 100.0)]
    monkeypatch.setattr(xplane, "read_planes", lambda path: {
        "/device:TPU:0": {xplane.OPS_LINE: ops}})
    monkeypatch.setattr(spans, "program_spans", lambda path: [
        ("serve.admit", 50.0, 1000.0, {"seq": 1}),
        ("serve.prefill.args", 200.0, 600.0, {"seq": 2}),
        ("http.generate", 0.0, 5000.0, {"seq": 3}),
        ("serve.decode.args", 1500.0, 3300.0, {"seq": 4})])
    got = spans.idle_by_span("x")
    assert got == pytest.approx({
        "serve.admit": 500e-9,              # 100-200 and 600-1000
        "serve.prefill.args": 400e-9,
        "outside_spans": 400e-9,            # 1000-1100 and 1200-1500
        "serve.decode.args": 1700e-9})


NEW = ["serve_prefill_share_pct", "serve_host_ms_per_round",
       "serve_queue_wait_ms_mean", "serve_readback_mib_per_round",
       "train_data_wait_ms_per_step", "train_host_ms_per_step",
       "train_outer_ms_per_round", "train_attn_bwd_roofline",
       "train_attn_fwd_roofline", "train_optimizer_ms_per_step",
       "train_fwd_bwd_ms_per_step"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_gives_nothing_to_read(monkeypatch, name):
    """The parent of the PR that added the spans: no ``spans`` under
    ``/stats``, no recorder, ``%attn.N`` kernels, no scope in the trace.
    The reader returns None and does not raise."""
    monkeypatch.setattr(spans, "fit_records", lambda run="timed": [])
    monkeypatch.setattr(spans, "newest_xplane", lambda root=None: RECORDED)
    old = {"decode_steps": 1, "tokens_generated": 5}
    for facts in ({"kind": "closed", "trace": None,
                   "stats_samples": [old, dict(old, decode_steps=9)]},
                  {"kind": "closed"},
                  dict(traced_fit({custom_call("attn.5"): 0.2}),
                       traffic={"strategy": {"kwargs": {"H": 100}}}),
                  {"kind": "fit", "trace": None,
                   "traffic": {"strategy": {}}}):
        assert reader(name)(facts) is None


@pytest.mark.parametrize("cell,names", [
    ("gpt2-base.serve-closed", NEW[:4]),
    ("gpt2-base.train-fold4-diloco", NEW[4:7]),
])
def test_traced_rehearsal_lists_the_span_metrics(run, cell, names):
    """The device's readers have nothing to read on the CPU; the ones that
    read the program's spans and counters do."""
    # the accepted ``train_mfu_pct`` refuses a device without peaks, as it
    # should: the rehearsal, which prints no number, borrows the v5e's
    patch = ("from perfbench import flops\n_peaks = flops.peaks\n"
             "flops.peaks = lambda kind: _peaks('TPU v5e')")
    code, out, err = run(["--workload", cell, "--seed", "3000000011",
                          "--seconds", "2", "--trace", "1", "--rehearse"],
                         patch=patch)
    assert code == 0, err[-2000:]
    line = last_json(out)
    assert line["correct"] and line["rehearsal"]
    assert set(names) <= set(line["metric_names"])
