"""The reduction from a profiler trace to busy, idle, kernel and collective
time: on hand-made events whose answers are known, and on a small trace
recorded on the chip and kept beside this file."""

import glob
import os

import pytest

from perfbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000.0            # events are in nanoseconds


def _planes():
    """Two chips. Chip 0: fusion 0-100us, an all-reduce 80-180us (20us of
    it under the fusion), a Pallas call 300-400us. Chip 1: one fusion
    0-200us. Host: a read-back 180-300us that explains chip 0's gap."""
    attn = ('%attn.7 = (bf16[2]) custom-call(bf16[2] %x), '
            'custom_call_target="tpu_custom_call"')
    return {
        "/device:TPU:0": {
            "XLA Ops": [("%fusion.12 = f32[4] fusion(f32[4] %attn_w)",
                         0.0, 100 * US),
                        ("%all-reduce.3 = f32[4] all-reduce(f32[4] %g)",
                         80 * US, 100 * US),
                        (attn, 300 * US, 100 * US)],
            "XLA Modules": [("jit_step(123)", 0.0, 180 * US),
                            ("jit_step(123)", 300 * US, 100 * US),
                            ("jit_step(123)", 600 * US, 100 * US)],
        },
        "/device:TPU:1": {
            "XLA Ops": [("%fusion.12 = f32[4] fusion(f32[4] %a)", 0.0,
                         200 * US)],
        },
        "/host:CPU": {
            "python3": [("$trainer.py:1 drain", 0.0, 1000 * US),
                        ("np.asarray(jax.Array)", 170 * US, 140 * US),
                        ("shard_args", 100 * US, 10 * US)],
        },
    }


def test_busy_is_the_union_and_collectives_know_what_hides_them():
    s = xplane.reduce_events(_planes())
    assert s.devices == 2
    # chip 0: [0,180] and [300,400] = 280us; chip 1: 200us; mean 240us
    assert s.busy_s == pytest.approx(240e-6)
    assert s.window_s == pytest.approx(400e-6)
    # 100us of all-reduce on chip 0, 80 of it with nothing else running
    assert s.collective_s == pytest.approx(50e-6)
    assert s.collective_exposed_s == pytest.approx(40e-6)
    assert s.op_seconds["fusion"] == pytest.approx(150e-6)
    assert s.op_seconds["all-reduce"] == pytest.approx(50e-6)
    assert s.top_ops(1)[0][0] == "fusion"


def test_kernels_are_found_by_their_custom_call_not_by_operand_names():
    s = xplane.reduce_events(_planes())
    # the fusion that merely reads %attn_w is not a kernel
    assert s.custom_call_seconds() == pytest.approx(100e-6)
    assert s.custom_call_seconds(head="^%attn") == pytest.approx(100e-6)
    assert s.custom_call_seconds(head="^%other") == 0.0


def test_gaps_are_booked_to_what_the_host_was_doing():
    s = xplane.reduce_events(_planes())
    # chip 0 idles 180-300us under the read-back; the Python tracer's
    # frame that spans everything is not the answer
    assert s.idle_gaps == {"np_asarray_jax_Array": pytest.approx(120e-6)}


def test_the_step_program_and_its_period():
    s = xplane.reduce_events(_planes())
    assert s.main_module() == ("jit_step", 3, pytest.approx(380e-6))
    runs, seconds, period = s.main_module_step()
    assert (runs, seconds, period) == (3, pytest.approx(100e-6),
                                       pytest.approx(300e-6))


def test_no_device_operation_is_nothing_to_read():
    assert xplane.reduce_events({"/host:CPU": {"t": [("x", 0, 5)]}}) is None
    assert xplane.op_kind("%convolution_add_fusion.41 = bf16[2]") == \
        "convolution_add_fusion"
    assert xplane.op_kind("copy-done.7") == "copy-done"


def test_recorded_trace_from_the_chip():
    """``recorded/`` holds a trace of a few steps of a small program on a
    TPU v5e (see its README for how it was made)."""
    found = glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb"))
    if not found:
        pytest.skip("no recorded trace beside the tests")
    s = xplane.reduce_events(xplane.read_planes(found[0]))
    assert s is not None and s.devices == 1
    assert 0.0 < s.busy_s <= s.window_s
    runs, seconds, period = s.main_module_step()
    assert runs >= 3 and 0.0 < seconds <= period
    # the recorded program is matrix products and a sort
    assert set(s.op_seconds) & {"fusion", "convolution_fusion", "sort",
                                "convolution"}
    assert sum(s.op_seconds.values()) == pytest.approx(
        sum(s.op_names.values()), rel=1e-6)
