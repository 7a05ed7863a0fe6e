"""The stall witnesses of the ``fit`` kind: ``pace`` over the prefetch
thread's draws, and the heartbeat thread."""

import time

import numpy as np

from perfbench import harness
from perfbench.kinds import fit


def test_pace_finds_a_stall_and_where():
    # 8 draws of warm-up, then 300 steps of 0.1 s with 2 s lost at step 120
    gaps = np.full(307, 0.1)
    gaps[8 + 120] += 2.0
    draws = np.concatenate([[0.0], np.cumsum(gaps)])
    got = fit.pace(list(draws), 100)
    assert abs(got["gap_ms_p50"] - 100.0) < 1e-6
    assert abs(got["gap_ms_max"] - 2100.0) < 1e-6
    assert got["gaps_late"] == 1
    assert abs(got["late_excess_s"] - 2.0) < 1e-9
    assert got["late"] == [[8 + 120, 12.0, 2100.0]]
    assert got["block_s"] == [10.0, 12.0]


def test_pace_of_an_even_run_has_nothing_late():
    got = fit.pace(list(np.arange(0, 50, 0.1)), 100)
    assert got["gaps_late"] == 0 and got["late"] == []
    assert fit.pace([0.0, 0.1], 100) == {}


def test_heartbeat_is_quiet_through_a_wait_and_stops():
    heart = harness.Heartbeat(every=0.01, late_s=0.05)
    lo = time.monotonic()
    heart.start()
    time.sleep(0.3)                     # a wait that releases the GIL
    got = heart.close(lo, time.monotonic())
    assert got["late"] == [] and got["late_max_s"] == 0.0
    assert got["beats"] > 5
    assert not heart.is_alive()
