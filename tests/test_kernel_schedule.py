"""``scripts/kernel_schedule.py``'s reading of the compiler's dump, on a
dump written by hand in the two files' own format: loops by their ``>``
marks, an empty bundle inside a loop, a loop's bodies cut where a
predicated region falls through, the slots' counts. The compile itself
needs the chip's compiler in a process of its own and is no test's."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUNDLES = """\
// kernel: paged_gqa_prefill_full.1
= control target key start
LH: loop header
LB: loop body
= control target key end

     0   :  { %s1 = smov 0  ;;  %s9 = smov 1 }
   0x1 LB: > { %s2 = sadd.s32 1, %s1 }
   0x2   : > { %v1 = vld [vmem:[#allocation2] sm:$0xff] }
   0x3 LB: >> { %v2 = vmatmul.bf16.gmra.mxu0 %v1  ;;  %v7 = vld [vmem:[#allocation3_spill] sm:$0xff] }
   0x4   : >> { %5 = vst [vmem:[#allocation5_spill] sm:$0xff] %v2 }
   0x5   :  {}
   0x6 PF: >> { %v4 = vadd.f32 %v2, %v2  ;;  %6 = vst [vmem:[#allocation2] sm:$0xff] %v4 }
   0x7   : >> { %v8 = vpop.f32.mrf.mxu1 }
   0x8   : > { %s3 = sadd.s32 1, %s2 }
   0x9 LB: >> { %v9 = vperm.xlu2 %v4 }
   0xa   :  { %s4 = smov 0 }
"""
#        MXU XLU VALU EUP VLD FILL VST SPILL SALU
UTIL = """\
== CAPACTIY:
MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU
    4     3     4     1     3     3     1     1     2
== UTILIZATION:
0 0 0 0 0 0 0 0 2
0 0 0 0 0 0 0 0 1
0 0 0 0 1 0 0 0 0
1 0 0 0 1 1 0 0 0
0 0 0 0 0 0 1 1 0
0 0 0 0 0 0 0 0 0
0 0 1 0 0 0 1 0 0
1 0 0 0 0 0 0 0 0
0 0 0 0 0 0 0 0 1
0 1 0 0 0 0 0 0 0
0 0 0 0 0 0 0 0 1
"""


@pytest.fixture(scope="module")
def schedule():
    spec = importlib.util.spec_from_file_location(
        "kernel_schedule", os.path.join(ROOT, "scripts",
                                        "kernel_schedule.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def loops(schedule, tmp_path):
    (tmp_path / "b.txt").write_text(BUNDLES)
    (tmp_path / "u.txt").write_text(UTIL)
    return schedule.loops_of(str(tmp_path / "b.txt"),
                             str(tmp_path / "u.txt"))


def test_loops_are_found_by_their_marks_and_nest(loops):
    assert [(lp["depth"], lp["bundles"]) for lp in loops] == [
        (1, 9), (2, 5), (2, 1)]
    # the grid's loop owns three lines, the rest are its inner loops'
    assert sum(r[0] for r in loops[0]["regions"]) == 3
    assert loops[1]["ops"] == {"vmatmul.bf16.gmra": 1, "vld": 1, "vst": 2,
                               "vadd.f32": 1, "vpop.f32.mrf": 1}
    assert loops[2]["ops"] == {"vperm": 1}


def test_a_loops_bodies_are_cut_where_a_region_falls_through(schedule,
                                                             loops):
    """The empty bundle stays in the body it follows; the second body
    starts at the ``PF`` mark."""
    first, second = loops[1]["regions"]
    assert (first[0], second[0]) == (3, 2)
    got = schedule.counts(first, vregs=2)
    assert got["bundles_a_score_vreg"] == 1.5
    assert (got["stores"], got["spill_stores"]) == (1, 1)
    assert (got["loads"], got["fill_loads"]) == (1, 1)
    assert got["fill_pct"]["MXU"] == round(100 / 12, 1)
    got = schedule.counts(second)
    assert (got["stores"], got["spill_stores"], got["bundles"]) == (1, 0, 2)
    assert "bundles_a_score_vreg" not in got


def test_the_two_files_must_hold_the_same_bundles(schedule, tmp_path):
    (tmp_path / "b.txt").write_text(BUNDLES)
    (tmp_path / "u.txt").write_text(UTIL + "0 0 0 0 0 0 0 0 0\n")
    with pytest.raises(AssertionError):
        schedule.loops_of(str(tmp_path / "b.txt"), str(tmp_path / "u.txt"))
