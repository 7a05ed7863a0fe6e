"""Layer: Strategy collectives. What DiLoCo's outer step costs a round:
the median period (retirement to retirement, ``fit.retire.wait`` spans)
of the timed fit's steps that take the outer branch (``step % H == 0``,
``step > 0``) less the median period of the others. The device is busy
throughout, so a longer step shows as a later retirement. A handful of
samples a window (one a round). From the program's span recorder. Moves
``train_tokens_per_s``."""
import statistics

from perfbench import spans


def read(facts):
    if facts.get("kind") != "fit":
        return None
    h = facts["traffic"]["strategy"].get("kwargs", {}).get("H")
    periods = spans.retire_periods(spans.fit_records("timed"))
    if not h or not periods:
        return None
    outer = [p for s, p, _w, _d in periods if s % h == 0]
    inner = [p for s, p, _w, _d in periods if s % h]
    if len(outer) < 2 or len(inner) < 2:
        return None
    return 1e3 * (statistics.median(outer) - statistics.median(inner))
