"""Layer: Step program (host side). The fit loop's own time a step: the
period from one step's retirement to the next (the end of one
``fit.retire.wait`` span to the end of the next) less that wait, in which
the host waits for the device, and less the ``fit.data_wait`` inside it;
median over the timed fit's steps. What is left is dispatch, logging and
the loop: the slack before the host, not the device, sets the pace is the
step's device time less this. From the program's span recorder. Moves
``train_tokens_per_s``."""
import statistics

from perfbench import spans


def read(facts):
    if facts.get("kind") != "fit":
        return None
    periods = spans.retire_periods(spans.fit_records("timed"))
    if len(periods) < 3:
        return None
    return 1e3 * statistics.median(p - w - d for _s, p, w, d in periods)
