"""Host time for one ``step(x, y)`` call to return (key split, analyses'
latches, the jitted call's dispatch), median over the window's steps.
Layer: entry points.  Source: host clock."""
from chipbench import stats


def read(run):
    return 1e3 * stats.median(run["step_dispatch_s"])
