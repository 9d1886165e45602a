"""Arithmetic the metric readers share."""


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between the
    sorted values, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def step_intervals_ms(run):
    """Milliseconds between successive step completions on the host clock.
    A reading of it is off by some half a millisecond: half a percent of the
    shortest step a cell has today (94 ms).  A cell with a much shorter step
    needs a reader that spans several steps a reading."""
    done = run["step_done_at"]
    return [1e3 * (b - a) for a, b in zip(done, done[1:])]


def samples_per_s(run):
    """Samples of every step completed in the window over the time from its
    opening to the last completion."""
    done = run["step_done_at"]
    return len(done) * run["samples_per_step"] / (done[-1] - run["window_open"])
