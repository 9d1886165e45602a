"""Device time of the collective operations, ms a step, mean over the
mesh's devices.  Layer: collectives.  Source: device trace."""


def read(run):
    trace = run["trace"]
    if not trace or trace["collective_s"] is None:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
