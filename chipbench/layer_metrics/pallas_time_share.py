"""Device time of Mosaic (Pallas) custom calls / device busy time.  Layer:
ops and kernels.  Source: device trace."""


def read(run):
    trace = run["trace"]
    if not trace or trace["pallas_s"] is None:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
