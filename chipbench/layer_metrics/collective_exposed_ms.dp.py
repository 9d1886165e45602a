"""The part of the collectives' device time during which no other operation
runs on that device, ms a step, mean over the mesh's devices.  Layer:
collectives.  Source: device trace."""


def read(run):
    trace = run["trace"]
    if not trace or trace["collective_exposed_s"] is None:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
