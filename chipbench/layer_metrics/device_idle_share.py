"""1 - union of device-operation intervals / traced window, on the worst
device of the mesh.  Layer: device.  Source: device trace."""


def read(run):
    trace = run["trace"]
    return trace and 100.0 * trace["idle_share_worst"]
