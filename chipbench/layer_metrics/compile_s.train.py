"""Host time of the step's first call: compilation, or a read of the
persistent compile cache, plus one step.  Layer: jit choke point.  Source:
host clock."""


def read(run):
    return run["first_call_s"]
