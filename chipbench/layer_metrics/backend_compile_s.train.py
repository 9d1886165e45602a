"""Seconds in the backend compile of the fused train step, or in reading it
from the persistent cache (which the backend compile's duration encloses),
before the window opened: the program's own ``jax.monitoring`` counters at
its jit choke point.  Layer: jit choke point.  Source: program counter."""
from chipbench import scope_reduce


def read(run):
    return scope_reduce.compile_seconds(
        scope_reduce.program_compile_log(), run["window_open"], step=True,
        fields=("backend_compile_s",))
