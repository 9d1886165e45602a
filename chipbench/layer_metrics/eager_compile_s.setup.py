"""Seconds tracing, lowering and compiling (or reading from the persistent
cache) every jitted function other than the fused train step, before the
window opened: the eager shape-resolving pass, ``amp``, the key split.  The
program's own ``jax.monitoring`` counters at its jit choke point.  Layer: jit
choke point.  Source: program counter."""
from chipbench import scope_reduce


def read(run):
    return scope_reduce.compile_seconds(
        scope_reduce.program_compile_log(), run["window_open"], step=False,
        fields=("trace_s", "lower_s", "backend_compile_s"))
