"""Seconds JAX spent tracing the fused train step's function to a jaxpr and
lowering it to a module, before the window opened: the program's own
``jax.monitoring`` counters at its jit choke point
(``executor_cache.compile_log()``).  Layer: jit choke point.  Source: program
counter."""
from chipbench import scope_reduce


def read(run):
    return scope_reduce.compile_seconds(
        scope_reduce.program_compile_log(), run["window_open"], step=True,
        fields=("trace_s", "lower_s"))
