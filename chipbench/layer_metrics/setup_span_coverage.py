"""Seconds of set-up under any of the program's process spans / ``setup_s``:
the tracing's own ratio for set-up, as ``scope_coverage`` is for the device.
What it cannot reach is the benchmark's own part of set-up: the interpreter's
start, the batches made on the host, the pool's ``device_put``, the warm-up
steps' waits.  Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    coverage = setup_spans.seconds(run, "coverage")
    return None if coverage is None else 100.0 * coverage
