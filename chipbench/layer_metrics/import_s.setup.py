"""Seconds of the package's own import before the window opened: the
program's process span ``process.import``, from the first line of
``incubator_mxnet_tpu/__init__.py`` to its last (JAX's import inside it only
where the package was first to import JAX).
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "import_s")
