#!/usr/bin/env python3
"""The tests' entry to chipbench for the readers of set-up's process trace
(``chipbench/setup_spans.py`` and the eight ``*.setup`` / ``first_call_rest_s``
/ ``setup_span_coverage`` readers): the toy cells of tests/chipbench/toy/,
reused by path, under a manifest that lists those metrics beside the four
that ``moves: setup_s`` had -- as tests/chipbench/rehearse.py is for that
directory's own manifest: the same harness on whatever JAX finds."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from chipbench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(
        manifest_path=os.path.join(HERE, "BENCHMARK.json"),
        rehearse=True))
