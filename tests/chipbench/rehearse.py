#!/usr/bin/env python3
"""The tests' entry to chipbench: the same harness, runner and readers over
the toy cells of tests/chipbench/toy/, on whatever JAX finds (here a CPU,
which the result line then names as its platform).  Only the tests use it;
the command the driver runs is chipbench/run.py, which has no such door."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(
        manifest_path=os.path.join(HERE, "toy", "BENCHMARK.json"),
        rehearse=True))
