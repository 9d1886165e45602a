#!/usr/bin/env python3
"""The tests' entry to chipbench for the toy cell of this directory
(``toy-nemotron-h-train``: the decoder whose layers differ in kind, with routed experts in a latent,
through the runner that compares with the layer-wise plain reference and reads the routers' counters), as
tests/chipbench/rehearse.py is for the toy cells of tests/chipbench/toy/:
the same harness on whatever JAX finds."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from chipbench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(
        manifest_path=os.path.join(HERE, "BENCHMARK.json"),
        rehearse=True))
