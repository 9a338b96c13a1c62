"""Where the benchmark lives, and its modules loaded by file path (its
directories are not packages: the driver runs `benchmarks/run.py` as a script)."""

from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(relpath: str):
    path = os.path.join(BENCH, relpath)
    name = "bench_test_" + relpath.replace("/", "_").replace("-", "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    if BENCH not in sys.path:  # run.py imports its siblings by bare name
        sys.path.insert(0, BENCH)
    spec.loader.exec_module(module)
    return module
