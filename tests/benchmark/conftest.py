"""Fixtures of the benchmark's CPU tests: the repo root on ``sys.path`` and
one toy checkout per test module (see ``toyroot.py``)."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import toyroot  # noqa: E402  (this directory is on sys.path under pytest)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toyroot.make(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def run_toy(toy_root):
    """Drive one toy cell through ``run.py``'s own ``run_cell`` in this
    process (a rehearsal: the CPU platform, counts only)."""
    import argparse
    import time

    from benchmark import run as bench_run

    def go(workload, seed=3, seconds=1.5, trace=0, rehearse=True):
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=trace,
                                  rehearse=rehearse)
        return bench_run.run_cell(args, time.perf_counter(), root=toy_root)
    return go
