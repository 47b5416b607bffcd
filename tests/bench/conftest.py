"""Fixtures of the benchmark's tests.

The tests run on the CPU and load no TPU library: the harness refuses to
report without a chip, so the tests drive it through ``run.run`` with the
CPU devices handed in and the cell cut to a tiny size (``benchtest``).
"""

import pytest

import benchtest


@pytest.fixture(scope="session")
def bench_run():
    return benchtest.load_run()


@pytest.fixture
def small_cell(bench_run, monkeypatch):
    """``bench_run`` with ``benchtest.on_cpu`` applied."""
    benchtest.on_cpu(bench_run, monkeypatch.setattr)
    return bench_run
