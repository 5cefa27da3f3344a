"""Tier-1 runs the benchmark's own CPU tests of the readers of the round
program's matrix work (``benchmark/tests/test_matmul_readers.py``),
collected here unchanged."""

from benchmark.tests.test_matmul_readers import *  # noqa: F401,F403
