"""Tier-1 runs the benchmark's own CPU tests: the cases of
``benchmark/tests/test_benchmark_json.py``, collected here unchanged (the harness that
judges every PR is under ``benchmark/``, which ``pytest tests/`` does not
reach)."""

from benchmark.tests.test_benchmark_json import *  # noqa: F401,F403
