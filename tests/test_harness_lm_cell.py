"""Tier-1 runs the benchmark's own CPU tests: the cases of
``benchmark/tests/test_lm_cell.py``, collected here unchanged. They drive
whole runs of the small language-model cell, so they have a file of their
own that ``--dist loadfile`` can place apart."""

import jax
import pytest

from benchmark.tests.test_lm_cell import *  # noqa: F401,F403
from benchmark.tests.test_lm_cell import _no_compile_cache  # noqa: F401


@pytest.fixture(autouse=True, scope="module")
def _compile_cache_as_found():
    """``_no_compile_cache`` switches the persistent cache off and leaves
    it off: this worker's later files want it back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
