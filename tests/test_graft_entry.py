"""The driver's entry surface must keep compiling: entry() single-device and
dryrun_multichip (client mesh + the extra mesh stages), in this process on
the virtual CPU mesh the conftest provides."""

import jax
import pytest


def test_entry_compiles_and_runs():
    import __graft_entry__ as g

    fn, args = g.entry()
    loss, metrics = jax.jit(fn)(*args)
    assert float(loss) > 0


@pytest.mark.parametrize("n", [4, 8])
def test_dryrun_multichip(n):
    import __graft_entry__ as g

    if len(jax.devices()) < n:
        pytest.skip(f"need {n} virtual devices")
    g.dryrun_multichip(n)
