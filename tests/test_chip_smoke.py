"""chip_smoke.py, the bring-up check, claims a chip: without a TPU it
refuses to run. Non-zero exit, no ``ok`` line, no fallback. (The
benchmark's own refusal is ``benchmark/tests/test_refuses_without_tpu.py``,
run in tier-1 through ``tests/test_harness_refuses_without_tpu.py``.)"""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_without_tpu_exits_nonzero_with_no_ok_line():
    """The chip check's first leg: in a sandbox without an accelerator the
    smoke exits non-zero and prints no ``ok`` line (it never sets
    JAX_PLATFORMS and never retries on another backend)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
