"""The package's one table of per-chip bf16 peaks.

``chip_smoke.py`` checks the attached chip against it and ``obs/goodput.py``
divides by it. f32 runs still quote the bf16 peak: XLA routes f32
contractions through the MXU. The benchmark keeps a table of its own
(``benchmark/peaks.json``), which the package does not import;
``tests/test_goodput.py`` holds the two to the same v5e figure.
"""

from __future__ import annotations

# public per-chip bf16 dense-matmul peaks, FLOPs/s, by substring of the
# lowercased device kind: more specific keys first, the first hit wins
PEAK_BF16 = {"v5 lite": 1.97e14, "v5e": 1.97e14, "v5p": 4.59e14,
             "v6 lite": 9.18e14, "v6e": 9.18e14,
             "v4": 2.75e14, "v3": 1.23e14, "v2": 4.5e13}


def peak_for_kind(device_kind) -> float | None:
    """The bf16 peak of one chip of ``device_kind``; None for a kind the
    table does not hold (a guessed peak would misreport, ADVICE r4)."""
    kind = str(device_kind).lower()
    return next((v for k, v in PEAK_BF16.items() if k in kind), None)


def bf16_peak() -> float | None:
    """This process's per-chip bf16 peak, or None off-TPU / on an unknown
    generation."""
    try:
        import jax

        d = jax.devices()[0]
        if d.platform != "tpu":
            return None
        return peak_for_kind(d.device_kind)
    except Exception:  # noqa: BLE001
        return None
