"""Model-FLOPs accounting for MFU reporting (BASELINE.md north-star rows).

Instead of hand-counting each architecture, ask XLA: the compiled forward's
``cost_analysis()["flops"]`` is the compiler's own FLOP count for the real
program on the real backend. Train-step FLOPs use the standard 3x-forward
accounting (fwd + 2 bwd matmul passes). MFU is quoted against the chip's
bf16 peak (same convention as bench.py: f32 runs still quote bf16 peak —
conservative, since XLA routes f32 contractions through the MXU).
"""

from __future__ import annotations

# public per-chip bf16 dense-matmul peaks, FLOPs/s (bench.py table; more
# specific keys first — substring match)
PEAK_BF16 = {"v5 lite": 1.97e14, "v5e": 1.97e14, "v5p": 4.59e14,
             "v6 lite": 9.18e14, "v6e": 9.18e14,
             "v4": 2.75e14, "v3": 1.23e14, "v2": 4.5e13}


def compiled_flops(fn, *args) -> float | None:
    """XLA's FLOP estimate for ``jit(fn)(*args)``; None when the backend
    does not expose cost analysis. Never raises — MFU is garnish."""
    try:
        import jax

        c = jax.jit(fn).lower(*args).compile()
        ca = c.cost_analysis()
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:  # noqa: BLE001
        return None


def bf16_peak() -> float | None:
    """This process's per-chip bf16 peak, or None off-TPU / on an unknown
    generation (a guessed peak would misreport, ADVICE r4)."""
    try:
        import jax

        d = jax.devices()[0]
        if d.platform != "tpu":
            return None
        kind = d.device_kind.lower()
        return next((v for k, v in PEAK_BF16.items() if k in kind), None)
    except Exception:  # noqa: BLE001
        return None


def train_mfu(samples_per_sec_per_chip: float,
              fwd_flops_per_sample: float) -> float | None:
    """MFU of a training loop: 3x-forward accounting vs bf16 peak."""
    peak = bf16_peak()
    if peak is None or not fwd_flops_per_sample:
        return None
    return samples_per_sec_per_chip * 3.0 * fwd_flops_per_sample / peak
