"""Provenance header for result blobs — who/what/where a number came from.

Every blob writer (scripts/chaos_soak.py, scripts/fleet_campaign.py)
stamps the same ``provenance`` block on its JSON blob so scripts/runstore.py
can index and compare figures across commits:

    {"provenance": {"git_sha": "79fc809", "jax": "0.4.x", "jaxlib": "...",
                    "device_kind": "TPU v4", "device_count": 4,
                    "dataset_source": "synthetic", "date": "2026-08-07"}}

Everything is best-effort and stdlib-only: git absent -> sha None; jax not
imported -> device fields None (this module NEVER imports jax itself — a
parent that leaves the chip to its child must stay jax-free); the
wall-clock ``date`` is PASSED IN by the caller (scripts layer), never read
here, keeping the module importable from clock-disciplined code. Historical blobs without
the block are tolerated everywhere (runstore indexes them headerless).
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys

log = logging.getLogger("fedml_tpu.obs.provenance")


def git_sha(cwd: str | None = None) -> str | None:
    """The short HEAD sha, or None outside a repo / without git."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=cwd or os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:  # noqa: BLE001 — provenance is best-effort
        log.debug("git sha lookup failed; provenance carries sha=None",
                  exc_info=True)
        return None


def _dist_version(name: str) -> str | None:
    try:
        from importlib import metadata
        return metadata.version(name)
    except Exception:  # noqa: BLE001
        log.debug("version lookup for %s failed", name, exc_info=True)
        return None


def _device_info() -> tuple[str | None, int | None]:
    """(device_kind, device_count) from an ALREADY-IMPORTED jax, else
    (None, None). Reading sys.modules instead of importing keeps a
    parent process that must never import jax safe to stamp from."""
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return None, None
    try:
        devs = jax_mod.devices()
        return devs[0].device_kind, len(devs)
    except Exception:  # noqa: BLE001
        log.debug("device enumeration failed; provenance device fields "
                  "are None", exc_info=True)
        return None, None


def provenance(date: str | None = None,
               dataset_source: str | None = None) -> dict:
    """The common provenance block. ``date`` is the caller's wall-clock
    date string (scripts stamp it; nothing here reads a clock)."""
    kind, count = _device_info()
    return {
        "git_sha": git_sha(),
        "jax": _dist_version("jax"),
        "jaxlib": _dist_version("jaxlib"),
        "device_kind": kind,
        "device_count": count,
        "dataset_source": dataset_source,
        "date": date,
    }


def stamp(blob: dict, date: str | None = None,
          dataset_source: str | None = None) -> dict:
    """Attach the provenance block to a result blob in place (and return
    it). Never overwrites an existing block — code that re-emits a measured
    line must not clobber the measuring process's stamp."""
    if "provenance" not in blob:
        blob["provenance"] = provenance(date=date,
                                        dataset_source=dataset_source)
    return blob
