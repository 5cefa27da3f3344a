"""Device-memory + host-RSS telemetry — the HBM view of a running fleet.

A mesh that exhausts HBM fails late and opaquely (an XLA allocation error
rounds in, long after the growth started); host-side leaks on a
million-client simulation kill the box the same way. This sampler makes
both visible while the run is still alive:

- per-device stats from ``jax.local_devices()[i].memory_stats()`` — TPU
  and GPU backends report ``bytes_in_use`` / ``peak_bytes_in_use`` /
  ``bytes_limit``; the CPU backend returns ``None``, which degrades to a
  graceful no-op (host RSS still reports). On the TPU runtime
  ``peak_bytes_in_use`` counts live arrays alone; what the allocator set
  aside for the loaded programs' temporaries is ``peak_bytes_reserved``, a
  disjoint region ten times the size on a ResNet-56 block (0.4 GB beside
  4.89 GB; PERF.md section 6, PR 22 and PR 25). The peak gauge is their sum;
- host RSS from ``/proc/self/status`` (``VmRSS``), the same figure ``top``
  shows — absent on non-procfs hosts, again a graceful no-op.

Gauges (process registry, scraped live via obs/httpd and dumped at close):

    fed_device_bytes_in_use{device}     current HBM bytes per local device
    fed_device_peak_bytes{device}       high-water mark per local device:
                                        live peak plus reserved peak
    fed_device_peak_live_bytes{device}  its parts: ``peak_bytes_in_use``
    fed_device_peak_reserved_bytes{device}  and ``peak_bytes_reserved``
                                        (only where the backend reports
                                        a reserved peak)
    fed_device_bytes_limit{device}      allocator capacity (feeds the
                                        health rule table's device_memory
                                        fraction, obs/health.py)
    fed_host_rss_bytes                  resident set size of this process

Opt-in via ``Telemetry(memwatch=...)``: a background daemon thread samples
every ``interval_s`` so scrapes between rounds stay fresh, and
``sample()`` runs synchronously at each round record so the ``mem`` block
on round records is exact-at-emit, not up-to-interval stale. Off (the
default): zero threads, zero gauges, nothing.
"""

from __future__ import annotations

import logging
import threading

from fedml_tpu.obs.metrics import REGISTRY, MetricsRegistry

log = logging.getLogger("fedml_tpu.obs.memwatch")


def host_rss_bytes() -> int | None:
    """Resident set size from ``/proc/self/status`` (VmRSS, kB); None where
    procfs is absent — callers must treat None as 'unknown', not 0."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        return None
    return None


def device_memory_stats() -> dict[str, dict]:
    """{device-label: {bytes_in_use, peak_bytes, peak_live_bytes,
    bytes_limit}} over ``jax.local_devices()``, with
    ``peak_reserved_bytes`` where the backend reports
    ``peak_bytes_reserved``; ``peak_bytes`` is live peak plus reserved peak.
    Backends without allocator stats (CPU) return None from
    ``memory_stats()`` and are skipped entirely — an empty dict means
    'nothing to report', never 'zero bytes'."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no jax / backend not up: no stats
        log.debug("device memory stats unavailable (no jax backend)",
                  exc_info=True)
        return {}
    out: dict[str, dict] = {}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-device probe is best-effort
            log.debug("memory_stats probe failed on %s", d, exc_info=True)
            stats = None
        if not stats:
            continue
        live = int(stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0)))
        entry = out[f"{d.platform}:{d.id}"] = {
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes": live,
            "peak_live_bytes": live,
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        }
        if "peak_bytes_reserved" in stats:
            entry["peak_reserved_bytes"] = int(stats["peak_bytes_reserved"])
            entry["peak_bytes"] = live + entry["peak_reserved_bytes"]
    return out


class MemoryWatcher:
    """Background sampler feeding the memory gauges. ``sample()`` is also
    callable synchronously (Telemetry calls it at every round record) and
    returns the compact ``mem`` block the event schema carries."""

    def __init__(self, interval_s: float = 5.0,
                 registry: MetricsRegistry | None = None):
        self.interval_s = float(interval_s)
        self.registry = registry or REGISTRY
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        self.last: dict | None = None  # most recent sample (health rules)

    # -------------------------------------------------------------- sampling
    def sample(self) -> dict:
        """One synchronous sample: update the gauges, remember it for the
        health rules, and return the round-record ``mem`` block —
        {host_rss_bytes, device_bytes_in_use, device_peak_bytes} with
        absent sources omitted (the block must stay honest on CPU)."""
        block: dict = {}
        rss = host_rss_bytes()
        if rss is not None:
            self.registry.gauge("fed_host_rss_bytes").set(rss)
            block["host_rss_bytes"] = rss
        devs = device_memory_stats()
        for label, st in devs.items():
            self.registry.gauge("fed_device_bytes_in_use",
                                device=label).set(st["bytes_in_use"])
            self.registry.gauge("fed_device_peak_bytes",
                                device=label).set(st["peak_bytes"])
            self.registry.gauge("fed_device_peak_live_bytes",
                                device=label).set(st["peak_live_bytes"])
            if "peak_reserved_bytes" in st:
                self.registry.gauge(
                    "fed_device_peak_reserved_bytes",
                    device=label).set(st["peak_reserved_bytes"])
            if st["bytes_limit"]:
                self.registry.gauge("fed_device_bytes_limit",
                                    device=label).set(st["bytes_limit"])
        if devs:
            block["device_bytes_in_use"] = sum(
                st["bytes_in_use"] for st in devs.values())
            block["device_peak_bytes"] = max(
                st["peak_bytes"] for st in devs.values())
        snap = {"host_rss_bytes": rss, "devices": devs}
        with self._lock:
            self.last = snap
        return block

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "MemoryWatcher":
        """Arm the background thread (idempotent). One immediate sample so
        gauges exist before the first interval elapses."""
        if self._thread is not None:
            return self
        self.sample()
        self._thread = threading.Thread(target=self._loop,
                                        name="obs-memwatch", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample()
            except Exception:  # noqa: BLE001 — telemetry must never kill a run
                log.exception("memory sample failed (continuing)")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
