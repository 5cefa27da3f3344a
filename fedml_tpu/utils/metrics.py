"""Metrics sink + logging (L-aux).

The reference's observability is wandb on rank 0 (main_fedavg.py:300-308,
FedAVGAggregator.py:136-162 wandb.log of Train/Acc etc.) plus rank-prefixed
python logging (fedml_api/utils/logger.py:8-33). In zero-egress TPU
environments wandb is unavailable, so the sink is local-first: an append-only
JSONL run log + in-memory summary (the wandb-summary.json analogue the
reference's CI consumes, CI-script-fedavg.sh:42-46). If wandb IS importable
and WANDB_MODE allows it, it mirrors transparently.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any


# The one place the compile cache lives when the environment names none: a
# fixed path inside the checkout (the path is part of the cache key, so a
# directory that moves never hits).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Persistent XLA compile cache: a repeat run of the same program
    deserializes instead of compiling. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, jax has already read it and no directory is set here; otherwise
    the cache goes to ``COMPILE_CACHE_DIR``. Call before the first jit.
    Returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # programs quicker than 1 s to compile are not worth a file: keeps the
    # directory small enough to travel with the tree
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir


def set_process_title(title: str) -> None:
    """Name the OS process (reference: setproctitle at main_fedavg.py:284-285)
    so ps/top show the role; silently skipped when setproctitle is absent."""
    try:
        import setproctitle

        setproctitle.setproctitle(title)
    except Exception:
        pass


def setup_logging(process_name: str = "fedml-tpu", level=logging.INFO,
                  log_dir: str | None = None):
    """Rank/process-prefixed format (logger.py:8-33 analogue)."""
    fmt = (f"[{process_name}] %(asctime)s %(levelname)s "
           "%(name)s:%(lineno)d %(message)s")
    handlers = [logging.StreamHandler()]
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        handlers.append(logging.FileHandler(
            os.path.join(log_dir, f"{process_name}.log")))
    logging.basicConfig(level=level, format=fmt, handlers=handlers, force=True)


class RunLogger:
    """wandb-compatible facade writing JSONL locally (and to wandb if live)."""

    def __init__(self, run_dir: str = "./runs", name: str | None = None,
                 config: dict | None = None, use_wandb: bool = False):
        self.name = name or time.strftime("run_%Y%m%d_%H%M%S")
        self.dir = os.path.join(run_dir, self.name)
        os.makedirs(self.dir, exist_ok=True)
        self.summary: dict[str, Any] = {}
        self._f = open(os.path.join(self.dir, "metrics.jsonl"), "a")
        if config:
            with open(os.path.join(self.dir, "config.json"), "w") as f:
                json.dump(config, f, indent=2, default=str)
        self._wandb = None
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb.init(project="fedml-tpu", name=self.name,
                                         config=config or {})
            except Exception:
                self._wandb = None

    def log(self, metrics: dict, step: int | None = None):
        rec = dict(metrics)
        if step is not None:
            rec["_step"] = step
        rec["_time"] = time.time()
        self._f.write(json.dumps(rec, default=float) + "\n")
        self._f.flush()
        self.summary.update(metrics)
        if self._wandb is not None:
            self._wandb.log(metrics, step=step)

    # reference metric names (FedAVGAggregator.py:136-162 wandb.log keys).
    # Train/Acc is the all-clients aggregate of the CURRENT model on train
    # splits (_local_test_on_all_clients) — train_all_* when the run produced
    # it, else the in-round sampled-client training metric as the closest
    # available analogue (listed later so the per-client aggregate wins).
    _WANDB_KEYS = (
        ("train_acc", "Train/Acc"), ("train_loss", "Train/Loss"),
        ("train_all_acc", "Train/Acc"), ("train_all_loss", "Train/Loss"),
        ("test_acc", "Test/Acc"), ("test_loss", "Test/Loss"),
        ("round", "round"),
    )

    def _wandb_summary(self) -> dict:
        out = dict(self.summary)
        for src, dst in self._WANDB_KEYS:
            if src in self.summary:
                out[dst] = self.summary[src]
        return out

    def finish(self):
        """Write the summary files: ``summary.json`` (raw keys) and a
        wandb-interop ``wandb-summary.json`` with the reference's metric
        names, also linked at ``<run_dir>/latest-run/files/wandb-summary.json``
        — the exact path shape the reference CI consumes
        (``wandb/latest-run/files/wandb-summary.json``,
        CI-script-fedavg.sh:42-46), so tooling written against the reference
        can point its ``wandb`` dir at ``run_dir`` unchanged."""
        with open(os.path.join(self.dir, "summary.json"), "w") as f:
            json.dump(self.summary, f, indent=2, default=float)
        wandb_summary = self._wandb_summary()
        with open(os.path.join(self.dir, "wandb-summary.json"), "w") as f:
            json.dump(wandb_summary, f, indent=2, default=float)
        latest = os.path.join(os.path.dirname(self.dir), "latest-run", "files")
        try:
            os.makedirs(latest, exist_ok=True)
            with open(os.path.join(latest, "wandb-summary.json"), "w") as f:
                json.dump(wandb_summary, f, indent=2, default=float)
        except OSError:
            pass  # read-only run_dir parent: the per-run copy above suffices
        self._f.close()
        if self._wandb is not None:
            self._wandb.finish()


def notify_sweep_done(path: str = "./tmp/fedml"):
    """Completion signal for sweep orchestrators — the reference writes into a
    named pipe (fedavg/utils.py:19-26); we write/touch a regular file if no
    fifo exists at ``path``."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    try:
        fd = os.open(path, os.O_WRONLY | os.O_NONBLOCK)
        os.write(fd, b"done\n")
        os.close(fd)
    except OSError:
        with open(path, "w") as f:
            f.write("done\n")
