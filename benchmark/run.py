"""The benchmark's one command: runs one cell once on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's population and weights from the seed, builds the engine,
drives the first rounds through the window's own call (which compiles, or
finds the programs in the cache), measures a window, frees the engine,
follows the same first rounds with the plain reference and compares. The
last line of standard output is the result; everything else goes to
standard error. Exits non-zero, with no result line, without a TPU or with
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# a traced run measures this long: two dispatch units at the least
TRACE_SECONDS = 6.0

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, check, engine  # noqa: E402


def say(msg: str) -> None:
    print(f"bench[{time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def find_chips(chips: int):
    """The devices of the run, or SystemExit without ``chips`` TPU chips or
    with a kind that the table of peaks does not hold."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, jax found "
                         f"{len(devs)}")
    try:
        peaks = cells.load_peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(str(e)) from e
    return devs[:chips], peaks


def configure_jax(config: dict) -> None:
    """The compile cache at a fixed path inside the checkout (or where the
    environment says), every program kept; the configuration's matmul
    precision as jax's default."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_default_matmul_precision",
                      config.get("matmul_precision", "default"))


def _loss_per_round(units, rounds_per_unit):
    import numpy as np

    loss = np.concatenate([np.atleast_1d(np.asarray(m["loss_sum"], np.float64))
                           for m in units])
    count = np.concatenate([np.atleast_1d(np.asarray(m["count"], np.float64))
                            for m in units])
    if len(loss) != len(units) * rounds_per_unit:
        raise RuntimeError(f"{len(units)} units of {rounds_per_unit} rounds "
                           f"returned {len(loss)} losses")
    return loss / np.maximum(count, 1.0), count


def memory_peak(devs) -> int:
    """Peak bytes on the fullest chip: the allocator's peak of live arrays
    plus what it reserved for the loaded programs' temporaries. On this
    runtime ``peak_bytes_in_use`` counts live arrays alone (0.65 GB beside
    5.2 GB reserved, femnist_cnn_c256_block, my chip run, PR 25); the two
    regions are disjoint (limit - in use - reserved = largest free block)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def prepare(cell: dict, seed: int):
    """(population, weights on the device, the same weights on the host),
    all made from the seed by the benchmark's own code."""
    import jax
    import numpy as np

    configure_jax(cell["config"])
    data = cell["population"].make(cell["config"]["population"], seed)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    params = cell["reference"].init_params(key)
    return data, params, jax.tree.map(np.asarray, params)


def first_units(cell: dict, data, params):
    """Build the engine and drive its first ``check_units`` dispatch units
    through the window's own call: they compile (or hit the cache), and they
    are what the reference follows. Returns the driver, ready for the
    window, and what the timed path produced: each round's mean training
    loss and the global model after each unit."""
    import jax

    api = engine.build(cell["config"], cell["traffic"], data, params,
                       cell["chips"])
    driver = engine.Driver(api, cell["traffic"])
    say("engine built")
    first, models = [], {}
    for _ in range(int(cell["traffic"]["check_units"])):
        first.append(jax.block_until_ready(driver.unit()))
        models[driver.next_round] = driver.model()
    losses, _ = _loss_per_round(first, driver.rounds_per_unit)
    say(f"first {driver.next_round} rounds done: losses "
        f"{[round(float(v), 5) for v in losses]}")
    return driver, {"losses": [float(v) for v in losses], "models": models}


def follow_reference(cell: dict, data, init, rounds: int, **kw):
    """The plain reference over the same first rounds, from the same
    population and weights. ``kw`` reaches the round reference's
    ``run_rounds``: a fault planted in ``pack``."""
    import jax

    t0 = time.perf_counter()
    fed = engine.fed_settings(cell["config"], cell["traffic"])
    losses, _, models = cell["round_reference"].run_rounds(
        cell["reference"].forward, jax.tree.map(jax.numpy.asarray, init),
        data, fed, fed["seed"], rounds,
        client_block=int(cell["config"]["reference_client_block"]), **kw)
    say(f"reference: {rounds} rounds in {time.perf_counter() - t0:.2f}s")
    return {"losses": losses, "init": init,
            "models": {i + 1: m for i, m in enumerate(models)}}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             devs, peaks: dict | None, out=sys.stdout) -> dict:
    """One run of one cell on ``devs``; prints and returns the result."""
    import jax

    traffic = cell["traffic"]
    say("imports done")
    data, params, init = prepare(cell, seed)
    say(f"population: {len(data.train_x)} rows, {data.num_clients} clients; "
        "weights made")
    driver, prog = first_units(cell, data, params)
    api = driver.api

    tracedir = None
    stack = contextlib.ExitStack()
    annotate = None
    if trace:
        tracedir = tempfile.mkdtemp(prefix="bench_trace_")
        seconds = min(seconds, TRACE_SECONDS)
        annotate = jax.profiler.TraceAnnotation
        stack.enter_context(jax.profiler.trace(tracedir))
    before, spans0 = engine.compile_counters(), engine.host_spans(api)
    setup_s = time.perf_counter() - T_START
    with stack:
        units, elapsed = engine.drive_window(driver, seconds, annotate)
    after, spans1 = engine.compile_counters(), engine.host_spans(api)
    compiled = {k: after[k] - before[k] for k in after}
    _, counts = _loss_per_round(units, driver.rounds_per_unit)
    rounds, samples = len(counts), float(counts.sum())
    say(f"window: {rounds} rounds in {elapsed:.3f}s, compiles {compiled}")
    if any(compiled.values()):
        raise RuntimeError(f"compiled inside the window: {compiled}")
    mem_peak = memory_peak(devs)

    bs, B = api.cfg.batch_size, api.num_batches
    run = {
        "rounds": rounds, "samples": samples, "elapsed_s": elapsed,
        "setup_s": setup_s,
        "slots": float(rounds * api.cfg.client_num_per_round * B * bs),
        "clients_per_round": api.cfg.client_num_per_round,
        "batches": B, "batch_size": bs, "chips": len(devs),
        "spans_s": {k: spans1.get(k, 0.0) - spans0.get(k, 0.0)
                    for k in spans1},
        "counts": cell["counts"], "peaks": peaks, "trace": None,
    }
    n_ref = driver.next_round - rounds  # the rounds before the window
    ref_data = (data.train_x, data.train_y, data.train_idx_map)
    del api, driver, units, params, data
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace:
        from benchmark import trace_reduce

        run["trace"] = trace_reduce.reduce_dir(tracedir)
        shutil.rmtree(tracedir, ignore_errors=True)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = run["trace"]["breakdown"]
        say(f"trace: busy {device['busy_s']:.3f}s of {device['window_s']:.3f}s")

    # the plain reference follows the same first rounds, now that the
    # engine's state is freed and the peak has been read
    ref = follow_reference(cell, ref_data, init, n_ref)
    correct, compared = check.decide(check.numbers(prog, ref),
                                     cell["limits"])

    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for name in cell[group]:
        reader = cells.metric_reader(group, name)
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    result = {"correct": bool(correct), "attempted": rounds, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(cells.load_benchmark(), args.workload)
    devs, peaks = find_chips(cell["chips"])
    run_cell(cell, args.seed, args.seconds, bool(args.trace), devs=devs,
             peaks=peaks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
