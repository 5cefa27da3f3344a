"""Performance instrumentation — XLA compile accounting + pipeline metrics.

Two metric groups land in the process-wide ``metrics.REGISTRY``:

**Compile accounting** (fed by ``jax.monitoring`` listeners, installed once
per process by :func:`install`):

    fed_xla_compiles_total            backend compile passes — every
                                      ``/jax/core/compile/backend_compile_
                                      duration`` event. NOTE: on this jax a
                                      persistent-cache HIT still records
                                      one (the deserialize is timed under
                                      the same event), so a FRESH compile
                                      is defined by the cache counters
                                      below, not this one
    fed_xla_compile_seconds           (histogram) per-pass wall clock
    fed_xla_cache_requests_total      compile requests that consulted the
                                      persistent cache (0 = cache off)
    fed_xla_cache_hits_total          persistent compile-cache hits
    fed_xla_cache_misses_total        persistent compile-cache misses —
                                      the real "fresh compile" count when
                                      the cache is enabled

``engine.warmup()`` (algorithms/fedavg.py) diffs these around its AOT
compile pass, which is how the "repeat run performs zero fresh compiles"
contract is asserted rather than assumed: with the cache enabled,
fresh = cache misses; with it off (requests delta 0), fresh = compile
passes.

**Pipeline metrics** (fed by core/pipeline.py and the pipelined drivers):

    fed_h2d_seconds                   (histogram) host time issuing a round
                                      batch's host->device transfers —
                                      the device_put call, not the DMA
                                      itself (which is async on TPU)
    fed_prefetch_stall_seconds        (histogram) time the round driver
                                      waited for the prefetch thread — 0 on
                                      every round means the accelerator
                                      never saw a host-side pack stall
    fed_dispatch_depth                (gauge) rounds dispatched but not yet
                                      drained — the async-dispatch depth;
                                      the pipeline keeps this >= drain_lag

**Sharded-server-state metrics** (fed by the engines; docs/PERFORMANCE.md
§Partitioned server state):

    fed_agg_bytes_total{mode}         client-update bytes aggregated, by
                                      server-state mode (replicated |
                                      sharded)
    fed_server_state_bytes{placement} (gauge) PER-DEVICE bytes of the
                                      server plane (model + server opt
                                      state); sharded ~ replicated/ndev

All hooks are host-side and cheap (a dict lookup + float add via memoized
children, same pattern as obs/comm_instrument.py).
"""

from __future__ import annotations

import contextlib
import logging
import threading
from functools import lru_cache

from fedml_tpu.obs.metrics import REGISTRY

log = logging.getLogger("fedml_tpu.obs.perf")

_install_lock = threading.Lock()
_installed = False
_tls = threading.local()


@lru_cache(maxsize=8)
def _counter(name: str):
    # lru_cache indirection; every call site passes a fed_* literal
    return REGISTRY.counter(name)  # fedlint: disable=metric-discipline


@lru_cache(maxsize=8)
def _hist(name: str):
    # lru_cache indirection; every call site passes a fed_* literal
    return REGISTRY.histogram(name)  # fedlint: disable=metric-discipline


@lru_cache(maxsize=64)
def _span_hist(name: str):
    # the SAME family RoundTracer spans feed (obs/tracing.py) so the
    # prefetch thread's pack/transfer spans and the engine's host spans
    # read through one Prometheus name
    return REGISTRY.histogram("fed_span_seconds", span=name)


# ---------------------------------------------- per-variant attribution
# The compile observatory (docs/OBSERVABILITY.md §Compile observatory):
# jax.monitoring events fire ON THE COMPILING THREAD, so a thread-local
# variant tag set around a ``.compile()`` call attributes that thread's
# compile/cache events to the jit variant being built. Everything outside
# an :func:`attribute_compiles` scope (first-dispatch jit compiles, eval
# fns, ...) lands under the reserved ``variant="_other"`` child — which
# also gives the families a pre-registerable zero child.
#
#     fed_xla_variant_compile_seconds_total{variant}   backend compile wall:
#                                                      the compile, or on a
#                                                      persistent-cache hit
#                                                      the load in its place
#     fed_xla_variant_compiles_total{variant}          compile passes
#     fed_xla_variant_cache_hits_total{variant}        persistent-cache hits
#     fed_xla_variant_cache_misses_total{variant}      fresh compiles
#     fed_xla_variant_trace_seconds_total{variant}     python -> jaxpr
#     fed_xla_variant_lower_seconds_total{variant}     jaxpr -> MLIR module
#     fed_xla_variant_cache_retrieval_seconds_total{variant}
#                                                      the part of the compile
#                                                      wall a hit spent
#                                                      reading the cache
#
# The engine (algorithms/fedavg.py) installs the listeners when it is built
# and tags ``task.init`` with ``init``, every per-round dispatch and every
# warmup lowering with its variant name. The scanned block's dispatch is
# not tagged (its frames stay as they were: PERF.md section 6): jax hands
# every duration event the jit function's name, and an event that no scope
# claimed is named by it where it is one of the engine's round programs.
# So a first call's trace, lower and compile-or-load land under the program
# that paid them; :func:`setup_phases` is the read side.
UNATTRIBUTED_VARIANT = "_other"
INIT_VARIANT = "init"
# the trace and lowering of a program on its way into the program store
# (core/program_store.py): they run inside the stored jit's own trace,
# whose time already holds them, so they are tagged apart
EXPORT_VARIANT = "_export"
# the jit functions algorithms/fedavg.py builds its round programs as
ROUND_PROGRAMS = frozenset({"block_fn", "sharded_block_fn", "round_fn",
                            "robust_round_fn"})
# family -> the key variant_compile_stats() reports it under
_VARIANT_FAMILIES = {
    "fed_xla_variant_compile_seconds_total": "seconds",
    "fed_xla_variant_compiles_total": "compiles",
    "fed_xla_variant_cache_hits_total": "cache_hits",
    "fed_xla_variant_cache_misses_total": "cache_misses",
    "fed_xla_variant_trace_seconds_total": "trace_seconds",
    "fed_xla_variant_lower_seconds_total": "lower_seconds",
    "fed_xla_variant_cache_retrieval_seconds_total":
        "cache_retrieval_seconds",
}
# jax.monitoring duration event -> the per-variant seconds family it feeds
_DURATION_FAMILIES = {
    "/jax/core/compile/jaxpr_trace_duration":
        "fed_xla_variant_trace_seconds_total",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "fed_xla_variant_lower_seconds_total",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "fed_xla_variant_cache_retrieval_seconds_total",
}


@lru_cache(maxsize=256)
def _variant_counter(name: str, variant: str):
    # lru_cache indirection; every call site passes a fed_* literal
    return REGISTRY.counter(name, variant=variant)  # fedlint: disable=metric-discipline


def _compile_variant(fun_name: str = "") -> str:
    """The variant an event belongs to: the enclosing
    :func:`attribute_compiles` scope's, else the jit function's own name
    (``fun_name`` as jax passes it: ``block_fn`` when tracing,
    ``jit(block_fn)`` when lowering and compiling) where that is a round
    program, else ``_other``."""
    variant = getattr(_tls, "compile_variant", None)
    if variant:
        return variant
    name = fun_name.removeprefix("jit(").removesuffix(")")
    return name if name in ROUND_PROGRAMS else UNATTRIBUTED_VARIANT


@contextlib.contextmanager
def attribute_compiles(variant: str):
    """Attribute this thread's jax.monitoring compile events to ``variant``
    for the duration of the scope (reentrant; inner scope wins)."""
    prev = getattr(_tls, "compile_variant", None)
    _tls.compile_variant = str(variant)
    try:
        yield
    finally:
        _tls.compile_variant = prev


def variant_compile_stats() -> dict:
    """{variant: {seconds, compiles, cache_hits, cache_misses,
    trace_seconds, lower_seconds, cache_retrieval_seconds}} from the live
    registry — the compile observatory's read side (warmup reports,
    report.py --compiles via the warmup event record, setup_phases,
    tests). A key is absent where no such event reached the variant."""
    out: dict[str, dict] = {}
    snap = REGISTRY.snapshot()
    for fam_name, key in _VARIANT_FAMILIES.items():
        for label_s, value in (snap.get(fam_name) or {}).items():
            # snapshot() keys children as "k=v" strings (jsonable contract)
            if not label_s.startswith("variant="):
                continue
            variant = label_s.split("=", 1)[1]
            out.setdefault(variant, {})[key] = value
    return out


def setup_phases() -> dict:
    """Where set-up went, in seconds, from the spans and counters the
    engine feeds: ``init_s`` is the ``init`` span (engine build,
    ``task.init`` and its compiles included); ``trace_s``, ``lower_s`` and
    ``compile_or_load_s`` are summed over the round-program variants,
    which is every variant but ``_other`` (what no engine dispatch paid
    for; the cache's hit, miss and retrieval events of an untagged block
    dispatch too, which carry no function name), ``init`` (already
    inside ``init_s``) and ``_export`` (a program store miss, already
    inside the stored jit's trace). A jitted function
    called while another is traced reports its own trace inside the outer
    one's, so ``trace_s`` can overcount (PERF.md section 5 has the gap)."""
    init = REGISTRY.histogram("fed_span_seconds", span="init").total
    out = {"init_s": init, "trace_s": 0.0, "lower_s": 0.0,
           "compile_or_load_s": 0.0}
    for variant, st in variant_compile_stats().items():
        if variant in (UNATTRIBUTED_VARIANT, INIT_VARIANT, EXPORT_VARIANT):
            continue
        out["trace_s"] += st.get("trace_seconds", 0.0)
        out["lower_s"] += st.get("lower_seconds", 0.0)
        out["compile_or_load_s"] += st.get("seconds", 0.0)
    return out


def ensure_compile_attr_families() -> None:
    """Pre-register the per-variant compile families at zero (under the
    reserved ``_other`` child) so a clean run's export carries them."""
    for fam in _VARIANT_FAMILIES:
        _variant_counter(fam, UNATTRIBUTED_VARIANT)


# ------------------------------------------------ convolution call sites
# docs/PERFORMANCE.md §Width-packed convolutions. Fed at TRACE time by
# ops/packed_conv.conv_general_dilated, one inc for each nn.Conv call it is
# handed while a program is traced (so a model traced twice counts twice:
# read the two paths as a share of one another, not as absolutes):
#
#     fed_conv_sites_total{path,p,lays_out}
#                                       path=packed: the kernel gradient
#                                       (by pack_factor's rule the forward
#                                       pass too) is a width-packed
#                                       convolution at pack factor p, for
#                                       which lays_out=x|dy, the saved input
#                                       or the incoming gradient, is laid
#                                       out again (grad_lays_out);
#                                       path=plain (p=1, lays_out=none):
#                                       handed on to lax.conv_general_dilated
@lru_cache(maxsize=16)
def _conv_sites(p: int, lays_out: str):
    return REGISTRY.counter("fed_conv_sites_total",
                            path="packed" if p > 1 else "plain", p=p,
                            lays_out=lays_out)


def record_conv_site(p: int, lays_out: str, n: int = 1) -> None:
    _conv_sites(p, lays_out).inc(n)


def conv_sites() -> dict:
    """{"packed": n, "plain": n}: convolution call sites traced so far, by
    the path that served them."""
    fam = REGISTRY.snapshot().get("fed_conv_sites_total") or {}
    return {path: sum(v for labels, v in fam.items()
                      if f"path={path}" in labels.split(","))
            for path in ("packed", "plain")}


def conv_site_counts() -> dict:
    """{(p, lays_out): n}: the same sites by what ``record_conv_site`` was
    handed. The program store diffs this around a trace and keeps the
    difference with the program."""
    fam = REGISTRY.snapshot().get("fed_conv_sites_total") or {}
    out = {}
    for label_s, n in fam.items():
        labels = dict(kv.split("=", 1) for kv in label_s.split(","))
        out[int(labels["p"]), labels["lays_out"]] = n
    return out


def replay_conv_sites(counted) -> None:
    """Count again the sites ``[[p, lays_out, n], ...]`` that the trace of a
    stored program counted: a program that is loaded traces nothing, and
    ``conv_sites()`` reads as after a trace."""
    for p, lays_out, n in counted:
        record_conv_site(int(p), str(lays_out), n)


# --------------------------------------------------------- folded silos
# core/client_fold.py, docs/PERFORMANCE.md §Folded silos. Fed at TRACE time
# by the fold's step, once for each trace of a block program that folds its
# cohort (the vmapped default is left as it was and counts nothing); a
# program the store loads counts again what its trace counted:
#
#     fed_client_fold_total{mode}       mode=scan
@lru_cache(maxsize=4)
def _client_fold(mode: str):
    return REGISTRY.counter("fed_client_fold_total", mode=mode)


def record_client_fold(mode: str, n: int = 1) -> None:
    _client_fold(mode).inc(n)


def client_fold_counts() -> dict:
    """{mode: n}: block programs traced (or loaded) so far, by fold."""
    fam = REGISTRY.snapshot().get("fed_client_fold_total") or {}
    return {label_s.split("=", 1)[1]: n for label_s, n in fam.items()}


# ------------------------------------------- what a block keeps of its pass
# models/lfm2_moe.py ``kept_names``, docs/PERFORMANCE.md §Folded silos. Fed
# at TRACE time by the model, once for each call of it that is traced (its
# ``init`` too); a program the store loads counts again what its trace
# counted:
#
#     fed_remat_sites_total{name,kept}  named outputs of the rematerialised
#                                       blocks' matrix products, one count a
#                                       block that has the name: kept=yes is
#                                       kept for the backward pass, kept=no
#                                       is made again there
#     fed_remat_kept_bytes_total        bytes one step keeps so, summed over
#                                       the traced calls
@lru_cache(maxsize=32)
def _remat_sites(name: str, kept: str):
    return REGISTRY.counter("fed_remat_sites_total", name=name, kept=kept)


def record_remat(plan, kept_bytes: float) -> None:
    """``plan``: for each block ``{name: kept}``."""
    for block in plan:
        for name, kept in block.items():
            _remat_sites(name, "yes" if kept else "no").inc()
    _counter("fed_remat_kept_bytes_total").inc(kept_bytes)


def remat_counts() -> dict:
    """{"sites": {(name, kept): n}, "kept_bytes": n} so far."""
    fam = REGISTRY.snapshot().get("fed_remat_sites_total") or {}
    sites = {}
    for label_s, n in fam.items():
        labels = dict(kv.split("=", 1) for kv in label_s.split(","))
        sites[labels["name"], labels["kept"]] = n
    return {"sites": sites,
            "kept_bytes": REGISTRY.total("fed_remat_kept_bytes_total")}


# --------------------------------------- the round programs' matrix work
# obs/work.py, docs/OBSERVABILITY.md. Counted where core/program_store.py
# exports a round program (a store miss: the walk of the trace it exports),
# per round of that program; a program the store loads counts again what its
# export counted. The plain jit (no compile cache directory, a mesh) counts
# nothing. Every dot_general and conv_general_dilated, by the innermost fed_*
# scope and the pass it belongs to (run=forward|recompute|gradient):
#
#     fed_matmul_flops_total{scope,run}  FLOPs a round of the shapes as issued
#     fed_matmul_bytes_total{scope,run}  their operands' and results' bytes
#
# summed over the round programs traced or loaded. matmul_ops() is the read
# side: the list a roofline needs, each shape at the larger of its bounds.
_matmul_lock = threading.Lock()
# {(scope, run, flops, bytes): ops a round}
_matmul: dict = {}


def record_matmul(ops: dict) -> None:
    """Count ``ops``, ``work.matmul_work``'s {(scope, run, flops, bytes):
    ops a round} of one round program."""
    with _matmul_lock:
        for key, n in ops.items():
            _matmul[key] = _matmul.get(key, 0.0) + n
    for (scope, run, flops, nbytes), n in ops.items():
        REGISTRY.counter("fed_matmul_flops_total", scope=scope,
                         run=run).inc(flops * n)
        REGISTRY.counter("fed_matmul_bytes_total", scope=scope,
                         run=run).inc(nbytes * n)


def matmul_op_counts() -> dict:
    """{(scope, run, flops, bytes): ops a round} counted so far."""
    with _matmul_lock:
        return dict(_matmul)


def matmul_ops() -> list:
    """[(scope, run, flops, bytes)] a round of the round programs traced or
    loaded so far, the products of one shape merged into one entry: a
    reader takes each entry at the larger of its two bounds, which merging
    equal shapes leaves as it was."""
    counted = sorted(matmul_op_counts().items())
    return [(scope, run, flops * n, nbytes * n)
            for (scope, run, flops, nbytes), n in counted]


def ensure_matmul_families() -> None:
    """Pre-register every ``run`` at zero (under ``scope="outside"``): a
    process that counted no product reads as that."""
    from fedml_tpu.obs import work

    for run in work.RUNS:
        REGISTRY.counter("fed_matmul_flops_total", scope=work.OUTSIDE, run=run)
        REGISTRY.counter("fed_matmul_bytes_total", scope=work.OUTSIDE, run=run)


def traced_counts() -> dict:
    """What traces have counted so far, for the program store to diff
    around a trace and keep with the program."""
    return {"conv_sites": conv_site_counts(),
            "client_fold": client_fold_counts(),
            "remat": remat_counts(),
            "matmul": matmul_op_counts()}


def traced_since(before: dict) -> dict:
    """The part of ``traced_counts()`` counted since ``before``, as lists a
    record's JSON header holds."""
    now = traced_counts()
    sites = [[p, lays_out, n - before["conv_sites"].get((p, lays_out), 0)]
             for (p, lays_out), n in sorted(now["conv_sites"].items())
             if n > before["conv_sites"].get((p, lays_out), 0)]
    folds = [[mode, n - before["client_fold"].get(mode, 0)]
             for mode, n in sorted(now["client_fold"].items())
             if n > before["client_fold"].get(mode, 0)]
    was = before["remat"]
    remat = {"sites": [[name, kept, n - was["sites"].get((name, kept), 0)]
                       for (name, kept), n in sorted(now["remat"]["sites"]
                                                     .items())
                       if n > was["sites"].get((name, kept), 0)],
             "kept_bytes": now["remat"]["kept_bytes"] - was["kept_bytes"]}
    matmul = [[*key, n - before["matmul"].get(key, 0.0)]
              for key, n in sorted(now["matmul"].items())
              if n > before["matmul"].get(key, 0.0)]
    return {"conv_sites": sites, "client_fold": folds, "remat": remat,
            "matmul": matmul}


def replay_traced(header: dict) -> None:
    """Count again what the trace of a stored program counted."""
    replay_conv_sites(header.get("conv_sites", ()))
    for mode, n in header.get("client_fold", ()):
        record_client_fold(str(mode), n)
    remat = header.get("remat", {})
    for name, kept, n in remat.get("sites", ()):
        _remat_sites(str(name), str(kept)).inc(n)
    _counter("fed_remat_kept_bytes_total").inc(remat.get("kept_bytes", 0))
    record_matmul({(str(scope), str(run), flops, nbytes): n
                   for scope, run, flops, nbytes, n in header.get("matmul",
                                                                  ())})


# ------------------------------------------------------- the expert layer
# models/lfm2_moe.py, docs/OBSERVABILITY.md. The model counts on the device
# and the counts ride out of the block program in its metrics
# (``moe_stats_*``); the block's caller hands them over as device arrays
# (``Task.handoff``, ``note_moe_stats``), one jitted add folds them into a
# running sum that stays on the device, and the sum is read when somebody
# asks the registry for a snapshot, an export or a ``fed_moe`` total:
# nothing waits on the device where a block is dispatched.
#
#     fed_moe_rows_total{kind}          rows of the grouped products:
#                                       kind=real held an assignment,
#                                       kind=dispatched were computed (the
#                                       row budget, or held * tokens where
#                                       a step took the full-size path)
#     fed_moe_fallback_steps_total      layer-steps whose tiles did not fit
#                                       the budget and took the exact path
#                                       of full size
#     fed_moe_expert_tokens_total{layer,expert}
#                                       assignments of each held expert,
#                                       summed over silos: layer counts the
#                                       expert layers from 0, expert the
#                                       held experts from experts_held[0].
#                                       Fed where the round keeps the
#                                       counts' [layer, expert] shape (the
#                                       fold); the vmapped and mesh rounds
#                                       sum a metric over all its axes
_moe_lock = threading.Lock()
# {name: (high, low)}: the counts handed over and not yet read, as two
# uint32 limbs on the device (no 64-bit integers there, and a float32 sum
# stops counting in ones at 2**24)
_moe_sum: dict | None = None


@lru_cache(maxsize=1)
def _moe_adder():
    import jax
    import jax.numpy as jnp

    def add(total, stats):
        out = {}
        for name, (high, low) in total.items():
            block = jnp.sum(stats[name].astype(jnp.uint32), axis=0)
            new = low + block  # wraps
            out[name] = (high + (new < low).astype(jnp.uint32), new)
        return out

    return jax.jit(add)


def _moe_shapes(total: dict) -> dict:
    return {name: low.shape for name, (_, low) in total.items()}


def note_moe_stats(stats: dict) -> None:
    """Fold one block's counts (device arrays, a leading axis of rounds)
    into the running sum, on the device."""
    import numpy as np

    global _moe_sum
    shapes = {name: v.shape[1:] for name, v in stats.items()}
    with _moe_lock:
        if _moe_sum is not None and _moe_shapes(_moe_sum) != shapes:
            _read_moe_sum()  # another model's counts: read, start anew
        if _moe_sum is None:
            _moe_sum = {name: (np.zeros(shape, np.uint32),) * 2
                        for name, shape in shapes.items()}
        _moe_sum = _moe_adder()(_moe_sum, stats)


def _read_moe_sum() -> None:
    """The running sum into the counters (under ``_moe_lock``)."""
    import numpy as np

    global _moe_sum
    total, _moe_sum = _moe_sum, None
    stats = {name: np.asarray(high, np.float64) * 2.0 ** 32 + np.asarray(low)
             for name, (high, low) in (total or {}).items()}
    for kind in ("real", "dispatched"):
        if f"rows_{kind}" in stats:
            REGISTRY.counter("fed_moe_rows_total", kind=kind).inc(
                float(stats[f"rows_{kind}"].sum()))
    if "fallback_steps" in stats:
        REGISTRY.counter("fed_moe_fallback_steps_total").inc(
            float(stats["fallback_steps"].sum()))
    if stats.get("expert_tokens", np.zeros(())).ndim == 2:
        for (layer, expert), n in np.ndenumerate(stats["expert_tokens"]):
            REGISTRY.counter("fed_moe_expert_tokens_total", layer=layer,
                             expert=expert).inc(float(n))


def _drain_moe() -> None:
    with _moe_lock:
        _read_moe_sum()


REGISTRY.add_collector("fed_moe", _drain_moe)


def moe_rows() -> dict:
    """{"real": n, "dispatched": n} of ``fed_moe_rows_total``."""
    fam = REGISTRY.snapshot().get("fed_moe_rows_total") or {}
    return {kind: fam.get(f"kind={kind}", 0.0)
            for kind in ("real", "dispatched")}


# ------------------------------------------------------ the program store
# core/program_store.py, docs/PERFORMANCE.md §Stored round programs:
#
#     fed_program_store_total{outcome}        first calls of a stored jit,
#                                             once for each abstract
#                                             signature: hit (the program
#                                             was loaded), miss (traced
#                                             once and stored), stale (a
#                                             record was there and did not
#                                             load: traced and written
#                                             anew), unkeyable (the trace
#                                             reads a value no rule keys,
#                                             or code the key does not
#                                             hold: traced as ever), error
#                                             (jax refuses to export the
#                                             program, or cannot call a
#                                             stored one: traced as ever)
#     fed_program_store_seconds_total{phase}  key (forming the key), load
#                                             (reading a record and calling
#                                             it), export (trace, lower,
#                                             serialize and write on a miss),
#                                             walk (the walk of the exported
#                                             trace's matrix work, inside
#                                             export)
PROGRAM_STORE_OUTCOMES = ("hit", "miss", "unkeyable", "stale", "error")
PROGRAM_STORE_PHASES = ("key", "load", "export", "walk")


@lru_cache(maxsize=8)
def _program_store(outcome: str):
    return REGISTRY.counter("fed_program_store_total", outcome=outcome)


@lru_cache(maxsize=4)
def _program_store_seconds(phase: str):
    return REGISTRY.counter("fed_program_store_seconds_total", phase=phase)


def record_program_store(outcome: str) -> None:
    _program_store(outcome).inc()


def record_program_store_seconds(phase: str, seconds: float) -> None:
    _program_store_seconds(phase).inc(seconds)


def program_store_counts() -> dict:
    """{outcome: n} from the live registry."""
    return {o: _program_store(o).value for o in PROGRAM_STORE_OUTCOMES}


def ensure_program_store_families() -> None:
    """Pre-register every outcome and phase at zero: a run in which the
    store never engaged reads as that, not as a metric that is missing."""
    for outcome in PROGRAM_STORE_OUTCOMES:
        _program_store(outcome)
    for phase in PROGRAM_STORE_PHASES:
        _program_store_seconds(phase)


# ------------------------------------------------------ compile accounting
def _on_event(name: str, **kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _counter("fed_xla_cache_hits_total").inc()
        _variant_counter("fed_xla_variant_cache_hits_total",
                         _compile_variant()).inc()
    elif name == "/jax/compilation_cache/cache_misses":
        _counter("fed_xla_cache_misses_total").inc()
        _variant_counter("fed_xla_variant_cache_misses_total",
                         _compile_variant()).inc()
    elif name == "/jax/compilation_cache/compile_requests_use_cache":
        _counter("fed_xla_cache_requests_total").inc()


def _on_duration(name: str, secs: float, fun_name: str = "", **kw) -> None:
    if name.endswith("/backend_compile_duration"):
        _counter("fed_xla_compiles_total").inc()
        _hist("fed_xla_compile_seconds").observe(secs)
        variant = _compile_variant(fun_name)
        _variant_counter("fed_xla_variant_compiles_total", variant).inc()
        _variant_counter("fed_xla_variant_compile_seconds_total",
                         variant).inc(secs)
    elif name in _DURATION_FAMILIES:
        _variant_counter(_DURATION_FAMILIES[name],
                         _compile_variant(fun_name)).inc(secs)


def install() -> bool:
    """Register the jax.monitoring listeners feeding the compile counters.
    Idempotent (listeners cannot be individually unregistered, so exactly
    one pair is ever installed); returns False when jax.monitoring is
    unavailable (counters then stay at 0 — callers must treat a 0 as
    "uninstrumented", not "no compiles")."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring
        except Exception:  # noqa: BLE001 — instrumentation is best-effort
            log.debug("jax.monitoring unavailable; compile counters stay "
                      "at 0 (= uninstrumented)", exc_info=True)
            return False
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True


def compiles_total() -> float:
    """XLA backend compile passes so far (callers diff around a phase;
    includes cache-hit deserializes — see module docstring)."""
    return REGISTRY.total("fed_xla_compiles_total")


def cache_hits_total() -> float:
    return REGISTRY.total("fed_xla_cache_hits_total")


def cache_misses_total() -> float:
    return REGISTRY.total("fed_xla_cache_misses_total")


def cache_requests_total() -> float:
    return REGISTRY.total("fed_xla_cache_requests_total")


# ------------------------------------------------------- pipeline metrics
def record_h2d(seconds: float) -> None:
    _hist("fed_h2d_seconds").observe(seconds)
    _span_hist("h2d").observe(seconds)


def record_prefetch_stall(seconds: float) -> None:
    _hist("fed_prefetch_stall_seconds").observe(seconds)


def set_dispatch_depth(n: int) -> None:
    REGISTRY.gauge("fed_dispatch_depth").set(n)


def record_span(name: str, seconds: float) -> None:
    """A host span observed off the engine's RoundTracer (the prefetch
    thread must not touch the tracer's per-round dict — see
    docs/PERFORMANCE.md §Caveats, Tracing)."""
    _span_hist(name).observe(seconds)


# ---------------------------------------------- fused-aggregation metrics
# docs/PERFORMANCE.md §Fused aggregation. Fed by the cross-process
# aggregator's flush paths:
#
#     fed_flush_seconds                 (histogram) one server aggregate
#                                       flush — ingest-side decode work is
#                                       per-arrival (overlapped), this is
#                                       the barrier-to-new-model latency
#     fed_agg_stack_bytes{mode}         (gauge) peak aggregation-staging
#                                       bytes of the last flush: stacked =
#                                       the full [K, ...] cohort stack,
#                                       fused = live pairwise partials
#                                       (O(log K) on the in-order path)
def record_flush_seconds(seconds: float) -> None:
    _hist("fed_flush_seconds").observe(seconds)


@lru_cache(maxsize=8)
def _agg_stack(mode: str):
    return REGISTRY.gauge("fed_agg_stack_bytes", mode=mode)


def set_agg_stack_bytes(mode: str, nbytes: float) -> None:
    """Peak aggregation-staging bytes of the last flush under ``mode``
    (fused | stacked) — the memory half of the fused-vs-stacked claim."""
    _agg_stack(mode).set(nbytes)


# --------------------------------------------- sharded-server-state metrics
# docs/PERFORMANCE.md §Partitioned server state. ``mode``/``placement`` is
# "replicated" or "sharded" so an A/B run exports both label sets side by
# side and the ~1/ndev per-device scaling is a metrics assertion, not a
# code comment.
@lru_cache(maxsize=8)
def _agg_bytes(mode: str):
    return REGISTRY.counter("fed_agg_bytes_total", mode=mode)


def record_agg_bytes(mode: str, nbytes: float) -> None:
    """Client-update bytes folded through aggregation this round (stacked
    cohort payload: K x model bytes) under the given server-state mode."""
    _agg_bytes(mode).inc(nbytes)


def set_server_state_bytes(placement: str, per_device_bytes: float) -> None:
    """PER-DEVICE resident bytes of the server plane (global model +
    server optimizer state). Sharded runs report ~1/ndev of the
    replicated figure — the acceptance metric for the partitioned
    server state (ISSUE 6)."""
    REGISTRY.gauge("fed_server_state_bytes",
                   placement=placement).set(per_device_bytes)


# ------------------------------------------------ buffered-async metrics
# docs/ROBUSTNESS.md §Asynchronous buffered rounds. Fed by the async server
# mode (distributed/fedavg/server_manager.py) and the virtual-clock
# simulator (core/async_buffer.py) identically:
#
#     fed_buffer_fill_seconds        (histogram) first arrival -> flush of
#                                    each buffered aggregate (virtual
#                                    seconds in the simulator)
#     fed_update_staleness           (histogram; prometheus quantile
#                                    labels) server version at aggregation
#                                    minus the version each folded update
#                                    trained against
#     fed_async_shed_total{reason}   arrivals the ingest path refused or
#                                    evicted: stale (admission bound),
#                                    overflow (backpressure shed-stalest),
#                                    nonfinite (quarantined at the door),
#                                    crash (simulator: dead-rank dispatch)
def record_buffer_fill(seconds: float) -> None:
    _hist("fed_buffer_fill_seconds").observe(seconds)


def record_update_staleness(staleness: float) -> None:
    _hist("fed_update_staleness").observe(float(staleness))


@lru_cache(maxsize=16)
def _async_shed(reason: str):
    return REGISTRY.counter("fed_async_shed_total", reason=reason)


def record_async_shed(reason: str) -> None:
    _async_shed(reason).inc()


def ensure_async_shed_families() -> None:
    """Pre-register every shed-reason child at zero so an async run's
    Prometheus export always carries the full family — a clean run must
    read as 'nothing shed', not 'metric missing'."""
    # mirrors core/async_buffer.SHED_REASONS (obs must not import core —
    # the dependency points the other way; drift is test-pinned)
    for reason in ("stale", "overflow", "nonfinite", "crash", "suspect",
                   "undecodable", "server_restart", "offline"):
        _async_shed(reason)


# --------------------------------------- secure aggregation + privacy
# docs/ROBUSTNESS.md §Secure aggregation / §Privacy ledger. Fed by the
# masked secure-aggregation tier (distributed/turboaggregate.py) and the
# DP aggregators (distributed/fedavg_robust.py, algorithms/
# fedavg_robust.py):
#
#     fed_secagg_rounds_total{outcome}    masked rounds by how they
#                                         decoded: full (whole cohort),
#                                         recovered (dropout + mask
#                                         recovery), shed (below the t+1
#                                         threshold / reveal lost —
#                                         round re-broadcast)
#     fed_secagg_dropped_slots_total      cohort slots whose masked
#                                         upload never arrived
#     fed_secagg_recovery_seconds         (histogram) reveal fan-out ->
#                                         last reveal reply per recovery
#     fed_privacy_epsilon                 cumulative DP ε at the ledger's
#                                         reporting δ — the budget the
#                                         privacy_budget health rule
#                                         alerts on
@lru_cache(maxsize=4)
def _secagg_rounds(outcome: str):
    return REGISTRY.counter("fed_secagg_rounds_total", outcome=outcome)


def record_secagg_round(outcome: str) -> None:
    _secagg_rounds(outcome).inc()


@lru_cache(maxsize=1)
def _secagg_dropped():
    return REGISTRY.counter("fed_secagg_dropped_slots_total")


def record_secagg_dropped(n: int) -> None:
    _secagg_dropped().inc(n)


def record_secagg_recovery_seconds(seconds: float) -> None:
    _hist("fed_secagg_recovery_seconds").observe(seconds)


def set_privacy_epsilon(eps: float) -> None:
    REGISTRY.gauge("fed_privacy_epsilon").set(float(eps))


#     fed_privacy_client_epsilon{stat}    per-client ε rollup at the
#                                         ledger's reporting δ: stat=max
#                                         (worst single client — the
#                                         never-under-report figure),
#                                         stat=mean, stat=count (clients
#                                         with any charge). Fed by
#                                         core/privacy.charge_and_record
#                                         when a ClientPrivacyLedger rides
#                                         the round.
@lru_cache(maxsize=4)
def _client_eps(stat: str):
    return REGISTRY.gauge("fed_privacy_client_epsilon", stat=stat)


def set_client_epsilon(eps_max: float, eps_mean: float, count: int) -> None:
    _client_eps("max").set(float(eps_max))
    _client_eps("mean").set(float(eps_mean))
    _client_eps("count").set(float(count))


def ensure_secagg_families() -> None:
    """Pre-register the secure-aggregation outcome children at zero so a
    masked run's Prometheus export always carries the full family."""
    for outcome in ("full", "recovered", "shed"):
        _secagg_rounds(outcome)
    _secagg_dropped()


def ensure_client_privacy_family() -> None:
    """Pre-register the per-client ε gauge children at zero so a DP
    masked run's export always carries the family (even before the first
    charge lands)."""
    for stat in ("max", "mean", "count"):
        _client_eps(stat)


# ---------------------------------------------------- server crash recovery
# docs/ROBUSTNESS.md §Server crash recovery:
#     fed_server_restarts_total          completed server restarts (the
#                                        restart epoch, synced at boot so
#                                        a restarted PROCESS's fresh
#                                        registry still reports the count)
#     fed_restart_epoch                  (gauge) the live restart epoch —
#                                        also on /healthz
#     fed_recovery_seconds               (histogram) checkpoint restore +
#                                        WAL replay wall time per boot
#     fed_ckpt_torn_total                torn checkpoint files skipped by
#                                        restore_latest's fallback
def sync_server_restarts(epoch: int) -> None:
    """Bring ``fed_server_restarts_total`` up to the WAL's restart epoch:
    a restarted process boots with a fresh registry, so the counter is
    advanced by the DELTA between the journaled epoch and whatever this
    process already counted (simulated in-process restarts inc once per
    boot; a twice-restarted real process lands at 2 in one step)."""
    delta = float(epoch) - REGISTRY.total("fed_server_restarts_total")
    if delta > 0:
        _counter("fed_server_restarts_total").inc(delta)
    REGISTRY.gauge("fed_restart_epoch").set(float(epoch))


def record_recovery_seconds(seconds: float) -> None:
    _hist("fed_recovery_seconds").observe(seconds)


def record_ckpt_torn() -> None:
    _counter("fed_ckpt_torn_total").inc()


def ensure_restart_families() -> None:
    """Pre-register the crash-recovery families at zero so any WAL-armed
    run's Prometheus export carries them (the restart-storm health rule
    and the ci.sh supervised-restart leg read the family, not its
    absence)."""
    _counter("fed_server_restarts_total")
    REGISTRY.gauge("fed_restart_epoch")
    _counter("fed_ckpt_torn_total")
