"""FedAvg — the centerpiece algorithm, TPU-first.

Reference behavior being matched (fedml_api/distributed/fedavg/ and
fedml_api/standalone/fedavg/fedavg_api.py:40-115):
  per round: sample clients (FedAVGAggregator.client_sampling:89-97)
  -> each client: local SGD from the global weights (MyModelTrainer.py:19-49)
  -> server: sample-weighted average of all returned weights
     (FedAVGAggregator.aggregate:58-87)
  -> periodic eval on train/test (fedavg_api.py:117-180).

TPU re-design: one round = ONE jitted program.
  - standalone mode (1 device): clients are a vmapped leading axis — the
    reference's sequential client loop (fedavg_api.py:56-66) becomes a batched
    axis so every client's local SGD runs concurrently on the MXU.
  - distributed mode (mesh): the vmapped block is shard_mapped over the
    'clients' mesh axis; aggregation is a weighted psum over ICI
    (replacing the MPI upload/download round, SURVEY.md §2.8).
The host loop only samples ids, packs data, and logs — no message machinery.

Server update is a hook (identity for FedAvg) so FedOpt/FedNova/robust
variants reuse this engine (see fedopt.py etc.).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.core import program_store
from fedml_tpu.core.client_data import (
    ClientBatch,
    FederatedData,
    IndexBatch,
    batch_global,
    pack_client_indices,
    pack_clients,
    pad_batches,
    pad_index_batches,
)
from fedml_tpu.core.client_source import (
    ClientDataSource,
    pack_clients_source,
)
from fedml_tpu.core.local import (LocalSpec, Task, handing_off, make_eval_fn,
                                  make_local_update)
from fedml_tpu.core.partition_rules import tree_bytes as _tree_bytes
from fedml_tpu.core.pipeline import (
    InflightRing,
    Prefetcher,
    compile_concurrently,
)
from fedml_tpu.core.robust_agg import (
    DEFAULT_NORM_MULT,
    QuarantineLedger,
    gated_aggregate,
    make_robust_aggregator,
)
from fedml_tpu.core.sampling import prepare_sampling, sample_for
from fedml_tpu.obs import goodput as _goodput
from fedml_tpu.obs import perf_instrument as _perf
from fedml_tpu.obs.tracing import RoundTracer
from fedml_tpu.utils.tree import tree_weighted_mean

log = logging.getLogger("fedml_tpu.fedavg")


# The round program's three scopes (fed_gather, fed_aggregate,
# fed_server_update) are each put once, on the shared function every driver
# calls. Single tokens without '/', so a trace reduction finds them through
# vmap(..), jvp(..) and transpose(..) wrappers in an op's name; metadata
# only: the lowered program computes the same bits. The local fit has no
# scope of its own (a frame or a ``with`` under its trace slowed set-up on
# the v5e host, PERF.md section 6): it is the device time outside the three.
@jax.named_scope("fed_gather")
def _gather_rows(dev_x, dev_y, idx, mask):
    """Row gather for the device-resident data plane (single-device and
    per-shard SPMD paths share this). Padded slots (mask==0) carry idx 0, so
    gathered garbage rows are zeroed to match the host packer's zero padding
    bit-for-bit — models with mutable batch_stats (BatchNorm ignores the
    loss mask) see identical statistics on both planes."""
    shp = idx.shape
    flat = idx.reshape(-1)
    x = jnp.take(dev_x, flat, axis=0).reshape(shp + dev_x.shape[1:])
    y = jnp.take(dev_y, flat, axis=0).reshape(shp + dev_y.shape[1:])
    mx = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim)) > 0
    my = mask.reshape(mask.shape + (1,) * (y.ndim - mask.ndim)) > 0
    return jnp.where(mx, x, jnp.zeros_like(x)), jnp.where(my, y, jnp.zeros_like(y))


def _sq_norm(tree):
    """Global squared L2 norm of a pytree (a scalar, inside jit)."""
    leaves = jax.tree.leaves(tree)
    return sum((jnp.vdot(v, v) for v in leaves), jnp.zeros(()))


def _update_norm(new_params, old_params):
    """||new - old|| over params — the single definition every telemetry
    path (standalone stats, mesh round fn, mesh block step) emits under
    the ``update_norm`` record key."""
    return jnp.sqrt(_sq_norm(jax.tree.map(jnp.subtract, new_params,
                                          old_params)))


def round_stats(old_net, new_net, nets, avg, nsamp) -> dict:
    """Telemetry round stats, computed IN-GRAPH so enabling them adds no
    device sync — they ride out with the metrics dict the round program
    already returns. (With telemetry off the round program is bit-identical
    to the pre-telemetry build: none of this is traced.)

    - ``update_norm``: ||new - old|| over params — the aggregate step size
      the server actually applied (post server_update / post hooks);
    - ``client_drift_mean``/``client_drift_max``: per-client ||net_k - avg||
      over the round's REAL clients (zero-sample padding excluded) — the
      non-IID dispersion statistic FedProx/FedNova papers reason about.
    """
    out = {"update_norm": _update_norm(new_net.params, old_net.params)}
    drift, real = _client_drift(nets.params, avg.params, nsamp)
    n_real = jnp.maximum(jnp.sum(real), 1.0)
    out["client_drift_mean"] = jnp.sum(drift * real) / n_real
    out["client_drift_max"] = jnp.max(drift * real)
    return out


def _client_drift(net_params, avg_params, nsamp):
    """[K] per-client ||net_k - avg|| over params plus the real-client mask
    (zero-sample padding excluded) — the ONE definition of client drift.
    ``round_stats`` reduces it locally; ``_mesh_drift_stats`` via
    psum/pmax, so the two stay in sync by construction."""
    drift_sq = sum(
        (jnp.sum((s - a) ** 2, axis=tuple(range(1, s.ndim)))
         for s, a in zip(jax.tree.leaves(net_params),
                         jax.tree.leaves(avg_params))),
        jnp.zeros(nsamp.shape),
    )
    drift = jnp.sqrt(drift_sq)
    real = (nsamp > 0).astype(drift.dtype)
    return drift, real


def agg_weights(nsamp, uniform: bool):
    """Aggregation weights: sample counts (FedAvg default) or, with
    ``uniform``, 1 per participating client / 0 for zero-sample padding —
    the pairing DP and size-weighted sampling require. Shared by the
    FedAvg and long-context engines."""
    if not uniform:
        return nsamp
    return jnp.where(nsamp > 0, jnp.ones_like(nsamp), jnp.zeros_like(nsamp))


def _mesh_drift_stats(net_params, avg_params, nsamp, axis) -> dict:
    """The client-drift half of ``round_stats`` under shard_map: each
    device computes its client shard's ||net_k - avg|| distances and the
    mean/max are psum/pmax-reduced over the mesh — so the mesh paths emit
    the SAME record keys as the standalone engine instead of only a
    partial stat set (``update_norm`` joins outside, where the updated
    params exist). Zero-sample padding is excluded exactly as in
    ``round_stats`` (shared ``_client_drift``)."""
    drift, real = _client_drift(net_params, avg_params, nsamp)
    n_real = jnp.maximum(jax.lax.psum(jnp.sum(real), axis), 1.0)
    return {
        "client_drift_mean": jax.lax.psum(jnp.sum(drift * real), axis)
        / n_real,
        "client_drift_max": jax.lax.pmax(jnp.max(drift * real), axis),
    }


def _device_bytes_limit() -> int | None:
    """What the default device says it holds (None where it does not say:
    the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def _refuse_cohort_beyond_device(params, clients: int) -> None:
    """A vmapped cohort keeps ``clients`` copies of the weights and of
    their gradients side by side. Where those alone pass the device's
    memory the round could only end in the compiler's out-of-memory error:
    say at construction what serves such a model."""
    limit = _device_bytes_limit()
    need = 2 * clients * _tree_bytes(params)
    if limit is not None and need > limit:
        raise ValueError(
            f"client_fold='vmap' fits {clients} clients side by side: their "
            f"weights and gradients alone take {need / 2**30:.1f} GiB of "
            f"the device's {limit / 2**30:.1f}. Fold them one after another "
            "(FedAvgConfig.client_fold='scan', docs/PERFORMANCE.md §Folded "
            "silos) or sample fewer clients a round")


@jax.named_scope("fed_aggregate")
def _shard_aggregate(nets, metrics, nsamp, axis):
    """Per-shard weighted aggregation under shard_map: weighted psum of the
    client nets (numerator+denominator over the mesh axis) and psum-med
    metric sums. Single source of truth for the sequential round fn AND the
    R-round block (their numerical identity is test-enforced)."""
    wsum = jax.tree.map(
        lambda t: jax.lax.psum(jnp.tensordot(nsamp, t, axes=([0], [0])), axis),
        nets,
    )
    den = jax.lax.psum(jnp.sum(nsamp), axis)
    avg = jax.tree.map(lambda t: t / jnp.maximum(den, 1e-12), wsum)
    msum = {k: jax.lax.psum(jnp.sum(v), axis) for k, v in metrics.items()}
    return avg, msum


def eval_subset(tx, ty, cfg: "FedAvgConfig", call_idx: int):
    """Apply the eval_max_samples subset policy (see FedAvgConfig).
    ``call_idx`` only matters in 'fresh' mode, where each eval resamples
    (reference FedAVGAggregator.py:99-107)."""
    if cfg.eval_max_samples is None or len(tx) <= cfg.eval_max_samples:
        return tx, ty
    if cfg.eval_subset_mode == "fresh":
        rs = np.random.RandomState((cfg.seed * 1_000_003 + call_idx) & 0x7FFFFFFF)
    elif cfg.eval_subset_mode == "fixed":
        rs = np.random.RandomState(cfg.seed)
    else:
        raise ValueError(f"eval_subset_mode={cfg.eval_subset_mode!r} "
                         "(expected 'fixed' or 'fresh')")
    sel = rs.choice(len(tx), cfg.eval_max_samples, replace=False)
    return tx[sel], ty[sel]


def _make_client_keys(seed: int):
    """Per-client training keys, derived inside jit: the same
    fold_in(fold_in(PRNGKey(seed), round), client_id) chain as the
    cross-process DistributedTrainer (distributed/fedavg/trainer.py)."""

    def client_keys(round_idx, ids):
        base = jax.random.fold_in(jax.random.PRNGKey(seed), round_idx)
        return jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)

    return client_keys


@dataclasses.dataclass(frozen=True)
class FedAvgConfig:
    """Flag surface parity with the reference argparse
    (fedml_experiments/distributed/fedavg/main_fedavg.py:48-119)."""

    comm_round: int = 10
    client_num_in_total: int = 10
    client_num_per_round: int = 10
    epochs: int = 1
    batch_size: int = 32
    client_optimizer: str = "sgd"  # 'sgd' | 'adam'
    lr: float = 0.03
    wd: float = 0.0
    momentum: float = 0.0
    frequency_of_the_test: int = 5
    seed: int = 0
    max_batches: int | None = None  # static per-client batch budget (B)
    ci: bool = False  # truncate eval, reference --ci semantics
    eval_batch_size: int = 256
    # cap global eval to a random subset of the test set — the reference's
    # stackoverflow validation subset of 10k samples
    # (FedAVGAggregator._generate_validation_set, FedAVGAggregator.py:99-107);
    # None = full test set
    eval_max_samples: int | None = None
    # rematerialize per-batch forwards under autodiff (jax.checkpoint) in
    # the default LocalSpec — HBM for FLOPs on deep models/long sequences
    remat: bool = False
    # 'fixed': ONE seeded subset reused every eval (comparable curves across
    # rounds); 'fresh': a new subset each eval — the reference's exact
    # semantics (random.sample per call, FedAVGAggregator.py:99-107),
    # deterministic here via (seed, eval-call-index)
    eval_subset_mode: str = "fixed"
    # 'uniform' (reference parity): uniform without replacement +
    # sample-weighted aggregate. 'size_weighted': P(k) ∝ n_k + UNIFORM
    # aggregate (the FedAvg paper's alternative scheme — both are
    # unbiased; size-weighting concentrates rounds on data-rich clients)
    sampling: str = "uniform"
    # client-compute precision policy (docs/PERFORMANCE.md §Mixed
    # precision): 'bf16' runs the vmapped local fits on bfloat16 casts of
    # the f32 master weights (grad-scale-free; aggregation and the server
    # update stay f32); 'f32' (default) traces no casts — bit-identical
    # to the pre-policy engine (test-enforced). Applied through the
    # default LocalSpec in BOTH runtimes (FedAvgAPI and the cross-process
    # DistributedTrainer), and grafted onto an explicitly-passed
    # LocalSpec that left compute_dtype at its default.
    precision: str = "f32"
    # per-client eval inside train() (reference _local_test_on_all_clients,
    # fedavg_api.py:117-180: every eval round the CURRENT global model is
    # scored on EVERY client's own train and test split, aggregated by
    # sample count). 'auto': on exactly when the dataset has per-client
    # test splits (natural partitions — where the weighting differs from a
    # shared global test set); 'on'/'off' force it.
    local_test_on_all_clients: str = "auto"
    # scheduled client availability (chaos/churn.py ChurnTrace, or None):
    # every engine's per-round cohort draw restricts to the trace's
    # available clients for the round's window (core/sampling.sample_
    # available). Orthogonal to chaos faults — scheduled-offline is the
    # fleet's NORMAL state, not a failure. Recorded in the run header via
    # asdict like every other flag, so a run replays from its header.
    churn_trace: object | None = None
    # how the single-device scanned block runs a round's cohort
    # (docs/PERFORMANCE.md §Folded silos): 'vmap' (default) fits the
    # clients side by side, K copies of weights and gradients alive at
    # once; 'scan' folds them one after another into a running weighted
    # sum (core/client_fold.py), one copy alive at a time, for a model
    # whose weights do not fit beside their own cohort. 'scan' serves
    # run_rounds with device_data=True and refuses the rest at construction.
    client_fold: str = "vmap"


def resolve_local_spec(local_spec: LocalSpec | None,
                       cfg: FedAvgConfig) -> LocalSpec:
    """The engine's LocalSpec: the default build honors ``cfg.precision``;
    an explicitly-passed spec (fedprox's prox_spec, engine subclasses)
    that left ``compute_dtype`` at its default is grafted with it, so
    ``precision='bf16'`` composes with every engine instead of silently
    reverting to f32 — a spec that SET its own compute_dtype wins."""
    from fedml_tpu.core.local import COMPUTE_DTYPES

    prec = getattr(cfg, "precision", "f32")
    if prec not in COMPUTE_DTYPES:
        raise ValueError(f"precision={prec!r} (one of "
                         f"{sorted(COMPUTE_DTYPES)})")
    if local_spec is None:
        return LocalSpec(optimizer=make_client_optimizer(cfg),
                         epochs=cfg.epochs, remat=cfg.remat,
                         compute_dtype=prec)
    if COMPUTE_DTYPES[prec] is not None \
            and local_spec.compute_dtype in ("f32", "float32"):
        return dataclasses.replace(local_spec, compute_dtype=prec)
    return local_spec


def make_client_optimizer(cfg: FedAvgConfig) -> optax.GradientTransformation:
    """The reference builds torch SGD(momentum, wd) or Adam(wd, amsgrad)
    per client (MyModelTrainer.py:24-32)."""
    if cfg.client_optimizer == "sgd":
        tx = optax.sgd(cfg.lr, momentum=cfg.momentum or None)
    elif cfg.client_optimizer == "adam":
        tx = optax.adam(cfg.lr)
    else:
        raise ValueError(cfg.client_optimizer)
    if cfg.wd:
        tx = optax.chain(optax.add_decayed_weights(cfg.wd), tx)
    return tx


class FedAvgAPI:
    """Host-side round driver + jitted round program.

    ``mesh=None`` -> single-device (standalone simulation parity).
    ``mesh=Mesh(..., ('clients',))`` -> SPMD over devices (distributed parity).
    """

    def __init__(
        self,
        dataset: FederatedData,
        task: Task,
        config: FedAvgConfig,
        mesh: Mesh | None = None,
        server_update: Callable | None = None,
        server_opt_init: Callable | None = None,
        client_result_hook: Callable | None = None,
        post_aggregate_hook: Callable | None = None,
        local_spec: LocalSpec | None = None,
        device_data: bool = False,
        donate: bool = False,
        block_working_set: bool = False,
        uniform_avg: bool = False,
        bucket_batches: bool = False,
        telemetry=None,
        aggregator: str | Callable | None = None,
        aggregator_params: dict | None = None,
        sanitize: bool | float | None = None,
        adversary_plan=None,
        prefetch: int = 0,
        drain_lag: int = 2,
        shard_server_state: bool = False,
        partition_rules=None,
    ):
        # the engine's host spans (docs/OBSERVABILITY.md §Engine spans):
        # init/pack/place/round/fetch/eval, each also an event of the jax
        # profiler's trace; with a tracing-enabled Telemetry bundle the
        # same spans feed the distributed tracer's single-rank timeline
        # (all host-side: nothing here touches the jitted round program).
        # The compile listeners go in before the first compile, so set-up
        # is accounted where it happens (perf_instrument.setup_phases).
        self.tracer = RoundTracer(
            sink=telemetry.tracer if telemetry is not None else None)
        _perf.install()
        with self.tracer.span("init"):
            self.data = dataset
            self.task = task
            self.cfg = config
            self.mesh = mesh
            # Streamed client state (core/client_source.py, docs/PERFORMANCE.md
            # §Streaming & cohort bucketing): a ClientDataSource keeps per-client
            # payload OUT of host memory — packing reads only the sampled
            # cohort's rows, so host RSS stays flat in population size (the
            # memwatch fed_host_rss_bytes gauge is the live evidence). The
            # device-resident planes require the full train set in HBM, which is
            # exactly what a streamed population cannot afford — refuse loudly.
            self._source = dataset if isinstance(dataset, ClientDataSource) \
                else None
            if self._source is not None and (device_data or block_working_set):
                raise ValueError(
                    "device_data/block_working_set park the FULL train set on "
                    "device — incompatible with a streamed ClientDataSource "
                    "(pass the host-packed plane, or materialize the dataset)")
            if self._source is not None \
                    and config.local_test_on_all_clients == "on":
                # 'auto' already degrades to the global test set (sources carry
                # no per-client test splits); a FORCED per-client eval would
                # die mid-run in evaluate_per_client — refuse at construction
                raise ValueError(
                    "local_test_on_all_clients='on' iterates every client's "
                    "own split — not available on a streamed ClientDataSource "
                    "(use 'auto'/'off': the global test split is evaluated)")
            # Pipelined round execution (core/pipeline.py, docs/PERFORMANCE.md):
            # ``prefetch`` > 0 arms the double-buffered host->device prefetch —
            # a packer thread prepares round r+1's batch and issues its
            # device_put while round r executes, with up to ``prefetch`` batches
            # staged ahead (2 = classic double buffering). ``drain_lag`` is how
            # many rounds behind dispatch the metrics/quarantine drain trails,
            # so JAX async dispatch stays that deep. Bit-identical to the
            # synchronous driver (packing is a pure function of (seed, round);
            # test-enforced); prefetch=0 (default) changes nothing.
            if prefetch < 0:
                raise ValueError(f"prefetch must be >= 0, got {prefetch}")
            if drain_lag < 0:
                raise ValueError(f"drain_lag must be >= 0, got {drain_lag}")
            self.prefetch = int(prefetch)
            self.drain_lag = int(drain_lag)
            # test/instrumentation hook: a callable observing the pipeline's
            # ("produced"/"got"/"drained", key) events — the overlap oracle
            self._pipe_on_event = None
            # Byzantine-robust aggregation (core/robust_agg.py). ``aggregator``
            # replaces the weighted mean with a robust estimator over the
            # stacked client updates: 'mean' | 'median' | 'trimmed_mean' |
            # 'krum' | 'multi_krum' | 'geometric_median', or a callable
            # ``(stacked, weights) -> (tree, info)``. ``sanitize`` fronts it
            # with the non-finite/norm-outlier gate (True = default norm_mult,
            # a float = that multiple, False = off; None = on iff an
            # aggregator is set). The default (None/None) keeps the round
            # program bit-identical to the plain weighted-mean build.
            if aggregator is None:
                self._robust_agg = None
            elif callable(aggregator):
                self._robust_agg = aggregator
            else:
                self._robust_agg = make_robust_aggregator(
                    aggregator, n=config.client_num_per_round,
                    **(aggregator_params or {}))
            if sanitize is None:
                sanitize = self._robust_agg is not None
            self._sanitize_mult = (
                None if sanitize is False
                else DEFAULT_NORM_MULT if sanitize is True else float(sanitize))
            self._needs_stacked = (self._robust_agg is not None
                                   or self._sanitize_mult is not None)
            # per-round gate/aggregator verdicts (suspected/rejected ranks);
            # rank = stacked slot + 1, matching the loopback runtime's worker
            # ranks so the two ledgers are comparable entry-for-entry
            self.quarantine = QuarantineLedger()
            # model-space adversary injection (chaos/adversary.py): perturb the
            # stacked client nets INSIDE the jitted round program, per the
            # plan's (round-window, rank) schedule — the standalone twin of a
            # Byzantine client lying on the wire.
            self._adversary = None
            if adversary_plan is not None:
                if mesh is not None:
                    raise ValueError(
                        "adversary_plan is a standalone-simulation feature "
                        "(single device); on a mesh run the cross-process "
                        "runtime with per-client adversaries instead")
                from fedml_tpu.chaos.adversary import make_in_graph_injector

                self._adversary = make_in_graph_injector(
                    adversary_plan, config.client_num_per_round)
                self.adversary_plan = adversary_plan
            # telemetry: an obs.Telemetry bundle. None (default) keeps the round
            # program bit-identical to the untelemetered build — the stats below
            # are extra jit OUTPUTS, so the off path has zero overhead and the
            # on path adds no device sync beyond the metrics it already returns.
            self.telemetry = telemetry
            self._emit_stats = telemetry is not None and telemetry.round_stats
            # uniform_avg: aggregate with weight 1 per REAL client (0 for
            # zero-sample padding) instead of sample counts. DP-FedAvg needs
            # this: with sample-weighted averaging a clipped update's influence
            # is (n_k/Σn)·C, unbounded by C/m on unbalanced data, which
            # invalidates the sensitivity the DP noise is calibrated for.
            # size_weighted sampling FORCES it: P(k) ∝ n_k + uniform average
            # is the unbiased pairing (sampling twice — by probability AND by
            # weight — would double-count data-rich clients).
            self.uniform_avg = uniform_avg or config.sampling == "size_weighted"
            if getattr(config, "churn_trace", None) is not None \
                    and mesh is not None:
                raise ValueError(
                    "churn_trace varies the per-round cohort size, which breaks "
                    "the mesh's static client-shard shapes — run churned "
                    "cohorts standalone or through the cross-process runtime "
                    "(rank-level scheduled availability)")
            self._client_sizes = prepare_sampling(config, dataset)
            self.rng = jax.random.PRNGKey(config.seed)

            # device-resident data plane: park the whole train set in HBM once;
            # each round ships only an IndexBatch (KBs) and the row gather runs
            # on device. Batches are bit-identical to the host packer's.
            # donate=True: the per-round program consumes the incoming net/opt
            # buffers (XLA writes outputs in place — no second copy of the model
            # in HBM). Opt-in because a caller may legitimately hold a reference
            # to api.net across rounds (e.g. comparing against round-0 weights);
            # the bench paths enable it. The R-round block fns always donate —
            # their contract never exposed intermediate nets.
            # block_working_set: do NOT park the whole train set in HBM. Each
            # run_rounds block instead uploads only the UNIQUE rows its sampled
            # clients touch (indices remapped into the compact array, row count
            # padded to a bucket so jit re-uses one compiled executable across
            # blocks). Batches stay bit-identical to the full-park plane
            # (test-enforced); what changes is transfer: ~R*K*samples rows
            # (tens of MB) per block instead of the full set (hundreds of MB)
            # up front — the difference between dying and finishing on a slow
            # host->device link. run_round falls back to the host-packed plane.
            self.donate = donate
            self.device_data = device_data
            self.block_working_set = block_working_set
            if block_working_set and not device_data:
                raise ValueError("block_working_set is a device_data mode "
                                 "(pass device_data=True)")
            if device_data and not block_working_set:
                sh = NamedSharding(mesh, P()) if mesh is not None else None
                put = (lambda a: jax.device_put(a, sh)) if sh else jax.device_put
                self._dev_x = put(dataset.train_x)
                self._dev_y = put(dataset.train_y)

            # static per-client batch budget: fixed across rounds so the round
            # program compiles once (see SURVEY.md §7 "hard parts" (1)).
            # Streamed sources answer from size METADATA — no payload read.
            if self._source is not None:
                max_count = int(np.max(self._source.client_sizes))
            else:
                max_count = max(len(v) for v in dataset.train_idx_map.values())
            b_needed = int(np.ceil(max_count / config.batch_size))
            self.num_batches = min(config.max_batches or b_needed, b_needed)
            # bucket_batches: shrink each round's (or block's) common batch
            # depth to the max the SAMPLED clients actually need, rounded up a
            # small static ladder. Trailing all-masked batch slots are exact
            # state no-ops (local.py's has_data select; rng chains are
            # position-based) — so this is bit-exact while skipping their full
            # compute cost, at the price of one extra jit variant per bucket
            # (<=4). On size-skewed natural partitions (FEMNIST lognormal)
            # most rounds sample no near-maximal client, so the common depth
            # drops well below num_batches.
            self.bucket_batches = bucket_batches
            ladder = sorted({-(-self.num_batches // d) for d in (8, 4, 2, 1)})
            self._b_ladder = [b for b in ladder if b > 0]

            self.local_spec = resolve_local_spec(local_spec, config)
            self.local_update = make_local_update(task, self.local_spec)
            self.eval_fn = make_eval_fn(task)

            # server update hook: (net_old, net_avg, opt_state) -> (net_new, opt_state)
            self.server_update = server_update or (lambda old, avg, s: (avg, s))
            self.client_result_hook = client_result_hook  # (net_k, net_global, rng) -> net_k
            self.post_aggregate_hook = post_aggregate_hook  # (net, rng) -> net

            # init model
            self.rng, init_key = jax.random.split(self.rng)
            x_sample = jnp.asarray(
                self._source.init_batch(config.batch_size)
                if self._source is not None
                else dataset.train_x[: config.batch_size])
            with _perf.attribute_compiles(_perf.INIT_VARIANT):
                self.net = task.init(init_key, x_sample)
            # federated TENSOR parallelism: a ('clients','model') mesh shards
            # each client's local fit over 'model' (Megatron specs, GSPMD
            # collectives) while 'clients' stays the manual FL axis — the
            # round program is shard_map(axis_names={'clients'}) so the model
            # axis remains auto and the compiler partitions the vmapped local
            # SGD. Params are placed TP-sharded up front.
            self._tp = mesh is not None and "model" in mesh.axis_names
            if self._tp:
                from fedml_tpu.parallel.tensor_parallel import shard_params

                params, self.tp_specs = shard_params(self.net.params, mesh)
                rep = NamedSharding(mesh, P())
                extra = jax.tree.map(lambda v: jax.device_put(v, rep),
                                     self.net.extra)
                self.net = self.net._replace(params=params, extra=extra)
            # Mesh-sharded server state (core/partition_rules.py,
            # docs/PERFORMANCE.md §Partitioned server state): the global model
            # + server optimizer state live PARTITIONED over the client mesh
            # axis per a regex partition-rule table; the round program
            # constrains the aggregate and the updated state to that layout, so
            # XLA reduce-scatters the weighted update sum into each device's
            # shard, runs the server update shard-locally, and all-gathers only
            # at the broadcast into the next round's local fits
            # (arXiv:2004.13336). Bitwise-identical to the replicated mesh path
            # (test-enforced: resharding moves bits, the psum aggregation math
            # is byte-for-byte the same program).
            self._sharded = bool(shard_server_state)
            self.partitioner = None
            self._agg_reshard = None
            if self._sharded:
                if mesh is None:
                    raise ValueError("shard_server_state partitions the server "
                                     "plane over a mesh — pass mesh=")
                if self._tp:
                    raise ValueError(
                        "shard_server_state composes with the pure 'clients' "
                        "mesh; a ('clients','model') TP mesh already shards "
                        "params over 'model'")
                from fedml_tpu.core.partition_rules import ServerStatePartitioner
                from fedml_tpu.core.robust_agg import COORDINATEWISE

                self.partitioner = ServerStatePartitioner(
                    mesh, rules=partition_rules)
                self.net = self.partitioner.shard(self.net)
                # coordinate-wise estimators run shard-local after an
                # all-to-all to param-sharded stacked layout (specs derived
                # from the NET template so custom rule tables apply);
                # krum/geo-median keep the gathered path (COORDINATEWISE)
                if isinstance(aggregator, str) and aggregator in COORDINATEWISE:
                    self._agg_reshard = self.partitioner.stacked_constrainer(
                        self.net)
            self.server_opt_state = server_opt_init(self.net.params) if server_opt_init else ()
            if self._sharded and server_opt_init is not None:
                # fedopt-style server optimizer state (momenta mirror the param
                # tree) shards by the same rule table — the Adam moments are
                # the 2x multiplier that makes sharding the server plane matter
                self.server_opt_state = self.partitioner.shard(
                    self.server_opt_state)

            if config.client_fold == "vmap":
                if mesh is None:
                    _refuse_cohort_beyond_device(self.net.params,
                                                 config.client_num_per_round)
                self.round_fn = self._build_round_fn()
            elif config.client_fold == "scan":
                from fedml_tpu.core import client_fold

                client_fold.check(self, FedAvgAPI)
                self.round_fn = client_fold.refused_round_fn
            else:
                raise ValueError(f"client_fold={config.client_fold!r} (one "
                                 "of 'vmap', 'scan')")
            self._test_cache = None
            self.history: list[dict] = []
            # per-round pack/bucket accounting (docs/PERFORMANCE.md §Streaming
            # & cohort bucketing): written at pack time (possibly on the
            # prefetch thread — single-key dict writes are GIL-atomic), popped
            # into the telemetry round record at emit time. Bounded by the
            # prefetch depth.
            self._pack_stats: dict[int, dict] = {}
            # server-plane sizing + per-round aggregation-bytes accounting
            # (obs/perf_instrument: fed_server_state_bytes{placement} /
            # fed_agg_bytes_total{mode}) — the metrics the sharded-vs-
            # replicated HBM claim is asserted on
            # sized component-by-component: one (net, opt) tuple would prefix
            # every leaf path with '0/'/'1/' and anchored custom rules would
            # resolve differently here than they did in shard()
            per_dev = (
                self.partitioner.bytes_per_device(self.net)
                + self.partitioner.bytes_per_device(self.server_opt_state)
                if self._sharded
                else _tree_bytes((self.net, self.server_opt_state)))
            self._state_placement = "sharded" if self._sharded else "replicated"
            self._agg_bytes_round = (_tree_bytes(self.net)
                                     * config.client_num_per_round)
            _perf.set_server_state_bytes(self._state_placement, per_dev)
            # rides every telemetry round record (report.py renders srv_B/mode)
            self._agg_record = {
                "mode": self._state_placement,
                "server_state_bytes_per_device": int(per_dev),
                "bytes_per_round": int(self._agg_bytes_round),
            }
            # mixed-precision runs stamp the policy on every round record
            # (report.py's `prec` column; absent = f32, so pre-policy logs
            # render unchanged)
            if self.local_spec.compute_dtype not in ("f32", "float32"):
                self._agg_record["prec"] = self.local_spec.compute_dtype

    # ------------------------------------------------------------------ round
    def _round_body(self, keys, net, server_opt_state, x, y, mask, nsamp,
                    hook_key, round_idx=None):
        """Per-shard body: vmap local fits, weighted-aggregate, server update.

        In distributed mode this runs inside shard_map: the leading client
        axis is this device's slice and the weighted mean is a psum over
        'clients'. In standalone mode axis_name is None and the weighted mean
        is local.
        """
        nets, metrics = jax.vmap(self.local_update, in_axes=(0, None, 0, 0, 0))(
            keys, net, x, y, mask
        )
        if self._adversary is not None and round_idx is not None:
            # Byzantine slots lie BEFORE any server-side defense sees them
            # (the clipping client_result_hook models the server's view).
            # The FULL NetState is perturbed — params AND extra — because
            # that is what a Byzantine client controls on the wire
            # (perturb_leaves hits every packed leaf), and the two
            # runtimes' gate verdicts must agree on models with
            # batch_stats, not just param-only ones.
            nets = self._adversary(nets, net, round_idx)
        if self.client_result_hook is not None:
            # x may be a pytree (FedNAS packs (train, val) streams) — take K
            # from the keys, which are always a flat [K, 2] array
            hkeys = jax.random.split(hook_key, keys.shape[0])
            nets = jax.vmap(lambda n, k: self.client_result_hook(n, net, k))(nets, hkeys)
        return nets, metrics, nsamp

    def _agg_weights(self, nsamp):
        return agg_weights(nsamp, self.uniform_avg)

    def _aggregate_and_update(self, net, server_opt_state, nets, metrics, nsamp, post_key):
        if self._needs_stacked:
            # gate -> estimator -> suspected merge -> all-rejected
            # fallback, via the ONE composition both runtimes share
            # (core/robust_agg.gated_aggregate). With a sharded server
            # state, coordinate-wise estimators get the partitioner's
            # stacked-layout constraint so their sorts run shard-local.
            with jax.named_scope("fed_aggregate"):
                avg, _, reasons = gated_aggregate(
                    nets, net, self._agg_weights(nsamp),
                    robust_fn=self._robust_agg,
                    norm_mult=self._sanitize_mult,
                    reshard_fn=self._agg_reshard)
        else:
            avg = tree_weighted_mean(nets, self._agg_weights(nsamp))
            reasons = None
        new_net, new_opt = self._update_from_aggregate(
            net, avg, server_opt_state, post_key)
        agg_metrics = {k: jnp.sum(v) for k, v in metrics.items()}
        if self._emit_stats:
            agg_metrics.update(round_stats(net, new_net, nets, avg, nsamp))
        if reasons is not None:
            # [K] reason codes ride out of the jit with the metrics and are
            # popped host-side into the quarantine ledger (never floated)
            agg_metrics["__quarantine"] = reasons
        return new_net, new_opt, agg_metrics

    @jax.named_scope("fed_server_update")
    def _update_from_aggregate(self, net, avg, server_opt_state, post_key):
        """constrain(aggregate) -> server_update -> post hook ->
        constrain(new state): the ONE server-side update composition every
        driver dispatches (stacked/robust, mesh per-round, sharded block).
        The sharded constraint points live only here, so the bitwise
        block ≡ per-round ≡ sharded parity contract cannot drift between
        copies; with a replicated state the constraints are skipped and
        this is plain server_update + hook. The avg constraint is the
        reduce-scatter point: the aggregate lands in rule-table layout, so
        the server update runs shard-local and the new state never
        materializes replicated (arXiv:2004.13336)."""
        if self._sharded:
            avg = self.partitioner.constrain(avg)
        new_net, new_opt = self.server_update(net, avg, server_opt_state)
        if self.post_aggregate_hook is not None:
            new_net = self.post_aggregate_hook(new_net, post_key)
        if self._sharded:
            new_net = self.partitioner.constrain(new_net)
            new_opt = self.partitioner.constrain(new_opt)
        return new_net, new_opt

    def _materialize(self, batch):
        """(x, y, mask, nsamp) from either data plane. IndexBatch -> on-device
        row gather from the HBM-resident train set (device_data mode);
        ClientBatch passes through."""
        if isinstance(batch, IndexBatch):
            x, y = _gather_rows(self._dev_x, self._dev_y, batch.idx, batch.mask)
            return x, y, batch.mask, batch.num_samples
        return batch.x, batch.y, batch.mask, batch.num_samples

    def _build_round_fn(self):
        cfg = self.cfg

        client_keys = _make_client_keys(cfg.seed)

        donate_args = (1, 2) if self.donate else ()

        if self.mesh is None:

            @partial(jax.jit, donate_argnums=donate_args)
            def round_fn(rng, net, server_opt_state, batch, round_idx, ids):
                x, y, mask, nsamp_in = self._materialize(batch)
                keys = client_keys(round_idx, ids)
                rng, kh, kp = jax.random.split(rng, 3)
                nets, metrics, nsamp = self._round_body(
                    keys, net, server_opt_state, x, y, mask, nsamp_in, kh,
                    round_idx=round_idx,
                )
                new_net, new_opt, m = self._aggregate_and_update(
                    net, server_opt_state, nets, metrics, nsamp, kp
                )
                return new_net, new_opt, m

            return round_fn

        mesh = self.mesh
        axis = mesh.axis_names[0]
        if axis == "model":
            raise ValueError("the first mesh axis is the client axis; put "
                             "'model' second: Mesh(..., ('clients','model'))")
        # clients shard over the FIRST axis only; a 'model' axis (federated
        # TP) is left auto for GSPMD and contributes no client slots
        ndev = int(mesh.shape[axis])
        self._smap_kw = (dict(mesh=mesh, axis_names={axis}) if self._tp
                         else dict(mesh=mesh))
        if cfg.client_num_per_round % ndev != 0:
            raise ValueError(
                f"client_num_per_round={cfg.client_num_per_round} must be a "
                f"multiple of the '{axis}' mesh size {ndev} (pad with "
                "zero-weight clients)"
            )

        def shard_fits(keys, net, x, y, mask, hook_key):
            # keys/x/y/mask have this device's client slice. The global
            # net enters replicated but the scan carry becomes device-varying
            # after the first local step — mark it varying up front (vma rule).
            net = jax.tree.map(lambda v: jax.lax.pcast(v, axis, to="varying"), net)
            nets, metrics = jax.vmap(self.local_update, in_axes=(0, None, 0, 0, 0))(
                keys, net, x, y, mask
            )
            if self.client_result_hook is not None:
                hkeys = jax.random.split(hook_key, keys.shape[0])
                nets = jax.vmap(lambda n, k: self.client_result_hook(n, net, k))(nets, hkeys)
            return nets, metrics

        def shard_body(keys, net, x, y, mask, nsamp, hook_key):
            nets, metrics = shard_fits(keys, net, x, y, mask, hook_key)
            avg, msum = _shard_aggregate(nets, metrics,
                                         self._agg_weights(nsamp), axis)
            if self._emit_stats:
                # full round_stats on the mesh too (the drift half lives
                # here, where the per-client nets exist; update_norm joins
                # after the server update) — replicated and sharded runs
                # emit identical record keys, and so do mesh vs standalone
                msum = dict(msum)
                msum.update(_mesh_drift_stats(nets.params, avg.params,
                                              nsamp, axis))
            return avg, msum

        smapped = jax.shard_map(
            shard_body,
            in_specs=(P(axis), P(), P(axis), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P()),
            **self._smap_kw,
        )

        def shard_body_devdata(keys, net, dev_x, dev_y, idx, mask, nsamp, hook_key):
            # device-resident plane under SPMD: the train set is replicated,
            # the index block is sharded -> each device gathers its own
            # clients' rows locally (no collective)
            x, y = _gather_rows(dev_x, dev_y, idx, mask)
            return shard_body(keys, net, x, y, mask, nsamp, hook_key)

        smapped_dd = jax.shard_map(
            shard_body_devdata,
            in_specs=(P(axis), P(), P(), P(), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P()),
            **self._smap_kw,
        )
        # the sharded block driver re-dispatches this per-round body from
        # an outer scan (_build_block_fn) — keep a handle
        self._smapped_dd = smapped_dd

        if self._needs_stacked:
            # Robust aggregation needs the FULL stacked client set (sorts,
            # pairwise distances — not psum-able). Run only the local fits
            # under shard_map (the same shard_fits the weighted-mean path
            # aggregates in-shard; out_specs P(axis): each device returns
            # its client shard) and aggregate in the enclosing jit, where
            # GSPMD handles the gather the estimator implies.
            smapped_fits = jax.shard_map(
                shard_fits,
                in_specs=(P(axis), P(), P(axis), P(axis), P(axis), P()),
                out_specs=(P(axis), P(axis)),
                **self._smap_kw,
            )

            def shard_fits_devdata(keys, net, dev_x, dev_y, idx, mask,
                                   hook_key):
                x, y = _gather_rows(dev_x, dev_y, idx, mask)
                return shard_fits(keys, net, x, y, mask, hook_key)

            smapped_fits_dd = jax.shard_map(
                shard_fits_devdata,
                in_specs=(P(axis), P(), P(), P(), P(axis), P(axis), P()),
                out_specs=(P(axis), P(axis)),
                **self._smap_kw,
            )

            @partial(jax.jit, donate_argnums=donate_args)
            def robust_round_fn(rng, net, server_opt_state, batch, round_idx,
                                ids):
                keys = client_keys(round_idx, ids)
                rng, kh, kp = jax.random.split(rng, 3)
                if isinstance(batch, IndexBatch):
                    nets, metrics = smapped_fits_dd(
                        keys, net, self._dev_x, self._dev_y,
                        batch.idx, batch.mask, kh)
                    nsamp = batch.num_samples
                else:
                    nets, metrics = smapped_fits(
                        keys, net, batch.x, batch.y, batch.mask, kh)
                    nsamp = batch.num_samples
                return self._aggregate_and_update(
                    net, server_opt_state, nets, metrics, nsamp, kp)

            return robust_round_fn

        @partial(jax.jit, donate_argnums=donate_args)
        def round_fn(rng, net, server_opt_state, batch, round_idx, ids):
            keys = client_keys(round_idx, ids)
            rng, kh, kp = jax.random.split(rng, 3)
            if isinstance(batch, IndexBatch):
                avg, metrics = smapped_dd(
                    keys, net, self._dev_x, self._dev_y,
                    batch.idx, batch.mask, batch.num_samples, kh,
                )
            else:
                avg, metrics = smapped(
                    keys, net, batch.x, batch.y, batch.mask, batch.num_samples, kh
                )
            new_net, new_opt = self._update_from_aggregate(
                net, avg, server_opt_state, kp)
            if self._emit_stats:
                # the drift half rode out of shard_body; the update norm
                # joins here, where the post-update params exist (on a
                # sharded state GSPMD psums the shard-local partials, so
                # the record still carries the FULL norm)
                metrics = dict(metrics)
                metrics["update_norm"] = _update_norm(new_net.params,
                                                      net.params)
            return new_net, new_opt, metrics

        return round_fn

    # ------------------------------------------------------------------ data
    def _pack_round_host(self, round_idx: int) -> ClientBatch:
        """Always the dense host-packed ClientBatch, regardless of
        device_data — for engines that consume .x/.y directly (FedDF's
        distillation batches, TurboAggregate's share encoding, affinity).
        Delegates through the explicit ``device_data`` argument (never a
        mutate-self-and-restore toggle: the prefetch thread packs
        concurrently with the driver, and a shared flag flip would race)."""
        return self._pack_round(round_idx, device_data=False)

    def _bucketed_B(self, b_needed: int) -> int:
        """Smallest ladder bucket covering ``b_needed`` (ladder tops out at
        num_batches, so the result never exceeds the static budget)."""
        for b in self._b_ladder:
            if b >= b_needed:
                return b
        return self.num_batches

    def _record_pack_stats(self, round_idx: int, b_needed: int,
                           batch) -> None:
        """One round's pack/bucket accounting: the dispatched batch depth
        (the ladder bucket when bucket_batches is on), the natural depth
        the cohort needed, the fraction of batch slots that are pure
        padding, and the packed host bytes — the numbers that show whether
        a skewed population is paying for its largest client every round."""
        if self.telemetry is None:
            return  # nobody will pop it — don't grow the dict forever
        if isinstance(batch, IndexBatch):
            K, B = batch.idx.shape[0], batch.idx.shape[1]
            nbytes = batch.idx.nbytes + batch.mask.nbytes
        else:
            K, B = batch.x.shape[0], batch.x.shape[1]
            nbytes = batch.x.nbytes + batch.y.nbytes + batch.mask.nbytes
        used = float(np.sum(np.ceil(
            np.asarray(batch.num_samples) / self.cfg.batch_size)))
        slots = float(K * B)
        self._pack_stats[round_idx] = {
            "bucket_B": int(B), "b_needed": int(b_needed),
            "budget_B": int(self.num_batches),
            "pad_frac": round(1.0 - used / slots, 4) if slots else 0.0,
            "bytes": int(nbytes),
        }

    def _pack_extra(self, round_idx: int) -> dict:
        """The optional ``pack`` block a telemetry round record carries —
        absent when nothing was recorded (engines that override packing)."""
        ps = self._pack_stats.pop(round_idx, None)
        return {"pack": ps} if ps else {}

    def _pack_round_indices_host(self, round_idx: int,
                                 pad_to: int | None = None) -> IndexBatch:
        """Host-side padded IndexBatch (no device placement) — shared by the
        per-round path and the R-round block packer. ``pad_to`` is the
        common batch depth: default the static num_batches; the bucketed
        paths pass their (smaller) bucket; 0 = natural depth (no pad)."""
        cfg = self.cfg
        ids = self._sampled_ids(round_idx)
        ib = pack_client_indices(
            self.data, ids, cfg.batch_size, max_batches=self.num_batches,
            seed=cfg.seed, round_idx=round_idx,
        )
        b_needed = ib.idx.shape[1]
        if pad_to is None:
            pad_to = (self._bucketed_B(b_needed)
                      if self.bucket_batches else self.num_batches)
            ib = pad_index_batches(ib, pad_to)
            self._record_pack_stats(round_idx, b_needed, ib)
            return ib
        return pad_index_batches(ib, pad_to)

    def _shard_round_batch(self, batch):
        """Mesh placement of one round's batch: every leaf client-sharded
        over the first mesh axis (no-op off-mesh). One definition shared by
        the round packer, the prefetch thread, and warmup lowering."""
        if self.mesh is None:
            return batch
        sh = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return jax.tree.map(lambda v: jax.device_put(v, sh), batch)

    def _pack_round(self, round_idx: int, device_data: bool | None = None):
        """One round's batch on the engine's data plane. ``device_data``
        overrides the engine default explicitly (None = self.device_data)
        so callers needing the dense host pack — and the prefetch thread —
        never toggle shared state."""
        cfg = self.cfg
        if device_data is None:
            device_data = self.device_data
        if device_data and not self.block_working_set:
            ib = self._pack_round_indices_host(round_idx)
            return self._shard_round_batch(ib)
        ids = self._sampled_ids(round_idx)
        if self._source is not None:
            # streamed plane: only the sampled cohort's rows are read
            cb = pack_clients_source(
                self._source, ids, cfg.batch_size,
                max_batches=self.num_batches, seed=cfg.seed,
                round_idx=round_idx)
        else:
            cb = pack_clients(
                self.data, ids, cfg.batch_size, max_batches=self.num_batches,
                seed=cfg.seed, round_idx=round_idx,
            )
        # fixed B across rounds -> single compilation (or, with
        # bucket_batches, the round's ladder bucket -> <=4 compilations)
        b_needed = cb.num_batches
        cb = pad_batches(cb, self._bucketed_B(b_needed)
                         if self.bucket_batches else self.num_batches)
        self._record_pack_stats(round_idx, b_needed, cb)
        return self._shard_round_batch(cb)

    def _sampled_ids(self, round_idx: int):
        return sample_for(self.cfg, round_idx, self._client_sizes)

    # ----------------------------------------------------------- round block
    # the methods the single-device block program's trace runs through
    _BLOCK_TRACE_PATH = ("_round_body", "_aggregate_and_update",
                         "_update_from_aggregate", "_agg_weights")

    def _block_trace_reads(self) -> dict:
        """Every value the trace of the single-device ``block_fn`` reads
        from ``self`` (its call arguments apart), by name, for the program
        store's key. **Whoever makes that trace read another attribute
        adds it here**: a value the key does not hold is a stale program.
        What is not listed follows from what is: ``_needs_stacked`` from
        ``_robust_agg`` and ``_sanitize_mult``; the task and the local
        spec are what ``local_update`` closes over; ``_agg_reshard`` and
        ``partitioner`` are None and ``_sharded`` False without a mesh.
        A subclass that overrides a method of the trace's path may read
        anything: it is unkeyable, and traces as ever."""
        cls = type(self)
        for name in self._BLOCK_TRACE_PATH:
            if getattr(cls, name) is not getattr(FedAvgAPI, name):
                raise program_store.Unkeyable(
                    f"{cls.__qualname__} overrides {name}: what it reads "
                    "from the engine is not known to the key")
        return {
            "engine": cls,
            # the seed of the client keys; lr, wd and epochs are baked
            # into local_update, which is keyed itself
            "cfg": self.cfg,
            "local_update": self.local_update,
            "adversary": self._adversary,
            "client_result_hook": self.client_result_hook,
            "post_aggregate_hook": self.post_aggregate_hook,
            "server_update": self.server_update,
            "robust_agg": self._robust_agg,
            "sanitize_mult": self._sanitize_mult,
            "uniform_avg": self.uniform_avg,
            "emit_stats": self._emit_stats,
            "client_fold": self.cfg.client_fold,
            "flags": {"donate": self.donate, "device_data": self.device_data,
                      "block_working_set": self.block_working_set,
                      "bucket_batches": self.bucket_batches,
                      "sharded": self._sharded},
        }

    def _build_block_fn(self):
        """R rounds as ONE compiled program: lax.scan over rounds, the whole
        block's index batches resident on device. Removes per-round host
        dispatch + transfer entirely — for small models (the flagship
        FedAvg-CNN) dispatch dominates, so this is the main throughput lever.
        Client keys are the same fold_in(fold_in(seed, round), client) chain
        as run_round; the per-round hook keys (kh, kp) are PRE-DERIVED with
        the exact split chain sequential run_round calls would draw
        (self.rng -> rk per round, rk -> (_, kh, kp)) and scanned with the
        rounds — so a block is bit-identical to the sequential path even for
        hooked engines (clipping client_result_hook, DP post_aggregate_hook;
        tested). With a mesh, the scan runs INSIDE shard_map: every
        device scans its client shard for R rounds and aggregation is a
        weighted psum per step — the whole block is one SPMD program and the
        host is out of the loop entirely (the v4-32 north-star path). The
        post-aggregate hook runs right after the server update inside the
        shard; its key is replicated, so the hook's draw (e.g. DP noise) is
        identical on every device and the net stays replicated — the same
        values the per-round path computes outside shard_map."""
        client_keys = _make_client_keys(self.cfg.seed)

        def derive_hook_keys(rng, n_rounds):
            """The sequential key stream, precomputed: run_round does
            ``self.rng, rk = split(self.rng)`` then ``_, kh, kp =
            split(rk, 3)`` — reproduce exactly that chain for each round in
            the block so hooked engines keep bit-exact key parity."""
            def kstep(r, _):
                r, rk = jax.random.split(r)
                _, kh, kp = jax.random.split(rk, 3)
                return r, (kh, kp)

            return jax.lax.scan(kstep, rng, None, length=n_rounds)

        if self.mesh is None:

            def make_step(dev_x, dev_y):
                def step(carry, inp):
                    net, opt = carry
                    idx_r, mask_r, nsamp_r, ids_r, r, kh, kp = inp
                    keys = client_keys(r, ids_r)
                    x, y = _gather_rows(dev_x, dev_y, idx_r, mask_r)
                    nets, metrics, _ = self._round_body(
                        keys, net, opt, x, y, mask_r, nsamp_r, kh,
                        round_idx=r,
                    )
                    net, opt, m = self._aggregate_and_update(
                        net, opt, nets, metrics, nsamp_r, kp
                    )
                    return (net, opt), m

                return step

            if self.cfg.client_fold == "scan":
                # the cohort one silo after another (core/client_fold.py)
                from fedml_tpu.core import client_fold

                make_step = client_fold.make_step(self, client_keys,
                                                  _gather_rows)

            def block_fn(rng, net, opt, dev_x, dev_y, idx, mask, nsamp, ids,
                         round_idxs):
                rng, (khs, kps) = derive_hook_keys(rng, idx.shape[0])
                (net, opt), ms = jax.lax.scan(
                    make_step(dev_x, dev_y), (net, opt),
                    (idx, mask, nsamp, ids, round_idxs, khs, kps)
                )
                return rng, net, opt, ms

            # jax.jit(block_fn) where no compile cache directory is set;
            # with one, a jit that loads the program a former process
            # exported in place of tracing it (core/program_store.py)
            return handing_off(program_store.stored_jit(
                block_fn, donate_argnums=(0, 1, 2),
                reads=self._block_trace_reads), self.task)

        mesh = self.mesh
        axis = mesh.axis_names[0]
        server_update = self.server_update
        local_update = self.local_update

        if self._sharded:
            # Sharded block: the replicated block scans INSIDE one
            # shard_map, where state is per-device-manual and a partitioned
            # carry cannot be expressed. Here the scan runs in the OUTER
            # jit instead, re-dispatching the per-round shard_mapped body
            # each step — same per-element ops, so block ≡ per-round stays
            # bitwise — with the carry constrained to the rule-table layout
            # (server update shard-local; net all-gathered at each step's
            # shard_map broadcast boundary, exactly like the per-round fn).
            smapped_dd = self._smapped_dd

            @partial(jax.jit, donate_argnums=(1, 2))
            def sharded_block_fn(rng, net, opt, dev_x, dev_y, idx, mask,
                                 nsamp, ids, round_idxs):
                rng, (khs, kps) = derive_hook_keys(rng, idx.shape[0])

                def step(carry, inp):
                    net, opt = carry
                    idx_r, mask_r, nsamp_r, ids_r, r, kh, kp = inp
                    keys = client_keys(r, ids_r)
                    avg, msum = smapped_dd(keys, net, dev_x, dev_y,
                                           idx_r, mask_r, nsamp_r, kh)
                    old_net = net
                    net, opt = self._update_from_aggregate(net, avg, opt, kp)
                    if self._emit_stats:
                        msum = dict(msum)
                        msum["update_norm"] = _update_norm(net.params,
                                                           old_net.params)
                    return (net, opt), msum

                (net, opt), ms = jax.lax.scan(
                    step, (net, opt),
                    (idx, mask, nsamp, ids, round_idxs, khs, kps))
                return rng, net, opt, ms

            return handing_off(sharded_block_fn, self.task)

        def shard_block(net, opt, dev_x, dev_y, idx, mask, nsamp, ids, rounds,
                        khs, kps):
            # idx/mask/nsamp/ids carry this device's client slice on axis 1:
            # [R, K/n, ...]; net/opt/rounds/khs/kps are replicated
            def step(carry, inp):
                net, opt = carry
                idx_r, mask_r, nsamp_r, ids_r, r, kh, kp = inp
                keys = client_keys(r, ids_r)
                x, y = _gather_rows(dev_x, dev_y, idx_r, mask_r)
                net_v = jax.tree.map(
                    lambda v: jax.lax.pcast(v, axis, to="varying"), net)
                nets, metrics = jax.vmap(
                    local_update, in_axes=(0, None, 0, 0, 0))(
                        keys, net_v, x, y, mask_r)
                if self.client_result_hook is not None:
                    # same per-device split count as the per-round mesh
                    # path's shard_body: block ≡ run_round on this mesh
                    hkeys = jax.random.split(kh, keys.shape[0])
                    nets = jax.vmap(
                        lambda n, k: self.client_result_hook(n, net_v, k))(
                            nets, hkeys)
                avg, msum = _shard_aggregate(
                    nets, metrics, self._agg_weights(nsamp_r), axis)
                old_net = net
                # self._sharded is always False here (the sharded block
                # scans in the outer jit above), so this is plain
                # server_update + hook — but through the ONE composition
                net, opt = self._update_from_aggregate(net, avg, opt, kp)
                if self._emit_stats:
                    # full round_stats, like shard_body: drift from the
                    # in-shard nets, update norm from the post-update params
                    msum = dict(msum)
                    msum.update(_mesh_drift_stats(nets.params, avg.params,
                                                  nsamp_r, axis))
                    msum["update_norm"] = _update_norm(net.params,
                                                       old_net.params)
                return (net, opt), msum

            (net, opt), ms = jax.lax.scan(
                step, (net, opt), (idx, mask, nsamp, ids, rounds, khs, kps))
            return net, opt, ms

        smapped_block = jax.shard_map(
            shard_block,
            in_specs=(P(), P(), P(), P(), P(None, axis), P(None, axis),
                      P(None, axis), P(None, axis), P(), P(), P()),
            out_specs=(P(), P(), P()),
            **self._smap_kw,
        )

        @partial(jax.jit, donate_argnums=(1, 2))
        def block_fn(rng, net, opt, dev_x, dev_y, idx, mask, nsamp, ids,
                     round_idxs):
            rng, (khs, kps) = derive_hook_keys(rng, idx.shape[0])
            net, opt, ms = smapped_block(net, opt, dev_x, dev_y,
                                         idx, mask, nsamp, ids, round_idxs,
                                         khs, kps)
            return rng, net, opt, ms

        return handing_off(block_fn, self.task)

    def run_rounds(self, start_round: int, num_rounds: int):
        """Run ``num_rounds`` rounds as one device-side program (requires
        ``device_data=True``; works single-chip and over a client mesh).
        Returns per-round metrics stacked along axis 0."""
        if not self.device_data:
            raise ValueError("run_rounds needs device_data=True")
        if getattr(self.cfg, "churn_trace", None) is not None:
            raise ValueError(
                "churn_trace varies the per-round cohort size — the scanned "
                "round block needs one static K across its rounds; drive "
                "churned runs through train()/run_round (per-round dispatch)")
        if self.mesh is not None and self._needs_stacked:
            # the mesh block scans INSIDE shard_map, where a robust
            # aggregator's full-stack sorts/distances cannot run — degrade
            # to per-round dispatch (run_round's fits-only mesh path),
            # returning the same stacked-metrics contract
            rounds = [self.run_round(r)
                      for r in range(start_round, start_round + num_rounds)]
            return {k: jnp.stack([m[k] for m in rounds])
                    for k in rounds[0]}
        if not hasattr(self, "_block_fn"):
            self._block_fn = self._build_block_fn()
        if self.telemetry is not None:
            t_wall = time.perf_counter()
            spans_before = dict(self.tracer.rounds[-1])
            if self.telemetry.tracer is not None:
                # one trace per scanned block (its spans are amortized
                # over the R rounds, like the 'block' event record)
                self.telemetry.tracer.begin_round(start_round)

        # These two frames sit under the whole trace of a first dispatch,
        # and on the v5e host that trace ran 15 to 40 % slower under every
        # reshaping of them that PR 26 tried (a place span, round ids, the
        # variant scope): PERF.md section 6. So the block path keeps its two
        # spans as they were, and perf_instrument names the program's
        # compile events by the jit function's name instead.
        with self.tracer.span("pack"):
            packed = self._pack_block_host(start_round, num_rounds)
            ids_l, placed = self._place_block(packed)
        with self.tracer.span("round"):
            ms = self._dispatch_block(placed)
        ms = self._drain_quarantine_block(ms, start_round, ids_l)
        if self.telemetry is not None:
            # per-round records from the scanned block's stacked metrics
            # (one sync for the whole block); the block's host spans
            # (pack + one dispatch) ride on a separate 'block' event since
            # they are amortized over the R rounds, not per-round
            wait = self._goodput_wait(ms)
            self._emit_block_records(start_round, num_rounds, ids_l, ms,
                                     spans=self._span_delta(spans_before),
                                     wall_s=time.perf_counter() - t_wall,
                                     compute_wait_s=wait)
            if self.telemetry.tracer is not None:
                self.telemetry.tracer.finish_round()  # see run_round
        return ms

    def _pack_block_host(self, start_round: int, num_rounds: int):
        """Host-side pack of one R-round block — a pure function of
        (seed, rounds), safe on the prefetch thread. Returns
        (rounds, ids_l, idx_stack, mask_stack, ns_stack), all numpy."""
        ids_l, idx_l, mask_l, ns_l = [], [], [], []
        # pack at natural depth first, then pad every round to the BLOCK's
        # common depth — the ladder bucket when bucket_batches is on (the
        # scan needs one B; jit caches per bucket, <=4 variants), the
        # static budget otherwise. One path, so the per-round pack stats
        # are recorded identically in both modes.
        for r in range(start_round, start_round + num_rounds):
            # host-side pack: the stacked block is device_put ONCE in
            # _place_block (per-round device_puts would round-trip, and on
            # multi-host meshes a sharded array can't return via np.asarray)
            ib = self._pack_round_indices_host(r, pad_to=0)
            ids_l.append(np.asarray(self._sampled_ids(r), np.int32))
            idx_l.append(ib.idx)
            mask_l.append(ib.mask)
            ns_l.append(ib.num_samples)
        B = (self._bucketed_B(max(a.shape[1] for a in idx_l))
             if self.bucket_batches else self.num_batches)
        for i, (ix, mk, ns) in enumerate(zip(idx_l, mask_l, ns_l)):
            b_needed = ix.shape[1]
            ib = pad_index_batches(
                IndexBatch(idx=ix, mask=mk, num_samples=ns), B)
            idx_l[i], mask_l[i] = ib.idx, ib.mask
            self._record_pack_stats(start_round + i, b_needed, ib)
        rounds = np.arange(start_round, start_round + num_rounds,
                           dtype=np.int32)
        return rounds, ids_l, np.stack(idx_l), np.stack(mask_l), np.stack(ns_l)

    def _place_block(self, packed):
        """Device placement for a packed block: working-set compaction (its
        grow-only caches are touched by exactly one placer at a time — the
        prefetch thread in pipelined mode, the driver otherwise) plus the
        block's H2D transfers. Returns (ids_l, dispatch args)."""
        rounds, ids_l, idx_stack, mask_stack, ns_stack = packed
        if self.block_working_set:
            idx_stack, dev_x, dev_y = self._compact_block_rows(idx_stack)
        else:
            dev_x, dev_y = self._dev_x, self._dev_y
        blocks = [idx_stack, mask_stack, ns_stack, np.stack(ids_l)]
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(None, self.mesh.axis_names[0]))
            blocks = [jax.device_put(b, sh) for b in blocks]
        blocks = [jnp.asarray(b) for b in blocks]
        return ids_l, (dev_x, dev_y, blocks, jnp.asarray(rounds))

    def _dispatch_block(self, placed):
        dev_x, dev_y, blocks, rounds = placed
        self.rng, self.net, self.server_opt_state, ms = self._block_fn(
            self.rng, self.net, self.server_opt_state, dev_x, dev_y,
            *blocks, rounds,
        )
        _perf.record_agg_bytes(self._state_placement,
                               self._agg_bytes_round * rounds.shape[0])
        return ms

    def _emit_block_records(self, start_round: int, num_rounds: int, ids_l,
                            ms, spans=None, pipeline=None, wall_s=None,
                            compute_wait_s: float = 0.0,
                            pipelined: bool = False):
        ms_host = {k: np.asarray(v) for k, v in ms.items()}
        self.telemetry.events.emit(
            "block", start=int(start_round), rounds=int(num_rounds),
            spans=spans or {},
            **({"pipeline": pipeline} if pipeline else {}))
        # the block's wall/spans/wait are amortized over its R rounds so
        # each per-round record carries a comparable goodput block (the
        # block variant's cost analysis covers R rounds -> cost_rounds=R)
        R = max(int(num_rounds), 1)
        per_spans = {k: v / R for k, v in (spans or {}).items()}
        for i in range(num_rounds):
            pack_extra = self._pack_extra(start_round + i)
            gp = ({} if wall_s is None else self._goodput_extra(
                wall_s / R, per_spans, pipelined=pipelined,
                compute_wait_s=compute_wait_s / R, pack_extra=pack_extra,
                block_rounds=R))
            self.telemetry.emit_round(
                start_round + i, clients=ids_l[i].tolist(),
                metrics={k: float(v[i]) for k, v in ms_host.items()},
                block=True, agg=self._agg_record,
                **gp,
                **pack_extra,
                **self._quarantine_extra(start_round + i),
                **self._privacy_extra())

    def _drain_block_entry(self, start_round: int, entry):
        """Block analogue of _drain_round_entry: the only sync, one block
        behind dispatch; ledger + telemetry flushed in block order."""
        num_rounds, ids_l, spans, pipeline, ms = entry
        wall = wait = None
        if self.telemetry is not None:
            wait = self._goodput_wait(ms)
            wall = self._goodput_interval()
        ms = self._drain_quarantine_block(ms, start_round, ids_l)
        with self.tracer.span("fetch", round=start_round):
            ms_host = {k: np.asarray(v) for k, v in ms.items()}
        if self.telemetry is not None:
            self._emit_block_records(start_round, num_rounds, ids_l, ms_host,
                                     spans=spans, pipeline=pipeline,
                                     wall_s=wall, compute_wait_s=wait or 0.0,
                                     pipelined=True)
        return start_round, ms_host

    def run_blocks_pipelined(self, start_round: int, num_blocks: int,
                             block_rounds: int):
        """``num_blocks`` scanned R-round blocks with block-level prefetch:
        block b+1's host pack + H2D run on the packer thread while block
        b's program executes; the metrics drain trails one block behind.
        Bit-identical to the same sequence of run_rounds calls
        (test-enforced). Returns drained [(start_round, host metrics)]."""
        self._warn_tracer_unsupported()
        if not self.device_data:
            raise ValueError("run_blocks_pipelined needs device_data=True")
        if self.mesh is not None and self._needs_stacked:
            # the robust mesh block already degrades to per-round dispatch
            # (see run_rounds) — pipeline per round instead of per block
            out = []
            for b in range(num_blocks):
                out.extend(self.run_pipelined(
                    start_round + b * block_rounds, block_rounds))
            return out
        if not hasattr(self, "_block_fn"):
            self._block_fn = self._build_block_fn()

        def produce(s):
            # packer thread: bare annotations, never self.tracer (see
            # _pack_round_placed)
            t0 = time.perf_counter()
            with TraceAnnotation("fed:prefetch_pack", round=s):
                packed = self._pack_block_host(s, block_rounds)
            t1 = time.perf_counter()
            with TraceAnnotation("fed:h2d", round=s):
                ids_l, placed = self._place_block(packed)
            h2d = time.perf_counter() - t1
            _perf.record_span("prefetch_pack", t1 - t0)
            _perf.record_h2d(h2d)
            return ids_l, placed, {"prefetch_pack": t1 - t0, "h2d": h2d}

        starts = [start_round + b * block_rounds for b in range(num_blocks)]
        pf = Prefetcher(produce, starts, depth=max(1, self.prefetch),
                        on_event=self._pipe_on_event)
        # block units are R rounds each, so the lag is capped at one block
        # — but drain_lag=0 (the documented "correlate api.net with its
        # metrics" escape hatch) must still mean drain-immediately here
        ring = InflightRing(min(self.drain_lag, 1), self._drain_block_entry,
                            on_event=self._pipe_on_event)
        self._gp_prev_drain_t = time.perf_counter()
        out = []
        try:
            for s in starts:
                (ids_l, placed, spans), stall = pf.get(s)
                with self.tracer.span("round"):
                    ms = self._dispatch_block(placed)
                spans = dict(spans, prefetch_stall=stall)
                out.extend(ring.push(
                    s, (block_rounds, ids_l, spans, {"depth": len(ring) + 1},
                        ms)))
            out.extend(ring.drain_all())
        finally:
            pf.close()
        return out

    # ----------------------------------------------------------------- warmup
    def _warmup_batch(self, B: int):
        """A zero-filled round batch with exactly the shapes/dtypes/sharding
        the round program sees at bucket depth ``B`` — values are irrelevant
        (lowering abstracts them); shapes select the jit variant."""
        K, bs = self.cfg.client_num_per_round, self.cfg.batch_size
        if self.device_data and not self.block_working_set:
            ib = IndexBatch(
                idx=np.zeros((K, B, bs), np.int32),
                mask=np.zeros((K, B, bs), np.float32),
                num_samples=np.zeros((K,), np.float32))
            return self._shard_round_batch(ib)
        if self._source is not None:
            (xs, xd), (ys, yd) = self._source.row_meta()
        else:
            x, y = self.data.train_x, self.data.train_y
            (xs, xd), (ys, yd) = ((x.shape[1:], x.dtype),
                                  (y.shape[1:], y.dtype))
        cb = ClientBatch(
            x=np.zeros((K, B, bs) + xs, xd),
            y=np.zeros((K, B, bs) + ys, yd),
            mask=np.zeros((K, B, bs), np.float32),
            num_samples=np.zeros((K,), np.float32))
        return self._shard_round_batch(cb)

    def warmup(self, block_rounds: int | None = None,
               per_round: bool = True,
               max_workers: int | None = None) -> dict:
        """AOT-compile every round-program variant this engine can dispatch
        — the <=4 bucket depths of the per-round fn plus, with
        ``block_rounds=R``, the scanned R-round block fn per bucket —
        concurrently on a thread pool (``.lower()`` serially, ``.compile()``
        overlapped; XLA releases the GIL).

        Wired to the persistent compile cache: warmup enables it when no
        cache dir is configured yet, every compile lands on disk, and the
        jit dispatch that later runs the round deserializes instead of
        recompiling — so a repeat run (or the N-1 sibling ranks of a
        simulated cluster) performs zero fresh compiles, which the returned
        report asserts rather than assumes (``fresh_compiles`` /
        ``cache_hits`` deltas from obs/perf_instrument).

        ``per_round=False`` drops the per-round variants (a block-only
        driver should not pay compiles it will never dispatch). Skipped
        variants that the first dispatch compiles instead: the block fn
        under ``block_working_set`` (its parked-row count is
        data-dependent) and on a robust mesh (that path degrades to
        per-round dispatch)."""
        if not getattr(jax.config, "jax_compilation_cache_dir", None):
            from fedml_tpu.utils.metrics import enable_compile_cache

            enable_compile_cache()
        cfg = self.cfg
        K = cfg.client_num_per_round
        buckets = (list(self._b_ladder) if self.bucket_batches
                   else [self.num_batches])
        rng = jax.random.PRNGKey(0)
        r0, ids = jnp.int32(0), jnp.zeros((K,), jnp.int32)
        # precision x bucket variant naming (_variant_name): a bf16
        # engine's warmed executables are DIFFERENT programs from the f32
        # engine's, and the report must say which ladder was precompiled
        lowered = {}
        if per_round:
            for B in buckets:
                name = self._variant_name(B=B)
                with _perf.attribute_compiles(name):
                    lowered[name] = self.round_fn.lower(
                        rng, self.net, self.server_opt_state,
                        self._warmup_batch(B), r0, ids)
        if block_rounds and self.device_data and not self.block_working_set \
                and not (self.mesh is not None and self._needs_stacked):
            if not hasattr(self, "_block_fn"):
                self._block_fn = self._build_block_fn()
            R = int(block_rounds)
            for B in buckets:
                bs = cfg.batch_size
                blocks = [np.zeros((R, K, B, bs), np.int32),
                          np.zeros((R, K, B, bs), np.float32),
                          np.zeros((R, K), np.float32),
                          np.zeros((R, K), np.int32)]
                if self.mesh is not None:
                    sh = NamedSharding(self.mesh,
                                       P(None, self.mesh.axis_names[0]))
                    blocks = [jax.device_put(b, sh) for b in blocks]
                blocks = [jnp.asarray(b) for b in blocks]
                name = self._variant_name(B=B, block_rounds=R)
                with _perf.attribute_compiles(name):
                    lowered[name] = self._block_fn.lower(
                        rng, self.net, self.server_opt_state,
                        self._dev_x, self._dev_y, *blocks,
                        jnp.asarray(np.arange(R, dtype=np.int32)))
        rep = compile_concurrently(lowered, max_workers=max_workers)
        rep.pop("executables", None)
        rep["bucket_depths"] = buckets
        log.info("warmup: %d variant(s) in %.2fs (%d fresh compiles, "
                 "%d persistent-cache hits)", len(rep["variants"]),
                 rep["seconds"], rep["fresh_compiles"], rep["cache_hits"])
        if self.telemetry is not None:
            # the compile observatory's event record: per-variant wall from
            # the AOT pass plus the registry's per-variant attribution
            # (hits/misses/backend seconds) — report.py --compiles renders it
            self.telemetry.events.emit(
                "compiles", variants=rep.get("per_variant") or {},
                seconds=rep["seconds"], fresh=rep["fresh_compiles"],
                cache_hits=rep["cache_hits"],
                cache_misses=rep["cache_misses"],
                instrumented=rep["instrumented"],
                attribution=_perf.variant_compile_stats())
        return rep

    _WORKING_SET_BUCKET = 8192  # rows; pad-to-bucket keeps ONE compiled block

    def _compact_block_rows(self, idx_stack: np.ndarray):
        """Working-set park: upload only the unique train rows this block's
        index batches touch. Indices are remapped into the compact array and
        its row count padded up to a _WORKING_SET_BUCKET multiple —
        GROW-ONLY across blocks (a later, slightly smaller working set pads
        up to the largest size seen instead of shrinking into a different
        bucket), so steady-state blocks hit one compiled executable (jit
        caches by shape) and a recompile can only happen on genuine growth."""
        uniq, inv = np.unique(idx_stack, return_inverse=True)
        remapped = inv.reshape(idx_stack.shape).astype(np.int32)
        # bucket round-up is >= len(uniq), and uniq indexes train_x so
        # len(uniq) <= len(train_x): the min never under-allocates
        n_rows = min(
            -(-len(uniq) // self._WORKING_SET_BUCKET) * self._WORKING_SET_BUCKET,
            len(self.data.train_x),
        )
        n_rows = max(n_rows, getattr(self, "_ws_rows", 0))
        if (n_rows == getattr(self, "_ws_rows", 0)
                and getattr(self, "_ws_uniq", None) is not None
                and np.array_equal(uniq, self._ws_uniq)):
            # same unique-row set as the previous block: the parked device
            # buffers are already exactly right — skip the host gather AND
            # the upload entirely
            return remapped, self._ws_dev_x, self._ws_dev_y
        self._ws_rows = n_rows
        self._ws_uniq = uniq
        # FRESH host buffers every refill: device_put may alias (CPU) or
        # asynchronously read (accelerator) the numpy buffer, so a cached
        # staging buffer refilled in place could corrupt the previous
        # block's parked rows while its round program is still in flight.
        # np.zeros is calloc'd (near-free); the real cost here is the row
        # gather, which only happens when the working set actually changed
        # (the unchanged case short-circuits above).
        cx = np.zeros((n_rows,) + self.data.train_x.shape[1:],
                      self.data.train_x.dtype)
        cy = np.zeros((n_rows,) + self.data.train_y.shape[1:],
                      self.data.train_y.dtype)
        cx[: len(uniq)] = self.data.train_x[uniq]
        cy[: len(uniq)] = self.data.train_y[uniq]
        sh = (NamedSharding(self.mesh, P()) if self.mesh is not None else None)
        put = (lambda a: jax.device_put(a, sh)) if sh else jax.device_put
        self._ws_dev_x, self._ws_dev_y = put(cx), put(cy)
        return remapped, self._ws_dev_x, self._ws_dev_y

    def _span_delta(self, before: dict) -> dict:
        """This call's span seconds: current tracer round minus a snapshot
        taken at entry. run_round/run_rounds may be driven directly (bench,
        CLI loops) without train()'s next_round() between calls, so the
        tracer's round dict ACCUMULATES — the emitted record must carry the
        delta, not the running total."""
        cur = self.tracer.rounds[-1]
        return {k: v - before.get(k, 0.0) for k, v in cur.items()
                if v - before.get(k, 0.0) > 0.0}

    # ------------------------------------------------------------- quarantine
    def _drain_quarantine(self, metrics: dict, round_idx: int, ids):
        """Pop the round's in-graph ``__quarantine`` reason codes (if the
        gate/aggregator is armed) into the host-side ledger + metric
        families. Returns the metrics dict without the codes — they are a
        [K] int vector, not a floatable round scalar."""
        if "__quarantine" not in metrics:
            return metrics
        metrics = dict(metrics)
        with self.tracer.span("fetch", round=round_idx):
            codes = np.asarray(metrics.pop("__quarantine"))
        self.quarantine.record_codes(round_idx, codes,
                                     clients=np.asarray(ids).tolist())
        return metrics

    def _drain_quarantine_block(self, ms: dict, start_round: int, ids_l):
        if "__quarantine" not in ms:
            return ms
        ms = dict(ms)
        with self.tracer.span("fetch", round=start_round):
            codes = np.asarray(ms.pop("__quarantine"))  # [R, K]
        for i in range(codes.shape[0]):
            self.quarantine.record_codes(start_round + i, codes[i],
                                         clients=ids_l[i].tolist())
        return ms

    def _quarantine_extra(self, round_idx: int) -> dict:
        """The per-round record field telemetry rides the verdicts on —
        absent entirely on clean rounds to keep records stable."""
        entries = self.quarantine.for_round(round_idx)
        return {"quarantine": entries} if entries else {}

    def _privacy_extra(self) -> dict:
        """The optional ``privacy`` block a DP engine rides on round
        records (docs/ROBUSTNESS.md §Privacy ledger) — {} here;
        FedAvgRobustAPI overrides with its accountant's cumulative ε."""
        return {}

    # ------------------------------------------------------ round economics
    def _variant_name(self, B=None, block_rounds: int | None = None) -> str:
        """The jit variant name this dispatch selects — the same
        ``round{prec}_b{B}`` / ``block{prec}_r{R}_b{B}`` scheme warmup()
        compiles under, so the goodput block finds the variant's cached
        XLA cost analysis (docs/PERFORMANCE.md §Round economics)."""
        prec = ("" if self.local_spec.compute_dtype in ("f32", "float32")
                else f"_{self.local_spec.compute_dtype}")
        if B is None:
            B = self.num_batches
        if block_rounds:
            return f"block{prec}_r{int(block_rounds)}_b{int(B)}"
        return f"round{prec}_b{int(B)}"

    def _goodput_wait(self, metrics) -> float:
        """Block until this round's device outputs are ready and return the
        wait — the device-compute backpressure the driver pays. Only called
        on telemetry paths that were about to sync on the same arrays
        anyway (emit floats them / drain np.asarray's them), so the off
        path stays bit-identical and sync-free."""
        t0 = time.perf_counter()
        with self.tracer.span("fetch"):
            try:
                jax.block_until_ready(metrics)
            except Exception:  # noqa: BLE001 — non-array metrics: nothing to wait
                pass
        return time.perf_counter() - t0

    def _goodput_extra(self, wall_s, spans, *, pipelined: bool = False,
                       compute_wait_s: float = 0.0, pack_extra=None,
                       block_rounds: int | None = None) -> dict:
        """The ``goodput`` block one round record carries (obs/goodput.py):
        exclusive duty-cycle buckets of this round's wall plus FLOPs/s and
        MFU when the dispatched variant's cost analysis is cached. {} when
        the wall was not measured."""
        if wall_s is None:
            return {}
        B = ((pack_extra or {}).get("pack") or {}).get("bucket_B")
        variant = self._variant_name(B=B, block_rounds=block_rounds)
        buckets = _goodput.buckets_from_spans(
            wall_s, spans, pipelined=pipelined,
            compute_wait_s=compute_wait_s)
        return {"goodput": _goodput.round_goodput(
            wall_s, buckets, variant=variant,
            cost_rounds=block_rounds or 1,
            n_devices=(self.mesh.size if self.mesh is not None else 1))}

    def _goodput_interval(self) -> float:
        """Per-round wall in pipelined mode: time since the previous drain
        (one drain per dispatch in steady state, so inter-drain time IS
        the per-round wall — docs/PERFORMANCE.md §Round economics)."""
        now = time.perf_counter()
        prev = getattr(self, "_gp_prev_drain_t", None)
        self._gp_prev_drain_t = now
        # None (no goodput block) when the interval base is missing — the
        # pipelined drivers seed the stamp at loop entry
        return (now - prev) if prev is not None else None

    # ------------------------------------------------------------------ train
    def _dispatch_round(self, round_idx: int, ids, cb):
        """Advance the rng chain and dispatch one round program — the ONE
        jit call site both the synchronous driver (run_round) and the
        pipelined drivers share, so their rng chains cannot diverge.
        The ``round`` span is the dispatch: trace, lower and
        compile-or-load on a variant's first call (attributed to that
        variant), the enqueue after. Returns the round's metrics as device
        arrays (no sync)."""
        B = jax.tree.leaves(cb.mask)[0].shape[1]
        with self.tracer.span("round", round=round_idx), \
                _perf.attribute_compiles(self._variant_name(B=B)):
            self.rng, rk = jax.random.split(self.rng)
            self.net, self.server_opt_state, metrics = self.round_fn(
                rk, self.net, self.server_opt_state, cb,
                jnp.int32(round_idx), jnp.asarray(ids, jnp.int32),
            )
        _perf.record_agg_bytes(self._state_placement, self._agg_bytes_round)
        return metrics

    def run_round(self, round_idx: int):
        if self.telemetry is not None:
            t_wall = time.perf_counter()
            spans_before = dict(self.tracer.rounds[-1])
            if self.telemetry.tracer is not None:
                self.telemetry.tracer.begin_round(round_idx)
        with self.tracer.span("pack", round=round_idx):
            ids = self._sampled_ids(round_idx)
            cb = self._pack_round(round_idx)
            # issue the H2D here, where a span can see it, and not
            # implicitly inside the dispatch (bit-identical: see
            # _place_round_batch; on a mesh _pack_round has placed already)
            with self.tracer.span("place", round=round_idx):
                cb = self._place_round_batch(cb)
        metrics = self._dispatch_round(round_idx, ids, cb)
        metrics = self._drain_quarantine(metrics, round_idx, ids)
        if self.telemetry is not None:
            # floating the metrics syncs on the round's outputs — a cost the
            # caller opted into by passing telemetry; the off path returns
            # the device arrays untouched (no sync, dispatch still overlaps)
            wait = self._goodput_wait(metrics)
            spans = self._span_delta(spans_before)
            pack_extra = self._pack_extra(round_idx)
            self.telemetry.emit_round(
                round_idx, clients=np.asarray(ids).tolist(),
                spans=spans,
                metrics={k: float(v) for k, v in metrics.items()},
                agg=self._agg_record,
                **self._goodput_extra(
                    time.perf_counter() - t_wall, spans,
                    compute_wait_s=wait, pack_extra=pack_extra),
                **pack_extra,
                **self._quarantine_extra(round_idx),
                **self._privacy_extra())
            if self.telemetry.tracer is not None:
                # close the trace envelope HERE: left open it would absorb
                # inter-round idle (timing loops, the post-run gap to
                # close()) and misreport per-round wall-clock. train()'s
                # eval spans still reach the histograms/event record; only
                # the single-rank trace view scopes to the round program.
                self.telemetry.tracer.finish_round()
        return metrics

    # --------------------------------------------------------------- pipeline
    def _place_round_batch(self, batch):
        """Issue the host->device transfer for a packed round batch NOW (in
        run_round's ``place`` span, or on the prefetch thread) instead of
        implicitly at jit dispatch. Leaves
        already on device (the mesh packer shards in _pack_round) pass
        through. Transfers are exact, so a placed batch is bit-identical to
        letting dispatch transfer it."""
        leaves, treedef = jax.tree.flatten(batch)
        return jax.tree.unflatten(
            treedef,
            [v if isinstance(v, jax.Array) else jax.device_put(v)
             for v in leaves])

    def _pack_round_placed(self, round_idx: int):
        """Prefetch producer (runs on the packer thread): sample ids, pack
        the round batch into FRESH host buffers (every pack path allocates
        anew — donation-safe while earlier rounds are still in flight), and
        issue its device_put. Returns (ids, device batch, span dict)."""
        # the packer thread must not touch self.tracer (its per-round dict
        # belongs to the driver thread) — spans go straight to the
        # fed_span_seconds / fed_h2d_seconds histograms and ride the round
        # record at drain time; in a profiler trace the bare annotations
        # show the packer thread as a host line of its own
        t0 = time.perf_counter()
        with TraceAnnotation("fed:prefetch_pack", round=round_idx):
            ids = self._sampled_ids(round_idx)
            cb = self._pack_round(round_idx)
        t1 = time.perf_counter()
        with TraceAnnotation("fed:h2d", round=round_idx):
            cb = self._place_round_batch(cb)
        h2d = time.perf_counter() - t1
        _perf.record_span("prefetch_pack", t1 - t0)
        _perf.record_h2d(h2d)
        return ids, cb, {"prefetch_pack": t1 - t0, "h2d": h2d}

    def _drain_round_entry(self, round_idx: int, entry):
        """Materialize one in-flight round's outputs (this is the only
        sync, and it happens drain_lag rounds behind dispatch): quarantine
        codes into the ledger, metrics to host, telemetry record flushed —
        all in dispatch order, so ledgers and event logs are bit-identical
        to the synchronous driver's."""
        ids, spans, pipeline, metrics = entry
        if self.telemetry is not None:
            # the drain is the pipeline's one sync point: the wait here IS
            # the device-compute backpressure this round cost the driver
            # (goodput's compute bucket); inter-drain time is the per-round
            # wall. Off path syncs implicitly at np.asarray — unchanged.
            wait = self._goodput_wait(metrics)
            wall = self._goodput_interval()
        metrics = self._drain_quarantine(metrics, round_idx, ids)
        with self.tracer.span("fetch", round=round_idx):
            host = {k: np.asarray(v) for k, v in metrics.items()}
        if self.telemetry is not None:
            pack_extra = self._pack_extra(round_idx)
            self.telemetry.emit_round(
                round_idx, clients=np.asarray(ids).tolist(),
                spans=spans, pipeline=pipeline,
                metrics={k: float(v) for k, v in host.items()},
                agg=self._agg_record,
                **self._goodput_extra(
                    wall, spans, pipelined=True, compute_wait_s=wait,
                    pack_extra=pack_extra),
                **pack_extra,
                **self._quarantine_extra(round_idx),
                **self._privacy_extra())
        return round_idx, host

    def _warn_tracer_unsupported(self):
        """Pipelined drivers overlap rounds, which the sequential per-round
        distributed-trace model (obs/tracing.py begin_round..finish_round)
        cannot represent — so they emit NO per-round traces. Say so loudly
        once instead of silently exporting an empty trace.json."""
        if (self.telemetry is not None and self.telemetry.tracer is not None
                and not getattr(self, "_tracer_warned", False)):
            self._tracer_warned = True
            log.warning(
                "pipelined drivers do not emit per-round distributed "
                "traces (rounds overlap; the trace model is sequential) — "
                "round records carry prefetch/h2d/stall spans instead; "
                "use the synchronous driver (prefetch=0) for trace runs")

    def run_pipelined(self, start_round: int, num_rounds: int):
        """Per-round dispatch through the prefetch pipeline: round r+1's
        pack + H2D overlap round r's execution, and the metrics drain
        trails ``drain_lag`` rounds behind so async dispatch stays that
        deep. Bit-identical to the run_round loop (same packs, same rng
        chain, same ledger order — test-enforced). Returns the drained
        [(round_idx, host metrics dict)] in round order."""
        self._warn_tracer_unsupported()
        depth = max(1, self.prefetch)
        pf = Prefetcher(self._pack_round_placed,
                        range(start_round, start_round + num_rounds),
                        depth=depth, on_event=self._pipe_on_event)
        ring = InflightRing(self.drain_lag, self._drain_round_entry,
                            on_event=self._pipe_on_event)
        self._gp_prev_drain_t = time.perf_counter()
        out = []
        try:
            for r in range(start_round, start_round + num_rounds):
                (ids, cb, spans), stall = pf.get(r)
                metrics = self._dispatch_round(r, ids, cb)
                spans = dict(spans, prefetch_stall=stall)
                out.extend(ring.push(
                    r, (ids, spans, {"depth": len(ring) + 1}, metrics)))
            out.extend(ring.drain_all())
        finally:
            pf.close()
        return out

    def _train_pipelined(self, rounds: int):
        """train() body with the pipeline armed: same eval cadence and
        history records as the synchronous loop; an eval round drains the
        ring (its own metrics must be host-side), which re-syncs — set
        frequency_of_the_test high for pure-throughput runs."""
        self._warn_tracer_unsupported()
        cfg = self.cfg
        depth = max(1, self.prefetch)
        pf = Prefetcher(self._pack_round_placed, range(rounds), depth=depth,
                        on_event=self._pipe_on_event)
        ring = InflightRing(self.drain_lag, self._drain_round_entry,
                            on_event=self._pipe_on_event)
        self._gp_prev_drain_t = time.perf_counter()
        pending: dict[int, dict] = {}
        try:
            for r in range(rounds):
                t0 = time.perf_counter()
                (ids, cb, spans), stall = pf.get(r)
                metrics = self._dispatch_round(r, ids, cb)
                spans = dict(spans, prefetch_stall=stall)
                for k, m in ring.push(
                        r, (ids, spans, {"depth": len(ring) + 1}, metrics)):
                    pending[k] = m
                if (r % cfg.frequency_of_the_test == 0) or (r == rounds - 1):
                    for k, m in ring.drain_all():
                        pending[k] = m
                    rec = self.eval_record(r, pending[r])
                    rec["round_time"] = time.perf_counter() - t0
                    self.history.append(rec)
                    log.info("round %d: %s", r, rec)
                    if self.telemetry is not None:
                        self.telemetry.emit_eval(r, rec)
                pending = {k: v for k, v in pending.items() if k >= r}
                self.tracer.next_round()
            ring.drain_all()
        finally:
            pf.close()
        return self.net

    def _eval_on_all_clients(self) -> bool:
        mode = getattr(self.cfg, "local_test_on_all_clients", "auto")
        if mode == "auto":
            # natural per-client test splits AND no validation-subset cap:
            # when eval_max_samples is configured (the reference's 10k
            # stackoverflow validation set, FedAVGAggregator.py:99-107) the
            # capped global eval wins — iterating every client's full split
            # is exactly what that cap exists to avoid at 342k-client scale
            return (self.data.test_idx_map is not None
                    and self.cfg.eval_max_samples is None)
        if mode in ("on", "off"):
            return mode == "on"
        raise ValueError(f"local_test_on_all_clients={mode!r} "
                         "(expected 'auto', 'on' or 'off')")

    def eval_record(self, round_idx: int, metrics) -> dict:
        """Assemble one eval-round history record for the current model:
        in-round training metrics plus either the per-client aggregate
        (reference _local_test_on_all_clients, fedavg_api.py:117-180 —
        the global model scored on every client's OWN train and test split,
        sum(num_correct)/sum(num_samples) weighting) or the global test-set
        eval. Shared by train() and the CLI round loop so the metrics
        schema cannot drift between them."""
        n = float(max(float(metrics.get("count", 1.0)), 1.0))
        rec = {
            "round": round_idx,
            "train_loss": float(metrics.get("loss_sum", 0.0)) / n,
            "train_acc": float(metrics.get("correct", 0.0)) / n,
        }
        with self.tracer.span("eval"):
            if self._eval_on_all_clients():
                _, tr = self.evaluate_per_client("train")
                _, te = self.evaluate_per_client("test")
                rec.update(
                    train_all_loss=float(tr["loss"]),
                    train_all_acc=float(tr["acc"]),
                    test_loss=float(te["loss"]), test_acc=float(te["acc"]),
                )
            else:
                ev = self.evaluate()
                rec.update(test_loss=float(ev["loss"]),
                           test_acc=float(ev["acc"]))
        return rec

    def train(self, num_rounds: int | None = None):
        cfg = self.cfg
        rounds = num_rounds or cfg.comm_round
        if self.telemetry is not None:
            from fedml_tpu.data import dataset_source

            self.telemetry.run_header(dataclasses.asdict(cfg),
                                      engine="standalone",
                                      dataset_source=dataset_source(
                                          self.data))
        if self.prefetch and rounds > 0:
            return self._train_pipelined(rounds)
        for r in range(rounds):
            t0 = time.perf_counter()
            metrics = self.run_round(r)
            if (r % cfg.frequency_of_the_test == 0) or (r == rounds - 1):
                rec = self.eval_record(r, metrics)
                rec["round_time"] = time.perf_counter() - t0
                self.history.append(rec)
                log.info("round %d: %s", r, rec)
                if self.telemetry is not None:
                    self.telemetry.emit_eval(r, rec)
            self.tracer.next_round()
        return self.net

    # ------------------------------------------------------------------ async
    def run_async(self, num_updates: int, buffer_k: int,
                  staleness="constant", staleness_bound: int | None = None,
                  deadline_s: float | None = None,
                  capacity: int | None = None, chaos_plan=None,
                  adversary_plan=None, base_duration_s: float = 1.0):
        """Buffered-async rounds on a virtual clock (docs/ROBUSTNESS.md
        §Asynchronous buffered rounds; core/async_buffer.py): worker slots
        train continuously against possibly-stale globals, the server
        aggregates every ``buffer_k`` sanitized arrivals with
        staleness-discounted weights through this engine's own gate/
        estimator/server_update composition, and admission control
        rejects-and-requeues updates staler than ``staleness_bound``. A
        chaos FaultPlan's straggle/crash rules drive the virtual durations,
        so async-vs-sync wall-clock claims are deterministic and replay
        bit-for-bit. ``buffer_k = cohort`` with ``staleness_bound = 0`` is
        bitwise-identical to the run_round loop — model bits AND quarantine
        ledger (test-enforced).

        Returns the runner (``.history`` per-update records, ``.stats()``
        wall-clock/staleness/shed summary); the engine's net/opt/rng/
        quarantine advance exactly as if the updates had run
        synchronously."""
        if self._source is not None:
            # the virtual-clock runner packs through pack_clients (index
            # maps) — refuse HERE instead of AttributeError-ing deep in
            # its event loop after warmup time is spent
            raise ValueError(
                "run_async is not wired for streamed ClientDataSources "
                "yet — materialize the dataset for the async simulator")
        from fedml_tpu.core.async_buffer import VirtualClockAsyncRunner

        runner = VirtualClockAsyncRunner(
            self, buffer_k, staleness=staleness,
            staleness_bound=staleness_bound, deadline_s=deadline_s,
            capacity=capacity, chaos_plan=chaos_plan,
            adversary_plan=adversary_plan, base_duration_s=base_duration_s)
        runner.run(num_updates)
        return runner

    # ------------------------------------------------------------------ state
    def load_state(self, net, server_opt_state, rng):
        """Install restored state, re-placing it for the engine's mesh (a
        checkpoint restored host-side lands on one device; the round program
        expects replicated layout when a mesh is active — or the Megatron
        TP layout on a ('clients','model') mesh, which a blanket
        replicated placement would silently discard)."""
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            put = lambda t: jax.tree.map(lambda v: jax.device_put(v, rep), t)
            if self._tp:
                from fedml_tpu.parallel.tensor_parallel import shard_params

                params, self.tp_specs = shard_params(net.params, self.mesh)
                net = net._replace(params=params, extra=put(net.extra))
            elif self._sharded:
                # checkpoints are saved gathered (core/checkpoint.py's
                # gather-on-save layout) — re-partition per the rule table
                # so resume lands in exactly the round program's layout
                net = self.partitioner.shard(net)
                server_opt_state = self.partitioner.shard(server_opt_state)
                rng = put(rng)
                self.net, self.server_opt_state, self.rng = (
                    net, server_opt_state, rng)
                return
            else:
                net = put(net)
            server_opt_state, rng = put(server_opt_state), put(rng)
        self.net, self.server_opt_state, self.rng = net, server_opt_state, rng

    # ------------------------------------------------------------------ eval
    def evaluate_per_client(self, split: str = "test", chunk: int = 64,
                            max_clients: int | None = None):
        """Reference-fidelity eval: iterate EVERY client's own split
        (_local_test_on_all_clients, fedavg_api.py:117-180), vectorized —
        clients are packed in chunks of ``chunk`` and evaluated as one
        vmapped masked batch block per chunk.

        Returns (per_client list of {client, loss, acc, count}, aggregate
        dict weighted by sample counts — the reference's Train/Acc /
        Test/Acc numbers).
        """
        import dataclasses as _dc

        if split == "test" and self.data.test_idx_map is not None:
            view = _dc.replace(self.data, train_x=self.data.test_x,
                               train_y=self.data.test_y,
                               train_idx_map=self.data.test_idx_map)
        elif split == "test":
            # no per-client test partition: every client shares the global
            # test set (the cross-silo datasets' convention)
            view = None
        else:
            view = self.data

        if view is None:
            ev = self.evaluate()
            agg = {"loss": float(ev["loss"]), "acc": float(ev["acc"]),
                   "count": float(ev["count"])}
            return [], agg

        ids = np.arange(view.num_clients if max_clients is None
                        else min(max_clients, view.num_clients))
        if self.cfg.ci:
            ids = ids[:1]  # --ci truncation (FedAVGAggregator.py:126-131)

        if not hasattr(self, "_chunk_eval"):

            @jax.jit
            def chunk_eval(net, x, y, mask):
                # [K, B, bs, ...] -> per-client metric sums
                def per_client(xk, yk, mk):
                    def body(acc, b):
                        xb, yb, mb = b
                        metr = self.task.eval_batch(net.params, net.extra, xb, yb, mb)
                        return {k: acc[k] + metr[k] for k in acc}, None

                    init = {"loss_sum": jnp.zeros(()), "correct": jnp.zeros(()),
                            "count": jnp.zeros(())}
                    acc, _ = lax.scan(body, init, (xk, yk, mk))
                    return acc

                return jax.vmap(per_client)(x, y, mask)

            self._chunk_eval = chunk_eval
        chunk_eval = self._chunk_eval

        per_client: list[dict] = []
        tot = {"loss_sum": 0.0, "correct": 0.0, "count": 0.0}
        for s in range(0, len(ids), chunk):
            cids = ids[s : s + chunk]
            cb = pack_clients(view, cids, self.cfg.eval_batch_size,
                              seed=self.cfg.seed, round_idx=0)
            m = jax.device_get(chunk_eval(self.net, jnp.asarray(cb.x),
                                          jnp.asarray(cb.y), jnp.asarray(cb.mask)))
            for i, cid in enumerate(cids):
                n = float(max(m["count"][i], 1.0))
                per_client.append({
                    "client": int(cid),
                    "loss": float(m["loss_sum"][i]) / n,
                    "acc": float(m["correct"][i]) / n,
                    "count": float(m["count"][i]),
                })
                for k in tot:
                    tot[k] += float(m[k][i])
        n = max(tot["count"], 1.0)
        agg = {"loss": tot["loss_sum"] / n, "acc": tot["correct"] / n, "count": tot["count"]}
        return per_client, agg

    def evaluate(self):
        """Global test-set eval (the reference evaluates per client over all
        clients, fedavg_api.py:117-180; on a global-shared test set the two
        coincide up to weighting)."""
        # 'fresh' only forces a rebuild when a subset is actually drawn —
        # uncapped eval would rebuild+re-upload an identical test set
        fresh = (self.cfg.eval_subset_mode == "fresh"
                 and self.cfg.eval_max_samples is not None
                 and len(self.data.test_x) > self.cfg.eval_max_samples)
        self._eval_calls = getattr(self, "_eval_calls", 0) + 1
        if self._test_cache is None or fresh:
            tx, ty = eval_subset(self.data.test_x, self.data.test_y,
                                 self.cfg, self._eval_calls)
            n = len(tx)
            if self.cfg.ci:
                n = min(n, 512)  # --ci truncation analogue (FedAVGAggregator.py:126-131)
            self._test_cache = tuple(
                jnp.asarray(a)
                for a in batch_global(tx[:n], ty[:n], self.cfg.eval_batch_size)
            )
        xb, yb, mb = self._test_cache
        return self.eval_fn(self.net, xb, yb, mb)
