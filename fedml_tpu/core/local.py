"""Local client update + evaluation engine (L2).

Replaces the reference's ModelTrainer ABC and its concrete local-SGD loops
(fedml_core/trainer/model_trainer.py:4-37;
fedml_api/distributed/fedavg/MyModelTrainer.py:19-49 — epochs x batches of
fwd/bwd/step on one device). Here the whole local fit is a pure function

    local_update(rng, global_net, x, y, mask) -> (new_net, metrics)

built from a Task (model-specific loss/predict) and an optax optimizer, with
the epoch/batch loops as lax.scan so XLA compiles ONE program per round. The
function is vmap-able over a leading client axis and shard_map-able over a
'clients' mesh axis — that composition is the entire distributed runtime.

Design notes (TPU semantics):
- Padded batches (mask all zero) are exact no-ops: the parameter/opt-state
  update is lax.select'ed out, so ragged client sizes cost no correctness for
  ANY optimizer, not just SGD.
- NetState carries {'params', 'extra'}: extra holds non-gradient collections
  (BatchNorm running stats, etc.). The reference averages the full state_dict
  including BN buffers (FedAVGAggregator.py:72-80), so both parts aggregate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax


class NetState(NamedTuple):
    """Model variables split into trainable params and mutable extras."""

    params: Any
    extra: Any  # dict of non-param collections (batch_stats, ...); may be {}


class Task(NamedTuple):
    """Model+objective bundle. The fedml_tpu analogue of a concrete
    ModelTrainer subclass (my_model_trainer_classification.py etc.)."""

    init: Callable  # (rng, x_sample) -> NetState
    # (params, extra, x, y, mask, rng, train) -> (loss, new_extra, metrics)
    loss: Callable
    # (params, extra, x) -> model outputs (eval mode)
    predict: Callable
    # (params, extra, x, y, mask) -> metrics dict with 'loss_sum','correct','count'
    eval_batch: Callable
    # (prefix, sink) or None: the training metrics whose name starts with
    # prefix are no round record's; a block program's caller hands them to
    # sink({name less prefix: device array}) unread (``handing_off``)
    handoff: tuple | None = None


class _HandingOff:
    """A block program whose returned metrics leave what the task declared
    (``Task.handoff``) with the task's sink, as device arrays: nothing
    waits on the device here."""

    def __init__(self, program, prefix: str, sink: Callable):
        self._program, self._prefix, self._sink = program, prefix, sink
        self.lower = program.lower
        # the jit's own: what it wraps is the traced function (or the store's)
        self.__wrapped__ = getattr(program, "__wrapped__", program)

    def __call__(self, *args):
        *state, ms = self._program(*args)
        n = len(self._prefix)
        handed = {k[n:]: v for k, v in ms.items()
                  if k.startswith(self._prefix)}
        if handed:
            self._sink(handed)
            ms = {k: v for k, v in ms.items()
                  if not k.startswith(self._prefix)}
        return (*state, ms)


def handing_off(program, task):
    """``program`` (a jitted block: ``(...) -> (*state, metrics)``) as it
    is, or where ``task`` declares a hand-off, wrapped to make it."""
    handoff = getattr(task, "handoff", None)
    return program if handoff is None else _HandingOff(program, *handoff)


@dataclasses.dataclass(frozen=True)
class LocalSpec:
    """Static configuration of a client's local fit."""

    optimizer: optax.GradientTransformation
    epochs: int = 1
    prox_mu: float = 0.0  # FedProx proximal coefficient (0 = plain FedAvg)
    # rematerialize the per-batch forward under autodiff (jax.checkpoint):
    # activations are recomputed in the backward pass instead of living in
    # HBM across it — the standard TPU memory/FLOPs trade for deep models
    # or long sequences. Numerics are identical (test-enforced).
    remat: bool = False
    # client-compute precision policy (docs/PERFORMANCE.md §Mixed
    # precision): 'bf16' casts params/extras/float inputs to bfloat16 for
    # the per-batch forward+backward (MXU-rate matmuls on TPU) while the
    # f32 MASTER weights stay the scan carry — gradients flow back through
    # the cast as f32 cotangents, the optimizer step / aggregation /
    # server update stay f32, and no loss scaling is needed (bfloat16
    # keeps f32's exponent range). 'f32' (default) traces NO casts: the
    # round program is bit-identical to the pre-policy build
    # (test-enforced).
    compute_dtype: str = "f32"


def _vma_of(tree) -> frozenset:
    """Union of shard_map varying-manual-axes across a pytree's leaves."""
    out: frozenset = frozenset()
    for v in jax.tree.leaves(tree):
        out = out | getattr(jax.typeof(v), "vma", frozenset())
    return out


def _match_vma(tree, target_vma: frozenset):
    """Mark invariant leaves device-varying over ``target_vma`` axes.

    Opt states may mix param-derived leaves (already varying inside shard_map)
    with freshly-created counters (e.g. the schedule step in
    ScaleByScheduleState) that are invariant; the per-client masked select in
    batch_step makes every carry leaf varying, so invariant ones must be cast
    up front or lax.scan rejects the carry."""

    def f(v):
        missing = target_vma - getattr(jax.typeof(v), "vma", frozenset())
        return lax.pcast(v, tuple(missing), to="varying") if missing else v

    return jax.tree.map(f, tree)


# accepted spellings of the LocalSpec precision policy -> compute dtype
# (None = no casts traced at all; the policy table of docs/PERFORMANCE.md
# §Mixed precision)
COMPUTE_DTYPES = {"f32": None, "float32": None,
                  "bf16": "bfloat16", "bfloat16": "bfloat16"}


def _cast_floats(tree, dtype):
    """Float leaves -> ``dtype``; everything else untouched (labels,
    masks, integer counters keep their dtypes)."""
    return jax.tree.map(
        lambda v: v.astype(dtype)
        if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating) else v, tree)


def make_local_update(task: Task, spec: LocalSpec):
    """Build the pure local-fit function for one client.

    Returned fn:
        local_update(rng, global_net: NetState, x[B,bs,...], y[B,bs,...],
                     mask[B,bs]) -> (NetState, metrics)

    metrics: dict of scalars averaged/summed over real samples only.
    The fn is vma-aware: when traced inside shard_map (varying params) it
    casts the opt-state carry to match, so it needs no axis plumbing.

    ``spec.compute_dtype='bf16'`` arms the mixed-precision policy: the
    loss/grad pass runs on bf16 casts of the f32 master params (and float
    inputs/extras), grads land f32 through the cast's transpose, and the
    optimizer/carry/upload stay f32 — see docs/PERFORMANCE.md §Mixed
    precision. The default traces no casts (bit-identity contract).
    """
    optimizer = spec.optimizer
    if spec.compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(
            f"compute_dtype={spec.compute_dtype!r} (one of "
            f"{sorted(COMPUTE_DTYPES)})")
    cdt = COMPUTE_DTYPES[spec.compute_dtype]
    cdt = jnp.dtype(cdt) if cdt is not None else None

    def batch_step(carry, batch):
        params, extra, opt_state, global_params, rng = carry
        x, y, m = batch
        rng, sub = jax.random.split(rng)

        def total_loss(p):
            if cdt is None:
                loss, new_extra, metr = task.loss(p, extra, x, y, m, sub,
                                                  True)
            else:
                # bf16 compute, f32 masters: the casts sit INSIDE the
                # grad closure so autodiff transposes them back to f32
                # cotangents; loss/metrics/extras re-land f32 so the scan
                # carry (and the uploaded NetState) never changes dtype.
                # grad-scale-free by design — bf16 keeps f32's exponent
                # range, so underflow scaling (the fp16 ritual) is moot.
                loss, new_extra, metr = task.loss(
                    _cast_floats(p, cdt), _cast_floats(extra, cdt),
                    _cast_floats(x, cdt), y, m, sub, True)
                loss = loss.astype(jnp.float32)
                new_extra = jax.tree.map(
                    lambda nv, ov: nv.astype(jnp.asarray(ov).dtype),
                    new_extra, extra)
                metr = _cast_floats(metr, jnp.float32)
            if spec.prox_mu > 0.0:
                # FedProx: + mu/2 * ||w - w_global||^2. The reference's
                # distributed FedProx trainer omits this term (its trainer is
                # byte-identical to FedAvg's — see SURVEY.md §2.2); we
                # implement the algorithm as published.
                sq = jax.tree.map(
                    lambda a, b: jnp.sum(jnp.square(a - b)), p, global_params
                )
                loss = loss + 0.5 * spec.prox_mu * sum(jax.tree.leaves(sq))
            return loss, (new_extra, metr)

        if spec.remat:
            # prevent_cse=False: inside lax.scan the CSE barriers are
            # unnecessary (per the jax.checkpoint docs) and only cost fusion
            total_loss = jax.checkpoint(total_loss, prevent_cse=False)

        # NOTE sequence-parallel fits need no grad psum here: with the task's
        # loss psum-ed over the seq axis and params entering seq-INVARIANT,
        # shard_map's vma-aware transpose emits the cross-shard psum of the
        # cotangent automatically (pinned by test_fedavg_seq equivalence).
        (loss, (new_extra, metr)), grads = jax.value_and_grad(
            total_loss, has_aux=True
        )(params)
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)

        # Padded (all-masked) batch -> exact no-op for params/opt/extra.
        has_data = jnp.sum(m) > 0
        keep = lambda new, old: jax.tree.map(
            lambda a, b: lax.select(has_data, a, b), new, old
        )
        params = keep(new_params, params)
        opt_state = keep(new_opt_state, opt_state)
        extra = keep(new_extra, extra)
        return (params, extra, opt_state, global_params, rng), metr

    def local_update(rng, global_net: NetState, x, y, mask):
        params, extra = global_net.params, global_net.extra
        opt_state = optimizer.init(params)
        vma = _vma_of(params)
        if vma:
            opt_state = _match_vma(opt_state, vma)

        def run_epoch(carry, _):
            params, extra, opt_state, rng = carry
            rng, sub = jax.random.split(rng)
            (params, extra, opt_state, _, _), metrs = lax.scan(
                batch_step,
                (params, extra, opt_state, global_net.params, sub),
                (x, y, mask),
            )
            return (params, extra, opt_state, rng), metrs

        (params, extra, _, _), metrs = lax.scan(
            run_epoch, (params, extra, opt_state, rng), None, length=spec.epochs
        )
        # metrs leaves: [epochs, B]; return SUMS so they aggregate across
        # clients by addition (weighted means are computed at the server)
        metrics = {
            "loss_sum": jnp.sum(metrs["loss_sum"]),
            "correct": jnp.sum(metrs["correct"]),
            "count": jnp.sum(metrs["count"]),
        }
        # what else a task counts (an expert layer's routing counts,
        # core/tasks.py ``stats``) keeps its own shape past the two loops
        for name in sorted(metrs.keys() - metrics.keys()):
            metrics[name] = jnp.sum(metrs[name], axis=(0, 1))
        return NetState(params, extra), metrics

    return local_update


def make_eval_fn(task: Task):
    """Jitted masked evaluation over a padded global batch set [B, bs, ...].

    The analogue of ModelTrainer.test / the server's
    test_on_server_for_all_clients (FedAVGAggregator.py:109-163), but the
    whole eval set is one scan on device.
    """

    def eval_fn(net: NetState, xb, yb, mb):
        def body(acc, batch):
            x, y, m = batch
            metr = task.eval_batch(net.params, net.extra, x, y, m)
            return {k: acc[k] + metr[k] for k in acc}, None

        init = {"loss_sum": jnp.zeros(()), "correct": jnp.zeros(()), "count": jnp.zeros(())}
        acc, _ = lax.scan(body, init, (xb, yb, mb))
        n = jnp.maximum(acc["count"], 1.0)
        return {"loss": acc["loss_sum"] / n, "acc": acc["correct"] / n, "count": acc["count"]}

    return jax.jit(eval_fn)
