"""Standard Task builders for flax modules.

The reference ships one ModelTrainer per task family:
my_model_trainer_classification.py (cross-entropy),
my_model_trainer_nwp.py (next-word prediction with pad masking),
my_model_trainer_tag_prediction.py (multi-label BCE) under
fedml_api/standalone/fedavg/. These builders are the equivalents: they wrap a
flax.linen module (which must accept ``train: bool``) into the pure
(init, loss, predict, eval_batch) bundle consumed by core.local.

Conventions:
- modules may carry 'dropout' rngs and mutable collections (batch_stats);
  both are handled generically.
- x: [bs, ...], y: [bs] int labels (classification) / [bs, seq] int tokens
  (sequence) / [bs, C] multi-hot (tags). mask: [bs] sample-validity.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import optax

from fedml_tpu.core.local import NetState, Task


def _split_variables(variables) -> NetState:
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    return NetState(params, extra)


def _apply_train(module, params, extra, x, rng):
    out = module.apply(
        {"params": params, **extra},
        x,
        train=True,
        mutable=list(extra.keys()),
        rngs={"dropout": rng},
    )
    logits, mutated = out
    new_extra = dict(extra)
    new_extra.update(mutated)
    return logits, new_extra


def _apply_eval(module, params, extra, x):
    return module.apply({"params": params, **extra}, x, train=False)


def _as_float_image(x):
    """Integer pixel blocks (the uint8 fast transfer path — see
    fedml_tpu/data/registry.py uint8_pixels) normalize to f32/255 ON DEVICE;
    float inputs pass through untouched. Trace-time dtype check, zero cost
    under jit."""
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer):
        return jnp.asarray(x, jnp.float32) / 255.0
    return x


def classification_task(module) -> Task:
    """Softmax cross-entropy over integer labels."""

    def init(rng, x_sample):
        p_rng, d_rng = jax.random.split(rng)
        variables = module.init(
            {"params": p_rng, "dropout": d_rng}, _as_float_image(x_sample), train=False
        )
        return _split_variables(variables)

    def loss(params, extra, x, y, mask, rng, train):
        x = _as_float_image(x)
        if train:
            logits, new_extra = _apply_train(module, params, extra, x, rng)
        else:
            logits, new_extra = _apply_eval(module, params, extra, x), extra
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        n = jnp.maximum(jnp.sum(mask), 1.0)
        l = jnp.sum(per_ex * mask) / n
        correct = jnp.sum((jnp.argmax(logits, -1) == y) * mask)
        metrics = {"loss_sum": jnp.sum(per_ex * mask), "correct": correct, "count": jnp.sum(mask)}
        return l, new_extra, metrics

    def predict(params, extra, x):
        return _apply_eval(module, params, extra, _as_float_image(x))

    def eval_batch(params, extra, x, y, mask):
        logits = _apply_eval(module, params, extra, _as_float_image(x))
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return {
            "loss_sum": jnp.sum(per_ex * mask),
            "correct": jnp.sum((jnp.argmax(logits, -1) == y) * mask),
            "count": jnp.sum(mask),
        }

    return Task(init, loss, predict, eval_batch)


def sequence_task(module, pad_id: int = 0, count_pad_in_acc: bool = False,
                  seq_axis: str | None = None,
                  stats: str | None = None,
                  jit_init: bool = False) -> Task:
    """Next-token prediction: module maps tokens [bs, T] -> logits [bs, T, V];
    labels are the inputs shifted by the module itself or provided as y
    [bs, T]. Tokens equal to ``pad_id`` are masked out of loss and accuracy
    (the reference masks PAD in nwp, my_model_trainer_nwp.py).

    seq_axis: sequence-parallel mode — x/y carry this device's sequence
    slice (the module runs ring/Ulysses attention over the axis), so the
    loss normalizer and the metric sums are psum-ed over it: every seq shard
    then holds the identical GLOBAL loss/metrics. No explicit gradient
    collective is needed: differentiating this psum-ed loss w.r.t.
    seq-invariant params makes shard_map's vma-aware transpose insert the
    gradient psum itself (see the NOTE in core/local.py), so the gradient
    equals the unsharded gradient exactly.

    stats: a collection the module sows sums into while it trains (an
    expert layer's routing counts). Each value joins the training metrics
    as ``<stats>_<name>`` and is summed with them over batches and clients;
    the collection is no part of the model's state.

    jit_init: ``init`` as one jitted program, for a model too large to
    initialise one op at a time."""

    def init(rng, x_sample):
        p_rng, d_rng = jax.random.split(rng)

        def make(p_rng, d_rng, x_sample):
            return module.init({"params": p_rng, "dropout": d_rng}, x_sample,
                               train=False)

        variables = dict((jax.jit(make) if jit_init else make)(
            p_rng, d_rng, x_sample))
        variables.pop(stats, None)
        return _split_variables(variables)

    def _train_logits(params, extra, x, rng):
        """(logits, new_extra, sown sums) of a training forward."""
        if stats is None:
            return (*_apply_train(module, params, extra, x, rng), {})
        logits, mutated = module.apply(
            {"params": params, **extra}, x, train=True,
            mutable=[*extra, stats], rngs={"dropout": rng})
        sown = {f"{stats}_{k}": v[-1]
                for k, v in mutated.pop(stats, {}).items()}
        return logits, {**extra, **mutated}, sown

    def _tok_mask(y, mask):
        tm = (y != pad_id).astype(jnp.float32)
        return tm * mask[:, None]

    def _seq_sum(v):
        return jax.lax.psum(v, seq_axis) if seq_axis is not None else v

    def loss(params, extra, x, y, mask, rng, train):
        sown = {}
        if train:
            logits, new_extra, sown = _train_logits(params, extra, x, rng)
        else:
            logits, new_extra = _apply_eval(module, params, extra, x), extra
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        tm = _tok_mask(y, mask)
        n = jnp.maximum(_seq_sum(jnp.sum(tm)), 1.0)
        l = _seq_sum(jnp.sum(per_tok * tm)) / n
        correct = _seq_sum(jnp.sum((jnp.argmax(logits, -1) == y) * tm))
        metrics = {"loss_sum": _seq_sum(jnp.sum(per_tok * tm)),
                   "correct": correct, "count": _seq_sum(jnp.sum(tm)),
                   **sown}
        return l, new_extra, metrics

    def predict(params, extra, x):
        return _apply_eval(module, params, extra, x)

    def eval_batch(params, extra, x, y, mask):
        logits = _apply_eval(module, params, extra, x)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        tm = _tok_mask(y, mask)
        return {
            "loss_sum": jnp.sum(per_tok * tm),
            "correct": jnp.sum((jnp.argmax(logits, -1) == y) * tm),
            "count": jnp.sum(tm),
        }

    return Task(init, loss, predict, eval_batch)


def routed_sequence_task(module) -> Task:
    """``sequence_task`` for a model with an expert layer
    (``models/lfm2_moe.py``): the routing counts it sows into ``moe_stats``
    ride out of the local fit with the loss sums, and the task declares
    them handed off (``Task.handoff``): a block program's caller gives them
    to the ``fed_moe_*`` counters as device arrays
    (``perf_instrument.note_moe_stats``) and they reach no round record."""
    from fedml_tpu.obs import perf_instrument

    task = sequence_task(module, stats="moe_stats", jit_init=True)
    return task._replace(
        handoff=("moe_stats_", perf_instrument.note_moe_stats))


def segmentation_task(
    module,
    ignore_index: int = 255,
    loss_mode: str = "ce",
    focal_gamma: float = 2.0,
    focal_alpha: float = 0.5,
) -> Task:
    """Pixel-wise segmentation: module maps [bs, H, W, C] -> logits
    [bs, H, W, num_classes]; y is [bs, H, W] int labels with ``ignore_index``
    marking void pixels (reference SegmentationLosses, fedseg/utils.py:66-110:
    CrossEntropyLoss(ignore_index=255) and FocalLoss). The focal variant here
    is the standard per-pixel (1-pt)^gamma weighting; the reference applies
    the transform to the batch-mean CE (utils.py:97-110), which collapses to
    a scalar reweighting — per-pixel is the published form.

    Metrics count *valid pixels* (not samples): loss_sum/correct/count are
    summed over non-ignored pixels of non-padded samples, so the engine's
    weighted aggregation stays exact.
    """

    def init(rng, x_sample):
        p_rng, d_rng = jax.random.split(rng)
        variables = module.init(
            {"params": p_rng, "dropout": d_rng}, _as_float_image(x_sample), train=False
        )
        return _split_variables(variables)

    def _pixel_metrics(logits, y, mask):
        valid = (y != ignore_index).astype(jnp.float32) * mask[:, None, None]
        y_safe = jnp.where(y == ignore_index, 0, y)
        per_px = optax.softmax_cross_entropy_with_integer_labels(logits, y_safe)
        if loss_mode == "focal":
            pt = jnp.exp(-per_px)
            per_px = focal_alpha * jnp.power(1.0 - pt, focal_gamma) * per_px
        correct = jnp.sum((jnp.argmax(logits, -1) == y) * valid)
        return per_px, valid, correct

    def loss(params, extra, x, y, mask, rng, train):
        x = _as_float_image(x)
        if train:
            logits, new_extra = _apply_train(module, params, extra, x, rng)
        else:
            logits, new_extra = _apply_eval(module, params, extra, x), extra
        per_px, valid, correct = _pixel_metrics(logits, y, mask)
        n = jnp.maximum(jnp.sum(valid), 1.0)
        l = jnp.sum(per_px * valid) / n
        metrics = {"loss_sum": jnp.sum(per_px * valid), "correct": correct, "count": jnp.sum(valid)}
        return l, new_extra, metrics

    def predict(params, extra, x):
        return _apply_eval(module, params, extra, _as_float_image(x))

    def eval_batch(params, extra, x, y, mask):
        logits = _apply_eval(module, params, extra, _as_float_image(x))
        per_px, valid, correct = _pixel_metrics(logits, y, mask)
        return {"loss_sum": jnp.sum(per_px * valid), "correct": correct, "count": jnp.sum(valid)}

    return Task(init, loss, predict, eval_batch)


def tag_prediction_task(module, threshold: float = 0.5) -> Task:
    """Multi-label (tag) prediction with sigmoid BCE; y is multi-hot [bs, C].
    Accuracy = micro-F1-style exact element accuracy over real samples."""

    def init(rng, x_sample):
        p_rng, d_rng = jax.random.split(rng)
        variables = module.init({"params": p_rng, "dropout": d_rng}, x_sample, train=False)
        return _split_variables(variables)

    def _metrics(logits, y, mask):
        per_ex = jnp.sum(optax.sigmoid_binary_cross_entropy(logits, y), axis=-1)
        pred = (jax.nn.sigmoid(logits) > threshold).astype(y.dtype)
        correct = jnp.sum(jnp.all(pred == y, axis=-1) * mask)
        return per_ex, correct

    def loss(params, extra, x, y, mask, rng, train):
        if train:
            logits, new_extra = _apply_train(module, params, extra, x, rng)
        else:
            logits, new_extra = _apply_eval(module, params, extra, x), extra
        per_ex, correct = _metrics(logits, y, mask)
        n = jnp.maximum(jnp.sum(mask), 1.0)
        l = jnp.sum(per_ex * mask) / n
        metrics = {"loss_sum": jnp.sum(per_ex * mask), "correct": correct, "count": jnp.sum(mask)}
        return l, new_extra, metrics

    def predict(params, extra, x):
        return _apply_eval(module, params, extra, x)

    def eval_batch(params, extra, x, y, mask):
        logits = _apply_eval(module, params, extra, x)
        per_ex, correct = _metrics(logits, y, mask)
        return {"loss_sum": jnp.sum(per_ex * mask), "correct": correct, "count": jnp.sum(mask)}

    return Task(init, loss, predict, eval_batch)


def aux_classification_task(module, aux_weight: float = 0.4) -> Task:
    """Cross-entropy with an auxiliary-head term for modules that return
    ``(logits, logits_aux)`` during training (DARTS derived nets,
    models/darts.NetworkCIFAR): train loss adds ``aux_weight *
    CE(logits_aux)`` when the head is present (reference
    FedNASTrainer.local_train, FedNASTrainer.py:179-183; standard DARTS
    auxiliary weight 0.4). Eval is plain classification on the main head —
    init/predict/eval_batch delegate to classification_task; only the
    train loss differs."""

    base = classification_task(module)

    def loss(params, extra, x, y, mask, rng, train):
        if not train:
            return base.loss(params, extra, x, y, mask, rng, train)
        x = _as_float_image(x)
        out, new_extra = _apply_train(module, params, extra, x, rng)
        logits, logits_aux = out if isinstance(out, tuple) else (out, None)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        # metrics track the MAIN head (the reference logs prec1 of logits)
        n = jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"loss_sum": jnp.sum(per_ex * mask),
                   "correct": jnp.sum((jnp.argmax(logits, -1) == y) * mask),
                   "count": jnp.sum(mask)}
        if logits_aux is not None:
            per_ex = per_ex + aux_weight * \
                optax.softmax_cross_entropy_with_integer_labels(logits_aux, y)
        return jnp.sum(per_ex * mask) / n, new_extra, metrics

    return Task(base.init, loss, base.predict, base.eval_batch)
