"""Silos folded one after another: ``FedAvgConfig.client_fold="scan"``.

The single-device round body vmaps the local fit over the cohort, which
multiplies weights and gradients by the silos: right for a small model and
many clients, impossible for a model whose weights do not fit beside their
own cohort. Here a round is a ``lax.scan`` over the cohort that carries the
running sum of ``n_k * w_k`` and the summed metrics, divides once and hands
the mean to the engine's own ``_update_from_aggregate``: one silo's weights
and gradients are alive at a time, and the device holds the global model,
the running sum, one silo's copy and its gradient (docs/PERFORMANCE.md
§Folded silos). K local fits run in sequence, so a model that fits vmapped
is faster vmapped.

The fold serves the scanned block on one device (``run_rounds`` with
``device_data=True``) and refuses the rest when the engine is built: a mesh,
host-packed rounds (``run_round``, ``train()``), the robust estimators and
the sanitizing gate (they need the clients' models side by side), the
per-client round statistics and the adversary plan. A ``client_result_hook``
runs on each silo's model inside the scan, with the keys the vmapped round
would hand it.

The sum is taken in the order of the cohort and divided once; the vmapped
round normalises the weights first and contracts them in one ``tensordot``:
the two agree to the order of their sums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.obs import perf_instrument as _perf


def check(api, base: type) -> None:
    """Raise ValueError for what the fold cannot serve. ``base`` is the
    engine class whose round body the fold stands in for: a subclass that
    overrides it would lose its override in silence."""
    overridden = [name for name in ("_round_body", "_aggregate_and_update")
                  if getattr(type(api), name) is not getattr(base, name)]
    cannot = [
        (bool(overridden), f"{type(api).__qualname__}, which overrides "
                           f"{overridden}"),
        (api.mesh is not None, "a mesh (the fold is one device's)"),
        (not api.device_data, "host-packed rounds: pass device_data=True "
                              "and drive run_rounds"),
        (api._needs_stacked, "a robust aggregator or the sanitizing gate "
                             "(they read the clients' models side by side)"),
        (api._emit_stats, "telemetry round_stats (per-client drift needs "
                          "the stacked models)"),
        (api._adversary is not None, "an adversary plan"),
    ]
    for hit, what in cannot:
        if hit:
            raise ValueError(f"client_fold='scan' cannot serve {what}")


class _RefusedRoundFn:
    """Stands where the per-round program would: calling it, or lowering
    it (``warmup(per_round=True)``), says what to do instead."""

    def __call__(self, *_args, **_kwargs):
        raise ValueError(
            "client_fold='scan' folds the silos inside the scanned block: "
            "drive run_rounds (and warmup(per_round=False)), not run_round "
            "or train()")

    lower = __call__


refused_round_fn = _RefusedRoundFn()


def make_step(api, client_keys, gather_rows):
    """``make_step(dev_x, dev_y)`` for ``_build_block_fn``: the step of the
    block's scan over rounds, with the cohort folded."""

    def build(dev_x, dev_y):
        _perf.record_client_fold("scan")

        def fit(net, key, idx_k, mask_k, hook_key):
            x, y = gather_rows(dev_x, dev_y, idx_k, mask_k)
            net_k, metr = api.local_update(key, net, x, y, mask_k)
            if api.client_result_hook is not None:
                net_k = api.client_result_hook(net_k, net, hook_key)
            return net_k, metr

        def step(carry, inp):
            net, opt = carry
            idx_r, mask_r, nsamp_r, ids_r, r, kh, kp = inp
            keys = client_keys(r, ids_r)
            hook_keys = jax.random.split(kh, keys.shape[0])
            weights = api._agg_weights(nsamp_r)

            def one(acc, silo):
                wsum, msum = acc
                key, idx_k, mask_k, w_k, hook_key = silo
                net_k, metr = fit(net, key, idx_k, mask_k, hook_key)
                wsum = jax.tree.map(lambda a, v: a + w_k * v, wsum, net_k)
                return (wsum, jax.tree.map(jnp.add, msum, metr)), None

            _, metr0 = jax.eval_shape(fit, net, keys[0], idx_r[0], mask_r[0],
                                      hook_keys[0])
            zeros = lambda t: jax.tree.map(  # noqa: E731
                lambda v: jnp.zeros(v.shape, v.dtype), t)
            with jax.named_scope("fed_client_fold"):
                (wsum, msum), _ = jax.lax.scan(
                    one, (zeros(net), zeros(metr0)),
                    (keys, idx_r, mask_r, weights, hook_keys))
                total = jnp.maximum(jnp.sum(weights), 1e-12)
                avg = jax.tree.map(lambda v: v / total, wsum)
            net, opt = api._update_from_aggregate(net, avg, opt, kp)
            return (net, opt), msum

        return step

    return build
