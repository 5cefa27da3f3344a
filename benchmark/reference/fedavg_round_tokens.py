"""Plain reference of the federated round for token rows: which clients a
round samples, which sequences each brings and in which order (the streams
of ``fedavg_round.py``, the published behaviour the program reproduces), the
local SGD fit on next-token cross-entropy, the sample-weighted average and
the server's update. ``jax.numpy`` float32 at matmul precision ``highest``.
Imports nothing of the program.

A row is a sequence of token ids and its labels the sequence one token on;
label 0 is padding and counts for nothing. A step's loss is the mean
cross-entropy over the batch's counted tokens. Its gradient is taken
sequence by sequence and summed, so that a model of the cell's size fits
beside its own gradient: exact, because nothing in the model reaches across
sequences (routing is a token's own). A round's loss is the sum over its
steps' counted tokens, at the weights each step started from, over their
count; a client's weight in the average is its number of sequences.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .fedavg_round import (pack_round, sample_cohort,  # noqa: F401
                           static_batches)


def make_step(forward, lr: float, wd: float):
    """jitted SGD step of one client on one batch: (new weights, the batch's
    loss sum, its counted tokens). The weights handed in are donated."""

    def sequence_loss(p, x, y, m):
        logits = forward(p, x[None])[0]
        per_tok = (jax.nn.logsumexp(logits, axis=-1)
                   - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        return jnp.sum(per_tok * (y != 0) * m)

    def step(p, xb, yb, mb):
        with jax.default_matmul_precision("highest"):
            count = jnp.sum((yb != 0) * mb[:, None])

            def one(acc, row):
                total, g = acc
                ls, gs = jax.value_and_grad(sequence_loss)(p, *row)
                return (total + ls, jax.tree.map(jnp.add, g, gs)), None

            (total, g), _ = jax.lax.scan(
                one, (jnp.zeros(()), jax.tree.map(jnp.zeros_like, p)),
                (xb, yb, mb))
            n = jnp.maximum(count, 1.0)
            new = jax.tree.map(
                lambda w, gw: jnp.where(count > 0,
                                        w - lr * (gw / n + wd * w), w), p, g)
            return new, total, count

    return jax.jit(step, donate_argnums=(0,))


def run_rounds(forward, params, data, fed: dict, seed: int, rounds: int, *,
               client_block: int, pack=pack_round):
    """Follow ``rounds`` federated rounds from ``params``. ``data`` is
    (train_x, train_y, idx_map) on the host; ``fed`` holds the FedAvg
    settings of the configuration with the cell's cohort. Clients are fitted
    one after another whatever ``client_block`` says. Returns the per-round
    mean losses (over counted tokens), the per-round counted tokens and the
    global models after each round (host numpy trees)."""
    del client_block
    train_x, train_y, idx_map = data
    if fed.get("epochs", 1) != 1 or fed.get("momentum", 0.0):
        raise ValueError("the reference follows one epoch of plain SGD")
    bs = int(fed["batch_size"])
    B = static_batches(idx_map, bs, fed.get("max_batches"))
    step = make_step(forward, float(fed["lr"]), float(fed.get("wd", 0.0)))
    fold = jax.jit(lambda acc, p, n: jax.tree.map(
        lambda a, w: a + n * w, acc, p), donate_argnums=(0,))
    losses, counts, models = [], [], []
    for r in range(rounds):
        ids = sample_cohort(seed, r, int(fed["client_num_in_total"]),
                            int(fed["client_num_per_round"]))
        idx, mask, nsamp = pack(idx_map, ids, bs, B, seed, r)
        wsum = jax.tree.map(jnp.zeros_like, params)
        loss = count = 0.0
        for k in range(len(ids)):
            p = jax.tree.map(jnp.copy, params)
            for b in range(B):
                rows = idx[k, b]
                p, ls, n = step(p, jnp.asarray(train_x[rows], jnp.int32),
                                jnp.asarray(train_y[rows], jnp.int32),
                                jnp.asarray(mask[k, b]))
                loss, count = loss + float(ls), count + float(n)
            wsum = fold(wsum, p, float(nsamp[k]))
        total = float(nsamp.sum())
        params = jax.tree.map(lambda v: v / total, wsum)
        losses.append(loss / count)
        counts.append(count)
        models.append(jax.tree.map(np.asarray, params))
    return losses, counts, models
