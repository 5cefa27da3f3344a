"""Plain reference of the federated round itself: which clients a round
samples, which rows each brings and in which order, the local SGD fit, the
sample-weighted average and the server's update. numpy for the bookkeeping,
``jax.numpy`` float32 at matmul precision ``highest`` for the arithmetic.
Imports nothing of the program; the seeded streams below are the published
behaviour that the program has to reproduce:

- cohort of round r: ``numpy.random.RandomState(seed * 1_000_003 + r)
  .choice(N, K, replace=False)``, sorted; everyone when K == N;
- client c's rows in round r: its row list shuffled by Fisher-Yates from the
  back with splitmix64 draws seeded by
  ``((seed * 7919 + r + 1) * GOLDEN + c + 1) mod 2**64``, cut to
  ``max_batches * batch_size`` rows, laid into batches in that order;
- local fit: one epoch of SGD over the batches that hold a row, loss the
  mean cross-entropy over the rows of the batch, weight decay added to the
  gradient; aggregate: mean of the clients' weights by their row counts;
  server: takes the aggregate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_M64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15


def sample_cohort(seed: int, round_idx: int, total: int, per_round: int):
    if total == per_round:
        return np.arange(total, dtype=np.int64)
    rs = np.random.RandomState(seed * 1_000_003 + round_idx)
    return np.sort(rs.choice(total, per_round, replace=False))


def _splitmix(z: int) -> int:
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


def shuffled_rows(rows, seed: int, round_idx: int, client: int, cap: int):
    rows = list(rows)
    s = (((seed * 7919 + round_idx + 1) & _M64) * _GOLDEN + client + 1) & _M64
    for t, i in enumerate(range(len(rows) - 1, 0, -1), start=1):
        j = _splitmix((s + t * _GOLDEN) & _M64) % (i + 1)
        rows[i], rows[j] = rows[j], rows[i]
    return rows[:cap]


def pack_round(idx_map, ids, batch_size, num_batches, seed, round_idx):
    """(idx [K, B, bs] int32, mask [K, B, bs] f32, nsamp [K] f32)."""
    cap = num_batches * batch_size
    idx = np.zeros((len(ids), cap), np.int32)
    mask = np.zeros((len(ids), cap), np.float32)
    for k, c in enumerate(ids):
        rows = shuffled_rows(idx_map[int(c)], seed, round_idx, int(c), cap)
        idx[k, :len(rows)] = rows
        mask[k, :len(rows)] = 1.0
    shape = (len(ids), num_batches, batch_size)
    return idx.reshape(shape), mask.reshape(shape), mask.sum(axis=1)


def static_batches(idx_map, batch_size, max_batches):
    """Batch slots a client is given: enough for the largest client of the
    population, capped at ``max_batches``."""
    need = -(-max(len(v) for v in idx_map.values()) // batch_size)
    return min(max_batches or need, need)


def make_block_fit(forward, lr: float, wd: float):
    """jitted fit of a block of clients from one global model: returns the
    sum of ``nsamp * weights`` over the block and its loss sum. A control
    lowers the precision inside ``forward``, by casts."""

    def client_fit(params, x, y, mask):
        def step(p, batch):
            xb, yb, mb = batch

            def loss_fn(p):
                logits = forward(p, xb.astype(jnp.float32) / 255.0)
                per_row = (jax.nn.logsumexp(logits, axis=-1)
                           - jnp.take_along_axis(
                               logits, yb[:, None], axis=-1)[:, 0])
                total = jnp.sum(per_row * mb)
                return total / jnp.maximum(jnp.sum(mb), 1.0), total

            (_, total), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            has_rows = jnp.sum(mb) > 0
            new = jax.tree.map(
                lambda w, gw: jnp.where(has_rows, w - lr * (gw + wd * w), w),
                p, g)
            return new, total

        p, totals = jax.lax.scan(step, params, (x, y, mask))
        return p, jnp.sum(totals)

    @jax.jit
    def block_fit(params, train_x, train_y, idx, mask, nsamp):
        with jax.default_matmul_precision("highest"):
            x = jnp.take(train_x, idx.reshape(-1), axis=0).reshape(
                idx.shape + train_x.shape[1:])
            y = jnp.take(train_y, idx.reshape(-1), axis=0).reshape(idx.shape)
            nets, loss = jax.vmap(client_fit, in_axes=(None, 0, 0, 0))(
                params, x, y, mask)
            wsum = jax.tree.map(
                lambda v: jnp.tensordot(nsamp, v, axes=([0], [0]),
                                        precision="highest"), nets)
            return wsum, jnp.sum(loss)

    return block_fit


def run_rounds(forward, params, data, fed: dict, seed: int, rounds: int, *,
               client_block: int, pack=pack_round):
    """Follow ``rounds`` federated rounds from ``params``. ``data`` is
    (train_x, train_y, idx_map) on the host; ``fed`` holds the FedAvg
    settings of the configuration with the cell's cohort. Returns the
    per-round mean losses, the per-round real row counts and the list of
    global models after each round (host numpy trees)."""
    train_x, train_y, idx_map = data
    if fed.get("epochs", 1) != 1 or fed.get("momentum", 0.0):
        raise ValueError("the reference follows one epoch of plain SGD")
    bs = int(fed["batch_size"])
    B = static_batches(idx_map, bs, fed.get("max_batches"))
    fit = make_block_fit(forward, float(fed["lr"]), float(fed.get("wd", 0.0)))
    dev_x, dev_y = jnp.asarray(train_x), jnp.asarray(train_y, jnp.int32)
    losses, counts, models = [], [], []
    for r in range(rounds):
        ids = sample_cohort(seed, r, int(fed["client_num_in_total"]),
                            int(fed["client_num_per_round"]))
        idx, mask, nsamp = pack(idx_map, ids, bs, B, seed, r)
        wsum, loss = None, 0.0
        for lo in range(0, len(ids), client_block):
            sl = slice(lo, lo + client_block)
            part, ls = fit(params, dev_x, dev_y, idx[sl], mask[sl], nsamp[sl])
            wsum = part if wsum is None else jax.tree.map(jnp.add, wsum, part)
            loss += float(ls)
        total = float(nsamp.sum())
        params = jax.tree.map(lambda v: v / total, wsum)
        losses.append(loss / total)
        counts.append(total)
        models.append(jax.tree.map(np.asarray, params))
    return losses, counts, models
