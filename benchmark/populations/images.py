"""Population generator ``images``: a federated image population made from
the seed, in bulk, on the host. A configuration names its generator
(``population.generator``) and the harness finds ``populations/<name>.py``
and calls its ``make(spec, seed)``.

A configuration's ``population`` block holds the parameters; nothing here
names a configuration. The arithmetic follows ``fedml_tpu/data/synthetic.py``
(class-conditional Gaussian images on the uint8 grid, a Dirichlet class mix a
client) but is vectorised, so that 3400 clients are made in seconds. Client
k holds the same number of rows under every seed, so that, with the cohorts
fixed by the traffic file, the seed changes which rows a round trains on and
not how much work it is.

The program receives only the ``FederatedData`` this returns.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_POOL = 4096  # distinct noise images; a sample is a class mean plus one


def client_sizes(spec: dict, num_clients: int) -> np.ndarray:
    """Per-client sample counts: the quantiles of a lognormal with the given
    mean and sigma, clipped, handed to the clients in one fixed shuffled
    order, the same under every seed."""
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - 0.5 * sigma * sigma
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / num_clients)
                  for i in range(num_clients)])
    sizes = np.clip(np.exp(mu + sigma * q).astype(np.int64),
                    int(spec["min"]), int(spec["max"]))
    return sizes[np.random.default_rng(0).permutation(num_clients)]


def _labels_natural(sizes, num_classes, alpha, rng):
    """Each client draws its labels from its own Dirichlet class mix."""
    mix = rng.dirichlet(np.full(num_classes, alpha), size=len(sizes))
    cdf = np.cumsum(mix, axis=1)
    owner = np.repeat(np.arange(len(sizes)), sizes)
    u = rng.random(len(owner))
    y = (u[:, None] > cdf[owner]).sum(axis=1)
    return np.minimum(y, num_classes - 1).astype(np.int64)


def _partition_lda(y, num_clients, num_classes, alpha, min_size, rng):
    """Latent Dirichlet allocation over a fixed pool (the reference's
    cifar10 loader): each class's rows are dealt to the clients in
    Dirichlet(alpha) proportions; drawn again until every client holds
    ``min_size`` rows, so that no client pads a batch slot."""
    for _ in range(1000):
        parts = [[] for _ in range(num_clients)]
        for c in range(num_classes):
            rows = rng.permutation(np.flatnonzero(y == c))
            cuts = (np.cumsum(rng.dirichlet(np.full(num_clients, alpha)))
                    * len(rows)).astype(np.int64)[:-1]
            for k, part in enumerate(np.split(rows, cuts)):
                parts[k].append(part)
        parts = [np.sort(np.concatenate(p)) for p in parts]
        if min(len(p) for p in parts) >= min_size:
            return {k: p for k, p in enumerate(parts)}
    raise RuntimeError("LDA partition never reached min_size")


def make(spec: dict, seed: int):
    """``FederatedData`` for the ``population`` block of a configuration."""
    from fedml_tpu.core.client_data import FederatedData

    rng = np.random.default_rng(int(seed))
    shape = tuple(spec["image_shape"])
    classes = int(spec["num_classes"])
    n_clients = int(spec["num_clients"])
    part = spec["partition"]
    if part["kind"] == "natural":
        sizes = client_sizes(spec["sizes"], n_clients)
        y = _labels_natural(sizes, classes, float(part["alpha"]), rng)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        idx_map = {k: np.arange(offs[k], offs[k + 1])
                   for k in range(n_clients)}
    elif part["kind"] == "lda":
        y = rng.integers(0, classes, int(spec["total_samples"])
                         ).astype(np.int64)
        idx_map = _partition_lda(y, n_clients, classes, float(part["alpha"]),
                                 int(part["min_size"]), rng)
    else:
        raise ValueError(f"unknown partition kind {part['kind']!r}")

    # x = clip((mean[y] + 0.5 * noise) * 32 + 128) on the uint8 grid, in
    # int16 so that the two gathers move a quarter of the float bytes
    means = np.rint(rng.normal(0, 1, (classes,) + shape) * 32 + 128
                    ).astype(np.int16)
    pool = np.rint(rng.normal(0, 1, (_POOL,) + shape) * 16).astype(np.int16)

    def pixels(labels):
        out = np.empty((len(labels),) + shape, np.uint8)
        for lo in range(0, len(labels), 65536):
            sl = slice(lo, lo + 65536)
            noise = pool[rng.integers(0, _POOL, len(labels[sl]))]
            np.clip(means[labels[sl]] + noise, 0, 255, out=noise)
            out[sl] = noise
        return out

    ty = rng.integers(0, classes, int(spec.get("test_samples", 256))
                      ).astype(np.int64)
    return FederatedData(train_x=pixels(y), train_y=y, test_x=pixels(ty),
                         test_y=ty, train_idx_map=idx_map, test_idx_map=None,
                         class_num=classes)
