"""Population generator ``tokens``: silos of token sequences made from the
seed, in bulk, on the host. A configuration names its generator
(``population.generator``) and the harness calls ``make(spec, seed)``.

``topics`` topics, each a Zipf(``zipf_exponent``) over its own seeded
permutation of the ids 1 .. ``vocab_size`` - 1 (id 0 is the pad id that
``sequence_task`` masks, and is never drawn); a silo's topic mixture is a
draw of Dirichlet(``dirichlet_alpha``); a sequence is drawn whole from one
topic of its silo's mixture. A row is ``seq_len`` tokens and its labels the
same sequence one token on. Every silo holds ``sequences_per_client`` rows
under every seed, so the seed changes what a round trains on and not how
much work it is.

The program receives only the ``FederatedData`` this returns.
"""

from __future__ import annotations

import numpy as np


def make(spec: dict, seed: int):
    """``FederatedData`` for the ``population`` block of a configuration."""
    from fedml_tpu.core.client_data import FederatedData

    rng = np.random.default_rng(int(seed))
    vocab, t = int(spec["vocab_size"]), int(spec["seq_len"])
    n_clients, per = int(spec["num_clients"]), int(spec["sequences_per_client"])
    topics = int(spec["topics"])
    ids = np.stack([rng.permutation(np.arange(1, vocab)) for _ in range(topics)])
    cdf = np.cumsum(np.arange(1, vocab, dtype=np.float64)
                    ** -float(spec["zipf_exponent"]))
    cdf /= cdf[-1]
    mix = rng.dirichlet(np.full(topics, float(spec["dirichlet_alpha"])),
                        size=n_clients)

    def sequences(topic):
        ranks = np.searchsorted(cdf, rng.random((len(topic), t + 1)))
        return ids[topic[:, None], np.minimum(ranks, vocab - 2)].astype(
            np.int32)

    topic = np.concatenate([rng.choice(topics, size=per, p=mix[k])
                            for k in range(n_clients)])
    train = sequences(topic)
    test = sequences(rng.integers(0, topics, int(spec.get("test_sequences", 4))))
    idx_map = {k: np.arange(k * per, (k + 1) * per) for k in range(n_clients)}
    return FederatedData(train_x=train[:, :-1], train_y=train[:, 1:],
                         test_x=test[:, :-1], test_y=test[:, 1:],
                         train_idx_map=idx_map, test_idx_map=None,
                         class_num=vocab)
