"""The population is a pure function of the seed, and the seed does not
change how much work a round is."""

import numpy as np

from benchmark.populations.images import make as make_population

SPEC = {"num_clients": 40, "image_shape": [28, 28, 1], "num_classes": 62,
        "sizes": {"mean": 60, "sigma": 0.5, "min": 20, "max": 160},
        "partition": {"kind": "natural", "alpha": 0.5}, "test_samples": 16}
LDA = {"num_clients": 4, "image_shape": [32, 32, 3], "num_classes": 10,
       "total_samples": 2000,
       "partition": {"kind": "lda", "alpha": 0.5, "min_size": 64}}


def _sizes(fd):
    return sorted(len(v) for v in fd.train_idx_map.values())


def test_same_seed_same_population():
    a, b = make_population(SPEC, 2 ** 31 + 9), make_population(SPEC, 2 ** 31 + 9)
    assert np.array_equal(a.train_x, b.train_x)
    assert np.array_equal(a.train_y, b.train_y)
    assert all(np.array_equal(a.train_idx_map[k], b.train_idx_map[k])
               for k in a.train_idx_map)


def test_other_seed_other_rows_same_size_for_each_client():
    a, b = make_population(SPEC, 1), make_population(SPEC, 2)
    assert [len(a.train_idx_map[k]) for k in range(40)] == \
        [len(b.train_idx_map[k]) for k in range(40)]
    assert len(set(_sizes(a))) > 20
    assert not np.array_equal(a.train_x[:100], b.train_x[:100])
    assert a.train_x.dtype == np.uint8 and a.train_x.shape[1:] == (28, 28, 1)
    assert min(_sizes(a)) >= 20 and max(_sizes(a)) <= 160


def test_lda_covers_every_row_once_and_fills_batches():
    fd = make_population(LDA, 7)
    rows = np.concatenate(list(fd.train_idx_map.values()))
    assert sorted(rows.tolist()) == list(range(2000))
    assert min(_sizes(fd)) >= 64
