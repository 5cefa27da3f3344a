"""Native (C++) packer: correctness vs the numpy path + throughput sanity."""

import time

import numpy as np
import pytest

from fedml_tpu import native
from fedml_tpu.core.client_data import pack_clients
from fedml_tpu.data.synthetic import synthetic_images

pytestmark = pytest.mark.skipif(not native.native_available(),
                                reason="g++ toolchain unavailable")


@pytest.fixture(scope="module")
def data():
    return synthetic_images(num_clients=40, image_shape=(28, 28, 1),
                            num_classes=10, samples_per_client=50, seed=0)


def test_native_matches_numpy_exactly(data):
    # both paths run the same splitmix64 Fisher-Yates seeded by client id,
    # so they must be BIT-identical (grouping-invariance oracle)
    ids = np.arange(16)
    a = pack_clients(data, ids, batch_size=10, max_batches=30, use_native=False)
    b = pack_clients(data, ids, batch_size=10, max_batches=30, use_native=True)
    np.testing.assert_array_equal(a.num_samples, b.num_samples)
    np.testing.assert_array_equal(a.mask, b.mask)
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)


def test_pack_grouping_invariant(data):
    # packing a client alone == packing it in a group (distributed rank
    # parity with the SPMD block)
    grp = pack_clients(data, np.array([3, 7, 11]), batch_size=10, round_idx=2)
    solo = pack_clients(data, np.array([7]), batch_size=10, round_idx=2,
                        max_batches=grp.num_batches)
    np.testing.assert_array_equal(grp.x[1], solo.x[0])
    np.testing.assert_array_equal(grp.y[1], solo.y[0])


def test_native_deterministic(data):
    ids = np.arange(8)
    b1 = pack_clients(data, ids, batch_size=10, round_idx=3, use_native=True)
    b2 = pack_clients(data, ids, batch_size=10, round_idx=3, use_native=True)
    np.testing.assert_array_equal(b1.x, b2.x)
    b3 = pack_clients(data, ids, batch_size=10, round_idx=4, use_native=True)
    assert not np.array_equal(b1.x, b3.x)  # round changes the shuffle


def test_native_truncates_oversize_client(data):
    ids = np.arange(4)
    cb = pack_clients(data, ids, batch_size=10, max_batches=2, use_native=True)
    assert cb.x.shape[1] == 2
    assert np.all(cb.num_samples <= 20)


def test_native_faster_at_scale():
    big = synthetic_images(num_clients=512, image_shape=(28, 28, 1),
                           num_classes=10, samples_per_client=100, seed=1)
    ids = np.arange(512)

    # correctness at scale only; wall-clock comparisons are CI flakes
    # (native against numpy has no chip timing: ROADMAP D8)
    a = pack_clients(big, ids, batch_size=20, max_batches=30, use_native=False)
    b = pack_clients(big, ids, batch_size=20, max_batches=30, use_native=True)
    np.testing.assert_allclose(a.num_samples, b.num_samples)
    np.testing.assert_allclose(np.sort(a.mask.sum(axis=(1, 2))),
                               np.sort(b.mask.sum(axis=(1, 2))))
