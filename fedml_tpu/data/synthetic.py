"""Synthetic federated data generators.

Two roles:
1. The LEAF synthetic(alpha, beta) logistic-regression benchmark
   (reference data/synthetic_0.5_0.5/ etc.): per-client softmax-linear models
   whose weights are drawn around a client-specific mean u_k ~ N(0, alpha),
   inputs around a client-specific mean B_k ~ N(0, beta).
2. Shape-compatible stand-ins for image/text datasets when the real files are
   absent (zero-egress environments): class-conditional Gaussian images and
   Markov-chain token streams — learnable, deterministic, correct shapes.
"""

from __future__ import annotations

import numpy as np

from fedml_tpu.core.client_data import FederatedData
from fedml_tpu.core.partition import partition_data


def synthetic_lr(
    num_clients: int = 30,
    alpha: float = 0.5,
    beta: float = 0.5,
    dim: int = 60,
    num_classes: int = 10,
    seed: int = 0,
) -> FederatedData:
    """LEAF synthetic(alpha,beta): y = argmax(softmax(W_k x + b_k))."""
    rng = np.random.RandomState(seed)
    sizes = np.clip(rng.lognormal(4, 2, num_clients).astype(int) + 50, 50, 10_000)
    B = rng.normal(0, beta, num_clients)
    xs, ys, idx_map, test_xs, test_ys, test_map = [], [], {}, [], [], {}
    tr_off = te_off = 0
    diag = np.array([(j + 1) ** -1.2 for j in range(dim)])
    for k in range(num_clients):
        u = rng.normal(0, alpha)
        W = rng.normal(u, 1, (dim, num_classes))
        b = rng.normal(u, 1, num_classes)
        v = rng.normal(B[k], 1, dim)
        n = int(sizes[k])
        x = rng.multivariate_normal(v, np.diag(diag), n).astype(np.float32)
        logits = x @ W + b
        y = np.argmax(logits, axis=1).astype(np.int64)
        n_tr = max(1, int(0.9 * n))
        xs.append(x[:n_tr]); ys.append(y[:n_tr])
        test_xs.append(x[n_tr:]); test_ys.append(y[n_tr:])
        idx_map[k] = np.arange(tr_off, tr_off + n_tr)
        test_map[k] = np.arange(te_off, te_off + (n - n_tr))
        tr_off += n_tr; te_off += n - n_tr
    fd = FederatedData(
        train_x=np.concatenate(xs), train_y=np.concatenate(ys),
        test_x=np.concatenate(test_xs), test_y=np.concatenate(test_ys),
        train_idx_map=idx_map, test_idx_map=test_map, class_num=num_classes,
    )
    fd.synthetic_fallback = True  # dataset_source: generated, not read
    return fd


def synthetic_leaf_exact(
    alpha: float = 1.0,
    beta: float = 1.0,
    num_clients: int = 30,
    dim: int = 60,
    num_classes: int = 10,
    seed: int = 0,
    test_json: str | None = None,
    split_seed: int | None = None,
) -> FederatedData:
    """Draw-order-exact LEAF synthetic(alpha, beta) dataset.

    The reference generates this benchmark with a FIXED numpy seed
    (data/synthetic_1_1/generate_synthetic.py:19 `np.random.seed(0)`), so the
    full 30-user sample set is deterministic and reproducible offline; only
    its train/test membership came from an unseeded `random.shuffle` before
    the 90/10 split. This function reproduces the generation process (the
    public FedProx-paper synthetic(alpha,beta) recipe) with the exact legacy
    RandomState call sequence, so the produced rows are bit-identical to the
    reference's committed data.

    test_json: path to a LEAF `mytest.json` produced by the reference
    generator (e.g. the one committed at data/synthetic_1_1/test/mytest.json).
    When given, the reference's exact train/test split is RECONSTRUCTED by
    matching each committed test row back to its generated row — train rows
    are everything else — so accuracy numbers are measured on the reference's
    own test set. When None, a seeded per-user shuffle + 90/10 split is used
    instead (same proportions, deterministic).

    seed: the GENERATION seed — 0 is the reference's fixed value; any other
    value produces a different (non-reference) dataset. split_seed: seeds
    only the fallback 90/10 split (defaults to seed), so run-seed sweeps can
    vary the split without silently changing the benchmark data.
    """
    if split_seed is None:
        split_seed = seed
    rs = np.random.RandomState(seed)
    sizes = rs.lognormal(4, 2, num_clients).astype(int) + 50
    mean_W = rs.normal(0, alpha, num_clients)       # per-user model mean
    B = rs.normal(0, beta, num_clients)             # per-user input mean-mean
    cov = np.diag(np.power(np.arange(1, dim + 1, dtype=np.float64), -1.2))
    mean_x = np.stack([rs.normal(B[k], 1, dim) for k in range(num_clients)])

    per_user: list[tuple[np.ndarray, np.ndarray]] = []
    for k in range(num_clients):
        W = rs.normal(mean_W[k], 1, (dim, num_classes))
        b = rs.normal(mean_W[k], 1, num_classes)    # mean_b aliases mean_W
        x = rs.multivariate_normal(mean_x[k], cov, int(sizes[k]))
        y = np.argmax(x @ W + b, axis=1)            # argmax(softmax) = argmax
        per_user.append((x, y))

    test_rows: dict[int, np.ndarray] | None = None
    if test_json is not None:
        import json

        with open(test_json) as f:
            d = json.load(f)
        if len(d["users"]) != num_clients:
            raise ValueError(
                f"{test_json}: {len(d['users'])} users, expected {num_clients}")
        test_rows = {}
        for k, u in enumerate(sorted(d["users"])):  # f_00000.. numeric order
            gx, gy = per_user[k]
            xs = np.asarray(d["user_data"][u]["x"], dtype=np.float64)
            ys = np.asarray(d["user_data"][u]["y"])
            taken = np.zeros(len(gx), bool)
            rows = np.empty(len(xs), np.int64)
            for r in range(len(xs)):
                diff = np.abs(gx - xs[r]).max(axis=1)
                diff[taken] = np.inf
                j = int(np.argmin(diff))
                if diff[j] > 1e-9 or int(gy[j]) != int(ys[r]):
                    raise ValueError(
                        f"{test_json}: user {u} row {r} does not match any "
                        f"generated sample (min |dx|={diff[j]:.3g}) — wrong "
                        "(alpha, beta) or a differently-seeded file?")
                taken[j] = True
                rows[r] = j
            test_rows[k] = rows

    xs, ys, idx_map, test_xs, test_ys, test_map = [], [], {}, [], [], {}
    tr_off = te_off = 0
    for k in range(num_clients):
        x, y = per_user[k]
        if test_rows is not None:
            te = test_rows[k]
            tr = np.setdiff1d(np.arange(len(x)), te)
        else:
            perm = np.random.RandomState(
                (split_seed * 9973 + k + 1) % (2 ** 32)).permutation(len(x))
            n_tr = int(0.9 * len(x))  # generator's split ratio (:80)
            tr, te = perm[:n_tr], perm[n_tr:]
        xs.append(x[tr].astype(np.float32)); ys.append(y[tr].astype(np.int64))
        test_xs.append(x[te].astype(np.float32)); test_ys.append(y[te].astype(np.int64))
        idx_map[k] = np.arange(tr_off, tr_off + len(tr))
        test_map[k] = np.arange(te_off, te_off + len(te))
        tr_off += len(tr); te_off += len(te)
    fd = FederatedData(
        train_x=np.concatenate(xs), train_y=np.concatenate(ys),
        test_x=np.concatenate(test_xs), test_y=np.concatenate(test_ys),
        train_idx_map=idx_map, test_idx_map=test_map, class_num=num_classes,
    )
    fd.synthetic_fallback = True  # dataset_source: generated, not read
    return fd


def synthetic_images(
    num_clients: int,
    image_shape: tuple[int, ...],
    num_classes: int,
    samples_per_client: int = 100,
    test_samples: int = 1000,
    partition_method: str = "natural",
    partition_alpha: float = 0.5,
    seed: int = 0,
    size_lognormal: bool = True,
    as_uint8: bool = False,
    partition_fix_path: str | None = None,
) -> FederatedData:
    """Class-conditional Gaussian images, shape-compatible stand-in for
    MNIST/FEMNIST/CIFAR when real files are absent. Each class c has a fixed
    random mean image m_c; samples are m_c + noise. 'natural' partitioning
    gives each client a skewed label distribution + lognormal size (LEAF-like);
    'homo'/'hetero' delegate to the standard partitioners."""
    rng = np.random.RandomState(seed)
    means = rng.normal(0, 1, (num_classes,) + image_shape).astype(np.float32)

    if size_lognormal:
        sizes = np.clip(
            rng.lognormal(np.log(samples_per_client), 0.5, num_clients).astype(int),
            max(10, samples_per_client // 5),
            samples_per_client * 5,
        )
    else:
        sizes = np.full(num_clients, samples_per_client)
    total = int(sizes.sum())

    if partition_method == "natural":
        # each client draws labels from its own dirichlet class mix
        ys = []
        for k in range(num_clients):
            mix = rng.dirichlet(np.repeat(partition_alpha, num_classes))
            ys.append(rng.choice(num_classes, sizes[k], p=mix))
        y = np.concatenate(ys).astype(np.int64)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        idx_map = {k: np.arange(offs[k], offs[k + 1]) for k in range(num_clients)}
    else:
        y = rng.choice(num_classes, total).astype(np.int64)
        idx_map = partition_data(y, num_clients, partition_method, partition_alpha,
                                 seed, fix_path=partition_fix_path)

    # noise from a shared pool: generating total*prod(shape) fresh gaussians
    # dominates wall-clock at 3400-client scale and adds nothing for learning
    pool = rng.normal(0, 1, (4096,) + image_shape).astype(np.float32)
    x = means[y] + 0.5 * pool[rng.randint(0, 4096, total)]
    ty = rng.choice(num_classes, test_samples).astype(np.int64)
    tx = means[ty] + 0.5 * pool[rng.randint(0, 4096, test_samples)]
    if as_uint8:
        # map the ~N(0,1.1) pixel field onto the uint8 grid; after the image
        # tasks' on-device /255 the model sees ~N(0.5, 0.125^2) — an affine
        # rescale of the float variant (standard [0,1] image normalization),
        # NOT the same raw scale, at 1/4 the host->device bytes. Real image
        # datasets are natively uint8, so this only affects the synthetic
        # stand-in; learning-rate-sensitive comparisons between the float
        # and uint8 synthetic variants are not scale-equivalent.
        q = lambda a: np.clip(a * 32.0 + 128.0, 0, 255).astype(np.uint8)
        x, tx = q(x), q(tx)
    fd = FederatedData(
        train_x=x if as_uint8 else x.astype(np.float32), train_y=y,
        test_x=tx if as_uint8 else tx.astype(np.float32), test_y=ty,
        train_idx_map=idx_map, test_idx_map=None, class_num=num_classes,
    )
    fd.synthetic_fallback = True
    return fd


def synthetic_segmentation(
    num_clients: int,
    image_shape: tuple[int, int, int] = (64, 64, 3),
    num_classes: int = 21,
    samples_per_client: int = 20,
    test_samples: int = 40,
    seed: int = 0,
    ignore_index: int = 255,
    partition_alpha: float = 0.5,
) -> FederatedData:
    """Blob-world segmentation stand-in for PASCAL VOC / COCO (FedSeg).

    Each image contains 1-3 axis-aligned rectangles of random foreground
    classes on a class-0 background; pixel labels follow the rectangles, with
    a 1-px ``ignore_index`` border around each object (mimicking VOC's void
    boundary pixels). Clients draw objects from a Dirichlet(partition_alpha)
    class mix -> non-IID, sharper as alpha shrinks (the LDA knob of
    cifar10/data_loader.py:172-196 applied to object classes).
    """
    rng = np.random.RandomState(seed)
    h, w, c = image_shape
    class_colors = rng.normal(0, 1, (num_classes, c)).astype(np.float32)

    def gen(n, class_probs):
        x = np.zeros((n, h, w, c), np.float32)
        y = np.zeros((n, h, w), np.int64)
        for i in range(n):
            x[i] = class_colors[0] + 0.3 * rng.normal(0, 1, (h, w, c))
            for _ in range(rng.randint(1, 4)):
                cls = 1 + int(rng.choice(num_classes - 1, p=class_probs))
                bh, bw = rng.randint(h // 4, h // 2), rng.randint(w // 4, w // 2)
                r0, c0 = rng.randint(0, h - bh), rng.randint(0, w - bw)
                x[i, r0:r0 + bh, c0:c0 + bw] = class_colors[cls] + \
                    0.3 * rng.normal(0, 1, (bh, bw, c))
                y[i, r0:r0 + bh, c0:c0 + bw] = cls
                # void boundary ring (all four edges)
                y[i, r0, c0:c0 + bw] = ignore_index
                y[i, r0 + bh - 1, c0:c0 + bw] = ignore_index
                y[i, r0:r0 + bh, c0] = ignore_index
                y[i, r0:r0 + bh, c0 + bw - 1] = ignore_index
        return x, y

    xs, ys, idx_map, off = [], [], {}, 0
    n_fg = num_classes - 1
    for k in range(num_clients):
        probs = rng.dirichlet(np.repeat(partition_alpha, n_fg))
        x, y = gen(samples_per_client, probs)
        xs.append(x); ys.append(y)
        idx_map[k] = np.arange(off, off + samples_per_client)
        off += samples_per_client
    tx, ty = gen(test_samples, np.full(n_fg, 1.0 / n_fg))
    fd = FederatedData(
        train_x=np.concatenate(xs), train_y=np.concatenate(ys),
        test_x=tx, test_y=ty,
        train_idx_map=idx_map, test_idx_map=None, class_num=num_classes,
    )
    fd.synthetic_fallback = True
    return fd


def synthetic_sequences(
    num_clients: int,
    seq_len: int,
    vocab_size: int,
    samples_per_client: int = 50,
    test_samples: int = 500,
    seed: int = 0,
    pad_id: int = 0,
) -> FederatedData:
    """Markov-chain token sequences, stand-in for Shakespeare/StackOverflow.

    x[t] is the context token, y[t] = x[t+1] (next-token target). Each client
    has its own transition sharpness -> non-IID. Sequences are full-length
    (no pad) except the synthetic raggedness left to per-sample masks.
    """
    rng = np.random.RandomState(seed)
    base = rng.dirichlet(np.ones(vocab_size - 1) * 0.3, vocab_size)  # rows: next-token dist

    def gen(n, sharp):
        seqs = np.zeros((n, seq_len + 1), dtype=np.int64)
        for i in range(n):
            t = rng.randint(1, vocab_size)
            for j in range(seq_len + 1):
                seqs[i, j] = t
                p = base[t] ** sharp
                p = p / p.sum()
                t = 1 + rng.choice(vocab_size - 1, p=p)
        return seqs

    xs, idx_map = [], {}
    off = 0
    for k in range(num_clients):
        sharp = 0.5 + rng.rand() * 1.5
        s = gen(samples_per_client, sharp)
        xs.append(s)
        idx_map[k] = np.arange(off, off + samples_per_client)
        off += samples_per_client
    seqs = np.concatenate(xs)
    test = gen(test_samples, 1.0)
    fd = FederatedData(
        train_x=seqs[:, :-1], train_y=seqs[:, 1:],
        test_x=test[:, :-1], test_y=test[:, 1:],
        train_idx_map=idx_map, test_idx_map=None, class_num=vocab_size,
    )
    fd.synthetic_fallback = True
    return fd


def synthetic_packed_population(path: str, num_clients: int, dim: int = 16,
                                num_classes: int = 5, seed: int = 0,
                                test_rows: int = 512) -> str:
    """Write a deterministic SYNTHETIC packed-npy population straight to
    disk (core/client_source.PackedNpySource layout) without ever
    materializing it — the fixture for the flat-memory evidence (ci.sh
    streamed smoke): lognormal-ish ragged client sizes with a heavy tail
    (the skew cohort bucketing exists for), labels planted from ONE pass
    over the feature rows actually written (x and y stream together — a
    second pass re-drawing x would store uncorrelated labels), and a
    held-out test split from the same planted mapping. Chunked writes keep
    the writer's RSS flat too."""
    import json as _json
    import os as _os

    _os.makedirs(path, exist_ok=True)
    rs = np.random.RandomState(seed)
    # 6 to 24 rows a client and one in 200 with 96: a tail that prices the
    # static batch budget by a client most cohorts never sample — the
    # FEMNIST-lognormal shape the bucket ladder exists for
    sizes = rs.randint(6, 25, num_clients).astype(np.int64)
    tail = max(num_clients // 200, 1)
    sizes[rs.choice(num_clients, tail, replace=False)] = 96
    offsets = np.zeros(num_clients + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    total = int(offsets[-1])
    W = rs.randn(dim, num_classes).astype(np.float32)
    with open(_os.path.join(path, "x.npy"), "wb") as fx, \
            open(_os.path.join(path, "y.npy"), "wb") as fy:
        np.lib.format.write_array_header_2_0(
            fx, {"descr": np.lib.format.dtype_to_descr(
                np.dtype(np.float32)),
                "fortran_order": False, "shape": (total, dim)})
        np.lib.format.write_array_header_2_0(
            fy, {"descr": np.lib.format.dtype_to_descr(np.dtype(np.int64)),
                 "fortran_order": False, "shape": (total,)})
        chunk = 1 << 18
        for s in range(0, total, chunk):
            m = min(chunk, total - s)
            x = rs.randn(m, dim).astype(np.float32)
            fx.write(x.tobytes())
            fy.write(np.argmax(x @ W, 1).astype(np.int64).tobytes())
    np.save(_os.path.join(path, "offsets.npy"), offsets)
    rs2 = np.random.RandomState(seed + 1)
    tx = rs2.randn(test_rows, dim).astype(np.float32)
    np.save(_os.path.join(path, "test_x.npy"), tx)
    np.save(_os.path.join(path, "test_y.npy"),
            np.argmax(tx @ W, 1).astype(np.int64))
    with open(_os.path.join(path, "meta.json"), "w") as f:
        _json.dump({"format": "fedml-packed-npy",
                    "num_clients": num_clients,
                    "class_num": num_classes, "source": "synthetic"}, f)
    return path
