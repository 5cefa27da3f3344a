"""Reproduce the reference's headline accuracy benchmarks (BASELINE.md).

Each entry below maps one row of the reference's published
accuracy-vs-rounds table (benchmark/README.md, mirrored in BASELINE.md) to
the equivalent fedml_tpu CLI invocation with the SAME hyperparameters:
model, dataset, client counts, sampling, batch size, optimizer, lr, rounds.

With real dataset files under --data_dir the runs reproduce the published
curves; without files the registry substitutes shape-identical synthetic
data, which exercises the identical compiled program (useful as a dry run /
throughput measurement, meaningless for accuracy).

Usage:
    python examples/reproduce_benchmarks.py --list
    python examples/reproduce_benchmarks.py femnist_cnn [--data_dir ...]
    python examples/reproduce_benchmarks.py all --rounds 10   # quick smoke

Reference rows (BASELINE.md):
  mnist_lr            MNIST + LR,       1000 clients, 10/round, bs=10,  lr=0.03,    >75%  @ 100+ rounds
  synthetic_1_1_lr    Synthetic(1,1)+LR,  30 clients, 10/round, bs=10,  lr=0.01,    >60%  @ 200+ rounds (no download needed)
  femnist_cnn         FEMNIST + CNN,    3400 clients, 10/round, bs=20,  lr=0.1,     84.9% @ 1500+ rounds
  fed_cifar100_rn18   ResNet18-GN,       500 clients, 10/round, bs=20,  lr=0.1,     44.7% @ 4000+ rounds
  shakespeare_rnn     Shakespeare RNN,   715 clients, 10/round, bs=4,   lr=1.0,     56.9% @ 1200+ rounds
  stackoverflow_nwp   SO NWP RNN,     342477 clients, 50/round, bs=16,  lr=10^-0.5, 19.5% @ 1500+ rounds
  cifar10_resnet56    CIFAR-10 + RN56,    10 clients, 10/round, bs=64,  lr=0.001,   93.19/87.12 (IID/LDA-0.5) @ 100 rounds, E=20
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as `python examples/reproduce_benchmarks.py` from a source
# checkout: sys.path[0] is examples/, the package lives one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIGS: dict[str, list[str]] = {
    # benchmark/README.md:12
    "mnist_lr": [
        "--algo", "fedavg", "--dataset", "mnist", "--model", "lr",
        "--client_num_in_total", "1000", "--client_num_per_round", "10",
        "--batch_size", "10", "--lr", "0.03", "--epochs", "1",
        "--comm_round", "100", "--frequency_of_the_test", "10",
    ],
    # benchmark/README.md:54 (the FEMNIST CNN row)
    "femnist_cnn": [
        "--algo", "fedavg", "--dataset", "femnist", "--model", "cnn",
        "--client_num_in_total", "3400", "--client_num_per_round", "10",
        "--batch_size", "20", "--lr", "0.1", "--epochs", "1",
        "--comm_round", "1500", "--frequency_of_the_test", "50",
        "--device_data", "1", "--uint8_pixels", "1",
        # bit-exact fast path: scan only the sampled clients' ladder
        # bucket instead of the 550-sample worst case every round
        "--bucket_batches", "1",
    ],
    # benchmark/README.md:14 (Linear Models table) — needs NO download: the
    # registry regenerates the reference's fixed-seed dataset bit-exactly;
    # scripts/repro_synthetic.py additionally evaluates on the reference's
    # committed test split
    "synthetic_1_1_lr": [
        "--algo", "fedavg", "--dataset", "synthetic_1_1", "--model", "lr",
        "--client_num_in_total", "30", "--client_num_per_round", "10",
        "--batch_size", "10", "--lr", "0.01", "--epochs", "1",
        "--comm_round", "220", "--frequency_of_the_test", "10",
    ],
    # benchmark/README.md:55
    "fed_cifar100_rn18": [
        "--algo", "fedavg", "--dataset", "fed_cifar100", "--model", "resnet18_gn",
        "--client_num_in_total", "500", "--client_num_per_round", "10",
        "--batch_size", "20", "--lr", "0.1", "--epochs", "1",
        "--comm_round", "4000", "--frequency_of_the_test", "100",
    ],
    # benchmark/README.md:56
    "shakespeare_rnn": [
        "--algo", "fedavg", "--dataset", "fed_shakespeare", "--model", "rnn",
        "--client_num_in_total", "715", "--client_num_per_round", "10",
        "--batch_size", "4", "--lr", "1.0", "--epochs", "1",
        "--comm_round", "1200", "--frequency_of_the_test", "50",
    ],
    # benchmark/README.md:57 (lr = 10**-0.5 ~= 0.3162)
    "stackoverflow_nwp": [
        "--algo", "fedavg", "--dataset", "stackoverflow_nwp", "--model", "rnn_stackoverflow",
        "--client_num_in_total", "342477", "--client_num_per_round", "50",
        "--batch_size", "16", "--lr", "0.31622776601", "--epochs", "1",
        "--comm_round", "1500", "--frequency_of_the_test", "50",
    ],
    # benchmark/README.md:105 cross-silo row (hetero = LDA alpha 0.5)
    "cifar10_resnet56": [
        "--algo", "fedavg", "--dataset", "cifar10", "--model", "resnet56",
        "--client_num_in_total", "10", "--client_num_per_round", "10",
        "--partition_method", "hetero", "--partition_alpha", "0.5",
        "--batch_size", "64", "--lr", "0.001", "--wd", "0.001",
        "--epochs", "20", "--comm_round", "100", "--frequency_of_the_test", "10",
    ],
}


def main(argv=None):
    ap = argparse.ArgumentParser("reproduce_benchmarks")
    ap.add_argument("name", nargs="?", help="config name or 'all'")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--data_dir", type=str, default=None)
    ap.add_argument("--rounds", type=int, default=None,
                    help="override comm_round (smoke runs)")
    args, extra = ap.parse_known_args(argv)

    if args.list or not args.name:
        for k, v in CONFIGS.items():
            print(f"{k:20s} {' '.join(v)}")
        return

    names = list(CONFIGS) if args.name == "all" else [args.name]
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        print(f"unknown config(s) {unknown}; valid: {', '.join(CONFIGS)}",
              file=sys.stderr)
        sys.exit(2)

    from fedml_tpu.experiments import cli
    for name in names:
        flags = list(CONFIGS[name])
        if args.data_dir:
            flags += ["--data_dir", args.data_dir]
        if args.rounds is not None:
            i = flags.index("--comm_round")
            flags[i + 1] = str(args.rounds)
        print(f"=== {name}: fedml_tpu.experiments.cli {' '.join(flags + extra)}")
        cli.main(flags + extra)


if __name__ == "__main__":
    main()
