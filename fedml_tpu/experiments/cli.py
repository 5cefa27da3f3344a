"""Unified experiment launcher (L5).

Mirror of the reference CLI surface: the ~20 argparse flags of
fedml_experiments/distributed/fedavg/main_fedavg.py:48-119 plus the
multi-algorithm dispatch of fedml_experiments/distributed/fed_launch/main.py
and algorithm-specific flags (--server_optimizer/--server_lr main_fedopt.py:
54-60; --defense_type/--norm_bound/--stddev robust_aggregation.py:33-36).

Where the reference wraps this in `mpirun -np N+1` + hostfiles + gpu_mapping
yamls, here `--mesh N` creates an N-device 'clients' mesh; no process
management exists to configure.

Usage:
    python -m fedml_tpu.experiments.cli --algo fedavg --dataset mnist \
        --model lr --client_num_in_total 50 --client_num_per_round 10 \
        --comm_round 20
"""

from __future__ import annotations

import argparse
import json
import logging
import time


def add_args(parser: argparse.ArgumentParser):
    # core flag surface (main_fedavg.py:48-119 parity)
    parser.add_argument("--algo", type=str, default="fedavg",
                        choices=["fedavg", "fedavg_seq", "fedopt", "fedprox",
                                 "fednova",
                                 "fedavg_robust", "hierarchical", "feddf",
                                 "feddf_hard", "fedcon", "fedavg_affinity", "fednas",
                                 "decentralized", "centralized", "turboaggregate",
                                 "fedseg", "split_nn", "fedgkt", "vfl"])
    parser.add_argument("--model", type=str, default="lr")
    parser.add_argument("--dataset", type=str, default="mnist")
    parser.add_argument("--data_dir", type=str, default=None,
                        help="real-dataset directory (fetch + layout: "
                             "scripts/download_<dataset>.sh); absent files "
                             "fall back to shape-identical synthetic data")
    parser.add_argument("--image_size", type=int, default=None,
                        help="square decode resolution for the folder/csv "
                             "image readers (imagenet/gld): 224 = reference "
                             "fidelity, default 64 = study scale")
    parser.add_argument("--partition_method", type=str, default=None,
                        help="homo | hetero (LDA) | hetero-bal | hetero-fix | natural")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--partition_fix_path", type=str, default=None,
                        help="hetero-fix: frozen net_dataidx_map.txt "
                             "(reference checked-in format)")
    parser.add_argument("--client_num_in_total", type=int, default=None)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--client_optimizer", type=str, default="sgd")
    parser.add_argument("--lr", type=float, default=0.03)
    parser.add_argument("--wd", type=float, default=0.0)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=10)
    parser.add_argument("--frequency_of_the_test", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--ci", type=int, default=0)
    parser.add_argument("--eval_subset_mode", type=str, default="fixed",
                        choices=["fixed", "fresh"],
                        help="validation-subset policy when eval is capped: "
                             "'fresh' resamples per eval (reference "
                             "FedAVGAggregator semantics), 'fixed' reuses one "
                             "seeded subset")
    parser.add_argument("--local_test_on_all_clients", type=str,
                        default="auto", choices=["auto", "on", "off"],
                        help="per-client eval each eval round (the "
                             "reference's _local_test_on_all_clients, "
                             "fedavg_api.py:117-180); 'auto' = on exactly "
                             "when the dataset has per-client test splits "
                             "and no validation-subset cap")
    # TPU execution surface (replaces --backend/--gpu_mapping/--is_mobile)
    parser.add_argument("--mesh", type=int, default=0,
                        help="devices on the 'clients' mesh axis; 0 = "
                             "single-device vmap. For --algo centralized "
                             "the axis is 'data' (0 = ALL devices when "
                             "--model_parallel > 1 or with fedavg_seq, "
                             "which have no single-device analogue)")
    parser.add_argument("--seq_shards", type=int, default=2,
                        help="fedavg_seq: devices on the 'seq' axis (the "
                             "'clients' axis gets --mesh/seq_shards)")
    parser.add_argument("--seq_impl", type=str, default="ring",
                        choices=["ring", "ulysses"])
    parser.add_argument("--model_parallel", type=int, default=1,
                        help="centralized: devices on a 'model' axis — "
                             "Megatron-style tensor (+MoE expert) "
                             "parallelism via GSPMD specs; composes with "
                             "the remaining devices as the 'data' axis")
    parser.add_argument("--lm_dim", type=int, default=64)
    parser.add_argument("--lm_depth", type=int, default=2)
    parser.add_argument("--lm_heads", type=int, default=4)
    parser.add_argument("--max_batches", type=int, default=None)
    parser.add_argument("--remat", type=int, default=0,
                        help="1 = jax.checkpoint the local-fit forwards "
                             "(recompute activations in backward; fits "
                             "deeper models / longer contexts in HBM)")
    parser.add_argument("--device_data", type=int, default=0,
                        help="1 = HBM-resident train set + per-round index blocks")
    parser.add_argument("--working_set", type=int, default=0,
                        help="with --device_data 1: per-block working-set "
                             "park (upload only the rows a block touches) "
                             "instead of parking the whole train set")
    parser.add_argument("--uint8_pixels", type=int, default=0,
                        help="1 = ship image pixels as uint8, normalize on device")
    parser.add_argument("--bucket_batches", type=int, default=0,
                        help="1 = shrink each round/block's common batch "
                             "depth to the sampled clients' ladder bucket "
                             "(bit-exact; skips padded no-op batch compute "
                             "at the cost of <=4 jit variants)")
    # algorithm-specific
    parser.add_argument("--server_optimizer", type=str, default="sgd")
    parser.add_argument("--server_lr", type=float, default=1.0)
    parser.add_argument("--server_momentum", type=float, default=0.9)
    parser.add_argument("--mu", type=float, default=0.1, help="FedProx mu")
    parser.add_argument("--defense_type", type=str, default="norm_diff_clipping",
                        choices=["norm_diff_clipping", "weak_dp", "dp", "none"])
    parser.add_argument("--norm_bound", type=float, default=30.0)
    parser.add_argument("--stddev", type=float, default=0.025)
    # defense_type=dp (real DP-FedAvg with RDP accounting, core/privacy.py)
    parser.add_argument("--noise_multiplier", type=float, default=1.0)
    parser.add_argument("--dp_delta", type=float, default=1e-5)
    # attack side of fedavg_robust (reference --poison_type/--attack_case,
    # edge_case_examples/data_loader.py:283): 'pixel'/'edge' are the
    # synthetic generators (zero files needed); 'southwest'/'greencar'/
    # 'ardis' read the reference's real archives via --edge_case_train/
    # --edge_case_test (data/poisoning.py inject_edge_case_files). The
    # round log gains backdoor_acc (targeted-task accuracy) at eval rounds.
    parser.add_argument("--poison_type", type=str, default="none",
                        choices=["none", "pixel", "edge", "southwest",
                                 "greencar", "ardis"])
    parser.add_argument("--poison_clients", type=int, default=1,
                        help="first K clients are attacker-controlled")
    parser.add_argument("--poison_target_label", type=int, default=None,
                        help="default: the archive's reference convention "
                             "(southwest 9, greencar 2, ardis from file)")
    parser.add_argument("--edge_case_train", type=str, default=None)
    parser.add_argument("--edge_case_test", type=str, default=None)
    parser.add_argument("--sampling", type=str, default="uniform",
                        choices=["uniform", "size_weighted"],
                        help="per-round client sampling: uniform (reference "
                             "parity, sample-weighted aggregate) or "
                             "size_weighted (P ∝ client size, uniform "
                             "aggregate — the FedAvg paper's alt scheme)")
    parser.add_argument("--async_ckpt", type=int, default=1,
                        help="write round checkpoints off the training "
                             "thread (disk I/O overlaps later rounds; the "
                             "state snapshot still happens synchronously)")
    parser.add_argument("--group_num", type=int, default=2)
    parser.add_argument("--group_comm_round", type=int, default=2)
    parser.add_argument("--distill_steps", type=int, default=20)
    parser.add_argument("--distill_lr", type=float, default=1e-3)
    parser.add_argument("--hard_sample_ratio", type=float, default=1.0)
    parser.add_argument("--fedmix_server", type=int, default=0)
    parser.add_argument("--val_fraction", type=float, default=0.0,
                        help=">0: val-gated early stop of distillation")
    # fedcon (condense_api.py flag surface: train type + ipc)
    parser.add_argument("--condense_train_type", type=str, default="ce",
                        choices=["ce", "soft"])
    parser.add_argument("--images_per_class", type=int, default=2)
    parser.add_argument("--condense_iters", type=int, default=20)
    parser.add_argument("--condense_steps", type=int, default=10)
    parser.add_argument("--condense_init_only", type=int, default=1,
                        help="1 = fedcon_init (condense once); 0 = re-condense")
    parser.add_argument("--recondense_every", type=int, default=5)
    # fedseg (--loss_type/--lr_scheduler surface of the reference fedseg main)
    parser.add_argument("--loss_type", type=str, default="ce")
    parser.add_argument("--lr_scheduler", type=str, default="poly")
    parser.add_argument("--lr_step", type=int, default=30)
    # checkpoint / logging
    parser.add_argument("--ckpt_dir", type=str, default=None)
    parser.add_argument("--resume", action="store_true")
    parser.add_argument("--trace_dir", type=str, default=None,
                        help="capture a jax.profiler XLA/TPU trace "
                             "(TensorBoard/Perfetto; files are large)")
    parser.add_argument("--trace_rounds", type=int, default=3,
                        help="round-loop algos: trace only the first N "
                             "rounds (a whole-run trace of a long job is "
                             "unloadably large); 0 = whole run")
    parser.add_argument("--run_dir", type=str, default="./runs")
    parser.add_argument("--run_name", type=str, default=None)
    # FedNAS (reference main_fednas.py:44-45,78-98): search discovers a
    # genotype; train federatedly trains the derived NetworkCIFAR
    parser.add_argument("--stage", type=str, default="search",
                        choices=["search", "train"],
                        help="fednas: 'search' runs bilevel DARTS search; "
                             "'train' trains the derived fixed-genotype net")
    parser.add_argument("--arch", type=str, default="FedNAS_V1",
                        help="fednas --stage train: genotype name "
                             "(FedNAS_V1/DARTS_V2) or a json file from a "
                             "search run")
    parser.add_argument("--nas_layers", type=int, default=None,
                        help="fednas cell count (default: 4 search / "
                             "8 train, the reference --layers default)")
    parser.add_argument("--init_channels", type=int, default=16)
    parser.add_argument("--auxiliary", type=int, default=0,
                        help="fednas train stage: add the auxiliary head")
    parser.add_argument("--auxiliary_weight", type=float, default=0.4)
    parser.add_argument("--drop_path_prob", type=float, default=0.5)
    parser.add_argument("--nas_method", type=str, default="darts",
                        choices=["darts", "gdas"],
                        help="fednas search: softmax-mixture DARTS or "
                             "Gumbel hard-selection GDAS")
    parser.add_argument("--tau", type=float, default=10.0,
                        help="GDAS gumbel-softmax temperature (static per "
                             "run; the reference anneals it per epoch)")
    return parser


log = logging.getLogger("cli")


def build_api(args):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import (classification_task, sequence_task,
                                      tag_prediction_task)
    from fedml_tpu.data.registry import DATASETS, load_dataset
    from fedml_tpu.models import create_model

    if args.poison_type != "none" and args.algo != "fedavg_robust":
        # refuse rather than silently run a clean baseline the user
        # believes is poisoned
        raise SystemExit(
            f"--poison_type {args.poison_type} is only wired for "
            "--algo fedavg_robust (the attack/defense engine)")

    if args.algo == "vfl":
        # vertical datasets live in their own registry (feature-partitioned)
        from fedml_tpu.algorithms.vfl import VFLAPI, VFLConfig
        from fedml_tpu.data.tabular import load_vertical, train_test_split_vertical
        from fedml_tpu.models.vfl import DenseTower

        xg, xh, y, vspec = load_vertical(args.dataset, data_dir=args.data_dir,
                                         seed=args.seed)
        (tg, th, ty), _ = train_test_split_vertical(xg, xh, y, seed=args.seed)
        api = VFLAPI(
            DenseTower(num_classes=vspec.num_classes),
            DenseTower(num_classes=vspec.num_classes),
            tg, th, ty,
            VFLConfig(epochs=args.epochs * args.comm_round,
                      batch_size=args.batch_size, guest_lr=args.lr,
                      host_lr=args.lr, seed=args.seed),
            num_classes=vspec.num_classes,
        )
        return api, None

    spec = DATASETS[args.dataset]
    data = load_dataset(
        args.dataset, data_dir=args.data_dir, client_num=args.client_num_in_total,
        partition_method=args.partition_method, partition_alpha=args.partition_alpha,
        seed=args.seed, uint8_pixels=bool(getattr(args, "uint8_pixels", 0)),
        partition_fix_path=args.partition_fix_path, image_size=args.image_size,
    )
    n_total = data.num_clients

    if args.algo == "fedseg":
        from fedml_tpu.algorithms.fedseg import FedSegAPI, FedSegConfig
        from fedml_tpu.models.segmentation import DeepLabLite, UNetLite

        seg_model = (DeepLabLite(num_classes=spec.num_classes)
                     if args.model in ("deeplab", "deeplab_lite")
                     else UNetLite(num_classes=spec.num_classes))
        scfg = FedSegConfig(
            comm_round=args.comm_round, client_num_in_total=n_total,
            client_num_per_round=min(args.client_num_per_round, n_total),
            epochs=args.epochs, batch_size=args.batch_size, lr=args.lr,
            wd=args.wd, frequency_of_the_test=args.frequency_of_the_test,
            seed=args.seed, max_batches=args.max_batches, ci=bool(args.ci),
            loss_type=args.loss_type, lr_scheduler=args.lr_scheduler,
            lr_step=args.lr_step,
        )
        return FedSegAPI(data, seg_model, scfg), data

    if args.algo == "split_nn":
        from fedml_tpu.algorithms.split_nn import SplitNNAPI, SplitNNConfig
        from fedml_tpu.models.gkt import SplitLowerNet, SplitUpperNet

        return SplitNNAPI(
            data, SplitLowerNet(),
            SplitUpperNet(num_classes=spec.num_classes),
            SplitNNConfig(epochs=args.epochs, batch_size=args.batch_size,
                          lr=args.lr, client_num=min(args.client_num_per_round,
                                                     n_total),
                          max_batches=args.max_batches, seed=args.seed),
        ), data

    if args.algo == "fedgkt":
        from fedml_tpu.algorithms.fedgkt import FedGKTAPI, FedGKTConfig
        from fedml_tpu.models.gkt import (GKTClientExtractor, GKTClientHead,
                                          GKTServerModel)

        nclients = min(args.client_num_per_round, n_total)
        gcfg = FedGKTConfig(
            comm_round=args.comm_round, client_num_in_total=nclients,
            client_num_per_round=nclients, epochs_client=args.epochs,
            epochs_server=args.epochs, batch_size=args.batch_size,
            lr_client=args.lr, lr_server=args.lr,
            max_batches=args.max_batches, seed=args.seed,
        )
        return FedGKTAPI(
            data, GKTClientExtractor(norm_type="group", blocks=1),
            GKTClientHead(num_classes=spec.num_classes),
            GKTServerModel(norm_type="group", blocks_per_stage=2,
                           num_classes=spec.num_classes),
            gcfg, num_classes=spec.num_classes,
        ), data

    cfg = FedAvgConfig(
        comm_round=args.comm_round, client_num_in_total=n_total,
        client_num_per_round=min(args.client_num_per_round, n_total),
        epochs=args.epochs, batch_size=args.batch_size,
        client_optimizer=args.client_optimizer, lr=args.lr, wd=args.wd,
        frequency_of_the_test=args.frequency_of_the_test, seed=args.seed,
        max_batches=args.max_batches, ci=bool(args.ci),
        remat=bool(args.remat),
        # stackoverflow evals run on a 10k-sample validation subset
        # (FedAVGAggregator._generate_validation_set, :99-107)
        eval_max_samples=(10_000 if args.dataset.startswith("stackoverflow")
                          else None),
        eval_subset_mode=args.eval_subset_mode,
        sampling=args.sampling,
        local_test_on_all_clients=args.local_test_on_all_clients,
    )
    if args.algo == "fedavg_seq":
        from fedml_tpu.algorithms.fedavg_seq import FedAvgSeqAPI
        from fedml_tpu.models.transformer import TransformerLM

        if spec.task != "sequence":
            raise ValueError("fedavg_seq needs a sequence dataset "
                             "(shakespeare / fed_shakespeare / stackoverflow_nwp)")
        from fedml_tpu.mesh.mesh import make_2d_mesh

        # NOTE --mesh 0 means "all devices" here (a 2-axis mesh has no
        # single-device vmap analogue), unlike the 1-axis algos
        sd = max(1, args.seq_shards)
        smesh = make_2d_mesh(args.mesh, sd, ("clients", "seq"),
                             minor_flag="--seq_shards")
        cd = int(smesh.shape["clients"])
        T = int(spec.input_shape[0])
        log.info("fedavg_seq mesh: %d client-shards x %d seq-shards (T=%d)",
                 cd, sd, T)
        return FedAvgSeqAPI(
            data,
            lambda seq_axis: TransformerLM(
                vocab_size=spec.num_classes, dim=args.lm_dim,
                depth=args.lm_depth, num_heads=args.lm_heads, max_len=T,
                seq_axis=seq_axis, seq_impl=args.seq_impl),
            cfg, mesh=smesh), data

    model = create_model(args.model, output_dim=spec.num_classes)
    task = {"classification": classification_task,
            "sequence": sequence_task,
            "tags": tag_prediction_task}[spec.task](model)

    mesh = None
    if args.mesh and args.algo not in ("hierarchical", "centralized"):
        # hierarchical builds its own 2-axis ('groups','clients') mesh
        # below; centralized builds a ('data'[,'model']) mesh in its branch
        mesh = Mesh(np.asarray(jax.devices()[: args.mesh]), ("clients",))

    algo = args.algo
    if algo == "fedavg":
        return FedAvgAPI(
            data, task, cfg, mesh=mesh,
            device_data=bool(getattr(args, "device_data", 0)),
            block_working_set=bool(getattr(args, "device_data", 0))
            and bool(getattr(args, "working_set", 0)),
            bucket_batches=bool(getattr(args, "bucket_batches", 0))), data
    if algo == "fedopt":
        from fedml_tpu.algorithms.fedopt import FedOptAPI

        return FedOptAPI(data, task, cfg, mesh=mesh,
                         server_optimizer=args.server_optimizer,
                         server_lr=args.server_lr,
                         server_momentum=args.server_momentum), data
    if algo == "fedprox":
        from fedml_tpu.algorithms.fedprox import FedProxAPI

        return FedProxAPI(data, task, cfg, mesh=mesh, mu=args.mu), data
    if algo == "fednova":
        from fedml_tpu.algorithms.fednova import FedNovaAPI

        return FedNovaAPI(data, task, cfg, mesh=mesh), data
    if algo == "fedavg_robust":
        from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustAPI

        poisoned_test = None
        if args.poison_type != "none":
            from fedml_tpu.data import poisoning

            if args.poison_clients < 1:
                raise SystemExit("--poison_clients must be >= 1 when "
                                 "--poison_type is set")
            ids = list(range(min(args.poison_clients, data.num_clients)))
            tl = args.poison_target_label
            if args.poison_type == "pixel":
                data, poisoned_test = poisoning.make_backdoor_dataset(
                    data, target_label=0 if tl is None else tl,
                    poison_client_ids=ids)
            elif args.poison_type == "edge":
                data, poisoned_test = poisoning.make_edge_case_dataset(
                    data, target_label=0 if tl is None else tl,
                    poison_client_ids=ids)
            else:  # real archive formats
                if not args.edge_case_train:
                    raise SystemExit(
                        f"--poison_type {args.poison_type} reads the real "
                        "archive: pass --edge_case_train (and optionally "
                        "--edge_case_test)")
                if tl is None:  # ardis: stays None -> labels from the file
                    tl = poisoning.EDGE_CASE_TARGETS.get(args.poison_type)
                data, poisoned_test = poisoning.inject_edge_case_files(
                    data, args.edge_case_train, args.edge_case_test,
                    poison_client_ids=ids, target_label=tl)
        return FedAvgRobustAPI(data, task, cfg, mesh=mesh,
                               defense_type=args.defense_type,
                               norm_bound=args.norm_bound,
                               stddev=args.stddev,
                               noise_multiplier=args.noise_multiplier,
                               poisoned_test=poisoned_test), data
    if algo == "hierarchical":
        from fedml_tpu.algorithms.hierarchical import HierarchicalFLAPI

        hmesh = None
        if args.mesh:
            # --mesh N with hierarchical: ('groups','clients') 2-axis mesh,
            # groups on the slow (DCN-able) axis, clients on ICI
            from fedml_tpu.mesh.mesh import make_hierarchical_mesh

            gd = min(args.group_num, max(1, args.mesh // 2))
            while args.group_num % gd or args.mesh % gd:
                gd -= 1
            if gd == 1:
                log.warning(
                    "hierarchical mesh degenerates to (1, %d): group_num=%d "
                    "shares no factor with --mesh %d, so intra-group syncs "
                    "span ALL devices instead of staying on the fast axis",
                    args.mesh, args.group_num, args.mesh)
            else:
                log.info("hierarchical mesh: %d groups x %d client-shards",
                         gd, args.mesh // gd)
            hmesh = make_hierarchical_mesh(gd, args.mesh // gd)
        return HierarchicalFLAPI(data, task, cfg, group_num=args.group_num,
                                 group_comm_round=args.group_comm_round,
                                 mesh=hmesh), data
    if algo in ("feddf", "feddf_hard"):
        from fedml_tpu.algorithms.feddf import FedDFAPI

        return FedDFAPI(data, task, cfg, mesh=mesh,
                        distill_steps=args.distill_steps,
                        distill_lr=args.distill_lr,
                        hard_sample_ratio=args.hard_sample_ratio,
                        fedmix_server=bool(args.fedmix_server),
                        val_fraction=args.val_fraction,
                        hard_label=(algo == "feddf_hard")), data
    if algo == "fedcon":
        from fedml_tpu.algorithms.fedcon import FedConAPI

        return FedConAPI(data, task, cfg, mesh=mesh,
                         images_per_class=args.images_per_class,
                         condense_iters=args.condense_iters,
                         condense_steps=args.condense_steps,
                         condense_train_type=args.condense_train_type,
                         init_only=bool(args.condense_init_only),
                         recondense_every=args.recondense_every), data
    if algo == "fedavg_affinity":
        from fedml_tpu.algorithms.fedavg_affinity import FedAvgAffinityAPI

        return FedAvgAffinityAPI(data, task, cfg), data
    if algo == "turboaggregate":
        from fedml_tpu.algorithms.turboaggregate import TurboAggregateAPI

        return TurboAggregateAPI(data, task, cfg), data
    if algo == "fednas":
        if args.stage == "train":
            from fedml_tpu.algorithms.fednas import FedNASTrainAPI

            return FedNASTrainAPI(
                data, cfg, mesh=mesh, genotype=args.arch,
                layers=args.nas_layers or 8,
                init_filters=args.init_channels,
                auxiliary=bool(args.auxiliary),
                auxiliary_weight=args.auxiliary_weight,
                drop_path_prob=args.drop_path_prob), data
        from fedml_tpu.algorithms.fednas import FedNASAPI

        return FedNASAPI(data, cfg, mesh=mesh, layers=args.nas_layers or 4,
                         init_filters=args.init_channels,
                         nas_method=args.nas_method, tau=args.tau), data
    if algo == "centralized":
        from fedml_tpu.centralized import CentralizedConfig, CentralizedTrainer

        ccfg = CentralizedConfig(epochs=args.epochs * args.comm_round,
                                 batch_size=args.batch_size, lr=args.lr,
                                 wd=args.wd, seed=args.seed)
        cmesh = None
        if args.mesh or args.model_parallel > 1:
            from fedml_tpu.mesh.mesh import make_2d_mesh, make_client_mesh

            tp = max(1, args.model_parallel)
            if tp > 1:
                cmesh = make_2d_mesh(args.mesh, tp, ("data", "model"),
                                     minor_flag="--model_parallel")
            else:
                cmesh = make_client_mesh(args.mesh or None, axis_name="data")
            dp = int(cmesh.shape["data"])
            if ccfg.batch_size % dp:
                raise ValueError(
                    f"--batch_size {ccfg.batch_size} not divisible by the "
                    f"data-parallel degree {dp} (batch rows shard over "
                    "'data')")
            if ccfg.eval_batch_size % dp:
                # eval batches are masked-padded, so rounding the eval
                # batch up to a divisible size changes layout only
                import dataclasses as _dc

                ccfg = _dc.replace(
                    ccfg,
                    eval_batch_size=-(-ccfg.eval_batch_size // dp) * dp)
        return CentralizedTrainer(task, data.train_x, data.train_y,
                                  data.test_x, data.test_y, ccfg,
                                  mesh=cmesh), data
    raise ValueError(f"unhandled algo {algo}")


def main(argv=None):
    from fedml_tpu.utils.metrics import (RunLogger, enable_compile_cache,
                                         set_process_title, setup_logging)

    args = add_args(argparse.ArgumentParser("fedml_tpu")).parse_args(argv)
    setup_logging(f"fedml-tpu-{args.algo}")
    set_process_title(f"fedml_tpu:{args.algo}:{args.dataset}")
    enable_compile_cache()
    log = logging.getLogger("cli")
    t0 = time.time()
    api, data = build_api(args)
    logger = RunLogger(args.run_dir, args.run_name,
                       config=vars(args))
    log.info("dataset=%s clients=%s algo=%s mesh=%d", args.dataset,
             data.num_clients if data is not None else "vertical", args.algo,
             args.mesh)

    import contextlib

    import jax

    round_loop = args.algo not in ("centralized", "vfl", "split_nn")
    stack = contextlib.ExitStack()
    if args.trace_dir and not (round_loop and args.trace_rounds > 0):
        # whole-run trace: single-shot algos, or --trace_rounds 0
        stack.enter_context(jax.profiler.trace(args.trace_dir))
        log.info("capturing XLA trace to %s", args.trace_dir)

    try:
        if args.algo == "centralized":
            api.train()
            for rec in api.history:
                logger.log(rec, step=rec.get("epoch"))
        elif args.algo in ("vfl", "split_nn"):
            hist = api.train(args.comm_round) if args.algo == "split_nn" else api.train()
            for i, rec in enumerate(hist or []):
                logger.log(rec, step=i)
                log.info("%s", rec)
        else:
            start_round = 0
            if args.resume and args.ckpt_dir:
                from fedml_tpu.core.checkpoint import latest_round, restore_round

                lr_ = latest_round(args.ckpt_dir)
                if lr_ is not None:
                    import numpy as np

                    tmpl = {"net": api.net, "server_opt_state": api.server_opt_state,
                            "rng": api.rng, "round": 0}
                    has_dp = getattr(api, "accountant", None) is not None
                    st = None
                    if has_dp:
                        # prefer the checkpoint's persisted RDP totals: a
                        # recompute with THIS run's q/z misstates epsilon
                        # when --noise_multiplier or client counts changed
                        # across the resume (server_manager persists the
                        # same key)
                        try:
                            st = restore_round(
                                args.ckpt_dir, lr_,
                                dict(tmpl, dp_rdp=np.asarray(
                                    api.accountant._rdp)))
                            api.accountant._rdp = np.asarray(st["dp_rdp"])
                        except Exception:
                            st = None  # pre-dp checkpoint: recompute below
                    if st is None:
                        st = restore_round(args.ckpt_dir, lr_, tmpl)
                        if has_dp:
                            # the epsilon claim is CUMULATIVE over the whole
                            # training run: re-charge the pre-resume rounds
                            # (only correct when q and z are unchanged; the
                            # persisted-totals path above avoids even that
                            # assumption)
                            api.accountant.step(api._dp_q, api._dp_z,
                                                rounds=int(st["round"]) + 1)
                    api.load_state(st["net"], st["server_opt_state"], st["rng"])
                    start_round = int(st["round"]) + 1
                    log.info("resumed from round %d", start_round - 1)
            trace_ctx = None
            if args.trace_dir and args.trace_rounds > 0:
                trace_ctx = stack.enter_context(contextlib.ExitStack())
                trace_ctx.enter_context(jax.profiler.trace(args.trace_dir))
                log.info("tracing rounds %d..%d to %s", start_round,
                         start_round + args.trace_rounds - 1, args.trace_dir)
            ckptr = None  # AsyncCheckpointer, created on first save
            for r in range(start_round, args.comm_round):
                if (trace_ctx is not None
                        and r - start_round == args.trace_rounds):
                    trace_ctx.close()  # stop after the trace window
                    trace_ctx = None
                metrics = api.run_round(r)
                if r % args.frequency_of_the_test == 0 or r == args.comm_round - 1:
                    if hasattr(api, "eval_record"):
                        # FedAvg-family engines: the shared record assembler
                        # (per-client aggregate on natural partitions)
                        rec = api.eval_record(r, metrics)
                    else:
                        ev = api.evaluate() if hasattr(api, "evaluate") else {}
                        if isinstance(ev, (int, float)):  # FedGKT: bare acc
                            ev = {"acc": float(ev), "loss": 0.0}
                        n = float(max(float(metrics.get("count", 1)), 1))
                        rec = {"round": r,
                               "train_loss": float(metrics.get("loss_sum", 0)) / n,
                               "train_acc": float(metrics.get("correct", 0)) / n}
                        if ev:
                            rec["test_acc"] = float(ev["acc"])
                            rec["test_loss"] = float(ev["loss"])
                    if getattr(api, "_poisoned", None) is not None:
                        rec["backdoor_acc"] = float(
                            api.evaluate_backdoor()["acc"])
                    if getattr(api, "accountant", None) is not None:
                        rec["epsilon"] = round(api.epsilon(args.dp_delta), 4)
                    logger.log(rec, step=r)
                    log.info("round %d: %s", r, rec)
                if args.ckpt_dir and (r % 10 == 0 or r == args.comm_round - 1):
                    extra = None
                    if getattr(api, "accountant", None) is not None:
                        import numpy as np

                        # cumulative RDP totals ride the checkpoint so a
                        # resume under different q/z still reports the true
                        # epsilon for the earlier rounds
                        extra = {"dp_rdp": np.asarray(api.accountant._rdp)}
                    if args.async_ckpt:
                        # lazily created; disk write overlaps later rounds
                        if ckptr is None:
                            from fedml_tpu.core.checkpoint import AsyncCheckpointer

                            ckptr = stack.enter_context(
                                AsyncCheckpointer(args.ckpt_dir))
                        ckptr.save(r, api.net, api.server_opt_state, api.rng,
                                   extra_state=extra)
                    else:
                        from fedml_tpu.core.checkpoint import save_round

                        save_round(args.ckpt_dir, r, api.net,
                                   api.server_opt_state, api.rng,
                                   extra_state=extra)
    finally:
        # stop the XLA trace even when training crashes — the trace
        # is most wanted precisely when a run misbehaves
        stack.close()
    logger.finish()
    log.info("done in %.1fs; summary=%s", time.time() - t0,
             json.dumps(logger.summary, default=float))


if __name__ == "__main__":
    main()
