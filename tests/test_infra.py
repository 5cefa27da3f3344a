"""Checkpoint/resume, metrics sink, CLI builder, centralized trainer."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.centralized import CentralizedConfig, CentralizedTrainer
from fedml_tpu.core.checkpoint import latest_round, restore_round, save_round
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_lr
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.utils.metrics import RunLogger
from fedml_tpu.utils.tree import tree_global_norm, tree_sub


def test_checkpoint_roundtrip(tmp_path):
    data = synthetic_lr(num_clients=4, dim=10, num_classes=3, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = FedAvgConfig(comm_round=4, client_num_in_total=4, client_num_per_round=4,
                       epochs=1, batch_size=16, lr=0.05, seed=0)
    api = FedAvgAPI(data, task, cfg)
    api.run_round(0)
    api.run_round(1)
    ck = str(tmp_path / "ck")
    save_round(ck, 1, api.net, api.server_opt_state, api.rng)
    net_after_r1 = api.net

    assert latest_round(ck) == 1
    tmpl = {"net": api.net, "server_opt_state": api.server_opt_state,
            "rng": api.rng, "round": 0}
    st = restore_round(ck, 1, tmpl)
    api2 = FedAvgAPI(data, task, cfg)
    api2.load_state(st["net"], st["server_opt_state"], st["rng"])
    d = tree_global_norm(tree_sub(api2.net.params, net_after_r1.params))
    assert float(d) == 0.0

    # resumed continuation == uninterrupted continuation
    api.run_round(2)
    api2.run_round(2)
    d = tree_global_norm(tree_sub(api2.net.params, api.net.params))
    assert float(d) < 1e-7


def test_npz_restore_rejects_structure_mismatch(tmp_path, monkeypatch):
    """The npz fallback maps leaves to the template BY INDEX: restoring a
    checkpoint whose leaf set differs from the template (e.g. a dp run's
    dp_rdp extra leaf, resumed without dp) must fail loudly, not shift
    every leaf by one and install RDP totals as model weights."""
    import numpy as np
    import orbax.checkpoint as ocp
    import pytest

    # force the npz fallback (orbax otherwise handles structure itself)
    monkeypatch.setattr(ocp, "StandardCheckpointer",
                        lambda *a, **k: (_ for _ in ()).throw(RuntimeError))
    data = synthetic_lr(num_clients=4, dim=10, num_classes=3, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=4,
                       client_num_per_round=4, epochs=1, batch_size=16,
                       lr=0.05, seed=0)
    api = FedAvgAPI(data, task, cfg)
    ck = str(tmp_path / "ck")
    save_round(ck, 0, api.net, api.server_opt_state, api.rng,
               extra_state={"dp_rdp": np.zeros(3)})
    base = {"net": api.net, "server_opt_state": api.server_opt_state,
            "rng": api.rng, "round": 0}
    with pytest.raises(ValueError, match="structure mismatch"):
        restore_round(ck, 0, base)  # template lacks the dp_rdp leaf
    # the matching template restores fine
    st = restore_round(ck, 0, dict(base, dp_rdp=np.zeros(3)))
    assert int(st["round"]) == 0


def test_async_checkpointer_equals_sync(tmp_path):
    """AsyncCheckpointer: background writes produce byte-equivalent
    restorable state (snapshot happens on the caller's thread, so donated
    buffers invalidated by later rounds can't corrupt it), one save in
    flight at a time, close() flushes, and a failed write surfaces."""
    import pytest

    from fedml_tpu.core.checkpoint import AsyncCheckpointer

    data = synthetic_lr(num_clients=4, dim=10, num_classes=3, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = FedAvgConfig(comm_round=4, client_num_in_total=4,
                       client_num_per_round=4, epochs=1, batch_size=16,
                       lr=0.05, seed=0)
    api = FedAvgAPI(data, task, cfg, donate=True)
    sync_ck, async_ck = str(tmp_path / "sync"), str(tmp_path / "async")
    with AsyncCheckpointer(async_ck) as ck:
        for r in range(3):
            api.run_round(r)
            save_round(sync_ck, r, api.net, api.server_opt_state, api.rng)
            ck.save(r, api.net, api.server_opt_state, api.rng)
            # keep training while the write is (possibly) still in flight
    assert latest_round(async_ck) == latest_round(sync_ck) == 2
    tmpl = {"net": api.net, "server_opt_state": api.server_opt_state,
            "rng": api.rng, "round": 0}
    a = restore_round(async_ck, 2, tmpl)
    s = restore_round(sync_ck, 2, tmpl)
    d = tree_global_norm(tree_sub(a["net"].params, s["net"].params))
    assert float(d) == 0.0

    # a failed background write raises on the next save/wait, not silently
    bad = AsyncCheckpointer(str(tmp_path))
    bad._inflight = bad._pool.submit(lambda: (_ for _ in ()).throw(
        OSError("disk gone")))
    with pytest.raises(OSError):
        bad.wait()
    bad.close()

    # ...but must not REPLACE an in-flight exception during unwinding
    bad2 = AsyncCheckpointer(str(tmp_path))
    with pytest.raises(RuntimeError, match="training crashed"):
        with bad2:
            bad2._inflight = bad2._pool.submit(lambda: (_ for _ in ()).throw(
                OSError("disk gone")))
            raise RuntimeError("training crashed")


def test_checkpoint_prune(tmp_path):
    data = synthetic_lr(num_clients=2, dim=6, num_classes=2, seed=0)
    task = classification_task(LogisticRegression(num_classes=2))
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=2, client_num_per_round=2,
                       batch_size=8)
    api = FedAvgAPI(data, task, cfg)
    ck = str(tmp_path / "ck")
    for r in range(5):
        save_round(ck, r, api.net, api.server_opt_state, api.rng, keep=2)
    kept = sorted(d for d in os.listdir(ck) if d.startswith("round_"))
    assert len(kept) == 2 and kept[-1].endswith("000004")


def test_run_logger(tmp_path):
    rl = RunLogger(str(tmp_path), "t1", config={"lr": 0.1})
    rl.log({"acc": 0.5}, step=0)
    rl.log({"acc": 0.7}, step=1)
    rl.finish()
    d = os.path.join(str(tmp_path), "t1")
    lines = open(os.path.join(d, "metrics.jsonl")).read().strip().split("\n")
    assert len(lines) == 2
    summary = json.load(open(os.path.join(d, "summary.json")))
    assert summary["acc"] == 0.7  # last value wins (wandb-summary semantics)
    assert json.load(open(os.path.join(d, "config.json")))["lr"] == 0.1


def test_run_logger_wandb_summary(tmp_path):
    """finish() emits the reference CI's summary-file interface: the
    reference reads Train/Acc from wandb/latest-run/files/wandb-summary.json
    (CI-script-fedavg.sh:42-46); the per-client aggregate (train_all_*) must
    win over the in-round sampled metric when both were logged."""
    rl = RunLogger(str(tmp_path), "t2")
    rl.log({"train_acc": 0.4, "train_all_acc": 0.55, "test_acc": 0.6,
            "round": 3}, step=3)
    rl.finish()
    for p in (os.path.join(str(tmp_path), "t2", "wandb-summary.json"),
              os.path.join(str(tmp_path), "latest-run", "files",
                           "wandb-summary.json")):
        ws = json.load(open(p))
        assert ws["Train/Acc"] == 0.55  # per-client aggregate, not in-round
        assert ws["Test/Acc"] == 0.6 and ws["round"] == 3
        assert ws["train_acc"] == 0.4  # raw keys preserved alongside


def test_centralized_trainer_learns():
    data = synthetic_lr(num_clients=4, dim=12, num_classes=3, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    tr = CentralizedTrainer(task, data.train_x, data.train_y, data.test_x,
                            data.test_y, CentralizedConfig(epochs=6, lr=0.1))
    tr.train()
    assert tr.history[-1]["test_acc"] > 0.6


def test_centralized_data_parallel_matches(mesh8):
    """pjit data-parallel epoch == single-device epoch (the DDP analogue)."""
    data = synthetic_lr(num_clients=4, dim=12, num_classes=3, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = CentralizedConfig(epochs=3, lr=0.1, batch_size=64, momentum=0.0)
    a = CentralizedTrainer(task, data.train_x, data.train_y, data.test_x,
                           data.test_y, cfg)
    b = CentralizedTrainer(task, data.train_x, data.train_y, data.test_x,
                           data.test_y, cfg, mesh=mesh8)
    a.train()
    b.train()
    d = tree_global_norm(tree_sub(a.net.params, b.net.params))
    assert float(d) / float(tree_global_norm(a.net.params)) < 1e-5


def test_cli_build_api_all_algos():
    from fedml_tpu.experiments.cli import add_args, build_api
    import argparse

    for algo in ["fedavg", "fedopt", "fedprox", "fednova", "fedavg_robust",
                 "hierarchical", "feddf", "fedavg_affinity", "turboaggregate",
                 "centralized"]:
        args = add_args(argparse.ArgumentParser()).parse_args([
            "--algo", algo, "--dataset", "mnist", "--model", "lr",
            "--client_num_in_total", "6", "--client_num_per_round", "4",
            "--comm_round", "1",
        ])
        api, data = build_api(args)
        assert api is not None

    # centralized with a ('data','model') TP mesh via --model_parallel
    args = add_args(argparse.ArgumentParser()).parse_args([
        "--algo", "centralized", "--dataset", "mnist", "--model", "lr",
        "--client_num_in_total", "6", "--comm_round", "1",
        "--mesh", "8", "--model_parallel", "4",
    ])
    api, _ = build_api(args)
    assert api.mesh is not None and api.mesh.axis_names == ("data", "model")


def test_cli_poison_type_wires_attack_and_backdoor_eval(tmp_path):
    """--poison_type: the synthetic 'pixel' attack and the real southwest
    archive both build a FedAvgRobustAPI with a poisoned eval set through
    the CLI (reference --poison_type parity, edge_case_examples
    data_loader.py:283)."""
    import argparse
    import pickle

    import numpy as np

    from fedml_tpu.experiments.cli import add_args, build_api

    base = ["--algo", "fedavg_robust", "--dataset", "mnist", "--model", "lr",
            "--client_num_in_total", "6", "--client_num_per_round", "4",
            "--comm_round", "1", "--poison_clients", "2"]
    args = add_args(argparse.ArgumentParser()).parse_args(
        base + ["--poison_type", "pixel"])
    api, data = build_api(args)
    assert api._poisoned is not None
    assert float(api.evaluate_backdoor()["acc"]) >= 0.0

    pkl = tmp_path / "sw.pkl"
    with open(pkl, "wb") as f:
        pickle.dump(np.random.RandomState(0).randint(
            0, 255, (12, 28, 28, 1), np.uint8), f)
    clean_args = add_args(argparse.ArgumentParser()).parse_args(base)
    clean_args.poison_type = "none"
    _, clean = build_api(clean_args)
    args = add_args(argparse.ArgumentParser()).parse_args(
        base + ["--poison_type", "southwest", "--edge_case_train", str(pkl),
                "--poison_target_label", "3"])
    api, data = build_api(args)
    assert api._poisoned is not None
    # the 12 edge rows actually landed in the two attacker partitions
    grown = (len(data.train_idx_map[0]) - len(clean.train_idx_map[0])
             + len(data.train_idx_map[1]) - len(clean.train_idx_map[1]))
    assert grown == 12
    assert len(data.train_x) == len(clean.train_x) + 12

    import pytest

    # real archive types refuse to run without a file (no silent synth swap)
    args = add_args(argparse.ArgumentParser()).parse_args(
        base + ["--poison_type", "greencar"])
    with pytest.raises(SystemExit):
        build_api(args)
    # poison flags on a non-robust algo refuse (no silent clean baseline)
    args = add_args(argparse.ArgumentParser()).parse_args(
        [*base, "--poison_type", "pixel"])
    args.algo = "fedavg"
    with pytest.raises(SystemExit):
        build_api(args)
    # zero attacker clients refuses
    args = add_args(argparse.ArgumentParser()).parse_args(
        base + ["--poison_type", "pixel", "--poison_clients", "0"])
    with pytest.raises(SystemExit):
        build_api(args)


def test_cli_fedseg_split_gkt_vfl_smoke(tmp_path):
    """CI-script parity: the remaining algorithm entries launch end-to-end
    through the unified CLI (tiny configs)."""
    from fedml_tpu.experiments.cli import main

    main(["--algo", "fedseg", "--dataset", "pascal_voc", "--comm_round", "1",
          "--client_num_per_round", "2", "--batch_size", "2", "--ci", "1",
          "--frequency_of_the_test", "1", "--run_dir", str(tmp_path)])
    main(["--algo", "split_nn", "--dataset", "mnist", "--client_num_in_total", "4",
          "--comm_round", "1", "--client_num_per_round", "2", "--batch_size", "8",
          "--max_batches", "2", "--ci", "1", "--run_dir", str(tmp_path)])
    main(["--algo", "fedgkt", "--dataset", "mnist", "--client_num_in_total", "4",
          "--comm_round", "1", "--client_num_per_round", "2", "--batch_size", "8",
          "--max_batches", "2", "--ci", "1", "--frequency_of_the_test", "1",
          "--run_dir", str(tmp_path)])
    main(["--algo", "vfl", "--dataset", "uci_susy", "--comm_round", "2",
          "--batch_size", "64", "--lr", "0.05", "--run_dir", str(tmp_path)])


@pytest.mark.parametrize("case", ["env_set", "env_unset", "two_cwds"])
def test_compile_cache_is_placed_from_outside(case, tmp_path):
    """enable_compile_cache: where JAX_COMPILATION_CACHE_DIR is set the code
    sets no directory at all; unset, it is one fixed path inside the
    checkout whatever the working directory. Fresh interpreters: the
    directory is process-global jax config (conftest has set it here)."""
    import subprocess
    import sys

    from fedml_tpu.utils.metrics import COMPILE_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    code = ("import jax\n"
            "from fedml_tpu.utils.metrics import enable_compile_cache\n"
            "seen = []\n"
            "upd = jax.config.update\n"
            "jax.config.update = lambda k, v: (seen.append(k), upd(k, v))\n"
            "d = enable_compile_cache()\n"
            "assert d == jax.config.jax_compilation_cache_dir\n"
            "print(d, 'jax_compilation_cache_dir' in seen,\n"
            "      jax.config.jax_persistent_cache_min_compile_time_secs)\n")

    def run(cwd, cache_env):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR", "FEDML_COMPILE_CACHE")}
        env["PYTHONPATH"] = repo
        env["HOME"] = str(tmp_path / "home")  # must not matter
        if cache_env:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_env
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    if case == "env_set":
        placed = str(tmp_path / "placed")
        assert run(repo, placed) == [placed, "False", "1.0"]
    elif case == "env_unset":
        assert run(repo, None) == [COMPILE_CACHE_DIR, "True", "1.0"]
    else:
        other = tmp_path / "elsewhere"
        other.mkdir()
        assert run(repo, None)[0] == run(str(other), None)[0] \
            == COMPILE_CACHE_DIR
