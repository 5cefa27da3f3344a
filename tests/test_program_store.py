"""The store of exported round programs (core/program_store.py): a block
program is traced once for each key, and loaded ever after.

CPU, a ``tmp_path`` compile cache directory, the smallest models that reach
the code: logistic regression, and a ResNet-8 where the model's own fields
or its convolution sites matter."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustAPI
from fedml_tpu.algorithms.fedopt import FedOptAPI
from fedml_tpu.core import program_store as ps
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models import create_model
from fedml_tpu.models.resnet import ResNetCIFAR
from fedml_tpu.obs import perf_instrument as perf
from fedml_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path):
    """The compile cache, and so the store, in a directory of the test's."""
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        yield str(tmp_path / "cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)


@pytest.fixture
def tests_are_keyed(monkeypatch):
    """Counts this directory among the roots whose code the key holds, for
    the tests of the walk's other rules: a hook that a test defines is
    unkeyable before the walk reaches what it closes over."""
    roots = ps._keyed_roots() + (os.path.join(ROOT, "tests", ""),)
    monkeypatch.setattr(ps, "_keyed_roots", lambda: roots)


@pytest.fixture(scope="module")
def images():
    return synthetic_images(num_clients=6, image_shape=(8, 8, 3),
                            num_classes=4, samples_per_client=24,
                            test_samples=16, seed=1, size_lognormal=False)


@pytest.fixture(scope="module")
def lr_task():
    return classification_task(create_model("lr", output_dim=4))


def _cfg(**kw):
    base = dict(comm_round=4, client_num_in_total=6, client_num_per_round=3,
                epochs=1, batch_size=8, lr=0.05, wd=0.0, seed=0,
                max_batches=2, frequency_of_the_test=100)
    base.update(kw)
    return FedAvgConfig(**base)


def _api(data, task, cfg=None, **kw):
    return FedAvgAPI(data, task, cfg or _cfg(), device_data=True, **kw)


def _block_args(api, rounds=2, start=0):
    _, (dev_x, dev_y, blocks, rnds) = api._place_block(
        api._pack_block_host(start, rounds))
    return (api.rng, api.net, api.server_opt_state, dev_x, dev_y, *blocks,
            rnds)


def _traced_fn(api):
    """The function the engine handed the store: the stored jit wraps the
    store's ``program``, which wraps it."""
    if not hasattr(api, "_block_fn"):
        api._block_fn = api._build_block_fn()
    return api._block_fn.__wrapped__.__wrapped__


def _ingredients(api, rounds=2):
    leaves, tree = jax.tree.flatten(_block_args(api, rounds))
    return ps.ingredients(_traced_fn(api), leaves, tree, (0, 1, 2),
                          api._block_trace_reads())


def _key(api, rounds=2):
    return ps.key_of(_ingredients(api, rounds))


def _records(cache_dir):
    d = os.path.join(cache_dir, ps.SUBDIR)
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def _params(api):
    return [np.asarray(v) for v in jax.tree.leaves(api.net)]


def _delta(before):
    return {k: v - before[k] for k, v in perf.program_store_counts().items()
            if v != before[k]}


# ------------------------------------------------------- miss, then hit
def test_miss_then_hit_in_one_process(images, lr_task, cache_dir):
    c0 = perf.program_store_counts()
    a = _api(images, lr_task)
    jax.block_until_ready(a.run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0}
    # the record is named by the key that the ingredients give
    assert _records(cache_dir) == [_key(a) + ps.SUFFIX]
    jax.block_until_ready(a.run_rounds(2, 2))  # jit's own cache: no lookup
    assert _delta(c0) == {"miss": 1.0}
    b = _api(images, lr_task)
    jax.block_until_ready(b.run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0, "hit": 1.0}
    assert len(_records(cache_dir)) == 1
    # a block of another length is another program
    jax.block_until_ready(b.run_rounds(2, 3))
    assert _delta(c0) == {"miss": 2.0, "hit": 1.0}
    assert len(_records(cache_dir)) == 2


_CHILD = """
import json, sys, hashlib
import jax, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
sys.path.insert(0, sys.argv[2])
from tests.test_program_store import _api, _cfg, _key, ResNetCIFAR
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.obs import perf_instrument as perf
data = synthetic_images(num_clients=6, image_shape=(8, 8, 3), num_classes=4,
                        samples_per_client=24, test_samples=16, seed=1,
                        size_lognormal=False)
task = classification_task(ResNetCIFAR(depth=8, num_classes=4,
                                       norm_type="group"))
api = _api(data, task)
key = _key(api)
jax.block_until_ready(api.run_rounds(0, 2))
h = hashlib.sha256()
for v in jax.tree.leaves(api.net):
    h.update(np.asarray(v).tobytes())
print(json.dumps({"key": key, "counts": perf.program_store_counts(),
                  "phases": perf.setup_phases(), "model": h.hexdigest(),
                  "conv_sites": perf.conv_sites()}))
"""


def test_miss_then_hit_across_two_processes(tmp_path):
    """A second PROCESS forms the same key, loads the program, and its
    trace of the round program is the stored jit's small one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, str(tmp_path / "cache"), ROOT],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["key"] == second["key"]
    assert first["counts"]["miss"] == 1 and first["counts"]["hit"] == 0
    assert second["counts"]["hit"] == 1 and second["counts"]["miss"] == 0
    assert first["model"] == second["model"]
    assert first["conv_sites"] == second["conv_sites"]
    # the first traced the model (inside the stored jit's trace); the
    # second did not
    assert second["phases"]["trace_s"] < 1.0
    assert second["phases"]["trace_s"] < 0.25 * first["phases"]["trace_s"]


# ------------------------------------------------- the loaded program
@pytest.mark.parametrize("path", ["miss", "hit"])
def test_loaded_program_equals_traced_bit_for_bit_and_donates(
        images, lr_task, cache_dir, path):
    jax.config.update("jax_compilation_cache_dir", None)
    plain = _api(images, lr_task)
    for s in (0, 2):
        jax.block_until_ready(plain.run_rounds(s, 2))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    if path == "hit":
        jax.block_until_ready(_api(images, lr_task).run_rounds(0, 2))
    c0 = perf.program_store_counts()
    stored = _api(images, lr_task)
    donated = [stored.rng] + jax.tree.leaves(stored.net)
    ms = []
    for s in (0, 2):
        ms.append(jax.block_until_ready(stored.run_rounds(s, 2)))
    assert _delta(c0) == {path: 1.0}
    for x, y in zip(_params(plain), _params(stored)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(np.asarray(plain.rng),
                                  np.asarray(stored.rng))
    assert set(ms[0]) == {"loss_sum", "correct", "count"}
    assert all(v.is_deleted() for v in donated)
    # the state a block returns is as free to move as the traced
    # program's: a committed array would compile the next program anew
    assert not any(v.committed for v in jax.tree.leaves(stored.net))


def test_loaded_program_is_accounted_to_block_fn(images, lr_task, cache_dir):
    """On a hit the round program's compile events are the stored jit's
    own: a small trace, a lowering and a compile or load, all under
    ``block_fn``, and nothing under ``_export``."""
    before = perf.variant_compile_stats()  # whatever shared this process
    jax.block_until_ready(_api(images, lr_task).run_rounds(0, 2))
    st0 = perf.variant_compile_stats()
    p0 = perf.setup_phases()
    api = _api(images, lr_task)
    jax.block_until_ready(api.run_rounds(0, 2))
    st = perf.variant_compile_stats()
    for key in ("trace_seconds", "lower_seconds", "compiles", "seconds"):
        assert st["block_fn"][key] > st0["block_fn"][key], key
    assert st.get(perf.EXPORT_VARIANT) == st0.get(perf.EXPORT_VARIANT)
    p = perf.setup_phases()
    assert p["trace_s"] + p["lower_s"] > p0["trace_s"] + p0["lower_s"]
    # what a miss traced on its way into the store is inside the stored
    # jit's own trace time, and is not summed a second time
    def since(variant, key):
        return st0[variant][key] - before.get(variant, {}).get(key, 0.0)

    assert since(perf.EXPORT_VARIANT, "lower_seconds") > 0
    assert 0 < since(perf.EXPORT_VARIANT, "trace_seconds") \
        <= since("block_fn", "trace_seconds")


def test_stored_jit_lowers_with_the_scopes(images, lr_task, cache_dir):
    """``.lower`` (what ``warmup()`` calls) goes through the store too, and
    the loaded program keeps its ops' scopes."""
    c0 = perf.program_store_counts()
    texts = []
    for _ in range(2):
        api = FedOptAPI(images, lr_task, _cfg(), device_data=True)
        api._block_fn = api._build_block_fn()
        texts.append(api._block_fn.lower(*_block_args(api)).as_text(
            debug_info=True))
    assert _delta(c0) == {"miss": 1.0, "hit": 1.0}
    for text in texts:
        for scope in ("fed_gather", "fed_aggregate", "fed_server_update"):
            assert scope in text, scope


# ------------------------------------------------------------- the key
def _clip(bound):
    def hook(net_k, net_global, rng):
        return jax.tree.map(lambda v: v.clip(-bound, bound), net_k)
    return hook


def _resnet_task(**kw):
    return classification_task(ResNetCIFAR(num_classes=4, **kw))


@pytest.mark.parametrize("what", [
    "lr", "wd", "epochs", "batch_size", "seed", "depth", "norm_type",
    "matmul_precision", "default_backend", "donate", "uniform_avg",
    "hook_constant", "argument_shape"])
def test_key_changes_with(images, lr_task, cache_dir, monkeypatch, what):
    if what in ("depth", "norm_type"):
        a = _api(images, _resnet_task(depth=8, norm_type="group"))
        b = _api(images, _resnet_task(
            **{"depth": 8, "norm_type": "group",
               what: {"depth": 14, "norm_type": "batch"}[what]}))
    elif what in ("lr", "wd", "epochs", "batch_size", "seed"):
        other = {"lr": 0.06, "wd": 1e-3, "epochs": 2, "batch_size": 4,
                 "seed": 1}[what]
        a, b = _api(images, lr_task), _api(images, lr_task,
                                           _cfg(**{what: other}))
    elif what == "hook_constant":
        # the clip hook of the robust engine closes over its bound
        a, b = (FedAvgRobustAPI(images, lr_task, _cfg(), device_data=True,
                                norm_bound=bound) for bound in (1.0, 2.0))
    elif what in ("donate", "uniform_avg"):
        a, b = _api(images, lr_task), _api(images, lr_task, **{what: True})
    else:
        a = b = _api(images, lr_task)
    ka = _key(a)
    if what == "matmul_precision":
        # another test file of this process may have left it at highest
        old = jax.config.jax_default_matmul_precision
        jax.config.update("jax_default_matmul_precision",
                          "default" if old == "highest" else "highest")
        try:
            kb = _key(b)
        finally:
            jax.config.update("jax_default_matmul_precision", old)
    elif what == "default_backend":
        # what ops/packed_conv.py chooses its convolutions by, and what a
        # test on a CPU tells it is a TPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kb = _key(b)
        monkeypatch.undo()
    elif what == "argument_shape":
        kb = _key(b, rounds=3)
    else:
        kb = _key(b)
    assert ka != kb
    # and with nothing changed the key is the same, engine after engine
    assert ka == _key(a)


def test_key_changes_with_one_byte_of_a_package_file(tmp_path):
    tree = tmp_path / "pkg"
    shutil.copytree(os.path.join(ROOT, "fedml_tpu", "ops"), tree)
    d0 = ps.tree_digest(str(tree))
    assert d0 == ps.tree_digest.__wrapped__(str(tree))
    target = tree / "packed_conv.py"
    target.write_bytes(target.read_bytes() + b"#")
    assert ps.tree_digest.__wrapped__(str(tree)) != d0
    # a file that is not Python is not read
    (tree / "notes.txt").write_text("x")
    target.write_bytes(target.read_bytes()[:-1])
    assert ps.tree_digest.__wrapped__(str(tree)) == d0


def test_two_populations_of_equal_shapes_share_key_and_module(
        lr_task, cache_dir):
    """The dataset's contents are not in the key, and need not be: the
    program that is stored does not depend on them."""
    apis = []
    for seed in (1, 2):
        data = synthetic_images(num_clients=6, image_shape=(8, 8, 3),
                                num_classes=4, samples_per_client=24,
                                test_samples=16, seed=seed,
                                size_lognormal=False)
        apis.append(_api(data, lr_task))
    assert not np.array_equal(apis[0].data.train_x, apis[1].data.train_x)
    assert _key(apis[0]) == _key(apis[1])
    modules = []
    for api in apis:
        leaves, tree = jax.tree.flatten(_block_args(api))
        modules.append(ps._export(_traced_fn(api), leaves, tree)[0])
    assert modules[0] == modules[1]


class _Opaque:
    scale = 0.5


def _opaque_hook():
    knob = _Opaque()

    def hook(net_k, net_global, rng):
        return jax.tree.map(lambda v: v * knob.scale, net_k)
    return hook


_USER_MODEL = """
import flax.linen as nn
from {layers} import Head

class Net(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return Head()(x.reshape((x.shape[0], -1)))
"""
_USER_LAYERS = """
import flax.linen as nn

class Head(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(4)(x) * 1.0
"""


def _user_engine(kind, images, lr_task, tmp_path, monkeypatch):
    """(an engine that runs user code under its trace, what Unkeyable says
    of it)."""
    if kind == "model_over_two_files":
        # the key could digest model.py; what model.py imports it cannot
        # follow, so an edit of layers.py would be a stale program
        tag = "".join(c for c in tmp_path.name if c.isalnum())
        (tmp_path / f"layers_{tag}.py").write_text(_USER_LAYERS)
        (tmp_path / f"model_{tag}.py").write_text(
            _USER_MODEL.format(layers=f"layers_{tag}"))
        monkeypatch.syspath_prepend(str(tmp_path))
        net = __import__(f"model_{tag}").Net()
        return _api(images, classification_task(net)), f"model_{tag}.py"
    if kind == "hook_of_no_file":  # <stdin>, exec, a notebook's cell
        ns = {"jax": jax}
        exec("def hook(net_k, net_global, rng):\n"
             "    return jax.tree.map(lambda v: v * 0.5, net_k)", ns)
        return (_api(images, lr_task, client_result_hook=ns["hook"]),
                "<string>")
    if kind == "hook_of_a_test_file":
        return (_api(images, lr_task, client_result_hook=_opaque_hook()),
                "test_program_store.py")
    assert kind == "unknown_object"
    monkeypatch.setattr(
        ps, "_keyed_roots", lambda roots=ps._keyed_roots(): roots + (
            os.path.join(ROOT, "tests", ""),))
    return (_api(images, lr_task, client_result_hook=_opaque_hook()),
            "_Opaque")


@pytest.mark.parametrize("kind", [
    "model_over_two_files", "hook_of_no_file", "hook_of_a_test_file",
    "unknown_object"])
def test_what_the_key_cannot_cover_is_unkeyable_and_runs_as_ever(
        images, lr_task, cache_dir, tmp_path, monkeypatch, kind):
    """Code outside the package and the versioned distributions may import
    and read anything, and an object no rule covers may hold anything: the
    engine traces, as with no store, and nothing is written."""
    jax.config.update("jax_compilation_cache_dir", None)
    plain, _ = _user_engine(kind, images, lr_task, tmp_path, monkeypatch)
    jax.block_until_ready(plain.run_rounds(0, 2))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    c0 = perf.program_store_counts()
    api, says = _user_engine(kind, images, lr_task, tmp_path, monkeypatch)
    with pytest.raises(ps.Unkeyable) as e:
        _key(api)
    assert says in str(e.value)
    jax.block_until_ready(api.run_rounds(0, 2))
    assert _delta(c0) == {"unkeyable": 1.0}
    assert _records(cache_dir) == []
    for x, y in zip(_params(plain), _params(api)):
        np.testing.assert_array_equal(x, y)


class _Sub(FedAvgAPI):
    """Overrides a method under the block's trace: what it reads from the
    engine the key cannot know."""

    def _agg_weights(self, nsamp):
        return super()._agg_weights(nsamp) * self.boost

    boost = 1.0


def test_subclass_that_overrides_the_trace_path_is_unkeyable(
        images, lr_task, cache_dir):
    c0 = perf.program_store_counts()
    api = _Sub(images, lr_task, _cfg(), device_data=True)
    jax.block_until_ready(api.run_rounds(0, 2))
    assert _delta(c0) == {"unkeyable": 1.0}
    assert _records(cache_dir) == []


@pytest.mark.parametrize("fault", ["truncated", "another_jax"])
def test_bad_record_reads_stale_and_is_written_anew(images, lr_task,
                                                    cache_dir, fault):
    a = _api(images, lr_task)
    jax.block_until_ready(a.run_rounds(0, 2))
    (name,) = _records(cache_dir)
    path = os.path.join(cache_dir, ps.SUBDIR, name)
    good = open(path, "rb").read()
    if fault == "truncated":
        bad = good[: len(good) - 100]
    else:
        line, _, rest = good.partition(b"\n")
        header = json.loads(line)
        header["environment"]["versions"]["jax"] = "0.0.1"
        bad = json.dumps(header, sort_keys=True).encode() + b"\n" + rest
    with open(path, "wb") as f:
        f.write(bad)
    c0 = perf.program_store_counts()
    b = _api(images, lr_task)
    jax.block_until_ready(b.run_rounds(0, 2))
    assert _delta(c0) == {"stale": 1.0}
    # whole again, under the same key (not byte for byte: the module's
    # locations hold the call stack of the dispatch that traced it)
    header, exported, tree = ps.read_record(path)
    was = json.loads(good.partition(b"\n")[0])
    assert {k: v for k, v in header.items() if k != "sizes"} \
        == {k: v for k, v in was.items() if k != "sizes"}
    assert len(tree) == was["sizes"][1]
    for x, y in zip(_params(a), _params(b)):
        np.testing.assert_array_equal(x, y)
    jax.block_until_ready(_api(images, lr_task).run_rounds(0, 2))
    assert _delta(c0) == {"stale": 1.0, "hit": 1.0}


def test_refused_export_counts_error_and_traces_as_ever(
        images, lr_task, cache_dir, monkeypatch):
    """What jax refuses to export (a Pallas kernel's custom call) traces
    as ever and leaves no record: the next engine asks again."""
    def refuse(*a, **k):
        raise ValueError("custom call target with no compatibility "
                         "guarantee")

    jax.config.update("jax_compilation_cache_dir", None)
    plain = _api(images, lr_task)
    jax.block_until_ready(plain.run_rounds(0, 2))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    c0 = perf.program_store_counts()
    with monkeypatch.context() as m:
        m.setattr(jax.export, "export", refuse)
        a = _api(images, lr_task)
        jax.block_until_ready(a.run_rounds(0, 2))
    assert _delta(c0) == {"error": 1.0}
    assert _records(cache_dir) == []
    b = _api(images, lr_task)  # jax.export is itself again
    jax.block_until_ready(b.run_rounds(0, 2))
    assert _delta(c0) == {"error": 1.0, "miss": 1.0}
    for x, y, z in zip(_params(plain), _params(a), _params(b)):
        np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(x, z)


def test_a_jax_that_cannot_call_a_stored_program_counts_error(
        images, lr_task, cache_dir, monkeypatch):
    """The stored program is called through jax's private lowering of
    ``call_exported``; where a jax has not got it the engine gets the
    plain jit, and the counter says so."""
    monkeypatch.setattr(ps, "_stored_program_p", lambda: None)
    c0 = perf.program_store_counts()
    api = _api(images, lr_task)
    api._block_fn = api._build_block_fn()
    assert _delta(c0) == {"error": 1.0}
    assert api._block_fn.__wrapped__.__qualname__.endswith(
        "_build_block_fn.<locals>.block_fn")
    jax.block_until_ready(api.run_rounds(0, 2))
    assert _delta(c0) == {"error": 1.0} and _records(cache_dir) == []


def test_exported_call_under_jit_still_commits_its_outputs():
    """Why ``_stored_program_p`` exists. If this fails, jax has mended
    ``jaxpr_transfer_mem_kinds`` (pxla.py): delete the private primitive
    and call ``Exported.call`` in ``_call_record``."""
    exported = jax.export.export(jax.jit(lambda x: x * 2))(
        jax.ShapeDtypeStruct((3,), np.float32))
    x = jax.numpy.ones(3)
    assert not x.committed
    assert jax.jit(exported.call)(x).committed
    (y,) = jax.jit(
        lambda x: ps._stored_program_p().bind(x, exported=exported))(x)
    assert not y.committed
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones(3, np.float32))


def test_hit_replays_the_convolution_sites_the_miss_counted(
        images, cache_dir):
    task = _resnet_task(depth=8, norm_type="group")
    s0 = perf.conv_site_counts()
    jax.block_until_ready(_api(images, task).run_rounds(0, 2))
    s1 = perf.conv_site_counts()
    miss = {k: v - s0.get(k, 0) for k, v in s1.items() if v != s0.get(k, 0)}
    assert sum(miss.values()) >= 7  # ResNet-8: seven nn.Conv calls a trace
    c0 = perf.program_store_counts()
    jax.block_until_ready(_api(images, task).run_rounds(0, 2))
    assert _delta(c0) == {"hit": 1.0}
    s2 = perf.conv_site_counts()
    assert {k: v - s1.get(k, 0) for k, v in s2.items()
            if v != s1.get(k, 0)} == miss
    total = perf.conv_sites()
    assert total["packed"] + total["plain"] == sum(s2.values())


@pytest.fixture
def fresh_counts(monkeypatch):
    """Gives the process a registry and a matrix-work count of their own,
    as a new process starts with them, each time it is called."""
    def fresh():
        monkeypatch.setattr(perf, "REGISTRY", MetricsRegistry())
        monkeypatch.setattr(perf, "_matmul", {})

    return fresh


def _matmul_families():
    snap = perf.REGISTRY.snapshot()
    return {f: snap.get(f) for f in ("fed_matmul_flops_total",
                                     "fed_matmul_bytes_total")}


def test_hit_counts_the_matrix_work_the_miss_walked(
        images, lr_task, cache_dir, fresh_counts, monkeypatch):
    """A miss traces the block program once (the body the export runs,
    counted inside it) and walks that trace for its products a round; a
    hit, in a registry of its own, walks nothing and counts the same; a
    block of another length is another program with the same work a
    round."""
    from fedml_tpu.obs import work

    bodies = []
    export = ps._export

    def counting(fun, *rest):
        def body(*args):
            bodies.append(fun.__name__)
            return fun(*args)

        body.__name__ = fun.__name__
        return export(body, *rest)

    monkeypatch.setattr(ps, "_export", counting)
    fresh_counts()
    c0 = perf.program_store_counts()
    jax.block_until_ready(_api(images, lr_task).run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0} and bodies == ["block_fn"]
    miss, families = perf.matmul_ops(), _matmul_families()
    runs = {run for _, run, _, _ in miss}
    assert miss and runs == {"forward", "gradient"}
    fresh_counts()

    def no_walk(*a, **k):
        raise AssertionError("a hit walks nothing")

    monkeypatch.setattr(work, "matmul_work", no_walk)
    jax.block_until_ready(_api(images, lr_task).run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0, "hit": 1.0}
    assert perf.matmul_ops() == miss and _matmul_families() == families
    monkeypatch.undo()
    monkeypatch.setattr(ps, "_export", counting)
    fresh_counts()
    jax.block_until_ready(_api(images, lr_task).run_rounds(0, 3))
    assert _delta(c0) == {"miss": 2.0, "hit": 1.0} and len(bodies) == 2
    assert perf.matmul_ops() == miss


# ------------------------------------------------ where it stays out
def test_without_a_cache_directory_it_is_the_plain_jit(images, lr_task,
                                                       cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    assert ps.store_dir() is None
    c0 = perf.program_store_counts()
    api = _api(images, lr_task)
    api._block_fn = api._build_block_fn()
    # jax.jit of the engine's own function, nothing in between
    assert api._block_fn.__wrapped__.__qualname__.endswith(
        "_build_block_fn.<locals>.block_fn")
    jax.block_until_ready(api.run_rounds(0, 2))
    assert _delta(c0) == {} and not os.path.exists(cache_dir)
    jax.config.update("jax_compilation_cache_dir", "gs://bucket/cache")
    assert ps.store_dir() is None


def test_mesh_engine_writes_nothing(images, lr_task, cache_dir, mesh8):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:3]), ("clients",))
    c0 = perf.program_store_counts()
    api = _api(images, lr_task, mesh=mesh)
    jax.block_until_ready(api.run_rounds(0, 2))
    assert _delta(c0) == {} and _records(cache_dir) == []


# -------------------------------------------------------- the records
def test_two_threads_storing_one_key_leave_one_whole_record(tmp_path):
    path = str(tmp_path / ps.SUBDIR / ("k" + ps.SUFFIX))
    header = {"environment": {"versions": {"jax": jax.__version__}},
              "conv_sites": []}
    payloads = [bytes([i]) * (1 << 16) for i in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    seen, stop = [], threading.Event()

    def write(i):
        for _ in range(20):
            ps.write_record(path, header, payloads[i], b"tree")

    def read():
        while not stop.is_set():
            try:
                rec = ps.read_record(path)
            except ps._Stale as e:  # a torn record: the fault looked for
                seen.append(e)
                return
            if rec is not None:
                seen.append(rec[1])

    try:
        reader = threading.Thread(target=read)
        writers = [threading.Thread(target=write, args=(i,))
                   for i in range(len(payloads))]
        reader.start()
        for t in writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not reader.is_alive() and not any(t.is_alive() for t in writers)
    assert seen and all(s in payloads for s in seen)
    assert os.listdir(os.path.dirname(path)) == ["k" + ps.SUFFIX]
    _, exported, tree = ps.read_record(path)
    assert exported in payloads and tree == b"tree"


def test_store_keeps_the_newest_records(tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "MAX_RECORDS", 3)
    directory = tmp_path / ps.SUBDIR
    header = {"environment": {"versions": {"jax": jax.__version__}}}
    for i in range(5):
        path = str(directory / f"k{i}{ps.SUFFIX}")
        assert ps.write_record(path, header, b"program", b"tree")
        os.utime(path, (1000 + i, 1000 + i))
    assert sorted(os.listdir(directory)) == [
        f"k{i}{ps.SUFFIX}" for i in (2, 3, 4)]
    # a record that is loaded counts as new: the oldest other one goes
    os.utime(str(directory / f"k2{ps.SUFFIX}"))
    ps.write_record(str(directory / f"k5{ps.SUFFIX}"), header, b"p", b"t")
    assert sorted(os.listdir(directory)) == [
        f"k{i}{ps.SUFFIX}" for i in (2, 4, 5)]


# ---------------------------------------------------- fingerprint rules
@dataclasses.dataclass(frozen=True)
class _Spec:
    rate: float
    fn: object = None


def test_fingerprint_rules(tests_are_keyed):
    fp = ps.fingerprint
    assert fp(_Spec(0.1)) == fp(_Spec(0.1)) != fp(_Spec(0.2))
    assert fp(1) != fp(1.0) != fp("1") and fp(True) != fp(1)
    assert fp((1, 2)) != fp([1, 2]) and fp({"a": 1}) != fp({"a": 2})
    assert fp(np.float32(2)) != fp(np.float64(2))
    assert fp(np.arange(3)) == fp(np.arange(3)) != fp(np.arange(3.0))
    assert fp(jax.numpy.float32) != fp(jax.numpy.bfloat16)
    assert fp(_clip(1.0)) == fp(_clip(1.0)) != fp(_clip(1.5))
    assert fp(_Spec(0.1, _clip(1.0))) != fp(_Spec(0.1, _clip(2.0)))
    import functools
    assert fp(functools.partial(_clip, 1.0)) != fp(functools.partial(_clip,
                                                                     2.0))
    cyc = [1]
    cyc.append(cyc)
    assert fp(cyc) == fp(cyc)
    with pytest.raises(ps.Unkeyable, match="data, not configuration"):
        fp(np.zeros(1 << 20))
    with pytest.raises(ps.Unkeyable, match="no rule for"):
        fp(threading.Lock())
    # installed, but no version of it is in the key
    with pytest.raises(ps.Unkeyable, match="site-packages/_pytest"):
        fp(pytest.raises)
    assert fp(os.path.join) == fp(os.path.join)  # the standard library
