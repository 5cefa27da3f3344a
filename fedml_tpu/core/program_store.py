"""A store of exported round programs beside the persistent compile cache.

The compile cache keeps what a program's trace ENDS in (the executable),
so a warm process still pays the Python trace of the model to find out
which executable it wants: 31 to 34 s of a 60 s set-up for the vmapped
ResNet-56 block on the chip's host (PERF.md section 5). This module keeps
what the trace PRODUCES (``jax.export``'s StableHLO) under a key made of
everything the trace reads, so that a later process loads the program and
Python never traces the model (docs/PERFORMANCE.md §Stored round programs).

:func:`stored_jit` is the one entry: ``FedAvgAPI._build_block_fn`` hands
it the single-device block program. Without a compile cache directory it
returns ``jax.jit(fun)`` and nothing here runs. With one it returns a jit
of the same name whose body, run once for each abstract signature (jit's
own cache), forms the key, looks the record up and calls the stored
program; on a miss it traces ``fun`` once through ``jax.export``, stores
the result, and calls what it stored, so that the executable a first run
compiles is the one every later run finds in the compile cache.

**A stale program is a different result, not a faster one.** The key holds
every ``.py`` file of the package, the versions of what lowers the program,
the platform, jit's own trace context, the abstract arguments, the donated
positions and a fingerprint of every value the trace reads from the engine
(``reads``). :func:`fingerprint` walks values by rule and raises
:class:`Unkeyable` at the first value no rule covers: the engine then
traces as it always has. No ``id()`` and no ``hash()`` enter the key: it is
equal across processes.

Outcomes are counted in ``fed_program_store_total{outcome}`` and timed in
``fed_program_store_seconds_total{phase}`` (obs/perf_instrument.py). A miss
also walks the trace it exports for the program's matrix work
(``obs/work.py``) and keeps the counts in the record's header, which a hit
counts again (``fed_matmul_flops_total``, ``perf_instrument.matmul_ops``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import hashlib
import importlib.metadata
import json
import logging
import os
import pickle
import sys
import sysconfig
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.obs import perf_instrument as _perf
from fedml_tpu.obs import work

log = logging.getLogger("fedml_tpu.core.program_store")

# the subdirectory of the compile cache directory that holds the records.
# jax's own eviction (jax/_src/lru_cache.py) globs ``*-cache`` files at the
# top of the directory alone: a subdirectory is neither counted nor deleted
SUBDIR = "fed_programs"
SUFFIX = ".fedprog"
FORMAT = 1
# every edit under the package orphans its records: the store keeps the
# newest of them, by the time they were written or last loaded
MAX_RECORDS = 64
# arrays in a closure or a field are keyed by their bytes up to this size;
# a larger one is data, and data makes the engine unkeyable
MAX_ARRAY_BYTES = 1 << 16

PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what lowers the program: their versions are in the key, so a function
# defined in one of them is keyed by its code and what it closes over,
# and the file that defines it is not read
VERSIONED = ("jax", "jaxlib", "flax", "optax", "numpy")


class Unkeyable(Exception):
    """A value the trace reads that no rule of :func:`fingerprint` covers."""


# ------------------------------------------------------------- fingerprint
@functools.lru_cache(maxsize=4)
def tree_digest(root: str = PACKAGE_ROOT) -> str:
    """One digest over every ``.py`` file under ``root``: relative path and
    contents, in sorted order."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


@functools.lru_cache(maxsize=1)
def _keyed_roots() -> tuple:
    """Directories whose code the key already holds: the package (by
    :func:`tree_digest`), the versioned distributions and the standard
    library (by version). Code that lies anywhere else is unkeyable."""
    roots = [PACKAGE_ROOT]
    for name in VERSIONED:
        mod = sys.modules.get(name) or __import__(name)
        roots.append(os.path.dirname(os.path.abspath(mod.__file__)))
    return tuple(os.path.join(r, "") for r in roots)


def _is_keyed(path: str) -> bool:
    if path.startswith("<frozen "):  # the interpreter's own: its version
        return True
    path = os.path.abspath(path)
    if path.startswith(_keyed_roots()):
        return True
    # the standard library's directory often holds site-packages too
    stdlib = os.path.join(sysconfig.get_paths()["stdlib"], "")
    return path.startswith(stdlib) and not {
        "site-packages", "dist-packages"} & set(path.split(os.sep))


_PRIMITIVES = (type(None), bool, int, float, complex, str, bytes)
_UNSET = "<a field that is not set>"


@functools.lru_cache(maxsize=1)
def _jit_wrapper() -> type:
    return type(jax.jit(lambda: None))


class _Walk:
    """One walk of :func:`fingerprint`: feeds a canonical description of
    the value into a digest. ``open`` holds the containers and functions
    the walk is inside of, so that a cycle is described by how far up it
    closes (an object met twice otherwise is described twice: whether two
    equal tuples are one object differs between processes)."""

    def __init__(self):
        self.h = hashlib.sha256()
        self.open: list[int] = []

    def put(self, *tokens) -> None:
        for t in tokens:
            b = t if isinstance(t, bytes) else str(t).encode()
            self.h.update(len(b).to_bytes(8, "little") + b)

    @contextlib.contextmanager
    def inside(self, v):
        """Yields False where ``v`` is being walked already."""
        if id(v) in self.open:
            self.put("cycle", len(self.open) - self.open.index(id(v)))
            yield False
            return
        self.open.append(id(v))
        try:
            yield True
        finally:
            self.open.pop()

    def keyed(self, path: str | None, what: str, where: str) -> None:
        """Raises unless the code at ``path`` is code the key holds: a
        file under a keyed root. A model in the user's ``model.py`` may
        import its blocks from a ``layers.py``, and read any global: the
        key cannot follow a trace through code it has no digest of, and
        code of ``<stdin>``, ``exec`` or a notebook has no file at all."""
        if not path or not _is_keyed(path):
            raise Unkeyable(
                f"{where}: {what} is defined in {path or 'no file'}, outside "
                "the package and the versioned distributions")

    def module(self, name: str, what: str, where: str) -> None:
        if name not in sys.builtin_module_names:
            self.keyed(getattr(sys.modules.get(name), "__file__", None),
                       what, where)

    def cls(self, c: type, where: str) -> None:
        self.put("class", c.__module__, c.__qualname__)
        self.module(c.__module__, f"the class {c.__qualname__}", where)

    def code(self, c: types.CodeType) -> None:
        self.put("code", c.co_code, c.co_names, c.co_varnames,
                 c.co_freevars, c.co_argcount, c.co_kwonlyargcount, c.co_flags)
        for const in c.co_consts:
            if isinstance(const, types.CodeType):
                self.code(const)
            else:
                self.walk(const, "constant")

    def function(self, f: types.FunctionType, where: str) -> None:
        self.put("function", f.__module__, f.__qualname__)
        self.keyed(f.__code__.co_filename, f"the function {f.__qualname__}",
                   where)
        with self.inside(f) as first:
            if not first:
                return
            self.code(f.__code__)
            self.walk(f.__defaults__, f"{where}.__defaults__")
            self.walk(f.__kwdefaults__, f"{where}.__kwdefaults__")
            for name, cell in zip(f.__code__.co_freevars,
                                  f.__closure__ or ()):
                try:
                    value = cell.cell_contents
                except ValueError:  # an empty cell: the name is not bound
                    self.put("empty cell", name)
                    continue
                self.walk(value, f"{where}.<closure {name}>")

    def items(self, v, pairs, where: str) -> None:
        with self.inside(v) as first:
            if first:
                for name, item in pairs:
                    self.put("item", name)
                    self.walk(item, f"{where}.{name}")

    def walk(self, v, where: str) -> None:
        if isinstance(v, _PRIMITIVES):
            self.put(type(v).__name__, repr(v))
        elif isinstance(v, enum.Enum):
            self.cls(type(v), where)
            self.put(repr(v))
        elif isinstance(v, (np.dtype, np.generic)):
            self.put("numpy", type(v).__name__, repr(v))
        elif isinstance(v, type):
            self.cls(v, where)
        elif isinstance(v, types.ModuleType):
            self.put("module", v.__name__)
            self.module(v.__name__, f"the module {v.__name__}", where)
        elif isinstance(v, jax.core.Tracer):
            raise Unkeyable(f"{where}: a tracer")
        elif isinstance(v, (np.ndarray, jax.Array)):
            if v.nbytes > MAX_ARRAY_BYTES:
                raise Unkeyable(f"{where}: an array of {v.shape} {v.dtype} "
                                "is data, not configuration")
            a = np.asarray(v)
            self.put("array", a.dtype.str, a.shape, a.tobytes())
        elif isinstance(v, types.FunctionType):
            self.function(v, where)
        elif isinstance(v, types.BuiltinFunctionType) and isinstance(
                v.__self__, (types.ModuleType, type(None))):
            self.put("builtin", v.__module__, v.__qualname__)
        elif isinstance(v, types.MethodType):
            self.put("method")
            self.walk(v.__func__, where)
            self.walk(v.__self__, f"{where}.__self__")
        elif isinstance(v, (functools.partial, jax.tree_util.Partial)):
            self.put("partial")
            self.walk(v.func, f"{where}.func")
            self.walk(v.args, f"{where}.args")
            self.walk(v.keywords, f"{where}.keywords")
        elif isinstance(v, _jit_wrapper()):
            self.put("jit")
            self.walk(v.__wrapped__, where)
        elif dataclasses.is_dataclass(v):
            # a flax module is one; fields alone: what a module binds
            # later (scope, state) is made by the trace, not read by it
            self.cls(type(v), where)
            self.items(v, ((f.name, getattr(v, f.name, _UNSET))
                           for f in dataclasses.fields(v)), where)
        elif isinstance(v, tuple) and hasattr(v, "_fields"):
            self.cls(type(v), where)
            self.items(v, zip(v._fields, v), where)
        elif isinstance(v, (tuple, list)):
            self.put(type(v).__name__, len(v))
            self.items(v, enumerate(v), where)
        elif isinstance(v, dict):
            self.put("dict", len(v))
            with self.inside(v) as first:
                if first:
                    for k, item in v.items():
                        self.walk(k, f"{where}.<key>")
                        self.walk(item, f"{where}[{k!r}]")
        elif isinstance(v, (set, frozenset)):
            self.put(type(v).__name__, len(v))
            for d in sorted(fingerprint(item, where) for item in v):
                self.put(d)
        else:
            raise Unkeyable(f"{where}: no rule for a {type(v).__module__}."
                            f"{type(v).__qualname__}")


def fingerprint(value, where: str = "value") -> str:
    """A digest of ``value`` that is equal wherever the value would give the
    same trace: primitives, strings and enums by ``repr``; dataclasses,
    named tuples, tuples, lists and dicts by field; functions, partials,
    bound methods and jit wrappers by qualified name, code bytes and
    constants, defaults, closure cells and ``__self__``; classes and
    modules by name; small arrays by dtype, shape and bytes. Raises
    :class:`Unkeyable`, naming the place, for anything else, and for any
    function, class or module whose file the key does not hold: one
    outside the package, the versioned distributions and the standard
    library, or with no file."""
    w = _Walk()
    w.walk(value, where)
    return w.h.hexdigest()


# ---------------------------------------------------------------- the key
@functools.lru_cache(maxsize=None)
def _version(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def environment() -> dict:
    """What lowers a program and for which device, in clear."""
    dev = jax.devices()[0]
    return {
        "python": sys.version.split()[0],
        "versions": {d: _version(d) for d in VERSIONED + ("libtpu",)},
        "platform": dev.platform,
        # what ops/packed_conv.py chooses its convolutions by
        "default_backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "platform_version": dev.client.platform_version,
    }


def trace_settings() -> dict:
    """The jax settings a trace depends on: jit's own trace context (what
    its cache keys a trace on) and, by name, the ones a reader asks for."""
    names = ("jax_default_matmul_precision", "jax_enable_x64",
             "jax_default_prng_impl", "jax_numpy_dtype_promotion",
             "jax_numpy_rank_promotion")
    try:
        from jax._src.config import trace_context

        context = fingerprint(trace_context(), "jax trace context")
    except (ImportError, AttributeError):
        context = "not reachable in this jax"
    return {"named": {n: repr(getattr(jax.config, n)) for n in names},
            "trace_context": context}


def signature(leaves, in_tree) -> dict:
    """The abstract arguments of a call, in clear: the tree and each leaf's
    shape, dtype and weak type. (No sharding: the store serves the
    program of an engine with no mesh, which runs on one device.)"""
    avals = [jax.typeof(x) for x in leaves]
    return {"tree": str(in_tree),
            "leaves": [f"{a.dtype.name}{list(a.shape)}"
                       + ("w" if getattr(a, "weak_type", False) else "")
                       for a in avals]}


def ingredients(fun, leaves, in_tree, donate_argnums, reads: dict) -> dict:
    """Everything the key is made of, in clear or as digests: the record
    keeps it, so that a reader can ask two records why a run missed."""
    return {
        "format": FORMAT,
        "program": f"{fun.__module__}.{fun.__qualname__}",
        "package": tree_digest(),
        "environment": environment(),
        "settings": trace_settings(),
        "arguments": signature(leaves, in_tree),
        "donated": list(donate_argnums),
        "reads": {name: fingerprint(value, name)
                  for name, value in sorted(reads.items())},
    }


def key_of(ingr: dict) -> str:
    return hashlib.sha256(
        json.dumps(ingr, sort_keys=True).encode()).hexdigest()


# ------------------------------------------------------------- the records
def store_dir() -> str | None:
    """Where the records live: ``SUBDIR`` of the compile cache directory,
    or None where none is set or it is not a local path (``gs://``)."""
    d = getattr(jax.config, "jax_compilation_cache_dir", None)
    if not d or "://" in str(d):
        return None
    return os.path.join(os.path.abspath(os.fspath(d)), SUBDIR)


class _Stale(Exception):
    """A record that is there and cannot be used."""


def write_record(path: str, header: dict, exported: bytes,
                 out_tree: bytes) -> bool:
    """One record, whole or not at all: a line of JSON (the key's
    ingredients in clear, the trace-time counts, the sizes of what
    follows), then the serialized ``Exported``, then the pickled output
    tree. Written under a temporary name and renamed, because sibling
    ranks share the directory. A directory that cannot be written costs
    the next process its trace and this one a warning."""
    header = dict(header, sizes=[len(exported), len(out_tree)])
    tmp = None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(json.dumps(header, sort_keys=True).encode() + b"\n")
            f.write(exported)
            f.write(out_tree)
        os.replace(tmp, path)
        with contextlib.suppress(OSError):
            _evict(os.path.dirname(path))
        return True
    except OSError as e:
        log.warning("program store: %s is not written: %r", path, e)
        if tmp is not None:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
        return False


def _evict(directory: str) -> None:
    """Keep the ``MAX_RECORDS`` newest files of ``directory``. A sibling
    rank may be doing the same: a file that is gone is gone."""
    def mtime(name):
        try:
            return os.stat(os.path.join(directory, name)).st_mtime
        except OSError:
            return 0.0

    names = sorted(os.listdir(directory), key=mtime, reverse=True)
    for name in names[MAX_RECORDS:]:
        with contextlib.suppress(OSError):
            os.unlink(os.path.join(directory, name))


def read_record(path: str):
    """(header, exported bytes, pickled output tree) of the record at
    ``path``, None where there is none; :class:`_Stale` where it is cut
    short or was written by another jax."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    line, sep, rest = blob.partition(b"\n")
    try:
        header = json.loads(line)
        n_exp, n_tree = header["sizes"]
        written_by = header["environment"]["versions"]["jax"]
    except (ValueError, KeyError, TypeError) as e:
        raise _Stale(f"unreadable header: {e!r}") from e
    if not sep or len(rest) != n_exp + n_tree:
        raise _Stale(f"{len(rest)} bytes after the header, "
                     f"{n_exp + n_tree} expected")
    if written_by != jax.__version__:
        raise _Stale(f"written by jax {written_by}")
    return header, rest[:n_exp], rest[n_exp:]


def _export(fun, leaves, in_tree, rounds: int = 1):
    """Trace ``fun`` once over flat leaves (``Exported.serialize`` refuses
    a tree of unregistered node types; a ``PyTreeDef`` itself pickles).
    Returns (serialized program, pickled output tree, what the trace
    counted: ``perf_instrument.traced_since``). The export's own trace and
    lowering happen inside the stored jit's trace, which reports them: they
    are tagged apart (``EXPORT_VARIANT``), not counted twice. The export
    takes the jit's trace from jit's own cache, so the model is traced once,
    and that trace's jaxpr is walked for its matrix work (``obs/work.py``),
    ``rounds`` rounds of it."""
    out_trees = []

    def flat(*flat_args):
        out = fun(*jax.tree.unflatten(in_tree, flat_args))
        out_leaves, out_tree = jax.tree.flatten(out)
        out_trees.append(out_tree)
        return out_leaves

    flat.__name__ = flat.__qualname__ = fun.__name__
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                  weak_type=getattr(a, "weak_type", False))
             for a in map(jax.typeof, leaves)]
    before = _perf.traced_counts()
    jitted = jax.jit(flat)
    with _perf.attribute_compiles(_perf.EXPORT_VARIANT):
        traced = jitted.trace(*specs)
        exported = jax.export.export(jitted)(*specs)
    with _timed("walk"):
        _perf.record_matmul(work.matmul_work(traced.jaxpr, rounds))
    return (bytes(exported.serialize()), pickle.dumps(out_trees[-1]),
            _perf.traced_since(before))


@functools.lru_cache(maxsize=1)
def _stored_program_p():
    """The primitive a stored program is called through: jax's own
    ``call_exported`` under another name. ``Exported.call`` would do, but
    for one line of ``jax/_src/interpreters/pxla.py`` (jax 0.9.0,
    ``jaxpr_transfer_mem_kinds``): a jit whose jaxpr holds a primitive
    NAMED ``call_exported`` returns COMMITTED arrays. The engine's state
    would then enter its second dispatch, and every other program it is
    handed to, committed where it was not: one more compile of each, the
    block's inside the benchmark's window. Same abstract evaluation, same
    lowering, both jax's; None where this jax has not got them, and the
    store then stays out of the way."""
    try:
        from jax._src.export import _export
        from jax.extend.core import Primitive
        from jax.interpreters import mlir

        p = Primitive("fed_stored_program")
        p.multiple_results = True
        p.def_effectful_abstract_eval(_export._call_exported_abstract_eval)
        mlir.register_lowering(p, _export._call_exported_lowering)
        return p
    except (ImportError, AttributeError) as e:
        log.warning("program store: off, this jax cannot call a stored "
                    "program: %r", e)
        return None


def _call_record(record, leaves):
    """Call the stored program on ``leaves``. Anything it raises makes the
    record stale: the bytes do not deserialize, or the program rejects the
    arguments."""
    _, exported, out_tree = record
    try:
        program = jax.export.deserialize(bytearray(exported))
        tree = pickle.loads(out_tree)
        # an argument the program never reads (the client ids of a model
        # that draws nothing) is not an operand of the call: the jit then
        # drops it from the executable's parameters, as it does where it
        # traces the program itself
        kept = set(program.module_kept_var_idx)
        operands = [x if i in kept else jnp.zeros(x.shape, x.dtype)
                    for i, x in enumerate(leaves)]
        return jax.tree.unflatten(
            tree, _stored_program_p().bind(*operands, exported=program))
    except Exception as e:  # noqa: BLE001 — whatever it is: trace anew
        raise _Stale(f"the stored program does not load: {e!r}") from e


@contextlib.contextmanager
def _timed(phase: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _perf.record_program_store_seconds(phase, time.perf_counter() - t0)


def _run(fun, args, donate_argnums, reads, rounds):
    """The body of the stored jit: runs while jit traces it, once for each
    abstract signature."""
    directory = store_dir()
    if directory is None:  # the directory went since the jit was built
        return fun(*args)
    leaves, in_tree = jax.tree.flatten(args)
    with _timed("key"):
        try:
            values = reads()
            ingr = ingredients(fun, leaves, in_tree, donate_argnums, values)
        except (Unkeyable, RecursionError) as e:
            _perf.record_program_store("unkeyable")
            log.info("program store: %s traces as ever: %s", fun.__name__, e)
            return fun(*args)
    path = os.path.join(directory, key_of(ingr) + SUFFIX)
    # beside the digests, for a reader: what each value reads as, where
    # that says something (an address says nothing, and is not in the key)
    clear = {name: text for name, value in values.items()
             if "0x" not in (text := repr(value)[:400])}

    outcome = "miss"
    with _timed("load"):
        try:
            record = read_record(path)
            if record is not None:
                out = _call_record(record, leaves)
                _perf.replay_traced(record[0])
                _perf.record_program_store("hit")
                with contextlib.suppress(OSError):
                    os.utime(path)  # loaded now: among the newest again
                return out
        except _Stale as e:
            outcome = "stale"
            log.warning("program store: %s is stale and is written anew: %s",
                        path, e)

    with _timed("export"):
        try:
            exported, out_tree, counted = _export(
                fun, leaves, in_tree, rounds(*args) if rounds else 1)
            header = dict(ingr, **counted, reads_in_clear=clear)
            # a miss runs what it stored: the executable this process
            # compiles is the one a later process's hit finds in the
            # compile cache
            out = _call_record((header, exported, out_tree), leaves)
        except Exception as e:  # noqa: BLE001 — jax refuses the export (a
            # custom call such as a Pallas kernel) or the call (a context
            # of several devices), or fun itself raises, as the plain
            # trace below then does again for the caller to see
            _perf.record_program_store("error")
            log.warning("program store: %s is not exported and traces as "
                        "ever: %r", fun.__name__, e)
            return fun(*args)
        write_record(path, header, exported, out_tree)
    _perf.record_program_store(outcome)
    return out


def stored_jit(fun, *, donate_argnums=(), reads, rounds=None):
    """``jax.jit(fun, donate_argnums=...)`` where no compile cache
    directory is set; else a jit of the same name, donation and interface
    (``.lower``) that loads ``fun``'s program from the store in place of
    tracing it (module docstring). ``reads()`` gives, by name, every value
    ``fun``'s trace reads that is not one of its arguments, or raises
    :class:`Unkeyable`; it is called when the first call is traced.
    ``rounds(*args)`` gives the rounds one call runs, by which the matrix
    work a miss counts is divided (one where it is None). The plain jit
    counts no matrix work."""
    if store_dir() is None:
        return jax.jit(fun, donate_argnums=donate_argnums)
    _perf.ensure_program_store_families()
    _perf.ensure_matmul_families()
    if _stored_program_p() is None:  # said in the log; and counted, here
        _perf.record_program_store("error")
        return jax.jit(fun, donate_argnums=donate_argnums)

    def program(*args):
        return _run(fun, args, donate_argnums, reads, rounds)

    # perf_instrument.ROUND_PROGRAMS attributes compile events by this name
    program.__name__ = program.__qualname__ = fun.__name__
    program.__wrapped__ = fun
    return jax.jit(program, donate_argnums=donate_argnums)
