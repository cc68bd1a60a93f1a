"""Where the persistent XLA compile cache lives — decided in ONE place.

Every entry point that compiles (the ``fleet`` roles, the benchmark's
``chipbench/harness.py``, ``chip_smoke.py``'s phases,
``tests/conftest.py``) calls
:func:`enable_compile_cache` before its first dispatch. The directory is
part of the cache key's neighbourhood: a directory that moves never hits,
so it is never a temporary name, a pid or a time.

JAX's cache computes its key from the lowered module, so even a hit first
traces the function and lowers it (Mosaic lowering of every Pallas kernel
included). The package's own programs skip that on a warm start:
:func:`stored_jit` keeps their executables in a *program store* inside the
cache directory, keyed without tracing (:class:`StoredProgram`).
"""

from __future__ import annotations

import dis
import functools
import hashlib
import importlib
import importlib.metadata
import json
import os
import pickle
import sys
import threading
import time
import types
from typing import Any, Callable, Optional

import jax

from mmlspark_tpu import obs

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# jax.monitoring's duration events of a program's first call, in the order
# they fire: the function traced to a jaxpr (a ``jit`` inside it fires its
# own, nested), the jaxpr lowered to an MLIR module (a Pallas kernel's Mosaic
# lowering happens here), then the whole compile request: the cache key (the
# module serialised with its metadata, and hashed), and a backend
# compilation or, when the persistent cache hits, a retrieval in its place
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_EVENTS = (_TRACE_EVENT, _LOWER_EVENT, _COMPILE_EVENT, _RETRIEVAL_EVENT)

_M_COMPILES = obs.counter(
    "mmlspark_xla_compiles_total",
    "XLA compile requests of this process, by what answered them: "
    "cache=hit (loaded from the persistent compile cache) | miss (compiled "
    "by the backend) | stored (loaded from the program store, with no trace "
    "or lowering); and the program store's failures: cache=unstorable (a "
    "program that could not be keyed or serialised stays on jit) | "
    "unloadable (an entry that failed to load was compiled and rewritten)",
    labels=("cache",),
)
_tls = threading.local()
_listening = False


# the library's own jitted functions (``jnp.add``, ``jax.random.uniform``,
# ...) fire a trace event each inside a program's trace: thousands a
# program, a fraction of a millisecond each, enough to push every other
# span out of the ring. A trace shorter than this is held back until the
# thread's next event tells whether a longer one contained it
_NESTED_TRACE_FLOOR_S = 0.005
_HELD_MAX = 64


def _record(name: str, start_ns: int, end_ns: int, attrs: dict, **ids: object) -> None:
    ids.setdefault("trace_id", obs.current_trace_id())
    ids.setdefault("parent_id", obs.current_span_id())
    obs.record_span(name, start_ns, end_ns, attrs=attrs, **ids)


def _flush_held(held: list) -> None:
    """Short traces that no later trace contained: each was a call's own."""
    for start_ns, end_ns, fun, ids in held:
        _record("xla.trace", start_ns, end_ns, {"fun": fun}, **ids)
    del held[:]


def _on_duration(event: str, duration: float, **kw: object) -> None:
    """A first call's stages as spans ending now, under the span that was
    open when the call was made: which call traced, lowered and compiled,
    and when, on the clock of every other span. Spans of one thread may
    nest (a ``jit`` inside a ``jit`` fires its own ``xla.trace``): a reader
    takes the union of their intervals, never the sum of their durations."""
    if event == _SAVED_EVENT:
        # fires on a hit, just before the retrieval's own event; no interval
        _tls.saved = duration
        return
    if event not in _EVENTS or not obs.enabled():
        return
    end_ns = time.perf_counter_ns()
    start_ns = end_ns - int(duration * 1e9)
    fun = str(kw.get("fun_name", ""))
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    # what this event contains was nested in it (lowering traces too: a
    # primitive lowered through a Python function); a thread's events nest
    # or follow each other, so those are the last ones held
    while held and held[-1][0] >= start_ns:
        held.pop()
    if event == _TRACE_EVENT:
        if duration >= _NESTED_TRACE_FLOOR_S:
            _record("xla.trace", start_ns, end_ns, {"fun": fun})
        elif len(held) < _HELD_MAX:
            # (inside one long event the list only grows, with traces that
            # event will take out: past the cap they are not kept at all)
            held.append((start_ns, end_ns, fun, {"trace_id": obs.current_trace_id(),
                                                 "parent_id": obs.current_span_id()}))
        return
    # lowering and compiling follow a call's outermost trace: none is open
    _flush_held(held)
    if event == _LOWER_EVENT:
        _record("xla.lower", start_ns, end_ns, {"fun": fun})
    elif event == _RETRIEVAL_EVENT:
        # the request's ``xla.compile`` span is recorded when the request
        # ends; its ids are minted here, so that the retrieval is its child
        ids = {"span_id": obs.new_span_id(),
               "trace_id": obs.current_trace_id() or obs.new_trace_id()}
        # saved_s: the backend time of the process that compiled this
        # program, less this retrieval
        _tls.hit = (ids, {"retrieval_s": duration, "saved_s": getattr(_tls, "saved", None)})
        _record("xla.retrieve", start_ns, end_ns, {}, trace_id=ids["trace_id"],
                parent_id=ids["span_id"])
    else:
        # with the retrieval a child, the span's self time on a hit is the
        # cache key (the module serialised and hashed) and no more
        ids, on_hit = getattr(_tls, "hit", None) or ({}, None)
        _tls.hit = _tls.saved = None
        cache = "miss" if on_hit is None else "hit"
        _M_COMPILES.labels(cache=cache).inc()
        _record("xla.compile", start_ns, end_ns,
                {"event": event, "cache": cache, "fun": fun, **(on_hit or {})}, **ids)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside always wins: JAX reads
    the variable itself, so nothing is set in code and child processes
    inherit it untouched. Otherwise the cache is ``<checkout>/.jax_cache``.
    Must run before the process's first compilation (JAX opens the cache
    once). Also starts recording every program's first call
    (``xla.trace``, ``xla.lower``, ``xla.compile`` and ``xla.retrieve``
    spans, ``mmlspark_xla_compiles_total``)."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    # JAX leaves op metadata (named scopes, kernel names) out of the cache
    # key by default, so a program that differs from a cached one only by
    # names loads that one's executable, and a device trace then shows the
    # other program's names (seen on the chip: PERF.md section 6, PR 25).
    # With the metadata in the key, what a trace names is what was written
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    outer = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outer:
        return outer
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- the program store -----------------------------------------------------------

# the backends whose executables the store keeps. An XLA:CPU executable
# carries the machine features of the host that compiled it (loaded on
# another host it may fault with SIGILL), and a CPU test may patch JAX
# itself, which no digest of this package sees: tests opt the CPU in
_STORE_PLATFORMS = ("tpu",)
_STORE_SUBDIR = "mmlspark-programs"
_PACKAGE = "mmlspark_tpu"
_ENV_PREFIXES = ("MMLSPARK_", "JAX_")
_ENV_NAMES = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_MAGIC = b"mmlspark-program 1\n"
_PLAIN = (bool, int, float, complex, str, bytes, type(None), type(Ellipsis))
_STORE_GLOBAL = dis.opmap["STORE_GLOBAL"]


# the store's frames are no user's: left out of the source locations of what
# a program traces, they leave its module (and JAX's cache key) as jit's
jax._src.source_info_util.register_exclusion(os.path.abspath(__file__))


def _on_event(event: str, **kw: object) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _tls.cache_hit = True


jax.monitoring.register_event_listener(_on_event)


class _Unkeyable(Exception):
    """A value that would not be described alike in another process."""


def count_traced(counter: Any, **labels: str) -> None:
    """Count one on ``counter`` as a program's trace decides something (the
    lowering a histogram took). A program the store loads is not traced, so
    the store keeps what its trace counted and counts it again on a load."""
    counter.labels(**labels).inc()
    effects = getattr(_tls, "effects", None)
    if effects is not None:
        effects.append((counter.name, counter.label_names, labels))


def _canon(v: Any, mutable: bool = False) -> Optional[str]:
    """A plain value (numbers, strings, None; tuples and frozensets of them,
    with ``mutable`` lists, sets and dicts too) as a string that reads the
    same in every process; None for anything else."""
    if isinstance(v, _PLAIN):
        return f"{type(v).__name__}:{v!r}"
    if isinstance(v, tuple) or (mutable and isinstance(v, list)):
        items = [_canon(x, mutable) for x in v]
    elif isinstance(v, frozenset) or (mutable and isinstance(v, set)):
        items = [_canon(x, mutable) for x in v]
        if None not in items:
            items.sort()   # a set's order follows the process's string hashes
    elif mutable and isinstance(v, dict):
        items = []
        for k, x in v.items():
            ck, cx = _canon(k), _canon(x, mutable)
            if ck is None or cx is None:
                return None
            items.append(f"{ck}={cx}")
        items.sort()
    else:
        return None
    if None in items:
        return None
    return f"{type(v).__name__}({','.join(items)})"


def _qualname(t: type) -> str:
    return f"{t.__module__}.{t.__qualname__}"


def _describe_sharding(s: Any) -> str:
    mesh = getattr(s, "mesh", None)
    return (f"{type(s).__name__}({[d.id for d in s._device_assignment]},"
            f"{getattr(s, 'spec', None)!r},"
            f"{_describe(mesh) if isinstance(mesh, jax.sharding.Mesh) else None},"
            f"{getattr(s, 'memory_kind', None)!r})")


def _describe(v: Any) -> str:
    """A static argument or an identity's data, as every process describes
    it: plain values and containers of them, meshes, dataclasses field by
    field. Anything else raises :class:`_Unkeyable` (its ``repr`` may leave
    out what tells two values apart, or hold an address): the program
    stays on ``jit``."""
    c = _canon(v, mutable=True)
    if c is not None:
        return c
    if isinstance(v, (list, tuple)):
        return f"{type(v).__name__}[{','.join(_describe(x) for x in v)}]"
    if isinstance(v, dict):
        return "dict{" + ",".join(sorted(f"{_describe(k)}={_describe(x)}"
                                         for k, x in v.items())) + "}"
    if isinstance(v, jax.sharding.Mesh):
        return (f"Mesh({[d.id for d in v.devices.flat]},{v.devices.shape},"
                f"{v.axis_names!r},{getattr(v, 'axis_types', None)!r})")
    if hasattr(type(v), "__dataclass_fields__"):
        return _qualname(type(v)) + "(" + ",".join(
            f"{name}={_describe(getattr(v, name))}" for name in type(v).__dataclass_fields__
        ) + ")"
    raise _Unkeyable(_qualname(type(v)))


# code objects are immutable: each is hashed once a process (kept alive by
# the memo, so its id is never reused)
_code_memo: dict = {}


def _code_facts(co: types.CodeType) -> tuple:
    """(digest, names it rebinds with ``global``) of a code object and the
    code nested in it."""
    memo = _code_memo.get(id(co))
    if memo is not None and memo[0] is co:
        return memo[1], memo[2]
    h = hashlib.sha256()
    for part in (co.co_qualname, co.co_filename, co.co_firstlineno, co.co_argcount,
                 co.co_posonlyargcount, co.co_kwonlyargcount, co.co_flags, co.co_names,
                 co.co_varnames, co.co_freevars, co.co_cellvars):
        h.update(repr(part).encode() + b"\0")
    h.update(co.co_code)
    h.update(co.co_linetable)
    h.update(co.co_exceptiontable)
    rebinds: set = set()
    for c in co.co_consts:
        if isinstance(c, types.CodeType):
            digest, inner = _code_facts(c)
            h.update(digest.encode())
            rebinds |= inner
        else:
            h.update((_canon(c) or _qualname(type(c))).encode() + b"\0")
    if _STORE_GLOBAL in co.co_code[::2]:
        rebinds.update(i.argval for i in dis.get_instructions(co) if i.opname == "STORE_GLOBAL")
    facts = (h.hexdigest(), frozenset(rebinds))
    _code_memo[id(co)] = (co, *facts)
    return facts


def _live(v: Any, depth: int = 0) -> str:
    """What a module's or a class's attribute, a default or a closure's cell
    holds, as far as it can change a trace: code through every wrapper
    (``__wrapped__``, ``functools.partial``, methods), plain values, the
    package's own classes member by member; any other object by its type."""
    c = _canon(v, mutable=depth > 0)
    if c is not None:
        return c
    if depth > 6:
        return "deep"
    if isinstance(v, types.FunctionType):
        parts = [_code_facts(v.__code__)[0]]
        parts += [_live(d, depth + 1) for d in v.__defaults__ or ()]
        parts += [f"{k}={_live(d, depth + 1)}" for k, d in sorted((v.__kwdefaults__ or {}).items())]
        for cell in v.__closure__ or ():
            try:
                parts.append(_live(cell.cell_contents, depth + 1))
            except ValueError:   # a cell not yet filled
                parts.append("empty")
        return "fn(" + ",".join(parts) + ")"
    if isinstance(v, (staticmethod, classmethod)):
        return f"{type(v).__name__}:{_live(v.__func__, depth + 1)}"
    if isinstance(v, property):
        return "property:" + ",".join(_live(f, depth + 1) for f in (v.fget, v.fset, v.fdel))
    if isinstance(v, functools.partial):
        return (f"partial:{_live(v.func, depth + 1)}:"
                + ",".join(_live(a, depth + 1) for a in v.args) + ":"
                + ",".join(f"{k}={_live(a, depth + 1)}" for k, a in sorted(v.keywords.items())))
    if isinstance(v, types.ModuleType):
        return f"module:{v.__name__}"
    if isinstance(v, type):
        if depth > 2 or not v.__module__.startswith(_PACKAGE):
            return f"type:{_qualname(v)}"
        return f"class:{_qualname(v)}(" + ",".join(
            f"{k}={_live(x, depth + 1)}" for k, x in sorted(vars(v).items())
            if k not in ("__dict__", "__weakref__", "__doc__")) + ")"
    try:
        wrapped = getattr(v, "__wrapped__", None) if callable(v) else None
    except Exception:
        wrapped = None
    if wrapped is not None:
        return f"{_qualname(type(v))}:{_live(wrapped, depth + 1)}"
    return f"object:{_qualname(type(v))}"


def _rebound(mod: types.ModuleType, own: dict) -> set:
    """The module's names that its functions rebind (``global``): state of
    the process, not constants."""
    names: set = set()
    for v in list(own.values()):
        members = list(vars(v).values()) if isinstance(v, type) else [v]
        for f in members:
            f = getattr(f, "__func__", f)
            if isinstance(f, types.FunctionType) and f.__globals__ is own:
                names |= _code_facts(f.__code__)[1]
    return names


# (path, mtime, size) -> the file's sha256
_file_memo: dict = {}


def _file_digest(mod: types.ModuleType) -> str:
    path = getattr(mod, "__file__", None)
    try:
        st = os.stat(path)
    except (OSError, TypeError):
        return "nofile"
    k = (path, st.st_mtime_ns, st.st_size)
    d = _file_memo.get(k)
    if d is None:
        with open(path, "rb") as f:
            d = _file_memo[k] = hashlib.sha256(f.read()).hexdigest()
    return d


def _module_digest(mod: types.ModuleType) -> str:
    """The module's source file, and what it holds now: its functions' code,
    defaults and closures, its classes' members, its plain-valued names
    (those its functions rebind excepted); a tuple, list or dict of
    functions (a table of them) by its functions."""
    own = vars(mod)
    h = hashlib.sha256(_file_digest(mod).encode())
    skip = _rebound(mod, own)
    for name, v in sorted(list(own.items()), key=lambda kv: kv[0]):
        if name in skip or (name.startswith("__") and name.endswith("__")):
            continue
        if isinstance(v, (list, tuple, dict, set)) and _canon(v) is None:
            # state or a table: its callables count, its data do not
            items = v.values() if isinstance(v, dict) else v
            part = ",".join(_live(x, 1) for x in list(items)[:256] if callable(x))
        else:
            part = _live(v)
        h.update(f"{name}={part}\n".encode())
    return h.hexdigest()


def _package_modules() -> list:
    return sorted(n for n, m in list(sys.modules.items())
                  if m is not None and (n == _PACKAGE or n.startswith(_PACKAGE + ".")))


def _code_digest(names: list) -> str:
    """The package's live code, over the modules named (imported first
    where this process has not yet: a module a program's trace imported)."""
    h = hashlib.sha256()
    for name in names:
        mod = sys.modules.get(name) or importlib.import_module(name)
        h.update(f"{name}:{_module_digest(mod)}\n".encode())
    return h.hexdigest()


def _platform_facts(platform: str) -> str:
    devices = jax.devices(platform)
    versions = {"python": sys.version, "jax": jax.__version__}
    for dist in ("jaxlib", "libtpu", "libtpu-nightly"):
        try:
            versions[dist] = importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            pass
    env = sorted((k, v) for k, v in os.environ.items()
                 if k.startswith(_ENV_PREFIXES) or k in _ENV_NAMES)
    config = sorted((k, _canon(v, mutable=True) or repr(v)) for k, v in jax.config.values.items())
    # what this thread's context managers hold (jit keys on it too)
    context = repr(jax._src.config.trace_context())
    if " at 0x" in context:
        raise _Unkeyable("trace context")
    return json.dumps([versions, platform, devices[0].client.platform_version,
                       devices[0].device_kind, len(devices), env, config, context])


def _leaf_key(x: Any) -> str:
    aval = jax.typeof(x)
    where = _describe_sharding(x.sharding) if isinstance(x, jax.Array) else _qualname(type(x))
    return f"{aval.dtype.name}{list(aval.shape)}{'~' if aval.weak_type else ''}@{where}"


def store_dir() -> Optional[str]:
    """The program store of the persistent cache in use: a subdirectory of
    it, so a fresh cache directory is a fresh store. None without a cache."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    cache = jax.config.jax_compilation_cache_dir
    return os.path.join(cache, _STORE_SUBDIR) if cache else None


def _write_atomic(path: str, data: bytes) -> None:
    """A reader sees the whole entry or none: written under a name of its
    own, then renamed over the entry."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


class StoredProgram:
    """``jax.jit(fun, static_argnames=...)`` whose executables live in the
    program store, for a function the package can name (``identity``: the
    function's name and the plain data its closure reads).

    A signature's first call computes a key without tracing: the identity,
    the static arguments, each argument's tree, shape, dtype, weak type and
    sharding, the JAX, jaxlib and libtpu versions, the backend's platform
    version, device kind and count, every ``jax.config`` value, every
    ``MMLSPARK_*`` and ``JAX_*`` variable (and ``XLA_FLAGS``,
    ``LIBTPU_INIT_ARGS``), and a digest of the package's live code
    (:func:`_code_digest`, over the modules loaded when the entry was
    written: a monkeypatched function or constant misses). A hit loads the
    executable; a miss lowers and compiles (JAX's persistent cache in front
    of the backend, as for ``jit``) and writes the entry. Either way the
    signature's executable is called from then on. Only on the backends of
    ``_STORE_PLATFORMS`` and with a compile cache; elsewhere it is ``jit``.
    """

    def __init__(self, fun: Callable, name: str, data: Any = None,
                 static_argnames: tuple = ()) -> None:
        self._jit = jax.jit(fun, static_argnames=static_argnames)
        self._statics = frozenset(static_argnames)
        self._name = name
        self._data = data
        self._programs: dict = {}   # signature -> what this process calls
        self._lock = threading.Lock()
        functools.update_wrapper(self, fun)
        # jax.clear_caches() lets go of the loaded executables too
        jax._src.util.register_cache(self, f"program store: {name}")

    def cache_clear(self) -> None:
        self._programs.clear()

    def __getattr__(self, attr: str) -> Any:
        # the jit object's own API (lower, _cache_size, ...)
        return getattr(self._jit, attr)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        statics = {k: v for k, v in kwargs.items() if k in self._statics}
        dynamic = {k: v for k, v in kwargs.items() if k not in self._statics}
        leaves, tree = jax.tree_util.tree_flatten((args, dynamic))
        sig = (tuple(sorted(statics.items(), key=lambda kv: kv[0])), tree,
               tuple((jax.typeof(x), x.sharding if isinstance(x, jax.Array) else type(x))
                     for x in leaves))
        program = self._programs.get(sig)
        if program is None:
            with self._lock:
                program = self._programs.get(sig)
                if program is None:
                    program = self._programs[sig] = self._first_call(args, kwargs, statics,
                                                                     tree, leaves)
        return program(*args, **dynamic)

    def _platform(self, leaves: list) -> str:
        for x in leaves:
            if isinstance(x, jax.Array):
                return next(iter(x.sharding.device_set)).platform
        return jax.default_backend()

    def _first_call(self, args: tuple, kwargs: dict, statics: dict, tree: Any,
                    leaves: list) -> Callable:
        plain = functools.partial(self._jit, **statics)
        platform = self._platform(leaves)
        where = store_dir()
        if platform not in _STORE_PLATFORMS or where is None:
            return plain
        t0 = time.perf_counter_ns()
        try:
            base = hashlib.sha256(json.dumps([
                self._name, _describe(self._data),
                sorted((k, _describe(v)) for k, v in statics.items()),
                str(tree), [_leaf_key(x) for x in leaves], _platform_facts(platform),
            ]).encode()).hexdigest()
        except _Unkeyable:
            _M_COMPILES.labels(cache="unstorable").inc()
            return plain
        index = os.path.join(where, base + ".modules")
        try:
            with open(index) as f:
                names = json.load(f)
        except (OSError, ValueError):
            names = _package_modules()
        try:
            path = os.path.join(where, f"{base}-{_code_digest(names)}.prog")
        except ImportError:   # a module of the index that no longer imports
            path = None
        if path is not None and os.path.exists(path):
            loaded = self._load(path, platform, t0)
            if loaded is not None:
                return loaded
            _M_COMPILES.labels(cache="unloadable").inc()
        return self._compile_and_store(args, kwargs, where, base, platform)

    def _load(self, path: str, platform: str, t0: int) -> Optional[Callable]:
        """The entry's executable, recorded as the compile request it
        answered: an ``xla.compile`` span (``cache="stored"``) whose self
        time is the key, and an ``xla.retrieve`` child for reading and
        deserialising. None if the entry does not load."""
        from jax.experimental import serialize_executable

        ids = {"span_id": obs.new_span_id(),
               "trace_id": obs.current_trace_id() or obs.new_trace_id(),
               "parent_id": obs.current_span_id()}
        t1 = time.perf_counter_ns()
        try:
            with open(path, "rb") as f:
                blob = f.read()
            if not blob.startswith(_MAGIC):
                return None
            entry = pickle.loads(blob[len(_MAGIC):])
            by_id = {d.id: d for d in jax.devices(platform)}
            compiled = serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                backend=platform, execution_devices=[by_id[i] for i in entry["devices"]])
        except Exception:
            return None
        t2 = time.perf_counter_ns()
        for fam, label_names, labels in entry["effects"]:
            obs.counter(fam, labels=label_names).labels(**labels).inc()
        _M_COMPILES.labels(cache="stored").inc()
        if obs.enabled():
            _record("xla.retrieve", t1, t2, {}, trace_id=ids["trace_id"],
                    parent_id=ids["span_id"])
            _record("xla.compile", t0, t2,
                    {"cache": "stored", "fun": self.__name__, "bytes": len(blob),
                     "key_ms": (t1 - t0) / 1e6, "load_ms": (t2 - t1) / 1e6}, **ids)
        return compiled

    def _compile_and_store(self, args: tuple, kwargs: dict, where: str,
                           base: str, platform: str) -> Callable:
        from jax.experimental import serialize_executable

        effects = _tls.effects = []
        _tls.cache_hit = False
        try:
            compiled = self._jit.lower(*args, **kwargs).compile()
        finally:
            _tls.effects = None
        if _tls.cache_hit and platform == "cpu":
            # an XLA:CPU executable that JAX's cache answered does not
            # serialise whole (its loaded copy fails at its first run); a
            # TPU's does, and loads back to the same outputs
            _M_COMPILES.labels(cache="unstorable").inc()
            return compiled
        # serialising and writing, as an ``xla.store`` span: what a store
        # miss adds to the compile request
        with obs.span("xla.store", attrs={"fun": self.__name__}) as sp:
            try:
                payload, in_tree, out_tree = serialize_executable.serialize(compiled)
                devices = [d.id for d in compiled._executable.xla_executable.local_devices()]
                blob = _MAGIC + pickle.dumps({
                    "payload": payload, "in_tree": in_tree, "out_tree": out_tree,
                    "devices": devices, "effects": effects})
                # the modules loaded now include those the trace imported
                names = _package_modules()
                _write_atomic(os.path.join(where, f"{base}-{_code_digest(names)}.prog"), blob)
                _write_atomic(os.path.join(where, base + ".modules"), json.dumps(names).encode())
            except Exception:   # const_args, host callbacks, a store not writable
                _M_COMPILES.labels(cache="unstorable").inc()
                return compiled
            sp.set_attr("bytes", len(blob))
        return compiled


def stored_jit(fun: Optional[Callable] = None, *, name: str, data: Any = None,
               static_argnames: tuple = ()) -> Any:
    """:class:`StoredProgram` of ``fun``; without ``fun``, a decorator."""
    if fun is None:
        return functools.partial(stored_jit, name=name, data=data,
                                 static_argnames=static_argnames)
    return StoredProgram(fun, name, data, static_argnames)
