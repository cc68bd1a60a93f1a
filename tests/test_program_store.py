"""The program store of ``core/compile_cache.py``, in one process: what its
key holds (every change it holds misses, and the program then computes
what plain ``jit`` does), what it does with an entry that does not load or
a program it cannot keep, and that the round program it loads names its
operations as plain ``jit``'s. The store serves only a TPU by itself; each
test here opts the CPU in, with a store of its own."""

import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.core import compile_cache as cc
from mmlspark_tpu.models import causal_lm
from mmlspark_tpu.ops import moe


def _opt_in(where, monkeypatch):
    monkeypatch.setattr(cc, "_STORE_PLATFORMS", ("tpu", "cpu"))
    monkeypatch.setattr(cc, "store_dir", lambda: str(where))


def _jax_cache(on: bool) -> None:
    from jax._src import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()   # JAX decides once whether it reads its cache


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A store of the test's own, with JAX's persistent cache out of the way:
    every program the test stores is compiled here, as on a first start."""
    _opt_in(tmp_path, monkeypatch)
    _jax_cache(False)
    yield tmp_path
    _jax_cache(True)


def _count(cache: str) -> float:
    return cc._M_COMPILES.labels(cache=cache).value


def _toy(x, y, *, scale):
    with jax.named_scope("toy.scope"):
        return jnp.tanh(x) * scale + y


def _program(data=None):
    """A fresh process's view of one stored program: nothing loaded yet."""
    return cc.stored_jit(_toy, name="tests.toy", data={"width": 4} if data is None else data,
                         static_argnames=("scale",))


def _plain(*args, **kw):
    return jax.jit(_toy, static_argnames=("scale",))(*args, **kw)


def _entries(where) -> list:
    return sorted(f for f in os.listdir(where) if f.endswith(".prog"))


def test_an_unchanged_key_loads_what_was_stored(store):
    args, kw = (jnp.arange(4.0), jnp.float32(1.0)), {"scale": 2}
    misses = _count("miss") + _count("hit")
    obs.clear_recent_spans()
    want = _program()(*args, **kw)
    assert _count("miss") + _count("hit") == misses + 1   # compiled once, then written
    (entry,) = _entries(store)
    (write,) = [s for s in obs.recent_spans() if s.name == "xla.store"]
    assert write.attrs == {"fun": "_toy", "bytes": os.path.getsize(store / entry)}
    stored = _count("stored")
    obs.clear_recent_spans()
    got = _program()(*args, **kw)
    assert _count("stored") == stored + 1
    assert np.array_equal(got, want)
    spans = {s.name: s for s in obs.recent_spans()}
    assert "xla.trace" not in spans and "xla.lower" not in spans
    load, read = spans["xla.compile"], spans["xla.retrieve"]
    assert load.attrs["cache"] == "stored" and load.attrs["fun"] == "_toy"
    assert load.attrs["bytes"] == os.path.getsize(store / _entries(store)[0])
    assert read.parent_id == load.span_id and read.trace_id == load.trace_id


def _patch_function(mp, undo):
    mp.setattr(moe, "route", lambda *a, **kw: None)


def _patch_constant(mp, undo):
    mp.setattr(causal_lm, "Q_BLOCK", causal_lm.Q_BLOCK // 2)


def _set_flag(mp, undo):
    was = jax.config.jax_default_matmul_precision
    undo.append(lambda: jax.config.update("jax_default_matmul_precision", was))
    jax.config.update("jax_default_matmul_precision", "highest")


# case -> (what the change does to the process, args, kwargs, identity's data)
_X, _Y = jnp.arange(4.0), jnp.float32(1.0)
MISSES = {
    "package_function": (_patch_function, (_X, _Y), {"scale": 2}, None),
    "module_constant": (_patch_constant, (_X, _Y), {"scale": 2}, None),
    "config_key": (None, (_X, _Y), {"scale": 2}, {"width": 5}),
    "shape": (None, (jnp.arange(5.0), _Y), {"scale": 2}, None),
    "dtype": (None, (_X.astype(jnp.bfloat16), _Y), {"scale": 2}, None),
    "sharding": (None, "device1", {"scale": 2}, None),
    "static_argument": (None, (_X, _Y), {"scale": 3}, None),
    "mmlspark_variable": (lambda mp, undo: mp.setenv("MMLSPARK_TPU_PALLAS", "1"),
                          (_X, _Y), {"scale": 2}, None),
    "jax_config_flag": (_set_flag, (_X, _Y), {"scale": 2}, None),
}


@pytest.mark.parametrize("case", sorted(MISSES))
def test_every_change_the_key_holds_misses(store, monkeypatch, case):
    change, args, kw, data = MISSES[case]
    _program()(_X, _Y, scale=2)
    stored = _count("stored")
    _program()(_X, _Y, scale=2)
    assert _count("stored") == stored + 1   # unchanged: a hit
    if args == "device1":
        args = (jax.device_put(_X, jax.devices()[1]), _Y)
    undo: list = []
    try:
        if change is not None:
            change(monkeypatch, undo)
        stored = _count("stored")
        got = _program(data)(*args, **kw)
        assert _count("stored") == stored, case
        assert np.array_equal(got, _plain(*args, **kw))
    finally:
        for step in undo:
            step()


def test_an_entry_that_does_not_load_is_compiled_counted_and_written_again(store):
    args, kw = (_X, _Y), {"scale": 2}
    want = _program()(*args, **kw)
    (entry,) = _entries(store)
    whole = (store / entry).read_bytes()
    (store / entry).write_bytes(whole[: len(whole) // 2])
    unloadable, stored = _count("unloadable"), _count("stored")
    assert np.array_equal(_program()(*args, **kw), want)
    assert _count("unloadable") == unloadable + 1 and _count("stored") == stored
    assert (store / entry).stat().st_size == len(whole)
    assert np.array_equal(_program()(*args, **kw), want)
    assert _count("stored") == stored + 1


def test_a_program_that_cannot_be_kept_runs_on_and_is_counted(store):
    def with_callback(x):
        return jax.pure_callback(lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    program = cc.stored_jit(with_callback, name="tests.callback")
    unstorable = _count("unstorable")
    assert np.array_equal(program(_X), np.arange(4.0) * 2)
    assert np.array_equal(program(_X + 1), np.arange(1.0, 5.0) * 2)
    assert _count("unstorable") == unstorable + 1
    assert _entries(store) == []


def test_a_value_described_by_its_address_is_not_keyed(store):
    class Opaque:
        def __hash__(self):
            return 0

        def __eq__(self, other):
            return isinstance(other, Opaque)

    program = cc.stored_jit(lambda x, *, tag: x + 1, name="tests.opaque", static_argnames=("tag",))
    unstorable = _count("unstorable")
    assert np.array_equal(program(_X, tag=Opaque()), np.arange(1.0, 5.0))
    assert _count("unstorable") == unstorable + 1
    assert _entries(store) == []


def test_a_load_counts_again_what_the_trace_counted(store):
    from mmlspark_tpu.ops import histogram

    def counted(x):
        histogram._count_lowering("plane", "pallas")
        return x * 2

    child = histogram._M_LOWERINGS.labels(op="plane", lowering="pallas")
    before = child.value
    cc.stored_jit(counted, name="tests.counted")(_X)
    assert child.value == before + 1
    cc.stored_jit(counted, name="tests.counted")(_X)   # loaded, not traced
    assert child.value == before + 2


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_the_stored_round_program_names_its_operations_as_plain_jit(store, monkeypatch):
    """The device-trace readers match scopes in each operation's HLO
    ``op_name`` (``jit(_scan_chunk)/while/body/...``): a loaded round
    program names them exactly as the decorator's plain ``jit`` did."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train

    T = importlib.import_module("mmlspark_tpu.models.gbdt.train")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "0")   # a grower with no host callback
    calls = []
    first = cc.StoredProgram._first_call

    def noting(self, args, kwargs, *rest):
        calls.append((args, kwargs))
        return first(self, args, kwargs, *rest)

    monkeypatch.setattr(cc.StoredProgram, "_first_call", noting)
    monkeypatch.setattr(T._scan_chunk, "_programs", {})
    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float64)
    cfg = TrainConfig(objective="binary", num_iterations=2, num_leaves=5,
                      min_data_in_leaf=5, seed=0)
    want = train(x, y, cfg, shard=False).to_model_string()
    stored = _count("stored")
    monkeypatch.setattr(T._scan_chunk, "_programs", {})
    assert train(x, y, cfg, shard=False).to_model_string() == want
    assert _count("stored") == stored + 1
    (loaded,) = T._scan_chunk._programs.values()
    args, kwargs = calls[-1]
    plain = jax.jit(T._scan_chunk.__wrapped__, static_argnames=tuple(T._scan_chunk._statics))
    names = _op_names(loaded)
    assert names == _op_names(plain.lower(*args, **kwargs).compile())
    assert any(n.startswith("jit(_scan_chunk)/while/body/") for n in names)


def test_a_cpu_executable_that_jaxs_cache_answered_is_not_stored(tmp_path, monkeypatch):
    """XLA:CPU's executables loaded from JAX's cache do not serialise whole:
    the store keeps none of them (a TPU's do, and are kept)."""
    _opt_in(tmp_path, monkeypatch)
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        def cached(x):
            return jnp.cos(x) * 3 - 1

        unstorable, outs = _count("unstorable"), []
        # called from one line, the two trace one module: the first writes
        # it into JAX's cache, the second (the process's own caches cleared)
        # is answered from there
        for program in (jax.jit(cached), cc.stored_jit(cached, name="tests.cached")):
            outs.append(program(jnp.arange(6.0)))
            jax.clear_caches()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert np.array_equal(outs[0], outs[1])
    assert _count("unstorable") == unstorable + 1
    assert _entries(tmp_path) == []
