"""The measurement inside ``fit`` and ``transform`` (PR 25): the span trees
of a small ``LightGBMClassifier.fit`` and a small
``ImageFeaturizer.transform``, the histogram row counter against the fitted
model's own counts, the scope and kernel names in the grower's lowered
program, the ``xla.compile`` spans, and ``core.profiling.trace``."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import DataFrame, obs


@pytest.fixture()
def fresh_obs():
    obs.set_enabled(True)
    obs.clear_recent_spans()
    yield
    obs.set_enabled(True)


def _fit(monkeypatch, n=700, d=5, leaves=6, trees=3, weights=None, **kw):
    """A small fit through the masked grower (`_grow_tree`), the one the
    chip runs: Pallas forced on, so the kernels run in the interpreter."""
    from mmlspark_tpu.models.gbdt import LightGBMClassifier

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    r = np.random.default_rng(7)
    x = r.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * r.normal(size=n) > 0).astype(np.int32)
    cols = {"features": x, "label": y}
    if weights is not None:
        cols["w"] = weights
        kw["weight_col"] = "w"
    clf = LightGBMClassifier(num_iterations=trees, num_leaves=leaves, min_data_in_leaf=5,
                             max_bin=31, seed=0, **kw)
    t0 = time.time_ns()
    model = clf.fit(DataFrame.from_dict(cols))
    return model, (t0, time.time_ns()), (n, d, leaves, trees)


def _tree_checks(spans, root_name, bounds):
    """One root of that name; every other span of its trace hangs under it,
    lies inside its parent's interval and inside the call's epoch bounds."""
    roots = [s for s in spans if s.name == root_name]
    assert len(roots) == 1
    root = roots[0]
    assert root.parent_id is None
    tree = [s for s in spans if s.trace_id == root.trace_id]
    by_id = {s.span_id: s for s in tree}
    for s in tree:
        assert bounds[0] <= s.wall_ns and s.wall_ns + s.duration_ns <= bounds[1] + 1_000_000
        if s is root:
            continue
        assert s.parent_id in by_id, (s.name, "has no parent in its trace")
        p = by_id[s.parent_id]
        # perf_counter intervals: exact nesting on one thread
        assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns, (s.name, p.name)
    return root, tree


def _children(tree, parent):
    return [s.name for s in sorted(tree, key=lambda s: s.start_ns) if s.parent_id == parent.span_id]


def test_fit_yields_the_span_tree(monkeypatch, fresh_obs):
    _model, bounds, (n, d, leaves, trees) = _fit(monkeypatch)
    spans = [s for s in obs.recent_spans() if s.name.startswith("gbdt.")]
    root, tree = _tree_checks(spans, "gbdt.fit", bounds)
    assert len(tree) == len(spans), "one trace id per fit"
    assert root.attrs == {"rows": n, "features": d, "trees": trees, "num_leaves": leaves,
                          "devices": jax.device_count()}
    assert _children(tree, root) == [
        "gbdt.gather", "gbdt.bin_fit", "gbdt.bin_transform", "gbdt.upload", "gbdt.upload",
        "gbdt.chunk", "gbdt.model_string"]
    chunk = [s for s in tree if s.name == "gbdt.chunk"][0]
    assert _children(tree, chunk) == ["gbdt.chunk.dispatch", "gbdt.chunk.wait",
                                      "gbdt.chunk.unpack"]
    assert chunk.attrs["rounds"] == trees
    attrs = {s.name: s.attrs for s in tree}
    assert attrs["gbdt.bin_transform"] == {"cells": n * d}
    uploads = [s.attrs for s in tree if s.name == "gbdt.upload"]
    assert uploads[0]["bytes"] == n * d + 4 * n and uploads[1]["bytes"] == 8 * n


def test_nothing_is_recorded_with_the_registry_disabled(monkeypatch, fresh_obs):
    obs.set_enabled(False)
    before = obs.REGISTRY.snapshot().get("mmlspark_gbdt_hist_rows_total")
    _fit(monkeypatch, trees=1)
    _featurizer(batch=4).transform(DataFrame.from_dict({"image": _images(6)}))
    jax.jit(lambda v: v * 5 - 2)(jnp.arange(11.0)).block_until_ready()  # a compilation
    assert obs.recent_spans() == []
    assert obs.REGISTRY.snapshot().get("mmlspark_gbdt_hist_rows_total") == before


def _hist_rows():
    fam = obs.REGISTRY.snapshot()["mmlspark_gbdt_hist_rows_total"]
    return {labels["kind"]: value for labels, value in fam["samples"]}


def _right_child_rows(tree: dict) -> int:
    """Rows of the right child at each split, from the final leaf counts:
    split k moved rows out of leaf ``leaf[k]`` into the new leaf k+1, and
    every later split of either stays inside its subtree."""
    size = [int(c) for c in tree["counts"]]
    moved = 0
    for k in reversed(range(len(tree["leaf"]))):
        if tree["active"][k]:
            moved += size[k + 1]
            size[tree["leaf"][k]] += size[k + 1]
    return moved


def test_hist_rows_counter_matches_the_fitted_models_counts(monkeypatch, fresh_obs):
    before = _hist_rows() if "mmlspark_gbdt_hist_rows_total" in obs.REGISTRY.snapshot() else {}
    model, _bounds, (n, _d, leaves, trees) = _fit(monkeypatch)
    after = _hist_rows()
    streamed = after["streamed"] - before.get("streamed", 0)
    selected = after["selected"] - before.get("selected", 0)
    fitted = json.loads(model.get("model_string"))["trees"]
    assert len(fitted) == trees
    # one pass over all rows on the devices (padded to the mesh) per
    # histogram call: the root's and one per step
    n_dev = jax.device_count()
    assert streamed == trees * leaves * (-(-n // n_dev) * n_dev)
    assert selected == sum(n + _right_child_rows(t) for t in fitted)
    assert 0 < selected <= streamed


def test_hist_rows_selected_is_a_weighted_count_under_row_weights(monkeypatch, fresh_obs):
    n = 600
    before = _hist_rows() if "mmlspark_gbdt_hist_rows_total" in obs.REGISTRY.snapshot() else {}
    _fit(monkeypatch, n=n, trees=1, leaves=2, weights=np.full(n, 2.0, np.float32))
    after = _hist_rows()
    selected = after["selected"] - before.get("selected", 0)
    # the root pass alone selects every row at weight 2
    assert selected >= 2 * n
    assert after["streamed"] - before.get("streamed", 0) == 2 * n   # 600 = 8 x 75


def test_model_string_does_not_depend_on_the_instrumentation(monkeypatch, fresh_obs):
    on, _b, _s = _fit(monkeypatch)
    obs.set_enabled(False)
    off, _b, _s = _fit(monkeypatch)
    assert on.get("model_string") == off.get("model_string")


def test_growers_lowered_program_names_its_passes_and_its_kernel(monkeypatch):
    from mmlspark_tpu.models.gbdt.treegrow import grow_tree

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    n, d = 640, 4
    r = np.random.default_rng(0)
    bins = jnp.asarray(r.integers(0, 16, size=(n, d)), jnp.uint8)
    g = jnp.asarray(r.normal(size=n), jnp.float32)
    ones = jnp.ones((n,), jnp.float32)

    def grow(b, gr):
        return grow_tree(b, gr, ones, ones, num_leaves=4, lambda_l2=0.0, min_gain=0.0,
                         learning_rate=0.1, feature_mask=jnp.ones((d,), jnp.float32),
                         min_data_in_leaf=1, num_bins=16)

    text = jax.jit(grow).lower(bins, g).as_text(debug_info=True)
    for name in ("gbdt.hist.mask", "gbdt.hist.pad", "gbdt.hist.widen", "gbdt.best_split",
                 "gbdt.apply_split", "plane_histogram"):
        assert name in text, name


def _images(n, size=8):
    return np.random.default_rng(3).integers(0, 255, size=(n, size, size, 3), dtype=np.uint8)


def _featurizer(batch):
    from mmlspark_tpu.models import ImageFeaturizer

    def apply_fn(vs, x):
        return {"pool": x.mean(axis=(1, 2)) * vs["scale"], "logits": x.sum(axis=(1, 2, 3))}

    return ImageFeaturizer(input_col="image", output_col="features", batch_size=batch,
                           image_size=8, apply_fn=apply_fn,
                           variables={"scale": jnp.ones((3,), jnp.float32)})


def test_transform_yields_the_span_tree(fresh_obs):
    stage = _featurizer(batch=8)
    rows = 8 * 5 + 3   # six batches on the 8-device mesh, the last one padded
    df = DataFrame.from_dict({"image": _images(rows)})
    stage.transform(df)["features"]  # warm: compiles
    obs.clear_recent_spans()
    t0 = time.time_ns()
    feats = stage.transform(df)["features"]
    bounds = (t0, time.time_ns())
    assert feats.shape == (rows, 3)
    spans = [s for s in obs.recent_spans() if s.name.startswith(("featurize.", "xla_model."))]
    root, tree = _tree_checks(spans, "featurize.partition", bounds)
    assert len(tree) == len(spans), "one trace id per partition"
    assert root.attrs == {"rows": rows}
    assert _children(tree, root) == ["featurize.coerce", "xla_model.apply_batch"]
    apply = [s for s in tree if s.name == "xla_model.apply_batch"][0]
    batches = apply.attrs["batches"]
    assert apply.attrs["rows"] == rows and batches == 6
    kids = _children(tree, apply)
    # a lone call: the parent's sequence, and the wait for the turn a leaf
    assert kids[:2] == ["xla_model.prepare", "xla_model.turn"]
    assert apply.attrs["overlapped"] is False
    assert kids[-2:] == ["xla_model.drain", "xla_model.concat"]
    assert kids.count("xla_model.stage") == batches
    assert kids.count("xla_model.dispatch") == batches
    # four in flight: the loop waits once per batch from the fourth on
    assert kids.count("xla_model.backpressure") == batches - 3
    stage_bytes = {s.attrs["bytes"] for s in tree if s.name == "xla_model.stage"}
    assert stage_bytes == {8 * 8 * 8 * 3}


def test_a_forced_compilation_records_one_span_and_a_counter_tick(fresh_obs):
    from mmlspark_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()  # conftest already did: registering twice adds no listener

    def compiles():
        fam = obs.REGISTRY.snapshot().get("mmlspark_xla_compiles_total", {"samples": []})
        return sum(v for _labels, v in fam["samples"])

    salt = float(time.time_ns() % 1_000_003)   # a program no cache has seen
    fn = jax.jit(lambda v: jnp.sin(v) * salt + jnp.cos(v + salt))
    x = jnp.arange(13.0)
    x.block_until_ready()
    obs.clear_recent_spans()
    n0 = compiles()
    t0 = time.time_ns()
    fn(x).block_until_ready()
    t1 = time.time_ns()
    spans = obs.recent_spans("xla.compile")
    assert len(spans) == 1 and compiles() - n0 == 1
    sp = spans[0]
    assert sp.attrs["cache"] == "miss" and "lambda" in sp.attrs["fun"]
    assert sp.attrs["event"] == "/jax/core/compile/backend_compile_duration"
    assert t0 <= sp.wall_ns and sp.wall_ns + sp.duration_ns <= t1
    assert sp.parent_id is None
    fn(x).block_until_ready()  # compiled: no further request
    assert len(obs.recent_spans("xla.compile")) == 1
    # a compilation inside a span hangs under it: which call compiled
    with obs.span("some.caller") as caller:
        jax.jit(lambda v: jnp.tanh(v) - salt)(x).block_until_ready()
    inner = obs.recent_spans("xla.compile")[-1]
    assert inner.parent_id == caller.span_id and inner.trace_id == caller.trace_id


def test_profiling_trace_keeps_the_host_tracers_off_and_writes_the_spans(
        tmp_path, monkeypatch, fresh_obs):
    from mmlspark_tpu.core import profiling

    seen = {}
    real_start = jax.profiler.start_trace

    def start(log_dir, **kw):
        seen.update(kw)
        return real_start(log_dir, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    with obs.span("before.capture"):
        pass
    with profiling.trace(str(tmp_path)):
        with obs.span("inside.capture", attrs={"k": 1}):
            jnp.ones(3).block_until_ready()
    opts = seen["profiler_options"]
    assert opts.host_tracer_level == 0 and opts.python_tracer_level == 0
    with open(os.path.join(str(tmp_path), profiling.SPANS_FILE)) as f:
        written = json.load(f)
    names = [s["name"] for s in written["spans"]]
    assert "inside.capture" in names and "before.capture" not in names
    inside = [s for s in written["spans"] if s["name"] == "inside.capture"][0]
    assert inside["wall_ns"] >= written["capture_start_ns"] and inside["attrs"] == {"k": 1}
    assert any(fn.endswith(".xplane.pb") for _d, _s, files in os.walk(str(tmp_path))
               for fn in files)


# -- the cold path: a program's first call (PR 35) ---------------------------------

def _salt():
    return float(time.time_ns() % 1_000_003)   # a program no cache has seen


def _union_ns(spans):
    total, at = 0, None
    for s, e in sorted((sp.start_ns, sp.end_ns) for sp in spans):
        if at is None or s > at:
            total += e - s
            at = e
        elif e > at:
            total += e - at
            at = e
    return total


@pytest.mark.parametrize("kind,fun", [("xla.trace", "first_call"),
                                      ("xla.lower", "jit(first_call)"),
                                      ("xla.compile", "jit(first_call)")])
def test_a_first_call_under_a_span_records_its_stage_as_a_child(
        monkeypatch, fresh_obs, kind, fun):
    from mmlspark_tpu.core import compile_cache

    # jnp's own traces inside first_call's stay out however slow the machine
    monkeypatch.setattr(compile_cache, "_NESTED_TRACE_FLOOR_S", 3600.0)
    salt = _salt()

    def first_call(v):
        return jnp.sin(v) * salt + jnp.cos(v - salt)

    fn = jax.jit(first_call)
    x = jnp.arange(17.0)
    x.block_until_ready()
    obs.clear_recent_spans()
    with obs.span("some.caller") as caller:
        t0 = time.time_ns()
        fn(x).block_until_ready()
        t1 = time.time_ns()
    mine = [s for s in obs.recent_spans(kind) if s.parent_id == caller.span_id]
    assert len(mine) == 1, [(s.name, s.attrs) for s in obs.recent_spans()]
    sp = mine[0]
    assert sp.attrs["fun"] == fun and sp.trace_id == caller.trace_id
    assert t0 <= sp.wall_ns and sp.wall_ns + sp.duration_ns <= t1
    assert caller.start_ns <= sp.start_ns and sp.end_ns <= caller.end_ns
    # the three stages of one call follow each other
    order = [s.name for s in sorted(obs.recent_spans(), key=lambda s: s.start_ns)
             if s.parent_id == caller.span_id]
    assert order == ["xla.trace", "xla.lower", "xla.compile"]
    with obs.span("some.caller"):
        fn(x).block_until_ready()   # built: no further stage
    assert len(obs.recent_spans(kind)) == 1


@pytest.mark.parametrize("floor,nested", [(0.0, True), (3600.0, False)])
def test_a_jit_inside_a_jit_nests_its_trace_and_short_ones_are_left_out(
        monkeypatch, fresh_obs, floor, nested):
    from mmlspark_tpu.core import compile_cache

    # (the floor itself is 5 ms: a loaded machine traces slower than that)
    monkeypatch.setattr(compile_cache, "_NESTED_TRACE_FLOOR_S", floor)
    salt = _salt()

    @jax.jit
    def inner(v):
        return jnp.tanh(v) + salt

    def outer(v):
        for _ in range(3):
            v = inner(v) * jnp.cos(v)
        return v

    x = jnp.arange(9.0)
    x.block_until_ready()
    obs.clear_recent_spans()
    jax.jit(outer)(x).block_until_ready()
    traces = obs.recent_spans("xla.trace")
    funs = [s.attrs["fun"] for s in traces]
    assert funs.count("outer") == 1
    whole = [s for s in traces if s.attrs["fun"] == "outer"][0]
    if not nested:
        # jnp's own jitted functions and the inner jit fired events inside
        # outer's trace, all under the floor: none is a span, and outer's
        # own, as short, is one because nothing contained it
        assert funs == ["outer"]
        return
    assert "inner" in funs and len(traces) >= 3
    for s in traces:
        assert whole.start_ns <= s.start_ns and s.end_ns <= whole.end_ns
    assert _union_ns(traces) == whole.duration_ns < sum(s.duration_ns for s in traces)


def test_program_new_marks_the_call_that_built_a_shapes_program(fresh_obs):
    stage = _featurizer(batch=8)
    df = DataFrame.from_dict({"image": _images(11)})
    for first in (True, False):
        obs.clear_recent_spans()
        stage.transform(df)["features"]
        (call,) = obs.recent_spans("xla_model.apply_batch")
        if first:
            assert call.attrs["program_new"] is True and call.attrs["shape"] == [8, 8, 8, 3]
            built = [s for s in obs.recent_spans("xla.lower") if s.trace_id == call.trace_id]
            assert [s.attrs["fun"] for s in built] == ["jit(run)"]
        else:
            assert "program_new" not in call.attrs and "shape" not in call.attrs
            assert not obs.recent_spans("xla.lower")


_COLD_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
from mmlspark_tpu import obs
with obs.span("an.importer") as importer:
    import mmlspark_tpu.models.gbdt
from mmlspark_tpu.core.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
x = jnp.arange(19.0)
x.block_until_ready()

def f(v):
    return jnp.sin(v) * 3 - jnp.cos(v)

def call():   # one call site: the cache key holds the caller's source line
    with obs.span("a.caller"):
        jax.jit(f)(x).block_until_ready()

starts = []
for _ in range(2):
    jax.clear_caches()   # a new process, as far as jit can tell
    starts.append(len(obs.recent_spans()))
    call()
rows = [dict(s.to_dict(), start_ns=s.start_ns, end_ns=s.end_ns) for s in obs.recent_spans()]
print(json.dumps({"spans": rows, "starts": starts, "importer": importer.span_id}))
"""


@pytest.fixture(scope="module")
def cold_process(tmp_path_factory):
    """A fresh interpreter with a cache directory of its own: imports the
    packages, then calls a new ``jit`` of one function twice."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("xla_cache")))
    out = subprocess.run([sys.executable, "-c", _COLD_PROCESS], env=env, cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("module", ["mmlspark_tpu", "mmlspark_tpu.models",
                                    "mmlspark_tpu.models.gbdt"])
def test_each_package_records_one_import_span(cold_process, module):
    spans = [s for s in cold_process["spans"] if s["name"] == "mmlspark.import"]
    assert sorted(s["attrs"]["module"] for s in spans) == [
        "mmlspark_tpu", "mmlspark_tpu.models", "mmlspark_tpu.models.gbdt"]
    (sp,) = [s for s in spans if s["attrs"]["module"] == module]
    assert sp["duration_ms"] > 0
    if module == "mmlspark_tpu":
        # ``from mmlspark_tpu import obs`` ran it before any span was open
        assert sp["parent_id"] is None
    else:
        # imported under an open span, it nests through the thread's stack
        assert sp["parent_id"] == cold_process["importer"]


def test_a_cache_hit_records_its_retrieval_as_a_child_and_what_it_saved(cold_process):
    spans, (first, second) = cold_process["spans"], cold_process["starts"]

    def request(rows):
        (caller,) = [s for s in rows if s["name"] == "a.caller"]
        kids = [s for s in rows if s["parent_id"] == caller["span_id"]]
        (compile_,) = [s for s in kids if s["name"] == "xla.compile"]
        assert {s["name"] for s in kids} == {"xla.trace", "xla.lower", "xla.compile"}
        return compile_, [s for s in rows if s["name"] == "xla.retrieve"]

    miss, none = request(spans[first:second])
    assert miss["attrs"]["cache"] == "miss" and none == []
    assert "saved_s" not in miss["attrs"] and "retrieval_s" not in miss["attrs"]
    hit, (got,) = request(spans[second:])
    assert hit["attrs"]["cache"] == "hit" and hit["attrs"]["fun"] == "jit(f)"
    assert got["parent_id"] == hit["span_id"] and got["trace_id"] == hit["trace_id"]
    assert hit["start_ns"] <= got["start_ns"] and got["end_ns"] <= hit["end_ns"]
    assert hit["attrs"]["retrieval_s"] * 1e9 == pytest.approx(
        got["end_ns"] - got["start_ns"], abs=2)
    assert isinstance(hit["attrs"]["saved_s"], float)
