"""The benchmark's yardstick, checked without a chip: the reduction from a
trace to what the per-layer metrics read (on a hand-made trace with known
answers and on small recorded pieces of the cells' own chip traces), the
work counted from shapes against XLA's own count, and the cells' real
shapes compiled for a described v5e:2x2 (topology in a fixture, never at
import)."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec, work, xplane  # noqa: E402

MS = 1e6  # ns


def _hand_made() -> dict:
    """Two devices, a 100 ms window, two fits; answers worked out by hand."""
    dev0 = [
        ["while:while.2", 10 * MS, 25 * MS],               # 10..35, spans its body
        ["fusion:fusion.1", 10 * MS, 10 * MS],             # 10..20
        ["custom-call:closed_call.7", 15 * MS, 15 * MS],   # 15..30 (overlaps: union 10..30)
        ["all-reduce:all-reduce.3", 30 * MS, 5 * MS],      # 30..35
        ["fusion:fusion.1", 60 * MS, 20 * MS],             # 60..80
        ["custom-call:closed_call.7", 95 * MS, 10 * MS],   # 95..105, cut at the window's end
        ["fusion:fusion.9", 120 * MS, 5 * MS],             # outside the window
        ["custom-call:sort.4", 62 * MS, 0.01 * MS],        # 10 us: no pass over the rows
    ]
    dev1 = [["fusion:fusion.1", 0.0, 50 * MS]]
    spans = [
        ["chipbench.window", 0.0, 100 * MS],
        ["chipbench.fit", 5 * MS, 35 * MS],        # 5..40: device work 10..35
        ["chipbench.fit", 50 * MS, 45 * MS],       # 50..95: device work 60..80
        ["chipbench.warmup", -50 * MS, 20 * MS],   # before the window
    ]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "spans": spans}


def test_reduce_hand_made_trace():
    r = xplane.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["devices"] == 2
    # device 0: 10..35, 60..80, 95..100 = 25 + 20 + 5 ms; device 1: 50 ms
    assert r["busy_s_each"] == pytest.approx([0.050, 0.050])
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["op_seconds"]["fusion:fusion.1"] == pytest.approx(0.030)
    assert r["op_counts"]["custom-call:closed_call.7"] == 2
    assert "fusion:fusion.9" not in r["op_seconds"]
    assert "while:while.2" not in r["op_seconds"]  # a loop is not a leaf
    assert r["kernel_s"] == pytest.approx(0.02501) and r["kernel_calls"] == 3
    assert r["all_reduce_s"] == pytest.approx(0.005)
    # idle 50 ms: 0..5 no span; 5..10 fit, before its first op; 35..40 fit,
    # after its last; 40..50 none; 50..60 second fit, before; 80..95 after
    idle = r["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(0.050)
    assert idle["unattributed"] == pytest.approx(0.015)
    assert idle["fit:before_first_op"] == pytest.approx(0.015)
    assert idle["fit:after_last_op"] == pytest.approx(0.020)
    assert r["idle_gaps"][0][1] == pytest.approx(0.025)  # 35..60, the longest
    assert [f["wall_s"] for f in r["fits"]] == pytest.approx([0.035, 0.045])
    assert [f["device_span_s"] for f in r["fits"]] == pytest.approx([0.025, 0.020])
    b = xplane.breakdown(r)
    assert b["device_ops"][0][0] == "fusion:fusion.1" and len(b["idle_gaps"]) <= 10


def test_device_operation_names_are_cut_to_opcode_and_name():
    call = ("%closed_call.94 = f32[1024,48]{1,0:T(8,128)S(1)} custom-call(s32[32,2625024]{1,0} "
            "%pad_bitcast_fusion.6, f32[2625024,3]{1,0:T(8,128)} %pad.16), custom_call_target=x")
    loop = ("%while.75 = (s32[]{:T(128)}, f32[2625000]{0:T(1024)S(1)}, /*index=5*/pred[4,62]"
            "{1,0:T(4,128)(4,1)}) while((s32[]{:T(128)}, f32[2]) %tuple), condition=%c, body=%b")
    assert xplane.short_name(call) == "custom-call:closed_call.94"
    assert xplane.short_name(loop) == "while:while.75"
    assert xplane.short_name("%all-reduce.3 = f32[7168,3]{1,0} all-reduce(f32[7168,3] %x)") \
        == "all-reduce:all-reduce.3"
    assert xplane.is_kernel_call("custom-call:closed_call.94")
    assert xplane.is_container("while:while.75") and not xplane.is_container(call)
    assert xplane.is_all_reduce("all-reduce:all-reduce.3")
    assert not xplane.is_kernel_call("fusion:uses_custom-call_result.1")


def test_metric_readers_on_the_hand_made_trace():
    r = xplane.reduce(_hand_made())
    peaks = work.peaks("TPU v5 lite")
    cell = {"shapes": {"trees": 8, "fits": 2, "rows_per_device": 2_625_000, "features": 28,
                       "batches": 4, "rows": 8192},
            "config": json.load(open(os.path.join(ROOT, "chipbench/configs/resnet50_224.json"))),
            "peaks": peaks, "chips": 1}

    def read(name):
        return importlib.import_module(f"chipbench.metrics.{name}").read(r, cell)

    assert read("gbdt_device_ms_per_tree") == pytest.approx(50.0 / 8)
    assert read("hist_kernel_ms_per_tree") == pytest.approx(25.01 / 8)
    assert read("psum_ms_per_tree") == pytest.approx(5.0 / 8)
    assert read("gbdt_fit_fixed_ms") == pytest.approx((10.0 + 25.0) / 2)
    assert read("feed_gap_ms_per_batch") == pytest.approx(50.0 / 4)
    assert read("featurizer_device_ms_per_batch") == pytest.approx(50.0 / 4)
    call = work.histogram_call(2_625_000, 28)
    assert call["bytes"] == 2_625_000 * 28 + 2_625_000 * 12 + 28 * 256 * 12
    least = call["bytes"] / 819e9  # memory bound: ops / 197e12 is far smaller
    assert call["ops"] / 197e12 < least
    # the 10 us sort is a custom call too, but no histogram pass: not counted
    assert 0.01e-3 < least < 10e-3
    assert read("hist_kernel_roofline") == pytest.approx(100 * least * 2 / 0.025)
    floor = (2_625_000 * 28 + 2_625_000 * 12) / 819e9
    assert read("gbdt_step_mfu") == pytest.approx(100 * floor * 8 / 0.100)
    flops = work.resnet_flops_per_image(cell["config"])
    assert read("featurizer_mfu") == pytest.approx(100 * flops * 8192 / (0.100 * 197e12))


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = xplane.reduce({"devices": {}, "spans": [["chipbench.window", 0.0, 1e9]]})
    cell = {"shapes": {"trees": 4, "batches": 8, "rows": 100, "rows_per_device": 10,
                       "features": 28}, "peaks": None, "chips": 1, "config": {}}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        assert reader.read(empty, cell) is None, m["name"]
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def _brute_busy(ops: list, lo: float, hi: float, step: float = 1000.0) -> float:
    """Busy time by marking a fine grid (1 us): slow, obviously right."""
    n = int((hi - lo) / step)
    busy = np.zeros(n, bool)
    for _name, s, d in ops:
        a, b = int(max(0, (s - lo) // step)), int(min(n, -(-(s + d - lo) // step)))
        if b > a:
            busy[a:b] = True
    return busy.sum() * step * 1e-9


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "testdata"))
    if f.endswith(".json")))
def test_reduce_recorded_trace(name):
    """A piece of a cell's own chip trace, cut by ``trace_fixture.py``."""
    with open(os.path.join(ROOT, "chipbench", "testdata", name + ".json")) as f:
        events = json.load(f)
    r = xplane.reduce(events)
    plane = sorted(events["devices"])[0]
    ops = events["devices"][plane]
    assert ops and r["devices"] >= 1
    hi = r["window_s"] * 1e9
    assert r["busy_s_each"][0] == pytest.approx(_brute_busy(ops, 0.0, hi), abs=len(ops) * 2e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = r["window_s"] - r["busy_s_each"][0]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle, rel=1e-9, abs=1e-12)
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(idle, rel=1e-9, abs=1e-12)
    total = sum(d for n, s, d in ops
                if s + d > 0 and s < hi and not xplane.is_container(n)) * 1e-9
    assert sum(r["op_seconds"].values()) == pytest.approx(total)
    if name.endswith("dp4"):  # four chips: the plane psum is an all-reduce on every one
        assert r["devices"] == 4 and r["all_reduce_s"] > 0 and r["kernel_calls"] > 0
    else:
        assert r["devices"] == 1 and r["all_reduce_s"] == 0
    # the drivers' spans name the idle time: almost none is left unattributed
    assert r["idle_by_span"].get("unattributed", 0.0) <= 0.05 * r["window_s"]


def test_a_fixture_cut_from_a_trace_reduces_like_the_trace():
    """``trace_fixture.trimmed``: the piece keeps every operation that
    overlaps it, on a clock that starts with its own window."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_fixture

    ms = 1e6
    events = {"devices": {"/device:TPU:0": [["fusion:a", 100 * ms, 50 * ms], ["fusion:b", 480 * ms, 40 * ms],
                                            ["fusion:c", 900 * ms, 10 * ms]]},
              "spans": [[xplane.WINDOW_SPAN, 0.0, 1000 * ms], ["chipbench.fit", 400 * ms, 300 * ms]]}
    piece = trace_fixture.trimmed(events, seconds=0.2, lead=0.05)   # 450..650 ms
    assert [o[0] for o in piece["devices"]["/device:TPU:0"]] == ["fusion:b"]
    r = xplane.reduce(piece)
    assert r["window_s"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx(0.04)
    assert r["idle_by_span"] == pytest.approx({"fit:before_first_op": 0.03, "fit:after_last_op": 0.13})


def test_resnet_flops_within_5_percent_of_xla_cost_analysis():
    import jax
    import jax.numpy as jnp

    from chipbench.reference import resnet as ref

    cfg = spec.sized(json.load(open(os.path.join(ROOT, "chipbench/configs/resnet50_224.json"))),
                     False)
    mine = work.resnet_flops_per_image(cfg)
    assert mine == pytest.approx(8.17e9, rel=0.01)
    weights = jax.eval_shape(lambda k: ref.make_weights(cfg, k), jax.random.PRNGKey(0))
    pixels = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.uint8)
    cost = jax.jit(lambda w, p: ref.forward(w, p, cfg)).lower(weights, pixels).cost_analysis()
    assert mine == pytest.approx(cost["flops"], rel=0.05)
    assert sum(k * k * ci * co + 4 * co for _n, k, _s, ci, co, _h in ref.conv_table(cfg)) \
        + 2048 * 1000 + 1000 == cfg["parameters"]


# -- the cells' real shapes, compiled for a described v5e:2x2 ------------------


@pytest.fixture(scope="module")
def v5e():
    import jax
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile-only client cannot read an executable back: keep these
    # programs out of the persistent cache
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    yield devices
    jax.config.update(key, before)


def _fits(compiled, hbm_bytes: float) -> float:
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < hbm_bytes, f"{used / 1e9:.2f} GB does not fit {hbm_bytes / 1e9:.0f} GB"
    return used


def test_featurizer_at_batch_2048_compiles_for_one_v5e_chip(v5e):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench.drivers import featurize_stream as drv
    from chipbench.reference import resnet as ref
    from mmlspark_tpu.models import ImageFeaturizer
    from mmlspark_tpu.models.resnet import RESNETS

    cfg = spec.sized(json.load(open(os.path.join(ROOT, "chipbench/configs/resnet50_224.json"))),
                     False)
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(
        lambda k: drv.program_variables(ref.make_weights(cfg, k), cfg, k), jax.random.PRNGKey(0))
    variables = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), shapes)
    module = RESNETS[cfg["model"]](num_classes=cfg["num_classes"], num_filters=cfg["num_filters"])
    stage = ImageFeaturizer(
        input_col="image", output_col="features", batch_size=cfg["batch_size"],
        image_size=cfg["image_size"],
        apply_fn=lambda vs, x: module.apply(vs, x, train=False), variables=shapes)
    program = stage._build().get("apply_fn")
    batch = jax.ShapeDtypeStruct(
        (cfg["batch_size"], cfg["image_size"], cfg["image_size"], cfg["channels"]),
        jnp.uint8, sharding=one)
    compiled = jax.jit(program).lower(variables, batch).compile()
    used = _fits(compiled, work.peaks("TPU v5 lite")["hbm_bytes"])
    assert used > 0.25 * 16e9  # the program's own footprint clears a quarter of the chip
    assert "all-reduce" not in compiled.as_text()


@pytest.mark.parametrize("chips,traffic", [(1, "fit_2625k_x4"), (4, "fit_10500k_x4")],
                         ids=["one_chip", "sharded_over_four"])
def test_plane_histogram_at_the_cells_rows_compiles_for_v5e(v5e, chips, traffic):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mmlspark_tpu.ops import histogram as H

    rows = json.load(open(os.path.join(ROOT, "chipbench", "traffic", traffic + ".json")))["rows"]
    cfg = json.load(open(os.path.join(ROOT, "chipbench/configs/higgs_gbdt.json")))
    assert rows // chips == cfg["rows_per_chip"]
    mesh = Mesh(np.array(v5e[:chips]), ("data",))

    def arg(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    compiled = jax.jit(lambda b, s: H.plane_histogram(
        b, s, num_bins=cfg["hist_bins"], mesh=mesh, shard_axis="data")).lower(
        arg((rows, cfg["features"]), jnp.uint8, "data", None),
        arg((rows, cfg["stat_channels"]), jnp.float32, "data", None)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (chips > 1)
    _fits(compiled, work.peaks("TPU v5 lite")["hbm_bytes"])
