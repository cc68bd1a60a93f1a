"""The elastic gang's scaled-out data plane (PR: make distribution pay).

Ring reduce-scatter + allgather on TcpReducer (bit-identical to the
full-mesh baseline by the sorted-member f64 accumulation contract),
streaming quantile-sketch binning (out-of-core: the global float matrix
never materializes), histogram-build/allreduce overlap, and the
voting-parallel (PV-Tree) exchange that cuts payload from O(d*B) to
O(2K*B) on wide data.

Tier-1 keeps the small-N ring/sketch/voting coverage; the 1M-row
bench-shaped memory-ceiling test is ``slow`` (ROADMAP tier budget).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.faults import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env() -> dict:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    return env


@pytest.fixture()
def gang_registry():
    from mmlspark_tpu.serving import fleet

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=2.0)
    yield reg
    reg.stop()


# -- the ring reducer ---------------------------------------------------------


def _reduce_all(reducers, arrs, fn="allreduce"):
    out = [None] * len(reducers)

    def side(i):
        out[i] = getattr(reducers[i], fn)(arrs[i])
        if fn == "allreduce_async":
            out[i] = out[i].result(30.0)

    ts = [threading.Thread(target=side, args=(i,))
          for i in range(len(reducers))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30.0)
    return out


def test_ring_reducer_bit_identical_to_mesh_and_reference(gang_registry):
    """Worlds 2 and 3, f32 and f64 payloads, sync and async: the ring
    exchange must produce byte-for-byte the mesh exchange's result,
    which is itself the sorted-member f64 accumulation — the contract
    every gang checkpoint rests on. The ring must also put FEWER payload
    bytes on the wire (f32 contributions travel as f32; f64 partial
    sums only for 1/world of the plane per peer)."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        TcpReducer,
    )

    for world in (2, 3):
        names = [chr(ord("a") + i) for i in range(world)]
        members = [
            GangMember(gang_registry.url, n, heartbeat_s=0.2)
            for n in names
        ]
        try:
            time.sleep(0.5)
            gen = Generation(gen=1, members=names)
            rng = np.random.default_rng(world)
            arrs32 = [
                rng.normal(size=(7, 5)).astype(np.float32) for _ in names
            ]
            arrs64 = [rng.normal(size=11) for _ in names]
            got = {}
            bytes_sent = {}
            for mode in ("mesh", "ring"):
                reds = [
                    TcpReducer(m, gen, timeout_s=20.0, mode=mode)
                    for m in members
                ]
                r32 = _reduce_all(reds, arrs32)
                r64 = _reduce_all(reds, arrs64, fn="allreduce_async")
                got[mode] = (r32, r64)
                bytes_sent[mode] = sum(r.payload_bytes_sent for r in reds)
                for r in reds:
                    r.close()
            # reference: sorted-member f64 accumulation
            ref32 = arrs32[0].astype(np.float64)
            for a in arrs32[1:]:
                ref32 = ref32 + a
            ref32 = ref32.astype(np.float32)
            ref64 = arrs64[0].copy()
            for a in arrs64[1:]:
                ref64 = ref64 + a
            for mode in ("mesh", "ring"):
                for i in range(world):
                    assert got[mode][0][i].tobytes() == ref32.tobytes()
                    assert got[mode][0][i].dtype == np.float32
                    assert got[mode][1][i].tobytes() == ref64.tobytes()
            assert bytes_sent["ring"] < bytes_sent["mesh"], (
                f"world {world}: ring {bytes_sent['ring']}B should "
                f"undercut mesh {bytes_sent['mesh']}B"
            )
        finally:
            for m in members:
                m.close()


def test_ring_world1_exact_noop(gang_registry):
    """World 1 returns the caller's array untouched — the anchor that
    keeps single-member gangs bit-identical to plain train()."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        TcpReducer,
    )

    m = GangMember(gang_registry.url, "solo", heartbeat_s=0.2)
    try:
        red = TcpReducer(
            m, Generation(gen=1, members=["solo"]), mode="ring"
        )
        x = np.arange(5, dtype=np.float32)
        assert red.allreduce(x) is x
        assert red.allreduce_async(x).result(1.0) is x
        assert red.payload_bytes_sent == 0
        red.close()
    finally:
        m.close()


def test_ring_step_fault_point_stalls_but_sums(gang_registry):
    """An armed ``elastic.ring_step`` delay stalls the pipeline without
    changing the sum (the chaos knob for the overlap path); the plan
    records fires from both phases."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        TcpReducer,
    )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        time.sleep(0.4)
        gen = Generation(gen=1, members=["a", "b"])
        ra = TcpReducer(a, gen, timeout_s=20.0, mode="ring")
        rb = TcpReducer(b, gen, timeout_s=20.0, mode="ring")
        plan = FaultPlan().on(
            "elastic.ring_step", delay_s=0.05, max_fires=2
        )
        with plan.armed():
            out = _reduce_all(
                [ra, rb], [np.ones(8), np.full(8, 2.0)]
            )
        np.testing.assert_array_equal(out[0], np.full(8, 3.0))
        np.testing.assert_array_equal(out[1], np.full(8, 3.0))
        assert len(plan.fires("elastic.ring_step")) == 2
        assert ra.ring_steps >= 2 and rb.ring_steps >= 2
        ra.close()
        rb.close()
    finally:
        a.close()
        b.close()


# -- ring vs mesh: whole-training bit-identity --------------------------------


def _train_args(data="synth:700x8:7", iters=5, extra=()):
    return [
        "--data", data, "--partitions", "6",
        "--num-iterations", str(iters), "--num-leaves", "7",
        "--min-data-in-leaf", "5", "--seed", "3",
        "--checkpoint-every", "2", "--heartbeat-s", "0.25",
        "--no-growback", *extra,
    ]


def _spawn(reg_url, name, ckpt, out_dir, world, train_args):
    argv = [
        sys.executable, "-m", "mmlspark_tpu.serving.fleet", "train",
        "--registry", reg_url, "--name", name, "--ckpt-dir", ckpt,
        "--world-size", str(world),
        "--out-model", os.path.join(out_dir, f"model-{name}.txt"),
        "--status-file", os.path.join(out_dir, f"status-{name}.json"),
        *train_args,
    ]
    return subprocess.Popen(
        argv, env=_child_env(), stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE, text=True,
    )


def _run_gang(reg_url, tag, world, out_dir, train_args):
    """One world-N gang to completion; returns (model, status-of-a)."""
    ck = os.path.join(out_dir, f"ck-{tag}")
    names = [f"{tag}{chr(ord('a') + i)}" for i in range(world)]
    procs = [
        _spawn(reg_url, n, ck, out_dir, world, train_args) for n in names
    ]
    models = []
    for p, n in zip(procs, names):
        _, err = p.communicate(timeout=180)
        assert p.returncode == 0, f"{n}: {err[-3000:]}"
        with open(os.path.join(out_dir, f"model-{n}.txt")) as f:
            models.append(f.read())
    assert all(m == models[0] for m in models), f"{tag}: members diverged"
    with open(os.path.join(out_dir, f"status-{names[0]}.json")) as f:
        return models[0], json.load(f)


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_ring_vs_mesh_boosters_bit_identical_worlds_1_2_3(
    gang_registry, tmp_path
):
    """Same seed, same rows: the full-mesh reducer and the ring reducer
    must produce byte-identical boosters at world sizes 1, 2 and 3 (and
    every member of a gang agrees with every other). World 1 is the
    exact-no-op anchor; worlds 2/3 exercise the real reduce-scatter.
    Ring payload bytes must undercut mesh at every multi-member world."""
    out = str(tmp_path)
    for world in (1, 2, 3):
        per_mode = {}
        for mode in ("mesh", "ring"):
            model, status = _run_gang(
                gang_registry.url, f"w{world}{mode[0]}", world, out,
                _train_args(extra=("--reduce-mode", mode)),
            )
            per_mode[mode] = (model, status)
        assert per_mode["ring"][0] == per_mode["mesh"][0], (
            f"world {world}: ring booster != mesh booster"
        )
        if world > 1:
            ring_b = per_mode["ring"][1]["payload_bytes"]
            mesh_b = per_mode["mesh"][1]["payload_bytes"]
            assert 0 < ring_b < mesh_b, (world, ring_b, mesh_b)


# -- streaming quantile sketches ----------------------------------------------


def test_sketch_partition_and_chunk_invariant():
    """The sketch counts are a pure function of the global rows: any
    chunking and any row partitioning yield the identical counts — the
    world-size invariance the elastic binning contract rests on."""
    from mmlspark_tpu.models.gbdt.sketch import QuantileSketch

    rng = np.random.default_rng(5)
    x = rng.normal(size=(997, 6)).astype(np.float32)
    x[rng.random(x.shape) < 0.05] = np.nan  # missing values skipped

    whole = QuantileSketch(6)
    whole.update(x)
    chunked = QuantileSketch(6)
    for lo in range(0, len(x), 64):
        chunked.update(x[lo:lo + 64])
    assert np.array_equal(whole.counts, chunked.counts)

    # two "hosts" with disjoint slices, merged by a stand-in reducer
    a, b = QuantileSketch(6), QuantileSketch(6)
    a.update(x[:400])
    b.update(x[400:])
    merged = a.counts + b.counts
    assert np.array_equal(whole.counts, merged)

    m1 = whole.to_binmapper(63)
    m2 = a.to_binmapper(63, reduce=lambda c: c + b.counts)
    for u1, u2 in zip(m1.uppers, m2.uppers):
        assert np.array_equal(u1, u2)


def test_sketch_binmapper_close_to_exact_quantiles():
    """Sketch-derived bins approximate the exact-quantile BinMapper:
    almost every cell lands in the same or an adjacent bin (bucket
    resolution ~0.8% relative at 16 bits), and NaNs still route to the
    missing bin."""
    from mmlspark_tpu.models.gbdt.binning import MISSING_BIN, BinMapper
    from mmlspark_tpu.models.gbdt.sketch import QuantileSketch

    rng = np.random.default_rng(9)
    x = np.concatenate(
        [rng.normal(size=(4000, 4)), rng.lognormal(size=(4000, 4))],
        axis=1,
    ).astype(np.float32)
    x[:50, 0] = np.nan
    sk = QuantileSketch(8)
    sk.update(x)
    approx = sk.to_binmapper(31)
    exact = BinMapper.fit(x, max_bin=31)
    ba = approx.transform(x)
    be = exact.transform(x)
    assert np.array_equal(ba[:50, 0], np.full(50, MISSING_BIN))
    # bin INDICES need not match (edges differ slightly); what matters
    # is the induced ordering: values mapped to far-apart bins by one
    # mapper must not collapse together by the other. Adjacent-bin
    # disagreement is the expected approximation noise.
    for f in range(8):
        qa = np.quantile(ba[:, f].astype(float), [0.25, 0.5, 0.75])
        qe = np.quantile(be[:, f].astype(float), [0.25, 0.5, 0.75])
        assert np.all(np.abs(qa - qe) <= 2), (f, qa, qe)
    # both mappers produce a usable number of bins
    assert sum(len(u) for u in approx.uppers) >= 8 * 20


def test_sketch_rejects_bad_shapes_and_bits():
    from mmlspark_tpu.models.gbdt.sketch import QuantileSketch

    with pytest.raises(ValueError):
        QuantileSketch(4, bits=4)
    sk = QuantileSketch(4)
    with pytest.raises(ValueError):
        sk.update(np.zeros((3, 5), np.float32))


# -- pre-binned input ---------------------------------------------------------


def test_binned_dataset_guards_and_training():
    """train() accepts a BinnedDataset (skipping fit/transform) and
    refuses the paths that would need the float matrix back."""
    from mmlspark_tpu.models.gbdt.binning import BinMapper, BinnedDataset
    from mmlspark_tpu.models.gbdt.sketch import QuantileSketch
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train

    rng = np.random.default_rng(3)
    x = rng.normal(size=(400, 5)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float64)
    mapper = BinMapper.fit(x, max_bin=31)
    ds = BinnedDataset(mapper.transform(x), mapper)
    cfg = TrainConfig(
        objective="binary", num_iterations=3, num_leaves=7,
        min_data_in_leaf=5, seed=1, max_bin=31,
    )
    ref = train(x, y, cfg, shard=False)
    got = train(ds, y, cfg, shard=False)
    # identical bins + mapper -> identical booster
    assert got.to_model_string() == ref.to_model_string()
    with pytest.raises(ValueError, match="dart"):
        train(ds, y, TrainConfig(
            objective="binary", num_iterations=2, boosting_type="dart",
            max_bin=31,
        ), shard=False)
    with pytest.raises(ValueError, match="init_booster"):
        train(ds, y, cfg, shard=False, init_booster=ref)
    with pytest.raises(ValueError, match="categorical"):
        train(ds, y, TrainConfig(
            objective="binary", num_iterations=2,
            categorical_features=(0,), max_bin=31,
        ), shard=False)
    with pytest.raises(ValueError, match="max_bin"):
        # codes quantized wider than the config's histogram space would
        # scatter into the wrong plane — must refuse, not corrupt
        train(ds, y, TrainConfig(
            objective="binary", num_iterations=2, max_bin=16,
        ), shard=False)
    with pytest.raises(ValueError):
        BinnedDataset(np.zeros((4, 3), np.int32), mapper)


# -- out-of-core streaming training -------------------------------------------


def test_streaming_world1_train_deterministic_and_binned(
    gang_registry, tmp_path
):
    """A world-1 streaming run (sketch-binned, chunk-ingested) trains to
    a deterministic booster: re-running the identical spec reproduces it
    byte-for-byte, and the trainer never holds the float matrix."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig
    from mmlspark_tpu.parallel.elastic import (
        ElasticTrainer,
        load_streaming_data,
    )

    stream, n, d = load_streaming_data("stream-synth:2000x6:7:256")
    cfg = TrainConfig(
        objective="binary", num_iterations=4, num_leaves=7,
        min_data_in_leaf=5, seed=3,
    )

    def run(tag):
        t = ElasticTrainer(
            gang_registry.url, f"solo{tag}", None, None, cfg,
            str(tmp_path / f"ck{tag}"), n_partitions=4, world_size=1,
            heartbeat_s=0.2, stream=stream, n_rows=n, n_features=d,
        )
        assert t.x is None and t.y is None
        return t.run().to_model_string()

    assert run("1") == run("2")


def test_stream_specs_and_dataframe_adapter(tmp_path):
    """stream-synth chunking is seed-deterministic and size-exact;
    stream_from_dataframe adapts a StreamingDataFrame (CSV on disk)
    without materializing it."""
    from mmlspark_tpu.parallel.elastic import (
        is_streaming_spec,
        load_streaming_data,
        stream_from_dataframe,
    )

    assert is_streaming_spec("stream-synth:10x2:0")
    assert not is_streaming_spec("synth:10x2:0")
    f1, n, d = load_streaming_data("stream-synth:1000x3:5:128")
    assert (n, d) == (1000, 3)
    chunks = list(f1())
    assert sum(len(x) for x, _ in chunks) == 1000
    assert all(x.shape[1] == 3 for x, _ in chunks)
    # re-iterable and deterministic
    again = list(f1())
    assert all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        for a, b in zip(chunks, again)
    )
    # CSV through StreamingDataFrame
    from mmlspark_tpu.io.stream import StreamingDataFrame

    path = str(tmp_path / "t.csv")
    with open(path, "w") as f:
        f.write("f1,label,f0\n")
        for i in range(300):
            f.write(f"{i * 0.5},{i % 2},{i}\n")
    sdf = StreamingDataFrame.from_csv(path, chunk_rows=64)
    factory, n2, d2 = stream_from_dataframe(sdf, "label")
    assert (n2, d2) == (300, 2)
    xs, ys = zip(*factory())
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    assert x.shape == (300, 2) and len(y) == 300
    # sorted-name feature order: f0 before f1
    assert np.allclose(x[:, 0], np.arange(300))
    assert np.allclose(y, np.arange(300) % 2)

    from mmlspark_tpu.parallel.elastic import load_streaming_data as lsd

    f3, n3, d3 = lsd(f"stream-csv:{path}:label:64")
    assert (n3, d3) == (300, 2)
    with pytest.raises(ValueError):
        lsd("stream-weird:1x1:0")


# -- voting-parallel gang mode ------------------------------------------------


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_voting_gang_o2k_payload_and_quality(gang_registry, tmp_path):
    """``--tree-parallelism voting`` (PV-Tree): members converge to one
    booster, the wire payload collapses toward O(2K*B) per exchange
    (asserted off the reducer's payload-byte counters: < half of full
    data-parallel at d=48, K=5), and the model's quality stays within
    tolerance of full data-parallel (train-set AUC within 0.02)."""
    from mmlspark_tpu.core.metrics import binary_auc
    from mmlspark_tpu.models.gbdt.booster import Booster
    from mmlspark_tpu.parallel.elastic import load_training_data

    out = str(tmp_path)
    args = _train_args(data="synth:1500x48:7", iters=5)
    full_model, full_st = _run_gang(
        gang_registry.url, "full", 2, out, args
    )
    vote_model, vote_st = _run_gang(
        gang_registry.url, "vote", 2, out,
        args + ["--tree-parallelism", "voting", "--top-k", "5"],
    )
    ratio = vote_st["payload_bytes"] / full_st["payload_bytes"]
    assert ratio < 0.5, (
        f"voting payload {vote_st['payload_bytes']}B is {ratio:.2f}x "
        f"of full {full_st['payload_bytes']}B — expected O(2K) collapse"
    )
    x, y = load_training_data("synth:1500x48:7")
    auc_full = binary_auc(y, Booster.from_model_string(full_model).predict(x))
    auc_vote = binary_auc(y, Booster.from_model_string(vote_model).predict(x))
    assert abs(auc_full - auc_vote) < 0.02, (auc_full, auc_vote)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_voting_quality_on_digits_golden(gang_registry, tmp_path):
    """The pinned quality contract on the digits golden (binary 3-vs-8,
    d=64): voting-parallel AUC within 0.02 of full data-parallel."""
    sklearn = pytest.importorskip("sklearn.datasets")
    from mmlspark_tpu.core.metrics import binary_auc
    from mmlspark_tpu.models.gbdt.booster import Booster

    digits = sklearn.load_digits()
    keep = np.isin(digits.target, (3, 8))
    x = digits.data[keep].astype(np.float32)
    y = (digits.target[keep] == 8).astype(np.float64)
    npz = str(tmp_path / "digits.npz")
    np.savez(npz, x=x, y=y)
    out = str(tmp_path)
    args = [
        "--data", f"npz:{npz}", "--partitions", "6",
        "--num-iterations", "8", "--num-leaves", "15",
        "--min-data-in-leaf", "5", "--seed", "3",
        "--checkpoint-every", "4", "--heartbeat-s", "0.25",
        "--no-growback",
    ]
    full_model, _ = _run_gang(gang_registry.url, "dfull", 2, out, args)
    vote_model, _ = _run_gang(
        gang_registry.url, "dvote", 2, out,
        args + ["--tree-parallelism", "voting", "--top-k", "8"],
    )
    auc_full = binary_auc(y, Booster.from_model_string(full_model).predict(x))
    auc_vote = binary_auc(y, Booster.from_model_string(vote_model).predict(x))
    assert auc_full > 0.97
    assert abs(auc_full - auc_vote) < 0.02, (auc_full, auc_vote)


# -- the 1M-row memory ceiling (bench-shaped; slow tier) ----------------------


@pytest.mark.slow
def test_streaming_1m_rows_memory_bounded(gang_registry, tmp_path):
    """The out-of-core contract at bench scale: ingesting 1M x 16 rows
    through streaming sketches costs bounded memory — strictly less
    than the 128 MB the f64 global matrix alone would take (the bins
    are 16 MB uint8; sketch 8 MB; y 8 MB; the rest is transient chunk
    buffers). The old ``binning_rows`` gather would have needed the
    whole matrix resident on every member."""
    import resource

    from mmlspark_tpu.models.gbdt.train import TrainConfig
    from mmlspark_tpu.parallel.elastic import (
        ElasticTrainer,
        load_streaming_data,
    )

    stream, n, d = load_streaming_data("stream-synth:1000000x16:11")
    cfg = TrainConfig(
        objective="binary", num_iterations=2, num_leaves=15,
        min_data_in_leaf=20, seed=3, growth_policy="depthwise",
    )
    trainer = ElasticTrainer(
        gang_registry.url, "big", None, None, cfg,
        str(tmp_path / "ck"), n_partitions=8, world_size=1,
        heartbeat_s=0.3, stream=stream, n_rows=n, n_features=d,
    )
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    binned, y = trainer._ingest_stream(None, 0, n)
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    delta_mb = (rss1 - rss0) / 1024
    assert binned.bins.shape == (n, d) and binned.bins.dtype == np.uint8
    assert trainer.x is None  # never held the float matrix
    # explicit memory ceiling: the f64 matrix alone is 128 MB — the
    # whole ingest (bins + y + sketch + chunk transients) must stay
    # under it, or "out-of-core" is a lie
    assert delta_mb < 120, f"ingest RSS delta {delta_mb:.0f} MB"
