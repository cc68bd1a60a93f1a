"""Headline benchmark: ImageFeaturizer ResNet-50 throughput (images/sec/chip).

North-star config (BASELINE.md): ResNet-50 featurization over a DataFrame at
>= 8,000 images/sec on v5e-32 => 250 images/sec/chip. ``vs_baseline`` is
measured images/sec/chip / 250. The single JSON line also carries an
``extra`` dict: Pallas histogram microbench, GBDT-vs-sklearn head-to-head,
VW throughput and serving loopback p50/p99.

The run needs a TPU: a child that comes up on the CPU backend exits at
once, and the parent then exits non-zero without printing a figure.

- the child process emits one JSON line PER SEGMENT as it completes
  (segments ordered by evidence value — see TPU_ORDER);
- the parent harvests lines with per-segment watchdog timeouts and kills a
  hung child; a segment that raises, a child that dies and a signal all
  end the run non-zero, after the partial assembly has been printed;
- the compile cache is placed by mmlspark_tpu.core.compile_cache: where
  ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(HERE, "bench_partial.json")

# Parent-side budget (seconds) for the whole run. Every knob has an env
# override.
TOTAL_TPU_BUDGET_S = int(os.environ.get("MMLSPARK_BENCH_TPU_BUDGET", "520"))
# watchdogs: first line covers backend init + first compile; later lines
# cover one segment each (compile cache makes repeats cheap)
FIRST_LINE_TIMEOUT_S = int(os.environ.get("MMLSPARK_BENCH_ATTEMPT_TIMEOUT", "300"))
SEGMENT_TIMEOUT_S = int(os.environ.get("MMLSPARK_BENCH_SEGMENT_TIMEOUT", "200"))
# compile-heavy segments build several fresh programs (two growth policies
# + the 63-bin variant; the ResNet trace): give their watchdogs more rope.
# A raised MMLSPARK_BENCH_SEGMENT_TIMEOUT still wins (max() at use); the
# phase deadline caps everything regardless.
SEGMENT_TIMEOUTS = {"gbdt": 280, "sklearn": 300, "featurizer": 280,
                    "pipeline": 240, "freshness": 240, "elastic": 600,
                    "throughput": 280, "tune": 420}

# Canonical segment set, and the order they run in: the metrics that need
# the chip first, most valuable first — the GBDT-vs-sklearn head-to-head,
# the kernel microbench, the headline featurizer.
SEGMENTS = ["serving", "modelstore", "tracing", "artifact", "overload",
            "throughput", "chaos", "freshness", "elastic", "tune",
            "pipeline", "hist", "vw", "gbdt", "sklearn", "featurizer"]
TPU_ORDER = ["sklearn", "gbdt", "hist", "featurizer", "pipeline", "vw",
             "serving", "modelstore", "tracing", "artifact", "overload",
             "throughput", "chaos", "freshness", "elastic", "tune"]


# ---------------------------------------------------------------------------
# segments (run inside the child process)
# ---------------------------------------------------------------------------


def _best_of(fn, n: int = 2) -> float:
    """Min wall-clock of n runs — on a shared box a single sklearn fit
    swings ~2x with host load, so BOTH sides of every head-to-head use
    the same min-of-n."""
    best = np.inf
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _seg_featurizer(on_accel: bool, n_dev: int) -> dict:
    """Full DataFrame -> features path, plus the device-resident model
    throughput (pre-staged batch) beside it."""
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models import ImageFeaturizer

    n_rows = 2048 if on_accel else 64
    batch = 256 if on_accel else 16
    size = 224
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, size=(n_rows, size, size, 3), dtype=np.uint8)
    df = DataFrame.from_dict({"image": imgs})
    feat = ImageFeaturizer(
        input_col="image",
        output_col="features",
        batch_size=batch,
        model_name="ResNet50",
        cut_output_layers=1,
        image_size=size,
    )
    warm = DataFrame.from_dict({"image": imgs[:batch]})
    feat.transform(warm)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        out = feat.transform(df)
        _ = out["features"]  # materialize
        dt = time.perf_counter() - t0
        best = max(best, n_rows / dt)
    diag: dict = {"featurizer_img_s_chip": round(best / n_dev, 2)}
    try:
        # device-resident rate: pre-staged batch, N dispatches, fetch the
        # last output
        inner = feat._build()
        from mmlspark_tpu.parallel.mesh import get_mesh
        from mmlspark_tpu.parallel.sharding import shard_batch

        mesh = get_mesh()
        vs = inner._device_variables(mesh)
        dev = shard_batch(imgs[:batch], mesh)
        fn = inner._compiled((batch, size, size, 3), mesh)
        np.asarray(fn(vs, dev))
        reps = 40 if on_accel else 4
        t0 = time.perf_counter()
        outs = [fn(vs, dev) for _ in range(reps)]
        _ = np.asarray(outs[-1])
        dres = reps * batch / (time.perf_counter() - t0) / n_dev
        diag["device_resident_img_s_chip"] = round(dres, 1)
    except Exception as e:  # noqa: BLE001
        diag["diag_error"] = str(e)[:200]
    return diag


def _seg_hist(on_accel: bool, n_dev: int) -> dict:
    """Pallas histogram kernel: (n, d) bins -> (d*B, 3) plane, builds/sec."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.histogram import NUM_BINS, plane_histogram, use_pallas

    n = 1 << 18 if on_accel else 1 << 12
    d = 64 if on_accel else 16
    rng = np.random.default_rng(1)
    bins = jnp.asarray(rng.integers(0, NUM_BINS, size=(n, d), dtype=np.int32))
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    hist = jax.jit(plane_histogram)
    np.asarray(hist(bins, stats))
    reps = 20
    t0 = time.perf_counter()
    outs = [hist(bins, stats) for _ in range(reps)]
    _ = np.asarray(outs[-1])
    dt = time.perf_counter() - t0
    from mmlspark_tpu.ops.histogram import hist_lowering

    out = {
        "hist_rows": n,
        "hist_features": d,
        "hist_builds_per_sec": round(reps / dt, 2),
        "hist_gcells_per_sec": round(reps * n * d / dt / 1e9, 3),
        "hist_pallas": bool(use_pallas()),
        "hist_lowering": hist_lowering(),
    }
    out.update(_hist_scaling(on_accel, n_dev, n, d))
    # reduced bin space (max_bin=63-class workloads): the one-hot compare
    # loop shrinks 4x — reported next to the full-space number
    import functools as _ft

    hist64 = jax.jit(_ft.partial(plane_histogram, num_bins=64))
    bins64 = jnp.asarray(rng.integers(0, 64, size=(n, d), dtype=np.int32))
    np.asarray(hist64(bins64, stats))
    t0 = time.perf_counter()
    outs = [hist64(bins64, stats) for _ in range(reps)]
    _ = np.asarray(outs[-1])
    dt = time.perf_counter() - t0
    out["hist64_gcells_per_sec"] = round(reps * n * d / dt / 1e9, 3)
    return out


def _fused_chunks_total() -> float:
    """Current value of mmlspark_gbdt_fused_chunks_total (0 when unset)."""
    from mmlspark_tpu.obs import REGISTRY

    fam = REGISTRY.snapshot().get("mmlspark_gbdt_fused_chunks_total")
    if not fam:
        return 0.0
    try:
        return float(sum(v for _, v in fam["samples"]))
    except Exception:  # noqa: BLE001
        return 0.0


def _hist_scaling(on_accel: bool, n_dev: int, n: int, d: int) -> dict:
    """Per-chip-count sharded histogram scaling: the ICI-allreduce claim
    as recorded numbers. Each row runs the per-shard kernel + explicit
    psum (ops.histogram.sharded_build_timed) on a k-device mesh.

    With >1 device already visible (real TPU slices), measured in
    process. On the single-device CPU fallback the row still gets
    measured honestly: a short subprocess forces 8 host devices and runs
    the identical code — the "chips" are host cores, which is exactly
    what the CPU lowering scales over."""
    import jax

    if jax.device_count() > 1:
        try:
            return _hist_scaling_rows(n, d)
        except Exception as e:  # noqa: BLE001
            return {"hist_scaling_error": str(e)[:120]}
    if on_accel:
        # a single-chip accelerator has no second chip to scale over, and
        # host-core numbers must never masquerade as its scaling rows
        return {}
    # CPU fallback: measure in a forced-multi-device child
    import json as _json
    import subprocess as _sp

    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import json\n"
        "from bench import _hist_scaling_rows\n"
        f"print(json.dumps(_hist_scaling_rows({n}, {d})))\n"
    )
    try:
        res = _sp.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=180, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        return _json.loads(res.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        return {"hist_scaling_error": str(e)[:120]}


def _hist_scaling_rows(n: int, d: int) -> dict:
    """hist_gcells_per_sec at 1, 2, 4, ... devices over the explicit
    shard_map + psum path, plus the observed allreduce-inclusive build
    time (mmlspark_gbdt_hist_allreduce_seconds)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mmlspark_tpu.ops.histogram import NUM_BINS, sharded_build_timed
    from mmlspark_tpu.parallel.mesh import DATA_AXIS, make_mesh

    rng = np.random.default_rng(1)
    ndev = jax.device_count()
    out: dict = {"hist_scaling_devices": ndev}
    k = 1
    while k <= ndev:
        devices = jax.devices()[:k]
        mesh = make_mesh({DATA_AXIS: k}, devices=devices)
        n_pad = ((n + k - 1) // k) * k
        bins = jnp.asarray(
            rng.integers(0, NUM_BINS, size=(n_pad, d), dtype=np.int32)
        )
        stats = jnp.asarray(rng.normal(size=(n_pad, 3)).astype(np.float32))
        sh = NamedSharding(mesh, P(DATA_AXIS, None))
        bins = jax.device_put(bins, sh)
        stats = jax.device_put(stats, sh)
        sharded_build_timed(bins, stats, mesh, DATA_AXIS)  # compile
        reps = 10
        t0 = time.perf_counter()
        for _ in range(reps):
            r = sharded_build_timed(bins, stats, mesh, DATA_AXIS)
        _ = np.asarray(r)
        dt = time.perf_counter() - t0
        out[f"hist_gcells_per_sec_{k}chip"] = round(
            reps * n_pad * d / dt / 1e9, 3
        )
        # allreduce-inclusive build time at the WIDEST mesh measured
        # (k stops at the largest power of two <= ndev)
        out["hist_allreduce_ms"] = round(dt / reps * 1e3, 3)
        k *= 2
    return out


def _seg_gbdt(on_accel: bool, n_dev: int) -> dict:
    """Boosting throughput (trees/sec) with the device-resident loop, for
    both growth policies: lossguide (LightGBM leaf-wise parity) and
    depthwise (one multi-leaf histogram pass per level)."""
    from mmlspark_tpu.models.gbdt import TrainConfig, train

    n, d = (200_000, 64) if on_accel else (20_000, 32)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float64)
    out = {"gbdt_rows": n, "gbdt_features": d}
    reps = 20
    for policy, key in (("lossguide", "gbdt_trees_per_sec"),
                        ("depthwise", "gbdt_depthwise_trees_per_sec")):
        # warm up at the EXACT timed shape AND iteration count: training is
        # one scan-fused program whose length is the iteration count
        cfg = TrainConfig(objective="binary", num_iterations=reps,
                          num_leaves=63, min_data_in_leaf=20, seed=0,
                          growth_policy=policy)
        train(x, y, cfg)
        out[key] = round(reps / _best_of(lambda: train(x, y, cfg)), 2)
        if policy == "lossguide":
            # the O(rounds) -> O(rounds/K) dispatch-reduction claim as an
            # asserted number: fused-chunk dispatches for one reps-round fit
            before = _fused_chunks_total()
            train(x, y, cfg)
            out["gbdt_fused_dispatch_count"] = int(
                _fused_chunks_total() - before
            )
            out["gbdt_rounds_per_dispatch"] = round(
                reps / max(out["gbdt_fused_dispatch_count"], 1), 1
            )
    return out


def _seg_sklearn(on_accel: bool, n_dev: int) -> dict:
    """Wall-clock head-to-head vs sklearn HistGradientBoosting (the same
    histogram-GBDT family as LightGBM) with matched hyperparameters — the
    analogue of the reference's headline 'LightGBM 10-30% faster than
    SparkML GBT' claim (docs/lightgbm.md:17-19). speedup > 1 = we win."""
    from mmlspark_tpu.models.gbdt import TrainConfig, train

    n, d, iters, leaves = (100_000, 32, 50, 63) if on_accel else (20_000, 16, 20, 31)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n + n // 4, d)).astype(np.float32)
    y = (np.sin(2 * x[:, 0]) + x[:, 1] * x[:, 2] > 0).astype(np.float64)
    x, xte, y, yte = x[:n], x[n:], y[:n], y[n:]  # held-out quality check
    out: dict = {}
    raw: dict = {}
    boosters: dict = {}
    for policy, key in (("lossguide", "gbdt_train_s"),
                        ("depthwise", "gbdt_depthwise_train_s")):
        cfg = TrainConfig(objective="binary", num_iterations=iters,
                          num_leaves=leaves, min_data_in_leaf=20, seed=7,
                          growth_policy=policy)
        train(x, y, cfg)

        def _fit(c=cfg, p=policy):
            boosters[p] = train(x, y, c)

        raw[key] = _best_of(_fit)
        out[key] = round(raw[key], 2)
    # matched reduced-bin head-to-head (both sides at 63 bins): isolates
    # the histogram-kernel win from the bin-budget hyperparameter
    cfg63 = TrainConfig(objective="binary", num_iterations=iters,
                        num_leaves=leaves, min_data_in_leaf=20, seed=7,
                        max_bin=63)
    train(x, y, cfg63)
    b63_box = {}

    def _fit63():
        b63_box["b"] = train(x, y, cfg63)

    raw63 = _best_of(_fit63)
    b63 = b63_box["b"]
    out["gbdt63_train_s"] = round(raw63, 2)
    try:
        from sklearn.ensemble import HistGradientBoostingClassifier
    except ImportError:
        return out
    sk = HistGradientBoostingClassifier(
        max_iter=iters, max_leaf_nodes=leaves, min_samples_leaf=20,
        learning_rate=cfg.learning_rate, early_stopping=False, random_state=7,
    )
    sk_s = _best_of(lambda: sk.fit(x, y))
    out["sklearn_train_s"] = round(sk_s, 2)
    sk63 = HistGradientBoostingClassifier(
        max_iter=iters, max_leaf_nodes=leaves, min_samples_leaf=20,
        learning_rate=cfg.learning_rate, early_stopping=False,
        random_state=7, max_bins=63,
    )
    sk63_s = _best_of(lambda: sk63.fit(x, y))
    out["sklearn63_train_s"] = round(sk63_s, 2)
    out["gbdt63_vs_sklearn63_speedup"] = round(sk63_s / raw63, 3)
    try:
        from mmlspark_tpu.core.metrics import binary_auc
        from mmlspark_tpu.models.gbdt.objectives import sigmoid

        out["gbdt63_auc"] = round(binary_auc(yte, sigmoid(b63.predict_raw(xte))), 4)
        out["sklearn63_auc"] = round(binary_auc(yte, sk63.predict_proba(xte)[:, 1]), 4)
    except Exception as e:  # noqa: BLE001
        out["auc63_error"] = str(e)[:120]
    # held-out quality next to the wall-clock: the speedup claim only
    # counts if the models are comparably good. Independent try: a 63-bin
    # predict failure must not suppress the headline AUC evidence
    try:
        from mmlspark_tpu.core.metrics import binary_auc
        from mmlspark_tpu.models.gbdt.objectives import sigmoid

        out["gbdt_auc"] = round(
            binary_auc(yte, sigmoid(boosters["lossguide"].predict_raw(xte))), 4
        )
        out["gbdt_depthwise_auc"] = round(
            binary_auc(yte, sigmoid(boosters["depthwise"].predict_raw(xte))), 4
        )
        out["sklearn_auc"] = round(binary_auc(yte, sk.predict_proba(xte)[:, 1]), 4)
    except Exception as e:  # noqa: BLE001
        out["auc_error"] = str(e)[:120]
    # ratios divide the RAW seconds (rounded values skew, and can be 0.0)
    out["gbdt_vs_sklearn_speedup"] = round(sk_s / raw["gbdt_train_s"], 3)
    out["gbdt_depthwise_vs_sklearn_speedup"] = round(
        sk_s / raw["gbdt_depthwise_train_s"], 3
    )
    return out


def _seg_vw(on_accel: bool, n_dev: int) -> dict:
    """Online-learning throughput: hashed sparse text rows/sec through the
    device SGD (the BASELINE 20-newsgroups-style tracked metric)."""
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitFeaturizer

    n = 100_000 if on_accel else 10_000
    rng = np.random.default_rng(5)
    vocab = [f"w{i}" for i in range(2000)]
    texts = np.array(
        [" ".join(rng.choice(vocab, size=12)) for _ in range(n)], dtype=object
    )
    y = rng.integers(0, 2, size=n).astype(np.float64)
    df = DataFrame.from_dict({"text": texts, "label": y})
    feat = VowpalWabbitFeaturizer(input_cols=["text"], output_col="features")
    clf = VowpalWabbitClassifier(num_passes=1)
    fdf = feat.transform(df)
    clf.fit(fdf)
    t0 = time.perf_counter()
    clf.fit(fdf)
    dt = time.perf_counter() - t0
    out = {"vw_rows": n, "vw_rows_per_sec": round(n / dt, 1)}
    # device-resident rate: a multi-pass fit uploads the rows ONCE and
    # streams p passes over them on device — this isolates the SGD kernel
    # from the e2e number above
    passes = 8
    clf_p = VowpalWabbitClassifier(num_passes=passes)
    clf_p.fit(fdf)
    t0 = time.perf_counter()
    clf_p.fit(fdf)
    dtp = time.perf_counter() - t0
    # per-pass marginal time: subtract the 1-pass run (upload + fixed
    # overheads). Noise can make the difference non-positive; report
    # nothing rather than an absurd clamped rate
    if dtp > dt * 1.05:
        marginal = (dtp - dt) / (passes - 1)
        out["vw_rows_per_sec_resident"] = round(n / marginal, 1)
    return out


def _seg_serving(on_accel: bool, n_dev: int) -> dict:
    """Loopback POST -> fixed-shape batch -> jitted model -> reply, ms."""
    import http.client

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.serving.query import ServingQuery
    from mmlspark_tpu.serving.server import WorkerServer

    dim = 64
    w_host = np.random.default_rng(2).normal(size=(dim, dim)).astype(np.float32)
    # r05 -> r06 p50 drift (0.71 -> 2.38 ms, "regression-suspect" per PR 6's
    # re-measure): bisected 2026-08-04 with a standalone echo probe against
    # PR 4 / PR 5 / HEAD checkouts on a quiet box — 0.83 / 0.79 / 0.82 ms
    # respectively. No code regression at any commit; the r06 number (and
    # PR 6's 2.47-3.1 ms re-measures) were shared-box load, which _best_of
    # already documents as swinging single fits ~2x.
    drift_note = (
        "r05->r06 p50 drift bisected: PR4=0.83 PR5=0.79 HEAD=0.82 ms on a "
        "quiet box (r05=0.71) - no code regression, r06 ran under box load"
    )

    def make_handler(model):
        def handler(reqs):
            x = np.stack(
                [np.asarray(json.loads(r.body)["x"], np.float32) for r in reqs]
            )
            pad = -len(x) % 8  # fixed-shape batch: pad to the 8-row bucket
            if pad:
                x = np.pad(x, ((0, pad), (0, 0)))
            y = np.asarray(model(x))[: len(reqs)]
            return {
                r.id: (200, json.dumps({"y": float(v)}).encode(), {})
                for r, v in zip(reqs, y)
            }

        return handler

    def measure_port(port: int, n_req: int = 300, warmup: int = 50) -> tuple:
        """p50/p99 ms of sequential POSTs against an endpoint — the ONE
        request loop both the direct and the gateway paths share."""
        payload = json.dumps({"x": [0.1] * dim})
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        lat = []
        for i in range(n_req):
            t0 = time.perf_counter()
            conn.request(
                "POST", "/", body=payload,
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            resp.read()
            lat.append((time.perf_counter() - t0) * 1e3)
        conn.close()
        lat = np.sort(np.asarray(lat[warmup:]))
        return (
            round(float(lat[len(lat) // 2]), 3),
            round(float(lat[int(len(lat) * 0.99)]), 3),
        )

    def measure(model) -> tuple:
        srv = WorkerServer()
        q = None
        try:
            info = srv.start()
            # max_wait_ms=0: no batch-accumulation wait — the continuous
            # low-latency mode; throughput deployments raise it to batch
            q = ServingQuery(srv, make_handler(model), max_wait_ms=0).start()
            return measure_port(info.port)
        finally:
            if q is not None:
                q.stop()
            srv.stop()

    def measure_via_gateway(model) -> tuple:
        """Same worker, fronted by a ServingGateway: isolates the gateway's
        added latency (the distributed mode's overhead budget)."""
        from mmlspark_tpu.serving.distributed import ServingGateway

        srv = WorkerServer()
        q = gw = None
        try:
            info = srv.start()
            q = ServingQuery(srv, make_handler(model), max_wait_ms=0).start()
            gw = ServingGateway(workers=[info])
            ginfo = gw.start()
            return measure_port(ginfo.port)
        finally:
            if gw is not None:
                gw.stop()
            if q is not None:
                q.stop()
            srv.stop()

    w = jnp.asarray(w_host)

    @jax.jit
    def model(x):
        return jnp.tanh(x @ w).sum(axis=-1)

    model(jnp.zeros((8, dim), jnp.float32)).block_until_ready()
    p50, p99 = measure(lambda x: model(jnp.asarray(x)))
    out = {"serving_p50_ms": p50, "serving_p99_ms": p99}
    # ROADMAP item 2: serving_p50_ms drifted 0.71 (r05) -> 2.38 (r06) with
    # no serving-path code change in PR 5. Settle it with this fresh
    # measurement: near the r05 number => the r06 reading was box noise;
    # near the r06 number on a quiet box => a real regression to hunt.
    out["serving_p50_r05_ms"] = 0.71
    out["serving_p50_r06_ms"] = 2.38
    out["serving_p50_drift_verdict"] = (
        "r06-was-box-noise" if p50 < 1.55 else "regression-suspect"
    )
    out["serving_p50_drift_bisect"] = drift_note

    # the reference's sub-ms claim is for EXECUTOR-LOCAL serving (model on
    # the machine answering the request, docs/mmlspark-serving.md:142-146).
    # On an accelerator every request also pays the host<->device round
    # trip; measure the model-on-serving-host deployment shape separately
    # so the capability is visible next to the device number.
    if jax.default_backend() == "cpu":
        out["serving_local_p50_ms"] = p50  # the run above IS model-on-host
        out["serving_local_p99_ms"] = p99
        run_local = lambda x: model(jnp.asarray(x))  # noqa: E731
    else:
        run_local = None
        try:
            cpu = jax.local_devices(backend="cpu")[0]
            w_cpu = jax.device_put(w_host, cpu)
            local_model = jax.jit(lambda x: jnp.tanh(x @ w_cpu).sum(axis=-1))

            def run_local(x):
                # explicit placement: the serving handler runs in its own
                # thread, where a default_device context would not apply
                return local_model(
                    jax.device_put(np.asarray(x, np.float32), cpu)
                )

            run_local(np.zeros((8, dim), np.float32)).block_until_ready()
            p50l, p99l = measure(run_local)
            out["serving_local_p50_ms"] = p50l
            out["serving_local_p99_ms"] = p99l
        except Exception as e:  # noqa: BLE001
            out["serving_local_error"] = str(e)[:200]
            run_local = None  # no baseline => no gateway delta either
    # gateway overhead budget: the same model-on-host worker behind a
    # ServingGateway — p50 delta vs serving_local_p50_ms IS the gateway tax
    if run_local is not None:
        try:
            p50g, p99g = measure_via_gateway(run_local)
            out["serving_gateway_p50_ms"] = p50g
            out["serving_gateway_p99_ms"] = p99g
        except Exception as e:  # noqa: BLE001
            out["serving_gateway_error"] = str(e)[:200]
    return out


def _seg_modelstore(on_accel: bool, n_dev: int) -> dict:
    """Multi-model serving + hot-swap: sustained loopback POSTs through a
    ModelStore worker while v2 loads and the serving alias flips.
    ``serving_swap_p99_ms`` is the p99 of the requests straddling the
    flip — the number that proves zero-downtime hot-swap costs nothing
    the client can see — plus resident-version accounting after the old
    version drains out."""
    import http.client

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.serving.modelstore import (
        LoadedModel,
        ModelDispatcher,
        ModelStore,
    )
    from mmlspark_tpu.serving.server import WorkerServer

    dim = 64

    def make_loaded(seed: int) -> LoadedModel:
        w_host = np.random.default_rng(seed).normal(
            size=(dim, dim)
        ).astype(np.float32)
        w = jnp.asarray(w_host)

        @jax.jit
        def model(x):
            return jnp.tanh(x @ w).sum(axis=-1)

        def handler(reqs):
            x = np.stack([
                np.asarray(json.loads(r.body)["x"], np.float32) for r in reqs
            ])
            pad = -len(x) % 8  # fixed-shape batch: pad to the 8-row bucket
            if pad:
                x = np.pad(x, ((0, pad), (0, 0)))
            y = np.asarray(model(x))[: len(reqs)]
            return {
                r.id: (200, json.dumps({"y": float(v)}).encode(), {})
                for r, v in zip(reqs, y)
            }

        def warmup():
            model(jnp.zeros((8, dim), jnp.float32)).block_until_ready()

        return LoadedModel(handler=handler, nbytes=int(w.nbytes), warmup=warmup)

    store = ModelStore()
    store.load("m", make_loaded(1))
    srv = WorkerServer()
    info = srv.start()
    disp = ModelDispatcher(srv, store, default_model="m").start()
    out: dict = {}
    try:
        import threading

        payload = json.dumps({"x": [0.1] * dim})
        conn = http.client.HTTPConnection("127.0.0.1", info.port, timeout=10)
        n_req, swap_at, warmup_n = 600, 300, 50
        lat = []
        swap_done_idx = [None]

        def do_swap() -> None:
            # load+warm v2, then flip — CONCURRENT with the request loop,
            # so requests genuinely straddle the flip (a swap that held
            # the store lock against dispatch would show up in the
            # straddling window's p99)
            v2 = store.load("m", make_loaded(2), wait=True)
            t_sw = time.perf_counter()
            store.swap("m", v2)
            out["modelstore_swap_ctl_ms"] = round(
                (time.perf_counter() - t_sw) * 1e3, 3
            )

        swapper = None
        for i in range(n_req):
            if i == swap_at:
                swapper = threading.Thread(target=do_swap)
                swapper.start()
            t0 = time.perf_counter()
            conn.request(
                "POST", "/", body=payload,
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            resp.read()
            lat.append((time.perf_counter() - t0) * 1e3)
            if (
                swap_done_idx[0] is None and swapper is not None
                and not swapper.is_alive()
            ):
                swap_done_idx[0] = i  # first request after the flip landed
        conn.close()
        if swapper is not None:
            swapper.join(60.0)
        arr = np.sort(np.asarray(lat[warmup_n:]))
        # the straddling window: requests issued while the load+swap ran,
        # plus a tail after the flip (bounded by the run's end)
        end = min(n_req, (swap_done_idx[0] or n_req - 25) + 25)
        window = np.sort(np.asarray(lat[swap_at:end]))
        out["serving_swap_p99_ms"] = round(
            float(window[int(len(window) * 0.99)]), 3
        )
        out["serving_multimodel_p50_ms"] = round(
            float(arr[len(arr) // 2]), 3
        )
        out["serving_multimodel_p99_ms"] = round(
            float(arr[int(len(arr) * 0.99)]), 3
        )
        # post-swap accounting: v1 drained + evicted, only v2 resident
        deadline = time.monotonic() + 5.0
        while store.resident_bytes() > dim * dim * 4 and (
            time.monotonic() < deadline
        ):
            time.sleep(0.05)
        out["modelstore_resident_models"] = sum(
            1 for v in store.models()["m"]["versions"]
            if v["state"] in ("ready", "warming")
        )
        out["modelstore_resident_bytes"] = store.resident_bytes()
    finally:
        disp.stop()
        srv.stop()
    return out


def _seg_tracing(on_accel: bool, n_dev: int) -> dict:
    """Observability tax on the echo serving path: p50/p99 of loopback
    POSTs with the span buffer + flight recorder ON (the always-on
    default) vs OFF — the <2% p99 overhead budget, measured where it
    would hurt (docs/observability.md)."""
    import http.client

    from mmlspark_tpu import obs
    from mmlspark_tpu.obs.flightrec import FLIGHT
    from mmlspark_tpu.serving.query import ServingQuery
    from mmlspark_tpu.serving.server import WorkerServer
    from mmlspark_tpu.serving.udfs import make_reply, request_to_json

    def handler(reqs):
        return {r.id: make_reply({"echo": request_to_json(r)}) for r in reqs}

    def measure(n_req: int = 400, warmup: int = 50) -> tuple:
        payload = json.dumps({"x": 1})
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        lat = []
        for _ in range(n_req):
            t0 = time.perf_counter()
            conn.request(
                "POST", "/", body=payload,
                headers={"Content-Type": "application/json"},
            )
            conn.getresponse().read()
            lat.append((time.perf_counter() - t0) * 1e3)
        conn.close()
        arr = np.sort(np.asarray(lat[warmup:]))
        return (
            round(float(arr[len(arr) // 2]), 3),
            round(float(arr[int(len(arr) * 0.99)]), 3),
        )

    def one(conn, payload) -> float:
        t0 = time.perf_counter()
        conn.request(
            "POST", "/", body=payload,
            headers={"Content-Type": "application/json"},
        )
        conn.getresponse().read()
        return (time.perf_counter() - t0) * 1e3

    srv = WorkerServer(name="tracebench")
    srv.start()
    q = ServingQuery(srv, handler, max_wait_ms=0).start()
    was_buf, was_flight = obs.BUFFER.enabled, FLIGHT.enabled
    out = {}
    try:
        measure(100, 0)  # warm the path before either timed run
        obs.BUFFER.enabled = FLIGHT.enabled = False
        p50_off, p99_off = measure()
        obs.BUFFER.enabled = FLIGHT.enabled = True
        p50_on, p99_on = measure()
        # the raw p99s swing with scheduler noise on a shared box; the
        # robust overhead number is the trimmed mean of PAIRED on/off
        # deltas relative to the baseline median — what the tier-1 gate
        # asserts < 2% (tests/test_traces.py)
        payload = json.dumps({"x": 1})
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        deltas, offs = [], []
        for _ in range(300):
            obs.BUFFER.enabled = FLIGHT.enabled = False
            off = one(conn, payload)
            obs.BUFFER.enabled = FLIGHT.enabled = True
            deltas.append(one(conn, payload) - off)
            offs.append(off)
        conn.close()
        d = np.sort(np.asarray(deltas))
        k = len(d) // 10
        paired_pct = 100.0 * float(d[k:-k].mean()) / float(np.median(offs))
        out = {
            "tracing_off_p50_ms": p50_off,
            "tracing_off_p99_ms": p99_off,
            "tracing_on_p50_ms": p50_on,
            "tracing_on_p99_ms": p99_on,
            "tracing_overhead_paired_pct": round(paired_pct, 2),
        }
    finally:
        obs.BUFFER.enabled, FLIGHT.enabled = was_buf, was_flight
        q.stop()
        srv.stop()
    return out


def _seg_overload(on_accel: bool, n_dev: int) -> dict:
    """Overload-containment proof (docs/robustness.md): goodput + p99 at
    1x/2x/4x offered load with adaptive admission control ON vs OFF.
    The claim under test: with admission on, 4x offered load holds p99
    within 2x of the 1x baseline (goodput saturates gracefully, excess
    is shed 429); without it, the queue grows unboundedly and p99
    collapses by an order of magnitude. The model is rate-limited (one
    request per batch, fixed service time) so capacity and queueing are
    deterministic; load is rate-paced across client threads."""
    import http.client

    from mmlspark_tpu.serving.admission import AdmissionController
    from mmlspark_tpu.serving.query import ServingQuery
    from mmlspark_tpu.serving.server import WorkerServer

    # Deliberately slow model + low rates: the interesting quantity is
    # QUEUEING (offered load vs service capacity), and a 10 ms service
    # time keeps the Python/HTTP per-request CPU cost a rounding error
    # even on a 1-2 core CI box — fast settings would measure the box's
    # scheduler, not the admission controller.
    svc_s = 0.010             # per-request service time: capacity ~100 rps
    base_rps = 40.0           # 1x = ~40% capacity; 4x = ~160% (overload)
    n_threads_base = 8        # each paced at base_rps / n_threads_base
    dur_s = 4.0

    def handler(reqs):
        time.sleep(svc_s * len(reqs))
        return {r.id: (200, b'{"ok": true}', {}) for r in reqs}

    def run_level(mult: int, admission: bool) -> dict:
        srv = WorkerServer(name="overloadbench")
        srv.start()
        ctrl = (
            AdmissionController(
                server=f"overloadbench-{mult}x", initial_limit=16,
                min_limit=1, wait_factor=1.0,
            )
            if admission else None
        )
        q = ServingQuery(
            srv, handler, admission=ctrl, max_batch_size=1, max_wait_ms=0,
        ).start()
        n_threads = n_threads_base * mult
        interval = n_threads_base / base_rps
        lock = threading.Lock()
        lats: list = []
        counts = {"sent": 0, "shed": 0}
        start_t = time.perf_counter() + 0.1
        # steady-state measurement: the warm window (load ramp + the
        # AIMD convergence transient) is driven but not recorded —
        # the claim is about the contained steady state, and without
        # admission the queue keeps growing through it either way
        warm_t = start_t + 1.0
        stop_t = warm_t + dur_s

        def client(k: int) -> None:
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=30
            )
            # stagger the pacing grid so threads don't fire in lockstep
            next_t = start_t + (k / n_threads) * interval
            while True:
                now = time.perf_counter()
                if now >= stop_t:
                    break
                if now < next_t:
                    time.sleep(next_t - now)
                next_t += interval
                t0 = time.perf_counter()
                try:
                    conn.request(
                        "POST", "/", body=b'{"x": 1}',
                        headers={"Content-Type": "application/json"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                except Exception:  # noqa: BLE001 — reconnect and continue
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", srv.port, timeout=30
                    )
                    continue
                dt_ms = (time.perf_counter() - t0) * 1e3
                if t0 < warm_t:
                    continue
                with lock:
                    counts["sent"] += 1
                    if resp.status == 200:
                        lats.append(dt_ms)
                    else:
                        counts["shed"] += 1
            conn.close()

        threads = [
            threading.Thread(target=client, args=(k,))
            for k in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(dur_s + 35.0)
        q.stop()
        srv.stop()
        arr = np.sort(np.asarray(lats)) if lats else np.asarray([0.0])
        return {
            "offered_rps": round(counts["sent"] / dur_s, 1),
            "goodput_rps": round(len(lats) / dur_s, 1),
            "shed": counts["shed"],
            "p50_ms": round(float(arr[len(arr) // 2]), 2),
            "p99_ms": round(float(arr[int((len(arr) - 1) * 0.99)]), 2),
        }

    out: dict = {"overload_svc_ms": svc_s * 1e3,
                 "overload_base_rps": base_rps}
    for mult in (1, 2, 4):
        on = run_level(mult, admission=True)
        out[f"overload_{mult}x_offered_rps"] = on["offered_rps"]
        out[f"overload_{mult}x_goodput_rps"] = on["goodput_rps"]
        out[f"overload_{mult}x_shed"] = on["shed"]
        out[f"overload_{mult}x_p99_ms"] = on["p99_ms"]
        if mult in (1, 4):
            off = run_level(mult, admission=False)
            out[f"overload_{mult}x_noadmission_goodput_rps"] = (
                off["goodput_rps"]
            )
            out[f"overload_{mult}x_noadmission_p99_ms"] = off["p99_ms"]
    # the two headline ratios: containment (admission on, 4x vs 1x —
    # the acceptance gate is <= 2) and collapse (what 4x does WITHOUT
    # admission, for contrast)
    p99_1x = max(0.01, out["overload_1x_p99_ms"])
    out["overload_containment_ratio"] = round(
        out["overload_4x_p99_ms"] / p99_1x, 2
    )
    out["overload_collapse_ratio"] = round(
        out["overload_4x_noadmission_p99_ms"] / p99_1x, 2
    )
    return out


def _seg_pipeline(on_accel: bool, n_dev: int) -> dict:
    """Pipeline compiler: fused vs staged transform on a 3-fusable-stage
    pipeline (featurize -> jitted UDF -> logistic head). Records p50
    transform latency, rows/sec throughput, the one-time plan+XLA compile
    cost, and an element-wise equality flag (the compiler's correctness
    contract measured, not assumed)."""
    import jax.numpy as jnp

    from mmlspark_tpu import DataFrame, Pipeline
    from mmlspark_tpu.featurize.featurize import Featurize
    from mmlspark_tpu.models.linear import LogisticRegression
    from mmlspark_tpu.stages.basic import UDFTransformer

    n_rows = 16384 if on_accel else 8192
    n_raw = 16
    rng = np.random.default_rng(7)
    cols = {f"x{i}": rng.standard_normal(n_rows) for i in range(n_raw)}
    cols["vec"] = rng.standard_normal((n_rows, 16)).astype(np.float32)
    cols["label"] = rng.integers(0, 4, n_rows)
    df = DataFrame.from_dict(cols, num_partitions=4)

    pipe = Pipeline([
        Featurize(input_cols=[f"x{i}" for i in range(n_raw)] + ["vec"],
                  output_col="features"),
        UDFTransformer(input_col="features", output_col="features_s",
                       vector_udf=lambda x: jnp.tanh(x * jnp.float32(0.5)),
                       jit_compatible=True),
        LogisticRegression(features_col="features_s", label_col="label",
                           max_iter=30),
    ])
    model = pipe.fit(df)

    def p50_rows_per_sec(transform, reps: int = 7) -> tuple:
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = transform(df)
            _ = out["prediction"]  # materialize
            lat.append(time.perf_counter() - t0)
        lat.sort()
        p50 = lat[len(lat) // 2]
        return round(p50 * 1e3, 3), round(n_rows / p50, 1)

    model.transform(df)  # staged compiles
    staged_p50_ms, staged_rps = p50_rows_per_sec(model.transform)

    compiled = model.compile()
    t0 = time.perf_counter()
    fused_out = compiled.transform(df)
    compile_s = time.perf_counter() - t0
    fused_p50_ms, fused_rps = p50_rows_per_sec(compiled.transform)

    staged_out = model.transform(df)
    exact = all(
        staged_out[c].dtype == fused_out[c].dtype
        and np.array_equal(staged_out[c], fused_out[c])
        for c in staged_out.columns
    )
    return {
        "pipeline_rows": n_rows,
        "pipeline_stages_fused": compiled.num_fused_stages,
        "pipeline_segments": len(compiled.segments),
        "pipeline_staged_p50_ms": staged_p50_ms,
        "pipeline_fused_p50_ms": fused_p50_ms,
        "pipeline_staged_rows_per_sec": staged_rps,
        "pipeline_fused_rows_per_sec": fused_rps,
        "pipeline_fused_speedup": round(fused_rps / max(staged_rps, 1e-9), 3),
        "pipeline_compile_s": round(compile_s, 3),
        "pipeline_exact_equal": bool(exact),
    }


def _seg_elastic(on_accel: bool, n_dev: int) -> dict:
    """Elastic self-healing training (parallel/elastic.py): a real 2-host
    gang (subprocess trainers, TCP histogram allreduce, shared checkpoint
    dir) with one host SIGKILLed mid-round. Records the recovery story as
    numbers: host-loss detection latency, reshard-to-first-new-round
    time, kill-to-completion wall, and the per-round throughput retained
    after the shrink (world 2 -> world 1). Runs on CPU subprocesses on
    every backend — the elastic plane is host-side by design."""
    import json as _json
    import subprocess
    import tempfile

    from mmlspark_tpu.serving import fleet

    out: dict = {}
    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    work = tempfile.mkdtemp(prefix="bench-elastic-")
    ck = os.path.join(work, "ck")
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=HERE)
    stall_round = 12
    train_args = [
        "--data", "synth:4000x16:7", "--partitions", "8",
        "--num-iterations", "24", "--num-leaves", "15",
        "--min-data-in-leaf", "5", "--seed", "3",
        "--checkpoint-every", "2", "--heartbeat-s", "0.25",
        "--no-growback",
    ]

    def spawn(name: str, fault: str = None) -> subprocess.Popen:
        argv = [sys.executable, "-m", "mmlspark_tpu.serving.fleet"]
        if fault:
            argv += ["--fault-plan", fault]
        argv += [
            "train", "--registry", reg.url, "--name", name,
            "--ckpt-dir", ck, "--world-size", "2",
            "--status-file", os.path.join(work, f"{name}.json"),
            *train_args,
        ]
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        )

    surv = vict = None
    try:
        fault = _json.dumps({"rules": [
            {"point": "gbdt.round", "at": [stall_round], "delay_s": 600},
        ]})
        surv = spawn("a")
        vict = spawn("b", fault=fault)
        latest = os.path.join(ck, "LATEST")
        deadline = time.monotonic() + 150.0
        while time.monotonic() < deadline:
            try:
                with open(latest) as f:
                    if f.read().strip() == f"round-{stall_round:07d}":
                        break
            except OSError:
                pass
            if vict.poll() is not None:
                raise RuntimeError(
                    "victim died early: " + vict.communicate()[1][-500:]
                )
            time.sleep(0.1)
        with open(latest) as f:
            if f.read().strip() != f"round-{stall_round:07d}":
                # never kill from an arbitrary earlier state: the
                # recorded numbers must measure THE mid-round-kill
                # scenario or fail the segment honestly
                raise RuntimeError(
                    f"gang never reached round {stall_round} within the "
                    "wait budget"
                )
        time.sleep(0.6)  # survivor is inside round 12's gang allreduce
        kill_t = time.monotonic()
        vict.kill()
        _, err = surv.communicate(timeout=240)
        if surv.returncode != 0:
            raise RuntimeError("survivor failed: " + err[-500:])
        done_t = time.monotonic()
        with open(os.path.join(work, "a.json")) as f:
            status = _json.load(f)
        pre = status.get("rounds_per_s_pre") or 0.0
        post = status.get("rounds_per_s_post") or 0.0
        out["elastic_world"] = 2
        out["elastic_reshards"] = status.get("reshards", 0)
        out["elastic_detect_latency_s"] = status.get("detect_latency_s")
        out["elastic_reshard_to_first_round_s"] = status.get(
            "reshard_to_first_round_s"
        )
        out["elastic_kill_to_done_s"] = round(done_t - kill_t, 3)
        out["elastic_rounds_per_s_pre_shrink"] = pre
        out["elastic_rounds_per_s_post_shrink"] = post
        # per-HOST round throughput retained after losing half the gang
        # (the survivor now histograms ALL rows but skips the allreduce)
        out["elastic_throughput_retained"] = (
            round(post / pre, 3) if pre else None
        )
        out["elastic_resume_round"] = status.get("resume_round")
    finally:
        # failure paths must not leak trainer subprocesses (the victim
        # sits in a 600s injected stall; the survivor may be mid-run)
        for proc in (surv, vict):
            if proc is not None and proc.poll() is None:
                proc.kill()
        for proc in (surv, vict):
            if proc is not None:
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001 — best-effort reap
                    pass
        reg.stop()
    try:
        out.update(_elastic_scale(env))
    except Exception as e:  # noqa: BLE001 — the base segment's measured
        # recovery numbers must survive a scale-block failure
        out["elastic_scale_error"] = str(e)[:200]
    try:
        out.update(_elastic_partition(env))
    except Exception as e:  # noqa: BLE001 — same isolation as the
        # scale block: a partition-block failure keeps the base numbers
        out["elastic_partition_error"] = str(e)[:200]
    return out


def _elastic_partition(env: dict) -> dict:
    """The PR-16 split-brain numbers: a 2-host gang whose minority
    member reaches the registry only through a chaos proxy. A
    conductor ``partition`` blackholes that link — the majority
    declares the minority dead and CAS-commits the next generation; the
    minority loses its registry quorum and PARKS (stops training, keeps
    heartbeating, commits nothing). Records partition-to-park latency
    (how fast a minority fences itself off), heal-to-rejoin latency
    (grow-back is ON here: the healed member is re-invited at the next
    checkpoint boundary), and the zombie-commit rejection count (three
    stale-epoch CAS attempts, all refused by the registry)."""
    import json as _json
    import subprocess
    import tempfile
    import urllib.parse

    from mmlspark_tpu import obs
    from mmlspark_tpu.chaos.conductor import ChaosConductor, Scenario
    from mmlspark_tpu.chaos.wire import ChaosProxy
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        GenerationConflictError,
        QuorumLostError,
    )
    from mmlspark_tpu.serving import fleet

    def cas_rejections() -> float:
        samples = obs.parse_text(obs.render())
        return sum(
            obs.sum_samples(
                samples, "mmlspark_registry_cas_commits_total",
                {"result": r},
            )
            for r in ("stale", "conflict")
        )

    out: dict = {}
    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    work = tempfile.mkdtemp(prefix="bench-elastic-part-")
    ck = os.path.join(work, "ck")
    reg_port = urllib.parse.urlparse(reg.url).port
    proxy = ChaosProxy(
        "127.0.0.1", reg_port, seed=13, name="reg-b"
    ).start()
    deadline = time.monotonic() + float(
        os.environ.get("MMLSPARK_BENCH_ELASTIC_PARTITION_BUDGET", "150")
    )

    def left(floor: float = 10.0) -> float:
        rem = deadline - time.monotonic()
        if rem < floor:
            raise RuntimeError(
                "elastic partition block over its wall budget "
                "(MMLSPARK_BENCH_ELASTIC_PARTITION_BUDGET)"
            )
        return rem

    train_args = [
        "--data", "synth:4000x16:7", "--partitions", "8",
        # iterations sized so the MAJORITY is still training through
        # heal + the next grow-back boundary (the gang is killed once
        # the latencies land — this block never waits for completion)
        "--num-iterations", "400", "--num-leaves", "15",
        "--min-data-in-leaf", "5", "--seed", "3",
        "--checkpoint-every", "2", "--heartbeat-s", "0.25",
        # grow-back stays ON: heal-to-rejoin latency IS the number
    ]

    def spawn(name: str, reg_url: str, extra=()) -> subprocess.Popen:
        argv = [
            sys.executable, "-m", "mmlspark_tpu.serving.fleet",
            "train", "--registry", reg_url, "--name", name,
            "--ckpt-dir", ck, "--world-size", "2",
            "--status-file", os.path.join(work, f"{name}.json"),
            *train_args, *extra,
        ]
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        )

    def status(name: str) -> dict:
        try:
            with open(os.path.join(work, f"{name}.json")) as f:
                return _json.load(f)
        except (OSError, ValueError):
            return {}

    surv = vict = None
    try:
        surv = spawn("a", reg.url)
        vict = spawn(
            "b", proxy.url, extra=["--gen-timeout-s", "240"],
        )
        latest = os.path.join(ck, "LATEST")
        while left():
            try:
                with open(latest) as f:
                    if f.read().strip() >= "round-0000004":
                        break
            except OSError:
                pass
            for p in (surv, vict):
                if p.poll() is not None:
                    raise RuntimeError(
                        "trainer died before the partition: "
                        + p.communicate()[1][-500:]
                    )
            time.sleep(0.05)
        ChaosConductor(Scenario.from_spec({"seed": 13, "steps": [
            {"at_s": 0.0, "action": "partition", "links": ["reg-b"]},
        ]}), proxies={"reg-b": proxy}).run()
        partition_t = time.monotonic()
        while left():
            if status("b").get("parked"):
                break
            time.sleep(0.05)
        park_t = time.monotonic()
        out["elastic_partition_to_park_s"] = round(park_t - partition_t, 3)
        sb = status("b")
        out["elastic_partition_minority_commits"] = len(
            sb.get("committed_gens", ())
        )
        ChaosConductor(Scenario.from_spec({"seed": 13, "steps": [
            {"at_s": 0.0, "action": "heal", "links": ["reg-b"]},
        ]}), proxies={"reg-b": proxy}).run()
        heal_t = time.monotonic()
        rejoin_s = None
        # a soft deadline: a missed grow-back loses only THIS number,
        # never the park latency already measured above
        rejoin_deadline = time.monotonic() + min(
            45.0, max(0.0, deadline - time.monotonic() - 15.0)
        )
        while time.monotonic() < rejoin_deadline:
            sb = status("b")
            if (
                not sb.get("parked")
                and sb.get("gen", 0) >= 3
                and "b" in sb.get("members", ())
            ):
                rejoin_s = round(time.monotonic() - heal_t, 3)
                break
            if surv.poll() is not None:
                break  # majority finished before the grow-back boundary
            time.sleep(0.05)
        out["elastic_heal_to_rejoin_s"] = rejoin_s
        # the zombie: three stale-epoch CAS attempts against the live
        # registry, every one refused (the count is the headline — a
        # zero here would mean a rollback LANDED)
        before = cas_rejections()
        z = GangMember(reg.url, "z", heartbeat_s=5.0)
        try:
            z.adopt(Generation(gen=1, members=["a", "b"]))
            for k in range(3):
                try:
                    z.commit_generation(
                        Generation(gen=2 + k, members=["z"]),
                        expected_gen=1,
                    )
                except (GenerationConflictError, QuorumLostError):
                    pass
        finally:
            z.close()
        out["elastic_zombie_rejections"] = int(cas_rejections() - before)
    finally:
        for proc in (surv, vict):
            if proc is not None and proc.poll() is None:
                proc.kill()
        for proc in (surv, vict):
            if proc is not None:
                try:
                    proc.wait(timeout=10)
                except Exception:  # noqa: BLE001 — best-effort reap
                    pass
        proxy.stop()
        reg.stop()
    return out


def _elastic_scale(env: dict) -> dict:
    """The PR-14 scale story: a >= 1M-row OUT-OF-CORE gang (streaming
    sketch binning + ring reduce-scatter; at this d=16 shape the
    feature-block overlap pipeline stays on one block by design — it
    engages at d >= 32) where distribution finally PAYS. Three
    identically-shaped 8-round runs (fresh process each, same chunking)
    supply the like-for-like numbers: world-2 ring vs world-1 rounds/s
    on the same box — the headline speedup, cold-start and EWMA
    structure cancelling out — and world-2 ring vs world-2 full-mesh
    payload-bytes-per-round (the one-off sketch-merge/ingest bytes
    subtracted via the status file's ingest_payload_bytes; recurring
    checkpoint gathers stay in, they are steady-state traffic). A
    separate world-2 ring run is then SIGKILLed mid-round for the
    recovery story (detect latency, kill-to-done) and its survivor's
    booster is compared byte-for-byte against a fresh world-1 run
    resumed from the reshard snapshot (the PR-10 contract at 1M rows).
    """
    import json as _json
    import subprocess
    import tempfile

    from mmlspark_tpu.serving import fleet

    rows = int(os.environ.get("MMLSPARK_BENCH_ELASTIC_ROWS", "1000000"))
    if rows <= 0:
        return {}
    out: dict = {"elastic_scale_rows": rows}
    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    work = tempfile.mkdtemp(prefix="bench-elastic-scale-")
    kill_round = 8
    total_rounds = 16
    # the block's own wall budget, strictly inside the 600s elastic
    # segment watchdog: every wait below is capped at the REMAINING
    # budget, so a wedged phase raises here (caught by _seg_elastic,
    # base recovery numbers preserved) instead of tripping the parent
    # watchdog and losing the whole segment
    deadline = time.monotonic() + float(
        os.environ.get("MMLSPARK_BENCH_ELASTIC_SCALE_BUDGET", "480")
    )

    def left(floor: float = 30.0) -> float:
        rem = deadline - time.monotonic()
        if rem < floor:
            raise RuntimeError(
                "elastic scale block over its wall budget "
                "(MMLSPARK_BENCH_ELASTIC_SCALE_BUDGET)"
            )
        return rem

    def args(iters: int, mode: str) -> list:
        return [
            "--data", f"stream-synth:{rows}x16:11", "--partitions", "8",
            "--num-iterations", str(iters), "--num-leaves", "31",
            "--min-data-in-leaf", "20", "--seed", "3",
            "--checkpoint-every", "4", "--heartbeat-s", "0.25",
            "--growth-policy", "depthwise", "--reduce-mode", mode,
            "--no-growback",
        ]

    def spawn(tag, name, ck, world, iters, mode, fault=None, extra=()):
        argv = [sys.executable, "-m", "mmlspark_tpu.serving.fleet"]
        if fault:
            argv += ["--fault-plan", fault]
        argv += [
            "train", "--registry", reg.url, "--name", name,
            "--ckpt-dir", ck, "--world-size", str(world),
            "--status-file", os.path.join(work, f"{tag}-{name}.json"),
            "--out-model", os.path.join(work, f"{tag}-{name}.model"),
            *args(iters, mode), *extra,
        ]
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        )

    procs: list = []
    try:
        # -- payload-bytes-per-round: ring vs full-mesh on identical
        # work. These same-shape 8-round runs (fresh process, rounds
        # 0-8, same chunking) are ALSO the throughput comparison: the
        # ring world-2 run's rounds/s against an identically-shaped
        # world-1 run — cold-start and EWMA structure cancel out, so
        # the speedup compares like with like
        for tag, world, mode in (
            ("ring", 2, "ring"), ("mesh", 2, "mesh"), ("solo", 1, "ring"),
        ):
            ck = os.path.join(work, f"ck-{tag}")
            group = [
                spawn(tag, f"{tag}{i}", ck, world, 8, mode)
                for i in range(world)
            ]
            procs += group
            for p in group:
                _, err = p.communicate(timeout=left())
                if p.returncode != 0:
                    raise RuntimeError(
                        f"{tag} baseline failed: " + err[-500:]
                    )
            with open(os.path.join(work, f"{tag}-{tag}0.json")) as f:
                st = _json.load(f)
            if world > 1:
                rounds_bytes = st["payload_bytes"] - st.get(
                    "ingest_payload_bytes", 0
                )
                out[f"elastic_scale_{mode}_payload_bytes_per_round"] = \
                    int(rounds_bytes / 8)
            if tag == "ring":
                out["elastic_scale_world2_rounds_per_s"] = \
                    st.get("rounds_per_s_post") or 0.0
            if tag == "solo":
                out["elastic_scale_world1_rounds_per_s"] = \
                    st.get("rounds_per_s_post") or 0.0
        out["elastic_scale_ring_payload_ratio"] = round(
            out["elastic_scale_ring_payload_bytes_per_round"]
            / max(out["elastic_scale_mesh_payload_bytes_per_round"], 1),
            3,
        )
        w2 = out["elastic_scale_world2_rounds_per_s"]
        w1 = out["elastic_scale_world1_rounds_per_s"]
        # THE headline: >1.0 means the 2-host gang beats the solo host
        # per round at real data scale (r08 recorded the inverse)
        out["elastic_scale_world2_speedup"] = (
            round(w2 / w1, 3) if w1 else None
        )
        # -- the kill run: world-2 ring, victim stalled entering round 8
        ck = os.path.join(work, "ck-kill")
        fault = _json.dumps({"rules": [
            {"point": "gbdt.round", "at": [kill_round], "delay_s": 600},
        ]})
        surv = spawn("kill", "a", ck, 2, total_rounds, "ring")
        vict = spawn("kill", "b", ck, 2, total_rounds, "ring",
                     fault=fault)
        procs += [surv, vict]
        latest = os.path.join(ck, "LATEST")
        wait_deadline = time.monotonic() + min(300.0, left())
        target = f"round-{kill_round:07d}"
        while time.monotonic() < wait_deadline:
            try:
                with open(latest) as f:
                    if f.read().strip() == target:
                        break
            except OSError:
                pass
            if vict.poll() is not None:
                raise RuntimeError(
                    "scale victim died early: "
                    + vict.communicate()[1][-500:]
                )
            time.sleep(0.2)
        with open(latest) as f:
            if f.read().strip() != target:
                raise RuntimeError(
                    f"scale gang never reached round {kill_round}"
                )
        time.sleep(1.0)  # survivor is inside the round's ring exchange
        kill_t = time.monotonic()
        vict.kill()
        _, err = surv.communicate(timeout=left())
        if surv.returncode != 0:
            raise RuntimeError("scale survivor failed: " + err[-500:])
        done_t = time.monotonic()
        with open(os.path.join(work, "kill-a.json")) as f:
            st = _json.load(f)
        out["elastic_scale_detect_latency_s"] = st.get("detect_latency_s")
        out["elastic_scale_kill_to_done_s"] = round(done_t - kill_t, 3)
        # -- bit-identity through kill -> reshard -> resume at 1M rows
        fresh = spawn(
            "fresh", "c", os.path.join(work, "ck-fresh"), 1,
            total_rounds, "ring",
            extra=["--resume-from", st["snapshot"]],
        )
        procs.append(fresh)
        _, err = fresh.communicate(timeout=left())
        if fresh.returncode != 0:
            raise RuntimeError("scale fresh-run failed: " + err[-500:])
        with open(os.path.join(work, "kill-a.model")) as f:
            surv_model = f.read()
        with open(os.path.join(work, "fresh-c.model")) as f:
            fresh_model = f.read()
        out["elastic_scale_bit_identical"] = bool(
            surv_model == fresh_model
        )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — best-effort reap
                pass
        reg.stop()
    return out


def _seg_tune(on_accel: bool, n_dev: int) -> dict:
    """Fleet-parallel ASHA (``fleet tune``) vs the sequential in-process
    TuneHyperparameters at EQUAL trial budget — the same 4 sampled
    configurations. ASHA runs the trials concurrently as supervisor
    charges AND early-stops the losers at rung boundaries, so it pays
    for the winner's full depth plus a fraction of everyone else's;
    the sequential tuner pays full depth (times k folds) for every
    draw, one after another. Records both wall-clocks, the speedup, and
    the trial-iteration budgets actually spent on each side. Runs on
    CPU subprocesses on every backend — like the elastic plane, trial
    scheduling is host-side by design."""
    import shutil
    import tempfile

    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.experiments import asha
    from mmlspark_tpu.experiments.controller import ExperimentController

    out: dict = {}
    n_trials = 4
    min_it, max_it, eta = 16, 256, 4
    data, valid = "synth:6000x16:1", "synth:2048x16:99"
    work = tempfile.mkdtemp(prefix="bench-tune-")
    # trial charges inherit the environment: pin them to CPU (each
    # `fleet trial` places its own compile cache — core/compile_cache.py)
    saved = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "PYTHONPATH")}
    os.environ.update(JAX_PLATFORMS="cpu", PYTHONPATH=HERE)
    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=2.0)
    ctrl = ExperimentController(
        reg.url, "bench", n_trials=n_trials, data=data, valid=valid,
        min_iters=min_it, max_iters=max_it, eta=eta, seed=11,
        workdir=work, deadline_s=240.0,
    )
    try:
        t0 = time.monotonic()
        res = ctrl.run()
        asha_wall = time.monotonic() - t0
        out["tune_asha_wall_s"] = round(asha_wall, 2)
        out["tune_asha_metric"] = round(float(res["winner"]["metric"]), 4)
        out["tune_trials"] = n_trials
        # trial-iterations ASHA actually spent: survivors per rung times
        # that rung's incremental depth (the early-stopping dividend)
        bounds = asha.rung_boundaries(min_it, max_it, eta)
        survivors = n_trials
        spent = 0
        for r, b in enumerate(bounds):
            prev = bounds[r - 1] if r else 0
            spent += survivors * (b - prev)
            survivors = asha.n_promote(survivors, eta)
        out["tune_asha_trial_iters"] = spent
    finally:
        ctrl.close()
        reg.stop()
        shutil.rmtree(work, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # sequential baseline: the same trial budget through the in-process
    # tuner (k=2 folds, its methodological floor)
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.automl import (
        DiscreteHyperParam,
        HyperparamBuilder,
        RangeHyperParam,
        TuneHyperparameters,
    )
    from mmlspark_tpu.models.gbdt import LightGBMClassifier
    from mmlspark_tpu.parallel.elastic import load_training_data

    x, y = load_training_data(data)
    df = DataFrame.from_dict({"features": x, "label": y})
    spaces = (
        HyperparamBuilder()
        .add_hyperparam("num_leaves", DiscreteHyperParam([7, 15, 31]))
        .add_hyperparam(
            "learning_rate", RangeHyperParam(0.05, 0.3, log=True)
        )
        .add_hyperparam("min_data_in_leaf", DiscreteHyperParam([5, 10, 20]))
        .build()
    )
    tuner = TuneHyperparameters(label_col="label")
    tuner.set(
        models=[LightGBMClassifier(num_iterations=max_it)],
        hyperparams=spaces, number_of_runs=n_trials, number_of_folds=2,
        seed=11,
    )
    t0 = time.monotonic()
    model = tuner.fit(df)
    seq_wall = time.monotonic() - t0
    out["tune_seq_wall_s"] = round(seq_wall, 2)
    out["tune_seq_metric"] = round(float(model.get("best_metric")), 4)
    out["tune_seq_trial_iters"] = n_trials * 2 * max_it  # k folds, full depth
    out["tune_speedup"] = round(seq_wall / max(asha_wall, 1e-9), 2)
    return out


def _seg_artifact(on_accel: bool, n_dev: int) -> dict:
    """Content-addressed artifact plane (serving/artifacts.py): the
    transfer rates the no-shared-fs recovery story pays for. Records
    push (put: pack+hash+install) and pull (ranged HTTP fetch + verify)
    MB/s over loopback, the sha256 verify overhead as a fraction of the
    pull, and the kill-mid-transfer story as a number: a peer that dies
    half-way through the body, with the fetch resuming from the byte
    offset on a second peer — resume-to-done wall seconds and the bytes
    that did NOT have to be re-transferred.

    PR 20 adds the push plane: replication-before-ack to two holders
    timed against the shared-filesystem baseline it replaces (two
    ``shutil.copyfile``), a mid-push RST with the retry resuming from
    the receiver's durable offset (overhead and bytes saved), and
    snapshot-to-servable — a vw snapshot put + replicated + resolved
    from a bare-hint artifact spec into a warmed LoadedModel, the
    no-shared-fs worker's boot path. Host-side by design: runs
    identically on every backend."""
    import hashlib
    import shutil
    import socket as socket_mod
    import tempfile
    import threading

    from mmlspark_tpu.serving.artifacts import (
        ArtifactServer,
        ArtifactStore,
    )

    out: dict = {}
    work = tempfile.mkdtemp(prefix="bench-artifact-")
    n_bytes = 32 << 20  # 32 MiB: big enough to time, small enough to bench
    payload = np.random.default_rng(0).integers(
        0, 256, size=n_bytes, dtype=np.uint8
    ).tobytes()
    src = os.path.join(work, "weights.bin")
    with open(src, "wb") as f:
        f.write(payload)
    try:
        producer = ArtifactStore(os.path.join(work, "producer"))
        t0 = time.perf_counter()
        ref = producer.put(src, name="weights.bin")
        push_s = time.perf_counter() - t0
        out["artifact_bytes_mb"] = round(n_bytes / 1e6, 1)
        out["artifact_push_mb_s"] = round(n_bytes / 1e6 / push_s, 1)
        srv = ArtifactServer(producer)
        consumer = ArtifactStore(os.path.join(work, "consumer"))
        t0 = time.perf_counter()
        consumer.fetch(ref.digest, [srv.url], name="weights.bin")
        pull_s = time.perf_counter() - t0
        out["artifact_pull_mb_s"] = round(n_bytes / 1e6 / pull_s, 1)
        # verify overhead: the sha256 pass every completed transfer pays
        t0 = time.perf_counter()
        hashlib.sha256(payload).hexdigest()
        verify_s = time.perf_counter() - t0
        out["artifact_verify_mb_s"] = round(n_bytes / 1e6 / verify_s, 1)
        out["artifact_verify_overhead_pct"] = round(
            100.0 * verify_s / pull_s, 1
        )

        # -- kill mid-transfer -> Range resume on a second peer ----------
        class TruncPeer:
            """Serves correct headers, sends half the body, dies."""

            def __init__(self):
                self._srv = socket_mod.create_server(("127.0.0.1", 0))
                self._srv.settimeout(0.5)
                self.port = self._srv.getsockname()[1]
                self.stop = threading.Event()
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                while not self.stop.is_set():
                    try:
                        conn, _ = self._srv.accept()
                    except socket_mod.timeout:
                        continue
                    except OSError:
                        return
                    try:
                        conn.settimeout(2.0)
                        data = b""
                        while b"\r\n\r\n" not in data:
                            data += conn.recv(4096)
                        body = payload
                        conn.sendall((
                            "HTTP/1.1 200 OK\r\n"
                            f"Content-Length: {len(body)}\r\n"
                            f"X-Artifact-Size: {len(body)}\r\n\r\n"
                        ).encode())
                        conn.sendall(body[: len(body) // 2])
                        conn.shutdown(socket_mod.SHUT_RDWR)
                    except OSError:
                        pass
                    finally:
                        conn.close()

            def close(self):
                self.stop.set()
                try:
                    self._srv.close()
                except OSError:
                    pass

        trunc = TruncPeer()
        resumer = ArtifactStore(os.path.join(work, "resumer"))
        from mmlspark_tpu import obs

        before = obs.parse_text(obs.render())
        t0 = time.perf_counter()
        resumer.fetch(
            ref.digest, [f"http://127.0.0.1:{trunc.port}", srv.url],
            name="weights.bin", backoffs_ms=(10,),
        )
        out["artifact_resume_to_done_s"] = round(
            time.perf_counter() - t0, 3
        )
        after = obs.parse_text(obs.render())
        out["artifact_resumes"] = int(obs.sum_samples(
            after, "mmlspark_artifact_resumes_total"
        ) - obs.sum_samples(before, "mmlspark_artifact_resumes_total"))
        out["artifact_resume_saved_mb"] = round(n_bytes / 2 / 1e6, 1)
        # what the RST cost vs an uninterrupted pull (includes the dead
        # first peer's half-body transfer and the failover)
        out["artifact_pull_resume_overhead_pct"] = round(
            100.0 * (out["artifact_resume_to_done_s"] - pull_s) / pull_s, 1
        )
        trunc.close()

        # -- push + replicate vs the shared-fs copy it replaces ----------
        holder_a = ArtifactStore(os.path.join(work, "holder-a"))
        holder_b = ArtifactStore(os.path.join(work, "holder-b"))
        srv_a = ArtifactServer(holder_a)
        srv_b = ArtifactServer(holder_b)
        t0 = time.perf_counter()
        confirmed = producer.replicate(
            ref.digest, [srv_a.url, srv_b.url], need=2, backoffs_ms=(10,)
        )
        repl_s = time.perf_counter() - t0
        out["artifact_push_replicate_2_s"] = round(repl_s, 3)
        out["artifact_push_replicate_2_mb_s"] = round(
            2 * n_bytes / 1e6 / repl_s, 1
        )
        assert len(confirmed) == 2
        t0 = time.perf_counter()
        shutil.copyfile(src, os.path.join(work, "copy-a.bin"))
        shutil.copyfile(src, os.path.join(work, "copy-b.bin"))
        copy_s = max(time.perf_counter() - t0, 1e-9)
        out["artifact_copy_2_s"] = round(copy_s, 3)
        out["artifact_push_replicate_vs_copy_x"] = round(repl_s / copy_s, 1)

        # -- mid-push RST -> retry resumes from the receiver's offset ----
        from mmlspark_tpu.chaos.wire import ChaosProxy, WireRule

        holder_c = ArtifactStore(os.path.join(work, "holder-c"))
        srv_c = ArtifactServer(holder_c)
        t0 = time.perf_counter()
        producer.push_to(srv_c.url, ref.digest)
        clean_push_s = max(time.perf_counter() - t0, 1e-9)
        holder_d = ArtifactStore(os.path.join(work, "holder-d"))
        srv_d = ArtifactServer(holder_d)
        # conn 0 is the offset probe, conn 1 the first 16 MiB window,
        # conn 2 the second — RST conn 2 mid-flight, so the receiver's
        # durable offset (windows install atomically) is one full window
        # the retry never re-sends
        wire = ChaosProxy(
            "127.0.0.1", srv_d.port,
            rules=[WireRule(
                "truncate_rst", direction="c2s",
                at_offset=1 << 20, conns=frozenset({2}),
            )],
        )
        wire.start()
        t0 = time.perf_counter()
        try:
            producer.push_to(f"http://127.0.0.1:{wire.port}", ref.digest)
        except Exception:  # noqa: BLE001 — the RST is the point
            pass
        part = os.path.join(holder_d.root, "partial", ref.digest + ".push")
        saved = os.path.getsize(part) if os.path.exists(part) else 0
        producer.push_to(srv_d.url, ref.digest)
        rst_push_s = time.perf_counter() - t0
        wire.stop()
        out["artifact_push_rst_to_done_s"] = round(rst_push_s, 3)
        out["artifact_push_resume_saved_mb"] = round(saved / 1e6, 1)
        out["artifact_push_resume_overhead_pct"] = round(
            100.0 * (rst_push_s - clean_push_s) / clean_push_s, 1
        )

        # -- snapshot-to-servable: the no-shared-fs worker's boot path ---
        from mmlspark_tpu.serving.modelstore.loaders import (
            build_loaded_model,
        )

        n_bits = 16
        snap = os.path.join(work, "bench-nofs-v000001.npz")
        meta = {"num_bits": n_bits, "loss": "logistic",
                "no_constant": False, "quantile_tau": 0.5}
        with open(snap, "wb") as f:
            np.savez(
                f,
                weights=np.zeros(1 << n_bits, np.float32),
                meta=json.dumps(meta).encode(),
            )
        pub = ArtifactStore(os.path.join(work, "nofs-pub"))
        t0 = time.perf_counter()
        ref2 = pub.put(snap, name=os.path.basename(snap))
        srv_p = ArtifactServer(pub)
        pub.replicate(ref2.digest, [srv_a.url], need=1, backoffs_ms=(10,))
        lm = build_loaded_model(
            f"artifact:vw:{ref2.spec}@{srv_a.url}"
        )
        lm.warmup()
        out["artifact_snapshot_to_servable_s"] = round(
            time.perf_counter() - t0, 3
        )
        lm.release()
        for s in (srv_a, srv_b, srv_c, srv_d, srv_p):
            s.stop()
        srv.stop()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def _seg_freshness(on_accel: bool, n_dev: int) -> dict:
    """Continuous learning: example->servable freshness under a sustained
    feedback stream WITH serving traffic concurrent (docs/online-learning.md).

    In-process fleet shape: a ModelStore worker serves the online model
    while the OnlineLearningLoop trains on streamed micro-batches and
    publishes every few hundred ms through the zero-drop load->warm->swap
    path. Records freshness p50/p99 over the run's publications,
    sustained training updates/sec, the swap count, the concurrent
    serving p50, and a deterministic autoscaler policy exercise
    (scripted overload->idle signals -> scale events)."""
    import http.client
    import tempfile
    import threading

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.online import (
        FeedbackStream,
        OnlineLearningLoop,
        OnlineTrainer,
        Publisher,
    )
    from mmlspark_tpu.serving.modelstore import ModelDispatcher, ModelStore
    from mmlspark_tpu.serving.server import WorkerServer

    bits = 16
    chunk_rows = 256
    rng = np.random.default_rng(11)

    def make_chunk() -> "DataFrame":
        rows = np.empty(chunk_rows, dtype=object)
        for r in range(chunk_rows):
            k = int(rng.integers(4, 13))
            rows[r] = {
                "i": rng.integers(0, 1 << bits, size=k).astype(np.int64),
                "v": rng.normal(size=k).astype(np.float32),
            }
        return DataFrame.from_dict({
            "features": rows,
            "label": rng.integers(0, 2, size=chunk_rows).astype(np.float64),
        })

    out: dict = {}
    stream = FeedbackStream(max_chunks=64)
    trainer = OnlineTrainer(num_bits=bits, batch=64)
    # compile warmup outside the measured window (first chunk traces the
    # SGD kernel; later chunks reuse the cached program per nnz bucket)
    trainer.step(make_chunk())
    store = ModelStore()
    srv = WorkerServer()
    info = srv.start()
    disp = ModelDispatcher(srv, store, default_model="vw-online").start()
    stop_all = threading.Event()
    run_s = 8.0 if on_accel else 6.0

    def producer() -> None:
        # sustained feedback: one micro-batch every ~40 ms (~6k rows/s)
        while not stop_all.is_set():
            try:
                stream.push(make_chunk())
            except Exception:  # noqa: BLE001 — injected-fault-free here
                pass
            stop_all.wait(0.04)

    served: dict = {"ok": 0, "err": 0, "lat": []}

    def traffic() -> None:
        conn = http.client.HTTPConnection("127.0.0.1", info.port, timeout=10)
        payload = json.dumps({"i": [1, 2, 3], "v": [1.0, 0.5, -0.25]})
        while not stop_all.is_set():
            t0 = time.perf_counter()
            try:
                conn.request(
                    "POST", "/", body=payload,
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                resp.read()
                ok = resp.status == 200
            except Exception:  # noqa: BLE001 — a drop, the gated number
                ok = False
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", info.port, timeout=10
                )
            served["ok" if ok else "err"] += 1
            served["lat"].append((time.perf_counter() - t0) * 1e3)
            time.sleep(0.002)
        conn.close()

    with tempfile.TemporaryDirectory() as snapdir:
        pub = Publisher(model="vw-online", snapshot_dir=snapdir, store=store)
        loop = OnlineLearningLoop(
            stream, trainer, pub, publish_every_s=0.5, poll_s=0.05,
        ).start()
        threads = [
            threading.Thread(target=producer, daemon=True),
        ]
        t_traffic = threading.Thread(target=traffic, daemon=True)
        for t in threads:
            t.start()
        # serving traffic starts once v1 is servable, so every request in
        # the window rides the hot-swap path at least once
        deadline = time.monotonic() + 30.0
        while store.serving_version("vw-online") is None and (
            time.monotonic() < deadline
        ):
            time.sleep(0.02)
        t_traffic.start()
        t0 = time.perf_counter()
        time.sleep(run_s)
        stop_all.set()
        for t in threads + [t_traffic]:
            t.join(5.0)
        wall = time.perf_counter() - t0
        loop.stop(final_publish=False)
        stats = loop.stats()
    disp.stop()
    srv.stop()
    fresh = sorted(stats["freshness_history_s"])
    if fresh:
        out["freshness_p50_ms"] = round(fresh[len(fresh) // 2] * 1e3, 1)
        out["freshness_p99_ms"] = round(
            fresh[min(len(fresh) - 1, int(len(fresh) * 0.99))] * 1e3, 1
        )
    out["freshness_publishes"] = stats["publishes"]
    out["freshness_publish_failures"] = stats["publish_failures"]
    out["online_examples"] = stats["examples"]
    out["online_updates_per_sec"] = round(stats["examples"] / wall, 1)
    out["online_dropped_chunks"] = stats["dropped_chunks"]
    out["freshness_swap_count"] = max(0, stats["publishes"] - 1)  # v1 aliases
    out["freshness_serving_ok"] = served["ok"]
    out["freshness_serving_errors"] = served["err"]
    if served["lat"]:
        lat = np.sort(np.asarray(served["lat"][20:] or served["lat"]))
        out["freshness_serving_concurrent_p50_ms"] = round(
            float(lat[len(lat) // 2]), 3
        )
    # autoscaler policy exercise: deterministic scripted signals through
    # the real decide() machinery — overload scales out to the cap, a
    # sustained idle window reaps back down; the recorded event count is
    # the policy working, not a simulation of it
    from mmlspark_tpu.online.autoscaler import Autoscaler, ScaleSignals

    clock = {"t": 0.0}
    asc = Autoscaler(
        min_replicas=1, max_replicas=3, scale_out_cooldown_s=1.0,
        scale_in_cooldown_s=2.0, idle_after_s=5.0,
        time_fn=lambda: clock["t"],
    )
    replicas = 1
    for _ in range(4):  # overload ticks: sheds observed
        clock["t"] += 2.0
        replicas, _why = asc.decide(
            replicas, ScaleSignals(shed_delta=5.0, inflight=8, limit=8)
        )
    for _ in range(8):  # idle ticks
        clock["t"] += 2.0
        replicas, _why = asc.decide(replicas, ScaleSignals())
    out["autoscaler_scale_out_events"] = sum(
        1 for d, _ in asc.events if d == "out"
    )
    out["autoscaler_scale_in_events"] = sum(
        1 for d, _ in asc.events if d == "in"
    )
    out["autoscaler_final_replicas"] = replicas
    return out


def _seg_throughput(on_accel: bool, n_dev: int) -> dict:
    """Data-plane throughput at a fixed p99 bound (ISSUE 12 acceptance):
    closed-loop keep-alive clients through the FULL rewritten path —
    multi-reactor gateway ingress -> pooled zero-re-parse forwarding ->
    multi-reactor worker -> continuous-batching ModelDispatcher — for
    the echo model AND a 3-stage fused ``pipeline:`` model scored
    through the columnar array fast path (asserted fallback-free).

    The number to beat is the r09 overload bench's 93 rps 4x-load
    goodput (a synthetic-capacity bound the old plumbing saturated
    at); the target is >= 10x that at a p99 under the bound. The
    overload segment still runs unchanged — it measures containment
    under a deliberately slow model; this measures the plumbing.

    Deployment shape matters for an honest number: worker, gateway and
    load generators each run as their OWN subprocess (as in any real
    fleet) — in-process client threads would fight the serving threads
    for the GIL and measure the bench, not the data plane."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from mmlspark_tpu import DataFrame, Pipeline
    from mmlspark_tpu.featurize.featurize import Featurize
    from mmlspark_tpu.models.linear import LogisticRegression
    from mmlspark_tpu.stages.basic import UDFTransformer

    P99_BOUND_MS = 50.0
    R09_GOODPUT = 93.0
    n_procs, n_threads = 4, 4  # 4 client processes x 4 keep-alive threads
    dur_s = 3.0

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # serving plumbing is host-side

    def spawn(code: str, *args: str):
        # payloads travel via a temp FILE path in argv (clients read
        # sys.argv[5]) — NOT stdin: communicate(input=...) silently
        # drops input when stdin isn't a pipe, which burned one round
        # of this bench. stdin=PIPE just detaches children from the
        # parent's stdin
        return subprocess.Popen(
            [sys.executable, "-c", code, *args], env=env,
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )

    def first_line(proc, what: str, timeout_s: float = 120.0) -> dict:
        line = [None]

        def read():
            line[0] = proc.stdout.readline()

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout_s)
        if not line[0]:
            proc.kill()
            raise RuntimeError(f"{what} did not report in {timeout_s}s: "
                               f"{proc.stderr.read()[-500:]}")
        return json.loads(line[0])

    _WORKER_CODE = """
import json, sys, time
from mmlspark_tpu.serving.modelstore import ModelDispatcher, ModelStore
from mmlspark_tpu.serving.server import WorkerServer
store = ModelStore()
store.load("echo", "echo", wait=True)
if sys.argv[1] != "-":
    store.load("scorer", "pipeline:" + sys.argv[1], wait=True)
srv = WorkerServer(name="tpbench", num_reactors=2)
info = srv.start()
disp = ModelDispatcher(srv, store, default_model="echo",
                       max_batch_size=64, pipeline_depth=2).start()
print(json.dumps({"port": info.port}), flush=True)
time.sleep(600)
"""

    _GATEWAY_CODE = """
import json, sys, time
from mmlspark_tpu.serving.distributed import ServingGateway
from mmlspark_tpu.serving.server import ServiceInfo
gw = ServingGateway(
    workers=[ServiceInfo(name="serving", host="127.0.0.1",
                         port=int(sys.argv[1]),
                         models=("echo", "scorer"))],
    num_dispatchers=4, num_reactors=2, request_timeout_s=30.0,
)
info = gw.start()
print(json.dumps({"port": info.port}), flush=True)
time.sleep(600)
"""

    # closed-loop load generator: keep-alive threads hammer as fast as
    # replies come back; warm window driven but unrecorded
    _CLIENT_CODE = """
import http.client, json, sys, threading, time
port, path, dur_s, n_threads = (int(sys.argv[1]), sys.argv[2],
                                float(sys.argv[3]), int(sys.argv[4]))
payload = open(sys.argv[5], "rb").read()
warm_s = float(sys.argv[6])
lock = threading.Lock()
lats, errs = [], [0]
start_t = time.perf_counter() + 0.05
warm_t = start_t + warm_s
stop_t = warm_t + dur_s
def client():
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    while True:
        t0 = time.perf_counter()
        if t0 >= stop_t:
            break
        try:
            conn.request("POST", path, body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            ok = resp.status == 200
        except Exception:
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            ok = False
        dt = (time.perf_counter() - t0) * 1e3
        if t0 < warm_t:
            continue
        with lock:
            (lats.append(round(dt, 3)) if ok else errs.__setitem__(
                0, errs[0] + 1))
ts = [threading.Thread(target=client) for _ in range(n_threads)]
[t.start() for t in ts]
[t.join(dur_s + 40.0) for t in ts]
print(json.dumps({"lats": lats, "errors": errs[0]}), flush=True)
"""

    def drive(port: int, path: str, payload: bytes, rows_per_req: int,
              warm_s: float = 0.8, procs_n: int = n_procs) -> dict:
        """``warm_s``: driven-but-unrecorded ramp — long enough for every
        dispatcher-batch bucket the load shape produces to have compiled
        (the pipeline drive sees row counts 8..512, i.e. 7 buckets)."""
        pf = os.path.join(tmp, "payload.json")
        with open(pf, "wb") as f:
            f.write(payload)
        # every generator starts at once — their measurement windows
        # overlap, the merged latencies are one offered-load picture
        procs = [
            spawn(_CLIENT_CODE, str(port), path, str(dur_s),
                  str(n_threads), pf, str(warm_s))
            for _ in range(procs_n)
        ]
        lats: list = []
        errors = 0
        for p in procs:
            out_s, _ = p.communicate(timeout=dur_s + 60.0)
            res = json.loads(out_s.strip().splitlines()[-1])
            lats.extend(res["lats"])
            errors += res["errors"]
        arr = np.sort(np.asarray(lats)) if lats else np.asarray([0.0])
        return {
            "rps": round(len(lats) / dur_s, 1),
            "rows_per_s": round(len(lats) * rows_per_req / dur_s, 1),
            "p50_ms": round(float(arr[len(arr) // 2]), 2),
            "p99_ms": round(float(arr[int((len(arr) - 1) * 0.99)]), 2),
            "errors": errors,
        }

    def fallback_count(port: int) -> int:
        """Worker-side compiler fallbacks, scraped off its /metrics."""
        import http.client as hc
        import re as _re

        conn = hc.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        return sum(int(v) for v in _re.findall(
            r"mmlspark_compiler_fallback_total\{[^}]*\} (\d+)", text
        ))

    out: dict = {
        "throughput_p99_bound_ms": P99_BOUND_MS,
        "throughput_r09_goodput_rps": R09_GOODPUT,
        "throughput_clients": n_procs * n_threads,
    }

    # fused 3-stage pipeline: featurize -> jitted UDF -> logistic
    rng = np.random.default_rng(7)
    n_fit = 2048
    cols = {f"x{i}": rng.standard_normal(n_fit) for i in range(8)}
    cols["vec"] = rng.standard_normal((n_fit, 8)).astype(np.float32)
    cols["label"] = rng.integers(0, 2, n_fit)
    fit_df = DataFrame.from_dict(cols, num_partitions=1)
    pipe = Pipeline([
        Featurize(input_cols=[f"x{i}" for i in range(8)] + ["vec"],
                  output_col="features"),
        UDFTransformer(input_col="features", output_col="features_s",
                       vector_udf=lambda x: jnp.tanh(x * jnp.float32(0.5)),
                       jit_compatible=True),
        LogisticRegression(features_col="features_s", label_col="label",
                           max_iter=10),
    ])
    model = pipe.fit(fit_df)
    tmp = tempfile.mkdtemp(prefix="tpbench-")
    worker = gateway = None
    try:
        pdir = os.path.join(tmp, "scorer")
        model.save(pdir)
        with open(os.path.join(pdir, "warmup.json"), "w") as f:
            json.dump(
                {**{f"x{i}": [0.0] * 8 for i in range(8)},
                 "vec": [[0.0] * 8] * 8, "label": [0] * 8}, f,
            )
        worker = spawn(_WORKER_CODE, pdir)
        wport = first_line(worker, "throughput worker")["port"]
        gateway = spawn(_GATEWAY_CODE, str(wport))
        gport = first_line(gateway, "throughput gateway")["port"]

        echo_payload = json.dumps({"x": [0.1] * 16}).encode()
        direct = drive(wport, "/", echo_payload, 1)
        out["throughput_echo_direct_rps"] = direct["rps"]
        out["throughput_echo_direct_p50_ms"] = direct["p50_ms"]
        out["throughput_echo_direct_p99_ms"] = direct["p99_ms"]
        gwres = drive(gport, "/", echo_payload, 1)
        out["throughput_echo_rps"] = gwres["rps"]
        out["throughput_echo_p50_ms"] = gwres["p50_ms"]
        out["throughput_echo_p99_ms"] = gwres["p99_ms"]
        out["throughput_echo_errors"] = gwres["errors"] + direct["errors"]

        # columnar fast path: 8 rows per request, one fused transform per
        # dispatcher batch, asserted fallback-free off the worker
        # metrics. select narrows the reply to the head's outputs —
        # the full reply would echo every intermediate feature vector,
        # and at these rates the reply ENCODE becomes the bottleneck,
        # not the data plane under test
        rows_n = 8
        cols_body = json.dumps({
            "cols": {
                **{f"x{i}": [round(0.1 * r, 3) for r in range(rows_n)]
                   for i in range(8)},
                "vec": [[0.05] * 8 for _ in range(rows_n)],
                "label": [0] * rows_n,
            },
            "select": ["prediction", "probability"],
        }).encode()
        fb_before = fallback_count(wport)
        # Direct first: r09's 93-rps goodput was recorded worker-direct
        # (the overload bench has no gateway), so the like-for-like
        # 10x comparison is the worker-direct number; the gateway run
        # (8 clients — deeper concurrency through the extra hop only
        # buys batch-queue depth; closed-loop law: rps = concurrency /
        # latency) prices the distributed hop on top
        pdirect = drive(wport, "/models/scorer", cols_body, rows_n,
                        warm_s=3.0, procs_n=3)
        out["throughput_pipeline_direct_rps"] = pdirect["rps"]
        out["throughput_pipeline_direct_rows_per_s"] = pdirect["rows_per_s"]
        out["throughput_pipeline_direct_p50_ms"] = pdirect["p50_ms"]
        out["throughput_pipeline_direct_p99_ms"] = pdirect["p99_ms"]
        pres = drive(gport, "/models/scorer", cols_body, rows_n,
                     warm_s=1.0, procs_n=2)
        out["throughput_pipeline_rps"] = pres["rps"]
        out["throughput_pipeline_rows_per_s"] = pres["rows_per_s"]
        out["throughput_pipeline_p50_ms"] = pres["p50_ms"]
        out["throughput_pipeline_p99_ms"] = pres["p99_ms"]
        out["throughput_pipeline_errors"] = pres["errors"] + pdirect["errors"]
        out["throughput_pipeline_fallback_free"] = (
            fallback_count(wport) == fb_before
        )
    finally:
        for p in (gateway, worker):
            if p is not None:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)

    # the acceptance ratios: r09's 93-rps goodput was worker-direct, so
    # the like-for-like 10x claim is the *_direct numbers; the gateway
    # ratios price the distributed hop at the same p99 bound
    out["throughput_echo_vs_r09"] = round(
        out.get("throughput_echo_direct_rps", 0.0) / R09_GOODPUT, 2
    )
    out["throughput_pipeline_vs_r09"] = round(
        out.get("throughput_pipeline_direct_rps", 0.0) / R09_GOODPUT, 2
    )
    out["throughput_gateway_echo_vs_r09"] = round(
        out.get("throughput_echo_rps", 0.0) / R09_GOODPUT, 2
    )
    out["throughput_p99_within_bound"] = bool(
        max(
            out.get("throughput_echo_p99_ms", 1e9),
            out.get("throughput_echo_direct_p99_ms", 1e9),
            out.get("throughput_pipeline_p99_ms", 1e9),
            out.get("throughput_pipeline_direct_p99_ms", 1e9),
        ) <= P99_BOUND_MS
    )
    return out


def _seg_chaos(on_accel: bool, n_dev: int) -> dict:
    """Hostile-wire survival (ISSUE 13): goodput retained and p99 under
    a standard hostile schedule — throttle + byte-flip + asymmetric
    partition via a seeded ChaosProxy (mmlspark_tpu/chaos/wire.py) —
    vs the clean baseline on the same in-process gateway + 2-worker
    fleet, plus the allreduce CRC corruption-detect-to-recovery time
    (flip -> NACK -> retransmit -> correct sum). Client threads share
    the GIL with the serving threads, so the honest claim is the
    RATIO, not the absolute rps."""
    import http.client as http_client

    from mmlspark_tpu import obs
    from mmlspark_tpu.chaos.wire import ChaosProxy, WireRule
    from mmlspark_tpu.serving.distributed import ServingGateway
    from mmlspark_tpu.serving.modelstore import ModelDispatcher, ModelStore
    from mmlspark_tpu.serving.server import ServiceInfo, WorkerServer

    out: dict = {}
    obs.reset()
    workers = []
    for _ in range(2):
        srv = WorkerServer(name="chbench")
        info = srv.start()
        store = ModelStore()
        store.load("echo", "echo", wait=True)
        disp = ModelDispatcher(srv, store, default_model="echo").start()
        workers.append((srv, disp, info))
    # each worker link rides its own proxy so the partition window can
    # blackhole one of them without touching the other
    w_proxies = [
        ChaosProxy("127.0.0.1", w[2].port, seed=11, name=f"bw{i}").start()
        for i, w in enumerate(workers)
    ]
    gw = ServingGateway(
        workers=[
            ServiceInfo("chbench", "127.0.0.1", p.port) for p in w_proxies
        ],
        num_dispatchers=4, request_timeout_s=2.0, retry_after_send=True,
    )
    ginfo = gw.start()
    client_proxy = ChaosProxy(
        "127.0.0.1", ginfo.port, seed=11, name="bclient"
    ).start()

    def measure(dur_s: float) -> tuple:
        stop = threading.Event()
        lats: list = []
        errs = [0]
        lock = threading.Lock()

        def client():
            conn = http_client.HTTPConnection(
                "127.0.0.1", client_proxy.port, timeout=10.0
            )
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    conn.request("POST", "/", b'{"x": 1}')
                    r = conn.getresponse()
                    r.read()
                    ok = r.status == 200
                except OSError:
                    conn.close()
                    conn = http_client.HTTPConnection(
                        "127.0.0.1", client_proxy.port, timeout=10.0
                    )
                    ok = False
                dt = time.perf_counter() - t0
                with lock:
                    if ok:
                        lats.append(dt)
                    else:
                        errs[0] += 1
            conn.close()

        threads = [
            threading.Thread(target=client, daemon=True) for _ in range(4)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(dur_s)
        stop.set()
        for t in threads:
            t.join(10)
        wall = time.perf_counter() - t_start
        lats.sort()
        p99 = lats[int(0.99 * (len(lats) - 1))] * 1e3 if lats else 0.0
        return len(lats) / wall, p99, errs[0]

    try:
        clean_rps, clean_p99, _ = measure(2.5)
        # the standard hostile schedule: throttle + jitter + a byte
        # flipped into the request stream every 64 KiB, and worker 0's
        # link blackholed for the middle of the window (asymmetric
        # partition -> idempotent failover)
        client_proxy.set_rules([
            WireRule("latency", delay_ms=0.5, jitter_ms=2.0),
            WireRule("throttle", direction="c2s", bytes_per_s=512 * 1024),
            WireRule("flip", direction="c2s", at_offset=4096,
                     every_bytes=65536),
        ])

        def partition_window():
            time.sleep(0.8)
            w_proxies[0].set_rules(
                [WireRule("blackhole", direction="c2s")]
            )
            time.sleep(1.0)
            w_proxies[0].clear_rules()

        pt = threading.Thread(target=partition_window, daemon=True)
        pt.start()
        hostile_rps, hostile_p99, hostile_errs = measure(2.5)
        pt.join(5)
        out["chaos_clean_rps"] = round(clean_rps, 1)
        out["chaos_clean_p99_ms"] = round(clean_p99, 2)
        out["chaos_hostile_rps"] = round(hostile_rps, 1)
        out["chaos_hostile_p99_ms"] = round(hostile_p99, 2)
        out["chaos_hostile_errors"] = hostile_errs
        out["chaos_goodput_retained"] = round(
            hostile_rps / clean_rps, 3
        ) if clean_rps else 0.0
        faults = sum(len(p.journal()) for p in (client_proxy, *w_proxies))
        out["chaos_wire_faults_applied"] = faults
    finally:
        client_proxy.set_rules([])
        gw.stop()
        for p in w_proxies:
            p.stop()
        client_proxy.stop()
        for srv, disp, _ in workers:
            disp.stop()
            srv.stop()

    # -- allreduce CRC: corruption-detect-to-recovery ------------------------
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        TcpReducer,
    )
    from mmlspark_tpu.serving.registry import DriverRegistry

    reg = DriverRegistry(ttl_s=10.0)
    # pre-bind b's allreduce port so the proxy fronts it BEFORE the
    # member's first heartbeat advertises anything — a post-construction
    # advertise_port assignment can lose that race, letting peer a dial
    # b direct and skip the fault schedule entirely
    import socket as socket_mod

    _ls = socket_mod.create_server(("127.0.0.1", 0))
    b_port = _ls.getsockname()[1]
    _ls.close()
    ab = ChaosProxy("127.0.0.1", b_port, seed=11, name="bab").start()
    b = GangMember(
        reg.url, "b", heartbeat_s=0.2,
        listen_port=b_port, advertise_port=ab.port,
    )
    a = GangMember(reg.url, "a", heartbeat_s=0.2)
    time.sleep(0.6)
    gen = Generation(gen=1, members=["a", "b"])
    ra = TcpReducer(a, gen, timeout_s=20.0)
    rb = TcpReducer(b, gen, timeout_s=20.0)
    try:
        payload = np.arange(4096, dtype=np.float64)

        def timed_allreduce() -> float:
            res = {}
            t0 = time.perf_counter()
            ta = threading.Thread(target=lambda: res.__setitem__(
                "a", ra.allreduce(payload)))
            tb = threading.Thread(target=lambda: res.__setitem__(
                "b", rb.allreduce(payload)))
            ta.start(); tb.start(); ta.join(25); tb.join(25)
            dt = (time.perf_counter() - t0) * 1e3
            assert np.array_equal(res["a"], 2 * payload)
            assert np.array_equal(res["b"], 2 * payload)
            return dt

        clean_ms = min(timed_allreduce() for _ in range(3))
        # flip one byte inside the NEXT a->b frame's payload: the whole
        # detect -> NACK -> retransmit -> correct-sum turnaround is the
        # recovery time. Offset = frames already sent x frame length
        # (32-byte head + 1-byte name + payload), plus 1000 into the
        # next frame's payload
        frame_len = 32 + 1 + payload.nbytes
        ab.set_rules([WireRule(
            "flip", direction="c2s", at_offset=ra.seq * frame_len + 1000,
        )])
        drops_before = b.crc_drops
        corrupt_ms = timed_allreduce()
        out["chaos_crc_detected"] = int(b.crc_drops - drops_before)
        out["chaos_crc_retransmits"] = ra.retransmits
        out["chaos_crc_clean_allreduce_ms"] = round(clean_ms, 2)
        out["chaos_crc_detect_to_recover_ms"] = round(corrupt_ms, 2)
    finally:
        ra.close(); rb.close(); a.close(); b.close()
        ab.stop(); reg.stop()
        obs.reset()
    return out


SEGMENT_FNS = {
    "serving": _seg_serving,
    "modelstore": _seg_modelstore,
    "tracing": _seg_tracing,
    "artifact": _seg_artifact,
    "overload": _seg_overload,
    "throughput": _seg_throughput,
    "chaos": _seg_chaos,
    "freshness": _seg_freshness,
    "elastic": _seg_elastic,
    "tune": _seg_tune,
    "pipeline": _seg_pipeline,
    "hist": _seg_hist,
    "vw": _seg_vw,
    "gbdt": _seg_gbdt,
    "sklearn": _seg_sklearn,
    "featurizer": _seg_featurizer,
}


# ---------------------------------------------------------------------------
# child driver: run requested segments, stream one JSON line per segment
# ---------------------------------------------------------------------------


def _deliberate_wedge() -> None:
    """Test hook (``MMLSPARK_BENCH_WEDGE_SEGMENT=<seg>``): block forever
    on a lock that is never released, so the stall-forensics path has a
    named frame to find — the SIGUSR2/watchdog dump must show this
    function at the top of the wedged thread's stack."""
    lock = threading.Lock()
    lock.acquire()
    lock.acquire()  # blocks forever — the dump names this frame


def run_child() -> None:
    import jax

    from mmlspark_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    if os.environ.get("MMLSPARK_TPU_CPU_ASYNC_DISPATCH") != "1":
        # pure_callback growers deadlock against XLA:CPU async
        # dispatch (docs/gbdt-training.md "Known issues"); the flag
        # must land before the CPU client exists, i.e. here
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    # stall forensics: SIGUSR2 -> all-thread stack dump into the
    # flightrec spool. The parent signals a stalled child and collects
    # the dump BEFORE killing it, so a wedged segment names its frame in
    # the BENCH json instead of just going missing.
    try:
        from mmlspark_tpu.obs import watchdog as _watchdog

        _watchdog.install_sigusr2()
    except Exception:  # noqa: BLE001 — forensics must never fail the bench
        _watchdog = None

    def emit(seg: str, data: dict) -> None:
        sys.stdout.write(json.dumps({"segment": seg, "data": data}) + "\n")
        sys.stdout.flush()

    emit("starting", {})
    devices = jax.devices()
    platform = devices[0].platform
    n_dev = len(devices)
    on_accel = platform not in ("cpu",)
    if not on_accel and os.environ.get("MMLSPARK_BENCH_REQUIRE_TPU") == "1":
        # TPU-attempt child that silently initialized on CPU: fail fast so
        # the parent doesn't burn its budget benchmarking the wrong backend
        sys.stderr.write("bench child: backend is cpu but TPU was required\n")
        raise SystemExit(3)

    # trivial 1-op warmup: proves the compile path end-to-end before
    # spending minutes tracing models
    import jax.numpy as jnp

    (jnp.ones((128, 128)) @ jnp.ones((128, 128))).block_until_ready()
    emit("init", {"platform": platform, "n_dev": n_dev})

    wanted = [
        s for s in os.environ.get(
            "MMLSPARK_BENCH_SEGMENTS", ",".join(SEGMENTS)
        ).split(",") if s in SEGMENT_FNS
    ]
    wedge = os.environ.get("MMLSPARK_BENCH_WEDGE_SEGMENT")
    for seg in wanted:
        if _watchdog is not None:
            # heartbeat: a segment that outlives its own budget by a
            # minute auto-dumps stacks even with no parent signaling
            _watchdog.tick("bench.segment", deadline_s=max(
                SEGMENT_TIMEOUT_S, SEGMENT_TIMEOUTS.get(seg, 0)) + 60)
        if seg == wedge:
            _deliberate_wedge()
        emit(seg, SEGMENT_FNS[seg](on_accel, n_dev))
    if _watchdog is not None:
        _watchdog.disarm("bench.segment")
    emit("done", {})


# ---------------------------------------------------------------------------
# parent orchestrator
# ---------------------------------------------------------------------------


class _Child:
    """Child process whose stdout lines are harvested with timeouts."""

    def __init__(self, segments: list, env: dict):
        env = dict(env)
        env["MMLSPARK_BENCH_SEGMENTS"] = ",".join(segments)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        self.q: queue.Queue = queue.Queue()
        self.err_chunks: list = []
        threading.Thread(target=self._pump_out, daemon=True).start()
        threading.Thread(target=self._pump_err, daemon=True).start()

    def _pump_out(self):
        for line in self.proc.stdout:
            self.q.put(line)
        self.q.put(None)  # EOF sentinel

    def _pump_err(self):
        for line in self.proc.stderr:
            self.err_chunks.append(line)
            if len(self.err_chunks) > 200:
                del self.err_chunks[:100]

    def next_record(self, timeout_s: float):
        """Next parsed {segment, data} record, or None on EOF/timeout."""
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                line = self.q.get(timeout=min(remaining, 5.0))
            except queue.Empty:
                continue
            if line is None:
                return None
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "segment" in rec:
                return rec

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    @property
    def stderr_tail(self) -> str:
        return "".join(self.err_chunks)[-2000:]


class _Assembly:
    """Accumulates segment results; can emit a valid JSON line at any time."""

    def __init__(self):
        self.extra: dict = {}
        self.done: set = set()
        self.platform = "unknown"
        self.n_dev = 1
        self.tpu_error = ""
        self._printed = False
        self._lock = threading.Lock()

    def absorb(self, rec: dict) -> str:
        seg = rec.get("segment", "")
        data = rec.get("data", {}) or {}
        if seg == "init":
            self.platform = data.get("platform", self.platform)
            self.n_dev = data.get("n_dev", self.n_dev)
            return seg
        if seg in SEGMENT_FNS and seg not in self.done:
            self.extra.update(data)
            self.done.add(seg)
            self._write_partial()
        return seg

    def _write_partial(self):
        try:
            with open(PARTIAL_PATH, "w") as f:
                json.dump({"done": sorted(self.done), "extra": self.extra}, f)
        except OSError:
            pass

    def emit(self) -> None:
        with self._lock:
            if self._printed:
                return
            self._printed = True
        per_chip = float(self.extra.get("featurizer_img_s_chip", 0.0))
        extra = dict(self.extra)
        extra.pop("featurizer_img_s_chip", None)
        if self.tpu_error:
            extra["tpu_error"] = self.tpu_error[-300:]
        missing = [s for s in SEGMENTS if s not in self.done]
        if missing:
            extra["segments_missing"] = missing
        result = {
            "metric": "imagefeaturizer_resnet50_throughput",
            "value": round(per_chip, 2),
            "unit": f"images/sec/chip ({self.platform} x{self.n_dev})",
            "vs_baseline": round(per_chip / 250.0, 3),
            "extra": extra,
        }
        print(json.dumps(result))
        sys.stdout.flush()


def _collect_stall_stacks(child: _Child,
                          timeout_s: float = 8.0) -> "dict | None":
    """Send SIGUSR2 to a still-running child and collect the stall dump
    it spools (obs/watchdog.py) — {thread_name: top_frame}. Returns None
    when the child can't be signaled or no dump lands in time; stall
    forensics must never block the harvest for long or fail it."""
    import glob
    import tempfile

    pid = getattr(child.proc, "pid", None)
    if pid is None or child.proc.poll() is not None:
        return None
    dump_dir = os.environ.get("MMLSPARK_FLIGHTREC_DIR") or os.path.join(
        tempfile.gettempdir(), "mmlspark_flightrec"
    )
    pattern = os.path.join(dump_dir, "stalldump-*.json")
    before = set(glob.glob(pattern))
    try:
        os.kill(pid, signal.SIGUSR2)
    except (OSError, AttributeError, ValueError):
        return None  # platform without SIGUSR2, or the child just died
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        new = [
            p for p in glob.glob(pattern)
            if p not in before and f"-{pid}-" in os.path.basename(p)
        ]
        if new:
            try:  # atomic rename on the writer side: never half-written
                with open(sorted(new)[-1]) as f:
                    payload = json.load(f)
            except (OSError, ValueError):
                return None
            def top(stack):
                # innermost frame that isn't the dump machinery itself:
                # the SIGUSR2 handler runs ON the wedged main thread, so
                # its literal top frames are obs/watchdog.py + obs/prof.py
                # walking the stacks — the frame worth reporting is the
                # one they interrupted
                for fr in reversed(stack):
                    if ("obs/watchdog.py" not in fr
                            and "obs/prof.py" not in fr):
                        return fr
                return stack[-1] if stack else ""

            return {
                t.get("name", "?"): top(t.get("stack") or [])
                for t in payload.get("threads", [])
            }
        time.sleep(0.25)
    return None


def _harvest(child: _Child, asm: _Assembly, remaining: list,
             deadline: float, order: list) -> bool:
    """Drain records from a child until done/EOF/hang/deadline; removes
    completed segments from ``remaining`` in place. Returns True if the
    child had to be killed while still running."""
    saw_line = False
    while remaining:
        budget = deadline - time.monotonic()
        if budget <= 0:
            break
        # the child runs segments in ``order``: the next record is the
        # first remaining segment, and that segment's watchdog applies
        nxt = next((s for s in order if s in remaining), None)
        seg_timeout = max(SEGMENT_TIMEOUT_S, SEGMENT_TIMEOUTS.get(nxt, 0))
        timeout = min(budget,
                      seg_timeout if saw_line else FIRST_LINE_TIMEOUT_S)
        rec = child.next_record(timeout)
        if rec is None:
            break  # EOF or watchdog timeout
        saw_line = True
        seg = asm.absorb(rec)
        if seg in remaining:
            remaining.remove(seg)
        if seg == "done":
            # give the child its natural exit (releases the chip cleanly)
            try:
                child.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            break
    was_running = child.proc.poll() is None
    if was_running and remaining:
        # the child is wedged on the first un-done segment: pull its
        # all-thread stacks BEFORE the kill destroys the evidence
        nxt = next((s for s in order if s in remaining), None)
        if nxt is not None:
            stacks = _collect_stall_stacks(child)
            if stacks:
                asm.extra.setdefault("stall_stacks", {})[nxt] = stacks
                asm._write_partial()
    child.kill()
    return was_running


def main() -> None:
    asm = _Assembly()
    start = time.monotonic()
    live_child: list = []

    def on_signal(signum, frame):  # driver timeout: flush what we have
        asm.tpu_error = asm.tpu_error or f"killed by signal {signum}"
        # emit FIRST: a driver may chase SIGTERM with SIGKILL, and waiting
        # on a slow child reap must not cost us the output line
        if asm.platform == "tpu":
            asm.emit()
        for c in live_child:
            try:
                c.kill()
            except Exception:  # noqa: BLE001
                pass
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    remaining = [s for s in TPU_ORDER]
    env = dict(os.environ)
    env["MMLSPARK_BENCH_REQUIRE_TPU"] = "1"  # CPU-silent init fails fast
    child = _Child(remaining, env)
    live_child[:] = [child]
    _harvest(child, asm, remaining, start + TOTAL_TPU_BUDGET_S, TPU_ORDER)
    live_child[:] = []
    if remaining:
        err = child.stderr_tail
        asm.tpu_error = err or "bench child hung"
        sys.stderr.write(
            f"bench: segments never completed: {remaining}; "
            f"stderr tail:\n{err[-600:]}\n"
        )
    if asm.platform != "tpu":
        # no figure without the chip: a CPU number must never be printed
        # under this benchmark's name
        sys.stderr.write(
            f"bench: no TPU (platform={asm.platform!r}); nothing measured\n"
        )
        raise SystemExit(1)
    asm.emit()
    if remaining:
        raise SystemExit(1)


if __name__ == "__main__":
    if "--child" in sys.argv:
        run_child()
    else:
        main()
