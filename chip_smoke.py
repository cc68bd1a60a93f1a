#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the paper's flagship path once, through the entry points a user
calls, at the full width of ResNet-50 (weights random, from a seed):

    DataFrame -> ImageFeaturizer(ResNet50) -> LightGBMClassifier
              -> served from ModelStore by `fleet worker`

plus the two other device trainers (VW, the compiled pipeline). Phases run
one after another as child processes — a chip belongs to one process at a
time, so this parent never imports JAX and each child releases the chip by
exiting. All children share one compile cache (mmlspark_tpu/core/
compile_cache.py: ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``).

    python3 chip_smoke.py              # needs a TPU; anything else fails
    python3 chip_smoke.py --rehearse   # same phases, tiny, CPU + interpreted
                                       # kernels; every line says so

Every phase prints one JSON line (platform / device_kind / n_dev, devices
that held its data, shapes, first-call and repeat-call wall seconds, compile
cache traffic, its checks). These are observations, not metrics. Exit 0 —
and the last stdout line ``{"ok": true, "device": {...}}`` — only if every
phase ran on a TPU and every check held. No network, nothing read from
outside the checkout; everything it writes goes under ``--out``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("featurize", "gbdt", "serve", "vw", "pipeline")
# wall-clock ceilings (s): the whole run must end inside 1200 s, compiles
# included; a phase gets its own ceiling or what is left, whichever is less
TOTAL_BUDGET_S = 1140
PHASE_BUDGET_S = {"featurize": 480, "gbdt": 480, "serve": 480, "vw": 300,
                  "pipeline": 300}

# one model configuration at full width, and its tiny CPU stand-in
SIZES = {
    False: dict(
        model="ResNet50", image=224, batch=256, images=1024,
        gbdt_rows=200_000, gbdt_features=64, gbdt_leaves=63, gbdt_iters=10,
        gbdt_min_leaf=20, head_leaves=15,
        vw_rows=100_000, vw_margin=0.15, pipe_rows=100_000, posts=8,
    ),
    True: dict(
        model="ResNet8_Digits", image=32, batch=16, images=32,
        gbdt_rows=2_000, gbdt_features=8, gbdt_leaves=15, gbdt_iters=5,
        gbdt_min_leaf=20, head_leaves=7,
        vw_rows=4_000, vw_margin=0.15, pipe_rows=4_096, posts=3,
    ),
}


# ---------------------------------------------------------------------------
# parent: stdlib only, never JAX
# ---------------------------------------------------------------------------


def _kill_group(proc: subprocess.Popen) -> None:
    """SIGKILL a child's whole process group (it and whatever it started)."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def _last_json_line(path: str) -> "dict | None":
    try:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
        return json.loads(lines[-1]) if lines else None
    except (OSError, ValueError):
        return None


def _tail(path: str, n: int = 2500) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def _run_child(phase: str, args, env: dict, budget: float, live: list) -> dict:
    """Run one phase as a child in its own process group; returns its result
    line, marked failed if it died, overran ``budget`` or printed none."""
    out_path = os.path.join(args.out, f"{phase}.out")
    err_path = os.path.join(args.out, f"{phase}.err")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--out", args.out] + (["--rehearse"] if args.rehearse else [])
    if budget <= 5:
        return {"phase": phase, "ok": False, "error": "no time left"}
    why = ""
    with open(out_path, "w") as fo, open(err_path, "w") as fe:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=fo, stderr=fe,
                                start_new_session=True)
        live[:] = [proc]
        try:
            rc = proc.wait(timeout=budget)
            if rc != 0:
                why = f"exit code {rc}"
        except subprocess.TimeoutExpired:
            why = f"exceeded {budget:.0f}s"
        finally:
            _kill_group(proc)  # also reaps anything it left behind
            live[:] = []
    rec = _last_json_line(out_path) or {"phase": phase, "ok": False}
    if why:
        rec.update(ok=False, error=why, stderr_tail=_tail(err_path))
    return rec


def main(argv: "list | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on CPU with interpret-mode kernels")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for everything the run writes")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, for debugging (a subset "
                    "run never prints the final ok line)")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return _run_phase(args.phase, args.out, args.rehearse)

    if not os.path.isdir(os.path.join(HERE, "mmlspark_tpu")):
        sys.stderr.write(
            f"chip_smoke: {HERE} holds no mmlspark_tpu package — run it "
            "from the root of a checkout\n"
        )
        return 2
    wanted = [p for p in args.phases.split(",") if p]
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        sys.stderr.write(f"chip_smoke: unknown phases {unknown}\n")
        return 2
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("MMLSPARK_TPU_PALLAS", "1")  # kernels, interpreted
    else:
        inherited = env.get("JAX_PLATFORMS", "")
        if inherited and "tpu" not in inherited.split(","):
            sys.stderr.write(
                f"chip_smoke: JAX_PLATFORMS={inherited!r} holds JAX off the "
                "TPU, so there is no accelerator to smoke; unset it (or use "
                "--rehearse for the CPU rehearsal)\n"
            )
            return 2
        # pinned: a TPU that fails to initialise (held by another process,
        # say) is an error instead of a silent CPU run; the CPU backend
        # stays for the float32 reference and the seeded weight init
        env["JAX_PLATFORMS"] = "tpu,cpu"
    os.makedirs(args.out, exist_ok=True)
    # zoo installs land inside the run's own directory: the seeded
    # ResNet-50 weights are materialised by the run, never found on disk
    env["MMLSPARK_TPU_HOME"] = os.path.join(args.out, "home")

    live: list = []

    def on_signal(signum, frame):
        for p in live:
            _kill_group(p)
        sys.stderr.write(f"chip_smoke: killed by signal {signum}\n")
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    deadline = time.monotonic() + TOTAL_BUDGET_S
    results: list = []
    for phase in wanted:
        budget = min(PHASE_BUDGET_S[phase], deadline - time.monotonic())
        rec = _run_child(phase, args, env, budget, live)
        print(json.dumps(rec), flush=True)
        results.append(rec)
        if not rec.get("ok"):
            sys.stderr.write(
                f"chip_smoke: phase {phase} FAILED "
                f"({rec.get('error', 'checks')}); see {args.out}/{phase}.err\n"
            )

    failed = [r["phase"] for r in results if not r.get("ok")]
    if failed:
        sys.stderr.write(f"chip_smoke: failed phases: {failed}\n")
        return 1
    if wanted != list(PHASES):
        sys.stderr.write("chip_smoke: subset run — no final result line\n")
        return 0
    devices = {
        (r.get("platform"), r.get("device_kind"), r.get("n_dev"))
        for r in results
    }
    if len(devices) != 1:
        sys.stderr.write(f"chip_smoke: phases disagree on the device: "
                         f"{sorted(map(str, devices))}\n")
        return 1
    platform, kind, count = devices.pop()
    if not args.rehearse and platform != "tpu":
        sys.stderr.write(f"chip_smoke: ran on {platform!r}, not a TPU\n")
        return 1
    final = {"ok": True,
             "device": {"platform": platform, "kind": kind, "count": count}}
    if args.rehearse:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: one phase each
# ---------------------------------------------------------------------------


class _Phase:
    """What every device phase shares: the compile cache, the cache-traffic
    counters, the device report, the placement census and the result line."""

    def __init__(self, name: str, out: str, rehearse: bool):
        import jax

        from mmlspark_tpu.core.compile_cache import enable_compile_cache

        self.name, self.out, self.rehearse = name, out, rehearse
        self.size = SIZES[rehearse]
        self.cache_dir = enable_compile_cache()
        self.cache = {"requests": 0, "hits": 0, "writes": 0}
        keys = {
            "/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
            # recorded when an entry is WRITTEN: a program that compiled
            # for longer than the cache's minimum and was not found
            "/jax/compilation_cache/cache_misses": "writes",
        }

        def on_event(event: str, **kw) -> None:
            if event in keys:
                self.cache[keys[event]] += 1

        jax.monitoring.register_event_listener(on_event)
        devs = jax.devices()
        self.rec: dict = {
            "phase": name,
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_dev": len(devs),
            "cache_dir": self.cache_dir,
        }
        if rehearse:
            self.rec["rehearsal"] = True
        elif devs[0].platform != "tpu":
            raise SystemExit(
                f"chip_smoke[{name}]: JAX came up on "
                f"{devs[0].platform!r}, not a TPU"
            )
        self.checks: dict = {}

    @contextlib.contextmanager
    def census(self, is_data):
        """While the body runs, sample ``jax.live_arrays()`` and keep the
        largest number of devices any array matching ``is_data`` was laid
        out over — where the system put the data, seen from outside it."""
        import jax

        seen = {"devices": 0}
        stop = threading.Event()

        def sample() -> None:
            while not stop.is_set():
                for a in jax.live_arrays():
                    try:
                        if is_data(a):
                            seen["devices"] = max(
                                seen["devices"], len(a.sharding.device_set)
                            )
                    except RuntimeError:  # deleted while we looked
                        pass
                stop.wait(0.01)

        t = threading.Thread(target=sample, daemon=True)
        t.start()
        try:
            yield seen
        finally:
            stop.set()
            t.join(timeout=5)

    def finish(self) -> int:
        from mmlspark_tpu.ops.native_loader import try_load

        self.rec["native_loaded"] = try_load() is not None
        self.rec["cache"] = self.cache
        self.rec["checks"] = self.checks
        self.rec["ok"] = all(bool(v) for v in self.checks.values())
        print(json.dumps(self.rec), flush=True)
        return 0 if self.rec["ok"] else 1


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _cosine(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64).ravel(), np.asarray(b, np.float64).ravel()
    return float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))


def _rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _smoke_images(n: int, size: int):
    """Seeded uint8 pixels with a class signal (class 1 has a brighter top
    half), so the GBDT head has something to learn from random weights."""
    import numpy as np

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 2, size=n)
    imgs = rng.integers(0, 256, size=(n, size, size, 3), dtype=np.uint8)
    top = imgs[labels == 1, : size // 2]
    imgs[labels == 1, : size // 2] = top // 2 + 128
    return imgs, labels


def _phase_featurize(ph: _Phase) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu import DataFrame, Pipeline
    from mmlspark_tpu.downloader.zoo import ModelDownloader
    from mmlspark_tpu.models import ImageFeaturizer
    from mmlspark_tpu.ops import image as image_ops

    sz = ph.size
    n, size, batch = sz["images"], sz["image"], sz["batch"]
    imgs, labels = _smoke_images(n, size)
    df = DataFrame.from_dict({"image": imgs, "label": labels})
    model = Pipeline([ImageFeaturizer(
        input_col="image", output_col="features", model_name=sz["model"],
        image_size=size, batch_size=batch, cut_output_layers=1,
    )]).fit(df)
    with ph.census(lambda a: a.shape == (batch, size, size, 3)) as seen:
        out, first_s = _timed(lambda: model.transform(df)["features"])
    _, repeat_s = _timed(lambda: model.transform(df)["features"])
    feats = np.asarray(out)

    # float32 reference: the same weights and the same preprocessing ops,
    # plain flax forward on the host CPU at full matmul precision
    module, variables, _schema = ModelDownloader().load(sz["model"])
    ref_module = module.clone(dtype=jnp.float32)
    cpu = jax.local_devices(backend="cpu")[0]

    def ref_forward(x):
        x = image_ops.normalize(image_ops.resize(x, size, size))
        return ref_module.apply(variables, x, train=False)["pool"]

    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(ref_forward)(imgs[:8].astype(np.float32)))
    width = ref.shape[1]
    cos = [_cosine(feats[i], ref[i]) for i in range(8)]
    ph.rec.update(
        shapes={"images": list(imgs.shape), "features": list(feats.shape)},
        devices_holding_data=seen["devices"],
        first_call_s=first_s, repeat_call_s=repeat_s,
        ref_cosine_min=round(min(cos), 6),
        ref_rel_err=round(_rel_err(feats[:8], ref), 5),
        params=int(sum(
            np.asarray(v).size for v in jax.tree_util.tree_leaves(variables)
        )),
    )
    ph.checks.update(
        shape=feats.shape == (n, width),
        finite=bool(np.isfinite(feats).all()),
        not_constant=bool(feats.std() > 0),
        # bf16 program against the f32 reference
        matches_f32_reference=min(cos) >= 0.99,
        data_on_every_device=seen["devices"] == ph.rec["n_dev"],
    )
    np.save(os.path.join(ph.out, "features.npy"), feats)
    np.save(os.path.join(ph.out, "labels.npy"), labels)
    np.save(os.path.join(ph.out, "images_head.npy"), imgs[: sz["posts"]])


def _phase_gbdt(ph: _Phase) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu import DataFrame, obs
    from mmlspark_tpu.core.metrics import binary_auc
    from mmlspark_tpu.models.gbdt import LightGBMClassifier
    from mmlspark_tpu.ops import histogram as H
    from mmlspark_tpu.parallel.mesh import DATA_AXIS, get_mesh
    from mmlspark_tpu.parallel.sharding import shard_batch

    sz = ph.size
    n, d = sz["gbdt_rows"], sz["gbdt_features"]
    on_tpu = ph.rec["platform"] == "tpu"
    # every program is dumped as it is LOWERED (before the compile cache is
    # consulted), so a warm cache still shows what each tree program holds
    ir_dir = os.path.join(ph.out, "gbdt_ir")
    os.makedirs(ir_dir, exist_ok=True)
    jax.config.update("jax_dump_ir_to", ir_dir)
    dumped: set = set(os.listdir(ir_dir))

    def tree_programs_use_kernel(label: str) -> bool:
        """Every training program lowered since the last call holds the
        Mosaic custom call (on CPU the interpreter leaves none: skipped)."""
        new = sorted(set(os.listdir(ir_dir)) - dumped)
        dumped.update(new)
        counts = {}
        for name in new:
            if "scan_chunk" in name:
                with open(os.path.join(ir_dir, name), errors="replace") as f:
                    counts[name] = f.read().count("tpu_custom_call")
        ph.rec.setdefault("tree_programs", {})[label] = counts
        return bool(counts) and (
            not on_tpu or all(c > 0 for c in counts.values())
        )

    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.int64)
    df = DataFrame.from_dict({"features": x, "label": y})
    fits: dict = {}
    holding = []
    for policy, max_bin in (("lossguide", 255), ("depthwise", 255),
                            ("lossguide", 63)):
        label = f"{policy}_{max_bin}"
        clf = LightGBMClassifier(
            num_iterations=sz["gbdt_iters"], num_leaves=sz["gbdt_leaves"],
            min_data_in_leaf=sz["gbdt_min_leaf"], growth_policy=policy,
            max_bin=max_bin, seed=0,
        )
        with ph.census(lambda a: a.ndim == 2 and a.shape[0] >= n) as seen:
            model, first_s = _timed(lambda: clf.fit(df))
        _, repeat_s = _timed(lambda: clf.fit(df))
        auc = binary_auc(y, model.transform(df)["probability"][:, 1])
        fits[label] = {"first_call_s": first_s, "repeat_call_s": repeat_s,
                       "train_auc": round(float(auc), 4)}
        holding.append(seen["devices"])
        ph.checks[f"{label}_auc"] = auc >= 0.95
        ph.checks[f"{label}_kernel_in_program"] = (
            tree_programs_use_kernel(label)
        )

    # the two histogram ops at the estimator's own shapes (uint8 bins, a
    # row count that is no multiple of the 512-row chunk, per-shard rows on
    # a multi-chip host) against the scatter reference
    mesh = get_mesh()
    sharded = mesh.devices.size > 1
    put = (lambda a: shard_batch(a, mesh)) if sharded else jnp.asarray
    bins = rng.integers(0, 256, size=(n, d), dtype=np.uint8)
    stats = rng.normal(size=(n, 3)).astype(np.float32)
    slots = 8
    slot = rng.integers(0, slots, size=n).astype(np.int32)
    kw = dict(mesh=mesh, shard_axis=DATA_AXIS) if sharded else {}
    plane = jax.jit(lambda b, s: H.plane_histogram(b, s, **kw))
    multi = jax.jit(
        lambda b, s, sl: H.multi_plane_histogram(b, s, sl, slots, **kw)
    )
    b_dev, s_dev, sl_dev = put(bins), put(stats), put(slot)
    hist: dict = {}
    for label, fn, fargs, ref_fn, rargs in (
        ("plane_histogram", plane, (b_dev, s_dev),
         H._plane_histogram_scatter, (bins.astype(np.int32), stats)),
        ("multi_plane_histogram", multi, (b_dev, s_dev, sl_dev),
         lambda b, s, sl: H._multi_plane_scatter(b, s, sl, slots),
         (bins.astype(np.int32), stats, slot)),
    ):
        text = fn.lower(*fargs).as_text()
        got, first_s = _timed(lambda: jax.block_until_ready(fn(*fargs)))
        _, repeat_s = _timed(lambda: jax.block_until_ready(fn(*fargs)))
        want = jax.jit(ref_fn)(*rargs)
        rel = _rel_err(got, want)
        hist[label] = {
            "first_call_s": first_s, "repeat_call_s": repeat_s,
            "rel_err_vs_scatter": float(f"{rel:.3g}"),
            "devices": len(got.sharding.device_set),
            "tpu_custom_calls": text.count("tpu_custom_call"),
        }
        ph.checks[f"{label}_equals_scatter"] = rel <= 1e-3
        ph.checks[f"{label}_kernel_in_program"] = (
            text.count("tpu_custom_call") > 0 or not on_tpu
        )

    # the head of the flagship: the classifier on the featurize phase's rows
    feats = np.load(os.path.join(ph.out, "features.npy"))
    labels = np.load(os.path.join(ph.out, "labels.npy"))
    hdf = DataFrame.from_dict({"features": feats, "label": labels})
    head = LightGBMClassifier(
        num_iterations=sz["gbdt_iters"], num_leaves=sz["head_leaves"],
        min_data_in_leaf=5, seed=0,
    )
    hmodel, head_first_s = _timed(lambda: head.fit(hdf))
    prob = hmodel.transform(hdf)["probability"]
    head_auc = float(binary_auc(labels, prob[:, 1]))
    fits["head"] = {"first_call_s": head_first_s,
                    "train_auc": round(head_auc, 4),
                    "shape": list(feats.shape)}
    ph.checks["head_finite"] = bool(np.isfinite(prob).all())
    ph.checks["head_auc"] = head_auc >= 0.9
    ph.checks["head_kernel_in_program"] = tree_programs_use_kernel("head")

    lowerings: dict = {}
    fam = obs.REGISTRY.snapshot().get("mmlspark_gbdt_hist_lowerings_total")
    for labels_, value in (fam or {}).get("samples", []):
        key = f"{labels_['op']}:{labels_['lowering']}"
        lowerings[key] = lowerings.get(key, 0) + int(value)
    scattered = sum(v for k, v in lowerings.items() if k.endswith("scatter"))
    ph.checks["no_histogram_took_the_scatter"] = scattered == 0
    ph.checks["kernels_were_chosen"] = any(
        k.endswith("pallas") and v > 0 for k, v in lowerings.items()
    )
    ph.checks["data_on_every_device"] = (
        min(holding) == ph.rec["n_dev"]
        and all(h["devices"] == ph.rec["n_dev"] for h in hist.values())
    )
    ph.rec.update(
        shapes={"x": [n, d], "head_x": list(feats.shape)},
        devices_holding_data=min(holding),
        first_call_s=fits["lossguide_255"]["first_call_s"],
        repeat_call_s=fits["lossguide_255"]["repeat_call_s"],
        fits=fits, histograms=hist, hist_lowerings=lowerings,
    )


def _phase_vw(ph: _Phase) -> None:
    import numpy as np

    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitFeaturizer

    n = ph.size["vw_rows"]
    rng = np.random.default_rng(5)
    vocab = np.array([f"w{i}" for i in range(2000)], dtype=object)
    word_w = rng.normal(size=len(vocab))
    toks = rng.integers(0, len(vocab), size=(n, 12))
    texts = np.array([" ".join(vocab[r]) for r in toks], dtype=object)
    # a label the hashed linear model can learn: the sign of the summed
    # per-word weights
    y = (word_w[toks].sum(axis=1) > 0).astype(np.float64)
    df = DataFrame.from_dict({"text": texts, "label": y})
    fdf = VowpalWabbitFeaturizer(
        input_cols=[], string_split_input_cols=["text"],
        output_col="features",
    ).transform(df)
    clf = VowpalWabbitClassifier(num_passes=3)
    with ph.census(lambda a: a.ndim == 2 and a.shape[0] >= n) as seen:
        model, first_s = _timed(lambda: clf.fit(fdf))
    _, repeat_s = _timed(lambda: clf.fit(fdf))
    pred = np.asarray(model.transform(fdf)["prediction"], np.float64)
    acc = float((pred == y).mean())
    majority = float(max(y.mean(), 1 - y.mean()))
    ph.rec.update(
        shapes={"rows": n, "tokens_per_row": 12},
        devices_holding_data=seen["devices"],
        first_call_s=first_s, repeat_call_s=repeat_s,
        train_accuracy=round(acc, 4), majority_rate=round(majority, 4),
        margin=ph.size["vw_margin"],
    )
    ph.checks.update(
        finite=bool(np.isfinite(pred).all()),
        beats_majority_by_margin=acc >= majority + ph.size["vw_margin"],
        data_on_every_device=seen["devices"] == ph.rec["n_dev"],
    )


def _phase_pipeline(ph: _Phase) -> None:
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu import DataFrame, Pipeline, obs
    from mmlspark_tpu.featurize.featurize import Featurize
    from mmlspark_tpu.models.linear import LogisticRegression
    from mmlspark_tpu.stages.basic import UDFTransformer

    n, n_raw, parts = ph.size["pipe_rows"], 16, 4
    rng = np.random.default_rng(7)
    cols = {f"x{i}": rng.standard_normal(n) for i in range(n_raw)}
    cols["vec"] = rng.standard_normal((n, 16)).astype(np.float32)
    cols["label"] = rng.integers(0, 4, n)
    df = DataFrame.from_dict(cols, num_partitions=parts)
    model = Pipeline([
        Featurize(input_cols=[f"x{i}" for i in range(n_raw)] + ["vec"],
                  output_col="features"),
        UDFTransformer(input_col="features", output_col="features_s",
                       vector_udf=lambda x: jnp.tanh(x * jnp.float32(0.5)),
                       jit_compatible=True),
        LogisticRegression(features_col="features_s", label_col="label",
                           max_iter=30),
    ]).fit(df)
    staged = model.transform(df)
    compiled = model.compile()
    with ph.census(
        lambda a: a.ndim >= 1 and a.shape[0] >= 1024
    ) as seen:
        fused, first_s = _timed(lambda: compiled.transform(df))
    _, repeat_s = _timed(lambda: compiled.transform(df))
    equal = all(
        staged[c].dtype == fused[c].dtype
        and np.array_equal(staged[c], fused[c])
        for c in staged.columns
    )
    fam = obs.REGISTRY.snapshot().get("mmlspark_compiler_fallback_total")
    fallbacks = int(sum(v for _, v in (fam or {}).get("samples", [])))
    ph.rec.update(
        shapes={"rows": n, "partitions": parts,
                "stages_fused": compiled.num_fused_stages,
                "segments": len(compiled.segments)},
        devices_holding_data=seen["devices"],
        first_call_s=first_s, repeat_call_s=repeat_s,
        compiler_fallbacks=fallbacks,
    )
    ph.checks.update(
        fused_equals_staged=bool(equal),
        stages_were_fused=compiled.num_fused_stages >= 2,
        no_compiler_fallback=fallbacks == 0,
    )


# -- serve: this child never imports JAX; the worker it starts holds the chip


def _count_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except OSError:
        return 0


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for_line(path: str, needle: str, proc: subprocess.Popen,
                   timeout_s: float) -> "str | None":
    """First line of the log at ``path`` containing ``needle``; None if the
    process dies or the time runs out first."""
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        try:
            with open(path, errors="replace") as f:
                for ln in f:
                    if needle in ln:
                        return ln.rstrip("\n")
        except OSError:
            pass
        if proc.poll() is not None:
            return None
        time.sleep(0.25)
    return None


def _spawn_logged(cmd: list, log_path: str, env: "dict | None" = None):
    """Start a fleet role from the checkout with its output in a log file."""
    with open(log_path, "w") as f:
        return subprocess.Popen(cmd, cwd=HERE, env=env, stdout=f,
                                stderr=subprocess.STDOUT)


def _stop(proc: "subprocess.Popen | None", grace_s: float) -> "int | None":
    """SIGTERM, wait, SIGKILL if it will not go; returns the exit code of a
    process that went by itself, None for one that had to be killed."""
    if proc is None or proc.poll() is not None:
        return None if proc is None else proc.returncode
    proc.send_signal(signal.SIGTERM)
    try:
        return proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)
        return None


def _phase_serve(out: str, rehearse: bool) -> int:
    import urllib.request

    import numpy as np

    sz = SIZES[rehearse]
    # what `featurize` left behind: the images to post and the rows the
    # replies must equal
    images = np.load(os.path.join(out, "images_head.npy"))
    want = np.load(os.path.join(out, "features.npy"))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache"
    )
    entries_before = _count_entries(cache_dir)
    fleet = [sys.executable, "-m", "mmlspark_tpu.serving.fleet"]
    reg_port, w_port, w2_port = _free_port(), _free_port(), _free_port()
    reg_url = f"http://127.0.0.1:{reg_port}/"
    # the registry does no device work: it is held to the CPU so it can
    # never take the chip from the worker (docs/serving.md)
    reg_env = dict(os.environ, JAX_PLATFORMS="cpu")
    worker_cmd = fleet + [
        "worker", "--registry", reg_url, "--model", f"zoo:{sz['model']}",
        "--host", "127.0.0.1", "--drain-s", "5",
    ]
    rec: dict = {"phase": "serve", "device_kind": None,
                 "devices_holding_data": None}
    if rehearse:
        rec["rehearsal"] = True
    checks: dict = {}
    registry = worker = second = None
    try:
        reg_log = os.path.join(out, "registry.log")
        registry = _spawn_logged(
            fleet + ["registry", "--host", "127.0.0.1", "--port",
                     str(reg_port)],
            reg_log, env=reg_env,
        )
        checks["registry_up"] = (
            _wait_for_line(reg_log, "registry:", registry, 120) is not None
        )
        w_log = os.path.join(out, "worker.log")
        t0 = time.perf_counter()
        worker = _spawn_logged(worker_cmd + ["--port", str(w_port)], w_log)
        dev_line = _wait_for_line(w_log, "worker: devices ", worker, 240)
        if dev_line:
            summary = json.loads(dev_line.split("worker: devices ", 1)[1])
            rec.update(platform=summary["platform"],
                       device_kind=summary["device_kind"],
                       n_dev=summary["num_devices"])
        checks["worker_reported_its_devices"] = dev_line is not None
        # "worker: host:port models=..." is printed only after the model
        # is loaded AND warmed, i.e. ResNet-50 is compiled
        ready = _wait_for_line(w_log, f"worker: 127.0.0.1:{w_port}",
                               worker, 420)
        rec["worker_ready_s"] = round(time.perf_counter() - t0, 3)
        checks["worker_ready"] = ready is not None
        if not rehearse and ready is not None:
            # one process per chip: a second local worker cannot have the
            # chips the first one holds. With the platform pinned that must
            # be a prompt error that says so — never a worker serving from
            # the CPU, never a hang
            w2_log = os.path.join(out, "worker2.log")
            t1 = time.perf_counter()
            second = _spawn_logged(
                worker_cmd + ["--port", str(w2_port)], w2_log
            )
            try:
                rc2 = second.wait(timeout=120)
            except subprocess.TimeoutExpired:
                rc2 = None
            rec["second_worker"] = {
                "exit_code": rc2,
                "seconds": round(time.perf_counter() - t1, 3),
                "log_tail": _tail(w2_log, 600),
            }
            checks["second_worker_refused_promptly"] = (
                rc2 is not None and rc2 != 0
            )
        times, cosines, rels, statuses = [], [], [], []
        for i in range(sz["posts"] if ready is not None else 0):
            body = json.dumps({"image": images[i].tolist()}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{w_port}/", data=body, method="POST",
                headers={"Content-Type": "application/json"},
            )
            t1 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as resp:
                statuses.append(resp.status)
                feats = np.asarray(json.loads(resp.read())["features"])
            times.append(round(time.perf_counter() - t1, 4))
            cosines.append(_cosine(feats, want[i]))
            rels.append(_rel_err(feats, want[i]))
        checks["all_200"] = bool(statuses) and all(s == 200 for s in statuses)
        # the same image through the batch-256 program of `featurize` and
        # the worker's own batch shape: equal to bf16 tolerance
        checks["features_equal_featurize_rows"] = (
            bool(cosines) and min(cosines) >= 0.99 and max(rels) <= 0.1
        )
        rc = _stop(worker, 60)
        checks["worker_exited_on_sigterm"] = rc == 0
        rec.update(
            shapes={"posts": len(statuses), "image": list(images.shape[1:]),
                    "features": int(want.shape[1])},
            first_call_s=times[0] if times else None,
            repeat_call_s=sorted(times[1:])[len(times[1:]) // 2]
            if len(times) > 1 else None,
            cosine_min=round(min(cosines), 6) if cosines else None,
            rel_err_max=round(max(rels), 5) if rels else None,
            worker_exit_code=rc,
            # the worker is another process: its cache traffic is seen only
            # as entries appearing in the shared directory
            cache={"new_entries": _count_entries(cache_dir) - entries_before},
        )
    finally:
        for p in (second, worker, registry):
            _stop(p, 10)
    rec["checks"] = checks
    rec["ok"] = all(bool(v) for v in checks.values())
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


def _run_phase(name: str, out: str, rehearse: bool) -> int:
    if name == "serve":
        return _phase_serve(out, rehearse)
    ph = _Phase(name, out, rehearse)
    {"featurize": _phase_featurize, "gbdt": _phase_gbdt,
     "vw": _phase_vw, "pipeline": _phase_pipeline}[name](ph)
    return ph.finish()


if __name__ == "__main__":
    sys.exit(main())
