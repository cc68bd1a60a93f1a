"""Driver: whole ``LightGBMClassifier.fit`` calls on one DataFrame.

The window drives what a user calls — ``LightGBMClassifier(...).fit(df)`` —
so binning, upload, ``train()``, the grower, the histogram kernel, on a
mesh of several chips the plane ``psum``, and the tree unpack are all
inside the time. Fits start while less than ``--seconds`` have passed; the
rate is all trees of all fits over all the time those fits took.

From the program it takes the estimator, the DataFrame and two counters;
the data, the sample and the comparison are the benchmark's own.
"""

from __future__ import annotations

import json
import time

import numpy as np

from chipbench import traffic_gen
from chipbench.reference import gbdt as ref

# PERF.md section 2 gives the readings each limit was set from
LIMIT_SPLIT_GAIN_GAP = 5e-4
LIMIT_LEAF_VALUE_MEDIAN = 2e-4


def _counter(name: str) -> dict:
    from mmlspark_tpu import obs

    fam = obs.REGISTRY.snapshot().get(name) or {}
    out: dict = {}
    for labels, value in fam.get("samples", []):
        key = ":".join(str(labels[k]) for k in sorted(labels)) or "total"
        out[key] = out.get(key, 0) + value
    return out


def _estimator(ctx: object) -> object:
    from mmlspark_tpu.models.gbdt import LightGBMClassifier

    cfg = ctx.config
    return LightGBMClassifier(
        objective=cfg["objective"], num_iterations=int(ctx.traffic["trees_per_fit"]),
        learning_rate=cfg["learning_rate"], num_leaves=cfg["num_leaves"],
        max_bin=cfg["max_bin"], min_data_in_leaf=cfg["min_data_in_leaf"],
        min_sum_hessian_in_leaf=cfg["min_sum_hessian_in_leaf"],
        lambda_l2=cfg["lambda_l2"], growth_policy=cfg["growth_policy"], seed=0,
    )


def setup(ctx: object) -> dict:
    from mmlspark_tpu.core.dataframe import DataFrame

    data = traffic_gen.generate(ctx.traffic, ctx.config, ctx.seed)
    df = DataFrame.from_dict({"features": data["x"], "label": data["y"]})
    clf = _estimator(ctx)
    # warm-up: one whole fit, the cell's one shape (the fit program is one
    # scan over the trees; there is no smaller call that compiles it)
    with ctx.span("warmup"):
        clf.fit(df)
    return {"clf": clf, "df": df, "data": data, "models": []}


def window(ctx: object, state: dict, seconds: float) -> dict:
    clf, df, models = state["clf"], state["df"], state["models"]
    trees_per_fit = int(ctx.traffic["trees_per_fit"])
    chunks0 = sum(_counter("mmlspark_gbdt_fused_chunks_total").values())
    fit_s = []
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds:
        t0 = time.perf_counter()
        with ctx.span("fit"):
            model = clf.fit(df)
        fit_s.append(time.perf_counter() - t0)
        models.append(model.get("model_string"))
    elapsed = sum(fit_s)
    trees = trees_per_fit * len(fit_s)
    lowerings = _counter("mmlspark_gbdt_hist_lowerings_total")
    return {
        "metrics": {"trees_per_s": trees / elapsed},
        "attempted": len(fit_s),
        "failed": sum(1 for m in models if not m),
        "work": {"fits": len(fit_s), "trees": trees, "trees_per_fit": trees_per_fit,
                 "rows": int(ctx.traffic["rows"]), "features": int(ctx.config["features"]),
                 "rows_per_device": -(-int(ctx.traffic["rows"]) // len(ctx.devices)),
                 "num_leaves": int(ctx.config["num_leaves"]),
                 "elapsed_s": elapsed, "fit_s": fit_s,
                 "dispatches": sum(_counter("mmlspark_gbdt_fused_chunks_total").values()) - chunks0,
                 "hist_lowerings": lowerings},
    }


def release(ctx: object, state: dict) -> None:
    import jax

    state["clf"] = None
    state["df"] = None
    jax.clear_caches()  # the fit programs and what they hold on the device


def parse_model(model_string: str, edges: list) -> dict:
    """The answer under judgement as plain arrays; thresholds are mapped
    onto the reference's own bin edges."""
    d = json.loads(model_string)
    trees = []
    for t in d["trees"]:
        active = np.asarray(t["active"], bool)
        feature = np.asarray(t["feature"], np.int64)
        thr = [np.inf if v in (None, "inf") else (-np.inf if v == "-inf" else float(v))
               for v in t["threshold"]]
        bins = np.array([ref.threshold_bin(edges, int(f), v) if a else 0
                         for f, v, a in zip(feature, thr, active)], np.int64)
        trees.append({"leaf": np.asarray(t["leaf"], np.int64), "feature": np.maximum(feature, 0),
                      "bin": bins, "active": active, "threshold": np.asarray(thr),
                      "values": np.asarray(t["values"], np.float64),
                      "counts": np.asarray(t["counts"], np.int64)})
    return {"base_score": float(d["base_score"]), "trees": trees}


def judge(ctx: object, state: dict, model_string: str, with_control: bool = False) -> dict:
    """Follow one fitted model tree by tree with the reference."""
    import jax.numpy as jnp

    cfg = ctx.config
    x, y = state["data"]["x"], state["data"]["y"]
    edges = ref.bin_edges(x, cfg["max_bin"], cfg["bin_sample_rows"], seed=0)
    bins = ref.pad_rows(ref.bin_matrix(x, edges), 0)
    model = parse_model(model_string, edges)
    n = len(y)
    bins_dev = jnp.asarray(bins)
    bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
    scores = np.full(n, model["base_score"], np.float64)
    L = cfg["num_leaves"]
    out = {"gain_gaps": [], "value_gaps": [], "count_mismatch": 0, "splits": 0,
           "control_gain_gaps": [], "control_value_gaps": []}
    for tree in model["trees"]:
        g, h = ref.grad_hess(scores, y)
        cols = [g, h, np.ones(n)]
        if with_control:
            cols += [np.asarray(jnp.asarray(c, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))
                     for c in (g, h)]
        stats = ref.pad_rows(np.stack(cols, axis=1).astype(np.float32), 0.0)
        row_leaf = ref.route(bins_t, jnp.asarray(tree["leaf"], jnp.int32),
                             jnp.asarray(tree["feature"], jnp.int32),
                             jnp.asarray(tree["bin"], jnp.int32),
                             jnp.asarray(tree["active"]), L)
        row_leaf = jnp.where(jnp.arange(bins.shape[0]) < n, row_leaf, -1)
        member = ref.tree_nodes(tree["leaf"], tree["active"], L)[0]
        leaf_hist = np.asarray(ref.leaf_histograms(bins_dev, jnp.asarray(stats), row_leaf, L))
        hist = ref.node_histograms(
            leaf_hist.reshape(leaf_hist.shape[0], leaf_hist.shape[1], L, len(cols)), member)
        r = ref.judge_tree(hist, tree, cfg)
        out["gain_gaps"] += r["gain_gaps"]
        out["value_gaps"] += r["value_gaps"]
        out["count_mismatch"] += r["count_mismatch"]
        out["splits"] += r["splits"]
        if with_control:
            c = ref.judge_tree(hist, tree, cfg, choose_at=3)
            out["control_gain_gaps"] += c["gain_gaps"]
            out["control_value_gaps"] += c["value_gaps"]
        # the reference's scores follow the program's answer (its leaf values)
        scores = scores + tree["values"][np.asarray(row_leaf)[:n]]
    return out


def _numbers(ctx: object, state: dict, with_control: bool) -> dict:
    models = state["models"]
    pick = int(ctx.rng(3).integers(len(models)))
    r = judge(ctx, state, models[pick], with_control)
    r["models_differing"] = sum(1 for m in models if m != models[pick])
    return r


def _gaps(gain_gaps: list, value_gaps: list) -> list:
    """The two gaps of one reading, the program's or the control's, beside
    their limits: one comparison for both."""
    gain = max(gain_gaps) if gain_gaps else float("inf")
    median = float(np.median(value_gaps)) if value_gaps else float("inf")
    return [
        {"name": "split_gain_gap_max", "value": gain, "limit": LIMIT_SPLIT_GAIN_GAP,
         "ok": gain <= LIMIT_SPLIT_GAIN_GAP},
        {"name": "leaf_value_gap_median", "value": median, "limit": LIMIT_LEAF_VALUE_MEDIAN,
         "ok": median <= LIMIT_LEAF_VALUE_MEDIAN},
    ]


def control(ctx: object, state: dict) -> list:
    """The reference with its (g, h) statistics rounded to bfloat16, the
    precision below the float32 the configuration states, in the program's
    place: at every step the split that histogram puts first, and at every
    leaf the value it gives, judged by the float32 reference. Beside each
    number, not compared: the worst leaf of the control and of the program
    (PERF.md section 2 says why that number sets no limit)."""
    r = _numbers(ctx, state, True)
    out = _gaps(r["control_gain_gaps"], r["control_value_gaps"])
    out[1].update(leaf_value_gap_max=max(r["control_value_gaps"]),
                  program_leaf_value_gap_max=max(r["value_gaps"]))
    return out


def check(ctx: object, state: dict) -> list:
    if not state["models"]:
        return [{"name": "fits_compared", "value": 0, "limit": 1, "ok": False}]
    r = _numbers(ctx, state, False)
    want_splits = (ctx.config["num_leaves"] - 1) * int(ctx.traffic["trees_per_fit"])
    out = _gaps(r["gain_gaps"], r["value_gaps"]) + [
        {"name": "leaf_rows_mismatch", "value": r["count_mismatch"], "limit": 0,
         "ok": r["count_mismatch"] == 0},
        {"name": "splits_short", "value": want_splits - r["splits"], "limit": 0,
         "ok": r["splits"] == want_splits},
        {"name": "models_differing", "value": r["models_differing"], "limit": 0,
         "ok": r["models_differing"] == 0},
    ]
    if not ctx.rehearse:
        # a run whose histograms took the scatter measured the reference
        # lowering, not the kernel
        scatter = sum(v for k, v in _counter("mmlspark_gbdt_hist_lowerings_total").items()
                      if "scatter" in k)
        out.append({"name": "scatter_lowerings", "value": scatter, "limit": 0,
                    "ok": scatter == 0})
    return out
