"""GBDT training loop.

The analogue of lightgbm/TrainUtils.scala's ``trainCore`` iteration loop
(:220-315): per boosting iteration compute grad/hess from current scores,
grow one tree per class (the compiled ``grow_tree`` program — histogram +
split search + partition assignment all on device), update scores from the
grower's own row->leaf output (free, no re-predict), evaluate + early-stop.

Boosting modes (``boostingType`` in lightgbm/LightGBMParams.scala, golden
matrix src/test/resources/benchmarks/benchmarks_VerifyLightGBMClassifier.csv):
- ``gbdt``  — plain gradient boosting.
- ``goss``  — gradient-based one-side sampling: keep the top ``top_rate``
  fraction of rows by |gradient|, sample ``other_rate`` of the rest and
  amplify their weight by (1-a)/b so histogram sums stay unbiased.
- ``dart``  — per iteration (unless ``skip_drop`` fires) drop a random
  subset of past iterations, fit the new tree against the scores without
  them, then normalize: new tree x 1/(k+1), dropped trees x k/(k+1).
- ``rf``    — random forest: constant gradients at the initial score,
  bagging per iteration, no shrinkage; prediction averages trees.

Device residency: scores, gradients, labels and bagging/GOSS masks live on
device (sharded over the mesh ``data`` axis) across all iterations — the
host sees only the per-tree split records and the eval-metric scalar
(lightgbm/TrainUtils.scala:220-315 keeps the equivalent state inside the
native booster for the same reason). LambdaRank's pairwise gradients are
device-resident too (objectives.lambdarank_grad_hess_device over padded
contiguous groups), so ranking joins the scan-fused path; only multihost
ranking (and pathological group sizes whose padded pair tensors exceed the
device budget) falls back to host gradients.

Distribution: rows are batch-sharded over the mesh ``data`` axis before the
loop. ``data_parallel`` lets GSPMD partition the histogram scatter and
insert the full-plane ICI allreduce; ``voting_parallel`` switches to the
PV-Tree grower (models/gbdt/voting.py) — local top-K feature votes, one
tiny vote psum, and an allreduce of only the winning candidates' histogram
columns (LightGBMParams.scala:13-18 semantics, real reduced communication).
Voting needs >1 shard; single-shard layouts fall back to data_parallel
with a log note. Categorical features vote and split like anywhere else.
"""

from __future__ import annotations

import functools
import logging
import time as _time
from dataclasses import dataclass, replace as _dc_replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core import compile_cache, faults
from mmlspark_tpu.models.gbdt import objectives
from mmlspark_tpu.parallel.mesh import DATA_AXIS as _DATA_AXIS
from mmlspark_tpu.models.gbdt.binning import BinMapper
from mmlspark_tpu.ops.histogram import NUM_BINS
from mmlspark_tpu.models.gbdt.booster import Booster, Tree, per_tree_raw
from mmlspark_tpu.models.gbdt import treegrow

log = logging.getLogger("mmlspark_tpu.gbdt")

BOOSTING_TYPES = ("gbdt", "goss", "dart", "rf")

# training telemetry (docs/observability.md): round wall-clock covers
# gradients + grow + score update + (fast path) on-device eval, i.e. the
# whole per-iteration cost the next perf PR will be judged against
_M_ROUNDS = obs.counter(
    "mmlspark_gbdt_rounds_total", "Completed boosting rounds",
)
_M_ROUND_SECONDS = obs.histogram(
    "mmlspark_gbdt_round_seconds",
    "Per-round wall time (scan-fused chunks report chunk time / rounds)",
)
_M_CHUNK_SECONDS = obs.histogram(
    "mmlspark_gbdt_chunk_seconds",
    "Scan-fused chunk wall time: dispatch + eval read + record unpack",
)
_M_FUSED_CHUNKS = obs.counter(
    "mmlspark_gbdt_fused_chunks_total",
    "Scan-fused chunk dispatches: a training run costs O(rounds / chunk) "
    "of these instead of O(rounds) per-round dispatches",
)
_M_HIST_ROWS = obs.counter(
    "mmlspark_gbdt_hist_rows_total",
    "Rows handed to the leaf-wise growers' histogram calls (kind=streamed) "
    "and the rows of them that counted (kind=selected): the useful share "
    "of a pass. Masked grower: all rows a call, and what its mask selected "
    "(with row weights the weighted count, rounded per call); partitioned "
    "grower: a call's bucket, and the smaller child's rows. Added when a tree's record "
    "reaches the host; dart's per-tree fetch does not carry it",
    labels=("kind",),
)
_M_HIST_STREAMED = _M_HIST_ROWS.labels(kind="streamed")
_M_HIST_SELECTED = _M_HIST_ROWS.labels(kind="selected")
_M_DEVICE_EVAL_ROUNDS = obs.counter(
    "mmlspark_gbdt_device_eval_rounds_total",
    "Boosting rounds whose eval metric was computed on device inside the "
    "fused chunk (no per-round host sync)",
)


@dataclass
class TrainConfig:
    objective: str = "binary"          # binary|multiclass|regression|lambdarank
    num_class: int = 1
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = -1
    lambda_l2: float = 0.0
    lambda_l1: float = 0.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    max_bin: int = 255
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    early_stopping_round: int = 0
    metric: str = ""                   # default chosen by objective
    seed: int = 0
    parallelism: str = "data_parallel"  # accepted for parity
    # lossguide = LightGBM's leaf-wise best-first growth (default);
    # depthwise = level-wise growth whose histograms batch into one
    # multi-leaf pass per level (XGBoost-hist policy; O(depth) row passes)
    growth_policy: str = "lossguide"
    top_k: int = 20                     # voting_parallel K (parity)
    verbosity: int = -1
    # feature indices treated as categorical (LightGBM categoricalSlotIndexes
    # analogue): identity-binned, split by subset membership
    categorical_features: tuple = ()
    boosting_type: str = "gbdt"        # gbdt|goss|dart|rf
    # dart knobs (LightGBM drop_rate/max_drop/skip_drop defaults)
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    # goss knobs (LightGBM top_rate/other_rate defaults)
    top_rate: float = 0.2
    other_rate: float = 0.1
    # lambdarank eval truncation: NDCG@eval_at on the validation rows
    eval_at: int = 5
    # regression-objective knobs (LightGBM TrainParams.scala:8-40)
    alpha: float = 0.9                 # quantile level / huber delta
    tweedie_variance_power: float = 1.5
    poisson_max_delta_step: float = 0.7
    fair_c: float = 1.0
    # training-lifecycle callbacks + dynamic learning rate
    # (LightGBMDelegate analogue, models/gbdt/delegate.py)
    delegate: Optional[Any] = None


def _objective_p1(cfg: "TrainConfig") -> float:
    """The (single) knob each regression objective consumes."""
    return {
        "quantile": cfg.alpha,
        "huber": cfg.alpha,
        "fair": cfg.fair_c,
        "poisson": cfg.poisson_max_delta_step,
        "tweedie": cfg.tweedie_variance_power,
    }.get(cfg.objective, 0.0)


_TREE_FIELDS = (
    "rec_leaf", "rec_feature", "rec_bin", "rec_is_cat", "rec_active",
    "rec_gain", "leaf_values", "leaf_counts", "rec_catmask",
)


def _trees_from_device_batched(pending: list, mapper: BinMapper) -> list:
    """Materialize many device-grown trees with ONE host fetch per field.

    The per-iteration loop keeps every split record on device; fetching the
    ~8 small record arrays tree by tree costs a full host round-trip each
    (70 ms over a remote-device link — it dominated training wall-clock).
    Stacking per field first turns 8 x n_trees fetches into 8."""
    if not pending:
        return []
    stacked = {
        f: np.asarray(jnp.stack([getattr(g, f) for g in pending]))
        for f in _TREE_FIELDS
    }
    counted = [g.hist_rows for g in pending if g.hist_rows is not None]
    if counted:
        _count_hist_rows(np.asarray(jnp.stack(counted)))
    return [
        _tree_from_host_records({f: stacked[f][i] for f in _TREE_FIELDS}, mapper)
        for i in range(len(pending))
    ]


def _count_hist_rows(rows: np.ndarray) -> tuple:
    """Add the growers' (..., 4) hist_rows records (GrownTree.hist_rows:
    streamed and selected, each as // 4096 and % 4096) to
    ``mmlspark_gbdt_hist_rows_total``; returns (streamed, selected)."""
    r = np.asarray(rows).reshape(-1, 4).astype(np.int64).sum(axis=0)
    streamed, selected = int(r[0] * 4096 + r[1]), int(r[2] * 4096 + r[3])
    _M_HIST_STREAMED.inc(streamed)
    _M_HIST_SELECTED.inc(selected)
    return streamed, selected


def _pad_catmask(cm: np.ndarray) -> np.ndarray:
    """Histogram-space catmask (S, B_hist) -> record-space (S, NUM_BINS).

    Training histograms use the smallest tile-aligned bin space covering
    ``max_bin``; stored trees keep the full uint8 space so prediction's
    category->bin lookup (category_bin_slot, clipped to NUM_BINS-1) can
    never index out of the mask. Padding bins carry no categories -> False
    (unseen categories route RIGHT, LightGBM's other-category default)."""
    if cm.shape[-1] >= NUM_BINS:
        return cm
    pad = [(0, 0)] * (cm.ndim - 1) + [(0, NUM_BINS - cm.shape[-1])]
    return np.pad(cm, pad)


def _tree_from_host_records(rec: dict, mapper: BinMapper) -> Tree:
    rec_leaf = rec["rec_leaf"]
    rec_feature = rec["rec_feature"]
    rec_bin = rec["rec_bin"]
    is_cat = rec["rec_is_cat"]
    thr = np.array(
        [
            mapper.threshold_value(int(f), int(b)) if (f >= 0 and not c) else np.inf
            for f, b, c in zip(rec_feature, rec_bin, is_cat)
        ],
        dtype=np.float64,
    )
    has_cat = bool(is_cat.any())
    return Tree(
        leaf=rec_leaf,
        feature=rec_feature,
        threshold=thr,
        active=rec["rec_active"],
        gain=rec["rec_gain"],
        values=rec["leaf_values"],
        counts=rec["leaf_counts"],
        is_cat=is_cat if has_cat else None,
        catmask=_pad_catmask(rec["rec_catmask"]) if has_cat else None,
    )


def _tree_from_device(grown: Any, mapper: BinMapper, value_scale: float = 1.0) -> Tree:
    rec_leaf = np.asarray(grown.rec_leaf)
    rec_feature = np.asarray(grown.rec_feature)
    rec_bin = np.asarray(grown.rec_bin)
    is_cat = np.asarray(grown.rec_is_cat)
    thr = np.array(
        [
            # categorical splits route by catmask, never by threshold:
            # +inf keeps any accidental numeric comparison all-left
            mapper.threshold_value(int(f), int(b)) if (f >= 0 and not c) else np.inf
            for f, b, c in zip(rec_feature, rec_bin, is_cat)
        ],
        dtype=np.float64,
    )
    has_cat = bool(is_cat.any())
    values = np.asarray(grown.leaf_values)
    if value_scale != 1.0:
        values = (values * value_scale).astype(values.dtype)
    return Tree(
        leaf=rec_leaf,
        feature=rec_feature,
        threshold=thr,
        active=np.asarray(grown.rec_active),
        gain=np.asarray(grown.rec_gain),
        values=values,
        counts=np.asarray(grown.leaf_counts),
        is_cat=is_cat if has_cat else None,
        catmask=_pad_catmask(np.asarray(grown.rec_catmask)) if has_cat else None,
    )


def grouped_ndcg(
    scores: np.ndarray, labels: np.ndarray, group_ids: np.ndarray, k: int = 5
) -> float:
    """Mean NDCG@k over query groups with LightGBM's 2^rel-1 gain.

    The real ranking eval the reference's early stopping uses
    (lightgbm/LightGBMRanker.scala; TrainUtils.scala:276-308 evaluates the
    native booster's ndcg@k). Mirrors recommendation/evaluator.py's
    per-user NDCG, specialized to flat score/label arrays."""
    total, n_groups = 0.0, 0
    for gid in np.unique(group_ids):
        m = group_ids == gid
        s, rel = scores[m], labels[m]
        if len(s) == 0:
            continue
        kk = min(k, len(s))
        order = np.argsort(-s, kind="stable")[:kk]
        gains = 2.0 ** rel - 1.0
        disc = 1.0 / np.log2(np.arange(2, kk + 2))
        dcg = float((gains[order] * disc).sum())
        ideal = np.sort(gains)[::-1][:kk]
        idcg = float((ideal * disc).sum())
        # all-zero-relevance groups score 1.0 (LightGBM's NDCG convention:
        # nothing to rank correctly means nothing ranked incorrectly)
        total += dcg / idcg if idcg > 0 else 1.0
        n_groups += 1
    return total / max(n_groups, 1)


def _local_block_rows(garr: Any, n: int) -> np.ndarray:
    """First ``n`` rows of THIS process's block of a process-stacked global
    array (the layout shard_batch_multihost builds: one contiguous block
    per process, local padding at the block tail)."""
    shards = sorted(
        garr.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    block = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    return block[:n]


def _gather_rows(local: np.ndarray, n: int, share: int) -> np.ndarray:
    """Pad this process's first-n rows to the common block size and
    allgather -> (nproc * share, ...) global rows (padding rows are 0).
    Every process computes validation metrics on the identical gathered
    arrays, so early-stopping decisions stay convergent across SPMD
    processes (divergent control flow would deadlock the next collective).
    """
    import jax.experimental.multihost_utils as mhu

    local = local.reshape(n, -1).astype(np.float64)
    buf = np.zeros((share, local.shape[1]), np.float64)
    buf[:n] = local
    ga = np.asarray(mhu.process_allgather(buf))
    return ga.reshape(-1, local.shape[1])


def _eval_metric(
    cfg: TrainConfig,
    scores: np.ndarray,
    y: np.ndarray,
    mask: np.ndarray,
    group_ids: Optional[np.ndarray] = None,
) -> tuple:
    """Returns (name, value, higher_is_better) on masked rows."""
    if mask.sum() == 0:
        return ("none", float("nan"), False)
    s, yy = scores[mask], y[mask]
    obj = cfg.objective
    metric = cfg.metric
    if obj == "binary":
        p = objectives.sigmoid(s)
        if metric in ("", "binary_logloss"):
            p = np.clip(p, 1e-15, 1 - 1e-15)
            return ("binary_logloss", float(-(yy * np.log(p) + (1 - yy) * np.log(1 - p)).mean()), False)
        if metric == "auc":
            from mmlspark_tpu.core.metrics import binary_auc

            return ("auc", binary_auc(yy, p), True)
        return ("binary_error", float(((p > 0.5) != (yy > 0.5)).mean()), False)
    if obj == "multiclass":
        p = objectives.softmax(s)
        idx = yy.astype(np.int64)
        return (
            "multi_logloss",
            float(-np.log(np.clip(p[np.arange(len(idx)), idx], 1e-15, 1)).mean()),
            False,
        )
    if obj == "lambdarank":
        k = cfg.eval_at
        if metric.startswith("ndcg@"):
            k = int(metric.split("@", 1)[1])
        g = group_ids[mask] if group_ids is not None else np.zeros(len(yy), np.int64)
        return (f"ndcg@{k}", grouped_ndcg(s, yy, g, k=k), True)
    return (
        objectives.regression_metric_name(obj),
        float(
            objectives.regression_loss(obj, s, yy, _objective_p1(cfg)).mean()
        ),
        False,
    )


def _iteration_core(
    bins: jnp.ndarray,
    scores: jnp.ndarray,
    y_enc: Optional[jnp.ndarray],
    w_it: jnp.ndarray,
    it_key: jnp.ndarray,
    fm: jnp.ndarray,
    cat_mask: Optional[jnp.ndarray],
    g_pre: Optional[jnp.ndarray],
    h_pre: Optional[jnp.ndarray],
    rank_idx: Optional[jnp.ndarray],
    rank_valid: Optional[jnp.ndarray],
    obj_p1: Any,
    top_rate: float,
    other_rate: float,
    lambda_l2: float,
    lambda_l1: float,
    min_sum_hessian: float,
    min_gain: float,
    learning_rate: float,
    *,
    objective: str,
    k: int,
    grad_pre: bool,
    is_goss: bool,
    has_cat: bool,
    num_leaves: int,
    max_depth: int,
    min_data_in_leaf: int,
    top_k: int,
    grower: treegrow.Grower,
    num_bins: int = NUM_BINS,
) -> tuple:
    """One boosting iteration (traced): gradients, GOSS weights, k tree
    grows and the score update. Shared by the per-iteration dispatch path
    (:func:`_fused_iteration`) and the scan-fused chunk path
    (:func:`_scan_chunk`). Returns (new_scores, list of GrownTree)."""
    if grad_pre:
        g_dev, h_dev = g_pre, h_pre
    elif objective == "binary":
        g_dev, h_dev = objectives.binary_grad_hess(scores, y_enc)
    elif objective == "multiclass":
        g_dev, h_dev = objectives.multiclass_grad_hess(scores, y_enc)
    elif objective == "lambdarank":
        # device-resident pairwise gradients over padded contiguous groups
        # — ranking trains scan-fused with zero per-iteration host syncs
        g_dev, h_dev = objectives.lambdarank_grad_hess_device(
            scores, y_enc, rank_idx, rank_valid
        )
    else:
        g_dev, h_dev = objectives.regression_grad_hess(
            objective, scores, y_enc, obj_p1
        )
    # pre-GOSS weights (bagging/user weights only): LightGBM's
    # RenewTreeOutput computes the leaf percentile over the sampled rows at
    # their ORIGINAL data weights — the (1-a)/b amplification is a
    # histogram-unbiasedness device, not a data weight
    w_renew = w_it
    if is_goss:
        g_abs = jnp.abs(g_dev).sum(axis=1) if k > 1 else jnp.abs(g_dev)
        u = jax.random.uniform(jax.random.fold_in(it_key, 2), w_it.shape)
        w_it = w_it * _goss_weights(g_abs, w_it, u, top_rate, other_rate)
    grow_kw = dict(
        num_leaves=num_leaves,
        lambda_l2=lambda_l2,
        lambda_l1=lambda_l1,
        min_sum_hessian=min_sum_hessian,
        min_gain=min_gain,
        learning_rate=learning_rate,
        feature_mask=fm,
        max_depth=max_depth,
        min_data_in_leaf=min_data_in_leaf,
        num_bins=num_bins,
    )
    grown_list, deltas = [], []
    for c in range(k) if k > 1 else [0]:
        gc = g_dev[:, c] if k > 1 else g_dev
        hc = h_dev[:, c] if k > 1 else h_dev
        grown = treegrow.grow_tree(
            bins, gc, hc, w_it, categorical_mask=cat_mask, grower=grower,
            top_k=top_k, **grow_kw,
        )
        if (
            objective in objectives.RENEWED_KINDS
            and not grad_pre
            and grower.kind != "voting"
        ):
            # LightGBM's RenewTreeOutput: quantile-family leaf values are
            # the weighted alpha-percentile of the leaf's residuals, not
            # the unit-hessian Newton step (which undershoots the target
            # percentile). Voting keeps Newton values: its row_leaf stays
            # shard-local and a global sort would defeat the reduced-
            # communication design.
            q = obj_p1 if objective == "quantile" else 0.5
            # percentile over the SAMPLED rows (w_it > 0) at their
            # pre-GOSS data weights (see w_renew above)
            w_sel = jnp.where(w_it > 0, w_renew, 0.0)
            w_q = (
                w_sel / jnp.maximum(1.0, jnp.abs(y_enc))
                if objective == "mape" else w_sel
            )
            renewed = objectives.leaf_quantile_renewal(
                grown.row_leaf, y_enc - scores, w_q, num_leaves, q
            ) * learning_rate
            grown = grown._replace(
                leaf_values=jnp.where(grown.leaf_counts > 0, renewed, 0.0)
            )
        grown_list.append(grown)
        deltas.append(grown.leaf_values[grown.row_leaf])
    new_scores = scores + (jnp.stack(deltas, axis=1) if k > 1 else deltas[0])
    return new_scores, grown_list


@functools.partial(
    jax.jit,
    static_argnames=(
        "objective", "k", "grad_pre", "is_goss", "has_cat",
        "num_leaves", "max_depth", "min_data_in_leaf", "top_k", "grower",
        "num_bins",
    ),
)
def _fused_iteration(
    bins: jnp.ndarray,
    scores: jnp.ndarray,
    y_enc: Optional[jnp.ndarray],
    w_it: jnp.ndarray,
    it_key: jnp.ndarray,
    fm: jnp.ndarray,
    cat_mask: Optional[jnp.ndarray],
    g_pre: Optional[jnp.ndarray],
    h_pre: Optional[jnp.ndarray],
    rank_idx: Optional[jnp.ndarray],
    rank_valid: Optional[jnp.ndarray],
    obj_p1: Any,
    top_rate: float,
    other_rate: float,
    lambda_l2: float,
    lambda_l1: float,
    min_sum_hessian: float,
    min_gain: float,
    learning_rate: float,
    *,
    objective: str,
    k: int,
    grad_pre: bool,
    is_goss: bool,
    has_cat: bool,
    num_leaves: int,
    max_depth: int,
    min_data_in_leaf: int,
    top_k: int,
    grower: treegrow.Grower,
    num_bins: int = NUM_BINS,
) -> tuple:
    """One whole boosting iteration as ONE XLA program — the dispatch-per-
    iteration path kept for the modes whose loop does host work between
    iterations (dart's tree mutation, lambdarank's host gradients,
    delegates, multihost's replicated reads). Everything else trains
    through :func:`_scan_chunk`, which fuses MANY iterations per dispatch.
    Returns (new_scores, tuple of GrownTree per class)."""
    new_scores, grown_list = _iteration_core(
        bins, scores, y_enc, w_it, it_key, fm, cat_mask, g_pre, h_pre,
        rank_idx, rank_valid,
        obj_p1, top_rate, other_rate, lambda_l2, lambda_l1, min_sum_hessian,
        min_gain, learning_rate,
        objective=objective, k=k, grad_pre=grad_pre, is_goss=is_goss,
        has_cat=has_cat, num_leaves=num_leaves,
        max_depth=max_depth, min_data_in_leaf=min_data_in_leaf,
        top_k=top_k, grower=grower, num_bins=num_bins,
    )
    return new_scores, tuple(grown_list)


# computed on device inside the scan so eval costs no extra host round
# trip (the host only reads the (C,) metric vector); all lower-is-better
# except auc/ndcg (see _HIGHER_METRICS)
_DEVICE_METRICS = (
    "binary_logloss", "binary_error", "multi_logloss", "auc",
) + objectives.REGRESSION_KINDS
_HIGHER_METRICS = ("ndcg", "auc")


def _device_metric(
    s: jnp.ndarray, y: jnp.ndarray, vw: jnp.ndarray, eval_kind: str,
    obj_p1: Any = 0.0,
) -> jnp.ndarray:
    """Masked-mean validation metric, formula-matched to :func:`_eval_metric`
    (same clips/logs so early-stopping decisions agree across paths)."""
    wsum = jnp.maximum(vw.sum(), 1.0)
    if eval_kind == "auc":
        return objectives.binary_auc_device(s, y, vw)
    if eval_kind == "binary_logloss":
        p = jnp.clip(jax.nn.sigmoid(s), 1e-15, 1 - 1e-15)
        loss = -(y * jnp.log(p) + (1.0 - y) * jnp.log(1.0 - p))
    elif eval_kind == "binary_error":
        p = jax.nn.sigmoid(s)
        loss = ((p > 0.5) != (y > 0.5)).astype(jnp.float32)
    elif eval_kind == "multi_logloss":
        p = jax.nn.softmax(s, axis=-1)
        picked = jnp.clip((p * y).sum(axis=-1), 1e-15, 1.0)
        loss = -jnp.log(picked)
    else:  # the regression-objective zoo's own pointwise loss
        loss = objectives.regression_loss(eval_kind, s, y, obj_p1, xp=jnp)
    return (loss * vw).sum() / wsum


# fields packed (in this order) into the one per-chunk host fetch;
# rec_catmask is appended only when the model has categorical splits
_PACK_FIELDS = (
    "rec_leaf", "rec_feature", "rec_bin", "rec_active", "rec_gain",
    "leaf_values", "leaf_counts", "rec_is_cat",
)


@compile_cache.stored_jit(  # a warm start loads it from the program store, untraced
    name="mmlspark_tpu.models.gbdt.train._scan_chunk",
    static_argnames=(
        "objective", "k", "grad_pre", "is_goss", "has_cat",
        "num_leaves", "max_depth", "min_data_in_leaf", "top_k", "grower",
        "bagging_freq", "eval_kind", "is_rf", "num_bins", "eval_k",
    ),
)
def _scan_chunk(
    bins: jnp.ndarray,
    scores0: jnp.ndarray,
    y_enc: Optional[jnp.ndarray],
    w_base: jnp.ndarray,
    bag0: jnp.ndarray,
    base_key: jnp.ndarray,
    it_idx: jnp.ndarray,          # (C,) int32 absolute iteration numbers
    fms: jnp.ndarray,             # (C, d) f32 feature-fraction masks
    cat_mask: Optional[jnp.ndarray],
    g_pre: Optional[jnp.ndarray],
    h_pre: Optional[jnp.ndarray],
    rank_idx: Optional[jnp.ndarray],
    rank_valid: Optional[jnp.ndarray],
    rank_idx_eval: Optional[jnp.ndarray],
    rank_valid_eval: Optional[jnp.ndarray],
    y_eval: Optional[jnp.ndarray],
    valid_w: Optional[jnp.ndarray],
    rf_base: Optional[jnp.ndarray],
    obj_p1: Any,
    bagging_fraction: float,
    top_rate: float,
    other_rate: float,
    lambda_l2: float,
    lambda_l1: float,
    min_sum_hessian: float,
    min_gain: float,
    learning_rate: float,
    *,
    objective: str,
    k: int,
    grad_pre: bool,
    is_goss: bool,
    has_cat: bool,
    num_leaves: int,
    max_depth: int,
    min_data_in_leaf: int,
    top_k: int,
    grower: treegrow.Grower,
    bagging_freq: int,
    eval_kind: str,
    is_rf: bool,
    num_bins: int = NUM_BINS,
    eval_k: int = 5,
) -> tuple:
    """C whole boosting iterations as ONE XLA program (``lax.scan`` over
    iterations). The per-iteration loop pays O(iterations) dispatches and
    fetches; this pays ONE dispatch per chunk, computes the eval metric on
    device, and packs every tree record of the chunk into a single f32
    buffer so the host does exactly one fetch.

    Returns (final_scores, final_bag, packed (C, k, W) f32, metrics (C,)).
    """
    L = num_leaves

    def body(carry: tuple, xs: tuple) -> tuple:
        scores, bag = carry
        it, fm = xs
        it_key = jax.random.fold_in(base_key, it)
        if bagging_freq > 0:
            u = jax.random.uniform(jax.random.fold_in(it_key, 1), bag.shape)
            newbag = (u < bagging_fraction).astype(jnp.float32)
            bag = jnp.where(it % bagging_freq == 0, newbag, bag)
            w_it = w_base * bag
        else:
            w_it = w_base
        new_scores, grown_list = _iteration_core(
            bins, scores, y_enc, w_it, it_key, fm, cat_mask, g_pre, h_pre,
            rank_idx, rank_valid,
            obj_p1, top_rate, other_rate, lambda_l2, lambda_l1,
            min_sum_hessian, min_gain, learning_rate,
            objective=objective, k=k, grad_pre=grad_pre, is_goss=is_goss,
            has_cat=has_cat, num_leaves=num_leaves,
            max_depth=max_depth, min_data_in_leaf=min_data_in_leaf,
            top_k=top_k, grower=grower, num_bins=num_bins,
        )
        recs = tuple(
            tuple(
                # counts split hi/lo so the f32 buffer stays exact past
                # 2^24 rows per leaf (a single f32 would round them)
                (getattr(g, f) // 4096, getattr(g, f) % 4096)
                if f == "leaf_counts"
                else (getattr(g, f),)
                for f in _PACK_FIELDS
            )
            for g in grown_list
        )
        recs = tuple(
            tuple(a for grp in r for a in grp)
            + ((jnp.zeros((4,), jnp.int32)
                if g.hist_rows is None else g.hist_rows),)
            + ((g.rec_catmask,) if has_cat else ())
            for r, g in zip(recs, grown_list)
        )
        if eval_kind == "none":
            m = jnp.float32(0.0)
        else:
            s_eval = new_scores
            if is_rf:
                s_eval = rf_base + new_scores / (it.astype(jnp.float32) + 1.0)
            if eval_kind == "ndcg":
                m = objectives.grouped_ndcg_device(
                    s_eval, y_eval, rank_idx_eval, rank_valid_eval, k=eval_k
                )
            else:
                m = _device_metric(s_eval, y_eval, valid_w, eval_kind, obj_p1)
        return (new_scores, bag), (recs, m)

    (scores, bag), (recs, metrics) = jax.lax.scan(
        body, (scores0, bag0), (it_idx, fms)
    )
    C = it_idx.shape[0]

    def flat(i: int, a: jnp.ndarray) -> jnp.ndarray:
        if has_cat and i == len(recs[0]) - 1:
            # categorical bitmask: 16 bools per f32 word (exact: < 2^16),
            # a 32x smaller fetch than one f32 per bool
            bits = a.reshape(C, -1, 16).astype(jnp.float32)
            return (bits * (2.0 ** jnp.arange(16, dtype=jnp.float32))).sum(-1)
        return a.astype(jnp.float32).reshape(C, -1)

    packed = jnp.stack(
        [
            jnp.concatenate(
                [flat(i, a) for i, a in enumerate(recs[c])], axis=1
            )
            for c in range(len(recs))
        ],
        axis=1,
    )  # (C, k, W)
    return scores, bag, packed, metrics


def _unpack_chunk_trees(
    packed: np.ndarray, keep: int, k: int, L: int, has_cat: bool,
    num_bins: int, mapper: BinMapper,
) -> tuple:
    """Split the chunk's packed f32 record buffer back into host Trees and
    the growers' (C, k, 4) hist_rows records."""
    widths = (
        [L - 1] * 5 + [L, L, L, L - 1, 4]
        + ([(L - 1) * num_bins // 16] if has_cat else [])
    )
    offs = np.cumsum([0] + widths)
    trees = []
    for i in range(keep):
        for c in range(k):
            row = packed[i, c]
            parts = [
                row[offs[j]: offs[j + 1]] for j in range(len(widths))
            ]
            counts = (
                parts[6].astype(np.int64) * 4096 + parts[7].astype(np.int64)
            )
            rec = {
                "rec_leaf": parts[0].astype(np.int32),
                "rec_feature": parts[1].astype(np.int32),
                "rec_bin": parts[2].astype(np.int32),
                "rec_active": parts[3] > 0.5,
                "rec_gain": parts[4].astype(np.float32),
                "leaf_values": parts[5].astype(np.float32),
                "leaf_counts": counts.astype(np.int32),
                "rec_is_cat": parts[8] > 0.5,
                "rec_catmask": (
                    (
                        (
                            parts[10].astype(np.int64)[:, None]
                            >> np.arange(16)
                        ) & 1
                    ).astype(bool).reshape(L - 1, num_bins)
                    if has_cat
                    else np.zeros((L - 1, num_bins), bool)
                ),
            }
            trees.append(_tree_from_host_records(rec, mapper))
    # the hist_rows of every tree of the chunk, kept or not: each made its
    # histogram passes
    return trees, packed[:, :, offs[9]: offs[10]]


@jax.jit
def _goss_weights(g_abs: jnp.ndarray, w: jnp.ndarray, u: jnp.ndarray,
                  top_rate: float, other_rate: float) -> jnp.ndarray:
    """One-side sampling weights on device: rows ranked by |g| among rows
    with nonzero base weight; top a kept at 1x, random b of the rest kept
    at (1-a)/b, remainder dropped."""
    eligible = w > 0
    n_eligible = jnp.maximum(eligible.sum(), 1)
    n_top = jnp.maximum((top_rate * n_eligible).astype(jnp.int32), 1)
    masked = jnp.where(eligible, g_abs, -jnp.inf)
    # value threshold for the top-a set (ties may admit a few extra rows;
    # LightGBM's exact-count selection differs by at most the tie set)
    srt = jnp.sort(masked)[::-1]
    thresh = srt[jnp.clip(n_top - 1, 0, masked.shape[0] - 1)]
    is_top = eligible & (masked >= thresh)
    # LightGBM draws b*n rows out of the (1-a)*n remainder — per-row
    # probability b/(1-a) — and amplifies by (1-a)/b, so each non-top row's
    # EXPECTED histogram weight is exactly 1 (unbiased)
    p_other = jnp.minimum(other_rate / jnp.maximum(1.0 - top_rate, 1e-12), 1.0)
    amp = (1.0 - top_rate) / jnp.maximum(other_rate, 1e-12)
    is_other = eligible & ~is_top & (u < p_other)
    return jnp.where(is_top, 1.0, jnp.where(is_other, amp, 0.0)).astype(jnp.float32)


def train(
    x: np.ndarray,
    y: np.ndarray,
    cfg: TrainConfig,
    sample_weight: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    valid_mask: Optional[np.ndarray] = None,
    group_ids: Optional[np.ndarray] = None,
    init_booster: Optional[Booster] = None,
    base_score: Any = 0.0,
    shard: bool = True,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 10,
    resume_from: Optional[str] = None,
    fused_rounds: int = 0,
) -> Booster:
    """Fit a booster on dense (n, d) features or a CSR triple.

    ``x`` may be a scipy-style CSR matrix (anything with ``data``/
    ``indices``/``indptr``/``shape``); binning then runs per-column over the
    stored values only (LightGBMUtils.scala:211-265 builds native datasets
    from dense or sparse rows the same way).

    ``base_score``: boost_from_average baseline (scalar, or (k,) for
    multiclass) — added to the initial scores AND stored on the booster so
    prediction replays it.

    ``fused_rounds``: scan-fused chunk control — 0 (default) sizes chunks
    automatically (the whole run without early stopping, bounded chunks
    with it), 1 forces the legacy one-dispatch-per-round loop (kept as
    the debugging/fallback path; bit-identical results), N > 1 caps the
    chunk at N rounds. Chunk size never changes the trained model — only
    how many XLA dispatches the loop costs (O(rounds / N) vs O(rounds)).

    Preemption safety (models/gbdt/checkpoint.py): ``checkpoint_dir``
    serializes trees + device score/bag state + host RNG every
    ``checkpoint_every`` rounds; ``resume_from`` continues from the last
    complete checkpoint and reproduces the uninterrupted run bit-for-bit
    (same config fingerprint enforced). Passing the same directory for
    both gives crash-loop-safe auto-resume. Single-process only."""
    if cfg.boosting_type not in BOOSTING_TYPES:
        raise ValueError(f"boosting_type must be one of {BOOSTING_TYPES}")
    canon = objectives.canonical_objective(cfg.objective)
    if canon not in ("binary", "multiclass", "lambdarank") + objectives.REGRESSION_KINDS:
        raise ValueError(f"unknown objective {cfg.objective!r}")
    if canon != cfg.objective:
        cfg = _dc_replace(cfg, objective=canon)
    if canon in objectives.LOG_LINK_KINDS and np.any(np.asarray(y) < 0):
        # log-link objectives model a nonnegative mean; LightGBM errors too
        raise ValueError(f"objective {canon!r} requires non-negative labels")
    if cfg.growth_policy not in ("lossguide", "depthwise"):
        raise ValueError(
            f"growth_policy must be 'lossguide' or 'depthwise', got {cfg.growth_policy!r}"
        )
    if cfg.growth_policy == "depthwise" and cfg.parallelism == "voting_parallel":
        # the voting grower is leaf-wise; silently dropping an explicit
        # depthwise request would benchmark/deploy the wrong policy
        raise ValueError("growth_policy='depthwise' is incompatible with voting_parallel")
    if cfg.boosting_type == "goss" and cfg.top_rate + cfg.other_rate > 1.0:
        # LightGBM hard-errors here too: the sampler's unbiasedness
        # guarantee needs b/(1-a) <= 1
        raise ValueError("goss requires top_rate + other_rate <= 1")
    from mmlspark_tpu.models.gbdt.binning import BinnedDataset, is_sparse

    pre_binned = isinstance(x, BinnedDataset)
    sparse_input = False if pre_binned else is_sparse(x)
    if pre_binned:
        # the out-of-core path: rows were binned chunk-by-chunk against
        # a mapper fitted from streaming sketches — everything that
        # would need the FLOAT matrix back is out of contract here
        if cfg.boosting_type == "dart":
            raise ValueError(
                "pre-binned input does not support dart (dropped-tree "
                "re-prediction needs the float matrix)"
            )
        if init_booster is not None and init_booster.trees:
            raise ValueError(
                "pre-binned input does not support init_booster "
                "(warm-start scoring needs the float matrix)"
            )
        if cfg.categorical_features:
            raise ValueError(
                "pre-binned input does not support categorical_features "
                "(identity binning is a fit-time decision)"
            )
        if x.mapper.max_bin > cfg.max_bin:
            # hist_bins is sized from cfg.max_bin: a code past it would
            # scatter into the wrong plane and train a silently wrong
            # model — refuse instead
            raise ValueError(
                f"pre-binned input was quantized with max_bin="
                f"{x.mapper.max_bin} but cfg.max_bin={cfg.max_bin}; "
                "bin codes would overflow the histogram space"
            )
    n, d = x.shape
    # np.matrix-shaped labels (scipy .sum(axis=) results) flatten silently
    y = np.asarray(y).reshape(n)
    k = cfg.num_class if cfg.objective == "multiclass" else 1
    cat_features = tuple(int(f) for f in (cfg.categorical_features or ()))

    # multi-host: every process calls train() with ITS OWN rows; the jitted
    # grower then runs SPMD over the process-spanning mesh and XLA carries
    # the histogram allreduce over DCN (the reference's per-machine dataset
    # build + socket allreduce, TrainUtils.scala:26-66,496-512)
    multihost = shard and jax.process_count() > 1
    # elastic gang training (parallel/elastic.py): each member trains its
    # contiguous partition rows UNSHARDED; the host growers' histograms
    # are summed across members by the gang's TCP allreduce, so every
    # member grows the identical tree. Checkpoints gather/scatter global
    # row state so a resume at a different world size is well-defined.
    from mmlspark_tpu.parallel import elastic as _elastic

    gang = _elastic.active_gang()
    if gang is not None:
        if shard or multihost:
            raise ValueError(
                "elastic gang training requires shard=False (members "
                "train their partition rows unsharded; the gang "
                "allreduce crosses hosts)"
            )
        if sparse_input:
            raise ValueError(
                "elastic gang training requires dense input (the global "
                "bin-bound gather is dense)"
            )
        if valid_mask is not None and np.any(valid_mask):
            raise ValueError(
                "elastic gang training does not support validation/"
                "early stopping (the eval metric would be member-local)"
            )
    # lambdarank across processes: each process computes its own groups'
    # pairwise gradients on host — a query group must live ENTIRELY on one
    # process (the reference has the same contract: LightGBMRanker requires
    # a query's rows on a single partition, LightGBMRanker.scala).
    # voting_parallel across processes: the shard_map grower's psums simply
    # ride DCN instead of ICI — same program, bigger mesh.

    if pre_binned:
        if multihost:
            raise ValueError(
                "pre-binned input is single-process / elastic-gang only"
            )
        mapper = x.mapper
    elif multihost:
        # bin bounds must be IDENTICAL on every process: fit the mapper on
        # a NaN-padded sample allgathered from all processes (NaN rows are
        # ignored by quantile fitting; for sparse inputs absent entries
        # densify to NaN, matching the missing-bin transform semantics)
        import jax.experimental.multihost_utils as mhu

        # FIXED buffer size (process-count-based only): processes may hold
        # unequal row counts, and allgather needs identical shapes — short
        # processes leave NaN rows, which quantile fitting ignores
        k_s = max(1, 50_000 // jax.process_count())
        samp = np.full((k_s, d), np.nan, np.float32)
        take = np.random.default_rng(cfg.seed).choice(
            n, min(n, k_s), replace=False
        )
        samp[: len(take)] = (
            _densify(x[take]) if sparse_input else np.asarray(x[take], np.float32)
        )
        if cat_features:
            if sparse_input:
                # match the single-host BinMapper error exactly — the
                # sample-densified path must not silently accept what one
                # process would reject
                raise ValueError(
                    "categorical features require dense input (sparse "
                    "columns have no stable category<->bin identity for "
                    "absent entries)"
                )
            # categorical hi must cover every category present ANYWHERE,
            # not just in the capped sample: allgather full-column extrema
            # (also makes the range validation a globally identical
            # decision — a raise on one process only would desync SPMD)
            ext = np.zeros((len(cat_features), 2), np.float64)
            for j, f in enumerate(cat_features):
                col = np.asarray(x[:, f], np.float64)
                col = col[~np.isnan(col)]
                ext[j] = (col.min(), col.max()) if len(col) else (0.0, 0.0)
            gext = np.asarray(mhu.process_allgather(ext))
            gmin = gext[..., 0].min(axis=0)
            gmax = gext[..., 1].max(axis=0)
            bad = np.flatnonzero((gmin < 0) | (gmax > cfg.max_bin - 2))
            if len(bad):
                raise ValueError(
                    f"categorical features {[cat_features[b] for b in bad]} "
                    f"have values outside [0, {cfg.max_bin - 2}] — "
                    "re-index categories first"
                )
            # plant the global max into this process's sample so the
            # fitted identity range covers the unsampled tail everywhere
            for j, f in enumerate(cat_features):
                samp[0, f] = gmax[j]
        global_sample = np.asarray(mhu.process_allgather(samp)).reshape(-1, d)
        with obs.span("gbdt.bin_fit"):
            mapper = BinMapper.fit(
                global_sample, max_bin=cfg.max_bin, seed=cfg.seed,
                categorical_features=cat_features,
            )
    elif gang is not None:
        # bin bounds must be identical on every gang member AND invariant
        # across world sizes (a resumed shrunk-world run must interpret
        # bins exactly like a fresh run from the same checkpoint): fit on
        # the gang-gathered GLOBAL rows, not this member's slice
        with obs.span("gbdt.bin_fit"):
            mapper = BinMapper.fit(
                gang.binning_rows(np.asarray(x, np.float32)),
                max_bin=cfg.max_bin, seed=cfg.seed,
                categorical_features=cat_features,
            )
    else:
        with obs.span("gbdt.bin_fit"):
            mapper = BinMapper.fit(
                x, max_bin=cfg.max_bin, seed=cfg.seed,
                categorical_features=cat_features,
            )
    if pre_binned:
        bins_host = x.bins
    else:
        with obs.span("gbdt.bin_transform", attrs={"cells": int(n) * int(d)}):
            bins_host = mapper.transform(x)
    # histogram bin space: the smallest MXU-tile-aligned width covering
    # every bin code (codes live in [0, max_bin-1]). At the default
    # max_bin=255 this is the full uint8 space (256); smaller max_bin
    # shrinks the one-hot compare loop — the VPU-bound part of the Pallas
    # kernel — nearly proportionally. 16-aligned: bf16 sublane tile.
    hist_bins = max(16, ((cfg.max_bin + 15) // 16) * 16)
    cat_mask_dev = None
    if cat_features:
        cat_mask_host = np.zeros(d, bool)
        cat_mask_host[list(cat_features)] = True
        cat_mask_dev = jnp.asarray(cat_mask_host)

    train_mask = (
        ~valid_mask if valid_mask is not None else np.ones(n, bool)
    )
    w = sample_weight if sample_weight is not None else np.ones(n, np.float32)
    w = np.where(train_mask, w, 0.0).astype(np.float32)

    bagging_fraction = cfg.bagging_fraction
    bagging_freq = cfg.bagging_freq
    if cfg.boosting_type == "rf" and not (bagging_freq > 0 and bagging_fraction < 1.0):
        # rf without bagging would grow the same tree every round; LightGBM
        # hard-errors here, we default to the classic 0.632 bootstrap rate
        log.info("rf boosting without bagging params: defaulting to bagging_fraction=0.632, bagging_freq=1")
        bagging_fraction, bagging_freq = 0.632, 1
    if cfg.boosting_type == "goss" and bagging_freq > 0:
        log.info("goss boosting: bagging disabled (GOSS is the row sampler)")
        bagging_freq = 0

    # device placement: rows sharded over the data axis when a mesh exists
    mesh = None
    with obs.span("gbdt.upload", attrs={
        "what": "bins,weights", "bytes": int(bins_host.nbytes + w.nbytes),
    }):
        if multihost:
            from mmlspark_tpu.parallel.mesh import get_mesh
            from mmlspark_tpu.parallel.sharding import (
                multihost_pad_target,
                shard_batch_multihost,
            )

            mesh = get_mesh()
            share = multihost_pad_target(n)  # equal local block per process
            pad = share - n
            bins_dev = shard_batch_multihost(
                np.pad(bins_host, ((0, pad), (0, 0))), mesh
            )
            w_dev = shard_batch_multihost(np.pad(w, (0, pad)), mesh)
            n_pad = share * jax.process_count()  # GLOBAL padded row count
        elif shard:
            from mmlspark_tpu.parallel.mesh import get_mesh
            from mmlspark_tpu.parallel.sharding import pad_batch, shard_batch

            mesh = get_mesh()
            n_dev = mesh.devices.size
            bins_p, n_real = pad_batch(bins_host, n_dev)
            pad = bins_p.shape[0] - n
            bins_dev = shard_batch(bins_p, mesh)
            w_dev = shard_batch(np.pad(w, (0, pad)), mesh)
            n_pad = n + pad
        else:
            pad = 0
            bins_dev = jnp.asarray(bins_host)
            w_dev = jnp.asarray(w)
            n_pad = n

    def padded(a: np.ndarray) -> jnp.ndarray:
        if pad:
            a = np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        if multihost:
            from mmlspark_tpu.parallel.sharding import shard_batch_multihost

            return shard_batch_multihost(a, mesh)
        if shard:
            from mmlspark_tpu.parallel.sharding import shard_batch

            return shard_batch(a)
        return jnp.asarray(a)

    # which grower the trees come from (treegrow.choose_grower: the growth
    # policy, voting, the mesh and its device's histogram lowering).
    # Decided here, once, before a round program is traced: the value is
    # that program's one static argument for it
    grower = treegrow.choose_grower(
        cfg.growth_policy,
        voting=cfg.parallelism == "voting_parallel",
        mesh=mesh,
        shard_axis=_DATA_AXIS,
    )

    # -- device-resident loop state -----------------------------------------
    # scores, labels and per-iteration gradients stay sharded on device for
    # the whole loop; the host receives only split records + eval scalars.
    with obs.span("gbdt.upload", attrs={"what": "labels,scores"}) as _up:
        if k > 1:
            scores0 = np.zeros((n, k), np.float32)
            y_onehot_dev = padded(np.eye(k, dtype=np.float32)[y.astype(np.int64)])
        else:
            scores0 = np.zeros(n, np.float32)
            y_dev = padded(y.astype(np.float32))
        scores0 = scores0 + np.asarray(base_score, np.float32)
        if init_score is not None:
            scores0 = scores0 + init_score.astype(scores0.dtype)
        if init_booster is not None and init_booster.trees:
            # score with ALL trees (not the best_iteration prefix predict_raw
            # would default to): merge() replays every init tree, so residuals
            # must be fit against exactly that
            all_iters = len(init_booster.trees) // init_booster.num_class
            prev = init_booster.predict_raw(
                _densify(x) if sparse_input else x, num_iteration=all_iters
            )
            scores0 = scores0 + prev.astype(scores0.dtype)
        scores = padded(scores0)
        _up.set_attr("bytes", int(scores0.nbytes) * (1 + max(k, 1)))

    is_rf = cfg.boosting_type == "rf"
    is_dart = cfg.boosting_type == "dart"
    is_goss = cfg.boosting_type == "goss"
    early_stopping_round = cfg.early_stopping_round
    if is_dart and early_stopping_round > 0:
        # dropout keeps rescaling trees INSIDE any recorded best-iteration
        # prefix, so the prefix can't reproduce the scores that won —
        # LightGBM hard-errors on this combination, we disable with a note
        log.info("early stopping is not available in dart mode; disabled")
        early_stopping_round = 0
    if is_rf:
        # constant gradients at the initial score; `scores` becomes the
        # running SUM of tree contributions (averaged for eval/predict)
        rf_base = scores
        scores = padded(np.zeros_like(scores0))
        if cfg.objective == "binary":
            g_rf, h_rf = objectives.binary_grad_hess(rf_base, y_dev)
        elif cfg.objective == "multiclass":
            g_rf, h_rf = objectives.multiclass_grad_hess(rf_base, y_onehot_dev)
        elif cfg.objective == "lambdarank":
            g_np, h_np = objectives.lambdarank_grad_hess(
                scores0.astype(np.float64), y.astype(np.float64), group_ids
            )
            g_rf, h_rf = padded(g_np.astype(np.float32)), padded(h_np.astype(np.float32))
        else:
            g_rf, h_rf = objectives.regression_grad_hess(
                cfg.objective, rf_base, y_dev,
                jnp.float32(_objective_p1(cfg)),
            )

    rng = np.random.default_rng(cfg.seed)
    base_key = jax.random.PRNGKey(cfg.seed)
    # per-iteration random masks and the small split-record reads must be
    # REPLICATED arrays under multihost (a bare jax.random.uniform commits
    # to process-local devices, incompatible with cross-process-sharded
    # operands); both jits are hoisted here so the cache hits every round
    if multihost:
        _rep_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()
        )
        _uniform_global = jax.jit(
            lambda key: jax.random.uniform(key, (n_pad,)),
            out_shardings=_rep_sharding,
        )
        _replicate_small = jax.jit(lambda t: t, out_shardings=_rep_sharding)
    else:
        def _uniform_global(key: Any) -> jnp.ndarray:
            return jax.random.uniform(key, (n_pad,))
    booster = Booster(
        trees=[], objective=cfg.objective, num_class=k, num_features=d,
        base_score=base_score, boosting_type=cfg.boosting_type,
        objective_param=(
            _objective_p1(cfg)
            if cfg.objective in ("quantile", "huber", "fair", "tweedie")
            else None
        ),
    )
    pending_trees: list = []  # device-grown records, materialized after the loop
    x_host_dense: Optional[np.ndarray] = None  # dart re-predicts dropped trees

    best_val = None
    best_iter = -1
    rounds_no_improve = 0
    bag = None
    mh_eval_ctx = None  # lazily gathered (y, valid) global eval arrays

    delegate = cfg.delegate
    lr_cur = float(cfg.learning_rate)

    # -- preemption-safe checkpoint/resume -----------------------------------
    # round-level state capture: trees so far, device scores/bag (exact f32
    # through the host round-trip), the host rng stream, early-stop counters.
    # Resume restores all of it, so the continued run replays the identical
    # iteration-by-iteration computation (chaos suite asserts bit-identity).
    start_round = 0
    resume_bag: Optional[np.ndarray] = None
    _ckpt_fp = None
    checkpoint_every = max(1, int(checkpoint_every))
    if checkpoint_dir or resume_from:
        if multihost:
            raise ValueError(
                "GBDT checkpoint/resume is single-process only (multihost "
                "runs re-rendezvous via jax.distributed instead)"
            )
        from mmlspark_tpu.models.gbdt.checkpoint import (
            TrainCheckpoint,
            config_fingerprint,
            load_checkpoint,
            save_checkpoint,
        )

        # elastic gang: fingerprint the GLOBAL dataset shape — the same
        # run re-sharded over a different world is still the same run
        _ckpt_fp = config_fingerprint(
            cfg, gang.global_n if gang is not None else n, d, k
        )
    if resume_from:
        _rck = load_checkpoint(resume_from)
        if _rck is not None:
            if _rck.fingerprint != _ckpt_fp:
                raise ValueError(
                    f"checkpoint at {resume_from!r} was written by a "
                    "different training configuration or dataset shape — "
                    "refusing to resume (fingerprint mismatch)"
                )
            start_round = _rck.round
            _res_scores = np.asarray(_rck.scores, np.float32)
            if gang is not None:
                # the checkpoint holds GLOBAL row state in global row
                # order: take this member's contiguous slice (which may
                # differ from the slice the checkpoint was written under
                # — that is exactly what a reshard is)
                _res_scores = np.asarray(gang.take_local(_res_scores))
            scores = padded(_res_scores.reshape(scores0.shape))
            resume_bag = _rck.bag
            if gang is not None and resume_bag is not None:
                resume_bag = np.asarray(gang.take_local(resume_bag))
            if resume_bag is not None:
                # the dispatch-per-iteration loop's bagging carry; the
                # fast path re-pads resume_bag into its own scan carry
                bag = padded(np.asarray(resume_bag, np.float32))
            rng.bit_generator.state = _rck.rng_state
            best_val = _rck.best_val
            best_iter = _rck.best_iter
            rounds_no_improve = _rck.rounds_no_improve
            lr_cur = _rck.lr
            booster.trees = list(_rck.booster.trees)
            booster.best_iteration = _rck.booster.best_iteration
            log.info("resuming GBDT training from round %d", start_round)

    def _save_ckpt(next_round: int, bag_state: Any) -> None:
        """Persist state as of entering ``next_round`` (reads the CURRENT
        loop locals — call only at a completed round boundary)."""
        scores_arr = np.asarray(scores)[:n]
        bag_arr = (
            np.asarray(bag_state)[:n] if bag_state is not None else None
        )
        if gang is not None:
            # collective: EVERY member gathers global row state (scatter
            # + allreduce keeps the gang in lockstep), but only the
            # generation coordinator writes the shared checkpoint dir
            scores_arr = gang.all_rows(scores_arr)
            if bag_arr is not None:
                bag_arr = gang.all_rows(bag_arr)
            if not gang.is_writer:
                return
        save_checkpoint(
            checkpoint_dir,
            TrainCheckpoint(
                round=next_round,
                booster=booster,
                scores=scores_arr,
                bag=bag_arr,
                rng_state=rng.bit_generator.state,
                fingerprint=_ckpt_fp,
                best_val=best_val,
                best_iter=best_iter,
                rounds_no_improve=rounds_no_improve,
                lr=lr_cur,
            ),
        )

    # -- scan-fused fast path ------------------------------------------------
    # Everything whose loop needs no host work between iterations trains as
    # chunked lax.scan programs: ONE dispatch (and one packed record fetch)
    # per chunk instead of one per iteration. Excluded: dart (mutates past
    # trees on host), delegates (host callbacks), multihost (replicated
    # small-read choreography), and lambdarank only when its groups are
    # non-contiguous or too large for the padded device kernel (rank_fast
    # above). Eval metrics all run on device now (incl. the searchsorted
    # rank-statistic AUC), so no metric forces the host loop.
    rank_fast = False
    rank_pads = None
    if cfg.objective == "lambdarank" and not multihost and group_ids is not None:
        gids = np.asarray(group_ids)
        runs = 1 + int((gids[1:] != gids[:-1]).sum()) if len(gids) else 0
        # non-contiguous group ids would change grouping semantics — the
        # host path handles those, so don't even build the pad grid
        if runs == len(np.unique(gids)):
            pi, va = objectives.lambdarank_pad_groups(group_ids)
            # padded pairwise tensors are (G, M, M): bound device memory (a
            # few hundred MB) or keep the host-gradient path
            if pi.shape[0] * pi.shape[1] * pi.shape[1] <= (1 << 26):
                rank_fast = True
                rank_pads = (pi, va)
    fast = (
        int(fused_rounds) != 1
        and delegate is None and not multihost and not is_dart
        and (cfg.objective != "lambdarank" or rank_fast)
    )
    eval_needed = valid_mask is not None and bool(np.any(valid_mask))
    eval_kind = "none"
    eval_k = cfg.eval_at
    if eval_needed:
        if cfg.objective == "binary":
            eval_kind = (
                "binary_logloss" if cfg.metric in ("", "binary_logloss")
                else "auc" if cfg.metric == "auc" else "binary_error"
            )
        elif cfg.objective == "multiclass":
            eval_kind = "multi_logloss"
        elif cfg.objective == "lambdarank":
            eval_kind = "ndcg"
            if cfg.metric.startswith("ndcg@"):
                eval_k = int(cfg.metric.split("@", 1)[1])
        else:
            eval_kind = cfg.objective
        if eval_kind not in _DEVICE_METRICS and not (
            eval_kind == "ndcg" and rank_fast
        ):
            fast = False

    if fast:
        eval_on = eval_kind != "none"
        use_bag = bagging_freq > 0 and bagging_fraction < 1.0
        # without early stopping the whole run is ONE chunk; with it, chunk
        # so overshoot past the stopping point is bounded (surplus trees
        # are computed then discarded — stopping decisions replay the (C,)
        # device metric vector and match the sequential path exactly)
        C_full = (
            cfg.num_iterations if early_stopping_round == 0
            else min(cfg.num_iterations, max(16, early_stopping_round))
        )
        if int(fused_rounds) > 1:
            C_full = max(1, min(C_full, int(fused_rounds)))
        if checkpoint_dir:
            # chunk boundaries ARE the checkpoint (and fault-injection)
            # boundaries; align them so every checkpoint lands exactly
            # every checkpoint_every rounds
            C_full = max(1, min(C_full, checkpoint_every))
        bag_dev = jnp.ones_like(w_dev)
        if resume_bag is not None:
            bag_dev = padded(np.asarray(resume_bag, np.float32))
        y_eval = valid_w = rf_base_dev = None
        rank_idx_dev = rank_valid_dev = None
        rank_idx_eval_dev = rank_valid_eval_dev = None
        if rank_fast:
            pi, va = rank_pads
            rank_idx_dev = jnp.asarray(pi)
            rank_valid_dev = jnp.asarray(va)
        if eval_on:
            y_eval = y_onehot_dev if k > 1 else y_dev
            valid_w = padded(valid_mask.astype(np.float32))
            if eval_kind == "ndcg":
                pi, va = objectives.lambdarank_pad_groups(
                    group_ids, keep=valid_mask
                )
                rank_idx_eval_dev = jnp.asarray(pi)
                rank_valid_eval_dev = jnp.asarray(va)
        grad_pre_f = is_rf
        if is_rf:
            g_pre_f, h_pre_f = g_rf, h_rf
            rf_base_dev = rf_base if eval_on else None
        else:
            g_pre_f = h_pre_f = None
        # lambdarank: y_dev is the relevance vector the device kernel reads
        y_enc_f = None if grad_pre_f else (y_onehot_dev if k > 1 else y_dev)
        it0 = start_round
        stopped = False
        while it0 < cfg.num_iterations and not stopped:
            # preemption fires BETWEEN rounds: state through round it0-1 is
            # checkpointed, rounds >= it0 have not run
            faults.inject("gbdt.round", step=it0)
            if gang is not None:
                # elastic gang boundary: straggler EWMA, loss detection,
                # grow-back — raises to abort when the world changed
                gang.on_round(it0)
            with obs.span("gbdt.chunk") as chunk_sp:
                with obs.span("gbdt.chunk.dispatch"):
                    C = min(C_full, cfg.num_iterations - it0)
                    if cfg.feature_fraction < 1.0:
                        fms = np.empty((C, d), np.float32)
                        for i in range(C):
                            fm = (rng.random(d) < cfg.feature_fraction).astype(np.float32)
                            if fm.sum() == 0:
                                fm[rng.integers(d)] = 1.0
                            fms[i] = fm
                    else:
                        fms = np.ones((C, d), np.float32)
                    scores, bag_dev, packed, metrics = _scan_chunk(
                        bins_dev, scores, y_enc_f, w_dev, bag_dev, base_key,
                        jnp.arange(it0, it0 + C, dtype=jnp.int32), jnp.asarray(fms),
                        cat_mask_dev, g_pre_f, h_pre_f,
                        rank_idx_dev, rank_valid_dev,
                        rank_idx_eval_dev, rank_valid_eval_dev,
                        y_eval, valid_w, rf_base_dev,
                        float(_objective_p1(cfg)),
                        float(bagging_fraction),
                        float(cfg.top_rate), float(cfg.other_rate),
                        float(cfg.lambda_l2), float(cfg.lambda_l1),
                        float(cfg.min_sum_hessian_in_leaf),
                        float(cfg.min_gain_to_split),
                        1.0 if is_rf else lr_cur,
                        objective=cfg.objective, k=k, grad_pre=grad_pre_f,
                        is_goss=is_goss,
                        has_cat=cat_mask_dev is not None,
                        num_leaves=int(cfg.num_leaves), max_depth=int(cfg.max_depth),
                        min_data_in_leaf=int(cfg.min_data_in_leaf),
                        top_k=int(cfg.top_k), grower=grower,
                        bagging_freq=int(bagging_freq) if use_bag else 0,
                        eval_kind=eval_kind, is_rf=is_rf, num_bins=hist_bins,
                        eval_k=int(eval_k),
                    )
                # the one blocking fetch of the chunk: the packed tree
                # records (and the (C,) eval metrics with them)
                with obs.span("gbdt.chunk.wait"):
                    mvals = np.asarray(metrics) if eval_on else None
                    packed_host = np.asarray(packed)
                keep = C
                if eval_on:
                    higher = eval_kind in _HIGHER_METRICS
                    for i in range(C):
                        val = float(mvals[i])
                        if cfg.verbosity > 0:
                            log.info("iter %d %s=%.6f", it0 + i, eval_kind, val)
                        if best_val is None or (
                            val > best_val if higher else val < best_val
                        ):
                            best_val, best_iter = val, it0 + i + 1
                            rounds_no_improve = 0
                        else:
                            rounds_no_improve += 1
                            if (
                                early_stopping_round > 0
                                and rounds_no_improve >= early_stopping_round
                            ):
                                log.info(
                                    "early stop at iter %d (best %d)",
                                    it0 + i, best_iter,
                                )
                                booster.best_iteration = best_iter
                                stopped = True
                                keep = i + 1
                                break
                with obs.span("gbdt.chunk.unpack") as unpack_sp:
                    trees, hist_rows = _unpack_chunk_trees(
                        packed_host, keep, k, int(cfg.num_leaves),
                        cat_mask_dev is not None, hist_bins, mapper,
                    )
                    booster.trees.extend(trees)
                    streamed, selected = _count_hist_rows(hist_rows)
                    unpack_sp.set_attr("hist_rows_streamed", streamed)
                    unpack_sp.set_attr("hist_rows_selected", selected)
                chunk_sp.set_attr("rounds", int(C))
            chunk_s = chunk_sp.duration_s
            _M_CHUNK_SECONDS.observe(chunk_s)
            _M_FUSED_CHUNKS.inc()
            if eval_on:
                _M_DEVICE_EVAL_ROUNDS.inc(keep)
            _M_ROUNDS.inc(keep)
            # one observation per completed round at the amortized cost —
            # sum and count stay exact for scrape-side mean/rate math
            per_round = chunk_s / max(keep, 1)
            for _ in range(keep):
                _M_ROUND_SECONDS.observe(per_round)
            it0 += C
            # checkpoint at the configured cadence: snapshot whenever this
            # chunk crossed a checkpoint_every boundary (chunk sizes that
            # do not divide the cadence still checkpoint at the first
            # boundary after each cadence point, never skip one)
            if (
                checkpoint_dir and not stopped
                and ((it0 - C) // checkpoint_every < it0 // checkpoint_every
                     or it0 >= cfg.num_iterations)
            ):
                _save_ckpt(it0, bag_dev if use_bag else None)

    # dispatch-per-iteration path (dart / lambdarank / multihost /
    # delegates / host-only eval metrics)
    for it in (range(0) if fast else range(start_round, cfg.num_iterations)):
        faults.inject("gbdt.round", step=it)
        if gang is not None:
            gang.on_round(it)
        t_round_ns = _time.perf_counter_ns()
        if delegate is not None:
            delegate.before_train_iteration(it)
            # dynamic learning rate (getLearningRate delegate semantics);
            # lr is a dynamic jit arg, so no recompile on change
            lr_cur = float(delegate.get_learning_rate(it, lr_cur))
        it_key = jax.random.fold_in(base_key, it)
        # bagging for this iteration (device mask, no host transfer)
        if bagging_freq > 0 and bagging_fraction < 1.0:
            if it % bagging_freq == 0 or bag is None:
                bag = (
                    _uniform_global(jax.random.fold_in(it_key, 1))
                    < bagging_fraction
                ).astype(jnp.float32)
        else:
            bag = None
        w_it = w_dev * bag if bag is not None else w_dev
        if cfg.feature_fraction < 1.0:
            fm = (rng.random(d) < cfg.feature_fraction).astype(np.float32)
            if fm.sum() == 0:
                fm[rng.integers(d)] = 1.0
        else:
            fm = np.ones(d, np.float32)
        fm_dev = jnp.asarray(fm)

        # dart: choose dropped iterations, fit against scores without them
        drop_set: list = []
        drop_contrib = None
        eff_scores = scores
        if is_dart and it > 0 and rng.random() >= cfg.skip_drop:
            sel = np.flatnonzero(rng.random(it) < cfg.drop_rate)
            if len(sel) > cfg.max_drop:
                sel = rng.choice(sel, cfg.max_drop, replace=False)
            drop_set = [int(s) for s in sel]
        if drop_set:
            if x_host_dense is None:
                x_host_dense = _densify(x) if sparse_input else np.asarray(x, np.float32)
            drop_contrib = _iterations_contrib(booster, x_host_dense, drop_set, k)
            eff_scores = scores - padded(drop_contrib)

        # dart normalization factors (paper semantics: new tree 1/(k+1),
        # dropped trees k/(k+1))
        n_drop = len(drop_set)
        nf_new = 1.0 / (n_drop + 1) if is_dart else 1.0
        nf_drop = n_drop / (n_drop + 1) if n_drop else 1.0

        # precomputed gradients: rf (constant at the initial score) and
        # lambdarank's group-sorted host path; everything else is computed
        # inside the fused program from the running scores
        g_pre = h_pre = None
        if is_rf:
            g_pre, h_pre = g_rf, h_rf
        elif cfg.objective == "lambdarank":
            # multihost: this process's score block only — its groups are
            # process-local by contract, so the pairwise grads are exact
            s_host = (
                _local_block_rows(eff_scores, n)
                if multihost else np.asarray(eff_scores)[:n]
            )
            g_np, h_np = objectives.lambdarank_grad_hess(
                s_host.astype(np.float64), y.astype(np.float64), group_ids
            )
            g_pre, h_pre = padded(g_np.astype(np.float32)), padded(h_np.astype(np.float32))
        grad_pre = g_pre is not None
        y_enc = None if grad_pre else (y_onehot_dev if k > 1 else y_dev)
        new_scores, grown_all = _fused_iteration(
            bins_dev, eff_scores, y_enc, w_it, it_key, fm_dev, cat_mask_dev,
            g_pre, h_pre, None, None,
            float(_objective_p1(cfg)),
            float(cfg.top_rate), float(cfg.other_rate),
            float(cfg.lambda_l2), float(cfg.lambda_l1),
            float(cfg.min_sum_hessian_in_leaf), float(cfg.min_gain_to_split),
            1.0 if is_rf else lr_cur,
            objective=cfg.objective, k=k, grad_pre=grad_pre, is_goss=is_goss,
            has_cat=cat_mask_dev is not None,
            num_leaves=int(cfg.num_leaves), max_depth=int(cfg.max_depth),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            top_k=int(cfg.top_k), grower=grower, num_bins=hist_bins,
        )
        # the fused step fit against eff_scores (dart: scores minus dropped
        # trees); the running total keeps the dropped contribution
        scores = (scores - eff_scores) + new_scores if drop_set else new_scores
        if is_dart and nf_new != 1.0:
            # the fused delta was unscaled; the stored tree shrinks by
            # nf_new, so fold the same factor into the running scores
            corr = [g.leaf_values[g.row_leaf] * (nf_new - 1.0) for g in grown_all]
            scores = scores + (jnp.stack(corr, axis=1) if k > 1 else corr[0])
        for grown in grown_all:
            if multihost:
                # the small split-record outputs must be fully replicated so
                # every process can read them to host (row_leaf stays
                # sharded — it is only ever consumed on device)
                grown = grown._replace(
                    **{
                        f: _replicate_small(getattr(grown, f))
                        for f in grown._fields
                        if f != "row_leaf"
                    }
                )
            if is_dart:
                # dart mutates PAST trees' values mid-loop, so it needs
                # host-materialized trees as it goes (eager, per-tree fetch)
                booster.trees.append(
                    _tree_from_device(grown, mapper, value_scale=nf_new)
                )
            else:
                # deferred materialization: split records stay on device;
                # the host fetch happens ONCE, batched, after the loop.
                # row_leaf (an (n,)-sized device buffer) is dropped here —
                # keeping it pinned per pending tree would hold
                # O(n_rows x num_iterations) accelerator memory
                pending_trees.append(grown._replace(row_leaf=None))
        if drop_set:
            # dropped trees shrink to k/(k+1): mutate their stored values
            # and fold the same correction into the running scores
            for itdrop in drop_set:
                for c in range(k):
                    t = booster.trees[itdrop * k + c]
                    t.values = (t.values * nf_drop).astype(t.values.dtype)
            scores = scores - padded(drop_contrib * (1.0 - nf_drop))

        # eval + early stopping on validation rows (the only host sync).
        # Multihost: every process must take this branch together — the
        # allgather inside is a collective
        eval_result = None
        stop_now = False
        if valid_mask is not None and (multihost or valid_mask.any()):
            name = None
            if multihost:
                s_eval = _local_block_rows(scores, n)
                if is_rf:
                    s_eval = _local_block_rows(rf_base, n) + s_eval / (it + 1)
                if mh_eval_ctx is None:
                    # y, the valid mask and (ranking) group ids are
                    # loop-invariant: one gather. Group labels are only
                    # unique per process — offset by process index so two
                    # processes' query 0s stay distinct queries globally
                    gid_l = (
                        group_ids.astype(np.float64) * jax.process_count()
                        + jax.process_index()
                        if group_ids is not None
                        else np.zeros(n, np.float64)
                    )
                    ym = _gather_rows(
                        np.stack(
                            [y, valid_mask.astype(np.float64), gid_l], 1
                        ),
                        n, share,
                    )
                    mh_eval_ctx = (
                        ym[:, 0], ym[:, 1] > 0.5, ym[:, 2].astype(np.int64)
                    )
                y_g, m_g, gid_g = mh_eval_ctx
                sg2 = _gather_rows(s_eval, n, share)
                s_g = sg2 if k > 1 else sg2[:, 0]
                if m_g.any():
                    name, val, higher = _eval_metric(
                        cfg, s_g, y_g, m_g,
                        gid_g if group_ids is not None else None,
                    )
            else:
                s_eval = np.asarray(scores)[:n]
                if is_rf:
                    s_eval = np.asarray(rf_base)[:n] + s_eval / (it + 1)
                name, val, higher = _eval_metric(cfg, s_eval, y, valid_mask, group_ids)
            if name is not None:
                eval_result = (name, val, higher)
                if cfg.verbosity > 0:
                    log.info("iter %d %s=%.6f", it, name, val)
                improved = (
                    best_val is None
                    or (higher and val > best_val)
                    or (not higher and val < best_val)
                )
                if improved:
                    best_val, best_iter, rounds_no_improve = val, it + 1, 0
                else:
                    rounds_no_improve += 1
                    if early_stopping_round > 0 and rounds_no_improve >= early_stopping_round:
                        log.info("early stop at iter %d (best %d)", it, best_iter)
                        booster.best_iteration = best_iter
                        stop_now = True
        if delegate is not None:
            delegate.after_train_iteration(
                it, eval_result, stop_now or it == cfg.num_iterations - 1
            )
        if checkpoint_dir and not stop_now and (it + 1) % checkpoint_every == 0:
            # materialize deferred trees now — the checkpointed booster
            # must contain every completed round (dart's are already eager)
            booster.trees.extend(_trees_from_device_batched(pending_trees, mapper))
            pending_trees = []
            _save_ckpt(it + 1, bag)
        done_ns = _time.perf_counter_ns()
        obs.record_span("gbdt.round", t_round_ns, done_ns)
        _M_ROUND_SECONDS.observe((done_ns - t_round_ns) / 1e9)
        _M_ROUNDS.inc()
        if stop_now:
            break

    booster.trees.extend(_trees_from_device_batched(pending_trees, mapper))
    # dart never records best_iteration: later dropouts rescale trees inside
    # any prefix, so no prefix reproduces a historical eval score
    if valid_mask is not None and best_iter > 0 and booster.best_iteration < 0 and not is_dart:
        booster.best_iteration = best_iter
    if init_booster is not None and init_booster.trees:
        new_best = booster.best_iteration
        init_iters = len(init_booster.trees) // init_booster.num_class
        booster = init_booster.merge(booster)
        if new_best > 0:
            # best iteration counts from the front of the merged tree list
            booster.best_iteration = init_iters + new_best
    return booster


def _densify(x: Any) -> np.ndarray:
    """CSR -> dense float32 with absent entries as NaN (prediction-time
    only; training stays sparse). NaN, not 0: trees trained on sparse data
    route absent entries through the missing bin."""
    from mmlspark_tpu.models.gbdt.binning import densify_missing, is_sparse

    if is_sparse(x):
        return densify_missing(x)
    return np.asarray(x, np.float32)


def _iterations_contrib(
    booster: Booster, x: np.ndarray, iterations: list, k: int
) -> np.ndarray:
    """Summed raw contribution of the given iterations: (n,) or (n, k)."""
    idx = [it * k + c for it in iterations for c in range(k)]
    per = per_tree_raw([booster.trees[i] for i in idx], x)  # (n, len(idx))
    if k == 1:
        return per.sum(axis=1).astype(np.float32)
    n = per.shape[0]
    out = np.zeros((n, k), np.float32)
    for j, i in enumerate(idx):
        out[:, i % k] += per[:, j]
    return out
