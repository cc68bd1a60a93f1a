"""LightGBM-compatible estimator facades on the TPU GBDT.

API parity with the reference's SparkML facades
(lightgbm/LightGBMClassifier.scala, LightGBMRegressor.scala,
LightGBMRanker.scala + LightGBMParams.scala): same estimator/model split,
same core params (num_leaves, num_iterations, learning_rate, objective,
parallelism=data_parallel|voting_parallel, early stopping via a validation
indicator column, init-score column, continued training via model string).

The distributed knobs of the reference (driver ports, barrier mode,
timeouts — LightGBMParams.scala) do not exist here: gang scheduling and the
histogram allreduce come from SPMD launch over the device mesh (SURVEY §5.8).
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame, Partition
from mmlspark_tpu.core.params import (
    ComplexParam,
    HasFeaturesCol,
    HasGroupCol,
    HasInitScoreCol,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasValidationIndicatorCol,
    HasWeightCol,
    Param,
)
from mmlspark_tpu.core.pipeline import Estimator, Model
from mmlspark_tpu.models.gbdt import objectives
from mmlspark_tpu.models.gbdt.booster import Booster
from mmlspark_tpu.models.gbdt.train import TrainConfig, train


def _read_column(df: DataFrame, name: str, dtype: Any = None) -> tuple:
    """Column ``name`` of all partitions as one C-ordered array of ``dtype``
    (``None``: as stored), for a caller that only READS it, and the bytes
    that had to be copied to make it.

    ``df[name]`` hands every caller a fresh array, which stages that mutate
    their column rely on; at 2,625,000 x 28 that copy, and the ``astype``
    after it, were 0.7 s of a 3.5 s fit (PERF.md section 6, PR 28).
    ``train()`` writes to none of its inputs, so a single non-empty
    partition whose array already has the dtype and the layout is handed on
    as it is — as a read-only view: a write would raise, not reach the
    caller's data — and anything else takes exactly one pass: several
    partitions are concatenated straight into ``dtype``."""
    arrs = [p[name] for p in df.partitions if p and len(p[name])]
    if not arrs:
        return np.array([], dtype=dtype), 0
    if len(arrs) > 1:
        out = np.concatenate(arrs, axis=0, dtype=dtype, casting="unsafe")
        return out, out.nbytes
    out = np.asarray(arrs[0], dtype=dtype, order="C")
    if not np.may_share_memory(out, arrs[0]):
        return out, out.nbytes
    out = out.view()
    out.flags.writeable = False
    return out, 0


class _LightGBMParams(
    HasFeaturesCol,
    HasLabelCol,
    HasWeightCol,
    HasValidationIndicatorCol,
    HasInitScoreCol,
):
    num_iterations = Param("boosting rounds", default=100, type_=int)
    learning_rate = Param("shrinkage", default=0.1, type_=float)
    num_leaves = Param("max leaves per tree", default=31, type_=int)
    max_depth = Param("max tree depth (-1 = unlimited)", default=-1, type_=int)
    lambda_l2 = Param("L2 leaf regularization", default=0.0, type_=float)
    lambda_l1 = Param("L1 leaf regularization (ThresholdL1)", default=0.0, type_=float)
    min_sum_hessian_in_leaf = Param(
        "min child hessian mass for a valid split", default=1e-3, type_=float
    )
    min_gain_to_split = Param("min split gain", default=0.0, type_=float)
    min_data_in_leaf = Param("min rows per leaf", default=20, type_=int)
    max_bin = Param(
        "histogram bins (max 255: uint8 bin matrix)",
        default=255,
        type_=int,
        validator=lambda v: 2 <= v <= 255,
    )
    feature_fraction = Param("feature subsample per tree", default=1.0, type_=float)
    bagging_fraction = Param("row subsample", default=1.0, type_=float)
    bagging_freq = Param("bagging frequency (0=off)", default=0, type_=int)
    early_stopping_round = Param("early stopping patience (0=off)", default=0, type_=int)
    metric = Param("eval metric name ('' = objective default)", default="", type_=str)
    parallelism = Param(
        "data_parallel | voting_parallel (parity; both lower to the sharded program)",
        default="data_parallel",
        type_=str,
    )
    growth_policy = Param(
        "lossguide (LightGBM leaf-wise, default) | depthwise (level-wise; "
        "one multi-leaf histogram pass per level — O(depth) row passes)",
        default="lossguide",
        type_=str,
        validator=lambda v: v in ("lossguide", "depthwise"),
    )
    default_listen_port = Param("parity no-op (no sockets on TPU)", default=12400, type_=int)
    use_barrier_execution_mode = Param("parity no-op (SPMD is the gang)", default=False, type_=bool)
    top_k = Param("voting_parallel K (parity)", default=20, type_=int)
    boost_from_average = Param("init score from label average", default=True, type_=bool)
    boosting_type = Param(
        "gbdt | goss | dart | rf (LightGBMParams boostingType)",
        default="gbdt",
        type_=str,
        validator=lambda v: v in ("gbdt", "goss", "dart", "rf"),
    )
    drop_rate = Param("dart: per-iteration tree dropout rate", default=0.1, type_=float)
    max_drop = Param("dart: max trees dropped per iteration", default=50, type_=int)
    skip_drop = Param("dart: probability of skipping dropout", default=0.5, type_=float)
    top_rate = Param("goss: large-gradient retain fraction", default=0.2, type_=float)
    other_rate = Param("goss: small-gradient sample fraction", default=0.1, type_=float)
    eval_at = Param("ranking eval truncation (ndcg@k)", default=5, type_=int)
    categorical_slot_indexes = Param(
        "feature indices treated as categorical (subset splits; "
        "LightGBMParams categoricalSlotIndexes analogue). Values must be "
        "non-negative integers < max_bin-1.",
        default=None,
    )
    model_string = Param("initial model for continued training", default="", type_=str)
    alpha = Param(
        "quantile level (objective=quantile) / huber delta (objective=huber)",
        default=0.9, type_=float,
    )
    tweedie_variance_power = Param(
        "tweedie variance power in (1, 2)", default=1.5, type_=float
    )
    poisson_max_delta_step = Param(
        "poisson hessian stabilizer exp(score + step)", default=0.7, type_=float
    )
    fair_c = Param("fair-loss scale c", default=1.0, type_=float)
    num_batches = Param("fold training into k sequential batches", default=0, type_=int)
    checkpoint_dir = Param(
        "directory for round-level preemption-safe checkpoints ('' = off); "
        "see docs/robustness.md", default="", type_=str,
    )
    checkpoint_every = Param(
        "boosting rounds between checkpoints (each save re-serializes the "
        "full booster — small values trade training throughput for a "
        "tighter recovery window)", default=10, type_=int
    )
    resume_from = Param(
        "checkpoint directory to resume training from ('' = fresh run); "
        "point it at checkpoint_dir for crash-loop-safe auto-resume",
        default="", type_=str,
    )
    delegate = ComplexParam(
        "LightGBMDelegate: lifecycle callbacks + dynamic learning rate"
    )
    seed = Param("rng seed", default=0, type_=int)
    verbosity = Param("log level", default=-1, type_=int)
    fused_rounds = Param(
        "scan-fused chunk size: 0 = auto (one dispatch per run, bounded "
        "chunks under early stopping), 1 = legacy per-round dispatch "
        "loop (fallback; identical model), N > 1 = cap chunks at N rounds",
        default=0, type_=int,
    )

    def _config(self, objective: str, num_class: int = 1) -> TrainConfig:
        return TrainConfig(
            objective=objective,
            num_class=num_class,
            num_iterations=self.get("num_iterations"),
            learning_rate=self.get("learning_rate"),
            num_leaves=self.get("num_leaves"),
            max_depth=self.get("max_depth"),
            lambda_l2=self.get("lambda_l2"),
            lambda_l1=self.get("lambda_l1"),
            min_sum_hessian_in_leaf=self.get("min_sum_hessian_in_leaf"),
            min_gain_to_split=self.get("min_gain_to_split"),
            min_data_in_leaf=self.get("min_data_in_leaf"),
            max_bin=self.get("max_bin"),
            feature_fraction=self.get("feature_fraction"),
            bagging_fraction=self.get("bagging_fraction"),
            bagging_freq=self.get("bagging_freq"),
            early_stopping_round=self.get("early_stopping_round"),
            metric=self.get("metric"),
            seed=self.get("seed"),
            parallelism=self.get("parallelism"),
            growth_policy=self.get("growth_policy"),
            top_k=self.get("top_k"),
            verbosity=self.get("verbosity"),
            categorical_features=tuple(self.get("categorical_slot_indexes") or ()),
            boosting_type=self.get("boosting_type"),
            delegate=self.get("delegate"),
            drop_rate=self.get("drop_rate"),
            max_drop=self.get("max_drop"),
            skip_drop=self.get("skip_drop"),
            top_rate=self.get("top_rate"),
            other_rate=self.get("other_rate"),
            eval_at=self.get("eval_at"),
            alpha=self.get("alpha"),
            tweedie_variance_power=self.get("tweedie_variance_power"),
            poisson_max_delta_step=self.get("poisson_max_delta_step"),
            fair_c=self.get("fair_c"),
        )

    def _gather(self, df: DataFrame, label_dtype: Any = np.float64) -> dict:
        """The columns ``train()`` reads, each in the dtype it reads them in
        (the label in ``label_dtype``; ``None``: as stored), and under
        ``copied_bytes`` how much of the feature matrix had to be copied to
        get there (0: ``train()`` reads the DataFrame's own array), which the
        ``gbdt.gather`` span carries as its attribute."""
        x, copied = _read_column(df, self.get("features_col"), np.float32)
        out = {
            "x": x,
            "y": _read_column(df, self.get("label_col"), label_dtype)[0],
            "copied_bytes": copied,
        }
        wc = self.get("weight_col")
        out["w"] = _read_column(df, wc, np.float32)[0] if wc else None
        vc = self.get("validation_indicator_col")
        out["valid"] = _read_column(df, vc, bool)[0] if vc else None
        ic = self.get("init_score_col")
        out["init"] = _read_column(df, ic, np.float32)[0] if ic else None
        return out

    def _describe_fit(self, sp: obs.Span, data: dict) -> None:
        """What a ``gbdt.fit`` root span says about its fit."""
        import jax

        rows, features = data["x"].shape
        sp.set_attr("rows", int(rows))
        sp.set_attr("features", int(features))
        sp.set_attr("trees", int(self.get("num_iterations")))
        sp.set_attr("num_leaves", int(self.get("num_leaves")))
        sp.set_attr("devices", jax.device_count())

    def _model_string(self, booster: Booster) -> str:
        with obs.span("gbdt.model_string"):
            return booster.to_model_string()

    def _init_booster(self) -> Optional[Booster]:
        s = self.get("model_string")
        return Booster.from_model_string(s) if s else None

    def _fit_batches(
        self, data: dict, make_cfg: Any, base_score: Any = 0.0, **kw: Any
    ) -> Booster:
        """numBatches semantics (LightGBMBase.scala:29-50): split rows into
        k sequential batches, fold the previous booster into each.

        ``base_score`` applies only to the first training segment (later
        segments continue from a booster whose predictions include it)."""
        nb = self.get("num_batches")
        booster = self._init_booster()
        delegate = self.get("delegate")
        if not (nb and nb > 1):
            kw.setdefault("checkpoint_dir", self.get("checkpoint_dir") or None)
            kw.setdefault("checkpoint_every", self.get("checkpoint_every"))
            kw.setdefault("resume_from", self.get("resume_from") or None)
        elif self.get("checkpoint_dir") or self.get("resume_from"):
            # refuse rather than silently train unprotected: numBatches
            # folds k train() calls whose round indices would collide in
            # one checkpoint directory
            raise ValueError(
                "checkpoint_dir/resume_from are incompatible with "
                "num_batches > 1 (per-segment round indices would collide "
                "in one checkpoint directory)"
            )
        kw.setdefault("fused_rounds", self.get("fused_rounds"))
        if nb and nb > 1:
            n = len(data["y"])
            bounds = np.linspace(0, n, nb + 1).astype(int)
            for i in range(nb):
                sl = slice(bounds[i], bounds[i + 1])
                kw_sl = {
                    k: (v[sl] if isinstance(v, np.ndarray) else v) for k, v in kw.items()
                }
                if delegate is not None:
                    delegate.before_train_batch(i, bounds[i + 1] - bounds[i], booster)
                booster = train(
                    data["x"][sl],
                    data["y"][sl],
                    make_cfg(),
                    sample_weight=None if data["w"] is None else data["w"][sl],
                    init_score=None if data["init"] is None else data["init"][sl],
                    valid_mask=None if data["valid"] is None else data["valid"][sl],
                    init_booster=booster,
                    base_score=0.0 if booster is not None else base_score,
                    **kw_sl,
                )
                if delegate is not None:
                    delegate.after_train_batch(i, booster)
            return booster
        return train(
            data["x"],
            data["y"],
            make_cfg(),
            sample_weight=data["w"],
            init_score=data["init"],
            valid_mask=data["valid"],
            init_booster=booster,
            base_score=0.0 if booster is not None else base_score,
            **kw,
        )


class _NativeModelIO:
    """Native LightGBM model interop on every model facade — the
    reference's saveNativeModel / loadNativeModelFromFile / ...FromString
    (lightgbm/LightGBMClassifier.scala). ``model_string`` transparently
    accepts BOTH our JSON format and LightGBM's text format, so a model
    trained with the reference (or python lightgbm) drops straight in."""

    def save_native_model(self, path: str) -> None:
        """Write the booster in LightGBM's own text format."""
        with open(path, "w") as f:
            f.write(self.booster.to_lightgbm_string())

    @classmethod
    def load_native_model_from_string(cls, text: str, **kw: Any):
        m = cls(**kw)
        m.set(model_string=text)
        m.booster  # parse eagerly: malformed input fails here, not at transform
        return m

    @classmethod
    def load_native_model_from_file(cls, path: str, **kw: Any):
        with open(path) as f:
            return cls.load_native_model_from_string(f.read(), **kw)


class LightGBMClassifier(Estimator, _LightGBMParams, HasProbabilityCol, HasRawPredictionCol, HasPredictionCol):
    objective = Param("binary | multiclass", default="binary", type_=str)

    def fit(self, df: DataFrame) -> "LightGBMClassificationModel":
        # one trace per fit: every span below (train()'s binning, upload
        # and chunks among them) is a descendant of this root
        with obs.span("gbdt.fit") as sp:
            with obs.span("gbdt.gather") as gsp:
                data = self._gather(df, label_dtype=None)
                gsp.set_attr("copied_bytes", data["copied_bytes"])
                # classes are whole numbers: an integer or bool label is read
                # as stored (a float one truncates), and the class count and
                # the priors below read that same array
                y = data["y"]
                if y.dtype.kind not in "biu":
                    y = y.astype(np.int64)
                n_classes = int(y.max()) + 1 if len(y) else 2
                objective = self.get("objective")
                if objective == "binary" and n_classes > 2:
                    objective = "multiclass"
                num_class = n_classes if objective == "multiclass" else 1
                data["y"] = y.astype(np.float64)
                base: Any = 0.0
                if self.get("boost_from_average") and data["init"] is None and len(y):
                    if objective == "binary":
                        p = float(np.clip(y.mean(), 1e-6, 1 - 1e-6))
                        base = float(np.log(p / (1 - p)))
                    else:  # multiclass: per-class log prior
                        priors = np.bincount(y, minlength=num_class) / len(y)
                        base = np.log(np.clip(priors, 1e-6, None)).astype(np.float32)
            self._describe_fit(sp, data)
            booster = self._fit_batches(
                data, lambda: self._config(objective, num_class), base_score=base
            )
            m = LightGBMClassificationModel(
                features_col=self.get("features_col"),
                prediction_col=self.get("prediction_col"),
                probability_col=self.get("probability_col"),
                raw_prediction_col=self.get("raw_prediction_col"),
            )
            m.set(model_string=self._model_string(booster))
            return m


def _booster_raw_device_fn(booster: Any, features_col: str, raw_key: str) -> Any:
    """Jit-traceable ``cols -> {raw_key: predict_raw(x)}`` bit-matching the
    host :meth:`Booster.predict_raw` for the pipeline compiler.

    The staged path already runs the tree traversal on device
    (``treegrow.predict_leaves``) — here the same program is traced into
    the fused segment (integer leaf outputs are exact under any lowering),
    the leaf-value gather is pure selection, and the cross-tree float32
    reduction uses :func:`~mmlspark_tpu.compiler.kernels.pairwise_sum`,
    which reproduces ``np.sum``'s association order so the device total is
    bit-equal to the host's. Returns None for an empty booster (host path
    covers the broadcast-base degenerate case).
    """
    from mmlspark_tpu.compiler.kernels import pairwise_sum
    from mmlspark_tpu.models.gbdt import treegrow
    from mmlspark_tpu.models.gbdt.booster import _stack_trees

    trees = booster.trees
    if booster.best_iteration > 0:
        trees = trees[: booster.best_iteration * booster.num_class]
    if not trees:
        return None
    stacked = _stack_trees(trees)
    (rec_leaf, rec_feature, rec_threshold, rec_active, values, is_cat,
     catmask, default_left) = stacked
    k = booster.num_class
    T = len(trees)
    denom = float((T // k) if booster.boosting_type == "rf" else 1)
    base = np.asarray(booster.base_score, np.float32)

    def fn(cols: dict) -> dict:
        import jax.numpy as jnp

        x = cols[features_col].astype(jnp.float32)
        leaves = treegrow.predict_leaves(
            x,
            jnp.asarray(rec_leaf),
            jnp.asarray(rec_feature),
            jnp.asarray(rec_threshold),
            jnp.asarray(rec_active),
            jnp.asarray(is_cat) if is_cat is not None else None,
            jnp.asarray(catmask) if catmask is not None else None,
            jnp.asarray(default_left) if default_left is not None else None,
        )  # (n, T) int32 — exact
        vals = jnp.asarray(values)  # (T, L)
        per_tree = vals[jnp.arange(T)[None, :], leaves]  # (n, T) gather
        d = jnp.float32(denom)
        b = jnp.asarray(base)
        if k == 1:
            raw = pairwise_sum(per_tree) / d + b
        else:
            raw = jnp.stack(
                [pairwise_sum(per_tree[:, c::k]) / d for c in range(k)],
                axis=1,
            ) + b
        return {raw_key: raw}

    return fn


class LightGBMClassificationModel(
    Model, _NativeModelIO, HasFeaturesCol, HasPredictionCol, HasProbabilityCol, HasRawPredictionCol
):
    model_string = Param("serialized booster", default="", type_=str)

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._booster: Optional[Booster] = None
        self._booster_src: Optional[str] = None

    @property
    def booster(self) -> Booster:
        s = self.get_or_fail("model_string")
        if self._booster is None or self._booster_src != s:
            self._booster = Booster.from_model_string(s)
            self._booster_src = s
        return self._booster

    def transform(self, df: DataFrame) -> DataFrame:
        booster = self.booster

        def fn(p: Partition) -> Partition:
            x = np.asarray(p[self.get("features_col")], np.float32)
            raw = booster.predict_raw(x)
            q = dict(p)
            if booster.num_class == 1:
                # imported models may carry a non-default sigmoid slope
                # ("binary sigmoid:s"): p = sigmoid(s * score)
                probs1 = objectives.sigmoid(booster.sigmoid * raw)
                probs = np.stack([1 - probs1, probs1], axis=1)
                raw2 = np.stack([-raw, raw], axis=1)
            else:
                probs = objectives.softmax(raw)
                raw2 = raw
            q[self.get("raw_prediction_col")] = raw2.astype(np.float64)
            q[self.get("probability_col")] = probs.astype(np.float64)
            q[self.get("prediction_col")] = probs.argmax(axis=1).astype(np.float64)
            return q

        return df.map_partitions(fn, parallel=False)

    def fusable_kernel(self) -> Any:
        """Device traversal + gather + numpy-order summed scores in the
        fused program; the sigmoid/softmax/argmax/float64 epilogue replays
        the exact staged numpy code as a host ``finalize`` (libm ``exp``
        has no bit-equal device twin with x64 off)."""
        from mmlspark_tpu.compiler.kernels import StageKernel, guard_f32_safe

        booster = self.booster
        fc = self.get("features_col")
        raw_c = self.get("raw_prediction_col")
        prob_c = self.get("probability_col")
        pred_c = self.get("prediction_col")
        raw_key = f"__device_raw__{raw_c}"
        fn = _booster_raw_device_fn(booster, fc, raw_key)
        if fn is None:
            return None

        def finalize(host: dict) -> dict:
            raw = host[raw_key]
            if booster.num_class == 1:
                probs1 = objectives.sigmoid(booster.sigmoid * raw)
                probs = np.stack([1 - probs1, probs1], axis=1)
                raw2 = np.stack([-raw, raw], axis=1)
            else:
                probs = objectives.softmax(raw)
                raw2 = raw
            return {
                raw_c: raw2.astype(np.float64),
                prob_c: probs.astype(np.float64),
                pred_c: probs.argmax(axis=1).astype(np.float64),
            }

        return StageKernel(
            reads=(fc,),
            writes=(raw_c, prob_c, pred_c),
            fn=fn,
            guard=guard_f32_safe,
            finalize=finalize,
            device_writes=(raw_key,),
            cost_hint=1.0 + len(booster.trees) / 100.0,
        )

    def predict_leaf(self, x: np.ndarray) -> np.ndarray:
        return self.booster.predict_leaf(np.asarray(x, np.float32))

    def features_shap(self, x: np.ndarray, approximate: bool = False) -> np.ndarray:
        """Exact TreeSHAP by default; ``approximate=True`` = the vectorized
        Saabas walk (orders of magnitude faster on large batches)."""
        return self.booster.feature_contribs(
            np.asarray(x, np.float32), approximate=approximate
        )

    def get_feature_importances(self, importance_type: str = "split") -> np.ndarray:
        return self.booster.feature_importances(importance_type)


class LightGBMRegressor(Estimator, _LightGBMParams, HasPredictionCol):
    objective = Param(
        "regression | regression_l1 | quantile | huber | fair | poisson | "
        "tweedie | gamma | mape (LightGBM objective passthrough, "
        "TrainParams.scala:8-40)",
        default="regression", type_=str,
    )

    def fit(self, df: DataFrame) -> "LightGBMRegressionModel":
        with obs.span("gbdt.fit") as sp:
            with obs.span("gbdt.gather") as gsp:
                data = self._gather(df)
                gsp.set_attr("copied_bytes", data["copied_bytes"])
                obj = objectives.canonical_objective(self.get("objective"))
                base = 0.0
                y = data["y"]
                if self.get("boost_from_average") and data["init"] is None and len(y):
                    # LightGBM's BoostFromScore per objective family: log-link
                    # objectives start at log(mean) (scores live in log space),
                    # quantile at the alpha-percentile, l1/mape at the median
                    if obj in objectives.LOG_LINK_KINDS:
                        base = float(np.log(np.clip(y.mean(), 1e-9, None)))
                    elif obj == "quantile":
                        base = float(np.percentile(y, self.get("alpha") * 100.0))
                    elif obj in ("regression_l1", "mape"):
                        base = float(np.median(y))
                    else:
                        base = float(y.mean())
            self._describe_fit(sp, data)
            booster = self._fit_batches(
                data, lambda: self._config(obj), base_score=base
            )
            m = LightGBMRegressionModel(
                features_col=self.get("features_col"),
                prediction_col=self.get("prediction_col"),
            )
            m.set(model_string=self._model_string(booster))
            return m


class LightGBMRegressionModel(Model, _NativeModelIO, HasFeaturesCol, HasPredictionCol):
    model_string = Param("serialized booster", default="", type_=str)

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._booster: Optional[Booster] = None
        self._booster_src: Optional[str] = None

    @property
    def booster(self) -> Booster:
        s = self.get_or_fail("model_string")
        if self._booster is None or self._booster_src != s:
            self._booster = Booster.from_model_string(s)
            self._booster_src = s
        return self._booster

    def transform(self, df: DataFrame) -> DataFrame:
        booster = self.booster
        fc = self.get("features_col")
        return df.with_column(
            self.get("prediction_col"),
            lambda p: booster.predict(np.asarray(p[fc], np.float32)).astype(np.float64),
        )

    def fusable_kernel(self) -> Any:
        """Like the classifier's kernel: scores on device, the objective's
        output transform (log-link ``np.exp``) + float64 cast on host."""
        from mmlspark_tpu.compiler.kernels import StageKernel, guard_f32_safe

        booster = self.booster
        fc = self.get("features_col")
        pred_c = self.get("prediction_col")
        raw_key = f"__device_raw__{pred_c}"
        fn = _booster_raw_device_fn(booster, fc, raw_key)
        if fn is None:
            return None

        def finalize(host: dict) -> dict:
            raw = host[raw_key]
            if booster.objective in objectives.LOG_LINK_KINDS:
                raw = np.exp(raw)
            return {pred_c: raw.astype(np.float64)}

        return StageKernel(
            reads=(fc,),
            writes=(pred_c,),
            fn=fn,
            guard=guard_f32_safe,
            finalize=finalize,
            device_writes=(raw_key,),
            cost_hint=1.0 + len(booster.trees) / 100.0,
        )

    def features_shap(self, x: np.ndarray, approximate: bool = False) -> np.ndarray:
        """Exact TreeSHAP by default; ``approximate=True`` = the vectorized
        Saabas walk (orders of magnitude faster on large batches)."""
        return self.booster.feature_contribs(
            np.asarray(x, np.float32), approximate=approximate
        )


class LightGBMRanker(Estimator, _LightGBMParams, HasGroupCol, HasPredictionCol):
    objective = Param("lambdarank", default="lambdarank", type_=str)
    evaluate_at = Param("NDCG truncation positions", default=[1, 3, 5, 10], type_=list)

    def fit(self, df: DataFrame) -> "LightGBMRankerModel":
        gc = self.get("group_col")
        if not gc:
            raise ValueError("LightGBMRanker requires group_col (query column)")
        with obs.span("gbdt.fit") as sp:
            with obs.span("gbdt.gather") as gsp:
                data = self._gather(df)
                gsp.set_attr("copied_bytes", data["copied_bytes"])
                groups_raw = _read_column(df, gc)[0]
                _, group_ids = np.unique(
                    groups_raw.astype(str) if groups_raw.dtype == object else groups_raw,
                    return_inverse=True,
                )
            self._describe_fit(sp, data)
            booster = self._fit_batches(
                data, lambda: self._config("lambdarank"), group_ids=group_ids
            )
            m = LightGBMRankerModel(
                features_col=self.get("features_col"),
                prediction_col=self.get("prediction_col"),
            )
            m.set(model_string=self._model_string(booster))
            return m


class LightGBMRankerModel(Model, _NativeModelIO, HasFeaturesCol, HasPredictionCol):
    model_string = Param("serialized booster", default="", type_=str)

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._booster: Optional[Booster] = None
        self._booster_src: Optional[str] = None

    @property
    def booster(self) -> Booster:
        s = self.get_or_fail("model_string")
        if self._booster is None or self._booster_src != s:
            self._booster = Booster.from_model_string(s)
            self._booster_src = s
        return self._booster

    def transform(self, df: DataFrame) -> DataFrame:
        booster = self.booster
        fc = self.get("features_col")
        return df.with_column(
            self.get("prediction_col"),
            lambda p: booster.predict_raw(np.asarray(p[fc], np.float32)).astype(np.float64),
        )
