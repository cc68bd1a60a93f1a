"""Fuzzing coverage: every registered stage runs + serialization round-trips.

The TestObject catalog below is the analogue of each suite's
``testObjects()`` in the reference; test_all_stages_covered is
FuzzingTest.scala's exhaustiveness gate.
"""

from __future__ import annotations

import numpy as np
import pytest

import mmlspark_tpu  # noqa: F401 - populate registry
from mmlspark_tpu import DataFrame, Pipeline, PipelineModel
from mmlspark_tpu.core.pipeline import STAGE_REGISTRY, Estimator, load_stage

from fuzzing import TestObject, assert_df_equal, run_stage


def _num_df(n=20, d=4, parts=2, seed=0):
    r = np.random.default_rng(seed)
    return DataFrame.from_dict(
        {
            "features": r.normal(size=(n, d)).astype(np.float32),
            "x": r.normal(size=n),
            "label": (r.random(n) > 0.5).astype(np.int32),
            "text": np.array([f"word{i % 5} token{i % 3} filler" for i in range(n)], dtype=object),
            "cat": np.array([["red", "green", "blue"][i % 3] for i in range(n)], dtype=object),
        },
        num_partitions=parts,
    )


def _nan_df():
    return DataFrame.from_dict({"x": [1.0, np.nan, 3.0, np.nan], "y": [np.nan, 2.0, 2.0, 4.0]})


def _array_df():
    arrs = np.empty(4, dtype=object)
    for i in range(4):
        arrs[i] = np.arange(i + 1, dtype=np.float64)
    return DataFrame.from_dict({"k": ["a", "a", "b", "b"], "arr": arrs, "v": [1.0, 2.0, 3.0, 4.0]})


def make_test_objects() -> list:
    from mmlspark_tpu import stages as S
    from mmlspark_tpu import featurize as F

    df = _num_df()
    objs = [
        TestObject(S.DropColumns(cols=["x"]), df),
        TestObject(S.SelectColumns(cols=["x", "label"]), df),
        TestObject(S.RenameColumn(input_col="x", output_col="x2"), df),
        TestObject(S.Repartition(n=1), df),
        TestObject(S.Lambda.of(lambda d: d.select("x")), df),
        TestObject(
            S.UDFTransformer(input_col="x", output_col="x2").set(udf=lambda v: v * 2), df
        ),
        TestObject(
            S.UDFTransformer(input_col="x", output_col="x2").set(
                vector_udf=lambda col: np.asarray(col) * 2
            ),
            df,
        ),
        TestObject(S.Explode(input_col="arr", output_col="el"), _array_df()),
        TestObject(S.Cacher(), df),
        TestObject(S.Timer().set(stage=S.DropColumns(cols=["x"])), df),
        TestObject(S.FixedMiniBatchTransformer(batch_size=8), df),
        TestObject(S.DynamicMiniBatchTransformer(), df),
        TestObject(
            S.TimeIntervalMiniBatchTransformer(interval_ms=10, max_batch_size=4), df
        ),
        TestObject(S.StratifiedRepartition(label_col="label", n=2), df),
        TestObject(S.ClassBalancer(input_col="label"), df),
        TestObject(
            S.EnsembleByKey(keys=["k"], cols=["v"], col_names=["mean_v"]), _array_df()
        ),
        TestObject(S.SummarizeData(), df.select("x", "label")),
        TestObject(
            S.TextPreprocessor(
                input_col="text", output_col="clean", map={"word1": "ONE"}
            ),
            df,
        ),
        TestObject(S.UnicodeNormalize(input_col="text", output_col="norm"), df),
        TestObject(F.CleanMissingData(input_cols=["x", "y"]), _nan_df()),
        TestObject(
            F.CleanMissingData(input_cols=["x"], cleaning_mode="Median"), _nan_df()
        ),
        TestObject(F.DataConversion(cols=["label"], convert_to="double"), df),
        TestObject(F.Featurize(input_cols=["x", "cat", "features"]), df),
        TestObject(F.ValueIndexer(input_col="cat", output_col="cat_idx"), df),
        TestObject(
            F.TextFeaturizer(input_col="text", output_col="tf", num_features=64), df
        ),
        TestObject(
            F.TextFeaturizer(
                input_col="text", output_col="tf", num_features=64,
                use_ngram=True, use_idf=False,
            ),
            df,
        ),
        TestObject(
            F.PageSplitter(
                input_col="text", output_col="pages",
                maximum_page_length=10, minimum_page_length=5,
            ),
            df,
        ),
    ]
    # batched-then-flattened path
    batched = S.FixedMiniBatchTransformer(batch_size=8).transform(df)
    objs.append(TestObject(S.FlattenBatch(), batched))
    # MultiNGram needs token arrays
    toks = np.empty(3, dtype=object)
    for i in range(3):
        toks[i] = [f"t{j}" for j in range(i + 2)]
    objs.append(
        TestObject(
            F.MultiNGram(input_col="toks", output_col="ngrams", lengths=[1, 2]),
            DataFrame.from_dict({"toks": toks}),
        )
    )
    # IndexToValue consumes indexed column + metadata
    vi_df = F.ValueIndexer(input_col="cat", output_col="cat_idx").fit(df).transform(df)
    objs.append(TestObject(F.IndexToValue(input_col="cat_idx", output_col="cat2"), vi_df))

    # train / automl / linear learners
    from mmlspark_tpu.models.linear import LinearRegression, LogisticRegression
    from mmlspark_tpu.train import (
        ComputeModelStatistics,
        ComputePerInstanceStatistics,
        OneVsRest,
        TrainClassifier,
        TrainRegressor,
    )
    from mmlspark_tpu.automl import (
        DiscreteHyperParam,
        FindBestModel,
        HyperparamBuilder,
        TuneHyperparameters,
    )

    lin_df = df.select("features", "label")

    # the pipeline compiler's CompiledPipeline is a registered Transformer
    from mmlspark_tpu.compiler import CompiledPipeline

    compiled = CompiledPipeline(
        stages=[LogisticRegression(max_iter=10).fit(lin_df)]
    )

    objs += [
        TestObject(LogisticRegression(max_iter=20), lin_df),
        TestObject(LinearRegression(), lin_df),
        TestObject(compiled, lin_df),
        TestObject(S.VectorZipper(input_cols=["x", "label"], output_col="z"), df),
        TestObject(
            S.FastVectorAssembler(input_cols=["x", "label"], output_col="fv"), df
        ),
        TestObject(
            S.MultiColumnAdapter(
                base_stage=F.ValueIndexer(), input_cols=["cat"], output_cols=["cat_idx"]
            ),
            df,
        ),
        TestObject(TrainClassifier(label_col="label"), df.select("x", "cat", "label")),
        TestObject(TrainRegressor(label_col="x"), df.select("features", "x")),
        TestObject(
            OneVsRest(classifier=LogisticRegression(max_iter=10), label_col="label"),
            lin_df,
        ),
    ]
    scored = LogisticRegression(max_iter=20).fit(lin_df).transform(lin_df)
    objs += [
        TestObject(ComputeModelStatistics(label_col="label"), scored),
        TestObject(ComputePerInstanceStatistics(label_col="label"), scored),
    ]
    spaces = HyperparamBuilder().add_hyperparam(
        "max_iter", DiscreteHyperParam([5, 10])
    ).build()
    tuner = TuneHyperparameters(label_col="label")
    tuner.set(models=[LogisticRegression()], hyperparams=spaces, number_of_runs=2, number_of_folds=2)
    objs.append(TestObject(tuner, lin_df))
    fb = FindBestModel()
    fb.set(models=[LogisticRegression(max_iter=10).fit(lin_df)])
    objs.append(TestObject(fb, lin_df))

    # gbdt facades (small configs keep the fuzzing pass fast)
    from mmlspark_tpu.models.gbdt import (
        LightGBMClassifier,
        LightGBMRanker,
        LightGBMRegressor,
    )

    # vw-equivalent stages
    from mmlspark_tpu import vw as V

    text_df = df.select("text", "label", "x", "features")
    vw_feat = V.VowpalWabbitFeaturizer(
        input_cols=[], string_split_input_cols=["text"], num_bits=12
    )
    vw_df = vw_feat.transform(text_df)
    objs += [
        TestObject(vw_feat, text_df),
        TestObject(
            V.VowpalWabbitFeaturizer(input_cols=["x", "features"], num_bits=12), text_df
        ),
        TestObject(V.VowpalWabbitClassifier(num_bits=12, num_passes=2), vw_df),
        TestObject(V.VowpalWabbitRegressor(num_bits=12), vw_df.rename({"label": "y", "x": "label"})),
    ]
    vw2 = V.VowpalWabbitFeaturizer(
        input_cols=["x"], output_col="f2", num_bits=12
    ).transform(vw_df)
    objs.append(
        TestObject(V.VowpalWabbitInteractions(input_cols=["features", "f2"], num_bits=12), vw2)
    )
    acts = np.empty(8, dtype=object)
    shared = np.empty(8, dtype=object)
    for i in range(8):
        acts[i] = [V.make_sparse([10 + a], [1.0]) for a in range(2)]
        shared[i] = V.make_sparse([5], [1.0])
    cb_df = DataFrame.from_dict(
        {
            "shared": shared,
            "features": acts,
            "chosen_action": np.ones(8, np.int64) + (np.arange(8) % 2),
            "probability": np.full(8, 0.5),
            "label": np.arange(8) % 2 * 1.0,
        }
    )
    objs.append(TestObject(V.VowpalWabbitContextualBandit(num_bits=10), cb_df))

    # io layer (network-bound stages are covered against a live localhost
    # server in test_io.py; parsers/consolidator fuzz offline)
    from mmlspark_tpu import io as IO
    from mmlspark_tpu.io.http_schema import HTTPRequestData, HTTPResponseData

    resps = np.empty(4, dtype=object)
    for i in range(4):
        resps[i] = HTTPResponseData(200, f'{{"v": {i}}}')
    resp_df = DataFrame.from_dict({"resp": resps})
    objs += [
        TestObject(
            IO.JSONInputParser(input_col="x", output_col="req", url="http://h/p"), df
        ),
        TestObject(
            IO.CustomInputParser(input_col="x", output_col="req").set_udf(
                lambda v: HTTPRequestData("http://h/p", "POST", entity=str(v))
            ),
            df,
        ),
        TestObject(IO.JSONOutputParser(input_col="resp", output_col="out"), resp_df),
        TestObject(IO.StringOutputParser(input_col="resp", output_col="out"), resp_df),
        TestObject(
            IO.CustomOutputParser(input_col="resp", output_col="out").set_udf(
                lambda r: r["status_code"]
            ),
            resp_df,
        ),
        TestObject(IO.PartitionConsolidator(), df),
    ]

    # cognitive stages: fuzz offline against an unreachable endpoint (rows
    # land deterministically in the error column; live-wire coverage is in
    # test_cognitive.py)
    from mmlspark_tpu import cognitive as C

    dead = "http://127.0.0.1:9"
    no_retry = {"use_advanced_handler": False}
    tiny = DataFrame.from_dict(
        {"text": np.array(["alpha"], dtype=object),
         "url": np.array(["http://img/x.jpg"], dtype=object),
         "blob": np.array([b"bytes"], dtype=object)}
    )
    ids_df_col = np.empty(1, dtype=object)
    ids_df_col[0] = ["f-1", "f-2"]
    series_col = np.empty(1, dtype=object)
    series_col[0] = [{"timestamp": "2026-01-01T00:00:00Z", "value": 1.0}]
    tiny = tiny.with_column("ids", ids_df_col).with_column("series", series_col)
    cog_stages = [
        C.TextSentiment(url=dead, output_col="o", **no_retry).set_col("text", "text"),
        C.LanguageDetector(url=dead, output_col="o", **no_retry).set_col("text", "text"),
        C.EntityDetector(url=dead, output_col="o", **no_retry).set_col("text", "text"),
        C.NER(url=dead, output_col="o", **no_retry).set_col("text", "text"),
        C.KeyPhraseExtractor(url=dead, output_col="o", **no_retry).set_col("text", "text"),
        C.RecognizeText(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.AnalyzeImage(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.OCR(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.RecognizeDomainSpecificContent(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.GenerateThumbnails(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.TagImage(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.DescribeImage(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.DetectFace(url=dead, output_col="o", **no_retry).set_col("image_url", "url"),
        C.VerifyFaces(url=dead, output_col="o", face_id1="a", face_id2="b", **no_retry),
        C.IdentifyFaces(url=dead, output_col="o", person_group_id="g", **no_retry).set_col("face_ids", "ids"),
        C.GroupFaces(url=dead, output_col="o", **no_retry).set_col("face_ids", "ids"),
        C.FindSimilarFace(url=dead, output_col="o", face_id="f-1", **no_retry).set_col("face_ids", "ids"),
        C.DetectAnomalies(url=dead, output_col="o", **no_retry).set_col("series", "series"),
        C.DetectLastAnomaly(url=dead, output_col="o", **no_retry).set_col("series", "series"),
        C.SpeechToText(url=dead, output_col="o", **no_retry).set_col("audio_data", "blob"),
        C.SpeechToTextSDK(url=dead, output_col="o", **no_retry).set_col("audio_data", "blob"),
        C.BingImageSearch(url=dead, output_col="o", **no_retry).set_col("query", "text"),
    ]
    objs += [TestObject(s, tiny) for s in cog_stages]

    qid_df = lin_df.with_column("query", np.arange(20) // 4)
    objs += [
        TestObject(
            LightGBMClassifier(num_iterations=3, num_leaves=4, min_data_in_leaf=2), lin_df
        ),
        TestObject(
            LightGBMRegressor(num_iterations=3, num_leaves=4, min_data_in_leaf=2),
            df.select("features", "x").rename({"x": "label"}),
        ),
        TestObject(
            LightGBMRanker(
                group_col="query", num_iterations=2, num_leaves=4, min_data_in_leaf=2
            ),
            qid_df,
        ),
    ]

    from mmlspark_tpu.nn import KNN, ConditionalKNN

    rng = np.random.RandomState(11)
    knn_feats = rng.randn(12, 4).astype(np.float32)
    conds = np.empty(12, dtype=object)
    for i in range(12):
        conds[i] = [i % 2]
    knn_df = DataFrame.from_dict(
        {
            "features": knn_feats,
            "values": np.arange(12),
            "label": np.arange(12) % 2,
            "conditioner": conds,
        }
    )
    objs += [
        TestObject(KNN(k=2), knn_df),
        TestObject(ConditionalKNN(k=2, label_col="label"), knn_df),
    ]

    from mmlspark_tpu.lime import ImageLIME, SuperpixelTransformer, TabularLIME
    from mmlspark_tpu.models.linear import LinearRegression

    lime_x = rng.randn(30, 3).astype(np.float32)
    lime_df = DataFrame.from_dict(
        {"features": lime_x, "label": (lime_x @ np.array([1.0, -1.0, 0.0])).astype(np.float32)}
    )
    lime_inner = LinearRegression().fit(lime_df)
    tiny_imgs = np.empty(2, dtype=object)
    for i in range(2):
        tiny_imgs[i] = rng.rand(16, 16, 3).astype(np.float32)
    img_df = DataFrame.from_dict({"image": tiny_imgs})

    from fuzzing import ImageMean

    objs += [
        TestObject(
            TabularLIME(input_col="features", model=lime_inner, n_samples=32,
                        prediction_col="prediction"),
            lime_df,
        ),
        TestObject(
            ImageLIME(input_col="image", model=ImageMean(input_col="image"),
                      n_samples=16, cell_size=8.0),
            img_df,
        ),
        TestObject(SuperpixelTransformer(input_col="image", cell_size=8.0), img_df),
    ]

    from mmlspark_tpu.recommendation import (
        SAR,
        RankingAdapter,
        RankingTrainValidationSplit,
        RecommendationIndexer,
    )

    rec_raw = DataFrame.from_dict(
        {
            "user": np.array(["a", "a", "b", "b", "c", "c"], dtype=object),
            "item": np.array(["x", "y", "x", "z", "y", "z"], dtype=object),
            "rating": np.ones(6, np.float32),
        }
    )
    rec_df = DataFrame.from_dict(
        {
            "user_idx": np.array([0, 0, 1, 1, 2, 2], np.int64),
            "item_idx": np.array([0, 1, 0, 2, 1, 2], np.int64),
            "rating": np.ones(6, np.float32),
        }
    )
    from mmlspark_tpu.isolationforest import IsolationForest

    objs += [
        TestObject(
            IsolationForest(num_estimators=5, max_samples=16),
            DataFrame.from_dict({"features": rng.randn(40, 3).astype(np.float32)}),
        ),
        TestObject(RecommendationIndexer(), rec_raw),
        TestObject(SAR(support_threshold=1), rec_df),
        TestObject(RankingAdapter(recommender=SAR(support_threshold=1), k=2), rec_df),
        TestObject(
            RankingTrainValidationSplit(
                estimator=SAR(support_threshold=1), k=2, min_ratings_per_user=2
            ),
            rec_df,
        ),
    ]

    from mmlspark_tpu.cyber import (
        AccessAnomaly,
        ComplementSampler,
        LinearScalarScaler,
        StandardScalarScaler,
        synthetic_access_df,
    )

    access_df = synthetic_access_df(
        n_departments=2, users_per_dept=3, resources_per_dept=3, accesses_per_user=5
    )
    scaler_df = DataFrame.from_dict(
        {"tenant": np.array([0, 0, 1, 1]), "v": np.array([1.0, 2.0, 3.0, 5.0])}
    )
    comp_df = DataFrame.from_dict(
        {
            "user_idx": np.array([0, 1], np.int64),
            "res_idx": np.array([0, 1], np.int64),
            "rating": np.ones(2),
        }
    )
    from mmlspark_tpu.image import (
        ImageSetAugmenter,
        ImageTransformer,
        ResizeImageTransformer,
        UnrollBinaryImage,
        UnrollImage,
    )

    png_blob = (
        b"\x89PNG\r\n\x1a\n" + b"\x00" * 8  # sentinel: decode fails -> 1x1 fallback
    )
    blobs = np.empty(1, dtype=object)
    blobs[0] = png_blob
    objs += [
        TestObject(ImageTransformer().resize(6, 6).flip(), img_df),
        TestObject(UnrollImage(), img_df),
        TestObject(UnrollBinaryImage(), DataFrame.from_dict({"image": blobs})),
        TestObject(ResizeImageTransformer(height=6, width=6), img_df),
        TestObject(ImageSetAugmenter(), img_df),
    ]

    objs += [
        TestObject(AccessAnomaly(rank=2, max_iter=3), access_df),
        TestObject(StandardScalarScaler(input_col="v", partition_key="tenant"), scaler_df),
        TestObject(LinearScalarScaler(input_col="v", partition_key="tenant"), scaler_df),
        TestObject(ComplementSampler(factor=1.0), comp_df),
    ]
    return objs


TEST_OBJECTS = make_test_objects()
_ids = [f"{type(o.stage).__name__}_{i}" for i, o in enumerate(TEST_OBJECTS)]


@pytest.mark.parametrize("obj", TEST_OBJECTS, ids=_ids)
def test_experiment_fuzzing(obj):
    out = run_stage(obj.stage, obj.fit_df, obj.df)
    assert out.count() >= 0  # materialized without raising


@pytest.mark.parametrize("obj", TEST_OBJECTS, ids=_ids)
def test_serialization_fuzzing(obj, tmp_path):
    if obj.skip_serialization:
        pytest.skip("unserializable stage")
    stage = obj.stage
    path = str(tmp_path / "stage")
    stage.save(path)
    stage2 = load_stage(path)
    out1 = run_stage(stage, obj.fit_df, obj.df)
    out2 = run_stage(stage2, obj.fit_df, obj.df)
    assert_df_equal(out1, out2, atol=obj.atol)


@pytest.mark.parametrize("obj", TEST_OBJECTS, ids=_ids)
def test_pipeline_serialization_fuzzing(obj, tmp_path):
    if obj.skip_serialization:
        pytest.skip("unserializable stage")
    pipe = Pipeline([obj.stage])
    model = pipe.fit(obj.fit_df)
    path = str(tmp_path / "pm")
    model.save(path)
    m2 = PipelineModel.load(path)
    assert_df_equal(model.transform(obj.df), m2.transform(obj.df), atol=obj.atol)


# Stages that are intentionally not in the TestObject catalog (bases,
# test-local helpers, stages needing special environments covered in their
# own test modules).
EXCLUDED = {
    # abstract/base-ish
    "Pipeline", "PipelineModel", "HasMiniBatcher", "CognitiveServiceBase",
    # covered by dedicated suites with model/zoo setup
    "XLAModel", "ImageFeaturizer", "CausalLMScorer",
    # network-bound: fuzzed against a live localhost server in test_io.py
    "HTTPTransformer", "SimpleHTTPTransformer",
    # fitted-model classes produced by their estimator (estimator is covered)
    "ClassBalancerModel", "CleanMissingDataModel", "FeaturizeModel",
    "ValueIndexerModel", "TextFeaturizerModel", "MeanShiftModel",
    "LogisticRegressionModel", "LinearRegressionModel",
    "TrainedClassifierModel", "TrainedRegressorModel", "OneVsRestModel",
    "TuneHyperparametersModel", "FindBestModelResult",
    "LightGBMClassificationModel", "LightGBMRegressionModel", "LightGBMRankerModel",
    "VowpalWabbitClassificationModel", "VowpalWabbitRegressionModel",
    "VowpalWabbitContextualBanditModel",
    "KNNModel", "ConditionalKNNModel", "TabularLIMEModel",
    "RecommendationIndexerModel", "SARModel", "RankingAdapterModel",
    "RankingTrainValidationSplitModel", "IsolationForestModel",
    "AccessAnomalyModel", "StandardScalarScalerModel", "LinearScalarScalerModel",
    "MultiColumnAdapterModel",
    "ImageMean",  # test-local inner model for ImageLIME fuzzing
    # test-local helper stages
    "AddOne", "MeanShift", "Holder", "Scale", "Center", "CenterModel", "T",
}


def test_all_stages_covered():
    covered = {type(o.stage).__name__ for o in TEST_OBJECTS}
    missing = []
    for name in STAGE_REGISTRY:
        if name in EXCLUDED or name.startswith("_"):
            continue
        if name not in covered:
            missing.append(name)
    assert not missing, f"stages lacking fuzzing TestObjects: {sorted(missing)}"
