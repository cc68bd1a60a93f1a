from mmlspark_tpu import obs

with obs.span("mmlspark.import", attrs={"module": __name__}):
    from mmlspark_tpu.models.gbdt.binning import BinMapper, BinnedDataset
    from mmlspark_tpu.models.gbdt.sketch import QuantileSketch
    from mmlspark_tpu.models.gbdt.booster import Booster, Tree
    from mmlspark_tpu.models.gbdt.checkpoint import (
        TrainCheckpoint,
        load_checkpoint,
        save_checkpoint,
    )
    from mmlspark_tpu.models.gbdt.delegate import LightGBMDelegate
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train
    from mmlspark_tpu.models.gbdt.estimators import (
        LightGBMClassificationModel,
        LightGBMClassifier,
        LightGBMRanker,
        LightGBMRankerModel,
        LightGBMRegressionModel,
        LightGBMRegressor,
    )

__all__ = [
    "BinMapper",
    "BinnedDataset",
    "QuantileSketch",
    "Booster",
    "Tree",
    "LightGBMDelegate",
    "TrainConfig",
    "train",
    "TrainCheckpoint",
    "save_checkpoint",
    "load_checkpoint",
    "LightGBMClassifier",
    "LightGBMClassificationModel",
    "LightGBMRegressor",
    "LightGBMRegressionModel",
    "LightGBMRanker",
    "LightGBMRankerModel",
]
