"""mmlspark_tpu: a TPU-native ML pipeline framework.

A ground-up rebuild of the capabilities of MMLSpark (Microsoft Machine
Learning for Apache Spark) designed for TPU hardware: DataFrame pipelines
whose compute stages lower to jitted XLA programs, distributed via
``jax.sharding`` meshes and ICI/DCN collectives instead of JVM sockets.

Reference capability map: see SURVEY.md at the repo root. The reference
(``/root/reference``, MMLSpark ~1.0.0-rc2) provides SparkML-compatible
estimators/transformers embedding native engines (CNTK, LightGBM, VW,
OpenCV); here those engines are rebuilt TPU-first (JAX/XLA/Pallas) with a
lightweight partitioned-columnar DataFrame as the dataflow substrate.
"""

# obs is stdlib-only and comes first: the span times the imports below, and
# a package imported inside it (models, models.gbdt) nests its own span
from mmlspark_tpu import obs

with obs.span("mmlspark.import", attrs={"module": __name__}):
    from mmlspark_tpu.version import __version__

    from mmlspark_tpu.core.dataframe import DataFrame, Row
    from mmlspark_tpu.core.pipeline import (
        Estimator,
        Model,
        Pipeline,
        PipelineModel,
        Transformer,
        load_stage,
    )

__all__ = [
    "__version__",
    "DataFrame",
    "Row",
    "Transformer",
    "Estimator",
    "Model",
    "Pipeline",
    "PipelineModel",
    "load_stage",
]
