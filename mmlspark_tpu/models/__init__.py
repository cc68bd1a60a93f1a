from mmlspark_tpu import obs

# the heavy imports of the package (causal_lm brings jax.experimental.pallas)
with obs.span("mmlspark.import", attrs={"module": __name__}):
    from mmlspark_tpu.models.xla_model import XLAModel
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
    from mmlspark_tpu.models.causal_lm import CausalLMScorer
    from mmlspark_tpu.models import resnet
    from mmlspark_tpu.models import sequence
    from mmlspark_tpu.models import vit

__all__ = ["XLAModel", "ImageFeaturizer", "CausalLMScorer", "resnet", "sequence", "vit"]
