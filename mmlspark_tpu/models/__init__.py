from mmlspark_tpu.models.xla_model import XLAModel
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu.models.causal_lm import CausalLMScorer
from mmlspark_tpu.models import resnet
from mmlspark_tpu.models import sequence
from mmlspark_tpu.models import vit

__all__ = ["XLAModel", "ImageFeaturizer", "CausalLMScorer", "resnet", "sequence", "vit"]
