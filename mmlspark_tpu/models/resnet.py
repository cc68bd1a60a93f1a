"""ResNet family in Flax — the deep-image backbone of the model zoo.

The reference ships CNTK model-zoo graphs (ResNet50 etc.) evaluated by the
CNTK JNI engine (downloader/ModelDownloader.scala, image/ImageFeaturizer
.scala:121-129). Here the backbone is a Flax module compiled by XLA for the
MXU: bf16 activations, fused conv+bn+relu, static shapes.

``apply_with_layers`` returns *named intermediate outputs* so
ImageFeaturizer can truncate output layers by name/count — the
``cutOutputLayers``/``layerNames`` capability (ImageFeaturizer.scala:96-129)
without graph surgery: XLA dead-code-eliminates branches that aren't used.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

ModuleDef = Any


def _conv_padding(kernel: int, strides: int, torch_padding: bool):
    """'SAME' unless torch parity is requested on a STRIDED conv.

    At stride 1 XLA's SAME padding equals torch's symmetric (k-1)//2; at
    stride 2 SAME becomes asymmetric ((0,1) for 3x3, (2,3) for 7x7) while
    torch stays symmetric — importing torchvision weights without matching
    this shifts every strided feature map by a pixel."""
    if torch_padding and strides > 1:
        p = (kernel - 1) // 2
        return ((p, p), (p, p))
    return "SAME"


class BottleneckBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    torch_padding: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9, dtype=self.dtype
        )
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = nn.relu(norm()(y))
        y = conv(
            self.filters, (3, 3), strides=(self.strides, self.strides),
            padding=_conv_padding(3, self.strides, self.torch_padding),
        )(y)
        y = nn.relu(norm()(y))
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1), strides=(self.strides, self.strides), name="proj")(residual)
            residual = norm(name="proj_bn")(residual)
        return nn.relu(y + residual)


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    torch_padding: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9, dtype=self.dtype
        )
        residual = x
        y = conv(
            self.filters, (3, 3), strides=(self.strides, self.strides),
            padding=_conv_padding(3, self.strides, self.torch_padding),
        )(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1), strides=(self.strides, self.strides), name="proj")(residual)
            residual = norm(name="proj_bn")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """ResNet with named stage outputs.

    Layer-name order (outermost last) mirrors the reference's model schema
    ``layerNames`` ordering used by ``cutOutputLayers``:
    ["logits", "pool", "layer4", "layer3", "layer2", "layer1", "stem"].
    """

    stage_sizes: Sequence[int]
    block: type = BottleneckBlock
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    small_inputs: bool = False  # CIFAR-style stem (3x3, no maxpool)
    # torch-exact padding on strided convs/pool so torchvision-imported
    # weights reproduce torchvision features (see _conv_padding)
    torch_padding: bool = False

    LAYER_NAMES = ("logits", "pool", "layer4", "layer3", "layer2", "layer1", "stem")

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> dict:
        outputs: dict = {}
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9, dtype=self.dtype
        )
        x = x.astype(self.dtype)
        if self.small_inputs:
            x = conv(self.num_filters, (3, 3), name="conv_init")(x)
        else:
            x = conv(
                self.num_filters, (7, 7), strides=(2, 2), name="conv_init",
                padding=_conv_padding(7, 2, self.torch_padding),
            )(x)
        x = nn.relu(norm(name="bn_init")(x))
        if not self.small_inputs:
            pool_pad = ((1, 1), (1, 1)) if self.torch_padding else "SAME"
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=pool_pad)
        outputs["stem"] = x
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block(
                    filters=self.num_filters * 2 ** i,
                    strides=strides,
                    dtype=self.dtype,
                    torch_padding=self.torch_padding,
                )(x, train=train)
            outputs[f"layer{i + 1}"] = x
        x = jnp.mean(x, axis=(1, 2))
        outputs["pool"] = x.astype(jnp.float32)
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        outputs["logits"] = x.astype(jnp.float32)
        return outputs


def resnet8(**kw: Any) -> ResNet:
    """Three-stage compact ResNet (~80k params at width 16): small enough
    to train in-repo and commit trained weights to the zoo, the committed
    counterpart of the reference's downloaded model files
    (downloader/Schema.scala:54-66)."""
    kw.setdefault("num_filters", 16)
    return ResNet(stage_sizes=[1, 1, 1], block=BasicBlock, **kw)


def resnet18(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=[2, 2, 2, 2], block=BasicBlock, **kw)


def resnet34(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 6, 3], block=BasicBlock, **kw)


def resnet50(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 6, 3], block=BottleneckBlock, **kw)


def resnet101(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 23, 3], block=BottleneckBlock, **kw)


RESNETS: dict = {
    "ResNet8": resnet8,
    "ResNet18": resnet18,
    "ResNet34": resnet34,
    "ResNet50": resnet50,
    "ResNet101": resnet101,
}


def init_resnet(
    name: str = "ResNet50",
    num_classes: int = 1000,
    image_size: int = 224,
    seed: int = 0,
    small_inputs: bool = False,
    dtype: Any = jnp.bfloat16,
    num_filters: int = 64,
) -> tuple:
    """Build a ResNet and init variables. Returns (module, variables).

    Init always runs on the host CPU backend: weight materialization is a
    one-off that needs no accelerator, and the seeded weights must not
    depend on which device made them.
    """
    model = RESNETS[name](
        num_classes=num_classes, small_inputs=small_inputs, dtype=dtype,
        num_filters=num_filters,
    )
    # host-side allocation: a jnp.zeros here would already dispatch to the
    # default backend before the CPU scope below
    dummy = np.zeros((1, image_size, image_size, 3), np.float32)
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    if cpu is not None:
        with jax.default_device(cpu):
            variables = jax.jit(
                lambda: model.init(jax.random.PRNGKey(seed), dummy, train=False)
            )()
        variables = jax.tree_util.tree_map(np.asarray, variables)
    else:
        variables = model.init(jax.random.PRNGKey(seed), dummy, train=False)
    return model, variables
