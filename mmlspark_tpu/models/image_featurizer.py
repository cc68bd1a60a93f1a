"""ImageFeaturizer — images to feature vectors through a zoo backbone.

Reference: image/ImageFeaturizer.scala:133-178 composes
Resize -> UnrollImage -> CNTKModel with ``cutOutputLayers`` truncating the
head so the net becomes a featurizer (:96-104); layer names come from the
model schema (:121-129).

TPU design: resize + normalize + backbone run as ONE jitted XLA program per
fixed batch shape — preprocessing fuses into the model instead of
materializing intermediate columns. ``cut_output_layers=k`` selects the
k-th entry of the schema's ``layer_names`` (0 = logits, 1 = pooled
features), and XLA prunes every head past it.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame, Partition
from mmlspark_tpu.core.params import (
    ComplexParam,
    HasBatchSize,
    HasInputCol,
    HasOutputCol,
    Param,
)
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.core.schema import image_row_to_array
from mmlspark_tpu.downloader.zoo import ModelDownloader
from mmlspark_tpu.models.xla_model import XLAModel
from mmlspark_tpu.ops import image as image_ops


class ImageFeaturizer(Model, HasInputCol, HasOutputCol, HasBatchSize):
    # default = the zoo entry with COMMITTED TRAINED weights
    # (mmlspark_tpu/downloader/builtin/, tools/train_zoo_backbone.py);
    # the large ResNet variants stay selectable for scale benchmarking
    model_name = Param("zoo model name", default="ResNet8_Digits", type_=str)
    cut_output_layers = Param(
        "how many output layers to drop (0=logits, 1=pooled features)",
        default=1,
        type_=int,
    )
    repo_dir = Param("model repository directory", type_=str)
    drop_na = Param("drop rows whose image failed to decode", default=True, type_=bool)
    apply_fn = ComplexParam("override: jittable (variables, images_f32) -> dict")
    variables = ComplexParam("override: backbone variables")
    image_size = Param("input resolution override", type_=int)
    bgr_input = Param(
        "treat incoming channel order as BGR (reference image format)",
        default=False,
        type_=bool,
    )

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._inner: Optional[XLAModel] = None
        self._schema: Any = None

    # -- model assembly ------------------------------------------------------

    def _build(self) -> XLAModel:
        if self._inner is not None:
            return self._inner
        if self.is_set("apply_fn") and self.is_set("variables"):
            apply_fn, variables = self.get("apply_fn"), self.get("variables")
            layer_names = ["logits", "pool"]
            size = self.get("image_size") or 224
        else:
            repo = ModelDownloader(self.get("repo_dir")) if self.get("repo_dir") else ModelDownloader()
            module, variables, schema = repo.load(self.get("model_name"))
            self._schema = schema
            layer_names = schema.layer_names
            size = self.get("image_size") or schema.image_size

            def apply_fn(vs: Any, x: Any) -> Any:
                return module.apply(vs, x, train=False)

        cut = self.get("cut_output_layers")
        if not 0 <= cut < len(layer_names):
            raise ValueError(
                f"cut_output_layers={cut} out of range for layers {layer_names}"
            )
        node = layer_names[cut]
        bgr = self.get("bgr_input")

        def full_fn(vs: Any, x: Any) -> Any:
            # x: (N,H,W,C) float32 raw pixels 0..255; entire preprocess is
            # inside the jitted program so it fuses with the backbone
            # named for the device trace (Flax names the backbone's modules)
            with jax.named_scope("featurize.preprocess"):
                if bgr:
                    x = image_ops.bgr_to_rgb(x)
                x = image_ops.resize(x, size, size)
                x = image_ops.normalize(x)
            out = apply_fn(vs, x)
            return out[node] if isinstance(out, dict) else out

        self._inner = XLAModel(
            input_col="__pixels__",
            output_col=self.get_or_fail("output_col"),
            batch_size=self.get("batch_size"),
            # keep host dtype: uint8 pixel batches transfer 4x less and the
            # program's leading resize casts to f32 on device anyway
            input_dtype=None,
        )
        self._inner.set(apply_fn=full_fn, variables=variables)
        return self._inner

    # -- host-side image coercion -------------------------------------------

    def _coerce_images(self, col: np.ndarray) -> tuple:
        """image structs / bytes / dense tensors -> ((N,H,W,C) float32, keep mask)."""
        if col.dtype != object:
            # uint8 pixel tensors stay uint8 (device-side cast; cheaper copy)
            x = col if col.dtype == np.uint8 else col.astype(np.float32)
            if x.ndim == 2:  # unrolled vectors: roll back using model size
                size = self.get("image_size") or (
                    self._schema.image_size if self._schema else 224
                )
                # unrolled layout is always reference CHW/BGR. With
                # bgr_input=False, convert to RGB here (roll bgr=True);
                # with bgr_input=True keep BGR planes (roll bgr=False) so
                # full_fn's single bgr_to_rgb flip lands on RGB — never two.
                x = np.asarray(
                    image_ops.roll(
                        jnp.asarray(x), size, size, bgr=not self.get("bgr_input")
                    )
                )
            return x, np.ones(len(x), bool)
        rows = []
        for r in col:
            if isinstance(r, (bytes, bytearray)):
                arr = image_ops.decode_image(bytes(r))
            elif r is None:
                arr = None
            else:
                arr = image_row_to_array(r)
            rows.append(arr)
        keep = np.array([a is not None for a in rows], dtype=bool)
        if not keep.all() and not self.get("drop_na"):
            raise ValueError("undecodable image rows present and drop_na=False")
        good = [np.asarray(a) for a in rows if a is not None]
        if not good:
            return np.zeros((0, 1, 1, 3), np.float32), keep
        # decoded JPEG/PNG arrive uint8 — keep them uint8 so the batch ships
        # to the device at 1 byte/px (the program casts on device)
        if all(a.dtype == np.uint8 for a in good):
            return np.stack(good), keep
        return np.stack([a.astype(np.float32) for a in good]), keep

    def pipeline_io(self) -> tuple:
        """Column deps for the pipeline compiler."""
        return (self.get_or_fail("input_col"),), (self.get_or_fail("output_col"),)

    @property
    def pipeline_row_preserving(self) -> bool:
        # drop_na may remove undecodable rows at runtime (object inputs
        # only) — the scheduler must not reorder branches around that
        return not self.get("drop_na")

    def fusable_kernel(self) -> Any:
        """Fusable for dense (N,H,W,C) pixel batches: the whole
        preprocess+backbone program (already one jitted fn in the staged
        path) traces into the fused segment with the weights as constants.
        Object columns (bytes/structs needing host decode) and unrolled
        2-D layouts guard-fall back to the staged path.

        ``exact_capable=False``: convolution lowerings are not bit-stable
        across batch shapes, so exact-mode compilation (the default) keeps
        this stage host-bound; ``compile(exact=False)`` fuses the backbone
        into the segment at allclose-level equality."""
        from mmlspark_tpu.compiler.kernels import StageKernel

        ic = self.get_or_fail("input_col")
        oc = self.get_or_fail("output_col")
        inner = self._build()
        apply_fn = inner.get_or_fail("apply_fn")
        variables = inner.get_or_fail("variables")

        def fn(cols: dict) -> dict:
            return {oc: apply_fn(variables, cols[ic])}

        def guard(cols: dict) -> Any:
            a = np.asarray(cols.get(ic))
            if a.dtype == object:
                return "object image column (host decode path)"
            if a.ndim != 4:
                return f"image column ndim={a.ndim} (unrolled host path)"
            return None

        return StageKernel(reads=(ic,), writes=(oc,), fn=fn, guard=guard,
                           cost_hint=20.0, exact_capable=False)

    def transform(self, df: DataFrame) -> DataFrame:
        ic = self.get_or_fail("input_col")
        inner = self._build()

        def fn(p: Partition) -> Partition:
            # one trace per partition; apply_batch's spans are its children
            with obs.span("featurize.partition", attrs={"rows": len(p[ic])}):
                with obs.span("featurize.coerce"):
                    x, keep = self._coerce_images(p[ic])
                feats = inner.apply_batch(x) if len(x) else np.zeros((0, 1), np.float32)
                q = dict(p)
                if not keep.all():  # undecodable rows dropped from every column
                    q = {k: v[keep] for k, v in p.items()}
                q[self.get_or_fail("output_col")] = feats
                return q

        return df.map_partitions(fn, parallel=False)
