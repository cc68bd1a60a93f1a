"""Train the natural-image zoo backbone from committed data.

The reference's zoo ships backbones trained on natural images
(downloader/ModelDownloader.scala:210-276); this egress-free build trains
its own: a width-32 ResNet-18 pretrained SELF-SUPERVISED on 32x32 patches
of the two natural photographs that ship with scikit-learn
(``sklearn.datasets.load_sample_images``: 'china.jpg', 'flower.jpg') using
rotation prediction (RotNet, Gidaris et al. 2018) — predicting which of
{0, 90, 180, 270} degrees a patch was rotated forces the network to learn
real visual structure (edges, orientation, texture, layout), which is what
makes the features TRANSFER.

Holdout discipline: training patches come only from the LEFT 75% of each
photo; the right strip is never seen, and the transfer gate
(tests/test_zoo_weights.py) probes features there.

Reproduce:  PYTHONPATH=. python tools/train_patch_backbone.py
            (uses the default JAX backend: a TPU finishes in ~2 min; on
            CPU expect ~30 min. Deterministic given the fixed seed.)
The checkpoint is stored float16 (~5.6 MB) and restored to f32 by
ModelDownloader.load.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

SEED = 11
PATCH = 32
N_PATCHES = 40_960
BATCH = 512
EPOCHS = 12
WIDTH = 32          # ResNet-18 at num_filters=32: ~2.8M params
TRAIN_FRACTION = 0.75  # left fraction of each photo used for training


def sample_patches(rng: np.ndarray, n: int, train_region: bool = True) -> np.ndarray:
    """(n, PATCH, PATCH, 3) uint8 patches from the committed photos."""
    from sklearn.datasets import load_sample_images

    images = load_sample_images().images  # [china, flower], (427, 640, 3) u8
    out = np.empty((n, PATCH, PATCH, 3), np.uint8)
    for i in range(n):
        img = images[int(rng.integers(2))]
        h, w = img.shape[:2]
        cut = int(w * TRAIN_FRACTION)
        if train_region:
            x0 = int(rng.integers(0, cut - PATCH))
        else:
            x0 = int(rng.integers(cut, w - PATCH))
        y0 = int(rng.integers(0, h - PATCH))
        out[i] = img[y0: y0 + PATCH, x0: x0 + PATCH]
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.core.compile_cache import enable_compile_cache
    from mmlspark_tpu.downloader.zoo import ModelDownloader, ModelSchema
    from mmlspark_tpu.models.resnet import resnet18
    from mmlspark_tpu.ops.image import normalize

    enable_compile_cache()
    rng = np.random.default_rng(SEED)
    patches = sample_patches(rng, N_PATCHES)
    rot = rng.integers(0, 4, N_PATCHES)
    x = np.stack([np.rot90(p, k) for p, k in zip(patches, rot)])
    y = rot.astype(np.int32)
    n_val = 2048
    xtr, ytr = x[:-n_val], y[:-n_val]
    xva, yva = x[-n_val:], y[-n_val:]

    model = resnet18(num_classes=4, small_inputs=True, num_filters=WIDTH)
    variables = model.init(
        jax.random.PRNGKey(SEED),
        jnp.zeros((1, PATCH, PATCH, 3), jnp.float32), train=True,
    )
    params, batch_stats = variables["params"], variables["batch_stats"]
    steps_per_epoch = len(xtr) // BATCH
    tx = optax.adamw(
        optax.cosine_decay_schedule(3e-3, EPOCHS * steps_per_epoch),
        weight_decay=1e-4,
    )
    opt_state = tx.init(params)

    def one_step(carry, idx):
        params, batch_stats, opt_state = carry
        xb = normalize(xtr_dev[idx].astype(jnp.float32))
        yb = ytr_dev[idx]

        def loss_fn(p):
            out, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                xb, train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                out["logits"], yb
            ).mean()
            return loss, mut["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, batch_stats, opt_state), loss

    # whole epoch = ONE dispatch (lax.scan over shuffled minibatches): the
    # same fusion pattern as the GBDT trainer
    @jax.jit
    def run_epoch(params, batch_stats, opt_state, key):
        perm = jax.random.permutation(key, len(xtr))[: steps_per_epoch * BATCH]
        idxs = perm.reshape(steps_per_epoch, BATCH)
        (params, batch_stats, opt_state), losses = jax.lax.scan(
            one_step, (params, batch_stats, opt_state), idxs
        )
        return params, batch_stats, opt_state, losses.mean()

    @jax.jit
    def accuracy(params, batch_stats, xb, yb):
        out = model.apply(
            {"params": params, "batch_stats": batch_stats},
            normalize(xb.astype(jnp.float32)), train=False,
        )
        return (out["logits"].argmax(-1) == yb).mean()

    xtr_dev = jax.device_put(jnp.asarray(xtr))
    ytr_dev = jax.device_put(jnp.asarray(ytr))
    xva_dev, yva_dev = jnp.asarray(xva), jnp.asarray(yva)
    for epoch in range(EPOCHS):
        t0 = time.time()
        params, batch_stats, opt_state, loss = run_epoch(
            params, batch_stats, opt_state, jax.random.PRNGKey(1000 + epoch)
        )
        acc = float(accuracy(params, batch_stats, xva_dev, yva_dev))
        print(
            f"epoch {epoch}: loss {float(loss):.4f} "
            f"rot-acc {acc:.4f} ({time.time() - t0:.1f}s)", flush=True,
        )
    assert acc > 0.75, f"rotation pretraining failed to learn (acc={acc})"

    to_np16 = lambda t: np.asarray(t, np.float16)  # noqa: E731
    variables = {
        "params": jax.tree_util.tree_map(to_np16, params),
        "batch_stats": jax.tree_util.tree_map(to_np16, batch_stats),
    }
    from mmlspark_tpu.downloader.zoo import PACKAGED_DIR

    schema = ModelSchema(
        name="ResNet18_Patches",
        variant="ResNet18",
        num_classes=4,
        image_size=PATCH,
        small_inputs=True,
        num_filters=WIDTH,
        seed=SEED,
    )
    dl = ModelDownloader(repo_dir=PACKAGED_DIR)
    dl.register(schema, variables)
    print("packaged", os.path.join(PACKAGED_DIR, "ResNet18_Patches.msgpack"))


if __name__ == "__main__":
    main()
