"""Train the packaged zoo backbone from committed data.

The reference ships a zoo of trained CNTK models fetched from a remote
repository (downloader/Schema.scala:54-66, ModelDownloader.scala:210-276).
This build is egress-free, so the zoo's trained entry is produced HERE —
a compact ResNet8 trained on the committed UCI digits dataset
(tests/resources/data/digits.csv, 1797 8x8 grayscale digits) — and the
resulting checkpoint + schema are committed under
mmlspark_tpu/downloader/builtin/.

Reproduce:  PYTHONPATH=. JAX_PLATFORMS=cpu python tools/train_zoo_backbone.py
Runtime:    ~2 min on CPU. Deterministic given the fixed seed.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from mmlspark_tpu.downloader.zoo import PACKAGED_DIR, ModelDownloader, ModelSchema
from mmlspark_tpu.models.resnet import resnet8

SEED = 7
IMAGE_SIZE = 32
EPOCHS = 40
BATCH = 128
# deterministic split: last 297 rows held out, never trained on (the
# transfer-learning test evaluates its linear heads there)
N_TRAIN = 1500


def load_digits() -> tuple:
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "tests", "resources", "data", "digits.csv",
    )
    raw = np.genfromtxt(path, delimiter=",", skip_header=1)
    x, y = raw[:, :64].reshape(-1, 8, 8), raw[:, 64].astype(np.int32)
    return x, y


def digits_to_images(x8: np.ndarray, size: int = IMAGE_SIZE) -> np.ndarray:
    """8x8 [0,16] grayscale -> (n, size, size, 3) float32 NORMALIZED with
    the exact preprocessing ImageFeaturizer applies (ops/image.normalize:
    /255 then ImageNet mean/std) so the committed weights see identical
    inputs through the featurizer path."""
    from mmlspark_tpu.ops.image import normalize

    rep = size // 8
    img = np.kron(x8 / 16.0, np.ones((rep, rep)))  # nearest-neighbor upsample
    rgb255 = np.repeat(img[..., None], 3, axis=-1).astype(np.float32) * 255.0
    return np.asarray(normalize(jnp.asarray(rgb255)), np.float32)


def main() -> None:
    from mmlspark_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    x8, y = load_digits()
    imgs = digits_to_images(x8)
    xtr, ytr = imgs[:N_TRAIN], y[:N_TRAIN]

    model = resnet8(num_classes=10, small_inputs=True)
    variables = model.init(jax.random.PRNGKey(SEED), xtr[:1], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    tx = optax.adamw(3e-3, weight_decay=1e-4)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, xb, yb):
        def loss_fn(p):
            out, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                xb, train=True, mutable=["batch_stats"],
            )
            logits = out["logits"]
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()
            return loss, (mut["batch_stats"], logits)

        (loss, (bs, logits)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        acc = (logits.argmax(-1) == yb).mean()
        return params, bs, opt_state, loss, acc

    rng = np.random.default_rng(SEED)
    n = len(xtr)
    for epoch in range(EPOCHS):
        order = rng.permutation(n)
        losses, accs = [], []
        for i in range(0, n - BATCH + 1, BATCH):
            idx = order[i : i + BATCH]
            params, batch_stats, opt_state, loss, acc = step(
                params, batch_stats, opt_state, jnp.asarray(xtr[idx]), jnp.asarray(ytr[idx])
            )
            losses.append(float(loss))
            accs.append(float(acc))
        print(f"epoch {epoch}: loss={np.mean(losses):.4f} acc={np.mean(accs):.4f}")

    # eval on the held-out tail (not used for model selection — reporting only)
    out = model.apply(
        {"params": params, "batch_stats": batch_stats}, jnp.asarray(imgs[N_TRAIN:]),
        train=False,
    )
    test_acc = float((np.asarray(out["logits"]).argmax(-1) == y[N_TRAIN:]).mean())
    print(f"held-out acc: {test_acc:.4f}")

    schema = ModelSchema(
        name="ResNet8_Digits",
        variant="ResNet8",
        num_classes=10,
        image_size=IMAGE_SIZE,
        small_inputs=True,
        layer_names=["logits", "pool", "layer3", "layer2", "layer1", "stem"],
        seed=SEED,
    )
    repo = ModelDownloader(repo_dir=PACKAGED_DIR)
    repo.register(schema, {"params": params, "batch_stats": batch_stats})
    print(f"wrote {PACKAGED_DIR}/ResNet8_Digits.msgpack sha256={schema.sha256}")


if __name__ == "__main__":
    main()
