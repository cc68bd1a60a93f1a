"""Plain ResNet-50 (He et al., arXiv:1512.03385, Table 1) in jax.numpy.

The benchmark's reference for the featurizer cell: preprocessing and the
forward pass to the pooled 2,048 features, float32, every contraction at
``precision=HIGHEST``, no kernels, no batching tricks. It imports nothing
of the program. Weights are a flat dict ``{name: array}`` made by
:func:`make_weights` from the seed; the driver hands the same values to the
program in the program's own tree.

Layout follows the paper, with the stride-2 convolution of a bottleneck on
its 3x3 (the common "v1.5" reading, which the configuration's file states
under ``assumed``). Padding is XLA's SAME, as the configuration is run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
BN_EPS = 1e-5


def conv_table(config: dict) -> list:
    """Every convolution as (name, k, stride, c_in, c_out, h_in) in order."""
    size, width = config["image_size"], config["num_filters"]
    exp = config["bottleneck_expansion"]
    out = [("stem", 7, 2, config["channels"], width, size)]
    h = -(-size // 2)          # stem stride 2
    h = -(-h // 2)             # max-pool stride 2
    c_in = width
    for i, blocks in enumerate(config["stage_sizes"]):
        f = width * 2 ** i
        for j in range(blocks):
            s = 2 if i > 0 and j == 0 else 1
            p = f"s{i}b{j}"
            out.append((p + ".c1", 1, 1, c_in, f, h))
            out.append((p + ".c2", 3, s, f, f, h))
            h_out = -(-h // s)
            out.append((p + ".c3", 1, 1, f, f * exp, h_out))
            if s != 1 or c_in != f * exp:
                out.append((p + ".proj", 1, s, c_in, f * exp, h))
            c_in, h = f * exp, h_out
    return out


def make_weights(config: dict, key: jax.Array) -> dict:
    """Seeded weights, made on the default device in one traced call.

    He-normal convolutions; BatchNorm scale 0.75..1.25, bias and running
    mean N(0, 0.1), running variance 0.75..1.25; the last BatchNorm of a
    block is scaled by 0.25 so that every block adds to its residual
    without the activations growing through 16 blocks."""
    table = conv_table(config)

    def build(key: jax.Array) -> dict:
        w = {}
        for n, (name, k, _s, c_in, c_out, _h) in enumerate(table):
            ks = jax.random.split(jax.random.fold_in(key, n), 5)
            std = np.sqrt(2.0 / (k * k * c_in))
            w[name + ".w"] = std * jax.random.normal(ks[0], (k, k, c_in, c_out), jnp.float32)
            last = 0.25 if name.endswith(".c3") else 1.0
            w[name + ".scale"] = last * jax.random.uniform(ks[1], (c_out,), jnp.float32, 0.75, 1.25)
            w[name + ".bias"] = 0.1 * jax.random.normal(ks[2], (c_out,), jnp.float32)
            w[name + ".mean"] = 0.1 * jax.random.normal(ks[3], (c_out,), jnp.float32)
            w[name + ".var"] = jax.random.uniform(ks[4], (c_out,), jnp.float32, 0.75, 1.25)
        return w

    return jax.jit(build)(key)


def _round_to(x: jnp.ndarray, dtype: object) -> jnp.ndarray:
    """Round a tensor to ``dtype`` and back, scaled per tensor so that its
    largest magnitude sits at the type's largest finite value."""
    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def forward(weights: dict, pixels: jnp.ndarray, config: dict,
            lower_dtype: object = None) -> jnp.ndarray:
    """uint8 (n, H, W, 3) pixels -> float32 (n, 2048) pooled features.

    ``lower_dtype`` is the control: every convolution's input and kernel
    are rounded to that type (float8 for a bfloat16 configuration) before
    the float32 contraction; ``None`` is the reference."""
    hi = jax.lax.Precision.HIGHEST

    def cbn(x: jnp.ndarray, name: str, stride: int, relu: bool) -> jnp.ndarray:
        y = jax.lax.conv_general_dilated(
            _round_to(x, lower_dtype), _round_to(weights[name + ".w"], lower_dtype),
            (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        inv = weights[name + ".scale"] / jnp.sqrt(weights[name + ".var"] + BN_EPS)
        y = (y - weights[name + ".mean"]) * inv + weights[name + ".bias"]
        return jnp.maximum(y, 0.0) if relu else y

    x = pixels.astype(jnp.float32) * (1.0 / 255.0)
    x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
    x = cbn(x, "stem", 2, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    names = {row[0]: row for row in conv_table(config)}
    for i, blocks in enumerate(config["stage_sizes"]):
        for j in range(blocks):
            p = f"s{i}b{j}"
            s = names[p + ".c2"][2]
            y = cbn(x, p + ".c1", 1, True)
            y = cbn(y, p + ".c2", s, True)
            y = cbn(y, p + ".c3", 1, False)
            r = cbn(x, p + ".proj", s, False) if p + ".proj" in names else x
            x = jnp.maximum(y + r, 0.0)
    return jnp.mean(x, axis=(1, 2))


def features_in_blocks(weights: dict, pixels: np.ndarray, config: dict,
                       block: int = 64, lower_dtype: object = None) -> np.ndarray:
    """The reference over many rows, ``block`` at a time so that it fits."""
    fn = jax.jit(lambda w, p: forward(w, p, config, lower_dtype))
    out = []
    for i in range(0, len(pixels), block):
        chunk = pixels[i:i + block]
        n = len(chunk)
        if n < block:  # one shape, one program
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], block - n, 0)])
        out.append(np.asarray(fn(weights, chunk))[:n])
    return np.concatenate(out)
