"""Plain LFM2-MoE (LiquidAI/LFM2-8B-A1B, ``model_type: lfm2_moe``) in jax.numpy.

The benchmark's reference for the corpus-scoring cell: token ids in, the
log-probability of every next token out. float32 throughout, every
contraction under ``jax.default_matmul_precision("highest")``, a dense loop
over all experts, whole score matrices, full logits then ``log_softmax``;
no kernels, no cache, no batching tricks. It imports nothing of the program.

Per layer ``i`` with residual stream ``x`` (RMSNorm eps ``norm_eps``, no
biases anywhere):

    y  = x + Mixer_i(RMSNorm_op(x))
    x' = y + FFN_i(RMSNorm_ffn(y))

- ``layer_types[i] == "conv"``: ``[B, C, X] = split3(W_in u)``, ``z = B*X``,
  ``c[t] = sum_j k[:, j] * z[t-2+j]`` (depthwise, causal, zero before the
  row's start, ``conv_L_cache`` = 3 taps), ``Mixer(u) = W_out (C * c)``.
- ``"full_attention"``: 32 query heads over 8 key/value heads of width 64;
  ``q`` and ``k`` get an RMSNorm over the head width (learned scale)
  *before* RoPE (``rope_theta``, half rotation, positions from 0 in every
  row); causal softmax attention scaled by 64**-0.5; ``W_o``.
- FFN of layers ``i < num_dense_layers``: ``W_2 (silu(W_1 u) * W_3 u)``.
- every other FFN: ``s = sigmoid(W_g u)``; ``S = top_k(s + b)`` with the
  layer's expert bias ``b``; ``w_e = s_e / (sum_{e' in S} s_e' + 1e-6)``
  (from ``s``, not ``s + b``) times ``routed_scaling_factor``; the sum over
  ``S`` of ``w_e * W_2^e (silu(W_1^e u) * W_3^e u)``.
- after the last layer one RMSNorm, then the head: the embedding transposed.

Weights are made from the seed one layer at a time (:func:`make_layer_weights`)
so that neither the reference nor the program's set-up ever holds a float32
copy of the model; every value is rounded to bfloat16 and held as float32, so
the program's bfloat16 copy holds the same numbers. Scales (``assumed`` in the
configuration's file): every sub-layer reads a unit-RMS input and writes about
0.003 RMS — a tenth of the embedding's 0.03 — into the residual stream, which
so grows by about 13% over 14 layers: nothing vanishes, nothing blows up, and
a router's near-tie that a rounding flips moves that token's stream by under
1%, so that the flip does not cascade through the routers of the layers
after it (at eight tenths of the embedding it did: the float32 reference and
its own float8 rounding then agreed on nothing).
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

ROUTE_EPS = 1e-6
EMBED_STD = 0.03      # logits of about unit spread through the tied head
OUT_STD = 0.003       # what a sub-layer adds to the residual stream: a tenth of it
BIAS_STD = 0.02       # expert bias: changes the top-4 set of about 1 token in 3
QK_SCALE = (1.5, 2.0)  # learned q/k norm scales: attention logits of spread ~3


def layer_kind(config: dict, i: int) -> tuple:
    """``(mixer, ffn)`` of layer ``i``: ("conv" | "full_attention", "dense" | "moe")."""
    return config["layer_types"][i], "dense" if i < config["num_dense_layers"] else "moe"


def _bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _uniform(key: jax.Array, shape: tuple, std: float) -> jnp.ndarray:
    a = std * np.sqrt(3.0)
    return _bf16(jax.random.uniform(key, shape, jnp.float32, -a, a))


def _scale(key: jax.Array, n: int, lo: float = 0.8, hi: float = 1.2) -> jnp.ndarray:
    return _bf16(jax.random.uniform(key, (n,), jnp.float32, lo, hi))


def make_embedding(config: dict, key: jax.Array) -> dict:
    """The embedding (also the head: LFM2 ties them) and the last norm."""
    k = jax.random.split(jax.random.fold_in(key, 1_000_000), 2)
    return {"embed": _uniform(k[0], (config["vocab_size"], config["hidden_size"]), EMBED_STD),
            "norm": _scale(k[1], config["hidden_size"])}


def make_layer_weights(config: dict, key: jax.Array, i: int,
                       kind: "tuple | None" = None) -> dict:
    """Layer ``i``'s weights from the seed: float32 arrays of bfloat16 values.

    ``kind`` (default: the layer's published kind) lets one program make
    every layer of a kind, with ``i`` a traced number."""
    h = config["hidden_size"]
    mixer, ffn = kind or layer_kind(config, i)
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    w = {"norm_op": _scale(ks[0], h), "norm_ffn": _scale(ks[1], h)}
    if mixer == "conv":
        w["conv_in"] = _uniform(ks[2], (h, 3 * h), h ** -0.5)
        w["conv_k"] = _uniform(ks[3], (h, config["conv_L_cache"]), config["conv_L_cache"] ** -0.5)
        w["conv_out"] = _uniform(ks[4], (h, h), OUT_STD * h ** -0.5)
    else:
        nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
        d = h // nq
        w["wq"] = _uniform(ks[2], (h, nq * d), h ** -0.5)
        w["wk"] = _uniform(ks[3], (h, nkv * d), h ** -0.5)
        w["wv"] = _uniform(ks[4], (h, nkv * d), h ** -0.5)
        w["wo"] = _uniform(ks[5], (nq * d, h), OUT_STD * (nq * d) ** -0.5)
        w["q_norm"] = _scale(ks[6], d, *QK_SCALE)
        w["k_norm"] = _scale(ks[7], d, *QK_SCALE)
    if ffn == "dense":
        f = config["intermediate_size"]
        w["w1"] = _uniform(ks[8], (h, f), h ** -0.5)
        w["w3"] = _uniform(ks[9], (h, f), h ** -0.5)
        w["w2"] = _uniform(ks[10], (f, h), OUT_STD / 0.6 * f ** -0.5)
    else:
        e, f = config["num_experts"], config["moe_intermediate_size"]
        w["router"] = _uniform(ks[8], (h, e), h ** -0.5)
        w["expert_bias"] = _bf16(config.get("expert_bias_std", BIAS_STD)
                                 * jax.random.normal(ks[9], (e,), jnp.float32))
        w["w1"] = _uniform(ks[10], (e, h, f), h ** -0.5)
        w["w3"] = _uniform(ks[11], (e, h, f), h ** -0.5)
        w["w2"] = _uniform(ks[12], (e, f, h), OUT_STD / 0.3 * f ** -0.5)
    return w


# -- the forward pass, one row (L, h) at a time ------------------------------

def _round_to(x: jnp.ndarray, dtype: object) -> jnp.ndarray:
    """Round a tensor to ``dtype`` and back, scaled per tensor so that its
    largest magnitude sits at the type's largest finite value."""
    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(eq: str, a: jnp.ndarray, b: jnp.ndarray, lower: object) -> jnp.ndarray:
    """Every matrix product of the model; ``lower`` is the control: both
    inputs rounded to that type before the float32 contraction."""
    return jnp.einsum(eq, _round_to(a, lower), _round_to(b, lower),
                      precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv_mixer(w: dict, u: jnp.ndarray, config: dict, lower: object = None) -> jnp.ndarray:
    taps = config["conv_L_cache"]
    b, c, x = jnp.split(_mm("th,hk->tk", u, w["conv_in"], lower), 3, axis=-1)
    z = b * x
    zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    conv = sum(w["conv_k"][:, j] * zp[j:j + z.shape[0]] for j in range(taps))
    return _mm("th,hk->tk", c * conv, w["conv_out"], lower)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Half rotation (as Llama): (L, heads, d), positions 0..L-1."""
    length, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attn_mixer(w: dict, u: jnp.ndarray, config: dict, lower: object = None) -> jnp.ndarray:
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // nq
    length = u.shape[0]
    q = _mm("th,hk->tk", u, w["wq"], lower).reshape(length, nq, d)
    k = _mm("th,hk->tk", u, w["wk"], lower).reshape(length, nkv, d)
    v = _mm("th,hk->tk", u, w["wv"], lower).reshape(length, nkv, d)
    q = rope(rmsnorm(q, w["q_norm"], config["norm_eps"]), config["rope_theta"])
    k = rope(rmsnorm(k, w["k_norm"], config["norm_eps"]), config["rope_theta"])
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = _mm("qhd,khd->hqk", q, k, lower) * d ** -0.5
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, lower).reshape(length, nq * d)
    return _mm("tk,kh->th", o, w["wo"], lower)


def dense_ffn(w: dict, u: jnp.ndarray, lower: object = None) -> jnp.ndarray:
    a = jax.nn.silu(_mm("th,hf->tf", u, w["w1"], lower)) * _mm("th,hf->tf", u, w["w3"], lower)
    return _mm("tf,fh->th", a, w["w2"], lower)


def route(w: dict, u: jnp.ndarray, config: dict) -> jnp.ndarray:
    """(T, E) combine weights: zero outside the selected experts. The
    router is never rounded by the control: a lower precision there picks
    other experts, which is another model and not a rounding of this one."""
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", u, w["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + w["expert_bias"], config["num_experts_per_tok"])
    picked = s * jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    return picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS) * config["routed_scaling_factor"]


def moe_ffn(w: dict, u: jnp.ndarray, config: dict, lower: object = None,
            experts: "tuple | None" = None) -> jnp.ndarray:
    """A dense loop over the experts ``[lo, hi)`` held (all of them by
    default): each computes every token, the routing weight keeps its own."""
    weights = route(w, u, config)
    lo, hi = experts or (0, config["num_experts"])

    def one(acc: jnp.ndarray, e: tuple) -> tuple:
        w1, w3, w2, col = e
        a = jax.nn.silu(_mm("th,hf->tf", u, w1, lower)) * _mm("th,hf->tf", u, w3, lower)
        return acc + col[:, None] * _mm("tf,fh->th", a, w2, lower), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w1"][lo:hi], w["w3"][lo:hi], w["w2"][lo:hi], weights.T[lo:hi]))
    return out


def layer(w: dict, x: jnp.ndarray, config: dict, kind: tuple,
          lower: object = None) -> jnp.ndarray:
    mixer, ffn = kind
    u = rmsnorm(x, w["norm_op"], config["norm_eps"])
    mix = conv_mixer if mixer == "conv" else attn_mixer
    y = x + mix(w, u, config, lower)
    u = rmsnorm(y, w["norm_ffn"], config["norm_eps"])
    if ffn == "dense":
        return y + dense_ffn(w, u, lower)
    return y + moe_ffn(w, u, config, lower)


def head(emb: dict, x: jnp.ndarray, tokens: jnp.ndarray, config: dict,
         lower: object = None) -> jnp.ndarray:
    """(L, h) final stream, (L,) ids -> (L-1,) log p(x[t+1] | x[0..t])."""
    u = rmsnorm(x, emb["norm"], config["norm_eps"])
    logits = _mm("th,vh->tv", u, emb["embed"], lower)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1)[:, 0]


_PROGRAMS: dict = {}


def _programs(config: dict, lower_dtype: object) -> dict:
    """The jitted pieces of :func:`logprobs` for one configuration and
    precision, built once a process: a program per kind of layer to make its
    weights (the layer's number is an argument) and one to run it."""
    tag = (json.dumps(config, sort_keys=True), str(lower_dtype))
    if tag not in _PROGRAMS:
        kinds = {layer_kind(config, i) for i in range(config["num_hidden_layers"])}
        _PROGRAMS[tag] = {
            "embed": jax.jit(lambda k: make_embedding(config, k)),
            "head": jax.jit(lambda e, x, t: head(e, x, t, config, lower_dtype)),
            "make": {kind: jax.jit(lambda k, i, kind=kind: make_layer_weights(config, k, i, kind))
                     for kind in kinds},
            "run": {kind: jax.jit(lambda w, x, kind=kind: layer(w, x, config, kind, lower_dtype))
                    for kind in kinds},
        }
    return _PROGRAMS[tag]


def logprobs(config: dict, key: jax.Array, rows: list, lower_dtype: object = None) -> list:
    """The reference over ``rows`` (int32 id arrays, each of a length the
    caller allows to compile: a row is run whole, at its own length), one
    layer of weights at a time: the next-token log-probabilities per row."""
    fns = _programs(config, lower_dtype)
    with jax.default_matmul_precision("highest"):
        emb = fns["embed"](key)
        xs = [emb["embed"][jnp.asarray(r)] for r in rows]
        for i in range(config["num_hidden_layers"]):
            kind = layer_kind(config, i)
            w = fns["make"][kind](key, i)
            xs = [fns["run"][kind](w, x) for x in xs]
            jax.block_until_ready(xs)
            del w
        return [np.asarray(fns["head"](emb, x, jnp.asarray(r))) for x, r in zip(xs, rows)]
