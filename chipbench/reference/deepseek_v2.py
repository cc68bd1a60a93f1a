"""Plain DeepSeek-V2 (``model_type: deepseek_v2``) in jax.numpy, whole or as
one chip's share of an expert-parallel deployment.

The benchmark's reference for the cell ``deepseek_v2_score_docs``: token ids
in, the log-probability of every next token out. float32 throughout, every
contraction under ``jax.default_matmul_precision("highest")``, whole score
rows a block of queries at a time, a plain masked softmax,
``jax.lax.top_k`` for groups and experts, full logits then ``log_softmax``;
no kernels, no cache. It imports nothing of the program (the arithmetic it
shares with the other language models' references — RMSNorm, the seeded
draws, the control's rounding, the host's table of the tokens routed to
each expert — it takes from ``chipbench/reference/lfm2.py`` and ``keye.py``).

Layer ``i`` with residual stream ``x`` (RMSNorm eps ``rms_norm_eps``, no
biases): ``y = x + Attn(RMSNorm_in(x))``, ``x' = y + FFN(RMSNorm_post(y))``;
after the last layer one RMSNorm and the untied head.

- latent attention, ``u`` the normed input, ``H`` heads of ``d_n + d_r``
  score dimensions and ``d_v`` value dimensions:
  ``c_q = RMSNorm(W_DQ u)`` (``q_lora_rank``, learned scale);
  ``[q_n | q_r]_i = (W_UQ c_q)_i``; ``[c_kv | k_r] = W_DKV u``
  (``kv_lora_rank + d_r``); ``c_kv <- RMSNorm(c_kv)`` (learned scale; ``k_r``
  is not normed); ``[k_n | v]_i = (W_UKV c_kv)_i``; ``q_r`` and ``k_r``
  rotated, pairing dimensions ``(2j, 2j + 1)`` as the published
  implementation does, positions from 0 in every row — ``k_r`` is one vector
  a token, the same for every head;
  ``s_i[t, s] = (q_n,i[t] . k_n,i[s] + q_r,i[t] . k_r[s]) sigma`` for ``s <=
  t``; ``o_i = softmax(s_i) v_i``; ``Attn = W_O [o_1 | ... | o_H]``.
- YaRN (``rope_scaling``), static, at every length: ``f_j = theta^(-2j /
  d_r)``; ``c(b) = d_r ln(original / (2 pi b)) / (2 ln theta)``; ``lo =
  floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``; ``r_j = clip((j - lo) /
  (hi - lo), 0, 1)``; the frequency used is ``f_j (1 - r_j) + f_j / factor
  r_j``; with ``m(mu) = 0.1 mu ln(factor) + 1`` cos and sin are scaled by
  ``m(mscale) / m(mscale_all_dim)`` and ``sigma = (d_n + d_r)^(-1/2)
  m(mscale_all_dim)^2``.
- FFN of layers ``i < first_k_dense_replace``: ``W_2 (silu(W_1 u) * W_3 u)``.
- every other FFN: ``p = softmax(W_g u)`` over all ``n_routed_experts``;
  ``g_a`` = the largest ``p_e`` of group ``a`` (``n_group`` groups of
  consecutive experts); ``A`` = the ``topk_group`` groups of largest ``g_a``;
  ``S`` = the ``num_experts_per_tok`` experts of largest ``p_e`` among those
  in ``A``; ``w_e = routed_scaling_factor p_e`` (``norm_topk_prob`` false;
  where true ``p_e`` over its sum over ``S``); the sum over ``S`` of ``w_e
  W_2^e (silu(W_1^e u) * W_3^e u)`` plus the shared experts, one gated FFN of
  width ``n_shared_experts * moe_intermediate_size``.
- the share: ``config["expert_range"] = (lo, hi)`` are the experts held
  (the weights made are theirs alone) and the sum over ``S`` runs over ``S``
  within the range; the shared experts are whole; what the absent experts
  would add is left out and the partial result goes on. Without the key the
  layer is whole. ``config["vocab_range"] = (lo, hi)``: ids, embedding, head
  and log-softmax are over those ids.

So that a run holds it: a row's attention is computed a block of
``ATTN_BLOCK`` queries at a time against the keys up to the block's end
(rounded up to ``EXTENT_STEP``); each held expert runs on the tokens routed
to it — the (expert, slot) table of token numbers is built on the host from
the router's choice — ``EXPERT_GROUP`` experts at a time; the head's logits
are made ``HEAD_BLOCK`` tokens at a time. None of that changes a number.

Weights from the seed a layer at a time, every value rounded to bfloat16 and
held as float32; an expert's weights are drawn from the seed and the
expert's number, so a share's weights are the whole layer's rows. Assumed
scales (the configuration's ``assumed`` names them): embedding and head
uniform of std 0.03; a matrix that reads a normed input std ``fan_in**-0.5``;
the stream's norms' scales uniform 0.8..1.2; the two latent norms' scales
uniform 1.5..2.0, which with ``sigma`` gives scores of spread about 3.4, a
softmax that keeps a few keys, and values of RMS about 1.75; ``W_O`` scaled
so that attention adds about 0.003 RMS, a tenth of the embedding's, to the
stream, the dense FFN's ``W_2`` likewise; the shared experts' ``W_2`` so
that they add about 0.002 and the routed experts' ``W_2^e`` so that **one
routing group's share** adds about 0.002 over all tokens with the x16 of
the routed weights counted in (:func:`routed_weight_rms`: the root mean
square over tokens of the weights that fall on one group, from a router
with logits of unit spread), so an expert layer adds about 0.003 here and a
flipped router does not cascade. The control rounds the inputs of every
product but the router's: another selection is another model.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.keye import expert_table
from chipbench.reference.lfm2 import EMBED_STD, OUT_STD, QK_SCALE, _mm, _scale, _uniform, rmsnorm

ATTN_BLOCK = 128     # queries a block: its scores are heads x 128 x keys
EXTENT_STEP = 4096   # a block's keys run to its end rounded up to this
EXPERT_GROUP = 4     # experts whose routed rows are held at once
HEAD_BLOCK = 2048    # tokens whose full logits are held at once
SHARED_STD = 0.002   # what the shared experts add to the stream
ROUTED_STD = 0.002   # what one routing group's routed experts add, over all tokens
ACT_RMS = 0.6        # silu(a) * b for a, b of unit spread
V_RMS = 1.75         # the values' RMS under the latent norm's scale


def dims(config: dict) -> tuple:
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"])


def ffn_kind(config: dict, i: int) -> str:
    return "dense" if i < config["first_k_dense_replace"] else "moe"


# -- YaRN ------------------------------------------------------------------------

def yarn_frequencies(config: dict) -> np.ndarray:
    """(d_r / 2,) rotation frequencies."""
    d, theta, sc = config["qk_rope_head_dim"], config["rope_theta"], config.get("rope_scaling")
    j = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / d)
    if not sc:
        return f

    def c(beta: float) -> float:
        return d * math.log(sc["original_max_position_embeddings"] / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    lo, hi = max(math.floor(c(sc["beta_fast"])), 0), min(math.ceil(c(sc["beta_slow"])), d - 1)
    r = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f * (1 - r) + f / sc["factor"] * r


def mscale(config: dict, key: str) -> float:
    sc = config.get("rope_scaling")
    if not sc or sc["factor"] <= 1 or not sc.get(key):
        return 1.0
    return 0.1 * sc[key] * math.log(sc["factor"]) + 1.0


def rope_pairs(x: jnp.ndarray, config: dict) -> jnp.ndarray:
    """Rotate (L, heads, d_r): dimensions ``(2j, 2j + 1)`` by ``t f_j``."""
    length, heads, d = x.shape
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(config), jnp.float32)[None, :]
    gain = mscale(config, "mscale") / mscale(config, "mscale_all_dim")
    cos, sin = jnp.cos(ang)[:, None, :] * gain, jnp.sin(ang)[:, None, :] * gain
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(length, heads, d)


# -- weights -----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _routed_weight_rms(experts: int, groups: int, topk_group: int, k: int, norm: bool,
                       scaling: float) -> float:
    rng = np.random.default_rng(0)
    p = np.exp(rng.standard_normal((8192, experts)))
    p /= p.sum(1, keepdims=True)
    best = p.reshape(-1, groups, experts // groups).max(-1)
    keep = np.zeros_like(best, bool)
    np.put_along_axis(keep, np.argsort(-best, 1)[:, :topk_group], True, 1)
    limited = np.where(np.repeat(keep, experts // groups, 1), p, 0.0)
    idx = np.argsort(-limited, 1)[:, :k]
    w = np.take_along_axis(p, idx, 1)
    w = w / w.sum(1, keepdims=True) if norm else w * scaling
    return float(np.sqrt(((w ** 2) * (idx < experts // groups)).sum(1).mean()))


def routed_weight_rms(config: dict) -> float:
    """Root mean square over tokens of the routed weights that fall on one
    routing group's experts, from a router whose logits have unit spread
    (a fixed draw of 8,192 tokens): 0.52 at the published 160 / 8 / 3 / 6, x16."""
    return _routed_weight_rms(
        config["n_routed_experts"], config["n_group"], config["topk_group"],
        config["num_experts_per_tok"], bool(config.get("norm_topk_prob", True)),
        float(config.get("routed_scaling_factor", 1.0)))


def make_embedding(config: dict, key: jax.Array) -> dict:
    """The embedding and the head (a matrix of its own) over the ids held,
    and the last norm."""
    lo, hi = config.get("vocab_range") or (0, config["vocab_size"])
    k = jax.random.split(jax.random.fold_in(key, 1_000_000), 3)
    shape = (hi - lo, config["hidden_size"])
    return {"embed": _uniform(k[0], shape, EMBED_STD), "norm": _scale(k[1], shape[1]),
            "head": _uniform(k[2], shape, EMBED_STD)}


def make_layer_weights(config: dict, key: jax.Array, i: int, kind: "str | None" = None) -> dict:
    """Layer ``i``'s weights from the seed: float32 arrays of bfloat16 values;
    of the routed experts those of ``expert_range`` (default: all). ``kind``
    (default: the layer's own) lets one program make every layer of a kind."""
    h = config["hidden_size"]
    heads, dn, dr, dv = dims(config)
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    ks = jax.random.split(jax.random.fold_in(key, i), 20)
    w = {
        "norm_op": _scale(ks[0], h), "norm_ffn": _scale(ks[1], h),
        "w_dq": _uniform(ks[2], (h, rq), h ** -0.5),
        "q_a_norm": _scale(ks[3], rq, *QK_SCALE),
        "w_uq": _uniform(ks[4], (rq, heads * (dn + dr)), rq ** -0.5),
        "w_dkv": _uniform(ks[5], (h, rkv + dr), h ** -0.5),
        "kv_a_norm": _scale(ks[6], rkv, *QK_SCALE),
        "w_ukv": _uniform(ks[7], (rkv, heads * (dn + dv)), rkv ** -0.5),
        "wo": _uniform(ks[8], (heads * dv, h), OUT_STD / V_RMS * (heads * dv) ** -0.5),
    }
    if (kind or ffn_kind(config, i)) == "dense":
        f = config["intermediate_size"]
        w["w1"] = _uniform(ks[9], (h, f), h ** -0.5)
        w["w3"] = _uniform(ks[10], (h, f), h ** -0.5)
        w["w2"] = _uniform(ks[11], (f, h), OUT_STD / ACT_RMS * f ** -0.5)
        return w
    f = config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * f
    lo, hi = config.get("expert_range") or (0, config["n_routed_experts"])
    out = ROUTED_STD / (ACT_RMS * routed_weight_rms(config)) * f ** -0.5

    def expert(e: jnp.ndarray) -> tuple:
        k1, k3, k2 = jax.random.split(jax.random.fold_in(ks[12], e), 3)
        return (_uniform(k1, (h, f), h ** -0.5), _uniform(k3, (h, f), h ** -0.5),
                _uniform(k2, (f, h), out))

    w["router"] = _uniform(ks[13], (h, config["n_routed_experts"]), h ** -0.5)
    w["w1"], w["w3"], w["w2"] = jax.lax.map(expert, jnp.arange(lo, hi))
    w["ws1"] = _uniform(ks[14], (h, fs), h ** -0.5)
    w["ws3"] = _uniform(ks[15], (h, fs), h ** -0.5)
    w["ws2"] = _uniform(ks[16], (fs, h), SHARED_STD / ACT_RMS * fs ** -0.5)
    return w


# -- one row (L, h) at a time --------------------------------------------------

def projections(w: dict, u: jnp.ndarray, config: dict, lower: object = None) -> dict:
    """What latent attention reads of a row's normed input (L, h)."""
    heads, dn, dr, dv = dims(config)
    rkv, eps, length = config["kv_lora_rank"], config["rms_norm_eps"], u.shape[0]
    cq = rmsnorm(_mm("th,hr->tr", u, w["w_dq"], lower), w["q_a_norm"], eps)
    q = _mm("tr,rk->tk", cq, w["w_uq"], lower).reshape(length, heads, dn + dr)
    down = _mm("th,hr->tr", u, w["w_dkv"], lower)
    ckv = rmsnorm(down[:, :rkv], w["kv_a_norm"], eps)
    kv = _mm("tr,rk->tk", ckv, w["w_ukv"], lower).reshape(length, heads, dn + dv)
    return {"qn": q[..., :dn], "qr": rope_pairs(q[..., dn:], config),
            "kn": kv[..., :dn], "kr": rope_pairs(down[:, None, rkv:], config)[:, 0],
            "v": kv[..., dn:]}


def attn_block(p: dict, lo: "int | jnp.ndarray", size: int, extent: int, config: dict,
               lower: object = None) -> jnp.ndarray:
    """Queries ``lo .. lo + size`` of a row against its first ``extent``
    keys -> (size, H * d_v)."""
    def cut(x: jnp.ndarray) -> jnp.ndarray:
        return jax.lax.dynamic_slice_in_dim(x, lo, size, axis=0)

    _, dn, dr, _ = dims(config)
    sigma = (dn + dr) ** -0.5 * mscale(config, "mscale_all_dim") ** 2
    s = (_mm("qhd,khd->hqk", cut(p["qn"]), p["kn"][:extent], lower)
         + _mm("qhd,kd->hqk", cut(p["qr"]), p["kr"][:extent], lower)) * sigma
    seen = (lo + jnp.arange(size))[:, None] >= jnp.arange(extent)[None, :]
    prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return _mm("hqk,khd->qhd", prob, p["v"][:extent], lower).reshape(size, -1)


def dense_ffn(w1: jnp.ndarray, w3: jnp.ndarray, w2: jnp.ndarray, u: jnp.ndarray,
              lower: object = None) -> jnp.ndarray:
    a = jax.nn.silu(_mm("th,hf->tf", u, w1, lower)) * _mm("th,hf->tf", u, w3, lower)
    return _mm("tf,fh->th", a, w2, lower)


def route(w: dict, u: jnp.ndarray, config: dict) -> tuple:
    """(L, h) -> ((L, k) expert ids, (L, k) weights): softmax over all the
    experts, the ``topk_group`` groups of largest best expert, the top-k
    inside them. Never rounded by the control."""
    probs = jax.nn.softmax(jnp.einsum("th,he->te", u, w["router"],
                                      precision=jax.lax.Precision.HIGHEST), axis=-1)
    groups = config["n_group"]
    best = probs.reshape(probs.shape[0], groups, -1).max(-1)
    _, kept = jax.lax.top_k(best, config["topk_group"])
    keep = jnp.zeros_like(best, bool).at[jnp.arange(best.shape[0])[:, None], kept].set(True)
    limited = jnp.where(jnp.repeat(keep, probs.shape[1] // groups, axis=1), probs, 0.0)
    _, idx = jax.lax.top_k(limited, config["num_experts_per_tok"])
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    if config.get("norm_topk_prob", True):
        return idx, picked / picked.sum(-1, keepdims=True)
    return idx, picked * config.get("routed_scaling_factor", 1.0)


def experts_ffn(w: dict, u: jnp.ndarray, tok: jnp.ndarray, wt: jnp.ndarray,
                lower: object = None) -> jnp.ndarray:
    """(L, h) and the (held, capacity) tables of the held experts' tokens and
    weights -> (L, h): their weighted outputs added at their tokens."""
    held = tok.shape[0]
    group = math.gcd(EXPERT_GROUP, held)

    def one(acc: jnp.ndarray, e: tuple) -> tuple:
        w1, w3, w2, t, c = e
        rows = u[t]                                     # (group, capacity, h)
        a = jax.nn.silu(_mm("ech,ehf->ecf", rows, w1, lower)) * _mm("ech,ehf->ecf", rows, w3, lower)
        out = c[..., None] * _mm("ecf,efh->ech", a, w2, lower)
        return acc.at[t.reshape(-1)].add(out.reshape(-1, out.shape[-1])), None

    def grouped(x: jnp.ndarray) -> jnp.ndarray:
        return x.reshape(held // group, group, *x.shape[1:])

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        grouped(w["w1"]), grouped(w["w3"]), grouped(w["w2"]), grouped(tok), grouped(wt)))
    return out


def head(emb: dict, x: jnp.ndarray, tokens: jnp.ndarray, config: dict,
         lower: object = None) -> jnp.ndarray:
    """(L, h) final stream, (L,) ids -> (L-1,) log p(x[t+1] | x[0..t]) over
    the ids held."""
    u = rmsnorm(x, emb["norm"], config["rms_norm_eps"])
    tokens = tokens - (config.get("vocab_range") or (0,))[0]
    targets = jnp.concatenate([tokens[1:], tokens[:1]])
    block = min(HEAD_BLOCK, u.shape[0])
    if u.shape[0] % block:
        raise ValueError(f"{u.shape[0]} tokens are no multiple of the head's block {block}")

    def one(args: tuple) -> jnp.ndarray:
        ub, tb = args
        logp = jax.nn.log_softmax(_mm("th,vh->tv", ub, emb["head"], lower), axis=-1)
        return jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    out = jax.lax.map(one, (u.reshape(-1, block, u.shape[1]), targets.reshape(-1, block)))
    return out.reshape(-1)[:-1]


_PROGRAMS: dict = {}


def _programs(config: dict, lower_dtype: object) -> dict:
    """The jitted pieces of :func:`logprobs` for one configuration and
    precision, built once a process."""
    tag = (json.dumps(config, sort_keys=True), str(lower_dtype))
    if tag not in _PROGRAMS:
        eps = config["rms_norm_eps"]

        def before(w: dict, x: jnp.ndarray) -> dict:
            return projections(w, rmsnorm(x, w["norm_op"], eps), config, lower_dtype)

        def between(w: dict, x: jnp.ndarray, o: jnp.ndarray) -> tuple:
            y = x + _mm("tk,kh->th", o, w["wo"], lower_dtype)
            return y, rmsnorm(y, w["norm_ffn"], eps)

        def experts(w: dict, y: jnp.ndarray, u: jnp.ndarray, tok: jnp.ndarray,
                    wt: jnp.ndarray) -> jnp.ndarray:
            return (y + experts_ffn(w, u, tok, wt, lower_dtype)
                    + dense_ffn(w["ws1"], w["ws3"], w["ws2"], u, lower_dtype))

        _PROGRAMS[tag] = {
            "embed": jax.jit(lambda k: make_embedding(config, k)),
            "make": {kind: jax.jit(lambda k, i, kind=kind: make_layer_weights(config, k, i, kind))
                     for kind in ("dense", "moe")},
            "before": jax.jit(before),
            "block": jax.jit(lambda p, lo, size, extent: attn_block(
                p, lo, size, extent, config, lower_dtype), static_argnums=(2, 3)),
            "between": jax.jit(between),
            "route": jax.jit(lambda w, u: route(w, u, config)),
            "dense": jax.jit(lambda w, y, u: y + dense_ffn(
                w["w1"], w["w3"], w["w2"], u, lower_dtype)),
            "experts": jax.jit(experts),
            "head": jax.jit(lambda e, x, t: head(e, x, t, config, lower_dtype)),
        }
    return _PROGRAMS[tag]


def attention(fns: dict, p: dict) -> jnp.ndarray:
    """A row's attention, block by block: (L, H * d_v)."""
    length = p["qn"].shape[0]
    size = min(ATTN_BLOCK, length)
    if length % size:
        raise ValueError(f"a row of {length} is no multiple of the block {size}")
    out = []
    for lo in range(0, length, size):
        extent = min(length, -(-(lo + size) // EXTENT_STEP) * EXTENT_STEP)
        out.append(fns["block"](p, lo, size, extent))
    return jnp.concatenate(out, axis=0)


def run_layer(fns: dict, w: dict, x: jnp.ndarray, config: dict, kind: str) -> jnp.ndarray:
    y, u = fns["between"](w, x, attention(fns, fns["before"](w, x)))
    if kind == "dense":
        return fns["dense"](w, y, u)
    idx, weights = fns["route"](w, u)
    tok, wt = expert_table(np.asarray(idx), np.asarray(weights), config["n_routed_experts"],
                           config.get("expert_range"))
    return fns["experts"](w, y, u, jnp.asarray(tok), jnp.asarray(wt))


def logprobs(config: dict, key: jax.Array, rows: list, lower_dtype: object = None) -> list:
    """The reference over ``rows`` (int32 id arrays, each run whole at its
    own length), one layer of weights at a time: the next-token
    log-probabilities per row, over the ids held."""
    fns = _programs(config, lower_dtype)
    first = (config.get("vocab_range") or (0,))[0]
    with jax.default_matmul_precision("highest"):
        emb = fns["embed"](key)
        xs = [emb["embed"][jnp.asarray(r) - first] for r in rows]
        for i in range(config["num_hidden_layers"]):
            kind = ffn_kind(config, i)
            w = fns["make"][kind](key, i)
            xs = [run_layer(fns, w, x, config, kind) for x in xs]
            jax.block_until_ready(xs)
            del w
        return [np.asarray(fns["head"](emb, x, jnp.asarray(r))) for x, r in zip(xs, rows)]
