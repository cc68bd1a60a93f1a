"""Plain Keye-VL-2.0-30B-A3B language model (``model_type: KeyeVL2``) in jax.numpy.

The benchmark's reference for the long-document scoring cell: token ids in,
the log-probability of every next token out. float32 throughout, every
contraction under ``jax.default_matmul_precision("highest")``, whole-row
index scores, ``jax.lax.top_k`` for the selection, a plain masked softmax,
full logits then ``log_softmax``; no kernels, no cache. It imports nothing
of the program (the arithmetic it shares with the other language model's
reference — RMSNorm, RoPE, the seeded draws, the control's rounding — it
takes from ``chipbench/reference/lfm2.py``).

All layers are alike (``decoder_sparse_step: 1``, ``mlp_only_layers: []``,
no biases, RMSNorm eps ``rms_norm_eps``); with residual stream ``x``:

    y  = x + Attn(RMSNorm_in(x))
    x' = y + MoE(RMSNorm_post(y))

- attention (the ``qwen3_moe`` form): 32 query heads over 4 key/value heads
  of ``head_dim`` 128 (so ``W_q: 2048 -> 4096``); ``q`` and ``k`` get an
  RMSNorm over the head width (learned scale) *before* RoPE (``rope_theta``,
  half rotation, positions from 0 in every row: on text the three M-RoPE
  sections carry the same position); scores scaled by 128**-0.5.
- the indexer (``sa_config``; DeepSeek-V3.2-Exp eq. 1) reads the same normed
  input: ``qI = W_qI u`` as 16 heads of 64, ``kI = LayerNorm(W_kI u)`` (one
  head of 64, learned scale and bias), both rotated over their 64;
  ``w = W_w u * (16 * 64)**-0.5``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``;
  ``S_t`` = the ``topk`` keys ``s <= t`` of largest ``I[t, s]`` (all of them
  while ``t + 1 <= topk``), shared by the 32 heads; the softmax and the sum
  over values run over ``S_t`` alone; then ``W_o``.
- the expert FFN: ``p = softmax(W_g u)`` over all 128; ``S = top8(p)``;
  ``w_e = p_e / sum_{e' in S} p_e'`` (``norm_topk_prob``); the sum over ``S``
  of ``w_e W_2^e (silu(W_1^e u) * W_3^e u)``. No shared expert, no bias, no
  token dropped.
- after the last layer one RMSNorm, then the head ``W_head`` — a matrix of
  its own (``tie_word_embeddings: false``).

So that a run holds it: a row's attention is computed a block of
``ATTN_BLOCK`` queries at a time against the keys up to the block's end
(rounded up to ``EXTENT_STEP``), each block with its whole index scores,
its ``top_k`` and its whole softmax; each expert runs on the tokens routed
to it and not on all of them — the (expert, slot) table of token numbers is
built on the host from the router's choice, padded to a common capacity,
and the products are plain batched ``einsum``s over it, ``EXPERT_GROUP``
experts at a time; the head's logits are made ``HEAD_BLOCK`` tokens at a
time. None of that changes a number.

Weights are made from the seed one layer at a time, every value rounded to
bfloat16 and held as float32; scales as the other reference's (every
sub-layer adds about a tenth of the embedding's RMS to the stream), so a
router's flip or a swapped key at the selection's edge does not cascade.
The control rounds the inputs of every product but the router's and the
indexer's: another selection is another model, not a rounding of this one.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2 import (EMBED_STD, OUT_STD, QK_SCALE, _bf16, _mm, _scale,
                                      _uniform, rmsnorm, rope)

ATTN_BLOCK = 256     # queries a block: its scores are 32 heads x 256 x keys
EXTENT_STEP = 4096   # a block's keys run to its end rounded up to this
EXPERT_GROUP = 16    # experts whose routed rows are held at once
HEAD_BLOCK = 2048    # tokens whose full logits are held at once
KI_BIAS_STD = 0.1    # the indexer's LayerNorm bias


def make_embedding(config: dict, key: jax.Array) -> dict:
    """The embedding, the head (a matrix of its own) and the last norm."""
    k = jax.random.split(jax.random.fold_in(key, 1_000_000), 3)
    shape = (config["vocab_size"], config["hidden_size"])
    return {"embed": _uniform(k[0], shape, EMBED_STD), "norm": _scale(k[1], shape[1]),
            "head": _uniform(k[2], shape, EMBED_STD)}


def make_layer_weights(config: dict, key: jax.Array, i: int) -> dict:
    """Layer ``i``'s weights from the seed: float32 arrays of bfloat16 values."""
    h, d = config["hidden_size"], config["head_dim"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = config["num_experts"], config["moe_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, i), 18)
    return {
        "norm_op": _scale(ks[0], h), "norm_ffn": _scale(ks[1], h),
        "wq": _uniform(ks[2], (h, nq * d), h ** -0.5),
        "wk": _uniform(ks[3], (h, nkv * d), h ** -0.5),
        "wv": _uniform(ks[4], (h, nkv * d), h ** -0.5),
        "wo": _uniform(ks[5], (nq * d, h), OUT_STD * (nq * d) ** -0.5),
        "q_norm": _scale(ks[6], d, *QK_SCALE), "k_norm": _scale(ks[7], d, *QK_SCALE),
        "wqi": _uniform(ks[8], (h, heads * di), h ** -0.5),
        "wki": _uniform(ks[9], (h, di), h ** -0.5),
        "ki_norm": _scale(ks[10], di),
        "ki_bias": _bf16(KI_BIAS_STD * jax.random.normal(ks[11], (di,), jnp.float32)),
        "wwi": _uniform(ks[12], (h, heads), h ** -0.5),
        "router": _uniform(ks[13], (h, e), h ** -0.5),
        "w1": _uniform(ks[14], (e, h, f), h ** -0.5),
        "w3": _uniform(ks[15], (e, h, f), h ** -0.5),
        "w2": _uniform(ks[16], (e, f, h), OUT_STD / 0.2 * f ** -0.5),
    }


# -- one row (L, h) at a time --------------------------------------------------

def _hi(eq: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """A product the control never rounds (router, indexer)."""
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale + bias


def projections(w: dict, u: jnp.ndarray, config: dict, lower: object = None) -> dict:
    """What attention and the indexer read of a row's normed input (L, h)."""
    nq, nkv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    sa = config["sa_config"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta, length = config["rms_norm_eps"], config["rope_theta"], u.shape[0]
    q = _mm("th,hk->tk", u, w["wq"], lower).reshape(length, nq, d)
    k = _mm("th,hk->tk", u, w["wk"], lower).reshape(length, nkv, d)
    v = _mm("th,hk->tk", u, w["wv"], lower).reshape(length, nkv, d)
    ki = layernorm(_hi("th,hk->tk", u, w["wki"]), w["ki_norm"], w["ki_bias"], eps)
    return {
        "q": rope(rmsnorm(q, w["q_norm"], eps), theta),
        "k": rope(rmsnorm(k, w["k_norm"], eps), theta), "v": v,
        "qi": rope(_hi("th,hk->tk", u, w["wqi"]).reshape(length, heads, di), theta),
        "ki": rope(ki[:, None, :], theta)[:, 0],
        "wt": _hi("th,hj->tj", u, w["wwi"]) * (heads * di) ** -0.5,
    }


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray, wt: jnp.ndarray) -> jnp.ndarray:
    """(Q, J, di), (K, di), (Q, J) -> (Q, K): ``sum_j w_j relu(qI_j . kI)``."""
    return (wt[:, :, None] * jax.nn.relu(_hi("qjd,kd->qjk", qi, ki))).sum(1)


def selection(scores: jnp.ndarray, lo: "int | jnp.ndarray", topk: int) -> jnp.ndarray:
    """(Q, K) index scores of queries ``lo .. lo + Q`` against keys ``0 ..
    K`` -> (Q, K) bool: each query's ``topk`` causal keys of largest score
    (``jax.lax.top_k``), all its causal keys where it has no more."""
    causal = (lo + jnp.arange(scores.shape[0]))[:, None] >= jnp.arange(scores.shape[1])[None, :]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, scores.shape[1]))
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & causal


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, picked: jnp.ndarray,
           lower: object = None) -> jnp.ndarray:
    """(Q, nq, d), (K, nkv, d) x2, (Q, K) bool -> (Q, nq * d)."""
    groups = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    s = _mm("qhd,khd->hqk", q, k, lower) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(picked[None], s, -jnp.inf), axis=-1)
    return _mm("hqk,khd->qhd", p, v, lower).reshape(q.shape[0], -1)


def attn_block(p: dict, lo: "int | jnp.ndarray", size: int, extent: int, topk: int,
               lower: object = None) -> jnp.ndarray:
    """Queries ``lo .. lo + size`` of a row against its first ``extent`` keys."""
    def cut(x: jnp.ndarray) -> jnp.ndarray:
        return jax.lax.dynamic_slice_in_dim(x, lo, size, axis=0)

    picked = selection(index_scores(cut(p["qi"]), p["ki"][:extent], cut(p["wt"])), lo, topk)
    return attend(cut(p["q"]), p["k"][:extent], p["v"][:extent], picked, lower)


def route(w: dict, u: jnp.ndarray, config: dict) -> tuple:
    """(L, h) -> ((L, k) expert ids, (L, k) weights): softmax over all the
    experts, the top-k, renormalised over the selection (``norm_topk_prob``)."""
    probs = jax.nn.softmax(_hi("th,he->te", u, w["router"]), axis=-1)
    picked, idx = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    return idx, picked


def expert_table(idx: np.ndarray, weights: np.ndarray, num_experts: int,
                 experts: "tuple | None" = None) -> tuple:
    """On the host: per expert of ``[lo, hi)`` (default: all) the tokens
    routed to it and their weights, as (hi - lo, capacity) tables padded with
    token 0 at weight 0; the capacity is the smallest of a few multiples of
    the mean load that holds the busiest expert, so that few shapes compile."""
    lo, hi = experts or (0, num_experts)
    tokens, k = idx.shape
    flat = idx.reshape(-1)
    counts = np.bincount(flat, minlength=num_experts)
    mean = tokens * k / num_experts
    capacity = min(tokens, next(
        c for c in (int(np.ceil(m * mean / 8) * 8) for m in (1.25, 1.5, 2, 3, 4, 8, 16, 64, 1024))
        if c >= counts[lo:hi].max()))
    tok = np.zeros((hi - lo, capacity), np.int32)
    wt = np.zeros((hi - lo, capacity), np.float32)
    order = np.argsort(flat, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    for e in range(lo, hi):
        at = order[starts[e]:starts[e + 1]]
        tok[e - lo, :len(at)] = at // k
        wt[e - lo, :len(at)] = weights.reshape(-1)[at]
    return tok, wt


def experts_ffn(w: dict, u: jnp.ndarray, tok: jnp.ndarray, wt: jnp.ndarray, lo: int,
                lower: object = None) -> jnp.ndarray:
    """(L, h) and the tables of experts ``lo .. lo + len(tok)`` -> (L, h):
    their weighted outputs added at their tokens, a group of experts at a time."""
    held = tok.shape[0]
    group = min(EXPERT_GROUP, held)
    if held % group:
        raise ValueError(f"{held} experts are no multiple of the group {group}")

    def one(acc: jnp.ndarray, e: tuple) -> tuple:
        w1, w3, w2, t, c = e
        rows = u[t]                                     # (group, capacity, h)
        a = jax.nn.silu(_mm("ech,ehf->ecf", rows, w1, lower)) * _mm("ech,ehf->ecf", rows, w3, lower)
        out = c[..., None] * _mm("ecf,efh->ech", a, w2, lower)
        return acc.at[t.reshape(-1)].add(out.reshape(-1, out.shape[-1])), None

    def grouped(x: jnp.ndarray) -> jnp.ndarray:
        return x.reshape(held // group, group, *x.shape[1:])

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        grouped(w["w1"][lo:lo + held]), grouped(w["w3"][lo:lo + held]),
        grouped(w["w2"][lo:lo + held]), grouped(tok), grouped(wt)))
    return out


def head(emb: dict, x: jnp.ndarray, tokens: jnp.ndarray, config: dict,
         lower: object = None) -> jnp.ndarray:
    """(L, h) final stream, (L,) ids -> (L-1,) log p(x[t+1] | x[0..t])."""
    u = rmsnorm(x, emb["norm"], config["rms_norm_eps"])
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
        eps, topk = config["rms_norm_eps"], config["sa_config"]["topk"]

        def before(w: dict, x: jnp.ndarray) -> dict:
            return projections(w, rmsnorm(x, w["norm_op"], eps), config, lower_dtype)

        def between(w: dict, x: jnp.ndarray, o: jnp.ndarray) -> tuple:
            y = x + _mm("tk,kh->th", o, w["wo"], lower_dtype)
            u = rmsnorm(y, w["norm_ffn"], eps)
            return (y, u) + route(w, u, config)

        _PROGRAMS[tag] = {
            "embed": jax.jit(lambda k: make_embedding(config, k)),
            "make": jax.jit(lambda k, i: make_layer_weights(config, k, i)),
            "before": jax.jit(before),
            "block": jax.jit(lambda p, lo, size, extent: attn_block(
                p, lo, size, extent, topk, lower_dtype), static_argnums=(2, 3)),
            "between": jax.jit(between),
            "experts": jax.jit(lambda w, y, u, tok, wt: y + experts_ffn(
                w, u, tok, wt, 0, lower_dtype)),
            "head": jax.jit(lambda e, x, t: head(e, x, t, config, lower_dtype)),
        }
    return _PROGRAMS[tag]


def attention(fns: dict, p: dict) -> jnp.ndarray:
    """A row's attention, block by block: (L, nq * d)."""
    length = p["q"].shape[0]
    size = min(ATTN_BLOCK, length)
    if length % size:
        raise ValueError(f"a row of {length} is no multiple of the block {size}")
    out = []
    for lo in range(0, length, size):
        extent = min(length, -(-(lo + size) // EXTENT_STEP) * EXTENT_STEP)
        out.append(fns["block"](p, lo, size, extent))
    return jnp.concatenate(out, axis=0)


def run_layer(fns: dict, w: dict, x: jnp.ndarray, config: dict) -> jnp.ndarray:
    o = attention(fns, fns["before"](w, x))
    y, u, idx, weights = fns["between"](w, x, o)
    tok, wt = expert_table(np.asarray(idx), np.asarray(weights), config["num_experts"])
    return fns["experts"](w, y, u, jnp.asarray(tok), jnp.asarray(wt))


def logprobs(config: dict, key: jax.Array, rows: list, lower_dtype: object = None) -> list:
    """The reference over ``rows`` (int32 id arrays, each run whole at its
    own length), one layer of weights at a time: the next-token
    log-probabilities per row."""
    fns = _programs(config, lower_dtype)
    with jax.default_matmul_precision("highest"):
        emb = fns["embed"](key)
        xs = [emb["embed"][jnp.asarray(r)] for r in rows]
        for i in range(config["num_hidden_layers"]):
            w = fns["make"](key, i)
            xs = [run_layer(fns, w, x, config) for x in xs]
            jax.block_until_ready(xs)
            del w
        return [np.asarray(fns["head"](emb, x, jnp.asarray(r))) for x, r in zip(xs, rows)]
