"""Causal language model as a corpus scorer: one forward pass, built from
the configuration's keys (``lfm2_moe``, the ``KeyeVL2`` language model and
``deepseek_v2``).

A DataFrame column of token-id arrays of unequal length in, per row the
log-probability of every next token out
(``logprob[t] = log p(x[t+1] | x[0..t])``, ``t < L - 1``): perplexity
filtering, per-token likelihood features for a downstream learner. The
reference's deep-learning stage evaluates any network over DataFrame rows
(cntk/CNTKModel.scala:86-138); this is the stage for the networks people
score text with today.

The model (docs/models.md has the equations and says which key chooses
what): pre-norm residual layers, each a sequence mixer — a gated short
convolution (``layer_types[i] == "conv"``), grouped-query attention with
per-head QK RMSNorm and RoPE (``"full_attention"``; heads of ``head_dim``,
``hidden_size / num_attention_heads`` where the key is absent), over all
causal keys or, where the configuration has an ``sa_config``, over the
``topk`` keys an indexer picks for each query
(:mod:`mmlspark_tpu.ops.sparse_attention`), or, where it has a
``kv_lora_rank``, latent attention: low-rank queries and one low-rank
latent for keys and values, a rotation (YaRN's frequencies) on a part of
the score's width whose key is shared by all heads, values narrower than
scores (:mod:`mmlspark_tpu.ops.latent_attention`) — and a feed-forward
network, dense in the leading ``num_dense_layers`` (``first_k_dense_replace``)
layers and a sparse expert layer (:mod:`mmlspark_tpu.ops.moe`; its router is
the sigmoid one with a selection bias, the softmax one or the softmax one
limited to groups, ``moe.router_kind``), with ``n_shared_experts`` shared
experts every token passes through, in the rest; the head is the embedding
transposed unless ``tie_word_embeddings`` is false. A configuration may be
one chip's share of a deployment: ``expert_range`` names the experts held
of those the router scores (the layer computes their part of the result,
:func:`mmlspark_tpu.ops.moe.expert_ffn`), ``vocab_range`` the slice of the
vocabulary whose embedding and head rows are held (ids come from the slice,
and the log-probabilities are over it).
Weights and activations bfloat16 with float32 accumulation;
the residual stream, router scores, the norms' statistics, softmax and the
head's log-sum-exp in float32 (every product reads bfloat16: the norm that
feeds a sub-layer casts; a bfloat16 stream would round the whole sum at
every add, and a rounding error in the stream is what flips a near-tie in
a router). Causal attention is blockwise (a block of queries against the keys
up to its end — a Python loop over the blocks of rows of a few thousand
tokens, a device loop where an indexer selects the keys and rows run to
32,768, a flash kernel over the causal tiles for latent attention's 128
heads — never a whole ``L x L`` score matrix) and the head folds the
vocabulary into a log-sum-exp a tile of tokens at a time, never the whole
``tokens x vocabulary`` logits: on a TPU one Pallas kernel in which a tile's
logits live and die in VMEM and token tiles of right padding are skipped
(:mod:`mmlspark_tpu.ops.vocab_head`), elsewhere a block of tokens' logits
in float32 at a time.

:class:`CausalLMScorer` sorts a partition's rows into the length buckets it
was given, pads on the right and drives ``XLAModel.apply_batch`` once per
bucket with that bucket's batch size. Right padding needs no mask inside a
causal model: no real position sees a pad (attention and the convolution
look back only; norms, FFNs and the router are per token). Padded positions
are dropped from the output and counted. A batch travels as one int32 array
``(rows, L + 1)`` — the ids and, as the trailing column, the row's length
(:func:`mmlspark_tpu.models.sequence.pack_lengths`' convention) — and comes
back as one float32 array ``(rows, L - 1 + E + 2)``: the log-probabilities,
the row's real tokens routed to each expert, summed over the expert layers,
and in the batch's first row the visits and the row tiles of the experts'
grouped-matmul kernel (:func:`mmlspark_tpu.ops.moe.expert_ffn`; a count of
the batch, not of a row); with an indexer, four columns more: the keys the
row's real positions attended and the causal keys they had, summed over the
layers, each as ``count >> 12`` and ``count & 4095`` (float32 holds those
exactly).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame, Partition
from mmlspark_tpu.core.params import ComplexParam, HasInputCol, HasOutputCol
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.models.xla_model import XLAModel
from mmlspark_tpu.ops import histogram, latent_attention, moe, sparse_attention, vocab_head

_M_TOKENS = obs.counter(
    "mmlspark_lm_tokens_total",
    "Token positions the language-model scorer sent to the device: kind=real "
    "are the rows' own tokens, kind=padded the right padding of rows to "
    "their bucket's length and of batches to their size",
    labels=("kind",),
)
_M_ROUTED = obs.counter(
    "mmlspark_moe_tokens_routed_total",
    "Real tokens routed to each expert, summed over the expert layers; "
    "carried out of the program with each batch's output",
    labels=("expert",),
)
_M_GMM_TILES = obs.counter(
    "mmlspark_moe_gmm_tiles_total",
    "Row tiles of the experts' grouped-matmul kernel, summed over the expert "
    "layers: kind=visited are the (expert, row tile) pairs it multiplied, "
    "kind=aligned the row tiles that held a routed row (a tile two experts "
    "share is visited once for each); 0 where the products ran as XLA's "
    "ragged_dot; carried out of the program with each batch's output",
    labels=("kind",),
)
_M_HEAD_TILES = obs.counter(
    "mmlspark_lm_head_tiles_total",
    "Token tiles of the head's kernel (ops/vocab_head.py): kind=visited are "
    "the tiles it multiplied by the vocabulary, kind=skipped those it neither "
    "fetched nor multiplied because no position of theirs has a next token "
    "(right padding); 0 where the head ran as XLA operations; counted from "
    "the rows' lengths by the kernel's own tiling rule",
    labels=("kind",),
)
_M_ATTN_KEYS = obs.counter(
    "mmlspark_lm_attn_keys_total",
    "Keys the real positions of a learned sparse attention met, summed over "
    "its layers: kind=selected are the keys the indexer kept for a query, "
    "kind=causal all the keys at or before it; counted on the device and "
    "carried out of the program with each batch's output",
    labels=("kind",),
)


# queries per block of the blockwise grouped-query attention: the largest
# score tensor is batch x heads x Q_BLOCK x keys in float32, 1.07 GB at 32
# heads and 32,768 tokens a batch, be they 8 rows of 4,096 or one of 32,768
# (an indexer's products, batch x its heads x Q_BLOCK x keys, are half that
# at 16 heads); latent attention, 128 heads, never holds such a tensor on a
# TPU and sizes its own block elsewhere (ops/latent_attention.py); and tokens
# per block of the head's log-sum-exp in its XLA form, which holds a block's
# logits in HBM (0.5 GB at 65,536 ids, 1.2 GB at 151,936; the kernel a TPU
# runs holds none and takes its tiles from ops/vocab_head.tiling)
Q_BLOCK = 256
HEAD_BLOCK = 2048
# the four trailing columns of a batch's output where an indexer selects keys
# hold two counts as (count >> COUNT_BITS, count & (2**COUNT_BITS - 1))
COUNT_BITS = 12


def layer_kinds(config: dict) -> list:
    """``[(mixer, ffn)]`` per layer: ("conv" | "full_attention", "dense" | "moe").
    Without ``layer_types`` every mixer is attention; the leading dense
    layers are ``num_dense_layers`` (lfm2_moe's name) or
    ``first_k_dense_replace`` (deepseek_v2's)."""
    dense = config["num_dense_layers"] if "num_dense_layers" in config \
        else config["first_k_dense_replace"]
    kinds = config.get("layer_types") or ["full_attention"] * config["num_hidden_layers"]
    return [(kinds[i], "dense" if i < dense else "moe")
            for i in range(config["num_hidden_layers"])]


def router_width(config: dict) -> int:
    """The experts the router scores: ``num_experts`` or ``n_routed_experts``."""
    return config["num_experts"] if "num_experts" in config else config["n_routed_experts"]


def held_range(config: dict, key: str) -> Optional[tuple]:
    """``(lo, hi)`` under ``key`` where the configuration is a share:
    ``expert_range``, the experts held of those the router scores;
    ``vocab_range``, the ids whose embedding and head rows are held."""
    held = config.get(key)
    return None if held is None else (int(held[0]), int(held[1]))


def norm_eps(config: dict) -> float:
    """``norm_eps`` (lfm2_moe's name) or ``rms_norm_eps`` (qwen3_moe's)."""
    return config["norm_eps"] if "norm_eps" in config else config["rms_norm_eps"]


def head_matrix(variables: dict, config: dict) -> Any:
    """The head's (V, h) matrix: the embedding unless ``tie_word_embeddings``
    is false."""
    return variables["embed" if config.get("tie_word_embeddings", True) else "head"]


def selects_keys(config: dict) -> bool:
    """Whether the attention layers carry an indexer (``sa_config``)."""
    return bool(config.get("sa_config"))


def latent(config: dict) -> bool:
    """Whether attention is latent attention (``kv_lora_rank``)."""
    return bool(config.get("kv_lora_rank"))


# -- the layers ----------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float,
            dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """Statistics in float32, result in ``dtype`` (what the products read)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _mm(eq: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """bfloat16 in, float32 accumulation, bfloat16 out."""
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def conv_taps(kernel: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution along a row: ``c[t] = sum_j k[:, j] *
    z[t - (taps - 1) + j]``, zero before the row's start. (B, L, h)."""
    taps, length = kernel.shape[1], z.shape[1]
    zp = jnp.pad(z.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    k32 = kernel.astype(jnp.float32)
    return sum(k32[:, j] * zp[:, j:j + length] for j in range(taps))


def conv_mixer(w: dict, u: jnp.ndarray) -> jnp.ndarray:
    """Gated short convolution: ``W_out (C * conv(B * X))``. (B, L, h)."""
    with jax.named_scope("lm.mixer.conv"):
        b, c, x = jnp.split(_mm("blh,hk->blk", u, w["conv_in"]), 3, axis=-1)
        z = b.astype(jnp.float32) * x.astype(jnp.float32)
        gated = (c.astype(jnp.float32) * conv_taps(w["conv_k"], z)).astype(u.dtype)
        return _mm("blh,hk->blk", gated, w["conv_out"])


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Half rotation (as Llama), positions from 0 in every row.
    (B, L, heads, d), float32 in and out."""
    length, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def qk_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm over the head width with a learned scale; float32."""
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     q_block: int) -> jnp.ndarray:
    """Blockwise causal grouped-query attention. ``q`` (B, L, nkv, g, d),
    ``k`` / ``v`` (B, L, nkv, d) -> (B, L, nkv, g, d).

    A block of ``q_block`` queries meets the keys up to its own end, so the
    largest score tensor is ``B x heads x q_block x L`` in float32 and the
    work is the causal half plus the diagonal blocks."""
    length, d = q.shape[1], q.shape[-1]
    qb = min(q_block, length)
    if length % qb:
        raise ValueError(f"row length {length} is no multiple of the query block {qb}")
    out = []
    for lo in range(0, length, qb):
        hi = lo + qb
        s = jnp.einsum("bqngd,bknd->bngqk", q[:, lo:hi], k[:, :hi],
                       preferred_element_type=jnp.float32) * d ** -0.5
        seen = (lo + jnp.arange(qb))[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v[:, :hi],
                              preferred_element_type=jnp.float32).astype(v.dtype))
    return jnp.concatenate(out, axis=1)


def indexer(w: dict, u: jnp.ndarray, config: dict) -> tuple:
    """The indexer's reading of (B, L, h): ``qI`` (B, L, J, di) and ``kI``
    (B, L, di) — one key head, through a LayerNorm — both rotated, bfloat16;
    and the heads' weights (B, L, J) float32, scaled by ``(J di) ** -0.5``."""
    with jax.named_scope("lm.attn.index"):
        sa = config["sa_config"]
        heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
        rows, length, _ = u.shape
        theta = config["rope_theta"]
        qi = _mm("blh,hk->blk", u, w["wqi"]).reshape(rows, length, heads, di)
        qi = rope(qi.astype(jnp.float32), theta).astype(u.dtype)
        ki = _mm("blh,hk->blk", u, w["wki"]).astype(jnp.float32)
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + norm_eps(config))
        ki = ki * w["ki_norm"].astype(jnp.float32) + w["ki_bias"].astype(jnp.float32)
        ki = rope(ki[:, :, None, :], theta)[:, :, 0].astype(u.dtype)
        wt = jnp.einsum("blh,hj->blj", u, w["wwi"], preferred_element_type=jnp.float32)
        return qi, ki, wt * (heads * di) ** -0.5


def _qkv(w: dict, u: jnp.ndarray, config: dict) -> tuple:
    """Attention's own reading of (B, L, h): ``q`` (B, L, nkv, g, d), ``k``
    and ``v`` (B, L, nkv, d), ``q`` and ``k`` normed per head and rotated."""
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    rows, length, h = u.shape
    d = config.get("head_dim", h // nq)
    eps, theta = norm_eps(config), config["rope_theta"]
    q = _mm("blh,hk->blk", u, w["wq"]).reshape(rows, length, nq, d)
    k = _mm("blh,hk->blk", u, w["wk"]).reshape(rows, length, nkv, d)
    v = _mm("blh,hk->blk", u, w["wv"]).reshape(rows, length, nkv, d)
    q = rope(qk_norm(q, w["q_norm"], eps), theta).astype(u.dtype)
    k = rope(qk_norm(k, w["k_norm"], eps), theta).astype(u.dtype)
    return q.reshape(rows, length, nkv, nq // nkv, d), k, v


def attn_mixer(w: dict, u: jnp.ndarray, config: dict, q_block: int) -> jnp.ndarray:
    """GQA with per-head QK RMSNorm before RoPE, over all causal keys.
    (B, L, h)."""
    with jax.named_scope("lm.mixer.attn"):
        q, k, v = _qkv(w, u, config)
        o = causal_attention(q, k, v, q_block)
        return _mm("blk,kh->blh", o.reshape(*u.shape[:2], -1), w["wo"])


def sparse_attn_mixer(w: dict, u: jnp.ndarray, config: dict, q_block: int,
                      real: jnp.ndarray) -> tuple:
    """The same attention over the ``topk`` keys the layer's indexer picks
    for each query. (B, L, h) and the (B, L) bool of positions that are no
    padding -> ((B, L, h), (B,) int32: the keys each row's real positions
    attended)."""
    with jax.named_scope("lm.mixer.attn"):
        o, kept = sparse_attention.sparse_attention(
            *_qkv(w, u, config), *indexer(w, u, config), real,
            config["sa_config"]["topk"], q_block)
        return _mm("blk,kh->blh", o.reshape(*u.shape[:2], -1), w["wo"]), kept


def _pairs_first(w: jnp.ndarray) -> jnp.ndarray:
    """Reorder the last axis ``(x0, x1, x2, x3, ...)`` to ``(x0, x2, ...,
    x1, x3, ...)``: the published rotation's pairs ``(2j, 2j + 1)`` become the
    halves' ``(j, j + d / 2)``."""
    d = w.shape[-1]
    return jnp.swapaxes(w.reshape(*w.shape[:-1], d // 2, 2), -1, -2).reshape(w.shape)


def latent_operands(w: dict, u: jnp.ndarray, config: dict) -> tuple:
    """Latent attention's reading of (B, L, h), in the kernel's layouts:
    ``q_n`` (B, H, L, d_n) and ``q_r`` (B, H, L, d_r), both times the softmax
    scale; ``k_n`` (B, H, L, d_n), the shared ``k_r`` (B, L, d_r), ``v`` (B,
    H, L, d_v). ``q_r`` and ``k_r`` are rotated, with their dimensions
    reordered alike (:func:`_pairs_first`, on the weights' columns)."""
    with jax.named_scope("lm.attn.latent"):
        heads = config["num_attention_heads"]
        dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
        rank, eps, scaling = config["kv_lora_rank"], norm_eps(config), config.get("rope_scaling")
        freqs = latent_attention.yarn_frequencies(dr, config["rope_theta"], scaling)
        gain = (latent_attention.yarn_mscale(scaling, "mscale")
                / latent_attention.yarn_mscale(scaling, "mscale_all_dim"))
        scale = latent_attention.softmax_scale(dn + dr, scaling)

        def heads_of(c: jnp.ndarray, wu: jnp.ndarray) -> jnp.ndarray:
            return jnp.einsum("blr,rhd->bhld", c, wu, preferred_element_type=jnp.float32)

        cq = rmsnorm(_mm("blh,hr->blr", u, w["w_dq"]), w["q_a_norm"], eps)
        w_uq = w["w_uq"].reshape(-1, heads, dn + dr)
        qn = (heads_of(cq, w_uq[..., :dn]) * scale).astype(u.dtype)
        qr = latent_attention.rotate_halves(
            heads_of(cq, _pairs_first(w_uq[..., dn:])), freqs, gain * scale).astype(u.dtype)
        kr = latent_attention.rotate_halves(
            jnp.einsum("blh,hr->blr", u, _pairs_first(w["w_dkv"][:, rank:]),
                       preferred_element_type=jnp.float32), freqs, gain).astype(u.dtype)
        ckv = rmsnorm(_mm("blh,hr->blr", u, w["w_dkv"][:, :rank]), w["kv_a_norm"], eps)
        w_ukv = w["w_ukv"].reshape(-1, heads, dn + dv)
        kn = heads_of(ckv, w_ukv[..., :dn]).astype(u.dtype)
        v = heads_of(ckv, w_ukv[..., dn:]).astype(u.dtype)
        return qn, qr, kn, kr, v


def latent_attn_mixer(w: dict, u: jnp.ndarray, config: dict,
                      lengths: jnp.ndarray) -> jnp.ndarray:
    """Latent attention over all causal keys. (B, L, h), (B,) real lengths."""
    with jax.named_scope("lm.mixer.attn"):
        o = latent_attention.attend(*latent_operands(w, u, config), lengths)
        wo = w["wo"].reshape(o.shape[1], o.shape[3], -1)
        return _mm("bhld,hdk->blk", o, wo)


def dense_ffn(w: dict, u: jnp.ndarray, names: tuple = ("w1", "w3", "w2"),
              scope: str = "lm.ffn.dense") -> jnp.ndarray:
    """``W_2 (silu(W_1 u) * W_3 u)``. (T, h)."""
    with jax.named_scope(scope):
        a = jnp.einsum("th,hf->tf", u, w[names[0]], preferred_element_type=jnp.float32)
        g = jnp.einsum("th,hf->tf", u, w[names[1]], preferred_element_type=jnp.float32)
        return _mm("tf,fh->th", (jax.nn.silu(a) * g).astype(u.dtype), w[names[2]])


def shared_ffn(w: dict, u: jnp.ndarray) -> jnp.ndarray:
    """The shared experts, one gated FFN of their summed width every token
    passes through: ``W_2^s (silu(W_1^s u) * W_3^s u)``. (T, h)."""
    return dense_ffn(w, u, ("ws1", "ws3", "ws2"), "lm.ffn.shared")


def moe_ffn(w: dict, u: jnp.ndarray, config: dict, experts: Optional[tuple]) -> tuple:
    """The sparse expert layer over (T, h) tokens -> (its part of the result
    for the experts held, the (T, k) expert ids the router chose, the (2,)
    int32 visits and row tiles of the experts' kernel)."""
    kind = moe.router_kind(config)
    if kind == "softmax":
        idx, weights = moe.route_softmax(u, w["router"], config["num_experts_per_tok"],
                                         config.get("norm_topk_prob", True))
    elif kind == "group_limited":
        idx, weights = moe.route_group_limited(
            u, w["router"], config["num_experts_per_tok"], config["n_group"],
            config["topk_group"], config.get("norm_topk_prob", True),
            float(config.get("routed_scaling_factor", 1.0)))
    else:
        idx, weights = moe.route(u, w["router"], w["expert_bias"],
                                 config["num_experts_per_tok"],
                                 float(config["routed_scaling_factor"]))
    out, tiles = moe.expert_ffn(u, idx, weights, w["w1"], w["w3"], w["w2"],
                                router_width(config), experts)
    if config.get("n_shared_experts"):
        out = out + shared_ffn(w, u)
    return out, idx, tiles


def head_logprobs(embed: jnp.ndarray, u: jnp.ndarray, targets: jnp.ndarray,
                  block: int, work: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(T, h) normed final states, the head's (V, h) matrix (the embedding
    where they are tied) and (T,) target ids -> (T,) float32
    ``log softmax(u E^T)[target]``. On a TPU, where the shapes can be tiled
    (:func:`mmlspark_tpu.ops.vocab_head.plan`), one kernel that keeps a
    tile's logits in VMEM and skips the token tiles none of whose positions
    is marked in ``work`` (T,) bool (they get 0; default: all have work).
    Elsewhere ``block`` tokens at a time: the logits of a block in float32,
    their log-sum-exp, the target's logit, and on (every position)."""
    tokens = u.shape[0]
    tiles = vocab_head.plan(tokens, u.shape[1], embed.shape[0])
    if tiles is not None:
        return vocab_head.head_kernel(embed, u, targets, work, tiles=tiles,
                                      **histogram._pallas_call_kwargs())
    block = min(block, tokens)
    if tokens % block:
        raise ValueError(f"{tokens} tokens are no multiple of the head's block {block}")

    def one(args: tuple) -> jnp.ndarray:
        ub, tb = args
        logits = jnp.einsum("th,vh->tv", ub, embed, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)

    out = jax.lax.map(one, (u.reshape(tokens // block, block, -1),
                            targets.reshape(tokens // block, block)))
    return out.reshape(tokens)


def count_columns(count: jnp.ndarray) -> jnp.ndarray:
    """(B,) int32 -> (B, 2) float32 ``(count >> COUNT_BITS, count & mask)``:
    float32 holds both halves exactly, and their sums over a model's layers,
    where a row of 32,768 positions counts 6.7e7 keys a layer."""
    return jnp.stack([count >> COUNT_BITS, count & ((1 << COUNT_BITS) - 1)],
                     axis=-1).astype(jnp.float32)


def forward(variables: dict, packed: jnp.ndarray, config: dict, q_block: int = Q_BLOCK,
            head_block: int = HEAD_BLOCK) -> jnp.ndarray:
    """(B, L + 1) int32 — ids, then each row's length — to (B, L - 1 + E + 2)
    float32: next-token log-probabilities (0 from the row's last real token
    on), the row's real tokens routed to each of the ``E`` experts the router
    scores over all layers, and in row 0 the batch's ``[visited, aligned]``
    tiles of the experts' kernel; with an indexer four columns more
    (:func:`count_columns`: the keys attended, the causal keys). Where the
    configuration is a share, the experts of ``expert_range`` alone add to
    the stream and ids and log-probabilities are over ``vocab_range``."""
    ids, lengths = packed[:, :-1], packed[:, -1]
    rows, length = ids.shape
    num_experts = router_width(config)
    experts = held_range(config, "expert_range")
    eps = norm_eps(config)
    real = jnp.arange(length)[None, :] < lengths[:, None]
    kept = 0.0
    if "vocab_range" in config:  # the stage refuses an id outside the slice; batch padding is 0
        ids = jnp.maximum(ids - held_range(config, "vocab_range")[0], 0)
    with jax.named_scope("lm.embed"):
        x = variables["embed"][ids].astype(jnp.float32)
    load = jnp.zeros((rows, num_experts), jnp.float32)
    tiles = jnp.zeros((2,), jnp.int32)
    for (mixer, ffn), w in zip(layer_kinds(config), variables["layers"]):
        u = rmsnorm(x, w["norm_op"], eps)
        if mixer == "conv":
            y = conv_mixer(w, u)
        elif latent(config):
            y = latent_attn_mixer(w, u, config, lengths)
        elif selects_keys(config):
            y, n = sparse_attn_mixer(w, u, config, q_block, real)
            kept = kept + count_columns(n)
        else:
            y = attn_mixer(w, u, config, q_block)
        x = x + y.astype(jnp.float32)
        u = rmsnorm(x, w["norm_ffn"], eps).reshape(rows * length, -1)
        if ffn == "dense":
            y = dense_ffn(w, u)
        else:
            y, idx, visited = moe_ffn(w, u, config, experts)
            tiles = tiles + visited
            with jax.named_scope("lm.moe.route"):
                load = load + moe.expert_load(idx.reshape(rows, length, -1), real, num_experts)
        x = x + y.reshape(rows, length, -1).astype(jnp.float32)
    with jax.named_scope("lm.head"):
        u = rmsnorm(x, variables["norm"], eps).reshape(rows * length, -1)
        targets = jnp.concatenate([ids[:, 1:], jnp.zeros((rows, 1), ids.dtype)], axis=1)
        work = vocab_head.has_next(lengths, length)  # the rest read as 0
        logp = head_logprobs(head_matrix(variables, config), u, targets.reshape(-1),
                             head_block, work.reshape(-1))
        logp = jnp.where(work, logp.reshape(rows, length), 0.0)[:, :-1]
    # a count of the batch: its first row carries it (every batch has a real one)
    tiles = jnp.zeros((rows, 2), jnp.float32).at[0].set(tiles.astype(jnp.float32))
    if not selects_keys(config):
        return jnp.concatenate([logp, load, tiles], axis=1)
    layers = sum(1 for mixer, _ffn in layer_kinds(config) if mixer != "conv")
    causal = count_columns(lengths * (lengths + 1) // 2) * layers
    return jnp.concatenate([logp, load, tiles, kept, causal], axis=1)


# -- the stage -----------------------------------------------------------------

class CausalLMScorer(Model, HasInputCol, HasOutputCol):
    """Per-row next-token log-probabilities of a token-id column.

    ``config`` holds the model's published keys (``hidden_size``,
    ``layer_types``, ``num_experts`` ...: the model's own ``config.json``);
    ``variables`` is ``{"embed": (V, h), "norm": (h,), "layers": [per-layer
    dict]}``, with ``"head": (V, h)`` where the head is not the embedding
    (docs/models.md names every array). Hand it arrays that already
    live on the device and they are used where they are: 9 GB of weights are
    not copied. ``buckets`` are ``[length, rows per batch]`` pairs; a row
    goes to the shortest bucket that holds it, and every bucket is one
    compiled shape (warm them all: :meth:`warm_up`)."""

    config = ComplexParam("the model's config.json keys as a dict")
    variables = ComplexParam("model variables: embed, norm, layers (see docs/models.md)")
    buckets = ComplexParam(
        "length buckets as [[length, rows_per_batch], ...]; each is one compiled shape",
        default=[[512, 8]],
    )

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._inner: Optional[XLAModel] = None

    def _build(self) -> XLAModel:
        if self._inner is None:
            config = dict(self.get_or_fail("config"))

            def apply_fn(vs: Any, packed: Any) -> Any:
                return forward(vs, packed, config)

            self._inner = XLAModel(input_col="__tokens__", output_col=self.get_or_fail("output_col"),
                                   input_dtype=None)  # int32 ids stay int32
            self._inner.set(apply_fn=apply_fn, variables=self.get_or_fail("variables"))
            # forward and the config it reads: a warm start loads each bucket's program untraced
            self._inner.program_identity = ("mmlspark_tpu.models.causal_lm.forward", config)
        return self._inner

    def _buckets(self) -> list:
        buckets = sorted((int(length), int(rows)) for length, rows in self.get("buckets"))
        if not buckets:
            raise ValueError("CausalLMScorer needs at least one length bucket")
        return buckets

    def warm_up(self) -> None:
        """Compile and run every bucket's shape once (a two-token row each)."""
        inner = self._build()
        for length, rows in self._buckets():
            packed = np.zeros((1, length + 1), np.int32)
            packed[0, -1] = 2
            inner.apply_batch(packed, batch_size=rows)

    def transform(self, df: DataFrame) -> DataFrame:
        ic = self.get_or_fail("input_col")
        oc = self.get_or_fail("output_col")
        inner = self._build()
        buckets = self._buckets()
        edges = np.array([length for length, _ in buckets])
        config = self.get_or_fail("config")
        num_experts = router_width(config)
        held = held_range(config, "expert_range")
        ids_lo, ids_hi = held_range(config, "vocab_range") or (0, None)
        counts_keys = selects_keys(config)
        vocab, width = head_matrix(self.get_or_fail("variables"), config).shape
        # what the head's kernel visits follows from a batch's shape and its rows' lengths
        head_plans = [vocab_head.plan(batch * length, width, vocab) for length, batch in buckets]

        def fn(p: Partition) -> Partition:
            rows = [np.asarray(r, np.int32) for r in p[ic]]
            lens = np.array([len(r) for r in rows], np.int64)
            if len(rows) and (lens.min() < 2 or lens.max() > edges[-1]):
                raise ValueError(
                    f"CausalLMScorer: rows of {lens.min()}..{lens.max()} tokens; a row needs "
                    f"2 tokens at least and {edges[-1]} (the longest bucket) at most")
            if ids_hi is not None and any(
                    len(r) and (r.min() < ids_lo or r.max() >= ids_hi) for r in rows):
                raise ValueError(f"CausalLMScorer: this share holds the ids [{ids_lo}, {ids_hi}) "
                                 "of the vocabulary; a row has an id outside them")
            bucket_of = np.searchsorted(edges, lens, side="left")
            out = np.empty(len(rows), dtype=object)
            routed = np.zeros(num_experts, np.float64)
            tiles = np.zeros(2, np.float64)  # the experts' kernel: visited, aligned
            keys = np.zeros(4, np.float64)  # selected and causal, each (high, low)
            head_tiles = np.zeros(2, np.int64)  # the head's kernel: visited, skipped
            real = padded = 0
            # one trace per partition; every bucket's apply_batch is a child
            with obs.span("lm.score", attrs={"rows": len(rows)}) as sp:
                for b, (length, batch) in enumerate(buckets):
                    at = np.nonzero(bucket_of == b)[0]
                    if not len(at):
                        continue
                    packed = np.full((len(at), length + 1), ids_lo, np.int32)  # the pad id
                    for j, i in enumerate(at):
                        packed[j, :lens[i]] = rows[i]
                    packed[:, -1] = lens[at]
                    batches = -(-len(at) // batch)
                    with obs.span("lm.bucket", attrs={
                            "length": length, "rows": len(at), "batches": batches}):
                        res = inner.apply_batch(packed, batch_size=batch)
                    for j, i in enumerate(at):
                        out[i] = res[j, :lens[i] - 1].copy()
                    counts = res[:, length - 1:].sum(0, dtype=np.float64)
                    routed += counts[:num_experts]
                    tiles += counts[num_experts:num_experts + 2]
                    if counts_keys:
                        keys += counts[num_experts + 2:]
                    real += int(lens[at].sum())
                    padded += batches * batch * length - int(lens[at].sum())
                    if head_plans[b] is not None:
                        sent = np.zeros(batches * batch, np.int64)  # batch padding: no tokens
                        sent[:len(at)] = lens[at]
                        head_tiles += vocab_head.count_tiles(sent, length, head_plans[b][0])
                sp.set_attr("tokens_real", real)
                sp.set_attr("tokens_padded", padded)
                sp.set_attr("gmm_tiles_visited", int(tiles[0]))
                sp.set_attr("gmm_tiles_aligned", int(tiles[1]))
                sp.set_attr("head_tiles", int(head_tiles[0]))
                sp.set_attr("head_tiles_skipped", int(head_tiles[1]))
                if held is not None:
                    sp.set_attr("moe_pairs_held", int(routed[held[0]:held[1]].sum()))
                    sp.set_attr("moe_pairs_routed", int(routed.sum()))
                if counts_keys:
                    selected, causal = (int(hi) * (1 << COUNT_BITS) + int(lo)
                                        for hi, lo in keys.reshape(2, 2))
                    sp.set_attr("attn_keys_selected", selected)
                    sp.set_attr("attn_keys_causal", causal)
            _M_TOKENS.labels(kind="real").inc(real)
            _M_TOKENS.labels(kind="padded").inc(padded)
            for e, n in enumerate(routed):
                _M_ROUTED.labels(expert=str(e)).inc(float(n))
            _M_GMM_TILES.labels(kind="visited").inc(int(tiles[0]))
            _M_GMM_TILES.labels(kind="aligned").inc(int(tiles[1]))
            _M_HEAD_TILES.labels(kind="visited").inc(int(head_tiles[0]))
            _M_HEAD_TILES.labels(kind="skipped").inc(int(head_tiles[1]))
            if counts_keys:
                _M_ATTN_KEYS.labels(kind="selected").inc(selected)
                _M_ATTN_KEYS.labels(kind="causal").inc(causal)
            q = dict(p)
            q[oc] = out
            return q

        return df.map_partitions(fn, parallel=False)
