"""Operations and bytes of the long-document scoring cell — a language
model whose attention runs over an indexer's selection — counted from its
shapes alone, whatever implements a layer.

Kept with the benchmark, beside ``work_lm.py``, so that no PR that claims a
gain can change the yardstick. Nothing here reads the program or a trace.
A multiply-add is two operations; norms, activations, RoPE, the ReLU and
the weighting of the index scores, the selection itself (comparisons, no
products) and the softmax are left out. Every layer is an attention layer
with an indexer and an expert FFN (the configuration's ``layer_types`` and
``num_dense_layers: 0`` say so to ``work_lm.py``'s readers).
"""

from __future__ import annotations


def token_flops(config: dict) -> float:
    """Matrix products one token needs in every layer, the pairs of
    attention and of the indexer and the head left out: the projections of
    attention at the published head width (``head_dim``, not ``hidden /
    heads``), the indexer's three projections, the router and the
    ``num_experts_per_tok`` experts a token is routed to."""
    h, d = config["hidden_size"], config["head_dim"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layer = (2 * h * nq * d + 2 * h * nkv * d            # W_q, W_o; W_k, W_v
             + h * heads * di + h * di + h * heads       # W_qI, W_kI, W_w
             + h * config["num_experts"]
             + 3 * h * config["moe_intermediate_size"] * config["num_experts_per_tok"])
    return 2.0 * layer * config["num_hidden_layers"]


def index_pairs(lengths: list) -> float:
    """Causal (query, key) pairs of rows of those lengths: ``L (L + 1) / 2``
    a row; the indexer scores every one."""
    return float(sum(n * (n + 1) // 2 for n in map(int, lengths)))


def attended_pairs(lengths: list, topk: int) -> float:
    """(query, key) pairs attention runs over: position ``t`` meets
    ``min(t + 1, topk)`` keys."""
    total = 0
    for n in map(int, lengths):
        m = min(n, topk)
        total += m * (m + 1) // 2 + (n - m) * topk
    return float(total)


def index_flops(config: dict, lengths: list) -> float:
    """One layer's index scores over those rows: a product of
    ``indexer_head_dim`` for each of the indexer's heads a causal pair."""
    sa = config["sa_config"]
    return index_pairs(lengths) * 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def attend_flops(config: dict, lengths: list) -> float:
    """One layer's attention over the selection: two products of the query
    heads' whole width for every attended pair."""
    width = config["num_attention_heads"] * config["head_dim"]
    return attended_pairs(lengths, config["sa_config"]["topk"]) * 2.0 * 2 * width


def attention_flops(config: dict, lengths: list) -> float:
    """One layer's index scores and its attention over the selection."""
    return index_flops(config, lengths) + attend_flops(config, lengths)


def attend_bytes(config: dict, tokens: int) -> float:
    """What the attention over the selection must move a layer: q, k, v read
    once, the result written once (bfloat16)."""
    d, nq, nkv = config["head_dim"], config["num_attention_heads"], config["num_key_value_heads"]
    return float(tokens) * 2 * (2 * nq * d + 2 * nkv * d)


def attention_bytes(config: dict, tokens: int) -> float:
    """What one layer's sparse attention must move for ``tokens`` positions:
    q, k, v, the indexer's queries and key (bfloat16) and its heads' weights
    (float32) read once, the result written once."""
    d, sa = config["head_dim"], config["sa_config"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return float(tokens) * (2 * (2 * nq * d + 2 * nkv * d + heads * di + di) + 4 * heads)


def step_flops(config: dict, lengths: list) -> float:
    """All the work of scoring documents of those lengths: per token the
    layers' products, per layer the index pairs and the attended pairs, and
    the untied head over the vocabulary for every position with a next token."""
    tokens = sum(int(n) for n in lengths)
    return (tokens * token_flops(config)
            + config["num_hidden_layers"] * attention_flops(config, lengths)
            + (tokens - len(lengths)) * 2.0 * config["hidden_size"] * config["vocab_size"])
