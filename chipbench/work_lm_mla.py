"""Operations and bytes of the cell that scores documents with one chip's
share of a latent-attention, shared-and-routed-experts language model
(``deepseek_v2``), counted from its shapes alone, whatever implements a layer.

Kept with the benchmark, beside ``work_lm.py`` and ``work_lm_sparse.py``, so
that no PR that claims a gain can change the yardstick. Nothing here reads
the program or a trace. A multiply-add is two operations; norms,
activations, the rotation, the router's softmax and selection and the
attention's softmax are left out. ``config`` is the configuration's file:
``n_routed_experts`` and ``vocab_size`` count what this chip holds,
``published`` what the model has; a balanced router is assumed, which sends
the held experts ``held / published`` of every token's
``num_experts_per_tok`` pairs.
"""

from __future__ import annotations


def attn_params(config: dict) -> int:
    """The five projections of one latent-attention layer: ``W_DQ``,
    ``W_UQ``, ``W_DKV``, ``W_UKV``, ``W_O``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    return (h * rq + rq * heads * (dn + dr) + h * (rkv + dr) + rkv * heads * (dn + dv)
            + heads * dv * h)


def held_share(config: dict) -> float:
    """The share of the router's experts this chip holds."""
    return config["n_routed_experts"] / config["published"]["n_routed_experts"]


def expert_layer_params(config: dict) -> int:
    """One expert layer as held: attention, the shared experts, the router
    at its published width, the held experts."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    return (attn_params(config) + 3 * h * f * config["n_shared_experts"]
            + h * config["published"]["n_routed_experts"]
            + 3 * h * f * config["n_routed_experts"])


def token_flops(config: dict) -> float:
    """Matrix products one token needs in every layer run, attention's pairs
    and the head left out: the five projections; the dense FFN in the
    leading layers; in the rest the shared experts, the router and the
    token's ``num_experts_per_tok * held_share`` routed experts."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    sparse = config["num_hidden_layers"] - dense
    ffn = (3 * h * f * config["n_shared_experts"] + h * config["published"]["n_routed_experts"]
           + 3 * h * f * config["num_experts_per_tok"] * held_share(config))
    return 2.0 * (config["num_hidden_layers"] * attn_params(config)
                  + dense * 3 * h * config["intermediate_size"] + sparse * ffn)


def causal_pairs(lengths: list) -> float:
    """Causal (query, key) pairs of rows of those lengths: ``n (n + 1) / 2``."""
    return float(sum(n * (n + 1) // 2 for n in map(int, lengths)))


def pair_flops(config: dict) -> float:
    """One pair in one layer: a score of ``d_n + d_r`` and a value of
    ``d_v`` for every head."""
    return 2.0 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"])


def pairs_call(config: dict, lengths: list) -> dict:
    """One layer's causal pairs over rows of those real lengths: ``q``,
    ``k_n``, the shared ``k_r`` and ``v`` read once, the result written once
    (bfloat16); padding and the masked half of a diagonal tile count for
    nothing."""
    heads = config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    tokens = float(sum(int(n) for n in lengths))
    return {"flops": causal_pairs(lengths) * pair_flops(config),
            "bytes": 2.0 * tokens * (heads * (dn + dr) + heads * dn + dr + 2 * heads * dv)}


def held_experts_call(config: dict, batch_tokens: int) -> dict:
    """One expert layer's routed products over one batch, as held: the
    ``batch_tokens * num_experts_per_tok * held_share`` rows a balanced
    router sends here through the three products of a gated FFN, the held
    experts' matrices read once, the rows read once and written once
    (bfloat16)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = batch_tokens * config["num_experts_per_tok"] * held_share(config)
    return {"flops": 2.0 * 3 * h * f * rows,
            "bytes": 2.0 * (3 * h * f * config["n_routed_experts"] + 2 * rows * h)}


def step_flops(config: dict, lengths: list) -> float:
    """All the work of scoring documents of those lengths: per token the
    layers' products, per layer the causal pairs, and the head over the
    slice of the vocabulary for every position with a next token."""
    tokens = sum(int(n) for n in lengths)
    return (tokens * token_flops(config)
            + config["num_hidden_layers"] * causal_pairs(lengths) * pair_flops(config)
            + (tokens - len(lengths)) * 2.0 * config["hidden_size"] * config["vocab_size"])
