"""Operations and bytes of the language-model scoring cell, counted from its
shapes alone, whatever implements a layer.

Kept with the benchmark, beside ``work.py``, so that no PR that claims a
gain can change the yardstick. Nothing here reads the program or a trace.
A multiply-add is two operations; norms, activations, the convolution's
taps, RoPE and the softmax are left out (under 1% of a token's work).
"""

from __future__ import annotations


def layer_kinds(config: dict) -> list:
    """``[(mixer, ffn)]`` of the layers run: the first ``num_hidden_layers``
    of ``layer_types``, as the reference reads them."""
    from chipbench.reference.lfm2 import layer_kind

    return [layer_kind(config, i) for i in range(config["num_hidden_layers"])]


def token_flops(config: dict) -> float:
    """Matrix products one token needs in every layer, attention's scores
    and the head left out: the mixers' projections, the dense FFNs, the
    router and the ``num_experts_per_tok`` experts a token is routed to."""
    h = config["hidden_size"]
    kv = h // config["num_attention_heads"] * config["num_key_value_heads"]
    total = 0.0
    for mixer, ffn in layer_kinds(config):
        total += 2.0 * (4 * h * h if mixer == "conv" else 2 * h * h + 2 * h * kv)
        if ffn == "dense":
            total += 2.0 * 3 * h * config["intermediate_size"]
        else:
            total += 2.0 * h * config["num_experts"]
            total += 2.0 * 3 * h * config["moe_intermediate_size"] * config["num_experts_per_tok"]
    return total


def step_flops(config: dict, rows: int, tokens: int, tokens_sq: float) -> float:
    """All the work of scoring ``rows`` documents of ``tokens`` real tokens
    in all (``tokens_sq`` = the sum of their squared lengths): per token the
    layers' products, per attention layer the causal scores and their use
    (position ``t`` meets ``t + 1`` keys: ``n (n + 1) / 2`` pairs a row,
    two products of the query heads' whole width each), and the head over
    the vocabulary for every position that has a next token."""
    h = config["hidden_size"]
    attn_layers = sum(1 for mixer, _ffn in layer_kinds(config) if mixer != "conv")
    pairs = (tokens_sq + tokens) / 2.0
    return (tokens * token_flops(config)
            + attn_layers * 2.0 * 2 * h * pairs
            + (tokens - rows) * 2.0 * h * config["vocab_size"])


def experts_call(config: dict, batch_tokens: int) -> dict:
    """One expert layer over one batch: every token's ``num_experts_per_tok``
    routed rows through the three products of a gated FFN, every held
    expert's weights read once, the routed rows read once and written once
    (bfloat16)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    routed = batch_tokens * config["num_experts_per_tok"]
    return {
        "flops": 2.0 * 3 * h * f * routed,
        "bytes": 2.0 * (3 * h * f * config["num_experts"] + 2 * routed * h),
    }
