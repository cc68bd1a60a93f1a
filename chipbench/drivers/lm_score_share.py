"""Driver: token-id documents through ``CausalLMScorer`` as one chip's share
of an expert-parallel deployment (``deepseek_v2``: latent attention, a
group-limited router, shared experts; the chip holds one routing group's
experts and a slice of the vocabulary).

The window is the other language-model cells', to the letter
(``lm_score_stream.window``: one closed-loop
``StreamingDataFrame.transform(CausalLMScorer).foreach_chunk(sink)`` over a
pool of ``corpus_chunks`` traffic), and so are the pool, the sample and the
statistics of the comparison; the configuration's keys, the share, its
weights and its plain reference (``chipbench/reference/deepseek_v2.py``) are
this driver's own. The share reaches the program as two keys of the stage's
configuration — ``expert_range`` and ``vocab_range`` — beside the model's
published router width; the reference is handed the same dictionary.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from chipbench.drivers import lm_score_stream as base
from chipbench.drivers.lm_score_stream import release, window  # noqa: F401  (the harness's)
from chipbench.reference import deepseek_v2 as ref

# |logprob - reference| over every real position of the sampled rows, as a
# share of the spread of the reference's own log-probabilities there (the
# other cells' statistics); PERF.md section 2 gives the readings each limit
# was set from. No maximum: a near-tie in a router or between two groups
# flips a token's experts
LIMIT_REL_ERR_MEDIAN = 0.009
LIMIT_REL_ERR_P90 = 0.024

# what the program holds in float32 (values that bfloat16 holds exactly)
_FLOAT32 = ("norm", "norm_op", "norm_ffn", "q_a_norm", "kv_a_norm", "router")
_MODEL_KEYS = (
    "first_k_dense_replace", "hidden_size", "intermediate_size", "kv_lora_rank",
    "moe_intermediate_size", "n_group", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "tie_word_embeddings", "topk_group",
    "topk_method", "v_head_dim", "expert_range", "vocab_range")


def model_config(config: dict) -> dict:
    """The model's own keys of the configuration's file, with the router at
    its published width and the vocabulary at its published size: the file
    counts what is held (``n_routed_experts``, ``vocab_size``) and says
    which (``expert_range``, ``vocab_range``)."""
    lo, hi = config["expert_range"]
    first, last = config["vocab_range"]
    if hi - lo != config["n_routed_experts"] or last - first != config["vocab_size"]:
        raise ValueError("the configuration's ranges do not hold what its counts say")
    return dict({k: config[k] for k in _MODEL_KEYS},
                n_routed_experts=config["published"]["n_routed_experts"],
                vocab_size=config["published"]["vocab_size"])


_WEIGHT_PROGRAMS: dict = {}


def program_variables(config: dict, key: object) -> dict:
    """The seeded weights in the tree the program reads, made on the device
    a layer at a time by the reference's own function and cast to bfloat16
    there (the values are bfloat16 already), so that no float32 copy of a
    layer outlives its program."""
    import jax
    import jax.numpy as jnp

    def cast(w: dict) -> dict:
        return {k: v if k in _FLOAT32 else v.astype(jnp.bfloat16) for k, v in w.items()}

    make = _WEIGHT_PROGRAMS.setdefault(json.dumps(config, sort_keys=True), {
        kind: jax.jit(lambda k, i, kind=kind: cast(ref.make_layer_weights(config, k, i, kind)))
        for kind in ("dense", "moe")})
    if "embed" not in make:
        make["embed"] = jax.jit(lambda k: cast(ref.make_embedding(config, k)))
    layers = [make[ref.ffn_kind(config, i)](key, i) for i in range(config["num_hidden_layers"])]
    return dict(make["embed"](key), layers=layers)


def make_pool(traffic: dict, model: dict, seed: int) -> list:
    """The other cells' pool, its ids drawn uniformly over the slice held."""
    first, last = model["vocab_range"]
    pool = base.make_pool(traffic, {"vocab_size": last - first}, seed)
    return [[row + np.int32(first) for row in rows] for rows in pool] if first else pool


def setup(ctx: object) -> dict:
    try:
        from mmlspark_tpu.models import causal_lm
        from mmlspark_tpu.ops import latent_attention  # noqa: F401  (what the model needs)
    except ImportError as e:
        # a checkout whose scorer has no latent attention cannot run the
        # configuration: said before any weight is made
        sys.stderr.write(f"chipbench: this checkout's mmlspark_tpu has no latent attention "
                         f"({e}): the cell cannot run here\n")
        sys.exit(2)

    traffic = ctx.traffic
    model = model_config(ctx.config)
    key = ctx.key()
    scorer = causal_lm.CausalLMScorer(
        input_col="tokens", output_col="logprob", config=model,
        variables=program_variables(model, key), buckets=traffic["buckets"],
    )
    pool = make_pool(traffic, model, ctx.seed)
    # the rows the comparison reads, the same of a pool chunk each time it
    # passes: per bucket the rows the seed picked
    pick_rng = ctx.rng(2)
    per_bucket = int(traffic["check_rows_per_bucket"])
    picks = []
    for rows in pool:
        by_bucket: dict = {}
        for r, row in enumerate(rows):
            by_bucket.setdefault(base._bucket_of(traffic, len(row)), []).append(r)
        picks.append(sorted(int(r) for b in sorted(by_bucket) for r in pick_rng.choice(
            by_bucket[b], min(per_bucket, len(by_bucket[b])), replace=False)))
    # warm-up: one whole chunk through the stage itself; it holds rows of
    # every bucket, so every shape of the cell compiles (or loads) and runs
    with ctx.span("warmup"):
        scorer.transform(base._as_frame(pool[0]))["logprob"]
    return {"scorer": scorer, "pool": pool, "picks": picks, "model": model, "key": key,
            "sample": []}


def compare(state: dict, traffic: dict, lower_dtype: object = None) -> dict:
    """``lm_score_stream.compare`` with this configuration's reference: every
    distinct sampled row once, padded on the right (with the slice's first
    id) to its bucket's length, so that the reference compiles one set of
    programs a bucket; a chunk that passed several times is compared each time."""
    first = state["model"]["vocab_range"][0]
    chunks = sorted({k for k, _rows in state["sample"]})
    flat = [(k, j) for k in chunks for j in range(len(state["check_rows"][k]))]
    padded = []
    for k, j in flat:
        row = state["check_rows"][k][j]
        full = np.full(base._bucket_of(traffic, len(row)), first, np.int32)
        full[:len(row)] = row
        padded.append(full)
    if "want" not in state:  # the control reads the same reference
        state["want"] = dict(zip(flat, ref.logprobs(state["model"], state["key"], padded)))
    want = state["want"]
    if lower_dtype is not None:
        low = dict(zip(flat, ref.logprobs(state["model"], state["key"], padded, lower_dtype)))
    gaps, refs, rows = [], [], 0
    for k, got_rows in state["sample"]:
        for j, got in enumerate(got_rows):
            n = len(state["check_rows"][k][j]) - 1
            if lower_dtype is not None:
                got = low[(k, j)][:n]
            if len(got) != n:
                got = np.full(n, np.inf)
            gaps.append(np.abs(np.asarray(got, np.float64) - want[(k, j)][:n]))
            refs.append(np.asarray(want[(k, j)][:n], np.float64))
            rows += 1
    spread = float(np.concatenate(refs).std())
    gap = np.nan_to_num(np.concatenate(gaps), nan=np.inf, posinf=np.inf) / max(spread, 1e-30)
    out = {"rows": rows, "positions": len(gap), "logprob_spread": spread,
           "max": float(gap.max())}
    for q in (50, 75, 90, 99):
        out[f"p{q}"] = float(np.percentile(gap, q, method="lower"))
    sys.stderr.write("chipbench: lm_score " + json.dumps(
        {"of": "control" if lower_dtype is not None else "program", "rel_err": out}) + "\n")
    return out


def _readings(r: dict) -> list:
    return [
        {"name": "logprob_rel_err_median", "value": r["p50"],
         "limit": LIMIT_REL_ERR_MEDIAN, "ok": r["p50"] <= LIMIT_REL_ERR_MEDIAN},
        {"name": "logprob_rel_err_p90", "value": r["p90"],
         "limit": LIMIT_REL_ERR_P90, "ok": r["p90"] <= LIMIT_REL_ERR_P90},
        {"name": "rows_compared", "value": r["rows"], "limit": 1, "ok": r["rows"] >= 1},
    ]


def control(ctx: object, state: dict) -> list:
    """The reference with every matmul input but the router's rounded to
    float8 (e4m3), the precision below the bfloat16 the configuration
    states, in the program's place on the same sample."""
    import jax.numpy as jnp

    return _readings(compare(state, ctx.traffic, lower_dtype=jnp.float8_e4m3fn))


def check(ctx: object, state: dict) -> list:
    if not state["sample"]:
        return [{"name": "rows_compared", "value": 0, "limit": 1, "ok": False}]
    return _readings(compare(state, ctx.traffic))
