"""Driver: long token-id documents through ``CausalLMScorer`` with a learned
sparse attention (the ``KeyeVL2`` language model).

The window is the other language-model cell's, to the letter
(``lm_score_stream.window``: one closed-loop
``StreamingDataFrame.transform(CausalLMScorer).foreach_chunk(sink)`` over a
pool of ``corpus_chunks`` traffic), and so are the pool, the sample and the
statistics of the comparison; the configuration's keys, its weights and its
plain reference (``chipbench/reference/keye.py``) are this driver's own.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from chipbench.drivers import lm_score_stream as base
from chipbench.drivers.lm_score_stream import release, window  # noqa: F401  (the harness's)
from chipbench.reference import keye as ref

# |logprob - reference| over every real position of the sampled rows, as a
# share of the spread of the reference's own log-probabilities there (the
# other cell's statistics); PERF.md section 2 gives the readings each limit
# was set from. No maximum: a near-tie in a router flips a token's experts,
# a near-tie at the selection's edge swaps one key of 2,048
LIMIT_REL_ERR_MEDIAN = 0.015
LIMIT_REL_ERR_P90 = 0.057

# what the program holds in float32 (values that bfloat16 holds exactly)
_FLOAT32 = ("norm", "norm_op", "norm_ffn", "q_norm", "k_norm", "ki_norm", "ki_bias", "router")
_MODEL_KEYS = (
    "head_dim", "hidden_size", "layer_types", "moe_intermediate_size", "norm_topk_prob",
    "num_attention_heads", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rms_norm_eps", "rope_theta", "sa_config",
    "tie_word_embeddings", "vocab_size")


def model_config(config: dict) -> dict:
    """The model's own keys of the configuration's file."""
    return {k: config[k] for k in _MODEL_KEYS}


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
        "layer": jax.jit(lambda k, i: cast(ref.make_layer_weights(config, k, i))),
        "embed": jax.jit(lambda k: cast(ref.make_embedding(config, k))),
    })
    layers = [make["layer"](key, i) for i in range(config["num_hidden_layers"])]
    return dict(make["embed"](key), layers=layers)


def setup(ctx: object) -> dict:
    try:
        from mmlspark_tpu.models import causal_lm
        from mmlspark_tpu.ops import sparse_attention  # noqa: F401  (what the model needs)
    except ImportError as e:
        # a checkout whose scorer has no attention over an indexer's
        # selection cannot run the configuration: said before any weight is made
        sys.stderr.write(f"chipbench: this checkout's mmlspark_tpu has no learned sparse "
                         f"attention ({e}): the cell cannot run here\n")
        sys.exit(2)

    traffic = ctx.traffic
    model = model_config(ctx.config)
    key = ctx.key()
    scorer = causal_lm.CausalLMScorer(
        input_col="tokens", output_col="logprob", config=model,
        variables=program_variables(model, key), buckets=traffic["buckets"],
    )
    pool = base.make_pool(traffic, model, ctx.seed)
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
    distinct sampled row once, padded on the right to its bucket's length
    (one set of reference programs a bucket); a chunk that passed several
    times is compared each time."""
    chunks = sorted({k for k, _rows in state["sample"]})
    flat = [(k, j) for k in chunks for j in range(len(state["check_rows"][k]))]
    padded = []
    for k, j in flat:
        row = state["check_rows"][k][j]
        full = np.zeros(base._bucket_of(traffic, len(row)), np.int32)
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
    """The reference with every matmul input but the router's and the
    indexer's rounded to float8 (e4m3), the precision below the bfloat16 the
    configuration states, in the program's place on the same sample."""
    import jax.numpy as jnp

    return _readings(compare(state, ctx.traffic, lower_dtype=jnp.float8_e4m3fn))


def check(ctx: object, state: dict) -> list:
    if not state["sample"]:
        return [{"name": "rows_compared", "value": 0, "limit": 1, "ok": False}]
    return _readings(compare(state, ctx.traffic))
