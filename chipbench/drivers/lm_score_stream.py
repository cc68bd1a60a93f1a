"""Driver: a streamed DataFrame of token-id documents through ``CausalLMScorer``.

The window drives what a user calls:
``StreamingDataFrame.transform(CausalLMScorer).foreach_chunk(sink)`` — the
stage's length buckets, ``XLAModel.apply_batch`` once per bucket, and the
language-model program at each bucket's batch. From the program it takes the
stage, the stream and the DataFrame; weights, documents, the sample and the
comparison are the benchmark's own (``chipbench/reference/lfm2.py``).

Traffic (``kind: corpus_chunks``): every chunk holds the same multiset of
document lengths — per group ``[lo, hi, n]``, ``n`` lengths evenly spaced
over ``(lo, hi]`` (the first group includes its ``lo``) — so ``rows_per_s``
does not wander with the seed; the seed draws the ids (uniform over the
vocabulary) and the order of the rows in a chunk. A pool of ``pool_chunks``
distinct chunks cycles in one closed loop that ends at the first chunk
boundary after ``--seconds``.
"""

from __future__ import annotations

import collections
import json
import sys
import time

import numpy as np

from chipbench.reference import lfm2 as ref

# |logprob - reference| over every real position of the sampled rows, as a
# share of the spread (standard deviation) of the reference's own
# log-probabilities over those positions, so that one limit serves the
# published width and the test size (the spread grows with the hidden size);
# PERF.md section 2 gives the readings each limit was set from. No maximum: a
# near-tie in a router flips one token's experts and moves that position's
# log-probability by what a whole expert adds
LIMIT_REL_ERR_MEDIAN = 0.018
LIMIT_REL_ERR_P90 = 0.09

# what the program holds in float32 (values that bfloat16 holds exactly)
_FLOAT32 = ("norm", "norm_op", "norm_ffn", "q_norm", "k_norm", "router", "expert_bias")
_MODEL_KEYS = (
    "conv_L_cache", "hidden_size", "intermediate_size", "layer_types",
    "moe_intermediate_size", "norm_eps", "num_attention_heads", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "rope_theta", "routed_scaling_factor", "vocab_size", "expert_bias_std")


def model_config(config: dict) -> dict:
    """The model's own keys of the configuration's file, and the one
    assumed scale the file states as a number (the expert bias's spread)."""
    return {k: config[k] for k in _MODEL_KEYS}


_WEIGHT_PROGRAMS: dict = {}


def program_variables(config: dict, key: object, kinds: list) -> dict:
    """The seeded weights in the tree the program reads, made on the device
    a layer at a time by the reference's own function and cast to bfloat16
    there (the values are bfloat16 already), so that no float32 copy of a
    layer outlives its program. ``kinds`` is the program's list of layer
    kinds: a layer gets the weights of the kind the program runs."""
    import jax
    import jax.numpy as jnp

    def cast(w: dict) -> dict:
        return {k: v if k in _FLOAT32 else v.astype(jnp.bfloat16) for k, v in w.items()}

    # one program per kind of layer (the layer's number is an argument),
    # built once a process
    make = _WEIGHT_PROGRAMS.setdefault(json.dumps(config, sort_keys=True), {})
    layers = []
    for i, kind in enumerate(kinds):
        if kind not in make:
            make[kind] = jax.jit(
                lambda k, i, kind=kind: cast(ref.make_layer_weights(config, k, i, kind)))
        layers.append(make[kind](key, i))
    if "embed" not in make:
        make["embed"] = jax.jit(lambda k: cast(ref.make_embedding(config, k)))
    return dict(make["embed"](key), layers=layers)


def chunk_lengths(traffic: dict) -> np.ndarray:
    """The multiset of document lengths of every chunk, ascending."""
    out = []
    for g, (lo, hi, n) in enumerate(traffic["lengths"]):
        if g == 0:
            out.append(np.rint(np.linspace(lo, hi, n)))
        else:
            out.append(lo + np.rint((hi - lo) * np.arange(1, n + 1) / n))
    return np.concatenate(out).astype(np.int64)


def make_pool(traffic: dict, config: dict, seed: int) -> list:
    """``pool_chunks`` chunks: per chunk a list of int32 id arrays in the
    order the seed drew."""
    lengths = chunk_lengths(traffic)
    pool = []
    for c in range(int(traffic["pool_chunks"])):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 300, c]))
        order = rng.permutation(len(lengths))
        pool.append([rng.integers(0, config["vocab_size"], int(n), dtype=np.int32)
                     for n in lengths[order]])
    return pool


def _as_frame(rows: list) -> object:
    from mmlspark_tpu.core.dataframe import DataFrame

    col = np.empty(len(rows), dtype=object)
    col[:] = rows
    return DataFrame.from_dict({"tokens": col})


def _bucket_of(traffic: dict, n: int) -> int:
    return min(length for length, _rows in traffic["buckets"] if length >= n)


class _Spanned:
    """The stage, with a host span around each ``transform`` call."""

    def __init__(self, stage: object, ctx: object):
        self._stage, self._ctx = stage, ctx

    def transform(self, df: object) -> object:
        with self._ctx.span("transform"):
            return self._stage.transform(df)


def setup(ctx: object) -> dict:
    try:
        from mmlspark_tpu.models import causal_lm
    except ImportError as e:
        # a checkout from before the stage existed cannot run the configuration
        sys.stderr.write(f"chipbench: this checkout's mmlspark_tpu has no language-model "
                         f"scorer ({e}): the cell cannot run here\n")
        sys.exit(2)

    traffic = ctx.traffic
    model = model_config(ctx.config)
    key = ctx.key()
    variables = program_variables(model, key, causal_lm.layer_kinds(model))
    scorer = causal_lm.CausalLMScorer(
        input_col="tokens", output_col="logprob", config=model, variables=variables,
        buckets=traffic["buckets"],
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
            by_bucket.setdefault(_bucket_of(traffic, len(row)), []).append(r)
        picks.append(sorted(int(r) for b in sorted(by_bucket) for r in pick_rng.choice(
            by_bucket[b], min(per_bucket, len(by_bucket[b])), replace=False)))
    # warm-up: one whole chunk through the stage itself; it holds rows of
    # every bucket, so every shape of the cell compiles (or loads) and runs
    with ctx.span("warmup"):
        scorer.transform(_as_frame(pool[0]))["logprob"]
    return {"scorer": scorer, "pool": pool, "picks": picks, "model": model, "key": key,
            "sample": []}


def window(ctx: object, state: dict, seconds: float) -> dict:
    from mmlspark_tpu.io.stream import StreamingDataFrame

    traffic = ctx.traffic
    pool, sample = state["pool"], state["sample"]
    order = ctx.rng(1).permutation(len(pool))
    seen: list = []
    done = {"rows": 0, "chunks": 0, "t_end": 0.0}
    chunk_s: list = []  # said with the window's work: a stalled chunk shows by itself
    t_start = time.perf_counter()

    def make_chunk(i: int) -> object:
        if time.perf_counter() - t_start >= seconds:
            return None
        with ctx.span("pool_pick"):
            k = int(order[i % len(order)])
            seen.append(k)
            return _as_frame(pool[k])

    def sink(out: object) -> None:
        with ctx.span("sink"):
            scored = out["logprob"]  # materialise the chunk's column
            k = seen[done["chunks"]]
            done["rows"] += len(scored)
            sample.append((k, [np.array(scored[r], np.float32) for r in state["picks"][k]]))
            done["chunks"] += 1
            now = time.perf_counter()
            chunk_s.append(now - (done["t_end"] or t_start))
            done["t_end"] = now

    stream = StreamingDataFrame.from_generator(make_chunk)
    total = stream.transform(_Spanned(state["scorer"], ctx)).foreach_chunk(sink)
    elapsed = done["t_end"] - t_start
    attempted = sum(len(pool[k]) for k in seen)
    lengths = chunk_lengths(traffic)
    held = collections.Counter(_bucket_of(traffic, n) for n in lengths)
    batches = sum(-(-held[length] // rows) for length, rows in traffic["buckets"])
    batch_tokens = max(length * rows for length, rows in traffic["buckets"])
    return {
        "metrics": {"rows_per_s": done["rows"] / elapsed},
        "attempted": attempted,
        "failed": attempted - int(total),
        "work": {"rows": done["rows"], "chunks": done["chunks"],
                 "batches": done["chunks"] * batches, "batch_tokens": batch_tokens,
                 "tokens_real": done["chunks"] * int(lengths.sum()),
                 "tokens_real_sq": done["chunks"] * int((lengths.astype(np.float64) ** 2).sum()),
                 "elapsed_s": elapsed, "chunk_s": chunk_s},
    }


def release(ctx: object, state: dict) -> None:
    """Free the program's device state; keep what the comparison reads."""
    state["check_rows"] = [[np.array(state["pool"][k][r]) for r in picks]
                           for k, picks in enumerate(state["picks"])]
    state["scorer"] = None  # and with it the program's copy of the weights
    state["pool"] = None


def compare(state: dict, traffic: dict, lower_dtype: object = None) -> dict:
    """|logprob - reference| over every real position of the sampled rows
    (``lower_dtype`` = the control in the program's place). The reference
    runs each distinct sampled row once, padded on the right to its
    bucket's length so that it compiles one program per bucket; a chunk
    that passed several times is compared each time."""
    chunks = sorted({k for k, _rows in state["sample"]})
    flat = [(k, j) for k in chunks for j in range(len(state["check_rows"][k]))]
    padded = []
    for k, j in flat:
        row = state["check_rows"][k][j]
        full = np.zeros(_bucket_of(traffic, len(row)), np.int32)
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
    """One reading, the program's or the control's, beside its limits: one
    comparison for both."""
    return [
        {"name": "logprob_rel_err_median", "value": r["p50"],
         "limit": LIMIT_REL_ERR_MEDIAN, "ok": r["p50"] <= LIMIT_REL_ERR_MEDIAN},
        {"name": "logprob_rel_err_p90", "value": r["p90"],
         "limit": LIMIT_REL_ERR_P90, "ok": r["p90"] <= LIMIT_REL_ERR_P90},
        {"name": "rows_compared", "value": r["rows"], "limit": 1, "ok": r["rows"] >= 1},
    ]


def control(ctx: object, state: dict) -> list:
    """The reference with every matmul input rounded to float8 (e4m3), the
    precision below the bfloat16 the configuration states, in the program's
    place on the same sample."""
    import jax.numpy as jnp

    return _readings(compare(state, ctx.traffic, lower_dtype=jnp.float8_e4m3fn))


def check(ctx: object, state: dict) -> list:
    if not state["sample"]:
        return [{"name": "rows_compared", "value": 0, "limit": 1, "ok": False}]
    return _readings(compare(state, ctx.traffic))
