"""Driver: a streamed DataFrame of image chunks through ``ImageFeaturizer``.

The window drives what a user calls:
``StreamingDataFrame.transform(ImageFeaturizer).foreach_chunk(sink)`` — the
feed, ``XLAModel.apply_batch`` and the ResNet program at the configuration's
batch. From the program it takes the featurizer stage, the stream and the
DataFrame; weights, pixels, the sample and the comparison are the
benchmark's own (``chipbench/traffic_gen.py``, ``chipbench/reference/``).
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import traffic_gen
from chipbench.reference import resnet as ref

# the widest relative L2 gap of a sampled row's features from the reference;
# PERF.md section 2 gives the readings the limit was set from
LIMIT_FEATURE_REL_ERR = 0.012


def program_variables(weights: dict, config: dict, key: object) -> dict:
    """The seeded weights in the tree the program's flax ResNet reads."""
    import jax

    params: dict = {}
    stats: dict = {}

    def put(conv: str, bn: str, name: str, into_p: dict, into_s: dict) -> None:
        into_p[conv] = {"kernel": weights[name + ".w"]}
        into_p[bn] = {"scale": weights[name + ".scale"], "bias": weights[name + ".bias"]}
        into_s[bn] = {"mean": weights[name + ".mean"], "var": weights[name + ".var"]}

    put("conv_init", "bn_init", "stem", params, stats)
    names = {row[0] for row in ref.conv_table(config)}
    n = 0
    for i, blocks in enumerate(config["stage_sizes"]):
        for j in range(blocks):
            block = f"BottleneckBlock_{n}"
            p, s = params.setdefault(block, {}), stats.setdefault(block, {})
            for c in range(3):
                put(f"Conv_{c}", f"BatchNorm_{c}", f"s{i}b{j}.c{c + 1}", p, s)
            if f"s{i}b{j}.proj" in names:
                put("proj", "proj_bn", f"s{i}b{j}.proj", p, s)
            n += 1
    width = config["num_filters"] * 2 ** (len(config["stage_sizes"]) - 1) \
        * config["bottleneck_expansion"]
    params["head"] = {
        "kernel": 0.01 * jax.random.normal(key, (width, config["num_classes"])),
        "bias": jax.numpy.zeros((config["num_classes"],)),
    }
    return {"params": params, "batch_stats": stats}


class _Spanned:
    """The stage, with a host span around each ``transform`` call."""

    def __init__(self, stage: object, ctx: object):
        self._stage, self._ctx = stage, ctx

    def transform(self, df: object) -> object:
        with self._ctx.span("transform"):
            return self._stage.transform(df)


def setup(ctx: object) -> dict:
    import jax

    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models import ImageFeaturizer
    from mmlspark_tpu.models.resnet import RESNETS

    cfg, traffic = ctx.config, ctx.traffic
    key = ctx.key()
    weights = ref.make_weights(cfg, key)
    variables = program_variables(weights, cfg, jax.random.fold_in(key, 10_000))
    module = RESNETS[cfg["model"]](
        num_classes=cfg["num_classes"], num_filters=cfg["num_filters"])
    featurizer = ImageFeaturizer(
        input_col="image", output_col="features",
        batch_size=cfg["batch_size"], image_size=cfg["image_size"],
        apply_fn=lambda vs, x: module.apply(vs, x, train=False),
        variables=variables,
    )
    pool = traffic_gen.generate(traffic, cfg, ctx.seed)["chunks"]
    # warm-up: one whole chunk through the stage itself. One batch would
    # compile the cell's one program; the whole chunk also puts the host
    # buffers of a chunk (staging, fetches, the concatenated features) on
    # memory the machine has already backed, so that the first run on a
    # fresh machine reads like the next
    warm = DataFrame.from_dict({"image": pool[0]})
    with ctx.span("warmup"):
        np.asarray(featurizer.transform(warm)["features"])
    return {"featurizer": featurizer, "pool": pool, "weights": weights, "sample": []}


def window(ctx: object, state: dict, seconds: float) -> dict:
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.io.stream import StreamingDataFrame

    cfg, traffic = ctx.config, ctx.traffic
    pool, sample = state["pool"], state["sample"]
    batch = cfg["batch_size"]
    per_batch = int(traffic["check_rows_per_batch"])
    order = ctx.rng(1).permutation(len(pool))
    pick_rng = ctx.rng(2)
    picks: list = []
    done = {"rows": 0, "chunks": 0, "t_end": 0.0}
    t_start = time.perf_counter()

    def make_chunk(i: int) -> "DataFrame | None":
        if time.perf_counter() - t_start >= seconds:
            return None
        with ctx.span("pool_pick"):
            k = int(order[i % len(order)])
            picks.append(k)
            return DataFrame.from_dict({"image": pool[k]})

    def sink(out: "DataFrame") -> None:
        with ctx.span("sink"):
            feats = out["features"]  # materialise the chunk's features
            k = picks[done["chunks"]]
            n = len(feats)
            done["rows"] += n
            # the sample for the comparison: rows of every batch of the chunk
            rows = np.concatenate([
                lo + pick_rng.choice(min(batch, n - lo), min(per_batch, n - lo), replace=False)
                for lo in range(0, n, batch)])
            sample.append((k, rows, np.array(feats[rows], np.float32)))
            done["chunks"] += 1
            done["t_end"] = time.perf_counter()

    stream = StreamingDataFrame.from_generator(make_chunk)
    total = stream.transform(_Spanned(state["featurizer"], ctx)).foreach_chunk(sink)
    elapsed = done["t_end"] - t_start
    attempted = sum(len(pool[k]) for k in picks)
    return {
        "metrics": {"rows_per_s": done["rows"] / elapsed},
        "attempted": attempted,
        "failed": attempted - int(total),
        "work": {"rows": done["rows"], "chunks": done["chunks"],
                 "batches": done["chunks"] * -(-len(pool[0]) // batch),
                 "batch_rows": batch, "elapsed_s": elapsed},
    }


def release(ctx: object, state: dict) -> None:
    """Free the program's device state; keep what the comparison reads."""
    state["check_pixels"] = [np.array(state["pool"][k][rows]) for k, rows, _f in state["sample"]]
    state["featurizer"] = None  # and with it the program's copy of the weights
    state["pool"] = None


def compare(state: dict, config: dict, lower_dtype: object = None,
            block: int = 64) -> dict:
    """Worst relative L2 gap of the sampled rows' features from the
    reference (``lower_dtype`` = the control in the program's place)."""
    pixels = np.concatenate(state["check_pixels"])
    got = np.concatenate([f for _k, _r, f in state["sample"]])
    want = ref.features_in_blocks(state["weights"], pixels, config, block=block)
    if lower_dtype is not None:
        got = ref.features_in_blocks(state["weights"], pixels, config, block=block,
                                     lower_dtype=lower_dtype)
    gap = np.linalg.norm(got - want, axis=1) / np.maximum(np.linalg.norm(want, axis=1), 1e-30)
    finite = bool(np.isfinite(got).all())
    return {"rows": len(got), "rel_err_max": float(gap.max()) if finite else float("inf")}


def _readings(r: dict) -> list:
    """One reading, the program's or the control's, beside its limits: one
    comparison for both."""
    return [
        {"name": "feature_rel_err_max", "value": r["rel_err_max"],
         "limit": LIMIT_FEATURE_REL_ERR, "ok": r["rel_err_max"] <= LIMIT_FEATURE_REL_ERR},
        {"name": "rows_compared", "value": r["rows"], "limit": 1, "ok": r["rows"] >= 1},
    ]


def control(ctx: object, state: dict) -> list:
    """The reference in float8 (e4m3), the precision below the bfloat16 the
    configuration states, in the program's place on the same sample."""
    import jax.numpy as jnp

    return _readings(compare(state, ctx.config, lower_dtype=jnp.float8_e4m3fn,
                             block=8 if ctx.rehearse else 64))


def check(ctx: object, state: dict) -> list:
    if not state["sample"]:
        return [{"name": "rows_compared", "value": 0, "limit": 1, "ok": False}]
    return _readings(compare(state, ctx.config, block=8 if ctx.rehearse else 64))
