"""Fuser: merge runs of adjacent fusable stages into single XLA programs.

Stage-by-stage execution of a fitted pipeline dispatches one jitted
program per stage per partition, materializing every intermediate column
on the host between stages. The fuser instead traces the stage kernels of
a maximal run of adjacent fusable stages into ONE ``jax.jit`` program:
intermediates stay on device, dispatch overhead is paid once, and XLA
sees the whole segment.

Two load-bearing design points:

- **Exactness.** The compiled pipeline's contract is element-wise
  equality with staged execution. Cross-stage XLA fusion can legally
  change the lowering of an op (e.g. fuse a featurization chain into a
  dot's operand and pick a different accumulation strategy — observed on
  CPU: ~1 ulp logit drift). In ``exact`` mode (the default) the fuser
  therefore pins stage boundaries with ``jax.lax.optimization_barrier``
  around every kernel's inputs: each stage's ops lower exactly as they
  would standalone, while the segment still runs as one program (single
  dispatch, device-resident intermediates). ``exact=False`` drops the
  barriers and lets XLA fuse across stages freely — faster, but only
  allclose-level equal.
- **Bounded compile cache.** Batches are padded to power-of-two buckets
  (the ``_bucket`` idiom from ``serving/query.py``) capped at
  ``max_bucket``, so a segment compiles at most ``log2(max_bucket)+1``
  programs per distinct feature shape no matter what partition sizes
  arrive. Row-wise kernels make pad-and-slice sound.

A segment that cannot run a given DataFrame (an object-dtype input, a
kernel guard refusal) falls back to staged execution for that call —
recorded in ``mmlspark_compiler_fallback_total{reason=...}`` — so
compiled pipelines never fail where the staged pipeline would not.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.compiler.partitioner import ShardingPlan, plan_sharding
from mmlspark_tpu.core.dataframe import DataFrame, Partition
from mmlspark_tpu.serving.query import _bucket

_M_COMPILE = obs.histogram(
    "mmlspark_compiler_compile_seconds",
    "Wall time of a fused segment's first call per bucket (trace+compile)",
    labels=("segment",),
    buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0),
)
_M_BUCKET_COMPILES = obs.counter(
    "mmlspark_compiler_bucket_compiles_total",
    "Fused-program compilations (one per new bucket/shape per segment)",
    labels=("segment",),
)
_M_SEG_LATENCY = obs.histogram(
    "mmlspark_compiler_segment_latency_seconds",
    "Per-call latency of compiled-pipeline segments",
    labels=("segment",),
)
_M_FALLBACK = obs.counter(
    "mmlspark_compiler_fallback_total",
    "Fused segments that fell back to staged execution",
    labels=("reason",),
)

class Segment:
    """Base: one schedulable unit of a compiled pipeline."""

    name: str = "segment"
    nodes: list = []

    @property
    def stage_names(self) -> list:
        return [n.name for n in self.nodes]

    @property
    def reads(self) -> tuple:
        out: list = []
        produced: set = set()
        for n in self.nodes:
            out.extend(c for c in n.reads if c not in produced)
            produced.update(n.writes)
        return tuple(dict.fromkeys(out))

    @property
    def writes(self) -> tuple:
        out: list = []
        for n in self.nodes:
            out.extend(n.writes)
        return tuple(dict.fromkeys(out))

    def apply(self, df: DataFrame) -> DataFrame:
        raise NotImplementedError


class HostSegment(Segment):
    """A single host-bound (or opaque) stage, executed via its own
    ``transform`` — per-stage fallback is the *plan* for these, not an
    error path."""

    def __init__(self, node: Any, name: str):
        self.nodes = [node]
        self.name = name
        self.opaque = node.opaque

    def apply(self, df: DataFrame) -> DataFrame:
        t0 = time.perf_counter()
        out = self.nodes[0].stage.transform(df)
        m = _M_SEG_LATENCY.labels(segment=self.name)
        if m._on:
            m.observe(time.perf_counter() - t0)
        return out


class FusedSegment(Segment):
    """A maximal run of adjacent fusable stages compiled as one program."""

    def __init__(
        self,
        nodes: list,
        name: str,
        exact: bool = True,
        max_bucket: int = 1024,
        mesh: Any = None,
        partition_mode: str = "auto",
    ):
        self.nodes = nodes
        self.name = name
        self.exact = exact
        self.max_bucket = max(1, int(max_bucket))
        self.mesh = mesh
        self.partition_mode = partition_mode
        self.kernels = [n.kernel for n in nodes]
        # a cross-row kernel would see padded lanes in its reductions, so
        # pad-and-slice bucketing is only sound when every kernel is row-wise;
        # otherwise the segment compiles per exact batch shape instead
        self.row_wise = all(k.row_wise for k in self.kernels)
        self._jit_cache: dict = {}
        self._sharding: Optional[ShardingPlan] = None
        self.last_fallback_error: Optional[str] = None

    # -- planning ------------------------------------------------------------

    @property
    def sharding(self) -> ShardingPlan:
        if self._sharding is None:
            self._sharding = plan_sharding(
                self.kernels,
                mesh=self.mesh,
                bucket=self.max_bucket,
                mode=self.partition_mode,
            )
        return self._sharding

    # -- program construction ------------------------------------------------

    @property
    def device_outputs(self) -> tuple:
        """Columns the fused program returns: plain kernels' writes plus
        finalize kernels' raw device outputs (their final writes are
        produced on host by the epilogue)."""
        out: list = []
        for k in self.kernels:
            out.extend(k.fn_outputs)
        return tuple(dict.fromkeys(out))

    def _traced_fn(self):
        kernels = list(self.kernels)
        outputs = list(self.device_outputs)
        exact = self.exact

        def fn(cols: dict) -> dict:
            import jax

            env = dict(cols)
            for k in kernels:
                ins = {c: env[c] for c in k.reads}
                if exact:
                    # pin the stage boundary: the kernel's ops see opaque
                    # operands, exactly like the staged jit saw host arrays,
                    # so XLA cannot re-lower them via cross-stage fusion
                    ins = jax.lax.optimization_barrier(ins)
                env.update(k.fn(ins))
            return {c: env[c] for c in outputs}

        return fn

    def _compiled(self, key: tuple, cols: dict, bucket: int):
        entry = self._jit_cache.get(key)
        if entry is None:
            import jax

            in_sh = self.sharding.in_shardings(cols)
            if in_sh is not None:
                fn = jax.jit(self._traced_fn(), in_shardings=(in_sh,))
            else:
                fn = jax.jit(self._traced_fn())
            self._jit_cache[key] = entry = {"fn": fn, "compiled": False}
        return entry

    # -- execution -----------------------------------------------------------

    def _guard(self, part: Partition) -> Optional[str]:
        for k in self.kernels:
            if k.guard is None:
                continue
            ins = {c: part[c] for c in k.reads if c in part}
            reason = k.guard(ins)
            if reason:
                return reason
        for c in self.reads:
            arr = part.get(c)
            if arr is None:
                return f"missing column {c!r}"
            if np.asarray(arr).dtype == object:
                return f"object column {c!r}"
        return None

    def _staged(self, df: DataFrame, reason: str) -> DataFrame:
        m = _M_FALLBACK.labels(reason=reason[:60])
        if m._on:
            m.inc()
        for n in self.nodes:
            df = n.stage.transform(df)
        return df

    def apply(self, df: DataFrame) -> DataFrame:
        # guard on the first non-empty partition; the whole call either
        # runs fused or falls back (partitions must agree on dtypes)
        probe = next((p for p in df.partitions if p), None)
        if probe is not None:
            reason = self._guard(probe)
            if reason is not None:
                return self._staged(df, reason)
        t0 = time.perf_counter()
        with obs.span(f"compiler.segment.{self.name}"):
            try:
                out = df.map_partitions(self._apply_partition, parallel=False)
            except Exception as e:  # noqa: BLE001 — never fail where staged wouldn't
                # label stays bounded (exception class); the free-form
                # message would mint a metric series per distinct shape/
                # value it quotes — detail goes to explain()/introspection
                self.last_fallback_error = f"{type(e).__name__}: {e}"
                return self._staged(df, f"error:{type(e).__name__}")
        m = _M_SEG_LATENCY.labels(segment=self.name)
        if m._on:
            m.observe(time.perf_counter() - t0)
        return out

    def _apply_partition(self, part: Partition) -> Partition:
        reads = self.reads
        cols: dict = {}
        n = 0
        for c in reads:
            arr = np.asarray(part[c])
            n = max(n, arr.shape[0] if arr.ndim else 0)
            cols[c] = arr
        b = _bucket(max(n, 1), cap=self.max_bucket) if self.row_wise else max(n, 1)
        padded: dict = {}
        for c, arr in cols.items():
            padded[c] = _pad_rows(arr, b)
        key = (b,) + tuple(
            (c, padded[c].shape[1:], str(padded[c].dtype)) for c in reads
        )
        entry = self._compiled(key, padded, b)
        t0 = time.perf_counter()
        chunks = [padded]
        if n > b:  # oversized partition: run in bucket-size chunks
            chunks = []
            for start in range(0, n, b):
                chunk = {c: _pad_rows(arr[start:start + b], b) for c, arr in cols.items()}
                chunks.append(chunk)
        outs: list = []
        rest = chunks
        if not entry["compiled"]:
            # first call on this bucket pays trace+compile: block it to
            # completion so the compile-seconds histogram below holds the
            # whole first call (dispatching an already-compiled fn never
            # blocks here)
            out0 = entry["fn"](chunks[0])
            for v in out0.values():
                getattr(v, "block_until_ready", lambda: None)()
            outs.append(out0)
            rest = chunks[1:]
            dt = time.perf_counter() - t0
            entry["compiled"] = True
            mc = _M_COMPILE.labels(segment=self.name)
            if mc._on:
                mc.observe(dt)
            mb = _M_BUCKET_COMPILES.labels(segment=self.name)
            if mb._on:
                mb.inc()
        for chunk in rest:
            outs.append(entry["fn"](chunk))
        q = dict(part)
        merged: dict = {}
        for c in self.device_outputs:
            vals = [np.asarray(o[c]) for o in outs]
            merged[c] = np.concatenate(vals, axis=0)[:n] if len(vals) > 1 else vals[0][:n]
        for k in self.kernels:
            if k.finalize is not None:
                # host epilogue: replay the staged path's numpy tail on the
                # fetched device outputs (sliced to true rows already)
                host_cols = {c: merged[c] for c in k.fn_outputs}
                q.update(k.finalize(host_cols))
                continue
            for c in k.writes:
                v = merged[c]
                dt_ = k.out_dtypes.get(c)
                q[c] = v.astype(dt_) if dt_ is not None and v.dtype != dt_ else v
        return q


def _pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Pad axis 0 up to ``bucket`` rows (repeat row 0 — a real row keeps
    padded lanes NaN/inf-free); zero-rows when the array is empty."""
    n = arr.shape[0]
    if n == bucket:
        return arr
    if n == 0:
        return np.zeros((bucket,) + arr.shape[1:], arr.dtype)
    if n > bucket:
        return arr[:bucket]
    reps = np.repeat(arr[:1], bucket - n, axis=0)
    return np.concatenate([arr, reps], axis=0)


def build_segments(
    plan: Any,
    exact: bool = True,
    max_bucket: int = 1024,
    mesh: Any = None,
    partition_mode: str = "auto",
) -> list:
    """Partition the plan's nodes into segments: maximal runs of adjacent
    fusable stages become one :class:`FusedSegment`; everything else is a
    :class:`HostSegment` of its own."""
    segments: list = []
    run: list = []

    def flush() -> None:
        if not run:
            return
        idx = len(segments)
        name = f"s{idx}:" + "+".join(n.name for n in run)
        segments.append(FusedSegment(
            list(run), name, exact=exact, max_bucket=max_bucket,
            mesh=mesh, partition_mode=partition_mode,
        ))
        run.clear()

    for n in plan.nodes:
        if n.kind == "fused" and exact and not n.kernel.exact_capable:
            # the kernel cannot promise bit-equality (conv lowerings vary
            # with batch shape): exact mode runs the stage host-bound
            flush()
            segments.append(HostSegment(n, f"s{len(segments)}:{n.name}"))
        elif n.kind == "fused":
            run.append(n)
            if n.kernel.finalize is not None:
                # a finalize kernel's outputs live on host after its
                # epilogue — nothing later can read them on device, so it
                # always ends its fusion run
                flush()
        else:
            flush()
            segments.append(HostSegment(n, f"s{len(segments)}:{n.name}"))
    flush()
    return segments
