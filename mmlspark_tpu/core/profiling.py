"""Profiling/tracing (SURVEY.md §5.1: the reference has StopWatch timers +
the Timer pipeline stage; the TPU equivalent adds device-level tracing).

- :func:`trace` captures the XLA/TPU device timeline (TensorBoard/
  Perfetto) with the profiler's host tracers off, and writes the ``obs``
  spans of the capture beside it on the same (epoch) clock.
- :func:`annotate` marks a host span in the profiler's own host trace,
  for callers who run their own capture with the host tracer on (the
  log-per-stage analogue of stages/Timer.scala:57-92).
- :class:`ProfiledRun` collects per-stage wall times for a pipeline the
  way VW's TrainingStats DataFrame reports per-partition timings. Stage
  timings ride the obs span API (``mmlspark_tpu.obs``), so each stage
  lands in the process metrics registry as
  ``mmlspark_trace_span_seconds{span="pipeline.<Stage>"}`` AND lands in
  ``obs_spans.json`` beside a :func:`trace` capture — the same numbers show
  up on ``/metrics`` and next to the device timeline.
"""

from __future__ import annotations

import contextlib
import json
import os
import time as _time
from typing import Any, Iterator, Optional

import jax

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame


SPANS_FILE = "obs_spans.json"


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a device profiler trace into ``log_dir`` and write the
    ``obs`` spans of the capture beside it (``obs_spans.json``).

    The profiler's own host and Python tracers stay OFF: with the host
    tracer on, the TPU runtime logs one event per transfer chunk — a 16x
    slower feed and tens of GB of host memory for one chunk of images
    (PERF.md section 6, PR 24). What the host was doing comes from the
    program's spans instead: ``Span.to_dict()`` rows whose ``wall_ns`` is
    epoch nanoseconds, the clock of the trace's ``profile_start_time``, so
    they lay on the device timeline as they are."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    t0 = _time.time_ns()
    jax.profiler.start_trace(
        log_dir, create_perfetto_link=create_perfetto_link,
        profiler_options=options,
    )
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        spans = [s.to_dict() for s in obs.recent_spans() if s.wall_ns >= t0]
        with open(os.path.join(log_dir, SPANS_FILE), "w") as f:
            json.dump({"capture_start_ns": t0, "spans": spans}, f)


def annotate(name: str) -> Any:
    """Named host span in the profiler's host trace: shows only in a capture
    whose host tracer is on (not :func:`trace`'s, see there)."""
    return jax.profiler.TraceAnnotation(name)


def _pipeline_stages(pipeline_model: Any) -> list:
    """The stage list of a PipelineModel, or [model] for a single
    transformer. Must not raise on plain transformers: anything without a
    ``params()`` classmethod / ``get`` accessor (a bare function wrapper,
    a duck-typed stage) profiles as one stage."""
    try:
        params = type(pipeline_model).params()
    except Exception:  # noqa: BLE001 — params() is a Params-API contract
        return [pipeline_model]
    if "stages" not in params:
        return [pipeline_model]
    try:
        return list(pipeline_model.get("stages"))
    except Exception:  # noqa: BLE001 — declared but unreadable
        return [pipeline_model]


class ProfiledRun:
    """Time each stage of a pipeline transform; emit a stats DataFrame.

    >>> prof = ProfiledRun()
    >>> out = prof.transform(pipeline_model, df)
    >>> prof.stats().head()   # stage, seconds
    """

    def __init__(self) -> None:
        self.records: list = []

    def transform(
        self, pipeline_model: Any, df: DataFrame,
        trace_id: Optional[str] = None,
    ) -> DataFrame:
        cur = df
        with obs.span("pipeline.transform", trace_id=trace_id):
            for stage in _pipeline_stages(pipeline_model):
                name = type(stage).__name__
                with obs.span(f"pipeline.{name}") as sp:
                    cur = stage.transform(cur)
                self.records.append((name, sp.duration_ns))
        return cur

    def stats(self) -> DataFrame:
        import numpy as np

        return DataFrame.from_dict(
            {
                "stage": np.array([r[0] for r in self.records], dtype=object),
                "seconds": np.array([r[1] / 1e9 for r in self.records]),
            }
        )
