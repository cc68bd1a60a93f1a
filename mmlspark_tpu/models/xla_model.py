"""XLAModel — batched model evaluation on TPU (the CNTKModel analogue).

The reference broadcasts a serialized CNTK graph to executors and feeds
minibatches through the native eval API per partition
(cntk/CNTKModel.scala:86-138,490-530). The TPU design:

- the "graph" is a jittable ``apply_fn(variables, x)``; XLA HLO is the
  compiled artifact (compile-once-per-shape replaces broadcast-once).
- weights are replicated onto the device mesh a single time per transform
  (the broadcast analogue, cntk/CNTKModel.scala:411-413).
- partitions are padded to a fixed batch size (FixedMiniBatchTransformer
  analogue — static shapes are load-bearing on TPU: any new shape is a new
  XLA compilation) and batch-sharded over the mesh ``data`` axis.
- multi-output graphs return name->array dicts; ``output_node`` selects one
  (the ARGUMENT_i/OUTPUT_i resolution analogue,
  com/microsoft/CNTK/SerializableFunction.scala:115-129). XLA dead-code
  eliminates the unused heads.
"""

from __future__ import annotations

import collections
import concurrent.futures as _futures
import threading
from typing import Any, Callable, Optional

import jax
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core.dataframe import DataFrame, Partition
from mmlspark_tpu.core.params import (
    ComplexParam,
    HasBatchSize,
    HasInputCol,
    HasOutputCol,
    Param,
)
from mmlspark_tpu.core.pipeline import Model
from mmlspark_tpu.parallel.mesh import get_mesh
from mmlspark_tpu.parallel.sharding import pad_batch, replicate, shard_batch

_M_CALLS = obs.counter(
    "mmlspark_xla_model_calls_total",
    "apply_batch calls by what their first dispatch found on the device: "
    "overlapped = a batch of an earlier call on the same model still in "
    "flight, cold = nothing",
    labels=("start",),
)


def _to_host(y: Any, landed: threading.Event) -> np.ndarray:
    """Fetch one batch's result; on the host (or failed), its slot of the
    window is free."""
    try:
        return np.asarray(y)
    finally:
        landed.set()


class _Turns:
    """A lock whose waiters are served in the order they arrived."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._next = 0      # the ticket the next arrival draws
        self._serving = 0   # the ticket whose holder dispatches now

    def acquire(self) -> None:
        with self._cond:
            ticket = self._next
            self._next += 1
            while self._serving != ticket:
                self._cond.wait()

    def release(self) -> None:
        with self._cond:
            self._serving += 1
            self._cond.notify_all()


class _Feed:
    """What one model's calls share of the way to the device. Calls take
    turns at dispatching, so a second caller's batches queue behind all of
    the first's and never between them; ``window`` holds, oldest first, one
    event for each batch dispatched and not yet known to be on the host
    (set when its fetch ends), and only the holder of the turn touches it;
    one fetcher thread hands results back in dispatch order, whichever call
    they belong to."""

    def __init__(self) -> None:
        self.turns = _Turns()
        self.window: collections.deque = collections.deque()
        self.fetcher = _futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="xla-model-fetch")
        self.lock = threading.Lock()  # the weights' one copy on the device


class XLAModel(Model, HasInputCol, HasOutputCol, HasBatchSize):
    apply_fn = ComplexParam(
        "jittable function (variables, batch) -> array | dict[name, array]"
    )
    variables = ComplexParam("model variables pytree (replicated to the mesh)")
    output_node = Param(
        "name of the output to keep when apply_fn returns a dict", type_=str
    )
    batch_size = Param(
        "fixed minibatch size; padded to a multiple of the mesh size",
        default=64,
        type_=int,
    )
    input_dtype = Param(
        "cast input batches to this dtype; None = keep the host dtype "
        "(e.g. ship uint8 pixels and cast on device: 4x less host->device "
        "traffic when the program starts with a cast anyway)",
        default="float32",
        type_=str,
    )

    def __init__(self, **kw: Any):
        super().__init__(**kw)
        self._jit_cache: dict = {}
        self._dev_vars: Any = None
        self._dev_vars_src: Any = None
        self._feed = _Feed()

    @classmethod
    def from_flax(
        cls,
        module: Any,
        variables: Any,
        output_node: Optional[str] = None,
        **kw: Any,
    ) -> "XLAModel":
        def apply_fn(vs: Any, x: Any) -> Any:
            return module.apply(vs, x, train=False)

        m = cls(**kw)
        m.set(apply_fn=apply_fn, variables=variables)
        if output_node is not None:
            m.set(output_node=output_node)
        return m

    # -- device-side plumbing ----------------------------------------------

    def _effective_batch(self, mesh: Any, batch_size: Optional[int] = None) -> int:
        bs = batch_size or self.get("batch_size")
        n_dev = mesh.devices.size
        return ((bs + n_dev - 1) // n_dev) * n_dev

    def _device_variables(self, mesh: Any) -> Any:
        vs = self.get_or_fail("variables")
        with self._feed.lock:
            if self._dev_vars is None or self._dev_vars_src is not vs:
                self._dev_vars = replicate(vs, mesh)
                self._dev_vars_src = vs
            return self._dev_vars

    def _compiled(self, shape: tuple, mesh: Any) -> Callable:
        key = (shape, id(mesh))
        fn = self._jit_cache.get(key)
        if fn is None:
            apply_fn = self.get_or_fail("apply_fn")
            node = self.get("output_node")

            def run(vs: Any, x: Any) -> Any:
                out = apply_fn(vs, x)
                if isinstance(out, dict):
                    if node is None:
                        raise ValueError(
                            f"apply_fn returned outputs {sorted(out)}; set output_node"
                        )
                    out = out[node]
                return out

            # two callers that miss at once keep one program
            fn = self._jit_cache.setdefault(key, self._program(run, node))
        return fn

    # how many minibatches of this model may be in flight on device at once,
    # whichever calls they belong to: JAX's async dispatch then overlaps host
    # staging of batch i+1..i+k with compute of batch i, while bounding live
    # HBM for inputs+outputs
    _MAX_IN_FLIGHT = 4

    def apply_batch(self, x: np.ndarray, batch_size: Optional[int] = None) -> np.ndarray:
        """Evaluate one host batch (used by transform and by serving).

        ``batch_size`` overrides the stage's for this call: a stage whose
        rows come in several shapes (length buckets of token ids: long rows
        in small batches, short rows in large ones) drives one call per
        shape, each a compiled program of its own (``_compiled`` keys on the
        batch's shape). Integer inputs pass as they are with
        ``input_dtype=None``; what a row carries beside its data (its
        length) rides as a trailing column.

        Double-buffered: the calling thread ONLY stages + dispatches (upload
        of batch k+1 streams while batch k computes), and result fetches run
        on the model's fetcher thread so a blocking device-to-host copy never
        serializes with the next dispatch (CNTKModel.scala:515-520 batches
        for the same keep-the-accelerator-busy reason). The in-flight
        window bounds live HBM and applies backpressure.

        Safe to call from several threads, and worth it: the turn at
        dispatching and the window are the model's (``_Feed``), not the
        call's. A call gives up its turn BEFORE it drains, so the next
        call's first batch is staged and queued while this call's last
        batches still compute, and the device's queue does not run empty
        between two calls (a stream's chunks, io/stream.py). Each call
        returns its own rows in its own order; a lone call finds the turn
        free and the window empty."""
        feed = self._feed
        # spans time the host in each call and add no synchronisation: when
        # the device started is the device trace's to say
        with obs.span("xla_model.apply_batch") as sp:
            with obs.span("xla_model.prepare"):
                mesh = get_mesh()
                vs = self._device_variables(mesh)
                bs = self._effective_batch(mesh, batch_size)
                dt = self.get("input_dtype")
                x = np.asarray(x, dtype=dt) if dt else np.asarray(x)
                padded, n = pad_batch(x, bs)
                shape = padded[:bs].shape
                program_new = (shape, id(mesh)) not in self._jit_cache
                fn = self._compiled(shape, mesh)
            sp.set_attr("rows", int(n))
            sp.set_attr("batches", padded.shape[0] // bs)
            if program_new:
                # this call builds the shape's program: the xla.trace,
                # xla.lower and xla.compile spans under its first dispatch
                sp.set_attr("program_new", True)
                sp.set_attr("shape", list(shape))
            mine: list = []
            with obs.span("xla_model.turn"):
                feed.turns.acquire()
            try:
                for i in range(0, padded.shape[0], bs):
                    batch = padded[i: i + bs]
                    with obs.span("xla_model.stage", attrs={"bytes": batch.nbytes}):
                        chunk = shard_batch(batch, mesh)
                    with obs.span("xla_model.dispatch"):
                        if not mine:
                            # fetches finish in dispatch order: what is left
                            # after the finished ones is still on the device
                            while feed.window and feed.window[0].is_set():
                                feed.window.popleft()
                            overlapped = bool(feed.window)
                            sp.set_attr("overlapped", overlapped)
                            _M_CALLS.labels(start="overlapped" if overlapped else "cold").inc()
                        y = fn(vs, chunk)  # async dispatch, no host sync
                        landed = threading.Event()
                        mine.append(feed.fetcher.submit(_to_host, y, landed))
                        feed.window.append(landed)
                        # the device keeps a batch for as long as it needs it;
                        # a call that kept its last one by name through its
                        # drain would hold a fifth while the next call stages
                        del chunk, y
                    if len(feed.window) >= self._MAX_IN_FLIGHT:
                        # the oldest may be an earlier call's: waited for
                        # here, collected (and its failure raised) there
                        with obs.span("xla_model.backpressure"):
                            feed.window.popleft().wait()
            finally:
                feed.turns.release()
            with obs.span("xla_model.drain"):
                outs = [f.result() for f in mine]
            with obs.span("xla_model.concat"):
                return np.concatenate(outs, axis=0)[:n]

    # -- stage interface ----------------------------------------------------

    def transform(self, df: DataFrame) -> DataFrame:
        ic = self.get_or_fail("input_col")
        oc = self.get_or_fail("output_col")

        def fn(p: Partition) -> Partition:
            q = dict(p)
            x = p[ic]
            if x.dtype == object:  # ragged rows: stack (must be uniform shape)
                x = np.stack(list(x))
            q[oc] = self.apply_batch(x)
            return q

        # partitions run sequentially: there is one device mesh; within a
        # call overlap comes from async dispatch inside JAX, and between
        # calls from apply_batch's turns, for a caller that has two to make
        return df.map_partitions(fn, parallel=False)

    # (name, data) of a program the package can name — the function apply_fn
    # is and the plain data its closure reads: its executables live in the
    # program store (core/compile_cache.py). A user's apply_fn has none and
    # stays on jit
    program_identity: Optional[tuple] = None

    def _program(self, run: Callable, node: Optional[str]) -> Callable:
        from mmlspark_tpu.core.compile_cache import stored_jit

        if self.program_identity is None:
            return jax.jit(run)
        name, data = self.program_identity
        return stored_jit(run, name=name, data=(data, node))
