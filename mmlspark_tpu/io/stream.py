"""Out-of-core streaming DataFrame source.

The eager ``core.dataframe.DataFrame`` materializes every column in
memory; the reference instead streams partitions from disk through its
custom file formats (io/binary/BinaryFileFormat.scala:112-149 reads
portioned binary records on demand). ``StreamingDataFrame`` is that
capability here: a re-iterable source of bounded eager CHUNKS (each a
normal DataFrame), so a fitted pipeline can score datasets far larger than
host memory — the benchmark's ResNet-50 featurization stream runs through
it (chipbench/drivers/featurize_stream.py).

Semantics:
- A chunk is a plain eager DataFrame; every existing Transformer works on
  it unchanged (``transform`` maps the stage lazily over chunks — Spark's
  microbatch model).
- The source factory is re-invocable: each traversal re-opens the
  underlying file/generator, so a StreamingDataFrame can be consumed more
  than once (like a Spark source, unlike a Python generator).
- ``fit`` on unbounded data is out of scope, as in SparkML: estimators
  need a bounded DataFrame (``materialize`` a sample for that).

The look-ahead of a transformed stream. Iterating ``transform(stage)`` —
and only that: a bare source and ``map_chunks`` stay one chunk at a time —
keeps ``_CHUNKS_IN_FLIGHT`` chunks between the source and the consumer: a
feeder thread pulls chunk k+1 and hands ``stage.transform`` of it to a
worker while chunk k's transform still runs, and the consumer gets the
results strictly in the source's order. A stage that feeds a device
(``XLAModel.apply_batch``: its calls take turns at dispatching and give the
turn up before they drain) then has chunk k+1's first batch on its way
while chunk k's last batches compute; without the look-ahead the device
stands idle at every chunk's border for as long as that transfer takes.
What follows from it:

- Nothing is pulled or transformed before iteration starts. The source is
  pulled by the feeder alone, in order, and never more than
  ``_CHUNKS_IN_FLIGHT`` chunks beyond what the consumer has been handed.
  Chunk k is handed over as soon as its transform is done, also while the
  source blocks on a later chunk.
- A stage's ``transform`` may run on two threads at once, on different
  chunks — the contract ``DataFrame.map_partitions(parallel=True)`` already
  holds a stage's partition function to.
- A sink that feeds its own source (a feedback loop) sees the source read
  up to ``_CHUNKS_IN_FLIGHT`` chunks ahead of what it has been handed; so
  does ``first()`` or a ``materialize(max_rows=)`` that stops early: the
  chunks in flight are transformed and dropped.
- A failure in chunk k's transform (or in the source's pull of it) is
  raised where chunk k would have been handed over. Closing the iterator
  early stops the feeder, waits for the transforms in flight and closes
  the upstream generator on the feeder's thread; only a feeder that is
  inside a pull at that moment is left to finish it (it cannot be
  interrupted there) and closes the generator when the pull returns.
"""

from __future__ import annotations

import concurrent.futures as _futures
import os
import queue
import threading
from typing import Any, Callable, Iterator, Optional, Sequence

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame

# chunks of a transformed stream between the source and the consumer. Two:
# one whose transform holds the device and one whose first batch is already
# staged behind it, which is all a chunk's border needs; a third would hold
# another chunk in host memory for nothing the chip runs show (PERF.md,
# Findings, PR 30)
_CHUNKS_IN_FLIGHT = 2
_END = object()


def _in_flight(src: Callable[[], Iterator[DataFrame]],
               fn: Callable[[DataFrame], DataFrame]) -> Iterator[DataFrame]:
    """``fn`` of every chunk of ``src()``, in order, ``_CHUNKS_IN_FLIGHT``
    of them pulled and being transformed at a time (the module's docstring
    has the contract)."""
    slots = threading.Semaphore(_CHUNKS_IN_FLIGHT)  # freed as the consumer is handed a chunk
    results: queue.SimpleQueue = queue.SimpleQueue()  # futures in source order, then None
    lock = threading.Lock()
    state = {"stop": False, "pulling": False}
    workers = _futures.ThreadPoolExecutor(
        max_workers=_CHUNKS_IN_FLIGHT, thread_name_prefix="stream-transform")

    def begin_pull() -> bool:
        with lock:
            state["pulling"] = not state["stop"]
            return state["pulling"]

    def end_pull() -> bool:
        with lock:
            state["pulling"] = False
            return not state["stop"]

    def feed() -> None:
        it = None
        try:
            it = src()
            while True:
                slots.acquire()
                if not begin_pull():
                    return
                try:
                    chunk = next(it, _END)
                finally:
                    wanted = end_pull()
                if chunk is _END or not wanted:
                    return
                results.put(workers.submit(fn, chunk))
        except BaseException as e:  # noqa: BLE001 - the source's failure, raised in its place
            failed: _futures.Future = _futures.Future()
            failed.set_exception(e)
            results.put(failed)
        finally:
            if hasattr(it, "close"):
                it.close()
            results.put(None)

    feeder = threading.Thread(target=feed, name="stream-feed", daemon=True)
    feeder.start()
    try:
        while True:
            result = results.get()
            if result is None:
                return
            chunk = result.result()
            slots.release()
            yield chunk
    finally:
        with lock:
            state["stop"] = True
            in_pull = state["pulling"]
        slots.release()
        if not in_pull:
            feeder.join()
        workers.shutdown(wait=True)


class StreamingDataFrame:
    def __init__(self, source: Callable[[], Iterator[DataFrame]]):
        self._source = source

    # -- construction --------------------------------------------------------

    @staticmethod
    def from_generator(
        make_chunk: Callable[[int], Optional[DataFrame]], num_chunks: Optional[int] = None
    ) -> "StreamingDataFrame":
        """``make_chunk(i)`` -> DataFrame or None (None = end of stream)."""

        def source() -> Iterator[DataFrame]:
            i = 0
            while num_chunks is None or i < num_chunks:
                chunk = make_chunk(i)
                if chunk is None:
                    return
                yield chunk
                i += 1

        return StreamingDataFrame(source)

    @staticmethod
    def from_csv(
        path: str,
        chunk_rows: int = 65536,
        header: bool = True,
        columns: Optional[Sequence[str]] = None,
        numeric_only: Optional[bool] = None,
    ) -> "StreamingDataFrame":
        """Chunked CSV: reads ~chunk_rows lines at a time, never the whole
        file. Column dtypes are inferred per chunk; pass ``numeric_only``
        explicitly for dtype stability across chunks whose string values
        appear late."""
        from mmlspark_tpu.io.csv import parse_csv_bytes, split_csv_header

        def source() -> Iterator[DataFrame]:
            with open(path, "rb") as f:
                head = b""
                if header or columns is None:
                    # header line (or first line for width discovery)
                    head = f.readline()
                _, names = split_csv_header(
                    head + b"\n" if head and not head.endswith(b"\n") else head,
                    header,
                    columns,
                )
                if not header:
                    # first line was data: hand it to the first chunk
                    carry = head
                else:
                    carry = b""
                while True:
                    lines = f.readlines(chunk_rows * 64)  # hint: avg 64 B/line
                    if not lines and not carry:
                        return
                    body = carry + b"".join(lines)
                    carry = b""
                    # a quoted field may contain newlines (write_csv emits
                    # them): an odd quote count means the chunk boundary cut
                    # a record — extend until the record closes
                    while lines and body.count(b'"') % 2 == 1:
                        more = f.readline()
                        if not more:
                            break
                        body += more
                    if not body.strip():
                        continue  # a run of blank lines is not end-of-file
                    yield parse_csv_bytes(body, names, numeric_only)

        return StreamingDataFrame(source)

    @staticmethod
    def from_binary_files(
        path: str,
        files_per_chunk: int = 256,
        recursive: bool = True,
        pattern: Optional[str] = None,
    ) -> "StreamingDataFrame":
        """Directory -> chunks of DataFrame[path, bytes]; file contents are
        read only when their chunk is consumed (BinaryFileFormat.scala's
        portioned reads)."""
        from mmlspark_tpu.io.binary import _iter_files
        import fnmatch

        def source() -> Iterator[DataFrame]:
            batch_paths: list = []
            for fp in _iter_files(path, recursive):
                if pattern and not fnmatch.fnmatch(os.path.basename(fp), pattern):
                    continue
                batch_paths.append(fp)
                if len(batch_paths) >= files_per_chunk:
                    yield _load_files(batch_paths)
                    batch_paths = []
            if batch_paths:
                yield _load_files(batch_paths)

        return StreamingDataFrame(source)

    # -- lazy transforms -----------------------------------------------------

    def map_chunks(self, fn: Callable[[DataFrame], DataFrame]) -> "StreamingDataFrame":
        src = self._source

        def source() -> Iterator[DataFrame]:
            for chunk in src():
                yield fn(chunk)

        return StreamingDataFrame(source)

    def transform(self, stage: Any) -> "StreamingDataFrame":
        """Lazily apply a fitted Transformer/PipelineModel chunk-by-chunk,
        ``_CHUNKS_IN_FLIGHT`` chunks at a time and in order (the module's
        docstring says what a stage and a sink may assume)."""
        src = self._source
        return StreamingDataFrame(lambda: _in_flight(src, stage.transform))

    # -- consumption ---------------------------------------------------------

    def iter_chunks(self) -> Iterator[DataFrame]:
        return self._source()

    def foreach_chunk(self, fn: Callable[[DataFrame], None]) -> int:
        n = 0
        for chunk in self._source():
            fn(chunk)
            n += len(chunk)
        return n

    def count(self) -> int:
        return sum(len(chunk) for chunk in self._source())

    def first(self) -> Optional[DataFrame]:
        for chunk in self._source():
            return chunk
        return None

    def materialize(self, max_rows: Optional[int] = None) -> DataFrame:
        """Concatenate chunks into an eager DataFrame; stops PULLING the
        source as soon as ``max_rows`` rows are buffered — on an
        unbounded source (an infinite feedback generator, a live ingest
        stream) the iterator is never drained past the cap. The chunk
        that crosses the cap is truncated to exactly ``max_rows`` rows.
        ``max_rows <= 0`` returns an empty frame without touching the
        source at all (no chunk is ever pulled just to be discarded).

        The online suite (tests/test_online.py) pins this contract:
        FeedbackStream's pull sources are unbounded by design, and a
        ``materialize`` that drained them would hang forever."""
        if max_rows is not None and max_rows <= 0:
            return DataFrame.from_dict({})
        chunks: list = []
        rows = 0
        src = self._source()
        for chunk in src:
            chunks.append(chunk)
            rows += len(chunk)
            if max_rows is not None and rows >= max_rows:
                # release the generator's resources eagerly (an open CSV
                # file handle, a live socket) instead of waiting for GC
                if hasattr(src, "close"):
                    src.close()
                break
        if not chunks:
            return DataFrame.from_dict({})
        cols: dict = {}
        for name in chunks[0].columns:
            cat = np.concatenate([c[name] for c in chunks])
            cols[name] = cat[:max_rows] if max_rows is not None else cat
        return DataFrame.from_dict(cols)

    def write_csv(self, path: str, header: bool = True) -> int:
        """Stream chunks to a CSV file (proper quoting); returns rows
        written."""
        import csv as _csv

        rows = 0
        with open(path, "w", newline="") as f:
            w = _csv.writer(f)
            for i, chunk in enumerate(self._source()):
                names = chunk.columns
                if i == 0 and header:
                    w.writerow(names)
                mats = [np.asarray(chunk[c]) for c in names]
                for r in range(len(chunk)):
                    w.writerow([_cell(m[r]) for m in mats])
                rows += len(chunk)
        return rows


def _cell(v: Any) -> str:
    if isinstance(v, (bytes, bytearray)):
        return v.decode("utf-8", "replace")
    if isinstance(v, (float, np.floating)) and float(v).is_integer():
        return str(int(v))
    return str(v)


def _load_files(paths: list) -> DataFrame:
    blobs = np.empty(len(paths), dtype=object)
    for i, fp in enumerate(paths):
        with open(fp, "rb") as f:
            blobs[i] = f.read()
    return DataFrame.from_dict(
        {"path": np.array(list(paths), dtype=object), "bytes": blobs}
    )
