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
"""

from __future__ import annotations

import os
from typing import Any, Callable, Iterator, Optional, Sequence

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame


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
        """Lazily apply a fitted Transformer/PipelineModel chunk-by-chunk."""
        return self.map_chunks(stage.transform)

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
