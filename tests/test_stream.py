"""StreamingDataFrame: out-of-core chunked sources (the capability of the
reference's portioned binary reads, io/binary/BinaryFileFormat.scala:112-149).
"""

import os

import numpy as np
import pytest

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.io.stream import StreamingDataFrame


def counting_stream(n_chunks=10, rows=20, produced=None):
    produced = produced if produced is not None else []

    def make_chunk(i):
        produced.append(i)
        return DataFrame.from_dict(
            {"x": np.full(rows, float(i)), "i": np.arange(rows, dtype=np.float64)}
        )

    return StreamingDataFrame.from_generator(make_chunk, num_chunks=n_chunks), produced


def test_count_and_materialize():
    s, _ = counting_stream(5, 10)
    assert s.count() == 50
    df = s.materialize()
    assert len(df) == 50
    assert df["x"][0] == 0.0 and df["x"][-1] == 4.0


def test_lazy_one_chunk_at_a_time():
    s, produced = counting_stream(10, 4)
    it = s.iter_chunks()
    next(it)
    assert produced == [0]  # chunk 1 not built until asked for
    next(it)
    assert produced == [0, 1]


def test_materialize_stops_early():
    s, produced = counting_stream(100, 10)
    df = s.materialize(max_rows=25)
    assert len(df) == 25
    assert len(produced) == 3  # 3 chunks cover 25 rows; 97 never built


def test_reiterable_source():
    s, produced = counting_stream(3, 5)
    assert s.count() == 15
    assert s.count() == 15  # second traversal re-invokes the factory
    assert produced == [0, 1, 2, 0, 1, 2]


def test_transform_streams_through_stage():
    from mmlspark_tpu.stages import Lambda

    s, produced = counting_stream(6, 8)
    doubler = Lambda.of(lambda df: df.with_column("y", df["x"] * 2))
    out = s.transform(doubler)
    assert produced == []  # still lazy
    total = out.foreach_chunk(lambda c: None)
    assert total == 48


def test_stream_csv_chunks(tmp_path):
    p = tmp_path / "big.csv"
    n = 1000
    with open(p, "w") as f:
        f.write("a,b\n")
        for i in range(n):
            f.write(f"{i},{i * 2}\n")
    s = StreamingDataFrame.from_csv(str(p), chunk_rows=128)
    chunks = list(s.iter_chunks())
    assert len(chunks) > 1  # actually chunked
    assert sum(len(c) for c in chunks) == n
    df = s.materialize()
    np.testing.assert_allclose(df["a"], np.arange(n))
    np.testing.assert_allclose(df["b"], 2 * np.arange(n))


def test_stream_csv_no_header(tmp_path):
    p = tmp_path / "nh.csv"
    with open(p, "w") as f:
        for i in range(50):
            f.write(f"{i},{i + 1}\n")
    s = StreamingDataFrame.from_csv(str(p), chunk_rows=16, header=False)
    df = s.materialize()
    assert len(df) == 50
    np.testing.assert_allclose(df[df.columns[0]], np.arange(50))


def test_stream_binary_files(tmp_path):
    for i in range(7):
        (tmp_path / f"f{i}.bin").write_bytes(bytes([i]) * 10)
    s = StreamingDataFrame.from_binary_files(str(tmp_path), files_per_chunk=3)
    chunks = list(s.iter_chunks())
    assert [len(c) for c in chunks] == [3, 3, 1]
    df = s.materialize()
    assert len(df) == 7
    assert all(len(b) == 10 for b in df["bytes"])


def test_write_csv_roundtrip(tmp_path):
    s, _ = counting_stream(4, 5)
    out = tmp_path / "out.csv"
    rows = s.write_csv(str(out))
    assert rows == 20
    from mmlspark_tpu.io.csv import read_csv

    df = read_csv(str(out))
    assert len(df) == 20 and set(df.columns) == {"x", "i"}


def test_stream_csv_serial_consolidator_semantics(tmp_path, monkeypatch):
    """Consolidation holds under SERIAL partition execution too: exactly one
    output partition carries all rows."""
    from mmlspark_tpu.io.consolidator import PartitionConsolidator

    df = DataFrame.from_dict({"x": np.arange(12, dtype=np.float64)},
                             num_partitions=4)
    # force serial execution through the nested-pool path (dataframe._run
    # runs partitions serially inside an "mml-task"-named thread)
    import threading

    t = threading.current_thread()
    monkeypatch.setattr(t, "name", "mml-task-forced")
    out = PartitionConsolidator().transform(df)
    sizes = sorted((len(p["x"]) for p in out._parts), reverse=True)
    assert sizes[0] == 12 and sum(sizes) == 12
    assert sorted(out["x"]) == list(range(12))


def test_stream_csv_quoted_newlines(tmp_path):
    """Chunk boundaries must not split quoted fields containing newlines."""
    import csv as _csv

    p = tmp_path / "q.csv"
    with open(p, "w", newline="") as f:
        w = _csv.writer(f)
        w.writerow(["a", "b"])
        for i in range(200):
            w.writerow([i, f"line1\nline2-{i}"])
    s = StreamingDataFrame.from_csv(str(p), chunk_rows=16)
    df = s.materialize()
    assert len(df) == 200
    assert all("\n" in v for v in df["b"])
