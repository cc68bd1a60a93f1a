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


# -- the look-ahead of a transformed stream -----------------------------------

import threading
import time

from mmlspark_tpu.io import stream as stream_mod

DEPTH = stream_mod._CHUNKS_IN_FLIGHT


def _stream_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(("stream-feed", "stream-transform"))]


def _no_stream_threads(within=5.0):
    end = time.monotonic() + within
    while _stream_threads() and time.monotonic() < end:
        time.sleep(0.01)
    return not _stream_threads()


class _Source:
    """A counting source: which chunks were pulled, by which thread, and
    whether its generator was closed; ``hold[i]`` makes the pull of chunk i
    wait for that event."""

    def __init__(self, n, hold=None, fail_at=None):
        self.n, self.hold, self.fail_at = n, hold or {}, fail_at
        self.pulled, self.threads, self.closed, self.timed_out = [], set(), [], []

    def __call__(self):
        try:
            for i in range(self.n):
                if i in self.hold and not self.hold[i].wait(20):
                    self.timed_out.append(i)
                if i == self.fail_at:
                    raise OSError(f"source broke at chunk {i}")
                self.pulled.append(i)
                self.threads.add(threading.get_ident())
                yield DataFrame.from_dict({"x": np.full(3, float(i))})
        finally:
            self.closed.append(True)


class _Stage:
    """``transform`` doubles x; ``gate`` holds every call until it is set,
    ``slow`` sleeps on the chunks it names, ``fail_at`` raises there."""

    def __init__(self, gate=None, slow=(), fail_at=None):
        self.gate, self.slow, self.fail_at = gate, set(slow), fail_at
        self.started = []

    def transform(self, df):
        i = int(df["x"][0])
        self.started.append(i)
        if self.gate is not None:
            assert self.gate.wait(20)
        if i in self.slow:
            time.sleep(0.05)
        if i == self.fail_at:
            raise ValueError(f"stage broke at chunk {i}")
        return df.with_column("y", df["x"] * 2)


def _ids(chunks):
    return [int(c["x"][0]) for c in chunks]


def _case_order():
    # even chunks finish after the odd ones that follow them
    src, stage = _Source(9), _Stage(slow=range(0, 9, 2))
    out = StreamingDataFrame(src).transform(stage)
    chunks = list(out.iter_chunks())
    assert _ids(chunks) == list(range(9))
    assert all((c["y"] == 2 * c["x"]).all() for c in chunks)
    assert src.pulled == list(range(9)) and src.closed == [True]
    assert out.foreach_chunk(lambda c: None) == 27   # re-iterable, like its source
    assert _no_stream_threads()


def _case_never_more_than_the_depth_ahead():
    gate = threading.Event()
    src, stage = _Source(12), _Stage(gate=gate)
    got = []
    consumer = threading.Thread(
        target=lambda: got.extend(StreamingDataFrame(src).transform(stage).iter_chunks()))
    consumer.start()
    end = time.monotonic() + 10
    while len(stage.started) < DEPTH and time.monotonic() < end:
        time.sleep(0.005)
    time.sleep(0.2)   # nothing has been handed over: the feeder must stand still
    assert src.pulled == list(range(DEPTH)) and sorted(stage.started) == list(range(DEPTH))
    gate.set()
    consumer.join(20)
    assert not consumer.is_alive() and _ids(got) == list(range(12))
    # one thread pulls, and it is not the consumer's
    assert len(src.threads) == 1 and consumer.ident not in src.threads
    assert _no_stream_threads()


def _case_nothing_runs_before_iteration():
    src, stage = _Source(4), _Stage()
    out = StreamingDataFrame(src).transform(stage)
    it = out.iter_chunks()
    time.sleep(0.05)
    assert src.pulled == [] and stage.started == [] and not _stream_threads()
    assert _ids([next(it)]) == [0]
    it.close()
    assert _no_stream_threads()


def _case_a_failure_surfaces_at_its_chunk():
    src, stage = _Source(50), _Stage(fail_at=2, slow=[0, 1])
    it = StreamingDataFrame(src).transform(stage).iter_chunks()
    assert _ids([next(it), next(it)]) == [0, 1]
    with pytest.raises(ValueError, match="stage broke at chunk 2"):
        next(it)
    assert src.closed == [True] and len(src.pulled) <= 2 + DEPTH
    assert _no_stream_threads(within=0)   # the feeder ended with the failure
    # and the source's own failure, after the chunks before it
    src = _Source(50, fail_at=3)
    it = StreamingDataFrame(src).transform(_Stage()).iter_chunks()
    assert _ids([next(it), next(it), next(it)]) == [0, 1, 2]
    with pytest.raises(OSError, match="source broke at chunk 3"):
        next(it)
    assert src.closed == [True] and _no_stream_threads(within=0)


def _case_early_close_stops_the_feeder():
    src = _Source(1000)
    out = StreamingDataFrame(src).transform(_Stage())
    it = out.iter_chunks()
    next(it)
    it.close()
    assert src.closed == [True] and len(src.pulled) <= 1 + DEPTH
    assert _no_stream_threads(within=0)
    for take in (lambda: out.first(), lambda: out.materialize(max_rows=7)):
        src.pulled.clear()
        src.closed.clear()
        got = take()
        assert len(got) in (3, 7)
        assert src.closed == [True] and len(src.pulled) <= 3 + DEPTH
        assert _no_stream_threads(within=0)
    # a break out of a loop over it, once the iterator is dropped
    for chunk in out.iter_chunks():
        break
    assert _no_stream_threads()


def _case_a_blocked_source_does_not_delay_the_chunk_before():
    held = threading.Event()
    src = _Source(3, hold={1: held})
    it = StreamingDataFrame(src).transform(_Stage()).iter_chunks()
    assert _ids([next(it)]) == [0]          # handed over while chunk 1's pull still blocks
    assert src.pulled == [0] and not src.timed_out
    held.set()
    assert _ids(list(it)) == [1, 2]
    assert _no_stream_threads()
    # closed while the feeder is inside a pull: the close returns, the feeder
    # ends when the pull does and closes the generator itself
    held = threading.Event()
    src = _Source(3, hold={1: held})
    it = StreamingDataFrame(src).transform(_Stage()).iter_chunks()
    next(it)
    it.close()
    assert src.closed == [] and not src.timed_out
    held.set()
    assert _no_stream_threads() and src.closed == [True] and src.pulled == [0, 1]


def _case_many_chunks_under_a_short_switch_interval():
    """Several streams at once, each with its own feeder and workers, the
    interpreter switching threads every 10 microseconds: every stream still
    hands its chunks over in order, whole, and leaves no thread."""
    import os
    import sys

    streams, chunks = (os.cpu_count() or 4) + 3, 60
    got: dict = {}

    def consume(k):
        src = _Source(chunks)
        out = list(StreamingDataFrame(src).transform(_Stage(slow=[7, 8, 31])).iter_chunks())
        got[k] = (_ids(out), src.pulled, src.closed, len(src.threads))

    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consume, args=(k,)) for k in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(keep)
    assert not any(t.is_alive() for t in threads) and len(got) == streams
    for ids, pulled, closed, pullers in got.values():
        assert ids == list(range(chunks)) == pulled and closed == [True] and pullers == 1
    assert _no_stream_threads()


_LOOK_AHEAD_CASES = {
    "order_of_results": _case_order,
    "never_more_than_the_depth_ahead": _case_never_more_than_the_depth_ahead,
    "nothing_runs_before_iteration": _case_nothing_runs_before_iteration,
    "a_failure_surfaces_at_its_chunk": _case_a_failure_surfaces_at_its_chunk,
    "early_close_stops_the_feeder": _case_early_close_stops_the_feeder,
    "a_blocked_source_does_not_delay_the_chunk_before":
        _case_a_blocked_source_does_not_delay_the_chunk_before,
    "many_chunks_under_a_short_switch_interval": _case_many_chunks_under_a_short_switch_interval,
}


@pytest.mark.parametrize("case", sorted(_LOOK_AHEAD_CASES))
def test_transformed_stream_keeps_chunks_in_flight(case):
    _LOOK_AHEAD_CASES[case]()


def test_map_chunks_and_a_bare_source_stay_one_chunk_at_a_time():
    src = _Source(5)
    it = StreamingDataFrame(src).map_chunks(lambda c: c).iter_chunks()
    next(it)
    assert src.pulled == [0] and not _stream_threads()
