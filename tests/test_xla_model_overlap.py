"""``XLAModel.apply_batch`` from two threads: the turn at dispatching and
the in-flight window are the model's, so a second call's batches queue
behind all of the first's, the device holds ``_MAX_IN_FLIGHT`` batches of
the model at most whichever calls they belong to, and each call still
returns its own rows. A lone call behaves as it always did."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu import obs
from mmlspark_tpu.models import xla_model
from mmlspark_tpu.models.xla_model import XLAModel

BATCH = 8   # one row a device on the suite's 8-device mesh


def _model():
    def apply_fn(vs, x):
        return x * vs["scale"] + x.sum(axis=1, keepdims=True)

    m = XLAModel(input_col="x", output_col="y", batch_size=BATCH)
    m.set(apply_fn=apply_fn, variables={"scale": jnp.full((), 3.0, jnp.float32)})
    return m


def _rows(rng, batches, short=3):
    return rng.normal(size=(batches * BATCH - short, 5)).astype(np.float32)


def _serial(x):
    """What one caller alone gets from a model of its own."""
    got = _model().apply_batch(x)
    np.testing.assert_allclose(
        got, x * np.float32(3.0) + x.sum(axis=1, keepdims=True), rtol=1e-5, atol=1e-6)
    return got


def _calls_counted():
    fam = obs.REGISTRY.snapshot().get("mmlspark_xla_model_calls_total", {"samples": []})
    out = {"cold": 0.0, "overlapped": 0.0}
    for labels, value in fam["samples"]:
        out[dict(labels)["start"]] += value
    return out


def _calls_since(before):
    now = _calls_counted()
    return {k: now[k] - before[k] for k in now}


class _Device:
    """Stands where the device would: counts the model's batches between
    their dispatch and the end of their fetch, and keeps a fetch ``fetch_s``
    on its way so that batches stay in flight long enough to be seen."""

    def __init__(self, monkeypatch, fetch_s=0.0):
        self.lock = threading.Lock()
        self.in_flight = self.most = 0
        self.fetch_s = fetch_s
        to_host, compiled = xla_model._to_host, XLAModel._compiled

        def slow_to_host(y, landed):
            time.sleep(self.fetch_s)
            try:
                return to_host(y, landed)
            finally:
                with self.lock:
                    self.in_flight -= 1

        def counting_compiled(model, shape, mesh):
            fn = compiled(model, shape, mesh)

            def dispatch(vs, chunk):
                with self.lock:
                    self.in_flight += 1
                    self.most = max(self.most, self.in_flight)
                return fn(vs, chunk)

            return dispatch

        monkeypatch.setattr(xla_model, "_to_host", slow_to_host)
        monkeypatch.setattr(XLAModel, "_compiled", counting_compiled)


def _dispatches(apply_span):
    kids = [s for s in obs.recent_spans() if s.parent_id == apply_span.span_id]
    return sorted((s.wall_ns, s.wall_ns + s.duration_ns) for s in kids
                  if s.name == "xla_model.dispatch")


@pytest.fixture()
def fresh_obs():
    obs.set_enabled(True)
    obs.clear_recent_spans()
    yield
    obs.set_enabled(True)


def _two_calls(monkeypatch, rng, batches_a=7, batches_b=5):
    """Call B arrives while call A is held in its dispatch loop (its fetches
    are slow, so its window is full): returns what each got and wanted."""
    xa, xb = _rows(rng, batches_a), _rows(rng, batches_b, short=0)
    want = {"a": _serial(xa), "b": _serial(xb)}
    device = _Device(monkeypatch, fetch_s=0.03)
    m = _model()
    m.apply_batch(xa[:BATCH])   # warm: compiles, and the weights go to the device
    while device.in_flight:
        time.sleep(0.005)
    device.most = 0
    obs.clear_recent_spans()
    before = _calls_counted()
    got = {}
    a = threading.Thread(target=lambda: got.setdefault("a", m.apply_batch(xa)))
    b = threading.Thread(target=lambda: got.setdefault("b", m.apply_batch(xb)))
    a.start()
    end = time.monotonic() + 10
    while device.most < XLAModel._MAX_IN_FLIGHT and time.monotonic() < end:
        time.sleep(0.001)   # A has filled the window and waits on its first fetch
    b.start()
    a.join(30)
    b.join(30)
    assert not a.is_alive() and not b.is_alive()
    return device, got, want, _calls_since(before)


def _case_each_call_gets_the_serial_result(monkeypatch, rng):
    _device, got, want, _counted = _two_calls(monkeypatch, rng)
    for k in ("a", "b"):
        np.testing.assert_array_equal(got[k], want[k])


def _case_dispatches_of_the_first_call_precede_the_seconds(monkeypatch, rng):
    _two_calls(monkeypatch, rng)
    applies = sorted((s for s in obs.recent_spans() if s.name == "xla_model.apply_batch"),
                     key=lambda s: s.wall_ns)
    assert [s.attrs["batches"] for s in applies] == [7, 5]
    first, second = (_dispatches(s) for s in applies)
    assert len(first) == 7 and len(second) == 5
    assert max(end for _s, end in first) <= min(start for start, _e in second)
    # the second call's wait for the first is a named leaf of its own
    turns = [s for s in obs.recent_spans() if s.name == "xla_model.turn"]
    assert sorted(s.parent_id for s in turns) == sorted(s.span_id for s in applies)
    waited = {s.parent_id: s.duration_ns for s in turns}
    assert waited[applies[1].span_id] > 10 * waited[applies[0].span_id]
    # and it began dispatching before the first call had drained
    drain_a = [s for s in obs.recent_spans()
               if s.name == "xla_model.drain" and s.parent_id == applies[0].span_id][0]
    assert second[0][0] < drain_a.wall_ns + drain_a.duration_ns


def _case_the_window_is_the_models(monkeypatch, rng):
    device, _got, _want_, _counted = _two_calls(monkeypatch, rng)
    assert device.most == XLAModel._MAX_IN_FLIGHT   # reached, and never passed
    assert device.in_flight == 0


def _case_the_counter_says_cold_and_overlapped(monkeypatch, rng):
    _device, _got, _want_, counted = _two_calls(monkeypatch, rng)
    assert counted == {"cold": 1.0, "overlapped": 1.0}
    applies = sorted((s for s in obs.recent_spans() if s.name == "xla_model.apply_batch"),
                     key=lambda s: s.wall_ns)
    assert [s.attrs["overlapped"] for s in applies] == [False, True]


def _case_a_lone_call_is_cold_every_time(monkeypatch, rng):
    x = _rows(rng, 6)
    want = _serial(x)
    _Device(monkeypatch, fetch_s=0.002)
    m = _model()
    before = _calls_counted()
    for _ in range(3):   # one after the other: each finds the last one's batches landed
        np.testing.assert_array_equal(m.apply_batch(x), want)
    assert _calls_since(before) == {"cold": 3.0, "overlapped": 0.0}
    apply = [s for s in obs.recent_spans() if s.name == "xla_model.apply_batch"][-1]
    names = [s.name for s in sorted(obs.recent_spans(), key=lambda s: s.wall_ns)
             if s.parent_id == apply.span_id]
    # the sequence a call had before the turn was the model's, plus the turn
    assert names == (["xla_model.prepare", "xla_model.turn"]
                     + ["xla_model.stage", "xla_model.dispatch"] * 3
                     + ["xla_model.stage", "xla_model.dispatch", "xla_model.backpressure"] * 3
                     + ["xla_model.drain", "xla_model.concat"])


def _case_a_call_that_fails_gives_its_turn_up(monkeypatch, rng):
    m = _model()
    x = _rows(rng, 2)
    want = m.apply_batch(x)
    boom = {"at": 1}
    stage = xla_model.shard_batch

    def failing_shard_batch(batch, mesh):
        boom["at"] -= 1
        if boom["at"] < 0:
            raise OSError("the transfer failed")
        return stage(batch, mesh)

    monkeypatch.setattr(xla_model, "shard_batch", failing_shard_batch)
    with pytest.raises(OSError, match="the transfer failed"):
        m.apply_batch(x)   # its first batch is dispatched, its second is not
    monkeypatch.setattr(xla_model, "shard_batch", stage)
    done = []
    t = threading.Thread(target=lambda: done.append(m.apply_batch(x)))
    t.start()
    t.join(20)
    assert not t.is_alive(), "the turn was never given up"
    np.testing.assert_array_equal(done[0], want)


def _case_two_first_calls_share_one_program_and_one_copy_of_the_weights(monkeypatch, rng):
    m = _model()
    x = _rows(rng, 2)
    start = threading.Barrier(2)
    got = []

    def call():
        start.wait(10)
        got.append(m.apply_batch(x))

    threads = [threading.Thread(target=call) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(got) == 2 and len(m._jit_cache) == 1
    np.testing.assert_array_equal(got[0], got[1])
    np.testing.assert_array_equal(got[0], _serial(x))


def _case_many_callers_under_a_short_switch_interval(monkeypatch, rng):
    """More callers than cores, the interpreter switching threads every 10
    microseconds: a lost update of the turn or the window would hand a
    caller another's rows, pass the window, or leave a caller waiting."""
    import os
    import sys

    callers, rounds = (os.cpu_count() or 4) + 3, 3
    xs = [_rows(rng, 1 + i % 6, short=i % BATCH) for i in range(callers)]
    want = [_serial(x) for x in xs]
    device = _Device(monkeypatch)
    m = _model()
    before = _calls_counted()
    wrong: list = []

    def call(i):
        for _ in range(rounds):
            if not np.array_equal(m.apply_batch(xs[i]), want[i]):
                wrong.append(i)

    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=call, args=(i,)) for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(keep)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    assert device.most <= XLAModel._MAX_IN_FLIGHT and device.in_flight == 0
    assert sum(_calls_since(before).values()) == callers * rounds
    assert len(m._feed.window) < XLAModel._MAX_IN_FLIGHT
    assert all(landed.is_set() for landed in m._feed.window)


_CASES = {
    "each_call_gets_the_serial_result": _case_each_call_gets_the_serial_result,
    "dispatches_of_the_first_call_precede_the_seconds":
        _case_dispatches_of_the_first_call_precede_the_seconds,
    "the_window_is_the_models": _case_the_window_is_the_models,
    "the_counter_says_cold_and_overlapped": _case_the_counter_says_cold_and_overlapped,
    "a_lone_call_is_cold_every_time": _case_a_lone_call_is_cold_every_time,
    "a_call_that_fails_gives_its_turn_up": _case_a_call_that_fails_gives_its_turn_up,
    "two_first_calls_share_one_program_and_one_copy_of_the_weights":
        _case_two_first_calls_share_one_program_and_one_copy_of_the_weights,
    "many_callers_under_a_short_switch_interval":
        _case_many_callers_under_a_short_switch_interval,
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_apply_batch_from_two_threads(case, monkeypatch, rng, fresh_obs):
    _CASES[case](monkeypatch, rng)
