"""bench.py orchestration semantics.

The parent/child protocol must stream one record per segment, print no
figure without a TPU, end non-zero when anything is missing, and pull a
wedged child's stacks before killing it — these tests pin that without
touching any accelerator.
"""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_emit_idempotent(capsys):
    """Signal handler + normal path may both call emit: one line only."""
    b = _load_bench()
    asm = b._Assembly()
    asm.emit()
    asm.emit()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1


def test_child_streams_segment_lines():
    """The child emits init + one line per requested segment + done, each
    a self-contained JSON record (the incremental-harvest contract)."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["MMLSPARK_BENCH_SEGMENTS"] = "serving"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-1500:]
    recs = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    segs = [r["segment"] for r in recs]
    assert segs == ["starting", "init", "serving", "done"]
    serving = recs[2]["data"]
    assert "serving_p50_ms" in serving
    assert "serving_gateway_p50_ms" in serving  # the gateway-overhead budget

class _FakeProc:
    def __init__(self, running: bool):
        self._running = running

    def poll(self):
        return None if self._running else 0

    def wait(self, timeout=None):
        if self._running:
            raise subprocess.TimeoutExpired("fake", timeout)
        return 0


class _FakeChild:
    """Replays scripted records; None = watchdog timeout/EOF. ``running``
    is the proc state _harvest sees when deciding whether to kill."""

    def __init__(self, records, running_at_end: bool):
        self._records = list(records)
        self.proc = _FakeProc(running_at_end)
        self.killed = False

    def next_record(self, timeout_s):
        if self._records:
            return self._records.pop(0)
        return None

    def kill(self):
        self.killed = True
        self.proc._running = False


def test_stalled_child_yields_stall_stacks_naming_the_wedge(tmp_path,
                                                            monkeypatch):
    """Stall forensics through the real parent/child pair: a child
    deliberately wedged inside a segment (MMLSPARK_BENCH_WEDGE_SEGMENT)
    is SIGUSR2'd by the harvest loop before the kill, and the collected
    dump lands in extra["stall_stacks"] naming _deliberate_wedge as the
    blocked frame."""
    import time as _time

    b = _load_bench()
    monkeypatch.setattr(b, "PARTIAL_PATH", str(tmp_path / "p.json"))
    monkeypatch.setattr(b, "SEGMENT_TIMEOUT_S", 4)
    monkeypatch.setattr(b, "SEGMENT_TIMEOUTS", {})
    monkeypatch.setenv("MMLSPARK_FLIGHTREC_DIR", str(tmp_path / "spool"))
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["MMLSPARK_BENCH_WEDGE_SEGMENT"] = "serving"
    env["MMLSPARK_FLIGHTREC_DIR"] = str(tmp_path / "spool")
    asm = b._Assembly()
    child = b._Child(["serving"], env)
    remaining = ["serving"]
    try:
        engaged = b._harvest(child, asm, remaining,
                             _time.monotonic() + 60, ["serving"])
    finally:
        child.kill()
    assert engaged is True  # wedged child had to be killed
    assert remaining == ["serving"]
    stacks = asm.extra["stall_stacks"]["serving"]
    assert "_deliberate_wedge" in stacks["MainThread"]


def test_collect_stall_stacks_tolerates_pidless_child():
    """_FakeChild-style children (and already-dead ones) have no
    signalable pid: forensics returns None fast instead of raising."""
    b = _load_bench()
    assert b._collect_stall_stacks(
        _FakeChild([], running_at_end=True)
    ) is None


def test_segment_order_covers_all_segments():
    """TPU_ORDER must be a permutation of SEGMENTS — a segment missing
    from the order would silently never run."""
    b = _load_bench()
    assert sorted(b.TPU_ORDER) == sorted(b.SEGMENTS)
    assert set(b.SEGMENTS) == set(b.SEGMENT_FNS)


def test_child_refuses_the_cpu_backend_when_a_tpu_is_required():
    """The parent starts its one child with MMLSPARK_BENCH_REQUIRE_TPU=1: a
    child that comes up on the CPU exits non-zero before any segment."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", MMLSPARK_BENCH_REQUIRE_TPU="1",
               MMLSPARK_BENCH_SEGMENTS="serving")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=240, cwd=REPO,
    )
    assert p.returncode != 0
    assert "backend is cpu but TPU was required" in p.stderr
    segs = [json.loads(ln)["segment"]
            for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert segs == ["starting"]


def test_no_tpu_exits_nonzero_and_prints_no_figure(tmp_path, monkeypatch,
                                                   capsys):
    """`python bench.py` where no child ever reports a TPU: there is no CPU
    phase to fall back to — the parent exits non-zero and stdout carries no
    result line, so a CPU number never appears under this benchmark's
    name."""
    b = _load_bench()
    monkeypatch.setattr(b, "PARTIAL_PATH", str(tmp_path / "p.json"))

    class _Refused(_FakeChild):
        stderr_tail = "bench child: backend is cpu but TPU was required\n"

    spawned = []

    def _fake_child(remaining, env):
        spawned.append(env.get("MMLSPARK_BENCH_REQUIRE_TPU"))
        return _Refused([{"segment": "starting", "data": {}}],
                        running_at_end=False)

    monkeypatch.setattr(b, "_Child", _fake_child)
    # main() installs SIGTERM/SIGINT handlers that os._exit: not in pytest
    monkeypatch.setattr(b.signal, "signal", lambda *a: None)
    try:
        b.main()
        code = 0
    except SystemExit as e:
        code = e.code
    out = capsys.readouterr()
    assert code not in (0, None)
    assert spawned == ["1"]  # one attempt, TPU required
    assert out.out.strip() == ""
    assert "no TPU" in out.err
