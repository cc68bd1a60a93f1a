"""Chaos suite: drive every fault-injection point end-to-end on CPU.

Each test arms a deterministic :class:`FaultPlan` (core/faults.py) and
asserts the matching recovery machinery actually recovers:

- ``io.send_request``  — injected network errors become status-0 rows;
  injected 5xx retried through by AdvancedHandler;
- ``gateway.forward``  — workers dying mid-flight; the gateway
  re-dispatches and completes 100% of accepted requests;
- ``gateway.response`` — post-send hangs; at-most-once 504 vs opt-in
  re-dispatch;
- ``parallel.barrier`` — a slow host; the timeout diagnostic names the
  missing host off a TTL'd registry roster;
- ``gbdt.round``       — preemption between boosting rounds; training
  resumed from the round checkpoint is bit-identical to uninterrupted.
"""

from __future__ import annotations

import http.client
import json
import random
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.faults import FaultPlan, Preempted, active_plan

pytestmark = pytest.mark.chaos


# -- the plan/schedule machinery itself --------------------------------------


def test_fault_plan_schedules_are_deterministic():
    def fires(seed):
        plan = FaultPlan(seed=seed).on("p", probability=0.3, payload=1)
        with plan.armed():
            for i in range(50):
                plan.check("p", step=i)
        return plan.fires()

    a, b = fires(7), fires(7)
    assert a == b and 0 < len(a) < 50  # same seed -> same schedule
    assert fires(8) != a               # different seed -> different schedule


def test_fault_plan_at_every_and_max_fires():
    plan = FaultPlan().on("a", at=(2, 5), payload="x")
    plan.on("b", after=1, every=3, payload="y", max_fires=2)
    with plan.armed():
        got_a = [plan.check("a", step=i) for i in range(7)]
        got_b = [plan.check("b", step=i) for i in range(12)]
    assert [i for i, v in enumerate(got_a) if v] == [2, 5]
    assert [i for i, v in enumerate(got_b) if v] == [1, 4]  # capped at 2


def test_fault_plan_json_spec_roundtrip():
    plan = FaultPlan.from_spec(
        '{"seed": 3, "rules": [{"point": "io.send_request", '
        '"error": "ConnectionError", "at": [0]}, '
        '{"point": "io.send_request", "payload": 503, "at": [1]}]}'
    )
    assert plan.seed == 3 and plan.points() == ["io.send_request"]
    with plan.armed():
        with pytest.raises(ConnectionError):
            plan.check("io.send_request", step=0)
        assert plan.check("io.send_request", step=1) == 503
    assert active_plan() is None  # armed() uninstalls
    # a typo'd error name must fail at plan load, not as a mystery
    # FaultError from inside the injected call site
    with pytest.raises(ValueError, match="unknown fault error name"):
        FaultPlan.from_spec(
            '{"rules": [{"point": "p", "error": "ConectionError"}]}'
        )


# -- io.send_request ---------------------------------------------------------


def test_send_request_injected_faults_follow_error_contract():
    from mmlspark_tpu.io.clients import send_request

    plan = FaultPlan().on(
        "io.send_request", error=ConnectionError, at=(0,)
    ).on("io.send_request", payload=503, at=(1,))
    with plan.armed():
        # injected network error -> status-0 row, never an exception
        r0 = send_request({"url": "http://127.0.0.1:1/"})
        assert r0["status_code"] == 0 and "injected" in r0["reason"]
        # injected int payload -> synthetic HTTP status
        r1 = send_request({"url": "http://127.0.0.1:1/"})
        assert r1["status_code"] == 503
    # a delay-only rule (payload True, a bool) must fall through to the
    # REAL request after sleeping — not become a status_code=True row
    plan2 = FaultPlan().on("io.send_request", delay_s=0.05, at=(0,))
    with plan2.armed():
        t0 = time.monotonic()
        r2 = send_request({"url": "http://127.0.0.1:1/"}, timeout=2.0)
        assert time.monotonic() - t0 >= 0.05
        assert r2["status_code"] == 0  # the real connect was attempted


def test_advanced_handler_retries_through_injected_5xx():
    from mmlspark_tpu.io.clients import AdvancedHandler
    from mmlspark_tpu.io.http_schema import HTTPRequestData
    from mmlspark_tpu.serving.query import ServingQuery
    from mmlspark_tpu.serving.server import WorkerServer

    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, _echo_handler).start()
    plan = FaultPlan().on("io.send_request", payload=503, at=(0, 1))
    try:
        with plan.armed():
            resp = AdvancedHandler(backoffs_ms=(5, 5, 5))(
                HTTPRequestData(
                    f"http://127.0.0.1:{info.port}/", "POST",
                    {"Content-Type": "application/json"}, '{"v": 1}',
                )
            )
        assert resp["status_code"] == 200
        assert json.loads(resp["entity"]) == {"echo": {"v": 1}}
        assert len(plan.fires()) == 2  # two synthetic 503s were retried
    finally:
        q.stop()
        srv.stop()


# -- serving gateway ---------------------------------------------------------


def _echo_handler(reqs):
    out = {}
    for r in reqs:
        body = json.loads(r.body) if r.body else {}
        out[r.id] = (200, json.dumps({"echo": body}).encode(), {})
    return out


def _worker(handler=_echo_handler):
    from mmlspark_tpu.serving.query import ServingQuery
    from mmlspark_tpu.serving.server import WorkerServer

    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, handler).start()
    return srv, q, info


def _post(port, path, obj, method="POST"):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        body = json.dumps(obj) if obj is not None else None
        c.request(method, path, body=body,
                  headers={"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, r.read()
    finally:
        c.close()


def test_gateway_worker_death_mid_flight_zero_lost():
    """Every 4th forward attempt dies like a worker crash; the gateway
    re-dispatches and 100% of accepted requests complete correctly."""
    from mmlspark_tpu.serving.distributed import ServingGateway

    s1, q1, i1 = _worker()
    s2, q2, i2 = _worker()
    # a 25%-of-attempts fault rate sits ABOVE the default 20% retry
    # budget by design elsewhere (the budget exists to clamp exactly this
    # much amplification); here the property under test is zero-loss
    # re-dispatch itself, so size the budget for the injected rate
    gw = ServingGateway(
        workers=[i1, i2], request_timeout_s=5.0, retry_budget_ratio=0.5,
    )
    ginfo = gw.start()
    plan = FaultPlan().on(
        "gateway.forward", error=ConnectionResetError, every=4
    )
    try:
        with plan.armed():
            for i in range(40):
                status, data = _post(ginfo.port, "/", {"i": i})
                assert status == 200, f"request {i} lost (status {status})"
                assert json.loads(data)["echo"]["i"] == i
        assert gw.retried >= 10 and gw.failed == 0
        assert len(plan.fires()) == gw.retried
    finally:
        gw.stop()
        for s, q in ((s1, q1), (s2, q2)):
            q.stop()
            s.stop()


def test_gateway_post_send_hang_is_at_most_once_504():
    from mmlspark_tpu.serving.distributed import ServingGateway

    s1, q1, i1 = _worker()
    gw = ServingGateway(workers=[i1], request_timeout_s=5.0)
    ginfo = gw.start()
    plan = FaultPlan().on("gateway.response", error=TimeoutError, at=(0,))
    try:
        with plan.armed():
            status, data = _post(ginfo.port, "/", {"i": 0})
            assert status == 504 and b"timed out" in data
            status, data = _post(ginfo.port, "/", {"i": 1})
            assert status == 200  # the hang was not held against the pool
        assert gw.failed == 1
    finally:
        gw.stop()
        q1.stop()
        s1.stop()


def test_gateway_post_send_hang_redispatches_when_idempotent():
    from mmlspark_tpu.serving.distributed import ServingGateway

    s1, q1, i1 = _worker()
    s2, q2, i2 = _worker()
    gw = ServingGateway(
        workers=[i1, i2], request_timeout_s=5.0, retry_after_send=True
    )
    ginfo = gw.start()
    plan = FaultPlan().on("gateway.response", error=TimeoutError, at=(0,))
    try:
        with plan.armed():
            status, data = _post(ginfo.port, "/", {"i": 0})
        assert status == 200 and json.loads(data)["echo"]["i"] == 0
        assert gw.retried == 1 and gw.failed == 0
    finally:
        gw.stop()
        for s, q in ((s1, q1), (s2, q2)):
            q.stop()
            s.stop()


@pytest.mark.xdist_group("latency")
def test_gateway_health_endpoint_and_graceful_drain():
    from mmlspark_tpu.serving.distributed import ServingGateway

    def slow_echo(reqs):
        time.sleep(0.4)
        return _echo_handler(reqs)

    s1, q1, i1 = _worker(slow_echo)
    gw = ServingGateway(workers=[i1], request_timeout_s=10.0)
    ginfo = gw.start()
    status, data = _post(ginfo.port, "/health", None, method="GET")
    health = json.loads(data)
    assert status == 200 and health["status"] == "ok"
    assert health["backends"] == 1

    results = []

    def client():
        results.append(_post(ginfo.port, "/", {"i": 1}))

    t = threading.Thread(target=client)
    t.start()
    time.sleep(0.1)  # request accepted and dispatched to the slow worker

    drain_health = []

    def probe():
        time.sleep(0.05)  # after drain() has flipped the flag
        drain_health.append(_post(ginfo.port, "/health", None, method="GET"))

    p = threading.Thread(target=probe)
    p.start()
    try:
        assert gw.drain(timeout_s=10.0)  # waits out the in-flight request
        t.join(5.0)
        p.join(5.0)
        # the accepted request was NOT dropped by the roll
        assert results and results[0][0] == 200
        assert json.loads(results[0][1])["echo"]["i"] == 1
        # while draining, /health told the balancer to route elsewhere
        assert drain_health and drain_health[0][0] == 503
        assert json.loads(drain_health[0][1])["status"] == "draining"
    finally:
        q1.stop()
        s1.stop()


# -- registry TTL + clean deregistration -------------------------------------


@pytest.mark.xdist_group("latency")
def test_registry_ttl_expires_silently_dead_workers():
    from mmlspark_tpu.serving.registry import DriverRegistry
    from mmlspark_tpu.serving.server import ServiceInfo

    reg = DriverRegistry(host="127.0.0.1", port=0, ttl_s=0.25)
    try:
        info = ServiceInfo("svc", "host-a", 1234)
        assert DriverRegistry.register(reg.url, info)
        assert [e["host"] for e in reg.services("svc")] == ["host-a"]
        time.sleep(0.4)  # no heartbeat: the entry must expire, not linger
        assert reg.services("svc") == []
        assert DriverRegistry.register(reg.url, info)  # heartbeat revives
        assert reg.services("svc")
    finally:
        reg.stop()


def test_fleet_worker_deregisters_on_clean_shutdown():
    from mmlspark_tpu.serving import fleet

    reg = fleet.run_registry(host="127.0.0.1", port=0)
    srv, q, stop = fleet.run_worker(
        reg.url, model="echo", host="127.0.0.1", heartbeat_s=30.0
    )
    try:
        deadline = time.monotonic() + 5.0
        while not reg.services("serving") and time.monotonic() < deadline:
            time.sleep(0.02)
        assert reg.services("serving")
        stop.stop()  # clean SIGTERM path: roster entry removed NOW
        assert reg.services("serving") == []
    finally:
        q.stop()
        srv.stop()
        reg.stop()


# -- barrier timeout diagnostics ---------------------------------------------


@pytest.mark.xdist_group("latency")
def test_barrier_timeout_names_missing_host():
    from mmlspark_tpu.parallel.distributed import BarrierTimeoutError, barrier
    from mmlspark_tpu.serving.registry import DriverRegistry
    from mmlspark_tpu.serving.server import ServiceInfo

    reg = DriverRegistry(host="127.0.0.1", port=0, ttl_s=0.5)
    try:
        DriverRegistry.register(reg.url, ServiceInfo("hosts", "host-a", 1))
        DriverRegistry.register(reg.url, ServiceInfo("hosts", "host-b", 2))
        time.sleep(0.7)  # both heartbeats lapse...
        DriverRegistry.register(reg.url, ServiceInfo("hosts", "host-a", 1))
        # ...and only host-a comes back: host-b is the dead one
        plan = FaultPlan().on("parallel.barrier", delay_s=2.0)
        with plan.armed():
            with pytest.raises(BarrierTimeoutError) as ei:
                barrier(
                    "epoch-sync",
                    timeout_s=0.2,
                    expected=["host-a", "host-b"],
                    alive=lambda: reg.live_hosts("hosts"),
                )
        assert ei.value.missing == ["host-b"]
        assert "host-b" in str(ei.value) and "epoch-sync" in str(ei.value)
    finally:
        reg.stop()


def test_barrier_without_timeout_and_error_surfaces():
    from mmlspark_tpu.parallel.distributed import barrier

    barrier("fast-path")  # single-process no-op must stay a no-op
    plan = FaultPlan().on("parallel.barrier", error=RuntimeError, at=(0,))
    with plan.armed():
        with pytest.raises(RuntimeError):
            barrier("boom", timeout_s=5.0)  # worker-thread error surfaces


# -- GBDT preemption + checkpoint/resume -------------------------------------


def _toy_binary(n=400, d=8, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] + 0.1 * r.normal(size=n) > 0).astype(
        np.float64
    )
    return x, y


def _preempt_resume_roundtrip(tmp_path, cfg, preempt_round, valid_mask=None):
    """Train uninterrupted; train again preempted at ``preempt_round`` and
    resume from the checkpoint; return both model strings."""
    from mmlspark_tpu.models.gbdt.train import train

    x, y = _toy_binary()
    kw = dict(valid_mask=valid_mask, checkpoint_every=1)
    ref = train(x, y, cfg, checkpoint_dir=str(tmp_path / "ref"), **kw)
    ck = str(tmp_path / "ck")
    plan = FaultPlan().on("gbdt.round", at=(preempt_round,), error=Preempted)
    with plan.armed():
        with pytest.raises(Preempted):
            train(x, y, cfg, checkpoint_dir=ck, **kw)
    assert plan.fires() == [("gbdt.round", preempt_round)]
    resumed = train(x, y, cfg, checkpoint_dir=ck, resume_from=ck, **kw)
    return ref.to_model_string(), resumed.to_model_string()


def test_gbdt_preempt_resume_bit_identical(tmp_path):
    """The headline guarantee: preempt at round k, resume, get the SAME
    model bit-for-bit (scan-fused fast path)."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig

    cfg = TrainConfig(
        objective="binary", num_iterations=8, num_leaves=7, seed=5
    )
    ref, resumed = _preempt_resume_roundtrip(tmp_path, cfg, preempt_round=5)
    assert resumed == ref


def test_gbdt_preempt_resume_bit_identical_with_sampling(tmp_path):
    """Resume mid-bagging-period with feature subsampling: the checkpoint
    must carry the bagging mask AND the host RNG stream exactly."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig

    cfg = TrainConfig(
        objective="binary", num_iterations=8, num_leaves=7, seed=11,
        bagging_fraction=0.7, bagging_freq=2, feature_fraction=0.6,
    )
    # round 5 is mid-period (5 % 2 != 0): the restored mask, not a fresh
    # draw, must drive rounds 5..7
    ref, resumed = _preempt_resume_roundtrip(tmp_path, cfg, preempt_round=5)
    assert resumed == ref


def test_gbdt_preempt_resume_bit_identical_goss_with_eval(tmp_path):
    from mmlspark_tpu.models.gbdt.train import TrainConfig

    cfg = TrainConfig(
        objective="binary", num_iterations=8, num_leaves=7, seed=3,
        boosting_type="goss", feature_fraction=0.6,
    )
    valid = np.zeros(400, bool)
    valid[350:] = True  # eval path: best_val/best_iter counters checkpoint too
    ref, resumed = _preempt_resume_roundtrip(
        tmp_path, cfg, preempt_round=5, valid_mask=valid
    )
    assert resumed == ref


def test_gbdt_preempt_resume_bit_identical_dart_slow_path(tmp_path):
    """dart runs the dispatch-per-iteration path and mutates PAST trees
    with host-rng dropouts — the harshest resume case."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig

    cfg = TrainConfig(
        objective="binary", num_iterations=8, num_leaves=7, seed=9,
        boosting_type="dart", drop_rate=0.5, skip_drop=0.0,
    )
    ref, resumed = _preempt_resume_roundtrip(tmp_path, cfg, preempt_round=5)
    assert resumed == ref


def test_gbdt_resume_rejects_config_mismatch(tmp_path):
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train

    x, y = _toy_binary()
    ck = str(tmp_path / "ck")
    cfg = TrainConfig(objective="binary", num_iterations=4, num_leaves=7)
    train(x, y, cfg, checkpoint_dir=ck, checkpoint_every=2)
    other = TrainConfig(objective="binary", num_iterations=4, num_leaves=15)
    with pytest.raises(ValueError, match="fingerprint"):
        train(x, y, other, resume_from=ck)


def test_checkpoint_torn_save_is_invisible(tmp_path):
    """LATEST flips only after a round dir is complete: garbage from a
    preemption mid-save must never be loaded."""
    import os

    from mmlspark_tpu.models.gbdt.booster import Booster
    from mmlspark_tpu.models.gbdt.checkpoint import (
        TrainCheckpoint,
        load_checkpoint,
        save_checkpoint,
    )

    d = str(tmp_path)
    rng = np.random.default_rng(0)
    ck = TrainCheckpoint(
        round=2, booster=Booster(), scores=np.zeros(4, np.float32),
        bag=None, rng_state=rng.bit_generator.state, fingerprint="fp",
    )
    save_checkpoint(d, ck)
    # a torn save: round dir partially written, LATEST not yet flipped
    torn = os.path.join(d, "round-0000003")
    os.makedirs(torn)
    with open(os.path.join(torn, "state.json"), "w") as f:
        f.write("{ totally not json")
    loaded = load_checkpoint(d)
    assert loaded is not None and loaded.round == 2
    # completing round 4 prunes history beyond keep_last
    save_checkpoint(d, TrainCheckpoint(
        round=4, booster=Booster(), scores=np.zeros(4, np.float32),
        bag=None, rng_state=rng.bit_generator.state, fingerprint="fp",
    ), keep_last=2)
    assert load_checkpoint(d).round == 4
    rounds = sorted(e for e in os.listdir(d) if e.startswith("round-"))
    assert len(rounds) == 2


def test_checkpoint_prune_never_eats_the_live_checkpoint(tmp_path):
    """A fresh run writing LOW round numbers into a dir still holding a
    previous run's HIGHER rounds must not prune its own just-committed
    checkpoint (pruning is by recency, not round number)."""
    import os
    import time as _time

    from mmlspark_tpu.models.gbdt.booster import Booster
    from mmlspark_tpu.models.gbdt.checkpoint import (
        TrainCheckpoint,
        load_checkpoint,
        save_checkpoint,
    )

    d = str(tmp_path)
    rng = np.random.default_rng(0)

    def ck(rnd):
        return TrainCheckpoint(
            round=rnd, booster=Booster(), scores=np.zeros(4, np.float32),
            bag=None, rng_state=rng.bit_generator.state, fingerprint="fp",
        )

    save_checkpoint(d, ck(20))
    _time.sleep(0.02)  # mtime ordering must be unambiguous
    save_checkpoint(d, ck(30))
    _time.sleep(0.02)
    save_checkpoint(d, ck(10), keep_last=2)  # the new, shorter run
    loaded = load_checkpoint(d)
    assert loaded is not None and loaded.round == 10
    assert os.path.isdir(os.path.join(d, "round-0000010"))


def test_gateway_ingress_history_stays_bounded():
    """LB /health probes and data traffic must not accumulate in the
    gateway ingress replay history forever (the gateway re-dispatches
    across workers; it never replays epochs)."""
    from mmlspark_tpu.serving.distributed import ServingGateway

    s1, q1, i1 = _worker()
    gw = ServingGateway(workers=[i1], request_timeout_s=5.0)
    ginfo = gw.start()
    try:
        for i in range(30):
            assert _post(ginfo.port, "/", {"i": i})[0] == 200
            assert _post(ginfo.port, "/health", None, method="GET")[0] == 200
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with gw._ingress._lock:
                n_hist = sum(len(v) for v in gw._ingress._history.values())
            if n_hist == 0:
                break
            time.sleep(0.05)  # the post-batch auto_commit may still be due
        assert n_hist == 0, f"{n_hist} requests leaked into ingress history"
    finally:
        gw.stop()
        q1.stop()
        s1.stop()


def test_estimator_checkpoint_rejects_num_batches(tmp_path):
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    r = np.random.default_rng(1)
    df = DataFrame.from_dict(
        {
            "features": r.normal(size=(60, 4)).astype(np.float32),
            "label": (r.random(60) > 0.5).astype(np.float64),
        },
        num_partitions=1,
    )
    est = LightGBMClassifier(
        num_iterations=2, num_batches=2, checkpoint_dir=str(tmp_path / "ck")
    )
    with pytest.raises(ValueError, match="num_batches"):
        est.fit(df)


def test_estimator_checkpoint_resume_params(tmp_path):
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    r = np.random.default_rng(4)
    x = r.normal(size=(200, 5)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float64)
    df = DataFrame.from_dict({"features": x, "label": y}, num_partitions=2)
    common = dict(num_iterations=6, num_leaves=7, seed=3, checkpoint_every=1)
    ref = LightGBMClassifier(
        checkpoint_dir=str(tmp_path / "ref"), **common
    ).fit(df)
    ck = str(tmp_path / "ck")
    plan = FaultPlan().on("gbdt.round", at=(4,), error=Preempted)
    with plan.armed():
        with pytest.raises(Preempted):
            LightGBMClassifier(checkpoint_dir=ck, **common).fit(df)
    resumed = LightGBMClassifier(
        checkpoint_dir=ck, resume_from=ck, **common
    ).fit(df)
    assert (
        resumed.booster.to_model_string() == ref.booster.to_model_string()
    )


# -- retry_with_backoff: jitter + deadline -----------------------------------


def test_retry_full_jitter_desynchronizes_and_deadline_caps():
    from mmlspark_tpu.core.utils import retry_with_backoff

    sleeps = []
    t = [0.0]

    def fake_sleep(s):
        sleeps.append(s)
        t[0] += s

    calls = []

    def fail():
        calls.append(1)
        raise ValueError("down")

    with pytest.raises(ValueError):
        retry_with_backoff(
            fail, backoffs_ms=(100, 500, 1000), rng=random.Random(1),
            sleep=fake_sleep, clock=lambda: t[0],
        )
    assert len(calls) == 4
    # full jitter: every wait inside [0, backoff], NOT the fixed schedule
    assert all(0.0 <= s <= b / 1000.0 for s, b in zip(sleeps, (100, 500, 1000)))
    assert sleeps != [0.1, 0.5, 1.0]

    # deadline: no sleep extends past it, no attempt starts after it
    sleeps.clear()
    calls.clear()
    t[0] = 0.0
    with pytest.raises(ValueError):
        retry_with_backoff(
            fail, backoffs_ms=(1000, 1000, 1000), jitter=False,
            deadline_s=1.5, sleep=fake_sleep, clock=lambda: t[0],
        )
    assert len(calls) == 2 and sleeps == [1.0]  # second wait would overshoot

    # jitter=False keeps the legacy fixed schedule
    sleeps.clear()

    def flaky():
        if not sleeps:
            raise ValueError("once")
        return 42

    assert retry_with_backoff(
        flaky, backoffs_ms=(100,), jitter=False, sleep=fake_sleep,
        clock=lambda: t[0],
    ) == 42
    assert sleeps == [0.1]


# -- self-healing soak: supervisor + breakers + retry budget -----------------


@pytest.mark.xdist_group("latency")
def test_chaos_soak_supervisor_restores_fleet_and_breakers_cycle():
    """The PR-5 acceptance soak: ~30 s of sustained traffic through
    gateway + 2 subprocess workers while one worker is SIGKILLed
    mid-soak and latency faults run on the forward path. The fleet
    supervisor must restore the roster without operator action, the dead
    worker's breaker must demonstrably cycle (open -> half-open ->
    closed, metric evidence), no request may be dropped, and retry
    amplification must stay <= 1.25 — containment, not a retry storm."""
    import os
    import socket

    from mmlspark_tpu import obs
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.supervisor import (
        FleetSupervisor,
        charge_from_worker_args,
    )

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    soak_s = float(os.environ.get("MMLSPARK_CHAOS_SOAK_S", "30"))
    reg = fleet.run_registry(host="127.0.0.1", port=0)
    ports = [free_port(), free_port()]
    charges = [
        charge_from_worker_args(
            f"--model echo --host 127.0.0.1 --port {p} --heartbeat-s 0.5",
            reg.url, i,
        )
        for i, p in enumerate(ports)
    ]
    sup = FleetSupervisor(
        charges, registry_url=reg.url, probe_s=0.3, backoff_s=0.3,
        stable_s=20.0,
    ).start()
    from mmlspark_tpu.serving.distributed import ServingGateway

    gw = ServingGateway(
        registry_url=reg.url, refresh_s=0.2, cooldown_s=0.4,
        evict_after=3, request_timeout_s=5.0,
    )
    ginfo = gw.start()
    counters: dict = {"ok": 0, "other": 0, "dropped": 0, "n": 0}
    stop_traffic = threading.Event()
    lock = threading.Lock()

    def scrape():
        return fleet.scrape_metrics(f"http://127.0.0.1:{ginfo.port}")

    def client_loop():
        i = 0
        while not stop_traffic.is_set():
            i += 1
            try:
                status, _ = _post(ginfo.port, "/", {"i": i})
            except Exception:  # noqa: BLE001 — a DROP, the thing we gate on
                status = None
            with lock:
                counters["n"] += 1
                if status == 200:
                    counters["ok"] += 1
                elif status is None:
                    counters["dropped"] += 1
                else:
                    counters["other"] += 1
            time.sleep(0.002)

    try:
        deadline = time.monotonic() + 60.0
        while gw.pool.size() < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert gw.pool.size() == 2, "both workers must be routable pre-soak"
        before = scrape()
        victim = charges[0]
        victim_addr = f"127.0.0.1:{ports[0]}"
        # latency faults on the forward path for the whole soak (the
        # injected-delay half of "worker crash + latency faults")
        plan = FaultPlan(seed=5).on(
            "gateway.forward", delay_s=0.02, probability=0.05
        )
        threads = [threading.Thread(target=client_loop) for _ in range(2)]
        t0 = time.monotonic()
        with plan.armed():
            for t in threads:
                t.start()
            time.sleep(soak_s * 0.2)
            victim.proc.kill()              # the worker crash, for real
            while time.monotonic() - t0 < soak_s:
                time.sleep(0.25)
            stop_traffic.set()
            for t in threads:
                t.join(10.0)
        assert len(plan.fires()) > 0        # latency chaos actually ran
        # -- self-healing: the supervisor restored the roster ----------------
        assert victim.restarts >= 1, "supervisor never restarted the victim"
        deadline = time.monotonic() + 20.0
        while gw.pool.size() < 2 and time.monotonic() < deadline:
            time.sleep(0.2)
        assert gw.pool.size() == 2, "roster not restored after the kill"
        assert victim.alive()
        # -- no request was dropped ------------------------------------------
        assert counters["n"] > 100          # the soak actually soaked
        assert counters["dropped"] == 0, (
            f"{counters['dropped']}/{counters['n']} requests got no reply"
        )
        assert counters["other"] == 0, (
            f"{counters['other']}/{counters['n']} requests failed "
            f"(expected every request to complete via retry containment)"
        )
        # -- breaker cycle, from the exported counters -----------------------
        after = scrape()

        def delta(name, match=None):
            return obs.sum_samples(after, name, match) - obs.sum_samples(
                before, name, match
            )

        opened = delta(
            "mmlspark_gateway_breaker_transitions_total",
            {"backend": victim_addr, "state": "open"},
        )
        half = delta(
            "mmlspark_gateway_breaker_transitions_total",
            {"backend": victim_addr, "state": "half_open"},
        )
        closed = delta(
            "mmlspark_gateway_breaker_transitions_total",
            {"backend": victim_addr, "state": "closed"},
        )
        assert opened >= 1, "the dead worker's breaker never opened"
        assert half >= 1, "the breaker never probed half-open"
        assert closed >= 1, "the breaker never re-closed"
        assert gw.pool.breaker_states()[victim_addr] == "closed"
        # -- retry amplification ---------------------------------------------
        forwarded = delta("mmlspark_gateway_requests_total")
        retried = delta("mmlspark_gateway_retries_total")
        amplification = (forwarded + retried) / max(1, counters["n"])
        assert amplification <= 1.25, (
            f"retry amplification {amplification:.3f} — containment failed "
            f"(forwarded {forwarded:.0f} + retried {retried:.0f} for "
            f"{counters['n']} requests)"
        )
    finally:
        stop_traffic.set()
        sup.stop()
        gw.stop()
        reg.stop()
        # the soak floods the process-global obs state (latency-bucket
        # exemplars pointing at traces that age out of the span ring,
        # hundreds of injected-fault flight records in the bounded
        # flight ring) — reset so later in-process tests (the smoke
        # gates especially) start from clean counters
        obs.reset()


# -- continuous learning under chaos: kill the worker mid-training -----------


@pytest.mark.xdist_group("latency")
def test_chaos_online_worker_kill_mid_training_zero_drop(tmp_path):
    """The continuous-learning acceptance soak (docs/online-learning.md):
    sustained serving traffic for the online model through the gateway
    while the OnlineLearningLoop trains on a live feedback stream and
    publishes every ~0.5 s — and one serving worker is SIGKILLed
    mid-soak, with the supervisor in AUTOSCALE mode — and the Publisher
    runs in ARTIFACT mode (docs/artifacts.md): every snapshot reaches
    the workers as ``artifact:vw:<name>@<sha256>`` pulled over HTTP
    (hash-verified), never as a filesystem path, so the soak proves the
    no-shared-filesystem deployment end-to-end. Gates: the supervisor
    restarts the victim warm (its ``--load artifact:`` seed spec pulls
    the model back over HTTP before re-registering), publication
    resumes (>= 3 successful publications AFTER the kill), ZERO dropped
    or failed requests across every version flip, zero feedback loss,
    the freshness burn rate ends green, and the autoscaler never shrank
    the fleet below its floor."""
    import os
    import socket

    from mmlspark_tpu import obs
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.online import (
        Autoscaler,
        FeedbackStream,
        FleetSignals,
        OnlineLearningLoop,
        OnlineTrainer,
        Publisher,
    )
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.distributed import ServingGateway
    from mmlspark_tpu.serving.supervisor import (
        FleetSupervisor,
        charge_from_worker_args,
    )

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    bits = 10
    rng = np.random.default_rng(17)

    def feedback_chunk(n=64):
        rows = np.empty(n, dtype=object)
        for r in range(n):
            k = int(rng.integers(2, 7))
            rows[r] = {
                "i": rng.integers(0, 1 << bits, size=k).astype(np.int64),
                "v": rng.normal(size=k).astype(np.float32),
            }
        return DataFrame.from_dict({
            "features": rows,
            "label": rng.integers(0, 2, size=n).astype(np.float64),
        })

    # wall-clock budgets (soak length, freshness budget) scale by the
    # deploy smoke's box-speed factor: a loaded CI box gets more
    # seconds, never a weaker zero-drop/zero-loss gate
    from tools.deploy.smoke import box_speed_factor

    speed = box_speed_factor()
    soak_s = float(
        os.environ.get("MMLSPARK_CHAOS_ONLINE_SOAK_S", "14")
    ) * speed
    reg = fleet.run_registry(host="127.0.0.1", port=0)
    # seed snapshot in its OWN dir (the live publisher prunes its
    # snapshot dir; the restart --load spec must survive all soak long)
    trainer = OnlineTrainer(num_bits=bits, batch=32)
    trainer.step(feedback_chunk())
    seed_examples = trainer.examples  # pre-stream seed, excluded below
    seed_dir = tmp_path / "seed"
    seed_pub = Publisher(
        model="vw-online", snapshot_dir=str(seed_dir),
        worker_urls=["http://127.0.0.1:1/"],  # snapshot only, never reached
    )
    seed_path = seed_pub._write_snapshot(trainer)
    # ARTIFACT mode: the workers never see a snapshot path — the seed
    # (and every live publication below) travels as a content-addressed
    # blob pulled from this process's artifact ingress
    from mmlspark_tpu.serving.artifacts import ArtifactServer, ArtifactStore

    producer = ArtifactStore(str(tmp_path / "artstore"))
    seed_ref = producer.put(seed_path, name=os.path.basename(seed_path))
    art_srv = ArtifactServer(producer)
    # raise the AIMD queue-wait floor with the box speed: under
    # full-suite load scheduler jitter alone can exceed the 2ms default,
    # collapse the admission limit, and shed a 429 the zero-drop gate
    # below would count as a failed request (the template also feeds
    # supervisor restarts and autoscaled spawns, so the floor rides along)
    worker_args = [
        f"--model echo --host 127.0.0.1 --port {p} --heartbeat-s 0.5 "
        f"--admission-min-target-ms {25.0 * speed:g} "
        f"--load vw-online=artifact:vw:{seed_ref.spec}@{art_srv.url}"
        for p in (free_port(), free_port())
    ]
    autoscaler = Autoscaler(
        min_replicas=2, max_replicas=3, scale_out_cooldown_s=5.0,
        scale_in_cooldown_s=10.0, idle_after_s=3600.0,
    )
    gw = ServingGateway(
        registry_url=reg.url, refresh_s=0.2, cooldown_s=0.4,
        evict_after=3, request_timeout_s=5.0,
    )
    ginfo = gw.start()
    charges = [
        charge_from_worker_args(w, reg.url, i)
        for i, w in enumerate(worker_args)
    ]
    sup = FleetSupervisor(
        charges, registry_url=reg.url, probe_s=0.3, backoff_s=0.3,
        stable_s=20.0, autoscaler=autoscaler,
        worker_template=fleet._strip_port(worker_args[0]),
        signals_fn=FleetSignals(
            registry_url=reg.url,
            gateway_url=f"http://127.0.0.1:{ginfo.port}",
        ),
    ).start()
    # disk-backed spill: the soak can assert no FEEDBACK loss (not just
    # no request loss) — every ingested example must end trained,
    # buffered, deliberately shed, or crash-replayable
    stream = FeedbackStream(max_chunks=64, spill_dir=str(tmp_path / "spill"))
    publisher = Publisher(
        model="vw-online", snapshot_dir=str(tmp_path / "snaps"),
        registry_url=reg.url,
        artifact_store=producer, artifact_url=art_srv.url,
    )
    # the freshness budget must absorb the kill-recovery window: a
    # publication that lands while the restarted victim is still cold
    # (fresh process JAX boot + artifact pull + warm) is only servable
    # once that worker finishes warming, which under full-suite load
    # runs well past 15 s on this box — the budget is a timing knob,
    # the green-at-end gate below stays pinned
    loop = OnlineLearningLoop(
        stream, trainer, publisher, publish_every_s=0.5, poll_s=0.05,
        freshness_budget_ms=30_000.0 * speed,
    )
    counters = {"ok": 0, "other": 0, "dropped": 0, "n": 0}
    stop_traffic = threading.Event()
    lock = threading.Lock()
    payload = {"i": [1, 2, 3], "v": [1.0, -0.5, 0.25]}

    def client_loop():
        while not stop_traffic.is_set():
            try:
                status, _ = _post(ginfo.port, "/models/vw-online", payload)
            except Exception:  # noqa: BLE001 — a DROP, the thing we gate on
                status = None
            with lock:
                counters["n"] += 1
                if status == 200:
                    counters["ok"] += 1
                elif status is None:
                    counters["dropped"] += 1
                else:
                    counters["other"] += 1
            time.sleep(0.003)

    def producer_loop():
        while not stop_traffic.is_set():
            try:
                stream.push(feedback_chunk())
            except Exception:  # noqa: BLE001 — bounded buffer shed is fine
                pass
            stop_traffic.wait(0.06)

    try:
        # both workers warm (seed vw-online loaded pre-registration) and
        # routable before traffic starts
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            infos = reg.services("serving")
            if len(infos) >= 2 and all(
                "vw-online" in (i.get("models") or ()) for i in infos
            ) and gw.pool.size() >= 2:
                break
            time.sleep(0.2)
        assert gw.pool.size() >= 2, "workers never became routable"
        loop.start()
        threads = [
            threading.Thread(target=client_loop) for _ in range(2)
        ] + [threading.Thread(target=producer_loop)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        victim = charges[0]
        time.sleep(soak_s * 0.3)
        with lock:
            pre_kill_n = counters["n"]
        publishes_at_kill = publisher.publishes
        victim.proc.kill()  # SIGKILL mid-continuous-training, for real
        while time.monotonic() - t0 < soak_s:
            time.sleep(0.25)
        stop_traffic.set()
        for t in threads:
            t.join(10.0)
        # -- the supervisor restarted the victim WARM -----------------------
        assert victim.restarts >= 1, "supervisor never restarted the victim"
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not victim.alive():
            time.sleep(0.2)
        assert victim.alive()
        # -- publication resumed: >= 3 successful publishes post-kill -------
        assert publisher.publishes - publishes_at_kill >= 3, (
            f"only {publisher.publishes - publishes_at_kill} publications "
            f"after the kill (total {publisher.publishes})"
        )
        # -- zero drops across every flip -----------------------------------
        assert counters["n"] > 100 and pre_kill_n > 10
        assert counters["dropped"] == 0, (
            f"{counters['dropped']}/{counters['n']} requests got no reply"
        )
        assert counters["other"] == 0, (
            f"{counters['other']}/{counters['n']} requests failed across "
            f"{publisher.publishes} publications"
        )
        # -- freshness burn is green at the end -----------------------------
        rep = loop.slo_engine.tick()
        assert rep["online-freshness"]["status"] == "green", rep
        assert publisher.failures == 0 or (
            publisher.publishes >= 3 * publisher.failures
        )
        # -- the autoscaler held the floor ----------------------------------
        assert len(sup.charges) >= 2, "autoscaler shrank below min_replicas"
        # -- no silent feedback loss ----------------------------------------
        loop.stop()  # freeze consumption before the accounting reads
        # every example that entered the stream is accounted for: folded
        # into the model, still buffered, or deliberately shed by the
        # bounded buffer (counted) — nothing vanished
        with stream._cond:
            buffered = sum(len(c) for _, c, _ in stream._buf)
        consumed = trainer.examples - seed_examples
        assert stream.ingested == (
            consumed + buffered + stream.dropped_examples
        ), (stream.ingested, consumed, buffered, stream.dropped_examples)
        # and the backlog is crash-durable: a fresh stream over the same
        # spill replays exactly the unserved examples
        replay = FeedbackStream(spill_dir=str(tmp_path / "spill"))
        assert replay.replayed == buffered, (replay.replayed, buffered)
    finally:
        stop_traffic.set()
        loop.stop()
        stream.close()
        sup.stop()
        gw.stop()
        art_srv.stop()
        reg.stop()
        # same hygiene as the PR-5 soak: this floods process-global obs
        # state (freshness histograms, online counters, exemplars) that
        # later in-process smoke gates must not inherit
        obs.reset()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_no_shared_fs_publisher_killed_host_b_pulls_replica(tmp_path):
    """The shared-filesystem-free acceptance drill (docs/robustness.md
    "Artifact plane"): three real process trees — worker "host A", a
    ``fleet online`` publisher in artifact mode with ``--replicas 1``,
    and later a fresh worker "host B" — share NOTHING but the registry
    and the wire; every process gets its own scratch dir. The publisher
    trains on ingested feedback and publishes; replication-before-ack
    means each snapshot is confirmed durable on host A's artifact
    ingress BEFORE any worker is driven to load it. The publisher is
    then SIGKILLed — its disk is gone, as a dead host's disk would be.
    Host B joins afterward with a bare ``artifact:vw:<name>@<digest>``
    seed spec (NO URL hint, NO filesystem access to anyone): it must
    resolve the digest off the roster, pull the bytes from the
    surviving replica on host A, warm, and register. Host A then drains
    away, leaving host B alone to answer through the gateway. Gates:
    zero dropped and zero failed requests across the publisher kill,
    the host-B join, and the host-A drain; host B's answers carry a
    real VW margin; the invariant checker ends green."""
    import os
    import signal
    import subprocess
    import sys

    from mmlspark_tpu import obs
    from mmlspark_tpu.chaos.invariants import InvariantChecker
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.distributed import ServingGateway

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(repo, ".jax_cache")
    out = str(tmp_path)

    def spawn(role, *args):
        log = open(os.path.join(out, f"{role.replace(' ', '-')}.log"), "w")
        return subprocess.Popen(
            [sys.executable, "-m", "mmlspark_tpu.serving.fleet", *args],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )

    def entry(service, pred=lambda e: True):
        for e in reg.services(service):
            if pred(e):
                return e
        return None

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=3.0)
    gw = ServingGateway(
        registry_url=reg.url, refresh_s=0.2, cooldown_s=0.4,
        evict_after=3, request_timeout_s=5.0,
    )
    ginfo = gw.start()
    procs: dict = {}
    counters = {"ok": 0, "other": 0, "dropped": 0, "n": 0}
    stop_traffic = threading.Event()
    lock = threading.Lock()
    margins: list = []

    def client_loop():
        while not stop_traffic.is_set():
            try:
                status, body = _post(
                    ginfo.port, "/models/vw-online",
                    {"i": [1, 2, 3], "v": [1.0, -0.5, 0.25]},
                )
            except Exception:  # noqa: BLE001 — a DROP, the thing we gate on
                status, body = None, b""
            with lock:
                counters["n"] += 1
                if status == 200:
                    counters["ok"] += 1
                    try:
                        margins.append(json.loads(body)["margin"])
                    except (ValueError, KeyError):
                        pass
                elif status is None:
                    counters["dropped"] += 1
                else:
                    counters["other"] += 1
            time.sleep(0.01)

    traffic = threading.Thread(target=client_loop)
    rng = np.random.default_rng(23)
    try:
        # -- host A: a worker whose scratch dir nobody else can reach ---
        procs["host-a"] = spawn(
            "host-a", "worker", "--registry", reg.url, "--model", "echo",
            "--heartbeat-s", "0.5", "--artifact-dir",
            os.path.join(out, "host-a-art"), "--port", "0",
        )
        # -- the publisher host: artifact mode + replication-before-ack -
        procs["pub"] = spawn(
            "pub", "online", "--registry", reg.url,
            "--model", "vw-online", "--num-bits", "10", "--batch", "32",
            "--publish-every-s", "0.5", "--heartbeat-s", "0.5",
            "--snapshot-dir", os.path.join(out, "pub-snaps"),
            "--artifact-dir", os.path.join(out, "pub-art"),
            "--replicas", "1",
        )
        deadline = time.monotonic() + 120.0
        ingest = None
        while time.monotonic() < deadline and ingest is None:
            ingest = entry("serving-online")
            time.sleep(0.2)
        assert ingest is not None, "publisher never registered"
        rows = [
            {"i": rng.integers(0, 1 << 10, size=3).tolist(),
             "v": rng.normal(size=3).tolist(),
             "label": int(rng.integers(0, 2))}
            for _ in range(64)
        ]
        status, _ = _post(int(ingest["port"]), "/ingest", {"rows": rows})
        assert status == 200
        # replication-before-ack made host A a replica holder BEFORE it
        # was driven to load: its roster entry must advertise the model
        # AND the snapshot blob
        vw_ref = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            e = entry("serving", lambda e: "vw-online" in (
                e.get("models") or ()
            ))
            if e is not None:
                refs = sorted(
                    r for r in (e.get("artifacts") or ())
                    if r.startswith("vw-online")
                )
                if refs:
                    vw_ref = refs[-1]
                    break
            time.sleep(0.2)
        assert vw_ref is not None, (
            "host A never both served and held a replica"
        )
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and gw.pool.size() < 1:
            time.sleep(0.2)
        traffic.start()
        time.sleep(1.0)
        checker = InvariantChecker(
            gateway_url=f"http://127.0.0.1:{ginfo.port}",
            registry_url=reg.url, tolerance=2,
        )
        assert checker.check(final=False) == []
        # -- the publisher host dies: SIGKILL, disk unreachable ---------
        os.kill(procs["pub"].pid, signal.SIGKILL)
        procs["pub"].wait(10.0)
        with lock:
            n_at_kill = counters["n"]
        # -- host B: fresh process tree, bare digest seed spec ----------
        procs["host-b"] = spawn(
            "host-b", "worker", "--registry", reg.url, "--model", "echo",
            "--load", f"vw-online=artifact:vw:{vw_ref}",
            "--heartbeat-s", "0.5", "--artifact-dir",
            os.path.join(out, "host-b-art"), "--port", "0",
        )
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and gw.pool.size() < 2:
            assert procs["host-b"].poll() is None, (
                "host B died instead of pulling the replica"
            )
            time.sleep(0.2)
        assert gw.pool.size() >= 2, "host B never became routable"
        # -- host A drains away: host B alone answers -------------------
        procs["host-a"].terminate()
        procs["host-a"].wait(30.0)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and gw.pool.size() > 1:
            time.sleep(0.2)
        time.sleep(2.0)  # traffic answered by host B alone
        stop_traffic.set()
        traffic.join(10.0)
        with lock:
            snap = dict(counters)
        assert snap["n"] > n_at_kill > 20, snap
        assert snap["dropped"] == 0, (
            f"{snap['dropped']}/{snap['n']} requests got no reply"
        )
        assert snap["other"] == 0, (
            f"{snap['other']}/{snap['n']} requests failed"
        )
        assert margins, "no answer ever carried a VW margin"
        # host B, now the only backend, answers with the real model
        status, body = _post(
            ginfo.port, "/models/vw-online",
            {"i": [1, 2, 3], "v": [1.0, -0.5, 0.25]},
        )
        assert status == 200 and "margin" in json.loads(body)
        assert checker.check(final=True) == []
    finally:
        stop_traffic.set()
        if traffic.is_alive():
            traffic.join(5.0)
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        gw.stop()
        reg.stop()
        obs.reset()


# -- chaos smoke through the deployed-fleet client ---------------------------


@pytest.mark.xdist_group("latency")
def test_smoke_containment_gate_enforces_bursts_waives_scattered():
    """The breaker-must-have-opened requirement applies only to plans
    that guarantee a contiguous failure burst: scattered schedules
    (every-N strides, probability draws) interleave successes that reset
    the streak — chaos the breaker is *right* not to trip on."""
    from tools.deploy import smoke

    before = {"gateway_raw": {}}

    def after(fires, opened):
        return {"gateway_raw": {
            ("mmlspark_gateway_breaker_state",
             (("backend", "10.0.0.1:1"),)): 0.0,
            ("mmlspark_gateway_retry_budget_remaining_ratio", ()): 1.0,
            ("mmlspark_faults_injected_total",
             (("point", "gateway.forward"),)): float(fires),
            ("mmlspark_gateway_breaker_transitions_total",
             (("backend", "10.0.0.1:1"), ("state", "open"))): float(opened),
        }}

    scattered = FaultPlan().on(
        "gateway.forward", error=ConnectionError, every=4
    )
    assert smoke._verify_containment(before, after(8, 0), scattered)
    burst = FaultPlan().on(
        "gateway.forward", error=ConnectionError, at=(0, 1, 2)
    )
    # a contiguous burst with zero opens: the layer slept through chaos
    assert not smoke._verify_containment(before, after(3, 0), burst)
    assert smoke._verify_containment(before, after(3, 1), burst)
    # no plan at all (raw/swap smoke): sane gauges suffice
    assert smoke._verify_containment(before, after(0, 0), None)


def test_smoke_script_fault_plan_chaos_smokes_the_fleet(capsys):
    from mmlspark_tpu.serving import fleet
    from tools.deploy import smoke

    reg = fleet.run_registry(host="127.0.0.1", port=0)
    srv, q, stop = fleet.run_worker(
        reg.url, model="echo", host="127.0.0.1", heartbeat_s=0.5
    )
    # short breaker open period: the worker's breaker trips under the
    # injected forward faults, then half-open-probes closed again well
    # inside the retrying client's backoff schedule
    gw = fleet.run_gateway(
        reg.url, host="127.0.0.1", port=0, breaker_cooldown_s=0.2
    )
    try:
        deadline = time.monotonic() + 5.0
        while gw.pool.size() < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gw.pool.size() == 1
        # in-process smoke: the plan arms THIS process, which also hosts
        # the gateway — the 3 consecutive gateway.forward faults open the
        # single worker's breaker (containment-gate evidence) and the
        # retrying client rides it out
        plan = json.dumps({
            "seed": 0,
            "rules": [
                {"point": "gateway.forward", "error": "ConnectionError",
                 "at": [0, 1, 2]},
            ],
        })
        rc = smoke.main([gw.url, "--n", "12", "--fault-plan", plan])
        out = capsys.readouterr().out
        assert rc == 0, out           # 100% completion under injected chaos
        assert "faults injected" in out
        assert "breaker opened 1 time(s) — ok" in out
    finally:
        from mmlspark_tpu.core import faults

        faults.clear()  # smoke.main installs the plan process-globally
        gw.stop()
        stop.stop()
        q.stop()
        srv.stop()
        reg.stop()
