"""Serving tests: real HTTP against WorkerServer + ServingQuery (the
reference tests serving the same way — live localhost servers)."""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.serving import (
    DriverRegistry,
    ServingQuery,
    WorkerServer,
    make_reply,
    request_to_json,
    serve_transformer,
)


def _post(port: int, path: str, obj, conn=None):
    c = conn or http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    body = json.dumps(obj)
    c.request("POST", path, body=body, headers={"Content-Type": "application/json"})
    r = c.getresponse()
    data = r.read()
    if conn is None:
        c.close()
    return r.status, data


def _echo_handler(reqs):
    out = {}
    for r in reqs:
        obj = request_to_json(r)
        code, body, headers = make_reply({"echo": obj})
        out[r.id] = (code, body, headers)
    return out


def test_worker_server_roundtrip():
    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, _echo_handler).start()
    try:
        status, data = _post(info.port, "/", {"a": 1})
        assert status == 200
        assert json.loads(data) == {"echo": {"a": 1}}
        assert srv.requests_seen == 1
    finally:
        q.stop()
        srv.stop()


def test_keep_alive_and_batching():
    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, _echo_handler, max_batch_size=8).start()
    conn = http.client.HTTPConnection("127.0.0.1", info.port, timeout=10)
    try:
        for i in range(20):
            status, data = _post(info.port, "/", i, conn=conn)
            assert status == 200
            assert json.loads(data) == {"echo": i}
    finally:
        conn.close()
        q.stop()
        srv.stop()


def _run_latency_round() -> dict:
    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, _echo_handler, max_wait_ms=1.0).start()
    errs = []

    def client(k):
        try:
            conn = http.client.HTTPConnection("127.0.0.1", info.port, timeout=10)
            for i in range(25):
                status, data = _post(info.port, "/", {"k": k, "i": i}, conn=conn)
                assert status == 200 and json.loads(data)["echo"]["i"] == i
            conn.close()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    lat = q.latency_quantiles_ms()
    q.stop()
    srv.stop()
    return lat


@pytest.mark.xdist_group("latency")
def test_concurrent_clients_and_latency():
    # pinned to one xdist worker-group: the p50 gate below measures real
    # wall time and must not share a core slice with compile-heavy tests
    #
    # reference claims ~1ms end-to-end on cluster hardware
    # (docs/mmlspark-serving.md:142-146); loopback p50 on this CPU box is
    # under a millisecond, so gate at 2 ms server-side — a regression into
    # multi-ms territory must fail CI, not hide under a loose bound.
    # Best-of-2: a shared CI box under external load measures 2-3x the
    # quiet p50 through no fault of the serving path, and a REAL
    # regression fails both rounds anyway
    lat = _run_latency_round()
    assert lat["n"] >= 100
    if lat["p50"] >= 2.0:
        lat = _run_latency_round()
    assert lat["p50"] < 2.0, lat


def test_handler_error_becomes_500():
    srv = WorkerServer()
    info = srv.start()

    def bad_handler(reqs):
        raise RuntimeError("boom")

    q = ServingQuery(srv, bad_handler).start()
    status, data = _post(info.port, "/", {"x": 1})
    assert status == 500 and b"boom" in data
    assert q.errors == 1
    q.stop()
    srv.stop()


def test_404_off_path():
    srv = WorkerServer(api_path="/api")
    info = srv.start()
    q = ServingQuery(srv, _echo_handler).start()
    status, _ = _post(info.port, "/other", {})
    assert status == 404
    status, _ = _post(info.port, "/apifoo", {})  # shared prefix != on path
    assert status == 404
    status, _ = _post(info.port, "/api", {"ok": 1})
    assert status == 200
    status, _ = _post(info.port, "/api/sub?x=1", {"ok": 1})
    assert status == 200
    q.stop()
    srv.stop()


def test_bad_request_does_not_poison_batch():
    """One malformed concurrent request must 400 alone; well-formed
    requests in the same batch still succeed."""
    w = np.eye(3, dtype=np.float32)
    q = serve_transformer(lambda x: x @ w, "f", "s", max_wait_ms=20.0)
    results = {}

    def client(key, payload):
        results[key] = _post(q.server.port, "/", payload)

    threads = [
        threading.Thread(target=client, args=("good", [1.0, 2.0, 3.0])),
        threading.Thread(target=client, args=("short", [1.0])),
        threading.Thread(target=client, args=("text", "zzz")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["good"][0] == 200
    assert json.loads(results["good"][1]) == [1.0, 2.0, 3.0]
    assert results["short"][0] == 400
    q.stop()
    q.server.stop()


def test_microbatch_epochs_and_commit():
    srv = WorkerServer()
    info = srv.start()
    q = ServingQuery(srv, _echo_handler, mode="microbatch", epoch_interval_ms=30).start()
    try:
        res = []
        for i in range(5):
            res.append(_post(info.port, "/", i))
        assert all(s == 200 for s, _ in res)
        time.sleep(0.1)
        assert srv.epoch >= 1
        assert not srv._history  # committed epochs pruned
    finally:
        q.stop()
        srv.stop()


def test_replay_recovery():
    """Crash-before-reply: requests are unanswered; replay() rehydrates the
    epoch's queue and a recovered dispatcher answers them."""
    srv = WorkerServer()
    info = srv.start()
    results = []

    def client(i):
        results.append(_post(info.port, "/", i))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    # crashing dispatcher: pops the batch, dies before replying
    time.sleep(0.2)
    doomed = srv.get_next_batch(10, timeout_s=1.0)
    assert len(doomed) == 3
    epoch = srv.epoch
    assert srv.replay(epoch) == 3  # unanswered -> rehydrated
    q = ServingQuery(srv, _echo_handler).start()  # recovered dispatcher
    for t in threads:
        t.join(10.0)
    assert sorted(json.loads(d)["echo"] for s, d in results) == [0, 1, 2]
    assert all(s == 200 for s, _ in results)
    replayed = [r for r in doomed]
    assert all(r.attempt == 1 for r in replayed)
    q.stop()
    srv.stop()


def test_reply_idempotent():
    srv = WorkerServer()
    info = srv.start()
    got = {}

    def handler(reqs):
        got["ids"] = [r.id for r in reqs]
        return {r.id: (200, b"first", {}) for r in reqs}

    q = ServingQuery(srv, handler).start()
    status, data = _post(info.port, "/", 1)
    assert (status, data) == (200, b"first")
    assert srv.reply_to(got["ids"][0], b"second") is False  # routing removed
    q.stop()
    srv.stop()


def test_serve_transformer_model():
    """End-to-end: fitted model served over HTTP with fixed-bucket batching
    (the ImageFeaturizer/CNTKModel serving scenario at unit scale)."""
    import jax
    import jax.numpy as jnp

    w = np.array([[1.0, 2.0], [3.0, 4.0], [0.5, -0.5]], np.float32)

    @jax.jit
    def model(x):
        return x @ w

    q = serve_transformer(model, "features", "scores", max_wait_ms=1.0)
    try:
        port = q.server.port
        status, data = _post(port, "/", [1.0, 0.0, 2.0])
        assert status == 200
        np.testing.assert_allclose(json.loads(data), [2.0, 1.0], atol=1e-5)
        # a second, different batch size hits another bucket fine
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        for i in range(5):
            status, data = _post(port, "/", [float(i), 1.0, 0.0], conn=conn)
            np.testing.assert_allclose(
                json.loads(data), [i + 3.0, 2 * i + 4.0], atol=1e-4
            )
        conn.close()
        status, data = _post(port, "/", "not-a-vector-json{{{")
        # invalid body for the model -> 400 or 500, never a hang
        assert status in (400, 500)
    finally:
        q.stop()
        q.server.stop()


def test_serve_dataframe_transformer():
    from mmlspark_tpu.stages.basic import UDFTransformer

    t = UDFTransformer(input_col="x", output_col="y").set(
        vector_udf=lambda col: np.asarray(col) * 10
    )
    q = serve_transformer(t, "x", "y")
    try:
        status, data = _post(q.server.port, "/", 4.0)
        assert status == 200
        assert json.loads(data) == 40.0
    finally:
        q.stop()
        q.server.stop()


def test_driver_registry():
    reg = DriverRegistry()
    srv = WorkerServer(name="model-a")
    info = srv.start()
    try:
        assert DriverRegistry.register(reg.url, info)
        services = reg.services("model-a")
        assert len(services) == 1
        assert services[0]["port"] == info.port
        # client can reach the advertised worker
        q = ServingQuery(srv, _echo_handler).start()
        s = services[0]
        status, _ = _post(s["port"], s["path"], {"via": "registry"})
        assert status == 200
        q.stop()
    finally:
        srv.stop()
        reg.stop()


def test_worker_server_forwarding_option(monkeypatch):
    """forwarding= opens an ssh -R tunnel for the bound port and reports
    the public endpoint (HTTPSourceV2.scala:657-665 parity). The ssh spawn
    is faked: the command/port plumbing is what's under test."""
    import mmlspark_tpu.io.port_forwarding as pf

    started = {}

    class FakeProc:
        def poll(self):
            return None

        def terminate(self):
            started["stopped"] = True

        def wait(self, timeout=None):
            return 0

        import io as _io

        stderr = _io.BytesIO()

    def fake_popen(cmd, **kw):
        started["cmd"] = cmd
        return FakeProc()

    monkeypatch.setattr(pf.subprocess, "Popen", fake_popen)
    srv = WorkerServer(
        forwarding={"remote_host": "gateway.example", "remote_port": 9000}
    )
    info = srv.start()
    try:
        assert info.forwarded_host == "gateway.example"
        assert info.forwarded_port == 9000
        assert f"9000:127.0.0.1:{info.port}" in " ".join(started["cmd"])
    finally:
        srv.stop()
    assert started.get("stopped")


# -- distributed mode: N workers behind one gateway --------------------------


def _worker_with_handler(tag):
    """A backend WorkerServer+ServingQuery replying with its tag."""
    srv = WorkerServer()
    info = srv.start()

    def handler(reqs):
        out = {}
        for r in reqs:
            try:
                v = json.loads(r.body)["x"]
            except (ValueError, KeyError):
                out[r.id] = (400, b"bad body", {})
                continue
            out[r.id] = (
                200,
                json.dumps({"y": v * 2, "worker": tag}).encode(),
                {"Content-Type": "application/json"},
            )
        return out

    q = ServingQuery(srv, handler, max_wait_ms=0).start()
    return srv, q, info


def test_gateway_round_robins_over_workers():
    from mmlspark_tpu.serving import ServingGateway

    backends = [_worker_with_handler(f"w{i}") for i in range(3)]
    gw = ServingGateway(workers=[b[2] for b in backends])
    ginfo = gw.start()
    try:
        seen = set()
        for i in range(30):
            status, data = _post(ginfo.port, "/", {"x": i})
            assert status == 200
            d = json.loads(data)
            assert d["y"] == i * 2
            seen.add(d["worker"])
        assert seen == {"w0", "w1", "w2"}  # all workers share the load
    finally:
        gw.stop()
        for srv, q, _ in backends:
            q.stop()
            srv.stop()


def test_gateway_survives_worker_death_zero_lost():
    """Kill one worker mid-stream: every accepted request still gets a
    correct reply from a DIFFERENT worker (the cross-worker replay of the
    reference's uncommitted-epoch recovery, DistributedHTTPSource)."""
    from mmlspark_tpu.serving import ServingGateway

    backends = [_worker_with_handler(f"w{i}") for i in range(3)]
    gw = ServingGateway(workers=[b[2] for b in backends], request_timeout_s=3.0)
    ginfo = gw.start()
    errs = []
    answers = {}
    lock = threading.Lock()

    def client(k):
        try:
            for i in range(40):
                x = k * 1000 + i
                status, data = _post(ginfo.port, "/", {"x": x})
                assert status == 200, (status, data)
                d = json.loads(data)
                assert d["y"] == x * 2
                with lock:
                    answers[x] = d["worker"]
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    # kill worker 0 while traffic is in flight
    time.sleep(0.05)
    backends[0][1].stop()
    backends[0][0].stop()
    for t in threads:
        t.join()
    gw.stop()
    for srv, q, _ in backends[1:]:
        q.stop()
        srv.stop()
    assert not errs, errs[:3]
    assert len(answers) == 160  # zero lost requests
    survivors = {w for w in answers.values()}
    assert {"w1", "w2"} <= survivors  # the load moved to live workers


def test_gateway_discovers_workers_from_registry():
    from mmlspark_tpu.serving import DriverRegistry, ServingGateway

    reg = DriverRegistry()
    backends = [_worker_with_handler(f"r{i}") for i in range(2)]
    try:
        for _, _, info in backends:
            assert DriverRegistry.register(reg.url, info)
        gw = ServingGateway(registry_url=reg.url, refresh_s=0.2)
        ginfo = gw.start()
        try:
            assert gw.pool.size() == 2
            status, data = _post(ginfo.port, "/", {"x": 21})
            assert status == 200 and json.loads(data)["y"] == 42
            # a THIRD worker registering later joins without a restart
            late = _worker_with_handler("late")
            backends.append(late)
            assert DriverRegistry.register(reg.url, late[2])
            deadline = time.monotonic() + 5.0
            while gw.pool.size() < 3 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert gw.pool.size() == 3
            seen = set()
            for i in range(30):
                _, data = _post(ginfo.port, "/", {"x": i})
                seen.add(json.loads(data)["worker"])
            assert "late" in seen
        finally:
            gw.stop()
    finally:
        reg.stop()
        for srv, q, _ in backends:
            q.stop()
            srv.stop()


def test_gateway_all_workers_down_503():
    from mmlspark_tpu.serving import ServingGateway

    srv, q, info = _worker_with_handler("only")
    gw = ServingGateway(workers=[info], request_timeout_s=1.0, max_attempts=2)
    ginfo = gw.start()
    try:
        status, _ = _post(ginfo.port, "/", {"x": 1})
        assert status == 200
        q.stop()
        srv.stop()
        status, data = _post(ginfo.port, "/", {"x": 2})
        assert status == 503
        assert b"no live" in data
    finally:
        gw.stop()


def test_static_pool_worker_recovers_after_cooldown():
    """A static (no-registry) pool must let a briefly-down worker rejoin:
    eviction is disabled there, cooldown alone rate-limits attempts."""
    from mmlspark_tpu.serving import ServingGateway

    srv, q, info = _worker_with_handler("w")
    gw = ServingGateway(
        workers=[info], request_timeout_s=1.0, cooldown_s=0.3, max_attempts=2
    )
    ginfo = gw.start()
    try:
        assert _post(ginfo.port, "/", {"x": 1})[0] == 200
        port = info.port
        q.stop()
        srv.stop()
        # many failures while down — would trip any eviction threshold
        for _ in range(5):
            assert _post(ginfo.port, "/", {"x": 2})[0] == 503
        # worker comes back on the SAME port (static deployments pin ports)
        srv2 = WorkerServer(port=port)
        srv2.start()
        q2 = ServingQuery(srv2, lambda reqs: {
            r.id: (200, b'{"y": 42}', {}) for r in reqs
        }, max_wait_ms=0).start()
        time.sleep(0.4)  # let the cooldown lapse
        try:
            status, data = _post(ginfo.port, "/", {"x": 3})
            assert status == 200 and json.loads(data)["y"] == 42
        finally:
            q2.stop()
            srv2.stop()
    finally:
        gw.stop()


def test_registry_roster_is_bounded():
    from mmlspark_tpu.serving import DriverRegistry, ServiceInfo

    reg = DriverRegistry(max_entries_per_service=5)
    try:
        for p in range(20):  # crash-looping worker on ephemeral ports
            DriverRegistry.register(
                reg.url, ServiceInfo("serving", "127.0.0.1", 40000 + p)
            )
        roster = reg.services("serving")
        assert len(roster) == 5
        # newest registrations survive
        assert {e["port"] for e in roster} == set(range(40015, 40020))
    finally:
        reg.stop()


def test_fleet_roles_bring_up_and_smoke():
    """The deployment recipe's code path (tools/deploy): fleet.py roles
    bring up registry + 2 workers + gateway; the smoke client round-trips
    through the gateway and both workers serve."""
    from mmlspark_tpu.serving import fleet

    reg = fleet.run_registry(host="127.0.0.1", port=0)
    workers = [
        fleet.run_worker(reg.url, model="echo", host="127.0.0.1",
                         heartbeat_s=0.5)
        for _ in range(2)
    ]
    gw = fleet.run_gateway(reg.url, host="127.0.0.1", port=0)
    try:
        deadline = time.monotonic() + 5.0
        while gw.pool.size() < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gw.pool.size() == 2
        for i in range(20):
            status, data = _post(
                int(gw.url.rsplit(":", 1)[1].rstrip("/")), "/", {"x": i}
            )
            assert status == 200
            assert json.loads(data)["echo"]["x"] == i
    finally:
        gw.stop()
        for srv, q, stop in workers:
            stop.set()
            q.stop()
            srv.stop()
        reg.stop()


def test_fleet_worker_heartbeat_survives_registry_restart():
    """A restarted registry re-learns live workers from heartbeats — the
    operational property the deployment doc promises."""
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.registry import DriverRegistry

    reg = fleet.run_registry(host="127.0.0.1", port=0)
    port = int(reg.url.rsplit(":", 1)[1].rstrip("/"))
    srv, q, stop = fleet.run_worker(
        reg.url, model="echo", host="127.0.0.1", heartbeat_s=0.2
    )
    try:
        time.sleep(0.4)
        assert reg.services("serving")
        reg.stop()
        reg2 = None
        for _ in range(50):  # the freed port may linger in TIME_WAIT
            try:
                reg2 = DriverRegistry(host="127.0.0.1", port=port)
                break
            except OSError:
                time.sleep(0.1)
        assert reg2 is not None, "could not rebind registry port"
        try:
            deadline = time.monotonic() + 5.0
            while not reg2.services("serving") and time.monotonic() < deadline:
                time.sleep(0.05)
            assert reg2.services("serving"), "heartbeat did not re-register"
        finally:
            reg2.stop()
    finally:
        stop.set()
        q.stop()
        srv.stop()


def test_gateway_conn_cache_prunes_departed_backends():
    """Registry churn must not leak pooled connections: when a backend
    leaves the pool, the next dispatch closes and forgets its cached
    keep-alive connection (per dispatcher thread)."""
    from mmlspark_tpu.serving import ServingGateway

    from mmlspark_tpu.serving.distributed import BackendPool

    s1, q1, i1 = _worker_with_handler("p1")
    s2, q2, i2 = _worker_with_handler("p2")
    gw = ServingGateway(workers=[i1, i2], request_timeout_s=2.0)
    try:
        b1, b2 = gw.pool.members()
        # registry-style pool: no static members, so refresh() can drop
        # a departed backend (static pools never shrink by design)
        gw._pool = BackendPool()
        gw.pool.refresh([b1, b2])
        # populate this thread's cache with live connections to both
        c1, cached1 = gw._conn_for(b1)
        c2, _ = gw._conn_for(b2)
        assert not cached1
        c1.send(
            b"POST / HTTP/1.1\r\nContent-Length: 8\r\n\r\n" + b'{"x": 1}'
        )
        assert c1.read_response().body
        assert set(gw._conns.by_backend) == {
            (b1.host, b1.port), (b2.host, b2.port)
        }
        # b2 leaves the roster; next dispatch to b1 prunes b2's conn
        gw.pool.refresh([b1])
        c1b, cached = gw._conn_for(b1)
        assert cached and c1b is c1  # live entry survives, still pooled
        assert set(gw._conns.by_backend) == {(b1.host, b1.port)}
        assert c2._closed  # pruned connection was closed
    finally:
        for s, q in ((s1, q1), (s2, q2)):
            q.stop()
            s.stop()


# -- BackendPool eviction/revival edge cases ---------------------------------


def _pool_backend(port):
    from mmlspark_tpu.serving.distributed import Backend

    return Backend(host="10.0.0.1", port=port)


def test_pool_breaker_opens_and_closes_on_reregistration():
    """A dead worker's roster entry keeps its registration timestamp; a
    refresh carrying the SAME stamp must not close its open breaker —
    only an actual re-registration (newer stamp, i.e. a new process)
    resets it immediately."""
    from mmlspark_tpu.serving.distributed import BackendPool

    b = _pool_backend(9001)
    pool = BackendPool(cooldown_s=60.0, evict_after=3)
    pool.refresh([b], stamps={b: 100.0})
    for _ in range(3):
        pool.report_failure(b)
    # breaker OPEN: skipped entirely, not even as a cooled-down fallback
    assert pool.breaker_states() == {"10.0.0.1:9001": "open"}
    assert pool.size() == 0 and pool.next() is None
    pool.refresh([b], stamps={b: 100.0})  # stale roster echo: same stamp
    assert pool.size() == 0 and pool.next() is None
    pool.refresh([b], stamps={b: 101.0})  # real re-registration: new stamp
    assert pool.breaker_states() == {"10.0.0.1:9001": "closed"}
    assert pool.size() == 1 and pool.next() == b


def test_pool_static_backend_never_evicted():
    """Static backends (constructor list) only cool down: with no registry
    to revive them, eviction would lose a briefly-down worker forever —
    both at evict_after=0 (eviction off) and above any threshold."""
    from mmlspark_tpu.serving.distributed import BackendPool

    for evict_after in (0, 3):
        b = _pool_backend(9002)
        pool = BackendPool([b], cooldown_s=10.0, evict_after=evict_after)
        for _ in range(10):  # far past any eviction threshold
            pool.report_failure(b)
        assert pool.size() == 1
        # cooled down, but still reachable via the fallback (it may have
        # recovered — better one retry than a refused request)
        assert pool.next() == b
        pool.refresh([], stamps={})  # roster refresh cannot drop it either
        assert pool.size() == 1


def test_pool_cooldown_fallback_when_all_backends_cooling():
    """With every backend cooling down, next() must still hand out one of
    them (round-robin would otherwise refuse all traffic during a blip),
    and exclusions are honored before the fallback."""
    from mmlspark_tpu.serving.distributed import BackendPool

    b1, b2 = _pool_backend(9003), _pool_backend(9004)
    pool = BackendPool([b1, b2], cooldown_s=60.0, evict_after=0)
    pool.report_failure(b1)
    pool.report_failure(b2)
    got = pool.next()
    assert got in (b1, b2)
    other = b2 if got == b1 else b1
    assert pool.next(exclude={got}) == other
    assert pool.next(exclude={b1, b2}) is None
    # recovery clears the cooldown entirely
    pool.report_ok(b1)
    assert pool.next(exclude={b2}) == b1
