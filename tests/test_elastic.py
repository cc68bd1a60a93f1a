"""Elastic self-healing distributed training (parallel/elastic.py).

Unit layer: partition assignment invariance, straggler policy, the
registry-stamped generation protocol, TCP allreduce + loss detection,
and the world-1 bit-identity anchor. Chaos layer (subprocess gangs over
a real registry): SIGKILL one training host mid-round — survivors
detect, re-shard, resume, and the final booster is bit-identical to a
fresh shrunk-world run from the same checkpoint; a supervisor-restarted
host grows back in at the next checkpoint boundary.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.faults import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env() -> dict:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    return env


# -- partition assignment -----------------------------------------------------


def test_partition_assignment_contiguous_and_world_invariant():
    """Members take contiguous partition runs in sorted order, so the
    concatenation of member rows is the global dataset in original order
    at EVERY world size — the bit-identity contract's foundation."""
    from mmlspark_tpu.parallel.elastic import (
        assign_partitions,
        member_row_slice,
        partition_bounds,
    )

    bounds = partition_bounds(1003, 8)
    assert bounds[0][0] == 0 and bounds[-1][1] == 1003
    assert all(b[0] == a[1] for a, b in zip(bounds, bounds[1:]))
    for members in (["a"], ["a", "b"], ["c", "a", "b"], list("abcdefgh")):
        asg = assign_partitions(8, members)
        flat = [p for m in sorted(members) for p in asg[m]]
        assert flat == list(range(8))  # every partition exactly once
        slices = [member_row_slice(1003, 8, members, m)
                  for m in sorted(members)]
        assert slices[0][0] == 0 and slices[-1][1] == 1003
        assert all(s[1] == t[0] for s, t in zip(slices, slices[1:]))


def test_straggler_tracker_flags_sustained_slow_only():
    from mmlspark_tpu.parallel.elastic import StragglerTracker

    t = StragglerTracker(factor=3.0, sustain=3)
    fast = {"a": 0.1, "b": 0.1, "c": 0.1}
    assert t.observe(fast) == []
    slow = {"a": 0.1, "b": 0.1, "c": 0.9}
    assert t.observe(slow) == []          # 1st slow observation
    assert t.observe(slow) == []          # 2nd
    assert t.observe(slow) == ["c"]       # sustained -> flagged
    assert t.observe(fast) == []          # recovered -> streak reset
    assert t.observe(slow) == []          # must re-sustain from scratch


# -- generation protocol over the registry ------------------------------------


@pytest.fixture()
def gang_registry():
    from mmlspark_tpu.serving import fleet

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.0)
    yield reg
    reg.stop()


def test_generation_record_is_registry_stamped_latest_wins(gang_registry):
    from mmlspark_tpu.parallel.elastic import GangMember, Generation

    m = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    try:
        m.commit_generation(Generation(gen=1, members=["a", "b"]))
        m.commit_generation(Generation(
            gen=2, members=["a"], reason="lost", resume_round=6,
        ))
        g = m.read_generation()
        assert g.gen == 2 and g.members == ["a"] and g.reason == "lost"
        assert g.resume_round == 6 and g.committer == "a"
        assert g.stamp > 0  # the REGISTRY stamped it, not the member
    finally:
        m.close()


def test_gang_members_form_generation_and_detect_loss(gang_registry):
    """Two members rendezvous through the registry (lowest name commits
    generation 1); when one's heartbeats stop, the survivor's next round
    boundary raises HostLostError naming exactly the dead host."""
    from mmlspark_tpu.parallel.elastic import (
        GangContext,
        GangMember,
        HostLostError,
        WorldChangedError,
        Generation,
    )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        gens = {}

        def await_b():
            gens["b"] = b.await_generation(2, timeout_s=20.0)

        t = threading.Thread(target=await_b)
        t.start()
        gens["a"] = a.await_generation(2, timeout_s=20.0)
        t.join(20.0)
        assert gens["a"].gen == 1 and gens["a"].members == ["a", "b"]
        assert gens["b"].gen == 1
        ros = a.roster()
        assert set(ros) == {"a", "b"} and "ewma_ms" in ros["a"]
        # b dies (clean close deregisters; a crash would TTL out instead)
        b.close()
        deadline = time.monotonic() + 10.0
        while "b" in (a.roster() or {}) and time.monotonic() < deadline:
            time.sleep(0.1)
        gang = GangContext(a, gens["a"], n_rows=100, n_partitions=4)
        # inside the loss grace, absence is not yet death (debounces a
        # freshly-restarted registry's empty roster)
        gang.on_round(0)
        time.sleep(gang.loss_grace_s + 0.2)
        with pytest.raises(HostLostError) as ei:
            gang.on_round(1)
        assert ei.value.lost == ["b"]
        # a newer generation committed by someone else aborts too (all
        # of THIS gang's members alive, so loss detection stays quiet)
        gang2 = GangContext(
            a, Generation(gen=2, members=["a"]), n_rows=100, n_partitions=4
        )
        a.commit_generation(Generation(gen=5, members=["a"]))
        with pytest.raises(WorldChangedError):
            gang2.on_round(1)
    finally:
        a.close()
        b.close()


def test_forced_detect_and_reshard_commit_retries_through_fault(
    gang_registry, tmp_path
):
    """Fault point ``elastic.detect``: a payload declares a named member
    lost without killing anything; ``elastic.reshard``: an injected
    commit refusal is retried until the plan relents."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig
    from mmlspark_tpu.parallel.elastic import (
        ElasticTrainer,
        GangContext,
        GangMember,
        Generation,
        HostLostError,
    )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        gen = Generation(gen=1, members=["a", "b"])
        a.adopt(gen)
        gang = GangContext(a, gen, n_rows=100, n_partitions=4)
        plan = FaultPlan().on("elastic.detect", payload="b", at=(0,))
        with plan.armed():
            with pytest.raises(HostLostError) as ei:
                gang.on_round(0)
        assert ei.value.lost == ["b"]
        # the reshard commit: first attempt refused, second lands
        x = np.zeros((100, 4), np.float32)
        trainer = ElasticTrainer(
            gang_registry.url, "a", x, np.zeros(100), TrainConfig(),
            str(tmp_path / "ck"), n_partitions=4, heartbeat_s=0.05,
        )
        plan2 = FaultPlan().on(
            "elastic.reshard", error=ConnectionError, max_fires=1
        )
        with plan2.armed():
            trainer._reshard(a, gen, ei.value)
        assert len(plan2.fires()) == 1  # refused once, then committed
        g2 = a.read_generation()
        assert g2.gen == 2 and g2.members == ["a"] and g2.reason == "lost"
        assert trainer.status["reshards"] == 1
    finally:
        a.close()
        b.close()


# -- the TCP allreduce --------------------------------------------------------


def test_tcp_reducer_allreduce_sums_and_detects_loss(gang_registry):
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        HostLostError,
        TcpReducer,
    )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        time.sleep(0.3)  # both registered
        gen = Generation(gen=1, members=["a", "b"])
        ra = TcpReducer(a, gen, timeout_s=20.0)
        rb = TcpReducer(b, gen, timeout_s=20.0)
        out = {}

        def side(red, arrs, key):
            got = [red.allreduce(x) for x in arrs]
            out[key] = got

        xa = [np.arange(6, dtype=np.float32).reshape(2, 3),
              np.ones(4, np.float64)]
        xb = [np.full((2, 3), 10.0, np.float32),
              np.full(4, 2.0, np.float64)]
        t = threading.Thread(target=side, args=(rb, xb, "b"))
        t.start()
        side(ra, xa, "a")
        t.join(20.0)
        for got_a, got_b, ea, eb in zip(out["a"], out["b"], xa, xb):
            np.testing.assert_array_equal(got_a, got_b)
            np.testing.assert_allclose(got_a, ea + eb)
            assert got_a.dtype == ea.dtype and got_a.shape == ea.shape
        # b vanishes: a's next allreduce fails naming it once the TTL
        # lapses, instead of hanging forever (the socket-allreduce fix)
        rb.close()
        b.close()
        with pytest.raises(HostLostError) as ei:
            ra.allreduce(np.ones(2))
        assert ei.value.lost == ["b"]
        ra.close()
    finally:
        a.close()
        b.close()


# -- world-1 anchor: the gang path IS the plain path --------------------------


def test_world1_elastic_training_bit_identical_to_plain_train(
    gang_registry, tmp_path
):
    """A single-member gang must train bit-identically to plain
    unsharded ``train()`` — the anchor that makes the shrunk-world
    comparison meaningful."""
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train
    from mmlspark_tpu.parallel.elastic import (
        ElasticTrainer,
        load_training_data,
    )

    x, y = load_training_data("synth:400x6:7")
    cfg = TrainConfig(
        objective="binary", num_iterations=4, num_leaves=7,
        min_data_in_leaf=5, seed=3,
    )
    booster = ElasticTrainer(
        gang_registry.url, "solo", x, y, cfg, str(tmp_path / "ck"),
        n_partitions=4, world_size=1, heartbeat_s=0.2,
        status_file=str(tmp_path / "status.json"),
    ).run()
    ref = train(x, y, cfg, shard=False)
    assert booster.to_model_string() == ref.to_model_string()
    status = json.load(open(tmp_path / "status.json"))
    assert status["done"] and status["gen"] == 1


def test_snapshot_checkpoint_freezes_latest(tmp_path):
    from mmlspark_tpu.models.gbdt.booster import Booster
    from mmlspark_tpu.models.gbdt.checkpoint import (
        TrainCheckpoint,
        load_checkpoint,
        save_checkpoint,
    )
    from mmlspark_tpu.parallel.elastic import snapshot_checkpoint

    d = str(tmp_path)
    assert snapshot_checkpoint(d, 2) == (None, 0)  # nothing yet
    rng = np.random.default_rng(0)
    save_checkpoint(d, TrainCheckpoint(
        round=6, booster=Booster(), scores=np.zeros(4, np.float32),
        bag=None, rng_state=rng.bit_generator.state, fingerprint="fp",
    ))
    snap, rnd = snapshot_checkpoint(d, 2)
    assert rnd == 6 and os.path.isdir(snap)
    # later checkpoints do not disturb the frozen snapshot
    save_checkpoint(d, TrainCheckpoint(
        round=8, booster=Booster(), scores=np.ones(4, np.float32),
        bag=None, rng_state=rng.bit_generator.state, fingerprint="fp",
    ))
    loaded = load_checkpoint(snap)
    assert loaded.round == 6 and float(loaded.scores.sum()) == 0.0


def test_charge_from_train_args_builds_train_argv():
    from mmlspark_tpu.serving.supervisor import charge_from_train_args

    c = charge_from_train_args(
        "--name hostA --data synth:100x4:0 --ckpt-dir /tmp/ck",
        "http://reg:9090/", 0,
    )
    assert c.argv[1:5] == ["-m", "mmlspark_tpu.serving.fleet", "train",
                           "--registry"]
    assert "--name" in c.argv and "hostA" in c.argv
    assert c.health_url is None          # trainers have no HTTP ingress
    assert c.name == "train-0:hostA"


# -- chaos: the acceptance scenario -------------------------------------------


_TRAIN_ARGS = [
    "--data", "synth:600x8:5", "--partitions", "4",
    "--num-iterations", "12", "--num-leaves", "7",
    "--min-data-in-leaf", "5", "--seed", "3",
    "--checkpoint-every", "2", "--heartbeat-s", "0.25",
]


def _spawn_trainer(
    reg_url: str, name: str, ckpt: str, out_dir: str, world: int,
    extra: list = (), fault: str = None, train_args: list = None,
):
    argv = [sys.executable, "-m", "mmlspark_tpu.serving.fleet"]
    if fault:
        argv += ["--fault-plan", fault]
    argv += [
        "train", "--registry", reg_url, "--name", name,
        "--ckpt-dir", ckpt, "--world-size", str(world),
        "--out-model", os.path.join(out_dir, f"model-{name}.txt"),
        "--status-file", os.path.join(out_dir, f"status-{name}.json"),
        *(train_args if train_args is not None else _TRAIN_ARGS),
        *extra,
    ]
    return subprocess.Popen(
        argv, env=_child_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )


def _status(out_dir: str, name: str) -> dict:
    try:
        with open(os.path.join(out_dir, f"status-{name}.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_elastic_host_loss_mid_round_resumes_bit_identical(tmp_path):
    """The acceptance scenario: a 2-host gang trains over the TCP
    histogram allreduce; one host is SIGKILLed MID-ROUND (an injected
    ``gbdt.round`` stall parks it inside round 6 while the survivor
    blocks in the round's allreduce). The survivor must detect the loss
    (TTL expiry), abort the in-flight round (through an armed
    ``train.round_abort`` point), re-shard to world 1, resume from the
    snapshotted checkpoint, and finish — and its final booster must be
    BIT-IDENTICAL to a fresh world-1 run started from that same
    snapshot. Recovery timings land in the status file (the bench's
    ``elastic`` segment records the same numbers)."""
    from mmlspark_tpu.serving import fleet

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    out = str(tmp_path)
    ck = os.path.join(out, "ck")
    try:
        # victim stalls ENTERING round 6 (a chunk boundary), so the
        # survivor is wedged inside round 6's first gang allreduce when
        # the SIGKILL lands — a genuine mid-round loss
        victim_fault = json.dumps({
            "rules": [{"point": "gbdt.round", "at": [6], "delay_s": 600}],
        })
        # the survivor's abort path runs through an armed
        # train.round_abort (delay: a slow abort must still recover)
        survivor_fault = json.dumps({
            "rules": [
                {"point": "train.round_abort", "delay_s": 0.1,
                 "max_fires": 1},
            ],
        })
        surv = _spawn_trainer(
            reg.url, "a", ck, out, world=2, extra=["--no-growback"],
            fault=survivor_fault,
        )
        vict = _spawn_trainer(
            reg.url, "b", ck, out, world=2, extra=["--no-growback"],
            fault=victim_fault,
        )
        # wait for the round-6 checkpoint to commit, then give the
        # survivor a beat to enter round 6's allreduce before the kill
        latest = os.path.join(ck, "LATEST")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                with open(latest) as f:
                    if f.read().strip() == "round-0000006":
                        break
            except OSError:
                pass
            assert vict.poll() is None, vict.communicate()[1][-2000:]
            time.sleep(0.1)
        time.sleep(0.6)
        vict.kill()
        out_a, err_a = surv.communicate(timeout=180)
        assert surv.returncode == 0, err_a[-3000:]
        sa = _status(out, "a")
        assert sa["done"] and sa["reshards"] == 1
        assert sa["members"] == ["a"] and sa["gen"] == 2
        assert sa["reshard_reasons"] == ["lost"]
        assert sa["resume_round"] == 6
        assert sa["snapshot"] and os.path.isdir(sa["snapshot"])
        # recovery timings recorded (the bench reads these)
        assert sa["detect_latency_s"] > 0
        assert sa["reshard_to_first_round_s"] > 0
        # -- the hard contract: fresh world-1 run from the SAME snapshot
        fresh = _spawn_trainer(
            reg.url, "c", os.path.join(out, "ck-fresh"), out, world=1,
            extra=["--resume-from", sa["snapshot"]],
        )
        out_c, err_c = fresh.communicate(timeout=180)
        assert fresh.returncode == 0, err_c[-3000:]
        with open(os.path.join(out, "model-a.txt")) as f:
            survivor_model = f.read()
        with open(os.path.join(out, "model-c.txt")) as f:
            fresh_model = f.read()
        assert survivor_model == fresh_model, (
            "survivor's resumed booster != fresh shrunk-world run from "
            "the same checkpoint"
        )
    finally:
        reg.stop()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_elastic_per_host_ckpt_dirs_artifact_pull_growback(tmp_path):
    """The no-shared-filesystem acceptance gate (docs/artifacts.md):
    a 2-host gang where every host owns a PRIVATE checkpoint dir
    (``--artifact-dir`` mode — every member writes its own checkpoints,
    reshard snapshots replicate as content-addressed artifacts). One
    host is SIGKILLed mid-run under a live supervisor: the survivor
    re-shards from ITS OWN disk, the restarted victim is grown back at
    the next checkpoint boundary and must PULL the agreed resume
    snapshot over HTTP (hash-verified) because the generation record
    names a path on the survivor's disk, not its own. Both hosts finish
    with identical boosters — and that booster is byte-identical to a
    plain shared-dir/solo run of the same data+config, the invariance
    the whole artifact plane must preserve."""
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.supervisor import (
        FleetSupervisor,
        charge_from_train_args,
    )

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    out = str(tmp_path)
    # slow every chunk so the run comfortably outlives the restart
    fault = json.dumps({"rules": [{"point": "gbdt.round", "delay_s": 0.35}]})
    env = _child_env()

    def spawn(argv):
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        )

    def args(name):
        # PER-HOST dirs: ck-a vs ck-b, art-a vs art-b — nothing shared
        return (
            f"--name {name} --data synth:600x8:5 --partitions 4 "
            f"--world-size 2 --ckpt-dir {out}/ck-{name} "
            f"--artifact-dir {out}/art-{name} --num-iterations 40 "
            f"--num-leaves 7 --min-data-in-leaf 5 --seed 3 "
            f"--checkpoint-every 2 --heartbeat-s 0.25 "
            f"--out-model {out}/model-{name}.txt "
            f"--status-file {out}/status-{name}.json"
        )

    charges = [
        charge_from_train_args(args(n), reg.url, i)
        for i, n in enumerate("ab")
    ]
    for c in charges:  # arm the chunk-slowdown plan in every trainer
        c.argv = c.argv[:3] + ["--fault-plan", fault] + c.argv[3:]
    sup = FleetSupervisor(
        charges, registry_url=reg.url, probe_s=0.3, backoff_s=0.3,
        stable_s=30.0, spawn=spawn,
    ).start()
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if _status(out, "a").get("gen") == 1:
                break
            time.sleep(0.2)
        assert _status(out, "a").get("gen") == 1, "gang never formed"
        time.sleep(2.0)  # into the run, past the first checkpoints
        victim = charges[1]
        victim.proc.kill()
        deadline = time.monotonic() + 150.0
        while time.monotonic() < deadline:
            sa, sb = _status(out, "a"), _status(out, "b")
            if sa.get("done") and sb.get("done"):
                break
            time.sleep(0.4)
        sa, sb = _status(out, "a"), _status(out, "b")
        assert sa.get("done") and sb.get("done"), (sa, sb)
        assert victim.restarts >= 1, "supervisor never restarted the victim"
        # survivor shrank from its OWN dir, victim grew back
        assert sa["reshard_reasons"][:1] == ["lost"]
        assert sa["gen"] >= 3 and sorted(sa["members"]) == ["a", "b"]
        # the victim's resume point came over HTTP: the generation
        # record named a snapshot on the SURVIVOR's disk, so the victim
        # had to pull the content-addressed bytes from a peer
        assert sb.get("artifact_fetches", 0) >= 1, (
            "victim never pulled a checkpoint artifact", sb,
        )
        with open(os.path.join(out, "model-a.txt")) as f:
            ma = f.read()
        with open(os.path.join(out, "model-b.txt")) as f:
            mb = f.read()
        assert ma == mb, "grown-back gang disagreed on the final booster"
    finally:
        sup.stop()
        reg.stop()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_elastic_per_host_reshard_bit_identical_via_artifact(tmp_path):
    """Gate 1's hard bit-identity contract, with the shared filesystem
    removed: per-host checkpoint dirs, one host SIGKILLed mid-round —
    the survivor re-shards from ITS OWN disk and publishes the frozen
    resume snapshot as a content-addressed artifact. A fresh world-1
    trainer then warm-starts from ``--resume-from artifact:<name>@
    <digest>@<url>`` — the snapshot bytes travel over HTTP, hash-
    verified, from the survivor's (restart-surviving) store — and its
    final booster must equal the survivor's byte-for-byte. Same claim
    as the shared-dir gate, new transport."""
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.artifacts import ArtifactServer, ArtifactStore

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    out = str(tmp_path)
    try:
        victim_fault = json.dumps({
            "rules": [{"point": "gbdt.round", "at": [6], "delay_s": 600}],
        })
        art = {n: os.path.join(out, f"art-{n}") for n in "abc"}
        surv = _spawn_trainer(
            reg.url, "a", os.path.join(out, "ck-a"), out, world=2,
            extra=["--no-growback", "--artifact-dir", art["a"]],
        )
        vict = _spawn_trainer(
            reg.url, "b", os.path.join(out, "ck-b"), out, world=2,
            extra=["--no-growback", "--artifact-dir", art["b"]],
            fault=victim_fault,
        )
        # per-host dirs: watch the SURVIVOR's own checkpoint stream
        latest = os.path.join(out, "ck-a", "LATEST")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                with open(latest) as f:
                    if f.read().strip() == "round-0000006":
                        break
            except OSError:
                pass
            assert vict.poll() is None, vict.communicate()[1][-2000:]
            time.sleep(0.1)
        time.sleep(0.6)
        vict.kill()
        _, err_a = surv.communicate(timeout=180)
        assert surv.returncode == 0, err_a[-3000:]
        sa = _status(out, "a")
        assert sa["done"] and sa["reshards"] == 1 and sa["gen"] == 2
        assert sa["snapshot"].startswith(os.path.join(out, "ck-a"))
        # the survivor advertised the snapshot as an artifact; its store
        # survives the process (re-indexed from disk) — serve it
        store = ArtifactStore(art["a"])
        name = os.path.basename(sa["snapshot"])
        refs = [r for r in store.refs() if r.startswith(name + "@")]
        assert refs, (store.refs(), name)
        srv = ArtifactServer(store)
        try:
            fresh = _spawn_trainer(
                reg.url, "c", os.path.join(out, "ck-c"), out, world=1,
                extra=[
                    "--artifact-dir", art["c"],
                    "--resume-from", f"artifact:{refs[0]}@{srv.url}",
                ],
            )
            _, err_c = fresh.communicate(timeout=180)
            assert fresh.returncode == 0, err_c[-3000:]
        finally:
            srv.stop()
        sc = _status(out, "c")
        assert sc.get("artifact_fetches", 0) >= 1, sc
        with open(os.path.join(out, "model-a.txt")) as f:
            survivor_model = f.read()
        with open(os.path.join(out, "model-c.txt")) as f:
            fresh_model = f.read()
        assert survivor_model == fresh_model, (
            "survivor's resumed booster != fresh world-1 run from the "
            "artifact-pulled snapshot"
        )
    finally:
        reg.stop()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_elastic_supervisor_growback_at_checkpoint_boundary(tmp_path):
    """``fleet supervise`` training charges close the loop: a SIGKILLed
    trainer is restarted with its full argv, auto-resumes from the
    shared checkpoint dir, and is grown back into the gang at the next
    checkpoint boundary (generation reason ``grow``) — and both hosts
    finish with the identical booster."""
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.supervisor import (
        FleetSupervisor,
        charge_from_train_args,
    )

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    out = str(tmp_path)
    ck = os.path.join(out, "ck")
    # slow every chunk so the run comfortably outlives the restart
    fault = json.dumps({"rules": [{"point": "gbdt.round", "delay_s": 0.35}]})
    env = _child_env()

    def spawn(argv):
        return subprocess.Popen(
            argv, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        )

    def args(name):
        return (
            f"--name {name} --data synth:600x8:5 --partitions 4 "
            f"--world-size 2 --ckpt-dir {ck} --num-iterations 40 "
            f"--num-leaves 7 --min-data-in-leaf 5 --seed 3 "
            f"--checkpoint-every 2 --heartbeat-s 0.25 "
            f"--out-model {out}/model-{name}.txt "
            f"--status-file {out}/status-{name}.json"
        )

    charges = [
        charge_from_train_args(args(n), reg.url, i)
        for i, n in enumerate("ab")
    ]
    for c in charges:  # arm the chunk-slowdown plan in every trainer
        c.argv = c.argv[:3] + ["--fault-plan", fault] + c.argv[3:]
    sup = FleetSupervisor(
        charges, registry_url=reg.url, probe_s=0.3, backoff_s=0.3,
        stable_s=30.0, spawn=spawn,
    ).start()
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if _status(out, "a").get("gen") == 1:
                break
            time.sleep(0.2)
        assert _status(out, "a").get("gen") == 1, "gang never formed"
        time.sleep(2.0)  # into the run
        victim = charges[1]
        victim.proc.kill()
        deadline = time.monotonic() + 150.0
        while time.monotonic() < deadline:
            sa, sb = _status(out, "a"), _status(out, "b")
            if sa.get("done") and sb.get("done"):
                break
            time.sleep(0.4)
        sa, sb = _status(out, "a"), _status(out, "b")
        assert sa.get("done") and sb.get("done"), (sa, sb)
        assert victim.restarts >= 1, "supervisor never restarted the victim"
        # the survivor shrank (lost), then the restarted host grew back:
        # the final generation includes both again
        assert sa["reshard_reasons"][:1] == ["lost"]
        assert sa["gen"] >= 3 and sorted(sa["members"]) == ["a", "b"]
        with open(os.path.join(out, "model-a.txt")) as f:
            ma = f.read()
        with open(os.path.join(out, "model-b.txt")) as f:
            mb = f.read()
        assert ma == mb, "grown-back gang disagreed on the final booster"
    finally:
        sup.stop()
        reg.stop()


# -- split brain: quorum CAS, fencing, parking --------------------------------


def test_declared_dead_pinned_to_monotonic_not_wall_clock(
    gang_registry, monkeypatch
):
    """An NTP step (wall clock jumps an hour) must neither mass-declare
    death nor mask a real one: sighting ages are time.monotonic()
    deltas, so only genuinely-stale sightings cross the grace."""
    from mmlspark_tpu.parallel.elastic import GangMember

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        deadline = time.monotonic() + 10.0
        while (
            set(a.roster() or {}) != {"a", "b"}
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert set(a.roster()) == {"a", "b"}
        # b crashes (no clean deregister): silence its heartbeats and
        # let the TTL prune it from the roster
        b.registry_urls = []
        deadline = time.monotonic() + 10.0
        while "b" in (a.roster() or {}) and time.monotonic() < deadline:
            time.sleep(0.1)
        ros = a.roster()
        assert "b" not in ros
        real = time.time
        with monkeypatch.context() as mp:
            # the NTP step: wall clock leaps one hour forward. b's last
            # sighting is ~2s old on the monotonic clock — a 30s grace
            # must NOT declare it dead just because the wall moved
            mp.setattr(time, "time", lambda: real() + 3600.0)
            assert a.declared_dead(["b"], ros, grace_s=30.0) == []
        # and the real death is still detected once the (monotonic)
        # grace genuinely elapses
        time.sleep(0.6)
        assert a.declared_dead(["b"], ros, grace_s=0.5) == ["b"]
    finally:
        a.close()
        b.close()


def test_commit_generation_zero_acks_raises_not_false_success():
    """Regression: with every registry dead, commit_generation used to
    swallow every POST failure and report the commit as done. Now zero
    acks raises (QuorumLostError) and the ack count is visible."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        QuorumLostError,
    )

    m = GangMember(
        "http://127.0.0.1:9/,http://127.0.0.1:19/", "a", heartbeat_s=30.0
    )
    try:
        with pytest.raises(QuorumLostError):
            m.commit_generation(
                Generation(gen=1, members=["a"]), expected_gen=0
            )
        assert m.commit_acks == 0
        assert m.committed_gens == []
    finally:
        m.close()


def test_generation_cas_concurrent_commits_exactly_one_winner(gang_registry):
    """Two members race conflicting gen-2 commits from the same adopted
    gen 1: the registry's CAS admits exactly one; the loser gets a
    rejection carrying the winning record, not a silent last-write."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        GenerationConflictError,
    )

    def stale_count():
        return obs.sum_samples(
            obs.parse_text(obs.render()),
            "mmlspark_registry_cas_commits_total", {"result": "stale"},
        )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    b = GangMember(gang_registry.url, "b", heartbeat_s=0.2)
    try:
        a.commit_generation(Generation(gen=1, members=["a", "b"]))
        gb = b.await_generation(2, timeout_s=10.0)
        assert gb.gen == 1
        before = stale_count()
        barrier = threading.Barrier(2)
        results: dict = {}

        def race(m):
            barrier.wait()
            try:
                results[m.name] = m.commit_generation(
                    Generation(gen=2, members=[m.name])
                )
            except GenerationConflictError as e:
                results[m.name] = e

        t = threading.Thread(target=race, args=(b,))
        t.start()
        race(a)
        t.join(10.0)
        winners = [
            n for n, r in results.items() if isinstance(r, Generation)
        ]
        losers = [
            n for n, r in results.items()
            if isinstance(r, GenerationConflictError)
        ]
        assert len(winners) == 1 and len(losers) == 1, results
        # the loser's rejection names the winning world
        loss = results[losers[0]]
        assert loss.current is not None
        assert loss.current.gen == 2
        assert loss.current.members == [winners[0]]
        # the registry counted the rejected commit
        assert stale_count() == before + 1
        # and the record IS the winner's, not the last writer's
        g = a.read_generation()
        assert g.gen == 2 and g.members == [winners[0]]
    finally:
        a.close()
        b.close()


def test_registry_restart_does_not_resurrect_superseded_generation():
    """HA: gen 2 wins a 2-of-3 majority while registry C is down. C
    restarts empty, a straggler re-posts the OLD gen-1 record to it, and
    anti-entropy must reconcile C to the HIGHEST committed generation —
    never resurrect the superseded world."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        GenerationConflictError,
    )
    from mmlspark_tpu.serving.registry import DriverRegistry

    reg_a = DriverRegistry(host="127.0.0.1", port=0, ttl_s=30.0)
    reg_b = DriverRegistry(host="127.0.0.1", port=0, ttl_s=30.0)
    reg_c = DriverRegistry(host="127.0.0.1", port=0, ttl_s=30.0)
    urls = f"{reg_a.url},{reg_b.url},{reg_c.url}"
    m = GangMember(urls, "a", heartbeat_s=30.0)
    regs = [reg_a, reg_b]
    try:
        g1 = m.commit_generation(
            Generation(gen=1, members=["a", "b"]), expected_gen=0
        )
        assert g1.gen == 1 and m.commit_acks == 3
        reg_c.stop()  # C misses the next commit
        g2 = m.commit_generation(Generation(gen=2, members=["a"]))
        assert g2.gen == 2 and m.commit_acks == 2  # majority of 3
        # C restarts EMPTY; a partitioned straggler's heartbeat re-post
        # lands the superseded gen-1 record on it first
        reg_c2 = DriverRegistry(host="127.0.0.1", port=0, ttl_s=30.0)
        regs.append(reg_c2)
        z = GangMember(reg_c2.url, "b", heartbeat_s=30.0)
        try:
            z.adopt(g1)
            z.heartbeat()  # re-posts the adopted gen-1 record
            # anti-entropy pulls from A: the gen record merges to the
            # HIGHEST gen, not the freshest timestamp
            reg_c2.peers = [reg_a.url]
            reg_c2.reconcile_now()
            got = z.read_generation()
            assert got.gen == 2 and got.members == ["a"]
            # and a CAS commit against the reconciled C from the stale
            # world is rejected, not adopted
            with pytest.raises(GenerationConflictError):
                z.commit_generation(
                    Generation(gen=2, members=["b"]), expected_gen=1
                )
        finally:
            z.close()
    finally:
        m.close()
        for r in regs:
            r.stop()


def test_registry_commit_cas_fault_point_refuses_then_relents(gang_registry):
    """Fault point ``registry.commit_cas``: an injected error refuses
    the commit server-side (503 — a missing ack), so a single-registry
    deployment loses its majority-of-1; the retry lands once the plan
    relents."""
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        QuorumLostError,
    )

    m = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    try:
        plan = FaultPlan().on(
            "registry.commit_cas", error=RuntimeError, max_fires=1
        )
        with plan.armed():
            with pytest.raises(QuorumLostError):
                m.commit_generation(
                    Generation(gen=1, members=["a"]), expected_gen=0
                )
            assert m.commit_acks == 0
            g = m.commit_generation(
                Generation(gen=1, members=["a"]), expected_gen=0
            )
        assert g.gen == 1 and m.commit_acks == 1
        assert len(plan.fires("registry.commit_cas")) == 1
    finally:
        m.close()


def test_fenced_out_only_on_registry_confirmed_exclusion(gang_registry):
    """The fencing token: a member whose adopted epoch is superseded by
    a committed generation that EXCLUDES it refuses to write; blindness
    or a newer world that still INCLUDES it never fences."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.parallel.elastic import GangMember, Generation

    def fenced_count():
        return obs.sum_samples(
            obs.parse_text(obs.render()),
            "mmlspark_elastic_fenced_writes_total", {"plane": "checkpoint"},
        )

    a = GangMember(gang_registry.url, "a", heartbeat_s=0.2)
    z = GangMember(gang_registry.url, "z", heartbeat_s=0.2)
    try:
        g1 = a.commit_generation(
            Generation(gen=1, members=["a", "z"]), expected_gen=0
        )
        z.adopt(g1)
        assert not z.fenced_out("checkpoint")  # current world includes z
        a.commit_generation(Generation(gen=2, members=["a"]))
        before = fenced_count()
        assert z.fenced_out("checkpoint")      # superseded AND excluded
        assert fenced_count() == before + 1
        # a newer world that still includes the member does not fence
        a.commit_generation(Generation(gen=3, members=["a", "z"]))
        assert not z.fenced_out("checkpoint")
    finally:
        a.close()
        z.close()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_partition_drill_minority_parks_majority_wins_zombie_fenced(
    tmp_path,
):
    """The split-brain acceptance drill (docs/chaos.md): member b's
    registry link runs through a seeded chaos proxy; a conductor
    ``partition`` step blackholes it. The majority side (a, with the
    registry) declares b dead, CAS-commits gen 2 and trains on; the
    minority (b) loses its registry quorum and PARKS — stops training,
    commits nothing, keeps heartbeating. The survivor's booster is
    bit-identical to a fresh majority-only run from the same snapshot; a
    zombie's late generation commit and late (stale-epoch) publication
    are both rejected and counted; the generation-monotonicity and
    single-writer laws stay green through the whole soak; post-heal the
    parked member's heartbeats reach the registry again."""
    import urllib.parse

    from mmlspark_tpu import obs
    from mmlspark_tpu.chaos.conductor import ChaosConductor, Scenario
    from mmlspark_tpu.chaos.invariants import InvariantChecker
    from mmlspark_tpu.chaos.wire import ChaosProxy
    from mmlspark_tpu.parallel.elastic import (
        GangMember,
        Generation,
        GenerationConflictError,
    )
    from mmlspark_tpu.serving import fleet
    from mmlspark_tpu.serving.modelstore import ModelDispatcher, ModelStore
    from mmlspark_tpu.serving.server import WorkerServer

    def counter(name, match=None):
        return obs.sum_samples(obs.parse_text(obs.render()), name, match)

    reg = fleet.run_registry(host="127.0.0.1", port=0, ttl_s=1.2)
    out = str(tmp_path)
    ck = os.path.join(out, "ck")
    reg_port = urllib.parse.urlparse(reg.url).port
    proxy = ChaosProxy("127.0.0.1", reg_port, seed=13, name="reg-b").start()
    surv = vict = fresh = None
    try:
        # b's ONLY path to the registry is the proxy; the park fault
        # point fires (armed with a tiny delay) as b stops training
        park_fault = json.dumps({
            "rules": [{"point": "elastic.park", "delay_s": 0.05}],
        })
        surv = _spawn_trainer(
            reg.url, "a", ck, out, world=2, extra=["--no-growback"],
        )
        vict = _spawn_trainer(
            f"http://127.0.0.1:{proxy.port}/", "b", ck, out, world=2,
            extra=["--no-growback", "--gen-timeout-s", "240"],
            fault=park_fault,
        )
        # wait until the 2-member gang is genuinely training (a couple
        # of checkpoints committed) before cutting the wire
        latest = os.path.join(ck, "LATEST")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                with open(latest) as f:
                    if f.read().strip() >= "round-0000004":
                        break
            except OSError:
                pass
            assert surv.poll() is None, surv.communicate()[1][-2000:]
            assert vict.poll() is None, vict.communicate()[1][-2000:]
            time.sleep(0.1)
        checker = InvariantChecker(
            registry_url=reg.url, service_name="train",
            status_files=[
                os.path.join(out, "status-a.json"),
                os.path.join(out, "status-b.json"),
                os.path.join(out, "status-c.json"),
            ],
        )
        cut = ChaosConductor(
            Scenario.from_spec({"seed": 13, "steps": [
                {"at_s": 0.0, "action": "partition", "links": ["reg-b"]},
                {"at_s": 0.0, "action": "mark", "note": "partition open"},
            ]}),
            proxies={"reg-b": proxy},
        )
        journal = cut.run()
        assert [e["action"] for e in journal] == ["partition", "mark"]
        assert journal[0]["links"] == ["reg-b"]
        # the soak: majority trains to completion while the invariant
        # laws are evaluated continuously
        soak_deadline = time.monotonic() + 180.0
        while surv.poll() is None and time.monotonic() < soak_deadline:
            assert checker.check(final=False) == []
            time.sleep(0.3)
        out_a, err_a = surv.communicate(timeout=30)
        assert surv.returncode == 0, err_a[-3000:]
        sa = _status(out, "a")
        assert sa["done"] and sa["reshards"] == 1
        assert sa["members"] == ["a"] and sa["gen"] == 2
        assert sa["committed_gens"] == [1, 2]  # a bootstrapped AND won
        # -- the minority parked: zero commits, training stopped
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            sb = _status(out, "b")
            if sb.get("parked"):
                break
            time.sleep(0.2)
        sb = _status(out, "b")
        assert sb.get("parked") is True, sb
        assert sb["parks"] >= 1
        assert sb["park_reasons"][0] in ("quorum", "conflict")
        assert sb["committed_gens"] == []
        assert not sb.get("done")
        assert vict.poll() is None, "parked member must keep running"
        # -- zombie generation commit: a SIGSTOP'd coordinator waking
        # after the reshard tries to move the world FORWARD from its
        # stale epoch; the CAS rejects (expected_gen 1 < committed 2)
        z = GangMember(reg.url, "z", heartbeat_s=0.5)
        try:
            z.adopt(Generation(gen=1, members=["a", "b"]))
            before = counter(
                "mmlspark_registry_cas_commits_total",
                {"result": "conflict"},
            )
            with pytest.raises(GenerationConflictError) as ei:
                z.commit_generation(
                    Generation(gen=3, members=["b", "z"]), expected_gen=1
                )
            assert ei.value.current is not None
            assert ei.value.current.gen == 2
            assert counter(
                "mmlspark_registry_cas_commits_total",
                {"result": "conflict"},
            ) == before + 1
        finally:
            z.close()
        # -- zombie publication: the committed gen rides load/swap as an
        # epoch; a worker that saw the winner's epoch 2 refuses epoch 1
        srv = WorkerServer()
        winfo = srv.start()
        ModelDispatcher(srv, ModelStore(), default_model="m").start()
        try:
            import http.client

            conn = http.client.HTTPConnection(
                "127.0.0.1", winfo.port, timeout=10
            )

            def publish(epoch):
                conn.request(
                    "POST", "/models/m/load",
                    body=json.dumps(
                        {"spec": "zoo:NoSuch", "epoch": epoch}
                    ),
                    headers={"Content-Type": "application/json"},
                )
                r = conn.getresponse()
                return r.status, json.loads(r.read() or b"{}")

            publish(2)  # the winner's epoch is now the highest seen
            before = counter(
                "mmlspark_elastic_fenced_publications_total",
                {"model": "m"},
            )
            fence_plan = FaultPlan().on("publish.fence", delay_s=0.01)
            with fence_plan.armed():
                code, body = publish(1)
            assert code == 409 and body["fenced"] is True
            assert body["highest_epoch"] == 2
            assert len(fence_plan.fires("publish.fence")) == 1
            assert counter(
                "mmlspark_elastic_fenced_publications_total",
                {"model": "m"},
            ) == before + 1
            conn.close()
        finally:
            srv.stop()
        # -- the hard contract: a fresh majority-only run from the same
        # snapshot produces the SAME booster bytes
        fresh = _spawn_trainer(
            reg.url, "c", os.path.join(out, "ck-fresh"), out, world=1,
            extra=["--resume-from", sa["snapshot"]],
        )
        out_c, err_c = fresh.communicate(timeout=180)
        assert fresh.returncode == 0, err_c[-3000:]
        with open(os.path.join(out, "model-a.txt")) as f:
            survivor_model = f.read()
        with open(os.path.join(out, "model-c.txt")) as f:
            fresh_model = f.read()
        assert survivor_model == fresh_model, (
            "survivor's booster != fresh majority-only run from the "
            "same snapshot"
        )
        # -- heal: the parked member's heartbeats reach the registry
        # again (it parked, it never died), and the final invariant
        # check — including generation monotonicity across the whole
        # drill — is green
        heal = ChaosConductor(
            Scenario.from_spec({"seed": 13, "steps": [
                {"at_s": 0.0, "action": "heal", "links": ["reg-b"]},
                {"at_s": 0.5, "action": "check", "final": True},
            ]}),
            proxies={"reg-b": proxy}, checker=checker,
        )
        heal.run()
        assert heal.violations == []
        deadline = time.monotonic() + 20.0
        back = False
        while time.monotonic() < deadline:
            entries = fleet.roster_entries_from_registry(
                reg.url, "train-gang"
            )
            if any(e.get("host") == "b" for e in entries):
                back = True
                break
            time.sleep(0.2)
        assert back, "parked member's heartbeats never resumed post-heal"
    finally:
        for p in (surv, vict, fresh):
            if p is not None and p.poll() is None:
                p.kill()
        proxy.stop()
        reg.stop()


@pytest.mark.chaos
@pytest.mark.xdist_group("latency")
def test_chaos_reshard_pull_blackholed_replica_fails_over(
    gang_registry, tmp_path,
):
    """One replica holder blackholed DURING the reshard pull: the
    grow-back member resolving the agreed resume snapshot by digest
    dials the advertising peers in the gang's deterministic sorted-name
    order — the first peer's ingress swallows every response byte
    (asymmetric partition, not a clean refusal) — and the fetch must
    burn one bounded timeout, fail over to the surviving holder, and
    land hash-verified bytes that unpack to the exact committed
    snapshot tree."""
    from mmlspark_tpu.chaos.wire import ChaosProxy, WireRule
    from mmlspark_tpu.parallel.elastic import GangMember, replicate_snapshot
    from mmlspark_tpu.serving.artifacts import (
        ArtifactStore,
        pack_dir,
        unpack_dir,
    )

    out = str(tmp_path)
    # the committer's frozen reshard snapshot, on its PRIVATE disk
    snap = os.path.join(out, "ck-a", "round-0000006")
    os.makedirs(snap)
    rng = np.random.default_rng(21)
    for fn in ("booster.json", "state.bin"):
        with open(os.path.join(snap, fn), "wb") as f:
            f.write(rng.bytes(40_000))
    stores = {
        n: ArtifactStore(os.path.join(out, f"art-{n}")) for n in "abc"
    }
    a = GangMember(
        gang_registry.url, "a", heartbeat_s=0.2, artifact_store=stores["a"],
    )
    b = GangMember(
        gang_registry.url, "b", heartbeat_s=0.2, artifact_store=stores["b"],
    )
    c = GangMember(
        gang_registry.url, "c", heartbeat_s=0.2, artifact_store=stores["c"],
    )
    # the committer's artifact ingress goes dark mid-pull: peers dial the
    # ADVERTISED port, so pointing it through a blackholing proxy is
    # exactly a host whose replies stopped arriving
    wire = ChaosProxy(
        "127.0.0.1", a.artifact_port, seed=7, name="reshard-blackhole",
        rules=[WireRule("blackhole", direction="s2c")],
    ).start()
    a.artifact_port = wire.port
    try:
        pack = os.path.join(out, "snap.pack")
        pack_dir(snap, pack)
        ref = stores["a"].put(pack, name="round-0000006")
        # replicate-before-commit pushed the snapshot to holder b (the
        # training plane's majority target for a world of 3 is 1)
        status: dict = {}
        assert replicate_snapshot(a, ref.digest, ["a", "b", "c"], status) == 1
        assert status["snapshot_replicas"] == 1
        assert stores["b"].has(ref.digest)
        # both advertisements must ride a heartbeat before c can resolve
        deadline = time.monotonic() + 15.0
        peers = c.artifact_peers(ref.digest)
        while time.monotonic() < deadline and len(peers) < 2:
            time.sleep(0.1)
            peers = c.artifact_peers(ref.digest)
        assert len(peers) == 2, peers
        assert str(wire.port) in peers[0], (
            "sorted-name failover order must dial the blackholed "
            "committer first", peers,
        )
        # per-connection timeout bounds the blackhole's cost: the dark
        # peer blocks the socket until exactly this budget expires
        t0 = time.monotonic()
        path = stores["c"].fetch(
            ref.digest, peers, name="round-0000006", timeout_s=8.0,
        )
        dt = time.monotonic() - t0
        assert dt < 25.0, f"failover burned {dt:.1f}s, not one timeout"
        local = os.path.join(out, "ck-c", f"pulled-{ref.digest[:16]}")
        unpack_dir(path, local)
        for fn in ("booster.json", "state.bin"):
            with open(os.path.join(snap, fn), "rb") as want, \
                    open(os.path.join(local, fn), "rb") as got:
                assert got.read() == want.read(), fn
        assert any(e.kind == "blackhole" for e in wire.journal()), (
            "the drill never actually exercised the blackhole"
        )
    finally:
        wire.stop()
        for m in (a, b, c):
            m.close()
