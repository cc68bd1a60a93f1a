"""Elastic self-healing distributed training: survive host loss mid-round,
re-shard, and resume bit-identically.

The training-plane sibling of the serving supervisor (PR 5): the paper's
headline rebuild of LightGBM's gang-scheduled socket allreduce previously
died on the first lost host — ``barrier()`` named the missing process and
raised, and the run was over until a human restarted it from a checkpoint.
This module closes the detect -> react loop:

- **Gang membership** rides the existing DriverRegistry heartbeats: every
  trainer registers under ``<service>-gang`` and heartbeats; a host whose
  beats stop vanishes from the TTL'd roster.
- **Detection**: a lost host surfaces either as a TTL expiry seen at a
  round boundary (:meth:`GangContext.on_round`) or as a gang allreduce
  whose peer frames never arrive mid-round (the socket-level failure the
  reference's ``allreduce`` hit, recoverable here instead of fatal).
- **Reaction**: survivors abort the in-flight round (state through the
  last checkpoint stands), agree on a new epoch/world through a
  **registry-stamped generation** record, re-shard the data partitions
  contiguously over the shrunk gang, and resume from the latest round
  checkpoint — all in-process, no operator action.
- **Contract**: the resumed booster on ``k-1`` hosts is **bit-identical**
  to a fresh ``k-1``-host run started from that same checkpoint (the
  reshard snapshots the checkpoint it resumed from so the claim is
  auditable; tests/test_elastic.py proves it byte-for-byte).
- **Grow-back**: a supervisor-restarted host re-registers and rejoins at
  the next checkpoint boundary (generation bump with reason ``grow``)
  instead of being lost for the run.
- **Stragglers**: per-host round-time EWMAs ride the heartbeat payload;
  the generation coordinator flags sustained-slow hosts
  (:class:`StragglerTracker`) and can evict them through the same resize
  path (reason ``straggler``).

Data plane: within a generation the gang trains the existing GBDT loop
(``models/gbdt/train.py``, unsharded per host) with the PR-8 host growers'
histograms **summed across members** by :class:`TcpReducer` — the literal
LightGBM data-parallel pattern (local histogram + allreduce + identical
split decisions everywhere), carried over plain TCP so a dead peer is a
recoverable socket timeout, not an uncancellable XLA collective. Every
member grows the identical tree; the booster is SPMD-identical across the
gang.

Global row order is world-invariant: partitions are contiguous row blocks
of the common dataset and members take contiguous partition runs in
sorted-name order, so the gathered checkpoint scores mean the same thing
at every world size — the property the bit-identity contract rests on.

Fault points (docs/robustness.md): ``elastic.detect`` fires at every
detection check (a payload forces a named host "lost" without killing
anything), ``elastic.reshard`` as a reshard commit is attempted (an error
is "the coordinator refused", retried), ``train.round_abort`` as an
in-flight round is aborted (a delay stalls the abort -> reshard
turnaround, visible in the detection-latency metric).
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import socket
import struct
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Optional

import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core import faults
from mmlspark_tpu.obs import watchdog
from mmlspark_tpu.parallel.distributed import BarrierTimeoutError

_M_GEN = obs.gauge(
    "mmlspark_elastic_generation_count",
    "Current training-gang generation (bumped by every reshard)",
)
_M_MEMBERS = obs.gauge(
    "mmlspark_elastic_members_count", "Live members of the training gang",
)
_M_RESHARDS = obs.counter(
    "mmlspark_elastic_reshards_total",
    "Generation bumps: world changed and partitions were re-assigned",
    labels=("reason",),
)
_M_DETECT = obs.histogram(
    "mmlspark_elastic_detect_seconds",
    "Host-loss detection latency: last heartbeat seen -> loss declared",
)
_M_ROUND_EWMA = obs.gauge(
    "mmlspark_elastic_round_ewma_seconds",
    "Per-host boosting-round wall-time EWMA (straggler signal)",
    labels=("host",),
)
_M_STRAGGLERS = obs.gauge(
    "mmlspark_elastic_stragglers_count",
    "Members currently flagged sustained-slow by the coordinator",
)
_M_ABORTS = obs.counter(
    "mmlspark_elastic_round_aborts_total",
    "In-flight rounds abandoned because the gang changed under them",
)
_M_ALLREDUCE = obs.histogram(
    "mmlspark_elastic_allreduce_seconds",
    "Gang histogram-allreduce wall time (ring reduce-scatter + "
    "allgather by default; mode=mesh keeps the full-mesh baseline)",
)
_M_CRC_DROPS = obs.counter(
    "mmlspark_elastic_crc_failures_total",
    "Allreduce frames dropped because their payload CRC32 did not match "
    "— wire corruption detected instead of silently summed",
)
_M_RETRANSMITS = obs.counter(
    "mmlspark_elastic_retransmits_total",
    "Allreduce frames re-sent after a peer's corruption NACK",
)
_M_RING_STEPS = obs.counter(
    "mmlspark_elastic_ring_steps_total",
    "Ring-collective steps executed (each moves O(payload/world) bytes)",
    labels=("phase",),
)
_M_PAYLOAD_BYTES = obs.counter(
    "mmlspark_elastic_payload_bytes_total",
    "Allreduce payload bytes put on the wire (frame heads excluded)",
    labels=("mode",),
)
_M_OVERLAP_BLOCKS = obs.counter(
    "mmlspark_elastic_overlap_blocks_total",
    "Histogram feature blocks built while an earlier block's allreduce "
    "was in flight (the compute/communication pipeline)",
)
_M_VOTE_ROUNDS = obs.counter(
    "mmlspark_elastic_vote_rounds_total",
    "Voting-parallel exchanges: a (d,) ballot sum + top-2K candidate "
    "columns instead of the full histogram plane",
)
_M_PARKS = obs.counter(
    "mmlspark_elastic_parks_total",
    "Members that parked (stopped training, kept heartbeating) because "
    "they lost registry quorum or lost a generation CAS race — the "
    "minority side of a partition parking instead of split-braining",
    labels=("reason",),
)
_M_FENCED = obs.counter(
    "mmlspark_elastic_fenced_writes_total",
    "Writes refused because the writer's adopted epoch was superseded "
    "(a fenced-out zombie cannot persist, publish, or advertise)",
    labels=("plane",),
)


# -- the allreduce wire frame --------------------------------------------------
#
# v2 head (32 bytes): gen(q) seq(q) nonce(I) crc(I) name_len(i) nbytes(i).
# ``crc`` is the payload's CRC32 — v1 (`<qqIii`) carried NO checksum, so
# one flipped bit on the wire was silently summed into every member's
# identical histograms (the worst possible failure: bit-identical and
# wrong everywhere). A receiver that sees a CRC mismatch DROPS the frame,
# counts it, and answers with a NACK control frame (nbytes == -1, no
# payload); the sender retransmits from its recent-frame cache. A frame
# that stays missing past the allreduce timeout is the ordinary peer-loss
# path — corruption can delay a round or evict a peer, never corrupt a sum.
_FRAME_HEAD = "<qqIIii"
_FRAME_HEAD_LEN = struct.calcsize(_FRAME_HEAD)
_NACK_NBYTES = -1
# sanity bounds: a bit-flip inside the HEAD desyncs the stream — refuse
# to interpret absurd lengths and drop the connection instead (the
# sender reconnects; the frame re-requests or times out into peer-loss).
# 1 GiB is far above any real histogram frame but well below int32 max,
# so a high-bit flip in nbytes cannot command a giant blocking read
_MAX_NAME_LEN = 256
_MAX_FRAME_BYTES = 1 << 30


class HostLostError(RuntimeError):
    """A gang member stopped answering mid-run; carries the culprits."""

    def __init__(self, lost: list, gen: int = 0, detail: str = ""):
        self.lost = sorted(set(lost))
        self.gen = gen
        msg = (
            f"training gang generation {gen} lost host(s): "
            f"{', '.join(self.lost) or '?'}"
        )
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


class WorldChangedError(RuntimeError):
    """Another member committed a newer generation — re-form, don't die."""

    def __init__(self, gen: int):
        self.gen = gen
        super().__init__(f"training gang moved to generation {gen}")


class QuorumLostError(RuntimeError):
    """This member cannot reach a strict majority of the registries —
    it may be on the minority side of a partition. The only safe move is
    to PARK (stop training, keep heartbeating, commit nothing): a
    minority that reshards to its own world double-writes the epoch."""

    def __init__(self, detail: str = ""):
        super().__init__(
            "lost registry quorum" + (f": {detail}" if detail else "")
        )


class GenerationConflictError(RuntimeError):
    """A generation commit lost its compare-and-swap race: another
    member already committed a conflicting epoch. Carries the winning
    record (when the registry returned it) so the loser can park and
    rejoin the winning generation after heal."""

    def __init__(self, gen: int, current: Optional["Generation"] = None):
        self.gen = gen
        self.current = current
        msg = f"generation {gen} commit rejected by CAS"
        if current is not None:
            msg += (
                f" (registry holds gen {current.gen} "
                f"members={current.members})"
            )
        super().__init__(msg)


# -- deterministic partition assignment ---------------------------------------


def partition_bounds(n_rows: int, n_partitions: int) -> list:
    """Contiguous ``(lo, hi)`` row slices of the global dataset."""
    p = max(1, int(n_partitions))
    return [
        (i * n_rows // p, (i + 1) * n_rows // p) for i in range(p)
    ]


def assign_partitions(n_partitions: int, members: list) -> dict:
    """Member name -> list of partition ids. Members take CONTIGUOUS
    partition runs in sorted-name order, so the concatenation of every
    member's rows is the global dataset in its original order at every
    world size — the invariance the checkpoint bit-identity contract
    needs (a round-robin assignment would permute rows per world)."""
    names = sorted(members)
    m = len(names)
    out = {}
    for j, name in enumerate(names):
        out[name] = list(range(j * n_partitions // m,
                               (j + 1) * n_partitions // m))
    return out


def member_row_slice(
    n_rows: int, n_partitions: int, members: list, me: str
) -> tuple:
    """This member's contiguous ``(lo, hi)`` global row range."""
    parts = assign_partitions(n_partitions, members)[me]
    bounds = partition_bounds(n_rows, n_partitions)
    if not parts:
        return (0, 0)
    return (bounds[parts[0]][0], bounds[parts[-1]][1])


# -- straggler policy ---------------------------------------------------------


class StragglerTracker:
    """Flag members whose round-time EWMA stays ``factor`` x the gang
    median for ``sustain`` consecutive observations. Pure policy — the
    coordinator feeds it roster EWMAs and acts on the flags."""

    def __init__(self, factor: float = 3.0, sustain: int = 3):
        self.factor = float(factor)
        self.sustain = max(1, int(sustain))
        self._slow_streak: dict = {}

    def observe(self, ewmas: dict) -> list:
        """``{host: ewma_seconds}`` -> hosts flagged sustained-slow."""
        vals = [v for v in ewmas.values() if v and v > 0]
        if len(vals) < 2:
            self._slow_streak.clear()
            return []
        median = float(np.median(vals))
        flagged = []
        for host, v in ewmas.items():
            if v and median > 0 and v > self.factor * median:
                self._slow_streak[host] = self._slow_streak.get(host, 0) + 1
                if self._slow_streak[host] >= self.sustain:
                    flagged.append(host)
            else:
                self._slow_streak.pop(host, None)
        for host in list(self._slow_streak):
            if host not in ewmas:
                self._slow_streak.pop(host)
        return sorted(flagged)


# -- generation record over the registry --------------------------------------


@dataclass
class Generation:
    """One agreed (epoch, world): who trains, and from where."""

    gen: int
    members: list
    reason: str = "init"
    resume_round: int = 0
    snapshot: Optional[str] = None
    # content-addressed identity of the resume snapshot (serving/
    # artifacts.py): a member whose LOCAL disk lacks the snapshot path
    # pulls these exact bytes over HTTP from any advertising peer —
    # per-host checkpoint dirs stop being fatal
    snapshot_digest: Optional[str] = None
    committer: str = ""
    detect_latency_s: float = 0.0
    stamp: float = 0.0          # registry-side registration ts
    # straggler evictions: name -> boot stamp at eviction. Grow-back
    # re-admits an evicted host only once it re-registers with a NEW
    # boot (a restarted process gets a clean slate; the same slow
    # process does not bounce straight back in)
    evicted: dict = field(default_factory=dict)

def _post_json(url: str, payload: dict, timeout: float = 5.0) -> bool:
    from mmlspark_tpu.io.clients import send_request
    from mmlspark_tpu.io.http_schema import HTTPRequestData

    resp = send_request(
        HTTPRequestData(
            url, "POST", {"Content-Type": "application/json"},
            json.dumps(payload),
        ),
        timeout=timeout,
    )
    return resp["status_code"] == 200


def _post_json_status(
    url: str, payload: dict, timeout: float = 5.0
) -> tuple:
    """POST returning ``(status_code, decoded_body)`` — the CAS commit
    path needs the 409 body (it carries the winning record), not just a
    success bool."""
    from mmlspark_tpu.io.clients import send_request
    from mmlspark_tpu.io.http_schema import HTTPRequestData

    resp = send_request(
        HTTPRequestData(
            url, "POST", {"Content-Type": "application/json"},
            json.dumps(payload),
        ),
        timeout=timeout,
    )
    try:
        body = json.loads(resp["entity"])
    except (ValueError, TypeError):
        body = {}
    return resp["status_code"], body


def _generation_from_entry(e: dict) -> "Generation":
    """Roster generation entry (``host="generation"``) -> Generation."""
    return Generation(
        gen=int(e.get("port", 0)),
        members=list(e.get("members", [])),
        reason=e.get("reason", ""),
        resume_round=int(e.get("resume_round", 0)),
        snapshot=e.get("snapshot"),
        snapshot_digest=e.get("snapshot_digest"),
        committer=e.get("committer", ""),
        detect_latency_s=float(e.get("detect_latency_s", 0.0)),
        stamp=float(e.get("ts", 0.0)),
        evicted=dict(e.get("evicted") or {}),
    )


def _get_roster(url: str, timeout: float = 5.0) -> Optional[dict]:
    from mmlspark_tpu.io.clients import send_request
    from mmlspark_tpu.io.http_schema import HTTPRequestData

    resp = send_request(
        HTTPRequestData(url.rstrip("/") + "/", "GET"), timeout=timeout
    )
    if resp["status_code"] != 200:
        return None
    try:
        return json.loads(resp["entity"])
    except ValueError:
        return None


# -- gang membership ----------------------------------------------------------


class GangMember:
    """One training host's registry presence: heartbeat registration,
    TTL'd roster reads, and the registry-stamped generation record.

    The member's heartbeat carries its allreduce listener port and its
    round-time EWMA; it also re-posts the member's currently-adopted
    generation record each beat so the record outlives the registry TTL
    for as long as anyone still believes in it."""

    def __init__(
        self,
        registry_urls: Any,
        name: str,
        service: str = "train",
        advertise_host: str = "127.0.0.1",
        heartbeat_s: float = 1.0,
        artifact_store: Any = None,
        listen_port: int = 0,
        advertise_port: Optional[int] = None,
    ):
        """``artifact_store`` (serving/artifacts.py ArtifactStore): when
        given, this member also runs a tiny artifact ingress (ranged
        ``GET /artifacts/<digest>``) and advertises the store's contents
        on every heartbeat — checkpoint snapshots become pullable from
        any surviving peer, so the gang no longer needs a shared
        checkpoint directory.

        ``listen_port``/``advertise_port``: fix the allreduce listener
        port and/or advertise a DIFFERENT port on the roster — how a
        member's allreduce link is pointed through a chaos proxy (peers
        dial the advertised port; chaos/wire.py) or through real NAT."""
        from mmlspark_tpu.serving.fleet import split_registry_urls

        self.registry_urls = split_registry_urls(registry_urls)
        if not self.registry_urls:
            raise ValueError("elastic training needs at least one --registry")
        self.name = name
        self.service = service
        self.advertise_host = advertise_host
        self.heartbeat_s = float(heartbeat_s)
        self.boot = time.time()
        self.ewma_s = 0.0
        self.artifact_store = artifact_store
        self._artifact_srv: Any = None
        self.artifact_port: Optional[int] = None
        if artifact_store is not None:
            from mmlspark_tpu.serving import artifacts as artifacts_mod
            from mmlspark_tpu.serving.server import WorkerServer

            srv = WorkerServer(
                host="0.0.0.0", port=0, name=f"{service}-artifacts"
            )
            artifacts_mod.attach(srv, artifact_store)
            info = srv.start()
            self._artifact_srv = srv
            self.artifact_port = info.port
        self.last_seen: dict = {}   # member -> MONOTONIC ts last on roster
        self._adopted: Optional[Generation] = None
        # registry reachability (monotonic ts of each registry's last
        # answer): the quorum signal — a member whose majority-reachable
        # age exceeds ``quorum_grace_s`` is on the minority side of a
        # partition and must park rather than reshard
        self._reg_seen: dict = {}
        self._boot_mono = time.monotonic()
        self.quorum_grace_s = max(2.0, 5.0 * self.heartbeat_s)
        self.commit_acks = 0            # registries acking the last commit
        self.committed_gens: list = []  # gens THIS member CAS-committed
        self._stop = threading.Event()
        # allreduce frame listener (one across generations; the port is
        # what peers learn from the roster)
        self._inbox: dict = {}          # (gen, nonce, seq, sender) -> bytes
        self._inbox_cond = threading.Condition()
        # CRC accounting: frames dropped for checksum mismatch; the keys
        # stay recorded so the waiting allreduce re-NACKs until the
        # retransmit lands (a lost NACK must not strand the round)
        self.crc_drops = 0
        self._crc_dropped: set = set()
        # the active TcpReducer (if any): the read loop's back-channel
        # for NACK-triggered retransmits
        self._reducer: Any = None
        self._srv = socket.create_server(("0.0.0.0", int(listen_port)))
        self._srv.settimeout(0.5)
        self.port = self._srv.getsockname()[1]
        self.advertise_port = (
            int(advertise_port) if advertise_port else self.port
        )
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"gang-listen-{name}", daemon=True
        )
        self._accept_thread.start()
        self._beat_thread = threading.Thread(
            target=self._beat_loop, name=f"gang-beat-{name}", daemon=True
        )
        self._beat_thread.start()

    # -- listener ------------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(
                target=self._read_loop, args=(conn,), daemon=True
            ).start()

    def _read_loop(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(None)
            f = conn.makefile("rb")
            while not self._stop.is_set():
                head = f.read(_FRAME_HEAD_LEN)
                if len(head) < _FRAME_HEAD_LEN:
                    return
                gen, seq, nonce, crc, name_len, nbytes = struct.unpack(
                    _FRAME_HEAD, head
                )
                if not 0 < name_len <= _MAX_NAME_LEN or nbytes > \
                        _MAX_FRAME_BYTES or (
                            nbytes < 0 and nbytes != _NACK_NBYTES
                        ):
                    # a bit-flip inside the HEAD desyncs the stream:
                    # refuse to interpret garbage lengths — drop the
                    # connection (the sender reconnects; the missing
                    # frame re-requests or times out into peer-loss)
                    self.crc_drops += 1
                    _M_CRC_DROPS.inc()
                    return
                sender = f.read(name_len).decode("utf-8", "replace")
                if nbytes == _NACK_NBYTES:
                    # corruption NACK: the peer received our (gen, seq)
                    # frame torn — retransmit from the reducer's cache
                    red = self._reducer
                    if red is not None:
                        red.handle_nack(sender, gen, nonce, seq)
                    continue
                payload = f.read(nbytes)
                if len(payload) < nbytes:
                    return
                key = (gen, nonce, seq, sender)
                if zlib.crc32(payload) & 0xFFFFFFFF != crc:
                    # detected wire corruption: a dropped frame (and a
                    # NACK back), NEVER a silently wrong sum
                    self.crc_drops += 1
                    _M_CRC_DROPS.inc()
                    with self._inbox_cond:
                        self._crc_dropped.add(key)
                    red = self._reducer
                    if red is not None:
                        red.send_nack(sender, gen, nonce, seq)
                    continue
                with self._inbox_cond:
                    self._inbox[key] = payload
                    self._crc_dropped.discard(key)
                    self._inbox_cond.notify_all()
        except Exception:  # noqa: BLE001 — a dead peer's conn just ends
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def take_frame(
        self, gen: int, nonce: int, seq: int, sender: str, timeout_s: float
    ) -> Optional[bytes]:
        deadline = time.monotonic() + timeout_s
        with self._inbox_cond:
            while True:
                buf = self._inbox.pop((gen, nonce, seq, sender), None)
                if buf is not None:
                    return buf
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._inbox_cond.wait(min(remaining, 0.05))

    def drop_stale_frames(self, current_gen: int) -> None:
        with self._inbox_cond:
            for key in [k for k in self._inbox if k[0] < current_gen]:
                del self._inbox[key]
            for key in [k for k in self._crc_dropped if k[0] < current_gen]:
                self._crc_dropped.discard(key)

    def crc_dropped(self, key: tuple) -> bool:
        """Was ``(gen, nonce, seq, sender)`` dropped for a bad CRC (and
        not yet replaced by a clean retransmit)? The allreduce waiter
        re-NACKs such keys each roster check — a lost NACK must not
        strand the round until the timeout."""
        with self._inbox_cond:
            return key in self._crc_dropped

    def _attach_reducer(self, reducer: Any) -> None:
        self._reducer = reducer

    def _detach_reducer(self, reducer: Any) -> None:
        if self._reducer is reducer:
            self._reducer = None

    # -- registration ---------------------------------------------------------

    def _registration(self) -> dict:
        reg = {
            "name": f"{self.service}-gang",
            "host": self.name,
            "port": self.advertise_port,
            "addr": self.advertise_host,
            "boot": self.boot,
            "ewma_ms": round(self.ewma_s * 1e3, 3),
        }
        if self.artifact_store is not None:
            # advertise name@sha256 refs + the ingress serving them, so
            # peers resolve checkpoint pulls straight off the roster
            reg["artifact_port"] = self.artifact_port
            reg["artifacts"] = self.artifact_store.refs()
        return reg

    def artifact_peers(self, digest: str) -> list:
        """Gang members currently advertising ``digest`` -> artifact
        base URLs (the fetch failover order is sorted-name, matching the
        rest of the gang's determinism conventions)."""
        ros = self.roster() or {}
        suffix = "@" + digest
        peers = []
        for name in sorted(ros):
            if name == self.name:
                continue
            e = ros[name]
            port = e.get("artifact_port")
            if port and any(
                a.endswith(suffix) for a in e.get("artifacts") or ()
            ):
                peers.append(f"http://{e.get('addr', '127.0.0.1')}:{port}")
        return peers

    def artifact_holders(self, members: Any = None) -> list:
        """Gang members running an artifact ingress -> base URLs (the
        push targets for snapshot replicate-before-commit); ``members``
        narrows to a generation's roster. Unlike
        :meth:`artifact_peers`, holders need not already advertise a
        digest — they are where the digest is going."""
        ros = self.roster() or {}
        urls = []
        for name in sorted(ros):
            if name == self.name:
                continue
            if members is not None and name not in members:
                continue
            e = ros[name]
            port = e.get("artifact_port")
            if port:
                urls.append(f"http://{e.get('addr', '127.0.0.1')}:{port}")
        return urls

    def heartbeat(self) -> None:
        """One registration beat to every registry (also refreshes the
        adopted generation record's TTL).

        Conflict rule: the registry's copy of a generation is
        authoritative (last writer wins — one entry per gen number). If
        the current record for our adopted gen carries DIFFERENT members
        (racing survivors with divergent lost-sets each committed), we
        ADOPT the registry's copy instead of re-posting ours, so the
        record converges instead of flapping; the training loop notices
        the membership change at its next round boundary."""
        gen = self._adopted
        if gen is not None:
            cur = self.read_generation()
            if cur is not None and cur.gen >= gen.gen and (
                cur.gen > gen.gen
                or sorted(cur.members) != sorted(gen.members)
            ):
                self._adopted = gen = cur
        # explicit short per-call budget: a blackholed registry must cost
        # a bounded slice of the beat, never park the heartbeat thread
        # (pinned by the chaos-proxy blackhole test)
        from mmlspark_tpu.serving.fleet import beat_timeout

        timeout = beat_timeout(self.heartbeat_s, factor=2.0)
        for url in self.registry_urls:
            try:
                if _post_json(url, self._registration(), timeout=timeout):
                    self._reg_seen[url] = time.monotonic()
                if gen is not None:
                    # the registry monotone-guards generation re-posts: a
                    # 409 here means OUR copy is the superseded one (the
                    # heartbeat conflict rule above adopts the winner at
                    # the next beat) — never last-writer-wins
                    _post_json(url, self._gen_payload(gen), timeout=timeout)
            except Exception:  # noqa: BLE001 — registry may be restarting
                pass

    def _beat_loop(self) -> None:
        while not self._stop.is_set():
            self.heartbeat()
            self._stop.wait(self.heartbeat_s)

    def roster(self) -> Optional[dict]:
        """Live gang members (TTL-filtered by the registry): name ->
        entry, or **None when no registry answered** — blindness is not
        evidence of death (a restarting registry must not make every
        survivor declare every peer lost and split-brain the gang).
        Tracks ``last_seen`` MONOTONIC times for the loss grace and the
        detection-latency metric (wall clock steps must not distort
        either). The first live registry answers (registry HA)."""
        for url in self.registry_urls:
            data = _get_roster(url)
            if data is None:
                continue
            self._reg_seen[url] = time.monotonic()
            entries = {
                e.get("host"): e for e in data.get(f"{self.service}-gang", [])
            }
            now = time.monotonic()
            for host in entries:
                self.last_seen[host] = now
            return entries
        return None

    # -- registry quorum -------------------------------------------------------

    def majority(self) -> int:
        """Strict majority of the configured registries (majority-of-1
        for single-registry deployments)."""
        return len(self.registry_urls) // 2 + 1

    def quorum_age_s(self) -> float:
        """Seconds since a strict majority of the registries was last
        reachable from here (0-ish while healthy). The park trigger:
        an age beyond ``quorum_grace_s`` means this member may be on
        the minority side of a partition — it must stop training and
        commit nothing, because the majority side is entitled to
        declare it dead and reshard without it."""
        times = sorted(
            (self._reg_seen.get(u, self._boot_mono)
             for u in self.registry_urls),
            reverse=True,
        )
        return time.monotonic() - times[self.majority() - 1]

    # -- generation record -----------------------------------------------------

    def _gen_payload(self, g: Generation) -> dict:
        return {
            "name": f"{self.service}-gen",
            # the (host, port) identity key: one entry per generation,
            # re-posts replace (heartbeat refresh), max port wins on read
            "host": "generation",
            "port": int(g.gen),
            "members": list(g.members),
            "reason": g.reason,
            "resume_round": int(g.resume_round),
            "snapshot": g.snapshot,
            "snapshot_digest": g.snapshot_digest,
            "committer": g.committer,
            "detect_latency_s": g.detect_latency_s,
            "evicted": dict(g.evicted),
        }

    def declared_dead(
        self, candidates: list, ros: Optional[dict], grace_s: float
    ) -> list:
        """THE loss policy, shared by round-boundary detection and the
        allreduce wait (one implementation — the two sites must never
        drift): a candidate is dead only when the roster is NOT blind
        (some registry answered AND it has collected our own heartbeat
        — a freshly-restarted registry's empty roster is blindness, not
        mass death), the candidate is absent, and its last sighting is
        older than the grace (debounces the re-registration race).

        Sighting ages are MONOTONIC deltas: a wall-clock step (NTP slew,
        manual date set) must neither mass-declare death nor mask a real
        one — pinned by the clock-step test."""
        if not candidates or ros is None or self.name not in ros:
            return []
        now = time.monotonic()
        return [
            c for c in candidates
            if c not in ros
            and now - self.last_seen.get(c, self._boot_mono) >= grace_s
        ]

    def read_generation(self) -> Optional[Generation]:
        # consult EVERY answering registry and take the highest
        # generation (registry HA: a just-restarted registry may answer
        # with an empty roster while a peer still holds the record)
        entries: list = []
        for url in self.registry_urls:
            data = _get_roster(url)
            if data is None:
                continue
            self._reg_seen[url] = time.monotonic()
            entries.extend(data.get(f"{self.service}-gen", []))
        if entries:
            e = max(
                entries,
                key=lambda x: (x.get("port", 0), x.get("ts", 0.0)),
            )
            return _generation_from_entry(e)
        return None

    def commit_generation(
        self, g: Generation, expected_gen: Optional[int] = None,
    ) -> Generation:
        """Quorum compare-and-swap commit: POST the record to EVERY
        registry's ``/generation/commit`` with the predecessor claim
        (``expected_gen``, derived from the adopted generation when not
        given) and count acks. Succeeds only when a strict majority
        acks (majority-of-1 for single-registry fleets); raises

        - :class:`GenerationConflictError` when a registry rejects the
          CAS because a conflicting epoch already won (carries the
          winner so the loser can park and rejoin it), and
        - :class:`QuorumLostError` when fewer than a majority of
          registries ack — including the zero-ack case (a dead or
          partitioned registry list must never read as success; the
          old code swallowed every POST failure and proceeded as
          committed).
        """
        g.committer = self.name
        if expected_gen is None:
            if self._adopted is not None:
                expected_gen = int(self._adopted.gen)
            else:
                cur0 = self.read_generation()
                expected_gen = int(cur0.gen) if cur0 is not None else 0
        payload = {
            "name": f"{self.service}-gen",
            "gen": int(g.gen),
            "expected_gen": int(expected_gen),
            "record": self._gen_payload(g),
        }
        acks = 0
        conflict: Optional[Generation] = None
        conflict_gen = -1
        for url in self.registry_urls:
            try:
                status, body = _post_json_status(
                    url.rstrip("/") + "/generation/commit", payload
                )
            except Exception:  # noqa: BLE001 — unreachable: not an ack
                continue
            self._reg_seen[url] = time.monotonic()
            if status == 200:
                acks += 1
            elif status == 404:
                # pre-CAS registry: fall back to the plain roster POST
                try:
                    if _post_json(url, self._gen_payload(g)):
                        acks += 1
                except Exception:  # noqa: BLE001
                    pass
            elif status == 409:
                cur = body.get("current") if isinstance(body, dict) else None
                cg = int(body.get("current_gen", 0)) if isinstance(
                    body, dict
                ) else 0
                if cg > conflict_gen:
                    conflict_gen = cg
                    conflict = (
                        _generation_from_entry(cur) if cur else None
                    )
        self.commit_acks = acks
        if acks < self.majority():
            # a minority of acks is NOT a commit, whatever the mix of
            # rejections and silence — but a CAS rejection is the more
            # specific diagnosis (it carries the winning epoch to park
            # against); plain blindness is quorum loss
            if conflict_gen >= 0:
                raise GenerationConflictError(int(g.gen), conflict)
            raise QuorumLostError(
                f"generation {g.gen} commit acked by {acks} of "
                f"{len(self.registry_urls)} registries "
                f"(majority is {self.majority()})"
            )
        self.committed_gens.append(int(g.gen))
        self._adopted = g
        _M_GEN.set(g.gen)
        _M_MEMBERS.set(len(g.members))
        return g

    def adopt(self, g: Generation) -> None:
        self._adopted = g
        _M_GEN.set(g.gen)
        _M_MEMBERS.set(len(g.members))

    def fenced_out(self, plane: str) -> bool:
        """Is this member's adopted epoch superseded by a committed
        generation that EXCLUDES it? The committed gen is the fencing
        token: a fenced-out writer must refuse to persist or advertise
        on ``plane`` (counted in ``mmlspark_elastic_fenced_writes_total``)
        — a SIGSTOP'd zombie coordinator that wakes after the survivors
        resharded cannot roll the fleet back. Blindness is NOT fencing
        (the quorum park path owns that side); only a registry-confirmed
        newer world fences."""
        g = self._adopted
        if g is None:
            return False
        cur = self.read_generation()
        if cur is None:
            return False
        superseded = cur.gen > g.gen or (
            cur.gen == g.gen and sorted(cur.members) != sorted(g.members)
        )
        if superseded and self.name not in cur.members:
            _M_FENCED.labels(plane=plane).inc()
            return True
        return False

    def await_generation(
        self,
        world_size: int,
        timeout_s: float = 60.0,
        min_gen: int = 0,
        poll_s: float = 0.1,
    ) -> Generation:
        """Adopt the current generation once it includes this member; if
        none exists, the lowest-named of the first ``world_size``
        registrants commits generation 1."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            g = self.read_generation()
            if g is not None and g.gen > min_gen and self.name in g.members:
                self.adopt(g)
                return g
            if min_gen == 0 and self._adopted is None:
                # bootstrap only before EVER adopting a generation: a
                # parked or resharded member re-awaiting must not fork a
                # fresh gen-1 world while blind to the winner's record.
                # Generation records are DURABLE (no TTL): a brand-new
                # gang may take over a committed gen only when every
                # incumbent member is gone from the roster — it then
                # CONTINUES the sequence (gen+1, CAS on the incumbent
                # gen), never rewinds it; a single live incumbent blocks
                # the takeover (grow-back owns joining a live gang)
                ros = self.roster()
                names = sorted(ros or {})
                incumbent_alive = g is not None and any(
                    m in (ros or {}) for m in g.members
                )
                if (
                    not incumbent_alive
                    and self.name in names
                    and len(names) >= world_size
                    and self.name == names[0]
                ):
                    base = g.gen if g is not None else 0
                    try:
                        return self.commit_generation(
                            Generation(
                                gen=base + 1, members=names[:world_size]
                            ),
                            expected_gen=base,
                        )
                    except (QuorumLostError, GenerationConflictError):
                        pass  # lost the race or the quorum: keep polling
            time.sleep(poll_s)
        raise TimeoutError(
            f"member {self.name!r}: no generation including me appeared "
            f"within {timeout_s:g}s (world_size={world_size}, "
            f"current={self.read_generation()})"
        )

    def close(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        if self._artifact_srv is not None:
            try:
                self._artifact_srv.stop()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        from mmlspark_tpu.io.clients import send_request
        from mmlspark_tpu.io.http_schema import HTTPRequestData

        for url in self.registry_urls:
            try:
                send_request(
                    HTTPRequestData(
                        url, "DELETE", {"Content-Type": "application/json"},
                        json.dumps({
                            "name": f"{self.service}-gang",
                            "host": self.name, "port": self.advertise_port,
                        }),
                    ),
                    timeout=5.0,
                )
            except Exception:  # noqa: BLE001 — registry may be gone
                pass


# -- the TCP allreduce --------------------------------------------------------


class _PendingReduce:
    """Handle for an in-flight :meth:`TcpReducer.allreduce_async`."""

    def __init__(self) -> None:
        self._ev = threading.Event()
        self._val: Any = None
        self._exc: Optional[BaseException] = None

    def _set(self, val: Any = None, exc: Optional[BaseException] = None):
        self._val, self._exc = val, exc
        self._ev.set()

    def result(self, timeout_s: Optional[float] = None) -> np.ndarray:
        if not self._ev.wait(timeout_s):
            raise TimeoutError("allreduce_async result not ready")
        if self._exc is not None:
            raise self._exc
        return self._val


class TcpReducer:
    """Framed-TCP sum-allreduce among one generation's members.

    Two wire patterns, both accumulating in f64 in **sorted-member
    order** so every member computes the bit-identical total:

    - ``mode="ring"`` (default): chunked ring reduce-scatter +
      allgather. The flat payload splits into ``world`` contiguous
      segments (``partition_bounds`` — the same math that slices the
      dataset); member ``i`` owns segment ``i``. Scatter phase: each
      member sends every OTHER owner's segment of its local contribution
      (raw input dtype — an f32 contribution upcasts to f64 exactly, so
      the wire carries half the bytes with zero precision loss); the
      owner, holding all ``world`` contributions of its segment, sums
      them in sorted-member order in f64. Gather phase: each owner sends
      its summed f64 segment to every peer. 2(w-1) steps of
      O(payload/world) each — per-member bytes drop from ``(w-1) * 8n``
      to ``(w-1)/w * (itemsize + 8) * n``, strictly less at every world
      size for f32 payloads and ~2x/w of full mesh for large worlds.
    - ``mode="mesh"``: the original everyone-sends-everything exchange,
      kept as the A/B baseline (bit-identical results by construction;
      tests pin ring == mesh byte-for-byte).

    Every member executes the identical sequence of collectives (the host
    growers are SPMD over the gang), so monotonically increasing ``seq``
    numbers pair frames without negotiation (a ring op consumes two: one
    per phase). :meth:`allreduce_async` runs the exchange on a dedicated
    worker thread so the growers can overlap the NEXT histogram block's
    build with this block's wire time — seqs are allocated on the
    calling thread, keeping the SPMD frame pairing deterministic.

    A peer whose frame never arrives AND whose registry heartbeats have
    lapsed raises :class:`HostLostError` — the socket-level failure the
    reference's LightGBM allreduce dies on becomes the detection signal.
    """

    def __init__(
        self,
        member: GangMember,
        generation: Generation,
        timeout_s: float = 60.0,
        connect_timeout_s: float = 10.0,
        mode: str = "ring",
    ):
        if mode not in ("ring", "mesh"):
            raise ValueError(f"unknown reduce mode {mode!r}")
        self.member = member
        self.gen = generation.gen
        self.members = sorted(generation.members)
        self.me = member.name
        self.mode = mode
        self.timeout_s = float(timeout_s)
        self.connect_timeout_s = float(connect_timeout_s)
        # same loss debounce as GangContext.on_round: a freshly
        # restarted registry's empty roster must not read as mass death
        self.loss_grace_s = max(1.0, 2.0 * member.heartbeat_s)
        # incarnation nonce: a content hash of the generation record,
        # identical on every member that adopted the SAME record —
        # frames from an aborted same-gen-number incarnation (the
        # membership-conflict path) key differently and can never be
        # consumed as this incarnation's sums
        self.nonce = zlib.crc32(json.dumps(
            [generation.gen, sorted(generation.members),
             generation.resume_round, generation.committer],
        ).encode()) & 0xFFFFFFFF
        self.seq = 0
        self._conns: dict = {}
        self._send_lock = threading.Lock()
        # recent outgoing frames, keyed (gen, nonce, seq, peer): the
        # retransmit source when a peer NACKs a CRC-torn frame (ring
        # frames differ per peer — each owner gets its own segment). The
        # gang is SPMD-lockstep, so peers only ever NACK recent seqs;
        # the cap covers a couple of in-flight overlapped ops
        self._sent_frames: dict = {}
        self._sent_cap = max(16, 6 * len(self.members))
        # (seq, peer) frames whose send transiently failed — retried at
        # each roster check (a dropped send must not wedge the PEER)
        self._unsent: set = set()
        self.retransmits = 0
        self.payload_bytes_sent = 0
        self.ring_steps = 0
        self.ops = 0
        self.world = len(self.members)
        self._rank = self.members.index(self.me) if self.me in self.members else 0
        # async worker: one thread, FIFO — started on first use
        self._jobs: Any = None
        self._worker: Optional[threading.Thread] = None
        self._failed: Optional[BaseException] = None
        member.drop_stale_frames(self.gen)
        member._attach_reducer(self)

    def _conn(self, peer: str) -> socket.socket:
        c = self._conns.get(peer)
        if c is not None:
            return c
        ros = self.member.roster()
        if ros is None:
            # blind (no registry answered) is transient, not a death
            raise OSError("no registry reachable for peer lookup")
        e = ros.get(peer)
        if e is None:
            raise HostLostError([peer], self.gen, "peer not on roster")
        c = socket.create_connection(
            (e.get("addr", "127.0.0.1"), int(e["port"])),
            timeout=self.connect_timeout_s,
        )
        c.settimeout(None)
        self._conns[peer] = c
        return c

    # -- frame bookkeeping ----------------------------------------------------

    def _post_frames(self, seq: int, payloads: dict) -> None:
        """Build, cache and (best-effort) send one frame per peer.
        ``payloads``: peer -> payload bytes. A payload OBJECT shared by
        several peers (the whole mesh exchange; the ring gather phase)
        serializes into ONE frame that every cache entry references —
        w-1 identical multi-MB frames would otherwise be copied and
        retained per collective. Failed sends land in ``_unsent`` and
        are retried at every roster check."""
        name = self.me.encode()
        frame_for: dict = {}  # id(payload) -> built frame
        with self._send_lock:
            for peer, payload in payloads.items():
                frame = frame_for.get(id(payload))
                if frame is None:
                    head = struct.pack(
                        _FRAME_HEAD, self.gen, seq, self.nonce,
                        zlib.crc32(payload) & 0xFFFFFFFF,
                        len(name), len(payload),
                    )
                    frame = head + name + payload
                    frame_for[id(payload)] = frame
                self._sent_frames[(self.gen, self.nonce, seq, peer)] = frame
                while len(self._sent_frames) > self._sent_cap:
                    del self._sent_frames[next(iter(self._sent_frames))]
                try:
                    self._conn(peer).sendall(frame)
                    self.payload_bytes_sent += len(payload)
                    _M_PAYLOAD_BYTES.labels(mode=self.mode).inc(len(payload))
                except (OSError, HostLostError):
                    # a dead socket is not yet a dead HOST: the roster
                    # decides at the next check (may be mid-restart)
                    self._conns.pop(peer, None)
                    self._unsent.add((seq, peer))

    def _resend_unsent(self) -> None:
        with self._send_lock:
            for seq, peer in list(self._unsent):
                frame = self._sent_frames.get(
                    (self.gen, self.nonce, seq, peer)
                )
                if frame is None:
                    self._unsent.discard((seq, peer))
                    continue
                try:
                    self._conn(peer).sendall(frame)
                    self._unsent.discard((seq, peer))
                    n = len(frame) - _FRAME_HEAD_LEN - len(self.me.encode())
                    self.payload_bytes_sent += n
                    _M_PAYLOAD_BYTES.labels(mode=self.mode).inc(n)
                except (OSError, HostLostError):
                    self._conns.pop(peer, None)

    def _collect(self, seq: int, senders: list) -> dict:
        """Wait for one frame from each of ``senders`` at ``seq``.
        Shared loss machinery of both modes: re-send transiently-failed
        frames, re-NACK CRC-dropped keys, consult the roster's loss
        policy, and surface wedged peers at the timeout."""
        got: dict = {}
        deadline = time.monotonic() + self.timeout_s
        next_roster_check = time.monotonic() + 0.5
        while len(got) < len(senders):
            missing = [p for p in senders if p not in got]
            buf = self.member.take_frame(
                self.gen, self.nonce, seq, missing[0], 0.05
            )
            if buf is not None:
                got[missing[0]] = buf
                continue
            now = time.monotonic()
            if now >= next_roster_check:
                next_roster_check = now + 0.5
                self._resend_unsent()
                for p in missing:
                    # a frame we dropped for bad CRC: re-NACK until the
                    # clean retransmit lands (the first NACK — sent by
                    # the read loop — may itself have been lost)
                    if self.member.crc_dropped(
                        (self.gen, self.nonce, seq, p)
                    ):
                        self.send_nack(p, self.gen, self.nonce, seq)
                # one shared loss policy with on_round (blindness is
                # not death; grace debounces): GangMember.declared_dead
                dead = self.member.declared_dead(
                    missing, self.member.roster(), self.loss_grace_s
                )
                if dead:
                    now_m = time.monotonic()
                    latency = [
                        now_m - self.member.last_seen.get(p, now_m)
                        for p in dead
                    ]
                    for lat in latency:
                        _M_DETECT.observe(max(0.0, lat))
                    raise HostLostError(
                        dead, self.gen,
                        f"allreduce seq {seq}: no frame, heartbeats lapsed "
                        f"(detect latency ~{max(latency):.2f}s)",
                    )
                g = self.member.read_generation()
                if g is not None and g.gen > self.gen:
                    raise WorldChangedError(g.gen)
                # the minority side of a partition: peers AND registries
                # unreachable. Waiting out the full allreduce timeout
                # would leave a zombie training long after the majority
                # resharded — park as soon as the quorum grace lapses
                if self.member.quorum_age_s() > self.member.quorum_grace_s:
                    raise QuorumLostError(
                        f"allreduce seq {seq}: no registry majority for "
                        f"{self.member.quorum_age_s():.1f}s"
                    )
            if now >= deadline:
                raise HostLostError(
                    missing, self.gen,
                    f"allreduce seq {seq} timed out after "
                    f"{self.timeout_s:g}s with live heartbeats — wedged "
                    "peer(s)",
                )
        return got

    # -- the collectives ------------------------------------------------------

    def allreduce(self, arr: np.ndarray) -> np.ndarray:
        """Sum ``arr`` across the gang; returns the same dtype/shape.
        World 1 is an exact no-op (bit-identical to unsharded training)."""
        if self.world <= 1:
            return arr
        if self._failed is not None:
            raise self._failed
        with self._send_lock:
            seq = self.seq
            self.seq += 2 if self.mode == "ring" else 1
        return self._allreduce_at(arr, seq)

    def allreduce_async(self, arr: np.ndarray) -> _PendingReduce:
        """Start an allreduce on the reducer's worker thread and return
        a handle. Seqs are allocated HERE, on the calling thread — every
        member submits the identical op sequence, so frames pair even
        though the wire work happens off-thread. The caller overlaps the
        next histogram block's build with this block's wire time."""
        p = _PendingReduce()
        if self.world <= 1:
            p._set(val=arr)
            return p
        if self._failed is not None:
            p._set(exc=self._failed)
            return p
        with self._send_lock:
            seq = self.seq
            self.seq += 2 if self.mode == "ring" else 1
            if self._jobs is None:
                import queue as _queue

                self._jobs = _queue.Queue()
                self._worker = threading.Thread(
                    target=self._work_loop,
                    name=f"reduce-{self.me}-g{self.gen}", daemon=True,
                )
                self._worker.start()
        self._jobs.put((arr, seq, p))
        return p

    def _work_loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            arr, seq, pending = job
            if self._failed is not None:
                # once the gang broke, later queued ops must fail fast,
                # not each burn a full timeout
                pending._set(exc=self._failed)
                continue
            try:
                pending._set(val=self._allreduce_at(arr, seq))
            except BaseException as e:  # noqa: BLE001 — re-raised in the caller
                self._failed = e
                pending._set(exc=e)

    def _allreduce_at(self, arr: np.ndarray, seq: int) -> np.ndarray:
        t0 = time.perf_counter()
        self.ops += 1
        src = np.asarray(arr)
        if self.mode == "ring":
            out = self._allreduce_ring(src, seq)
        else:
            out = self._allreduce_mesh(src, seq)
        _M_ALLREDUCE.observe(time.perf_counter() - t0)
        return out

    def _allreduce_mesh(self, src: np.ndarray, seq: int) -> np.ndarray:
        """The legacy full-mesh exchange: every member sends its full
        f64 contribution to every peer; everyone sums locally."""
        x = np.ascontiguousarray(src.astype(np.float64))
        peers = [m for m in self.members if m != self.me]
        payload = x.tobytes()  # serialized ONCE; _post_frames shares it
        self._post_frames(seq, {p: payload for p in peers})
        got = self._collect(seq, peers)
        bufs = {self.me: x.reshape(-1)}
        for p, buf in got.items():
            bufs[p] = np.frombuffer(buf, np.float64)
        total = bufs[self.members[0]].astype(np.float64, copy=True)
        for m in self.members[1:]:
            total = total + bufs[m]
        return total.reshape(x.shape).astype(src.dtype)

    def _allreduce_ring(self, src: np.ndarray, seq: int) -> np.ndarray:
        """Ring reduce-scatter + allgather; seq (scatter) and seq+1
        (gather). Accumulation order per element is members[0..w-1] in
        f64 — bit-identical to the mesh exchange's sum."""
        # contributions travel in the input dtype when that upcasts to
        # f64 exactly (f32/f64); anything else is cast to f64 up front,
        # exactly like the mesh path
        wire_dtype = src.dtype if src.dtype in (
            np.dtype(np.float32), np.dtype(np.float64)
        ) else np.dtype(np.float64)
        flat = np.ascontiguousarray(src.astype(wire_dtype)).reshape(-1)
        w = self.world
        bounds = partition_bounds(flat.size, w)
        rank = self._rank
        # fault point elastic.ring_step: fires before each ring step on
        # each member (context names phase/step); a delay stalls the
        # pipeline (visible in allreduce seconds), an error kills the
        # trainer — the supervisor-restart path
        # -- scatter: send every other owner its segment of my contribution
        payloads = {}
        for t in range(1, w):
            j = (rank + t) % w
            peer = self.members[j]
            faults.inject(
                "elastic.ring_step",
                context={"phase": "scatter", "step": t, "peer": peer},
            )
            lo, hi = bounds[j]
            payloads[peer] = flat[lo:hi].tobytes()
            self.ring_steps += 1
            _M_RING_STEPS.labels(phase="scatter").inc()
        self._post_frames(seq, payloads)
        peers = [m for m in self.members if m != self.me]
        got = self._collect(seq, peers)
        # -- owner sum: all w contributions of MY segment, sorted order
        lo, hi = bounds[rank]
        seg_len = hi - lo
        contrib = {self.me: flat[lo:hi]}
        for p, buf in got.items():
            piece = np.frombuffer(buf, wire_dtype)
            if piece.size != seg_len:
                # only reachable through a CRC-colliding corruption of a
                # resized frame — refuse to sum garbage
                raise HostLostError(
                    [p], self.gen,
                    f"ring segment from {p} has {piece.size} elements, "
                    f"expected {seg_len}",
                )
            contrib[p] = piece
        total_seg = contrib[self.members[0]].astype(np.float64, copy=True)
        for m in self.members[1:]:
            total_seg = total_seg + contrib[m]
        # -- allgather: every owner distributes its summed f64 segment
        seg_bytes = np.ascontiguousarray(total_seg).tobytes()
        payloads = {}
        for t in range(1, w):
            peer = self.members[(rank + t) % w]
            faults.inject(
                "elastic.ring_step",
                context={"phase": "gather", "step": t, "peer": peer},
            )
            payloads[peer] = seg_bytes
            self.ring_steps += 1
            _M_RING_STEPS.labels(phase="gather").inc()
        self._post_frames(seq + 1, payloads)
        got = self._collect(seq + 1, peers)
        out = np.empty(flat.size, np.float64)
        out[lo:hi] = total_seg
        for j, m in enumerate(self.members):
            if m == self.me:
                continue
            jlo, jhi = bounds[j]
            piece = np.frombuffer(got[m], np.float64)
            if piece.size != jhi - jlo:
                raise HostLostError(
                    [m], self.gen,
                    f"ring gather segment from {m} has {piece.size} "
                    f"elements, expected {jhi - jlo}",
                )
            out[jlo:jhi] = piece
        return out.reshape(src.shape).astype(src.dtype)

    def send_nack(self, peer: str, gen: int, nonce: int, seq: int) -> None:
        """Tell ``peer`` its (gen, seq) frame arrived torn — control
        frame with ``nbytes == -1``; the peer retransmits from its
        recent-frame cache. Best-effort: a lost NACK is re-sent by the
        waiting allreduce at its next roster check."""
        head = struct.pack(
            _FRAME_HEAD, gen, seq, nonce, 0,
            len(self.me.encode()), _NACK_NBYTES,
        )
        with self._send_lock:
            try:
                self._conn(peer).sendall(head + self.me.encode())
            except (OSError, HostLostError):
                self._conns.pop(peer, None)

    def handle_nack(self, peer: str, gen: int, nonce: int, seq: int) -> None:
        """A peer reported our frame corrupt: retransmit it. Called from
        the member's read loop thread; a frame no longer cached (ancient
        seq, different incarnation) is ignored — the peer's timeout path
        handles it as peer-loss."""
        with self._send_lock:
            frame = self._sent_frames.get((gen, nonce, seq, peer))
            if frame is None:
                return
            try:
                self._conn(peer).sendall(frame)
            except (OSError, HostLostError):
                self._conns.pop(peer, None)
                return
        self.retransmits += 1
        _M_RETRANSMITS.inc()

    def close(self) -> None:
        self.member._detach_reducer(self)
        if self._jobs is not None:
            self._jobs.put(None)
        for c in self._conns.values():
            try:
                c.close()
            except OSError:
                pass
        self._conns.clear()


# -- the per-generation training context --------------------------------------


class GangContext:
    """What ``train()`` and the host growers consult while a generation
    trains. Installed process-globally with :func:`activate` (the host
    growers run on callback threads, so a thread-local would miss)."""

    def __init__(
        self,
        member: GangMember,
        generation: Generation,
        n_rows: int,
        n_partitions: int,
        checkpoint_every: int = 10,
        reducer: Optional[TcpReducer] = None,
        stragglers: Optional[StragglerTracker] = None,
        evict_stragglers: bool = False,
        min_world: int = 1,
        allow_growback: bool = True,
        global_rows: Optional[np.ndarray] = None,
        ckpt_dir: Optional[str] = None,
        all_write: bool = False,
        voting_top_k: Optional[int] = None,
    ):
        """``global_rows``: the full global feature matrix when the host
        already has it (the ``fleet train`` data model: every host loads
        the same ``--data``) — :meth:`binning_rows` then avoids
        allreducing the entire dataset just to re-fit bin bounds.

        ``all_write``: every member writes checkpoints to its own (host-
        local) ``ckpt_dir`` instead of only the coordinator writing a
        shared one — the artifact-mode data model, where checkpoint
        bytes replicate by content-addressed pull, not by shared mount.
        The gather collective still runs on every member either way, so
        the written state is bit-identical across the gang."""
        self.member = member
        self.generation = generation
        self.members = sorted(generation.members)
        self.world = len(self.members)
        self.global_n = int(n_rows)
        self.n_partitions = int(n_partitions)
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.reducer = reducer
        self.straggler_tracker = stragglers
        self.evict_stragglers = evict_stragglers
        self.min_world = max(1, int(min_world))
        self.allow_growback = allow_growback
        self.global_rows = global_rows
        self.ckpt_dir = ckpt_dir
        self.all_write = bool(all_write)
        # voting-parallel (PV-Tree): the host growers exchange only the
        # top-2K candidate features' histogram columns instead of the
        # full plane; None = full data-parallel
        self.voting_top_k = (
            int(voting_top_k) if voting_top_k else None
        )
        # loss debounce: a peer missing from the roster is only declared
        # dead once its last sighting is older than this — an
        # answering-but-freshly-restarted registry returns an EMPTY
        # roster, and that window must not read as "everyone died"
        self.loss_grace_s = max(1.0, 2.0 * member.heartbeat_s)
        self.lo, self.hi = member_row_slice(
            n_rows, n_partitions, self.members, member.name
        )
        self.lost: list = []
        self.world_changed: Optional[int] = None
        self.quorum_lost = False
        self.rounds_seen = 0
        self._round_t = time.monotonic()
        self._last_it = 0
        self.started_t = time.monotonic()
        self.first_round_done_t: Optional[float] = None
        self._join_seq = 0
        self.flagged_stragglers: list = []
        # where replicate-before-commit bookkeeping lands (the owning
        # ElasticTrainer points this at its status dict)
        self.status_sink: Optional[dict] = None

    # -- data movement --------------------------------------------------------

    @property
    def is_coordinator(self) -> bool:
        """The generation coordinator (lowest-named member): runs the
        grow-back / straggler policy at checkpoint boundaries and is the
        shared-dir mode's sole checkpoint writer."""
        return self.member.name == self.members[0]

    @property
    def is_writer(self) -> bool:
        """Does THIS member persist checkpoints? Shared-dir mode: only
        the coordinator (two writers on one mount would race). Artifact
        mode (``all_write``): everyone — each host's dir is its own, and
        the bytes are bit-identical by the gather-collective contract.
        Every member participates in the gather either way."""
        return self.all_write or self.is_coordinator

    def allreduce(self, arr: np.ndarray) -> np.ndarray:
        if self.reducer is None or self.world <= 1:
            return arr
        try:
            return self.reducer.allreduce(arr)
        except HostLostError as e:
            self.lost = e.lost
            raise
        except WorldChangedError as e:
            self.world_changed = e.gen
            raise
        except QuorumLostError:
            self.quorum_lost = True
            raise

    def allreduce_blocks(self, builders: list) -> list:
        """Compute/communication overlap: ``builders`` are zero-arg
        callables producing arrays (e.g. per-feature-block histograms).
        Block ``i``'s allreduce rides the reducer's worker thread while
        block ``i+1`` is still being BUILT — double-buffered (at most
        two blocks in flight), critical-path ordered (results return in
        submission order). Elementwise sums are blocking-invariant, so
        the concatenated result is bit-identical to one whole-plane
        allreduce."""
        if self.reducer is None or self.world <= 1:
            return [b() for b in builders]
        try:
            out: list = []
            pending: list = []
            for b in builders:
                if pending:
                    # this block's build runs while the previous
                    # block(s) are on the wire — the overlap the
                    # counter advertises
                    _M_OVERLAP_BLOCKS.inc()
                arr = b()
                pending.append(self.reducer.allreduce_async(arr))
                if len(pending) >= 2:
                    # true double-buffer: harvest the older op before
                    # building a third block, bounding peak memory to
                    # two blocks (cube + wire frames) at any moment
                    out.append(pending.pop(0).result())
            while pending:
                out.append(pending.pop(0).result())
            return out
        except HostLostError as e:
            self.lost = e.lost
            raise
        except WorldChangedError as e:
            self.world_changed = e.gen
            raise
        except QuorumLostError:
            self.quorum_lost = True
            raise

    def all_rows(self, local: np.ndarray) -> np.ndarray:
        """Local rows -> the (global_n, ...) array in global row order
        (scatter + sum-allreduce: every element is one member's value
        plus zeros, so the result is EXACT at any wire dtype). f32
        payloads stay f32 on the scatter wire — half the checkpoint
        gather's bytes at zero precision cost; the ring's owner still
        accumulates in f64. The collective every member runs at
        checkpoint time."""
        local = np.asarray(local)
        if self.world <= 1:
            return local
        wire = (
            np.float32 if local.dtype == np.float32 else np.float64
        )
        out = np.zeros((self.global_n,) + local.shape[1:], wire)
        out[self.lo:self.hi] = local
        return self.allreduce(out).astype(local.dtype)

    def take_local(self, global_arr: np.ndarray) -> np.ndarray:
        return np.asarray(global_arr)[self.lo:self.hi]

    def binning_rows(self, local: np.ndarray) -> np.ndarray:
        """The global rows bin bounds are fitted on. When the host holds
        the full dataset (``global_rows``), hand it over directly —
        bit-identical to the gather, with zero network traffic; the
        allreduce path remains for gangs whose members only hold their
        own slice."""
        if self.global_rows is not None:
            return np.asarray(self.global_rows, local.dtype)
        return self.all_rows(local)

    # -- round boundary hooks --------------------------------------------------

    def on_round(self, it: int) -> None:
        """Called by the training loop entering round/chunk ``it``:
        update the straggler EWMA, run the detection check (fault point
        ``elastic.detect``), and — on checkpoint boundaries, coordinator
        only — grow-back and straggler policy. Raises
        :class:`HostLostError` / :class:`WorldChangedError` to abort."""
        # stall forensics: a round that never reaches the next boundary
        # (e.g. an allreduce wedged on a dead peer's half-open socket)
        # auto-dumps all-thread stacks after the deadline
        watchdog.tick("elastic.round")
        now = time.monotonic()
        if self.rounds_seen > 0:
            # boundaries are CHUNK boundaries on the scan-fused path and
            # ROUND boundaries on the per-iteration path: amortize over
            # the rounds actually elapsed since the last boundary
            dt = (now - self._round_t) / max(1, it - self._last_it)
            a = 0.3
            self.member.ewma_s = (
                dt if self.member.ewma_s == 0.0
                else a * dt + (1 - a) * self.member.ewma_s
            )
            _M_ROUND_EWMA.labels(host=self.member.name).set(
                self.member.ewma_s
            )
            if self.first_round_done_t is None:
                self.first_round_done_t = now
        self._round_t = now
        self._last_it = it
        self.rounds_seen += 1
        if (
            self.world > 1 and self.rounds_seen == 2
            and self.reducer is not None
            and self.reducer.seq <= self._join_seq
        ):
            raise RuntimeError(
                "elastic gang trained a round without a single gang "
                "allreduce — the host histogram lowering was not selected "
                "(elastic training requires the CPU host growers: "
                "shard=False and MMLSPARK_TPU_HIST_HOST!=0)"
            )
        # fault point elastic.detect: a payload names a member to declare
        # lost without killing anything (chaos for the reshard path); an
        # injected error is the detector itself failing
        forced = faults.inject(
            "elastic.detect", context={"gen": self.generation.gen, "it": it}
        )
        ros = self.member.roster()
        # roster None = every registry unreachable; a roster that lacks
        # even OUR OWN entry is a registry that just restarted and has
        # not collected heartbeats yet. Blindness in either form is not
        # evidence of death — hold rather than split-brain the gang.
        # For visible peers, a miss only counts once the last sighting
        # is older than the loss grace (debounces the re-register race).
        # Sustained blindness past the quorum grace is different from a
        # blip: this member is (at best) on the minority side of a
        # partition, and in a multi-member gang it must PARK rather than
        # train into an epoch the majority is entitled to reshard away.
        if (
            self.world > 1
            and self.member.quorum_age_s() > self.member.quorum_grace_s
        ):
            self.quorum_lost = True
            raise QuorumLostError(
                f"round {it}: no registry majority for "
                f"{self.member.quorum_age_s():.1f}s"
            )
        now_m = time.monotonic()
        lost = self.member.declared_dead(
            [m for m in self.members if m != self.member.name],
            ros, self.loss_grace_s,
        )
        if isinstance(forced, str) and forced in self.members:
            lost.append(forced)
        if lost:
            for m in lost:
                _M_DETECT.observe(
                    max(0.0, now_m - self.member.last_seen.get(m, now_m))
                )
            self.lost = sorted(set(lost))
            raise HostLostError(self.lost, self.generation.gen,
                                "heartbeats lapsed at round boundary")
        g = self.member.read_generation()
        if g is not None and (
            g.gen > self.generation.gen
            or (
                # same gen number, DIFFERENT members: racing survivors
                # with divergent lost-sets committed conflicting records
                # and the registry's last writer won — defer to it
                g.gen == self.generation.gen
                and sorted(g.members) != self.members
            )
        ):
            self.world_changed = g.gen
            raise WorldChangedError(g.gen)
        if (
            it % self.checkpoint_every == 0 and self.is_coordinator
            and ros is not None
        ):
            self._coordinate(ros, it)

    def _freeze_resume(self, next_gen: int, it: int) -> tuple:
        """Artifact-mode resume point for a grow/straggler reshard:
        freeze the latest checkpoint, ``put()`` it as a content-
        addressed artifact, and return ``(snapshot, digest,
        resume_round)`` — so a joiner with an empty (host-local) dir can
        pull the exact agreed bytes over HTTP. Shared-dir mode returns
        ``(None, None, it)``: members resume from the shared LATEST as
        before."""
        store = self.member.artifact_store
        if store is None or not self.ckpt_dir:
            return None, None, it
        if self.member.fenced_out("artifact"):
            # the epoch moved past us while we were deciding to resize:
            # a fenced-out writer must not persist or advertise snapshot
            # bytes (the commit below would lose its CAS anyway — this
            # refuses the WRITE, not just the record)
            cur = self.member.read_generation()
            self.world_changed = (
                cur.gen if cur is not None else self.generation.gen + 1
            )
            raise WorldChangedError(self.world_changed)
        snap, resume_round = snapshot_checkpoint(self.ckpt_dir, next_gen)
        if snap is None:
            return None, None, it
        try:
            ref = store.put(snap, name=os.path.basename(snap))
        except Exception:  # noqa: BLE001 — a refused put degrades to
            # shared-dir semantics rather than blocking the resize
            return snap, None, resume_round
        return snap, ref.digest, resume_round

    def _coordinate(self, ros: dict, it: int) -> None:
        """Checkpoint-boundary duties of the generation coordinator:
        grow-back (admit re-registered hosts) and straggler policy."""
        joiners = sorted(
            j for j in set(ros) - set(self.members)
            # an evicted straggler only re-enters with a fresh boot (a
            # restarted process); the same slow process stays out
            if self.generation.evicted.get(j) != ros[j].get("boot")
        )
        # capacity: every member must own at least one partition — a
        # 0-row member would gang-sum empty-gradient NaNs into everyone
        joiners = joiners[:max(0, self.n_partitions - self.world)]
        if joiners and self.allow_growback and it > 0:
            snap, digest, resume_round = self._freeze_resume(
                self.generation.gen + 1, it
            )
            g = Generation(
                gen=self.generation.gen + 1,
                members=sorted(set(self.members) | set(joiners)),
                reason="grow",
                resume_round=resume_round,
                snapshot=snap,
                snapshot_digest=digest,
            )
            # replicate-before-commit: the joiners (and any survivor
            # that outlives this host) must be able to pull the agreed
            # resume bytes even if this host dies right after the CAS
            replicate_snapshot(
                self.member, digest, g.members, status=self.status_sink
            )
            self.member.commit_generation(g)
            _M_RESHARDS.labels(reason="grow").inc()
            self.world_changed = g.gen
            raise WorldChangedError(g.gen)
        if self.straggler_tracker is not None and self.world > 1:
            ewmas = {
                m: float(ros[m].get("ewma_ms", 0.0)) / 1e3
                for m in self.members if m in ros
            }
            flagged = self.straggler_tracker.observe(ewmas)
            self.flagged_stragglers = flagged
            _M_STRAGGLERS.set(len(flagged))
            evictable = [m for m in flagged if m != self.member.name]
            if (
                self.evict_stragglers and evictable
                and self.world - len(evictable) >= self.min_world
            ):
                snap, digest, resume_round = self._freeze_resume(
                    self.generation.gen + 1, it
                )
                g = Generation(
                    gen=self.generation.gen + 1,
                    members=[m for m in self.members if m not in evictable],
                    reason="straggler",
                    resume_round=resume_round,
                    snapshot=snap,
                    snapshot_digest=digest,
                    evicted={
                        **self.generation.evicted,
                        **{m: ros.get(m, {}).get("boot") for m in evictable},
                    },
                )
                replicate_snapshot(
                    self.member, digest, g.members, status=self.status_sink
                )
                self.member.commit_generation(g)
                _M_RESHARDS.labels(reason="straggler").inc()
                self.world_changed = g.gen
                raise WorldChangedError(g.gen)

    # -- abort classification --------------------------------------------------

    def abort_reason(self, exc: BaseException) -> Optional[Exception]:
        """Was ``exc`` a gang change? In-callback failures surface as
        ``XlaRuntimeError`` with the real cause recorded on this context,
        so classify by state, not by exception type."""
        if isinstance(exc, (
            HostLostError, WorldChangedError,
            QuorumLostError, GenerationConflictError,
        )):
            return exc
        if self.lost:
            return HostLostError(self.lost, self.generation.gen)
        if self.world_changed is not None:
            return WorldChangedError(self.world_changed)
        if self.quorum_lost:
            return QuorumLostError("recorded on gang context")
        return None

    def join(self, timeout_s: float = 30.0) -> None:
        """Generation-formation barrier: one tiny allreduce proves every
        member's transport before any training work. A member that died
        between commit and join surfaces as a
        :class:`~mmlspark_tpu.parallel.distributed.BarrierTimeoutError`
        naming the missing host (the same diagnostic shape the SPMD
        barrier raises)."""
        if self.reducer is None or self.world <= 1:
            return
        old = self.reducer.timeout_s
        self.reducer.timeout_s = timeout_s
        try:
            total = self.reducer.allreduce(np.ones(1))
            if int(round(float(total[0]))) != self.world:
                raise RuntimeError(
                    f"gen {self.generation.gen} join barrier summed "
                    f"{total[0]} != world {self.world}"
                )
        except HostLostError as e:
            raise BarrierTimeoutError(
                f"elastic-gen-{self.generation.gen}", timeout_s,
                missing=e.lost,
            ) from e
        finally:
            self.reducer.timeout_s = old
            self._join_seq = self.reducer.seq

    def healthy(self) -> bool:
        return not self.lost and self.world_changed is None

    def close(self) -> None:
        watchdog.disarm("elastic.round")  # a finished gang is not a stall
        if self.reducer is not None:
            self.reducer.close()


# -- process-global active gang (callback threads must see it) ---------------

_ACTIVE_GANG: Optional[GangContext] = None


def active_gang() -> Optional[GangContext]:
    return _ACTIVE_GANG


@contextlib.contextmanager
def activate(gang: GangContext) -> Iterator[GangContext]:
    global _ACTIVE_GANG
    if _ACTIVE_GANG is not None:
        raise RuntimeError("one elastic gang per process")
    _ACTIVE_GANG = gang
    try:
        yield gang
    finally:
        _ACTIVE_GANG = None


def gang_sum() -> Optional[Callable[[np.ndarray], np.ndarray]]:
    """The host growers' hook: a summing callable when a multi-member
    gang is active, else None (the common case costs one global read)."""
    g = _ACTIVE_GANG
    if g is None or g.world <= 1:
        return None
    return g.allreduce


def gang_blocks() -> Optional[Callable[[list], list]]:
    """The host growers' overlap hook: a callable summing a LIST of
    lazily-built arrays with block ``i``'s wire time hidden behind block
    ``i+1``'s build (GangContext.allreduce_blocks), else None."""
    g = _ACTIVE_GANG
    if g is None or g.world <= 1 or g.reducer is None:
        return None
    return g.allreduce_blocks


def gang_voting_k() -> Optional[int]:
    """Voting-parallel hook: the PV-Tree ``top_k`` when the active gang
    trains in voting mode (host growers exchange ballots + top-2K
    candidate columns instead of the full plane), else None."""
    g = _ACTIVE_GANG
    if g is None or g.world <= 1 or g.reducer is None:
        return None
    return g.voting_top_k


def note_vote_round() -> None:
    """Growers report one completed voting exchange (metrics only)."""
    _M_VOTE_ROUNDS.inc()


# -- checkpoint snapshot (the bit-identity audit trail) -----------------------


def snapshot_checkpoint(ckpt_dir: str, gen: int) -> tuple:
    """Copy the LATEST complete checkpoint into
    ``<ckpt_dir>/reshard-g<gen>`` so the exact state a reshard resumed
    from survives later checkpoints — a fresh shrunk-world run from this
    snapshot must reproduce the survivor's booster bit-for-bit. Returns
    ``(snapshot_dir, resume_round)``; ``(None, 0)`` when no checkpoint
    exists yet (the reshard then restarts from round 0)."""
    latest = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(latest):
        return None, 0
    with open(latest) as f:
        name = f.read().strip()
    src = os.path.join(ckpt_dir, name)
    # the round rides the snapshot name: a leftover same-gen snapshot
    # from an earlier run of this ckpt_dir can never be silently reused
    # for a different resume point, and racing survivors whose LATEST
    # reads were skewed publish DISTINCT snapshots, each self-consistent
    # with the (snapshot, resume_round) pair its generation record names
    snap = os.path.join(ckpt_dir, f"reshard-g{gen:04d}-{name}")
    if not os.path.isdir(snap):
        # build in a private tmp, publish with one atomic rename —
        # racing survivors (divergent lost-sets can slip two committers
        # past the lowest-survivor gate) then FIRST-WIN cleanly instead
        # of interleaving rmtree/copytree on the same path
        tmp = snap + f".tmp-{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        shutil.copytree(src, os.path.join(tmp, name))
        with open(os.path.join(tmp, "LATEST"), "w") as f:
            f.write(name)
        try:
            os.rename(tmp, snap)
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)  # a racer won
    with open(os.path.join(snap, "LATEST")) as f:
        committed = f.read().strip()
    return snap, int(committed.split("-")[-1])


# -- the elastic trainer ------------------------------------------------------


def replicate_snapshot(
    member: GangMember,
    digest: Optional[str],
    members: list,
    status: Optional[dict] = None,
    timeout_s: float = 30.0,
) -> int:
    """Replicate-before-commit for the training plane: push a frozen
    snapshot to the other artifact ingresses of the generation about to
    be committed, so the committed record never names bytes only the
    coordinator's host holds — a coordinator SIGKILLed right after the
    commit leaves the resume point pullable from the survivors. Quorum
    target: a majority of the NEW world counting the local copy
    (``len(members) // 2`` remote confirms). Below quorum this DEGRADES
    (the commit proceeds; the shortfall is recorded in ``status``)
    instead of raising: a lone survivor must still be able to reshard,
    and a missed replica costs a re-pull from the coordinator or at
    worst a retrainable round — strict replication-before-ack lives on
    the publish planes (Publisher, experiments winner) where a lost
    blob means a lost model."""
    store = member.artifact_store
    if digest is None or store is None:
        return 0
    holders = member.artifact_holders(members)
    majority = len(members) // 2
    need = min(majority, len(holders))
    confirmed = 0
    if need > 0:
        try:
            confirmed = len(store.replicate(
                digest, holders, need=need, timeout_s=timeout_s,
                backoffs_ms=(100, 300),
            ))
        except Exception:  # noqa: BLE001 — below quorum / refused round
            confirmed = 0
    if status is not None:
        status["snapshot_replicas"] = confirmed
        if confirmed < majority:
            status["snapshot_replica_shortfalls"] = (
                status.get("snapshot_replica_shortfalls", 0) + 1
            )
    return confirmed


class ElasticTrainer:
    """Drive one host's share of an elastic GBDT training run.

    All hosts run this same loop (SPMD at the control plane): join the
    gang, adopt/form a generation, load the contiguous partition run
    assigned for that world, and train through ``models/gbdt/train.py``
    with gang-summed histograms. A lost host aborts the in-flight round,
    re-shards, and resumes from the latest checkpoint; a re-registered
    host is grown back at the next checkpoint boundary."""

    def __init__(
        self,
        registry_urls: Any,
        name: str,
        x: np.ndarray,
        y: np.ndarray,
        cfg: Any,
        ckpt_dir: str,
        n_partitions: int = 8,
        world_size: int = 1,
        service: str = "train",
        checkpoint_every: int = 2,
        heartbeat_s: float = 0.5,
        gen_timeout_s: float = 120.0,
        allreduce_timeout_s: float = 120.0,
        resume_from: Optional[str] = None,
        advertise_host: str = "127.0.0.1",
        straggler_factor: float = 3.0,
        straggler_rounds: int = 3,
        evict_stragglers: bool = False,
        min_world: int = 1,
        status_file: Optional[str] = None,
        allow_growback: bool = True,
        artifact_dir: Optional[str] = None,
        allreduce_port: int = 0,
        advertise_allreduce_port: Optional[int] = None,
        reduce_mode: str = "ring",
        stream: Optional[Callable[[], Iterator]] = None,
        n_rows: Optional[int] = None,
        n_features: Optional[int] = None,
        sketch_bits: int = 16,
        on_complete: Optional[Callable[[Any], None]] = None,
    ):
        """``artifact_dir``: enables **artifact mode** — ``ckpt_dir`` is
        treated as HOST-LOCAL (every member writes its own checkpoints),
        reshard snapshots are published as content-addressed artifacts
        out of an :class:`~mmlspark_tpu.serving.artifacts.ArtifactStore`
        rooted here, and a member whose disk lacks the agreed resume
        snapshot pulls it over HTTP from any surviving peer. Without it,
        the original shared-``ckpt_dir`` data model is unchanged.

        ``reduce_mode``: the gang allreduce wire pattern — ``ring``
        (chunked reduce-scatter + allgather, the default) or ``mesh``
        (the legacy everyone-sends-everything baseline). Bit-identical
        results either way; only bytes-on-the-wire differ.

        ``stream``: **out-of-core mode** — instead of in-memory ``x``/
        ``y``, a re-invocable factory yielding ``(x_chunk, y_chunk)``
        pairs in global row order (``load_streaming_data`` builds one
        from a spec; StreamingDataFrame adapts via
        ``stream_from_dataframe``). Each generation, the member streams
        its row slice twice: pass 1 feeds a per-host quantile sketch
        whose counts merge across the gang THROUGH THE REDUCER (bin
        bounds come out identical on every member at every world size,
        no global gather), pass 2 bins the slice into a uint8 matrix.
        The full float matrix never exists in memory; requires
        ``n_rows``/``n_features``."""
        self.registry_urls = registry_urls
        self.name = name
        self._stream = stream
        if stream is not None:
            if n_rows is None or n_features is None:
                raise ValueError(
                    "stream mode requires n_rows and n_features"
                )
            if x is not None or y is not None:
                raise ValueError("pass either x/y or stream, not both")
            self.x = self.y = None
            self.n = int(n_rows)
            self.n_features = int(n_features)
        else:
            self.x = np.asarray(x)
            self.y = np.asarray(y)
            self.n = len(self.x)
            self.n_features = int(self.x.shape[1])
        self.sketch_bits = int(sketch_bits)
        self.reduce_mode = reduce_mode
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        self.n_partitions = int(n_partitions)
        self.world_size = int(world_size)
        self.service = service
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.heartbeat_s = heartbeat_s
        self.gen_timeout_s = gen_timeout_s
        self.allreduce_timeout_s = allreduce_timeout_s
        self.resume_from = resume_from
        self.advertise_host = advertise_host
        self.straggler_factor = straggler_factor
        self.straggler_rounds = straggler_rounds
        self.evict_stragglers = evict_stragglers
        self.min_world = min_world
        self.status_file = status_file
        self.allow_growback = allow_growback
        # runs with the finished booster BEFORE the done status flush:
        # anything a status-file watcher will read the moment it sees
        # ``done`` (e.g. the exported model file) must be durable first
        self.on_complete = on_complete
        self.artifact_dir = artifact_dir
        # chaos-proxy/NAT support: bind the allreduce listener to a fixed
        # port and/or advertise a different one on the roster (peers dial
        # the advertised port — e.g. a ChaosProxy in front of this host)
        self.allreduce_port = int(allreduce_port)
        self.advertise_allreduce_port = advertise_allreduce_port
        self._member: Any = None
        self._store: Any = None
        if artifact_dir:
            from mmlspark_tpu.serving.artifacts import ArtifactStore

            self._store = ArtifactStore(artifact_dir)
        if self.world_size > self.n_partitions:
            # every member must own >= 1 partition (a 0-row member's
            # gang-summed empty gradients would poison the whole gang)
            raise ValueError(
                f"world_size {self.world_size} > n_partitions "
                f"{self.n_partitions}: every member needs at least one "
                "partition"
            )
        self.status: dict = {
            "name": name, "gen": 0, "members": [], "round": 0,
            "reshards": 0, "reshard_reasons": [], "resume_round": 0,
            "snapshot": None, "detect_latency_s": None,
            "reshard_to_first_round_s": None, "rounds_per_s_pre": None,
            "rounds_per_s_post": None, "done": False,
            "artifact_fetches": 0, "crc_drops": 0, "retransmits": 0,
            "reduce_mode": reduce_mode, "payload_bytes": 0,
            "ingest_payload_bytes": 0, "ring_steps": 0,
            "allreduce_ops": 0,
            # split-brain stance: parked == currently refusing to train
            # (minority side / lost CAS race); committed_gens are the
            # epochs THIS member won the commit for — the invariant
            # checker's at-most-one-writer law joins these across the
            # fleet's status files
            "parked": False, "parks": 0, "park_reasons": [],
            "committed_gens": [], "commit_acks": 0,
            # replicate-before-commit bookkeeping: confirmed replica
            # pushes of the latest frozen snapshot, and commits that
            # went ahead despite a replication shortfall (liveness
            # outranks strictness on the training plane)
            "snapshot_replicas": 0, "snapshot_replica_shortfalls": 0,
        }

    # -- status ---------------------------------------------------------------

    def _write_status(self) -> None:
        if not self.status_file:
            return
        if self._member is not None:
            self.status["crc_drops"] = self._member.crc_drops
            self.status["committed_gens"] = list(
                self._member.committed_gens
            )
            self.status["commit_acks"] = self._member.commit_acks
        tmp = self.status_file + f".tmp-{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(self.status, f)
            os.replace(tmp, self.status_file)
        except OSError:
            pass

    # -- the loop -------------------------------------------------------------

    def run(self) -> Any:
        from mmlspark_tpu.ops.histogram import use_host_hist

        # the gang data plane lives in the host growers' histograms —
        # refuse to train "distributed" through a lowering that would
        # silently never call the gang allreduce
        if not use_host_hist():
            raise RuntimeError(
                "elastic gang training requires the host histogram "
                "lowering (MMLSPARK_TPU_HIST_HOST)"
            )
        member = GangMember(
            self.registry_urls, self.name, service=self.service,
            advertise_host=self.advertise_host,
            heartbeat_s=self.heartbeat_s,
            artifact_store=self._store,
            listen_port=self.allreduce_port,
            advertise_port=self.advertise_allreduce_port,
        )
        self._member = member
        try:
            self._resolve_resume_from(member)
            gen = member.await_generation(
                self.world_size, timeout_s=self.gen_timeout_s
            )
            while True:
                booster = self._train_generation(member, gen)
                if booster is not None:
                    if self.on_complete is not None:
                        self.on_complete(booster)
                    self.status["done"] = True
                    self._write_status()
                    return booster
                g = member.read_generation()
                if (
                    g is not None and self.name not in g.members
                    and g.evicted.get(self.name) == member.boot
                ):
                    # evicted as a straggler: exit so a supervisor
                    # restart (fresh boot) can grow this host back in
                    raise HostLostError(
                        [self.name], g.gen,
                        "evicted as sustained straggler",
                    )
                # min_gen = gen - 1: a membership CONFLICT resolves to a
                # record with the SAME generation number (the registry's
                # last writer), which must still be adoptable
                gen = member.await_generation(
                    self.world_size, timeout_s=self.gen_timeout_s,
                    min_gen=gen.gen - 1,
                )
        finally:
            member.close()

    def _train_generation(self, member: GangMember, gen: Generation):
        """Train under one generation. Returns the booster on completion
        or None when the gang changed (the caller re-forms)."""
        from mmlspark_tpu.models.gbdt.train import train

        lo, hi = member_row_slice(
            self.n, self.n_partitions, gen.members, self.name
        )
        if hi <= lo:
            raise RuntimeError(
                f"member {self.name!r} holds no partitions at world "
                f"{len(gen.members)} (n_partitions={self.n_partitions})"
            )
        reducer = (
            TcpReducer(
                member, gen, timeout_s=self.allreduce_timeout_s,
                mode=self.reduce_mode,
            )
            if len(gen.members) > 1 else None
        )
        gang = GangContext(
            member, gen, n_rows=self.n,
            n_partitions=self.n_partitions,
            checkpoint_every=self.checkpoint_every, reducer=reducer,
            global_rows=self.x,
            stragglers=StragglerTracker(
                self.straggler_factor, self.straggler_rounds
            ),
            evict_stragglers=self.evict_stragglers,
            min_world=self.min_world,
            allow_growback=self.allow_growback,
            ckpt_dir=self.ckpt_dir,
            all_write=self._store is not None,
            voting_top_k=(
                self.cfg.top_k
                if getattr(self.cfg, "parallelism", "") == "voting_parallel"
                else None
            ),
        )
        gang.status_sink = self.status
        self.status.update(
            gen=gen.gen, members=sorted(gen.members), parked=False,
        )
        # per-round cost changes with the WORLD (a survivor histograms
        # twice the rows after a 2->1 shrink): a fresh generation gets a
        # fresh EWMA, so the straggler signal and the recorded
        # rounds-per-second never blend two world sizes (the r08->r12
        # throughput comparison depends on this honesty)
        member.ewma_s = 0.0
        self._write_status()
        # the agreed resume point: a reshard's snapshot when there is
        # one (every survivor resumes from the SAME state even if the
        # writer's live dir ran one chunk ahead), else the live dir
        # (crash-loop-safe auto-resume for supervisor-restarted hosts).
        # An explicit --resume-from only seeds the run BEFORE it has a
        # checkpoint of its own: later generations (grow/straggler carry
        # no snapshot) must resume from the run's LATEST, not roll the
        # whole gang back to the stale seed
        has_own_ckpt = os.path.exists(os.path.join(self.ckpt_dir, "LATEST"))
        snap = self._resolve_snapshot(member, gen)
        resume = snap or (
            self.resume_from if not has_own_ckpt else None
        ) or self.ckpt_dir
        resume_t0 = time.monotonic()
        try:
            gang.join(timeout_s=self.gen_timeout_s)
            if self._stream is not None:
                # out-of-core: two streaming passes over this member's
                # slice — sketch (merged via the reducer, a collective
                # EVERY member of the generation runs) then uint8 bins.
                # Per-generation by design: the merged counts are a pure
                # function of the global rows, so every generation (and
                # every world size) derives the identical mapper
                x_arg, y_arg = self._ingest_stream(reducer, lo, hi)
                if reducer is not None:
                    # the sketch merge consumed seqs; re-anchor the
                    # trained-without-allreduce guard at the loop start,
                    # and record the one-off ingestion wire cost so the
                    # bench's per-round payload math can subtract it
                    gang._join_seq = reducer.seq
                    self.status["ingest_payload_bytes"] += (
                        reducer.payload_bytes_sent
                    )
            else:
                x_arg, y_arg = self.x[lo:hi], self.y[lo:hi]
            with activate(gang):
                booster = train(
                    x_arg, y_arg, self.cfg, shard=False,
                    checkpoint_dir=self.ckpt_dir,
                    checkpoint_every=self.checkpoint_every,
                    resume_from=resume,
                )
            if gang.first_round_done_t is not None and gen.gen > 1:
                # generation adopted -> first completed round of the new
                # world: the reshard-to-first-new-round recovery time
                self.status["reshard_to_first_round_s"] = round(
                    gang.first_round_done_t - resume_t0, 4
                )
            self.status["round"] = int(self.cfg.num_iterations)
            if member.ewma_s:
                self.status["rounds_per_s_post"] = round(
                    1.0 / member.ewma_s, 3
                )
            self._write_status()
            return booster
        except BaseException as e:  # noqa: BLE001 — classify, then decide
            abort = gang.abort_reason(e)
            if abort is None:
                if isinstance(e, BarrierTimeoutError) and e.missing:
                    abort = HostLostError(e.missing, gen.gen, "join barrier")
                else:
                    raise
            # fault point train.round_abort: fires as the in-flight round
            # is abandoned; an injected delay stalls the abort -> reshard
            # turnaround (shows up in recovery timings), an error kills
            # the trainer (the supervisor-restart path)
            faults.inject(
                "train.round_abort",
                context={"gen": gen.gen, "cause": type(abort).__name__},
            )
            _M_ABORTS.inc()
            if member.ewma_s:
                # throughput at the old world size, as of the abort —
                # the denominator of "throughput retained after shrink"
                self.status["rounds_per_s_pre"] = round(
                    1.0 / member.ewma_s, 3
                )
            if isinstance(abort, HostLostError):
                try:
                    self._reshard(member, gen, abort)
                except (QuorumLostError, GenerationConflictError) as pe:
                    # the reshard commit could not win a majority (or
                    # lost the CAS): this member is the minority — park,
                    # never fork a minority world
                    self._park(member, gen, pe)
            elif isinstance(
                abort, (QuorumLostError, GenerationConflictError)
            ):
                self._park(member, gen, abort)
            return None
        finally:
            if reducer is not None:
                self.status["retransmits"] += reducer.retransmits
                self.status["payload_bytes"] += reducer.payload_bytes_sent
                self.status["ring_steps"] += reducer.ring_steps
                self.status["allreduce_ops"] += reducer.ops
            gang.close()

    def _ingest_stream(self, reducer: Optional[TcpReducer], lo: int, hi: int):
        """Out-of-core ingestion of this member's ``[lo, hi)`` slice.

        Pass 1 streams the slice through a :class:`QuantileSketch`
        (fixed d x 2^bits counts); the counts are summed across the gang
        by the reducer — the ONLY network the binning costs, chunked
        through the ring like any histogram — and every member derives
        the identical bin bounds. Pass 2 re-streams and bins the slice
        straight into a preallocated uint8 matrix. Peak memory is
        chunk + bins + sketch; the float matrix never materializes."""
        from mmlspark_tpu.models.gbdt.binning import BinnedDataset
        from mmlspark_tpu.models.gbdt.sketch import QuantileSketch

        def slice_chunks(pass_name: str, with_y: bool):
            """Yield ``(x_slice, y_slice_or_None, row0)`` for the parts
            of each chunk inside [lo, hi); shared by both passes so the
            slice arithmetic and the completeness guard can never
            diverge (``with_y`` skips the f64 label conversion on the
            binning pass, which discards labels). A short pass (a
            one-shot generator exhausted by pass 1, a source shrinking
            between passes) fails loudly — np.empty bins would
            otherwise train a garbage model silently."""
            cursor = 0
            for x_chunk, y_chunk in self._stream():
                c0, c1 = cursor, cursor + len(x_chunk)
                cursor = c1
                s0, s1 = max(lo, c0), min(hi, c1)
                if s1 > s0:
                    yield (
                        np.asarray(x_chunk[s0 - c0:s1 - c0]),
                        np.asarray(y_chunk[s0 - c0:s1 - c0], np.float64)
                        if with_y else None,
                        s0 - lo,
                    )
            if cursor != self.n:
                raise RuntimeError(
                    f"stream yielded {cursor} rows on the {pass_name} "
                    f"pass, expected n_rows={self.n} (the source must "
                    "be re-iterable and stable across passes)"
                )

        d = self.n_features
        sk = QuantileSketch(d, bits=self.sketch_bits)
        y_local = np.empty(hi - lo, np.float64)
        for x_sl, y_sl, row0 in slice_chunks("sketch", with_y=True):
            sk.update(x_sl)
            y_local[row0:row0 + len(y_sl)] = y_sl
        mapper = sk.to_binmapper(
            self.cfg.max_bin,
            reduce=reducer.allreduce if reducer is not None else None,
        )
        bins = np.empty((hi - lo, d), np.uint8)
        for x_sl, _y, row0 in slice_chunks("binning", with_y=False):
            mapper.transform_into(x_sl, bins, row0)
        return BinnedDataset(bins, mapper), y_local

    def _resolve_resume_from(self, member: GangMember) -> None:
        """An ``--resume-from artifact:<name>@<digest>[@peer,...]`` seed
        is pulled over HTTP (hash-verified) and unpacked into this
        host's checkpoint dir before the run starts — a fresh host can
        warm-start from a checkpoint it has never had on disk."""
        spec = self.resume_from
        if not spec or not str(spec).startswith("artifact:"):
            return
        if self._store is None:
            raise RuntimeError(
                "--resume-from artifact:… requires --artifact-dir"
            )
        from mmlspark_tpu.serving.artifacts import parse_spec, unpack_dir

        _scheme, name, digest, hints = parse_spec(spec)
        peers = list(hints) + [
            p for p in member.artifact_peers(digest) if p not in hints
        ]
        if not peers:
            # no spec-embedded hint and nobody advertising yet: wait out
            # the heartbeat window before giving up
            peers = self._await_peers(member, digest)
        path = self._store.fetch(
            digest, peers, name=name, timeout_s=self.gen_timeout_s,
        )
        os.makedirs(self.ckpt_dir, exist_ok=True)
        local = os.path.join(self.ckpt_dir, f"pulled-{digest[:16]}")
        unpack_dir(path, local)
        self.status["artifact_fetches"] += 1
        self.resume_from = local

    def _resolve_snapshot(
        self, member: GangMember, gen: Generation
    ) -> Optional[str]:
        """The local directory to resume this generation from, or None
        when the record names no snapshot.

        Shared-dir mode: the recorded path, trusted as before. Artifact
        mode: a path is only *mine* when it lives under MY ``ckpt_dir``
        (per-host disks: the committer's path means nothing here even if
        it happens to be readable); anyone else pulls the content-
        addressed bytes over HTTP from an advertising peer, verifies,
        and unpacks into its own checkpoint dir — the grow-back victim's
        whole recovery story."""
        if not gen.snapshot and not gen.snapshot_digest:
            return None
        if self._store is None:
            return gen.snapshot
        own_root = os.path.realpath(self.ckpt_dir) + os.sep
        if gen.snapshot and os.path.realpath(
            gen.snapshot
        ).startswith(own_root) and os.path.isdir(gen.snapshot):
            return gen.snapshot
        if not gen.snapshot_digest:
            return None
        digest = gen.snapshot_digest
        os.makedirs(self.ckpt_dir, exist_ok=True)
        local = os.path.join(self.ckpt_dir, f"pulled-{digest[:16]}")
        if os.path.isdir(local):
            return local
        try:
            # the committer itself advertises the snapshot; so may other
            # members that pulled it already (replication widens the
            # fan-in). Its advertisement rides the NEXT heartbeat, so an
            # empty peer list right after the commit is a race, not an
            # absence — wait it out before fetching
            peers = self._await_peers(member, digest)
            self._store.fetch(
                digest, peers,
                name=os.path.basename(gen.snapshot or f"ckpt-{digest[:12]}"),
                timeout_s=self.gen_timeout_s,
            )
        except Exception:
            # last resort before dying mid-recovery: this member's OWN
            # checkpoint stream (all_write mode: every member persists)
            # is bit-identical content — but only the EXACT agreed round
            # is safe to stand in for the snapshot (a member resuming
            # from a different round would diverge the gang's sums)
            own = self._own_ckpt_round()
            if own is not None and own == int(gen.resume_round):
                return None  # fall through to resume = self.ckpt_dir
            raise
        from mmlspark_tpu.serving.artifacts import unpack_dir

        unpack_dir(self._store.path(digest), local)
        self.status["artifact_fetches"] += 1
        self._write_status()
        return local

    def _await_peers(self, member: GangMember, digest: str) -> list:
        """Poll the roster until someone advertises ``digest`` (bounded
        by the generation timeout) — debounces the commit-to-heartbeat
        advertisement window."""
        deadline = time.monotonic() + max(
            10.0 * self.heartbeat_s, 5.0
        )
        peers = member.artifact_peers(digest)
        while not peers and time.monotonic() < deadline:
            time.sleep(self.heartbeat_s)
            peers = member.artifact_peers(digest)
        return peers

    def _own_ckpt_round(self) -> Optional[int]:
        try:
            with open(os.path.join(self.ckpt_dir, "LATEST")) as f:
                return int(f.read().strip().rsplit("-", 1)[-1])
        except (OSError, ValueError):
            return None

    def _park(
        self, member: GangMember, gen: Generation, err: Exception,
    ) -> None:
        """The minority-side stance after losing quorum or a CAS race:
        stop training, commit NOTHING, keep heartbeating (the member's
        beat thread runs on), and wait in ``await_generation`` to rejoin
        the winning epoch once the partition heals (grow-back re-admits
        us at the majority coordinator's next checkpoint boundary)."""
        reason = (
            "conflict" if isinstance(err, GenerationConflictError)
            else "quorum"
        )
        faults.inject(
            "elastic.park", context={"gen": gen.gen, "reason": reason}
        )
        _M_PARKS.labels(reason=reason).inc()
        self.status["parked"] = True
        self.status["parks"] += 1
        self.status["park_reasons"].append(reason)
        self._write_status()

    def _reshard(
        self, member: GangMember, gen: Generation, err: HostLostError
    ) -> None:
        """Commit (coordinator) or await the shrunk generation."""
        survivors = sorted(set(gen.members) - set(err.lost))
        if self.name not in survivors:
            return  # evicted/forced out: wait for grow-back
        detect_latency = max(
            (
                time.monotonic() - member.last_seen[m]
                for m in err.lost if m in member.last_seen
            ),
            default=0.0,
        )
        self.status["reshards"] += 1
        self.status["reshard_reasons"].append("lost")
        self.status["detect_latency_s"] = round(detect_latency, 3)
        self._write_status()
        cur = member.read_generation()
        if cur is not None and cur.gen > gen.gen:
            return  # another survivor already committed the next world
        if self.name == survivors[0]:
            # fault point elastic.reshard: an injected error is "the
            # commit refused" — retried until the plan relents
            for attempt in range(100):
                try:
                    faults.inject(
                        "elastic.reshard",
                        context={"gen": gen.gen + 1, "attempt": attempt},
                    )
                    break
                except Exception:  # noqa: BLE001 — injected refusal
                    time.sleep(self.heartbeat_s)
            if member.fenced_out("checkpoint"):
                # the fleet moved past us while we were deciding (a
                # SIGSTOP'd zombie waking after the survivors resharded
                # lands here): refuse to persist the snapshot or commit
                return
            snap, resume_round = snapshot_checkpoint(
                self.ckpt_dir, gen.gen + 1
            )
            digest = None
            if snap is not None and self._store is not None:
                # publish the frozen resume point as a content-addressed
                # artifact: fellow survivors (and the grow-back victim,
                # later) pull these exact bytes over HTTP instead of
                # needing this host's disk mounted
                try:
                    ref = self._store.put(snap, name=os.path.basename(snap))
                    digest = ref.digest
                except Exception:  # noqa: BLE001 — a refused put degrades
                    # to shared-dir semantics rather than blocking recovery
                    digest = None
            self.status.update(snapshot=snap, resume_round=resume_round)
            # replicate-before-commit: fellow survivors hold the frozen
            # resume point BEFORE the shrunk generation is committed —
            # this host dying post-commit strands nothing
            replicate_snapshot(member, digest, survivors, status=self.status)
            member.commit_generation(Generation(
                gen=gen.gen + 1, members=survivors, reason="lost",
                resume_round=resume_round, snapshot=snap,
                snapshot_digest=digest,
                detect_latency_s=round(detect_latency, 3),
            ))
            _M_RESHARDS.labels(reason="lost").inc()
        self._write_status()


# -- data specs for the fleet `train` role ------------------------------------


def load_training_data(spec: str) -> tuple:
    """``synth:<n>x<d>:<seed>`` — the deterministic toy binary dataset
    every host regenerates identically; ``npz:<path>`` — ``x``/``y``
    arrays on a shared filesystem."""
    if spec.startswith("synth:"):
        shape, _, seed = spec[len("synth:"):].partition(":")
        n, _, d = shape.partition("x")
        n, d, seed = int(n), int(d), int(seed or 0)
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, d)).astype(np.float32)
        y = (
            x[:, 0] + 0.5 * x[:, 1] + 0.1 * r.normal(size=n) > 0
        ).astype(np.float64)
        return x, y
    if spec.startswith("npz:"):
        with np.load(spec[len("npz:"):]) as z:
            return np.asarray(z["x"]), np.asarray(z["y"])
    raise ValueError(f"unknown training data spec {spec!r}")


def is_streaming_spec(spec: str) -> bool:
    return str(spec).startswith(("stream-synth:", "stream-csv:"))


def load_streaming_data(spec: str) -> tuple:
    """Out-of-core data specs -> ``(chunk_factory, n_rows, n_features)``.

    - ``stream-synth:<n>x<d>:<seed>[:<chunk>]`` — the synth dataset
      generated chunk-by-chunk: chunk ``i`` draws from
      ``default_rng([seed, i])``, so every host produces the identical
      global row stream without ever holding it (default chunk 65536).
    - ``stream-csv:<path>:<label>[:<chunk>]`` — a CSV streamed through
      :class:`~mmlspark_tpu.io.stream.StreamingDataFrame` (label column
      named; every other numeric column is a feature). ``n``/``d`` come
      from one counting pre-pass (the file is on disk; rows are never
      all resident).
    """
    if spec.startswith("stream-synth:"):
        body = spec[len("stream-synth:"):]
        parts = body.split(":")
        shape = parts[0]
        seed = int(parts[1]) if len(parts) > 1 and parts[1] else 0
        chunk = int(parts[2]) if len(parts) > 2 and parts[2] else 65536
        n_s, _, d_s = shape.partition("x")
        n, d = int(n_s), int(d_s)

        def factory() -> Iterator:
            done = 0
            i = 0
            while done < n:
                c = min(chunk, n - done)
                r = np.random.default_rng([seed, i])
                x = r.normal(size=(c, d)).astype(np.float32)
                y = (
                    x[:, 0] + 0.5 * x[:, 1] + 0.1 * r.normal(size=c) > 0
                ).astype(np.float64)
                yield x, y
                done += c
                i += 1

        return factory, n, d
    if spec.startswith("stream-csv:"):
        from mmlspark_tpu.io.stream import StreamingDataFrame

        body = spec[len("stream-csv:"):]
        parts = body.rsplit(":", 2)
        if len(parts) == 3 and parts[2].isdigit():
            path, label, chunk = parts[0], parts[1], int(parts[2])
        else:
            path, _, label = body.rpartition(":")
            chunk = 65536
        sdf = StreamingDataFrame.from_csv(
            path, chunk_rows=chunk, numeric_only=True
        )
        factory, n, d = stream_from_dataframe(sdf, label)
        return factory, n, d
    raise ValueError(f"unknown streaming data spec {spec!r}")


def stream_from_dataframe(sdf: Any, label_col: str) -> tuple:
    """Adapt a :class:`~mmlspark_tpu.io.stream.StreamingDataFrame` into
    an elastic-trainer chunk factory: every column except ``label_col``
    becomes a feature (sorted-name order, so every host agrees on the
    layout). Returns ``(factory, n_rows, n_features)``; the counting
    pre-pass touches only chunk SHAPES, never accumulates rows."""
    feat_cols: list = []
    n = 0
    for chunk in sdf.iter_chunks():
        if not feat_cols:
            feat_cols = sorted(c for c in chunk.columns if c != label_col)
        n += len(chunk)

    def factory() -> Iterator:
        for chunk in sdf.iter_chunks():
            x = np.stack(
                [np.asarray(chunk[c], np.float32) for c in feat_cols],
                axis=1,
            )
            y = np.asarray(chunk[label_col], np.float64)
            yield x, y

    return factory, n, len(feat_cols)


__all__ = [
    "ElasticTrainer",
    "GangContext",
    "GangMember",
    "Generation",
    "GenerationConflictError",
    "HostLostError",
    "QuorumLostError",
    "StragglerTracker",
    "TcpReducer",
    "WorldChangedError",
    "active_gang",
    "activate",
    "assign_partitions",
    "gang_blocks",
    "gang_sum",
    "gang_voting_k",
    "is_streaming_spec",
    "load_streaming_data",
    "load_training_data",
    "member_row_slice",
    "partition_bounds",
    "replicate_snapshot",
    "snapshot_checkpoint",
    "stream_from_dataframe",
]
