"""Process pool for the host histogram kernel — feature-parallel bincount
and whole-level depthwise grow steps.

Why processes: the host lowering's ``np.bincount`` accumulation loop holds
the GIL (measured: an 8-thread pool runs 3.3x SLOWER than serial), so the
only way to use more than one core per histogram build is separate
interpreters. sklearn's HistGradientBoosting — the bench head-to-head —
parallelizes its Cython histogram over features with OpenMP; this pool is
the numpy equivalent: W forked workers, each owning a stripe of features.

Transport: a Connection-per-worker pickle protocol costs ~0.6 ms per
roundtrip in syscalls alone (32 sends/receives at 8 workers) — as much as
the histogram itself. The hot path instead uses ONE shared task pipe and
ONE shared reply pipe: the main process stages all task parameters in a
fixed control shm block and writes W single bytes (each byte IS the
stripe id, so racing readers cannot steal each other's stripe), workers
read 1 byte, execute, write 1 status byte back. Arena (re)mapping is
generation-stamped inside the control block, so remaps need no extra
roundtrip. Connections remain for startup handshake and error detail.

Life cycle: lazily forked on the first large-enough call (small calls and
therefore most unit-test fits never start it), torn down atexit (tokens
0xFF + closing the task pipe EOFs every blocked worker). Fork, not spawn:
children only ever touch numpy and pipes (glibc's atfork handlers keep
malloc consistent), there is no __main__ re-execution hazard for
unguarded user scripts, and startup is milliseconds. A fork gone wrong
can only hang a child — the handshake/task timeouts turn that into a
permanent, logged degrade to the serial kernel.
``MMLSPARK_TPU_HIST_WORKERS`` overrides the worker count; ``0``/``1``
disables.

Determinism: each (slot, feature, bin) cell is accumulated by exactly one
worker with the same row-order ``np.bincount`` the serial kernel uses, so
pooled and serial results are bit-identical.
"""

from __future__ import annotations

import atexit
import logging
import os
import select
import time
from typing import Any, Optional

import numpy as np

log = logging.getLogger("mmlspark_tpu.histpool")

# below this many (row, feature) items the roundtrip costs more than the
# bincount itself — stay serial (also keeps unit-test fits pool-free)
MIN_POOL_ITEMS = 120000

_ARENAS = ("bins", "stats", "base", "out", "out0", "out1", "cand")
_CTRL_BYTES = 4 << 20          # fixed-size control block (never regrown)
_TOK_QUIT = 255

# control-block layout (all offsets in bytes)
_OFF_HDR = 0                   # int64[16]: gen, op, n, d, ns, nb, cur,
#                                prev, has_pair, P, s_prev, width,
#                                has_scan, has_cat
_OFF_FLT = 256                 # float64[4]: min_data, msh, lam, l1
_OFF_NAMES = 512               # len(_ARENAS) x 64 utf-8 shm names
_OFF_VAR = 4096                # fm f32[d] | cat u8[d] | rs u8[P] | pl i64[P]
_OP_RUN, _OP_GROW = 1, 2


def feature_candidates(
    cube: np.ndarray,         # (S, fdim, nb, 3) histogram stripe
    fm: np.ndarray,           # (fdim,) feature mask
    min_data: float,
    msh: float,
    lam: float,
    l1: float,
    cat_f: "np.ndarray | None",   # (fdim,) bool, or None (no categoricals)
) -> tuple:
    """Per-feature best split per slot — the numpy mirror of
    ``treegrow.make_leaf_best`` restricted to a feature stripe. Returns
    (gain (fdim, S) f64, bin/prefix (fdim, S) int64); masked-out and
    invalid candidates carry -inf. Shared by the pool workers and the
    serial host grower so both paths run literally the same scan.

    Tie-break parity with the XLA grower's flat (d*B) argmax: the
    per-bin argmax here takes the LOWEST bin among equals, and the
    caller's cross-feature argmax takes the lowest feature — together
    exactly the flat first-max."""
    c = cube.astype(np.float64)
    hg, hh, hc = c[..., 0], c[..., 1], c[..., 2]
    cg = np.cumsum(hg, axis=2)
    ch = np.cumsum(hh, axis=2)
    cc = np.cumsum(hc, axis=2)
    G, H, C = cg[..., -1:], ch[..., -1:], cc[..., -1:]

    def gscore(Gv: np.ndarray, Hv: np.ndarray) -> np.ndarray:
        if l1:
            t = np.sign(Gv) * np.maximum(np.abs(Gv) - l1, 0.0)
        else:
            t = Gv
        with np.errstate(divide="ignore", invalid="ignore"):
            return t * t / (Hv + lam)

    with np.errstate(invalid="ignore"):
        gain = gscore(cg, ch) + gscore(G - cg, H - ch) - gscore(G, H)
    valid = (
        (fm > 0)[None, :, None]
        & (cc >= min_data) & ((C - cc) >= min_data)
        & (ch >= msh) & ((H - ch) >= msh)
    )
    gain = np.where(valid, gain, -np.inf)
    if cat_f is not None and cat_f.any():
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(hc > 0, hg / (hh + 1e-12), -np.inf)
        order = np.argsort(-ratio, axis=2, kind="stable")
        cgs = np.cumsum(np.take_along_axis(hg, order, 2), axis=2)
        chs = np.cumsum(np.take_along_axis(hh, order, 2), axis=2)
        ccs = np.cumsum(np.take_along_axis(hc, order, 2), axis=2)
        with np.errstate(invalid="ignore"):
            gain_cat = (
                gscore(cgs, chs) + gscore(G - cgs, H - chs) - gscore(G, H)
            )
        valid_cat = (
            (fm > 0)[None, :, None]
            & (ccs >= min_data) & ((C - ccs) >= min_data)
            & (chs >= msh) & ((H - chs) >= msh)
        )
        gain = np.where(
            cat_f[None, :, None],
            np.where(valid_cat, gain_cat, -np.inf),
            gain,
        )
    bb = np.argmax(gain, axis=2)                     # (S, fdim): lowest bin
    bg = np.take_along_axis(gain, bb[..., None], 2)[..., 0]
    return bg.T, bb.T.astype(np.int64)


def _workers_wanted() -> int:
    env = os.environ.get("MMLSPARK_TPU_HIST_WORKERS")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            return 0
    cpus = os.cpu_count() or 1
    # leave headroom for the main process + XLA's own threads (16 workers
    # measured best on a 24-core box; 8 within 10%)
    return min(16, max(0, cpus - 8)) if cpus >= 16 else min(8, max(0, cpus - 2))


def _stripe_hist(
    out: np.ndarray, b: np.ndarray, base: np.ndarray, s: np.ndarray,
    f0: int, f1: int, ns: int, nb: int,
) -> None:
    """Weighted bincounts for features [f0, f1) into out[:, f0:f1].
    ``out`` is (ns, d, nb, 3); base offsets of ns*nb drop the row."""
    trash = ns * nb
    for f in range(f0, f1):
        idx = base + b[:, f]
        for j in range(3):
            out[:, f, :, j] = np.bincount(
                idx, weights=s[j], minlength=trash + 1
            )[:trash].reshape(ns, nb)


class _Ctrl:
    """Typed views over the fixed control shm block (main and workers
    parse the identical layout)."""

    def __init__(self, buf) -> None:
        self.hdr = np.frombuffer(buf, np.int64, 16, _OFF_HDR)
        self.flt = np.frombuffer(buf, np.float64, 4, _OFF_FLT)
        self.names = np.frombuffer(
            buf, "S64", len(_ARENAS), _OFF_NAMES
        )
        self.buf = buf

    def var_views(self, d: int, P: int) -> tuple:
        off = _OFF_VAR
        fm = np.frombuffer(self.buf, np.float32, d, off)
        off += 4 * d
        cat = np.frombuffer(self.buf, np.uint8, d, off)
        off += d
        off = (off + 7) & ~7
        rs = np.frombuffer(self.buf, np.uint8, max(P, 1), off)
        off += max(P, 1)
        off = (off + 7) & ~7
        pl = np.frombuffer(self.buf, np.int64, max(P, 1), off)
        return fm, cat, rs, pl


def _attach(name: str):
    """SharedMemory attach with resource-tracker registration suppressed:
    on this interpreter SharedMemory(name=) registers even for attaches
    (cpython bpo-39959) and concurrent worker register/unregister
    messages corrupt the shared tracker cache. The parent owns the
    segments and unlinks them."""
    from multiprocessing import resource_tracker as _rt
    from multiprocessing import shared_memory

    orig = _rt.register
    _rt.register = lambda *a, **k: None
    try:
        return shared_memory.SharedMemory(name=name)
    finally:
        _rt.register = orig


def _exec_task(ctrl: "_Ctrl", bufs: dict, stripe: int) -> None:
    """Execute the staged task for one feature stripe (shared by the
    token and Connection protocols)."""
    (_, op, n, d, ns, nb, cur, prev, has_pair, P, s_prev, width,
     has_scan, has_cat) = (int(v) for v in ctrl.hdr[:14])
    per = (d + width - 1) // width
    f0, f1 = stripe * per, min((stripe + 1) * per, d)
    if f0 >= f1:
        return
    bins_dt = np.uint8 if int(ctrl.hdr[14]) == 1 else np.int32
    b = np.frombuffer(bufs["bins"].buf, bins_dt, n * d).reshape(n, d)
    s = np.frombuffer(
        bufs["stats"].buf, np.float32, 3 * n
    ).reshape(3, n).astype(np.float64)
    base = np.frombuffer(bufs["base"].buf, np.int64, n)
    fm_v, cat_v, rs_v, pl_v = ctrl.var_views(d, P)
    scan = None
    if has_scan:
        min_data, msh, lam, l1 = (float(v) for v in ctrl.flt[:4])
        cat_f = cat_v.astype(bool) if has_cat else None
    if op == _OP_GROW:
        cube = np.frombuffer(
            bufs["out%d" % cur].buf, np.float32, ns * d * nb * 3
        ).reshape(ns, d, nb, 3)
        if not has_pair:
            _stripe_hist(cube, b, base, s, f0, f1, ns, nb)
        else:
            # histogram only the smaller sibling; derive the other from
            # the previous level's cube (ping-pong arena, state that
            # lives only within one tree)
            fdim = f1 - f0
            half = np.empty((P, fdim, nb, 3), np.float32)
            _stripe_hist(half, b[:, f0:f1], base, s, 0, fdim, P, nb)
            prev_cube = np.frombuffer(
                bufs["out%d" % prev].buf, np.float32, s_prev * d * nb * 3
            ).reshape(s_prev, d, nb, 3)
            parent_local = pl_v[:P]
            parents_ok = parent_local >= 0
            parents = prev_cube[np.maximum(parent_local, 0), f0:f1]
            other = parents - half
            if not parents_ok.all():
                bad = ~parents_ok
                other[bad] = 0.0
                half[bad] = 0.0
            rs = rs_v[:P].astype(bool)[:, None, None, None]
            cube[0:2 * P:2, f0:f1] = np.where(rs, other, half)
            cube[1:2 * P:2, f0:f1] = np.where(rs, half, other)
            if 2 * P < ns:
                cube[2 * P:, f0:f1] = 0.0
        target = cube
    else:
        target = np.frombuffer(
            bufs["out"].buf, np.float32, ns * d * nb * 3
        ).reshape(ns, d, nb, 3)
        _stripe_hist(target, b, base, s, f0, f1, ns, nb)
    if has_scan:
        cand = np.frombuffer(
            bufs["cand"].buf, np.float64, d * ns * 2
        ).reshape(d, ns, 2)
        bg, bb = feature_candidates(
            target[:, f0:f1], fm_v[f0:f1], min_data, msh, lam, l1,
            cat_f[f0:f1] if has_scan and cat_f is not None else None,
        )
        cand[f0:f1, :, 0] = bg
        cand[f0:f1, :, 1] = bb


def _worker_main(
    wid: int, conn: Any, ctrl_name: str, task_fd: int, reply_fd: int
) -> None:
    """Worker loop. Children run numpy + pipes only — never jax/XLA/BLAS
    — which is what makes the fork start safe."""
    bufs: dict = {}
    ctrl = None
    gen = -1
    try:
        ctrl_shm = _attach(ctrl_name)
        ctrl = _Ctrl(ctrl_shm.buf)
        conn.send("pong")                 # startup handshake
    except Exception as e:  # noqa: BLE001
        try:
            conn.send(("error", repr(e)))
        except Exception:  # noqa: BLE001
            return
        return
    # hybrid wait: after finishing a task, spin on a non-blocking read for
    # a short window (the next level's tokens arrive within ~2 ms during a
    # fit; a blocking read costs ~0.1-0.5 ms of wakeup latency per level),
    # then park in select() so an idle pool burns nothing
    import fcntl

    fcntl.fcntl(task_fd, fcntl.F_SETFL,
                fcntl.fcntl(task_fd, fcntl.F_GETFL) | os.O_NONBLOCK)
    spin_s = 0.05
    spin_until = 0.0
    while True:
        tok = b""
        try:
            while True:
                try:
                    tok = os.read(task_fd, 1)
                    break
                except BlockingIOError:
                    if time.monotonic() >= spin_until:
                        select.select([task_fd], [], [])
        except OSError:
            break
        if not tok or tok[0] == _TOK_QUIT:
            break
        status = b"\x00"
        try:
            if int(ctrl.hdr[0]) != gen:
                # generation bump: (re)attach arenas named in the block
                for key, raw in zip(_ARENAS, ctrl.names):
                    name = bytes(raw).rstrip(b"\x00").decode()
                    if not name:
                        continue
                    if key in bufs:
                        if bufs[key][1] == name:
                            continue
                        bufs[key][0].close()
                    shm = _attach(name)
                    bufs[key] = [shm, name]
                gen = int(ctrl.hdr[0])
            _exec_task(ctrl, {k: v[0] for k, v in bufs.items()}, tok[0])
        except Exception as e:  # noqa: BLE001 — report, main degrades
            status = b"\x01"
            try:
                conn.send(("error", repr(e)))
            except Exception:  # noqa: BLE001
                break
        try:
            os.write(reply_fd, status)
        except OSError:
            break
        spin_until = time.monotonic() + spin_s
    for v in bufs.values():
        try:
            v[0].close()
        except Exception:  # noqa: BLE001
            pass


class _HistPool:
    def __init__(self) -> None:
        self.procs: list = []
        self.conns: list = []
        self.shms: dict = {}
        self.caps: dict = {k: 0 for k in _ARENAS}
        self.dead = False
        self.width = 0
        self.toks: dict = {}
        self.ctrl_shm = None
        self.ctrl: Optional[_Ctrl] = None
        self.gen = 0
        self.task_w = self.reply_r = -1
        self._extra_fds: list = []

    # -- lifecycle ---------------------------------------------------------

    def _start(self) -> bool:
        import multiprocessing as mp
        from multiprocessing import shared_memory

        w = _workers_wanted()
        if w <= 1:
            return False
        try:
            ctx = mp.get_context("fork")
        except ValueError:
            # the token pipes rely on fd inheritance; without fork there
            # is no cheap transport, and the serial kernel is already
            # within ~2x of a chatty pool — stay serial
            log.info("hist pool requires the fork start method; serial")
            return False
        try:
            import warnings

            self.ctrl_shm = shared_memory.SharedMemory(
                create=True, size=_CTRL_BYTES
            )
            self.ctrl = _Ctrl(self.ctrl_shm.buf)
            self.ctrl.hdr[0] = 0
            task_r, self.task_w = os.pipe()
            self.reply_r, reply_w = os.pipe()
            self._extra_fds = [task_r, reply_w]
            for i in range(w):
                ours, theirs = ctx.Pipe(duplex=True)
                p = ctx.Process(
                    target=_worker_main,
                    args=(i, theirs, self.ctrl_shm.name, task_r, reply_w),
                    daemon=True,
                )
                with warnings.catch_warnings():
                    # the interpreter warns that fork + threads can
                    # deadlock; the children run numpy + pipes only and
                    # the handshake/task timeouts degrade a wedged child
                    # to the serial kernel
                    warnings.simplefilter("ignore", RuntimeWarning)
                    warnings.simplefilter("ignore", DeprecationWarning)
                    p.start()
                theirs.close()
                self.conns.append(ours)
                self.procs.append(p)
            deadline = time.monotonic() + 30.0
            for conn in self.conns:
                remaining = max(deadline - time.monotonic(), 0.0)
                if not conn.poll(remaining) or conn.recv() != "pong":
                    raise RuntimeError("worker failed startup handshake")
        except Exception as e:  # noqa: BLE001
            log.warning("hist pool start failed (%s); staying serial", e)
            self._shutdown()
            return False
        self.width = w
        atexit.register(self._shutdown)
        return True

    def _shutdown(self) -> None:
        if self.task_w >= 0:
            try:
                os.write(self.task_w, bytes([_TOK_QUIT]) * len(self.procs))
            except OSError:
                pass
            try:
                os.close(self.task_w)   # EOF wakes any blocked reader
            except OSError:
                pass
            self.task_w = -1
        for p in self.procs:
            try:
                p.join(timeout=1.0)
                if p.is_alive():
                    p.terminate()
            except Exception:  # noqa: BLE001
                pass
        for fd in [self.reply_r] + self._extra_fds:
            if fd >= 0:
                try:
                    os.close(fd)
                except OSError:
                    pass
        self.reply_r = -1
        self._extra_fds = []
        for conn in self.conns:
            try:
                conn.close()
            except Exception:  # noqa: BLE001
                pass
        self.ctrl = None  # drop the typed views before closing the block
        for s in list(self.shms.values()) + (
            [self.ctrl_shm] if self.ctrl_shm is not None else []
        ):
            # close and unlink separately: a caller still holding a view
            # of an arena makes close() raise BufferError, but the
            # segment must be unlinked (and tracker-unregistered) anyway
            try:
                s.close()
            except Exception:  # noqa: BLE001
                pass
            try:
                s.unlink()
            except Exception:  # noqa: BLE001
                pass
            # a caller-held view keeps the mmap exported; silence the
            # interpreter-exit __del__ retry (the segment is already
            # unlinked, nothing leaks)
            s.close = lambda: None
        self.procs, self.conns, self.shms = [], [], {}
        self.ctrl_shm = None
        self.caps = {k: 0 for k in _ARENAS}
        self.toks = {}
        self.dead = True

    # -- arenas ------------------------------------------------------------

    def _ensure_arenas(self, need: dict) -> None:
        """Grow shared buffers to at least the needed byte sizes; workers
        re-attach lazily via the generation stamp in the control block."""
        from multiprocessing import shared_memory

        grow = {k: v for k, v in need.items() if v > self.caps[k]}
        if not grow:
            return
        for key, size in grow.items():
            size = max(size * 2, 1 << 20)  # 2x headroom, 1 MiB floor
            old = self.shms.get(key)
            self.shms[key] = shared_memory.SharedMemory(create=True, size=size)
            self.caps[key] = size
            self.toks.pop(key, None)  # fresh arena: cached content gone
            if old is not None:
                old.close()
                old.unlink()
        for i, key in enumerate(_ARENAS):
            shm = self.shms.get(key)
            self.ctrl.names[i] = (shm.name if shm else "").encode()
        self.gen += 1

    def _write_arena(
        self, key: str, dtype, data: np.ndarray, token: Any
    ) -> None:
        """Copy ``data`` into the named arena unless the caller's token
        says the arena already holds it (the host grower reuses bins and
        stats across a tree's levels — tokens are object ids the CALLER
        keeps alive for the duration, so they cannot be recycled)."""
        tok = None
        if token is not None:
            tok = (token, data.shape, data.dtype.str)
            if self.toks.get(key) == tok:
                return
        flat = np.frombuffer(self.shms[key].buf, dtype, data.size)
        flat[:] = data.reshape(-1)
        self.toks[key] = tok

    # -- task dispatch -----------------------------------------------------

    def _dispatch(self, d: int) -> bool:
        """Wake one worker per feature stripe and collect status bytes."""
        width = int(self.ctrl.hdr[11])
        try:
            os.write(self.task_w, bytes(range(width)))
        except OSError:
            return False
        got = 0
        errs = 0
        deadline = time.monotonic() + 60.0
        while got < width:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            r, _, _ = select.select([self.reply_r], [], [], remaining)
            if not r:
                return False
            chunk = os.read(self.reply_r, width - got)
            if not chunk:
                return False
            got += len(chunk)
            errs += sum(1 for c in chunk if c != 0)
        if errs:
            for conn in self.conns:
                while conn.poll(0):
                    msg = conn.recv()
                    if isinstance(msg, tuple):
                        log.warning("hist pool worker failed: %s", msg[1])
            return False
        return True

    def _stage(
        self, op: int, b: np.ndarray, base: np.ndarray, s3: np.ndarray,
        ns: int, nb: int, scan: Optional[tuple],
        cur: int, prev: int, pair: Optional[tuple],
        bins_token: Any, stats_token: Any,
    ):
        n, d = b.shape
        if self.dead or n * d < MIN_POOL_ITEMS:
            return None
        if not self.procs and not self._start():
            self.dead = True
            return None
        P = len(pair[0]) if pair is not None else 0
        if _OFF_VAR + 4 * d + d + 16 + 9 * max(P, 1) > _CTRL_BYTES:
            return None  # shapes beyond the fixed control block
        need = {
            "bins": b.nbytes,
            "stats": s3.nbytes,
            "base": base.nbytes,
            ("out%d" % cur if op == _OP_GROW else "out"): ns * d * nb * 3 * 4,
        }
        if scan is not None:
            need["cand"] = d * ns * 2 * 8
        self._ensure_arenas(need)
        self._write_arena("bins", b.dtype, b, bins_token)
        self._write_arena("stats", np.float32, s3, stats_token)
        self._write_arena("base", np.int64, base, None)
        width = min(self.width, d)
        hdr = self.ctrl.hdr
        hdr[1:15] = 0
        hdr[1] = op
        hdr[14] = b.dtype.itemsize
        hdr[2], hdr[3], hdr[4], hdr[5] = n, d, ns, nb
        hdr[6], hdr[7] = cur, prev
        hdr[11] = width
        fm_v, cat_v, rs_v, pl_v = self.ctrl.var_views(d, P)
        if scan is not None:
            fm, cat_f, min_data, msh, lam, l1 = scan
            self.ctrl.flt[:4] = (min_data, msh, lam, l1)
            fm_v[:] = np.asarray(fm, np.float32)
            hdr[12] = 1
            if cat_f is not None:
                cat_v[:] = np.asarray(cat_f, np.uint8)
                hdr[13] = 1
        if pair is not None:
            right_small, parent_local, s_prev = pair
            hdr[8], hdr[9], hdr[10] = 1, P, s_prev
            rs_v[:P] = np.asarray(right_small, np.uint8)
            pl_v[:P] = parent_local
        # publish the generation last: workers reading a stale gen would
        # re-attach before touching the arenas
        hdr[0] = self.gen
        return self._dispatch(d)

    # -- public ops --------------------------------------------------------

    def bincounts(
        self, b: np.ndarray, base: np.ndarray, s3: np.ndarray,
        ns: int, nb: int, scan: Optional[tuple] = None,
        bins_token: Any = None, stats_token: Any = None,
    ) -> "Optional[tuple]":
        """Pooled equivalent of the serial per-feature bincount loop.

        ``b``: (n, d) int32 bins (in range); ``base``: (n,) int64 plane
        offsets (a trash offset of ns*nb drops the row); ``s3``: (3, n)
        f32 stats. ``scan``: optional (fm, cat_f, min_data, msh, lam,
        l1) — the workers also run :func:`feature_candidates` on their
        stripe. Returns (cube (ns, d, nb, 3) f32, cand (d, ns, 2) f64 or
        None), both aliasing the shared arenas — valid until the NEXT
        call — or None when the pool should not / could not run (caller
        falls back to the serial loop)."""
        n, d = b.shape
        try:
            ok = self._stage(
                _OP_RUN, b, base, s3, ns, nb, scan, 0, 0, None,
                bins_token, stats_token,
            )
        except Exception as e:  # noqa: BLE001
            log.warning("hist pool degraded to serial: %s", e)
            self._shutdown()
            return None
        if ok is None:
            return None
        if not ok:
            log.warning("hist pool task failed; degrading to serial")
            self._shutdown()
            return None
        cube = np.frombuffer(
            self.shms["out"].buf, np.float32, ns * d * nb * 3
        ).reshape(ns, d, nb, 3)
        cand = None
        if scan is not None:
            cand = np.frombuffer(
                self.shms["cand"].buf, np.float64, d * ns * 2
            ).reshape(d, ns, 2)
        return cube, cand

    def grow_level(
        self, b: np.ndarray, base: np.ndarray, s3: np.ndarray,
        S: int, nb: int, scan: tuple, pair: Optional[tuple], cur: int,
        bins_token: Any = None, stats_token: Any = None,
    ) -> "Optional[tuple]":
        """One depthwise level fully in the workers: stripe histograms
        (of the smaller sibling only when ``pair`` is given), sibling
        derivation against the previous level's cube (ping-pong arenas
        out0/out1 — state that lives only WITHIN one tree; every tree
        opens with a full pair=None build), and the split scan.

        ``pair``: (right_small (P,) bool, parent_local (P,) i64 with -1
        for dead pairs, S_prev). Returns (cube (S, d, nb, 3) f32 view,
        gains (d, S) f64, bins (d, S) i64) aliasing the arenas, or None
        to run serial."""
        n, d = b.shape
        try:
            ok = self._stage(
                _OP_GROW, b, base, s3, S, nb, scan, cur, 1 - cur, pair,
                bins_token, stats_token,
            )
        except Exception as e:  # noqa: BLE001
            log.warning("hist pool degraded to serial: %s", e)
            self._shutdown()
            return None
        if ok is None:
            return None
        if not ok:
            log.warning("hist pool task failed; degrading to serial")
            self._shutdown()
            return None
        cube = np.frombuffer(
            self.shms["out%d" % cur].buf, np.float32, S * d * nb * 3
        ).reshape(S, d, nb, 3)
        cand = np.frombuffer(
            self.shms["cand"].buf, np.float64, d * S * 2
        ).reshape(d, S, 2)
        return cube, cand[:, :, 0], cand[:, :, 1].astype(np.int64)


_POOL: Optional[_HistPool] = None


def get_pool() -> _HistPool:
    global _POOL
    if _POOL is None:
        _POOL = _HistPool()
    return _POOL


def pooled_bincounts(
    b: np.ndarray, base: np.ndarray, s3: np.ndarray, ns: int, nb: int
) -> Optional[np.ndarray]:
    """Entry point used by the host histogram kernel. None = run serial.
    The returned cube aliases the pool's shared arena — consume (or
    copy) it before the next pooled call."""
    res = get_pool().bincounts(b, base, s3, ns, nb)
    return None if res is None else res[0]
