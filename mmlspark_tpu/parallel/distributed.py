"""Multi-host rendezvous and gang launch.

Replaces the reference's driver TCP rendezvous server
(LightGBMUtils.scala:116-185) and handshake protocol
(LightGBMConstants.scala:34-40, TrainUtils.scala:453-494) with
``jax.distributed`` over DCN: one coordinator address, every host calls
``initialize`` and the JAX runtime forms the global device mesh; SPMD
launch provides the gang semantics that the reference got from Spark
barrier execution mode (LightGBMBase.scala:122-131).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Sequence

import jax

from mmlspark_tpu import obs
from mmlspark_tpu.core import faults

_initialized = False

_M_BARRIER_WAIT = obs.histogram(
    "mmlspark_parallel_barrier_wait_seconds",
    "Time spent inside gang barriers, by barrier name", labels=("name",),
)
_M_BARRIER_TIMEOUTS = obs.counter(
    "mmlspark_parallel_barrier_timeouts_total",
    "Barriers abandoned by timeout", labels=("name",),
)


class BarrierTimeoutError(TimeoutError):
    """A gang sync point that did not complete in time — carries enough
    diagnostics to name the culprit instead of hanging forever."""

    def __init__(
        self,
        name: str,
        timeout_s: float,
        missing: Sequence[str] = (),
        process_index: int = 0,
        process_count: int = 1,
    ):
        self.name = name
        self.timeout_s = timeout_s
        self.missing = list(missing)
        msg = (
            f"barrier {name!r} timed out after {timeout_s:g}s on process "
            f"{process_index}/{process_count}"
        )
        if self.missing:
            msg += f"; missing hosts: {', '.join(self.missing)}"
        else:
            msg += (
                "; no roster provided — pass expected=/alive= to barrier() "
                "to identify the missing host"
            )
        super().__init__(msg)


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the multi-host gang. No-ops for single-process runs and when
    already initialized (so library code can call it unconditionally).

    Environment fallbacks (set by the launcher): MMLSPARK_TPU_COORDINATOR,
    MMLSPARK_TPU_NUM_PROCESSES, MMLSPARK_TPU_PROCESS_ID.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MMLSPARK_TPU_COORDINATOR")
    if coordinator_address is None:
        _initialized = True  # single-host mode
        return
    num_processes = num_processes or int(os.environ.get("MMLSPARK_TPU_NUM_PROCESSES", "1"))
    process_id = (
        process_id
        if process_id is not None
        else int(os.environ.get("MMLSPARK_TPU_PROCESS_ID", "0"))
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True


def is_coordinator() -> bool:
    return jax.process_index() == 0


def _barrier_collective() -> None:
    if jax.process_count() == 1:
        return
    import jax.numpy as jnp

    # A cross-host collective is the barrier: every host must contribute.
    jax.block_until_ready(
        jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
            jnp.ones((jax.local_device_count(),))
        )
    )


def barrier(
    name: str = "mmlspark_tpu_barrier",
    timeout_s: Optional[float] = None,
    expected: Optional[Sequence[str]] = None,
    alive: Optional[Callable[[], Sequence[str]]] = None,
) -> None:
    """Host-level sync point. On multi-host this rides a tiny psum over the
    global mesh; single-host it is a no-op.

    ``timeout_s``: instead of blocking forever on a slow/dead host (the
    failure the reference's Spark barrier stage would eventually kill),
    raise :class:`BarrierTimeoutError` after this many seconds. The
    abandoned collective keeps waiting on a daemon thread — XLA offers no
    cancellation — but the caller gets control back with a diagnosis.

    ``expected``/``alive``: optional roster for the diagnosis — the full
    gang's host names and a callable returning the currently-live ones
    (e.g. a TTL'd DriverRegistry roster, serving/registry.py); the error
    then names exactly which hosts never arrived.

    Fault point ``parallel.barrier``: an injected delay simulates the slow
    host; an injected error simulates local rendezvous failure."""

    def _wait() -> None:
        faults.inject("parallel.barrier", context={"name": name})
        _barrier_collective()

    t0 = time.perf_counter()

    def _observe() -> None:
        _M_BARRIER_WAIT.labels(name=name).observe(time.perf_counter() - t0)

    if timeout_s is None:
        with obs.span("parallel.barrier"):
            _wait()
        _observe()
        return
    done = threading.Event()
    errs: list = []

    def _run() -> None:
        try:
            _wait()
        except BaseException as e:  # noqa: BLE001 — re-raised in the caller
            errs.append(e)
        finally:
            done.set()

    threading.Thread(
        target=_run, name=f"barrier-{name}", daemon=True
    ).start()
    if not done.wait(timeout_s):
        _M_BARRIER_TIMEOUTS.labels(name=name).inc()
        _observe()  # the timeout IS the observed wait — the tail must show
        missing: list = []
        if expected is not None and alive is not None:
            try:
                missing = sorted(set(expected) - set(alive()))
            except Exception:  # noqa: BLE001 — roster is best-effort
                missing = []
        raise BarrierTimeoutError(
            name, timeout_s, missing,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        )
    _observe()
    if errs:
        raise errs[0]
