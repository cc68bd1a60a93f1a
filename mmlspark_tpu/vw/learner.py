"""Device SGD kernel for VW-style online learning.

The TPU rebuild of VW's native train loop + spanning-tree allreduce
(vw/VowpalWabbitBase.scala:235-266,401-429): each mesh shard runs an
in-compiler online pass over its rows (``lax.scan`` over fixed-shape
minibatches of gathered/scattered sparse features), and shards average
weights with ``pmean`` over ICI at every pass boundary — exactly VW's
"allreduce weights once per pass" semantics, minus the driver server.

Adaptive (AdaGrad) per-coordinate learning rates stand in for VW's
``--adaptive`` default; ``power_t`` scales the global schedule for the
non-adaptive path.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.collectives import shard_apply
from mmlspark_tpu.parallel.mesh import DATA_AXIS, get_mesh

LOSS_LOGISTIC = "logistic"
LOSS_SQUARED = "squared"
LOSS_QUANTILE = "quantile"
LOSS_HINGE = "hinge"
LOSS_POISSON = "poisson"
LOSSES = (LOSS_LOGISTIC, LOSS_SQUARED, LOSS_QUANTILE, LOSS_HINGE, LOSS_POISSON)


class SGDState(NamedTuple):
    """Full optimizer state of the VW online learner.

    Carrying ``g2`` (the AdaGrad accumulator) and ``t`` (the minibatch
    counter for the non-adaptive schedule) across calls is what makes
    incremental training *bit-identical* to one batch run over the
    concatenated rows (asserted in tests/test_online.py): warm-starting
    on weights alone would reset the per-coordinate step sizes every
    micro-batch. Fields may be numpy or jax arrays — the continuous-
    training loop keeps them device-resident between micro-batches and
    only pulls ``w`` to host at publish time."""

    w: Any    # (2^num_bits,) f32 weights
    g2: Any   # (2^num_bits,) f32 AdaGrad sum of squared gradients
    t: Any    # scalar f32: minibatches seen (power_t schedule input)


def sgd_init(num_bits: int,
             initial_weights: Optional[np.ndarray] = None) -> SGDState:
    """Fresh optimizer state for :func:`train_sparse_sgd_state`."""
    d = 1 << num_bits
    w = (
        np.zeros(d, np.float32) if initial_weights is None
        else np.asarray(initial_weights, np.float32)
    )
    if w.shape != (d,):
        raise ValueError(f"initial weights shape {w.shape} != ({d},)")
    return SGDState(w=w, g2=np.zeros(d, np.float32), t=np.float32(0.0))


def _dloss(loss: str, margin: jnp.ndarray, y: jnp.ndarray, tau: float) -> jnp.ndarray:
    """d(loss)/d(margin) — VW's loss zoo. logistic/hinge expect y in
    {-1,+1}; squared/quantile raw y; poisson log-space margins vs counts.
    ``tau`` is the pinball level (--quantile_tau; VW passes loss flags
    through its arg string, VowpalWabbitBase.scala:495-508)."""
    if loss == LOSS_LOGISTIC:
        return -y * jax.nn.sigmoid(-y * margin)
    if loss == LOSS_SQUARED:
        return margin - y
    if loss == LOSS_QUANTILE:
        return jnp.where(margin >= y, 1.0 - tau, -tau)
    if loss == LOSS_HINGE:
        return jnp.where(y * margin < 1.0, -y, 0.0)
    if loss == LOSS_POISSON:
        # clamp like VW's poisson link: an unclamped exp overflows f32 for
        # moderately scaled features and NaN-poisons the weights for good
        return jnp.exp(jnp.clip(margin, -30.0, 30.0)) - y
    raise ValueError(f"unknown loss {loss!r}")


@functools.partial(
    jax.jit,
    static_argnames=("loss", "num_passes", "batch", "adaptive", "axis"),
)
def _shard_train(
    idx: jnp.ndarray,  # (n, K) int32
    val: jnp.ndarray,  # (n, K) f32, 0-padded
    y: jnp.ndarray,  # (n,) f32
    wt: jnp.ndarray,  # (n,) f32 example weights, 0 for padding rows
    w0: jnp.ndarray,  # (D,) f32 initial weights
    g20: jnp.ndarray,  # (D,) f32 initial AdaGrad accumulator
    t0: jnp.ndarray,  # scalar f32: minibatches already seen
    tau: jnp.ndarray,  # pinball level (quantile loss only)
    *,
    loss: str,
    num_passes: int,
    batch: int,
    lr: float,
    power_t: float,
    l2: float,
    adaptive: bool,
    axis: Optional[str],
) -> tuple:
    n = idx.shape[0]
    nb = n // batch
    idx_b = idx[: nb * batch].reshape(nb, batch, -1)
    val_b = val[: nb * batch].reshape(nb, batch, -1)
    y_b = y[: nb * batch].reshape(nb, batch)
    wt_b = wt[: nb * batch].reshape(nb, batch)

    def minibatch(carry, xs):
        w, g2, t = carry
        bi, bv, by, bw = xs
        gathered = w[bi]  # (B, K) gather from HBM
        margin = (gathered * bv).sum(-1)
        dl = _dloss(loss, margin, by, tau) * bw  # (B,)
        g = dl[:, None] * bv + l2 * gathered * (bv != 0)  # (B, K)
        if adaptive:
            # the accumulator scatter runs BEFORE the denominator gather so
            # a feature repeated across the minibatch sees the whole
            # batch's g^2 — the aggressive-step blowup a fused
            # single-scatter variant suffers on duplicate-heavy data
            g2 = g2.at[bi].add(g * g)
            denom = jnp.sqrt(g2[bi]) + 1e-6
            w = w.at[bi].add(-lr * g / denom)
        else:
            step = lr * (1.0 / (1.0 + t)) ** power_t
            w = w.at[bi].add(-step * g)
        return (w, g2, t + 1.0), None

    def one_pass(carry, _):
        w, g2, t = carry
        (w, g2, t), _ = jax.lax.scan(
            minibatch, (w, g2, t), (idx_b, val_b, y_b, wt_b)
        )
        if axis is not None:
            w = jax.lax.pmean(w, axis)  # <- the per-pass allreduce
            g2 = jax.lax.pmean(g2, axis)
            # pmean output is axis-invariant; keep the carry type stable
            w = jax.lax.pcast(w, axis, to="varying")
            g2 = jax.lax.pcast(g2, axis, to="varying")
        return (w, g2, t), None

    if axis is not None:
        # carry becomes device-varying after the first shard-local update;
        # mark it so from the start (shard_map varying-axis typing)
        w0 = jax.lax.pcast(w0, axis, to="varying")
        g20 = jax.lax.pcast(g20, axis, to="varying")
    (w, g2, t), _ = jax.lax.scan(
        one_pass, (w0, g20, jnp.float32(t0)), None, length=num_passes
    )
    if axis is not None:
        # shards already hold identical pmean-ed weights; this extra pmean is
        # a no-op numerically but types the output as axis-invariant
        w = jax.lax.pmean(w, axis)
        g2 = jax.lax.pmean(g2, axis)
    return w, g2, t


def train_sparse_sgd_state(
    idx: np.ndarray,
    val: np.ndarray,
    y: np.ndarray,
    wt: Optional[np.ndarray],
    num_bits: int,
    state: Optional[SGDState] = None,
    *,
    loss: str = LOSS_LOGISTIC,
    num_passes: int = 1,
    batch: int = 0,
    lr: float = 0.5,
    power_t: float = 0.5,
    l2: float = 0.0,
    adaptive: bool = True,
    distributed: bool = True,
    quantile_tau: float = 0.5,
) -> SGDState:
    """One incremental training step: continue from ``state`` (or fresh
    zeros) over this (padded) sparse micro-batch, returning the FULL
    updated optimizer state with **device-resident** arrays.

    This is the continuous-training entry point (mmlspark_tpu/online/):
    state fields stay on device between calls — no host round-trip per
    micro-batch — and because the AdaGrad accumulator and schedule
    counter ride along, feeding rows chunk-by-chunk is bit-identical to
    one :func:`train_sparse_sgd` call over the concatenation whenever
    chunk sizes are multiples of the minibatch size (unsharded path;
    asserted in tests/test_online.py). Batch semantics, sharding and the
    per-pass ``pmean`` allreduce are exactly :func:`train_sparse_sgd`'s.
    """
    d = 1 << num_bits
    n = len(y)
    if batch <= 0:
        batch = 1024 if jax.default_backend() == "tpu" else 64
    wt = np.ones(n, np.float32) if wt is None else np.asarray(wt, np.float32)
    mesh = get_mesh()
    n_shards = mesh.shape[DATA_AXIS] if distributed else 1
    # multi-host: every process holds ITS OWN rows; local blocks join a
    # process-spanning sharded array and the same shard_map program runs
    # SPMD with the per-pass pmean crossing processes over DCN (the
    # spanning-tree-allreduce analogue, VowpalWabbitBase.scala:401-429)
    multihost = distributed and jax.process_count() > 1
    if multihost:
        from mmlspark_tpu.parallel.sharding import multihost_pad_target

        # ALL sizing must come from the allgathered target, never local n:
        # processes hold unequal row counts but must compile the same
        # static-batch SPMD program over the same global shape
        # floor of 1: if EVERY process holds zero rows the program still
        # needs one inert zero-weight chunk (matching the single-host
        # max(n, 1) path) instead of zero-length sharded arrays
        target = max(1, multihost_pad_target(n))
        ldc = jax.local_device_count()
        batch = max(1, min(batch, max(1, target // ldc)))
        gran = ldc * batch  # whole per-device minibatches per process block
        share = ((target + gran - 1) // gran) * gran
        n_pad = share
    else:
        batch = max(1, min(batch, max(1, n // max(1, n_shards))))
        chunk = n_shards * batch
        n_pad = int(np.ceil(max(n, 1) / chunk)) * chunk
    if n_pad != n:
        pad = n_pad - n
        idx = np.concatenate([idx, np.zeros((pad, idx.shape[1]), idx.dtype)])
        val = np.concatenate([val, np.zeros((pad, val.shape[1]), val.dtype)])
        y = np.concatenate([np.asarray(y, np.float32), np.zeros(pad, np.float32)])
        wt = np.concatenate([wt, np.zeros(pad, np.float32)])  # padding = no-op
    if state is None:
        state = sgd_init(num_bits)
    w0, g20, t0 = state
    if getattr(w0, "shape", None) != (d,):
        raise ValueError(
            f"state weights shape {getattr(w0, 'shape', None)} != ({d},)"
        )
    kwargs = dict(
        loss=loss,
        num_passes=num_passes,
        batch=batch,
        lr=lr,
        power_t=power_t,
        l2=l2,
        adaptive=adaptive,
    )
    tau = np.float32(quantile_tau)
    if not distributed or n_shards == 1:
        w, g2, t = _shard_train(
            jnp.asarray(idx, jnp.int32),
            jnp.asarray(val),
            jnp.asarray(y, jnp.float32),
            jnp.asarray(wt),
            jnp.asarray(w0, jnp.float32),  # no-op on a device array
            jnp.asarray(g20, jnp.float32),
            jnp.asarray(t0, jnp.float32),
            tau,
            axis=None,
            **kwargs,
        )
        return SGDState(w=w, g2=g2, t=t)

    fn = shard_apply(
        functools.partial(_shard_train, axis=DATA_AXIS, **kwargs),
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )
    if multihost:
        from mmlspark_tpu.parallel.sharding import shard_batch_multihost

        rows = shard_batch_multihost(
            (idx.astype(np.int32), val.astype(np.float32),
             np.asarray(y, np.float32), wt.astype(np.float32)),
            mesh,
        )
        # state: identical host arrays (or replicated device arrays from a
        # previous step) == replicated
        w, g2, t = jax.jit(fn)(
            *rows, np.asarray(w0, np.float32), np.asarray(g20, np.float32),
            np.float32(t0), tau,
        )
        return SGDState(w=w, g2=g2, t=t)
    w, g2, t = jax.jit(fn)(
        jnp.asarray(idx, jnp.int32),
        jnp.asarray(val),
        jnp.asarray(y, jnp.float32),
        jnp.asarray(wt),
        jnp.asarray(w0, jnp.float32),
        jnp.asarray(g20, jnp.float32),
        jnp.asarray(t0, jnp.float32),
        tau,
    )
    return SGDState(w=w, g2=g2, t=t)


def train_sparse_sgd(
    idx: np.ndarray,
    val: np.ndarray,
    y: np.ndarray,
    wt: Optional[np.ndarray],
    num_bits: int,
    *,
    loss: str = LOSS_LOGISTIC,
    num_passes: int = 1,
    batch: int = 0,
    lr: float = 0.5,
    power_t: float = 0.5,
    l2: float = 0.0,
    adaptive: bool = True,
    initial_weights: Optional[np.ndarray] = None,
    distributed: bool = True,
    quantile_tau: float = 0.5,
) -> np.ndarray:
    """Train on the (padded) sparse batch; returns the (2^num_bits,) weights.

    ``distributed=True`` shards rows over the mesh ``data`` axis via
    ``shard_map`` so every pass ends in an ICI ``pmean``.

    ``batch <= 0`` = auto: 1024 on TPU (the gather/scatter SGD step is
    latency-bound there — bigger minibatches keep the chip busy), 64
    elsewhere (closer to VW's per-example updates)."""
    state = train_sparse_sgd_state(
        idx, val, y, wt, num_bits,
        sgd_init(num_bits, initial_weights),
        loss=loss, num_passes=num_passes, batch=batch, lr=lr,
        power_t=power_t, l2=l2, adaptive=adaptive, distributed=distributed,
        quantile_tau=quantile_tau,
    )
    return np.asarray(state.w)


@functools.partial(jax.jit, static_argnames=())
def _predict_margin(idx: jnp.ndarray, val: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return (w[idx] * val).sum(-1)


def predict_margin(idx: np.ndarray, val: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Batched sparse dot with the weight vector (scoring hot path)."""
    return np.asarray(
        _predict_margin(jnp.asarray(idx, jnp.int32), jnp.asarray(val), jnp.asarray(w))
    )
