"""Device-side leaf-wise tree growth + prediction kernels.

The TPU replacement for LightGBM's native histogram trainer
(lightgbm/TrainUtils.scala:220-315 drives `LGBM_BoosterUpdateOneIter`,
whose C++ internally builds per-leaf histograms and allreduces them across
workers over sockets). Here:

- the WHOLE per-tree growth loop is ONE jitted XLA program
  (``lax.fori_loop`` over split steps; static shapes L-1 steps);
- histograms are scatter-adds into a (num_leaves x features x bins) cube;
  under a row-sharded mesh GSPMD turns the scatter into partial histograms
  + an ICI allreduce — exactly LightGBM's data_parallel mode
  (LightGBMConstants "data_parallel", LightGBMParams.scala:13-18) with XLA
  collectives instead of socket rings;
- prediction replays split records with ``lax.scan`` — vectorized over
  rows x trees, no pointer-chasing (TPU-friendly tree inference).

Convention: a split sends ``bin <= threshold_bin`` (and missing/NaN) LEFT;
the left child keeps the parent's leaf id, the right child gets a fresh id.
Trees are therefore fully described by the ordered split records + leaf
values — LightGBM's leaf-wise growth expressed as a replay log.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.ops.histogram import NUM_BINS  # uint8 bin space; bin 0 = missing

log = logging.getLogger("mmlspark_tpu.gbdt")


class GrownTree(NamedTuple):
    """Device outputs of one grown tree (fixed shapes; L = num_leaves)."""

    rec_leaf: jnp.ndarray      # (L-1,) int32 parent leaf id per split
    rec_feature: jnp.ndarray   # (L-1,) int32
    rec_bin: jnp.ndarray       # (L-1,) int32 threshold bin (<= goes left)
    rec_active: jnp.ndarray    # (L-1,) bool: split actually made
    rec_gain: jnp.ndarray      # (L-1,) float32
    leaf_values: jnp.ndarray   # (L,) float32 (shrinkage applied)
    leaf_counts: jnp.ndarray   # (L,) int32
    row_leaf: jnp.ndarray      # (n,) int32 final leaf of every row
    rec_is_cat: jnp.ndarray    # (L-1,) bool: categorical subset split
    rec_catmask: jnp.ndarray   # (L-1, B) bool: bins going LEFT (cat splits)
    # (4,) int32, or None from a grower that does not count: the rows its
    # histogram calls were handed and the rows of them that counted (the
    # masked grower: what the masks selected; the partitioned one: the
    # smaller children), each as (// 4096, % 4096) so an f32 record carries
    # them exactly
    hist_rows: Optional[jnp.ndarray] = None


def threshold_l1(G: jnp.ndarray, l1: Any) -> jnp.ndarray:
    """LightGBM ThresholdL1: sign(G) * max(|G| - l1, 0). The ONE L1
    soft-threshold both growers (single-chip and voting) share."""
    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)


def split_gain_term(G: jnp.ndarray, H: jnp.ndarray, lam: Any, l1: Any) -> jnp.ndarray:
    """One side's contribution to split gain: ThresholdL1(G)^2 / (H + lam)."""
    t = threshold_l1(G, l1)
    return t * t / (H + lam)


def make_leaf_best(
    d: int,
    feature_mask: jnp.ndarray,
    min_data_in_leaf: int,
    msh: Any,
    lam: Any,
    l1: Any,
    cat_f: jnp.ndarray,
    has_categorical: bool,
    num_bins: int = NUM_BINS,
):
    """Best-split search over ONE leaf's (d*B, 3) histogram plane — the
    single source of split semantics shared by the leaf-wise (lossguide)
    and depthwise growers. Returns (gain, feature, bin/prefix, catmask)."""
    B = num_bins

    def gscore(Gv: jnp.ndarray, Hv: jnp.ndarray) -> jnp.ndarray:
        return split_gain_term(Gv, Hv, lam, l1)

    def leaf_best(plane: jnp.ndarray) -> tuple:
        cube = plane.reshape(d, B, 3)
        hg, hh, hc = cube[..., 0], cube[..., 1], cube[..., 2]
        cg = jnp.cumsum(hg, axis=1)
        ch = jnp.cumsum(hh, axis=1)
        cc = jnp.cumsum(hc, axis=1)
        G, H, C = cg[:, -1:], ch[:, -1:], cc[:, -1:]
        GL, HL, CL = cg, ch, cc
        GR, HR, CR = G - GL, H - HL, C - CL
        gain_num = gscore(GL, HL) + gscore(GR, HR) - gscore(G, H)
        feat_ok = (feature_mask > 0)[:, None]
        valid_num = (
            feat_ok
            & (CL >= min_data_in_leaf) & (CR >= min_data_in_leaf)
            & (HL >= msh) & (HR >= msh)
        )
        if has_categorical:
            # categorical subset split (LightGBM's sorted-by-ratio scan:
            # order category bins by G/H, then the best LEFT set is some
            # prefix — Fisher's optimal-partition result for convex
            # losses). ``bb`` for a categorical split is the PREFIX LENGTH
            # in this order, not a bin.
            ratio = jnp.where(hc > 0, hg / (hh + 1e-12), -jnp.inf)
            order = jnp.argsort(-ratio, axis=1)  # (d, B) bin ids, best first
            sgs = jnp.take_along_axis(hg, order, 1)
            shs = jnp.take_along_axis(hh, order, 1)
            scs = jnp.take_along_axis(hc, order, 1)
            cgs = jnp.cumsum(sgs, axis=1)
            chs = jnp.cumsum(shs, axis=1)
            ccs = jnp.cumsum(scs, axis=1)
            gain_cat = (
                gscore(cgs, chs) + gscore(G - cgs, H - chs) - gscore(G, H)
            )
            valid_cat = (
                feat_ok
                & (ccs >= min_data_in_leaf)
                & ((C - ccs) >= min_data_in_leaf)
                & (chs >= msh) & ((H - chs) >= msh)
            )
            gain = jnp.where(
                cat_f[:, None],
                jnp.where(valid_cat, gain_cat, -jnp.inf),
                jnp.where(valid_num, gain_num, -jnp.inf),
            )
        else:
            gain = jnp.where(valid_num, gain_num, -jnp.inf)
        flat = gain.reshape(-1)
        best = jnp.argmax(flat)
        bf = (best // B).astype(jnp.int32)
        bb = (best % B).astype(jnp.int32)
        if has_categorical:
            # left-set membership per bin for the chosen feature:
            # rank[bin] = position of bin in the sorted order; prefix <= bb
            order_sel = order[bf]                 # (B,)
            rank = jnp.argsort(order_sel)         # inverse permutation
            catmask = rank <= bb                  # (B,) bool: LEFT bins
        else:
            catmask = jnp.zeros((B,), bool)
        return flat[best], bf, bb, catmask

    return leaf_best


@dataclasses.dataclass(frozen=True)
class Grower:
    """Which grower a fit's trees come from, and for what: the one value
    :func:`choose_grower` returns. Hashable, so it is the one static
    argument that says it to a jitted program — a program traced for one
    lowering or layout cannot be reused under another (for the values the
    rule builds: see ``lowering``)."""

    # partitioned | masked | hostcall (leaf-wise), depthwise |
    # depthwise_hostcall (level-wise), voting
    kind: str
    # the one-device histogram lowering of the target device the choice
    # was made for (pallas | cpu | scatter, ops/histogram.py). A key and
    # a record, not a selector: no grower or kernel reads it, and the
    # kernels still ask hist_lowering(mesh) at trace time, so a Grower
    # built by hand with another device's lowering runs this device's
    lowering: str
    # the caller's mesh and the axis its rows are sharded over. None: a
    # one-device call on the process's default device. With no rows
    # sharded over it the mesh only names the device the kernels are
    # lowered for
    mesh: Any = None
    shard_axis: Optional[str] = None
    # the level-wise growers' variants; tests build the other values as
    # references
    sibling_subtract: bool = True
    vector_split: bool = True


def choose_grower(
    growth_policy: str = "lossguide",
    voting: bool = False,
    mesh: Any = None,
    shard_axis: Optional[str] = None,
    lowering: Optional[str] = None,
) -> Grower:
    """Which grower a layout gets — the one rule, read from what the code
    can see: the growth policy, whether voting-parallel was asked for, the
    mesh and the axis that shards its rows, and the histogram lowering of
    the device the mesh names (``lowering``; None asks
    :func:`ops.histogram.hist_lowering`).

    - ``voting`` (:func:`voting.grow_tree_voting`) where it was asked for
      and the rows are sharded; on one shard there is nobody to vote with
      and the request falls back to what follows;
    - level-wise: ``depthwise_hostcall`` (the whole tree behind one host
      callback — a per-level histogram callback alone leaves ~9 ms/tree of
      XLA:CPU glue plus ~1 ms of bridge cost per crossing) on an unsharded
      CPU lowering, ``depthwise`` (:func:`_grow_tree_depthwise`) anywhere
      else. Sibling subtraction is always on; the vectorized level
      application pays on a TPU (the chain of tiny dependent ops per split
      dominates wall clock there) and costs ~30% on a CPU (full-width
      scatters per level), so it follows the platform;
    - leaf-wise: ``masked`` (:func:`_grow_tree`) where the rows are sharded
      over the mesh: a split is one masked pass per shard plus a plane
      ``psum``; the partitioned grower's global row permutation would
      cross chips;
    - ``partitioned`` (:func:`_grow_tree_partitioned`) on one device with
      the Pallas lowering, i.e. one TPU chip: a split costs its leaf's
      rows. ``higgs_gbdt_fit`` on one v5e (PERF.md section 6, builder's
      chip runs, PR 26; 2,625,000 x 28, 255 leaves): 479 device-ms a
      tree and 1.147 trees/s, against the masked grower's 4,733 and
      0.195;
    - ``hostcall`` (:func:`_grow_tree_lossguide_hostcall`) on one CPU
      device: the whole tree behind one host callback;
    - ``masked`` for what is left (one device, XLA scatter lowering).

    The trainer asks once, before it traces its round program. Tests force
    a grower by replacing this function."""
    from mmlspark_tpu.ops.histogram import (
        _rows_sharded,
        _target_device,
        hist_lowering,
    )

    if mesh is None:
        shard_axis = None
    if lowering is None:
        lowering = hist_lowering(mesh)
    sharded = _rows_sharded(mesh, shard_axis)
    if voting and not sharded:
        log.info(
            "voting_parallel needs >1 data shard; "
            "falling back to data_parallel"
        )
        voting = False
    vector_split = True
    if voting:
        kind = "voting"
    elif growth_policy == "depthwise":
        hostcall = lowering == "cpu" and not sharded
        kind = "depthwise_hostcall" if hostcall else "depthwise"
        vector_split = _target_device(mesh).platform == "tpu"
    elif sharded:
        kind = "masked"
    elif lowering == "pallas" and (mesh is None or mesh.devices.size == 1):
        kind = "partitioned"
    else:
        kind = "hostcall" if lowering == "cpu" else "masked"
    return Grower(
        kind, lowering, mesh, shard_axis, vector_split=vector_split
    )


def grow_tree(
    bins: jnp.ndarray,            # (n, d) uint8/int32
    grad: jnp.ndarray,            # (n,) f32
    hess: jnp.ndarray,            # (n,) f32
    row_weight: jnp.ndarray,      # (n,) f32 (bagging/validation mask; 0 = ignore)
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,    # (d,) f32 1/0 (feature_fraction)
    max_depth: int = -1,
    min_data_in_leaf: int = 20,
    categorical_mask: Optional[jnp.ndarray] = None,  # (d,) bool
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    grower: Optional[Grower] = None,
    top_k: int = 20,
) -> GrownTree:
    """Grow one tree with ``grower`` — what :func:`choose_grower` decided
    for the caller's layout; None asks it for a leaf-wise tree on the
    process's default device. The one dispatch on that value.

    The categorical-split machinery (per-leaf argsort of category bins) is
    statically compiled OUT when ``categorical_mask`` is None — the common
    all-numerical case pays nothing for it.

    ``lambda_l1`` soft-thresholds gradient sums in both split gains and
    leaf values; ``min_sum_hessian`` invalidates splits whose child
    hessian mass is below it (LightGBM lambda_l1 /
    min_sum_hessian_in_leaf semantics). ``top_k`` is the voting grower's
    ballot size."""
    if grower is None:
        grower = choose_grower()
    hyper = dict(
        num_leaves=num_leaves, lambda_l2=lambda_l2, min_gain=min_gain,
        learning_rate=learning_rate, feature_mask=feature_mask,
        max_depth=max_depth, min_data_in_leaf=min_data_in_leaf,
        lambda_l1=lambda_l1, min_sum_hessian=min_sum_hessian,
        num_bins=num_bins,
    )
    if grower.kind == "voting":
        from mmlspark_tpu.models.gbdt.voting import grow_tree_voting

        return grow_tree_voting(
            bins, grad, hess, row_weight, top_k=top_k, mesh=grower.mesh,
            axis=grower.shard_axis, categorical_mask=categorical_mask,
            **hyper,
        )
    if grower.kind in ("depthwise", "depthwise_hostcall"):
        return grow_tree_depthwise(
            bins, grad, hess, row_weight, categorical_mask=categorical_mask,
            grower=grower, **hyper,
        )
    has_categorical = categorical_mask is not None
    if not has_categorical:
        categorical_mask = jnp.zeros((bins.shape[1],), bool)
    hyper.update(
        categorical_mask=categorical_mask, has_categorical=has_categorical
    )
    if grower.kind == "hostcall":
        # CPU lowering: the whole leaf-wise tree behind ONE host callback
        # (see choose_grower for the cost argument)
        return _grow_tree_lossguide_hostcall(
            bins, grad, hess, row_weight, **hyper
        )
    if grower.kind == "partitioned":
        return _grow_tree_partitioned(
            bins, grad, hess, row_weight, grower=grower, **hyper
        )
    if grower.kind != "masked":
        raise ValueError(f"unknown grower {grower.kind!r}")
    return _grow_tree(bins, grad, hess, row_weight, grower=grower, **hyper)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "max_depth", "min_data_in_leaf", "has_categorical",
        "num_bins", "grower",
    ),
)
def _grow_tree(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    max_depth: int,
    min_data_in_leaf: int,
    categorical_mask: jnp.ndarray,
    has_categorical: bool,
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    grower: Grower = Grower("masked", "scatter"),
) -> GrownTree:
    mesh, shard_axis = grower.mesh, grower.shard_axis
    n, d = bins.shape
    L = num_leaves
    B = num_bins
    with jax.named_scope("gbdt.hist.widen"):
        bins = bins.astype(jnp.int32)
    cat_f = categorical_mask.astype(bool)
    lam = lambda_l2
    l1 = lambda_l1
    msh = min_sum_hessian
    g = grad * row_weight
    h = hess * row_weight
    cnt_w = row_weight

    def soft(Gv: jnp.ndarray) -> jnp.ndarray:
        return threshold_l1(Gv, l1)

    # per-row (g, h, count) stats; the histogram op picks its lowering
    # (Pallas one-hot matmul on TPU — per shard + psum under a mesh —
    # host bincount / scatter on CPU) — see ops/histogram.py
    from mmlspark_tpu.ops.histogram import plane_histogram

    row_stats = jnp.stack([g, h, cnt_w], axis=-1)  # (n, 3)

    def plane_hist(mask: jnp.ndarray) -> jnp.ndarray:
        """Histogram of the rows selected by ``mask`` -> (d*B, 3)."""
        return plane_histogram(
            bins, row_stats, mask, num_bins=B, mesh=mesh,
            shard_axis=shard_axis, bins_in_range=True,
        )

    def selected_rows(plane: jnp.ndarray) -> jnp.ndarray:
        """The rows a histogram call's mask selected, read off its plane:
        every row falls in one bin of feature 0, so that feature's count
        channel sums to the (weighted) count. No pass over the rows."""
        return jnp.round(plane[:B, 2].sum()).astype(jnp.int32)

    # best split of ONE leaf from its plane. Only state-free validity
    # (min_data, feature_fraction) is applied there; per-leaf state
    # (activity, depth) is applied at selection time, so cached results
    # stay exact until the leaf's histogram changes.
    leaf_best = make_leaf_best(
        d, feature_mask, min_data_in_leaf, msh, lam, l1, cat_f,
        has_categorical, num_bins=B,
    )

    def step(k: int, state: tuple) -> tuple:
        (hist, row_leaf, leaf_depth, done,
         cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
         rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
         rec_is_cat, rec_catmask, sel_hi, sel_lo) = state

        with jax.named_scope("gbdt.best_split"):
            # hist is carried incrementally: (L, d*B, 3) cube, only the two
            # children of the previous split changed (LightGBM's
            # parent-minus-child trick). The split-search cache mirrors
            # that: re-evaluate ONLY those two leaves' planes, keep every
            # other leaf's cached best split (their histograms are
            # untouched).
            pg, pf, pb, pcm = jax.vmap(leaf_best)(hist[prev_pair])
            cache_gain = cache_gain.at[prev_pair].set(pg)
            cache_feat = cache_feat.at[prev_pair].set(pf)
            cache_bin = cache_bin.at[prev_pair].set(pb)
            cache_catmask = cache_catmask.at[prev_pair].set(pcm)

            # selection: apply the per-leaf state masks to the cached gains
            num_active = k + 1
            leaf_ids = jnp.arange(L, dtype=jnp.int32)
            leaf_ok = leaf_ids < num_active
            if max_depth > 0:
                leaf_ok = leaf_ok & (leaf_depth < max_depth)
            sel = jnp.where(leaf_ok, cache_gain, -jnp.inf)
            bl = jnp.argmax(sel).astype(jnp.int32)
            best_gain = sel[bl]
            bf = cache_feat[bl]
            bb = cache_bin[bl]
            catmask = cache_catmask[bl]
            do_split = (
                (~done) & (best_gain > min_gain) & jnp.isfinite(best_gain)
            )

        new_id = jnp.int32(k + 1)
        with jax.named_scope("gbdt.apply_split"):
            in_leaf = row_leaf == bl
            row_bins = bins[:, bf]
            if has_categorical:
                is_cat_split = cat_f[bf]
                goes_right = in_leaf & jnp.where(
                    is_cat_split, ~catmask[row_bins], row_bins > bb
                )
            else:
                is_cat_split = jnp.asarray(False)
                goes_right = in_leaf & (row_bins > bb)
            moved = do_split & goes_right
            row_leaf = jnp.where(moved, new_id, row_leaf)
            moved_f = moved.astype(jnp.float32)
        # incremental histogram update: scatter only the moved rows into the
        # right child's plane; the parent keeps (old - right)
        right_plane = plane_hist(moved_f)
        picked = selected_rows(right_plane)
        sel_hi = sel_hi + picked // 4096
        sel_lo = sel_lo + picked % 4096
        with jax.named_scope("gbdt.apply_split"):
            hist = hist.at[new_id].set(right_plane).at[bl].add(
                jnp.where(do_split, -right_plane, 0.0)
            )
            child_depth = leaf_depth[bl] + 1
            leaf_depth = jnp.where(
                do_split,
                leaf_depth.at[bl].set(child_depth).at[new_id].set(child_depth),
                leaf_depth,
            )
            rec_leaf = rec_leaf.at[k].set(jnp.where(do_split, bl, -1))
            rec_feature = rec_feature.at[k].set(jnp.where(do_split, bf, -1))
            rec_bin = rec_bin.at[k].set(jnp.where(do_split, bb, -1))
            rec_active = rec_active.at[k].set(do_split)
            rec_gain = rec_gain.at[k].set(
                jnp.where(do_split, best_gain, 0.0)
            )
            rec_is_cat = rec_is_cat.at[k].set(do_split & is_cat_split)
            rec_catmask = rec_catmask.at[k].set(
                jnp.where(do_split & is_cat_split, catmask, False)
            )
            done = done | ~do_split
            # the two leaves whose planes changed — next step refreshes them
            prev_pair = jnp.stack([bl, new_id])
        return (hist, row_leaf, leaf_depth, done,
                cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
                rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
                rec_is_cat, rec_catmask, sel_hi, sel_lo)

    # root histogram: the only full-data cube write of the whole tree
    root_plane = plane_hist(jnp.ones((n,), jnp.float32))
    root_rows = selected_rows(root_plane)
    hist0 = jnp.zeros((L, d * B, 3), jnp.float32).at[0].set(root_plane)
    init = (
        hist0,
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((L,), jnp.int32),
        jnp.asarray(False),
        jnp.full((L,), -jnp.inf, jnp.float32),   # cache_gain
        jnp.zeros((L,), jnp.int32),              # cache_feat
        jnp.zeros((L,), jnp.int32),              # cache_bin
        jnp.zeros((L, B), bool),                 # cache_catmask
        jnp.zeros((2,), jnp.int32),              # prev_pair: root twice
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.zeros((L - 1,), bool),
        jnp.zeros((L - 1,), jnp.float32),
        jnp.zeros((L - 1,), bool),
        jnp.zeros((L - 1, B), bool),
        root_rows // 4096,                       # sel_hi
        root_rows % 4096,                        # sel_lo
    )
    (_, row_leaf, _, _, _, _, _, _, _,
     rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
     rec_is_cat, rec_catmask, sel_hi, sel_lo) = (
        jax.lax.fori_loop(0, L - 1, step, init)
    )

    # leaf values: -ThresholdL1(G)/(H+lambda) * lr per final leaf
    from mmlspark_tpu.ops.histogram import leaf_stat_sums

    sums = leaf_stat_sums(
        row_leaf, row_stats, L, mesh=mesh, shard_axis=shard_axis
    )
    Gl, Hl, Cl = sums[:, 0], sums[:, 1], sums[:, 2]
    leaf_values = -soft(Gl) / (Hl + lambda_l2) * learning_rate
    leaf_values = jnp.where(Cl > 0, leaf_values, 0.0)
    # one histogram call per step (a finished tree's steps still stream the
    # rows, under an empty mask) and one for the root: L calls of n rows
    streamed = n * L
    hist_rows = jnp.stack([
        jnp.int32(streamed // 4096), jnp.int32(streamed % 4096), sel_hi, sel_lo,
    ])
    return GrownTree(
        rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
        leaf_values, Cl.astype(jnp.int32), row_leaf,
        rec_is_cat, rec_catmask, hist_rows,
    )


def _range_sizes(n: int, min_size: int = 512) -> tuple:
    """Static power-of-2 row-bucket sizes for the partitioned grower: the
    smallest bucket covering a range's row count bounds overshoot at 2x."""
    sizes = []
    s = min(min_size, n)
    while s < n:
        sizes.append(s)
        s *= 2
    sizes.append(n)
    return tuple(sizes)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "max_depth", "min_data_in_leaf", "has_categorical",
        "num_bins", "grower",
    ),
)
def _grow_tree_partitioned(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    max_depth: int,
    min_data_in_leaf: int,
    categorical_mask: jnp.ndarray,
    has_categorical: bool,
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    grower: Grower = Grower("partitioned", "pallas"),
) -> GrownTree:
    """Leaf-wise growth over rows kept PARTITIONED by leaf — the TPU
    expression of LightGBM's DataPartition + histogram-subtraction core
    (the reason native LightGBM's per-split cost is O(leaf rows), not
    O(dataset rows); TrainUtils.scala:220-315 drives that C++ engine).

    Identical split semantics to :func:`_grow_tree` (same ``make_leaf_best``,
    same records); only the COST model changes: a split does device work
    proportional to the rows of the leaf it splits, never to n.

    - the loop carries ``rows``, (1 + ceil(d/4), n) int32 with the
      128-lane dimension on the positions: a position's row id and its
      uint8 bins packed four to a word. Every leaf owns a contiguous
      [start, start+count) range of positions. The stats stay in row
      order and are fetched through the row ids;
    - a split takes the smallest static power-of-2 bucket that covers the
      PARENT's range, slices it out of ``rows``, partitions it stably
      (left block, right block; positions of the bucket outside the
      range stay put) with ONE sort of all its rows on a key that is the
      side first and the position second, and writes it back with
      ``dynamic_update_slice``. On the v5e a sort moves a row's nine
      words for 3-6 ns, where fetching one word of it through a
      permutation costs 7-15 ns (PERF.md section 6, PR 26);
    - the new histogram pass covers ONLY the smaller child's bucket, a
      slice — the larger sibling is parent - smaller (LightGBM's
      subtraction trick). The root is the one full pass of a tree;
    - every shape is static, one branch a bucket size. The histogram,
      which only reads ``rows``, picks its branch with a ``lax.switch``.
      The partition, which hands ``rows`` on, runs every bucket's branch
      as a loop of one turn or none: XLA:TPU updates a loop's carry in
      place and copies all of what a ``switch`` returns, twice a split
      (84 MB each at Higgs' size). The reverse holds for what a branch
      only reads: as loops, the histogram's branches cost a copy of
      ``rows`` each, 20x the whole tree (PERF.md section 6, PR 26). A
      step that makes no split (a finished tree) runs no branch: no
      bucket, no kernel call.

    One-device layouts only: the partition is a global permutation, which
    would become cross-device traffic under a sharded mesh
    (:func:`choose_grower` keeps those on :func:`_grow_tree`). The
    grower's mesh only names the device the kernels are lowered for."""
    from mmlspark_tpu.ops.histogram import leaf_stat_sums, plane_histogram

    mesh = grower.mesh
    n, d = bins.shape
    L = num_leaves
    B = num_bins
    if B > 256:
        raise ValueError(f"bins are packed as bytes: num_bins {B} > 256")
    cat_f = categorical_mask.astype(bool)
    lam = lambda_l2
    l1 = lambda_l1
    msh = min_sum_hessian
    g = grad * row_weight
    h = hess * row_weight
    row_stats = jnp.stack([g, h, row_weight], axis=-1)  # (n, 3), row order
    sizes = _range_sizes(n)
    sizes_arr = jnp.asarray(sizes, jnp.int32)
    branch_rows = jnp.asarray((0,) + sizes, jnp.int32)  # by bucket_branch
    W = -(-d // 4)  # words of four bins

    leaf_best = make_leaf_best(
        d, feature_mask, min_data_in_leaf, msh, lam, l1, cat_f,
        has_categorical, num_bins=B,
    )

    def bucket_branch(count: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
        """1 + the index in ``sizes`` of the smallest bucket covering
        ``count`` rows; 0, no bucket, for a step that splits nothing."""
        covering = 1 + jnp.sum(count > sizes_arr).astype(jnp.int32)
        return jnp.where(live, covering, 0)

    def bucket_of(rows: jnp.ndarray, start: jnp.ndarray, sz: int) -> tuple:
        """The ``sz`` positions from (about) ``start`` on: their slice of
        ``rows``, where the bucket begins, each position's offset in it
        and the offset at which the range starts."""
        st = jnp.clip(start, 0, n - sz)
        part = jax.lax.dynamic_slice_in_dim(rows, st, sz, 1)
        return part, st, jnp.arange(sz, dtype=jnp.int32), start - st

    def step(k: int, state: tuple) -> tuple:
        (hist, rows, leaf_start, leaf_count, leaf_depth, done,
         cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
         rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
         rec_is_cat, rec_catmask, hist_hi, hist_lo) = state

        with jax.named_scope("gbdt.best_split"):
            # refresh the two planes the previous split changed (all other
            # leaves' cached best splits are still exact)
            pg, pf, pb, pcm = jax.vmap(leaf_best)(hist[prev_pair])
            cache_gain = cache_gain.at[prev_pair].set(pg)
            cache_feat = cache_feat.at[prev_pair].set(pf)
            cache_bin = cache_bin.at[prev_pair].set(pb)
            cache_catmask = cache_catmask.at[prev_pair].set(pcm)

            num_active = k + 1
            leaf_ids = jnp.arange(L, dtype=jnp.int32)
            leaf_ok = leaf_ids < num_active
            if max_depth > 0:
                leaf_ok = leaf_ok & (leaf_depth < max_depth)
            sel = jnp.where(leaf_ok, cache_gain, -jnp.inf)
            bl = jnp.argmax(sel).astype(jnp.int32)
            best_gain = sel[bl]
            bf = cache_feat[bl]
            bb = cache_bin[bl]
            catmask = cache_catmask[bl]
            do_split = (
                (~done) & (best_gain > min_gain) & jnp.isfinite(best_gain)
            )
            is_cat_split = cat_f[bf] if has_categorical else jnp.asarray(False)
            new_id = jnp.int32(k + 1)
            s = leaf_start[bl]
            c = leaf_count[bl]

        def partition(sz: int):
            def f(_: int, carry: tuple) -> tuple:
                rows, _ = carry
                part, st, local, first = bucket_of(rows, s, sz)
                word = jax.lax.dynamic_index_in_dim(part, 1 + bf // 4, 0, False)
                col = (word >> (8 * (bf % 4))) & 255
                if has_categorical:
                    right = jnp.where(is_cat_split, ~catmask[col], col > bb)
                else:
                    right = col > bb
                # side first, position second: before the range, its left
                # rows, its right rows, after it. Sorting on it IS the
                # stable partition, and no two keys are equal
                side = jnp.where(
                    local < first, 0,
                    jnp.where(local >= first + c, 3, jnp.where(right, 2, 1)),
                )
                moved = jax.lax.sort(
                    (side * sz + local,) + tuple(part), num_keys=1
                )[1:]
                return (
                    jax.lax.dynamic_update_slice_in_dim(
                        rows, jnp.stack(moved), st, 1
                    ),
                    jnp.sum(side == 1).astype(jnp.int32),
                )
            return f

        with jax.named_scope("gbdt.partition"):
            # the bucket's branch as a loop of one turn, every other
            # bucket's as a loop of none (see the docstring)
            part_branch = bucket_branch(c, do_split)
            c_left = jnp.int32(0)
            for i, sz in enumerate(sizes):
                rows, c_left = jax.lax.fori_loop(
                    0, (part_branch == i + 1).astype(jnp.int32),
                    partition(sz), (rows, c_left),
                )
        c_right = c - c_left
        small_left = c_left <= c_right
        s_small = jnp.where(small_left, s, s + c_left)
        c_small = jnp.minimum(c_left, c_right)

        def smaller_child(sz: int):
            def f(rows: jnp.ndarray) -> jnp.ndarray:
                part, _, local, first = bucket_of(rows, s_small, sz)
                # or XLA:TPU lays ALL of ``rows`` out anew for the
                # transposition below, in every branch
                part = jax.lax.optimization_barrier(part)
                with jax.named_scope("gbdt.partition"):
                    ssl = jnp.take(row_stats, part[0], axis=0)  # (sz, 3)
                with jax.named_scope("gbdt.hist.widen"):
                    packed = part[1:]
                    bsl = jnp.stack(
                        [(packed >> (8 * j)) & 255 for j in range(4)], axis=1
                    ).reshape(4 * W, sz)[:d].T
                m = ((local >= first) & (local < first + c_small)).astype(
                    jnp.float32
                )
                return plane_histogram(
                    bsl, ssl, m, num_bins=B, mesh=mesh, bins_in_range=True
                )
            return f

        # smaller child's histogram from its (now contiguous) range
        hist_branch = bucket_branch(c_small, do_split)
        small_plane = jax.lax.switch(
            hist_branch,
            [lambda rows: jnp.zeros((d * B, 3), jnp.float32)]
            + [smaller_child(sz) for sz in sizes],
            rows,
        )
        with jax.named_scope("gbdt.apply_split"):
            # the rows this step's histogram call was handed, and those
            # of them in the smaller child
            counted = jnp.stack(
                [branch_rows[hist_branch], jnp.where(do_split, c_small, 0)]
            )
            hist_hi = hist_hi + counted // 4096
            hist_lo = hist_lo + counted % 4096
            parent_plane = hist[bl]
            big_plane = parent_plane - small_plane
            left_plane = jnp.where(small_left, small_plane, big_plane)
            right_plane = jnp.where(small_left, big_plane, small_plane)
            hist = hist.at[bl].set(
                jnp.where(do_split, left_plane, parent_plane)
            ).at[new_id].set(
                jnp.where(do_split, right_plane, hist[new_id])
            )
            leaf_start = jnp.where(
                do_split, leaf_start.at[new_id].set(s + c_left), leaf_start
            )
            leaf_count = jnp.where(
                do_split,
                leaf_count.at[bl].set(c_left).at[new_id].set(c_right),
                leaf_count,
            )
            child_depth = leaf_depth[bl] + 1
            leaf_depth = jnp.where(
                do_split,
                leaf_depth.at[bl].set(child_depth).at[new_id].set(child_depth),
                leaf_depth,
            )
            rec_leaf = rec_leaf.at[k].set(jnp.where(do_split, bl, -1))
            rec_feature = rec_feature.at[k].set(jnp.where(do_split, bf, -1))
            rec_bin = rec_bin.at[k].set(jnp.where(do_split, bb, -1))
            rec_active = rec_active.at[k].set(do_split)
            rec_gain = rec_gain.at[k].set(
                jnp.where(do_split, best_gain, 0.0)
            )
            rec_is_cat = rec_is_cat.at[k].set(do_split & is_cat_split)
            rec_catmask = rec_catmask.at[k].set(
                jnp.where(do_split & is_cat_split, catmask, False)
            )
            done = done | ~do_split
            prev_pair = jnp.stack([bl, new_id])
        return (hist, rows, leaf_start, leaf_count, leaf_depth, done,
                cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
                rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
                rec_is_cat, rec_catmask, hist_hi, hist_lo)

    # root histogram: the one full pass of a tree
    hist0 = (
        jnp.zeros((L, d * B, 3), jnp.float32)
        .at[0]
        .set(plane_histogram(
            bins, row_stats, num_bins=B, mesh=mesh, bins_in_range=True
        ))
    )
    with jax.named_scope("gbdt.partition"):
        # once a tree: the rows as the loop moves them (bins of value
        # < 256 by the caller's word, four to an int32)
        quads = jnp.pad(bins.astype(jnp.int32), ((0, 0), (0, 4 * W - d)))
        quads = quads.reshape(n, W, 4)
        words = (quads[..., 0] | (quads[..., 1] << 8)
                 | (quads[..., 2] << 16) | (quads[..., 3] << 24)).T
        rows0 = jnp.concatenate([jnp.arange(n, dtype=jnp.int32)[None], words])
    init = (
        hist0,
        rows0,                                    # row ids and packed bins, by position
        jnp.zeros((L,), jnp.int32),               # leaf_start
        jnp.zeros((L,), jnp.int32).at[0].set(n),  # leaf_count
        jnp.zeros((L,), jnp.int32),               # leaf_depth
        jnp.asarray(False),
        jnp.full((L,), -jnp.inf, jnp.float32),
        jnp.zeros((L,), jnp.int32),
        jnp.zeros((L,), jnp.int32),
        jnp.zeros((L, B), bool),
        jnp.zeros((2,), jnp.int32),
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.full((L - 1,), -1, jnp.int32),
        jnp.zeros((L - 1,), bool),
        jnp.zeros((L - 1,), jnp.float32),
        jnp.zeros((L - 1,), bool),
        jnp.zeros((L - 1, B), bool),
        jnp.asarray([n // 4096, n // 4096], jnp.int32),  # hist_hi: handed, picked
        jnp.asarray([n % 4096, n % 4096], jnp.int32),    # hist_lo
    )
    (_, rows, leaf_start, leaf_count, _, _,
     _, _, _, _, _,
     rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
     rec_is_cat, rec_catmask, hist_hi, hist_lo) = (
        jax.lax.fori_loop(0, L - 1, step, init)
    )

    with jax.named_scope("gbdt.partition"):
        # position -> leaf from the final ranges (they tile [0, n): each
        # position lies in exactly one of them; an unused leaf's is empty),
        # then back to row order through the row ids: the one n-long
        # scatter of a tree
        pos = jnp.arange(n, dtype=jnp.int32)[:, None]
        in_leaf = (pos >= leaf_start[None, :]) & (
            pos < (leaf_start + leaf_count)[None, :]
        )
        leaf_of_pos = jnp.argmax(in_leaf, axis=1).astype(jnp.int32)
        row_leaf = jnp.zeros((n,), jnp.int32).at[rows[0]].set(
            leaf_of_pos, unique_indices=True
        )

    sums = leaf_stat_sums(row_leaf, row_stats, L, mesh=mesh)
    Gl, Hl, Cl = sums[:, 0], sums[:, 1], sums[:, 2]
    leaf_values = -threshold_l1(Gl, lambda_l1) / (Hl + lambda_l2) * learning_rate
    leaf_values = jnp.where(Cl > 0, leaf_values, 0.0)
    hist_rows = jnp.stack([hist_hi[0], hist_lo[0], hist_hi[1], hist_lo[1]])
    return GrownTree(
        rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
        leaf_values, Cl.astype(jnp.int32), row_leaf,
        rec_is_cat, rec_catmask, hist_rows,
    )


def grow_tree_depthwise(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    max_depth: int = -1,
    min_data_in_leaf: int = 20,
    categorical_mask: Optional[jnp.ndarray] = None,
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    grower: Optional[Grower] = None,
) -> GrownTree:
    """Depthwise (level-wise) growth — the XGBoost-hist/SparkML-GBT grow
    policy, built for the TPU cost model: every level's leaf histograms
    come from ONE ``multi_plane_histogram`` pass over the rows, so a tree
    costs O(depth) row passes instead of lossguide's O(num_leaves). Split
    semantics (gain, min_data, L1/hessian floors, categorical subsets)
    come from the same ``make_leaf_best`` as the leaf-wise grower; output
    is the identical GrownTree record format.

    With ``max_depth`` unset, depth caps at ceil(log2(num_leaves)) — the
    balanced depth that can realize the leaf budget.

    Sibling subtraction (LightGBM's histogram-subtraction trick;
    ``grower.sibling_subtract``, on in every grower the rule returns): from
    level 1 on, only the RIGHT child of every pair is histogrammed and the
    left plane is derived as parent - right. The multi-plane kernel's MXU
    cost scales with the slot count, so this halves the dominant
    per-level matmul width — the per-tree histogram work drops from
    ~2*num_leaves to ~num_leaves plane-equivalents.

    ``grower``: one of :func:`choose_grower`'s two level-wise growers; None
    asks it for the process's default device."""
    if grower is None:
        grower = choose_grower("depthwise")
    has_categorical = categorical_mask is not None
    if not has_categorical:
        categorical_mask = jnp.zeros((bins.shape[1],), bool)
    L = int(num_leaves)
    # levels beyond the leaf budget can never split anything: cap the
    # static unroll so a huge max_depth doesn't emit useless row passes
    n_levels = (
        min(int(max_depth), L - 1) if max_depth > 0
        else max(1, int(np.ceil(np.log2(L))))
    )
    if grower.kind == "depthwise_hostcall":
        return _grow_tree_depthwise_hostcall(
            bins, grad, hess, row_weight,
            num_leaves=L, n_levels=n_levels, num_bins=num_bins,
            min_data_in_leaf=min_data_in_leaf, min_gain=min_gain,
            lambda_l2=lambda_l2, lambda_l1=lambda_l1,
            min_sum_hessian=min_sum_hessian, learning_rate=learning_rate,
            feature_mask=feature_mask, categorical_mask=categorical_mask,
            has_categorical=has_categorical,
            sibling_subtract=grower.sibling_subtract,
        )
    if grower.kind != "depthwise":
        raise ValueError(f"not a level-wise grower: {grower.kind!r}")
    return _grow_tree_depthwise(
        bins, grad, hess, row_weight,
        num_leaves=L, lambda_l2=lambda_l2, min_gain=min_gain,
        learning_rate=learning_rate, feature_mask=feature_mask,
        n_levels=n_levels, min_data_in_leaf=min_data_in_leaf,
        categorical_mask=categorical_mask, has_categorical=has_categorical,
        lambda_l1=lambda_l1, min_sum_hessian=min_sum_hessian,
        num_bins=num_bins, grower=grower,
    )


def _grown_tree_shapes(n: int, L: int, B: int) -> tuple:
    return (
        jax.ShapeDtypeStruct((L - 1,), jnp.int32),    # rec_leaf
        jax.ShapeDtypeStruct((L - 1,), jnp.int32),    # rec_feature
        jax.ShapeDtypeStruct((L - 1,), jnp.int32),    # rec_bin
        jax.ShapeDtypeStruct((L - 1,), jnp.bool_),    # rec_active
        jax.ShapeDtypeStruct((L - 1,), jnp.float32),  # rec_gain
        jax.ShapeDtypeStruct((L,), jnp.float32),      # leaf_values
        jax.ShapeDtypeStruct((L,), jnp.int32),        # leaf_counts
        jax.ShapeDtypeStruct((n,), jnp.int32),        # row_leaf
        jax.ShapeDtypeStruct((L - 1,), jnp.bool_),    # rec_is_cat
        jax.ShapeDtypeStruct((L - 1, B), jnp.bool_),  # rec_catmask
    )


def _grow_tree_lossguide_hostcall(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    *,
    num_leaves: int,
    max_depth: int,
    num_bins: int,
    min_data_in_leaf: int,
    min_gain: float,
    lambda_l2: float,
    lambda_l1: float,
    min_sum_hessian: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    categorical_mask: jnp.ndarray,
    has_categorical: bool,
) -> GrownTree:
    """The host leaf-wise grower (models/gbdt/hostgrow.py) behind one
    pure_callback; traceable inside jit / the scan-fused round loop."""
    from mmlspark_tpu.models.gbdt.hostgrow import grow_tree_lossguide_host

    n, d = bins.shape
    L, B = num_leaves, num_bins
    kern = functools.partial(
        grow_tree_lossguide_host,
        L, int(max_depth), B, min_data_in_leaf, has_categorical,
    )
    args = (
        jnp.float32(min_gain), jnp.float32(lambda_l2),
        jnp.float32(lambda_l1), jnp.float32(min_sum_hessian),
        jnp.float32(learning_rate),
        bins, grad, hess, row_weight, feature_mask, categorical_mask,
    )
    out_shapes = _grown_tree_shapes(n, L, B)
    from mmlspark_tpu.ops.histogram import _callback

    return GrownTree(*_callback(kern, out_shapes, *args))


def _grow_tree_depthwise_hostcall(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    *,
    num_leaves: int,
    n_levels: int,
    num_bins: int,
    min_data_in_leaf: int,
    min_gain: float,
    lambda_l2: float,
    lambda_l1: float,
    min_sum_hessian: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    categorical_mask: jnp.ndarray,
    has_categorical: bool,
    sibling_subtract: bool,
) -> GrownTree:
    """The host grower (models/gbdt/hostgrow.py) behind one
    pure_callback; traceable inside jit / the scan-fused round loop."""
    from mmlspark_tpu.models.gbdt.hostgrow import grow_tree_depthwise_host

    n, d = bins.shape
    L, B = num_leaves, num_bins
    # static structure in the partial; regularization/lr knobs ride as
    # operands — inside the scan-fused loop they are traced scalars
    kern = functools.partial(
        grow_tree_depthwise_host,
        L, n_levels, B, min_data_in_leaf, sibling_subtract, has_categorical,
    )
    out_shapes = _grown_tree_shapes(n, L, B)
    args = (
        jnp.float32(min_gain), jnp.float32(lambda_l2),
        jnp.float32(lambda_l1), jnp.float32(min_sum_hessian),
        jnp.float32(learning_rate),
        bins, grad, hess, row_weight, feature_mask, categorical_mask,
    )
    from mmlspark_tpu.ops.histogram import _callback

    return GrownTree(*_callback(kern, out_shapes, *args))


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "n_levels", "min_data_in_leaf", "has_categorical",
        "num_bins", "grower",
    ),
)
def _grow_tree_depthwise(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_weight: jnp.ndarray,
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,
    n_levels: int,
    min_data_in_leaf: int,
    categorical_mask: jnp.ndarray,
    has_categorical: bool,
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    grower: Grower = Grower("depthwise", "scatter"),
) -> GrownTree:
    from mmlspark_tpu.ops.histogram import multi_plane_histogram

    mesh, shard_axis = grower.mesh, grower.shard_axis
    sibling_subtract = grower.sibling_subtract
    vector_split = grower.vector_split

    n, d = bins.shape
    L = num_leaves
    B = num_bins
    bins = bins.astype(jnp.int32)
    cat_f = categorical_mask.astype(bool)
    g = grad * row_weight
    h = hess * row_weight
    cnt_w = row_weight
    row_stats = jnp.stack([g, h, cnt_w], axis=-1)
    leaf_best = make_leaf_best(
        d, feature_mask, min_data_in_leaf, min_sum_hessian,
        lambda_l2, lambda_l1, cat_f, has_categorical, num_bins=B,
    )

    row_slot = jnp.zeros((n,), jnp.int32)
    k = jnp.int32(0)                       # splits made so far (record cursor)
    rec_leaf = jnp.full((L - 1,), -1, jnp.int32)
    rec_feature = jnp.full((L - 1,), -1, jnp.int32)
    rec_bin = jnp.full((L - 1,), -1, jnp.int32)
    rec_active = jnp.zeros((L - 1,), bool)
    rec_gain = jnp.zeros((L - 1,), jnp.float32)
    rec_is_cat = jnp.zeros((L - 1,), bool)
    rec_catmask = jnp.zeros((L - 1, B), bool)
    # frontier of the CURRENT level: lut maps record-slot -> local plane
    # index (sentinel = not in frontier); inv maps plane index -> slot
    lut = jnp.where(jnp.arange(L) == 0, 0, L).astype(jnp.int32)
    inv = jnp.full((1,), 0, jnp.int32)     # level 0: just the root
    cube_prev = None                       # previous level's plane cube
    parent_local = None                    # pair p -> parent's plane in it

    for level in range(n_levels):
        S = int(inv.shape[0])
        local = jnp.where(row_slot < L, lut[jnp.clip(row_slot, 0, L - 1)], S)
        if sibling_subtract and level > 0:
            # LightGBM's histogram subtraction, TPU-shaped: the frontier
            # is sibling pairs at locals (2p, 2p+1); histogram only the
            # RIGHT children (matmul width P*6 instead of S*6 — the MXU
            # cost of the multi-plane kernel scales with slot count) and
            # derive left = parent - right from the previous level's cube.
            P = S // 2
            is_right = (local < 2 * P) & (local % 2 == 1)
            slot_pair = jnp.where(is_right, local // 2, P)  # P = no plane
            half = multi_plane_histogram(
                bins, row_stats, slot_pair, P, num_bins=B,
                mesh=mesh, shard_axis=shard_axis, bins_in_range=True,
            )
            ok = (parent_local >= 0)[:, None, None]
            parents = cube_prev[
                jnp.clip(parent_local, 0, cube_prev.shape[0] - 1)
            ]
            left = jnp.where(ok, parents - half, 0.0)
            right = jnp.where(ok, half, 0.0)
            inter = jnp.stack([left, right], axis=1).reshape(
                2 * P, d * B, 3
            )
            cube = (
                inter if S == 2 * P
                else jnp.zeros((S, d * B, 3), jnp.float32).at[: 2 * P].set(inter)
            )
        else:
            cube = multi_plane_histogram(
                bins, row_stats, local, S, num_bins=B,
                mesh=mesh, shard_axis=shard_axis, bins_in_range=True,
            )
        cube_prev = cube
        gains, feats, bbs, catms = jax.vmap(leaf_best)(cube)
        # budget: when fewer than S splits remain, best-gain nodes win
        order = jnp.argsort(-gains)
        S_next = min(2 * S, L)

        if vector_split:
            # ONE vectorized application of the whole level's splits.
            # The sequential fori_loop below is semantically a chain of
            # ~30 tiny dependent XLA ops per split — at 63 splits x 50
            # trees that dependency chain, not the histogram FLOPs,
            # dominated on-chip wall clock. Every split in a level
            # touches a DIFFERENT leaf, so the only cross-split coupling
            # is the budget/record ordering — reproduced exactly by a
            # cumsum over the gain-sorted valid mask (argsort is stable,
            # and the budget cuts a suffix: once k + rank hits L-1 every
            # later valid fails too, so surviving ranks are unchanged).
            slot_s = inv[order]
            gain_s = gains[order]
            ok = (
                (slot_s >= 0) & jnp.isfinite(gain_s) & (gain_s > min_gain)
            )
            rank = jnp.cumsum(ok.astype(jnp.int32)) - ok.astype(jnp.int32)
            ok = ok & (k + rank < L - 1)
            ks = k + rank                    # record index per sorted pos
            new_id = ks + 1
            bf_s, bb_s, cm_s = feats[order], bbs[order], catms[order]
            if has_categorical:
                is_cat_s = cat_f[bf_s]
            else:
                is_cat_s = jnp.zeros_like(ok)
            # record scatters; invalid positions write out-of-range (drop)
            idx = jnp.where(ok, ks, L - 1)   # rec arrays are (L-1,)
            rec_leaf = rec_leaf.at[idx].set(slot_s, mode="drop")
            rec_feature = rec_feature.at[idx].set(bf_s, mode="drop")
            rec_bin = rec_bin.at[idx].set(bb_s, mode="drop")
            rec_active = rec_active.at[idx].set(True, mode="drop")
            rec_gain = rec_gain.at[idx].set(gain_s, mode="drop")
            rec_is_cat = rec_is_cat.at[idx].set(is_cat_s, mode="drop")
            rec_catmask = rec_catmask.at[idx].set(
                jnp.where(is_cat_s[:, None], cm_s, False), mode="drop"
            )
            # next frontier: pair p (= rank) at locals (2p, 2p+1)
            lut = (
                jnp.full((L,), L, jnp.int32)
                .at[jnp.where(ok, slot_s, L)].set(2 * rank, mode="drop")
                .at[jnp.where(ok, new_id, L)].set(2 * rank + 1, mode="drop")
            )
            inv = (
                jnp.full((S_next,), -1, jnp.int32)
                .at[jnp.where(ok, 2 * rank, S_next)].set(slot_s, mode="drop")
                .at[jnp.where(ok, 2 * rank + 1, S_next)].set(
                    new_id, mode="drop"
                )
            )
            pl_n = S_next // 2
            parent_local = (
                jnp.full((pl_n,), -1, jnp.int32)
                .at[jnp.where(ok, rank, pl_n)].set(order, mode="drop")
            )
            # row routing: per ORIGINAL local j, this level's chosen split.
            # The lookup arrays are (S+1,) with slot S as the ALL-FALSE
            # pad: rows whose leaf left the frontier carry local == L,
            # which the clamped gather maps to S — so invalid sorted
            # positions must dump OUT of range (S+1, dropped), never
            # into slot S itself (that pollution rerouted frozen-leaf
            # rows by garbage split params)
            sj = jnp.where(ok, order, S + 1)  # scatter index by local
            split_ok_l = jnp.zeros((S + 1,), bool).at[sj].set(
                True, mode="drop"
            )
            split_bf_l = jnp.zeros((S + 1,), jnp.int32).at[sj].set(
                bf_s, mode="drop"
            )
            split_bb_l = jnp.zeros((S + 1,), jnp.int32).at[sj].set(
                bb_s, mode="drop"
            )
            split_new_l = jnp.zeros((S + 1,), jnp.int32).at[sj].set(
                new_id, mode="drop"
            )
            j_r = local                       # (n,) in [0, S]
            okr = split_ok_l[j_r]
            bf_r = split_bf_l[j_r]
            row_bins = jnp.take_along_axis(bins, bf_r[:, None], axis=1)[:, 0]
            if has_categorical:
                split_iscat_l = jnp.zeros((S + 1,), bool).at[sj].set(
                    is_cat_s, mode="drop"
                )
                split_cm_l = jnp.zeros((S + 1, B), bool).at[sj].set(
                    cm_s, mode="drop"
                )
                goes_right = okr & jnp.where(
                    split_iscat_l[j_r],
                    ~split_cm_l[j_r, row_bins],
                    row_bins > split_bb_l[j_r],
                )
            else:
                goes_right = okr & (row_bins > split_bb_l[j_r])
            row_slot = jnp.where(goes_right, split_new_l[j_r], row_slot)
            k = k + ok.sum(dtype=jnp.int32)
            continue

        lut_next0 = jnp.full((L,), L, jnp.int32)
        inv_next0 = jnp.full((S_next,), -1, jnp.int32)
        parent_local0 = jnp.full((S_next // 2,), -1, jnp.int32)

        def split_one(i: int, carry: tuple) -> tuple:
            (k, n_split, row_slot, lut_next, inv_next, parent_local_n,
             rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
             rec_is_cat, rec_catmask) = carry
            j = order[i]
            slot_j = inv[j]
            gain = gains[j]
            valid = (
                (slot_j >= 0)
                & jnp.isfinite(gain)
                & (gain > min_gain)
                & (k < L - 1)
            )
            bf, bb, cm = feats[j], bbs[j], catms[j]
            new_id = k + 1
            in_leaf = row_slot == slot_j
            row_bins = bins[:, bf]
            if has_categorical:
                goes_right = in_leaf & jnp.where(
                    cat_f[bf], ~cm[row_bins], row_bins > bb
                )
                is_cat_split = cat_f[bf]
            else:
                goes_right = in_leaf & (row_bins > bb)
                is_cat_split = jnp.asarray(False)
            row_slot = jnp.where(valid & goes_right, new_id, row_slot)
            ks = jnp.clip(k, 0, L - 2)
            rec_leaf = rec_leaf.at[ks].set(jnp.where(valid, slot_j, rec_leaf[ks]))
            rec_feature = rec_feature.at[ks].set(jnp.where(valid, bf, rec_feature[ks]))
            rec_bin = rec_bin.at[ks].set(jnp.where(valid, bb, rec_bin[ks]))
            rec_active = rec_active.at[ks].set(rec_active[ks] | valid)
            rec_gain = rec_gain.at[ks].set(jnp.where(valid, gain, rec_gain[ks]))
            rec_is_cat = rec_is_cat.at[ks].set(
                rec_is_cat[ks] | (valid & is_cat_split)
            )
            rec_catmask = rec_catmask.at[ks].set(
                jnp.where(valid & is_cat_split, cm, rec_catmask[ks])
            )
            # children join the next level's frontier
            both_ok = valid
            lut_next = jnp.where(
                both_ok,
                lut_next.at[slot_j].set(2 * n_split).at[new_id].set(2 * n_split + 1),
                lut_next,
            )
            inv_next = jnp.where(
                both_ok,
                inv_next.at[2 * n_split].set(slot_j).at[2 * n_split + 1].set(new_id),
                inv_next,
            )
            # pair p's parent plane lives at local j of THIS level's cube
            ps = jnp.clip(n_split, 0, parent_local_n.shape[0] - 1)
            parent_local_n = parent_local_n.at[ps].set(
                jnp.where(both_ok, j, parent_local_n[ps])
            )
            k = k + valid.astype(jnp.int32)
            n_split = n_split + valid.astype(jnp.int32)
            return (k, n_split, row_slot, lut_next, inv_next, parent_local_n,
                    rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
                    rec_is_cat, rec_catmask)

        (k, _, row_slot, lut, inv, parent_local,
         rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
         rec_is_cat, rec_catmask) = jax.lax.fori_loop(
            0, S,
            split_one,
            (k, jnp.int32(0), row_slot, lut_next0, inv_next0, parent_local0,
             rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
             rec_is_cat, rec_catmask),
        )

    from mmlspark_tpu.ops.histogram import leaf_stat_sums

    sums = leaf_stat_sums(
        row_slot, row_stats, L, mesh=mesh, shard_axis=shard_axis
    )
    Gl, Hl, Cl = sums[:, 0], sums[:, 1], sums[:, 2]
    leaf_values = (
        -threshold_l1(Gl, lambda_l1) / (Hl + lambda_l2) * learning_rate
    )
    leaf_values = jnp.where(Cl > 0, leaf_values, 0.0)
    return GrownTree(
        rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
        leaf_values, Cl.astype(jnp.int32), row_slot,
        rec_is_cat, rec_catmask,
    )


# -- prediction -------------------------------------------------------------


def category_bin_slot(vals: Any, B: int = NUM_BINS, xp: Any = np):
    """Category value -> bin slot, the ONE encoding shared by training
    (identity binning in BinMapper), device prediction (predict_leaves) and
    host SHAP replay (_tree_contribs): NaN -> 0 (missing bin), value v ->
    v+1, clipped into [0, B-1]. ``xp`` selects numpy (host) or jax.numpy
    (traced)."""
    finite = xp.nan_to_num(vals, nan=-1.0)  # NaN -> -1 -> rounds to slot 0
    # clip in float first: huge values must not overflow the int cast
    slot = xp.round(xp.clip(finite, -1.0, float(B))).astype(xp.int32) + 1
    return xp.clip(xp.where(xp.isnan(vals), 0, slot), 0, B - 1)


@jax.jit
def predict_leaves(
    x: jnp.ndarray,            # (n, d) float32 raw features
    rec_leaf: jnp.ndarray,     # (T, S) int32
    rec_feature: jnp.ndarray,  # (T, S) int32
    rec_threshold: jnp.ndarray,  # (T, S) float32 (real-valued; <= goes left)
    rec_active: jnp.ndarray,   # (T, S) bool
    rec_is_cat: Optional[jnp.ndarray] = None,   # (T, S) bool
    rec_catmask: Optional[jnp.ndarray] = None,  # (T, S, B) bool; index = value+1
    rec_default_left: Optional[jnp.ndarray] = None,  # (T, S) bool; NaN direction
) -> jnp.ndarray:
    """Replay split logs for all trees at once -> (n, T) leaf indices.

    Numerical: NaN goes LEFT by default (missing-bin semantics);
    ``rec_default_left`` overrides the direction per split (LightGBM's
    decision_type default-left bit — imported default-right splits route
    NaN right). Categorical splits route by set membership — a category
    value v looks up catmask[v + 1] (identity binning; NaN -> slot 0, the
    missing category). Passing rec_is_cat/rec_default_left as None
    statically compiles that machinery OUT — the common case pays nothing
    for it (mirrors grow_tree's gating)."""
    n = x.shape[0]
    T, S = rec_leaf.shape
    B = NUM_BINS
    row_leaf = jnp.zeros((n, T), jnp.int32)
    has_cat = rec_is_cat is not None
    has_dl = rec_default_left is not None
    if has_cat and rec_catmask is None:
        rec_catmask = jnp.zeros((T, S, B), bool)

    # scan over split steps: right child id of step k is k+1
    def body(row_leaf: jnp.ndarray, inputs: tuple) -> tuple:
        it = iter(inputs)
        k, leaf, feat, thr, active = (next(it) for _ in range(5))
        if has_cat:
            is_cat, catmask = next(it), next(it)
        if has_dl:
            dleft = next(it)
        vals = jnp.take_along_axis(
            x, jnp.broadcast_to(jnp.clip(feat, 0, x.shape[1] - 1)[None, :], (n, T)), axis=1
        )
        in_leaf = row_leaf == leaf[None, :]
        if has_dl:
            right_num = jnp.where(
                jnp.isnan(vals), ~dleft[None, :], vals > thr[None, :]
            )
        else:
            right_num = (vals > thr[None, :]) & ~jnp.isnan(vals)
        if has_cat:
            vbin = category_bin_slot(vals, B, jnp)  # (n, T)
            left_cat = jnp.take_along_axis(
                jnp.broadcast_to(catmask[None], (n, T, B)), vbin[..., None], axis=2
            )[..., 0]
            decide = jnp.where(is_cat[None, :], ~left_cat, right_num)
        else:
            decide = right_num
        goes_right = in_leaf & active[None, :] & decide
        row_leaf = jnp.where(goes_right, jnp.int32(k + 1), row_leaf)
        return row_leaf, None

    ks = jnp.arange(S, dtype=jnp.int32)
    xs = (ks, rec_leaf.T, rec_feature.T, rec_threshold.T, rec_active.T)
    if has_cat:
        xs = xs + (rec_is_cat.T, jnp.moveaxis(rec_catmask, 1, 0))
    if has_dl:
        xs = xs + (rec_default_left.T,)
    row_leaf, _ = jax.lax.scan(body, row_leaf, xs)
    return row_leaf


@jax.jit
def predict_scores(
    x: jnp.ndarray,
    rec_leaf: jnp.ndarray,
    rec_feature: jnp.ndarray,
    rec_threshold: jnp.ndarray,
    rec_active: jnp.ndarray,
    leaf_values: jnp.ndarray,  # (T, L) float32
    rec_is_cat: Optional[jnp.ndarray] = None,
    rec_catmask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Sum of tree outputs -> (n,) raw score."""
    leaves = predict_leaves(
        x, rec_leaf, rec_feature, rec_threshold, rec_active, rec_is_cat, rec_catmask
    )
    per_tree = jnp.take_along_axis(
        jnp.broadcast_to(leaf_values[None], (x.shape[0], *leaf_values.shape)),
        leaves[..., None],
        axis=2,
    )[..., 0]  # (n, T)
    return per_tree.sum(axis=1)
