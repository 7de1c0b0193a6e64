"""Histogram gradient-boosted decision trees with psum histogram sync.

dmlc-core exists to serve xgboost: the reference's RowBlock feeds xgboost's
hist updater, and the tracker's tree+ring topology (reference
tracker/dmlc_tracker/tracker.py:185-252) was built so rabit could allreduce
per-node gradient histograms across workers. This module is that workload
rebuilt TPU-first — the one model family a reference user most expects to
find:

- **quantile binning** on device: features → uint8 bin ids once, up front
  (xgboost's hist trick — split finding then never touches floats)
- **level-wise growth with static shapes**: a depth-D tree is a complete
  binary tree; level ℓ builds one [2^ℓ, F, n_bins, 2] (grad, hess)
  histogram by segment-sum, finds every node's best split with cumsum +
  argmax (pure vectorized XLA, no data-dependent control flow), and
  descends sample node ids with one gather — every array shape is a
  function of (D, F, n_bins) only, so the whole tree build jits once
- **rabit's allreduce, as psum**: under a mesh the samples are sharded over
  ``axis``; each shard segment-sums its local histogram and ONE fused psum
  per level syncs (grad, hess) across ICI — byte-for-byte the collective
  pattern rabit runs for distributed xgboost, with the socket tree replaced
  by XLA's all-reduce. Split finding afterwards is replicated determinism:
  every shard sees identical histograms and picks identical splits, so no
  further communication crosses the mesh until the next level's histogram.
- deterministic accumulation: per-shard sums then one psum — fixed
  reduction order, comparable across backends (SURVEY §7 hard parts).

Inference is the same complete-tree descent: D gathers per tree, no
branches, vmapped over trees.

Scoping note (a deliberate semantic difference from xgboost): absent
entries in sparse input densify to 0.0 and bin like any value — there is
no learned per-node default direction for missing values (xgboost's
sparsity-aware split). Dense numeric data behaves identically; highly
sparse data where absence is informative will split differently. NaNs in
dense input land in the last bin (searchsorted semantics), not a
dedicated missing bin.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.obs.device_telemetry import instrumented_jit
from dmlc_tpu.params.parameter import Parameter, field
from dmlc_tpu.utils.logging import check


class GBDTParam(Parameter):
    """Hyper-parameters (a dmlc Parameter struct, parameter.h style)."""

    objective = field(
        str, "logistic",
        description="Loss: logistic (labels 0/1), squared, or softmax "
                    "(labels are class ids; set num_class).",
    )
    num_class = field(
        int, 0, lower_bound=0,
        description="Class count for objective=softmax (>= 2); 0 for "
                    "scalar objectives.",
    )
    num_trees = field(int, 20, lower_bound=1)
    max_depth = field(int, 6, lower_bound=1, upper_bound=12)
    learning_rate = field(float, 0.3, lower_bound=0.0)
    num_bins = field(
        int, 256, lower_bound=2, upper_bound=65536,
        description="Histogram bins per feature (255 cut points).",
    )
    reg_lambda = field(
        float, 1.0, lower_bound=0.0,
        description="L2 regularization on leaf values (xgboost lambda).",
    )
    min_child_weight = field(
        float, 1.0, lower_bound=0.0,
        description="Minimum hessian sum in a child for a split to count.",
    )
    subsample = field(
        float, 1.0, lower_bound=0.0, upper_bound=1.0,
        description="Per-tree row subsampling rate (stochastic gradient "
                    "boosting; bernoulli mask on (g, h)).",
    )
    colsample_bytree = field(
        float, 1.0, lower_bound=0.0, upper_bound=1.0,
        description="Per-tree feature subsampling rate (ceil(c*F) "
                    "features drawn without replacement).",
    )
    seed = field(
        int, 0,
        description="PRNG seed for subsample/colsample masks "
                    "(deterministic per (seed, tree)).",
    )


# ---------------------------------------------------------------------------
# binning
# ---------------------------------------------------------------------------


def fit_bins(x, num_bins: int = 256) -> np.ndarray:
    """Per-feature quantile cut points → edges [F, num_bins-1] (f32).

    Bin b holds values in (edges[b-1], edges[b]]; ids are produced by
    ``searchsorted(edges, x)`` so they always land in [0, num_bins).
    Mirrors xgboost's sketch → cut conversion at demo fidelity (exact
    quantiles of the supplied sample rather than a streaming sketch).

    On an accelerator backend the [N, F] quantile computes on device
    (the sort is the expensive part; on-chip it's ~free while the host
    quantile was the single biggest stage of a TPU fit) and only the
    tiny [F, num_bins-1] cut matrix comes back for the monotonic fixup.
    On the cpu backend numpy's introselect-based quantile beats an XLA
    full sort, so it stays host-side. ``x`` may already be a device
    array — the accelerator path then skips the H2D.
    """
    if not isinstance(x, jax.Array):
        x = np.asarray(x, dtype=np.float32)
    check(x.ndim == 2, "fit_bins expects [N, F]")
    qs = np.linspace(0.0, 1.0, num_bins + 1)[1:-1]
    if jax.default_backend() != "cpu":
        q = jnp.quantile(jnp.asarray(x, dtype=jnp.float32),
                         jnp.asarray(qs, dtype=jnp.float32), axis=0)
        edges = np.asarray(q).T.astype(np.float32)  # tiny D2H
    else:
        edges = np.quantile(
            np.asarray(x, dtype=np.float32), qs, axis=0
        ).T.astype(np.float32)  # [F, B-1]
    # strictly increasing edges keep searchsorted stable when a feature has
    # few distinct values (ties collapse quantiles to equal cut points).
    # The sequential recurrence e[b] = max(e[b], e[b-1] + d[b-1]) with
    # d = 4·eps·max(|e|, 1) is solved in closed form: with c = exclusive
    # cumsum of d, substituting f[b] = e[b] − c[b] turns it into
    # f[b] = max(f[b], f[b-1]), i.e. a running maximum — one vector pass
    # instead of a per-bin host loop (which dominated fit_bins for wide
    # feature spaces). float64 keeps the tiny increments from rounding
    # away inside the accumulate; strictness survives the f32 cast
    # because each increment (4·eps·scale) exceeds f32 ulp spacing.
    eps = np.finfo(np.float32).eps
    e = edges.astype(np.float64)
    d = 4.0 * eps * np.maximum(np.abs(e), 1.0)
    c = np.cumsum(d, axis=1) - d  # exclusive prefix sum
    return (c + np.maximum.accumulate(e - c, axis=1)).astype(np.float32)


def apply_bins(x, edges):
    """x [N, F] float → bin ids [N, F] int32 via per-feature searchsorted."""
    x = jnp.asarray(x, dtype=jnp.float32)
    edges = jnp.asarray(edges, dtype=jnp.float32)
    binned = jax.vmap(
        lambda col, cuts: jnp.searchsorted(cuts, col, side="left"),
        in_axes=(1, 0), out_axes=1,
    )(x, edges)
    return binned.astype(jnp.int32)


def _apply_bins_np(x: np.ndarray, edges: np.ndarray,
                   num_bins: int) -> np.ndarray:
    """Host-side twin of :func:`apply_bins` in the smallest dtype that
    holds the ids — for streaming/multi-process paths where the binned
    matrix is assembled on the host anyway (a device round trip would
    D2H the matrix right back)."""
    dt = (np.uint8 if num_bins <= 256
          else np.uint16 if num_bins <= 65536 else np.int32)
    out = np.empty(x.shape, dtype=dt)
    for f in range(x.shape[1]):
        out[:, f] = np.searchsorted(edges[f], x[:, f], side="left")
    return out


# ---------------------------------------------------------------------------
# gradients
# ---------------------------------------------------------------------------


def _grad_hess(objective: str, margin, label):
    """Per-row (g, h) for the second-order boosting objective.

    softmax: margin is [N, K], label holds class ids; (g, h) are [N, K]
    with the diagonal-hessian approximation p(1−p) — xgboost's
    multi:softprob formulation (K channels share one tree structure)."""
    if objective == "logistic":
        p = jax.nn.sigmoid(margin)
        return p - label, jnp.maximum(p * (1.0 - p), 1e-16)
    if objective == "squared":
        return margin - label, jnp.ones_like(margin)
    if objective == "softmax":
        p = jax.nn.softmax(margin, axis=-1)
        onehot = jax.nn.one_hot(
            label.astype(jnp.int32), margin.shape[-1], dtype=margin.dtype
        )
        return p - onehot, jnp.maximum(p * (1.0 - p), 1e-16)
    raise ValueError(f"unknown objective {objective!r}")


def _loss(objective: str, margin, label):
    if objective == "logistic":
        return jnp.maximum(margin, 0.0) - margin * label + jnp.log1p(
            jnp.exp(-jnp.abs(margin))
        )
    if objective == "softmax":
        logp = jax.nn.log_softmax(margin, axis=-1)
        return -jnp.take_along_axis(
            logp, label.astype(jnp.int32)[:, None], axis=1
        )[:, 0]
    return 0.5 * (margin - label) ** 2


def _grad_loss_core(objective: str, margin, y, w, psum_axis):
    """(g, h, weighted mean loss) for one boosting round — the ONE
    definition both the fused-scan and per-tree-loop paths trace (like
    _build_tree_core: a change here cannot diverge the two paths'
    models). Instance weights scale (g, h) — xgboost's semantics: a
    weight-2 row contributes exactly like two copies of itself to every
    histogram, split gain, and leaf value."""
    g, h = _grad_hess(objective, margin, y)
    if w is not None:
        wexp = w if g.ndim == 1 else w[:, None]
        g = g * wexp
        h = h * wexp
        lsum = jnp.sum(w * _loss(objective, margin, y))
        wsum = jnp.sum(w)
        if psum_axis is not None:
            lsum, wsum = jax.lax.psum((lsum, wsum), psum_axis)
        return g, h, lsum / jnp.maximum(wsum, 1e-12)
    loss = jnp.mean(_loss(objective, margin, y))
    if psum_axis is not None:
        loss = jax.lax.pmean(loss, psum_axis)
    return g, h, loss


def _margin_update_core(margin, leaf, node, learning_rate):
    # leaf [2^D] (scalar objectives) or [2^D, K] (softmax): axis-0 take
    # serves both, yielding [N] or [N, K] updates
    return margin + learning_rate * jnp.take(leaf, node, axis=0)


# ---------------------------------------------------------------------------
# one tree, level by level (all static shapes)
# ---------------------------------------------------------------------------


def _level_histogram(xb, node, g, h, n_nodes, num_bins):
    """(grad, hess) histogram [n_nodes, F, num_bins, C] by segment-sum.

    One flat key (node, feature, bin) per (sample, feature) cell; a
    single scatter pass fills all 2C channels (C = 1 for scalar
    objectives, K for softmax — the channels share one key, so
    multiclass costs one wider scatter, not K scatters). Every sample
    stays live through the build (leaf-in-place nodes route left), so no
    masking pass is needed.
    """
    nf = xb.shape[1]
    n_seg = n_nodes * nf * num_bins
    # the key space can exceed int32 at permitted hyperparameters (e.g.
    # num_bins=65536, F=1024, depth≥6), where the flat key would wrap
    # negative and segment_sum silently misroutes updates. An int64
    # fallback is NOT a fix: jax defaults to x64-disabled, so the cast
    # would quietly truncate back to int32. Refuse loudly instead.
    check(
        n_seg < (1 << 31),
        "histogram key space nodes*features*bins = %d*%d*%d = %d overflows "
        "int32; reduce max_depth, num_bins, or the feature count "
        "(or shard features) so the product stays below 2**31",
        n_nodes, nf, num_bins, n_seg,
    )
    key_dtype = jnp.int32
    feat = jnp.arange(nf, dtype=key_dtype)[None, :]
    flat = (
        (node[:, None].astype(key_dtype) * nf + feat) * num_bins
        + xb.astype(key_dtype)
    ).reshape(-1)
    g2 = g[:, None] if g.ndim == 1 else g
    h2 = h[:, None] if h.ndim == 1 else h
    c = g2.shape[1]
    gh = jnp.concatenate([g2, h2], axis=1)  # [N, 2C]
    vals = jnp.broadcast_to(
        gh[:, None, :], (gh.shape[0], nf, 2 * c)
    ).reshape(-1, 2 * c)
    hist = jax.ops.segment_sum(vals, flat, num_segments=n_seg)
    hist = hist.reshape(n_nodes, nf, num_bins, 2 * c)
    return hist[..., :c], hist[..., c:]


def _find_splits(ghist, hhist, reg_lambda, min_child_weight,
                 feat_mask=None):
    """Vectorized best split per node.

    ghist/hhist [n_nodes, F, B, C] → (feature [n_nodes], bin [n_nodes],
    gain [n_nodes], gtot [n_nodes, C], htot [n_nodes, C]). A split at
    bin t sends bins ≤ t left. gain = ½ Σ_c (GL²/(HL+λ) + GR²/(HR+λ) −
    G²/(H+λ)), the xgboost structure score summed over channels (all
    classes share one structure); children whose total hessian is under
    min_child_weight are masked out. feature = -1 flags "no
    positive-gain split" (leaf).
    """
    gl = jnp.cumsum(ghist, axis=2)
    hl = jnp.cumsum(hhist, axis=2)
    gtot = gl[:, 0, -1]  # [n, C] (identical for every feature)
    htot = hl[:, 0, -1]
    gr = gtot[:, None, None, :] - gl
    hr = htot[:, None, None, :] - hl
    lam = reg_lambda

    def score(gsum, hsum):
        # an empty child at reg_lambda=0 is 0/0: select 0 instead of
        # letting a NaN survive the mask and poison every argmax
        denom = hsum + lam
        return jnp.where(
            denom > 0.0, gsum * gsum / denom, 0.0
        ).sum(axis=-1)

    gain = 0.5 * (
        score(gl, hl) + score(gr, hr)
        - score(gtot, htot)[:, None, None]
    )
    # cover = total hessian mass across channels (xgboost's multiclass
    # min_child_weight semantics)
    hl_tot = hl.sum(axis=-1)
    hr_tot = hr.sum(axis=-1)
    ok = (hl_tot >= min_child_weight) & (hr_tot >= min_child_weight)
    # the last bin's "split" sends everything left — never a real split
    ok = ok.at[:, :, -1].set(False)
    if feat_mask is not None:  # colsample: undrawn features can't split
        ok = ok & feat_mask[None, :, None]
    gain = jnp.where(ok, gain, -jnp.inf)
    flat = gain.reshape(gain.shape[0], -1)
    best = jnp.argmax(flat, axis=1)
    nbins = ghist.shape[2]
    feature = (best // nbins).astype(jnp.int32)
    split_bin = (best % nbins).astype(jnp.int32)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    feature = jnp.where(best_gain > 0.0, feature, -1)
    return feature, split_bin, best_gain, gtot, htot


def _stochastic_masks(base_key, tree_idx, n_rows, n_features, subsample,
                      colsample, psum_axis):
    """(row_mask [N] f32 | None, feat_mask [F] bool | None) for one tree.

    Deterministic per (seed, tree): ``fold_in(key, t)`` — so the fused
    scan and the live-logging loop produce IDENTICAL masks (proven by
    test). The feature draw uses the pre-axis key (every shard must mask
    the same ceil(c·F) features or their histograms disagree); the row
    draw folds in the shard index so different shards drop different
    rows — the distributed-bagging shape. Mesh builds therefore match
    single-device builds only at subsample=1 (stochastic distributed
    boosting differs by construction, as in xgboost).
    """
    k = jax.random.fold_in(base_key, tree_idx)
    feat_mask = None
    if colsample < 1.0:
        keep = max(1, int(np.ceil(colsample * n_features)))
        order = jax.random.permutation(jax.random.fold_in(k, 1),
                                       n_features)
        feat_mask = jnp.zeros((n_features,), dtype=bool).at[
            order[:keep]].set(True)
    row_mask = None
    if subsample < 1.0:
        rk = jax.random.fold_in(k, 2)
        if psum_axis is not None:
            rk = jax.random.fold_in(rk, jax.lax.axis_index(psum_axis))
        row_mask = (jax.random.uniform(rk, (n_rows,))
                    < subsample).astype(jnp.float32)
    return row_mask, feat_mask


def _apply_stochastic_masks(base_key, t, n_features, g, h, subsample,
                            colsample, psum_axis):
    """(masked g, masked h, feat_mask) for tree ``t`` — the ONE
    application of :func:`_stochastic_masks` both the scan body and the
    live-logging loop trace (bit-identical masks are what the
    scan==loop forest-equivalence test enforces)."""
    row_mask, feat_mask = _stochastic_masks(
        base_key, t, g.shape[0], n_features, subsample, colsample,
        psum_axis,
    )
    if row_mask is not None:
        rexp = row_mask if g.ndim == 1 else row_mask[:, None]
        g = g * rexp
        h = h * rexp
    return g, h, feat_mask


def _build_tree_core(xb, g, h, max_depth, num_bins, reg_lambda,
                     min_child_weight, psum_axis=None, feat_mask=None):
    """One tree, level by level, all static shapes; traceable inside jit,
    shard_map, AND lax.scan (no Python-level data dependence).

    Tree encoding (complete binary tree, n_internal = 2^D − 1 internal
    nodes then 2^D leaves): ``feature``/``bin`` [n_internal] (−1 = the
    node is a leaf-in-place: descent keeps every sample left so the
    subtree collapses to its leftmost leaf), ``leaf`` [2^D] f32 leaf
    values (−G/(H+λ), already learning-rate-free).

    With ``psum_axis``: xb/g/h are per-shard local; each level does local
    segment-sums and ONE psum of the stacked (g, h) histogram — the rabit
    allreduce. Everything after the psum is shard-invariant.
    """
    n_leaves = 1 << max_depth
    n = xb.shape[0]
    node = jnp.zeros((n,), dtype=jnp.int32)  # id within current level
    feats, bins, gains = [], [], []
    for depth in range(max_depth):
        n_nodes = 1 << depth
        ghist, hhist = _level_histogram(xb, node, g, h, n_nodes, num_bins)
        if psum_axis is not None:
            ghist, hhist = jax.lax.psum((ghist, hhist),
                                        axis_name=psum_axis)
        feature, split_bin, gain, _gt, _ht = _find_splits(
            ghist, hhist, reg_lambda, min_child_weight,
            feat_mask=feat_mask,
        )
        feats.append(feature)
        bins.append(split_bin)
        # realized gain per node (0 at leaf-in-place nodes): the raw
        # material of gain-based feature importance
        gains.append(jnp.where(feature >= 0, gain, 0.0))
        # descend: right iff this sample's bin at the split feature
        # exceeds the threshold; leaf-in-place nodes send all left
        nfeat = jnp.take(feature, node)  # [N]
        nbin = jnp.take(split_bin, node)
        fval = jnp.take_along_axis(
            xb, jnp.maximum(nfeat, 0)[:, None], axis=1
        )[:, 0]
        go_right = (nfeat >= 0) & (fval > nbin)
        node = node * 2 + go_right.astype(jnp.int32)
    # leaf values from the last level's (G, H) per leaf — [2^D] for
    # scalar objectives, [2^D, K] vector leaves for softmax
    gleaf = jax.ops.segment_sum(g, node, num_segments=n_leaves)
    hleaf = jax.ops.segment_sum(h, node, num_segments=n_leaves)
    if psum_axis is not None:
        gleaf, hleaf = jax.lax.psum((gleaf, hleaf), axis_name=psum_axis)
    # empty leaves at reg_lambda=0 are 0/0: emit 0 — unseen data can
    # route there at predict time and must not read NaN
    denom = hleaf + reg_lambda
    leaf = jnp.where(denom > 0.0, -gleaf / denom, 0.0)
    return (
        jnp.concatenate(feats),
        jnp.concatenate(bins),
        jnp.concatenate(gains),
        leaf,
        node,
    )


def make_tree_builder(
    max_depth: int,
    num_bins: int,
    reg_lambda: float,
    min_child_weight: float,
    mesh: Optional[Mesh] = None,
    axis: str = "dp",
    with_feat_mask: bool = False,
):
    """Jitted (xb, g, h[, feat_mask]) → tree arrays; the level loop is
    unrolled (depth is a compile-time constant, ≤ 12), so one jit covers
    the whole build. See :func:`_build_tree_core` for the encoding and
    mesh semantics; ``with_feat_mask`` adds the colsample feature mask
    as a trailing (replicated) argument."""

    def _build(xb, g, h, *maybe_mask):
        return _build_tree_core(
            xb, g, h, max_depth, num_bins, reg_lambda, min_child_weight,
            psum_axis=axis if mesh is not None else None,
            feat_mask=maybe_mask[0] if with_feat_mask else None,
        )

    if mesh is None:
        return instrumented_jit(_build, "gbdt.build_tree")
    data_specs = (P(axis), P(axis), P(axis)) + (
        (P(),) if with_feat_mask else ())
    sharded = shard_map(
        _build,
        mesh=mesh,
        in_specs=data_specs,
        out_specs=(P(), P(), P(), P(), P(axis)),
    )
    return instrumented_jit(sharded, "gbdt.build_tree")


def make_forest_builder(
    num_trees: int,
    max_depth: int,
    num_bins: int,
    reg_lambda: float,
    min_child_weight: float,
    learning_rate: float,
    objective: str,
    mesh: Optional[Mesh] = None,
    axis: str = "dp",
    weighted: bool = False,
    num_class: int = 0,
    with_eval: bool = False,
    subsample: float = 1.0,
    colsample: float = 1.0,
    seed: int = 0,
):
    """The whole boosting loop as ONE jitted ``lax.scan`` over trees.

    Per-tree Python loops pay (grad + build + margin-update) dispatches
    per tree — dozens of host→device round trips per fit, the dominant
    cost in dispatch-latency-bound settings. Trees have identical static
    shapes, which is exactly the shape contract ``lax.scan`` wants: the
    carry is the margin, each step emits (feature, bin, leaf, loss), and
    the stacked ys ARE the ``{feature: [T, ...], ...}`` layout
    ``predict_trees`` consumes. One dispatch per fit; XLA sees the whole
    forest and schedules/fuses across the per-tree stages.

    Returns jitted ``(xb, y[, w][, xe, ye]) → (trees_dict, history [T]
    [, eval_history [T]])`` — the instance-weight array only when
    ``weighted``; the binned eval set (+ per-tree post-update eval
    losses in the output, the xgboost watchlist) only when
    ``with_eval`` (mesh builds don't take an eval set — evaluate the
    replicated model after fit instead).
    """
    psum_axis = axis if mesh is not None else None
    offsets = jnp.asarray(_tree_level_offsets(max_depth), dtype=jnp.int32)

    def _forest(xb, y, *rest):
        i = 0
        w = rest[i] if weighted else None
        i += 1 if weighted else 0
        xe, ye = (rest[i], rest[i + 1]) if with_eval else (None, None)

        def _zero_margin(ref):
            m = jnp.zeros_like(ref)
            if objective == "softmax":
                m = m[:, None] * jnp.ones((num_class,), dtype=jnp.float32)
            return m

        stochastic = subsample < 1.0 or colsample < 1.0
        base_key = jax.random.PRNGKey(seed)

        def body(carry, t):
            margin, vmargin = carry
            g, h, loss = _grad_loss_core(objective, margin, y, w,
                                         psum_axis)
            feat_mask = None
            if stochastic:
                g, h, feat_mask = _apply_stochastic_masks(
                    base_key, t, xb.shape[1], g, h, subsample,
                    colsample, psum_axis,
                )
            feature, split_bin, gain, leaf, node = _build_tree_core(
                xb, g, h, max_depth, num_bins, reg_lambda,
                min_child_weight, psum_axis, feat_mask=feat_mask,
            )
            margin = _margin_update_core(margin, leaf, node, learning_rate)
            if with_eval:
                vnode = _descend_tree(xe, feature, split_bin, max_depth,
                                      offsets)
                vmargin = _margin_update_core(vmargin, leaf, vnode,
                                              learning_rate)
                # post-update loss: "how good is the forest so far on
                # held-out data" — the watchlist quantity
                vloss = jnp.mean(_loss(objective, vmargin, ye))
            else:
                vloss = loss  # unused; keeps the scan ys uniform
            return (margin, vmargin), (
                feature, split_bin, gain, leaf, loss, vloss)

        # derive the initial margin FROM y (not fresh zeros): inside
        # shard_map the scan carry must match the body output's varying
        # manual axes, and only values computed from the sharded operand
        # carry that type
        vmargin0 = _zero_margin(ye) if with_eval else jnp.zeros(())
        _, (feats, bins, gains, leaves, losses, vlosses) = jax.lax.scan(
            body, (_zero_margin(y), vmargin0),
            jnp.arange(num_trees, dtype=jnp.int32)
        )
        trees = {"feature": feats, "bin": bins, "gain": gains,
                 "leaf": leaves}
        if with_eval:
            return trees, losses, vlosses
        return trees, losses

    if mesh is None:
        return instrumented_jit(_forest, "gbdt.forest")
    check(not with_eval,
          "mesh forest builds don't take an eval set — evaluate the "
          "replicated model after fit")
    data_specs = (P(axis), P(axis)) + ((P(axis),) if weighted else ())
    sharded = shard_map(
        _forest,
        mesh=mesh,
        in_specs=data_specs,
        out_specs=(P(), P()),
    )
    return instrumented_jit(sharded, "gbdt.forest")


def _tree_level_offsets(max_depth: int) -> np.ndarray:
    """Start offset of each level's nodes in the flat feature/bin arrays."""
    return np.cumsum([0] + [1 << d for d in range(max_depth)])[:-1]


def _descend_tree(xb, feature, split_bin, max_depth, offsets):
    """Leaf index [N] for binned rows under one tree's flat arrays —
    the D-gather descent shared by prediction and eval-set tracking."""
    node = jnp.zeros((xb.shape[0],), dtype=jnp.int32)
    for depth in range(max_depth):
        idx = offsets[depth] + node
        nfeat = jnp.take(feature, idx)
        nbin = jnp.take(split_bin, idx)
        fval = jnp.take_along_axis(
            xb, jnp.maximum(nfeat, 0)[:, None], axis=1
        )[:, 0]
        go_right = (nfeat >= 0) & (fval > nbin)
        node = node * 2 + go_right.astype(jnp.int32)
    return node


def predict_trees(trees: Dict, xb, max_depth: int):
    """Sum of leaf values over all trees for binned rows xb [N, F].

    trees: {"feature": [T, n_internal], "bin": [T, n_internal],
    "leaf": [T, 2^D] or [T, 2^D, K] (softmax vector leaves)} stacked
    over trees; the descent is D gathers per tree, vmapped over T — no
    data-dependent control flow. Returns [N] or [N, K].
    """
    offsets = jnp.asarray(_tree_level_offsets(max_depth), dtype=jnp.int32)

    def one_tree(feature, split_bin, leaf):
        node = _descend_tree(xb, feature, split_bin, max_depth, offsets)
        return jnp.take(leaf, node, axis=0)

    per_tree = jax.vmap(one_tree)(
        trees["feature"], trees["bin"], trees["leaf"]
    )  # [T, N] or [T, N, K]
    return jnp.sum(per_tree, axis=0)


class GBDTLearner:
    """In-core histogram boosting: fit(x, y) → trees (xgboost hist mode).

    With a ``mesh``, samples are sharded over ``axis`` for the histogram
    build (the distributed-xgboost layout: each worker holds a row shard,
    histograms allreduce) and the model is replicated. The margin cache is
    updated incrementally per tree — predictions never rescan the forest
    during training.
    """

    def __init__(self, mesh: Optional[Mesh] = None, axis: str = "dp",
                 **hyper):
        self.param = GBDTParam()
        self.param.init(hyper)
        self.mesh = mesh
        self.axis = axis
        self.edges: Optional[np.ndarray] = None
        self.trees: Optional[Dict] = None
        self._builder = None
        self._forest = None  # fused lax.scan boosting loop (default path)
        self._engine = None  # multi-process row-count sync, lazy
        self._eval_step = None  # cached watchlist step (loop path)
        self.eval_history: Optional[list] = None  # per-tree eval_set loss
        self.best_iteration: Optional[int] = None  # its argmin (0-based)

    # ---- fit -----------------------------------------------------------
    def _local_shards(self) -> int:
        """Shard sections THIS process's rows divide over along the axis
        (one shared implementation: ``parallel.local_axis_shards``)."""
        from dmlc_tpu.parallel import local_axis_shards

        return local_axis_shards(self.mesh, self.axis)

    def _check_divisible(self, n: int) -> None:
        if self.mesh is None:
            return
        shards = self._local_shards()
        check(n % shards == 0,
              "N %d (this process's rows) must divide its %d mesh shards "
              "(pad or trim the training set)", n, shards)

    def _get_engine(self):
        """Cached DeviceEngine for tiny cross-process agreement
        collectives (row counts, weighted-ness) — cached so its jitted
        reduction survives across fits."""
        if self._engine is None:
            from dmlc_tpu.collective.device import DeviceEngine

            self._engine = DeviceEngine(self.mesh)
        return self._engine

    def _check_edges(self, num_features: int) -> None:
        """User-supplied edges must match (F, num_bins-1): oversize bin
        ids would walk off the end of the segment key space and
        segment_sum SILENTLY drops out-of-range updates — wrong splits
        with no error (the failure mode this check converts into one)."""
        want = (num_features, self.param.num_bins - 1)
        check(self.edges.shape == want,
              "edges shape %s does not match (num_features, num_bins-1) "
              "= %s", self.edges.shape, want)

    def _sync_row_count(self, n_local: int, trim: bool) -> int:
        """Multi-process row-count agreement: ``make_array_from_process_
        local_data`` infers the global shape ASSUMING every process
        contributes equally — ragged counts produce divergent global
        shapes across processes and the level-psum hangs or crashes
        instead of erroring. One tiny allreduce makes ragged input either
        a clean trim (``trim=True``: everyone cuts to the global-min
        multiple of their shards) or a clean error."""
        if self.mesh is None or jax.process_count() <= 1:
            return n_local
        shards = self._local_shards()
        usable = (n_local // shards) * shards if trim else n_local
        # one allreduce carries both bounds: min(x) and min(-x) = -max(x)
        lo, neg_hi = (int(v) for v in self._get_engine().allreduce(
            np.array([usable, -usable]), op="min"))
        if trim:
            return lo
        check(lo == -neg_hi,
              "processes hold unequal row counts (%d..%d); global "
              "assembly requires equal local N — trim (fit_uri: "
              "drop_remainder=True) or pad", lo, -neg_hi)
        return n_local

    def fit(self, x: np.ndarray, y: np.ndarray, log_every: int = 0,
            edges: Optional[np.ndarray] = None,
            weight: Optional[np.ndarray] = None,
            eval_set: Optional[tuple] = None):
        """Train on an in-memory dense [N, F] float matrix. Returns the
        per-tree weighted mean loss history (evaluated pre-update, so
        entry 0 is the base-margin loss).

        ``weight`` [N] scales each row's (g, h) — xgboost's instance
        weights: a weight-2 row trains exactly like two copies of it
        (histograms, split gains, leaf values; proven by test).

        ``eval_set=(x_val, y_val)`` tracks the held-out loss after every
        tree (the xgboost watchlist) INSIDE the fused scan — no extra
        dispatches; afterwards ``self.eval_history`` holds the per-tree
        losses and ``self.best_iteration`` the argmin, which
        :meth:`truncate` can cut the forest back to. Single-process only
        (evaluate a replicated mesh model after fit instead).

        Multi-process meshes: ``x``/``y`` are this process's LOCAL rows,
        and every process must pass IDENTICAL ``edges`` (bin boundaries
        are the one piece of global state the histogram psum assumes —
        the reference stack's analog is rabit allreducing xgboost's
        quantile sketches; compute them from a shared sample, or on rank
        0 and broadcast via the collective engine).

        With a mesh AND subsample/colsample_bytree < 1, ``log_every>0``
        trains a DIFFERENT (equally valid) forest than the default fused
        scan: the scan's shard_map folds the shard index into the mask
        PRNG, which the live-logging path's plain jit cannot reproduce.
        A warning is emitted; use ``log_every=0`` when you need the
        scan-identical model.
        """
        p = self.param
        x = np.asarray(x, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        check(x.ndim == 2 and y.shape == (x.shape[0],),
              "fit expects x [N, F], y [N]")
        if weight is not None:
            weight = np.asarray(weight, dtype=np.float32)
            check(weight.shape == y.shape, "weight must be [N]")
        if eval_set is not None:
            check(self.mesh is None,
                  "eval_set requires mesh=None (evaluate the replicated "
                  "model after a mesh fit)")
            xe = np.asarray(eval_set[0], dtype=np.float32)
            ye = np.asarray(eval_set[1], dtype=np.float32)
            check(xe.ndim == 2 and xe.shape[1] == x.shape[1]
                  and ye.shape == (xe.shape[0],),
                  "eval_set must be (x_val [Ne, F], y_val [Ne])")
        multiprocess = self.mesh is not None and jax.process_count() > 1
        if multiprocess:
            check(edges is not None,
                  "multi-process fit requires shared edges= (per-host "
                  "quantiles would bin the same value differently)")
            self._sync_row_count(x.shape[0], trim=False)
        self._check_divisible(x.shape[0])
        if not multiprocess and jax.default_backend() != "cpu":
            # ONE H2D of the float matrix feeds both the device quantile
            # (fit_bins accelerator path) and the device searchsorted
            x = jnp.asarray(x)
        if edges is not None:
            self.edges = np.asarray(edges, dtype=np.float32)
            self._check_edges(x.shape[1])
        else:
            self.edges = fit_bins(x, p.num_bins)
        if multiprocess:
            # bin on host: the global assembly consumes host arrays, so
            # device apply_bins would D2H the matrix straight back
            return self._fit_binned(
                _apply_bins_np(x, self.edges, p.num_bins), y, log_every,
                weight)
        # apply_bins already lives on device; _fit_binned's jnp.asarray
        # is a no-op there (a np.asarray round trip would D2H+H2D the
        # whole matrix for nothing)
        eval_xb = eval_y = None
        if eval_set is not None:
            eval_xb = apply_bins(xe, self.edges)
            eval_y = ye
        return self._fit_binned(apply_bins(x, self.edges), y, log_every,
                                weight, eval_xb, eval_y)

    def fit_uri(
        self,
        uri: str,
        num_features: int,
        part_index: int = 0,
        num_parts: int = 1,
        sample_rows: int = 1 << 16,
        log_every: int = 0,
        drop_remainder: bool = False,
        edges: Optional[np.ndarray] = None,
        nthread: Optional[int] = None,
    ):
        """Train from any parser uri (LibSVM text, RecordIO row groups,
        ``#cachefile``, object store) without materializing the dense
        float matrix — the external-memory answer for hist mode:

        pass 1 streams blocks through a vectorized reservoir sample
        (Algorithm R) to fit the bin edges (``sample_rows`` caps the
        sketch; ≥ N keeps every row and reproduces ``fit`` exactly);
        pass 2 re-streams (``before_first``) and bins each block on the
        host into the compact binned matrix (uint8/uint16 when num_bins
        allows — ~4-8x smaller than the float matrix it replaces).

        Multi-host: pass the per-host InputSplit part via
        part_index/num_parts (the reference's part-k/n sharding contract).
        Binary row-group shards ride the same call via the reference's
        own format idiom (src/data.cc:70-76): ``uri + "?format=recordio"``.
        Under a mesh, ``drop_remainder=True`` trims the tail rows that
        don't divide the axis extent (a uri's row count is unknown up
        front); the default raises instead of silently dropping data.
        Multi-process: each process parses its own part AND must receive
        identical ``edges=`` (see ``fit``) — passing them also skips the
        sketch pass entirely. ``nthread`` fans chunk parsing across
        worker threads (None → the ``DMLC_TPU_NTHREAD`` env knob).
        """
        from dmlc_tpu.data import create_parser

        p = self.param
        check(num_features > 0, "fit_uri requires num_features")
        if self.mesh is not None and jax.process_count() > 1:
            check(edges is not None,
                  "multi-process fit_uri requires shared edges= (per-host "
                  "sketches would bin the same value differently)")
        parser = create_parser(uri, part_index, num_parts, nthread=nthread)
        try:
            if edges is not None:
                self.edges = np.asarray(edges, dtype=np.float32)
                self._check_edges(num_features)
            else:
                # pass 1: reservoir sample for edges
                rng = np.random.RandomState(p.num_bins * 7919 + 13)
                reservoir = np.empty((sample_rows, num_features),
                                     dtype=np.float32)
                seen = 0
                for block in parser:
                    dense = block.to_dense(num_features)
                    n = len(dense)
                    gidx = np.arange(seen, seen + n)
                    take_direct = gidx < sample_rows
                    reservoir[gidx[take_direct]] = dense[take_direct]
                    rest = ~take_direct
                    if rest.any():
                        draws = (rng.random_sample(int(rest.sum()))
                                 * (gidx[rest] + 1)).astype(np.int64)
                        hit = draws < sample_rows
                        reservoir[draws[hit]] = dense[rest][hit]
                    seen += n
                check(seen > 0, "uri produced no rows: %s", uri)
                self.edges = fit_bins(reservoir[:min(seen, sample_rows)],
                                      p.num_bins)
            # pass 2: stream + bin on the host (no device chatter per
            # block)
            from dmlc_tpu import obs

            parser.before_first()
            xb_parts, y_parts, w_parts = [], [], []
            any_weight = False
            for block in parser:
                # gbdt consumes chunks here (no DeviceFeed): the binning
                # slice terminates each pipelined chunk's arrow chain
                fid = getattr(block, "flow_id", 0)
                with obs.span("bin_block", rows=len(block), flow=fid):
                    obs.flow_end(fid, "chunk")
                    dense = block.to_dense(num_features)
                    xb_parts.append(
                        _apply_bins_np(dense, self.edges, p.num_bins))
                y_parts.append(np.asarray(block.label, dtype=np.float32))
                # instance weights ride the format when present (libsvm
                # label:weight — data.h Row semantics); all-absent stays
                # the unweighted fast path
                if block.weight is not None:
                    any_weight = True
                    w_parts.append(
                        np.asarray(block.weight, dtype=np.float32))
                else:
                    w_parts.append(
                        np.ones(len(block), dtype=np.float32))
        finally:
            parser.close()
        # both branches must fail cleanly on a rowless uri/part (a
        # byte-split part of a small file can legitimately be empty; on a
        # mesh, dying in np.concatenate would strand the other processes
        # in the row-count collective)
        check(xb_parts, "uri produced no rows: %s (part %d/%d)",
              uri, part_index, num_parts)
        # keep the compact dtype — _level_histogram widens bin ids into
        # the (int32/int64) segment key itself, so upcasting here would
        # re-materialize the float-matrix-sized array fit_uri exists to
        # avoid
        xb = np.concatenate(xb_parts)
        y = np.concatenate(y_parts)
        if self.mesh is not None and jax.process_count() > 1:
            # weighted-ness must agree across the world: a process whose
            # part happens to carry no label:weight rows would otherwise
            # build the 2-input SPMD program while its peers build the
            # 3-input one — mismatched executables against the same
            # collectives. Any process's weights make the fit weighted
            # (the ones-fill above already covers the absent rows).
            any_weight = bool(self._get_engine().allreduce(
                np.array([int(any_weight)]), op="max")[0])
        weight = np.concatenate(w_parts) if any_weight else None
        if drop_remainder and self.mesh is not None:
            shards = self._local_shards()
            # equalize ACROSS processes too: global assembly assumes every
            # process contributes the same local N (ragged InputSplit
            # parts are the norm, not the exception)
            n = self._sync_row_count((xb.shape[0] // shards) * shards,
                                     trim=True)
            xb, y = xb[:n], y[:n]
            if weight is not None:
                weight = weight[:n]
        else:
            self._sync_row_count(xb.shape[0], trim=False)
        self._check_divisible(xb.shape[0])
        return self._fit_binned(xb, y, log_every, weight)

    def _fit_binned(self, xb: np.ndarray, y: np.ndarray, log_every: int,
                    weight: Optional[np.ndarray] = None,
                    eval_xb=None, eval_y=None):
        from dmlc_tpu import obs
        from dmlc_tpu.utils.logging import log_info

        p = self.param
        # one fit = one "epoch"; trees are the steps (both the fused-scan
        # and the live-logging path funnel their history through _obs_fit,
        # and both go through the shared fit-loop helper — same metrics,
        # goodput window, and watchdog pass as the feed-driven learners)
        from dmlc_tpu.models.fitloop import FitLoopObs

        fl = FitLoopObs("gbdt")
        _t_fit = time.monotonic_ns()

        def _obs_fit(history):
            fl.note_step(len(history))
            fl.end_epoch(0, len(history), _t_fit,
                         history[-1] if history else None)
            return history
        if p.objective == "softmax":
            # the shared chokepoint: fit AND fit_uri funnel here, so both
            # get the clean errors (out-of-range ids silently one_hot to
            # all-zero rows and train a NaN model otherwise)
            check(p.num_class >= 2,
                  "objective=softmax requires num_class >= 2")
            for arr, what in ((y, "softmax labels"),
                              (eval_y, "softmax eval labels")):
                if arr is None:
                    continue
                a = np.asarray(arr)
                check(len(a) == 0 or (
                    float(a.min()) >= 0 and float(a.max()) < p.num_class),
                    "%s must be class ids in [0, %d)", what, p.num_class)
        weighted = weight is not None
        multiprocess = self.mesh is not None and jax.process_count() > 1
        if multiprocess:
            # each process contributes its local rows; the global array
            # spans the world (DeviceFeed._put_tree's multi-host shape)
            shard = NamedSharding(self.mesh, P(self.axis))
            y_np = np.asarray(y, dtype=np.float32)
            xb = jax.make_array_from_process_local_data(
                shard, np.asarray(xb))
            yd = jax.make_array_from_process_local_data(shard, y_np)
            if weighted:
                weight = jax.make_array_from_process_local_data(
                    shard, np.asarray(weight, dtype=np.float32))
        else:
            xb = jnp.asarray(xb)
            yd = jnp.asarray(y)
            if weighted:
                weight = jnp.asarray(weight, dtype=jnp.float32)
            if self.mesh is not None:
                shard = NamedSharding(self.mesh, P(self.axis))
                xb = jax.device_put(xb, shard)
                yd = jax.device_put(yd, shard)
                if weighted:
                    weight = jax.device_put(weight, shard)
        with_eval = eval_xb is not None
        if with_eval:
            eval_xb = jnp.asarray(eval_xb)
            eval_yd = jnp.asarray(eval_y)
        self.eval_history = None
        self.best_iteration = None
        wargs = (weight,) if weighted else ()
        eargs = (eval_xb, eval_yd) if with_eval else ()
        if not log_every:
            # the default path: the WHOLE boosting loop is one lax.scan
            # dispatch (make_forest_builder) — per-tree dispatch overhead
            # retired, XLA schedules across tree stages
            if self._forest is None or self._forest[0] != (weighted,
                                                           with_eval):
                self._forest = ((weighted, with_eval), make_forest_builder(
                    p.num_trees, p.max_depth, p.num_bins, p.reg_lambda,
                    p.min_child_weight, p.learning_rate, p.objective,
                    self.mesh, self.axis, weighted=weighted,
                    num_class=p.num_class, with_eval=with_eval,
                    subsample=p.subsample,
                    colsample=p.colsample_bytree, seed=p.seed,
                ))
            with obs.span("fit", model="gbdt", trees=p.num_trees):
                out = self._forest[1](xb, yd, *wargs, *eargs)
            if with_eval:
                self.trees, losses, vlosses = out
                self._set_eval_history(np.asarray(vlosses))
            else:
                self.trees, losses = out
            return _obs_fit([float(v) for v in np.asarray(losses)])
        # live-logging path: one dispatch per tree so losses stream out
        # while training runs (the scan only reports at the end). Only
        # this path carries a margin across dispatches.
        mshape = ((len(y),) if p.objective != "softmax"
                  else (len(y), p.num_class))
        if multiprocess:
            margin = jax.make_array_from_process_local_data(
                shard, np.zeros(mshape, dtype=np.float32))
        else:
            margin = jnp.zeros(mshape, dtype=jnp.float32)
        stochastic = p.subsample < 1.0 or p.colsample_bytree < 1.0
        colsample_on = p.colsample_bytree < 1.0
        if self._builder is None or self._builder[0] != colsample_on:
            self._builder = (colsample_on, make_tree_builder(
                p.max_depth, p.num_bins, p.reg_lambda,
                p.min_child_weight, self.mesh, self.axis,
                with_feat_mask=colsample_on,
            ))
        if stochastic:
            # jitted so the mask math runs with global-array semantics
            # (an eager multiply would reject multi-process sharded g/h).
            # Same helper + fold_in scheme as the scan body — identical
            # masks and therefore identical forests at mesh=None (the
            # mesh scan also folds in the shard index, which a
            # non-shard_map jit cannot: there the two paths are both
            # valid stochastic boosting but not mask-identical). The
            # closure constant is a 2-int key — no recompile concern.
            if self.mesh is not None:
                from dmlc_tpu.utils.logging import log_warning
                log_warning(
                    "gbdt: log_every with mesh + subsample/colsample < 1 "
                    "draws different stochastic masks than the fused-scan "
                    "path (log_every=0), so the two settings train "
                    "different (equally valid) forests; set log_every=0 "
                    "for a scan-identical model")
            base_key = jax.random.PRNGKey(p.seed)
            nf = int(xb.shape[1])
            mask_step = instrumented_jit(
                lambda t, g, h: _apply_stochastic_masks(
                    base_key, t, nf, g, h, p.subsample,
                    p.colsample_bytree, None),
                "gbdt.mask_step")
        grad_fn = self._make_grad_fn(weighted)
        update_fn = self._make_margin_update()
        if with_eval:
            eval_step = self._make_eval_step()
            vshape = ((len(eval_y),) if p.objective != "softmax"
                      else (len(eval_y), p.num_class))
            vmargin = jnp.zeros(vshape, dtype=jnp.float32)
            vlosses = []
        feats, bins, gains, leaves = [], [], [], []
        history = []
        with obs.span("fit", model="gbdt", trees=p.num_trees):
            for t in range(p.num_trees):
                g, h, mean_loss = grad_fn(margin, yd, *wargs)
                margs = ()
                if stochastic:
                    g, h, feat_mask = mask_step(t, g, h)
                    if colsample_on:
                        margs = (feat_mask,)
                feature, split_bin, gain, leaf, node = self._builder[1](
                    xb, g, h, *margs)
                feats.append(feature)
                bins.append(split_bin)
                gains.append(gain)
                leaves.append(leaf)
                margin = update_fn(margin, leaf, node)
                history.append(float(mean_loss))
                if with_eval:
                    vmargin, vloss = eval_step(eval_xb, eval_yd, feature,
                                               split_bin, leaf, vmargin)
                    vlosses.append(float(vloss))
                if (t + 1) % log_every == 0:
                    log_info("tree %d loss %.6f", t + 1, history[-1])
        self.trees = {
            "feature": jnp.stack(feats),
            "bin": jnp.stack(bins),
            "gain": jnp.stack(gains),
            "leaf": jnp.stack(leaves),
        }
        if with_eval:
            self._set_eval_history(np.asarray(vlosses))
        return _obs_fit(history)

    def _make_grad_fn(self, weighted: bool = False):
        objective = self.param.objective

        def _fn(margin, y, *maybe_w, axis=None):
            return _grad_loss_core(
                objective, margin, y,
                maybe_w[0] if weighted else None, axis)

        if self.mesh is None:
            return instrumented_jit(_fn, "gbdt.grad")
        data = (P(self.axis),) * (3 if weighted else 2)
        return instrumented_jit(shard_map(
            lambda *args: _fn(*args, axis=self.axis),
            mesh=self.mesh,
            in_specs=data,
            out_specs=(P(self.axis), P(self.axis), P()),
        ), "gbdt.grad")

    def _make_margin_update(self):
        lr = self.param.learning_rate

        def _fn(margin, leaf, node):
            return _margin_update_core(margin, leaf, node, lr)

        if self.mesh is None:
            return instrumented_jit(_fn, "gbdt.margin_update")
        return instrumented_jit(shard_map(
            _fn, mesh=self.mesh,
            in_specs=(P(self.axis), P(), P(self.axis)),
            out_specs=P(self.axis),
        ), "gbdt.margin_update")

    # ---- predict -------------------------------------------------------
    def predict_margin(self, x: np.ndarray) -> np.ndarray:
        check(self.trees is not None, "model not fitted")
        xb = apply_bins(np.asarray(x, dtype=np.float32), self.edges)
        margin = self.param.learning_rate * predict_trees(
            self.trees, xb, self.param.max_depth
        )
        return np.asarray(margin)

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Probabilities under logistic ([N]) and softmax ([N, K],
        xgboost multi:softprob — argmax for class ids), raw margin under
        squared."""
        margin = self.predict_margin(x)
        if self.param.objective == "logistic":
            return np.asarray(jax.nn.sigmoid(jnp.asarray(margin)))
        if self.param.objective == "softmax":
            return np.asarray(jax.nn.softmax(jnp.asarray(margin), axis=-1))
        return margin

    # ---- checkpointing via the Stream surface (SURVEY §5.4) -------------
    def save(self, uri: str) -> None:
        from dmlc_tpu.io.filesystem import create_stream
        from dmlc_tpu.io.serializer import save_obj

        check(self.trees is not None, "model not fitted")
        with create_stream(uri, "w") as out:
            payload = {
                "param": self.param.to_dict(),
                "edges": np.asarray(self.edges),
                "feature": np.asarray(self.trees["feature"]),
                "bin": np.asarray(self.trees["bin"]),
                "leaf": np.asarray(self.trees["leaf"]),
            }
            if "gain" in self.trees:  # tolerant like load: a model
                # restored from a pre-gain checkpoint must stay savable
                payload["gain"] = np.asarray(self.trees["gain"])
            save_obj(out, payload)

    def load(self, uri: str) -> None:
        from dmlc_tpu.io.filesystem import create_stream
        from dmlc_tpu.io.serializer import load_obj

        with create_stream(uri, "r") as stream:
            payload = load_obj(stream)
        self.param.init(payload["param"], allow_unknown=True)
        # the cached builders bake in the PREVIOUS hyperparameters; a
        # fit() after load() must rebuild them against the restored ones
        self._builder = None
        self._forest = None
        self._eval_step = None
        self.edges = payload["edges"]
        self.trees = {
            "feature": jnp.asarray(payload["feature"]),
            "bin": jnp.asarray(payload["bin"]),
            "leaf": jnp.asarray(payload["leaf"]),
        }
        if "gain" in payload:  # absent in pre-gain checkpoints
            self.trees["gain"] = jnp.asarray(payload["gain"])

    def _make_eval_step(self):
        """Cached jitted watchlist step for the live-logging path: the
        eval arrays are ARGUMENTS, not closure constants (a fresh
        closure per fit would bake [Ne, F] into the jaxpr and recompile
        every call)."""
        if getattr(self, "_eval_step", None) is None:
            p = self.param
            offsets = jnp.asarray(_tree_level_offsets(p.max_depth),
                                  dtype=jnp.int32)
            lr = p.learning_rate
            objective = p.objective

            def eval_step(exb, eyd, feature, split_bin, leaf, vmargin):
                vnode = _descend_tree(exb, feature, split_bin,
                                      p.max_depth, offsets)
                vmargin = _margin_update_core(vmargin, leaf, vnode, lr)
                return vmargin, jnp.mean(_loss(objective, vmargin, eyd))

            self._eval_step = instrumented_jit(eval_step, "gbdt.eval_step")
        return self._eval_step

    def _set_eval_history(self, vlosses: np.ndarray) -> None:
        self.eval_history = [float(v) for v in vlosses]
        self.best_iteration = int(np.argmin(vlosses))

    def truncate(self, num_trees: int) -> None:
        """Cut the forest back to its first ``num_trees`` trees — the
        early-stopping companion to ``best_iteration`` (a scan has
        static length, so selection happens after the fit):

            learner.fit(x, y, eval_set=(xv, yv))
            learner.truncate(learner.best_iteration + 1)
        """
        check(self.trees is not None, "model not fitted")
        total = self.trees["feature"].shape[0]
        check(1 <= num_trees <= total,
              "num_trees must be in [1, %d]", total)
        self.trees = {k: v[:num_trees] for k, v in self.trees.items()}

    def feature_importance(self, kind: str = "gain") -> np.ndarray:
        """Per-feature importance [F] — xgboost get_score semantics:
        ``gain`` sums each feature's realized split gains over the
        forest; ``split`` counts its splits."""
        check(self.trees is not None, "model not fitted")
        check(kind in ("gain", "split"), "kind must be gain or split")
        feats = np.asarray(self.trees["feature"]).ravel()
        if kind == "split":
            vals = np.ones_like(feats, dtype=np.float32)
        else:
            check("gain" in self.trees,
                  "checkpoint predates gain recording — refit for "
                  "gain importance (split importance still works)")
            vals = np.asarray(self.trees["gain"]).ravel()
        mask = feats >= 0
        out = np.zeros(self.edges.shape[0], dtype=np.float32)
        np.add.at(out, feats[mask], vals[mask])
        return out
