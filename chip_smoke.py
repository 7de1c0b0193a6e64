#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dmlc_tpu still starts on the chip.

Drives the system's main path once, through the entry points a user calls
(``LinearLearner.fit_uri`` -> ``create_parser`` (native pipeline) ->
``DeviceFeed`` -> compiled step -> ``JobSnapshot``), at the full width of
the widest model the repo has (a 2^20-feature hashed sparse space: linear
and FM at ``num_factors=8``), plus GBDT, every Pallas kernel compiled by
Mosaic, the staging pool under donation, the CPU worker worlds beside a
parent that holds the chip, and — with more than one chip — the same fits
over ``data_parallel_mesh(jax.devices())``. Weights start from zeros or a
seed, data is generated from seeds, every phase is checked against a plain
numpy (float64) or XLA reference on the same input.

Contract: ONE process uses the chip (the children it starts are a ``make``
and CPU-pinned socket workers, all joined before it exits). With no TPU it
exits non-zero at preflight and runs nothing — there is no CPU
continuation. The first phase that fails ends the run: reason on stderr,
exit code 1, no result line. On success the LAST stdout line is one JSON
object with exactly these keys: ``{"ok": true, "device": {"platform",
"kind", "count"}}``, the device as jax reports it. The line before it
(``[chip_smoke] detail {...}``, also written to
``chiprun_out/chip_smoke_detail.json``) carries per-phase wall seconds split
into compile and run, step counts, losses and compile counts. Those are
set-up facts (``smoke_*``), not benchmark numbers.

Run it through the chip tool: ``chiprun -- python3 chip_smoke.py``
(``--chips 4`` for the all-chips phase). Needs no network, no file that
``.gitignore`` lists: the native library is rebuilt from ``cpp/`` here.
"""

import functools
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DETAIL_DIR = os.path.join(HERE, "chiprun_out")  # gitignored

DENSE_ROWS = 131_072  # 32 steps/epoch at batch 4096
DENSE_FEATURES = 28  # HIGGS: ids 1..28 -> num_features 29
CRITEO_ROWS = 131_072  # 16 steps/epoch at batch 8192
CRITEO_DIM = 1 << 20  # hashed feature space (bench.py CRITEO_DIM)
CRITEO_NNZ = 39  # 13 numeric + 26 categorical per row
LN2 = math.log(2.0)  # logistic loss of an all-zero model


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg, *args):
    if not cond:
        raise SmokeFailure(msg % args if args else msg)


def say(msg, *args):
    print("[chip_smoke] " + (msg % args if args else msg), flush=True)


# ---------------------------------------------------------------------------
# compile accounting: jax's own monitoring events, so "compile seconds"
# means lowering + backend compile (or persistent-cache retrieval) for
# EVERY program the phase built, instrumented or not. Tracing is left out:
# its events nest (an inner jit's trace is inside the outer one's) and
# would count twice.
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.backend_compiles = {}  # fun_name -> count
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
            if event == _COMPILE_EVENTS[1]:
                name = kw.get("fun_name", "?")
                self.backend_compiles[name] = (
                    self.backend_compiles.get(name, 0) + 1)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def run_phase(name, fn, clock, results):
    """Run one phase; record wall/compile/run seconds beside its facts.
    A raise propagates — no phase is wrapped in a catch that lets the run
    exit 0."""
    say("phase %s ...", name)
    c0, h0, m0 = clock.seconds, clock.cache_hits, clock.cache_misses
    t0 = time.perf_counter()
    facts = fn() or {}
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    facts.update(
        status=facts.get("status", "passed"),
        smoke_wall_s=round(wall, 2),
        smoke_compile_s=round(compile_s, 2),
        smoke_run_s=round(max(0.0, wall - compile_s), 2),
        smoke_cache_hits=clock.cache_hits - h0,
        smoke_cache_misses=clock.cache_misses - m0,
    )
    results[name] = facts
    say("phase %s %s in %.1fs (compile %.1fs)", name, facts["status"],
        wall, compile_s)


# ---------------------------------------------------------------------------
# data, generated from seeds; the arrays the text was printed from are the
# references' inputs (values rounded to the printed precision first)
# ---------------------------------------------------------------------------


def make_dense(path):
    """HIGGS-shaped libsvm with a planted linear signal (so the loss has
    somewhere to fall): 28 dense features, ids 1..28."""
    import numpy as np

    rng = np.random.RandomState(42)
    x = np.round(rng.rand(DENSE_ROWS, DENSE_FEATURES), 6)
    w_true = rng.randn(DENSE_FEATURES)
    y = ((x - 0.5) @ w_true + 0.25 * rng.randn(DENSE_ROWS) > 0).astype(int)
    with open(path, "w") as fh:
        for start in range(0, DENSE_ROWS, 16384):
            lines = []
            for i in range(start, min(start + 16384, DENSE_ROWS)):
                row = x[i]
                lines.append("%d " % y[i] + " ".join(
                    "%d:%.6f" % (j + 1, row[j])
                    for j in range(DENSE_FEATURES)))
            fh.write("\n".join(lines) + "\n")
    dense = np.zeros((DENSE_ROWS, DENSE_FEATURES + 1), np.float32)
    dense[:, 1:] = x
    return dense, y.astype(np.float32)


def make_criteo(path):
    """The Criteo-shaped set of bench.py: 39 nnz/row over 2^20 hashed ids
    (7-digit ids, sorted per row), labels from a planted sparse signal."""
    import numpy as np

    rng = np.random.RandomState(7)
    ids = rng.randint(0, CRITEO_DIM, size=(CRITEO_ROWS, CRITEO_NNZ))
    ids.sort(axis=1)
    vals = np.round(rng.rand(CRITEO_ROWS, CRITEO_NNZ), 4)
    w_true = rng.randn(CRITEO_DIM)
    y = ((vals * w_true[ids]).sum(axis=1) > 0).astype(int)
    with open(path, "w") as fh:
        for start in range(0, CRITEO_ROWS, 8192):
            lines = []
            for i in range(start, min(start + 8192, CRITEO_ROWS)):
                ri, rv = ids[i], vals[i]
                lines.append("%d " % y[i] + " ".join(
                    "%d:%.4f" % (ri[j], rv[j]) for j in range(CRITEO_NNZ)))
            fh.write("\n".join(lines) + "\n")
    return ids.astype(np.int64), vals.astype(np.float32), \
        y.astype(np.float32)


# ---------------------------------------------------------------------------
# float64 numpy references (the same SGD the compiled steps run)
# ---------------------------------------------------------------------------


def _logistic(margin, label):
    import numpy as np

    loss = (np.maximum(margin, 0.0) - margin * label
            + np.log1p(np.exp(-np.abs(margin))))
    return loss, 1.0 / (1.0 + np.exp(-margin)) - label


def ref_dense_sgd(x, y, batch, epochs, lr):
    import numpy as np

    x = x.astype(np.float64)
    w, b, history = np.zeros(x.shape[1]), 0.0, []
    for _ in range(epochs):
        loss_sum = 0.0
        for s in range(0, len(x), batch):
            xb, yb = x[s:s + batch], y[s:s + batch]
            loss, g = _logistic(xb @ w + b, yb)
            loss_sum += loss.sum()
            w = w - lr * (xb.T @ g) / len(xb)
            b = b - lr * g.sum() / len(xb)
        history.append(loss_sum / len(x))
    return history, w, b


def ref_sparse_sgd(ids, vals, y, dim, batch, lr, factors=None, lr_fm=0.05):
    """One epoch of linear (``factors`` None) or FM csr SGD — models/fm.py
    ``_fm_entry_grads`` / linear ``_local_grads`` in numpy float64.
    ``factors``: the [dim, K] initial factor table for FM."""
    import numpy as np

    w, b = np.zeros(dim), 0.0
    v = None if factors is None else factors.astype(np.float64)
    lr = lr if factors is None else lr_fm
    loss_sum = 0.0
    for s in range(0, len(ids), batch):
        ib, xb, yb = ids[s:s + batch], vals[s:s + batch].astype(
            np.float64), y[s:s + batch]
        margin = b + (xb * w[ib]).sum(axis=1)
        if v is not None:
            xv = xb[:, :, None] * v[ib]  # [B, nnz, K]
            ssum = xv.sum(axis=1)  # [B, K]
            margin = margin + 0.5 * (ssum * ssum - (xv * xv).sum(axis=1)
                                     ).sum(axis=1)
        loss, g = _logistic(margin, yb)
        loss_sum += loss.sum()
        n, flat = len(ib), ib.ravel()
        gw = np.bincount(flat, weights=(xb * g[:, None]).ravel(),
                         minlength=dim)
        if v is not None:
            dv = ((g[:, None] * xb)[:, :, None]
                  * (ssum[:, None, :] - xv)).reshape(-1, v.shape[1])
            gv = np.stack([np.bincount(flat, weights=dv[:, k], minlength=dim)
                           for k in range(v.shape[1])], axis=1)
            v = v - lr * gv / n
        w = w - lr * gw / n
        b = b - lr * g.sum() / n
    return loss_sum / len(ids), w, v


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def _on_tpu(arr):
    return all(d.platform == "tpu" for d in arr.devices())


def _compiles(fn_name):
    from dmlc_tpu.obs.device_telemetry import compile_counts

    return compile_counts().get(fn_name, 0)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def preflight():
    """TPU or exit; rebuild the native library from the committed sources;
    require it. Returns the device identity."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            "chip_smoke: jax found no TPU (platform %r, device_kind %r) — "
            "this script only runs on the chip; nothing was run\n"
            % (dev.platform, dev.device_kind))
        sys.exit(2)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    # the .so is gitignored: one lying in the tree is not evidence
    proc = subprocess.run(
        ["make", "-B", "-C", os.path.join(HERE, "cpp")],
        capture_output=True, text=True, timeout=600)
    check(proc.returncode == 0, "make -B -C cpp failed:\n%s",
          proc.stderr[-2000:])
    from dmlc_tpu import native
    from dmlc_tpu.utils.jax_compat import place_compile_cache

    check(native.available(), "native library did not load after the build")
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    facts = {
        "smoke_jax": jax.__version__,
        "smoke_libtpu": libtpu_version,
        "smoke_compile_cache_dir": place_compile_cache(),
        "smoke_native_simd_level": native.simd_level(),
    }
    say("platform=%s device_kind=%s devices=%d jax=%s libtpu=%s cache=%s",
        device["platform"], device["kind"], device["count"],
        facts["smoke_jax"], libtpu_version, facts["smoke_compile_cache_dir"])
    return device, facts


def dense_flagship(ctx):
    """fit_uri on the HIGGS shape with snapshots, then resume."""
    import numpy as np

    from dmlc_tpu import obs
    from dmlc_tpu.collective import JobSnapshot
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.parsers import NativePipelineParser
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.obs import xla_cost

    path, snap_uri = ctx["dense_path"], os.path.join(ctx["tmp"], "snap")
    parser = create_parser(path, 0, 1)
    check(isinstance(parser, NativePipelineParser),
          "create_parser returned %s, not the native pipeline",
          type(parser).__name__)
    parser.close()

    step_compiles0 = _compiles("linear.step")
    backend0 = dict(ctx["clock"].backend_compiles)
    learner = LinearLearner()
    history = learner.fit_uri(path, batch_size=4096, epochs=2,
                              num_features=DENSE_FEATURES + 1,
                              snapshot_uri=snap_uri)
    check(len(history) == 2 and all(np.isfinite(history)),
          "dense losses not finite: %r", history)
    check(history[1] < history[0] < LN2, "dense loss not falling: %r",
          history)
    check(learner.params["w"].shape == (DENSE_FEATURES + 1,)
          and _on_tpu(learner.params["w"]),
          "params['w'] is not resident on a TPU device")
    step_compiles = _compiles("linear.step") - step_compiles0
    check(step_compiles == 1,
          "expected exactly one linear.step compile for one batch shape, "
          "got %d", step_compiles)
    # no second XLA compile behind the cost extraction: the step program
    # went through the backend compiler (or the cache) exactly once
    step_builds = sum(
        v - backend0.get(k, 0)
        for k, v in ctx["clock"].backend_compiles.items()
        if k.endswith("(step)"))
    check(step_builds == 1,
          "the linear step was built %d times by the backend, not once",
          step_builds)
    site = xla_cost.sites_from_flat(
        obs.registry().flat_values()).get("linear.step", {})
    check(site.get("flops", 0) > 0,
          "dmlc_xla_flops{fn=linear.step} gauge absent after the fit: %r",
          site)
    extract_ms = xla_cost.per_fn()["linear.step"]["extract_ms"]

    ref_hist, ref_w, _ = ref_dense_sgd(
        ctx["dense_x"], ctx["dense_y"], 4096, 2, 0.1)
    loss_dev = max(_rel(a, b) for a, b in zip(history, ref_hist))
    w_dev = float(np.max(np.abs(np.asarray(learner.params["w"]) - ref_w)))
    # the TPU's default f32 matmul multiplies in bf16 passes; 1e-2 is far
    # outside that and far inside a wrong gradient
    check(loss_dev < 1e-2 and w_dev < 1e-2,
          "dense fit disagrees with the float64 reference: loss rel %.3g, "
          "max |dw| %.3g", loss_dev, w_dev)

    version, state, _meta = JobSnapshot(
        snap_uri, rank=0, world_size=1).restore()
    check(version > 0 and state is not None and state["epoch"] == 1,
          "no committed snapshot manifest for epoch 1 (version %r)", version)
    resumed = LinearLearner()
    history3 = resumed.fit_uri(path, batch_size=4096, epochs=3,
                               num_features=DENSE_FEATURES + 1,
                               snapshot_uri=snap_uri, resume=True)
    check(len(history3) == 3 and history3[:2] == history,
          "resume did not restore the history: %r vs %r", history3, history)
    check(np.isfinite(history3[2]) and history3[2] < history3[1],
          "resumed epoch loss did not continue falling: %r", history3)
    ctx["dense_history"] = history
    return {
        "smoke_steps": 3 * (DENSE_ROWS // 4096),
        "smoke_losses": [round(h, 6) for h in history3],
        "smoke_linear_step_compiles": step_compiles,
        "smoke_cost_extract_ms": extract_ms,
        "smoke_ref_loss_rel": float("%.3g" % loss_dev),
        "smoke_ref_max_abs_dw": float("%.3g" % w_dev),
        "smoke_snapshot_version": int(version),
        "smoke_step_flops": site["flops"],
    }


def widest_model(ctx):
    """The widest model the repo has: linear and FM (num_factors=8, a 32 MB
    table) over 2^20 hashed features, csr layout, batch 8192."""
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models import FMLearner, LinearLearner
    from dmlc_tpu.models.fm import init_fm_params

    path, nf = ctx["criteo_path"], CRITEO_DIM + 1
    ids, vals, y = ctx["criteo"]
    lin0, fm0 = _compiles("linear.step"), _compiles("fm.step")

    linear = LinearLearner()
    (lin_loss,) = linear.fit_uri(path, batch_size=8192, layout="csr",
                                 num_features=nf, epochs=1)
    ref_loss, ref_w, _ = ref_sparse_sgd(ids, vals, y, nf, 8192, 0.1)
    lin_dw = float(np.max(np.abs(np.asarray(linear.params["w"]) - ref_w)))
    check(np.isfinite(lin_loss) and _rel(lin_loss, ref_loss) < 1e-3
          and lin_dw < 1e-4,
          "sparse linear epoch disagrees with the float64 reference: loss "
          "%.6f vs %.6f, max |dw| %.3g", lin_loss, ref_loss, lin_dw)
    check(_on_tpu(linear.params["w"]), "sparse linear params not on the TPU")

    fm = FMLearner(num_features=nf)
    check(fm.param.num_factors == 8, "FM default num_factors changed")
    feed = DeviceFeed(
        create_parser(path, 0, 1),
        BatchSpec(batch_size=8192, layout="csr", num_features=nf))
    try:
        (fm_loss,) = fm.fit_feed(feed, epochs=1)
    finally:
        feed.close()
    v0 = np.asarray(init_fm_params(nf, 8, fm.param.init_scale)["v"])
    ref_fm_loss, _, ref_v = ref_sparse_sgd(
        ids, vals, y, nf, 8192, 0.1, factors=v0)
    fm_dv = float(np.max(np.abs(np.asarray(fm.params["v"]) - ref_v)))
    check(fm.params["v"].shape == (nf, 8) and _on_tpu(fm.params["v"]),
          "FM factor table is not [2^20+1, 8] on the TPU")
    check(np.isfinite(fm_loss) and _rel(fm_loss, ref_fm_loss) < 1e-3
          and fm_dv < 1e-4,
          "FM epoch disagrees with the float64 reference: loss %.6f vs "
          "%.6f, max |dv| %.3g", fm_loss, ref_fm_loss, fm_dv)
    ctx["criteo_loss"] = lin_loss
    return {
        "smoke_steps": 2 * (CRITEO_ROWS // 8192),
        "smoke_linear_loss": round(lin_loss, 6),
        "smoke_fm_loss": round(fm_loss, 6),
        "smoke_linear_nnz_buckets_compiled": _compiles("linear.step") - lin0,
        "smoke_fm_nnz_buckets_compiled": _compiles("fm.step") - fm0,
        "smoke_ref_linear_loss_rel": float(
            "%.3g" % _rel(lin_loss, ref_loss)),
        "smoke_ref_fm_loss_rel": float("%.3g" % _rel(fm_loss, ref_fm_loss)),
        "smoke_fm_table_mb": round(nf * 8 * 4 / 2 ** 20, 1),
    }


def _fit_gbdt(ctx, mesh=None, log_every=0):
    from dmlc_tpu.models import GBDTLearner

    learner = GBDTLearner(mesh=mesh, num_trees=8, max_depth=6, num_bins=64)
    history = learner.fit(ctx["dense_x"], ctx["dense_y"],
                          log_every=log_every)
    return learner, history


def _same_forest(a, b):
    import numpy as np

    return (np.array_equal(np.asarray(a["feature"]), np.asarray(b["feature"]))
            and np.array_equal(np.asarray(a["bin"]), np.asarray(b["bin"]))
            and np.allclose(np.asarray(a["leaf"]), np.asarray(b["leaf"]),
                            rtol=1e-4, atol=1e-6))


def gbdt(ctx):
    """The _bench_gbdt shape: one fused-scan dispatch; the per-tree loop
    (the repo's own parity twin) must build the identical forest."""
    import numpy as np

    forest0, tree0 = _compiles("gbdt.forest"), _compiles("gbdt.build_tree")
    learner, history = _fit_gbdt(ctx)
    forest_compiles = _compiles("gbdt.forest") - forest0
    check(forest_compiles == 1 and _compiles("gbdt.build_tree") == tree0,
          "GBDT fit was not ONE fused-scan dispatch")
    check(len(history) == 8 and np.all(np.isfinite(history))
          and history[-1] < history[0],
          "GBDT history not finite and falling: %r", history)
    looped, loop_history = _fit_gbdt(ctx, log_every=8)
    check(_same_forest(learner.trees, looped.trees),
          "fused-scan and per-tree loop built different forests")
    check(np.allclose(history, loop_history, rtol=1e-4),
          "scan/loop loss histories differ: %r vs %r", history, loop_history)
    acc = float(np.mean((learner.predict(ctx["dense_x"][:8192]) > 0.5)
                        == (ctx["dense_y"][:8192] > 0.5)))
    check(acc > 0.6, "GBDT train accuracy %.3f is no better than chance", acc)
    ctx["gbdt_trees"], ctx["gbdt_history"] = learner.trees, list(history)
    return {
        "smoke_trees": 8,
        "smoke_losses": [round(float(h), 6) for h in history],
        "smoke_forest_compiles": forest_compiles,
        "smoke_train_accuracy": round(acc, 4),
    }


def kernels(ctx):
    """Every Pallas kernel compiled by Mosaic (interpret=False) at the
    shapes the learners above use, against its XLA / numpy reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.data import vparse
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )
    from dmlc_tpu.ops import full_attention, make_pallas_flash_local
    from dmlc_tpu.ops.pallas_kernels import (
        coo_segment_sum,
        fused_linear_grads,
        tokenize_boundaries,
    )

    rng = np.random.RandomState(3)
    facts = {}

    # fused dense grads, one learner batch [4096, 29]
    x, y = ctx["dense_x"][:4096], ctx["dense_y"][:4096]
    w = (0.1 * rng.randn(x.shape[1])).astype(np.float32)
    wgt = np.ones(len(y), np.float32)
    gw, gb, loss_sum, wsum = jax.block_until_ready(fused_linear_grads(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(wgt), jnp.asarray(w),
        0.05, interpret=False))
    loss, g = _logistic(x.astype(np.float64) @ w + 0.05, y)
    ref_gw = x.astype(np.float64).T @ g
    gw_dev = float(np.max(np.abs(np.asarray(gw) - ref_gw)))
    loss_dev = _rel(float(loss_sum), loss.sum())
    # f32 sums of 4096 terms, and the chip's exp/log1p (measured on the
    # v5e: loss 1.3e-5 relative off float64, the XLA step identically so)
    check(np.allclose(gw, ref_gw, rtol=1e-4, atol=1e-2)
          and np.isclose(gb, g.sum(), rtol=1e-4, atol=1e-2)
          and loss_dev < 1e-4 and float(wsum) == len(y),
          "fused_linear_grads disagrees with the float64 reference: max "
          "|dgw| %.3g, gb %.6f vs %.6f, loss rel %.3g", gw_dev, float(gb),
          g.sum(), loss_dev)
    facts["fused_linear_grads"] = "compiled as written, matches float64"
    facts["smoke_fused_max_abs_dgw"] = float("%.3g" % gw_dev)
    facts["smoke_fused_loss_rel"] = float("%.3g" % loss_dev)

    # COO segment-sum at one Criteo batch: 8192 rows x 39 nnz
    ids, vals, _ = ctx["criteo"]
    rid = np.repeat(np.arange(8192, dtype=np.int32), CRITEO_NNZ)
    contrib = (vals[:8192].ravel()
               * rng.randn(rid.size).astype(np.float32))
    got = jax.block_until_ready(coo_segment_sum(
        jnp.asarray(contrib), jnp.asarray(rid), 8192, interpret=False))
    want = np.bincount(rid, weights=contrib.astype(np.float64),
                       minlength=8192)
    seg_dev = float(np.max(np.abs(np.asarray(got) - want)))
    check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
          "coo_segment_sum disagrees with numpy bincount (max |d| %.3g)",
          seg_dev)
    facts["coo_segment_sum"] = (
        "compiled after repair (output block re-laid lane-major), "
        "matches float64")
    facts["smoke_segsum_max_abs_dev"] = float("%.3g" % seg_dev)

    # byte tokenizer over 1 MiB of the libsvm text the dense phase parsed
    with open(ctx["dense_path"], "rb") as fh:
        chunk = np.frombuffer(fh.read(1 << 20), dtype=np.uint8)
    starts, ends = tokenize_boundaries(chunk, interpret=False)
    ref_starts, ref_ends = vparse.token_boundary_masks(chunk)
    check(np.array_equal(starts, ref_starts)
          and np.array_equal(ends, ref_ends),
          "tokenize_boundaries disagrees with vparse.token_boundary_masks")
    facts["tokenize_boundaries"] = "compiled as written, byte-identical"

    # flash attention wrapper, T=2048 causal
    q, k, v = (jnp.asarray(rng.randn(1, 2048, 2, 128).astype(np.float32))
               for _ in range(3))
    out = jax.block_until_ready(
        jax.jit(make_pallas_flash_local(causal=True))(q, k, v))
    want = full_attention(q, k, v, causal=True)
    flash_err = float(jnp.max(jnp.abs(out - want)))
    # the tolerance of the repo's own flash test: both sides multiply in
    # the MXU's bf16 passes
    check(out.shape == q.shape
          and np.allclose(out, want, rtol=2e-2, atol=2e-2),
          "make_pallas_flash_local(T=2048, causal) vs full_attention: "
          "max |d| %.3g", flash_err)
    facts["make_pallas_flash_local"] = "compiled as written"
    facts["smoke_flash_max_abs_err"] = float("%.3g" % flash_err)

    # one use_pallas=True train step per layout against the XLA step
    nf = CRITEO_DIM + 1
    offsets = np.arange(8193, dtype=np.int32) * CRITEO_NNZ
    batches = {
        "dense": (DENSE_FEATURES + 1, {
            "x": x, "label": y, "weight": wgt}),
        "csr": (nf, {
            "label": ctx["criteo"][2][:8192], "weight": np.ones(
                8192, np.float32),
            "indices": ids[:8192].ravel().astype(np.int32),
            "values": vals[:8192].ravel(), "offsets": offsets}),
    }
    for layout, (dim, host_batch) in batches.items():
        outs = {}
        for use_pallas in (False, True):
            step = make_linear_train_step(
                None, layout=layout, num_features=dim,
                use_pallas=use_pallas)
            params = init_linear_params(dim)
            velocity = {key: jnp.zeros_like(val)
                        for key, val in params.items()}
            for _ in range(2):  # second step sees non-zero weights
                params, velocity, metrics = step(
                    params, velocity,
                    {key: jnp.asarray(val)
                     for key, val in host_batch.items()})
            outs[use_pallas] = (np.asarray(params["w"]),
                                float(metrics["loss_sum"]))
        # the kernels add in exact f32 on the VPU, XLA's dense step
        # multiplies in bf16 passes on the MXU: 1e-2 relative covers that
        check(np.allclose(outs[True][0], outs[False][0], rtol=1e-2,
                          atol=1e-4)
              and _rel(outs[True][1], outs[False][1]) < 1e-3,
              "use_pallas=True %s step disagrees with the XLA step "
              "(loss %.6f vs %.6f)", layout, outs[True][1], outs[False][1])
        facts["train_step_pallas_" + layout] = "matches the XLA step"
    facts.update(dma_row_writer())
    facts.update(run_sums())
    facts.update(dlrm_step())
    return facts


def run_sums(interpret=False, rows=8192, calls=30, seed=45):
    """The SGD step's id sums as a TPU takes them (``models/fm.py``
    ``_sum_by_slot``: the segmented scan ``run_sums`` along each id's
    sorted run, each run's total laid at its slot) against XLA's
    ``segment_sum`` by sorted slot, at the ``criteo-dlrm`` cell's
    ``[212992,16]``: the table ids of ``rows`` rows of the configuration's
    power law in id order (some ids thousands of entries long). Both
    timed over ``calls`` calls; they agree within float32 rounding of a
    tree of adds. The keywords are the CPU test's
    (tests/test_chip_smoke.py), which runs it small at the configuration's
    rehearse size in Pallas' interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.models import fm

    bench = os.path.join(HERE, "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import spec

        cell = spec.Cell("criteo-dlrm.libsvm", rehearse=interpret)
        ids = cell.config.rows(dict(cell.cfg, rows=rows), seed)["ids"]
    finally:
        sys.path.remove(bench)
    ids = np.sort(ids[:, int(cell.cfg["dense_features"]):].ravel())
    order = fm._slots_in_id_order(
        jnp.asarray(ids, jnp.int32), int(cell.cfg["num_features"]))
    upd = np.random.RandomState(seed).randn(ids.size, int(
        cell.cfg["num_factors"])).astype(np.float32) * np.float32(1e-3)
    slot = np.asarray(order.slot)
    want = np.zeros((order.ids.shape[0], upd.shape[1]))
    np.add.at(want, slot, upd.astype(np.float64))
    size = np.zeros(order.ids.shape[0])
    np.add.at(size, slot, np.abs(upd).astype(np.float64).sum(axis=1))
    outs, ms = {}, {}
    on_device = jnp.asarray(upd)
    for name, platform in (("scan", "tpu"), ("segment_sum", None)):
        summed = jax.jit(functools.partial(
            fm._sum_by_slot, platform=platform, interpret=interpret))
        got = jax.block_until_ready(summed(on_device, order))
        t = time.perf_counter()
        for _ in range(calls):
            got = summed(on_device, order)
        jax.block_until_ready(got)
        ms[name] = (time.perf_counter() - t) / calls * 1e3
        outs[name] = np.asarray(got, np.float64)
    # a tree of adds in a block of entries, one add a block crossed
    depth = math.log2(fm._RUN_BLOCK) + -(-ids.size // fm._RUN_BLOCK) + 2
    eps = float(np.finfo(np.float32).eps)
    off = np.abs(outs["scan"] - want) / (depth * eps * size[:, None] + 1e-30)
    diff = float(np.max(np.abs(outs["scan"] - outs["segment_sum"])))
    check(float(np.max(off)) <= 1.0
          and not outs["scan"][int(order.distinct):].any(),
          "the run sums are off the float64 sums by %.3g of their float32 "
          "bound (largest |scan - segment_sum| %.3g)", float(np.max(off)),
          diff)
    say("run_sums over [%d,%d], %d distinct ids: scan %.3f ms, "
        "segment_sum %.3f ms, largest |difference| %.3g", ids.size,
        upd.shape[1], int(order.distinct), ms["scan"], ms["segment_sum"],
        diff)
    return {"run_sums": "[%d,%d] over %d runs (longest %d): scan %.3f ms, "
            "segment_sum %.3f ms, largest |difference| %.3g" % (
                ids.size, upd.shape[1], int(order.distinct),
                int(np.bincount(slot).max()), ms["scan"],
                ms["segment_sum"], diff)}


def dma_row_writer(interpret=False, chunk=2048, passes=13, height=65539,
                   cells=((7812351, "float32"), (18228818, "float32"),
                          (2187459, "int32"))):
    """The FM step's writer of pure writes (``models/fm.py``
    ``_write_rows`` on a TPU at 128 lanes: one DMA a distinct lane row)
    as the step calls it, once a chunk inside a loop that carries the
    donated array: equal to XLA's scatter to the bit on a table of
    ``height`` rows (no multiple of 8; a lane row's two dtypes), and
    compiled by Mosaic IN PLACE at the ``cells``' sizes (kdd12-fm,
    kdd12-fm-difacto, the memory-adaptive FM's base rows): the array
    aliased to the result whole, nothing of its size beside it. The
    keywords are the CPU test's (tests/test_chip_smoke.py), which runs
    the first half small in Pallas' interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.models import fm

    def put_all(array, target, new, platform):
        def put(i, array):
            return fm._write_rows(
                array, jax.lax.dynamic_slice_in_dim(target, i * chunk, chunk),
                jax.lax.dynamic_slice_in_dim(new, i * chunk, chunk),
                platform=platform, interpret=interpret)

        return jax.lax.fori_loop(0, passes, put, array)

    def program(platform):
        return jax.jit(functools.partial(put_all, platform=platform),
                       donate_argnums=0)

    def bits(array):
        return np.asarray(array).view(np.uint32)

    rng = np.random.RandomState(41)
    slots = passes * chunk
    # as ``_put_lane_rows`` hands them over: distinct, the idle slots (one
    # in thirteen here) naming the row ``height`` + their own number
    target = height + np.arange(slots, dtype=np.int32)
    live = rng.rand(slots) < 12.0 / 13.0
    target[live] = rng.choice(height, int(live.sum()), replace=False)
    for kind in (np.float32, np.int32):
        table, new = (rng.randint(-2 ** 31, 2 ** 31, (n, 128)).astype(
            np.int32).view(kind) for n in (height, slots))
        got, want = (bits(program(platform)(
            jnp.asarray(table), jnp.asarray(target), jnp.asarray(new)))
            for platform in ("tpu", None))
        check(np.array_equal(got, want),
              "the DMA row writer (%s) disagrees with XLA's scatter in %d "
              "of %d rows", kind.__name__,
              int((got != want).any(axis=1).sum()), height)
        check(np.array_equal(got[target[live]], bits(new)[live])
              and int((got != bits(table)).any(axis=1).sum())
              <= int(live.sum()),
              "the DMA row writer (%s) wrote a row it was not given",
              kind.__name__)
    facts = {"dma_row_writer": "matches XLA's scatter to the bit (%d live "
             "of %d slots, f32 and s32)" % (int(live.sum()), slots)}
    if interpret:
        return facts
    jax.config.update("jax_enable_compilation_cache", False)  # a cached
    # executable answers no memory_analysis
    try:
        for rows, kind in cells:
            compiled = program("tpu").lower(
                jax.ShapeDtypeStruct((rows, 128), kind),
                jax.ShapeDtypeStruct((slots,), jnp.int32),
                jax.ShapeDtypeStruct((slots, 128), kind)).compile()
            memory = compiled.memory_analysis()
            check("tpu_custom_call" in compiled.as_text()
                  and memory.alias_size_in_bytes >= rows * 128 * 4
                  and memory.temp_size_in_bytes < 1 << 20,
                  "the DMA row writer over %s[%d,128] is not in place: "
                  "alias %d bytes, temp %d", kind, rows,
                  memory.alias_size_in_bytes, memory.temp_size_in_bytes)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    facts["dma_row_writer_in_place"] = "compiled at %s" % ", ".join(
        "%s[%d,128]" % (kind, rows) for rows, kind in cells)
    return facts


def dlrm_step(steps=3, rehearse=False, seed=42):
    """``models/dlrm.py``'s step at the ``criteo-dlrm`` cell's full size
    (33,762,591 ids of 16 columns as lane rows, the published MLPs,
    batches of 8192 rows of 39 entries laid out as the feed lays them)
    for ``steps`` steps, against the configuration's float64 reference:
    each step's loss, every dense parameter and every row the batches
    touch, under the cell's own limits. ``rehearse``: at the
    configuration's rehearse size (the CPU test's,
    tests/test_chip_smoke.py)."""
    import jax.numpy as jnp
    import numpy as np

    bench = os.path.join(HERE, "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import spec

        cell = spec.Cell("criteo-dlrm.libsvm", rehearse=rehearse)
    finally:
        sys.path.remove(bench)
    from dmlc_tpu.device.csr import round_up_bucket

    cfg = cell.cfg
    rows = int(cfg["batch_rows_per_chip"])
    data = cell.config.rows(dict(cfg, rows=steps * rows), seed)
    model = cell.config.learner(cfg, None)
    model.init_tables(seed)
    touched = np.unique(data["ids"])
    at = jnp.asarray(touched, jnp.int32)

    def logical():
        out = {name: np.asarray(model.table_rows(name, at), np.float64)
               for name in model.table_names()}
        out.update({k: np.float64(v) for k, v in model.scalars().items()})
        return out

    before = logical()
    width = data["ids"].shape[1]
    pad = round_up_bucket(rows * width) - rows * width
    losses, batches = [], []
    for i in range(steps):
        part = slice(i * rows, (i + 1) * rows)
        batches.append({
            "label": data["label"][part], "values": data["values"][part],
            "ids": np.searchsorted(touched, data["ids"][part])})
        model._ensure(int(cfg["num_features"]))
        sums = model.train_step({
            "label": jnp.asarray(data["label"][part], jnp.float32),
            "weight": jnp.ones((rows,), jnp.float32),
            "indices": jnp.asarray(np.pad(
                data["ids"][part].ravel(), (0, pad)), jnp.int32),
            "values": jnp.asarray(np.pad(
                data["values"][part].ravel(), (0, pad)), jnp.float32),
            "offsets": jnp.arange(rows + 1, dtype=jnp.int32) * width})
        losses.append(float(sums["loss_sum"]) / float(sums["weight_sum"]))
        check(int(sums["left_out"]) == 0, "the step left %d entries out",
              int(sums["left_out"]))
    ref_losses, ref = cell.config.reference_steps(cfg, before, batches)
    after = logical()
    loss_rel = max(_rel(a, b) for a, b in zip(losses, ref_losses))
    update_rel = {
        k: float(np.max(np.abs(after[k] - ref[k]))
                 / max(np.max(np.abs(ref[k] - before[k])), 1e-30))
        for k in before}
    worst = max(update_rel, key=update_rel.get)
    check(loss_rel <= cfg["check"]["loss_rel_tol"]
          and update_rel[worst] <= cfg["check"]["update_rel_tol"],
          "the DLRM step is off its float64 reference: loss_rel %.3g "
          "(limit %g), update_rel %.3g on %s (limit %g)", loss_rel,
          cfg["check"]["loss_rel_tol"], update_rel[worst], worst,
          cfg["check"]["update_rel_tol"])
    return {"dlrm_step": "%d steps of %d rows over %s[%d,128] match the "
            "float64 reference: loss_rel %.2g, update_rel %.2g (%s), "
            "row writer %s" % (
                steps, rows, model.params.rows.dtype,
                model.params.rows.shape[0], loss_rel, update_rel[worst],
                worst, model.row_writer)}


def staging_pool(ctx):
    """The accelerator-only pool branch: a Python-path feed (pooled
    staging) under LinearLearner's donating step must keep recycling."""
    from dmlc_tpu.data import PipelinedParser
    from dmlc_tpu.data.parsers import LibSVMParser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.io import create_input_split
    from dmlc_tpu.models import LinearLearner

    parser = PipelinedParser(
        LibSVMParser(create_input_split(ctx["dense_path"], 0, 1, "text"),
                     nthread=1), nthread=2)
    feed = DeviceFeed(parser, BatchSpec(
        batch_size=4096, layout="dense", num_features=DENSE_FEATURES + 1))
    check(feed.pool.recycle, "the pool does not recycle on the TPU")
    try:
        (loss,) = LinearLearner().fit_feed(feed, epochs=1)
        stats = feed.pool.stats()
    finally:
        feed.close()
    check(stats["reused"] > 0,
          "the staging pool never recycled under donation: %r", stats)
    # same rows, same batches, same compiled math as the native-path fit
    check(_rel(loss, ctx["dense_history"][0]) < 1e-6,
          "python-path epoch loss %.8f != native-path %.8f (recycled "
          "staging rewrote a batch in flight?)", loss,
          ctx["dense_history"][0])
    return {"smoke_steps": DENSE_ROWS // 4096, "smoke_loss": round(loss, 6),
            "smoke_pool": stats}


def cpu_children(ctx):
    """bench.py's child worlds while this process holds the chip: the
    parity workers pin themselves to the cpu backend, the socket workers
    import jax but never initialize a backend — neither may touch the
    chip (a child that did would fail on the libtpu lockfile)."""
    import bench_collective
    from dmlc_tpu.tools.parity import run_parity

    parity = run_parity(world=2, steps=3)
    check(parity["pass"], "parity world failed its own criterion: %r",
          {k: parity[k] for k in ("criterion", "max_loss_rel",
                                  "max_grad_ulp")})
    sock = bench_collective.socket_allreduce_metrics(
        world=2, cases=(("socket_tree_64k", 64 << 10, "tree"),), iters=2)
    check(sock["socket_tree_64k_gbps"] > 0, "socket world returned %r", sock)
    return {"smoke_parity_criterion": parity["criterion"],
            "smoke_parity_max_loss_rel": float(
                "%.3g" % parity["max_loss_rel"]),
            "smoke_parity_max_grad_ulp": int(parity["max_grad_ulp"]),
            "smoke_socket_workers": 2}


def all_chips(ctx):
    """The dense, CSR and GBDT fits again over every chip of the host."""
    import jax
    import numpy as np

    n = len(jax.devices())
    if n == 1:
        say("all_chips skipped: jax reports one device, there is no mesh "
            "to shard over (run with --chips 4)")
        return {"status": "skipped",
                "reason": "one device visible; no mesh to shard over"}
    from dmlc_tpu import obs
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models import LinearLearner
    from dmlc_tpu.obs import xla_cost
    from dmlc_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh(jax.devices())
    facts = {"smoke_devices": n}
    for name, path, kw, single in (
        ("dense", ctx["dense_path"],
         dict(batch_size=4096, epochs=2, num_features=DENSE_FEATURES + 1),
         ctx["dense_history"][-1]),
        ("csr", ctx["criteo_path"],
         dict(batch_size=8192, epochs=1, layout="csr",
              num_features=CRITEO_DIM + 1),
         ctx["criteo_loss"]),
    ):
        feed = DeviceFeed(
            create_parser(path, 0, 1),
            BatchSpec(batch_size=kw["batch_size"],
                      layout=kw.get("layout", "dense"),
                      num_features=kw["num_features"]),
            mesh=mesh)
        try:
            batch = next(iter(feed))
            for key, arr in batch.items():
                if isinstance(arr, jax.Array):
                    held = {s.device for s in arr.addressable_shards}
                    check(len(held) == n,
                          "%s batch array %r has shards on %d of %d devices",
                          name, key, len(held), n)
        finally:
            feed.close()
        compiles0 = _compiles("linear.step")
        learner = LinearLearner(mesh=mesh)
        history = learner.fit_uri(path, **kw)
        check(len(learner.params["w"].sharding.device_set) == n,
              "%s params are not on all %d devices", name, n)
        site = xla_cost.sites_from_flat(
            obs.registry().flat_values())["linear.step"]
        check(site.get("collective_bytes", 0) > 0,
              "%s mesh step reports no collective bytes: %r", name, site)
        # per-shard sums then one psum: only the gradient's summation
        # order differs from the one-chip run
        dev = _rel(history[-1], single)
        check(np.all(np.isfinite(history)) and dev < 1e-4,
              "%s %d-chip loss %.8f vs one-chip %.8f (rel %.3g > 1e-4)",
              name, n, history[-1], single, dev)
        facts["smoke_%s_loss" % name] = round(history[-1], 6)
        facts["smoke_%s_loss_rel_vs_one_chip" % name] = float("%.3g" % dev)
        facts["smoke_%s_collective_bytes" % name] = site["collective_bytes"]
        facts["smoke_%s_step_compiles" % name] = (
            _compiles("linear.step") - compiles0)
    # per-shard histograms then a psum change the summation order: a
    # near-tied gain deep in a tree may flip and every later tree then
    # differs, so the forests need not be node-identical (on the 4-device
    # CPU mesh 254 of 504 nodes differ at this shape while the loss
    # stays within 0.5%). What must hold: the well-separated root split
    # is the same and the boosting loss tracks the one-chip fit.
    meshed, history = _fit_gbdt(ctx, mesh=mesh)
    one = ctx["gbdt_trees"]
    root_same = all(
        int(np.asarray(meshed.trees[key])[0, 0]) == int(
            np.asarray(one[key])[0, 0]) for key in ("feature", "bin"))
    dev = max(_rel(a, b) for a, b in zip(history, ctx["gbdt_history"]))
    check(np.all(np.isfinite(history)) and root_same and dev < 2e-2,
          "the %d-chip GBDT fit left the one-chip fit: root split same=%s, "
          "loss rel %.3g (> 2e-2)", n, root_same, dev)
    facts["smoke_gbdt_loss_rel_vs_one_chip"] = float("%.3g" % dev)
    facts["smoke_gbdt_nodes_differing"] = int(np.sum(
        np.asarray(meshed.trees["feature"]) != np.asarray(one["feature"])))
    return facts


def result_line(device):
    """The last stdout line of a run that passed: these keys and no
    others — whoever runs the script parses it strictly. Everything else
    the run learned goes on the detail line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main():
    t_run = time.perf_counter()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        device, facts = preflight()  # exits non-zero off the chip
        import jax

        clock = CompileClock()
        results = {"preflight": dict(facts, status="passed")}
        ctx = {"tmp": tmp, "clock": clock,
               "dense_path": os.path.join(tmp, "higgs_like.svm"),
               "criteo_path": os.path.join(tmp, "criteo_like.svm")}
        t0 = time.perf_counter()
        ctx["dense_x"], ctx["dense_y"] = make_dense(ctx["dense_path"])
        ctx["criteo"] = make_criteo(ctx["criteo_path"])
        results["preflight"]["smoke_datagen_s"] = round(
            time.perf_counter() - t0, 1)
        for name, fn in (
            ("dense_flagship", dense_flagship),
            ("widest_model", widest_model),
            ("gbdt", gbdt),
            ("kernels", kernels),
            ("staging_pool", staging_pool),
            ("cpu_children", cpu_children),
            ("all_chips", all_chips),
        ):
            run_phase(name, lambda fn=fn: fn(ctx), clock, results)
    except SmokeFailure as err:
        sys.stderr.write("chip_smoke: FAILED: %s\n" % err)
        sys.exit(1)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    detail = {
        "device": device,
        "smoke_wall_s": round(time.perf_counter() - t_run, 1),
        "smoke_compile_s": round(clock.seconds, 1),
        "smoke_cache_hits": clock.cache_hits,
        "smoke_cache_misses": clock.cache_misses,
        "smoke_live_device_mb": round(sum(
            a.nbytes for a in jax.live_arrays()) / 2 ** 20, 1),
        "phases": results,
    }
    os.makedirs(DETAIL_DIR, exist_ok=True)
    with open(os.path.join(DETAIL_DIR, "chip_smoke_detail.json"), "w") as fh:
        json.dump(detail, fh, indent=1)
    say("detail %s", json.dumps(detail))
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main()
