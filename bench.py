#!/usr/bin/env python
"""Headline benchmark: HIGGS-like LibSVM ingest throughput.

Measures a full pass of the sharded ingest pipeline (InputSplit chunking →
native chunk parse → CSR RowBlocks) over a deterministic synthetic HIGGS-like
file (600k rows × 28 dense features ≈ 190 MB), the same workload as the
reference's `test/libsvm_parser_test.cc` harness.

Methodology (the numbers must be defensible on a noisy 1-core host):
- one untimed warmup pass first (builds the native lib on fresh checkouts,
  warms the page cache, primes thread pools);
- the shared vCPU's effective speed swings ~1.6x on a minutes timescale
  (measured: a fixed numpy probe ranges 1.26-2.03 GB/s over two minutes,
  and identical parse binaries score 360 vs 600 MB/s depending on the
  window). The headline therefore runs as THREE thread-config sweeps
  spread across the whole bench run; each sweep records a host-speed
  probe next to its trials, and the headline is the best sweep's best
  configuration median — the software's capability, controlled for host
  throttling. Every sweep, trial, and probe lands in `extra` so a
  drifting number can be root-caused from the JSON alone;
- the native pipeline's per-stage counters (reader/parse/consumer ns)
  for the winning configuration are reported alongside.

vs_baseline compares against the reference C++ parser (libsvm_parser_test,
compiled -O3, best of nthread ∈ {4,8,16}) measured on the same class of
host: 334 MB/s (see BASELINE.md "measured" section).

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "MB/s", "vs_baseline": N, "extra": {...}}

Artifact discipline (round-4 lesson: the full per-sweep JSON outgrew the
driver's tail-capture window and the round's headline number survived
nowhere machine-readable): the stdout line is a COMPACT summary — headline
context, every tier's median, device status — bounded well under 2 KB.
The complete per-sweep/per-trial record is written to a detail file
(env DMLC_TPU_BENCH_DETAIL, default $DMLC_TPU_BENCH_DIR/bench_detail.json)
whose path the stdout line carries.

Every record names the device it ran on (``platform``, ``device_kind``,
``device_count``, as jax reports them). The device tiers run only on a TPU:
on any other backend they do not run at all and the record says
``device_tiers: "not measured"`` — a CPU timing is never written under a
device metric's name. On a TPU a device tier that raises is recorded under
its ``*_error`` key, the line still prints, and the process exits non-zero.
This process holds the chip; the worker processes it spawns (parity world,
socket allreduce) stay on the host.
"""

import json
import os
import statistics
import sys
import time

REFERENCE_MBPS = 334.0  # reference libsvm_parser_test on this host class
ROWS = 600_000
FEATURES = 28
TRIALS = 3
HEADLINE_TRIALS = 3  # per sweep; three sweeps are spread across main()
CACHE_DIR = os.environ.get("DMLC_TPU_BENCH_DIR", "/tmp/dmlc_tpu_bench")
DATA_PATH = os.path.join(CACHE_DIR, f"higgs_like_{ROWS}.svm")


def _ensure_data() -> str:
    if os.path.exists(DATA_PATH) and os.path.getsize(DATA_PATH) > 0:
        return DATA_PATH
    os.makedirs(CACHE_DIR, exist_ok=True)
    import numpy as np

    rng = np.random.RandomState(42)
    tmp = DATA_PATH + ".tmp"
    with open(tmp, "w") as fh:
        chunk_rows = 20_000
        for start in range(0, ROWS, chunk_rows):
            n = min(chunk_rows, ROWS - start)
            labels = rng.randint(0, 2, size=n)
            vals = rng.rand(n, FEATURES)
            lines = []
            for i in range(n):
                row = vals[i]
                lines.append(
                    str(labels[i])
                    + " "
                    + " ".join(
                        f"{j + 1}:{row[j]:.6f}" for j in range(FEATURES)
                    )
                )
            fh.write("\n".join(lines) + "\n")
    os.replace(tmp, DATA_PATH)
    return DATA_PATH


def _one_pass(path: str, nthread: int) -> tuple:
    """One timed full parse pass → (MB/s, per-stage stats dict)."""
    from dmlc_tpu.data import create_parser

    t0 = time.time()
    parser = create_parser(path, 0, 1, nthread=nthread)
    rows = 0
    nnz = 0
    for block in parser:
        rows += len(block)
        nnz += block.num_nonzero
    dt = time.time() - t0
    stats = parser.stats() if hasattr(parser, "stats") else None
    mbps = parser.bytes_read / (1 << 20) / dt
    parser.close()
    assert rows == ROWS, f"row count mismatch: {rows}"
    assert nnz == ROWS * FEATURES, f"nnz mismatch: {nnz}"
    return mbps, stats


def _host_probe() -> float:
    """Fixed-work CPU probe (GB/s), ~0.1s; -1.0 if the probe itself fails
    (it is context for the score, never a reason to lose it). The shared
    vCPU's effective speed swings ~1.6x on a minutes timescale; a probe
    recorded next to each sweep makes that drift visible in the JSON
    instead of silently moving the score."""
    try:
        import numpy as np

        buf = getattr(_host_probe, "_buf", None)
        if buf is None:
            buf = np.random.RandomState(0).randint(
                0, 255, size=20_000_000, dtype=np.uint8
            )
            _host_probe._buf = buf
        t0 = time.perf_counter()
        for _ in range(3):
            int(buf.sum())
        return round(3 * buf.nbytes / (time.perf_counter() - t0) / 1e9, 2)
    except Exception:
        return -1.0


def _headline_threads() -> list:
    cpus = os.cpu_count() or 1
    return sorted({1, 2, min(8, max(1, cpus)), min(16, max(1, cpus))})


def _headline_sweep(path: str) -> dict:
    """One thread-config sweep → {probe_gbps, trials, stats}."""
    probe = _host_probe()
    trials = {}
    stats_by_cfg = {}
    for nthread in _headline_threads():
        runs = []
        run_stats = []
        for _ in range(HEADLINE_TRIALS):
            mbps, stats = _one_pass(path, nthread)
            runs.append(round(mbps, 1))
            run_stats.append(stats)
        trials[nthread] = runs
        # keep the stats of the median trial — the one the score reports
        median_idx = runs.index(sorted(runs)[len(runs) // 2])
        stats_by_cfg[nthread] = run_stats[median_idx]
    return {"probe_gbps": probe, "trials": trials, "stats": stats_by_cfg}


def _combine_headline(sweeps: list) -> tuple:
    """Best sweep's best configuration median → (headline, extra)."""
    best = None  # (median, sweep index, cfg)
    for i, sw in enumerate(sweeps):
        for cfg, runs in sw["trials"].items():
            med = statistics.median(runs)
            if best is None or med > best[0]:
                best = (med, i, cfg)
    headline, idx, best_cfg = best
    runs = sweeps[idx]["trials"][best_cfg]
    extra = {
        "sweeps": [
            {
                "probe_gbps": sw["probe_gbps"],
                "trials_mbps": {str(k): v for k, v in sw["trials"].items()},
            }
            for sw in sweeps
        ],
        "headline_sweep": idx,
        "headline_cfg_nthread": best_cfg,
        "headline_spread_mbps": [min(runs), max(runs)],
    }
    stats = sweeps[idx]["stats"].get(best_cfg)
    if stats:
        sec = 1e9
        extra["stages"] = {
            "chunks": stats["chunks"],
            "reader_io_s": round(stats["reader_io_ns"] / sec, 3),
            "reader_wait_s": round(stats["reader_wait_ns"] / sec, 3),
            "parse_s": round(stats["parse_ns"] / sec, 3),
            "worker_wait_s": round(stats["worker_wait_ns"] / sec, 3),
            "consumer_wait_s": round(stats["consumer_wait_ns"] / sec, 3),
        }
    return headline, extra


def _ensure_rowrec(src: str, rec: str) -> str:
    """Binary row-group twin of a text file (data/rowrec.py): the
    scan-free format — framing + memcpy — that binary shards should use.
    ``rec`` must encode the workload shape in its name (like the sources
    do) so constant bumps regenerate it rather than silently benching a
    stale conversion."""
    from dmlc_tpu.data.rowrec import convert_to_recordio

    if not (os.path.exists(rec) and os.path.getsize(rec) > 0):
        convert_to_recordio(src, rec + ".tmp", rows_per_group=4096)
        os.replace(rec + ".tmp", rec)
    return rec


def _ensure_recordio(path: str) -> str:
    return _ensure_rowrec(
        path, os.path.join(CACHE_DIR, f"higgs_like_{ROWS}.rec"))


def _rowrec_sweep(rec: str, expected_rows: int) -> dict:
    """One recordio-ingest sweep over a row-group file → {probe_gbps,
    trials} (first trial is an in-sweep warmup, dropped)."""
    from dmlc_tpu.data import create_parser

    probe = _host_probe()
    runs = []
    for _ in range(TRIALS + 1):
        t0 = time.time()
        parser = create_parser(rec, 0, 1, data_format="recordio", nthread=1)
        rows = sum(len(b) for b in parser)
        dt = time.time() - t0
        mb = parser.bytes_read / (1 << 20)
        parser.close()
        assert rows == expected_rows, f"recordio row mismatch: {rows}"
        runs.append(round(mb / dt, 1))
    return {"probe_gbps": probe, "trials": runs[1:]}


def _recordio_sweep(path: str) -> dict:
    return _rowrec_sweep(_ensure_recordio(path), ROWS)


def _ensure_criteo_recordio() -> str:
    """Binary row-group twin of the Criteo-shaped file: the sparse
    north-star workload's steady-state shard format."""
    return _ensure_rowrec(
        _ensure_criteo_like(),
        os.path.join(
            CACHE_DIR,
            f"criteo_like_{CRITEO_ROWS}x{CRITEO_NNZ}_d{CRITEO_DIM}.rec",
        ),
    )


def _criteo_recordio_sweep() -> dict:
    """One sparse binary-shard ingest sweep. Kept next to the text tier
    so the 'binary shards hold their multiple on the sparse shape' claim
    is harness-measured every round."""
    return _rowrec_sweep(_ensure_criteo_recordio(), CRITEO_ROWS)


def _ensure_shard(path: str) -> str:
    """Baked columnar twin of the higgs-shaped text file (io/shard.py,
    baked through tools/bake.py so the bench exercises the product CLI
    path). Idempotent: the bake sidecar digest skips a re-bake when the
    source and bake params are unchanged."""
    from dmlc_tpu.tools.bake import bake_dataset

    dst = os.path.join(CACHE_DIR, f"higgs_like_{ROWS}.dtsh")
    bake_dataset(path, dst, data_format="libsvm", rows_per_window=16384)
    return dst


def _shard_sweep(path: str) -> dict:
    """One baked-shard ingest sweep → {probe_gbps, trials, bake_mbps}.

    Trials are MB/s over the *shard* bytes (what the steady-state epoch
    actually reads), matching the recordio tier's accounting.
    ``bake_mbps`` is the one-off conversion cost in source-text MB/s —
    forced (not sidecar-skipped) so every sweep measures a real bake and
    the combine step can take the best window like any other score."""
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.tools.bake import bake_dataset

    probe = _host_probe()
    dst = os.path.join(CACHE_DIR, f"higgs_like_{ROWS}.dtsh")
    t0 = time.time()
    bake_dataset(path, dst, data_format="libsvm", rows_per_window=16384,
                 force=True)
    bake_dt = time.time() - t0
    src_mb = os.path.getsize(path) / (1 << 20)
    runs = []
    for _ in range(TRIALS + 1):
        t0 = time.time()
        parser = create_parser(dst, 0, 1, nthread=1)
        rows = sum(len(b) for b in parser)
        dt = time.time() - t0
        mb = parser.bytes_read / (1 << 20)
        parser.close()
        assert rows == ROWS, f"shard row mismatch: {rows}"
        runs.append(round(mb / dt, 1))
    return {
        "probe_gbps": probe,
        "trials": runs[1:],
        "bake_mbps": round(src_mb / bake_dt, 1),
    }


def _combine_tier(sweeps: list) -> tuple:
    """Best sweep's score (median of its trials unless the sweep recorded
    an explicit score) → (value, sweeps-for-extra). The host is bimodal
    (BASELINE.md): a tier scored from ONE window is a coin flip, so every
    tier runs three sweeps spread across the bench and scores the best
    window — same discipline as the headline."""
    best = None
    for sw in sweeps:
        if "error" in sw or not sw.get("trials"):
            continue
        score = sw.get("score", statistics.median(sw["trials"]))
        if best is None or score > best:
            best = score
    return best, sweeps



def _bench_nthread() -> int:
    """Parse workers, native fill and device dispatch contend on small
    hosts: measured on the 1-core driver box, nthread=1 beats 2 by ~1.5x
    on the feed benches."""
    return 1 if (os.cpu_count() or 1) <= 2 else 2


def _timed_sgd_epochs(make_feed, size_mb, step_fn, layout, params, velocity,
                      stats_out=None):
    """TRIALS+1 timed epochs (first = warmup) through one jitted step —
    the single timing protocol every ingest->SGD bench in this file uses.
    ``stats_out`` (a list) collects ``feed.stats()`` for each non-warmup
    epoch — the per-stage stall breakdown next to its timing."""
    import jax

    from dmlc_tpu.models.fitloop import step_batch

    runs = []
    for trial in range(TRIALS + 1):
        feed = make_feed()
        t0 = time.time()
        for batch in feed:
            params, velocity, _m = step_fn(
                params, velocity, step_batch(batch, layout)
            )
        jax.block_until_ready(params)
        runs.append(round(size_mb / (time.time() - t0), 1))
        if stats_out is not None and trial > 0 and hasattr(feed, "stats"):
            stats_out.append(feed.stats())
        feed.close()
    return runs


CRITEO_ROWS = 200_000
CRITEO_DIM = 1 << 20  # hashed feature space
CRITEO_NNZ = 39  # 13 numeric + 26 categorical, Criteo shape


def _ensure_criteo_like() -> str:
    """Synthetic Criteo-shaped libsvm: 39 features/row drawn from a 2^20
    hashed id space with 7-digit ids — the high-cardinality SPARSE workload
    (the headline HIGGS file is dense-28 with 1-2 digit ids; a framework
    that only ingests that shape fast has not demonstrated the Criteo-class
    contract SURVEY §7 names)."""
    import numpy as np

    path = os.path.join(
        CACHE_DIR,
        f"criteo_like_{CRITEO_ROWS}x{CRITEO_NNZ}_d{CRITEO_DIM}.svm",
    )
    if os.path.exists(path) and os.path.getsize(path) > 0:
        return path
    os.makedirs(CACHE_DIR, exist_ok=True)
    rng = np.random.RandomState(7)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        for start in range(0, CRITEO_ROWS, 10_000):
            n = min(10_000, CRITEO_ROWS - start)
            labels = rng.randint(0, 2, size=n)
            ids = rng.randint(0, CRITEO_DIM, size=(n, CRITEO_NNZ))
            ids.sort(axis=1)
            vals = rng.rand(n, CRITEO_NNZ)
            lines = []
            for i in range(n):
                lines.append(
                    str(labels[i]) + " " + " ".join(
                        f"{ids[i, j]}:{vals[i, j]:.4f}"
                        for j in range(CRITEO_NNZ)
                    )
                )
            fh.write("\n".join(lines) + "\n")
    os.replace(tmp, path)
    return path


def _criteo_parse_sweep() -> dict:
    """One sparse high-cardinality parse sweep over the Criteo-shaped file
    → {probe_gbps, trials} (first trial is an in-sweep warmup, dropped).
    The {1,2}-thread configs both run; the sweep's trials are the better
    config's (mirroring the headline's per-config discipline at the
    1-core-host scale)."""
    from dmlc_tpu.data import create_parser

    path = _ensure_criteo_like()
    size_mb = os.path.getsize(path) / (1 << 20)
    probe = _host_probe()
    best_runs, best_med = None, -1.0
    for nthread in sorted({1, _bench_nthread()}):
        runs = []
        for _ in range(TRIALS + 1):
            t0 = time.time()
            parser = create_parser(path, 0, 1, nthread=nthread)
            rows = sum(len(b) for b in parser)
            dt = time.time() - t0
            parser.close()
            assert rows == CRITEO_ROWS, f"criteo row count mismatch: {rows}"
            runs.append(round(size_mb / dt, 1))
        med = statistics.median(runs[1:])
        if med > best_med:
            best_runs, best_med = runs[1:], med
    return {"probe_gbps": probe, "trials": best_runs}


# parse-stage corpora, read/synthesized once per run and kept in memory so
# every parse_only sweep times parse_chunk ALONE — no file I/O, no pipeline
# threads, no per-sweep page-cache variance
_PARSE_ONLY_CORPUS: dict = {}


def _parse_only_corpora() -> dict:
    if _PARSE_ONLY_CORPUS:
        return _PARSE_ONLY_CORPUS
    import numpy as np

    def _chunks(raw: bytes, target: int) -> list:
        out, pos = [], 0
        while pos < len(raw):
            cut = raw.rfind(b"\n", pos, pos + target) + 1
            if cut <= pos:  # no newline in window: take the rest
                cut = len(raw)
            out.append(raw[pos:cut])
            pos = cut
        return out

    with open(_ensure_data(), "rb") as fh:
        svm = fh.read(64 << 20)
    svm = svm[: svm.rfind(b"\n") + 1]
    _PARSE_ONLY_CORPUS["libsvm"] = _chunks(svm, 8 << 20)

    # dense CSV corpus, higgs-shaped (label + FEATURES columns), ~24 MB
    rng = np.random.RandomState(11)
    rows = []
    for start in range(0, 120_000, 20_000):
        labels = rng.randint(0, 2, size=20_000)
        vals = rng.rand(20_000, FEATURES)
        for i in range(20_000):
            rows.append(
                str(labels[i]) + ","
                + ",".join(f"{v:.4f}" for v in vals[i])
            )
    csv = ("\n".join(rows) + "\n").encode()
    _PARSE_ONLY_CORPUS["csv"] = _chunks(csv, 8 << 20)
    return _PARSE_ONLY_CORPUS


def _parse_only_sweep() -> dict:
    """Parse-STAGE microbench: in-memory chunks through parse_chunk per
    (format, backend), nothing else on the clock. The tier's trials (and
    so parse_only_mbps) are the production libsvm path — native when the
    core is loaded, else the python vector path; per-backend medians land
    in ``formats`` as ``*_gbps`` and are lifted into extra for the sentry.
    The python backends time a single chunk (they are 20-60 MB/s; the
    point is tracking the ratio, not burning bench wall-clock)."""
    from dmlc_tpu import native
    from dmlc_tpu.data import vparse
    from dmlc_tpu.data.parsers import _native_libsvm
    from dmlc_tpu.data.row_block import RowBlockContainer

    corpora = _parse_only_corpora()
    probe = _host_probe()

    def _time(chunks, fn):
        mb = sum(len(c) for c in chunks) / (1 << 20)
        runs = []
        for _ in range(TRIALS + 1):  # first is warmup, dropped
            t0 = time.time()
            for chunk in chunks:
                fn(chunk)
            runs.append(round(mb / (time.time() - t0), 1))
        return runs[1:]

    formats: dict = {}
    trials = None
    native_on = native.available()
    if native_on:
        runs = _time(corpora["libsvm"], _native_libsvm)
        trials = runs
        formats["libsvm_native_gbps"] = round(
            statistics.median(runs) / 1024, 3)
        csv_runs = _time(
            corpora["csv"], lambda c: native.parse_csv_chunk(c))
        formats["csv_native_gbps"] = round(
            statistics.median(csv_runs) / 1024, 3)
    vec_runs = _time(
        corpora["libsvm"][:1],
        lambda c: vparse.parse_libsvm_vector(c, RowBlockContainer()),
    )
    formats["libsvm_vector_gbps"] = round(
        statistics.median(vec_runs) / 1024, 3)
    csv_vec = _time(corpora["csv"][:1], vparse.parse_csv_vector_table)
    formats["csv_vector_gbps"] = round(statistics.median(csv_vec) / 1024, 3)
    if trials is None:
        trials = vec_runs
    return {"probe_gbps": probe, "trials": trials, "formats": formats,
            "native": native_on}


def _bench_criteo_sgd() -> dict:
    """Criteo sparse END-TO-END on the attached device: parse → sharded-COO
    staging → csr train step (segment-sum SpMV grads over the 2^20 feature
    space) → SGD — the north-star workload's device loop."""
    import jax.numpy as jnp

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )

    path = _ensure_criteo_like()
    size_mb = os.path.getsize(path) / (1 << 20)
    nthread = _bench_nthread()
    # auto bucket: the sixteenth-octave policy (device/csr.round_up_bucket)
    # pads ~2.5% on this shape vs 64% at the old fixed pow2 bucket —
    # measured +22% on this tier
    spec = BatchSpec(batch_size=8192, layout="csr",
                     num_features=CRITEO_DIM + 1)
    step = make_linear_train_step(
        None, learning_rate=0.05, layout="csr",
        num_features=CRITEO_DIM + 1, donate_batch=True,
    )
    params = init_linear_params(CRITEO_DIM + 1)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    sgd_runs = _timed_sgd_epochs(
        lambda: DeviceFeed(create_parser(path, 0, 1, nthread=nthread), spec),
        size_mb, step, "csr", params, velocity,
    )
    return {
        "criteo_like_csr_sgd_mbps": round(statistics.median(sgd_runs[1:]), 1),
        "criteo_like_csr_sgd_trials_mbps": sgd_runs[1:],
    }


def _bench_gbdt(path: str) -> dict:
    """Histogram-GBDT boosting rate on the attached device — the
    xgboost-over-rabit workload (models/gbdt.py) measured per the
    harness-or-it-didn't-happen bar. Metric = boosted row-visits per
    second (rows × trees / fit wall; each fit re-bins, a few percent of
    the wall on this shape): the histogram build (segment-sum + cumsum
    split finding) dominates, the same profile distributed xgboost
    allreduces. One learner serves every trial so the warmup fit
    genuinely absorbs the tree-builder jit compile (fresh learners would
    recompile per trial and score compile time as throughput)."""
    import numpy as np

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.models.gbdt import GBDTLearner

    rows_cap = 131_072
    parser = create_parser(path, 0, 1, nthread=1)
    xs, ys, seen = [], [], 0
    try:
        for block in parser:
            xs.append(block.to_dense(FEATURES + 1))  # 1-based ids
            ys.append(np.asarray(block.label, dtype=np.float32))
            seen += len(block)
            if seen >= rows_cap:
                break
    finally:
        parser.close()
    x = np.concatenate(xs)[:rows_cap]
    y = np.concatenate(ys)[:rows_cap]
    trees, depth = 8, 6
    runs = []
    learner = GBDTLearner(num_trees=trees, max_depth=depth,
                          learning_rate=0.3, num_bins=64)
    for _ in range(TRIALS + 1):  # first = jit compile warmup
        t0 = time.time()
        history = learner.fit(x, y)
        dt = time.time() - t0
        assert np.all(np.isfinite(history)), history
        runs.append(round(x.shape[0] * trees / dt / 1e6, 2))
    return {
        "gbdt_fit_mrows_s": statistics.median(runs[1:]),
        "gbdt_fit_trials_mrows_s": runs[1:],
        "gbdt_shape": f"{x.shape[0]}x{x.shape[1]} t{trees} d{depth} b64",
    }


def _bench_recordio_sgd(path: str) -> dict:
    """Recordio row-group → native StageBatch → dense SGD on the attached
    device: the scan-free binary ingest path driven all the way to the
    chip (host-side it parses at GB/s; this tier proves that throughput
    survives to the training loop instead of dying before H2D)."""
    import jax.numpy as jnp

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )

    rec = _ensure_recordio(path)
    size_mb = os.path.getsize(rec) / (1 << 20)
    spec = BatchSpec(batch_size=16384, layout="dense", num_features=29)
    params = init_linear_params(29)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    step = make_linear_train_step(None, learning_rate=0.1, layout="dense",
                                  donate_batch=True)
    runs = _timed_sgd_epochs(
        lambda: DeviceFeed(
            create_parser(rec, 0, 1, data_format="recordio", nthread=1),
            spec,
        ),
        size_mb, step, "dense", params, velocity,
    )
    return {
        "recordio_sgd_mbps": round(statistics.median(runs[1:]), 1),
        "recordio_sgd_trials_mbps": runs[1:],
    }


def _bench_shard_sgd(path: str) -> dict:
    """Baked columnar shard → dense SGD on the attached device: the
    ISSUE's 'ingest at RecordIO speed' claim measured end-to-end. Scored
    in *source-text* MB/s (same ``size_mb`` as sgd_e2e_mbps) so the
    sentry compares it directly against the text-parse epoch — the baked
    epoch must beat it or the format isn't paying for itself."""
    import jax.numpy as jnp

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )

    shard = _ensure_shard(path)
    size_mb = os.path.getsize(path) / (1 << 20)
    spec = BatchSpec(batch_size=16384, layout="dense", num_features=29)
    params = init_linear_params(29)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    step = make_linear_train_step(None, learning_rate=0.1, layout="dense",
                                  donate_batch=True)
    runs = _timed_sgd_epochs(
        lambda: DeviceFeed(create_parser(shard, 0, 1, nthread=1), spec),
        size_mb, step, "dense", params, velocity,
    )
    return {
        "sgd_e2e_shard_mbps": round(statistics.median(runs[1:]), 1),
        "sgd_e2e_shard_trials_mbps": runs[1:],
    }


def _median_stall_stages(stats_list) -> dict:
    """Median per-stage stall breakdown (seconds) over the non-warmup
    epochs' ``DeviceFeed.stats()`` records, pool/parse counters included —
    the 'where did the pipelined epoch's time go' artifact field."""
    if not stats_list:
        return {}
    out = {}
    for key in ("host_batch_ns", "dispatch_ns", "host_wait_ns",
                "consume_ns"):
        vals = [s.get(key, 0) for s in stats_list]
        out[key.replace("_ns", "_s")] = round(
            statistics.median(vals) / 1e9, 3)
    pools = [s.get("pool") or {} for s in stats_list]
    out["pool_allocated"] = int(statistics.median(
        [p.get("allocated", 0) for p in pools]))
    out["pool_reused"] = int(statistics.median(
        [p.get("reused", 0) for p in pools]))
    pipes = [s.get("pipeline") or {} for s in stats_list]
    if any(p.get("chunks") for p in pipes):
        out["parse_s"] = round(statistics.median(
            [p.get("parse_ns", 0) for p in pipes]) / 1e9, 3)
        out["parse_wait_s"] = round(statistics.median(
            [p.get("consumer_wait_ns", 0) for p in pipes]) / 1e9, 3)
    return out


def _bench_device_feed(path: str) -> dict:
    """Feed-only (parse→densify→H2D) and ingest→SGD MB/s on the attached
    accelerator, median of warm passes (the jitted step persists across
    passes — steady-state epochs, not first-compile)."""
    import jax

    from dmlc_tpu.data.parsers import create_parser
    from dmlc_tpu.device.feed import BatchSpec, DeviceFeed
    from dmlc_tpu.models.fitloop import step_batch
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )
    import jax.numpy as jnp

    size_mb = os.path.getsize(path) / (1 << 20)
    spec = BatchSpec(batch_size=16384, layout="dense", num_features=29)
    nthread = _bench_nthread()

    def _feed(feed_spec=spec):
        return DeviceFeed(
            create_parser(path, 0, 1, nthread=nthread), feed_spec
        )

    # feed-only at prefetch 1 vs 2: each dispatch pays real latency, so
    # a second batch in flight may hide it — the A/B lands in the
    # artifact so the better window is known per-deployment, not guessed
    feed_runs = []
    prefetch_ab = {}
    stage_samples = {"host_batch_ns": [], "dispatch_ns": [],
                     "host_wait_ns": []}
    for depth in (1, 2):
        depth_spec = BatchSpec(batch_size=16384, layout="dense",
                               num_features=29, prefetch=depth)
        runs = []
        for trial in range(TRIALS + 1):  # first is compile/cache warmup
            feed = _feed(depth_spec)
            t0 = time.time()
            last = None
            for batch in feed:
                last = batch
            jax.block_until_ready(last["x"])
            runs.append(round(size_mb / (time.time() - t0), 1))
            stats = feed.stats()
            if trial > 0 and depth == 1:  # stage medians at the base depth
                for key in stage_samples:
                    stage_samples[key].append(stats[key])
            feed.close()
        prefetch_ab[f"feed_dense_prefetch{depth}_trials_mbps"] = runs[1:]
        if depth == 1:
            feed_runs = runs
    feed_stages = {
        key.replace("_ns", "_s"): round(statistics.median(vals) / 1e9, 3)
        for key, vals in stage_samples.items()
    }

    params = init_linear_params(29)
    velocity = {"w": jnp.zeros_like(params["w"]),
                "b": jnp.zeros_like(params["b"])}
    step = make_linear_train_step(None, learning_rate=0.1, layout="dense",
                                  donate_batch=True)
    sgd_runs = _timed_sgd_epochs(
        _feed, size_mb, step, "dense", params, velocity
    )

    # tentpole A/B: fully-serial ingest (threaded=False parser — no parse
    # fan-out, no host prefetch thread, one transfer in flight) vs the
    # async pipeline (chunk-parse workers + host prefetch + transfer
    # window 2). Same step, same data: the spread IS the overlap win, and
    # the pipelined epochs' stage breakdown says where remaining time sat.
    sparams = init_linear_params(29)
    svel = {"w": jnp.zeros_like(sparams["w"]),
            "b": jnp.zeros_like(sparams["b"])}
    serial_spec = BatchSpec(batch_size=16384, layout="dense",
                            num_features=29, prefetch=1)
    serial_runs = _timed_sgd_epochs(
        lambda: DeviceFeed(
            create_parser(path, 0, 1, nthread=1, threaded=False),
            serial_spec, host_prefetch=0,
        ),
        size_mb, step, "dense", sparams, svel,
    )
    pparams = init_linear_params(29)
    pvel = {"w": jnp.zeros_like(pparams["w"]),
            "b": jnp.zeros_like(pparams["b"])}
    pipe_spec = BatchSpec(batch_size=16384, layout="dense",
                          num_features=29, prefetch=2)
    pipe_stats: list = []
    pipe_runs = _timed_sgd_epochs(
        lambda: DeviceFeed(
            create_parser(path, 0, 1, nthread=max(2, nthread)),
            pipe_spec, host_prefetch=2,
        ),
        size_mb, step, "dense", pparams, pvel, stats_out=pipe_stats,
    )

    # the same text uri with #cachefile: epoch 1 builds a row-group cache
    # (DiskRowIter semantics, disk_row_iter.h:95-141), warm epochs stream
    # binary — the reference's own answer to per-epoch text-parse tax,
    # here at the native recordio rate. Scored like every tier: warmup
    # epoch (the build) dropped, median of warm epochs.
    cache_uri = path + "#" + os.path.join(CACHE_DIR, "higgs_sgd_cache.rec")
    kparams = init_linear_params(29)
    kvel = {"w": jnp.zeros_like(kparams["w"]),
            "b": jnp.zeros_like(kparams["b"])}
    cached_runs = _timed_sgd_epochs(
        lambda: DeviceFeed(
            create_parser(cache_uri, 0, 1, nthread=nthread), spec
        ),
        size_mb, step, "dense", kparams, kvel,
    )

    # sparse path e2e: csr layout (native COO staging) through the csr
    # train step — the genuinely-sparse Criteo-class shape
    cparams = init_linear_params(29)
    cvel = {"w": jnp.zeros_like(cparams["w"]),
            "b": jnp.zeros_like(cparams["b"])}
    csr_step = make_linear_train_step(
        None, learning_rate=0.1, layout="csr", num_features=29,
        donate_batch=True,
    )
    csr_spec = BatchSpec(batch_size=16384, layout="csr", num_features=29)
    csr_runs = _timed_sgd_epochs(
        lambda: _feed(csr_spec), size_mb, csr_step, "csr", cparams, cvel
    )

    out = {
        "feed_dense_mbps": round(statistics.median(feed_runs[1:]), 1),
        "feed_dense_trials_mbps": feed_runs[1:],
        **prefetch_ab,
        "feed_stages": feed_stages,
        "sgd_e2e_mbps": round(statistics.median(sgd_runs[1:]), 1),
        "sgd_e2e_trials_mbps": sgd_runs[1:],
        "sgd_e2e_serial_mbps": round(statistics.median(serial_runs[1:]), 1),
        "sgd_e2e_serial_trials_mbps": serial_runs[1:],
        "sgd_e2e_pipelined_mbps": round(statistics.median(pipe_runs[1:]), 1),
        "sgd_e2e_pipelined_trials_mbps": pipe_runs[1:],
        "pipelined_stall_stages": _median_stall_stages(pipe_stats),
        "sgd_e2e_cached_mbps": round(statistics.median(cached_runs[1:]), 1),
        "sgd_e2e_cached_trials_mbps": cached_runs[1:],
        "sgd_csr_e2e_mbps": round(statistics.median(csr_runs[1:]), 1),
        "sgd_csr_e2e_trials_mbps": csr_runs[1:],
    }
    # Sharded sparse H2D accounting (one batch, host-side): per-device
    # entry bytes under the 8-shard partition vs the replicated layout.
    # Native-only (the sharded fill lives in pipeline.cc); its absence
    # must not discard the timing metrics above.
    try:
        parser = create_parser(path, 0, 1, nthread=nthread)
        try:
            if hasattr(parser, "read_batch_coo_sharded"):
                batch_rows, shards = 16384, 8
                sharded = parser.read_batch_coo_sharded(batch_rows, shards)
                out["csr_batch_nnz"] = sharded.num_nonzero
                out["csr_nnz_per_device_8shard"] = sharded.nnz_bucket
                # shipped per entry: indices + values (8 B); the row
                # mapping crosses H2D as per-shard CSR offsets (4 B/row),
                # not per-entry row_ids (device/feed._put_csr)
                rows_local = batch_rows // shards
                out["csr_h2d_bytes_per_device"] = (
                    sharded.nnz_bucket * 8 + (rows_local + 1) * 4
                )
                out["csr_h2d_bytes_per_device_replicated"] = (
                    sharded.num_nonzero * 8 + (batch_rows + 1) * 4
                )
        finally:
            parser.close()
    except Exception as err:  # keep the timing metrics measured above
        out["csr_shard_accounting_error"] = str(err)
    return out


def _remote_sweep(path: str) -> dict:
    """One loopback fake-S3 → parallel range-GET readahead → native push
    pipeline sweep → {probe_gbps, trials, score, conns} (the Criteo-class
    object-store ingest shape, hermetic). The in-process HTTP server shares
    the host CPUs, so every number here is a floor. Score = the better
    connection-count config's median."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    from fake_object_store import serve

    from dmlc_tpu.data.parsers import NativePipelineParser, create_parser
    from dmlc_tpu.io.filesystem import register_filesystem
    from dmlc_tpu.io.object_store import S3FileSystem

    server, store, base = serve()
    old_env = {k: os.environ.get(k) for k in
               ("S3_ENDPOINT", "AWS_ACCESS_KEY_ID", "AWS_SECRET_ACCESS_KEY",
                "DMLC_TPU_READAHEAD_CONNS")}
    probe = _host_probe()
    try:
        os.environ["S3_ENDPOINT"] = base
        os.environ.pop("AWS_ACCESS_KEY_ID", None)
        os.environ.pop("AWS_SECRET_ACCESS_KEY", None)
        register_filesystem("s3://", lambda uri: S3FileSystem())
        with open(path, "rb") as fh:
            store.objects[("bench", "higgs.svm")] = fh.read()
        size = os.path.getsize(path)
        nthread = 1 if (os.cpu_count() or 1) <= 2 else 2
        best = None  # (median, runs, conns)
        for conns in (1, 4):
            os.environ["DMLC_TPU_READAHEAD_CONNS"] = str(conns)
            runs = []
            for _ in range(2):
                t0 = time.time()
                parser = create_parser(
                    "s3://bench/higgs.svm", 0, 1, nthread=nthread
                )
                if not isinstance(parser, NativePipelineParser):
                    parser.close()
                    raise RuntimeError(
                        "native remote routing declined; got "
                        + type(parser).__name__
                    )
                rows = sum(len(b) for b in parser)
                dt = time.time() - t0
                parser.close()
                assert rows == ROWS, f"remote row count mismatch: {rows}"
                runs.append(round(size / (1 << 20) / dt, 1))
            med = statistics.median(runs)
            if best is None or med > best[0]:
                best = (med, runs, conns)
        return {"probe_gbps": probe, "trials": best[1],
                "score": best[0], "conns": best[2]}
    finally:
        server.shutdown()
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class _TenantFeed:
    """One tenant's DeviceFeed over a fresh two-job dispatcher fleet.

    Each construction is one epoch of the multi-tenant shape: job
    ``train`` drives the jitted SGD step through this feed while job
    ``aux`` — same source, its own ledger — is drained concurrently by a
    background thread. close() tears the whole fleet down, so the
    _timed_sgd_epochs protocol (fresh feed per epoch) measures fleet
    bring-up + contended serving, not a warm single-tenant pipe."""

    def __init__(self, path, spec, nworkers=2, nchunks=8):
        import threading

        from dmlc_tpu.data import (BlockService, DataDispatcher,
                                   RemoteBlockParser)
        from dmlc_tpu.device.feed import DeviceFeed

        self._disp = DataDispatcher()
        self._disp.add_job("train", path, nchunks=nchunks)
        self._disp.add_job("aux", path, nchunks=nchunks)
        self._workers = [
            BlockService(dispatcher=self._disp.address,
                         nthread=_bench_nthread())
            for _ in range(nworkers)
        ]
        self.aux_rows = 0

        def _drain_aux():
            try:
                aux = RemoteBlockParser(self._disp.address, dispatcher=True,
                                        job="aux")
                for block in aux:
                    self.aux_rows += len(block)
                aux.close()
            except Exception:  # the aux tenant must not fail the timing
                pass

        self._aux_thread = threading.Thread(target=_drain_aux, daemon=True)
        self._aux_thread.start()
        self._feed = DeviceFeed(
            RemoteBlockParser(self._disp.address, dispatcher=True,
                              job="train"),
            spec,
        )

    def __iter__(self):
        return iter(self._feed)

    def stats(self):
        return self._feed.stats()

    def close(self):
        self._feed.close()
        self._aux_thread.join(timeout=60)
        for svc in self._workers:
            svc.close()
        self._disp.close()


def _bench_multijob(path: str) -> dict:
    """Multi-tenant fleet tiers: ingest→SGD with a second tenant live on
    the same dispatcher (sgd_e2e_multijob_mbps), and the cross-job
    source-cache hit ratio — a fresh fleet serves the source to one job
    cold, then to a second job that should parse NOTHING
    (cache_cross_job_hit_ratio = 1.0 is the PR 12 acceptance bar)."""
    import jax.numpy as jnp

    from dmlc_tpu.data import (BlockService, DataDispatcher,
                               RemoteBlockParser, reset_source_cache,
                               source_cache)
    from dmlc_tpu.device.feed import BatchSpec
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )

    size_mb = os.path.getsize(path) / (1 << 20)
    # the shared cache must hold the whole parsed source or the warm
    # tenant re-parses evicted parts; budget ~4x text size, restored after
    old_cache_mb = os.environ.get("DMLC_TPU_DATA_CACHE_MB")
    os.environ["DMLC_TPU_DATA_CACHE_MB"] = str(
        max(256, int(size_mb * 4) + 64))
    reset_source_cache()
    try:
        spec = BatchSpec(batch_size=16384, layout="dense", num_features=29)
        params = init_linear_params(29)
        velocity = {"w": jnp.zeros_like(params["w"]),
                    "b": jnp.zeros_like(params["b"])}
        step = make_linear_train_step(None, learning_rate=0.1,
                                      layout="dense", donate_batch=True)
        runs = _timed_sgd_epochs(
            lambda: _TenantFeed(path, spec), size_mb, step, "dense",
            params, velocity,
        )

        # cold/warm cache pass on a fresh fleet: ONE worker so every part
        # leased for the warm job is cached where it was parsed. Both
        # ledgers are registered up front (a worker whose whole fleet
        # drains retires its stream), then drained one after the other.
        reset_source_cache()
        nchunks = 8
        with DataDispatcher() as disp:
            disp.add_job("cold", path, nchunks=nchunks)
            disp.add_job("warm", path, nchunks=nchunks)
            with BlockService(dispatcher=disp.address,
                              nthread=_bench_nthread()) as svc:
                cold = RemoteBlockParser(disp.address, dispatcher=True,
                                         job="cold")
                cold_rows = sum(len(b) for b in cold)
                cold.close()
                hits_before = source_cache().hits
                parsed_before = svc.chunks_parsed
                warm = RemoteBlockParser(disp.address, dispatcher=True,
                                         job="warm")
                warm_rows = sum(len(b) for b in warm)
                warm.close()
                hit_ratio = (source_cache().hits - hits_before) / nchunks
                warm_parsed = svc.chunks_parsed - parsed_before
        assert warm_rows == cold_rows, "tenants saw different row counts"
        return {
            "sgd_e2e_multijob_mbps": round(statistics.median(runs[1:]), 1),
            "sgd_e2e_multijob_trials_mbps": runs[1:],
            "cache_cross_job_hit_ratio": round(hit_ratio, 3),
            "cache_cross_job_warm_parses": warm_parsed,
        }
    finally:
        if old_cache_mb is None:
            os.environ.pop("DMLC_TPU_DATA_CACHE_MB", None)
        else:
            os.environ["DMLC_TPU_DATA_CACHE_MB"] = old_cache_mb
        reset_source_cache()


def _bench_snapshot(path: str) -> dict:
    """Preemption-proof snapshot overhead: the SAME ingest→SGD epoch
    armed with async job snapshots vs unarmed (ckpt_overhead_ratio —
    the ≤5% acceptance bar), plus the wall time a relaunched run pays
    to restore the committed snapshot (resume_restore_s). Both are
    sentry-gated lower-is-better."""
    import shutil
    import tempfile

    from dmlc_tpu.collective.checkpoint import JobSnapshot
    from dmlc_tpu.collective.snapshot import load_snapshot
    from dmlc_tpu.models.linear import LinearLearner

    def _fit_s(snapshot_uri=None):
        learner = LinearLearner(learning_rate=0.1)
        t0 = time.time()
        learner.fit_uri(path, batch_size=16384, epochs=1, num_features=29,
                        snapshot_uri=snapshot_uri)
        return time.time() - t0

    snap_dir = tempfile.mkdtemp(prefix="dmlc-bench-snap-")
    try:
        unarmed = [_fit_s() for _ in range(TRIALS + 1)][1:]
        armed = [
            _fit_s(snapshot_uri=os.path.join(snap_dir, f"t{trial}"))
            for trial in range(TRIALS + 1)
        ][1:]
        base_s = statistics.median(unarmed)
        armed_s = statistics.median(armed)
        snap = JobSnapshot(os.path.join(snap_dir, f"t{TRIALS}"))
        t0 = time.time()
        version, _state, _meta = load_snapshot(snap)
        restore_s = time.time() - t0
        return {
            "ckpt_overhead_ratio": round(
                max(0.0, armed_s / base_s - 1.0), 4),
            "resume_restore_s": round(restore_s, 4),
            "snapshot_restored_version": version,
            "snapshot_unarmed_trials_s": [round(v, 3) for v in unarmed],
            "snapshot_armed_trials_s": [round(v, 3) for v in armed],
        }
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)


# keys lifted verbatim from the full record into the compact stdout line:
# every tier median + device/collective status the verdict reads
_COMPACT_KEYS = (
    "recordio_ingest_mbps", "criteo_like_parse_mbps",
    "parse_only_mbps", "parse_only_libsvm_native_gbps",
    "parse_only_libsvm_vector_gbps", "parse_only_csv_native_gbps",
    "parse_only_csv_vector_gbps",
    "criteo_recordio_ingest_mbps", "shard_ingest_gbps", "bake_mbps",
    "remote_ingest_mbps",
    "feed_dense_mbps", "sgd_e2e_mbps", "sgd_e2e_serial_mbps",
    "sgd_e2e_pipelined_mbps", "sgd_e2e_cached_mbps",
    "sgd_csr_e2e_mbps", "recordio_sgd_mbps", "sgd_e2e_shard_mbps",
    "criteo_like_csr_sgd_mbps",
    "gbdt_fit_mrows_s",
    "sgd_e2e_multijob_mbps", "cache_cross_job_hit_ratio",
    "sgd_goodput_ratio", "sgd_mfu", "ckpt_overhead_ratio",
    "resume_restore_s",
    "platform", "device_kind", "device_count", "device_tiers",
    "device_feed_probe_gbps", "device_feed_probe_gbps_post",
    "device_tier_probes_gbps",
    "socket_tree_64k_gbps", "socket_ring_8m_gbps", "socket_world",
    "socket_note", "psum_single_device_gbps", "psum_step_ms",
    "psum_devices", "psum_platform", "psum_algo_gbps",
    "psum_ici_utilization", "spmd_psum_step_gbps", "spmd_step_ms",
    "spmd_devices", "spmd_platform", "ici_utilization",
    "bucket_fused_ms", "bucket_per_tensor_ms",
    "engine_allreduce_gbps", "engine_reduce_single_process_gbps",
    "headline_cfg_nthread", "headline_spread_mbps", "headline_sweep",
)


# sentry direction registry carried on every record (obs/sentry.py
# record_directions): extra keys the gate scores that no suffix rule
# covers
BENCH_DIRECTIONS = {
    "sgd_goodput_ratio": "higher",
    # snapshot tax and restore latency regress upward: gate them down
    "ckpt_overhead_ratio": "lower",
    "resume_restore_s": "lower",
    # model FLOP utilization of the whole-run goodput window
    # (obs/xla_cost.py analytics over the peak-FLOPs ceiling)
    "sgd_mfu": "higher",
}


def _compact_summary(headline: float, extra: dict) -> dict:
    """The single stdout line: bounded (≤2 KB) so the driver's tail capture
    can never truncate it mid-JSON."""
    compact = {}
    for key in _COMPACT_KEYS:
        if key in extra:
            compact[key] = extra[key]
    if isinstance(extra.get("parity"), dict):
        compact["parity"] = extra["parity"]
    if isinstance(extra.get("sentry"), dict):
        compact["sentry_regressions"] = len(
            extra["sentry"].get("regressions", []))
    for key, val in extra.items():
        if key.endswith("_error"):
            compact[key] = str(val)[:120]
    if "detail_path" in extra:
        compact["detail_path"] = extra["detail_path"]
    line = {
        "metric": "higgs_libsvm_ingest",
        "value": round(headline, 1),
        "unit": "MB/s",
        "vs_baseline": round(headline / REFERENCE_MBPS, 3),
        "extra": compact,
    }
    # hard bound: shed payloads in increasing order of verdict value until
    # the line fits — first the bulky optional, then error texts, then
    # non-tier context keys; the loop cannot exit oversize while anything
    # sheddable remains (the bare metric/value core is ~120 bytes)
    def _oversize():
        return len(json.dumps(line)) > 2048

    if _oversize():
        compact.pop("parity", None)
    if _oversize():
        for key in [k for k in compact if k.endswith("_error")]:
            compact.pop(key, None)
            if not _oversize():
                break
    if _oversize():
        for key in [k for k in compact
                    if k.startswith(("socket_", "headline_", "psum_",
                                     "bucket_", "engine_", "device_feed_",
                                     "device_tier_"))]:
            compact.pop(key, None)
            if not _oversize():
                break
    return line


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    t_run0 = time.time()
    path = _ensure_data()

    _one_pass(path, 1)  # warmup: native build, page cache, allocators

    # host tiers all follow the headline's bimodal-host discipline: three
    # sweeps spread across the run, probe next to each, best sweep scores
    host_tiers = {
        "recordio_ingest": lambda: _recordio_sweep(path),
        "criteo_like_parse": _criteo_parse_sweep,
        "parse_only": _parse_only_sweep,
        "criteo_recordio_ingest": _criteo_recordio_sweep,
        "shard_ingest": lambda: _shard_sweep(path),
        "remote_ingest": lambda: _remote_sweep(path),
    }
    tier_sweeps = {name: [] for name in host_tiers}

    def run_host_tier_sweeps():
        for name, fn in host_tiers.items():
            try:
                tier_sweeps[name].append(fn())
            except Exception as err:  # the headline must still print
                tier_sweeps[name].append({"error": str(err)})

    sweeps = [_headline_sweep(path)]
    run_host_tier_sweeps()  # tier sweep 1

    extra = {
        "criteo_like_file_mb": round(
            os.path.getsize(_ensure_criteo_like()) / (1 << 20), 1),
        "criteo_like_feature_space": CRITEO_DIM,
        "recordio_file_mb": round(
            os.path.getsize(_ensure_recordio(path)) / (1 << 20), 1),
        "criteo_recordio_file_mb": round(
            os.path.getsize(_ensure_criteo_recordio()) / (1 << 20), 1),
        "shard_file_mb": round(
            os.path.getsize(_ensure_shard(path)) / (1 << 20), 1),
    }
    # this process holds the chip from here on: the record names the
    # device every number below ran on
    import jax

    dev = jax.devices()[0]
    extra["platform"] = dev.platform
    extra["device_kind"] = dev.device_kind
    extra["device_count"] = len(jax.devices())
    on_chip = dev.platform == "tpu"
    # device tiers that raised (TPU only): the line still prints, then
    # the process exits non-zero
    device_failures = []

    def _run_device_tiers():
        # host-speed context bracketing the device tiers: the tiers share
        # this host's cores with jax's runtime threads, so each carries
        # the host probe read just before it ran — a slow tier can be
        # attributed to a slow window instead of a regression
        extra["device_feed_probe_gbps"] = _host_probe()
        tier_probes = {}
        for tier_fn, err_key in (
            (lambda: _bench_device_feed(path), "device_feed_error"),
            (lambda: _bench_recordio_sgd(path), "recordio_sgd_error"),
            (lambda: _bench_shard_sgd(path), "shard_sgd_error"),
            (_bench_criteo_sgd, "criteo_sgd_error"),
            (lambda: _bench_gbdt(path), "gbdt_error"),
            (lambda: _bench_multijob(path), "multijob_error"),
            (lambda: _bench_snapshot(path), "snapshot_error"),
        ):
            tier_probes[err_key.replace("_error", "_probe_gbps")] = (
                _host_probe()
            )
            try:
                extra.update(tier_fn())
            except Exception as err:  # noqa: BLE001 - print the line, then fail
                extra[err_key] = str(err)
                device_failures.append(err_key)
        extra["device_tier_probes_gbps"] = tier_probes
        try:
            # chip-vs-CPU-world parity artifact (north star: bit-exact
            # loss parity vs the CPU/MPI path; tools/parity.py documents
            # the reduction-order construction and what cross-backend
            # tolerance means). Its socket-world workers pin themselves
            # to the cpu backend — they never touch the chip this
            # process holds.
            from dmlc_tpu.tools.parity import run_parity

            parity = run_parity(world=2, steps=3)
            extra["parity"] = {
                k: parity[k]
                for k in ("single_backend", "bitexact", "max_grad_ulp",
                          "max_loss_rel", "max_param_abs_diff",
                          "criterion", "pass")
            }
        except Exception as err:  # noqa: BLE001
            extra["parity_error"] = str(err)
            device_failures.append("parity_error")
        extra["device_feed_probe_gbps_post"] = _host_probe()

    if on_chip:
        _run_device_tiers()
    else:
        extra["device_tiers"] = "not measured"

    sweeps.append(_headline_sweep(path))
    run_host_tier_sweeps()  # tier sweep 2

    from bench_collective import DEVICE_TIER_ERRORS, collective_metrics

    extra.update(collective_metrics(device_tiers=on_chip))
    device_failures.extend(k for k in DEVICE_TIER_ERRORS if k in extra)

    sweeps.append(_headline_sweep(path))
    run_host_tier_sweeps()  # tier sweep 3

    for name, tier in tier_sweeps.items():
        value, sw_extra = _combine_tier(tier)
        if value is None:
            extra[name + "_error"] = "; ".join(
                sw.get("error", "no trials") for sw in tier)
        else:
            extra[name + "_mbps"] = round(value, 1)
            extra[name + "_sweeps"] = sw_extra
    # per-(format, backend) parse-stage medians: best window across the
    # three parse_only sweeps, lifted to flat *_gbps keys so the sentry
    # gates each backend's parse throughput independently of the e2e tiers
    fmt_best: dict = {}
    for sw in tier_sweeps.get("parse_only", ()):
        for key, v in (sw.get("formats") or {}).items():
            if isinstance(v, (int, float)):
                fmt_best[key] = max(fmt_best.get(key, 0.0), float(v))
    for key, v in fmt_best.items():
        extra["parse_only_" + key] = v
    # the shard tier's headline is GB/s (the ISSUE's acceptance unit) and
    # the one-off bake cost rides inside its sweeps — lift both to flat
    # keys so the sentry gates them like any other throughput
    if "shard_ingest_mbps" in extra:
        extra["shard_ingest_gbps"] = round(
            extra.pop("shard_ingest_mbps") / 1024, 2)
    bake_best = [sw.get("bake_mbps") for sw in tier_sweeps.get(
        "shard_ingest", ()) if isinstance(sw.get("bake_mbps"), (int, float))]
    if bake_best:
        extra["bake_mbps"] = max(bake_best)
    if "remote_ingest_mbps" in extra:
        # The loopback harness runs BOTH http ends and the parser on this
        # host's core(s): at 1 core the serial budget is parse + server
        # slice/send + client recv, so ~55-70% of the local number IS the
        # all-on-one-core ceiling, not a product limit — the product path
        # (readahead fetch threads + native push parse) overlaps these on
        # independent cores/NICs on a real host.
        extra["remote_ingest_note"] = (
            "loopback fake-S3 shares this host's core(s) with the parser; "
            "serial floor, not the product ceiling"
        )

    headline, headline_extra = _combine_headline(sweeps)
    extra = {**headline_extra, **extra}

    try:
        # whole-run obs registry dump (per-stage histograms included);
        # detail-file only — too big for the compact stdout summary
        from dmlc_tpu import obs

        extra["metrics"] = obs.registry().snapshot()
    except Exception as err:
        extra["metrics_error"] = str(err)[:120]

    try:
        # device-side picture (compile counts, peak HBM, H2D MB/s) —
        # placed before the sentry pass so compiles.<fn>/hbm.peak_bytes/
        # h2d_mbps gate against history like any other metric
        from dmlc_tpu.obs import device_telemetry

        extra["device_telemetry"] = device_telemetry.detail_section()
    except Exception as err:
        extra["device_telemetry_error"] = str(err)[:120]

    try:
        # compiled-program cost records (obs/xla_cost.py): per-jit-site
        # flops / bytes accessed / peak memory / in-graph collective
        # bytes, cached at compile time by the instrumented_jit hook —
        # the SPMD psum step's dmlc_xla_collective_bytes lands here
        from dmlc_tpu.obs import xla_cost

        extra["xla"] = xla_cost.detail_section()
    except Exception as err:
        extra["xla_error"] = str(err)[:120]

    # goodput/MFU describe the device tiers' fit loops — with no chip
    # there is nothing to attribute and the keys stay absent
    if on_chip:
        try:
            # whole-run goodput attribution (obs/goodput.py): the run's
            # registry totals ARE the delta-from-zero, the wall is this
            # process's elapsed time, and the ceilings are the run's OWN
            # measurements — parse_only tier for parse, the host H2D probe
            # for h2d — so the binding verdict rides the artifact and
            # sgd_goodput_ratio gates against history via the direction map
            from dmlc_tpu import obs
            from dmlc_tpu.obs import goodput as _goodput
            from dmlc_tpu.obs import xla_cost as _xla_cost

            flat = obs.registry().flat_values()
            # the device's published peaks (knob overrides win; an unknown
            # kind has none and the record then carries no sgd_mfu)
            ceilings = _xla_cost.device_peaks(extra["device_kind"])
            probe = extra.get("device_feed_probe_gbps")
            if isinstance(probe, (int, float)) and probe > 0:
                ceilings["h2d_mbps"] = round(float(probe) * 1000.0, 1)
            parse_peak = max(
                (float(v) for k, v in extra.items()
                 if k.startswith("parse_only_") and k.endswith("_gbps")
                 and isinstance(v, (int, float))),
                default=0.0,
            )
            if parse_peak > 0:
                ceilings["parse_mbps"] = round(parse_peak * 1000.0, 1)
            att = _goodput.attribute(
                flat, max(time.time() - t_run0, 1e-9),
                ceilings=ceilings, current=flat,
            )
            extra["goodput"] = att
            extra["sgd_goodput_ratio"] = att["goodput"]["ratio"]
            if att.get("mfu") is not None:
                # model FLOP utilization rides the record only when the
                # run compiled an analyzable hot step — sentry gates it
                # higher-is-better via BENCH_DIRECTIONS
                extra["sgd_mfu"] = att["mfu"]
        except Exception as err:
            extra["goodput_error"] = str(err)[:120]

    try:
        # advisory perf-sentry pass (report-only — the blocking gate is
        # `dmlc_tpu.tools bench-gate` in scripts/ci_checks.sh): gate this
        # run against the committed round history so the regression
        # verdict rides the artifact itself
        import glob as _glob

        from dmlc_tpu.obs import sentry

        hist = sentry.load_records(sorted(_glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json"))))
        if hist:
            fresh_rec = {"metric": "higgs_libsvm_ingest",
                         "value": round(headline, 1), "extra": extra,
                         "directions": dict(BENCH_DIRECTIONS)}
            regs = sentry.gate(
                sentry.record_values(fresh_rec),
                sentry.metric_series(hist),
                directions=sentry.record_directions(hist + [fresh_rec]),
            )
            extra["sentry"] = {
                "history_records": len(hist),
                "regressions": [
                    {k: r[k] for k in ("metric", "value", "baseline",
                                       "severity")} for r in regs[:5]
                ],
            }
    except Exception as err:
        extra["sentry_error"] = str(err)[:120]

    # full record to the detail file; COMPACT summary (≤2 KB) to stdout
    detail_path = os.environ.get(
        "DMLC_TPU_BENCH_DETAIL",
        os.path.join(CACHE_DIR, "bench_detail.json"),
    )
    detail_line = json.dumps(
        {
            "metric": "higgs_libsvm_ingest",
            "value": round(headline, 1),
            "unit": "MB/s",
            "vs_baseline": round(headline / REFERENCE_MBPS, 3),
            "extra": extra,
            # per-record sentry direction registry (obs/sentry.py):
            # names extra keys the gate scores beyond the suffix rules
            "directions": dict(BENCH_DIRECTIONS),
        }
    )
    try:
        os.makedirs(os.path.dirname(detail_path) or ".", exist_ok=True)
        with open(detail_path, "w") as fh:
            fh.write(detail_line + "\n")
        extra["detail_path"] = detail_path
    except OSError as err:  # detail is best-effort; the summary must print
        extra["detail_write_error"] = str(err)[:120]

    print(json.dumps(_compact_summary(headline, extra)))
    if device_failures:
        sys.exit("bench: device tier(s) failed on %s: %s" % (
            extra["device_kind"], ", ".join(device_failures)))


if __name__ == "__main__":
    main()
