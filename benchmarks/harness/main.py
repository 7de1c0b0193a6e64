"""One run of one cell: set-up, check, window, result line.

Order of set-up, each part timed from the process's own start:
python + ``import jax``; the one call that opens the PJRT client
(``jax.devices()``, alone, before any module of the program is imported);
the native library (built by the first run of a checkout, loaded
afterwards); the program's imports; data from the seed; parameters on the
device; the correctness check, which is also the warm-up of the step.

``setup_s`` = process start to the opening of the window, less
``backend_open_s``: no file of this repository takes part in opening the
chip, and it wanders by seconds from run to run (PERF.md section 2).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from harness import spec

RUN_DIR = os.path.join(spec.ROOT, ".bench_run")  # gitignored scratch
TRACE_START_SHARE = 0.3  # the profiler starts this far into the window
TRACE_SECONDS = 3.0  # and runs this long (at most 40% of the window)
PRINTED_COUNTERS = (  # on the detail line, as the window's deltas
    "dmlc_feed_restarts_total", "dmlc_feed_prewound_restarts_total",
    "dmlc_fit_touched_rows_total", "dmlc_fit_entries_total")


def _process_age_s() -> float:
    """Seconds since the kernel started this process: interpreter start-up
    and every import count as set-up."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


def _say(msg: str) -> None:
    print("[bench] " + msg, file=sys.stderr, flush=True)


class _Marks:
    """Seconds since process start at named points of set-up."""

    def __init__(self):
        try:
            age = _process_age_s()
        except (OSError, ValueError, IndexError):
            age = -1.0
        # a kernel that reports no usable start time: count from here (the
        # interpreter's own start-up, some 20 ms, is then left out)
        self.from_kernel = 0.0 <= age < 300.0
        self._base = (age if self.from_kernel else 0.0) - time.perf_counter()
        self.at = {}

    def now(self) -> float:
        return self._base + time.perf_counter()

    def mark(self, name: str) -> float:
        self.at[name] = self.now()
        return self.at[name]

    def parts(self) -> dict:
        """Seconds each part of set-up took, in order."""
        out, last = {}, 0.0
        for name, at in self.at.items():
            out[name + "_s"] = at - last
            last = at
        return out


class _CompileCount:
    """jax's own compile and cache events, for every program (a program
    new to the process counts whether it was compiled or read back)."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class _Profiler:
    """jax's profiler for a few seconds in the middle of the window,
    bounded by a ``bench.trace`` annotation of its own."""

    def __init__(self, trace_dir: str, seconds: float):
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        self.trace_dir = trace_dir
        self.state = "off"
        self._start_at = time.perf_counter() + TRACE_START_SHARE * seconds
        self._length = min(TRACE_SECONDS, 0.4 * seconds)
        self._span = None

    def on_batch(self, now: float) -> None:
        """Called by the window before each batch goes to the step."""
        import jax

        if self.state == "off" and now >= self._start_at:
            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("bench.trace")
            self._span.__enter__()
            self._stop_at = time.perf_counter() + self._length
            self.state = "on"
        elif self.state == "on" and now >= self._stop_at:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def _ensure_native() -> float:
    """Build cpp/libdmlc_tpu.so if this checkout has none (its first run);
    never rebuild one that is there. Returns the seconds it took."""
    lib = os.path.join(spec.ROOT, "cpp", "libdmlc_tpu.so")
    if os.path.exists(lib):
        return 0.0
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", os.path.join(spec.ROOT, "cpp")],
                   check=True, stdout=subprocess.DEVNULL)
    return time.perf_counter() - t0


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--rehearse", action="store_true",
        help="run the control flow off the TPU at the configuration's "
             "'rehearse' size; prints no metric values")
    return p.parse_args(argv)


def _build_feed(cell, data_path, mesh):
    """Parser and feed as ``fit_uri`` builds them."""
    from dmlc_tpu import collective
    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed

    shards = 1 if mesh is None else mesh.size
    cfg = cell.cfg
    return DeviceFeed(
        create_parser(data_path, collective.rank(), collective.world_size()),
        BatchSpec(batch_size=int(cfg["batch_rows_per_chip"]) * shards,
                  layout=cfg["layout"],
                  num_features=int(cfg["num_features"])),
        mesh=mesh,
    )


def _clear_rows(cell_dir):
    """Remove a run's data files (hundreds of MB; the trace stays)."""
    for old in os.listdir(cell_dir):
        if old.startswith("rows."):
            os.remove(os.path.join(cell_dir, old))


def _write_data(cell, data):
    """The rows as the traffic mix says: LIBSVM text, or that text baked
    to .dtsh shards (the text is then removed)."""
    from harness import textgen

    cell_dir = os.path.join(RUN_DIR, cell.name)
    os.makedirs(cell_dir, exist_ok=True)
    _clear_rows(cell_dir)
    text = os.path.join(cell_dir, "rows.libsvm")
    nbytes = textgen.write_libsvm(
        text, data["label"], data["ids"], data["value_text"],
        data["pool_index"])
    fmt = cell.traffic["input"]
    if fmt == "libsvm":
        return text, nbytes
    if fmt == "dtsh":
        from dmlc_tpu.tools.bake import bake_dataset

        shard = os.path.join(cell_dir, "rows.dtsh")
        bake_dataset(text, shard, data_format="libsvm", force=True)
        os.remove(text)
        return shard, os.path.getsize(shard)
    raise SystemExit("traffic input %r is not known" % fmt)


def _memory_peak(devices):
    """Peak bytes on the fullest chip: live arrays at their peak plus the
    runtime's reservation for the programs' temporaries at its peak (on a
    TPU a step's scratch is not among the bytes 'in use'; see PERF.md)."""
    best, parts = 0, {}
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if in_use + reserved >= best:
            best = in_use + reserved
            parts = {"peak_bytes_in_use": in_use,
                     "peak_bytes_reserved": reserved,
                     "bytes_limit": int(stats.get("bytes_limit", 0))}
    return best, parts


def run(argv=None) -> int:
    marks = _Marks()
    args = _parse(argv)
    cell = spec.Cell(args.workload, rehearse=args.rehearse)
    if args.trace:
        # the program's own bridge: its spans enter the profiler's trace
        os.environ["DMLC_TPU_TRACE_JAX"] = "1"

    import jax

    marks.mark("import_jax")
    t0 = time.perf_counter()
    devices = jax.devices()
    backend_open_s = time.perf_counter() - t0
    marks.mark("backend_open")
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        _say("no TPU (jax reports %r): this benchmark measures nothing "
             "off the chip" % platform)
        return 2
    if len(devices) < cell.chips:
        _say("cell %s needs %d chips, jax reports %d"
             % (cell.name, cell.chips, len(devices)))
        return 2
    devices = devices[: cell.chips]
    kind = devices[0].device_kind
    peaks = None if args.rehearse else spec.peaks(kind)
    # every program goes to the persistent cache, not only the slow ones,
    # so that a later run of this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = _CompileCount()

    make_s = _ensure_native()
    marks.mark("native_built")
    sys.path.insert(0, spec.ROOT)
    from dmlc_tpu import native, obs
    from dmlc_tpu.obs import trace as obs_trace
    from dmlc_tpu.parallel import data_parallel_mesh
    from dmlc_tpu.utils.jax_compat import place_compile_cache

    from harness import check, window, xplane

    if not native.available():
        _say("the native library did not load")
        return 2
    cache_dir = place_compile_cache()
    marks.mark("program_imports")

    data = cell.config.rows(cell.cfg, args.seed)
    data_path, data_bytes = _write_data(cell, data)
    marks.mark("data")

    mesh = data_parallel_mesh(devices) if cell.traffic.get("mesh") else None
    model = cell.config.learner(cell.cfg, mesh)
    cell.config.init_params(cell.cfg, args.seed, model, mesh)
    jax.block_until_ready(model.params)
    marks.mark("params")

    # the program's spans are live while a listener is registered; the
    # traced run keeps them for the readers, the plain run records none
    spans = []
    keep_span = spans.append
    if args.trace:
        obs_trace.add_listener(keep_span)

    feed = _build_feed(cell, data_path, mesh)
    facts = check.run(cell, model, feed, data, int(cell.cfg["check"]["steps"]))
    del data
    feed.before_first()
    marks.mark("check_and_warmup")

    # ---- the window ----------------------------------------------------
    profiler = _Profiler(os.path.join(RUN_DIR, cell.name, "trace"),
                         args.seconds) if args.trace else None
    before = compiles.compiles + compiles.hits
    setup_end = marks.mark("window_open")
    raw = window.measure(model, feed, args.seconds, obs, obs.registry(),
                         profiler.on_batch if profiler else None)
    if profiler:
        profiler.stop()  # if the window ended first
    feed.close()
    obs_trace.remove_listener(keep_span)
    _clear_rows(os.path.dirname(data_path))

    # ---- facts for the readers -----------------------------------------
    run_facts = dict(raw)
    run_facts.update(
        cell=cell.name, cfg=cell.cfg, chips=cell.chips, peaks=peaks,
        config=cell.config, setup_s=setup_end - backend_open_s,
        backend_open_s=backend_open_s, spans=spans,
        compiles_in_window=compiles.compiles + compiles.hits - before,
        batch_rows=feed.spec.batch_size, trace=None)
    if profiler and profiler.state == "done":
        try:
            run_facts["trace"] = xplane.reduce(
                xplane.find_trace(profiler.trace_dir),
                span_names=sorted({s["name"] for s in spans}),
                window="bench.trace")
        except ValueError:
            if not args.rehearse:  # off the TPU a trace has no device plane
                raise

    metrics, notes = {}, {}
    for entry, reader in cell.per_layer if args.trace else cell.end_to_end:
        value = reader.read(run_facts)
        if value is not None:
            metrics[entry["name"]] = {
                "value": float(value), "unit": entry["unit"]}
            if hasattr(reader, "note"):  # what a reader adds to the detail
                notes[entry["name"]] = reader.note(run_facts)

    peak, peak_parts = _memory_peak(devices)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    detail = {
        "cell": cell.name, "seed": args.seed, "trace": args.trace,
        "backend_open_s": backend_open_s, "setup_parts": marks.parts(),
        "start_from_kernel": marks.from_kernel,
        "first_run_make_s": make_s, "window_s": raw["window_s"],
        "rows": raw["rows"], "batches": raw["batches"],
        "full_passes": len(raw["pass_rows"]), "data_bytes": data_bytes,
        "check": facts, "compiles_in_window": run_facts["compiles_in_window"],
        "compile_cache": {"dir": cache_dir, "hits": compiles.hits,
                          "misses": compiles.misses},
        "memory": peak_parts, "pipeline": raw["pipeline"], "notes": notes,
        # the window's deltas of program counters no metric reads
        "counters": {name: window.family_sum(raw["counters"], name)
                     for name in PRINTED_COUNTERS},
    }
    # every full pass must have delivered the whole file: a shortfall is
    # rows lost to parse errors or dropped batches
    rows_in_file = int(cell.cfg["rows"])
    failed = sum(max(0, rows_in_file - n) for n in raw["pass_rows"])
    if run_facts["compiles_in_window"]:
        _say("%d programs compiled inside the window"
             % run_facts["compiles_in_window"])
    # every number `correct` rests on, beside its limit
    compared = dict(
        facts["compared"], rows_failed=[failed, 0],
        compiles_in_window=[run_facts["compiles_in_window"], 0])
    correct = all(value <= limit for value, limit in compared.values())
    result = {"correct": correct, "attempted": raw["rows"] + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if run_facts["trace"] is not None:
        tr = run_facts["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        detail["trace"] = {k: tr[k] for k in (
            "programs", "step_program", "collective_exposed_s", "program_s")}
    print("[bench] detail " + json.dumps(detail), flush=True)
    if args.rehearse:  # no metric value leaves a run off the chip
        result = dict(result, rehearsal=True, metric_names=sorted(metrics))
        del result["metrics"]
    result["compared"] = compared  # last on the line
    print(json.dumps(result), flush=True)
    for name, (value, limit) in compared.items():
        _say("compared %s %r limit %r" % (name, value, limit))
    return 0 if correct else 1
