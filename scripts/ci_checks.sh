#!/usr/bin/env bash
# Repo consistency checks, one entry point: metric-name lint, faultpoint/
# knob lint, and the perf-sentry self-check. Run from anywhere; wired
# into the tier-1 suite by tests/test_sentry.py so it cannot rot.
set -euo pipefail
cd "$(dirname "$0")/.."

python scripts/check_metric_names.py
python scripts/check_faultpoints.py
python scripts/check_partition_rules.py
python -m dmlc_tpu.tools bench-gate --smoke

# obs-top --once smoke against a local StatusServer fixture: exercises
# the /metrics + /workers endpoint contract and the CLI's table path
# end to end (device telemetry metric names included).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import json, sys, time

from dmlc_tpu.obs import plane
from dmlc_tpu.obs.metrics import Registry
from dmlc_tpu.tools import obs_top

reg = Registry()
reg.counter("dmlc_xla_compiles_total", "", fn="linear.step").inc(2)
reg.counter("dmlc_feed_h2d_bytes_total", "", feed="f0").inc(1 << 20)
reg.histogram("dmlc_feed_h2d_mbps", "", feed="f0").observe(512.0)
reg.gauge("dmlc_device_live_bytes", "", device="cpu:0").set(1 << 22)
reg.histogram("dmlc_feed_consume_ns", "", feed="f0").observe(2e6)

sp = plane.StatusPlane(num_workers=1)
blob, _ = plane.build_payload(rank=0, epoch=1, reg=reg)
sp.note_live(0, time.time(), "epoch=1")
sp.note_payload(0, json.loads(blob), time.time_ns())
srv = plane.StatusServer(sp, port=0)
srv.start()
try:
    rc = obs_top.main(["--once", "--status", "127.0.0.1:%d" % srv.port])
finally:
    srv.close()
if rc != 0:
    sys.exit("ci_checks: obs-top --once smoke failed (rc=%d)" % rc)
print("ci_checks: obs-top smoke OK")
EOF

# dispatcher-failover smoke: a 2-worker data fleet loses one worker to
# an injected crash mid-epoch; the lease table must still drain every
# chunk exactly once (requeue >= 1 proves the reassignment path ran).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import sys, tempfile, os

from dmlc_tpu import resilience
from dmlc_tpu.data import BlockService, DataDispatcher, RemoteBlockParser

fd, path = tempfile.mkstemp(suffix=".svm")
with os.fdopen(fd, "w") as fh:
    for i in range(20):
        fh.write("%d 1:%d\n" % (i % 2, i))
try:
    resilience.reset()
    resilience.configure("service.worker_crash:nth=1")
    with DataDispatcher(path, nchunks=4, lease_s=1.0,
                        dead_after_s=0.75) as disp:
        workers = [BlockService(dispatcher=disp.address, nthread=1)
                   for _ in range(2)]
        try:
            p = RemoteBlockParser(disp.address, dispatcher=True)
            rows = sum(len(b) for b in p)
            p.close()
            ok = disp.join(timeout=30)
            snap = disp.snapshot()
        finally:
            for svc in workers:
                svc.close()
    if not ok or rows != 20:
        sys.exit("ci_checks: dispatcher smoke lost rows (%d/20, ok=%s)"
                 % (rows, ok))
    if snap["chunks"]["acked"] != snap["chunks"]["total"]:
        sys.exit("ci_checks: lease table not drained: %r" % (snap,))
    if snap["requeued"] < 1:
        sys.exit("ci_checks: the injected crash never forced a requeue")
finally:
    resilience.reset()
    os.unlink(path)
print("ci_checks: dispatcher failover smoke OK")
EOF

# two-job shared-cache smoke: tenants A and B read the SAME source over
# one fleet; job B must be served entirely from the shared source cache
# (zero chunk parses) with bit-identical rows — the PR 12 acceptance bar.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import sys, tempfile, os

from dmlc_tpu import resilience
from dmlc_tpu.data import (BlockService, DataDispatcher, RemoteBlockParser,
                           reset_source_cache, source_cache)

fd, path = tempfile.mkstemp(suffix=".svm")
with os.fdopen(fd, "w") as fh:
    for i in range(20):
        fh.write("%d 1:%d\n" % (i % 2, i))
try:
    resilience.reset()
    reset_source_cache()
    def drain(job):
        p = RemoteBlockParser(disp.address, dispatcher=True, job=job)
        sig = sorted((b.label.tobytes(), b.value.tobytes()) for b in p)
        p.close()
        return sig
    with DataDispatcher() as disp:
        disp.add_job("a", path, nchunks=4)
        disp.add_job("b", path, nchunks=4)
        with BlockService(dispatcher=disp.address, nthread=1) as svc:
            sig_a = drain("a")
            parsed_a = svc.chunks_parsed
            sig_b = drain("b")
            parsed_b = svc.chunks_parsed - parsed_a
            hits = source_cache().hits
        ok = disp.join(timeout=30, job="a") and disp.join(timeout=30,
                                                          job="b")
    if not ok:
        sys.exit("ci_checks: two-job smoke never drained both ledgers")
    if parsed_a != 4:
        sys.exit("ci_checks: job A parsed %d chunks, wanted 4" % parsed_a)
    if parsed_b != 0:
        sys.exit("ci_checks: job B re-parsed %d chunks; the shared cache "
                 "missed" % parsed_b)
    if hits < 4:
        sys.exit("ci_checks: cross-job hit count %d < 4" % hits)
    if sig_a != sig_b:
        sys.exit("ci_checks: tenants saw different bytes for one source")
finally:
    resilience.reset()
    reset_source_cache()
    os.unlink(path)
print("ci_checks: two-job shared-cache smoke OK (job B parsed 0 chunks)")
EOF

# parse-parity smoke: the scalar oracle, the numpy vector path, and (when
# loaded) the native core must produce byte-identical RowBlocks over a
# canned corpus of grammar corner cases. A digest mismatch here means the
# vectorized hot path and the reference parser have diverged.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import hashlib, sys

import numpy as np

from dmlc_tpu.data import vparse
from dmlc_tpu.data.row_block import RowBlockContainer

CORPUS = (
    b"1 1:1.5 3:2\n0 2:4\n",
    b"1:0.5 4:1e-3 7:2\n\n-1 12:3.25\n",          # blank line mid-chunk
    b"0.5:2.5 1:1 2:2\n1 qid:7 3:4\n",           # weighted head + qid
    b"1 1:1\r\n0 2:2\r\n",                        # CRLF
    b"1 5:1e308 6:5e-324 7:-0.0\n",              # huge/denormal/signed zero
    b"0 1048576:0.125 2097151:9\n",              # long feature ids
    b"1 1:1\n0 2:2",                              # no trailing newline
)

def digest(parse):
    h = hashlib.sha256()
    for chunk in CORPUS:
        out = RowBlockContainer()
        parse(chunk, out)
        blk = out.to_block()
        for arr in (blk.offset, blk.index, blk.label, blk.value,
                    blk.weight, blk.qid):
            h.update(b"|" if arr is None else np.ascontiguousarray(
                arr).tobytes())
    return h.hexdigest()

scalar = digest(vparse.parse_libsvm_scalar)
vector = digest(vparse.parse_libsvm_vector)
if scalar != vector:
    sys.exit("ci_checks: parse parity FAILED (scalar %s != vector %s)"
             % (scalar[:12], vector[:12]))

from dmlc_tpu import native
if native.available():
    from dmlc_tpu.data.parsers import _native_libsvm

    def native_parse(chunk, out):
        got = _native_libsvm(chunk)
        if got is None:
            sys.exit("ci_checks: native core refused a corpus chunk")
        blk = got.to_block()
        out.push_arrays(
            blk.label, np.diff(blk.offset), blk.index,
            value=blk.value, weight=blk.weight, qid=blk.qid)

    nat = digest(native_parse)
    if nat != scalar:
        sys.exit("ci_checks: parse parity FAILED (native %s != scalar %s)"
                 % (nat[:12], scalar[:12]))
    print("ci_checks: parse-parity smoke OK (scalar == vector == native)")
else:
    print("ci_checks: parse-parity smoke OK (scalar == vector; no native)")
EOF

# Pallas sparse-step parity: the COO segment-sum kernel (interpret mode,
# passed explicitly) vs XLA's scatter spmv on exactly-representable f32
# data — sums are integers, so ANY reduction order must produce
# identical bits.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import sys

import numpy as np

import jax.numpy as jnp

from dmlc_tpu.ops.spmv import spmv, spmv_pallas

rng = np.random.RandomState(11)
entries, rows, nfeat = 1024, 192, 64
values = rng.randint(1, 5, entries).astype(np.float32)
indices = rng.randint(0, nfeat, entries).astype(np.int32)
rid = np.sort(rng.randint(0, rows, entries)).astype(np.int32)
vec = rng.randint(-4, 5, nfeat).astype(np.float32)
ref = spmv(jnp.asarray(values), jnp.asarray(indices), jnp.asarray(rid),
           jnp.asarray(vec), rows)
got = spmv_pallas(jnp.asarray(values), jnp.asarray(indices),
                  jnp.asarray(rid), jnp.asarray(vec), rows,
                  interpret=True)
if not np.array_equal(np.asarray(ref), np.asarray(got)):
    sys.exit("ci_checks: pallas spmv parity FAILED (max delta %g)"
             % float(np.abs(np.asarray(ref) - np.asarray(got)).max()))
print("ci_checks: pallas spmv parity OK (bit-identical vs XLA scatter)")
EOF

# SPMD collective smoke: the same short LibSVM fit run two ways — a
# single-process 2-virtual-device mesh with DMLC_TPU_COLLECTIVE=device
# (gradient allreduce as the in-graph bucketed psum) and a 2-process
# socket-engine world on the hostsync fallback (fused-buffer
# collective.allreduce). Loss history and final params must be
# BIT-identical, and the SPMD run must move zero collective D2H bytes.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=2" \
DMLC_TPU_COLLECTIVE=device python - <<'EOF'
import json, os, shutil, subprocess, sys, tempfile

import numpy as np

NF, ROWS, EPOCHS = 8, 64, 3
HYPER = dict(objective="logistic", learning_rate=0.1, num_features=NF)

# the full file for the mesh run plus a pre-split half per socket
# worker: rank r must read EXACTLY the rows the mesh places on device r
# (InputSplit's newline-seek hands a boundary row to part 0, which
# would skew step counts and partial-sum row sets)
workdir = tempfile.mkdtemp()
data = os.path.join(workdir, "toy.svm")
halves = [os.path.join(workdir, "toy.%d.svm" % r) for r in range(2)]
rows = []
for i in range(ROWS):
    feats = " ".join(
        "%d:%d" % (j + 1, (i * 7 + j * 3) % 10) for j in range(NF))
    rows.append("%d %s\n" % (i % 2, feats))
open(data, "w").write("".join(rows))
open(halves[0], "w").write("".join(rows[: ROWS // 2]))
open(halves[1], "w").write("".join(rows[ROWS // 2:]))

WORKER = r'''
import json, os, sys
rank, port, data, out = (int(sys.argv[1]), int(sys.argv[2]),
                         sys.argv[3], sys.argv[4])
from dmlc_tpu import collective
from dmlc_tpu.models import LinearLearner
collective.init()  # DMLC_TPU_COLLECTIVE=socket forces the tree engine
assert collective.engine_kind() == "socket", collective.engine_kind()
learner = LinearLearner(sync="host", objective="logistic",
                        learning_rate=0.1, num_features=8)
hist = learner.fit_uri(data, batch_size=32, epochs=3, num_features=8,
                       part_index=0, num_parts=1)
import numpy as np
json.dump({"hist": [h.hex() for h in map(float, hist)],
           "w": np.asarray(learner.params["w"]).tobytes().hex(),
           "b": np.asarray(learner.params["b"]).tobytes().hex()},
          open(out, "w"))
collective.finalize()
'''

worker_py = os.path.join(workdir, "worker.py")
open(worker_py, "w").write(WORKER)

from dmlc_tpu.tracker.rendezvous import RabitTracker
tracker = RabitTracker("127.0.0.1", 2, port=19590, port_end=19690)
tracker.start(2)
procs, outs = [], []
for rank in range(2):
    out = os.path.join(workdir, "r%d.json" % rank)
    outs.append(out)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               DMLC_TPU_COLLECTIVE="socket",
               DMLC_TRACKER_URI="127.0.0.1",
               DMLC_TRACKER_PORT=str(tracker.port),
               DMLC_TASK_ID=str(rank), PYTHONPATH=os.getcwd())
    procs.append(subprocess.Popen(
        [sys.executable, worker_py, str(rank), str(tracker.port),
         halves[rank], out], env=env))
for p in procs:
    if p.wait(timeout=240) != 0:
        sys.exit("ci_checks: socket hostsync worker failed (rc=%d)"
                 % p.returncode)
tracker.join(); tracker.close()
socket_runs = [json.load(open(o)) for o in outs]
if socket_runs[0] != socket_runs[1]:
    sys.exit("ci_checks: socket ranks disagree on the fitted model")

# the mesh twin: whole file (world=1), global batch 64 sharded 32/32
import jax
from jax.sharding import Mesh
from dmlc_tpu import collective, obs
from dmlc_tpu.models import LinearLearner
collective.init()  # DMLC_TPU_COLLECTIVE=device forces DeviceEngine
assert collective.engine_kind() == "device", collective.engine_kind()
mesh = Mesh(np.asarray(jax.devices()), ("dp",))
learner = LinearLearner(mesh=mesh, **HYPER)
hist = learner.fit_uri(data, batch_size=ROWS, epochs=EPOCHS,
                       num_features=NF)
spmd = {"hist": [h.hex() for h in map(float, hist)],
        "w": np.asarray(learner.params["w"]).tobytes().hex(),
        "b": np.asarray(learner.params["b"]).tobytes().hex()}
if spmd != socket_runs[0]:
    sys.exit("ci_checks: SPMD psum run diverged from the socket tree:\n"
             "  spmd   %r\n  socket %r" % (spmd, socket_runs[0]))
# the acceptance claim in observable form: training's gradient sync
# crossed ICI in-graph, so the host-path collective moved nothing back
d2h = obs.registry().counter(
    "dmlc_collective_d2h_bytes_total", "", op="allreduce").value
if d2h != 0:
    sys.exit("ci_checks: SPMD run copied %d collective D2H bytes" % d2h)
shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: SPMD collective smoke OK "
      "(device psum == socket tree, bit-exact; 0 collective D2H bytes)")
EOF

# watchdog/goodput smoke: a short linear fit with a scripted mid-run
# slowdown (the feed throttled from epoch 4 on) must trip the collapse
# watchdog through the fit loop's own ledger — exactly one
# watchdog.alert in the flight-recorder dump plus the
# dmlc_watchdog_alerts_total{kind="collapse"} bump — and the status
# plane must serve the run's roofline attribution at /goodput.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import json, os, shutil, sys, tempfile, time, urllib.request

import numpy as np

from dmlc_tpu import obs
from dmlc_tpu.data.parsers import LibSVMParser
from dmlc_tpu.device.feed import BatchSpec, DeviceFeed
from dmlc_tpu.io.input_split import create_input_split
from dmlc_tpu.models.linear import LinearLearner
from dmlc_tpu.obs import flight, plane

workdir = tempfile.mkdtemp(prefix="dmlc_wd_smoke_")
rec = flight.configure(workdir, capacity=64, rank=0, install=False)

NF, ROWS, EPOCHS, SLOW_FROM = 16, 600, 6, 4
rng = np.random.RandomState(0)
lines = []
for i in range(ROWS):
    ids = np.sort(rng.choice(NF, size=1 + i % 5, replace=False))
    lines.append("%d %s" % (i % 2, " ".join(
        "%d:%.4f" % (j, rng.rand()) for j in ids)))
svm = os.path.join(workdir, "t.svm")
with open(svm, "w") as fh:
    fh.write("\n".join(lines) + "\n")


class ThrottledFeed:
    """The scripted regression: from epoch SLOW_FROM on every batch
    costs an extra 250 ms, collapsing rows/s ~100x mid-run."""

    def __init__(self, feed):
        self._feed = feed
        self._epoch = -1

    def __getattr__(self, name):
        return getattr(self._feed, name)

    def __iter__(self):
        self._epoch += 1
        for batch in self._feed:
            if self._epoch >= SLOW_FROM:
                time.sleep(0.25)
            yield batch


reg = obs.registry()
t0_ns = time.time_ns()
m0 = reg.flat_values()

split = create_input_split(svm, 0, 1, "text", threaded=False)
feed = DeviceFeed(
    LibSVMParser(split, nthread=1),
    BatchSpec(batch_size=128, layout="dense", num_features=NF))
learner = LinearLearner(learning_rate=0.1)
learner.fit_feed(ThrottledFeed(feed), epochs=EPOCHS)
feed.close()
t1_ns = time.time_ns()
m1 = reg.flat_values()

# the collapse must have fired exactly once (fire-once hysteresis:
# epoch 4 trips it, epoch 5 stays silent) and landed in the dump
alerts = [r for r in rec.records() if r["kind"] == "watchdog.alert"]
if [a.get("alert") for a in alerts] != ["collapse"]:
    sys.exit("ci_checks: expected one collapse alert, got %r" % alerts)
bumped = reg.counter(
    "dmlc_watchdog_alerts_total", "", kind="collapse").value
if bumped != 1:
    sys.exit("ci_checks: alerts counter = %r, want 1" % bumped)
dump_path = rec.dump("watchdog_smoke")
dumped = json.load(open(dump_path))["records"]
if not any(r["kind"] == "watchdog.alert" and r.get("alert") == "collapse"
           for r in dumped):
    sys.exit("ci_checks: collapse alert missing from flight dump")

# the plane rolls the same run's heartbeat delta into /goodput
sp = plane.StatusPlane(num_workers=1, heartbeat_gap=60.0)
sp.note_payload(0, {"sent_unix_ns": t0_ns, "anchor_unix_ns": 1,
                    "metrics": m0, "spans": []}, recv_unix_ns=t0_ns)
sp.note_payload(0, {"sent_unix_ns": t1_ns, "anchor_unix_ns": 1,
                    "metrics": m1, "spans": []}, recv_unix_ns=t1_ns)
srv = plane.StatusServer(sp, port=0)
srv.start()
try:
    url = "http://127.0.0.1:%d/goodput" % srv.port
    with urllib.request.urlopen(url, timeout=10) as resp:
        body = json.loads(resp.read())
finally:
    srv.close()
att = body["ranks"]["0"]
if att["binding"] != "device_step":
    sys.exit("ci_checks: /goodput binding = %r, want device_step "
             "(the throttle rides the consume span)" % att["binding"])
if att["counters"]["rows"] != ROWS * EPOCHS:
    sys.exit("ci_checks: /goodput rows = %r" % att["counters"]["rows"])
if not body["job"] or body["job"]["binding"] != "device_step":
    sys.exit("ci_checks: job roll-up missing or wrong: %r" % body["job"])
flight.reset()
shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: watchdog smoke OK "
      "(collapse fired once, dumped; /goodput names device_step)")
EOF

# determinism-audit smoke: the same short fit run as a 2-process pair
# with DMLC_TPU_AUDIT=1. Clean pair: zero divergences, no replay
# bundles, bit-identical model digest chains across ranks. Faulted
# pair: rank 1 gets a single silently-corrupted chunk (the
# audit.corrupt faultpoint flips one digit — parseable, wrong bytes);
# the worker's epoch self-check must localize the fork to the exact
# (parse, rank 1, seq 0) in audit-rank1.json, and a tracker-side
# AuditPlane fed both ranks' exports must flag the cross-rank model
# fork. Finally the disabled-vs-enabled parse overhead is measured
# (min-of-3; <2% steady-state target, generous CI bound).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import glob, json, os, shutil, subprocess, sys, tempfile, time

import numpy as np

workdir = tempfile.mkdtemp(prefix="dmlc_audit_smoke_")
NF, ROWS = 12, 400
rng = np.random.RandomState(5)
svm = os.path.join(workdir, "a.svm")
with open(svm, "w") as fh:
    for i in range(ROWS):
        ids = np.sort(rng.choice(NF, size=1 + i % 4, replace=False))
        fh.write("%d %s\n" % (i % 2, " ".join(
            "%d:%.4f" % (j, rng.rand()) for j in ids)))

WORKER = r'''
import json, sys
data, out = sys.argv[1], sys.argv[2]
import numpy as np
from dmlc_tpu.models import LinearLearner
from dmlc_tpu.obs import audit
learner = LinearLearner(objective="logistic", learning_rate=0.1,
                        num_features=12)
list(learner.fit_uri(data, batch_size=64, epochs=2, num_features=12))
a = audit.auditor()
json.dump({"divergences": a.snapshot()["divergences"],
           "export": a.export(),
           "w": np.asarray(learner.params["w"]).tobytes().hex()},
          open(out, "w"))
'''
worker_py = os.path.join(workdir, "worker.py")
open(worker_py, "w").write(WORKER)

def run_pair(tag, faults=None):
    rundir = os.path.join(workdir, tag)
    os.makedirs(rundir)
    procs, outs = [], []
    for rank in range(2):
        out = os.path.join(rundir, "r%d.json" % rank)
        outs.append(out)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DMLC_TPU_AUDIT="1", DMLC_TPU_NTHREAD="1",
                   DMLC_TASK_ID=str(rank), PYTHONPATH=os.getcwd())
        env.pop("DMLC_TPU_FAULTS", None)
        env.pop("DMLC_TPU_STATUS_PORT", None)
        if rank == 1 and faults:
            env["DMLC_TPU_FAULTS"] = faults
        procs.append(subprocess.Popen(
            [sys.executable, worker_py, svm, out], env=env, cwd=rundir))
    for p in procs:
        if p.wait(timeout=240) != 0:
            sys.exit("ci_checks: audit smoke worker failed (rc=%d)"
                     % p.returncode)
    return rundir, [json.load(open(o)) for o in outs]

def plane_forks(reports, rundir):
    from dmlc_tpu.obs.audit import AuditPlane
    from dmlc_tpu.obs.metrics import Registry
    out_dir = os.path.join(rundir, "tracker")
    os.makedirs(out_dir, exist_ok=True)
    plane = AuditPlane(reg=Registry(), out_dir=out_dir)
    found = []
    for rank, rep in enumerate(reports):
        found += plane.note_audit(rank, rep["export"])
    return found

# clean pair: identical inputs -> identical chains, zero divergences
rundir, reports = run_pair("clean")
if any(rep["divergences"] for rep in reports):
    sys.exit("ci_checks: clean audit run reported divergences: %r"
             % [rep["divergences"] for rep in reports])
if glob.glob(os.path.join(rundir, "audit-rank*.json")):
    sys.exit("ci_checks: clean audit run wrote a replay bundle")
heads = [rep["export"]["chains"]["model"]["head"] for rep in reports]
if heads[0] != heads[1] or reports[0]["w"] != reports[1]["w"]:
    sys.exit("ci_checks: clean ranks disagree on the model chain")
if plane_forks(reports, rundir):
    sys.exit("ci_checks: AuditPlane flagged a fork on the clean pair")

# faulted pair: one corrupted chunk on rank 1, epoch 0
rundir, reports = run_pair("corrupt", faults="audit.corrupt:nth=1")
if reports[0]["divergences"]:
    sys.exit("ci_checks: corruption on rank 1 flagged rank 0: %r"
             % reports[0]["divergences"])
divs = reports[1]["divergences"]
if not divs or (divs[0]["stage"], divs[0]["seq"]) != ("parse", 0):
    sys.exit("ci_checks: rank 1 self-check missed the fork "
             "(want stage=parse seq=0): %r" % divs)
bundle_file = os.path.join(rundir, "audit-rank1.json")
if os.path.exists(os.path.join(rundir, "audit-rank0.json")):
    sys.exit("ci_checks: clean rank 0 wrote a replay bundle")
bundle = json.load(open(bundle_file))
if (bundle["divergence"]["stage"], bundle["divergence"]["seq"],
        bundle["rank"]) != ("parse", 0, 1):
    sys.exit("ci_checks: bundle localization wrong: %r"
             % bundle["divergence"])
forks = plane_forks(reports, rundir)
if not forks or (forks[0]["stage"], forks[0]["rank"]) != ("model", 1):
    sys.exit("ci_checks: AuditPlane missed the cross-rank model fork: %r"
             % forks)
rc = subprocess.call([sys.executable, "-m", "dmlc_tpu.tools",
                      "audit-report", rundir],
                     stdout=subprocess.DEVNULL)
if rc != 1:
    sys.exit("ci_checks: audit-report rc=%d on a diverged bundle, "
             "want 1" % rc)

# overhead: disabled vs full-audit parse pass over a bigger corpus
from dmlc_tpu.data.parsers import LibSVMParser
from dmlc_tpu.io.input_split import create_input_split
from dmlc_tpu.obs import audit as audit_mod

big = os.path.join(workdir, "big.svm")
with open(big, "w") as fh:
    for i in range(20000):
        fh.write("%d %d:%.4f %d:%.4f\n"
                 % (i % 2, i % NF, rng.rand(), NF + i % NF, rng.rand()))

def parse_pass():
    split = create_input_split(big, 0, 1, "text", threaded=False)
    parser = LibSVMParser(split, nthread=1)
    n = sum(1 for _ in parser)
    parser.close()
    return n

def best_of(trials=3):
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        parse_pass()
        best = min(best, time.perf_counter() - t0)
    return best

os.environ.pop("DMLC_TPU_FAULTS", None)
os.environ.pop("DMLC_TPU_AUDIT", None)
audit_mod.reset_auditor()
parse_pass()  # warm the page cache + import path before timing
base = best_of()
os.environ["DMLC_TPU_AUDIT"] = "1"
audit_mod.reset_auditor()
parse_pass()
audited = best_of()
if audit_mod.auditor().snapshot()["divergences"]:
    sys.exit("ci_checks: overhead pass reported divergences")
os.environ.pop("DMLC_TPU_AUDIT", None)
audit_mod.reset_auditor()
ratio = audited / base if base > 0 else 1.0
print("ci_checks: audit parse overhead x%.3f (steady-state target "
      "<1.02)" % ratio)
if ratio > 1.15:
    sys.exit("ci_checks: audit overhead x%.3f exceeds the CI bound "
             "1.15" % ratio)
shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: audit smoke OK (self-check + cross-rank localized "
      "(parse, rank 1, seq 0); clean pair chain-identical)")
EOF

# baked-shard smoke: bake a toy corpus through the CLI, prove the
# ShardParser replays the text parser's rows bit-identically
# (rows_digest over the canonical audit stream), then run a shuffled
# (DMLC_TPU_SHUFFLE=13) 2-worker dispatcher epoch with the determinism
# audit armed — the global permutation must preserve the per-epoch
# row-set exactly (order-insensitive digest == unshuffled aggregate)
# with ZERO audit divergences on the pre-tokenized fast path.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import hashlib, os, sys, tempfile

import numpy as np

from dmlc_tpu import resilience
from dmlc_tpu.data import (BlockService, DataDispatcher, RemoteBlockParser,
                           create_parser, reset_source_cache)
from dmlc_tpu.obs import audit
from dmlc_tpu.obs.audit import rows_digest
from dmlc_tpu.tools import bake

ROWS = 120
workdir = tempfile.mkdtemp(prefix="dmlc_shard_smoke_")
svm = os.path.join(workdir, "toy.svm")
dst = os.path.join(workdir, "toy.dtsh")
rng = np.random.RandomState(9)
with open(svm, "w") as fh:
    for i in range(ROWS):
        ids = np.sort(rng.choice(16, size=1 + i % 5, replace=False))
        fh.write("%d %s\n" % (i, " ".join(
            "%d:%.4f" % (j, rng.rand()) for j in ids)))


def drain_digest(parser):
    from dmlc_tpu.data.row_block import RowBlockContainer
    out = RowBlockContainer()
    for block in parser:
        out.push_block(block)
    parser.close()
    return rows_digest(out)


def rowset_digest(faults=None, shuffle=None):
    """Order-insensitive exact digest of one dispatcher epoch's rows:
    per-row (label, indices, values) signatures, sorted then hashed."""
    resilience.reset()
    reset_source_cache()
    audit.reset_auditor()
    os.environ.pop("DMLC_TPU_SHUFFLE", None)
    if shuffle is not None:
        os.environ["DMLC_TPU_SHUFFLE"] = str(shuffle)
    if faults:
        resilience.configure(faults)
    sigs = []
    with DataDispatcher(dst, nchunks=4, lease_s=1.0,
                        dead_after_s=0.75) as disp:
        workers = [BlockService(dispatcher=disp.address, nthread=1)
                   for _ in range(2)]
        try:
            p = RemoteBlockParser(disp.address, dispatcher=True)
            for b in p:
                for r in range(len(b)):
                    lo, hi = b.offset[r], b.offset[r + 1]
                    sigs.append(b.label[r].tobytes()
                                + b.index[lo:hi].tobytes()
                                + b.value[lo:hi].tobytes())
            p.close()
            ok = disp.join(timeout=30)
        finally:
            for svc in workers:
                svc.close()
    if not ok or len(sigs) != ROWS:
        sys.exit("ci_checks: shard smoke lost rows (%d/%d, ok=%s)"
                 % (len(sigs), ROWS, ok))
    h = hashlib.sha256()
    for sig in sorted(sigs):
        h.update(sig)
    return h.hexdigest()


try:
    if bake.main([svm, dst, "--format", "libsvm",
                  "--rows-per-window", "32"]) != 0:
        sys.exit("ci_checks: bake CLI failed")
    text = drain_digest(create_parser(svm, 0, 1, data_format="libsvm"))
    baked = drain_digest(create_parser(dst, 0, 1))
    if baked != text:
        sys.exit("ci_checks: baked shard is NOT bit-identical to the "
                 "text parse (%s != %s)" % (baked[:12], text[:12]))
    os.environ["DMLC_TPU_AUDIT"] = "1"
    plain = rowset_digest()
    shuffled = rowset_digest(shuffle=13)
    if shuffled != plain:
        sys.exit("ci_checks: shuffled epoch changed the row-set")
    divs = audit.auditor().snapshot()["divergences"]
    if divs:
        sys.exit("ci_checks: shard smoke audit divergences: %r" % divs)
finally:
    os.environ.pop("DMLC_TPU_AUDIT", None)
    os.environ.pop("DMLC_TPU_SHUFFLE", None)
    resilience.reset()
    reset_source_cache()
    audit.reset_auditor()
    import shutil
    shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: baked-shard smoke OK (bake == text bit-exact; "
      "shuffled 2-worker epoch row-set identical, 0 divergences)")
EOF

# preemption smoke: a 2-process dmlc-submit fit with job snapshots and
# the determinism audit armed is SIGTERMed mid-epoch on both ranks once
# each wrote its epoch-0 snapshot part; each rank finalizes a just-in-time
# coordinated snapshot, exits with the relaunch code (75), the launcher
# relaunches without consuming attempts, and the resumed job's per-rank
# final params + loss history + audit chain heads are bit-identical to
# an uninterrupted run with zero audit divergences.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF'
import os, shutil, subprocess, sys, tempfile

import numpy as np

WORKER = r'''
import hashlib, os, signal, sys, threading, time
import numpy as np
from dmlc_tpu import collective as rabit
from dmlc_tpu.models import LinearLearner
from dmlc_tpu.obs.audit import auditor

DATA, SNAP, KILL, SENTDIR = sys.argv[1:5]
NFEAT, EPOCHS = 6, 4

rabit.init()
rank = rabit.rank()
sentinel = os.path.join(SENTDIR, "life.rank%d" % rank)
first = not os.path.exists(sentinel)
if first:
    with open(sentinel, "w") as fh:
        fh.write("armed")
if KILL == "sigterm" and first:
    # the "cloud" preempts this host: once this rank wrote its epoch-0
    # snapshot part it gets a real SIGTERM, solidly mid-epoch-1 for the
    # rank. Keying on the rank's OWN part (not the global LATEST, which
    # needs every drifting rank's part + the rank-0 barrier) keeps the
    # kill deterministically inside the fit.
    def preempt_host():
        part = os.path.join(SNAP, "snap_v1.rank%d" % rank)
        while not os.path.exists(part):
            time.sleep(0.002)
        os.kill(os.getpid(), signal.SIGTERM)
    threading.Thread(target=preempt_host, daemon=True).start()

model = LinearLearner(learning_rate=0.5)
history = model.fit_uri(DATA, batch_size=16, epochs=EPOCHS,
                        num_features=NFEAT, drop_remainder=True,
                        snapshot_uri=SNAP, resume=not first)
blob = b"".join(np.ascontiguousarray(np.asarray(model.params[k]))
                .tobytes() for k in ("w", "b"))
blob += repr([round(float(x), 12) for x in history]).encode()
audit = auditor()
head = (audit.export_state() or {}).get("model", {}).get("head", "-")
div = len(getattr(audit, "divergences", ()))
rabit.tracker_print(
    "RESULT rank=%d digest=%s epochs=%d head=%s div=%d"
    % (rank, hashlib.sha256(blob).hexdigest()[:16], len(history),
       (head or "-")[:16], div))
rabit.finalize()
'''

workdir = tempfile.mkdtemp(prefix="dmlc_preempt_smoke_")
rng = np.random.RandomState(23)
data = os.path.join(workdir, "p.svm")
with open(data, "w") as fh:
    for _ in range(320):
        x = rng.rand(6)
        fh.write("%d %s\n" % (int(x.sum() > 3), " ".join(
            "%d:%.6f" % (j, x[j]) for j in range(6))))
worker_py = os.path.join(workdir, "worker.py")
open(worker_py, "w").write(WORKER)


def run_job(tag, kill, max_attempts):
    snap = os.path.join(workdir, "snap_%s" % tag)
    sent = os.path.join(workdir, "sent_%s" % tag)
    os.makedirs(sent)
    env = dict(os.environ, JAX_PLATFORMS="cpu", DMLC_TPU_AUDIT="1",
               DMLC_TPU_PREEMPT_DEADLINE_S="10",
               PYTHONPATH=os.getcwd())
    env.pop("DMLC_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, "dmlc-submit", "--cluster", "local", "-n", "2",
         "--max-attempts", str(max_attempts), "--host-ip", "127.0.0.1",
         sys.executable, worker_py, data, snap, kill, sent],
        capture_output=True, text=True, timeout=300, env=env)
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        sys.exit("ci_checks: preemption smoke %s run failed (rc=%d)\n%s"
                 % (tag, proc.returncode, out))
    results = {}
    for line in out.splitlines():
        if "RESULT" in line:
            kv = dict(p.split("=")
                      for p in line.split("RESULT", 1)[1].split())
            results[int(kv["rank"])] = kv
    if sorted(results) != [0, 1]:
        sys.exit("ci_checks: preemption smoke %s: missing RESULT "
                 "lines\n%s" % (tag, out))
    for r, kv in sorted(results.items()):
        if int(kv["epochs"]) != 4:
            sys.exit("ci_checks: %s rank %d finished %s epochs, want 4"
                     % (tag, r, kv["epochs"]))
        if int(kv["div"]) != 0:
            sys.exit("ci_checks: %s rank %d reported %s audit "
                     "divergences" % (tag, r, kv["div"]))
    return results, out


try:
    clean, _ = run_job("clean", "none", max_attempts=1)
    chaos, out = run_job("sigterm", "sigterm", max_attempts=2)
    if "preempted (exit 75)" not in out:
        sys.exit("ci_checks: SIGTERM never engaged the exit-75 relaunch "
                 "path\n%s" % out)
    for r in (0, 1):
        if (chaos[r]["digest"] != clean[r]["digest"]
                or chaos[r]["head"] != clean[r]["head"]):
            sys.exit("ci_checks: rank %d resumed run diverged from the "
                     "uninterrupted twin:\n  clean %r\n  chaos %r"
                     % (r, clean[r], chaos[r]))
finally:
    shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: preemption smoke OK (2-proc SIGTERM -> exit-75 "
      "relaunch; per-rank params+history+audit bit-identical, 0 "
      "divergences)")
EOF

# MFU smoke: a short CPU linear fit with device telemetry on must leave
# compiled-program analytics behind — /xla serves nonzero flops for
# linear.step, the bench-detail assembly (same goodput.attribute path)
# carries a gateable sgd_mfu, and the extraction's second lowering must
# not show up as a post-warmup recompile. bench-gate --smoke already ran
# above, so a regressing sgd_mfu fails this script either way.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" DMLC_TPU_PEAK_FLOPS=1e6 \
python - <<'EOF'
import json, os, shutil, sys, tempfile, time, urllib.request

import numpy as np

import bench
from dmlc_tpu import obs
from dmlc_tpu.models import LinearLearner
from dmlc_tpu.obs import device_telemetry as dt
from dmlc_tpu.obs import goodput, plane, xla_cost

NF, ROWS = 12, 400
rng = np.random.RandomState(7)
workdir = tempfile.mkdtemp(prefix="dmlc_mfu_smoke_")
svm = os.path.join(workdir, "m.svm")
with open(svm, "w") as fh:
    for i in range(ROWS):
        ids = np.sort(rng.choice(NF, size=1 + i % 4, replace=False))
        fh.write("%d %s\n" % (i % 2, " ".join(
            "%d:%.4f" % (j, rng.rand()) for j in ids)))

dt.reset()
t0 = time.time()
learner = LinearLearner(objective="logistic", learning_rate=0.1,
                        num_features=NF)
list(learner.fit_uri(svm, batch_size=64, epochs=1, num_features=NF))
warm = dict(dt.compile_counts())
list(learner.fit_uri(svm, batch_size=64, epochs=2, num_features=NF))
wall = max(time.time() - t0, 1e-9)
if dict(dt.compile_counts()) != warm:
    sys.exit("ci_checks: mfu smoke recompiled past warmup: %r -> %r"
             % (warm, dt.compile_counts()))

reg = obs.registry()
flat = reg.flat_values()
if flat.get('dmlc_xla_recompiles_total{fn="linear.step"}', 0.0):
    sys.exit("ci_checks: mfu smoke tripped the recompile sentinel")
sites = xla_cost.sites_from_flat(flat)
if sites.get("linear.step", {}).get("flops", 0.0) <= 0.0:
    sys.exit("ci_checks: no analyzed linear.step in the registry: %r"
             % sorted(sites))

# the /xla endpoint end to end, fed by the worker's own payload blob
sp = plane.StatusPlane(num_workers=1)
blob, _ = plane.build_payload(rank=0, epoch=2, reg=reg)
sp.note_payload(0, json.loads(blob), time.time_ns())
srv = plane.StatusServer(sp, port=0)
srv.start()
try:
    url = "http://127.0.0.1:%d/xla" % srv.port
    with urllib.request.urlopen(url, timeout=10) as resp:
        body = json.loads(resp.read())
finally:
    srv.close()
served = body.get("ranks", {}).get("0", {}).get("linear.step", {})
if served.get("flops", 0.0) <= 0.0:
    sys.exit("ci_checks: /xla served no linear.step flops: %r" % body)

# the bench-detail assembly: same attribute() call bench.py makes,
# against the tiny DMLC_TPU_PEAK_FLOPS ceiling set for this smoke
extra = {"xla": xla_cost.detail_section()}
att = goodput.attribute(flat, wall, current=flat)
if att.get("mfu") is not None:
    extra["sgd_mfu"] = att["mfu"]
if not extra["xla"]["sites"].get("linear.step"):
    sys.exit("ci_checks: bench detail xla section lost linear.step")
if extra.get("sgd_mfu", 0.0) <= 0.0:
    sys.exit("ci_checks: bench detail carries no sgd_mfu (att=%r)"
             % {k: att.get(k) for k in ("mfu", "compute", "counters")})
if bench.BENCH_DIRECTIONS.get("sgd_mfu") != "higher":
    sys.exit("ci_checks: sgd_mfu is not gated higher-is-better")
shutil.rmtree(workdir, ignore_errors=True)
print("ci_checks: mfu smoke OK (/xla serves linear.step flops; "
      "sgd_mfu %.4f rides the detail record; 0 post-warmup recompiles)"
      % extra["sgd_mfu"])
EOF

echo "ci_checks: all checks passed"
