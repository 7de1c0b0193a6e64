"""True multi-PROCESS mesh integration: jax.distributed over CPU.

Everything else in the suite runs one process with 8 virtual devices;
these tests launch TWO processes (2 virtual devices each) that rendezvous
through ``jax.distributed.initialize`` into one 4-device global mesh —
executing the code paths single-process tests cannot reach:

- ``DeviceFeed._put_tree``'s ``jax.process_count() > 1`` branch
  (``make_array_from_process_local_data`` assembly of per-host batches);
- cross-process XLA collectives inside the jitted train step (the Gloo
  CPU backend standing in for ICI/DCN);
- the multi-host ingest contract: each process parses its OWN InputSplit
  part (part=rank), exactly-once across the world;
- ``DeviceEngine``'s world>1 allreduce/broadcast branch.

This is the closest a single machine gets to the v5e-64 north star's
launch shape (SURVEY §5.8: one process per host, global mesh).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared bootstrap for every worker: force CPU before any backend, pin 2
# virtual devices per process, rendezvous, then import the repo.
# argv: rank world port [extras...]
PREAMBLE = r'''
import os, sys
import jax

jax.config.update("jax_platforms", "cpu")
rank, world, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=world, process_id=rank)
sys.path.insert(0, "__REPO__")
'''

TRAIN_BODY = r'''
import numpy as np
import jax.numpy as jnp

from dmlc_tpu.data import create_parser
from dmlc_tpu.device import BatchSpec, DeviceFeed
from dmlc_tpu.models.fitloop import step_batch
from dmlc_tpu.models.linear import init_linear_params, make_linear_train_step
from dmlc_tpu.parallel import data_parallel_mesh

uri, LAYOUT = sys.argv[4], sys.argv[5]
mesh = data_parallel_mesh()  # GLOBAL: 4 devices across 2 processes
assert jax.process_count() == world and jax.device_count() == 2 * world

FEATS = 8 if LAYOUT == "dense" else 101
# each process parses its OWN part (the multi-host ingest contract);
# drop_remainder keeps per-process step counts equal for the collectives
spec = BatchSpec(batch_size=64, layout=LAYOUT, num_features=FEATS,
                 drop_remainder=True, nnz_bucket=1024)
step = make_linear_train_step(mesh, learning_rate=0.5, layout=LAYOUT,
                              num_features=FEATS)
params = init_linear_params(FEATS)
velocity = {k: jnp.zeros_like(v) for k, v in params.items()}

losses = []
rows_seen = 0
for epoch in range(2):
    feed = DeviceFeed(create_parser(uri, rank, world, nthread=1), spec,
                      mesh=mesh)
    lsum = wsum = 0.0
    for batch in feed:
        rows_seen += batch["num_rows"]
        params, velocity, m = step(params, velocity,
                                   step_batch(batch, LAYOUT))
        lsum += float(m["loss_sum"]); wsum += float(m["weight_sum"])
    feed.close()
    losses.append(round(lsum / max(wsum, 1e-12), 8))
print("RESULT rank=%d losses=%s rows=%d w0=%.8f"
      % (rank, ",".join("%.8f" % v for v in losses), rows_seen,
         float(params["w"][0])), flush=True)
'''

ENGINE_BODY = r'''
import numpy as np

from dmlc_tpu.collective.device import DeviceEngine

eng = DeviceEngine()
assert eng.world_size == world and eng.rank == rank
got = eng.allreduce(np.arange(5, dtype=np.float64) + 100.0 * rank)
want = sum(np.arange(5) + 100.0 * r for r in range(world))
assert np.array_equal(got, want), (got, want)
gmax = eng.allreduce(np.array([rank + 1.0]), op="max")
assert float(gmax[0]) == world
bcast = eng.broadcast(
    np.array([7, 8, 9], dtype=np.int64) if rank == 0 else None, root=0)
assert list(bcast) == [7, 8, 9]
print("RESULT rank=%d ok=1" % rank, flush=True)
'''


PS_BODY = r'''
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh

from dmlc_tpu.models.linear import make_feature_sharded_train_step

devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
mesh = Mesh(np.asarray(devs).reshape(2, 2), ("dp", "mp"))  # dp SPANS procs
step, sh = make_feature_sharded_train_step(mesh, learning_rate=0.3)
rng = np.random.RandomState(0)  # same seed both ranks: global batches
B, F = 16, 4
params = {
    "w": jax.device_put(jnp.zeros(F), sh["w"]),
    "b": jax.device_put(jnp.zeros(()), sh["b"]),
}
losses = []
for _ in range(3):
    x = rng.rand(B, F).astype(np.float32)
    y = (rng.rand(B) > 0.5).astype(np.float32)
    w = np.ones(B, np.float32)
    params, m = step(
        params,
        jax.device_put(jnp.asarray(x), sh["x"]),
        jax.device_put(jnp.asarray(y), sh["label"]),
        jax.device_put(jnp.asarray(w), sh["weight"]),
    )
    losses.append(round(float(m["loss_sum"]) / float(m["weight_sum"]), 8))
print("RESULT rank=%d losses=%s" % (
    rank, ",".join("%.8f" % v for v in losses)), flush=True)
'''


GBDT_BODY = r'''
import numpy as np

from dmlc_tpu.models.gbdt import GBDTLearner, fit_bins
from dmlc_tpu.parallel import data_parallel_mesh

mesh = data_parallel_mesh()  # GLOBAL: 4 devices across 2 processes
assert jax.process_count() == world

# both ranks generate the FULL dataset from one seed; each fits on its
# own half — shared edges from the full matrix stand in for the
# rabit-synced quantile sketch (models/gbdt.fit docstring)
rng = np.random.RandomState(17)
N, F = 1024, 6
x = rng.rand(N, F).astype(np.float32)
y = ((x[:, 0] > 0.5) | (x[:, 1] > 0.8)).astype(np.float32)
edges = fit_bins(x, 16)
half = N // world
lo, hi = rank * half, (rank + 1) * half

learner = GBDTLearner(mesh=mesh, num_trees=4, max_depth=3,
                      learning_rate=0.5, num_bins=16)
history = learner.fit(x[lo:hi], y[lo:hi], edges=edges)
feat = ",".join(str(int(v)) for v in
                np.asarray(learner.trees["feature"]).ravel())
bins = ",".join(str(int(v)) for v in
                np.asarray(learner.trees["bin"]).ravel())
leaf_sum = float(np.abs(np.asarray(learner.trees["leaf"])).sum())

# ragged InputSplit parts (byte-split text -> unequal rows per part):
# fit_uri with drop_remainder must equalize local counts ACROSS processes
# (the _sync_row_count min-allreduce) — divergent inferred global shapes
# would hang the level psum. Shared edges from the full file on each rank.
uri = sys.argv[4]
r2 = GBDTLearner(mesh=mesh, num_trees=3, max_depth=3,
                 learning_rate=0.5, num_bins=16)
# rank-identical edges: sketch over the WHOLE file (part 0/1)
from dmlc_tpu.data import create_parser
blocks = []
parser = create_parser(uri, 0, 1)
for blk in parser:
    blocks.append(blk.to_dense(6))
parser.close()
full_edges = fit_bins(np.concatenate(blocks), 16)
h2 = r2.fit_uri(uri, num_features=6, part_index=rank, num_parts=world,
                edges=full_edges, drop_remainder=True)
feat2 = ",".join(str(int(v)) for v in
                 np.asarray(r2.trees["feature"]).ravel())
assert all(np.isfinite(h2)), h2
print("RESULT rank=%d losses=%s feat=%s bins=%s leafsum=%.8f ragged=%s"
      % (rank, ",".join("%.8f" % v for v in history), feat, bins,
         leaf_sum, feat2), flush=True)
'''


def _launch_workers(tmp_path, body: str, port: str, extra_args=(),
                    world: int = 2, timeout: int = 300):
    """Run the PREAMBLE+body worker in ``world`` processes → list of
    outputs. Kills every child on any failure/timeout — a leaked worker
    would keep the coordinator port bound and wedge the next run."""
    script = tmp_path / "worker.py"
    script.write_text((PREAMBLE + body).replace("__REPO__", REPO))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the worker pins its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(r), str(world), port,
             *map(str, extra_args)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for r in range(world)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, out[-1500:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs



def _worker_losses(outs):
    """Parse each worker's RESULT losses= field; assert ranks agree →
    the shared per-step loss list."""
    fields = []
    for out in outs:
        line = next(ln for ln in out.splitlines() if "RESULT" in ln)
        fields.append(line.split("losses=")[1].split()[0])
    assert len(set(fields)) == 1, fields  # replicated metrics agree
    return [float(v) for v in fields[0].split(",")]


def _meshless_oracle(seed, lr, feats, batch, steps):
    """Replay the workers' exact batch stream through a mesh-less step →
    per-step losses (the numerical reference every distributed variant
    must match)."""
    import jax.numpy as jnp

    from dmlc_tpu.models.linear import (
        init_linear_params, make_linear_train_step)

    step = make_linear_train_step(None, learning_rate=lr)
    params = init_linear_params(feats)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(steps):
        x = rng.rand(batch, feats).astype(np.float32)
        y = (rng.rand(batch) > 0.5).astype(np.float32)
        b = {"x": jnp.asarray(x), "label": jnp.asarray(y),
             "weight": jnp.ones(batch)}
        params, velocity, m = step(params, velocity, b)
        losses.append(float(m["loss_sum"]) / float(m["weight_sum"]))
    return losses


MULTISLICE_BODY = r'''
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_tpu.models.linear import (
    init_linear_params, make_linear_train_step)
from dmlc_tpu.parallel import make_multislice_mesh

# each PROCESS is a virtual slice: the dcn axis crosses the process
# boundary (Gloo standing in for the data-center network), the inner dp
# axis stays within a process (standing in for ICI) — the true
# multi-slice communication shape
devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
mesh = make_multislice_mesh({"dp": 2}, num_slices=world, devices=devs)
assert mesh.axis_names == ("dcn", "dp")
step = make_linear_train_step(mesh, learning_rate=0.4,
                              axis=("dcn", "dp"))
rng = np.random.RandomState(1)  # same seed: global batches everywhere
B, F = 16, 6
params = init_linear_params(F)
velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
sharding = NamedSharding(mesh, P(("dcn", "dp")))
losses = []
for _ in range(3):
    x = rng.rand(B, F).astype(np.float32)
    y = (rng.rand(B) > 0.5).astype(np.float32)
    batch = {
        "x": jax.device_put(jnp.asarray(x), sharding),
        "label": jax.device_put(jnp.asarray(y), sharding),
        "weight": jax.device_put(jnp.ones(B), sharding),
    }
    params, velocity, m = step(params, velocity, batch)
    losses.append(round(float(m["loss_sum"]) / float(m["weight_sum"]), 8))
print("RESULT rank=%d losses=%s" % (
    rank, ",".join("%.8f" % v for v in losses)), flush=True)
'''


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_multislice_hybrid_dp_across_processes(tmp_path):
    """Hybrid dp=(dcn, dp) with the dcn axis CROSSING real process
    boundaries — each process is one virtual slice, so the psum's outer
    hop rides the inter-process transport exactly as DCN would. Must
    match the mesh-less oracle on the same batches."""
    got = _worker_losses(_launch_workers(tmp_path, MULTISLICE_BODY,
                                         "19799"))
    np.testing.assert_allclose(
        got, _meshless_oracle(seed=1, lr=0.4, feats=6, batch=16, steps=3),
        rtol=1e-5)


SUBMIT_WORKER = r'''
import os, sys
sys.path.insert(0, "__REPO__")
import jax

jax.config.update("jax_platforms", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
from dmlc_tpu.parallel.distributed import initialize_from_env

initialize_from_env()  # the DMLC_TPU_* half of the launcher contract
from dmlc_tpu import collective as rabit

rabit.init()  # the classic DMLC_* half (control plane via the tracker)
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dmlc_tpu.parallel import data_parallel_mesh
from jax import shard_map

mesh = data_parallel_mesh()
total = jax.jit(shard_map(
    lambda: jax.lax.psum(jnp.float32(1.0), "dp"),
    mesh=mesh, in_specs=(), out_specs=P()))()
rabit.tracker_print(
    "WORKER rank=%d global_devices=%d psum=%.1f"
    % (jax.process_index(), jax.device_count(), float(total)))
rabit.finalize()
'''


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_dmlc_submit_cluster_tpu_end_to_end(tmp_path):
    """The north-star COMMAND, end to end on one machine:
    ``dmlc-submit --cluster=tpu -n 2 -H hosts`` spawns one worker per
    (local)host, each rendezvouses on BOTH contracts — the classic
    DMLC_* tracker (control plane) and DMLC_TPU_* jax.distributed (data
    plane) — and a psum spans the resulting 4-device global mesh."""
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost\nlocalhost\n")
    worker = tmp_path / "worker.py"
    worker.write_text(SUBMIT_WORKER.replace("__REPO__", REPO))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    # own session + killpg cleanup: on a timeout, killing only dmlc-submit
    # would leak its shell=True worker grandchildren holding the
    # coordinator port (same hazard _launch_workers guards against);
    # a unique --tpu-coordinator-port isolates runs either way
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "dmlc-submit"),
         "--cluster", "tpu", "-n", "2", "-H", str(hostfile),
         "--host-ip", "127.0.0.1", "--tpu-coordinator-port", "19797",
         sys.executable, str(worker)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            import signal

            os.killpg(proc.pid, signal.SIGKILL)
    assert proc.returncode == 0, out[-1500:]
    for rank in range(2):
        assert f"WORKER rank={rank} global_devices=4 psum=4.0" in out, out


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_device_engine_collectives_across_processes(tmp_path):
    """DeviceEngine's world>1 branch (make_array_from_process_local_data
    + XLA AllReduce over the process mesh, broadcast framing) — the rabit
    data plane across REAL processes, unreachable single-process."""
    for out in _launch_workers(tmp_path, ENGINE_BODY, "19791"):
        assert "ok=1" in out


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_feature_sharded_step_across_processes(tmp_path):
    """The PS-analog (dp x mp) step with dp SPANNING processes: psums
    cross the process boundary and device_put places global arrays onto
    a partly non-addressable sharding. Must match a mesh-less oracle on
    the same batches."""
    got = _worker_losses(_launch_workers(tmp_path, PS_BODY, "19795"))
    np.testing.assert_allclose(
        got, _meshless_oracle(seed=0, lr=0.3, feats=4, batch=16, steps=3),
        rtol=1e-5)


def _oracle_losses(uri, world, layout, feats, epochs=2):
    """Single-process reference: replay the SAME global batches — step k
    consumes [part0 batch k ; part1 batch k ...] — through a mesh-less
    step. The multi-host run must match within fp-reassociation noise."""
    import jax.numpy as jnp

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.data.row_block import RowBlockContainer
    from dmlc_tpu.device.csr import pad_to_bucket
    from dmlc_tpu.models.linear import (
        init_linear_params, make_linear_train_step)

    # raw per-part row lists (label, ids, vals) in part order
    part_rows = []
    for r in range(world):
        rows_r = []
        parser = create_parser(str(uri), r, world, nthread=1)
        for block in parser:
            offs = np.asarray(block.offset)
            idx = np.asarray(block.index)
            val = np.asarray(block.value)
            lab = np.asarray(block.label)
            for i in range(len(block)):
                lo, hi = offs[i], offs[i + 1]
                rows_r.append((float(lab[i]), idx[lo:hi], val[lo:hi]))
        parser.close()
        part_rows.append(rows_r)
    nstep = min(len(pr) for pr in part_rows) // 64
    step = make_linear_train_step(None, learning_rate=0.5, layout=layout,
                                  num_features=feats)
    params = init_linear_params(feats)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for _ in range(epochs):
        lsum = wsum = 0.0
        for k in range(nstep):
            # the global batch: each part contributes its k-th 64-row slice
            cont = RowBlockContainer()
            for pr in part_rows:
                for lab, ids, vals in pr[k * 64:(k + 1) * 64]:
                    cont.push_row(lab, ids, value=vals)
            merged_block = cont.to_block()
            if layout == "dense":
                from dmlc_tpu.device.feed import block_to_dense

                x, labels, weights = block_to_dense(
                    merged_block, 64 * world, feats)
                merged = {"x": jnp.asarray(x), "label": jnp.asarray(labels),
                          "weight": jnp.asarray(weights)}
            else:
                b = pad_to_bucket(merged_block, 64 * world,
                                  nnz_bucket=1024 * world * 2)
                merged = {"label": jnp.asarray(b.labels),
                          "weight": jnp.asarray(b.weights),
                          "indices": jnp.asarray(b.indices),
                          "values": jnp.asarray(b.values),
                          "offsets": jnp.asarray(b.offsets)}
            params, velocity, m = step(params, velocity, merged)
            lsum += float(m["loss_sum"]); wsum += float(m["weight_sum"])
        losses.append(lsum / max(wsum, 1e-12))
    return losses


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
@pytest.mark.parametrize("layout,port", [("dense", "19787"),
                                         ("csr", "19789")])
def test_two_process_mesh_trains_and_agrees(tmp_path, layout, port):
    world = 2
    rng = np.random.RandomState(2)
    rows = 2000
    uri = tmp_path / "mh.svm"
    feats = 8 if layout == "dense" else 101
    with open(uri, "w") as fh:
        for _ in range(rows):
            if layout == "dense":
                vals = rng.rand(8)
                fh.write(str(rng.randint(0, 2)) + " " + " ".join(
                    f"{j}:{vals[j]:.5f}" for j in range(8)) + "\n")
            else:
                ids = sorted(rng.choice(100, size=5, replace=False))
                fh.write(str(rng.randint(0, 2)) + " " + " ".join(
                    f"{j}:{rng.rand():.5f}" for j in ids) + "\n")
    outs = _launch_workers(tmp_path, TRAIN_BODY, port,
                           extra_args=(uri, layout))
    results = {}
    for out in outs:
        line = next(ln for ln in out.splitlines() if "RESULT" in ln)
        kv = dict(item.split("=", 1) for item in line.split()[1:])
        results[int(kv["rank"])] = kv
    # replicated outputs: every process must hold IDENTICAL losses/params
    assert results[0]["losses"] == results[1]["losses"], results
    assert results[0]["w0"] == results[1]["w0"], results
    losses = [float(v) for v in results[0]["losses"].split(",")]
    assert losses[1] < losses[0]  # training moved
    # exactly-once across parts (up to the documented drop_remainder tail:
    # each process may drop < batch_size rows per epoch)
    total = sum(int(kv["rows"]) for kv in results.values())
    assert rows * 2 - total < 2 * world * 64, total
    # numerical correctness vs the single-process oracle over the SAME
    # global batches (the csr path trained on garbage before the
    # local-shard fix and still produced "agreeing" ranks — agreement
    # alone is not correctness)
    oracle = _oracle_losses(uri, world, layout, feats)
    np.testing.assert_allclose(losses, oracle, rtol=2e-5)


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_gbdt_three_process_world(tmp_path):
    """world=3 (6-device global mesh): nothing in the histogram-psum or
    row-count reconciliation may assume a two-process world or
    power-of-two device counts."""
    body = r'''
import numpy as np

from dmlc_tpu.models.gbdt import GBDTLearner, fit_bins
from dmlc_tpu.parallel import data_parallel_mesh

mesh = data_parallel_mesh()
assert jax.process_count() == world == 3
rng = np.random.RandomState(41)
N, F = 6 * 128, 5
x = rng.rand(N, F).astype(np.float32)
y = (x[:, 0] > 0.5).astype(np.float32)
edges = fit_bins(x, 8)
part = N // world
lo, hi = rank * part, (rank + 1) * part
learner = GBDTLearner(mesh=mesh, num_trees=3, max_depth=3, num_bins=8,
                      learning_rate=0.5)
h = learner.fit(x[lo:hi], y[lo:hi], edges=edges)
assert all(np.isfinite(h)), h
feat = ",".join(str(int(v)) for v in
                np.asarray(learner.trees["feature"]).ravel())
bins = ",".join(str(int(v)) for v in
                np.asarray(learner.trees["bin"]).ravel())
leafsum = float(np.abs(np.asarray(learner.trees["leaf"])).sum())
print("RESULT rank=%d feat=%s bins=%s leafsum=%.8f"
      % (rank, feat, bins, leafsum), flush=True)
'''
    outs = _launch_workers(tmp_path, body, _free_port(), world=3)
    results = []
    for out in outs:
        line = next(ln for ln in out.splitlines() if "RESULT" in ln)
        kv = dict(item.split("=", 1) for item in line.split()[1:])
        results.append(kv)
    for key in ("feat", "bins", "leafsum"):
        assert len({r[key] for r in results}) == 1, (key, results)
    # oracle: single-process full-data build picks the same trees —
    # structure AND thresholds AND leaf values (a psum bug that keeps
    # the argmax feature but shifts bins/leaves must not pass)
    from dmlc_tpu.models.gbdt import GBDTLearner, fit_bins

    rng = np.random.RandomState(41)
    x = rng.rand(6 * 128, 5).astype(np.float32)
    y = (x[:, 0] > 0.5).astype(np.float32)
    oracle = GBDTLearner(num_trees=3, max_depth=3, num_bins=8,
                         learning_rate=0.5)
    oracle.fit(x, y, edges=fit_bins(x, 8))
    assert results[0]["feat"] == ",".join(
        str(int(v)) for v in np.asarray(oracle.trees["feature"]).ravel())
    assert results[0]["bins"] == ",".join(
        str(int(v)) for v in np.asarray(oracle.trees["bin"]).ravel())
    np.testing.assert_allclose(
        float(results[0]["leafsum"]),
        float(np.abs(np.asarray(oracle.trees["leaf"])).sum()), rtol=2e-5)


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_gbdt_histogram_psum_across_processes(tmp_path):
    """The distributed-xgboost shape: each process holds a row shard,
    per-level (grad, hess) histograms cross processes in one psum, and
    every process must end with the single-process oracle's trees."""
    rng = np.random.RandomState(31)
    uri = tmp_path / "ragged.svm"
    with open(uri, "w") as fh:
        for i in range(1003):  # odd count -> byte-ragged parts
            vals = rng.rand(6)
            label = int(vals[0] > 0.5)
            # label:weight on the FIRST half only: the byte-split gives
            # rank 0 weighted rows and rank 1 none, so the processes'
            # local any_weight flags DISAGREE — the cross-process flag
            # allreduce must still build matching SPMD programs
            head = f"{label}:2.0" if i < 500 else str(label)
            fh.write("%s %s\n" % (head, " ".join(
                f"{j}:{vals[j]:.5f}" for j in range(6))))
    outs = _launch_workers(tmp_path, GBDT_BODY, _free_port(),
                           extra_args=(uri,))
    results = {}
    for out in outs:
        line = next(ln for ln in out.splitlines() if "RESULT" in ln)
        kv = dict(item.split("=", 1) for item in line.split()[1:])
        results[int(kv["rank"])] = kv
    # replicated model state: both processes hold identical trees —
    # including the ragged-parts fit_uri run (unequal local rows
    # min-allreduce-trimmed before global assembly)
    for key in ("losses", "feat", "bins", "leafsum", "ragged"):
        assert results[0][key] == results[1][key], (key, results)
    # oracle: the same full dataset fit single-process with the same edges
    from dmlc_tpu.models.gbdt import GBDTLearner, fit_bins

    rng = np.random.RandomState(17)
    N, F = 1024, 6
    x = rng.rand(N, F).astype(np.float32)
    y = ((x[:, 0] > 0.5) | (x[:, 1] > 0.8)).astype(np.float32)
    oracle = GBDTLearner(num_trees=4, max_depth=3, learning_rate=0.5,
                         num_bins=16)
    oracle_hist = oracle.fit(x, y, edges=fit_bins(x, 16))
    want_feat = ",".join(str(int(v)) for v in
                         np.asarray(oracle.trees["feature"]).ravel())
    want_bins = ",".join(str(int(v)) for v in
                         np.asarray(oracle.trees["bin"]).ravel())
    assert results[0]["feat"] == want_feat
    assert results[0]["bins"] == want_bins
    got_losses = [float(v) for v in results[0]["losses"].split(",")]
    np.testing.assert_allclose(got_losses, oracle_hist, rtol=2e-5)
    np.testing.assert_allclose(
        float(results[0]["leafsum"]),
        float(np.abs(np.asarray(oracle.trees["leaf"])).sum()), rtol=2e-5)


RECOVERY_WORKER = r'''
import os, sys
sys.path.insert(0, "__REPO__")
import jax

jax.config.update("jax_platforms", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
from dmlc_tpu.parallel.distributed import initialize_from_env

initialize_from_env()  # jax.distributed: 2 procs -> 4-device world
from dmlc_tpu import collective as rabit

rabit.init()  # tracker control plane (socket engine; recover keeps rank)
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_tpu.models.linear import init_linear_params, make_linear_train_step
from dmlc_tpu.parallel import data_parallel_mesh

CKPT, MODE = sys.argv[1], sys.argv[2]
EPOCHS, STEPS, B, F = 4, 2, 64, 6
rank = rabit.rank()
attempt = int(os.environ.get("DMLC_NUM_ATTEMPT", 0))
assert jax.device_count() == 4, jax.device_count()
mesh = data_parallel_mesh()
step = make_linear_train_step(mesh, learning_rate=0.5)
sharding = NamedSharding(mesh, P("dp"))


def round_fn():
    # rabit round contract: START from checkpoint state so a replay (or a
    # restarted process) resumes from the last agreed snapshot
    state = rabit.load_checkpoint(CKPT)
    if state is None:
        p0 = init_linear_params(F)
        state = (0, {k: np.asarray(v) for k, v in p0.items()},
                 {k: np.zeros_like(np.asarray(v)) for k, v in p0.items()},
                 [])
    epoch, pnp, vnp, losses = state
    if epoch >= EPOCHS:
        return state
    if MODE == "crash" and rank == 0 and attempt == 0 and epoch == 2:
        os._exit(17)  # hard kill AFTER epoch-2 checkpoint exists
    params = {k: jnp.asarray(v) for k, v in pnp.items()}
    vel = {k: jnp.asarray(v) for k, v in vnp.items()}
    rng = np.random.RandomState(100 + epoch)  # same global batches: SPMD
    lsum = wsum = 0.0
    for _ in range(STEPS):
        x = rng.rand(B, F).astype(np.float32)
        y = (rng.rand(B) > 0.5).astype(np.float32)
        batch = {"x": jax.device_put(jnp.asarray(x), sharding),
                 "label": jax.device_put(jnp.asarray(y), sharding),
                 "weight": jax.device_put(jnp.ones(B), sharding)}
        params, vel, m = step(params, vel, batch)
        lsum += float(m["loss_sum"]); wsum += float(m["weight_sum"])
    state = (epoch + 1,
             {k: np.asarray(v) for k, v in params.items()},
             {k: np.asarray(v) for k, v in vel.items()},
             losses + [round(lsum / max(wsum, 1e-12), 8)])
    if rank == 0:
        rabit.checkpoint(state, CKPT)  # shared URI: restarts resync here
    else:
        rabit.checkpoint(state)
    return state


state = (0, None, None, [])
while state[0] < EPOCHS:
    # socket-plane failures recover in-process (cmd='recover' keeps the
    # rank); a jax-plane failure is fail-stop by design — the process
    # exits and the tpu launcher's per-task retry restarts it into a
    # fresh jax.distributed rendezvous (SURVEY §5.3 TPU mapping)
    state = rabit.run_with_recovery(round_fn)
print("RESULT rank=%d attempt=%d losses=%s w0=%.8f"
      % (rank, attempt, ",".join("%.8f" % v for v in state[3]),
         float(state[1]["w"][0])), flush=True)
rabit.finalize()
'''


def _free_port() -> str:
    import socket as _socket

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def _run_recovery_job(tmp_path, mode: str, port: str):
    """dmlc-submit --cluster=tpu with per-task retries; → {rank: (attempt,
    losses, w0)} parsed from worker RESULT lines."""
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost\nlocalhost\n")
    worker = tmp_path / f"worker_{mode}.py"
    worker.write_text(RECOVERY_WORKER.replace("__REPO__", REPO))
    ckpt = tmp_path / f"ckpt_{mode}.bin"
    if ckpt.exists():  # a retried job must not resume a prior attempt's
        ckpt.unlink()  # checkpoint (the crash epoch would never re-run)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "dmlc-submit"),
         "--cluster", "tpu", "-n", "2", "-H", str(hostfile),
         "--host-ip", "127.0.0.1", "--tpu-coordinator-port", port,
         "--max-attempts", "3",
         sys.executable, str(worker), str(ckpt), mode],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=540)
    finally:
        if proc.poll() is None:
            import signal

            os.killpg(proc.pid, signal.SIGKILL)
    assert proc.returncode == 0, out[-2000:]
    # regex, not line splitting: the two workers' RESULT prints can land
    # glued on one pipe line (launcher relay buffering), which a
    # line-oriented parse collapses into a single rank
    import re

    results = {}
    for m in re.finditer(
        r"RESULT rank=(\d+) attempt=(\d+) "
        r"losses=([0-9.,\-]+?) w0=(-?\d+\.\d+)", out
    ):
        results[int(m.group(1))] = (
            int(m.group(2)), m.group(3), float(m.group(4)))
    assert sorted(results) == [0, 1], out[-2000:]
    return results


def _recovery_oracle():
    """Mesh-less replay of the exact batch stream → (losses, w0)."""
    import jax.numpy as jnp

    from dmlc_tpu.models.linear import (
        init_linear_params, make_linear_train_step)

    step = make_linear_train_step(None, learning_rate=0.5)
    params = init_linear_params(6)
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for epoch in range(4):
        rng = np.random.RandomState(100 + epoch)
        lsum = wsum = 0.0
        for _ in range(2):
            x = rng.rand(64, 6).astype(np.float32)
            y = (rng.rand(64) > 0.5).astype(np.float32)
            b = {"x": jnp.asarray(x), "label": jnp.asarray(y),
                 "weight": jnp.ones(64)}
            params, vel, m = step(params, vel, b)
            lsum += float(m["loss_sum"]); wsum += float(m["weight_sum"])
        losses.append(lsum / max(wsum, 1e-12))
    return losses, float(params["w"][0])


@pytest.mark.skipif(os.environ.get("DMLC_TPU_SKIP_MULTIHOST") == "1",
                    reason="multihost tier disabled")
def test_multihost_elastic_recovery_kill_and_rejoin(tmp_path):
    """VERDICT r04 missing #4, end to end at the multihost tier: one of
    the two REAL jax.distributed processes is killed mid-training (after
    the epoch-2 checkpoint) and rejoins — the tpu launcher's per-task
    retry restarts it, the tracker re-entry keeps its rank, both
    processes re-rendezvous in a fresh jax.distributed world, training
    resumes from the collective checkpoint URI, and the final trajectory
    matches both the crash-free multihost run and the mesh-less oracle.
    (Reference analog: tracker.py:279-291 recover re-entry + rabit
    checkpoint replay.)"""
    # dynamic ports (a fixed pair lands in TIME_WAIT between back-to-back
    # runs); the probe-then-bind gap is racy, so one retry with a fresh
    # port absorbs a lost race instead of flaking the tier
    def run(mode):
        try:
            return _run_recovery_job(tmp_path, mode, _free_port())
        except AssertionError:
            return _run_recovery_job(tmp_path, mode, _free_port())

    clean = run("clean")
    crashed = run("crash")
    # ranks agree within each run
    assert clean[0][1] == clean[1][1], clean
    assert crashed[0][1] == crashed[1][1], crashed
    # the killed worker really died and came back on a later attempt
    assert crashed[0][0] >= 1, crashed
    # crash+rejoin reproduces the crash-free trajectory exactly
    assert crashed[0][1] == clean[0][1], (crashed, clean)
    assert crashed[0][2] == pytest.approx(clean[0][2], rel=1e-6)
    # and the multihost trajectory matches the mesh-less oracle
    oracle_losses, oracle_w0 = _recovery_oracle()
    got = [float(v) for v in clean[0][1].split(",")]
    np.testing.assert_allclose(got, oracle_losses, rtol=1e-5)
    assert clean[0][2] == pytest.approx(oracle_w0, rel=1e-4)
