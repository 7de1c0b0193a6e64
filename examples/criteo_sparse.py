#!/usr/bin/env python
"""Criteo-class sparse training end to end — the north-star workload.

High-cardinality hashed features (2^20 id space, ~39 nnz/row) through the
sparse device path::

    python examples/criteo_sparse.py data.svm --num-features 1048577
    python examples/criteo_sparse.py --synthetic        # self-contained demo
    python examples/criteo_sparse.py data.rec --format recordio  # binary shards

The pipeline this demonstrates (every stage measured in bench.py):

1. parse — text LibSVM or (recommended for steady state: 5x the MB/s,
   ~40% smaller files) binary row-group RecordIO shards
   (``dmlc_tpu.tools rowrec`` converts);
2. ``DeviceFeed(spec, layout="csr")`` — static-shape COO batches: values/
   indices padded to ``nnz_bucket`` (no recompilation storms, SURVEY §7),
   row ids shipped as CSR offsets (4 B/row instead of 4 B/entry across
   H2D) and expanded on device;
3. ``make_linear_train_step(layout="csr")`` — segment-sum SpMV forward
   and scatter-add gradient (the TPU-native Row::SDot), one fused psum
   under a mesh, batch buffers donated;
4. on a multi-chip mesh the feed ships a ``ShardedCSRBatch``: each device
   receives ONLY its shard's entries (per-device H2D ∝ global_nnz/world —
   the Criteo-1TB scale contract).

Single-process; for the multi-host launch story see
``examples/distributed_sgd.py`` (this example is about the sparse device
path, that one about the launch/collective contract).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _synthesize(path: str, rows: int = 20_000, dim: int = 1 << 20,
                nnz: int = 39) -> None:
    # write-to-.tmp + atomic replace: an interrupted run must not leave a
    # truncated file that later runs silently reuse
    rng = np.random.RandomState(7)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        for start in range(0, rows, 5000):
            n = min(5000, rows - start)
            labels = rng.randint(0, 2, size=n)
            ids = rng.randint(0, dim, size=(n, nnz))
            ids.sort(axis=1)
            vals = rng.rand(n, nnz)
            fh.write("\n".join(
                str(labels[i]) + " " + " ".join(
                    f"{ids[i, j]}:{vals[i, j]:.4f}" for j in range(nnz))
                for i in range(n)) + "\n")
    os.replace(tmp, path)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("uri", nargs="?", default=None)
    ap.add_argument("--synthetic", action="store_true",
                    help="generate a small criteo-shaped file and train on it")
    ap.add_argument("--format", default="auto",
                    choices=["auto", "libsvm", "recordio"])
    ap.add_argument("--num-features", type=int, default=(1 << 20) + 1)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=8192)
    ap.add_argument("--nnz-bucket", type=int, default=1 << 19)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()
    if args.uri is None and not args.synthetic:
        ap.error("give a data URI or --synthetic")

    import jax.numpy as jnp

    from dmlc_tpu.data import create_parser
    from dmlc_tpu.device import BatchSpec, DeviceFeed
    from dmlc_tpu.models.fitloop import EpochMetrics, step_batch
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )

    uri = args.uri
    if args.synthetic:
        import tempfile

        uri = os.path.join(tempfile.gettempdir(), "criteo_sparse_demo.svm")
        if not os.path.exists(uri):
            _synthesize(uri)
        print(f"synthetic criteo-shaped data at {uri}")

    spec = BatchSpec(batch_size=args.batch_size, layout="csr",
                     num_features=args.num_features,
                     nnz_bucket=args.nnz_bucket)
    step = make_linear_train_step(
        None, learning_rate=args.lr, layout="csr",
        num_features=args.num_features, donate_batch=True,
    )
    params = init_linear_params(args.num_features)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}

    size_mb = None
    if "://" not in (uri or "") and os.path.exists(uri):
        size_mb = os.path.getsize(uri) / (1 << 20)
    for epoch in range(args.epochs):
        feed = DeviceFeed(
            create_parser(uri, 0, 1, data_format=args.format), spec)
        acc = EpochMetrics()
        t0 = time.time()
        nstep = 0
        for batch in feed:
            params, velocity, metrics = step(
                params, velocity, step_batch(batch, "csr"))
            acc.add(metrics)
            nstep += 1
        dt = time.time() - t0
        feed.close()
        rate = f", {size_mb / dt:.0f} MB/s" if size_mb else ""
        print(f"epoch {epoch}: loss {acc.mean_loss():.6f} "
              f"({nstep} steps, {dt:.2f}s{rate})")
    nnz_w = int(jnp.sum(params["w"] != 0))
    print(f"done: {nnz_w} touched weights of {args.num_features}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
