#!/usr/bin/env python
"""Histogram gradient-boosted trees — the xgboost-over-rabit workload.

The reference backbone's whole purpose was feeding RowBlocks to xgboost and
allreducing its gradient histograms through rabit's socket tree (reference
tracker/dmlc_tracker/tracker.py:185-252). This example runs that workload
on the rebuilt stack end to end::

    python examples/boosted_trees.py data.svm --num-features 29
    python examples/boosted_trees.py --synthetic          # self-contained
    python examples/boosted_trees.py --synthetic --dp 8   # mesh histogram psum

Pipeline:

1. ingest — any parser uri (LibSVM text, binary RecordIO row groups,
   ``#cachefile``, object-store) materialized to a dense matrix: GBDT's
   hist mode is an in-core epoch-free algorithm (xgboost's default), so
   ingest happens once, not per epoch;
2. quantile binning on device (``fit_bins``/``apply_bins``) — training
   never touches floats again;
3. level-wise tree growth: per-level (grad, hess) histograms by
   segment-sum; under ``--dp N`` the samples are sharded over an N-way
   mesh axis and ONE psum per level syncs histograms across ICI — rabit's
   allreduce as an XLA collective;
4. vectorized split finding + leaf values (cumsum/argmax, no
   data-dependent control flow — the whole tree build jits once).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _load_dense(uri: str, num_features: int, part: int, nparts: int):
    """Materialize a parser uri into dense x [N, F], y [N] (hist mode is
    in-core: one pass, BasicRowIter-style — basic_row_iter.h:61-82)."""
    from dmlc_tpu.data import create_parser

    xs, ys = [], []
    parser = create_parser(uri, part, nparts)
    for block in parser:
        xs.append(block.to_dense(num_features))
        ys.append(np.asarray(block.label, dtype=np.float32))
    parser.close()
    return np.concatenate(xs), np.concatenate(ys)


def _synthetic_multiclass(k: int, n: int = 8192, f: int = 12):
    rng = np.random.RandomState(19)
    x = rng.rand(n, f).astype(np.float32)
    y = np.minimum(
        (x[:, 0] > 0.5) * 2 + (x[:, 1] > 0.5), k - 1
    ).astype(np.float32)
    flip = rng.rand(n) < 0.04
    y[flip] = rng.randint(0, k, int(flip.sum()))
    return x, y


def _synthetic(n: int = 8192, f: int = 16):
    rng = np.random.RandomState(11)
    x = rng.rand(n, f).astype(np.float32)
    logit = (
        5.0 * (x[:, 0] > 0.6)
        - 4.0 * ((x[:, 1] > 0.25) & (x[:, 2] < 0.75))
        + 2.0 * x[:, 3]
        - 1.0
    )
    y = (rng.rand(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    return x, y


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("uri", nargs="?", help="training data uri (any parser)")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--num-features", type=int, default=0)
    ap.add_argument("--num-trees", type=int, default=20)
    ap.add_argument("--max-depth", type=int, default=5)
    ap.add_argument("--learning-rate", type=float, default=0.4)
    ap.add_argument("--num-bins", type=int, default=64)
    ap.add_argument("--objective", default="logistic",
                    choices=("logistic", "squared", "softmax"))
    ap.add_argument("--num-class", type=int, default=0,
                    help="class count for --objective softmax (labels "
                         "are class ids); --synthetic then generates a "
                         "4-class problem")
    ap.add_argument("--dp", type=int, default=0,
                    help="shard samples over a dp-way mesh axis "
                         "(histograms cross the mesh in one psum/level)")
    ap.add_argument("--save", help="checkpoint uri (any Stream backend)")
    args = ap.parse_args()

    from dmlc_tpu.models.gbdt import GBDTLearner

    mesh = None
    if args.dp:
        from dmlc_tpu.parallel import make_mesh

        mesh = make_mesh({"dp": args.dp})

    softmax = args.objective == "softmax"
    if softmax and args.num_class < 2:
        # default only where we control the data: a real uri's class
        # count is the user's to declare (guessing trains a wrong-width
        # model or dies on the label-range check)
        if args.uri and not args.synthetic:
            ap.error("--objective softmax with a data uri requires "
                     "--num-class")
        args.num_class = 4  # the synthetic multiclass default
    learner = GBDTLearner(
        mesh=mesh,
        objective=args.objective,
        num_class=args.num_class,
        num_trees=args.num_trees,
        max_depth=args.max_depth,
        learning_rate=args.learning_rate,
        num_bins=args.num_bins,
    )
    log_every = max(1, args.num_trees // 5)
    t0 = time.time()
    if args.synthetic or not args.uri:
        x, y = _synthetic_multiclass(args.num_class) if softmax \
            else _synthetic()
        if mesh:
            n = (x.shape[0] // args.dp) * args.dp
            x, y = x[:n], y[:n]
        history = learner.fit(x, y, log_every=log_every)
        dt = time.time() - t0
    else:
        if args.num_features <= 0:
            ap.error("--num-features is required with a data uri")
        # the streaming path: reservoir-sketch edges, bin block by block —
        # the dense float matrix never materializes during training
        # (hist external-memory); under --dp the tail rows that don't
        # divide the mesh are trimmed, matching the synthetic branch
        history = learner.fit_uri(args.uri, args.num_features,
                                  log_every=log_every,
                                  drop_remainder=bool(mesh))
        dt = time.time() - t0  # fit only — the eval reload isn't training
        x, y = _load_dense(args.uri, args.num_features, 0, 1)
    prob = learner.predict(x)
    acc = float(np.mean(prob.argmax(axis=1) == y)) if softmax \
        else float(np.mean((prob > 0.5) == (y > 0.5)))
    print(
        f"trees={args.num_trees} depth={args.max_depth} "
        f"rows={x.shape[0]} loss {history[0]:.4f} -> {history[-1]:.4f} "
        f"train-acc {acc:.4f} fit {dt:.2f}s"
        + (f" (dp={args.dp} histogram psum)" if mesh else "")
    )
    if args.save:
        learner.save(args.save)
        print(f"saved -> {args.save}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
