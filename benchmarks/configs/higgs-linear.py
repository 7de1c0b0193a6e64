"""higgs-linear: how the configuration in higgs-linear.json is generated,
built, checked and counted. Sizes and their sources are in the JSON file;
the learner and the float64 reference are logistic_sgd.py's.
"""

import os

import numpy as np

from harness.spec import load_module

_sgd = load_module(os.path.join(os.path.dirname(__file__), "logistic_sgd.py"))
learner = _sgd.learner
init_params = _sgd.init_params
reference_steps = _sgd.reference_steps


def rows(cfg, seed):
    """Dense rows: ids 1..28 in every row, each value one of a seeded pool
    of float32 numbers that is printed once in the source's text form."""
    from harness import textgen

    rng = np.random.default_rng(seed)
    n, k = int(cfg["rows"]), int(cfg["nnz_per_row"])
    pool = rng.normal(cfg["value_mean"], 1.0, int(cfg["value_pool"]))
    pool = pool.astype(np.float32)
    index = rng.integers(0, len(pool), size=(n, k), dtype=np.int32)
    values = pool[index]
    planted = rng.normal(0.0, 0.5, k).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(values @ planted - 0.2)))
    label = (rng.random(n) < p).astype(np.uint8)
    ids = np.broadcast_to(np.arange(1, k + 1, dtype=np.int32), (n, k))
    return {"label": label, "ids": ids, "values": values,
            "value_text": textgen.value_pool(pool, cfg["value_format"]),
            "pool_index": index}


def step_needs(cfg, batch_rows):
    """Least bytes and operations of one SGD step on ``batch_rows`` rows:
    the batch read once ([B, F] values, labels, weights), the weights read
    and written once; two multiply-adds per value (score, gradient)."""
    f = int(cfg["num_features"])
    return {"bytes": batch_rows * (f + 2) * 4 + f * 4 * 2,
            "flops": batch_rows * f * 4}
