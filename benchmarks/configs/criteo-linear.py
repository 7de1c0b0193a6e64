"""criteo-linear: how the configuration in criteo-linear.json is
generated, built, checked and counted. Sizes and their sources are in the
JSON file; the learner and the float64 reference are logistic_sgd.py's.
"""

import os

import numpy as np

from harness.spec import load_module

_sgd = load_module(os.path.join(os.path.dirname(__file__), "logistic_sgd.py"))
learner = _sgd.learner
init_params = _sgd.init_params
reference_steps = _sgd.reference_steps


def rows(cfg, seed):
    """One id of each field a row, ids within a field from a power law,
    every value the same number."""
    from harness import textgen

    rng = np.random.default_rng(seed)
    n = int(cfg["rows"])
    ids = textgen.field_power_law_ids(
        rng, n, cfg["field_sizes"], float(cfg["id_power_law_exponent"]))
    label = (rng.random(n) < float(cfg["positive_rate"])).astype(np.uint8)
    value = np.full((1, 1), cfg["value"], dtype=np.float32)
    return {"label": label, "ids": ids,
            "values": np.broadcast_to(value, ids.shape),
            "value_text": cfg["value_text"].encode(), "pool_index": None}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one chip's part of a step needs for its
    ``batch_rows`` rows: each entry's weight read once and written once,
    the batch arrays read once, and the [F] gradient once out and once in
    for the allreduce; two multiply-adds per entry."""
    nnz = batch_rows * int(cfg["nnz_per_row"])
    f = int(cfg["num_features"])
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    return {"bytes": nnz * 4 * 2 + batch + f * 4 * 2, "flops": nnz * 4}
