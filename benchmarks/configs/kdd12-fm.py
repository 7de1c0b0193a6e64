"""kdd12-fm: how the configuration in kdd12-fm.json is generated, built,
checked and counted. Sizes and their sources are in the JSON file.

The float64 reference below is written from the model's equations

    score(x) = b + sum_i w_i x_i + 1/2 sum_k [(sum_i v_ik x_i)^2 - sum_i v_ik^2 x_i^2]
    loss     = log(1 + exp(-score)) for label 1, log(1 + exp(score)) for 0
    step     : theta <- theta - lr * (sum_rows dloss/dtheta) / rows   (l2 = 0)

and imports nothing from ``dmlc_tpu.models``.
"""

import numpy as np


def rows(cfg, seed):
    """The file's rows as arrays: one id of each field a row, ids within a
    field from a power law, every value 1."""
    from harness import textgen

    rng = np.random.default_rng(seed)
    n = int(cfg["rows"])
    ids = textgen.field_power_law_ids(
        rng, n, cfg["field_sizes"], float(cfg["id_power_law_exponent"]))
    label = (rng.random(n) < float(cfg["positive_rate"])).astype(np.uint8)
    return {"label": label, "ids": ids, "values": None,
            "value_text": cfg["value_text"].encode(), "pool_index": None}


def learner(cfg, mesh):
    from dmlc_tpu.models import FMLearner

    return FMLearner(
        mesh=mesh, objective=cfg["objective"],
        learning_rate=cfg["learning_rate"], l2=cfg["l2"],
        num_factors=cfg["num_factors"], num_features=cfg["num_features"],
        init_scale=cfg["init_scale"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``); for one that keeps ``params`` as named tables,
    from the program's own initialiser: one jitted call with the seed as
    an argument (one program for every seed), straight on the device."""
    from dmlc_tpu.models.fm import init_fm_params

    from harness import tables

    def params(key):
        return {"params": init_fm_params(
            int(cfg["num_features"]), int(cfg["num_factors"]),
            float(cfg["init_scale"]), key)}

    tables.of(model, params).init_tables(seed)


def reference_steps(cfg, params, batches):
    """SGD steps in float64 numpy. ``params``: {"w": [R], "v": [R, K],
    "b": scalar} over the R rows the batches touch; a batch is
    {"label": [B], "ids": [B, k] positions into those rows, "values":
    [B, k]}. Returns the loss of each step and the parameters after."""
    w = params["w"].astype(np.float64).copy()
    v = params["v"].astype(np.float64).copy()
    b = float(params["b"])
    lr = float(cfg["learning_rate"])
    losses = []
    for batch in batches:
        y = batch["label"].astype(np.float64)
        ids = batch["ids"]
        x = batch["values"].astype(np.float64)
        xv = x[:, :, None] * v[ids]  # [B, k, K]
        s = xv.sum(axis=1)  # [B, K]
        score = b + (x * w[ids]).sum(axis=1) + 0.5 * (
            (s * s).sum(axis=1) - (xv * xv).sum(axis=(1, 2)))
        sign = 2.0 * y - 1.0
        losses.append(float(np.mean(np.logaddexp(0.0, -sign * score))))
        g = (1.0 / (1.0 + np.exp(-score)) - y) / len(y)  # dloss/dscore / B
        gv = (g[:, None] * x)[:, :, None] * (s[:, None, :] - xv)
        np.subtract.at(v, ids.ravel(), lr * gv.reshape(-1, v.shape[1]))
        np.subtract.at(w, ids.ravel(), lr * (g[:, None] * x).ravel())
        b -= lr * g.sum()
    return losses, {"w": w, "v": v, "b": np.float64(b)}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one SGD step needs for ``batch_rows``
    rows: each entry's table row read once and written once (factors and
    the linear weight), the batch arrays read once. The table itself is
    not counted: a sparse step need not pass over it."""
    k = int(cfg["num_factors"])
    nnz = batch_rows * int(cfg["nnz_per_row"])
    table = nnz * (k + 1) * 4 * 2
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    # per entry and factor: x*v, two segment sums, (s - xv), its scaling,
    # the update's multiply and subtract: about 10 operations
    return {"bytes": table + batch, "flops": nnz * k * 10 + nnz * 6}
