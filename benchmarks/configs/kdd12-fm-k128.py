"""kdd12-fm-k128: how the configuration in kdd12-fm-k128.json is generated,
built, checked and counted. Sizes and their sources are in the JSON file.

The model is kdd12-fm's at 128 factors, its table held as four column
slices over the chips of one host. With chip c holding v_c = v[:, 32c:32c+32]

    S_c[r,k] = sum_i v_c[i,k] x_ri        m_c[r] = 1/2 (sum_k S_c[r,k]^2 - sum_k sum_i (v_c[i,k] x_ri)^2)
    score_r  = b + sum_i w_i x_ri + sum_c m_c[r]
    loss     = log(1 + exp(-score)) for label 1, log(1 + exp(score)) for 0
    step     : theta <- theta - lr * (sum_rows dloss/dtheta) / rows   (l2 = 0)

which is the unsharded model's score, since the factors of an FM do not
interact. The float64 reference below is that unsharded model, written
from the equations, and imports nothing from ``dmlc_tpu.models``.
"""

import numpy as np


def rows(cfg, seed):
    """The file's rows as arrays: one id of each field a row, ids within a
    field from a power law, every value 1 (kdd12-fm's rows: the same
    generator, fields and law)."""
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

    if mesh is None or mesh.size != int(cfg["chips_sharing_the_table"]):
        raise SystemExit(
            "kdd12-fm-k128 holds its table over %d chips: run it in a cell "
            "whose traffic builds a mesh of that many"
            % int(cfg["chips_sharing_the_table"]))
    return FMLearner(
        mesh=mesh, objective=cfg["objective"],
        learning_rate=cfg["learning_rate"], l2=cfg["l2"],
        num_factors=cfg["num_factors"], num_features=cfg["num_features"],
        init_scale=cfg["init_scale"], table_sharding=cfg["table_sharding"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``); for one that keeps ``params`` as named tables,
    from the program's own initialiser: one jitted call with the seed as
    an argument (one program for every seed), placed as the learner
    places its parameters: each chip generates its own 7 GB of the table
    and no chip ever holds more."""
    from dmlc_tpu.models.fm import init_fm_params

    from harness import tables

    def params(key):
        return {"params": init_fm_params(
            int(cfg["num_features"]), int(cfg["num_factors"]),
            float(cfg["init_scale"]), key)}

    tables.of(model, params).init_tables(seed)


def reference_steps(cfg, params, batches):
    """SGD steps of the unsharded model in float64 numpy. ``params``:
    {"w": [R], "v": [R, K], "b": scalar} over the R rows the batches
    touch; a batch is {"label": [B], "ids": [B, k] positions into those
    rows, "values": [B, k]}. Returns the loss of each step and the
    parameters after."""
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
    """Least bytes and operations ONE CHIP's part of a step needs, given
    its ``batch_rows`` rows of the batch (the harness passes the step's
    rows over the chips): every entry of the WHOLE batch has its row of
    the chip's columns read once and written once, and its linear weight
    likewise (w is replicated, so every chip updates all of it); the
    whole batch's arrays are read once (a chip's own section from HBM,
    the others' as they arrive over ICI); the psum's f32[rows] goes out
    once and comes in once. The table itself is not counted: a sparse
    step need not pass over it. Whatever implements the step, this is the
    work of holding a quarter of the factors."""
    chips = int(cfg["chips_sharing_the_table"])
    k = int(cfg["num_factors"]) // chips
    rows_step = batch_rows * chips
    nnz = rows_step * int(cfg["nnz_per_row"])
    table = nnz * (k + 1) * 4 * 2
    batch = nnz * (4 + 4) + (rows_step + chips) * 4 + rows_step * (4 + 4)
    psum = rows_step * 4 * 2
    # per entry and factor as in kdd12-fm: about 10 operations
    return {"bytes": table + batch + psum, "flops": nnz * k * 10 + nnz * 6}
