"""kdd12-ffm: how the configuration in kdd12-ffm.json is generated, built,
checked and counted. Sizes, hyperparameters and their sources are in the
JSON file.

The model is libffm's (Juan et al., RecSys 2016, eq. 4; ffm.cpp ``wTx``).
For a row with entries e = (id i_e, value x_e), f(e) the field of entry
e, v[i, b] the k-vector id i keeps for partners of field b:

    r    = 1 / sum_e x_e^2
    phi  = r * sum_{e < e'} < v[i_e, f(e')], v[i_e', f(e)] > x_e x_e'
    loss = log(1 + exp(-phi)) for label 1, log(1 + exp(phi)) for 0

with no linear term and no bias, and the update is libffm's AdaGrad per
element. With G_i = (sum_rows dloss/dv_i) / rows, for every id i that an
entry of the batch names with a value:

    G = G + l2 * v;  a' = a + G^2;  v' = v - learning_rate * G / sqrt(a')

Every other row keeps its weights and its state. The tables are 2-D,
``[ids, k * fields]``, column ``c * fields + b`` factor c for partners of
field b. The k factors here are this chip's share of the published 4
(the JSON file's ``deployment``): phi is a sum over the factor index, so
the chip's margin is its columns' share, and the reference is given the
same share. The float64 reference below is written from the pairwise
form above and imports nothing from ``dmlc_tpu.models``.
"""

import numpy as np


def rows(cfg, seed):
    """kdd12-fm's rows, from kdd12-fm's own generator (the file beside
    this one): the data is that configuration's, unchanged. Column j of
    ``ids`` is field j."""
    import os

    from harness import spec

    return spec.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kdd12-fm.py")).rows(
            cfg, seed)


def learner(cfg, mesh):
    from dmlc_tpu.models import FFMLearner

    if mesh is not None:
        raise SystemExit(
            "kdd12-ffm is one chip's share of its table: run it in a cell "
            "whose traffic builds no mesh")
    return FFMLearner(
        objective=cfg["objective"], learning_rate=cfg["learning_rate"],
        l2=cfg["l2"], num_factors=cfg["num_factors"],
        num_features=cfg["num_features"], field_sizes=cfg["field_sizes"],
        init_scale=cfg["init_scale"], a_init=cfg["a_init"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``); for one that keeps ``params`` as named tables,
    from the program's own initialiser: one jitted call with the seed as
    an argument (one program for every seed), straight on the device:
    v uniform in [0, init_scale), the accumulator at a_init."""
    from dmlc_tpu.models.ffm import init_ffm_params

    from harness import tables

    def params(key):
        return {"params": init_ffm_params(
            int(cfg["num_features"]), int(cfg["num_factors"]),
            len(cfg["field_sizes"]), float(cfg["init_scale"]),
            float(cfg["a_init"]), key)}

    tables.of(model, params).init_tables(seed)


def reference_steps(cfg, params, batches):
    """Steps of the rule above in float64 numpy, the margin as the plain
    double sum over a row's pairs of entries. ``params``: {"v", "a": [R,
    k * fields]} over the R rows the batches touch; a batch is {"label":
    [B], "ids": [B, fields] positions into those rows, "values": [B,
    fields]}. The harness hands over compacted positions, not ids, so an
    entry's field is its COLUMN in ``ids`` (this generator writes one id
    of field j in column j), where the program takes it from the id's
    range. Returns the loss of each step and the tables after."""
    fields = len(cfg["field_sizes"])
    rows_, columns = params["v"].shape
    k = columns // fields
    v = params["v"].astype(np.float64).reshape(rows_, k, fields).copy()
    a = params["a"].astype(np.float64).reshape(rows_, k, fields).copy()
    lr, l2 = float(cfg["learning_rate"]), float(cfg["l2"])
    losses = []
    for batch in batches:
        y = batch["label"].astype(np.float64)
        ids = batch["ids"]
        x = batch["values"].astype(np.float64)
        ve = v[ids]  # [B, entry, k, partner's field]
        r = 1.0 / (x * x).sum(axis=1)
        phi = np.zeros(len(y))
        dphi = np.zeros_like(ve)  # dphi/dv[i_e, .] per entry, before r
        for e in range(fields):
            for e2 in range(e + 1, fields):
                xx = x[:, e] * x[:, e2]
                # entry e shows e2's field its vector for that field
                mine, theirs = ve[:, e, :, e2], ve[:, e2, :, e]
                phi += xx * (mine * theirs).sum(axis=1)
                dphi[:, e, :, e2] += xx[:, None] * theirs
                dphi[:, e2, :, e] += xx[:, None] * mine
        phi *= r
        sign = 2.0 * y - 1.0
        losses.append(float(np.mean(np.logaddexp(0.0, -sign * phi))))
        kappa = (1.0 / (1.0 + np.exp(-phi)) - y) / len(y)  # dloss/dphi / B
        # an id's whole gradient first, then the rule, once a touched row
        g = np.zeros_like(v)
        np.add.at(g, ids.ravel(), ((kappa * r)[:, None, None, None]
                                   * dphi).reshape(-1, k, fields))
        t = np.unique(ids[x != 0])
        g[t] += l2 * v[t]
        a[t] += g[t] ** 2
        v[t] -= lr * g[t] / np.sqrt(a[t])
    return losses, {"v": v.reshape(rows_, columns),
                    "a": a.reshape(rows_, columns)}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one step needs for ``batch_rows`` rows,
    counted as kdd12-fm's are: each entry's rows of v and of a (k *
    fields columns each) read once and written once, the batch arrays
    read once; the tables themselves are not counted."""
    columns = int(cfg["num_factors"]) * len(cfg["field_sizes"])
    nnz = batch_rows * int(cfg["nnz_per_row"])
    table = nnz * 2 * columns * 4 * 2
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    # per entry, field and factor: x*v, the (row, field) sum, the pair
    # product and its sum, the transposed read's subtract and two
    # scalings, an id's sum, and the rule's square, add, root, divide,
    # multiply, subtract: about a dozen operations
    return {"bytes": table + batch, "flops": nnz * columns * 12 + nnz * 6}
