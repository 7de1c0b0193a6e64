"""kdd12-fm-difacto: how the configuration in kdd12-fm-difacto.json is
generated, built, checked and counted. Sizes, hyperparameters and their
sources are in the JSON file.

The model, the data and the loss are kdd12-fm's

    score(x) = b + sum_i w_i x_i + 1/2 sum_k [(sum_i v_ik x_i)^2 - sum_i v_ik^2 x_i^2]
    loss     = log(1 + exp(-score)) for label 1, log(1 + exp(score)) for 0

and the update is difacto's (github.com/dmlc/difacto src/sgd/sgd_updater.cc;
Li et al., WSDM 2016). With g_i = (sum_rows dloss/dw_i) / rows and G_i the
same of v_i, for every id i that an entry of the batch names with a value:

    w, FTRL-proximal (McMahan et al., KDD 2013, algorithm 1; state z, n):
        n' = n + g^2;  z' = z + g - (sqrt(n') - sqrt(n)) / alpha * w
        w' = 0 if |z'| <= l1 else -(z' - sign(z') l1) / ((beta + sqrt(n')) / alpha + l2)
    v, AdaGrad per element (Duchi et al., 2011; state a):
        G = G + v_l2 * v;  a' = a + G^2;  v' = v - v_lr * G / (v_beta + sqrt(a'))
    b <- b - alpha * (sum_rows dloss/db) / rows

Every other row keeps its weights and its state. The float64 reference
below is written from these equations and imports nothing from
``dmlc_tpu.models``.
"""

import numpy as np

def rows(cfg, seed):
    """kdd12-fm's rows, from kdd12-fm's own generator (the file beside
    this one): the data is that configuration's, unchanged."""
    import os

    from harness import spec

    return spec.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kdd12-fm.py")).rows(
            cfg, seed)


def learner(cfg, mesh):
    from dmlc_tpu.models import FMLearner

    return FMLearner(
        mesh=mesh, objective=cfg["objective"],
        learning_rate=cfg["learning_rate"], l2=cfg["l2"],
        num_factors=cfg["num_factors"], num_features=cfg["num_features"],
        init_scale=cfg["init_scale"], optimizer=cfg["optimizer"],
        l1=cfg["l1"], lr_beta=cfg["lr_beta"],
        v_learning_rate=cfg["v_learning_rate"], v_lr_beta=cfg["v_lr_beta"],
        v_l2=cfg["v_l2"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``); for one that keeps ``params`` as named tables,
    from the program's own initialiser: one jitted call with the seed as
    an argument (one program for every seed), straight on the device:
    kdd12-fm's weights, the rule's state at zero."""
    from dmlc_tpu.models.fm import init_fm_params

    from harness import tables

    def params(key):
        return {"params": init_fm_params(
            int(cfg["num_features"]), int(cfg["num_factors"]),
            float(cfg["init_scale"]), key,
            optimizer=cfg["optimizer"])}

    tables.of(model, params).init_tables(seed)


def reference_steps(cfg, params, batches):
    """Steps of the rule above in float64 numpy. ``params``: {"w", "z",
    "n": [R], "v", "a": [R, K], "b": scalar} over the R rows the batches
    touch; a batch is {"label": [B], "ids": [B, k] positions into those
    rows, "values": [B, k]}. Returns the loss of each step and the
    parameters and state after."""
    w, z, n, v, a = (params[key].astype(np.float64).copy()
                     for key in ("w", "z", "n", "v", "a"))
    b = float(params["b"])
    alpha, beta = float(cfg["learning_rate"]), float(cfg["lr_beta"])
    l1, l2 = float(cfg["l1"]), float(cfg["l2"])
    v_lr, v_beta = float(cfg["v_learning_rate"]), float(cfg["v_lr_beta"])
    v_l2 = float(cfg["v_l2"])
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
        # an id's whole gradient first, then the rule, once a touched row
        gw = np.zeros_like(w)
        gv = np.zeros_like(v)
        np.add.at(gw, ids.ravel(), (g[:, None] * x).ravel())
        np.add.at(gv, ids.ravel(), (
            (g[:, None] * x)[:, :, None] * (s[:, None, :] - xv)
        ).reshape(-1, v.shape[1]))
        t = np.unique(ids[x != 0])
        root = np.sqrt(n[t] + gw[t] ** 2)
        z[t] += gw[t] - (root - np.sqrt(n[t])) / alpha * w[t]
        n[t] += gw[t] ** 2
        w[t] = np.where(
            np.abs(z[t]) <= l1, 0.0,
            -(z[t] - np.sign(z[t]) * l1) / ((beta + root) / alpha + l2))
        gv[t] += v_l2 * v[t]
        a[t] += gv[t] ** 2
        v[t] -= v_lr * gv[t] / (v_beta + np.sqrt(a[t]))
        b -= alpha * g.sum()
    return losses, {"w": w, "z": z, "n": n, "v": v, "a": a,
                    "b": np.float64(b)}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one step needs for ``batch_rows`` rows,
    counted as kdd12-fm's are, each entry's row read once and written
    once, with the state's columns beside the weights': 2K + 3 columns an
    entry (v, a, w, z, n) whatever layout holds them. The batch arrays
    are read once; the tables themselves are not counted."""
    k = int(cfg["num_factors"])
    nnz = batch_rows * int(cfg["nnz_per_row"])
    table = nnz * (2 * k + 3) * 4 * 2
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    # per entry and factor: kdd12-fm's 10 operations of forward and
    # backward, and the rule's square, add, root, divide, multiply,
    # subtract and decay: about 8 more; per entry FTRL's dozen
    return {"bytes": table + batch, "flops": nnz * k * 18 + nnz * 18}
