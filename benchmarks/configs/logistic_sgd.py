"""Logistic regression by mini-batch SGD, shared by the configurations that
train ``LinearLearner``: how the learner is built and started, and the
float64 reference, written from the model's equations

    score(x) = b + sum_i w_i x_i
    loss     = log(1 + exp(-score)) for label 1, log(1 + exp(score)) for 0
    step     : theta <- theta - lr * (sum_rows dloss/dtheta) / rows   (l2 = 0)

with the rows of a step summed over the whole batch, whatever chip held
them. Imports nothing from ``dmlc_tpu.models`` for the reference.
"""

import numpy as np


def learner(cfg, mesh):
    from dmlc_tpu.models import LinearLearner

    return LinearLearner(
        mesh=mesh, objective=cfg["objective"],
        learning_rate=cfg["learning_rate"], l2=cfg["l2"],
        momentum=cfg["momentum"], num_features=cfg["num_features"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage (``harness/tables.py``); for
    one that keeps ``params`` and ``velocity`` as named tables: zeros, as
    the learner starts (no seed to draw from), placed where the learner
    places them, so that the check can read the parameters before the
    first step."""
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.models.linear import init_linear_params

    from harness import tables

    def zeros(seed):
        params = init_linear_params(int(cfg["num_features"]))
        return {"params": params,
                "velocity": jax.tree_util.tree_map(jnp.zeros_like, params)}

    tables.of(model, zeros).init_tables(seed)


def reference_steps(cfg, params, batches):
    """SGD steps in float64 numpy. ``params``: {"w": [R], "b": scalar}
    over the R rows the batches touch; a batch is {"label": [B], "ids":
    [B, k] positions into those rows, "values": [B, k]}. Returns the loss
    of each step and the parameters after."""
    w = params["w"].astype(np.float64).copy()
    b = float(params["b"])
    lr = float(cfg["learning_rate"])
    losses = []
    for batch in batches:
        y = batch["label"].astype(np.float64)
        ids = batch["ids"]
        x = batch["values"].astype(np.float64)
        score = b + (x * w[ids]).sum(axis=1)
        sign = 2.0 * y - 1.0
        losses.append(float(np.mean(np.logaddexp(0.0, -sign * score))))
        g = (1.0 / (1.0 + np.exp(-score)) - y) / len(y)
        np.subtract.at(w, ids.ravel(), lr * (g[:, None] * x).ravel())
        b -= lr * g.sum()
    return losses, {"w": w, "b": np.float64(b)}
