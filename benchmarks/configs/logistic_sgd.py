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
    """Zeros, as the learner starts (no seed to draw from), placed where
    the learner would place them; set here so that the check can read the
    parameters before the first step."""
    import jax.numpy as jnp

    from dmlc_tpu.models.linear import (
        LINEAR_PARTITION_RULES,
        init_linear_params,
    )
    from dmlc_tpu.parallel.partition import shard_params

    params = init_linear_params(int(cfg["num_features"]))
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    if mesh is not None:
        params = shard_params(params, mesh, rules=LINEAR_PARTITION_RULES)
        velocity = shard_params(velocity, mesh, rules=LINEAR_PARTITION_RULES)
    model.params, model.velocity = params, velocity


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
