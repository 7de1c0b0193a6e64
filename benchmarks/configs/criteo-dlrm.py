"""criteo-dlrm: how the configuration in criteo-dlrm.json is generated,
built, checked and counted. Sizes, hyperparameters and their sources are
in the JSON file.

The model is facebookresearch/dlrm's (Naumov et al., arXiv:1906.00091;
``dlrm_s_pytorch.py``, ``--arch-interaction-op=dot`` without the
diagonal). One row: label y, x the 13 dense features, ids i_1..i_26, one
of each table; E the tables' rows, 16 wide:

    h1 = relu(W1 x + b1) [512]; h2 = relu(W2 h1 + b2) [256]; h3 = relu(W3 h2 + b3) [64]; z = relu(W4 h3 + b4) [16]
    e_f = E[i_f]                                   (f = 1..26)
    T = [z; e_1; ...; e_26] [27,16];  P = T T^t;  p = the 351 entries of P strictly under the diagonal, row-major
    r1 = relu(V1 [z; p] + c1) [512]; r2 = relu(V2 r1 + c2) [256]; s = V3 r2 + c3 [1]
    loss = mean over the batch of -(y log sigmoid(s) + (1 - y) log(1 - sigmoid(s)))
    step : theta <- theta - learning_rate * dloss/dtheta, for W, b, V, c and
           for the rows of E the batch names (a row named twice gets the sum)

The dense parameters go by the names ``bot.<l>.w`` (``[out, in]``),
``bot.<l>.b``, ``top.<l>.w``, ``top.<l>.b``. The float64 reference below
writes the forward and the backward pass out from these equations and
imports nothing from ``dmlc_tpu.models``.
"""

import numpy as np

TABLE = "emb"


def _dense(cfg):
    return int(cfg["dense_features"])


def rows(cfg, seed):
    """The file's rows as arrays: the 13 dense ids 1..13 (each a "field"
    of one id) with a real value printed ``%.4f``, then one id of each
    table, ids within a table from a power law, value 1. A dense value is
    ``log(1 + n)``, n = 0 for a fifth of them and else from a geometric
    law; the texts come from a pool (``textgen.value_pool``), whose last
    entry is the tables' ``1``, and ``values`` holds what each text reads
    back as in float32."""
    from harness import textgen

    rng = np.random.default_rng(seed)
    n = int(cfg["rows"])
    dense = _dense(cfg)
    ids = textgen.field_power_law_ids(
        rng, n, [1] * dense + list(cfg["field_sizes"]),
        float(cfg["id_power_law_exponent"]))
    label = (rng.random(n) < float(cfg["positive_rate"])).astype(np.uint8)
    pool = int(cfg["dense_value_pool"])
    counts = rng.geometric(1.0 / float(cfg["dense_count_mean"]), pool)
    counts[rng.random(pool) < float(cfg["dense_zero_share"])] = 0
    texts = textgen.value_pool(np.log1p(counts), cfg["dense_value_format"])
    one = np.zeros((1, texts.shape[1]), np.uint8)
    one[0, :len(cfg["value_text"])] = np.frombuffer(
        cfg["value_text"].encode(), np.uint8)
    read_back = np.array(
        [float(bytes(t[t != 0]).decode()) for t in texts]
        + [float(cfg["value_text"])], np.float32)
    pool_index = np.full(ids.shape, pool, np.int32)
    pool_index[:, :dense] = rng.integers(0, pool, (n, dense), np.int32)
    return {"label": label, "ids": ids, "values": read_back[pool_index],
            "value_text": np.concatenate([texts, one]),
            "pool_index": pool_index}


def learner(cfg, mesh):
    from dmlc_tpu.models.dlrm import DLRMLearner

    if mesh is not None:
        raise SystemExit(
            "criteo-dlrm lives whole on one chip: run it in a cell whose "
            "traffic builds no mesh")
    return DLRMLearner(
        learning_rate=cfg["learning_rate"], num_factors=cfg["num_factors"],
        num_features=cfg["num_features"], dense_features=_dense(cfg),
        field_sizes=cfg["field_sizes"], mlp_bot=cfg["mlp_bot"],
        mlp_top=cfg["mlp_top"])


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``): the table's rows uniform in +-sqrt(1 / rows
    of their table), W normal with sqrt(2 / (m + n)), b with sqrt(1 / m),
    one jitted program for every seed."""
    from harness import tables

    tables.of(model).init_tables(seed)


def _layers(params, net):
    n = sum(k.startswith(net + ".") for k in params) // 2
    return [("%s.%d.w" % (net, i), "%s.%d.b" % (net, i)) for i in range(n)]


def _mlp_forward(p, net, h, last_relu):
    """Returns (output, [(input, pre-activation)] a layer)."""
    kept = []
    layers = _layers(p, net)
    for at, (w, b) in enumerate(layers):
        a = h @ p[w].T + p[b]
        kept.append((h, a))
        h = np.maximum(a, 0.0) if last_relu or at + 1 < len(layers) else a
    return h, kept


def _mlp_backward(p, net, kept, dh, last_relu, grads):
    """``dh``: dloss/d(output); fills ``grads`` and returns
    dloss/d(input)."""
    layers = _layers(p, net)
    for at in reversed(range(len(layers))):
        w, b = layers[at]
        h, a = kept[at]
        da = dh * (a > 0) if last_relu or at + 1 < len(layers) else dh
        grads[w] = da.T @ h
        grads[b] = da.sum(axis=0)
        dh = da @ p[w]
    return dh


def reference_steps(cfg, params, batches):
    """SGD steps of the equations above in float64 numpy, forward and
    backward written out. ``params``: {"emb": [R, 16] over the R rows the
    batches touch, and every dense parameter under its name}; a batch is
    {"label": [B], "ids": [B, 39] positions into those rows, "values":
    [B, 39]}: the first 13 columns are the dense features (their
    ``values``; their positions name rows the model never reads), the
    other 26 the tables' ids, one a table (a value of 0 names none).
    Returns the loss of each step and the parameters after."""
    dense = _dense(cfg)
    lr = float(cfg["learning_rate"])
    p = {k: np.array(v, dtype=np.float64) for k, v in params.items()}
    under = np.tril_indices(len(cfg["field_sizes"]) + 1, -1)  # row-major
    losses = []
    for batch in batches:
        y = batch["label"].astype(np.float64)
        x = batch["values"][:, :dense].astype(np.float64)
        ids = batch["ids"][:, dense:]
        named = batch["values"][:, dense:] != 0
        z, bot = _mlp_forward(p, "bot", x, last_relu=True)
        e = p[TABLE][ids] * named[:, :, None]  # [B, 26, 16]
        t = np.concatenate([z[:, None, :], e], axis=1)  # [B, 27, 16]
        pairs = t @ t.transpose(0, 2, 1)
        r = np.concatenate([z, pairs[:, under[0], under[1]]], axis=1)
        s, top = _mlp_forward(p, "top", r, last_relu=False)
        s = s[:, 0]
        losses.append(float(np.mean(np.logaddexp(0.0, s) - y * s)))
        grads = {}
        ds = (1.0 / (1.0 + np.exp(-s)) - y) / len(y)
        dr = _mlp_backward(p, "top", top, ds[:, None], False, grads)
        dpairs = np.zeros_like(pairs)
        dpairs[:, under[0], under[1]] = dr[:, z.shape[1]:]
        dt = (dpairs + dpairs.transpose(0, 2, 1)) @ t
        _mlp_backward(p, "bot", bot, dr[:, :z.shape[1]] + dt[:, 0], True,
                      grads)
        de = dt[:, 1:] * named[:, :, None]
        for k, g in grads.items():
            p[k] -= lr * g
        np.subtract.at(p[TABLE], ids.ravel(), lr * de.reshape(-1, e.shape[2]))
    return losses, p


def _macs_per_row(cfg):
    """Multiply-adds of the two MLPs' matrix products for one row."""
    return sum(n * m for widths in (cfg["mlp_bot"], cfg["mlp_top"])
               for n, m in zip(widths[:-1], widths[1:]))


def dense_needs(cfg, batch_rows):
    """Operations the dense net's matrix products need for ``batch_rows``
    rows, forward and backward (the gradient of the weights and of the
    input: three products a layer, two operations a multiply-add)."""
    return {"flops": 2 * 3 * _macs_per_row(cfg) * batch_rows}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one step needs for ``batch_rows`` rows,
    counted as kdd12-fm's are: each table entry's row read once and
    written once, the batch arrays read once, the dense parameters read
    and written once; the table itself is not counted. Operations: the
    matrix products (``dense_needs``) and, per table entry and column,
    the interaction's products and sums there and back, the id's sum and
    the update: about 27 * 2 * 3 + 4."""
    k = int(cfg["num_factors"])
    fields = len(cfg["field_sizes"])
    nnz = batch_rows * int(cfg["nnz_per_row"])
    table = batch_rows * fields * k * 4 * 2
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    dense = sum(n * m + m for widths in (cfg["mlp_bot"], cfg["mlp_top"])
                for n, m in zip(widths[:-1], widths[1:])) * 4 * 2
    return {"bytes": table + batch + dense,
            "flops": dense_needs(cfg, batch_rows)["flops"]
            + batch_rows * fields * k * ((fields + 1) * 6 + 4)}
