"""kdd12-fm-k128-adaptive: how the configuration in
kdd12-fm-k128-adaptive.json is generated, built, checked and counted.
Sizes, hyperparameters and their sources are in the JSON file.

The data is kdd12-fm's, the loss and the rule kdd12-fm-difacto's, the width
kdd12-fm-k128's; what is new is difacto's memory-adaptive constraints
(github.com/dmlc/difacto ``sgd_param.h`` ``V_threshold``, ``l1_shrk``;
Li et al., WSDM 2016). Per id i: w, z, n, an exact count cnt, and whether
it holds factors (has_v); factors v_i, a_i only for the ids that hold
them. One step over a batch of B rows:

    count     while counted_rows < count_rows:
              cnt_i += entries of the batch that name i with a value
    forward   u_i = has_v_i and (w_i != 0 or not l1_shrk), BEFORE the step
              score = b + sum_i w_i x_i
                      + 1/2 sum_k [(sum_i u_i v_ik x_i)^2 - sum_i u_i v_ik^2 x_i^2]
    update    w, z, n by FTRL-proximal for every id named with a value,
              v, a by AdaGrad for the ids with u_i = 1, b by SGD
              (kdd12-fm-difacto's equations, mean gradients)
    activate  in id order: named with a value, not has_v, cnt_i > V_threshold
              and (w'_i != 0 or not l1_shrk) -> has_v while active_ids <
              capacity (else refused += 1); v_i = v0(i), a_i = 0

The float64 reference below is written from these equations and imports
nothing from ``dmlc_tpu.models``. It needs no generator for v0: the learner
answers ``table_rows("v", ids)`` of an id without factors with v0(i).

A run starts where a deployment stands near the end of the source's first
epoch (``init_params``): every id's count as the generator's own law gives
it over ``start_counted_rows`` rows, a factor row for every id counted past
the threshold, weights and state 0. The file's rows are the epoch's last.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_HASH = np.uint32(0x9E3779B9)  # harness/textgen.py's: ranks over a field
_EXACT = 4096  # the first ranks of a field in float64
_CHUNK = 1 << 21


def rows(cfg, seed):
    """kdd12-fm's rows, from kdd12-fm's own generator (the file beside
    this one): the data is that configuration's, unchanged."""
    import os

    from harness import spec

    return spec.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kdd12-fm.py")).rows(
            cfg, seed)


def learner(cfg, mesh):
    from dmlc_tpu.models import AdaptiveFMLearner

    return AdaptiveFMLearner(
        mesh=mesh, objective=cfg["objective"],
        learning_rate=cfg["learning_rate"], l2=cfg["l2"],
        num_factors=cfg["num_factors"], num_features=cfg["num_features"],
        init_scale=cfg["init_scale"], optimizer=cfg["optimizer"],
        l1=cfg["l1"], lr_beta=cfg["lr_beta"],
        v_learning_rate=cfg["v_learning_rate"], v_lr_beta=cfg["v_lr_beta"],
        v_l2=cfg["v_l2"], v_threshold=cfg["v_threshold"],
        l1_shrk=cfg["l1_shrk"], factor_capacity=cfg["factor_capacity"],
        count_rows=cfg["count_rows"])


def counts_at_start(cfg, seed):
    """``int32[num_features]``: how often each id was named in the
    ``start_counted_rows`` rows before the file's, by the law the rows
    are drawn from (``harness/textgen.py``'s ``field_power_law_ids``):
    rank r of a field of size S is named with p(r) = (r^(1-e) -
    (r+1)^(1-e)) / (1 - (S+1)^(1-e)) a row and lands on the id the
    generator's hash gives it (ranks that share an id add up). A rank's
    count is its expectation rounded at random, floor(rows p(r) + u): the
    law's mean and which ids stand just under the threshold, not a
    Poisson draw's spread. From the seed, in a stream of its own."""
    e = float(cfg["id_power_law_exponent"])
    rows = int(cfg["start_counted_rows"])
    out = np.zeros(int(cfg["num_features"]), np.int32)
    streams = np.random.default_rng([int(seed), 1])
    jobs, low = [], 1
    for size in (int(s) for s in cfg["field_sizes"]):
        norm = rows / (1.0 - (size + 1.0) ** (1.0 - e))
        for lo in range(1, size + 1, _CHUNK):
            jobs.append((low, size, norm, lo, min(lo + _CHUNK, size + 1),
                         streams.spawn(1)[0]))
        low += size

    def part(job):
        low, size, norm, lo, hi, rng = job
        rank = np.arange(lo, hi, dtype=np.uint32)
        # p(r) = (e - 1) (r + 1/2)^-e but for 1e-4 / r^2 of it: float32
        # past the first ranks, where the difference itself cancels
        mean = np.float32(norm * (e - 1.0)) * np.exp(np.float32(-e) * np.log(
            rank.astype(np.float32) + np.float32(0.5)))
        if lo < _EXACT:
            top = min(hi, _EXACT)
            edge = np.arange(lo, top + 1, dtype=np.float64) ** (1.0 - e)
            mean = mean.astype(np.float64)
            mean[:top - lo] = norm * (edge[:-1] - edge[1:])
        count = np.floor(
            mean + rng.random(hi - lo, dtype=np.float32)).astype(np.int32)
        within = ((rank * _HASH).astype(np.float32)  # wraps: the hash
                  * np.float32(size / 2.0 ** 32)).astype(np.int32)
        return low, size, np.minimum(within, size - 1), count

    with ThreadPoolExecutor(4) as pool:
        for low, size, within, count in pool.map(part, jobs):
            np.add.at(out[low:low + size], within, count)
    return out


def init_params(cfg, seed, model, mesh):
    """The learner builds its own storage from the seed
    (``harness/tables.py``: it defines all five calls), then takes the
    counts of the rows before the file's and hands every id counted past
    the threshold its factor row (``start_from_counts``): the state a
    deployment's step meets, so that the factor rows a batch reads and
    writes lie over the table a deployment fills and not in its first
    rows. The check's steps and the window start from it."""
    from harness import tables

    tables.of(model).init_tables(seed)
    model.start_from_counts(
        counts_at_start(cfg, seed), int(cfg["start_counted_rows"]))


def reference_steps(cfg, params, batches):
    """Steps of the equations above in float64 numpy. ``params``: {"w",
    "z", "n", "cnt", "has_v": [R], "v", "a": [R, K], "b", "active_ids",
    "refused", "counted_rows": scalars} over the R rows the batches touch,
    in increasing id order (``v`` of a row without factors is its v0); a
    batch is {"label": [B], "ids": [B, k] positions into those rows,
    "values": [B, k]}. Returns the loss of each step and everything
    after."""
    w, z, n, v, a = (params[key].astype(np.float64).copy()
                     for key in ("w", "z", "n", "v", "a"))
    cnt = np.rint(params["cnt"]).astype(np.int64)
    has_v = np.rint(params["has_v"]).astype(bool)
    b = float(params["b"])
    active, refused, counted = (
        int(round(float(params[key])))
        for key in ("active_ids", "refused", "counted_rows"))
    alpha, beta = float(cfg["learning_rate"]), float(cfg["lr_beta"])
    l1, l2 = float(cfg["l1"]), float(cfg["l2"])
    v_lr, v_beta = float(cfg["v_learning_rate"]), float(cfg["v_lr_beta"])
    v_l2 = float(cfg["v_l2"])
    threshold, shrink = int(cfg["v_threshold"]), bool(cfg["l1_shrk"])
    capacity, count_rows = int(cfg["factor_capacity"]), int(cfg["count_rows"])
    losses = []
    for batch in batches:
        y = batch["label"].astype(np.float64)
        ids = batch["ids"]
        x = batch["values"].astype(np.float64)
        named = np.bincount(ids[x != 0], minlength=len(w))
        if counted < count_rows:
            cnt += named
            counted += len(y)
        u = has_v & ((w != 0) | (not shrink))
        # [B, k, K], 92 MB at full size: as few passes over it as may be
        xv = v[ids]
        xv *= (x * u[ids])[:, :, None]
        s = xv.sum(axis=1)  # [B, K]
        score = b + (x * w[ids]).sum(axis=1) + 0.5 * (
            (s * s).sum(axis=1) - np.einsum("bkf,bkf->b", xv, xv))
        sign = 2.0 * y - 1.0
        losses.append(float(np.mean(np.logaddexp(0.0, -sign * score))))
        g = (1.0 / (1.0 + np.exp(-score)) - y) / len(y)  # dloss/dscore / B
        # an id's whole gradient first, then the rule, once a touched row
        gw = np.zeros_like(w)
        gv = np.zeros_like(v)
        np.add.at(gw, ids.ravel(), (g[:, None] * x).ravel())
        np.negative(xv, out=xv)
        xv += s[:, None, :]  # s - xv
        xv *= (g[:, None] * x)[:, :, None]
        np.add.at(gv, ids.ravel(), xv.reshape(-1, v.shape[1]))
        t = np.flatnonzero(named)
        root = np.sqrt(n[t] + gw[t] ** 2)
        z[t] += gw[t] - (root - np.sqrt(n[t])) / alpha * w[t]
        n[t] += gw[t] ** 2
        w[t] = np.where(
            np.abs(z[t]) <= l1, 0.0,
            -(z[t] - np.sign(z[t]) * l1) / ((beta + root) / alpha + l2))
        f = t[u[t]]  # the touched ids whose factors took part
        gv[f] += v_l2 * v[f]
        a[f] += gv[f] ** 2
        v[f] -= v_lr * gv[f] / (v_beta + np.sqrt(a[f]))
        b -= alpha * g.sum()
        for i in t:  # ascending
            if has_v[i] or cnt[i] <= threshold or (shrink and w[i] == 0):
                continue
            if active < capacity:
                has_v[i] = True  # v[i] is v0(i) still, a[i] 0
                active += 1
            else:
                refused += 1
    return losses, {
        "w": w, "z": z, "n": n, "cnt": cnt.astype(np.float64),
        "has_v": has_v.astype(np.float64), "v": v, "a": a,
        "b": np.float64(b), "active_ids": np.float64(active),
        "refused": np.float64(refused), "counted_rows": np.float64(counted)}


def step_needs(cfg, batch_rows):
    """Least bytes and operations one step needs for ``batch_rows`` rows,
    counted as kdd12-fm-difacto's are (each entry's row read once and
    written once, the batch arrays read once, the tables themselves not
    counted): 5 base columns an entry (w, z, n, cnt, slot) and 2 x 128
    factor columns (v, a) for the share of entries whose id holds
    factors in the regime the counts leave behind
    (``active_entry_share``, stated in the JSON file)."""
    k = int(cfg["num_factors"])
    nnz = batch_rows * int(cfg["nnz_per_row"])
    share = float(cfg["active_entry_share"])
    table = nnz * (5 + share * 2 * k) * 4 * 2
    batch = nnz * (4 + 4) + (batch_rows + 1) * 4 + batch_rows * (4 + 4)
    # per entry and factor kdd12-fm-difacto's 18 operations, for the
    # entries at full width; per entry FTRL's dozen, the count and test
    return {"bytes": table + batch,
            "flops": share * nnz * k * 18 + nnz * 20}
