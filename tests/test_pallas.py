"""Pallas fused train-step kernel (ops/pallas_kernels.py).

Every call here passes ``interpret=True`` explicitly: Mosaic targets the
TPU only, and product code never infers interpreter mode from the
backend. The same kernels compile with ``interpret=False`` on the chip in
``chip_smoke.py``'s kernel phase.
"""

import numpy as np
import pytest

from dmlc_tpu.ops import pallas_kernels


def _reference(objective, x, y, wgt, w, b):
    margin = x.astype(np.float64) @ w.astype(np.float64) + b
    if objective == "logistic":
        loss = (np.maximum(margin, 0) - margin * y
                + np.log1p(np.exp(-np.abs(margin))))
        dm = 1.0 / (1.0 + np.exp(-margin)) - y
    elif objective == "squared":
        loss = 0.5 * (margin - y) ** 2
        dm = margin - y
    else:
        sy = 2 * y - 1
        loss = np.maximum(0.0, 1 - sy * margin)
        dm = np.where(sy * margin < 1, -sy, 0.0)
    wg = wgt * dm
    return x.T @ wg, wg.sum(), (wgt * loss).sum(), wgt.sum()


@pytest.mark.parametrize("objective", ["logistic", "squared", "hinge"])
def test_fused_grads_parity(objective):
    rng = np.random.RandomState(0)
    n, f = 700, 28  # deliberately unaligned to tile/lane sizes
    x = rng.rand(n, f).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    wgt = rng.rand(n).astype(np.float32)
    w = (rng.randn(f) * 0.1).astype(np.float32)
    gw, gb, ls, ws = pallas_kernels.fused_linear_grads(
        x, y, wgt, w, 0.05, objective=objective, interpret=True
    )
    egw, egb, els, ews = _reference(objective, x, y, wgt, w, 0.05)
    np.testing.assert_allclose(np.asarray(gw), egw, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(gb), egb, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(ls), els, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(ws), ews, rtol=1e-6)


def test_multi_tile_accumulation():
    """Batches spanning several grid steps accumulate exactly."""
    rng = np.random.RandomState(1)
    n, f = 2048, 16
    x = rng.rand(n, f).astype(np.float32)
    y = (rng.rand(n) > 0.5).astype(np.float32)
    wgt = np.ones(n, np.float32)
    w = np.zeros(f, np.float32)
    gw, gb, ls, ws = pallas_kernels.fused_linear_grads(
        x, y, wgt, w, 0.0, tile_b=256, interpret=True
    )
    egw, egb, els, ews = _reference("logistic", x, y, wgt, w, 0.0)
    np.testing.assert_allclose(np.asarray(gw), egw, rtol=1e-5, atol=1e-4)
    assert float(ws) == n


def test_coo_segment_sum_bit_parity():
    """The sparse reduce kernel vs jax.ops.segment_sum on integer-valued
    f32 data: sums are exactly representable, so ANY reduction order must
    produce identical bits — the strongest pin available."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    for entries, rows in ((256, 6), (2048, 4096), (777, 100)):
        rid = rng.randint(0, rows, size=entries).astype(np.int32)
        contrib = rng.randint(-5, 6, size=entries).astype(np.float32)
        contrib[-entries // 8:] = 0.0  # a padded bucket tail
        ref = jax.ops.segment_sum(
            jnp.asarray(contrib), jnp.asarray(rid), num_segments=rows)
        got = pallas_kernels.coo_segment_sum(
            jnp.asarray(contrib), jnp.asarray(rid), rows, interpret=True)
        assert got.shape == (rows,)
        assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_spmv_pallas_matches_xla_spmv():
    from dmlc_tpu.ops.spmv import spmv, spmv_pallas
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    entries, rows, nfeat = 512, 64, 32
    nnz = 400
    values = np.zeros(entries, np.float32)
    values[:nnz] = rng.randint(1, 4, nnz).astype(np.float32)
    indices = np.zeros(entries, np.int32)
    indices[:nnz] = rng.randint(0, nfeat, nnz)
    rid = np.zeros(entries, np.int32)
    rid[:nnz] = np.sort(rng.randint(0, rows, nnz))
    vec = rng.randint(-3, 4, nfeat).astype(np.float32)  # exact products
    ref = spmv(jnp.asarray(values), jnp.asarray(indices),
               jnp.asarray(rid), jnp.asarray(vec), rows)
    got = spmv_pallas(jnp.asarray(values), jnp.asarray(indices),
                      jnp.asarray(rid), jnp.asarray(vec), rows,
                      interpret=True)
    assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_csr_model_step_with_pallas_matches_xla():
    """make_linear_train_step(layout='csr', use_pallas=True) routes the
    margin reduce through the Pallas kernel; the fit must track the XLA
    step to float tolerance (reduction order differs once weights are
    non-integer)."""
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    rows, nfeat, entries = 64, 32, 512
    nnz = 400
    indices = np.zeros(entries, np.int32)
    values = np.zeros(entries, np.float32)
    indices[:nnz] = rng.randint(0, nfeat, nnz)
    values[:nnz] = rng.rand(nnz).astype(np.float32)
    row_of = np.sort(rng.randint(0, rows, nnz))
    offsets = np.zeros(rows + 1, np.int32)
    np.add.at(offsets, row_of + 1, 1)
    offsets = np.cumsum(offsets).astype(np.int32)
    batch = {
        "label": jnp.asarray((rng.rand(rows) > 0.5).astype(np.float32)),
        "weight": jnp.ones(rows, jnp.float32),
        "indices": jnp.asarray(indices),
        "values": jnp.asarray(values),
        "offsets": jnp.asarray(offsets),
    }
    outs = {}
    for use_pallas in (False, True):
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        step = make_linear_train_step(
            None, layout="csr", num_features=nfeat, use_pallas=use_pallas,
            pallas_interpret=True,
        )
        for _ in range(3):
            params, velocity, metrics = step(params, velocity, batch)
        outs[use_pallas] = (np.asarray(params["w"]),
                            float(metrics["loss_sum"]))
    np.testing.assert_allclose(outs[False][0], outs[True][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[False][1], outs[True][1], rtol=1e-5)


def test_model_step_with_pallas_matches_xla():
    """make_linear_train_step(use_pallas=True) reproduces the XLA step."""
    from dmlc_tpu.models.linear import (
        init_linear_params,
        make_linear_train_step,
    )
    import jax.numpy as jnp

    rng = np.random.RandomState(2)
    n, f = 512, 12
    batch = {
        "x": jnp.asarray(rng.rand(n, f).astype(np.float32)),
        "label": jnp.asarray((rng.rand(n) > 0.5).astype(np.float32)),
        "weight": jnp.ones(n, jnp.float32),
    }
    outs = {}
    for use_pallas in (False, True):
        params = init_linear_params(f)
        velocity = {"w": jnp.zeros(f), "b": jnp.zeros(())}
        step = make_linear_train_step(
            None, layout="dense", use_pallas=use_pallas,
            pallas_interpret=True,
        )
        params, velocity, metrics = step(params, velocity, batch)
        outs[use_pallas] = (np.asarray(params["w"]),
                            float(metrics["loss_sum"]))
    np.testing.assert_allclose(outs[False][0], outs[True][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[False][1], outs[True][1], rtol=1e-5)
