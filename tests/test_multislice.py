"""Multi-slice (DCN) mesh path: make_multislice_mesh + the hybrid
dp=(dcn, ici) train step.

SURVEY §5.8 names the DCN outer axis as part of the TPU-native equivalent
of the reference's multi-host allreduce; these tests realize it on a
virtual 2x4 CPU mesh (two "slices" of four devices). The parity oracle is
the single-device step over the concatenated batch — hybrid sharding must
not change the math, only the collective routing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_tpu.models.linear import (
    init_linear_params,
    make_linear_train_step,
)
from dmlc_tpu.parallel import make_multislice_mesh
from jax import shard_map


def _mesh_2x4():
    if len(jax.devices()) != 8:
        pytest.skip("needs the virtual 8-device mesh")
    return make_multislice_mesh({"dp": 4}, num_slices=2)


class TestMakeMultisliceMesh:
    def test_shape_and_axis_order(self):
        mesh = _mesh_2x4()
        assert mesh.axis_names == ("dcn", "dp")
        assert mesh.shape["dcn"] == 2 and mesh.shape["dp"] == 4
        # outer axis = slices: consecutive devices stay within one slice
        # row (intra-slice collectives never cross the dcn boundary)
        arr = np.asarray(mesh.devices)
        assert arr.shape == (2, 4)
        ids = [d.id for d in arr[0]] + [d.id for d in arr[1]]
        assert ids == sorted(ids)

    def test_fill_axis(self):
        mesh = make_multislice_mesh({"dp": -1}, num_slices=2)
        assert mesh.shape["dp"] == len(jax.devices()) // 2

    def test_multi_ici_axes(self):
        if len(jax.devices()) != 8:
            pytest.skip("needs the virtual 8-device mesh")
        mesh = make_multislice_mesh({"dp": 2, "mp": 2}, num_slices=2)
        assert mesh.axis_names == ("dcn", "dp", "mp")
        assert dict(mesh.shape) == {"dcn": 2, "dp": 2, "mp": 2}

    def test_bad_slice_count(self):
        with pytest.raises(ValueError, match="do not split"):
            make_multislice_mesh({"dp": -1}, num_slices=3)

    def test_num_slices_required_without_slice_index(self):
        with pytest.raises(ValueError, match="num_slices is required"):
            make_multislice_mesh({"dp": -1})

    def test_bad_ici_product(self):
        with pytest.raises(ValueError, match="devices/slice"):
            make_multislice_mesh({"dp": 3}, num_slices=2)


class _FakeDev:
    def __init__(self, did, slice_index=None):
        self.id = did
        if slice_index is not None:
            self.slice_index = slice_index

    def __repr__(self):
        return f"dev{self.id}"


class TestMultisliceOrder:
    """The grouping policy on reported slice_index, with fake devices
    (real multi-slice hardware is unavailable; CPU devices report none)."""

    def test_hardware_slices_sorted_into_rows(self):
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=d % 2) for d in range(8)]
        ordered, n = _multislice_order(devs, 2)
        assert n == 2
        assert [d.slice_index for d in ordered] == [0] * 4 + [1] * 4

    def test_num_slices_inferred_from_hardware(self):
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=d // 4) for d in range(8)]
        _, n = _multislice_order(devs, None)
        assert n == 2

    def test_single_hardware_slice_allows_virtual_split(self):
        """Real single-slice TPU: every device reports slice_index=0; a
        virtual 2-way split must still work (the dryrun's rehearsal mode
        — regression guard for the all-report-zero case)."""
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=0) for d in range(8)]
        ordered, n = _multislice_order(devs, 2)
        assert n == 2 and len(ordered) == 8

    def test_unequal_hardware_slices_rejected(self):
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=0 if d < 2 else 1)
                for d in range(6)]
        with pytest.raises(ValueError, match="spans slices"):
            _multislice_order(devs, 2)

    def test_fewer_virtual_than_hardware_slices_rejected(self):
        """num_slices that does not tile the hardware slice count would
        put DCN hops inside an ICI axis — rejected."""
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=d // 2) for d in range(8)]
        with pytest.raises(ValueError, match="does not tile"):
            _multislice_order(devs, 2)  # 2 rows over 4 hardware slices

    def test_subdividing_hardware_slices_sorts_first(self):
        """num_slices = k x hardware slices is allowed (each dcn row
        subdivides ONE slice) — and interleaved-reporting devices must be
        sorted so rows never mix slices."""
        from dmlc_tpu.parallel.mesh import _multislice_order

        devs = [_FakeDev(d, slice_index=d % 2) for d in range(8)]
        ordered, n = _multislice_order(devs, 4)
        assert n == 4
        rows = [ordered[i * 2:(i + 1) * 2] for i in range(4)]
        for row in rows:
            assert len({d.slice_index for d in row}) == 1


class TestHybridDpStep:
    def _batch(self, rng, rows, feats):
        return {
            "x": rng.randn(rows, feats).astype(np.float32),
            "label": rng.randint(0, 2, size=rows).astype(np.float32),
            "weight": np.ones(rows, np.float32),
        }

    def test_hybrid_step_matches_single_device(self):
        """(dcn, dp)-sharded hybrid step == single-device step on the same
        global batch, for several steps (parameter trajectories track)."""
        mesh = _mesh_2x4()
        rng = np.random.RandomState(3)
        feats, rows = 12, 64  # rows % (2*4) == 0
        hybrid = make_linear_train_step(
            mesh, learning_rate=0.2, momentum=0.9, axis=("dcn", "dp")
        )
        oracle = make_linear_train_step(None, learning_rate=0.2, momentum=0.9)

        hp = init_linear_params(feats)
        hv = {k: jnp.zeros_like(v) for k, v in hp.items()}
        op = init_linear_params(feats)
        ov = {k: jnp.zeros_like(v) for k, v in op.items()}
        sharding = NamedSharding(mesh, P(("dcn", "dp")))
        for _ in range(4):
            batch = self._batch(rng, rows, feats)
            dev_batch = {
                k: jax.device_put(jnp.asarray(v), sharding)
                for k, v in batch.items()
            }
            hp, hv, hm = hybrid(hp, hv, dev_batch)
            op, ov, om = oracle(op, ov, {
                k: jnp.asarray(v) for k, v in batch.items()
            })
        np.testing.assert_allclose(
            np.asarray(hp["w"]), np.asarray(op["w"]), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(hm["loss_sum"]), np.asarray(om["loss_sum"]),
            rtol=1e-6,
        )

    def test_hybrid_psum_routes_both_axes(self):
        """A shard-local marker psummed over ("dcn", "dp") must see all 8
        shards — i.e. the hybrid reduction really spans slices."""
        mesh = _mesh_2x4()

        def marker():
            return jax.lax.psum(jnp.float32(1.0), ("dcn", "dp"))

        total = jax.jit(
            shard_map(marker, mesh=mesh, in_specs=(), out_specs=P())
        )()
        assert float(total) == 8.0
