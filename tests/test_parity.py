"""CPU↔TPU bit-parity harness (tools/parity.py).

The reduction-order construction must make a W-process socket-engine run
and a single-process run BIT-IDENTICAL on the same backend — for any
world size and either topology (the [W, N] slot exchange is exact under
any fold order because 0.0 + x == x bitwise). Cross-backend (the chip
path) reuses the same harness with a measured tolerance; on the CPU test
mesh both paths share a backend, so bitexact is the assertion here.
"""

import numpy as np

from dmlc_tpu.tools.parity import _ulp_diff, run_parity


class TestUlpDiff:
    def test_zero_for_identical(self):
        a = np.array([1.5, -2.25, 0.0, 3e-9], np.float32)
        assert _ulp_diff(a, a.copy()) == 0

    def test_one_ulp_neighbors(self):
        a = np.array([1.0], np.float32)
        b = np.nextafter(a, np.float32(2.0))
        assert _ulp_diff(a, b) == 1

    def test_across_zero(self):
        a = np.array([np.float32(-1e-45)])  # smallest negative subnormal
        b = np.array([np.float32(1e-45)])
        assert _ulp_diff(a, b) == 2


class TestBitExactParity:
    def test_world2_tree_bitexact(self):
        out = run_parity(world=2, steps=3, single_backend="cpu")
        assert out["bitexact"] is True
        assert out["max_grad_ulp"] == 0
        assert out["max_param_abs_diff"] == 0.0
        assert out["socket_losses"] == out["single_losses"]
        assert out["pass"] is True

    def test_world3_forced_ring_bitexact(self):
        """Ring reduce-scatter folds in a completely different order than
        the tree — the slot exchange must make that invisible."""
        out = run_parity(world=3, steps=2, force_ring=True,
                         single_backend="cpu")
        assert out["topology"] == "ring"
        assert out["bitexact"] is True
        assert out["max_grad_ulp"] == 0
        assert out["pass"] is True


class TestCrossBackendArm:
    """The rtol comparison arm (the criterion a chip run uses) must be
    proven on the CPU: a wrong rtol plumb or a broken pass/exit path
    would otherwise only surface on the chip (chip_smoke.py's
    cpu_children phase). The 'reordered'/'perturbed' kernels are
    CPU-only stand-ins for a second backend's accumulation-order and
    transcendental-rounding differences."""

    def test_reordered_kernel_passes_rtol(self):
        out = run_parity(world=2, steps=2, single_backend="cpu",
                         single_kernel="reordered", criterion="rtol")
        assert out["criterion"] == "rtol"
        assert out["bitexact"] is False        # grads really differ
        assert out["max_grad_ulp"] > 0
        assert out["max_loss_rel"] <= out["rtol"]
        assert out["pass"] is True             # ...but within tolerance

    def test_perturbed_kernel_pass_and_fail_by_rtol(self):
        """The same measured loss divergence passes a realistic tolerance
        and fails a too-tight one — both directions of the criterion.
        The pass-side rtol (1e-3) sits well above the divergence range
        the perturbed kernel can produce (~1e-7..1e-4), so the test can't
        go red from a jax/libm version nudging the rounding."""
        out = run_parity(world=2, steps=3, single_backend="cpu",
                         single_kernel="perturbed", criterion="rtol",
                         rtol=1e-3)
        assert 0.0 < out["max_loss_rel"] <= 1e-3
        assert out["pass"] is True
        tight = run_parity(world=2, steps=3, single_backend="cpu",
                           single_kernel="perturbed", criterion="rtol",
                           rtol=out["max_loss_rel"] / 10)
        assert tight["pass"] is False

    def test_auto_criterion_stays_bitexact_on_same_backend(self):
        out = run_parity(world=2, steps=2, single_backend="cpu")
        assert out["criterion"] == "bitexact"
        assert out["pass"] is True
