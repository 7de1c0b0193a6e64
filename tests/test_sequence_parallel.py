"""Sequence parallelism (ops/sequence_parallel.py): ring attention and
all-to-all (Ulysses) attention must equal exact full attention on the
8-device mesh — SURVEY §5.7's extension point, realized."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.ops.sequence_parallel import (
    full_attention,
    make_ring_attention,
    make_ulysses_attention,
)


def _mesh(axis="sp"):
    devs = np.asarray(jax.devices())
    return Mesh(devs, (axis,))


def _qkv(rng, b, t, h, d):
    shape = (b, t, h, d)
    return (
        jnp.asarray(rng.randn(*shape).astype(np.float32)),
        jnp.asarray(rng.randn(*shape).astype(np.float32)),
        jnp.asarray(rng.randn(*shape).astype(np.float32)),
    )


def _shard_seq(mesh, x, axis="sp"):
    return jax.device_put(x, NamedSharding(mesh, P(None, axis)))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(0)
        q, k, v = _qkv(rng, b=2, t=8 * n, h=4, d=16)
        want = full_attention(q, k, v, causal=causal)

        ring = make_ring_attention(mesh, causal=causal)
        got = ring(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_output_stays_sequence_sharded(self):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(1)
        q, k, v = _qkv(rng, b=1, t=4 * n, h=2, d=8)
        ring = make_ring_attention(mesh)
        out = ring(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        # each device holds only its sequence shard of the output
        assert out.addressable_shards[0].data.shape[1] == 4

    def test_long_sequence_never_materializes_full_scores(self):
        """The schedule's point: T x T never exists. Indirect check — a
        sequence whose full score matrix would be big still runs, and the
        jitted HLO contains no [T, T]-shaped intermediate."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        t = 64 * n
        rng = np.random.RandomState(2)
        q, k, v = _qkv(rng, b=1, t=t, h=1, d=8)
        ring = make_ring_attention(mesh)
        lowered = jax.jit(ring).lower(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        text = lowered.as_text()
        # MLIR renders shapes as NxM: the global score matrix would appear
        # as e.g. tensor<...512x512xf32> (it does in full_attention's HLO)
        assert f"{t}x{t}" not in text
        assert f"{t}x{t}" in jax.jit(full_attention).lower(q, k, v).as_text()
        out = ring(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        want = full_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(3)
        # heads must divide over the axis
        q, k, v = _qkv(rng, b=2, t=4 * n, h=n, d=16)
        want = full_attention(q, k, v, causal=causal)
        ulysses = make_ulysses_attention(mesh, causal=causal)
        got = ulysses(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_head_divisibility_checked(self):
        mesh = _mesh()
        n = mesh.shape["sp"]
        if n == 1:
            pytest.skip("needs >1 device to violate divisibility")
        rng = np.random.RandomState(4)
        q, k, v = _qkv(rng, b=1, t=2 * n, h=n + 1, d=8)
        ulysses = make_ulysses_attention(mesh)
        from dmlc_tpu.utils.logging import DMLCError

        with pytest.raises(DMLCError, match="heads"):
            ulysses(q, k, v)

    def test_custom_local_kernel_plugs_in(self):
        """local_attention hook: a Pallas flash kernel would slot in the
        same way this scaled replacement does."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(5)
        q, k, v = _qkv(rng, b=1, t=2 * n, h=n, d=8)

        calls = []

        def spy_kernel(q_, k_, v_):
            calls.append(q_.shape)
            return full_attention(q_, k_, v_)

        ulysses = make_ulysses_attention(mesh, local_attention=spy_kernel)
        got = ulysses(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        want = full_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )
        # the kernel saw the full sequence with the head shard
        assert calls and calls[0][1] == 2 * n and calls[0][2] == 1


class TestGradients:
    def test_ring_attention_differentiable(self):
        """The schedule must train, not just infer: grads flow through the
        scan + ppermute and match full attention's grads."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(6)
        q, k, v = _qkv(rng, b=1, t=4 * n, h=2, d=8)
        ring = make_ring_attention(mesh)

        def loss_ring(q_, k_, v_):
            return jnp.sum(ring(q_, k_, v_) ** 2)

        def loss_full(q_, k_, v_):
            return jnp.sum(full_attention(q_, k_, v_) ** 2)

        g_ring = jax.grad(loss_ring)(q, k, v)
        g_full = jax.grad(loss_full)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_full), rtol=5e-4, atol=5e-5
        )

    def test_causal_with_custom_kernel_rejected(self):
        from dmlc_tpu.utils.logging import DMLCError

        mesh = _mesh()
        with pytest.raises(DMLCError, match="local_attention"):
            make_ulysses_attention(
                mesh, causal=True, local_attention=full_attention
            )


class TestPallasFlashLocal:
    """The wrapper around the Mosaic kernel, with spies (Mosaic lowers on
    the TPU only and this suite is pinned to the CPU); the real kernel
    compiles and is compared with ``full_attention`` at T=2048 causal in
    ``chip_smoke.py``'s kernels phase."""

    def test_layout_adapter(self, monkeypatch):
        """The wrapper transposes [B,T,H,D] <-> [B,H,T,D] around the kernel
        and passes sm_scale; verified with a spy standing in for the Mosaic
        kernel (which only lowers on TPU)."""
        import dmlc_tpu.ops.sequence_parallel as sp

        seen = {}

        def fake_flash(q, k, v, *, causal, sm_scale, block_sizes):
            seen["shape"] = q.shape
            seen["causal"] = causal
            seen["sm_scale"] = sm_scale
            # exact reference in the kernel's own layout
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
            if causal:
                t = s.shape[-1]
                s = jnp.where(
                    jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30
                )
            return jnp.einsum(
                "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v
            )

        import jax.experimental.pallas.ops.tpu.flash_attention as fa

        monkeypatch.setattr(fa, "flash_attention", fake_flash)
        rng = np.random.RandomState(7)
        b, t, h, d = 2, 16, 4, 8
        q, k, v = _qkv(rng, b=b, t=t, h=h, d=d)
        kernel = sp.make_pallas_flash_local(causal=True)
        out = kernel(q, k, v)
        assert seen["shape"] == (b, h, t, d)  # kernel-layout transpose
        assert seen["causal"] is True
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(full_attention(q, k, v, causal=True)),
            rtol=2e-4, atol=2e-5,
        )

    def test_auto_blocks_divide_awkward_t(self, monkeypatch):
        """Auto block sizes must divide the sequence length (Pallas
        divisibility contract), including non-power-of-two T."""
        import dmlc_tpu.ops.sequence_parallel as sp

        seen = {}

        def fake_flash(q, k, v, *, causal, sm_scale, block_sizes):
            seen["bs"] = block_sizes
            return q

        import jax.experimental.pallas.ops.tpu.flash_attention as fa

        monkeypatch.setattr(fa, "flash_attention", fake_flash)
        rng = np.random.RandomState(9)
        for t in (1536, 3072, 1024, 256):
            q, k, v = _qkv(rng, b=1, t=t, h=1, d=8)
            sp.make_pallas_flash_local()(q, k, v)
            bs = seen["bs"]
            assert t % bs.block_q == 0 and t % bs.block_k_major == 0, t
            # backward blocks fully specified: the kernel trains
            assert bs.has_backward_blocks, t


class TestGroupedQueryAttention:
    """GQA/MQA: H_kv < H with H % H_kv == 0 (llama-class long-context
    models). The oracle is explicit KV-head repetition through classic
    MHA; the grouped path must match it bit-for-tolerance, on the single
    device and through both sharded schedules."""

    def _gqa_qkv(self, rng, b, t, h, hk, d):
        q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, t, hk, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, t, hk, d).astype(np.float32))
        return q, k, v

    @pytest.mark.parametrize("hk", [1, 2, 4])  # MQA .. MHA
    @pytest.mark.parametrize("causal", [False, True])
    def test_full_attention_gqa_matches_repeated_mha(self, hk, causal):
        rng = np.random.RandomState(20)
        q, k, v = self._gqa_qkv(rng, b=2, t=16, h=4, hk=hk, d=8)
        got = full_attention(q, k, v, causal=causal)
        rep = 4 // hk
        want = full_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            causal=causal,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_head_divisibility_enforced(self):
        from dmlc_tpu.utils.logging import DMLCError

        rng = np.random.RandomState(21)
        q, k, v = self._gqa_qkv(rng, b=1, t=8, h=4, hk=3, d=8)
        with pytest.raises(DMLCError):
            full_attention(q, k, v)

    def test_kv_head_mismatch_rejected(self):
        """k/v head disagreement must be an error, never silent mis-pairing
        (the classic MHA einsum made it a shape error; GQA keeps that)."""
        from dmlc_tpu.utils.logging import DMLCError

        rng = np.random.RandomState(26)
        q = jnp.asarray(rng.randn(1, 8, 4, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 8, 4, 8).astype(np.float32))
        with pytest.raises(DMLCError):
            full_attention(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention_gqa(self, causal):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(22)
        q, k, v = self._gqa_qkv(rng, b=2, t=8 * n, h=8, hk=2, d=16)
        want = full_attention(q, k, v, causal=causal)
        ring = make_ring_attention(mesh, causal=causal)
        got = ring(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_ulysses_gqa(self):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(23)
        # kv heads must also divide over the axis: hk = n, h = 2n
        q, k, v = self._gqa_qkv(rng, b=2, t=4 * n, h=2 * n, hk=n, d=16)
        want = full_attention(q, k, v)
        ulysses = make_ulysses_attention(mesh)
        got = ulysses(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_ulysses_rejects_indivisible_kv_heads(self):
        from dmlc_tpu.utils.logging import DMLCError

        mesh = _mesh()
        n = mesh.shape["sp"]
        if n == 1:
            pytest.skip("needs a real axis")
        rng = np.random.RandomState(24)
        q, k, v = self._gqa_qkv(rng, b=1, t=4 * n, h=2 * n, hk=1, d=8)
        ulysses = make_ulysses_attention(mesh)
        with pytest.raises(DMLCError):
            ulysses(
                _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
            )

    def test_ring_gqa_gradients_match(self):
        """Gradients flow through the grouped path identically to the
        repeated-MHA oracle (training parity, not just inference)."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(25)
        q, k, v = self._gqa_qkv(rng, b=1, t=4 * n, h=4, hk=2, d=8)
        ring = make_ring_attention(mesh, causal=True)

        def loss_ring(q, k, v):
            return jnp.sum(
                ring(_shard_seq(mesh, q), _shard_seq(mesh, k),
                     _shard_seq(mesh, v)) ** 2
            )

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
            )


class TestSlidingWindow:
    """Mistral-style sliding-window attention: query p attends (p-W, p].
    Oracle = explicit banded mask; the ring schedule must match exactly
    INCLUDING its block-skip shortcut for out-of-window hops."""

    def _oracle(self, q, k, v, window):
        d = q.shape[-1]
        t = q.shape[1]
        rep = q.shape[2] // k.shape[2]
        kk = jnp.repeat(k, rep, axis=2)
        vv = jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(float(d))
        qp = jnp.arange(t)[:, None]
        kp = jnp.arange(t)[None, :]
        mask = (qp >= kp) & ((qp - kp) < window)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    @pytest.mark.parametrize("window", [1, 5, 16, 1000])
    def test_full_attention_window_matches_banded_oracle(self, window):
        rng = np.random.RandomState(30)
        q = jnp.asarray(rng.randn(2, 24, 4, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 24, 2, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 24, 2, 8).astype(np.float32))
        got = full_attention(q, k, v, window=window)
        want = self._oracle(q, k, v, window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    @pytest.mark.parametrize("window", [3, 8, 17, 10_000])
    def test_ring_attention_window(self, window):
        """Windows smaller than, equal to, straddling, and larger than the
        per-device shard — the block-skip boundary cases."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(31)
        t = 8 * n
        q = jnp.asarray(rng.randn(2, t, 4, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(2, t, 2, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(2, t, 2, 16).astype(np.float32))
        want = full_attention(q, k, v, window=window)
        ring = make_ring_attention(mesh, window=window)
        got = ring(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_ulysses_window(self):
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(32)
        t = 4 * n
        q = jnp.asarray(rng.randn(1, t, 2 * n, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(1, t, n, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(1, t, n, 8).astype(np.float32))
        want = full_attention(q, k, v, window=7)
        ulysses = make_ulysses_attention(mesh, window=7)
        got = ulysses(
            _shard_seq(mesh, q), _shard_seq(mesh, k), _shard_seq(mesh, v)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )


    def test_negative_window_rejected(self):
        from dmlc_tpu.utils.logging import DMLCError

        rng = np.random.RandomState(33)
        q = jnp.asarray(rng.randn(1, 8, 2, 8).astype(np.float32))
        with pytest.raises(DMLCError):
            full_attention(q, q, q, window=-3)
        with pytest.raises(DMLCError):
            make_ring_attention(_mesh(), window=-1)

    def test_ring_window_gradients_match(self):
        """Gradients through the window-dependent block-skip cond equal the
        banded-oracle gradients (the skipped branch must thread m/l/o
        untouched in the backward pass too)."""
        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(34)
        t = 4 * n
        q = jnp.asarray(rng.randn(1, t, 4, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        window = 5  # straddles shard boundaries at t_local=4
        ring = make_ring_attention(mesh, window=window)

        def loss_ring(q, k, v):
            return jnp.sum(
                ring(_shard_seq(mesh, q), _shard_seq(mesh, k),
                     _shard_seq(mesh, v)) ** 2
            )

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, window=window) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
            )


class TestZigzagRing:
    """Zigzag layout for causal ring attention: device i holds chunks
    (i, 2N-1-i), balancing causal work across the ring. Parity oracle:
    zigzag_shard → ring(layout=zigzag) → zigzag_unshard == full attention
    on the natural order."""

    def test_shard_unshard_roundtrip(self):
        from dmlc_tpu.ops.sequence_parallel import (
            zigzag_shard, zigzag_unshard,
        )

        rng = np.random.RandomState(40)
        x = jnp.asarray(rng.randn(2, 48, 3, 4).astype(np.float32))
        y = zigzag_unshard(zigzag_shard(x, 4), 4)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    @pytest.mark.parametrize("window", [0, 6])
    def test_zigzag_causal_parity(self, window):
        from dmlc_tpu.ops.sequence_parallel import (
            zigzag_shard, zigzag_unshard,
        )

        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(41)
        t = 4 * n  # = 2N chunks of 2
        q = jnp.asarray(rng.randn(2, t, 4, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(2, t, 2, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(2, t, 2, 16).astype(np.float32))
        want = full_attention(q, k, v, causal=True, window=window)

        ring = make_ring_attention(
            mesh, causal=True, window=window, layout="zigzag"
        )
        zz = lambda x: _shard_seq(mesh, zigzag_shard(x, n))
        got = zigzag_unshard(
            jnp.asarray(ring(zz(q), zz(k), zz(v))), n
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_zigzag_gradients_match(self):
        from dmlc_tpu.ops.sequence_parallel import (
            zigzag_shard, zigzag_unshard,
        )

        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(42)
        t = 4 * n
        q = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        ring = make_ring_attention(mesh, causal=True, layout="zigzag")

        def loss_ring(q, k, v):
            zz = lambda x: _shard_seq(mesh, zigzag_shard(x, n))
            out = zigzag_unshard(jnp.asarray(ring(zz(q), zz(k), zz(v))), n)
            return jnp.sum(out ** 2)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
            )

    def test_zigzag_seq_divisibility_enforced(self):
        from dmlc_tpu.utils.logging import DMLCError

        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(43)
        t = 3 * n  # not divisible by 2N when n even... ensure odd multiple
        if t % (2 * n) == 0:
            t += n
        q = jnp.asarray(rng.randn(1, t, 2, 8).astype(np.float32))
        ring = make_ring_attention(mesh, causal=True, layout="zigzag")
        with pytest.raises((DMLCError, ValueError)):
            ring(_shard_seq(mesh, q), _shard_seq(mesh, q),
                 _shard_seq(mesh, q))


class TestRematRing:
    @pytest.mark.parametrize("layout,window", [
        ("contiguous", 0),
        ("contiguous", 6),   # window-skip cond under checkpoint
        ("zigzag", 0),       # zigzag branch under checkpoint
    ])
    def test_remat_matches_forward_and_gradients(self, layout, window):
        """remat=True must be numerically invisible: same outputs, same
        gradients — only the backward's memory/recompute trade changes.
        Covers every step-branch shape jax.checkpoint traces through."""
        from dmlc_tpu.ops.sequence_parallel import (
            zigzag_shard, zigzag_unshard,
        )

        mesh = _mesh()
        n = mesh.shape["sp"]
        rng = np.random.RandomState(50)
        t = 8 * n
        q = jnp.asarray(rng.randn(1, t, 4, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, t, 2, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, t, 2, 16).astype(np.float32))
        if layout == "zigzag":
            q, k, v = (zigzag_shard(x, n) for x in (q, k, v))
        plain = make_ring_attention(mesh, causal=True, window=window,
                                    layout=layout)
        remat = make_ring_attention(mesh, causal=True, window=window,
                                    layout=layout, remat=True)

        def loss(fn):
            def _l(q, k, v):
                return jnp.sum(
                    fn(_shard_seq(mesh, q), _shard_seq(mesh, k),
                       _shard_seq(mesh, v)) ** 2
                )
            return _l

        np.testing.assert_allclose(
            np.asarray(remat(_shard_seq(mesh, q), _shard_seq(mesh, k),
                             _shard_seq(mesh, v))),
            np.asarray(plain(_shard_seq(mesh, q), _shard_seq(mesh, k),
                             _shard_seq(mesh, v))),
            rtol=1e-6, atol=1e-7,
        )
        g1 = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(remat), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )
