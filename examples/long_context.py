"""Long-context attention over a sequence-parallel mesh — runnable demo.

The reference predates long-context training (SURVEY §5.7); this framework
ships the standard schedules TPU-first (docs/distributed.md). This demo
runs all of them on whatever devices exist (a TPU slice, or a virtual CPU
mesh via XLA_FLAGS=--xla_force_host_platform_device_count=8) and checks
each against exact full attention:

    python examples/long_context.py [--seq 512] [--heads 8] [--kv-heads 2]

Schedules shown: ring (contiguous + zigzag layouts, causal, sliding
window) and Ulysses all-to-all; grouped-query attention throughout.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--window", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_tpu.ops import (
        full_attention,
        make_ring_attention,
        make_ulysses_attention,
        zigzag_shard,
        zigzag_unshard,
    )

    devices = np.asarray(jax.devices())
    n = len(devices)
    mesh = Mesh(devices, ("sp",))
    print(f"mesh: {n} x {devices[0].platform} over axis 'sp'")

    t = args.seq - args.seq % (2 * n)  # zigzag needs T % 2N == 0
    if t <= 0:
        print(f"--seq {args.seq} is smaller than 2*num_devices ({2 * n}); "
              f"need at least one sequence chunk per device pair",
              file=sys.stderr)
        return 2
    rng = np.random.RandomState(0)
    q = jnp.asarray(
        rng.randn(1, t, args.heads, args.head_dim).astype(np.float32))
    k = jnp.asarray(
        rng.randn(1, t, args.kv_heads, args.head_dim).astype(np.float32))
    v = jnp.asarray(
        rng.randn(1, t, args.kv_heads, args.head_dim).astype(np.float32))
    print(f"shapes: q[1,{t},{args.heads},{args.head_dim}] "
          f"kv[1,{t},{args.kv_heads},{args.head_dim}] (GQA ratio "
          f"{args.heads // args.kv_heads})")

    def shard(x):
        return jax.device_put(x, NamedSharding(mesh, P(None, "sp")))

    def report(name, got, want):
        err = float(jnp.max(jnp.abs(got - want)))
        ok = err < 1e-3
        print(f"  {name:<42} max|Δ| vs exact = {err:.2e} "
              f"{'ok' if ok else 'MISMATCH'}")
        return ok

    ok = True

    want = full_attention(q, k, v, causal=True)
    ring = make_ring_attention(mesh, causal=True)
    got = ring(shard(q), shard(k), shard(v))
    ok &= report("ring, contiguous, causal", jnp.asarray(got), want)

    ring_zz = make_ring_attention(mesh, causal=True, layout="zigzag")
    got = zigzag_unshard(
        jnp.asarray(ring_zz(shard(zigzag_shard(q, n)),
                            shard(zigzag_shard(k, n)),
                            shard(zigzag_shard(v, n)))), n)
    ok &= report("ring, zigzag (load-balanced), causal", got, want)

    want_w = full_attention(q, k, v, window=args.window)
    ring_w = make_ring_attention(mesh, window=args.window)
    got = ring_w(shard(q), shard(k), shard(v))
    ok &= report(f"ring, sliding window W={args.window}",
                 jnp.asarray(got), want_w)

    if args.heads % n == 0 and args.kv_heads % n == 0:
        want_u = full_attention(q, k, v)
        ulysses = make_ulysses_attention(mesh)
        got = ulysses(shard(q), shard(k), shard(v))
        ok &= report("ulysses all-to-all", jnp.asarray(got), want_u)
    else:
        print(f"  ulysses skipped (heads {args.heads}/{args.kv_heads} do "
              f"not divide over {n} devices)")

    print("all schedules match exact attention" if ok else "MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
