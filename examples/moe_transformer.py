"""A sharded MoE-transformer block from this framework's parallel layers.

Composes, on ONE 2-D mesh (dp x sp over whatever devices exist):

- causal RING attention with GQA (seq sharded over sp, batch over dp),
- a switch-MoE FFN (experts sharded over the same sp axis — one axis can
  serve both schedules; tokens ride the identical sharding),
- residual connections and RMSNorm,

and checks the whole block, end to end, against a single-device reference
built from ``full_attention`` + ``moe_dense_oracle``. This is the
composition story: the parallel layers are factories over a shared mesh,
so a model is just Python composition plus one sharding annotation per
tensor (the scaling-book recipe).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    JAX_PLATFORMS=cpu python examples/moe_transformer.py
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2,
                    help="experts per token (1=switch, 2=GShard)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_tpu.ops import (
        full_attention,
        init_moe_params,
        make_moe_layer,
        make_ring_attention,
        moe_dense_oracle,
        shard_moe_params,
    )

    devices = np.asarray(jax.devices())
    n = len(devices)
    if n < 4 or n % 2:
        print(f"need an even device count >= 4, have {n}", file=sys.stderr)
        return 2
    mesh = Mesh(devices.reshape(2, n // 2), ("dp", "sp"))
    sp = mesh.shape["sp"]
    print(f"mesh: dp=2 x sp={sp} ({devices[0].platform})")

    d, h, hk = args.d_model, args.heads, args.kv_heads
    hd = d // h
    t = args.seq - args.seq % (2 * sp)
    b = 2
    e = args.experts - args.experts % sp
    if t <= 0 or e <= 0:
        print(f"--seq {args.seq} / --experts {args.experts} too small for "
              f"sp={sp} (need seq >= {2 * sp}, experts >= {sp})",
              file=sys.stderr)
        return 2

    rng = np.random.RandomState(0)
    params = {
        "wq": jnp.asarray(rng.randn(d, h * hd).astype(np.float32) / np.sqrt(d)),
        "wk": jnp.asarray(rng.randn(d, hk * hd).astype(np.float32) / np.sqrt(d)),
        "wv": jnp.asarray(rng.randn(d, hk * hd).astype(np.float32) / np.sqrt(d)),
        "wo": jnp.asarray(rng.randn(h * hd, d).astype(np.float32) / np.sqrt(d)),
        "moe": init_moe_params(e, d, 4 * d, seed=1),
    }
    x = jnp.asarray(rng.randn(b, t, d).astype(np.float32))

    def rmsnorm(v):
        return v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + 1e-6)

    ring = make_ring_attention(mesh, causal=True, axis="sp", batch_axis="dp")
    # capacity per (device, expert) against LOCAL tokens: top-k expert ids
    # are DISTINCT per token, so an expert receives at most one claim per
    # token — t//sp (= local tokens) is the tight no-drop bound for ANY k
    moe = make_moe_layer(mesh, e, capacity=t // sp, axis="sp",
                         batch_axis="dp", top_k=args.top_k)

    def qkv(v):
        vn = rmsnorm(v)
        q = (vn @ params["wq"]).reshape(b, t, h, hd)
        k = (vn @ params["wk"]).reshape(b, t, hk, hd)
        vv = (vn @ params["wv"]).reshape(b, t, hk, hd)
        return q, k, vv

    # ---- sharded block on the mesh --------------------------------------
    spec = NamedSharding(mesh, P("dp", "sp"))
    xs = jax.device_put(x, spec)
    q, k, v = qkv(xs)
    attn = jnp.asarray(
        ring(jax.device_put(q, spec), jax.device_put(k, spec),
             jax.device_put(v, spec))
    ).reshape(b, t, h * hd)
    y1 = xs + attn @ params["wo"]
    moe_params = shard_moe_params(params["moe"], mesh, axis="sp")
    ffn, aux = moe(moe_params, jax.device_put(rmsnorm(y1), spec))
    y_sharded = np.asarray(y1 + jnp.asarray(ffn))

    # ---- single-device reference ----------------------------------------
    q, k, v = qkv(x)
    attn_ref = full_attention(q, k, v, causal=True).reshape(b, t, h * hd)
    y1_ref = x + attn_ref @ params["wo"]
    ffn_ref, _ = moe_dense_oracle(params["moe"], rmsnorm(y1_ref),
                                  top_k=args.top_k)
    y_ref = np.asarray(y1_ref + ffn_ref)

    err = float(np.max(np.abs(y_sharded - y_ref)))
    print(f"block: ring-attn(GQA {h}q/{hk}kv, causal) + "
          f"MoE(E={e}, top-{args.top_k}) + residuals/RMSNorm over T={t}")
    print(f"max|Δ| sharded vs single-device = {err:.2e} "
          f"(aux={float(aux):.3f})")
    ok = err < 1e-3
    print("block matches the single-device reference" if ok else "MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
