"""Expert parallelism: switch-style MoE dispatch over a mesh axis.

The reference predates mixture-of-experts training (SURVEY §2.9 lists no
EP); this realizes the documented extension point the TPU-first way, the
same stance as ``sequence_parallel``:

- experts are SHARDED over the ``ep`` mesh axis (each device owns
  ``num_experts / ep_size`` expert FFNs — model memory scales out);
- tokens stay sharded over the same axis (data-parallel token shards);
- routing is top-1 (switch) or renormalized top-k (GShard) softmax
  gating with a STATIC per-(device, expert)
  capacity (XLA needs static shapes — the standard switch-transformer
  bucketing; over-capacity tokens pass through the residual with zero
  expert output, never a recompile);
- dispatch/return ride ONE ``all_to_all`` each way over the axis
  ([E, C, D] grouped by owning device), the canonical TPU MoE exchange —
  ICI bandwidth, no host involvement.

Parity oracle: ``moe_dense_oracle`` applies every token's routed expert
directly (no capacity, one device); with capacity ≥ tokens the sharded
layer must match it exactly (tests/test_moe.py, 8-device mesh).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.utils.logging import check


def init_moe_params(
    num_experts: int, d_model: int, d_hidden: int, seed: int = 0
) -> Dict:
    """{"wg": [D, E], "w1": [E, D, H], "w2": [E, H, D]} — wg replicated,
    w1/w2 sharded over ep on the expert dim by the layer."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    s1 = 1.0 / np.sqrt(d_model)
    s2 = 1.0 / np.sqrt(d_hidden)
    return {
        "wg": jax.random.normal(k1, (d_model, num_experts)) * s1,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden)) * s1,
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model)) * s2,
    }


def _route_topk(x, wg, num_experts: int, capacity: int, top_k: int):
    """Top-k routing with static capacity → (dispatch, combine, aux).

    x [T, D] (local tokens). dispatch [T, E, C] one-hot over every kept
    (token, choice); combine the same scaled by the RENORMALIZED gate
    probability of each choice (GShard: the k selected probs sum to 1 per
    token). Capacity fills first-choice tokens before second-choice —
    under pressure an expert drops k=2 overflow, not k=1 traffic. Tokens
    whose choice overflows get zero rows for that choice (the residual
    upstream handles them). aux is the switch/GShard load-balancing loss
    on FIRST choices: E * sum_e(frac_e * mean_prob_e)."""
    gates = jax.nn.softmax(x @ wg, axis=-1)  # [T, E]
    probs, ids = jax.lax.top_k(gates, top_k)  # [T, K]
    if top_k > 1:
        # GShard: the selected probs renormalize to a mixture. At k=1 the
        # RAW gate prob scales the output (switch semantics) — dividing
        # would make it exactly 1.0 and cut the router's gradient path
        # through the main output.
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(ids, num_experts, dtype=x.dtype)  # [T, K, E]
    # bucket positions: choice-major order (all first choices claim slots
    # before any second choice) — flatten [K, T, E], exclusive-cumsum
    oh_km = onehot.transpose(1, 0, 2).reshape(top_k * onehot.shape[0],
                                              num_experts)
    pos_flat = jnp.cumsum(oh_km, axis=0) - oh_km
    pos = (
        jnp.sum(pos_flat.reshape(top_k, -1, num_experts)
                .transpose(1, 0, 2) * onehot, axis=-1)
    ).astype(jnp.int32)  # [T, K]
    keep = pos < capacity
    kept = (
        onehot[:, :, :, None]
        * jax.nn.one_hot(pos, capacity, dtype=x.dtype)[:, :, None, :]
        * keep[:, :, None, None]
    )  # [T, K, E, C]
    dispatch = jnp.sum(kept, axis=1)  # [T, E, C]
    combine = jnp.sum(kept * probs[:, :, None, None], axis=1)
    frac = jnp.mean(onehot[:, 0], axis=0)
    mean_prob = jnp.mean(gates, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_dense_oracle(params: Dict, x, top_k: int = 1):
    """Single-device reference: every token through its top-k experts
    (renormalized gate mixture), no capacity limit.
    [B, T, D] -> ([B, T, D], aux)."""
    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    gates = jax.nn.softmax(xt @ params["wg"], axis=-1)
    probs, ids = jax.lax.top_k(gates, top_k)  # [T, K]
    if top_k > 1:
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    y = jnp.zeros_like(xt)
    for kk in range(top_k):
        w1 = params["w1"][ids[:, kk]]  # [T, D, H]
        w2 = params["w2"][ids[:, kk]]  # [T, H, D]
        h = jax.nn.gelu(jnp.einsum("td,tdh->th", xt, w1))
        y = y + jnp.einsum("th,thd->td", h, w2) * probs[:, kk:kk + 1]
    num_experts = params["wg"].shape[1]
    onehot = jax.nn.one_hot(ids[:, 0], num_experts, dtype=x.dtype)
    aux = num_experts * jnp.sum(
        jnp.mean(onehot, axis=0) * jnp.mean(gates, axis=0)
    )
    return y.reshape(b, t, d), aux


def make_moe_layer(
    mesh: Mesh,
    num_experts: int,
    capacity: int,
    axis: str = "ep",
    batch_axis=None,
    top_k: int = 1,
):
    """Jitted f(params, x[B, T, D]) -> (y[B, T, D], aux_loss).

    Tokens sharded over ``axis`` on T; expert weights sharded over the
    expert dim. ``capacity`` is PER (device, expert): each device may send
    at most ``capacity`` of its local tokens to any one expert (static
    shapes — raise it toward local_tokens for a no-drop guarantee).
    ``batch_axis`` (a second mesh axis) composes data parallelism: place x
    with P(batch_axis, axis) and each dp shard routes its own tokens
    independently (expert weights replicated across dp; aux averaged over
    both axes). ``top_k`` selects switch (1, default) or GShard-style
    top-2+ routing with renormalized gate mixtures; capacity admits first
    choices before second.
    """
    ep = mesh.shape[axis]
    check(num_experts % ep == 0,
          "num_experts %d must divide over axis size %d", num_experts, ep)
    check(1 <= top_k <= num_experts,
          "top_k %d must be in [1, %d]", top_k, num_experts)
    e_local = num_experts // ep

    def _local(params, x):
        b, t_local, d = x.shape
        xt = x.reshape(b * t_local, d)
        dispatch, combine, aux = _route_topk(
            xt, params["wg"], num_experts, capacity, top_k
        )
        # gather expert inputs: [E, C, D] with experts numbered
        # contiguously per owning device (expert e lives on device
        # e // e_local)
        xd = jnp.einsum("tec,td->ecd", dispatch, xt)
        # ONE all_to_all each way: trade "my tokens for every expert" for
        # "every device's tokens for my experts". split_axis=0 sends
        # slice [dst] to device dst; the received stack's leading axis
        # indexes the SOURCE device.
        xd = xd.reshape(ep, e_local, capacity, d)
        xd = jax.lax.all_to_all(xd, axis, split_axis=0, concat_axis=0)
        # [ep(source), e_local, C, D] -> [e_local, ep*C, D]: every
        # device's buckets for my experts, grouped per expert
        xd = xd.transpose(1, 0, 2, 3).reshape(e_local, ep * capacity, d)
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xd, params["w1"]))
        y = jnp.einsum("ech,ehd->ecd", h, params["w2"])
        # reverse exchange: slice [dst] = expert outputs for device dst's
        # tokens; received stack = my tokens' outputs by owner device,
        # which is exactly global expert order (contiguous per device)
        y = y.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
        y = y.reshape(num_experts, capacity, d)
        out = jnp.einsum("tec,ecd->td", combine, y)
        # aux is the mean of per-shard switch losses (each shard balances
        # its own routing mix — the standard distributed-MoE practice;
        # equals the global loss only when shards route identically).
        # Averaged over every token-sharding axis so it is replicated.
        aux = jax.lax.pmean(aux, axis_name=axis)
        if batch_axis is not None:
            aux = jax.lax.pmean(aux, axis_name=batch_axis)
        return out.reshape(b, t_local, d), aux

    # batch_axis composes dp on a multi-axis mesh (each dp-shard routes
    # its own tokens; expert weights stay replicated across dp)
    sharded = jax.jit(
        shard_map(
            _local,
            mesh=mesh,
            in_specs=(
                {"wg": P(), "w1": P(axis), "w2": P(axis)},
                P(batch_axis, axis),
            ),
            out_specs=(P(batch_axis, axis), P()),
        )
    )

    def _wrapped(params, x):
        check(x.shape[1] % ep == 0,
              "token dim %d must divide over axis size %d", x.shape[1], ep)
        return sharded(params, x)

    return _wrapped


def shard_moe_params(params: Dict, mesh: Mesh, axis: str = "ep") -> Dict:
    """Place params for :func:`make_moe_layer`: expert weights sharded on
    the expert dim, gate replicated — each device materializes only its
    own experts' FFNs."""
    return {
        "wg": jax.device_put(params["wg"], NamedSharding(mesh, P())),
        "w1": jax.device_put(params["w1"], NamedSharding(mesh, P(axis))),
        "w2": jax.device_put(params["w2"], NamedSharding(mesh, P(axis))),
    }
