"""Device-plane collectives: XLA over ICI/DCN.

This is the TPU replacement for rabit's socket tree/ring (SURVEY §5.8 "TPU
native equivalent"): inside jit, collectives are axis-name primitives
(psum/pmean/all_gather/ppermute) that XLA lowers to ICI AllReduce etc.; at
the host level, cross-process reductions ride a jitted psum over the global
mesh via jax.experimental.multihost_utils.

Byte accounting: in-graph psums are invisible to the host-side
``dmlc_collective_*`` counters (those meter the socket/D2H fallback ops),
but every jit site here goes through ``instrumented_jit``, so the
compile-time analytics hook (obs/xla_cost.py) reads each compiled
program's collective traffic out of its optimized HLO —
``dmlc_xla_collective_bytes{fn="collective.allreduce_step"}`` (and the
SPMD model steps' own labels) is where the in-graph allreduce bytes
surface.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu import obs
from dmlc_tpu.obs.device_telemetry import h2d_meter, instrumented_jit
from dmlc_tpu.utils.logging import DMLCError


def _bitor_reduce(x, axis=0):
    # rabit's bitwise-OR reduce (engine.h AllReduce<op::BitOR>);
    # integer-only, screened in DeviceEngine.allreduce()
    return jax.lax.reduce(
        x, jnp.zeros((), x.dtype), jax.lax.bitwise_or, (axis,)
    )


# The rabit op surface (engine.h op::Sum/Max/Min/BitOR + prod). Single
# source of truth: allreduce() validates against these keys and
# _reduce_fn() compiles from the same table — the two cannot drift.
_REDUCE_OPS = {
    "sum": jnp.sum,
    "max": jnp.max,
    "min": jnp.min,
    "prod": jnp.prod,
    "bitor": _bitor_reduce,
}


# ---- in-jit collectives (use inside shard_map/pjit-ed functions) ----------

def psum(x, axis: str = "dp"):
    """Cross-replica sum over a mesh axis (ICI AllReduce)."""
    return jax.lax.psum(x, axis_name=axis)


def pmean(x, axis: str = "dp"):
    return jax.lax.pmean(x, axis_name=axis)

def pmax(x, axis: str = "dp"):
    return jax.lax.pmax(x, axis_name=axis)


def pmin(x, axis: str = "dp"):
    return jax.lax.pmin(x, axis_name=axis)


def all_gather(x, axis: str = "dp", tiled: bool = False):
    return jax.lax.all_gather(x, axis_name=axis, tiled=tiled)


def ppermute_next(x, axis: str = "dp"):
    """Rotate shards one step around the mesh axis ring — the ICI analog of
    the tracker's ring links (tracker.py:212-225)."""
    size = axis_size(axis)
    perm = [(i, (i + 1) % size) for i in range(size)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def pbitor(x, axis: str = "dp"):
    """Cross-replica bitwise OR (rabit op::BitOR, in-graph). XLA has no
    OR all-reduce primitive, so shards are gathered and folded over the
    gathered dim — order-insensitive, so the result is bit-identical to
    the socket tree's fold regardless of topology. The gather is a psum
    of one-hot slots (each position has exactly one non-zero
    contributor, so the integer sum is exact): unlike ``all_gather``,
    whose result shard_map types as varying, a psum result is invariant
    over ``axis`` and may leave through a replicated ``out_specs``."""
    slots = jnp.zeros((axis_size(axis),) + x.shape, x.dtype)
    slots = slots.at[jax.lax.axis_index(axis)].set(x)
    return _bitor_reduce(jax.lax.psum(slots, axis), axis=0)


def bucketed_psum(tree, axis="dp", bucket: bool = True):
    """In-graph fused gradient allreduce: psum a pytree over ``axis`` with
    ONE collective per dtype. Call inside a jit/shard_map-traced step —
    this is the hot-path reduction the SPMD train steps use, so gradients
    never round-trip through host numpy or ``collective.allreduce``.

    ``bucket=True`` flattens the leaves and concatenates them into
    contiguous per-dtype buffers (dtype-preserving — bf16 grads are never
    silently upcast by a mixed concat), reduces each bucket with a single
    ``lax.psum``, and splits back to the original shapes. Large fused
    buckets are what push ICI utilization toward peak (SURVEY §7 hard
    parts). ``bucket=False`` issues one psum per leaf and leans on XLA's
    all-reduce combiner — kept for A/B measurement
    (bench_collective.grad_bucket_metrics).
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not bucket or len(leaves) <= 1:
        out = [jax.lax.psum(g, axis) for g in leaves]
        return jax.tree.unflatten(treedef, out)
    by_dtype: dict = {}
    for i, g in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(g).dtype, []).append(i)
    out = [None] * len(leaves)
    for idxs in by_dtype.values():
        flat = jnp.concatenate(
            [jnp.reshape(leaves[i], (-1,)) for i in idxs]
        )
        reduced = jax.lax.psum(flat, axis)
        offset = 0
        for i in idxs:
            size = leaves[i].size
            out[i] = jnp.reshape(
                reduced[offset:offset + size], jnp.shape(leaves[i])
            )
            offset += size
    return jax.tree.unflatten(treedef, out)


# ---- host-level collectives over the global device mesh -------------------


class DeviceEngine:
    """Host-callable allreduce/broadcast executing as XLA collectives.

    Single-process: reductions over the local mesh axis. Multi-process (one
    process per TPU host, bootstrapped by jax.distributed.initialize):
    reductions span all hosts over ICI/DCN via a jitted psum on a
    globally-sharded array.
    """

    def __init__(self, mesh: Optional[Mesh] = None, axis: str = "dp"):
        if mesh is None:
            devs = np.asarray(jax.devices())
            mesh = Mesh(devs, (axis,))
        self.mesh = mesh
        self.axis = axis
        self.rank = jax.process_index()
        self.world_size = jax.process_count()
        self._aborted = False
        self._proc_mesh: Optional[Mesh] = None
        self._reduce_fns: dict = {}
        # host-round-trip copy accounting (PR 8 H2D counters): every byte
        # this legacy path stages H2D and copies back D2H is a byte the
        # in-graph SPMD psum path does NOT move — obs-report reads these
        # to attribute exactly what retiring the host path eliminates.
        # None when device telemetry is off (no timing, no byte walk).
        self._h2d = h2d_meter(feed="collective")
        self._m_d2h = (
            obs.registry().counter(
                "dmlc_collective_d2h_bytes_total",
                "device->host result bytes copied back by host-path "
                "collectives (the copy the in-graph SPMD path eliminates)",
                op="allreduce",
            )
            if self._h2d is not None
            else None
        )

    def _process_mesh(self) -> Mesh:
        """(nproc, local) mesh with processes contiguous on the first axis
        — the layout for arrays whose leading dim is one shard per
        process."""
        if self._proc_mesh is None:
            devs = sorted(
                jax.devices(), key=lambda d: (d.process_index, d.id)
            )
            arr = np.asarray(devs).reshape(self.world_size, -1)
            self._proc_mesh = Mesh(arr, ("proc", "_local"))
        return self._proc_mesh

    def _reduce_fn(self, op: str):
        """Jitted [world, ...]-sharded → replicated reduction over dim 0.
        XLA lowers it to a real AllReduce over ICI/DCN: O(N) bytes per
        link, never a [world, N] materialization per host."""
        fn = self._reduce_fns.get(op)
        if fn is None:
            from jax.sharding import NamedSharding

            reduce_fn = _REDUCE_OPS[op]
            out_sharding = NamedSharding(self._process_mesh(), P())
            fn = instrumented_jit(
                lambda x: reduce_fn(x, axis=0),
                "collective.reduce",
                out_shardings=out_sharding,
            )
            self._reduce_fns[op] = fn
        return fn

    @staticmethod
    def _record(what: str, nbytes: int, t0: int) -> None:
        """Count a completed host collective in the obs registry.

        Registered per call — collectives are per-step, not per-row, and
        the registry hands back the same child for a repeated
        (name, labels) pair."""
        reg = obs.registry()
        reg.counter(
            "dmlc_collective_ops_total", "host collectives completed",
            op=what).inc()
        reg.counter(
            "dmlc_collective_moved_bytes_total",
            "payload bytes through host collectives", op=what).inc(nbytes)
        reg.histogram(
            "dmlc_collective_op_ns", "per-op host collective latency",
            op=what).observe(time.monotonic_ns() - t0)

    def _check_live(self) -> None:
        if self._aborted:
            raise DMLCError(
                "device engine aborted (pending recover); reinit before "
                "collectives"
            )

    def _translate(self, err: Exception, what: str) -> DMLCError:
        """Backend failures (Gloo/ICI transport errors, coordination-service
        loss) surface as assorted RuntimeError/ValueError types; collapse
        them into DMLCError so run_with_recovery's default recover_on
        catches device-plane peer failures exactly like socket ones.
        Deterministic user errors are screened out by _validate before the
        collective runs, so what reaches the wrap is transport-shaped."""
        self._aborted = True
        return DMLCError(f"device collective {what} failed: {err}")

    @staticmethod
    def _validate(array) -> np.ndarray:
        """Raise locally (unwrapped) on inputs every rank would reject —
        these must surface as user errors, not trigger recovery."""
        arr = np.asarray(array)
        if arr.dtype.kind not in "fiub":
            raise TypeError(
                f"device collectives need numeric arrays, got dtype "
                f"{arr.dtype}"
            )
        return arr

    def allreduce(self, array: np.ndarray, op: str = "sum") -> np.ndarray:
        """Allreduce a host array across all processes' devices.

        Each process contributes one shard of a [world, ...] device array
        (its leading dim sharded over the process axis) and a jitted
        replicated-output reduction runs as a true XLA AllReduce: O(N)
        traffic and memory per host. This is the data-plane path — large
        gradient arrays ride it, not just control-plane scalars.
        """
        self._check_live()
        arr = self._validate(array)
        if op not in _REDUCE_OPS:
            raise ValueError(f"unknown op {op!r}")
        if op == "bitor" and arr.dtype.kind not in "iub":
            raise TypeError(f"bitor needs an integer dtype, got {arr.dtype}")
        t0 = time.monotonic_ns()
        if self.world_size == 1:
            # Single process owns every device: nothing to reduce across
            # processes; return as-is (matches rabit world=1 semantics).
            self._record("allreduce", int(arr.nbytes), t0)
            return arr
        try:
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self._process_mesh(), P("proc"))
            t_h2d = time.monotonic_ns()
            garr = jax.make_array_from_process_local_data(
                sharding, arr[None], (self.world_size,) + arr.shape
            )
            if self._h2d is not None:
                # the host round-trip's up-leg: this process's shard staged
                # onto device before the reduction can run
                self._h2d.note(int(arr.nbytes), time.monotonic_ns() - t_h2d)
            with obs.span("allreduce", op=op, nbytes=int(arr.nbytes)):
                # mark the in-flight chunk (set by DeviceFeed around the
                # consume yield) so the op slice joins its arrow chain
                obs.flow_step(obs.current_flow(), "chunk")
                out = self._reduce_fn(op)(garr)
            res = np.asarray(out)
            if self._m_d2h is not None:
                # ...and the down-leg: the replicated result copied back to
                # host numpy
                self._m_d2h.inc(int(res.nbytes))
            self._record("allreduce", int(arr.nbytes), t0)
            return res
        except Exception as err:  # noqa: BLE001 — backend error translation
            # deterministic user errors were screened by _validate/op-check
            # above; what reaches here is transport-shaped (ValueError
            # included — see _translate's contract), so mark the engine
            # aborted and let run_with_recovery catch it
            raise self._translate(err, "allreduce") from err

    # fixed-size broadcast header: [ndim, dims[0..7], dtype_num]
    _HDR_SLOTS = 10
    # np.dtype(num) is not a constructor; invert .num over the numeric
    # dtypes the engine supports (kind in "fiub")
    _DTYPE_BY_NUM = {
        np.dtype(t).num: np.dtype(t)
        for t in (
            np.bool_, np.int8, np.int16, np.int32, np.int64,
            np.uint8, np.uint16, np.uint32, np.uint64,
            np.float16, np.float32, np.float64,
        )
    }

    def broadcast(self, array: Optional[np.ndarray], root: int = 0) -> np.ndarray:
        """Broadcast from ``root``; non-root ranks may pass None (rabit
        semantics). broadcast_one_to_all requires every process to supply
        the same array structure, so a fixed-size header round carries
        shape+dtype first and non-roots then contribute matching zeros.

        A root-side validation error travels THROUGH the header (ndim slot
        -1) instead of raising before it: every rank stays in lockstep and
        raises the same TypeError, rather than non-roots hanging in the
        collective while the root errored out locally."""
        from jax.experimental import multihost_utils

        self._check_live()
        is_root = self.rank == root
        t0 = time.monotonic_ns()
        if self.world_size == 1:
            assert array is not None
            arr = self._validate(array)
            self._record("broadcast", int(arr.nbytes), t0)
            return arr
        header = np.zeros(self._HDR_SLOTS, dtype=np.int64)
        arr = header  # placeholder payload when the root's input is invalid
        root_err: Optional[Exception] = None
        if is_root:
            try:
                arr = self._validate(array)
                if arr.ndim > self._HDR_SLOTS - 2:
                    raise ValueError(
                        f"broadcast supports <= {self._HDR_SLOTS - 2} dims, "
                        f"got {arr.ndim}"
                    )
                if arr.dtype.num not in self._DTYPE_BY_NUM:
                    raise TypeError(
                        f"broadcast cannot encode dtype {arr.dtype}; "
                        f"supported: "
                        f"{sorted(str(d) for d in self._DTYPE_BY_NUM.values())}"
                    )
                header[0] = arr.ndim
                header[1 : 1 + arr.ndim] = arr.shape
                header[-1] = arr.dtype.num
            except (TypeError, ValueError) as err:
                root_err = err
                header[0] = -1
        try:
            header = np.asarray(
                multihost_utils.broadcast_one_to_all(header, is_source=is_root)
            )
            if int(header[0]) < 0:
                # root's input was invalid: same user error on every rank,
                # no recovery cascade, engine stays live
                if root_err is not None:
                    raise root_err
                raise TypeError(
                    "broadcast root input was invalid (see root rank log)"
                )
            if not is_root:
                ndim = int(header[0])
                shape = tuple(int(d) for d in header[1 : 1 + ndim])
                arr = np.zeros(shape, dtype=self._DTYPE_BY_NUM[int(header[-1])])
            with obs.span("broadcast", root=root, nbytes=int(arr.nbytes)):
                obs.flow_step(obs.current_flow(), "chunk")
                out = np.asarray(
                    multihost_utils.broadcast_one_to_all(arr, is_source=is_root)
                )
            self._record("broadcast", int(arr.nbytes), t0)
            return out
        except (TypeError, ValueError) as err:
            if err is root_err or int(header[0]) < 0:
                raise  # validated user error, already lockstep
            raise self._translate(err, "broadcast") from err
        except Exception as err:  # noqa: BLE001 — backend error translation
            raise self._translate(err, "broadcast") from err

    def barrier(self) -> None:
        from jax.experimental import multihost_utils

        self._check_live()
        t0 = time.monotonic_ns()
        if self.world_size > 1:
            try:
                with obs.span("barrier"):
                    obs.flow_step(obs.current_flow(), "chunk")
                    multihost_utils.sync_global_devices("dmlc_tpu_barrier")
            except Exception as err:  # noqa: BLE001 — backend translation
                raise self._translate(err, "barrier") from err
        self._record("barrier", 0, t0)

    def abort(self) -> None:
        """Mark the engine dead: collectives fail fast with DMLCError until
        a new engine is built over a re-initialized runtime (the socket
        engine's abort() contract, for the device plane)."""
        self._aborted = True

    def shutdown(self) -> None:
        self._aborted = True


# ---- gradient-sync building block (the BASELINE north-star op) ------------


def make_allreduce_step(mesh: Mesh, axis: str = "dp", bucket: bool = True):
    """Return a jitted f(sharded_grads_pytree) -> summed pytree over the
    mesh axis. Large fused buckets + donation are what push ICI
    utilization ≥90% (SURVEY §7 hard parts).

    ``bucket=True`` (default) GUARANTEES one collective per dtype: leaves
    are flattened, concatenated into a contiguous buffer (grouped by dtype
    — no silent upcasts), reduced with a single psum, and split back.
    ``bucket=False`` issues one psum per leaf and leans on XLA's
    all-reduce combiner heuristics — kept for A/B measurement
    (bench_collective.grad_bucket_metrics) and for models whose step
    already fuses everything into one psum call. The reduction body is
    :func:`bucketed_psum` — the same in-graph primitive the SPMD train
    steps (models/linear.py, models/fm.py) trace directly."""

    def _sum(grads):
        return bucketed_psum(grads, axis=axis, bucket=bucket)

    spec = P(axis)
    return instrumented_jit(
        shard_map(
            _sum,
            mesh=mesh,
            in_specs=spec,
            out_specs=P(),
        ),
        "collective.allreduce_step",
        donate_argnums=(0,),
    )
