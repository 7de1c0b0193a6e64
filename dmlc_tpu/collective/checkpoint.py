"""Rabit-style checkpoint / resume over the Stream-to-URI surface.

The reference provides the *building blocks* for checkpointing —
``Serializable`` Load/Save over any ``Stream::Create`` URI (io.h:112-126),
STL serialization (serializer.h), ``Parameter::Save/Load`` — while the
checkpoint *policy* (rabit's CheckPoint/LoadCheckPoint/version_number used
for fault recovery with the tracker's ``recover`` re-entry,
tracker.py:279-291) lives downstream. The TPU build owes that policy: this
module implements it against any filesystem backend (file://, gs://, s3://,
mem://), so a restarted worker re-joins with ``cmd='recover'`` and restores
the last committed global state.

Layout under the checkpoint URI directory::

    ckpt_v{N}.bin          global state, written by rank 0 (or all ranks
                           when ``per_rank=True``: ckpt_v{N}.rank{R}.bin)
    LATEST                 text pointer "N" — committed last, so a torn
                           write of the state file is never visible

jax arrays in the state tree are converted to host numpy on save (the
device-buffer (de)serialization path SURVEY §5.4 calls for).

:class:`JobSnapshot` layers a coordinated *job*-level snapshot on the
same surface: every rank writes its own ``snap_v{N}.rank{R}`` part
(model + data-plane frontier + RNG + audit heads), rank 0 waits for all
parts of the version to land, then commits a crc-guarded manifest
naming every part — a two-phase commit where a torn or partial write is
never visible to :meth:`JobSnapshot.restore`. See
docs/robustness.md "Preemption & resume".
"""

from __future__ import annotations

import json
import struct
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from dmlc_tpu.io.filesystem import URI, create_stream, get_filesystem
from dmlc_tpu.io.serializer import load_obj, save_obj
from dmlc_tpu.io.stream import MemoryStream
from dmlc_tpu.utils.logging import DMLCError, check, log_warning


def _to_host(tree: Any) -> Any:
    """Device arrays → numpy, recursively, without requiring jax.

    Always a REAL copy, never a view: on the cpu backend
    ``np.asarray(jax_array)`` can alias the device buffer zero-copy, and
    the async snapshot writer serializes these trees while the next
    epoch's donating train steps are already reusing the donated
    buffers — an aliased "copy" would mutate under the writer (or
    outlive a freed buffer). ``np.array(..., copy=True)`` is the
    donation-safe boundary.

    An array divided over several devices (a table no single chip
    holds) is assembled shard by shard into the one logical array:
    through ``np.array`` it would first be gathered into a copy cached
    on the device array and then copied again, twice the table's bytes
    on the host for as long as the device array lives."""
    if isinstance(tree, dict):
        return {k: _to_host(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        mapped = [_to_host(v) for v in tree]
        if isinstance(tree, tuple):
            # NamedTuples (e.g. optax optimizer states) take *fields
            if hasattr(type(tree), "_fields"):
                return type(tree)(*mapped)
            return tuple(mapped)
        return mapped
    if hasattr(tree, "__array__") and not isinstance(tree, np.ndarray):
        if _is_divided(tree):
            out = np.empty(tree.shape, dtype=tree.dtype)
            for shard in tree.addressable_shards:
                if shard.replica_id == 0:  # one copy of each part
                    out[shard.index] = np.asarray(shard.data)
            return out
        return np.array(tree, copy=True)
    return tree


def _is_divided(arr: Any) -> bool:
    """A jax array whose devices each hold a PART of it, all of them
    addressable from this process (a replicated or single-device array,
    and anything that is not a jax array, is not)."""
    return (
        hasattr(arr, "addressable_shards")
        and not getattr(arr, "is_fully_replicated", True)
        and getattr(arr, "is_fully_addressable", False)
    )


class CheckpointManager:
    """CheckPoint / LoadCheckPoint / version_number (rabit API surface).

    ``rank`` selects the writer: by default only rank 0 commits the global
    state (every rank calls ``checkpoint`` — non-writers just bump their
    version, mirroring rabit where the global model is logically one).
    ``per_rank=True`` writes one state file per rank (rabit's local model)
    and loads this rank's own file.

    ``fallback_uri`` (default: the ``DMLC_TPU_CKPT_FALLBACK_URI`` knob) is
    the graceful-degradation path: when a commit to the primary URI fails
    even after the io layer's retries, the same version is committed to
    the fallback directory instead of losing the snapshot, and
    ``load_checkpoint`` considers both locations (newest committed version
    wins). Meant for a second failure domain — e.g. primary on an object
    store, fallback on local disk.
    """

    def __init__(
        self,
        uri: str,
        rank: int = 0,
        world_size: int = 1,
        per_rank: bool = False,
        keep: int = 2,
        fallback_uri: Optional[str] = None,
    ):
        check(keep >= 1, "keep must be >= 1")
        self.uri = uri.rstrip("/")
        self.rank = rank
        self.world_size = world_size
        self.per_rank = per_rank
        self.keep = keep
        if fallback_uri is None:  # "" explicitly disables the env knob
            from dmlc_tpu.params.knobs import ckpt_fallback_uri

            fallback_uri = ckpt_fallback_uri()
        fallback_uri = (fallback_uri or "").rstrip("/") or None
        if fallback_uri is not None:
            check(fallback_uri != self.uri,
                  "fallback checkpoint URI must differ from the primary")
        self._fallback_uri = fallback_uri
        self._fallback: Optional["CheckpointManager"] = None
        parsed = URI.parse(self.uri)
        if parsed.protocol in ("file://", ""):
            import os

            os.makedirs(parsed.name, exist_ok=True)
        self._version = 0
        latest = self._read_latest()
        if latest is not None:
            self._version = latest

    # ---- rabit surface -------------------------------------------------
    @property
    def version_number(self) -> int:
        """Number of committed checkpoints (rabit VersionNumber)."""
        return self._version

    def checkpoint(self, state: Any) -> int:
        """Commit ``state`` as version ``version_number + 1``; returns it.

        A commit that still fails after the io layer's retries degrades to
        the fallback URI (when configured) instead of dropping the
        snapshot; config-shaped errors (``FileNotFoundError`` etc. on a
        local path) are not degradation candidates and surface directly.
        """
        version = self._version + 1
        try:
            self._commit(version, state)
        except (DMLCError, OSError) as err:
            fb = self._fallback_manager()
            if fb is None or isinstance(
                err, (FileNotFoundError, PermissionError, IsADirectoryError,
                      NotADirectoryError)
            ):
                raise
            log_warning(
                "checkpoint v%d commit to %s failed (%s); degrading to "
                "fallback %s", version, self.uri, err, fb.uri,
            )
            from dmlc_tpu.obs import flight

            flight.record_event("ckpt.fallback", version=version,
                                uri=self.uri, error=str(err))
            fb._version = version - 1  # keep version numbering aligned
            fb._commit(version, state)
        self._version = version
        if self.rank == 0:
            self._prune(version)
        return version

    def _commit(self, version: int, state: Any) -> None:
        from dmlc_tpu.resilience import faultpoint

        faultpoint("ckpt.commit")
        if self.per_rank or self.rank == 0:
            stream = create_stream(self._state_uri(version, self.rank), "w")
            try:
                save_obj(stream, _to_host(state))
            finally:
                stream.close()
        if self.rank == 0:
            self._write_latest(version)

    def _fallback_manager(self) -> Optional["CheckpointManager"]:
        if self._fallback is None and self._fallback_uri is not None:
            self._fallback = CheckpointManager(
                self._fallback_uri, rank=self.rank,
                world_size=self.world_size, per_rank=self.per_rank,
                keep=self.keep, fallback_uri="",  # no fallback chains
            )
        return self._fallback

    def load_checkpoint(self) -> Tuple[int, Optional[Any]]:
        """(version, state) of the newest committed checkpoint, or (0, None).

        After a worker restart this re-reads LATEST, so a manager built
        fresh in the recovered process resumes from the last commit (the
        tracker keeps the rank stable across ``recover``,
        tracker.py:279-291). In ``per_rank`` mode the commit point (rank
        0's LATEST) cannot guarantee every rank's file landed, so a missing
        state file falls back version by version through the retained
        window before failing. With a fallback URI configured, whichever
        location holds the newest committed version is loaded — a restart
        after a degraded commit resumes from the fallback copy.
        """
        fb = self._fallback_manager()
        if fb is not None:
            primary_latest = self._read_latest() or 0
            if (fb._read_latest() or 0) > primary_latest:
                version, state = fb.load_checkpoint()
                self._version = max(self._version, version)
                return version, state
        return self._load_from_self()

    def _load_from_self(self) -> Tuple[int, Optional[Any]]:
        from dmlc_tpu.resilience import faultpoint

        faultpoint("ckpt.read")
        latest = self._read_latest()
        if not latest:
            return 0, None
        rank = self.rank if self.per_rank else 0
        floor = max(1, latest - self.keep + 1) if self.per_rank else latest
        for version in range(latest, floor - 1, -1):
            stream = create_stream(
                self._state_uri(version, rank), "r", allow_null=True
            )
            if stream is None:
                continue
            try:
                state = load_obj(stream)
            finally:
                stream.close()
            self._version = version
            return version, state
        raise DMLCError(
            f"checkpoint LATEST points at v{latest} but no readable state "
            f"file exists in {self.uri} (rank {rank})"
        )

    # ---- internals -----------------------------------------------------
    def _state_uri(self, version: int, rank: int) -> str:
        if self.per_rank:
            return f"{self.uri}/ckpt_v{version}.rank{rank}.bin"
        return f"{self.uri}/ckpt_v{version}.bin"

    def _write_latest(self, version: int) -> None:
        """Commit the LATEST pointer atomically.

        Local files go through write-temp-then-rename (a crash mid-write
        must never leave a truncated LATEST); object stores materialize the
        object only when the upload completes, which is already atomic
        (mem:// is a single-process test backend where this cannot race).
        """
        uri = f"{self.uri}/LATEST"
        parsed = URI.parse(uri)
        payload = str(version).encode()
        if parsed.protocol in ("file://", ""):
            import os

            tmp = parsed.name + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                fh.write(payload)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, parsed.name)
            return
        stream = create_stream(uri, "w")
        try:
            stream.write(payload)
        finally:
            stream.close()

    def _read_latest(self) -> Optional[int]:
        stream = create_stream(f"{self.uri}/LATEST", "r", allow_null=True)
        if stream is None:
            return None
        try:
            parts = []
            while True:
                piece = stream.read(4096)
                if not piece:
                    break
                parts.append(piece)
            text = b"".join(parts).decode().strip()
        finally:
            stream.close()
        return int(text) if text else None

    def _prune(self, newest: int) -> None:
        """Best-effort removal of checkpoints older than the ``keep`` window."""
        fs = get_filesystem(URI.parse(self.uri))
        delete = getattr(fs, "delete", None)
        if delete is None:
            return
        ranks = range(self.world_size) if self.per_rank else (0,)
        for version in range(max(1, newest - self.keep * 4), newest - self.keep + 1):
            for rank in ranks:
                try:
                    delete(URI.parse(self._state_uri(version, rank)))
                except Exception:
                    pass


# ---- coordinated job snapshots ----------------------------------------


class SnapshotSuperseded(DMLCError):
    """A rank moved past the awaited version without writing its part.

    Raised by the rank-0 part barrier when a peer's frontier marker shows
    it already wrote a part for a *newer* version: the peer's capture for
    the awaited version was superseded (newest-wins coalescing in the
    async writer) and its part will never land. The commit for the
    superseded version is abandoned — the newer version carries the
    durable state — instead of burning the full barrier timeout.
    """


#: Trailer magic for snapshot part files ("SNAP" little-endian).
PART_MAGIC = 0x534E4150
_PART_TRAILER = struct.Struct("<III")  # magic, crc32(payload), len(payload)


def _atomic_write(uri: str, payload: bytes) -> None:
    """Write ``payload`` so a crash never leaves a truncated file.

    Local files go through write-temp-fsync-rename; object stores
    materialize the object only on completed upload, which is already
    atomic (mem:// is a single-process test backend where this cannot
    race).
    """
    parsed = URI.parse(uri)
    if parsed.protocol in ("file://", ""):
        import os

        tmp = parsed.name + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            fh.write(payload)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, parsed.name)
        return
    stream = create_stream(uri, "w")
    try:
        stream.write(payload)
    finally:
        stream.close()


def _read_all(uri: str) -> Optional[bytes]:
    stream = create_stream(uri, "r", allow_null=True)
    if stream is None:
        return None
    try:
        parts = []
        while True:
            piece = stream.read(1 << 20)
            if not piece:
                break
            parts.append(piece)
    finally:
        stream.close()
    return b"".join(parts)


class JobSnapshot(CheckpointManager):
    """Two-phase-commit job snapshot: rank parts + a crc-guarded manifest.

    Phase 1: every rank serializes its state tree (model + optimizer +
    data-plane frontier + RNG + audit heads) to ``snap_v{N}.rank{R}``, a
    self-checking part file whose trailer records a crc32 and length of
    the payload. Phase 2: rank 0 waits for all ``world_size`` parts of
    the version to land and verify, fires the ``snap.commit`` faultpoint,
    then atomically writes ``snap_v{N}.manifest`` (crc-guarded, naming
    every part with its size and crc) and bumps LATEST. A crash at any
    point before the manifest lands leaves the previous version the
    newest *committed* one — torn or partial writes are never visible to
    :meth:`restore`.

    The barrier is filesystem-level (rank 0 polls for part files) rather
    than a collective op, so a background snapshot writer thread never
    touches the collective engine's sockets and a just-in-time preemption
    snapshot works even when peers are already tearing down.

    Version numbers must agree across ranks for the barrier to pair the
    right parts — callers that can skip commits (the async writer's
    newest-wins slot) pass an explicit epoch-derived ``version`` to
    :meth:`commit` so a skipped epoch leaves a *gap* in the sequence
    instead of shifting every later version (which would pair different
    epochs under one manifest). Each part write also bumps the rank's
    ``snap.rank{R}.frontier`` marker; the barrier reads the markers of
    still-missing ranks and abandons the commit
    (:class:`SnapshotSuperseded`) when a peer has already moved past the
    awaited version.
    """

    def __init__(
        self,
        uri: str,
        rank: int = 0,
        world_size: int = 1,
        keep: int = 2,
        fallback_uri: Optional[str] = None,
        part_timeout_s: float = 60.0,
    ):
        super().__init__(uri, rank=rank, world_size=world_size,
                         per_rank=True, keep=keep, fallback_uri=fallback_uri)
        self.part_timeout_s = part_timeout_s
        #: serialized payload size of this rank's last written part
        self.last_part_bytes = 0

    # ---- commit --------------------------------------------------------
    def commit(self, state: Any, meta: Optional[Dict[str, Any]] = None,
               version: Optional[int] = None) -> int:
        """Commit ``state`` (this rank's part) as the next version.

        Every rank calls ``commit`` with its own state tree; rank 0
        additionally runs the barrier + manifest phase. Returns the
        version number. Degrades to the fallback URI like
        :meth:`CheckpointManager.checkpoint` (all ranks observe the same
        failing filesystem, so degradation stays coordinated).

        ``version`` (optional) pins the version number explicitly —
        callers whose commit cadence can skip epochs (the async
        :class:`~dmlc_tpu.collective.snapshot.Snapshotter`) derive it
        from the epoch so every rank names the same epoch's part with
        the same version; it must advance past the newest version this
        rank has written. A commit whose barrier learns the version was
        superseded on a peer returns normally without a manifest — the
        newer version carries the durable state.
        """
        if version is None:
            version = self._version + 1
        else:
            version = int(version)
            check(version > self._version,
                  f"job snapshot version {version} must exceed this "
                  f"rank's newest written version {self._version} "
                  "(versions advance monotonically)")
        try:
            self._commit_snapshot(version, state, meta)
        except SnapshotSuperseded as err:
            from dmlc_tpu.obs import flight

            log_warning("%s", err)
            flight.record_event("snap.superseded", version=version)
        except (DMLCError, OSError) as err:
            fb = self._fallback_manager()
            if fb is None or isinstance(
                err, (FileNotFoundError, PermissionError, IsADirectoryError,
                      NotADirectoryError)
            ):
                raise
            log_warning(
                "job snapshot v%d commit to %s failed (%s); degrading to "
                "fallback %s", version, self.uri, err, fb.uri,
            )
            from dmlc_tpu.obs import flight

            flight.record_event("ckpt.fallback", version=version,
                                uri=self.uri, error=str(err))
            fb._version = version - 1
            fb._commit_snapshot(version, state, meta)
            self.last_part_bytes = fb.last_part_bytes
        self._version = version
        if self.rank == 0:
            self._prune(version)
        return version

    def _commit_snapshot(self, version: int, state: Any,
                         meta: Optional[Dict[str, Any]]) -> None:
        payload = self._write_part(version, state)
        if self.rank != 0:
            return
        parts = self._await_parts(version, own_payload=payload)
        from dmlc_tpu.resilience import faultpoint

        faultpoint("snap.commit")
        body = json.dumps({
            "version": version,
            "world_size": self.world_size,
            "parts": parts,
            "meta": meta or {},
        }, sort_keys=True).encode()
        head = b"%08x\n" % (zlib.crc32(body) & 0xFFFFFFFF)
        _atomic_write(self._manifest_uri(version), head + body)
        self._write_latest(version)
        from dmlc_tpu.obs import flight

        flight.record_event(
            "snap.commit", version=version, parts=len(parts),
            bytes=sum(p["size"] for p in parts),
        )

    def _write_part(self, version: int, state: Any) -> bytes:
        buf = MemoryStream()
        save_obj(buf, _to_host(state))
        payload = buf.getvalue()
        self.last_part_bytes = len(payload)
        trailer = _PART_TRAILER.pack(
            PART_MAGIC, zlib.crc32(payload) & 0xFFFFFFFF, len(payload)
        )
        _atomic_write(self._state_uri(version, self.rank), payload + trailer)
        # frontier marker: the newest version this rank wrote a part for.
        # Rank 0's barrier reads it to tell "peer is slow" (frontier
        # behind: keep waiting) from "peer skipped this version"
        # (frontier ahead: the awaited part will never land).
        _atomic_write(self._frontier_uri(self.rank), b"%d" % version)
        return payload

    def _await_parts(self, version: int, own_payload: bytes) -> list:
        """Rank 0 barrier: poll until every rank's part landed and verifies.

        Once a preemption notice is pending the barrier tightens to the
        remaining grace window: a peer that was itself preemption-killed
        behind this rank's epoch frontier will never write its part, and
        burning the full ``part_timeout_s`` would hold the process (and
        therefore the relaunch) hostage past the grace deadline. The
        failed commit degrades to the last committed version, which is
        exactly what resume falls back to.
        """
        from dmlc_tpu.resilience import preempt

        deadline = time.monotonic() + self.part_timeout_s
        entries: Dict[int, Dict[str, Any]] = {
            self.rank: {
                "name": self._part_name(version, self.rank),
                "size": len(own_payload),
                "crc": zlib.crc32(own_payload) & 0xFFFFFFFF,
            }
        }
        pending = [r for r in range(self.world_size) if r != self.rank]
        while pending:
            still = []
            for rank in pending:
                payload = self._read_part_payload(version, rank)
                if payload is None:
                    still.append(rank)
                    continue
                entries[rank] = {
                    "name": self._part_name(version, rank),
                    "size": len(payload),
                    "crc": zlib.crc32(payload) & 0xFFFFFFFF,
                }
            pending = still
            if pending:
                ahead = [r for r in pending
                         if self._read_frontier(r) > version]
                if ahead:
                    raise SnapshotSuperseded(
                        f"job snapshot v{version}: ranks {ahead} moved "
                        f"past this version without writing a part (their "
                        f"capture for it was superseded by a newer epoch); "
                        f"abandoning the v{version} manifest"
                    )
                now = time.monotonic()
                if preempt.requested():
                    deadline = min(
                        deadline, now + preempt.deadline_remaining())
                if now >= deadline:
                    raise DMLCError(
                        f"job snapshot v{version}: ranks {pending} did not "
                        f"write their part within the barrier window "
                        f"({self.part_timeout_s:.0f}s, or the preemption "
                        f"grace remainder once a notice is pending)"
                    )
                time.sleep(0.02)
        return [entries[r] for r in range(self.world_size)]

    # ---- restore -------------------------------------------------------
    def restore(self) -> Tuple[int, Optional[Any], Dict[str, Any]]:
        """(version, state, meta) of the newest committed snapshot.

        Walks the retained window newest-first, skipping versions whose
        manifest is torn or whose part fails its crc — a rank that
        crashed between part-write and manifest commit leaves the older
        version loadable. With a fallback URI configured, whichever
        location holds the newest *committed* (manifest present) version
        wins: a primary LATEST pointing at an uncommitted version does
        not shadow a committed fallback copy. A committed manifest whose
        ``world_size`` differs from this job's raises a clean
        ``DMLCError`` (resharding a per-rank snapshot is not supported).
        """
        fb = self._fallback_manager()
        if fb is not None:
            if self._newest_committed() < fb._newest_committed():
                version, state, meta = fb.restore()
                self._version = max(self._version, version)
                return version, state, meta
        latest = self._read_latest()
        if not latest:
            return 0, None, {}
        # walk the prune window (keep*4), not just `keep` raw numbers:
        # the committed sequence may have gaps (superseded versions), so
        # the previous committed manifest can sit more than `keep`
        # version numbers below LATEST
        floor = max(1, latest - self.keep * 4 + 1)
        for version in range(latest, floor - 1, -1):
            loaded = self._restore_version(version)
            if loaded is None:
                continue
            state, meta = loaded
            self._version = version
            return version, state, meta
        raise DMLCError(
            f"job snapshot LATEST points at v{latest} but no committed "
            f"version is readable in {self.uri} (rank {self.rank})"
        )

    def _newest_committed(self) -> int:
        """Newest version with an intact manifest (0 when none)."""
        latest = self._read_latest()
        if not latest:
            return 0
        floor = max(1, latest - self.keep * 4 + 1)
        for version in range(latest, floor - 1, -1):
            if self._read_manifest(version) is not None:
                return version
        return 0

    def _restore_version(self, version: int):
        manifest = self._read_manifest(version)
        if manifest is None:
            return None
        if manifest["world_size"] != self.world_size:
            raise DMLCError(
                f"job snapshot v{version} in {self.uri} was written by "
                f"world_size={manifest['world_size']} but this job runs "
                f"world_size={self.world_size}; per-rank snapshots cannot "
                "be resharded — restart with the original world size or "
                "point at a fresh snapshot directory"
            )
        entry = manifest["parts"][self.rank]
        payload = self._read_part_payload(version, self.rank)
        if payload is None or len(payload) != entry["size"] \
                or zlib.crc32(payload) & 0xFFFFFFFF != entry["crc"]:
            log_warning(
                "job snapshot v%d part %s missing or corrupt; trying an "
                "older version", version, entry["name"],
            )
            return None
        state = load_obj(MemoryStream(payload))
        meta = manifest.get("meta") or {}
        return state, meta

    def _read_manifest(self, version: int) -> Optional[Dict[str, Any]]:
        raw = _read_all(self._manifest_uri(version))
        if raw is None or b"\n" not in raw:
            return None
        head, body = raw.split(b"\n", 1)
        try:
            want = int(head, 16)
        except ValueError:
            return None
        if zlib.crc32(body) & 0xFFFFFFFF != want:
            return None
        try:
            return json.loads(body)
        except ValueError:
            return None

    def _read_part_payload(self, version: int, rank: int) -> Optional[bytes]:
        raw = _read_all(self._state_uri(version, rank))
        if raw is None or len(raw) < _PART_TRAILER.size:
            return None
        magic, crc, size = _PART_TRAILER.unpack(raw[-_PART_TRAILER.size:])
        payload = raw[:-_PART_TRAILER.size]
        if magic != PART_MAGIC or size != len(payload) \
                or zlib.crc32(payload) & 0xFFFFFFFF != crc:
            return None
        return payload

    # ---- layout / internals --------------------------------------------
    def _frontier_uri(self, rank: int) -> str:
        return f"{self.uri}/snap.rank{rank}.frontier"

    def _read_frontier(self, rank: int) -> int:
        """Newest version ``rank`` wrote a part for (0 when unknown)."""
        raw = _read_all(self._frontier_uri(rank))
        if raw is None:
            return 0
        try:
            return int(raw.decode().strip() or 0)
        except ValueError:
            return 0

    def _part_name(self, version: int, rank: int) -> str:
        return f"snap_v{version}.rank{rank}"

    def _state_uri(self, version: int, rank: int) -> str:
        return f"{self.uri}/{self._part_name(version, rank)}"

    def _manifest_uri(self, version: int) -> str:
        return f"{self.uri}/snap_v{version}.manifest"

    def _fallback_manager(self) -> Optional["JobSnapshot"]:
        if self._fallback is None and self._fallback_uri is not None:
            self._fallback = JobSnapshot(
                self._fallback_uri, rank=self.rank,
                world_size=self.world_size, keep=self.keep,
                fallback_uri="",  # no fallback chains
                part_timeout_s=self.part_timeout_s,
            )
        return self._fallback

    def _prune(self, newest: int) -> None:
        """Best-effort: retain the newest ``keep`` *committed* versions.

        The committed sequence may have gaps (a superseded commit skips
        a version number), so the retention window counts manifests
        rather than raw version numbers — a raw-number window would thin
        the restorable history whenever the cadence skipped an epoch.
        """
        fs = get_filesystem(URI.parse(self.uri))
        delete = getattr(fs, "delete", None)
        if delete is None:
            return
        floor = max(1, newest - self.keep * 4)
        kept = 0
        for version in range(newest, floor - 1, -1):
            if kept < self.keep:
                if self._read_manifest(version) is not None:
                    kept += 1
                continue
            try:
                delete(URI.parse(self._manifest_uri(version)))
            except Exception:
                pass
            for rank in range(self.world_size):
                try:
                    delete(URI.parse(self._state_uri(version, rank)))
                except Exception:
                    pass
