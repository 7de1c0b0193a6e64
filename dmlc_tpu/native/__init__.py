"""ctypes bindings for the native core (cpp/libdmlc_tpu.so).

Loading policy (DMLC_TPU_NATIVE env):
- unset / "auto": use the .so when present, else pure-Python fallbacks
- "0": never load (pure Python)
- "1": require it — raise if the library is missing

Every native entry point has a pure-Python twin, so the package works before
``make -C cpp`` has run; the twins live next to their call sites (parsers).
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

from dmlc_tpu.utils.logging import DMLCError, log_warning

_OK = 0
_EOVERFLOW = -1
_EPARSE = -2

HAS_WEIGHT = 1
HAS_QID = 2
HAS_VALUE = 4

_lib = None
_tried = False


def _candidate_paths():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = os.environ.get("DMLC_TPU_NATIVE_LIB")
    if env:
        yield env
    yield os.path.join(os.path.dirname(here), "cpp", "libdmlc_tpu.so")
    yield os.path.join(here, "cpp", "libdmlc_tpu.so")


def _bind(lib) -> None:
    i64 = ctypes.c_int64
    lib.parse_libsvm.restype = ctypes.c_int
    lib.parse_libsvm.argtypes = [
        ctypes.c_char_p, i64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p,
        i64, i64,
        ctypes.POINTER(i64), ctypes.POINTER(i64), ctypes.POINTER(ctypes.c_int),
    ]
    lib.parse_libfm.restype = ctypes.c_int
    lib.parse_libfm.argtypes = [
        ctypes.c_char_p, i64,
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        i64, i64,
        ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    lib.parse_csv.restype = ctypes.c_int
    lib.parse_csv.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_void_p,
        i64, i64,
        ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    lib.count_tokens.restype = None
    lib.count_tokens.argtypes = [
        ctypes.c_char_p, i64, ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    lib.recordio_pack_bound.restype = i64
    lib.recordio_pack_bound.argtypes = [ctypes.c_char_p, i64]
    lib.recordio_pack.restype = i64
    lib.recordio_pack.argtypes = [ctypes.c_char_p, i64, ctypes.c_void_p]
    lib.recordio_pack_batch.restype = i64
    lib.recordio_pack_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, i64, ctypes.c_void_p,
    ]
    lib.recordio_pack_batch_bound.restype = i64
    lib.recordio_pack_batch_bound.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, i64,
    ]
    lib.recordio_unpack.restype = ctypes.c_int
    lib.recordio_unpack.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(i64), ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    lib.recordio_find_head.restype = i64
    lib.recordio_find_head.argtypes = [ctypes.c_char_p, i64, i64]
    lib.ingest_open.restype = ctypes.c_void_p
    lib.ingest_open.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, i64, ctypes.c_int32, i64,
    ]
    lib.ingest_open_ex.restype = ctypes.c_void_p
    lib.ingest_open_ex.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, i64, ctypes.c_int32, i64, i64,
    ]
    lib.ingest_open_push.restype = ctypes.c_void_p
    lib.ingest_open_push.argtypes = [
        ctypes.c_int32, ctypes.c_int32, i64, ctypes.c_int32, i64,
    ]
    lib.ingest_push.restype = ctypes.c_int
    # data arg is c_void_p (not c_char_p) so writable buffers pass without
    # a bytes copy; bytes still pass directly
    lib.ingest_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p, i64]
    lib.ingest_push_eof.restype = ctypes.c_int
    lib.ingest_push_eof.argtypes = [ctypes.c_void_p]
    lib.ingest_push_reserve.restype = ctypes.c_void_p
    lib.ingest_push_reserve.argtypes = [ctypes.c_void_p, i64]
    lib.ingest_push_commit.restype = ctypes.c_int
    lib.ingest_push_commit.argtypes = [ctypes.c_void_p, i64]
    lib.ingest_push_abort.restype = None
    lib.ingest_push_abort.argtypes = [ctypes.c_void_p]
    lib.ingest_peek.restype = ctypes.c_int
    lib.ingest_peek.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(i64), ctypes.POINTER(i64), ctypes.POINTER(i64),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.ingest_fetch.restype = ctypes.c_int
    lib.ingest_fetch.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 7
    lib.ingest_fetch_view.restype = ctypes.c_void_p
    lib.ingest_fetch_view.argtypes = [ctypes.c_void_p] + [
        ctypes.POINTER(ctypes.c_void_p)
    ] * 7
    lib.ingest_block_free.restype = None
    lib.ingest_block_free.argtypes = [ctypes.c_void_p]
    lib.ingest_stage_batch.restype = ctypes.c_int
    lib.ingest_stage_batch.argtypes = [
        ctypes.c_void_p, i64, ctypes.POINTER(i64), ctypes.POINTER(i64),
    ]
    lib.ingest_fetch_batch_dense.restype = i64
    lib.ingest_fetch_batch_dense.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        i64, i64,
    ]
    lib.ingest_fetch_batch_coo.restype = i64
    lib.ingest_fetch_batch_coo.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, i64, i64,
    ]
    lib.ingest_stats.restype = None
    lib.ingest_stats.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
    ]
    lib.ingest_staged_max_shard_nnz.restype = i64
    lib.ingest_staged_max_shard_nnz.argtypes = [ctypes.c_void_p, i64, i64]
    lib.ingest_fetch_batch_coo_sharded.restype = i64
    lib.ingest_fetch_batch_coo_sharded.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64,
    ]
    lib.ingest_bytes_read.restype = i64
    lib.ingest_bytes_read.argtypes = [ctypes.c_void_p]
    lib.ingest_close.restype = None
    lib.ingest_close.argtypes = [ctypes.c_void_p]
    lib.dmlc_tpu_abi_version.restype = ctypes.c_int
    lib.dmlc_tpu_abi_version.argtypes = []
    lib.dmlc_tpu_simd_level.restype = ctypes.c_int
    lib.dmlc_tpu_simd_level.argtypes = []


_build_attempted = False


def _try_build(force: bool = False) -> None:
    """`make -C cpp` so fresh checkouts get the native core (the .so is a
    build artifact, not committed). Cross-process safe: holds an exclusive
    flock for the build so concurrent workers don't dlopen a half-written
    .so, and runs at most once per process. ``force`` adds -B: an
    EXISTING .so that failed to load (stale ABI surviving a git pull) can
    carry a fresh mtime, so a timestamp-based make would consider it up
    to date and leave it broken. A ``make`` that ran and failed is logged
    (once — this runs once per process) with the compiler's last stderr
    line: the package then carries on with the pure-Python twins, and the
    log says why."""
    global _build_attempted
    if _build_attempted:
        return
    _build_attempted = True
    import subprocess

    cpp_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "cpp",
    )
    if not os.path.exists(os.path.join(cpp_dir, "Makefile")):
        return
    lock_path = os.path.join(cpp_dir, ".build.lock")
    try:
        import fcntl

        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            proc = subprocess.run(
                ["make", "-C", cpp_dir] + (["-B"] if force else []),
                capture_output=True, text=True, timeout=120, check=False,
            )
    except (OSError, subprocess.TimeoutExpired, ImportError) as err:
        log_warning("native build did not run (%s: %s); using the "
                    "pure-Python parsers", type(err).__name__, err)
        return
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()
        log_warning(
            "native build failed (make -C %s exited %d): %s; using the "
            "pure-Python parsers", cpp_dir, proc.returncode,
            tail[-1] if tail else "no compiler output")


def _expected_abi_version() -> int:
    """DMLC_TPU_ABI_VERSION parsed out of THIS checkout's cpp/dmlc_tpu.h —
    the same header _try_build compiles, which is what the ctypes
    signatures in _bind were written against. Deliberately NOT read from
    a header adjacent to DMLC_TPU_NATIVE_LIB: a stale foreign lib must
    not self-validate against its own old header (the gate exists to
    protect _bind's signature contract, and that contract tracks this
    repo's header only). Falls back to the bound version constant when
    sources are absent (installed package) — bump _BOUND_ABI together
    with any header bump; it is asserted against the header by
    tests/test_native.py so the two cannot drift in a checkout."""
    global _expected_abi
    if _expected_abi is None:
        _expected_abi = _BOUND_ABI
        header = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "cpp", "dmlc_tpu.h",
        )
        try:
            with open(header) as fh:
                for line in fh:
                    if line.startswith("#define DMLC_TPU_ABI_VERSION"):
                        _expected_abi = int(line.split()[2])
                        break
        except (OSError, ValueError, IndexError):
            pass
    return _expected_abi


# the ABI generation _bind's ctypes signatures target; the header is
# authoritative in a checkout (see _expected_abi_version)
_BOUND_ABI = 7
_expected_abi = None


def simd_level() -> int:
    """SIMD tier the loaded parse engine actually selected (CPUID plus
    the ``DMLC_TPU_SIMD`` env gate, params/knobs.py): 0 = portable
    scalar, 2 = AVX2+BMI2. -1 when the native library is not loaded.
    The tier is latched at first native parse, so set the knob before
    touching data."""
    lib = get_lib()
    return int(lib.dmlc_tpu_simd_level()) if lib is not None else -1


def _load(path: str):
    """dlopen+bind, or None when the file is unusable — corrupt artifact,
    a stale build missing newly added symbols (AttributeError), or a
    stale/foreign ABI version: returning None lets get_lib's retry loop
    rebuild the .so (a gitignored artifact survives `git pull` across ABI
    bumps, so mismatch must route to rebuild, not raise — additive bumps
    like v5's ingest_drive_push add no Python-bound symbol that would
    otherwise trip the AttributeError path)."""
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    try:
        _bind(lib)
        ok = lib.dmlc_tpu_abi_version() == _expected_abi_version()
    except AttributeError:
        ok = False
    if not ok:
        # dlclose the rejected handle: dlopen caches by path, so without
        # this the post-rebuild retry would silently get the SAME stale
        # image back instead of the fresh .so on disk
        try:
            import _ctypes

            _ctypes.dlclose(lib._handle)
        except Exception:
            pass
        return None
    return lib


def get_lib():
    """The loaded native library, or None (per the DMLC_TPU_NATIVE policy)."""
    global _lib, _tried
    mode = os.environ.get("DMLC_TPU_NATIVE", "auto")
    if mode == "0":
        return None
    if _lib is not None:
        return _lib
    if _tried and mode != "1":
        return None
    _tried = True
    found_stale = False
    for attempt in range(2):
        found_stale = False
        for path in _candidate_paths():
            if os.path.exists(path):
                lib = _load(path)
                if lib is not None:
                    _lib = lib
                    return _lib
                found_stale = True
        if attempt == 0:
            # an existing-but-unloadable .so needs a FORCED rebuild: it
            # may be mtime-fresh (copied/pulled), so plain make would
            # consider it up to date
            _try_build(force=found_stale)
    if mode == "1":
        if found_stale:
            raise DMLCError(
                "DMLC_TPU_NATIVE=1: libdmlc_tpu.so exists but is stale or "
                "unloadable (wrong ABI?) and the forced rebuild failed; "
                "run `make -B -C cpp` and check the toolchain"
            )
        raise DMLCError(
            "DMLC_TPU_NATIVE=1 but libdmlc_tpu.so not found; run `make -C cpp`"
        )
    return None


def available() -> bool:
    return get_lib() is not None


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def parse_libsvm_chunk(chunk: bytes) -> Optional[dict]:
    """Native libsvm chunk parse → dict of arrays, or None if unavailable.

    Returns {labels f32[n], weights f32[n], qids i64[n], counts i64[n],
    indices u64[nnz], values f32[nnz], flags int}.
    """
    lib = get_lib()
    if lib is None:
        return None
    max_rows, max_nnz = _bounds(lib, chunk)
    labels = np.empty(max_rows, dtype=np.float32)
    weights = np.empty(max_rows, dtype=np.float32)
    qids = np.empty(max_rows, dtype=np.int64)
    counts = np.empty(max_rows, dtype=np.int64)
    indices = np.empty(max_nnz, dtype=np.uint64)
    values = np.empty(max_nnz, dtype=np.float32)
    out_rows = ctypes.c_int64()
    out_nnz = ctypes.c_int64()
    out_flags = ctypes.c_int()
    rc = lib.parse_libsvm(
        chunk, len(chunk),
        _ptr(labels), _ptr(weights), _ptr(qids), _ptr(counts),
        _ptr(indices), _ptr(values),
        max_rows, max_nnz,
        ctypes.byref(out_rows), ctypes.byref(out_nnz), ctypes.byref(out_flags),
    )
    if rc == _EPARSE:
        # tokens the branch-light native scan rejects (inf/nan/hex) may still
        # be valid for the Python twin — fall back instead of failing
        return None
    if rc != _OK:
        raise DMLCError(f"native libsvm parse failed rc={rc}")
    n, nnz = out_rows.value, out_nnz.value
    return {
        "labels": labels[:n],
        "weights": weights[:n],
        "qids": qids[:n],
        "counts": counts[:n],
        "indices": indices[:nnz],
        "values": values[:nnz],
        "flags": out_flags.value,
    }


def _bounds(lib, chunk: bytes):
    """(max_rows, max_nnz) upper bounds from the chunk length alone.

    Every row is >= 2 bytes ("0\\n") and every feature token >= 2 bytes, so
    len/2 bounds both. np.empty is a virtual allocation — untouched pages
    cost nothing — and the parse returns exact counts for trimming, so
    over-sizing beats scanning the chunk to size exactly.
    """
    bound = len(chunk) // 2 + 2
    return bound, bound


def parse_libfm_chunk(chunk: bytes) -> Optional[dict]:
    lib = get_lib()
    if lib is None:
        return None
    max_rows, max_nnz = _bounds(lib, chunk)
    labels = np.empty(max_rows, dtype=np.float32)
    counts = np.empty(max_rows, dtype=np.int64)
    fields = np.empty(max_nnz, dtype=np.uint64)
    indices = np.empty(max_nnz, dtype=np.uint64)
    values = np.empty(max_nnz, dtype=np.float32)
    out_rows = ctypes.c_int64()
    out_nnz = ctypes.c_int64()
    rc = lib.parse_libfm(
        chunk, len(chunk),
        _ptr(labels), _ptr(counts),
        _ptr(fields), _ptr(indices), _ptr(values),
        max_rows, max_nnz,
        ctypes.byref(out_rows), ctypes.byref(out_nnz),
    )
    if rc == _EPARSE:
        return None  # fall back to the Python twin (see parse_libsvm_chunk)
    if rc != _OK:
        raise DMLCError(f"native libfm parse failed rc={rc}")
    n, nnz = out_rows.value, out_nnz.value
    return {
        "labels": labels[:n],
        "counts": counts[:n],
        "fields": fields[:nnz],
        "indices": indices[:nnz],
        "values": values[:nnz],
    }


def parse_csv_chunk(chunk: bytes, expect_cols: int = 0) -> Optional[tuple]:
    """Native dense-CSV parse → (table f32[rows, cols]) or None."""
    lib = get_lib()
    if lib is None:
        return None
    max_rows = chunk.count(b"\n") + 2
    if expect_cols <= 0:
        nl = chunk.find(b"\n")
        first = chunk[: nl if nl >= 0 else len(chunk)]
        expect_cols_hint = first.count(b",") + 1
    else:
        expect_cols_hint = expect_cols
    out = np.empty((max_rows, expect_cols_hint), dtype=np.float32)
    out_rows = ctypes.c_int64()
    out_cols = ctypes.c_int64()
    rc = lib.parse_csv(
        chunk, len(chunk), _ptr(out),
        max_rows, expect_cols_hint,
        ctypes.byref(out_rows), ctypes.byref(out_cols),
    )
    if rc == _EPARSE:
        # ragged csv → caller falls back to the python path
        return None
    if rc != _OK:
        raise DMLCError(f"native csv parse failed rc={rc}")
    return out[: out_rows.value, : out_cols.value]


# ---------------------------------------------------------------------------
# RecordIO framing (cpp/recordio.cc — reference src/recordio.cc semantics)
# ---------------------------------------------------------------------------


def recordio_pack_records(records) -> Optional[bytes]:
    """Frame a batch of payloads into RecordIO bytes, or None (no native).
    Accepts any iterable of bytes-likes."""
    lib = get_lib()
    if lib is None:
        return None
    records = list(records)
    offsets = np.zeros(len(records) + 1, dtype=np.int64)
    for i, r in enumerate(records):
        offsets[i + 1] = offsets[i] + len(r)
    data = b"".join(bytes(r) for r in records)
    bound = lib.recordio_pack_batch_bound(data, _ptr(offsets), len(records))
    out = np.empty(int(bound), dtype=np.uint8)
    n = lib.recordio_pack_batch(data, _ptr(offsets), len(records), _ptr(out))
    if n < 0:
        raise DMLCError("RecordIO only accepts records < 2^29 bytes")
    return out[:n].tobytes()


def recordio_unpack_chunk(chunk: bytes) -> Optional[tuple]:
    """Decode all complete records in a chunk that starts at a record head.

    → (payloads: bytes, offsets: i64[n+1], consumed: int) or None (no
    native). Raises DMLCError on corrupt framing.
    """
    lib = get_lib()
    if lib is None:
        return None
    # reassembly re-inserts elided magics: output can exceed the input
    # payload bytes but never the input length plus one magic per frame
    cap = len(chunk) + 4
    out_data = np.empty(cap, dtype=np.uint8)
    max_rec = len(chunk) // 8 + 2
    out_offsets = np.zeros(max_rec + 1, dtype=np.int64)
    nrec = ctypes.c_int64()
    dlen = ctypes.c_int64()
    consumed = ctypes.c_int64()
    rc = lib.recordio_unpack(
        chunk, len(chunk), _ptr(out_data), _ptr(out_offsets),
        ctypes.byref(nrec), ctypes.byref(dlen), ctypes.byref(consumed),
    )
    if rc != _OK:
        raise DMLCError("Invalid RecordIO format (native unpack)")
    n = nrec.value
    return (
        out_data[: dlen.value].tobytes(),
        out_offsets[: n + 1].copy(),
        consumed.value,
    )


# ---------------------------------------------------------------------------
# Native ingest pipeline (cpp/pipeline.cc): reader thread + parse workers +
# ordered output queue, all in C++ — the ThreadedInputSplit/ThreadedParser
# composition of the reference as one native unit.
# ---------------------------------------------------------------------------

INGEST_LIBSVM = 0
INGEST_LIBFM = 1
INGEST_CSV = 2
INGEST_RECORDIO = 3  # row-group records (data/rowrec.py layout)


class _NativeBlock:
    """Owner of a native block handed off by ingest_fetch_view.

    Every numpy view created over the block's arrays keeps a reference to
    this owner (via the ctypes buffer object in its base chain), so the
    native buffers are freed exactly when the last view is collected.
    """

    __slots__ = ("_lib", "_ptr")

    def __init__(self, lib, ptr):
        self._lib = lib
        self._ptr = ptr

    def __del__(self):
        ptr, self._ptr = self._ptr, None
        if ptr:
            try:
                self._lib.ingest_block_free(ptr)
            except Exception:
                pass


def _block_view(owner, addr, n, ctype, dtype):
    """Zero-copy numpy view over `n` elements of native memory at `addr`."""
    if n == 0 or not addr:
        return np.empty(0, dtype=dtype)
    cbuf = (ctype * n).from_address(addr)
    cbuf._dmlc_block = owner  # lifetime: array.base -> cbuf -> owner
    return np.frombuffer(cbuf, dtype=dtype)


class IngestPipeline:
    """Handle over the native pipeline; yields dicts of zero-copy arrays.

    ``next_block()`` returns None at end of stream; raises DMLCError on a
    parse/IO error inside the pipeline (the cross-thread exception
    propagation contract of threadediter.h:456-466). The returned arrays
    view native memory owned by a ``_NativeBlock`` in their base chain — no
    copy on the handoff; the block is freed when the last view dies.
    """

    def __init__(
        self,
        paths,
        sizes,
        fmt: int,
        part: int,
        nparts: int,
        nthread: int = 2,
        chunk_bytes: int = (2 << 20) * 4,
        capacity: int = 8,
        csv_expect_cols: int = 0,
        push: bool = False,
        shuffle_seed: int = -1,
    ):
        lib = get_lib()
        if lib is None:
            raise DMLCError("native library unavailable")
        self._lib = lib
        self._fmt = fmt
        if push:
            # push mode: the caller streams partition bytes in (remote
            # ingest — parallel range-GET fetchers feed the native workers)
            self._handle = lib.ingest_open_push(
                fmt, nthread, chunk_bytes, capacity, csv_expect_cols
            )
        else:
            path_blob = b"".join(
                (p.encode() if isinstance(p, str) else bytes(p)) + b"\0"
                for p in paths
            )
            size_arr = np.asarray(sizes, dtype=np.int64)
            self._handle = lib.ingest_open_ex(
                path_blob, _ptr(size_arr), len(paths),
                fmt, part, nparts, nthread, chunk_bytes, capacity,
                csv_expect_cols, shuffle_seed,
            )
        if not self._handle:
            raise DMLCError(
                "ingest_open failed (bad arguments"
                + (", or chunk shuffle unavailable for this dataset"
                   if shuffle_seed >= 0 else "")
                + ")"
            )

    # ---- push mode (remote ingest feeders) ---------------------------

    def push(self, data) -> None:
        """Append partition-stream bytes (any buffer-protocol object,
        zero-copy handoff); blocks for backpressure when the parse workers
        are behind (the ctypes call releases the GIL)."""
        n = len(data)
        if isinstance(data, bytes):
            buf = data  # pointer to the bytes object's storage
        else:
            # writable buffers (bytearray from the readinto fetch path):
            # borrow the memory without a copy for the call's duration
            buf = ctypes.addressof((ctypes.c_char * n).from_buffer(data))
        rc = self._lib.ingest_push(self._handle, buf, n)
        if rc != 0:
            raise DMLCError(f"native ingest push failed rc={rc}")

    def push_reserve(self, want: int):
        """Writable memoryview over `want` bytes of the pipeline's own tail
        buffer (valid only until the next reserve/commit/push): remote
        responses readinto() native memory with zero Python-side copies."""
        ptr = self._lib.ingest_push_reserve(self._handle, want)
        if not ptr:
            raise DMLCError("native ingest push_reserve failed")
        return memoryview((ctypes.c_char * want).from_address(ptr)).cast("B")

    def push_commit(self, n: int) -> None:
        rc = self._lib.ingest_push_commit(self._handle, n)
        if rc != 0:
            raise DMLCError(f"native ingest push_commit failed rc={rc}")

    def push_eof(self) -> None:
        rc = self._lib.ingest_push_eof(self._handle)
        if rc != 0:
            raise DMLCError(f"native ingest push_eof failed rc={rc}")

    def push_abort(self) -> None:
        """Fail the pipeline so consumers blocked in next_block wake."""
        if self._handle:
            self._lib.ingest_push_abort(self._handle)

    def next_block(self) -> Optional[dict]:
        rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        ncols = ctypes.c_int64()
        flags = ctypes.c_int32()
        rc = self._lib.ingest_peek(
            self._handle,
            ctypes.byref(rows), ctypes.byref(nnz), ctypes.byref(ncols),
            ctypes.byref(flags),
        )
        if rc == 0:
            return None
        if rc < 0:
            raise DMLCError(f"native ingest pipeline failed rc={rc}")
        n, z = rows.value, nnz.value
        fl = flags.value

        ptrs = [ctypes.c_void_p() for _ in range(7)]
        block = self._lib.ingest_fetch_view(
            self._handle, *[ctypes.byref(q) for q in ptrs]
        )
        if not block:
            raise DMLCError("ingest_fetch_view with no staged block")
        owner = _NativeBlock(self._lib, block)
        (labels_p, weights_p, qids_p, offsets_p, indices_p, values_p,
         fields_p) = (q.value for q in ptrs)

        if self._fmt == INGEST_CSV:
            table = _block_view(
                owner, values_p, n * ncols.value, ctypes.c_float, np.float32
            ).reshape(n, ncols.value)
            return {"table": table}

        is_svm = self._fmt in (INGEST_LIBSVM, INGEST_RECORDIO)
        out = {
            "labels": _block_view(owner, labels_p, n, ctypes.c_float,
                                  np.float32),
            "offsets": _block_view(owner, offsets_p, n + 1, ctypes.c_int64,
                                   np.int64),
            "indices": _block_view(owner, indices_p, z, ctypes.c_uint32,
                                   np.uint32),
            "values": _block_view(owner, values_p, z, ctypes.c_float,
                                  np.float32),
            "flags": fl,
        }
        if is_svm:
            if fl & HAS_WEIGHT:
                out["weights"] = _block_view(
                    owner, weights_p, n, ctypes.c_float, np.float32
                )
            if fl & HAS_QID:
                out["qids"] = _block_view(
                    owner, qids_p, n, ctypes.c_int64, np.int64
                )
        else:
            out["fields"] = _block_view(
                owner, fields_p, z, ctypes.c_uint32, np.uint32
            )
        return out

    # ---- native batch staging (fixed-shape TPU feed) -----------------

    def stage_batch(self, batch_size: int):
        """Stage the next batch; → (rows, nnz) or None at end of stream.
        rows = min(batch_size, rows left); the matching fetch consumes."""
        rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        rc = self._lib.ingest_stage_batch(
            self._handle, batch_size, ctypes.byref(rows), ctypes.byref(nnz)
        )
        if rc == 0:
            return None
        if rc < 0:
            raise DMLCError(f"native ingest pipeline failed rc={rc}")
        return rows.value, nnz.value

    def fetch_batch_dense(self, batch_size: int, num_features: int):
        """Consume the staged batch densified to [batch, F]; → (x, labels,
        weights, rows). Rows past `rows` are zero-padded (weight 0)."""
        x = np.empty((batch_size, num_features), dtype=np.float32)
        labels = np.empty(batch_size, dtype=np.float32)
        weights = np.empty(batch_size, dtype=np.float32)
        rows = self._lib.ingest_fetch_batch_dense(
            self._handle, _ptr(x), _ptr(labels), _ptr(weights),
            batch_size, num_features,
        )
        if rows < 0:
            raise DMLCError(f"native dense batch fetch failed rc={rows}")
        return x, labels, weights, int(rows)

    def fetch_batch_coo(self, batch_size: int, nnz_bucket: int):
        """Consume the staged batch as padded COO; → (labels, weights,
        indices, values, row_ids, offsets, rows). offsets is the
        [batch_size + 1] CSR twin of row_ids — the feed ships it instead
        of the per-entry row array (H2D ∝ rows, not nnz)."""
        labels = np.empty(batch_size, dtype=np.float32)
        weights = np.empty(batch_size, dtype=np.float32)
        indices = np.empty(nnz_bucket, dtype=np.int32)
        values = np.empty(nnz_bucket, dtype=np.float32)
        row_ids = np.empty(nnz_bucket, dtype=np.int32)
        offsets = np.empty(batch_size + 1, dtype=np.int32)
        rows = self._lib.ingest_fetch_batch_coo(
            self._handle, _ptr(labels), _ptr(weights), _ptr(indices),
            _ptr(values), _ptr(row_ids), _ptr(offsets), batch_size,
            nnz_bucket,
        )
        if rows < 0:
            raise DMLCError(f"native coo batch fetch failed rc={rows}")
        return labels, weights, indices, values, row_ids, offsets, int(rows)

    def staged_max_shard_nnz(self, batch_size: int, num_shards: int) -> int:
        """Max per-shard nnz of the staged batch under a row-range split."""
        out = self._lib.ingest_staged_max_shard_nnz(
            self._handle, batch_size, num_shards
        )
        if out < 0:
            raise DMLCError("bad sharded staging arguments")
        return int(out)

    def fetch_batch_coo_sharded(
        self, batch_size: int, num_shards: int, nnz_bucket: int
    ):
        """Consume the staged batch partitioned per shard; → (labels,
        weights, indices, values, row_ids, offsets, rows) with flat
        [num_shards*nnz_bucket] entry arrays, LOCAL row ids, and flat
        [num_shards*(batch/num_shards + 1)] per-shard LOCAL CSR offsets."""
        labels = np.empty(batch_size, dtype=np.float32)
        weights = np.empty(batch_size, dtype=np.float32)
        total = num_shards * nnz_bucket
        indices = np.empty(total, dtype=np.int32)
        values = np.empty(total, dtype=np.float32)
        row_ids = np.empty(total, dtype=np.int32)
        offsets = np.empty(
            num_shards * (batch_size // num_shards + 1), dtype=np.int32
        )
        rows = self._lib.ingest_fetch_batch_coo_sharded(
            self._handle, _ptr(labels), _ptr(weights), _ptr(indices),
            _ptr(values), _ptr(row_ids), _ptr(offsets), batch_size,
            num_shards, nnz_bucket,
        )
        if rows < 0:
            raise DMLCError(f"native sharded coo fetch failed rc={rows}")
        return labels, weights, indices, values, row_ids, offsets, int(rows)

    def stats(self) -> dict:
        """Per-stage counters (SURVEY §5.1 pipeline timers): wall time
        per stage, and the CPU time the reader thread and the parse
        workers used, counted by those threads (``*_cpu_ns``)."""
        out = np.zeros(9, dtype=np.float64)
        self._lib.ingest_stats(self._handle, _ptr(out), 9)
        keys = ("bytes_read", "chunks", "reader_io_ns", "reader_wait_ns",
                "parse_ns", "worker_wait_ns", "consumer_wait_ns",
                "reader_cpu_ns", "parse_cpu_ns")
        return {k: (int(v) if k in ("bytes_read", "chunks") else float(v))
                for k, v in zip(keys, out)}

    @property
    def bytes_read(self) -> int:
        # a closed pipeline has no handle to ask: the garbage collector
        # may finalize this object before the parser that owns it, whose
        # own teardown then reads the count (a NULL handle is a crash)
        if not self._handle:
            return 0
        return int(self._lib.ingest_bytes_read(self._handle))

    def close(self) -> None:
        if self._handle:
            self._lib.ingest_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def recordio_find_head(buf: bytes, start: int = 0) -> Optional[int]:
    """First plausible record-head offset ≥ start: -1 when none exists, or
    None when the native library is unavailable (callers fall back to the
    numpy scan)."""
    lib = get_lib()
    if lib is None:
        return None
    return int(lib.recordio_find_head(buf, len(buf), start))
