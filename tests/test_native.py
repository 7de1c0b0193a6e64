"""Native core (cpp/libdmlc_tpu.so) vs pure-Python parser parity.

Skipped when the .so has not been built (`make -C cpp`).
"""

import os
import shutil
import numpy as np
import pytest

from dmlc_tpu import native
from dmlc_tpu.data.parsers import CSVParser, LibFMParser, LibSVMParser

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library not built"
)


class _FakeSource:
    def __init__(self):
        self.closed = False

    def next_chunk(self):
        return None

    def before_first(self):
        pass

    def close(self):
        self.closed = True


def _parse_both(parser_cls, chunk, monkeypatch, **kwargs):
    src1, src2 = _FakeSource(), _FakeSource()
    native_block = parser_cls(src1, **kwargs).parse_chunk(chunk).to_block()
    monkeypatch.setenv("DMLC_TPU_NATIVE", "0")
    python_block = parser_cls(src2, **kwargs).parse_chunk(chunk).to_block()
    return native_block, python_block


def _assert_blocks_equal(a, b):
    np.testing.assert_array_equal(a.offset, b.offset)
    np.testing.assert_allclose(a.label, b.label, rtol=1e-6)
    np.testing.assert_array_equal(a.index, b.index)
    for field in ("value", "weight"):
        av, bv = getattr(a, field), getattr(b, field)
        assert (av is None) == (bv is None), field
        if av is not None:
            np.testing.assert_allclose(av, bv, rtol=1e-5, atol=1e-7)
    assert (a.qid is None) == (b.qid is None)
    if a.qid is not None:
        np.testing.assert_array_equal(a.qid, b.qid)


class TestLibSVMParity:
    def test_plain(self, monkeypatch):
        chunk = b"1 1:0.5 7:2.25\n0 3:1e-3 4:-2.5e2\n1 2:0.125\n"
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert a.num_nonzero == 5

    def test_weights_mixed(self, monkeypatch):
        chunk = b"1:5.0 1:1 2:2\n0 3:3\n"
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert a.weight is not None
        np.testing.assert_allclose(a.weight, [5.0, 1.0])

    def test_qid_and_bare_indices(self, monkeypatch):
        chunk = b"2 qid:7 1:0.5 4\n1 qid:8 2\n"
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert list(a.qid) == [7, 8]
        # bare index -> value 1.0
        np.testing.assert_allclose(a.value, [0.5, 1.0, 1.0])

    def test_blank_lines_and_crlf(self, monkeypatch):
        chunk = b"1 1:2\r\n\r\n0 2:3\n\n"
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert len(a) == 2

    def test_malformed_raises(self):
        src = _FakeSource()
        with pytest.raises(Exception):
            LibSVMParser(src).parse_chunk(b"notanumber 1:2\n")

    def test_random_roundtrip(self, monkeypatch):
        rng = np.random.RandomState(3)
        lines = []
        for i in range(200):
            feats = sorted(rng.choice(1000, size=rng.randint(1, 20), replace=False))
            lines.append(
                f"{rng.randint(0, 2)} "
                + " ".join(f"{j}:{rng.rand() * 100:.6g}" for j in feats)
            )
        chunk = ("\n".join(lines) + "\n").encode()
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)


class TestAdversarialNumerics:
    def test_huge_exponents_fast_and_saturating(self):
        """Exponents like 1e-999999999 must saturate (±0/±inf) in bounded
        time — the clamp in ApplyExp10 (cpp/parse.cc), not an O(|exp|)
        loop."""
        import time

        src = _FakeSource()
        chunk = b"1 1:1e-999999999 2:1e999999999 3:-4.5e-400 4:2e400\n"
        t0 = time.process_time()
        block = LibSVMParser(src).parse_chunk(chunk).to_block()
        # CPU time, not wall time: immune to CI load; an O(|exp|) loop
        # would burn >=0.2s/token of CPU here (measured 206ms at 45M iters)
        assert time.process_time() - t0 < 0.25
        vals = block.value
        assert vals[0] == 0.0
        assert np.isinf(vals[1]) and vals[1] > 0
        assert vals[2] == 0.0
        assert np.isinf(vals[3]) and vals[3] > 0

    def test_leading_zero_runs_parity(self, monkeypatch):
        """Leading zeros must not consume the 19-significant-digit mantissa
        budget: tiny values with long zero prefixes and zero-padded ints
        match the pure-Python parser."""
        chunk = (
            b"1 1:0.000000000000000000123 2:0.0000000000000000001\n"
            b"0 1:0000000000000000000123 2:0.0000000000000000000000000005\n"
        )
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert a.value[0] > 0 and a.value[1] > 0  # not flushed to zero
        assert a.value[2] == 123.0

    def test_compensating_exponent_parity(self, monkeypatch):
        """A long zero run (or dropped-digit run) compensated by an explicit
        exponent must stay finite/exact: saturation applies only to the
        final combined exponent (ApplyExp10), never mid-scan."""
        big = b"123" + b"0" * 497  # 500-digit integer ~1.23e499
        chunk = (
            b"1 1:0." + b"0" * 420 + b"5e450 2:1e9\n"
            b"0 1:" + big + b"e-480 2:2.5\n"
        )
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        assert np.isfinite(a.value[0]) and a.value[0] > 0  # 5e29
        assert np.isfinite(a.value[2]) and a.value[2] > 0  # ~1.23e19

    def test_long_fraction_swar_parity(self, monkeypatch):
        """Fraction runs longer than one 8-wide SWAR group round-trip to the
        same float32 as the pure-Python parser."""
        chunk = (
            b"1 1:0.1234567890123456789 2:3.14159265358979 3:0.5\n"
            b"0 1:123456789.123456789 2:0.000000001\n"
        )
        a, b = _parse_both(LibSVMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)


class TestLibFMParity:
    def test_triples(self, monkeypatch):
        chunk = b"1 0:1:0.5 3:7:2.5\n0 1:2:-1.5\n"
        a, b = _parse_both(LibFMParser, chunk, monkeypatch)
        _assert_blocks_equal(a, b)
        np.testing.assert_array_equal(a.field, b.field)


class TestCSVParity:
    def test_dense(self, monkeypatch):
        chunk = b"1,0.5,2.5\n0,1.5,-3.5\n"
        a, b = _parse_both(
            CSVParser, chunk, monkeypatch, args={"label_column": "0"}
        )
        _assert_blocks_equal(a, b)
        np.testing.assert_allclose(a.label, [1.0, 0.0])

    def test_empty_cells(self, monkeypatch):
        chunk = b"1,,2\n0,3,\n"
        a, b = _parse_both(
            CSVParser, chunk, monkeypatch, args={"label_column": "0"}
        )
        _assert_blocks_equal(a, b)


class TestStaleLibRecovery:
    def test_load_rejects_garbage_so(self, tmp_path):
        """_load returns None (never raises) for an unloadable artifact —
        the signal get_lib's retry loop uses to force a rebuild."""
        from dmlc_tpu import native

        bad = tmp_path / "libdmlc_tpu.so"
        bad.write_bytes(b"\x7fELF not really a library")
        assert native._load(str(bad)) is None

    @pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
    def test_load_rejects_wrong_abi_and_dlcloses(self, tmp_path,
                                                 monkeypatch):
        """The ABI-version gate itself, isolated from the symbol-surface
        check (_bind is stubbed out): a .so exporting the wrong version is
        rejected AND its dlopen handle is closed, so reloading the same
        path after a rebuild reads the FRESH file — dlopen caches by
        path, and without the dlclose the retry silently gets the stale
        image back."""
        import subprocess

        from dmlc_tpu import native

        monkeypatch.setattr(native, "_bind", lambda lib: None)

        def build(version: int):
            src = tmp_path / "fake.cc"
            src.write_text(
                'extern "C" int dmlc_tpu_abi_version(void) '
                "{ return %d; }\n" % version
            )
            tmp_so = tmp_path / "fresh.so"
            subprocess.run(
                ["g++", "-shared", "-fPIC", "-o", str(tmp_so), str(src)],
                check=True, capture_output=True,
            )
            # atomic replace, like the Makefile's tmp+rename
            tmp_so.replace(tmp_path / "libdmlc_tpu.so")

        so = str(tmp_path / "libdmlc_tpu.so")
        current = native._expected_abi_version()
        build(current - 1)
        assert native._load(so) is None  # version gate fires
        build(current)  # "the rebuild" writes a current-ABI lib, SAME path
        lib = native._load(so)
        assert lib is not None, "stale dlopen image not released"
        assert lib.dmlc_tpu_abi_version() == current


def test_abi_version_gate_tracks_header():
    """The Python-side expected ABI comes from cpp/dmlc_tpu.h (the header
    _try_build compiles), and the sources-absent fallback constant must
    match it — this assertion is what makes a header bump that forgets
    native._BOUND_ABI fail loudly in a checkout instead of silently
    routing every install-without-sources load through the gate."""
    header = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "cpp", "dmlc_tpu.h",
    )
    with open(header) as fh:
        versions = [int(line.split()[2]) for line in fh
                    if line.startswith("#define DMLC_TPU_ABI_VERSION")]
    assert len(versions) == 1
    assert native._expected_abi_version() == versions[0]
    assert native._BOUND_ABI == versions[0], (
        "cpp/dmlc_tpu.h ABI bumped without updating native._BOUND_ABI"
    )


def test_failed_make_is_logged_with_the_compilers_last_line(monkeypatch):
    """A `make` that ran and failed must not vanish into
    capture_output: one warning names the exit code and the compiler's
    last stderr line, then the package carries on with the Python twins."""
    import subprocess
    import types

    from dmlc_tpu.utils import logging as dlog

    def fake_run(cmd, **kwargs):
        assert cmd[0] == "make" and kwargs.get("check") is False
        return types.SimpleNamespace(
            returncode=2, stdout="",
            stderr="parse.cc: In function 'int f()':\n"
                   "parse.cc:12:3: error: 'nope' was not declared\n"
                   "make: *** [Makefile:13: libdmlc_tpu.so] Error 1\n")

    seen = []
    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(native, "_build_attempted", False)
    dlog.set_log_sink(lambda severity, msg: seen.append((severity, msg)))
    try:
        native._try_build()
    finally:
        dlog.set_log_sink(None)
    assert len(seen) == 1 and seen[0][0] == "WARNING"
    assert "exited 2" in seen[0][1]
    assert "make: *** [Makefile:13: libdmlc_tpu.so] Error 1" in seen[0][1]
