"""Parser parity: the vectorized text-parse path (data/vparse.py) must be
byte/bit-identical to the scalar oracle — same blocks on the same input,
same error on the same malformed input — across weights, qid, comments,
blank lines, CRLF, missing trailing newlines, huge/denormal floats, and
deliberately broken grammar. Plus the pipeline-level contracts that ride
on it: process-pool workers keep ordering and poisoning, and the Pallas
tokenizer matches the numpy boundary masks.

The randomized corpora are seeded — failures reproduce exactly.
"""

import os
import random

import numpy as np
import pytest

from dmlc_tpu.data import vparse
from dmlc_tpu.data.row_block import RowBlockContainer

_BLOCK_FIELDS = ("offset", "label", "index", "value", "weight", "qid")


def _outcome(fn, chunk):
    """("OK", {field: array}) or ("ERR", exception type name)."""
    out = RowBlockContainer()
    try:
        fn(chunk, out)
        block = out.to_block()
    except Exception as err:  # noqa: BLE001 — error parity is the contract
        return ("ERR", type(err).__name__)
    return ("OK", {k: getattr(block, k) for k in _BLOCK_FIELDS})


def _assert_identical(chunk):
    """Scalar and vectorized agree to the byte (or raise the same type)."""
    a = _outcome(vparse.parse_libsvm_scalar, chunk)
    b = _outcome(vparse.parse_libsvm_vector, chunk)
    assert a[0] == b[0], (a, b, chunk[:120])
    if a[0] == "ERR":
        assert a[1] == b[1], (a, b, chunk[:120])
        return
    for key in _BLOCK_FIELDS:
        x, y = a[1][key], b[1][key]
        assert (x is None) == (y is None), (key, chunk[:120])
        if x is None:
            continue
        assert x.dtype == y.dtype and x.shape == y.shape, (key, chunk[:120])
        # tobytes: bit-identical, NaN payloads and signed zeros included
        assert x.tobytes() == y.tobytes(), (key, x[:8], y[:8], chunk[:120])


def _token(r):
    t = r.random()
    if t < 0.35:
        return str(r.randint(-5, 200)).encode()
    if t < 0.6:
        return ("%.6f" % r.uniform(-10, 10)).encode()
    if t < 0.7:
        return ("%g" % r.uniform(-1e300, 1e300)).encode()
    if t < 0.75:
        return ("%g" % r.uniform(-5e-324, 5e-310)).encode()  # denormals
    if t < 0.8:
        return r.choice([b"nan", b"inf", b"-inf", b"infinity", b"1e400",
                         b"+3", b".5", b"5.", b"1_0"])
    if t < 0.85:
        return r.choice([b"abc", b"1a", b"0x10", b"", b"-", b"+"])
    if t < 0.9:
        return str(r.randint(0, 2 ** 33)).encode()
    return ("%.17g" % (r.random() * 10 ** r.randint(-300, 300))).encode()


def _libsvm_line(r):
    t = r.random()
    if t < 0.05:
        return b""
    if t < 0.08:
        return b"   "
    head = _token(r)
    if r.random() < 0.2:
        head += b":" + _token(r)  # instance weight
    if r.random() < 0.05:
        head += b":" + _token(r)  # label:w:extra junk
    parts = [head]
    if r.random() < 0.1:
        parts.append(b"qid:" + str(r.randint(0, 99)).encode())
    for _ in range(r.randint(0, 6)):
        u = r.random()
        if u < 0.55:
            parts.append(_token(r) + b":" + _token(r))
        elif u < 0.75:
            parts.append(_token(r))  # bare index
        elif u < 0.8:
            parts.append(_token(r) + b":")  # dangling colon
        elif u < 0.85:
            parts.append(b":" + _token(r))  # leading colon
        elif u < 0.9:
            parts.append(b":")  # orphan colon
        elif u < 0.95:
            parts.append(_token(r) + b"::" + _token(r))
        else:
            parts.append(_token(r) + b":" + _token(r) + b":" + _token(r))
    line = r.choice([b" ", b"  ", b"\t", b" \t "]).join(parts)
    if r.random() < 0.1:
        line = b" " + line
    if r.random() < 0.1:
        line += b" "
    return line


def _libsvm_chunk(r):
    nl = r.choice([b"\n", b"\r\n", b"\r"])
    s = nl.join(_libsvm_line(r) for _ in range(r.randint(0, 20)))
    if r.random() < 0.7:
        s += nl  # 30%: no trailing newline
    return s


class TestLibSVMParity:
    FIXED = [
        b"1 2:3\n", b"1:2 3:4.5\n", b"1:2:3 4:5\n", b"1 : 2\n", b"1 :2\n",
        b": \n", b":\n", b"1 2:\n", b"1 qid:7 2:3\n", b"qid:7\n",
        b"1 2:3",  # no trailing newline
        b"", b"\n\n", b"1\r\n2\r\n", b"1 1\x002:3\n", b"-1 4:-0.0\n",
        b"1 2:3 \r\n", b"3 1_0:2\n", b"1 " + b"9" * 100 + b":1\n",
        b"1 2:nan 3:inf\n", b"+0 .5:5.\n", b"2:1", b"1 1:1 1:\n",
        b"1 a:b\n", b"1 2::3\n", b"1 1:1e-999999999 2:1e999999999\n",
    ]

    def test_fixed_corpus(self):
        for chunk in self.FIXED:
            _assert_identical(chunk)

    def test_randomized(self):
        r = random.Random(20260805)
        for _ in range(150):
            _assert_identical(_libsvm_chunk(r))

    def test_huge_and_denormal_floats(self):
        lines = [
            b"1 1:1e308 2:-1e308 3:5e-324 4:1.7976931348623157e308",
            b"0 5:2.2250738585072014e-308 6:4.9406564584124654e-324",
            b"1 7:123456789012345678901234567890 8:0.000000000000001",
        ]
        _assert_identical(b"\n".join(lines) + b"\n")


class TestWeightDetection:
    """Satellite: the instance-weight head must not be confused with a
    feature pair (the old fast path keyed on ``b":" in first_token``,
    which also matched a *feature-shaped* head like ``1:2`` — these pin
    the semantics the scalar oracle defines)."""

    def test_label_weight_head(self):
        out = RowBlockContainer()
        vparse.parse_libsvm_vector(b"1:2 3:4.5\n", out)
        b = out.to_block()
        assert b.label.tolist() == [1.0]
        assert b.weight is not None and b.weight.tolist() == [2.0]
        assert b.index.tolist() == [3]
        assert b.value is not None and b.value.tolist() == [4.5]

    def test_weighted_and_unweighted_rows_mix(self):
        out = RowBlockContainer()
        vparse.parse_libsvm_vector(b"1:5.0 1:1 2:2\n0 3:3\n", out)
        b = out.to_block()
        # unweighted rows in a weighted dataset default to weight 1.0
        assert b.weight is not None
        np.testing.assert_array_equal(b.weight, [5.0, 1.0])

    def test_head_with_two_colons_matches_oracle(self):
        # "label:w:extra" heads and feature-shaped junk must do whatever
        # the scalar oracle does — byte-identically (here: ValueError on
        # the materialized b"2:3" weight token vs b"1" label is NOT the
        # shape; the oracle splits on the first colon pair)
        for chunk in (b"1:2:3 4:5\n", b"1:2:3\n", b"1:2 3\n", b"1: 2:3\n"):
            _assert_identical(chunk)


def _csv_outcome(fn, chunk):
    try:
        return ("OK", fn(chunk))
    except Exception as err:  # noqa: BLE001
        return ("ERR", type(err).__name__)


def _assert_csv_identical(chunk):
    a = _csv_outcome(vparse.parse_csv_scalar_table, chunk)
    b = _csv_outcome(vparse.parse_csv_vector_table, chunk)
    assert a[0] == b[0], (a, b, chunk[:120])
    if a[0] == "ERR":
        assert a[1] == b[1], (a, b, chunk[:120])
        return
    assert a[1].shape == b[1].shape, (a[1].shape, b[1].shape, chunk[:120])
    assert a[1].tobytes() == b[1].tobytes(), chunk[:120]


def _csv_cell(r):
    t = r.random()
    if t < 0.5:
        return ("%.6f" % r.uniform(-100, 100)).encode()
    if t < 0.6:
        return str(r.randint(-9, 9)).encode()
    if t < 0.7:
        return b""
    if t < 0.75:
        return b" " + ("%g" % r.uniform(-1, 1)).encode() + b" "
    if t < 0.8:
        return r.choice([b"nan", b"inf", b"-1e400", b"1_5"])
    if t < 0.85:
        return r.choice([b'"1"', b"abc", b"1 2", b"  "])
    return ("%.17g" % (r.random() * 10 ** r.randint(-300, 300))).encode()


def _csv_chunk(r):
    nl = r.choice([b"\n", b"\r\n", b"\r"])
    lines = []
    for _ in range(r.randint(0, 15)):
        u = r.random()
        if u < 0.08:
            lines.append(b"")
        elif u < 0.12:
            lines.append(b"  ")
        elif u < 0.15:
            lines.append(b",")
        else:
            lines.append(b",".join(
                _csv_cell(r) for _ in range(r.randint(1, 6))))
    s = nl.join(lines)
    if r.random() < 0.7:
        s += nl
    return s


class TestCSVParity:
    FIXED = [
        b"1,2\n", b"1,\n", b",\n", b"1,2,3\n4,5\n", b"\n", b"",
        b"1,2\r\n3,4\r\n", b"1\r2\n", b" 1 , 2 \n", b"1,,3\n",
        b"1,2,",  # trailing comma, no newline
        b"  \n1,2\n", b"5\n", b"1,2\n3\n", b"1,2,\n3,4,\n",
    ]

    def test_fixed_corpus(self):
        for chunk in self.FIXED:
            _assert_csv_identical(chunk)

    def test_trailing_comma_is_blank_last_column(self):
        # satellite: a trailing comma means a blank last cell → 0.0, in
        # BOTH modes (the old uniform path re-joined lines and parsed it
        # right while the ragged path's `c or b"0"` did too, but the two
        # disagreed on column count when mixed)
        table = vparse.parse_csv_vector_table(b"1,2,\n4,5,6\n")
        np.testing.assert_array_equal(
            table, [[1.0, 2.0, 0.0], [4.0, 5.0, 6.0]])
        _assert_csv_identical(b"1,2,\n4,5,6\n")

    def test_quoted_cells_error_in_both(self):
        # dense numeric csv: quotes are not stripped — float(b'"1"')
        # raises, and the vectorized path must raise the same way
        _assert_csv_identical(b'"1",2\n')
        with pytest.raises(ValueError):
            vparse.parse_csv_vector_table(b'"1",2\n')

    def test_randomized(self):
        r = random.Random(40411)
        for _ in range(150):
            _assert_csv_identical(_csv_chunk(r))


class TestNativeParity:
    """Native C++ core vs the vectorized Python path on well-formed data
    (tests/test_native.py pins native vs the *scalar* python stack; this
    closes the triangle)."""

    @pytest.fixture(autouse=True)
    def _need_native(self):
        from dmlc_tpu import native

        if not native.available():
            pytest.skip("native library not built")

    def test_well_formed_roundtrip(self):
        from dmlc_tpu.data.parsers import _native_libsvm

        rng = np.random.RandomState(11)
        lines = []
        for i in range(300):
            feats = sorted(
                rng.choice(2000, size=rng.randint(1, 16), replace=False))
            lines.append(
                "%d " % rng.randint(0, 2)
                + " ".join("%d:%.6g" % (j, rng.rand() * 100) for j in feats))
        chunk = ("\n".join(lines) + "\n").encode()
        nat = _native_libsvm(chunk)
        assert nat is not None
        nat_block = nat.to_block()
        out = RowBlockContainer()
        vparse.parse_libsvm_vector(chunk, out)
        vec_block = out.to_block()
        np.testing.assert_array_equal(nat_block.offset, vec_block.offset)
        np.testing.assert_array_equal(nat_block.index, vec_block.index)
        np.testing.assert_allclose(nat_block.label, vec_block.label,
                                   rtol=1e-6)
        np.testing.assert_allclose(nat_block.value, vec_block.value,
                                   rtol=1e-5, atol=1e-7)


class TestAuditDigestParity:
    """Audit satellite: the canonical row digest (obs/audit.py
    ``rows_digest`` over ``audit_arrays``) is backend-independent — the
    native, vector, and scalar parses of one canned corpus hash
    identically, and a container hashes byte-for-byte like both its
    finalized block and any re-chunking of the same rows."""

    @staticmethod
    def _canned_chunk():
        # exactly-representable values (multiples of 0.25) so every
        # backend's float conversion lands on identical bits — digest
        # equality tests the canonical stream, not strtod rounding
        rng = random.Random(127)
        lines = []
        for i in range(200):
            feats = sorted(rng.sample(range(500), rng.randint(1, 12)))
            lines.append("%d " % (i % 2) + " ".join(
                "%d:%s" % (j, rng.randint(-40, 40) * 0.25) for j in feats))
        return ("\n".join(lines) + "\n").encode()

    def _digest(self, container):
        from dmlc_tpu.obs import audit

        return audit.rows_digest(container.to_block())

    def test_vector_scalar_digest_equal(self):
        chunk = self._canned_chunk()
        digests = {}
        for name, fn in (("vector", vparse.parse_libsvm_vector),
                         ("scalar", vparse.parse_libsvm_scalar)):
            out = RowBlockContainer()
            fn(chunk, out)
            digests[name] = self._digest(out)
        assert digests["vector"] == digests["scalar"]

    def test_native_digest_matches(self):
        from dmlc_tpu import native
        from dmlc_tpu.data.parsers import _native_libsvm

        if not native.available():
            pytest.skip("native library not built")
        chunk = self._canned_chunk()
        nat = _native_libsvm(chunk)
        assert nat is not None
        out = RowBlockContainer()
        vparse.parse_libsvm_vector(chunk, out)
        assert self._digest(nat) == self._digest(out)

    def test_container_block_and_slice_digests_equal(self):
        from dmlc_tpu.obs import audit

        chunk = self._canned_chunk()
        out = RowBlockContainer()
        vparse.parse_libsvm_vector(chunk, out)
        block = out.to_block()
        # container ≡ finalized block (concatenation invariance)
        assert audit.rows_digest(out) == audit.rows_digest(block)
        # ...and ≡ any re-chunking of the same rows (a container of
        # zero-copy slices hashes like the concatenated whole)
        resliced = RowBlockContainer()
        for start in range(0, len(block), 37):
            resliced.push_block(block.slice(start,
                                            min(start + 37, len(block))))
        assert audit.rows_digest(resliced) == audit.rows_digest(block)


def _write_corpus(path, rows=3000, seed=3):
    rng = random.Random(seed)
    lines = []
    for i in range(rows):
        feats = sorted(rng.sample(range(1000), rng.randint(1, 10)))
        lines.append("%d " % (i % 2) + " ".join(
            "%d:%.5f" % (j, rng.random()) for j in feats))
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


class TestBackendsEndToEnd:
    """create_parser honors DMLC_TPU_PARSE_BACKEND / DMLC_TPU_PARSE_PROCS
    and every route yields the same rows in the same order."""

    def _read_all(self, uri):
        from dmlc_tpu.data.parsers import create_parser

        parser = create_parser(uri)
        try:
            blocks = list(parser)
            labels = np.concatenate([b.label for b in blocks])
            nnz = sum(b.num_nonzero for b in blocks)
            return labels, nnz
        finally:
            parser.close()

    def test_backends_agree(self, tmp_path, monkeypatch):
        path = str(tmp_path / "corpus.svm")
        _write_corpus(path)
        results = {}
        for backend in ("auto", "vector", "scalar"):
            monkeypatch.setenv("DMLC_TPU_PARSE_BACKEND", backend)
            results[backend] = self._read_all(path)
        ref_labels, ref_nnz = results["auto"]
        for backend, (labels, nnz) in results.items():
            assert nnz == ref_nnz, backend
            np.testing.assert_array_equal(labels, ref_labels, err_msg=backend)

    def test_procs_ordering(self, tmp_path, monkeypatch):
        """DMLC_TPU_PARSE_PROCS>1: same rows, same order, multiple chunks
        in flight through the process pool."""
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.data.pipeline import PipelinedParser
        from dmlc_tpu.io.input_split import create_input_split

        path = str(tmp_path / "corpus.svm")
        _write_corpus(path, rows=2000, seed=9)

        def build(procs):
            monkeypatch.setenv("DMLC_TPU_PARSE_PROCS", str(procs))
            monkeypatch.setenv("DMLC_TPU_PARSE_BACKEND", "vector")
            source = create_input_split(path, 0, 1, "text",
                                        threaded=False)
            source.hint_chunk_size(4096)  # force many chunks in flight
            return PipelinedParser(LibSVMParser(source, nthread=1),
                                   nthread=2)

        serial = build(0)
        ref = [b.label for b in serial]
        serial.close()
        assert len(ref) > 3, "chunk hint failed to split the corpus"

        pooled = build(2)
        got = [b.label for b in pooled]
        stats = pooled.stats()
        pooled.close()
        assert stats["procs"] == 2
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_procs_error_poisoning_in_order(self, tmp_path, monkeypatch):
        """A chunk that fails to parse surfaces its error at the chunk's
        in-order position and poisons the window — identically with the
        process pool behind the workers."""
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.data.pipeline import PipelinedParser
        from dmlc_tpu.io.input_split import create_input_split

        path = str(tmp_path / "poison.svm")
        good = "\n".join("1 %d:1" % i for i in range(200))
        with open(path, "w") as fh:
            fh.write(good + "\nBADTOKEN 1:2\n" + good + "\n")

        for procs in (0, 2):
            monkeypatch.setenv("DMLC_TPU_PARSE_PROCS", str(procs))
            monkeypatch.setenv("DMLC_TPU_PARSE_BACKEND", "vector")
            source = create_input_split(path, 0, 1, "text",
                                        threaded=False)
            source.hint_chunk_size(1024)
            parser = PipelinedParser(LibSVMParser(source, nthread=1),
                                     nthread=2)
            try:
                with pytest.raises(ValueError):
                    for _ in parser:
                        pass
            finally:
                parser.close()

    def test_injected_fault_poisons_window(self, monkeypatch, tmp_path):
        """The parse.chunk faultpoint (docs/robustness.md catalog) fires
        on the worker thread and surfaces in order."""
        from dmlc_tpu import resilience
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.data.pipeline import PipelinedParser
        from dmlc_tpu.io.input_split import create_input_split
        from dmlc_tpu.resilience import InjectedFault

        path = str(tmp_path / "fault.svm")
        _write_corpus(path, rows=500, seed=5)
        monkeypatch.setenv("DMLC_TPU_FAULTS", "parse.chunk:nth=2")
        resilience.reset()
        try:
            source = create_input_split(path, 0, 1, "text",
                                        threaded=False)
            source.hint_chunk_size(4096)
            parser = PipelinedParser(LibSVMParser(source, nthread=1),
                                     nthread=2)
            try:
                with pytest.raises(InjectedFault):
                    for _ in parser:
                        pass
            finally:
                parser.close()
        finally:
            monkeypatch.delenv("DMLC_TPU_FAULTS")
            resilience.reset()


class TestPallasTokenizer:
    """The Pallas boundary kernel matches vparse.token_boundary_masks
    byte-for-byte (interpret mode, passed explicitly: Mosaic targets the
    TPU only)."""

    def test_mask_parity(self):
        from dmlc_tpu.ops import pallas_kernels

        r = random.Random(77)
        alphabet = b"0123456789.:-+e \t\r\nqid"
        for size in (0, 1, 127, 128, 129, 4096, 33000):
            data = bytes(r.choice(alphabet) for _ in range(size))
            a = np.frombuffer(data, dtype=np.uint8)
            ns, ne = vparse.token_boundary_masks(a)
            ps, pe = pallas_kernels.tokenize_boundaries(a, interpret=True)
            np.testing.assert_array_equal(ns, ps)
            np.testing.assert_array_equal(ne, pe)

    def test_gated_span_helper(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PALLAS", "parse")
        a = np.frombuffer(b"1 2:3 4:5\n0 6:7\n", dtype=np.uint8)
        starts, ends = vparse.pallas_token_spans(a, interpret=True)
        sm, em = vparse.token_boundary_masks(a)
        np.testing.assert_array_equal(starts, np.flatnonzero(sm))
        np.testing.assert_array_equal(ends, np.flatnonzero(em) + 1)
        # asking for the kernel where Mosaic cannot target the backend
        # fails loudly — never a silent return to the numpy tokenizer
        with pytest.raises(Exception):
            vparse.pallas_token_spans(a)
        monkeypatch.setenv("DMLC_TPU_PALLAS", "0")
        assert vparse.pallas_token_spans(a) is None
