"""Data layer tests (mirrors libsvm_parser_test.cc / csv_parser_test.cc /
dataiter_test.cc intent plus RowBlock unit coverage)."""

import numpy as np
import pytest

from dmlc_tpu.data import (
    BasicRowIter,
    CSVParser,
    DiskRowIter,
    LibFMParser,
    LibSVMParser,
    RowBlockContainer,
    ThreadedParser,
    create_parser,
    create_row_block_iter,
)
from dmlc_tpu.io import MemoryStream
from dmlc_tpu.io.filesystem import MemoryFileSystem
from dmlc_tpu.io.input_split import create_input_split


@pytest.fixture(autouse=True)
def _clean_memfs():
    MemoryFileSystem.reset()
    yield
    MemoryFileSystem.reset()


def put_and_split(body: bytes, key="test/data.txt", part=0, nparts=1):
    MemoryFileSystem.put(key, body)
    return create_input_split(f"mem://{key}", part, nparts, "text", threaded=False)


class TestRowBlock:
    def make_block(self):
        c = RowBlockContainer()
        c.push_row(1.0, [0, 3], [0.5, 2.0])
        c.push_row(0.0, [1], [1.5])
        c.push_row(1.0, [0, 2, 4], [1.0, 1.0, 3.0])
        return c.to_block()

    def test_shape_and_rows(self):
        b = self.make_block()
        assert len(b) == 3
        assert b.num_nonzero == 6
        row = b[2]
        assert row.label == 1.0
        np.testing.assert_array_equal(row.index, [0, 2, 4])
        assert row.get_value(2) == 3.0

    def test_sdot(self):
        b = self.make_block()
        w = np.arange(5, dtype=np.float32)
        assert b[0].sdot(w) == pytest.approx(0 * 0.5 + 3 * 2.0)

    def test_slice(self):
        b = self.make_block()
        s = b.slice(1, 3)
        assert len(s) == 2
        np.testing.assert_array_equal(s.offset, [0, 1, 4])
        np.testing.assert_array_equal(s[1].index, [0, 2, 4])

    def test_to_dense(self):
        b = self.make_block()
        dense = b.to_dense()
        assert dense.shape == (3, 5)
        assert dense[0, 3] == 2.0
        assert dense[1, 1] == 1.5

    def test_value_none_means_ones(self):
        c = RowBlockContainer()
        c.push_row(1.0, [0, 2])
        b = c.to_block()
        assert b.value is None
        assert b[0].get_value(0) == 1.0
        np.testing.assert_array_equal(b.to_dense(3)[0], [1, 0, 1])

    def test_save_load_roundtrip(self):
        c = RowBlockContainer()
        c.push_row(1.0, [0, 3], [0.5, 2.0], weight=0.9, qid=7)
        c.push_row(0.0, [1], [1.5], weight=1.1, qid=8)
        s = MemoryStream()
        c.save(s)
        s.seek(0)
        c2 = RowBlockContainer.load(s)
        b1, b2 = c.to_block(), c2.to_block()
        np.testing.assert_array_equal(b1.offset, b2.offset)
        np.testing.assert_array_equal(b1.index, b2.index)
        np.testing.assert_array_equal(b1.value, b2.value)
        np.testing.assert_array_equal(b1.weight, b2.weight)
        np.testing.assert_array_equal(b1.qid, b2.qid)
        assert c2.max_index == c.max_index

    def test_mem_cost(self):
        assert self.make_block().mem_cost_bytes() > 0


class TestLibSVMParser:
    def test_basic(self):
        split = put_and_split(b"1 0:0.5 3:2\n0 1:1.5\n1 0:1 2:1 4:3\n")
        parser = LibSVMParser(split, nthread=1)
        blocks = list(parser)
        assert len(blocks) == 1
        b = blocks[0]
        assert len(b) == 3
        np.testing.assert_array_equal(b.label, [1, 0, 1])
        np.testing.assert_array_equal(b.index, [0, 3, 1, 0, 2, 4])
        np.testing.assert_allclose(b.value, [0.5, 2, 1.5, 1, 1, 3])

    def test_weights(self):
        split = put_and_split(b"1:0.25 0:1\n0:0.75 1:2\n")
        b = LibSVMParser(split, nthread=1).next_block()
        np.testing.assert_allclose(b.label, [1, 0])
        np.testing.assert_allclose(b.weight, [0.25, 0.75])
        np.testing.assert_allclose(b.value, [1, 2])

    def test_qid_slow_path(self):
        split = put_and_split(b"1 qid:5 0:0.5\n0 qid:6 1:2\n")
        b = LibSVMParser(split, nthread=1).next_block()
        np.testing.assert_array_equal(b.qid, [5, 6])
        np.testing.assert_array_equal(b.index, [0, 1])

    def test_bare_index_fallback(self):
        split = put_and_split(b"1 0 3\n0 2\n")
        b = LibSVMParser(split, nthread=1).next_block()
        assert b.value is None or np.all(b.value == 1.0)
        np.testing.assert_array_equal(b.index, [0, 3, 2])

    def test_scientific_and_negative(self):
        split = put_and_split(b"-1 0:-2.5e-3 7:1e4\n")
        b = LibSVMParser(split, nthread=1).next_block()
        assert b.label[0] == -1
        np.testing.assert_allclose(b.value, [-2.5e-3, 1e4], rtol=1e-6)

    def test_multithread_matches_single(self):
        lines = b"".join(
            b"%d 0:%d.5 %d:2\n" % (i % 2, i, 1 + i % 17) for i in range(3000)
        )
        b1 = LibSVMParser(put_and_split(lines), nthread=1).next_block()
        b4 = LibSVMParser(put_and_split(lines, key="test/d2.txt"), nthread=4).next_block()
        np.testing.assert_array_equal(b1.label, b4.label)
        np.testing.assert_array_equal(b1.index, b4.index)
        np.testing.assert_allclose(b1.value, b4.value)
        np.testing.assert_array_equal(b1.offset, b4.offset)


class TestLibFMParser:
    def test_basic(self):
        split = put_and_split(b"1 2:3:0.5 0:1:2\n0 1:4:1.5\n")
        b = LibFMParser(split, nthread=1).next_block()
        np.testing.assert_array_equal(b.label, [1, 0])
        np.testing.assert_array_equal(b.field, [2, 0, 1])
        np.testing.assert_array_equal(b.index, [3, 1, 4])
        np.testing.assert_allclose(b.value, [0.5, 2, 1.5])


class TestCSVParser:
    def test_no_label_column(self):
        split = put_and_split(b"1,2,3\n4,5,6\n")
        b = CSVParser(split, {}, nthread=1).next_block()
        np.testing.assert_array_equal(b.label, [0, 0])
        np.testing.assert_array_equal(b.index, [0, 1, 2, 0, 1, 2])
        np.testing.assert_allclose(b.value, [1, 2, 3, 4, 5, 6])

    def test_label_column(self):
        split = put_and_split(b"7,1,2\n8,3,4\n")
        b = CSVParser(split, {"label_column": "0"}, nthread=1).next_block()
        np.testing.assert_array_equal(b.label, [7, 8])
        np.testing.assert_allclose(b.value, [1, 2, 3, 4])
        np.testing.assert_array_equal(b.index, [0, 1, 0, 1])

    def test_uri_args_via_factory(self):
        MemoryFileSystem.put("test/c.csv", b"9,1\n3,2\n")
        parser = create_parser(
            "mem://test/c.csv?format=csv&label_column=0", threaded=False
        )
        b = parser.next_block()
        np.testing.assert_array_equal(b.label, [9, 3])


class TestFactoryAndIters:
    LIBSVM = b"".join(b"%d 0:%d 3:1\n" % (i % 2, i) for i in range(500))

    def test_create_parser_default_libsvm(self):
        MemoryFileSystem.put("test/x.svm", self.LIBSVM)
        parser = create_parser("mem://test/x.svm")
        # mem:// is a registered remote-style filesystem: with the native
        # library loaded it takes the push-mode native pipeline; otherwise
        # the Python cross-chunk PipelinedParser stack
        from dmlc_tpu import native
        from dmlc_tpu.data.parsers import NativePipelineParser
        from dmlc_tpu.data.pipeline import PipelinedParser

        if native.available():
            assert isinstance(parser, NativePipelineParser)
        else:
            assert isinstance(parser, PipelinedParser)
        total = sum(len(b) for b in parser)
        assert total == 500

    def test_parser_before_first(self):
        MemoryFileSystem.put("test/x.svm", self.LIBSVM)
        parser = create_parser("mem://test/x.svm", threaded=False)
        n1 = sum(len(b) for b in parser)
        parser.before_first()
        n2 = sum(len(b) for b in parser)
        assert n1 == n2 == 500

    def test_basic_row_iter(self):
        MemoryFileSystem.put("test/x.svm", self.LIBSVM)
        it = create_row_block_iter("mem://test/x.svm")
        assert isinstance(it, BasicRowIter)
        blocks = list(it)
        assert len(blocks) == 1 and len(blocks[0]) == 500
        it.before_first()
        assert sum(len(b) for b in it) == 500
        assert it.num_col() == 4  # max index 3 + 1

    def test_disk_row_iter(self, tmp_path):
        MemoryFileSystem.put("test/x.svm", self.LIBSVM)
        cache = tmp_path / "rows.cache"
        it = create_row_block_iter(f"mem://test/x.svm#{cache}")
        assert isinstance(it, DiskRowIter)
        total1 = sum(len(b) for b in it)
        it.before_first()
        total2 = sum(len(b) for b in it)
        assert total1 == total2 == 500
        assert cache.exists()
        # reload from cache only (no source)
        it2 = DiskRowIter(None, str(cache))
        assert sum(len(b) for b in it2) == 500
        assert it2.num_col() == 4
        it.close()
        it2.close()

    def test_sharded_parse_exactly_once(self):
        MemoryFileSystem.put("test/x.svm", self.LIBSVM)
        labels = []
        for part in range(4):
            parser = create_parser("mem://test/x.svm", part, 4, threaded=False)
            for block in parser:
                labels.extend(block.label.tolist())
        assert len(labels) == 500
