"""Stream/FileSystem/serializer/URISpec tests (mirrors unittest_serializer.cc,
unittest_json.cc round-trip intent, filesys_test.cc, iostream_test.cc)."""

import gc
import random
import sys
import threading
import time

import numpy as np
import pytest

from dmlc_tpu.io import (
    FixedMemoryStream,
    MemoryStream,
    URI,
    URISpec,
    create_stream,
    load_obj,
    save_obj,
)
from dmlc_tpu.io.filesystem import (
    FILE_TYPE_DIR,
    FILE_TYPE_FILE,
    MemoryFileSystem,
    get_filesystem,
)
from dmlc_tpu.utils.threaded_iter import ThreadedIter


@pytest.fixture(autouse=True)
def _clean_memfs():
    MemoryFileSystem.reset()
    yield
    MemoryFileSystem.reset()


class TestURI:
    def test_parse(self):
        uri = URI.parse("hdfs://host:9000/a/b.txt")
        assert uri.protocol == "hdfs://"
        assert uri.host == "host:9000"
        assert uri.name == "/a/b.txt"

    def test_plain_path(self):
        uri = URI.parse("/tmp/x")
        assert uri.protocol == "file://"
        assert uri.name == "/tmp/x"
        assert uri.str_full() == "/tmp/x"


class TestURISpec:
    def test_args_and_cache(self):
        spec = URISpec("hdfs:///data/?format=libsvm&clabel=0#mycache", 2, 4)
        assert spec.uri == "hdfs:///data/"
        assert spec.args == {"format": "libsvm", "clabel": "0"}
        assert spec.cache_file == "mycache.split4.part2"

    def test_single_part_no_suffix(self):
        spec = URISpec("/data.txt#cache", 0, 1)
        assert spec.cache_file == "cache"

    def test_no_sugar(self):
        spec = URISpec("/plain.txt", 0, 1)
        assert spec.uri == "/plain.txt"
        assert spec.args == {}
        assert spec.cache_file == ""

    def test_double_hash_rejected(self):
        with pytest.raises(Exception):
            URISpec("/a#b#c", 0, 1)


class TestStreams:
    def test_memory_stream_roundtrip(self):
        s = MemoryStream()
        s.write_uint32(7)
        s.write_uint64(1 << 40)
        s.write_bytes_prefixed(b"hello")
        s.seek(0)
        assert s.read_uint32() == 7
        assert s.read_uint64() == 1 << 40
        assert s.read_bytes_prefixed() == b"hello"

    def test_fixed_memory_stream(self):
        buf = bytearray(8)
        s = FixedMemoryStream(buf)
        s.write(b"abcd")
        with pytest.raises(IOError):
            s.write(b"toolong67")
        s.seek(0)
        assert s.read(4) == b"abcd"

    def test_read_exact_raises_at_eof(self):
        s = MemoryStream(b"abc")
        with pytest.raises(EOFError):
            s.read_exact(4)

    def test_local_file_stream(self, tmp_path):
        path = str(tmp_path / "f.bin")
        with create_stream(path, "w") as s:
            s.write(b"data123")
        with create_stream(path, "r") as s:
            assert s.read(100) == b"data123"
        with create_stream(path, "a") as s:
            s.write(b"-more")
        with create_stream(path, "r") as s:
            assert s.read(100) == b"data123-more"

    def test_allow_null(self):
        assert create_stream("/nonexistent/x", "r", allow_null=True) is None


class TestSerializer:
    def test_roundtrip_nested(self):
        obj = {
            "ints": [1, -5, 2**70],
            "floats": (3.14, -0.0),
            "strs": {"k": "väl", "b": b"\x00\xff"},
            "none": None,
            "flag": True,
            "set": {1, 2, 3},
        }
        s = MemoryStream()
        save_obj(s, obj)
        s.seek(0)
        assert load_obj(s) == obj

    def test_ndarray(self):
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        s = MemoryStream()
        save_obj(s, {"w": arr})
        s.seek(0)
        out = load_obj(s)
        np.testing.assert_array_equal(out["w"], arr)
        assert out["w"].dtype == np.float32

    def test_unsupported_type_raises(self):
        with pytest.raises(TypeError):
            save_obj(MemoryStream(), object())


class TestMemoryFileSystem:
    def test_put_stat_list_read(self):
        MemoryFileSystem.put("h/a/x.txt", b"xx")
        MemoryFileSystem.put("h/a/y.txt", b"yyy")
        MemoryFileSystem.put("h/a/sub/z.txt", b"z")
        fs = get_filesystem(URI.parse("mem://h/a"))
        info = fs.get_path_info(URI.parse("mem://h/a/x.txt"))
        assert info.size == 2 and info.type == FILE_TYPE_FILE
        listing = fs.list_directory(URI.parse("mem://h/a"))
        names = [i.path.name for i in listing]
        assert names == ["/a/sub", "/a/x.txt", "/a/y.txt"]
        assert [i.type for i in listing] == [FILE_TYPE_DIR, FILE_TYPE_FILE, FILE_TYPE_FILE]
        rec = fs.list_directory_recursive(URI.parse("mem://h/a"))
        assert sorted(i.path.name for i in rec) == ["/a/sub/z.txt", "/a/x.txt", "/a/y.txt"]

    def test_write_via_stream(self):
        with create_stream("mem://h/out.bin", "w") as s:
            s.write(b"abc")
        with create_stream("mem://h/out.bin", "a") as s:
            s.write(b"def")
        with create_stream("mem://h/out.bin", "r") as s:
            assert s.read(10) == b"abcdef"


class TestThreadedIter:
    def test_basic_prefetch(self):
        ti = ThreadedIter(lambda: iter(range(100)), max_capacity=4)
        assert list(ti) == list(range(100))

    def test_before_first_restarts(self):
        ti = ThreadedIter(lambda: iter(range(5)))
        assert list(ti) == [0, 1, 2, 3, 4]
        ti.before_first()
        assert list(ti) == [0, 1, 2, 3, 4]

    def test_exception_propagates(self):
        def bad():
            yield 1
            raise ValueError("producer died")

        ti = ThreadedIter(bad)
        assert ti.next() == 1
        with pytest.raises(ValueError, match="producer died"):
            while ti.next() is not None:
                pass

    def test_early_close_mid_epoch(self):
        ti = ThreadedIter(lambda: iter(range(10**6)), max_capacity=2)
        assert ti.next() == 0
        ti.close()  # must not hang


class _Source:
    """A rewindable source of ``n`` items a pass; an item is (pass, place),
    so a consumer can tell which pass it was handed."""

    def __init__(self, n, rewinds=True):
        self.n = n
        self.rewinds = rewinds
        self.epoch = 0
        self.rewound_on = []  # thread idents, one a rewind

    def make_iter(self):
        epoch = self.epoch
        return ((epoch, k) for k in range(self.n))

    def rewind(self):
        if not self.rewinds:
            return False
        self.epoch += 1
        self.rewound_on.append(threading.get_ident())
        return True

    def a_pass(self, epoch):
        return [(epoch, k) for k in range(self.n)]


def _restart(ti, src):
    """What an owner does between passes: the staged pass where there is
    one, else rewind the source itself and restart the producer."""
    if ti.advance():
        return True
    src.rewind()
    ti.before_first()
    return False


class TestThreadedIterRewindsItself:
    def _iter(self, src, cap=2):
        return ThreadedIter(src.make_iter, max_capacity=cap,
                            rewind=src.rewind, name="rewinds-itself")

    def test_next_pass_is_staged_and_iteration_ends_at_the_mark(self):
        src = _Source(20)
        ti = self._iter(src)
        try:
            for epoch in range(3):
                # the end mark follows the rewind: once a pass has been
                # read to its end the next one is the producer's already
                assert list(ti) == src.a_pass(epoch)
                assert list(ti) == []  # nothing of the next pass leaks
                assert src.epoch == epoch + 1
                assert ti.advance() is True
            assert src.rewound_on and threading.get_ident() not in \
                src.rewound_on
        finally:
            ti.close()

    def test_advance_in_mid_pass_stops_the_producer(self):
        src = _Source(1000)
        ti = self._iter(src)
        try:
            assert [ti.next() for _ in range(3)] == src.a_pass(0)[:3]
            assert _restart(ti, src) is False
            assert src.rewound_on == [threading.get_ident()]
            assert list(ti) == src.a_pass(1)
        finally:
            ti.close()

    def test_advance_in_mid_pass_drops_the_rest_of_a_pass_wound_past(self):
        # a source shorter than the queue: the producer is a pass ahead
        # while the consumer is in mid-pass, and the source is rewound
        # once for the restart, not twice
        src = _Source(2)
        ti = self._iter(src, cap=8)
        try:
            assert ti.next() == (0, 0)
            deadline = time.monotonic() + 30
            while src.epoch < 2 and time.monotonic() < deadline:
                time.sleep(0.001)  # the producer runs ahead on its own
            assert src.epoch >= 2
            assert _restart(ti, src) is True
            assert list(ti) == src.a_pass(1)
            assert _restart(ti, src) is True
            assert list(ti) == src.a_pass(2)
        finally:
            ti.close()

    def test_a_rewind_that_declines_ends_the_producer(self):
        src = _Source(5, rewinds=False)
        ti = self._iter(src)
        assert list(ti) == src.a_pass(0)
        ti._thread.join(timeout=10)
        assert not ti._thread.is_alive()
        assert ti.advance() is False
        ti.before_first()
        assert list(ti) == src.a_pass(0)
        ti.close()

    def test_an_error_in_the_staged_pass_is_raised_in_that_pass(self):
        calls = []

        def make_iter():
            calls.append(len(calls))
            if len(calls) == 2:
                raise ValueError("second pass died")
            return iter(range(3))

        ti = ThreadedIter(make_iter, max_capacity=2, rewind=lambda: True)
        try:
            assert list(ti) == [0, 1, 2]
            assert ti.advance() is True
            with pytest.raises(ValueError, match="second pass died"):
                list(ti)
        finally:
            ti.close()

    def test_a_dropped_iterator_stops_its_thread(self):
        src = _Source(4)
        ti = self._iter(src)
        assert list(ti) == src.a_pass(0)
        thread = ti._thread
        assert thread.is_alive()  # parked on the staged pass
        del ti
        gc.collect()
        thread.join(timeout=10)
        assert not thread.is_alive()

    def test_stress_restarts_at_random_places(self):
        """More consumers than cores, each with its own iterator, a short
        switch interval: whatever place a restart falls on, the pass after
        it is whole and in order, and the source was rewound exactly once
        for every pass begun (by the producer or by the consumer)."""
        import random
        import sys
        failures = []

        def consumer(seed):
            rng = random.Random(seed)
            src = _Source(rng.choice((1, 2, 3, 17)))
            ti = self._iter(src, cap=rng.choice((1, 2, 4)))
            try:
                begun = 0
                for _ in range(60):
                    stop_at = rng.choice((None, 0, 1, 2, 9))
                    got = []
                    while stop_at is None or len(got) < stop_at:
                        item = ti.next()
                        if item is None:
                            break
                        got.append(item)
                    want = src.a_pass(begun)
                    if got != want[:len(got)] or (
                            stop_at is None and got != want):
                        failures.append((seed, begun, got))
                        return
                    _restart(ti, src)
                    begun += 1
                if list(ti) != src.a_pass(begun):
                    failures.append((seed, begun, "last pass"))
            except Exception as err:  # noqa: BLE001 — reported below
                failures.append((seed, repr(err)))
            finally:
                ti.close()

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=consumer, args=(s,))
                       for s in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert failures == []
