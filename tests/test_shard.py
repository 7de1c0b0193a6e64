"""Baked columnar shards (io/shard.py + tools/bake.py): format round
trip, corruption rejection, windowed global shuffle, audit coverage,
and dispatcher-ledger resume with shuffle armed.

The format's contract is bit-parity: bake(text) read back through
``ShardParser`` must deliver exactly the rows the text parser delivers
(``rows_digest`` over the canonical ``audit_arrays`` stream — invariant
to chunking, so re-windowing at bake time is invisible). Everything
else (shuffle, mmap, the dispatcher path) must preserve that parity.
"""

import hashlib
import os

import numpy as np
import pytest

from dmlc_tpu import resilience
from dmlc_tpu.data.parsers import create_parser
from dmlc_tpu.data.row_block import RowBlockContainer
from dmlc_tpu.io.shard import (
    MAGIC,
    ShardParser,
    ShardReader,
    ShardWriter,
    cache_token,
    is_shard_uri,
)
from dmlc_tpu.obs.audit import Auditor, rows_digest
from dmlc_tpu.resilience import InjectedFault
from dmlc_tpu.tools.bake import bake_dataset
from dmlc_tpu.utils.logging import DMLCError

ROWS = 600


@pytest.fixture(autouse=True)
def _clean_state():
    resilience.reset()
    yield
    resilience.reset()


@pytest.fixture()
def svm_file(tmp_path):
    """LibSVM corpus with unique labels (order-sensitive comparisons)
    and per-row varying sparsity, including empty rows."""
    rng = np.random.default_rng(7)
    path = tmp_path / "corpus.svm"
    with open(path, "w") as fh:
        for i in range(ROWS):
            n = int(rng.integers(0, 9))
            feats = sorted(rng.choice(60, size=n, replace=False))
            cols = " ".join("%d:%.5f" % (j, rng.random()) for j in feats)
            fh.write(("%d %s\n" % (i, cols)).rstrip() + "\n")
    return str(path)


@pytest.fixture()
def csv_file(tmp_path):
    rng = np.random.default_rng(11)
    path = tmp_path / "corpus.csv"
    with open(path, "w") as fh:
        for i in range(ROWS):
            fh.write("%d,%s\n" % (
                i, ",".join("%.4f" % v for v in rng.random(6))))
    return str(path)


def drain(parser):
    out = RowBlockContainer()
    for block in parser:
        out.push_block(block)
    parser.close()
    return out


def text_digest(uri, data_format):
    return rows_digest(drain(create_parser(uri, 0, 1,
                                           data_format=data_format)))


# ---------------------------------------------------------------------------
# round trip
# ---------------------------------------------------------------------------


class TestRoundTrip:
    def test_libsvm_bit_parity(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        out = bake_dataset(svm_file, dst, data_format="libsvm",
                           rows_per_window=64)
        assert out["rows"] == ROWS and not out["skipped"]
        assert rows_digest(drain(create_parser(dst, 0, 1))) == \
            text_digest(svm_file, "libsvm")

    def test_csv_dense_bit_parity(self, csv_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(csv_file, dst, data_format="csv", rows_per_window=50)
        assert rows_digest(drain(create_parser(dst, 0, 1))) == \
            text_digest(csv_file, "csv")

    def test_arrays_byte_exact(self, svm_file, tmp_path):
        """Beyond the digest: the concatenated columns are byte-equal."""
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=37)
        a = drain(create_parser(svm_file, 0, 1, data_format="libsvm")
                  ).to_block()
        b = drain(create_parser(dst, 0, 1)).to_block()
        assert a.offset.tobytes() == b.offset.tobytes()
        assert a.label.tobytes() == b.label.tobytes()
        assert a.index.tobytes() == b.index.tobytes()
        assert a.value.tobytes() == b.value.tobytes()

    def test_mmap_and_read_paths_agree(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        with ShardReader(dst, use_mmap=True) as mm, \
                ShardReader(dst, use_mmap=False) as fr:
            assert mm.num_windows == fr.num_windows > 1
            for i in range(mm.num_windows):
                assert rows_digest(mm.read_window(i)) == \
                    rows_digest(fr.read_window(i))

    def test_optional_columns_survive(self, tmp_path):
        """weight/qid/field segments round-trip (flag-gated columns;
        field is u32 in the format, like INDEX_DTYPE)."""
        src = RowBlockContainer()
        rng = np.random.default_rng(3)
        for i in range(40):
            src.push_row(float(i), [i % 5, 5 + i % 7],
                         value=[rng.random(), rng.random()],
                         weight=0.5 + i, qid=i // 4,
                         field=np.asarray([1, 2], dtype=np.uint32))
        dst = str(tmp_path / "opt.dtsh")
        with ShardWriter(dst, rows_per_window=16) as w:
            w.write_block(src.to_block())
        got = RowBlockContainer()
        with ShardReader(dst) as rd:
            for i in range(rd.num_windows):
                got.push_block(rd.read_window(i))
        assert rows_digest(got) == rows_digest(src)
        blk = got.to_block()
        assert blk.weight is not None and blk.qid is not None
        assert blk.field is not None

    def test_weighted_qid_libsvm_parity(self, tmp_path):
        """Real text path for the optional per-row columns: libsvm with
        ``label:weight`` and ``qid:n`` bakes bit-identically."""
        path = tmp_path / "wq.svm"
        rng = np.random.default_rng(5)
        with open(path, "w") as fh:
            for i in range(200):
                fh.write("%d:%.2f qid:%d 1:%.4f %d:%.4f\n" % (
                    i, 0.25 + (i % 4), i // 10, rng.random(),
                    2 + i % 9, rng.random()))
        dst = str(tmp_path / "wq.dtsh")
        bake_dataset(str(path), dst, data_format="libsvm",
                     rows_per_window=48)
        assert rows_digest(drain(create_parser(dst, 0, 1))) == \
            text_digest(str(path), "libsvm")

    def test_libfm_field_parity(self, tmp_path):
        """libfm's field column survives the bake bit-exactly."""
        path = tmp_path / "fm.libfm"
        rng = np.random.default_rng(6)
        with open(path, "w") as fh:
            for i in range(200):
                fh.write("%d 0:%d:%.4f 1:%d:%.4f\n" % (
                    i % 2, i % 7, rng.random(), 7 + i % 5, rng.random()))
        dst = str(tmp_path / "fm.dtsh")
        bake_dataset(str(path), dst, data_format="libfm",
                     rows_per_window=48)
        assert rows_digest(drain(create_parser(dst, 0, 1))) == \
            text_digest(str(path), "libfm")

    def test_partitioned_read_matches_whole(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        whole = drain(create_parser(dst, 0, 1))
        parts = RowBlockContainer()
        for k in range(3):
            part = drain(create_parser(dst, k, 3))
            parts.push_block(part.to_block())
        assert rows_digest(parts) == rows_digest(whole)

    def test_create_parser_format_resolution(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm")
        assert is_shard_uri(dst) and not is_shard_uri(svm_file)
        for uri, kw in ((dst, {}), (dst, {"data_format": "shard"}),
                        (dst + "?format=shard", {})):
            assert rows_digest(drain(create_parser(uri, 0, 1, **kw))) == \
                text_digest(svm_file, "libsvm")


class TestStageCounters:
    """The shard reader reports its pass under the native pipeline's
    names (``parser.stats()``), so whatever reads a text parser's
    read + parse cost reads a shard parser's too."""

    @pytest.fixture()
    def shard(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        return dst

    @pytest.mark.parametrize("threaded", [True, False])
    def test_cpu_and_wait_restart_with_every_pass(
            self, shard, threaded, monkeypatch):
        import itertools

        from dmlc_tpu.io import shard as shard_mod

        # a thread clock that advances 5 ns a reading: three readings a
        # window give the read 5 ns and the decode 5 ns
        ticks = itertools.count(0, 5)
        monkeypatch.setattr(shard_mod.time, "thread_time_ns",
                            lambda: next(ticks))
        parser = create_parser(shard, 0, 1, threaded=threaded)
        windows = sum(1 for _ in parser)
        assert windows == -(-ROWS // 64)
        stats = parser.stats()
        assert stats["reader_cpu_ns"] == 5 * windows
        assert stats["parse_cpu_ns"] == 5 * windows
        if threaded:  # the caller's waits for the prefetch thread
            assert stats["consumer_wait_ns"] > 0
        else:  # nothing between the caller and the reader to wait for
            assert "consumer_wait_ns" not in stats
        # a second pass reads one pass's worth again, not two
        parser.before_first()
        assert sum(1 for _ in parser) == windows
        again = parser.stats()
        assert again["reader_cpu_ns"] == 5 * windows
        assert again["parse_cpu_ns"] == 5 * windows
        parser.close()

    def test_feed_stats_carry_them(self, shard):
        from dmlc_tpu.device import BatchSpec, DeviceFeed

        feed = DeviceFeed(create_parser(shard, 0, 1),
                          BatchSpec(batch_size=64, layout="csr",
                                    num_features=61))
        assert sum(int(b["num_rows"]) for b in feed) == ROWS
        pipe = feed.stats()["pipeline"]
        assert {"reader_cpu_ns", "parse_cpu_ns", "consumer_wait_ns"} <= set(
            pipe)
        assert pipe["consumer_wait_ns"] > 0
        feed.close()


# ---------------------------------------------------------------------------
# bake CLI + idempotency
# ---------------------------------------------------------------------------


class TestBake:
    def test_rebake_is_idempotent(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        first = bake_dataset(svm_file, dst, data_format="libsvm")
        mtime = os.path.getmtime(dst)
        again = bake_dataset(svm_file, dst, data_format="libsvm")
        assert again["skipped"] and os.path.getmtime(dst) == mtime
        assert not first["skipped"]

    def test_content_change_rebakes(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm")
        with open(svm_file, "a") as fh:
            fh.write("1 3:0.5\n")
        out = bake_dataset(svm_file, dst, data_format="libsvm")
        assert not out["skipped"] and out["rows"] == ROWS + 1

    def test_param_change_rebakes(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        out = bake_dataset(svm_file, dst, data_format="libsvm",
                           rows_per_window=32)
        assert not out["skipped"]

    def test_parallel_bake_matches_single(self, svm_file, tmp_path):
        one = str(tmp_path / "one.dtsh")
        many = str(tmp_path / "many.dtsh")
        bake_dataset(svm_file, one, data_format="libsvm", rows_per_window=64)
        out = bake_dataset(svm_file, many, data_format="libsvm",
                           rows_per_window=64, nparts=3)
        assert len(out["outputs"]) == 3
        assert sum(p["rows"] for p in out["outputs"]) == ROWS
        # reading the 3-file family delivers the same rows as the 1-file
        # bake (file order = part order, so even the sequence matches)
        family = ";".join(p["path"] for p in out["outputs"])
        assert rows_digest(drain(create_parser(family, 0, 1))) == \
            rows_digest(drain(create_parser(one, 0, 1)))

    def test_cli_main(self, svm_file, tmp_path, capsys):
        from dmlc_tpu.tools.bake import main

        dst = str(tmp_path / "cli.dtsh")
        assert main([svm_file, dst, "--format", "libsvm"]) == 0
        assert "rows" in capsys.readouterr().out
        assert main([svm_file, dst, "--format", "libsvm"]) == 0
        assert "up to date" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# corruption fails closed
# ---------------------------------------------------------------------------


class TestCorruption:
    @pytest.fixture()
    def shard(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        return dst

    def _mutate(self, shard, tmp_path, fn):
        bad = str(tmp_path / "bad.dtsh")
        with open(shard, "rb") as fh:
            buf = fh.read()
        with open(bad, "wb") as fh:
            fh.write(fn(buf))
        return bad

    @pytest.mark.parametrize("name,mutate", [
        ("truncated", lambda b: b[: len(b) // 2]),
        ("torn_tail", lambda b: b[:-5]),
        ("crc_flip", lambda b: b[:-40] + bytes([b[-40] ^ 1]) + b[-39:]),
        ("bad_magic", lambda b: b"NOTSHARD" + b[8:]),
        ("empty", lambda b: b""),
        ("magic_only", lambda b: MAGIC),
    ])
    def test_rejected_at_open(self, shard, tmp_path, name, mutate):
        bad = self._mutate(shard, tmp_path, mutate)
        with pytest.raises(DMLCError):
            ShardReader(bad)

    def test_window_skew_rejected(self, shard, tmp_path):
        # flip the first window's tag byte: footer stays valid, the
        # window-level cross-check must catch it
        bad = self._mutate(
            shard, tmp_path,
            lambda b: b[:16] + bytes([b[16] ^ 0xFF]) + b[17:])
        rd = ShardReader(bad)
        with pytest.raises(DMLCError):
            rd.read_window(0)
        rd.close()

    def test_faultpoint_is_transient_oserror(self, shard):
        resilience.configure("shard.read:nth=1")
        with pytest.raises(InjectedFault) as exc:
            ShardReader(shard)
        assert isinstance(exc.value, OSError)
        resilience.reset()
        ShardReader(shard).close()  # unfaulted open works


# ---------------------------------------------------------------------------
# windowed global shuffle
# ---------------------------------------------------------------------------


def labels_in_order(dst, nparts, seed, epochs=1, unit=1):
    """Concatenated delivery order across a world of ``nparts`` readers,
    each advanced ``epochs - 1`` times."""
    out = []
    for k in range(nparts):
        p = ShardParser(dst, k, nparts, seed=seed, shuffle_window=unit)
        for _ in range(epochs - 1):
            p.before_first()
        out.append([v for b in p for v in b.label.tolist()])
        p.close()
    return [v for part in out for v in part]


class TestShuffle:
    @pytest.fixture()
    def shard(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=32)
        return dst

    def test_same_seed_same_order_across_world_sizes(self, shard):
        base = labels_in_order(shard, 1, seed=13)
        for world in (2, 3, 5):
            assert labels_in_order(shard, world, seed=13) == base

    def test_seed_changes_order_not_rowset(self, shard):
        a = labels_in_order(shard, 1, seed=13)
        b = labels_in_order(shard, 1, seed=14)
        plain = labels_in_order(shard, 1, seed=-1)
        assert a != b and a != plain
        assert sorted(a) == sorted(b) == sorted(plain)

    def test_epochs_reshuffle_and_replay(self, shard):
        e0 = labels_in_order(shard, 1, seed=13, epochs=1)
        e1 = labels_in_order(shard, 1, seed=13, epochs=2)
        assert e0 != e1 and sorted(e0) == sorted(e1)
        # a fresh parser replays epoch 0 exactly (resume determinism)
        assert labels_in_order(shard, 1, seed=13, epochs=1) == e0

    def test_reset_partition_composes_with_shuffle(self, shard):
        """Re-sharding mid-job slices the same epoch's global order."""
        full = labels_in_order(shard, 1, seed=21)
        p = ShardParser(shard, 0, 1, seed=21)
        p.reset_partition(0, 2)
        first = [v for b in p for v in b.label.tolist()]
        p.reset_partition(1, 2)
        second = [v for b in p for v in b.label.tolist()]
        p.close()
        assert first + second == full

    def test_shuffle_window_units_stay_contiguous(self, shard):
        """unit=2 moves pairs of windows together: the order differs
        from unit=1 but every aligned window pair stays adjacent."""
        a = labels_in_order(shard, 1, seed=13, unit=1)
        b = labels_in_order(shard, 1, seed=13, unit=2)
        assert sorted(a) == sorted(b) and a != b

    def test_env_knobs_arm_shuffle(self, shard, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_SHUFFLE", "13")
        via_env = [v for b in ShardParser(shard, 0, 1)
                   for v in b.label.tolist()]
        monkeypatch.delenv("DMLC_TPU_SHUFFLE")
        assert via_env == labels_in_order(shard, 1, seed=13)

    def test_uri_arg_beats_env(self, shard, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_SHUFFLE", "99")
        p = ShardParser(shard, 0, 1, args={"shuffle_chunks": "13"})
        got = [v for b in p for v in b.label.tolist()]
        p.close()
        assert got == labels_in_order(shard, 1, seed=13)


# ---------------------------------------------------------------------------
# audit plane coverage
# ---------------------------------------------------------------------------


class TestAudit:
    @pytest.fixture()
    def shard(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        return dst

    def _epoch(self, parser):
        for _ in parser:
            pass

    def test_shard_reader_has_native_digest_points(self, shard, monkeypatch):
        """DMLC_TPU_AUDIT armed must not force a text re-parse of baked
        input: the ShardParser itself records io_read + parse chains."""
        monkeypatch.setenv("DMLC_TPU_AUDIT", "1")
        from dmlc_tpu.obs import audit as audit_mod

        aud = Auditor(rank=0)
        monkeypatch.setattr(audit_mod, "auditor", lambda: aud)
        parser = create_parser(shard, 0, 1)
        self._epoch(parser)
        parser.close()
        snap = aud.snapshot()
        assert snap["chains"]["io_read"] > 0
        assert snap["chains"]["parse"] > 0
        assert not aud.divergences

    def test_epoch_roll_clean_without_shuffle(self, shard, monkeypatch):
        aud = Auditor(rank=0)
        p = ShardParser(shard, 0, 1, seed=-1)
        monkeypatch.setattr(p, "_audit", aud)
        p._stamp_audit()
        self._epoch(p)
        assert aud.roll_epoch(0) == []
        p.before_first()
        self._epoch(p)
        # identical bytes epoch over epoch: the self-compare must be
        # exercised (same shard signature) and clean
        assert aud.roll_epoch(1) == []
        assert not aud.divergences
        p.close()

    def test_epoch_roll_clean_with_shuffle(self, shard, monkeypatch):
        """Per-epoch reshuffle legitimately reorders delivery; the
        epoch-salted shard signature scopes chains to one epoch so the
        roll must not report false divergences."""
        aud = Auditor(rank=0)
        p = ShardParser(shard, 0, 1, seed=17)
        monkeypatch.setattr(p, "_audit", aud)
        p._stamp_audit()
        self._epoch(p)
        assert aud.roll_epoch(0) == []
        p.before_first()
        self._epoch(p)
        assert aud.roll_epoch(1) == []
        assert not aud.divergences
        p.close()

    def test_cross_run_chains_match(self, shard, monkeypatch):
        """Two runs over the same shard + seed + epoch produce identical
        chains (the cross-rank/restart comparison the tracker does)."""
        chains = []
        for _ in range(2):
            aud = Auditor(rank=0)
            p = ShardParser(shard, 0, 1, seed=17)
            monkeypatch.setattr(p, "_audit", aud)
            p._stamp_audit()
            self._epoch(p)
            snap = aud.export()
            chains.append((snap["shard"], snap["chains"]))
            p.close()
        assert chains[0] == chains[1]


# ---------------------------------------------------------------------------
# dispatcher path: shards through the ledger, resume mid-epoch
# ---------------------------------------------------------------------------


def _dispatcher_epoch(dst, faults, nworkers, shuffle_seed=None):
    """One dispatcher epoch over a baked shard; order-insensitive exact
    aggregate (integer-valued sums) + the final ledger snapshot."""
    from dmlc_tpu.data import (BlockService, DataDispatcher,
                               RemoteBlockParser, reset_source_cache)

    reset_source_cache()
    resilience.reset()
    if shuffle_seed is not None:
        os.environ["DMLC_TPU_SHUFFLE"] = str(shuffle_seed)
    if faults:
        resilience.configure(faults)
    try:
        with DataDispatcher(dst, nchunks=8, lease_s=1.0,
                            dead_after_s=0.75) as disp:
            workers = [BlockService(dispatcher=disp.address, nthread=1)
                       for _ in range(nworkers)]
            try:
                parser = RemoteBlockParser(disp.address, dispatcher=True)
                w = np.zeros(3)
                for block in parser:
                    w[0] += np.sum(np.asarray(block.label, dtype=np.float64))
                    w[1] += len(block.index)
                    w[2] += len(block)
                parser.close()
                assert disp.join(timeout=30), disp.snapshot()
                snap = disp.snapshot()
            finally:
                for svc in workers:
                    svc.close()
        return hashlib.sha256(w.tobytes()).hexdigest(), snap
    finally:
        resilience.reset()
        os.environ.pop("DMLC_TPU_SHUFFLE", None)


class TestDispatcher:
    @pytest.fixture()
    def shard(self, svm_file, tmp_path):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=32)
        return dst

    def test_shard_chunks_flow_through_ledger(self, shard):
        digest, snap = _dispatcher_epoch(shard, "", nworkers=1)
        assert snap["chunks"]["acked"] == 8
        assert snap["requeued"] == 0
        # same rows the local reader sees
        local = drain(create_parser(shard, 0, 1)).to_block()
        w = np.zeros(3)
        w[0] = np.sum(np.asarray(local.label, dtype=np.float64))
        w[1] = len(local.index)
        w[2] = len(local)
        assert digest == hashlib.sha256(w.tobytes()).hexdigest()

    def test_worker_killed_mid_epoch_resumes_bit_identical(self, shard):
        """The acceptance criterion: a seeded-shuffle 2-worker fleet
        loses a worker mid-epoch; the ledger requeues its leases and the
        epoch aggregate is bit-identical to the clean run — with zero
        audit divergences recorded on the redelivery path."""
        from dmlc_tpu.obs import audit as audit_mod

        clean, clean_snap = _dispatcher_epoch(
            shard, "", nworkers=1, shuffle_seed=13)
        assert clean_snap["chunks"]["acked"] == 8
        chaos, snap = _dispatcher_epoch(
            shard, "service.worker_crash:nth=3", nworkers=2,
            shuffle_seed=13)
        assert chaos == clean
        assert snap["chunks"]["acked"] == 8
        assert snap["requeued"] >= 1
        assert any(not w["live"] for w in snap["workers"].values())
        assert not audit_mod.auditor().divergences

    def test_shuffled_aggregate_equals_unshuffled(self, shard):
        """Shuffle permutes delivery, never membership: the exact
        order-insensitive aggregate matches the unshuffled epoch."""
        plain, _ = _dispatcher_epoch(shard, "", nworkers=1)
        shuffled, _ = _dispatcher_epoch(shard, "", nworkers=1,
                                        shuffle_seed=29)
        assert plain == shuffled


# ---------------------------------------------------------------------------
# source-cache keying
# ---------------------------------------------------------------------------


class TestCacheToken:
    def test_text_sources_unaffected(self, svm_file):
        assert cache_token(svm_file, "libsvm") is None

    def test_rebake_and_reseed_rotate_token(self, svm_file, tmp_path,
                                            monkeypatch):
        dst = str(tmp_path / "corpus.dtsh")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=64)
        base = cache_token(dst, "auto")
        assert base is not None
        assert cache_token(dst, "auto") == base  # stable
        monkeypatch.setenv("DMLC_TPU_SHUFFLE", "5")
        assert cache_token(dst, "auto") != base
        monkeypatch.delenv("DMLC_TPU_SHUFFLE")
        bake_dataset(svm_file, dst, data_format="libsvm", rows_per_window=32)
        assert cache_token(dst, "auto") != base
