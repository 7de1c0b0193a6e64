"""Determinism audit plane (obs/audit.py): digest canonicalization,
the worker-side chain ledger + epoch self-check, the tracker-side
cross-rank comparison, replay bundles, the numeric-health sentinel, and
the DMLC_TPU_AUDIT=0 allocation-free contract (the acceptance pin)."""

import gc
import json
import os
import sys

import numpy as np
import pytest

from dmlc_tpu.data.row_block import RowBlock, RowBlockContainer
from dmlc_tpu.obs import audit
from dmlc_tpu.obs.metrics import Registry


def _block(n=8, seed=0, with_value=True):
    rng = np.random.RandomState(seed)
    counts = rng.randint(1, 4, size=n)
    nnz = int(counts.sum())
    offset = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=offset[1:])
    return RowBlock(
        offset=offset,
        label=rng.randint(0, 2, size=n).astype(np.float32),
        index=rng.randint(0, 100, size=nnz).astype(np.uint32),
        value=(rng.rand(nnz).astype(np.float32) if with_value else None),
    )


class TestDigests:
    def test_digest_bytes_str_and_bytes_agree(self):
        assert audit.digest_bytes("1 2:3\n") == audit.digest_bytes(b"1 2:3\n")
        assert audit.digest_bytes(b"a") != audit.digest_bytes(b"b")

    def test_neutral_fills_make_presence_irrelevant(self):
        # a block with NO value array hashes like the same block with the
        # explicit all-ones values the reference defines as its meaning —
        # parser backends materialize presence differently and must
        # still agree
        b = _block(with_value=False)
        explicit = RowBlock(
            offset=b.offset, label=b.label, index=b.index,
            value=np.ones(int(b.offset[-1]), dtype=np.float32),
            weight=np.ones(len(b), dtype=np.float32),
            qid=np.zeros(len(b), dtype=np.int64),
        )
        assert audit.rows_digest(b) == audit.rows_digest(explicit)

    def test_content_changes_fork_the_digest(self):
        b = _block()
        forked = RowBlock(
            offset=b.offset, label=b.label.copy(), index=b.index,
            value=b.value)
        forked.label[0] += 1.0
        assert audit.rows_digest(b) != audit.rows_digest(forked)

    def test_container_parts_hash_like_the_block(self):
        b = _block(n=20, seed=3)
        parts = RowBlockContainer()
        for start in range(0, 20, 7):
            parts.push_block(b.slice(start, min(start + 7, 20)))
        assert audit.rows_digest(parts) == audit.rows_digest(b)

    def test_digest_arrays_sorted_and_none_safe(self):
        a = {"label": np.arange(3.0), "value": None}
        b = {"value": None, "label": np.arange(3.0)}
        assert audit.digest_arrays(a) == audit.digest_arrays(b)
        c = {"label": np.arange(3.0), "value": np.ones(2)}
        assert audit.digest_arrays(a) != audit.digest_arrays(c)


class TestAuditor:
    def _auditor(self, **kw):
        kw.setdefault("reg", Registry())
        kw.setdefault("mode", "full")
        kw.setdefault("rank", 0)
        return audit.Auditor(**kw)

    def test_chains_record_and_export(self):
        a = self._auditor()
        a.set_shard("d.svm", 0, 1)
        a.note_chunk(0, b"chunk0")
        a.note_parse(0, _block())
        a.note_batch(0, _block())
        nf = a.note_model(0, 0.5, {"w": np.zeros(10, dtype=np.float32)})
        assert nf == 0
        out = a.export()
        assert out["shard"] == "d.svm|0/1"
        assert set(out["chains"]) == {"io_read", "parse", "batch", "model"}
        for chain in out["chains"].values():
            assert chain["n"] == 1 and chain["head"] and chain["d"]

    def test_sample_mode_digests_every_nth(self):
        a = self._auditor(mode="sample", sample_n=4)
        for seq in range(8):
            a.note_chunk(seq, b"c%d" % seq)
        assert a.export()["chains"]["io_read"]["n"] == 2  # seqs 0 and 4

    def test_epoch_self_check_clean(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        a = self._auditor()
        a.set_shard("d.svm")
        for epoch in range(3):
            for seq in range(4):
                a.note_chunk(seq, b"chunk%d" % seq)
            assert a.roll_epoch(epoch) == []
        assert a.divergences == []
        assert not os.path.exists(tmp_path / "audit-rank0.json")

    def test_epoch_self_check_localizes_fork(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        a = self._auditor(rank=2)
        a.set_shard("d.svm")
        for seq in range(4):
            a.note_chunk(seq, b"chunk%d" % seq)
        a.roll_epoch(0)
        for seq in range(4):
            data = b"CORRUPT" if seq == 2 else b"chunk%d" % seq
            a.note_chunk(seq, data)
        found = a.roll_epoch(1)
        assert len(found) == 1
        div = found[0]
        assert (div["stage"], div["seq"], div["rank"]) == ("io_read", 2, 2)
        assert div["scope"] == "epoch"
        bundle = json.load(open(tmp_path / "audit-rank2.json"))
        assert bundle["divergence"]["seq"] == 2
        assert bundle["shard"]["uri"] == "d.svm"

    def test_shard_change_resets_comparison(self):
        a = self._auditor()
        a.set_shard("a.svm")
        a.note_chunk(0, b"aaa")
        a.roll_epoch(0)
        a.set_shard("b.svm")  # new shard: chains must not compare across
        a.note_chunk(0, b"bbb")
        assert a.roll_epoch(1) == []

    def test_note_model_counts_nonfinite(self):
        a = self._auditor()
        bad = np.array([1.0, np.nan, np.inf, 2.0], dtype=np.float32)
        assert a.note_model(0, float("nan"), {"w": bad}) == 3
        assert a.note_model(1, 0.5, {"w": np.ones(4, np.float32)}) == 0

    def test_model_chain_forks_on_param_drift(self):
        a, b = self._auditor(), self._auditor()
        w = np.arange(128, dtype=np.float32)
        a.note_model(0, 0.5, {"w": w})
        b.note_model(0, 0.5, {"w": w + 1e-3})
        da = a.export()["chains"]["model"]["d"]
        db = b.export()["chains"]["model"]["d"]
        assert da[0][0] == db[0][0] == 0 and da[0][1] != db[0][1]

    def test_check_redelivery(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        a = self._auditor()
        assert a.check_redelivery(3, "aa", "aa") is True
        assert a.check_redelivery(3, "aa", "bb") is False
        assert a.divergences[0]["stage"] == "redelivery"


class TestAuditPlane:
    def _payload(self, chains, shard="d.svm|0/1", epoch=0):
        return {"shard": shard, "epoch": epoch, "every": 1,
                "chains": {stage: {"n": len(d), "head": "h", "d": d}
                           for stage, d in chains.items()},
                "divergences": 0}

    def test_agreeing_ranks_no_divergence(self, tmp_path):
        plane = audit.AuditPlane(reg=Registry(), out_dir=str(tmp_path))
        d = [[0, "aa"], [1, "bb"]]
        assert plane.note_audit(0, self._payload({"parse": d})) == []
        assert plane.note_audit(1, self._payload({"parse": d})) == []
        view = plane.view()
        assert view["divergences"] == []
        assert view["ranks"]["0"]["chains"]["parse"]["n"] == 2

    def test_cross_rank_fork_localized(self, tmp_path):
        plane = audit.AuditPlane(reg=Registry(), out_dir=str(tmp_path))
        plane.note_audit(0, self._payload(
            {"parse": [[0, "aa"], [1, "bb"], [2, "cc"]]}))
        found = plane.note_audit(1, self._payload(
            {"parse": [[0, "aa"], [1, "XX"], [2, "cc"]]}))
        assert len(found) == 1
        div = found[0]
        assert (div["stage"], div["seq"], div["rank"]) == ("parse", 1, 1)
        assert div["against_rank"] == 0 and div["scope"] == "cross-rank"
        bundle = json.load(open(tmp_path / "audit-rank1.json"))
        assert bundle["divergence"]["seq"] == 1
        # one flag per (stage, rank): the cascade after the fork is quiet
        assert plane.note_audit(1, self._payload(
            {"parse": [[2, "YY"]]})) == []
        assert plane.view()["ranks"]["1"]["diverged"]

    def test_different_shards_never_compare(self, tmp_path):
        plane = audit.AuditPlane(reg=Registry(), out_dir=str(tmp_path))
        plane.note_audit(0, self._payload({"io_read": [[0, "aa"]]},
                                          shard="d.svm|0/2"))
        assert plane.note_audit(1, self._payload(
            {"io_read": [[0, "zz"]]}, shard="d.svm|1/2")) == []

    def test_model_chain_compares_across_shards(self, tmp_path):
        # SPMD replicas read different parts but must hold identical
        # params — the model chain compares shard-independently
        plane = audit.AuditPlane(reg=Registry(), out_dir=str(tmp_path))
        plane.note_audit(0, self._payload({"model": [[0, "mm"]]},
                                          shard="d.svm|0/2"))
        found = plane.note_audit(1, self._payload(
            {"model": [[0, "nn"]]}, shard="d.svm|1/2"))
        assert found and found[0]["stage"] == "model"

    def test_same_rank_reexport_is_not_a_fork(self, tmp_path):
        plane = audit.AuditPlane(reg=Registry(), out_dir=str(tmp_path))
        p = self._payload({"parse": [[0, "aa"]]})
        assert plane.note_audit(0, p) == []
        assert plane.note_audit(0, p) == []  # heartbeat re-send


class TestBundles:
    def test_first_divergence_wins(self, tmp_path):
        div1 = {"stage": "parse", "seq": 1}
        div2 = {"stage": "parse", "seq": 9}
        p1 = audit.write_bundle(0, div1, out_dir=str(tmp_path))
        assert p1 and json.load(open(p1))["divergence"]["seq"] == 1
        assert audit.write_bundle(0, div2, out_dir=str(tmp_path)) is None
        assert json.load(open(p1))["divergence"]["seq"] == 1

    def test_knob_snapshot_rides_the_bundle(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUDIT", "1")
        monkeypatch.setenv("DMLC_TPU_PARSE_BACKEND", "vector")
        path = audit.write_bundle(1, {"stage": "batch", "seq": 0},
                                  out_dir=str(tmp_path))
        knobs_snap = json.load(open(path))["knobs"]
        assert knobs_snap["DMLC_TPU_AUDIT"] == "1"
        assert knobs_snap["DMLC_TPU_PARSE_BACKEND"] == "vector"


class TestGating:
    def test_factory_off_returns_shared_noop(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_AUDIT", raising=False)
        audit.reset_auditor()
        try:
            a = audit.auditor()
            assert a is audit.NOOP_AUDITOR and not a.enabled
            assert audit.auditor() is a
        finally:
            audit.reset_auditor()

    def test_factory_on_returns_live_auditor(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUDIT", "1")
        audit.reset_auditor()
        try:
            a = audit.auditor()
            assert isinstance(a, audit.Auditor) and a.every == 1
        finally:
            audit.reset_auditor()

    def test_sample_knob(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_AUDIT", "sample")
        monkeypatch.setenv("DMLC_TPU_AUDIT_SAMPLE_N", "8")
        audit.reset_auditor()
        try:
            assert audit.auditor().every == 8
        finally:
            audit.reset_auditor()

    def test_disabled_hot_path_is_allocation_free(self, monkeypatch):
        """The acceptance pin: DMLC_TPU_AUDIT=0 call sites make one
        empty method call per note — no allocations on the hot path."""
        monkeypatch.delenv("DMLC_TPU_AUDIT", raising=False)
        audit.reset_auditor()
        a = audit.auditor()
        assert a is audit.NOOP_AUDITOR
        payload = b"chunk"

        def burst(n=2000):
            for i in range(n):
                a.note_chunk(i, payload)
                a.note_parse(i, None)
                a.note_batch(i, None)
                a.note_model(i, None)

        burst()  # warm caches before measuring
        deltas = []
        for _ in range(5):
            gc.collect()
            before = sys.getallocatedblocks()
            burst()
            gc.collect()
            deltas.append(sys.getallocatedblocks() - before)
        audit.reset_auditor()
        assert min(deltas) <= 0


class TestWatchdogNumeric:
    def _win(self, nonfinite=0):
        return {"goodput": {"ratio": 1.0, "rows_s": 100.0, "mbps": 1.0},
                "counters": {"steps": 10.0}, "window_s": 1.0,
                "binding": "model", "straggler_rank": -1,
                "nonfinite": nonfinite}

    def test_numeric_alert_fires_once_and_rearms(self):
        from dmlc_tpu.obs.watchdog import Watchdog

        wd = Watchdog(Registry(), profile=False)
        assert wd.observe(self._win()) == []
        fired = wd.observe(self._win(nonfinite=3))
        assert [a["kind"] for a in fired] == ["numeric"]
        assert fired[0]["nonfinite"] == 3
        # sustained excursion: one alert, not an alert storm
        assert wd.observe(self._win(nonfinite=5)) == []
        # cleared window re-arms
        assert wd.observe(self._win()) == []
        assert [a["kind"] for a in wd.observe(self._win(nonfinite=1))] \
            == ["numeric"]


class TestPayloadIntegration:
    def test_payload_carries_audit_key_only_when_live(self, monkeypatch):
        from dmlc_tpu.obs import plane as plane_mod

        monkeypatch.delenv("DMLC_TPU_AUDIT", raising=False)
        audit.reset_auditor()
        blob, _ = plane_mod.build_payload(0)
        assert "audit" not in json.loads(blob)

        live = audit.Auditor(reg=Registry(), mode="full", rank=0)
        live.set_shard("d.svm")
        live.note_chunk(0, b"chunk")
        monkeypatch.setattr(audit, "_AUDITOR", live)
        monkeypatch.setattr(audit, "_INIT", True)
        blob, _ = plane_mod.build_payload(0)
        obj = json.loads(blob)
        assert obj["audit"]["chains"]["io_read"]["n"] == 1
        audit.reset_auditor()

    def test_status_plane_routes_payload_to_audit_plane(self, tmp_path):
        from dmlc_tpu.obs.plane import StatusPlane

        plane = StatusPlane()
        plane.audit._out_dir = str(tmp_path)
        payload = {"audit": {"shard": "d.svm|0/1", "epoch": 0, "every": 1,
                             "divergences": 0,
                             "chains": {"parse": {"n": 1, "head": "h",
                                                  "d": [[0, "aa"]]}}}}
        plane.note_payload(0, dict(payload), 0)
        forked = {"audit": dict(payload["audit"],
                                chains={"parse": {"n": 1, "head": "x",
                                                  "d": [[0, "zz"]]}})}
        plane.note_payload(1, forked, 0)
        view = plane.audit_view()
        assert view["ranks"]["1"]["diverged"]
        assert view["divergences"][0]["seq"] == 0
