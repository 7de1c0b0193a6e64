"""Perf sentry (obs/sentry.py), the bench-gate CLI, obs-report --diff,
and the scripts/ci_checks.sh wiring.

The history fixtures are built under ``tmp_path`` from the synthetic
record the sentry itself defines (``sentry.SMOKE_HISTORY``), written in
the driver's ``BENCH_r*.json`` shape: the newest record must pass the
gate, a synthetic 20% headline regression on top of it must fail it (the
acceptance contract the tolerance defaults were tuned against). No
record in the repo root is a test input — records are not fixtures.
"""

import json
import os
import subprocess
import sys

import pytest

from dmlc_tpu.obs import flight, sentry
from dmlc_tpu.tools import bench_gate, obs_report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _driver_record(n, parsed):
    """One round in the driver's artifact shape; ``parsed`` None = the
    round printed no summary line (a truncated tail)."""
    return {"n": n, "cmd": "python bench.py", "rc": 0,
            "tail": "" if parsed is None else json.dumps(parsed) + "\n",
            "parsed": parsed}


@pytest.fixture
def bench_history(tmp_path):
    """BENCH_r01..r05.json under tmp_path from sentry.SMOKE_HISTORY, with
    one summary-less round (r04) in the middle; returns (glob, paths)."""
    rounds = list(sentry.SMOKE_HISTORY)
    rounds.insert(3, None)
    paths = []
    for n, parsed in enumerate(rounds, start=1):
        path = tmp_path / ("BENCH_r%02d.json" % n)
        path.write_text(json.dumps(_driver_record(n, parsed)))
        paths.append(str(path))
    return os.path.join(str(tmp_path), "BENCH_r*.json"), paths


class TestGateMath:
    def _series(self):
        return {"m_mbps": [100.0, 400.0, 410.0, 420.0, 430.0]}

    def test_window_uses_recent_history_only(self):
        # median of the last 3 (410,420,430) = 420; the stale 100 from
        # before the window must not drag the baseline down
        regs = sentry.gate({"m_mbps": 370.0}, self._series())
        assert [r["metric"] for r in regs] == ["m_mbps"]
        r = regs[0]
        assert r["baseline"] == 420.0
        # tol = max(0.10*420, 2*MAD(10)) = 42; breach = 378-370 = 8
        assert r["tolerance"] == pytest.approx(42.0)
        assert r["severity"] == pytest.approx(8.0 / 42.0)
        assert r["direction"] == "higher" and r["samples"] == 3

    def test_within_tolerance_passes(self):
        assert sentry.gate({"m_mbps": 380.0}, self._series()) == []

    def test_lower_is_better_for_stalls(self):
        series = {"stall.host_wait_s": [0.5, 0.5, 0.5]}
        assert sentry.gate({"stall.host_wait_s": 0.52}, series) == []
        regs = sentry.gate({"stall.host_wait_s": 1.0}, series)
        assert regs and regs[0]["direction"] == "lower"
        # an *improvement* way below baseline never trips a lower-better
        assert sentry.gate({"stall.host_wait_s": 0.01}, series) == []

    def test_min_samples_skips_thin_history(self):
        series = {"new_mbps": [500.0]}
        assert sentry.gate({"new_mbps": 1.0}, series) == []
        # and a metric with no history at all
        assert sentry.gate({"alien_mbps": 1.0}, {}) == []

    def test_ranked_worst_first_and_flight_event(self, tmp_path):
        series = {"a_mbps": [100.0] * 3, "b_mbps": [100.0] * 3}
        rec = flight.configure(str(tmp_path), capacity=8, rank=0,
                               install=False)
        try:
            regs = sentry.gate({"a_mbps": 80.0, "b_mbps": 10.0}, series)
            assert [r["metric"] for r in regs] == ["b_mbps", "a_mbps"]
            kinds = [r for r in rec.records()
                     if r["kind"] == "sentry.regression"]
            assert {r["metric"] for r in kinds} == {"a_mbps", "b_mbps"}
            assert kinds[0]["baseline"] == 100.0
        finally:
            flight.reset()

    def test_record_values_directions(self):
        rec = {
            "metric": "higgs_libsvm_ingest", "value": 600.0,
            "extra": {
                "recordio_ingest_mbps": 2300.0,
                "elapsed_s": 12.0,  # no gated suffix: ignored
                "pipelined_stall_stages": {"host_wait_s": 0.5,
                                           "chunks": 42},
            },
        }
        vals = sentry.record_values(rec)
        assert vals == {"higgs_libsvm_ingest": 600.0,
                        "recordio_ingest_mbps": 2300.0,
                        "stall.host_wait_s": 0.5}
        assert sentry.lower_is_better("stall.host_wait_s")
        assert not sentry.lower_is_better("recordio_ingest_mbps")

    def test_direction_registry_gates_unsuffixed_keys(self, tmp_path):
        # sgd_goodput_ratio has no throughput suffix: invisible to the
        # gate until the record's directions map names it
        rec = {"metric": "x_ingest", "value": 100.0,
               "extra": {"sgd_goodput_ratio": 0.4}}
        assert "sgd_goodput_ratio" not in sentry.record_values(rec)
        rec["directions"] = {"sgd_goodput_ratio": "higher"}
        vals = sentry.record_values(rec)
        assert vals["sgd_goodput_ratio"] == 0.4

        directions = sentry.record_directions([rec])
        assert directions == {"sgd_goodput_ratio": "higher"}
        assert not sentry.lower_is_better("sgd_goodput_ratio", directions)
        assert sentry.lower_is_better("q_s", {"q_s": "lower"})
        # the map overrides the prefix rules, both ways
        assert not sentry.lower_is_better("stall.x_s",
                                          {"stall.x_s": "higher"})

        # a goodput-ratio collapse now trips the gate, direction "higher"
        series = {"sgd_goodput_ratio": [0.9, 0.88, 0.92]}
        regs = sentry.gate({"sgd_goodput_ratio": 0.4}, series,
                           directions=directions)
        assert [r["metric"] for r in regs] == ["sgd_goodput_ratio"]
        assert regs[0]["direction"] == "higher"
        # and an "improvement" in a lower-is-better mapped key passes
        assert sentry.gate({"sgd_goodput_ratio": 0.4}, series,
                           directions={"sgd_goodput_ratio": "lower"}) == []

    def test_bench_gate_cli_threads_directions(self, tmp_path, capsys):
        base = {"metric": "x_ingest", "value": 100.0,
                "directions": {"sgd_goodput_ratio": "higher"}}
        hist_paths = []
        for i, ratio in enumerate((0.9, 0.88, 0.92)):
            p = tmp_path / f"BENCH_r{i}.json"
            p.write_text(json.dumps(
                {**base, "extra": {"sgd_goodput_ratio": ratio}}))
            hist_paths.append(str(p))
        fresh = tmp_path / "detail.json"
        fresh.write_text(json.dumps(
            {**base, "extra": {"sgd_goodput_ratio": 0.4}}))
        rc = bench_gate.main(
            ["--fresh", str(fresh),
             "--history", os.path.join(str(tmp_path), "BENCH_r*.json")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "sgd_goodput_ratio" in out


class TestLoadRecords:
    def test_null_parsed_round_yields_no_record(self, bench_history):
        # a round that recorded no summary line must not poison the series
        _glob, paths = bench_history
        assert sentry.load_record(paths[3]) == []
        assert len(sentry.load_records(paths)) == len(paths) - 1

    def test_driver_shape_and_jsonl_detail(self, tmp_path):
        p = tmp_path / "detail.json"
        p.write_text(
            json.dumps({"metric": "x_ingest", "value": 1.0}) + "\n"
            "torn{line\n"
            + json.dumps({"parsed": {"metric": "x_ingest",
                                     "value": 2.0}}) + "\n")
        recs = sentry.load_record(str(p))
        assert [r["value"] for r in recs] == [1.0, 2.0]
        assert all(r["source"] == str(p) for r in recs)


class TestBenchGateCLI:
    def test_smoke_self_check(self, capsys):
        assert bench_gate.main(["--smoke"]) == 0
        assert "OK" in capsys.readouterr().out

    def test_newest_round_passes_its_history(self, bench_history, capsys):
        glob_, paths = bench_history
        rc = bench_gate.main(["--fresh", paths[-1], "--history", glob_])
        assert rc == 0
        assert "within tolerance" in capsys.readouterr().out

    def test_synthetic_20pct_regression_fails(self, bench_history, tmp_path,
                                              capsys):
        glob_, paths = bench_history
        obj = json.load(open(paths[-1]))
        obj["parsed"]["value"] = round(obj["parsed"]["value"] * 0.8, 1)
        bad = tmp_path / "fresh_bad.json"
        bad.write_text(json.dumps(obj))
        rc = bench_gate.main(["--fresh", str(bad), "--history", glob_])
        assert rc == 1
        out = capsys.readouterr().out
        assert "higgs_libsvm_ingest" in out and "regression" in out

    def test_no_data_exits_2(self, tmp_path, monkeypatch, capsys):
        monkeypatch.delenv("DMLC_TPU_BENCH_DETAIL", raising=False)
        monkeypatch.delenv("DMLC_TPU_BENCH_DIR", raising=False)
        rc = bench_gate.main(
            ["--history", str(tmp_path / "nothing_*.json")])
        assert rc == 2

    def test_fresh_defaults_to_history_tail(self, bench_history, capsys):
        assert bench_gate.main(["--history", bench_history[0]]) == 0

    def test_fresh_without_history_is_advisory(self, tmp_path, capsys):
        # first bench round: a fresh record but an empty history window
        # is a bootstrap state, not a regression — advisory verdict, rc 0
        fresh = tmp_path / "detail.json"
        fresh.write_text(json.dumps({"metric": "x_ingest", "value": 100.0}))
        rc = bench_gate.main(
            ["--fresh", str(fresh),
             "--history", str(tmp_path / "BENCH_r*.json")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ADVISORY" in out and "no history" in out


class TestObsReportDiff:
    def _trace(self, path, scale):
        events = []
        for name, dur in (("io_read", 4000.0), ("consume", 1000.0)):
            events.append({"name": name, "ph": "X", "ts": 0.0,
                           "dur": dur * scale, "pid": 0, "tid": 1})
        # flow points must not count toward stage totals
        events.append({"name": "chunk", "cat": "dataflow", "ph": "t",
                       "id": 5, "ts": 1.0, "pid": 0, "tid": 1})
        path.write_text(json.dumps({"traceEvents": events}))
        return str(path)

    def test_diff_delta_table(self, tmp_path, capsys):
        a = self._trace(tmp_path / "a.json", scale=1.0)
        b = self._trace(tmp_path / "b.json", scale=2.0)
        assert obs_report.main(["--diff", a, b]) == 0
        out = capsys.readouterr().out
        rows = [line for line in out.splitlines()
                if line.startswith(("io_read", "consume"))]
        # sorted by absolute delta: io_read (+4ms) before consume (+1ms)
        assert [r.split()[0] for r in rows] == ["io_read", "consume"]
        assert "+100%" in rows[0] and "chunk" not in out

    def test_diff_unreadable_exits_2(self, tmp_path, capsys):
        a = self._trace(tmp_path / "a.json", scale=1.0)
        rc = obs_report.main(["--diff", a, str(tmp_path / "gone.json")])
        assert rc == 2


class TestCIChecks:
    def test_ci_checks_script_passes(self):
        """The lint + gate-smoke bundle stays green — wiring ci_checks.sh
        into tier-1 so a drifted catalog or broken gate fails the suite."""
        proc = subprocess.run(
            ["bash", os.path.join(REPO, "scripts", "ci_checks.sh")],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "all checks passed" in proc.stdout
