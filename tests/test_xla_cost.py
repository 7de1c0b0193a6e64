"""Compiled-step cost attribution: XLA cost/memory extraction, MFU roofline.

Pins the PR's claims: (1) cost/memory analytics are extracted exactly
once per (fn, bucket-shape) — at compile time, never per step — and the
second lowering used for extraction does not perturb the recompile
sentinel; (2) in-graph collective traffic (the PR 13 blind spot) is
visible again via ``dmlc_xla_collective_bytes``; (3) the sampled
device-step latency probe syncs exactly one step in N and vanishes
entirely when telemetry or metrics are off; (4) goodput attribution
grows model-based MFU / HBM-fraction verdicts that stay *absent* (not
zero) when no compiled hot step has been analyzed, keeping every
downstream surface byte-stable.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu import obs
from dmlc_tpu.obs import device_telemetry as dt
from dmlc_tpu.obs import flight, goodput, plane, xla_cost
from dmlc_tpu.obs.metrics import Registry
from dmlc_tpu.models.fitloop import FitLoopObs
from dmlc_tpu.tools import obs_report, obs_top


@pytest.fixture(autouse=True)
def _clean_module_state():
    dt.reset()
    flight.reset()
    yield
    dt.reset()
    flight.reset()


def _flat(reg, key, default=0.0):
    return reg.flat_values().get(key, default)


def _csr_batch(rng, nfeat, batch, nnz_bucket):
    from dmlc_tpu.data.row_block import RowBlockContainer
    from dmlc_tpu.device.csr import pad_to_bucket

    cont = RowBlockContainer()
    for _ in range(batch):
        feats = sorted(rng.choice(nfeat, size=4, replace=False))
        cont.push_row(float(rng.randint(0, 2)), feats,
                      value=rng.rand(4).astype(np.float32))
    dev = pad_to_bucket(cont.to_block(), batch, nnz_bucket=nnz_bucket)
    return {
        "label": jnp.asarray(dev.labels),
        "weight": jnp.asarray(dev.weights),
        "indices": jnp.asarray(dev.indices),
        "values": jnp.asarray(dev.values),
        "offsets": jnp.asarray(dev.offsets),
    }


# ---------------------------------------------------------------------------
# bucket signatures
# ---------------------------------------------------------------------------


class TestBucketSignature:
    def test_distinguishes_shapes_and_dtypes(self):
        a32 = jnp.zeros((4, 8), jnp.float32)
        a16 = jnp.zeros((4, 8), jnp.bfloat16)
        b32 = jnp.zeros((4, 16), jnp.float32)
        sigs = {
            xla_cost.bucket_signature((a32,), {}),
            xla_cost.bucket_signature((a16,), {}),
            xla_cost.bucket_signature((b32,), {}),
        }
        assert len(sigs) == 3
        assert "float32[4,8]" in xla_cost.bucket_signature((a32,), {})

    def test_pytree_and_scalar_leaves(self):
        batch = {"x": jnp.zeros((2,)), "n": 3}
        sig = xla_cost.bucket_signature((batch,), {})
        # dict leaves are flattened in a deterministic order; the python
        # int leaf falls back to its type name
        assert "float32[2]" in sig and "int" in sig

    def test_kwargs_participate(self):
        x = jnp.zeros((2,))
        assert xla_cost.bucket_signature((x,), {}) != xla_cost.bucket_signature(
            (x,), {"y": jnp.zeros((3,))})


# ---------------------------------------------------------------------------
# collective byte accounting from optimized HLO
# ---------------------------------------------------------------------------


class TestCollectiveBytesFromHlo:
    def test_sync_allreduce_counted(self):
        hlo = '  ROOT %all-reduce.7 = f32[1,1024]{1,0} all-reduce(f32[1,1024]{1,0} %p0), replica_groups={}\n'
        assert xla_cost.collective_bytes_from_hlo(hlo) == 4 * 1024

    def test_async_start_counted_done_not(self):
        hlo = (
            "  %ag = (f32[8]{0}, f32[16]{0}) all-gather-start(f32[8]{0} %x)\n"
            "  %agd = f32[16]{0} all-gather-done((f32[8]{0}, f32[16]{0}) %ag)\n"
        )
        # only the -start shapes count: 8*4 + 16*4; the -done result must
        # not be double-counted
        assert xla_cost.collective_bytes_from_hlo(hlo) == (8 + 16) * 4

    def test_pred_and_narrow_dtypes(self):
        hlo = (
            "  %a = pred[8]{0} all-reduce(pred[8]{0} %x)\n"
            "  %b = bf16[4,2]{1,0} all-to-all(bf16[4,2]{1,0} %y)\n"
        )
        assert xla_cost.collective_bytes_from_hlo(hlo) == 8 * 1 + 8 * 2

    def test_no_collectives_zero(self):
        hlo = "  %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %a, f32[64,64]{1,0} %b)\n"
        assert xla_cost.collective_bytes_from_hlo(hlo) == 0.0
        assert xla_cost.collective_bytes_from_hlo("") == 0.0


# ---------------------------------------------------------------------------
# compile-time extraction via the instrumented_jit hook
# ---------------------------------------------------------------------------


def _matmul_site(reg, name="t.step"):
    j = dt.instrumented_jit(lambda x: x @ x, name, reg=reg)
    return j, jnp.eye(64, dtype=jnp.float32)


class TestExtraction:
    def test_note_compile_sets_all_four_gauges(self):
        reg = Registry()
        j, x = _matmul_site(reg)
        j(x).block_until_ready()
        flat = reg.flat_values()
        assert flat['dmlc_xla_flops{fn="t.step"}'] > 0
        assert flat['dmlc_xla_bytes_accessed{fn="t.step"}'] > 0
        assert flat['dmlc_xla_peak_bytes{fn="t.step"}'] > 0
        assert flat['dmlc_xla_collective_bytes{fn="t.step"}'] == 0.0
        recs = [r for r in xla_cost.records() if r["fn"] == "t.step"]
        assert len(recs) == 1 and recs[0]["flops"] > 0

    def test_same_bucket_never_reextracted(self):
        reg = Registry()
        j, x = _matmul_site(reg)
        j(x).block_until_ready()
        base = xla_cost.extraction_count()
        for _ in range(5):
            j(x)
        # belt-and-braces: even an explicit re-notify of the same bucket
        # must hit the cache, not the compiler
        xla_cost.note_compile("t.step", j._jitted, (x,), reg=reg)
        assert xla_cost.extraction_count() == base

    def test_new_bucket_extracts_again(self):
        reg = Registry()
        j, x = _matmul_site(reg)
        j(x)
        j(jnp.eye(32, dtype=jnp.float32))
        per = xla_cost.per_fn()["t.step"]
        assert per["buckets"] == 2
        assert xla_cost.extraction_count() == 2

    def test_extraction_does_not_perturb_compile_sentinel(self):
        reg = Registry()
        j, x = _matmul_site(reg, name="t.sentinel")
        j(x)
        j(x)
        # the extraction's lower().compile() reuses jit's cached trace:
        # the counting shim (and so the recompile sentinel) sees exactly
        # one compile for one bucket
        assert dt.compile_counts(reg).get("t.sentinel", 0) == 1
        assert _flat(reg, "dmlc_xla_recompiles_total") == 0.0

    def test_metrics_off_skips_extraction(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS", "0")
        j = jax.jit(lambda x: x + 1)
        out = xla_cost.note_compile("t.off", j, (jnp.zeros(4),))
        assert out is None
        assert xla_cost.extraction_count() == 0

    def test_extraction_failure_degrades_to_absent(self):
        reg = Registry()

        class Broken:
            def lower(self, *a, **k):
                raise RuntimeError("no lowering for you")

        rec = xla_cost.note_compile("t.broken", Broken(), (jnp.zeros(2),),
                                    reg=reg)
        # never raises; the analytics simply stay absent (no record, no
        # gauges) and the caller's compile path is untouched
        assert rec is None
        assert xla_cost.extraction_count() == 0
        assert 'dmlc_xla_flops{fn="t.broken"}' not in reg.flat_values()

    def test_telemetry_off_is_plain_jit(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_DEVICE_TELEMETRY", "0")
        j = dt.instrumented_jit(lambda x: x * 2, "t.plainoff")
        assert type(j) is type(jax.jit(lambda x: x))
        j(jnp.zeros(3))
        assert not [r for r in xla_cost.records()
                    if r["fn"] == "t.plainoff"]


class TestLinearFitExtraction:
    def test_two_bucket_csr_fit_yields_two_records(self):
        from dmlc_tpu.models import init_linear_params, make_linear_train_step

        rng = np.random.RandomState(3)
        nfeat = 24
        step = make_linear_train_step(None, layout="csr", num_features=nfeat,
                                      learning_rate=0.1)
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        for nnz in (128, 256, 128, 256):
            params, velocity, _ = step(params, velocity,
                                       _csr_batch(rng, nfeat, 16, nnz))
        per = xla_cost.per_fn().get("linear.step")
        assert per is not None and per["buckets"] == 2
        buckets = {r["bucket"] for r in xla_cost.records()
                   if r["fn"] == "linear.step"}
        assert len(buckets) == 2
        flat = obs.registry().flat_values()
        assert flat['dmlc_xla_flops{fn="linear.step"}'] > 0


class TestSpmdCollectiveBytes:
    def test_psum_step_reports_collective_traffic(self):
        from dmlc_tpu.collective.device import make_allreduce_step

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >=2 devices (conftest forces 8 cpu)")
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(devs[:2]), ("dp",))
        step = make_allreduce_step(mesh)
        grads = {"w": jnp.ones((2, 256), jnp.float32)}
        step(grads)
        per = xla_cost.per_fn().get("collective.allreduce_step")
        assert per is not None
        # the in-graph psum is invisible to the host-side
        # dmlc_collective_* counters (the PR 13 blind spot) — it must
        # show up here
        assert per["collective_bytes"] > 0
        assert per["bytes_accessed"] > 0


# ---------------------------------------------------------------------------
# flat-snapshot parsing and step-cost selection
# ---------------------------------------------------------------------------


class TestFlatParsing:
    def test_sites_from_flat_roundtrip(self):
        reg = Registry()
        j, x = _matmul_site(reg, name="m.step")
        j(x)
        sites = xla_cost.sites_from_flat(reg.flat_values())
        assert "m.step" in sites
        assert sites["m.step"]["flops"] > 0
        assert set(sites["m.step"]) == set(xla_cost.FIELDS)

    def test_step_costs_only_hot_step_sites(self):
        flat = {
            'dmlc_xla_flops{fn="linear.step"}': 100.0,
            'dmlc_xla_bytes_accessed{fn="linear.step"}': 10.0,
            'dmlc_xla_flops{fn="linear.hostsync_grads"}': 9999.0,
            'dmlc_xla_flops{fn="fm.step_mp"}': 200.0,
            'dmlc_xla_bytes_accessed{fn="fm.step_mp"}': 5.0,
        }
        costs = xla_cost.step_costs(flat)
        # hostsync_grads is not a step site; among step sites the max wins
        assert costs["flops"] == 200.0
        assert costs["bytes"] == 10.0

    def test_step_costs_empty(self):
        assert xla_cost.step_costs({}) == {"flops": 0.0, "bytes": 0.0}


# ---------------------------------------------------------------------------
# goodput MFU / roofline
# ---------------------------------------------------------------------------


def _step_window(flops=2e9, bytes_accessed=4e8):
    flat = {
        'dmlc_fit_steps_total{model="linear"}': 50.0,
        "dmlc_feed_consume_ns:sum": 1.0e9,
        'dmlc_xla_flops{fn="linear.step"}': flops,
        'dmlc_xla_bytes_accessed{fn="linear.step"}': bytes_accessed,
    }
    return flat


class TestGoodputMfu:
    def test_attribute_yields_mfu_and_compute(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("DMLC_TPU_PEAK_HBM_GBPS", "100")
        flat = _step_window()
        att = goodput.attribute(flat, 2.0, current=flat)
        # 50 steps * 2e9 flops / 2 s / 1e12 peak = 0.05
        assert att["mfu"] == pytest.approx(0.05, abs=1e-6)
        assert att["compute"]["flops"] == pytest.approx(1e11)
        assert att["compute"]["floor_s"] == pytest.approx(0.1)
        # 50 * 4e8 B / 2 s / 100e9 Bps = 0.1
        assert att["hbm_fraction"] == pytest.approx(0.1, abs=1e-6)

    def test_mfu_clamped_to_one(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1")
        flat = _step_window()
        att = goodput.attribute(flat, 2.0, current=flat)
        assert att["mfu"] == 1.0

    def test_absent_without_analyzed_step(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        flat = {'dmlc_fit_steps_total{model="linear"}': 50.0,
                "dmlc_feed_consume_ns:sum": 1.0e9}
        att = goodput.attribute(flat, 2.0, current=flat)
        assert "mfu" not in att
        assert "compute" not in att
        assert "hbm_fraction" not in att

    def test_mfu_on_real_linear_fit(self, monkeypatch):
        # a tiny CPU fit against a petaflop ceiling rounds to 0.0000 —
        # pick a peak small enough that 4-decimal rounding keeps mfu > 0
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e6")
        from dmlc_tpu.models import init_linear_params, make_linear_train_step

        rng = np.random.RandomState(5)
        nfeat = 16
        step = make_linear_train_step(None, layout="csr", num_features=nfeat,
                                      learning_rate=0.1)
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        step(params, velocity, _csr_batch(rng, nfeat, 8, 64))
        reg = obs.registry()
        reg.counter("dmlc_fit_steps_total", model="linear").inc(10)
        flat = reg.flat_values()
        att = goodput.attribute(flat, 0.5, current=flat)
        assert att.get("mfu") is not None
        assert 0.0 < att["mfu"] <= 1.0

    def test_rolled_rederives_job_mfu(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        flat = _step_window()
        a0 = goodput.attribute(flat, 2.0, current=flat)
        a1 = goodput.attribute(flat, 2.0, current=flat)
        job = goodput.rolled([a0, a1])
        assert job is not None
        # counters sum across ranks, wall is the widest rank's window:
        # 2 x 1e11 flops / 2 s / 1e12 peak
        assert job.get("mfu") == pytest.approx(0.1, abs=1e-6)

    def test_format_attribution_compute_row(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        flat = _step_window()
        att = goodput.attribute(flat, 2.0, current=flat)
        text = goodput.format_attribution(att)
        assert "compute" in text
        assert "floor" in text and "mfu" in text

    def test_ledger_sets_mfu_gauge(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        reg = Registry()
        led = goodput.GoodputLedger(reg=reg)
        # progress lands after the ledger's opening snapshot so the
        # window delta carries the steps
        reg.counter("dmlc_fit_steps_total", model="linear").inc(50)
        reg.gauge("dmlc_xla_flops", fn="linear.step").set(2e9)
        att = led.tick(wall_ns=int(2e9))
        assert att.get("mfu") is not None
        assert _flat(reg, "dmlc_goodput_mfu_ratio") == att["mfu"] > 0.0


# ---------------------------------------------------------------------------
# surfaces: /xla endpoint, obs-top column, obs-report tables, bench gate
# ---------------------------------------------------------------------------


def _planted_metrics():
    return {
        'dmlc_xla_flops{fn="linear.step"}': 123456.0,
        'dmlc_xla_bytes_accessed{fn="linear.step"}': 4096.0,
        'dmlc_xla_peak_bytes{fn="linear.step"}': 2048.0,
        'dmlc_xla_collective_bytes{fn="linear.step"}': 512.0,
    }


class TestSurfaces:
    def test_plane_xla_view_and_endpoint(self):
        sp = plane.StatusPlane(num_workers=1, heartbeat_gap=60.0)
        sp.note_payload(0, {"sent_unix_ns": 1, "anchor_unix_ns": 1,
                            "metrics": _planted_metrics(), "spans": []},
                        recv_unix_ns=1)
        view = sp.xla_view()
        assert view["ranks"]["0"]["linear.step"]["flops"] == 123456.0
        assert "local" in view
        srv = plane.StatusServer(sp, port=0)
        srv.start()
        try:
            url = "http://127.0.0.1:%d/xla" % srv.port
            body = json.loads(urllib.request.urlopen(url, timeout=5).read())
            assert body["ranks"]["0"]["linear.step"]["collective_bytes"] == 512.0
        finally:
            srv.close()

    def test_obs_top_layout_byte_stable_without_mfu(self):
        rows, _ = obs_top.build_rows("", {"workers": {"0": {}}})
        header = obs_top.render_table(rows).splitlines()[0]
        assert "mfu" not in header

    def test_obs_top_mfu_column_when_present(self):
        gp = {"ranks": {"0": {"goodput": {"ratio": 0.5}, "binding": "feed",
                              "mfu": 0.42}}}
        rows, _ = obs_top.build_rows("", {"workers": {"0": {}}},
                                     goodput_obj=gp)
        table = obs_top.render_table(rows)
        assert "mfu" in table.splitlines()[0]
        assert "42%" in table

    def test_obs_report_xla_tables(self, capsys):
        obj = {"ranks": {"0": _sites()}, "local": {"sites": _sites(),
                                                   "extractions": 1}}
        assert obs_report._report_xla(obj) is True
        out = capsys.readouterr().out
        assert "linear.step" in out and "xla" in out

    def test_obs_report_xla_empty(self, capsys):
        assert obs_report._report_xla({"ranks": {}, "local": {}}) is False
        assert "no compiled sites" in capsys.readouterr().out

    def test_bench_gates_sgd_mfu_higher(self):
        import bench
        from dmlc_tpu.obs import sentry

        assert bench.BENCH_DIRECTIONS["sgd_mfu"] == "higher"
        rec = {"name": "sgd", "extra": {"sgd_mfu": 0.5},
               "directions": {"sgd_mfu": "higher"}}
        assert sentry.record_values(rec).get("sgd_mfu") == 0.5
        directions = sentry.record_directions([rec])
        assert not sentry.lower_is_better("sgd_mfu", directions)
        series = {"sgd_mfu": [0.5, 0.5, 0.5, 0.5]}
        regs = sentry.gate({"sgd_mfu": 0.2}, series, directions=directions)
        assert regs and regs[0]["metric"] == "sgd_mfu"
        assert regs[0]["direction"] == "higher"
        # improvement never alarms
        assert sentry.gate({"sgd_mfu": 0.6}, series,
                           directions=directions) == []


def _sites():
    return {"linear.step": {"flops": 123456.0, "bytes_accessed": 4096.0,
                            "peak_bytes": 2048.0, "collective_bytes": 512.0,
                            "buckets": 1}}


# ---------------------------------------------------------------------------
# published peaks: one table keyed by device_kind, knobs override, an
# unknown kind yields nothing
# ---------------------------------------------------------------------------


class TestDevicePeaks:
    @pytest.fixture(autouse=True)
    def _no_overrides(self, monkeypatch):
        for knob in ("DMLC_TPU_PEAK_FLOPS", "DMLC_TPU_PEAK_HBM_GBPS",
                     "DMLC_TPU_ICI_PEAK_GBPS"):
            monkeypatch.delenv(knob, raising=False)

    def test_known_kind_gets_published_peaks(self):
        # Google Cloud "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
        # 1,600 Gbit/s (= 200 GB/s) interconnect
        assert xla_cost.device_peaks("TPU v5 lite") == {
            "peak_flops": 197e12, "hbm_gbps": 819.0, "ici_gbps": 200.0}

    def test_override_wins(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("DMLC_TPU_ICI_PEAK_GBPS", "45")
        peaks = xla_cost.device_peaks("TPU v5 lite")
        assert peaks["peak_flops"] == 1e12 and peaks["ici_gbps"] == 45.0
        assert peaks["hbm_gbps"] == 819.0  # not overridden: the table
        # an override also serves a kind the table does not know
        assert xla_cost.device_peaks("cpu") == {
            "peak_flops": 1e12, "ici_gbps": 45.0}

    def test_unknown_kind_has_no_peaks_and_no_verdict(self):
        # this process's own device is the CPU backend: not in the table
        assert xla_cost.device_peaks() == {}
        assert xla_cost.device_peaks("TPU v99") == {}
        flat = _step_window()
        att = goodput.attribute(flat, 2.0, ceilings=xla_cost.device_peaks(),
                                current=flat)
        # absent, never made up — and no probe program ran to invent one
        assert "mfu" not in att and "compute" not in att
        assert "hbm_fraction" not in att
        assert att["roofline"]["collective"]["utilization"] is None

    def test_fit_loop_scores_against_its_device(self, monkeypatch):
        """FitLoopObs hands the device's peaks to its ledger: with a
        peak known the window gains mfu, on the bare CPU backend it
        stays absent."""
        from dmlc_tpu.models.fitloop import FitLoopObs

        for peak, expect in ((None, False), ("1e6", True)):
            if peak:
                monkeypatch.setenv("DMLC_TPU_PEAK_FLOPS", peak)
            reg = Registry()
            fl = FitLoopObs("linear", reg=reg)
            reg.gauge("dmlc_xla_flops", fn="linear.step").set(2e3)
            win = fl.end_epoch(0, 50, 0, 0.5)
            assert ("mfu" in win) is expect
