"""Device telemetry: recompile sentinel, HBM/H2D accounting, profiler capture.

Pins the PR's three claims: (1) the jit-body compile counter makes
FixedShapePool's one-trace-per-bucket design a live invariant and any
post-warmup compile an alarmed anomaly; (2) with
``DMLC_TPU_DEVICE_TELEMETRY=0`` the instrumented surfaces vanish — plain
``jax.jit`` callable, no meter, allocation-free dispatch branch; (3) the
``/profile`` endpoint reaches workers through the heartbeat-ack side
channel without breaking the original single-int wire contract.
"""

import gc
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_tpu import obs
from dmlc_tpu.obs import device_telemetry as dt
from dmlc_tpu.obs import flight, plane
from dmlc_tpu.obs.metrics import Registry


@pytest.fixture(autouse=True)
def _clean_module_state():
    dt.reset()
    yield
    dt.reset()
    flight.reset()


def _flat(reg, key):
    return reg.flat_values().get(key, 0)


class TestInstrumentedJit:
    def test_counts_one_compile_per_signature(self):
        reg = Registry()
        inst = dt.InstrumentedJit(lambda x: x * 2, "t.step", reg=reg)
        for size in (8, 8, 16, 8, 16):
            np.asarray(inst(jnp.ones(size)))
        assert inst.compiles == 2 and inst.calls == 5
        assert dt.compile_counts(reg) == {"t.step": 2}
        # each compiling call lands its wall time in the histogram
        assert _flat(reg, 'dmlc_xla_compile_ns{fn="t.step"}:count') == 2
        assert _flat(reg, 'dmlc_xla_recompiles_total{fn="t.step"}') == 0
        assert "t.step" in repr(inst)

    def test_post_warmup_recompile_is_an_anomaly(self, tmp_path, caplog):
        rec = flight.configure(str(tmp_path), capacity=16, rank=0,
                               install=False)
        reg = Registry()
        inst = dt.InstrumentedJit(lambda x: x + 1, "t.warm", reg=reg,
                                  warmup_calls=2)
        np.asarray(inst(jnp.ones(4)))
        np.asarray(inst(jnp.ones(4)))  # 2 calls, 1 compile: warmup done
        with caplog.at_level("WARNING", logger="dmlc_tpu.obs.device"):
            np.asarray(inst(jnp.ones(6)))  # call 3 compiles: anomaly
        assert _flat(reg, 'dmlc_xla_recompiles_total{fn="t.warm"}') == 1
        events = [r for r in rec.records() if r["kind"] == "xla.recompile"]
        assert len(events) == 1
        assert events[0]["fn"] == "t.warm"
        assert events[0]["compiles"] == 2 and events[0]["calls"] == 3
        assert any("recompile anomaly" in r.message for r in caplog.records)

    def test_compiles_inside_warmup_are_not_anomalies(self):
        reg = Registry()
        inst = dt.InstrumentedJit(lambda x: x + 1, "t.quiet", reg=reg,
                                  warmup_calls=8)
        for size in (4, 6, 8):
            np.asarray(inst(jnp.ones(size)))
        assert inst.compiles == 3
        assert _flat(reg, 'dmlc_xla_recompiles_total{fn="t.quiet"}') == 0

    def test_lower_passthrough(self):
        inst = dt.InstrumentedJit(lambda x: x + 1, "t.lower", reg=Registry())
        lowered = inst.lower(jnp.ones(4))
        assert hasattr(lowered, "compile")


class TestDisabledPath:
    def test_disabled_returns_plain_jax_jit(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_DEVICE_TELEMETRY", "0")

        def f(x):
            return x + 1

        inst = dt.instrumented_jit(f, "t.off")
        # not a wrapper object: the disabled dispatch path IS jax's own
        assert type(inst) is type(jax.jit(f))
        assert dt.h2d_meter(feed="fX") is None
        assert dt.sample() == {"hbm": {}, "live": {}}
        assert dt.maybe_start_hbm_poller() is False

    def test_disabled_put_branch_allocation_free(self):
        # With telemetry off the feed keeps meter=None and the only
        # per-put residue is one `is None` branch — pin it allocation-free
        # like the flow-id discipline in test_obs.py.
        from dmlc_tpu.device.feed import DeviceFeed

        from dmlc_tpu.obs.metrics import NOOP

        class _Feed:
            _h2d = None
            _h_put = NOOP  # the put span's counter under DMLC_TPU_METRICS=0
            _pass = 0

            def _put_tree_raw(self, arrays, specs):
                return arrays

        feed = _Feed()
        arrays = {"x": 1}
        specs = {}

        def burst(n=2000):
            for _ in range(n):
                DeviceFeed._put_tree(feed, arrays, specs)

        burst()  # warm caches before measuring
        deltas = []
        for _ in range(5):
            gc.collect()
            before = sys.getallocatedblocks()
            burst()
            gc.collect()
            deltas.append(sys.getallocatedblocks() - before)
        assert min(deltas) <= 0


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


class TestOneTracePerBucket:
    def test_bucketed_fit_compiles_once_per_bucket_then_alarms(self, tmp_path):
        """The live e2e proof: a CSR fit over two nnz buckets costs exactly
        two ``linear.step`` traces no matter how many batches flow, and an
        unbucketed shape past the warmup window trips the recompile alarm."""
        from dmlc_tpu.models import init_linear_params, make_linear_train_step

        rec = flight.configure(str(tmp_path), capacity=32, rank=0,
                               install=False)
        rng = np.random.RandomState(7)
        nfeat = 24
        before = dt.compile_counts().get("linear.step", 0)
        before_re = _flat(obs.registry(),
                          'dmlc_xla_recompiles_total{fn="linear.step"}')
        step = make_linear_train_step(None, layout="csr", num_features=nfeat,
                                      learning_rate=0.1)
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        batches = [_csr_batch(rng, nfeat, 16, 128),
                   _csr_batch(rng, nfeat, 16, 256)]
        # two shape buckets, many batches: alternate well past the warmup
        # window (DEFAULT_WARMUP_CALLS) so the later anomaly is post-warmup
        for i in range(dt.DEFAULT_WARMUP_CALLS + 2):
            params, velocity, _ = step(params, velocity, batches[i % 2])
        assert dt.compile_counts()["linear.step"] - before == 2
        assert _flat(obs.registry(),
                     'dmlc_xla_recompiles_total{fn="linear.step"}'
                     ) == before_re
        # an unbucketed nnz shape leaks in: third trace, alarmed
        stray = _csr_batch(rng, nfeat, 16, 512)
        params, velocity, _ = step(params, velocity, stray)
        assert dt.compile_counts()["linear.step"] - before == 3
        assert _flat(obs.registry(),
                     'dmlc_xla_recompiles_total{fn="linear.step"}'
                     ) == before_re + 1
        events = [r for r in rec.records() if r["kind"] == "xla.recompile"]
        assert events and events[-1]["fn"] == "linear.step"


class TestDonationCorrectness:
    """Donated batch/param buffers (donate_argnums) must change WHERE the
    step writes, never WHAT it computes — and must keep device memory and
    the trace count flat (ISSUE 16: the arena contract)."""

    def _fit(self, donate, rng_seed=11, epochs=3):
        from dmlc_tpu.models import init_linear_params, make_linear_train_step

        rng = np.random.RandomState(rng_seed)
        nfeat = 24
        step = make_linear_train_step(
            None, layout="csr", num_features=nfeat, learning_rate=0.1,
            donate_batch=donate,
        )
        params = init_linear_params(nfeat)
        velocity = {"w": jnp.zeros(nfeat), "b": jnp.zeros(())}
        # two nnz buckets, repeated across epochs (regenerated per step:
        # donation consumes the batch arrays)
        live_after_epoch = []
        for _ in range(epochs):
            rng_e = np.random.RandomState(rng_seed + 1)
            for i in range(6):
                batch = _csr_batch(rng_e, nfeat, 16, 128 if i % 2 else 256)
                params, velocity, _ = step(params, velocity, batch)
            gc.collect()
            live_after_epoch.append(sum(dt.sample()["live"].values()))
        return (np.asarray(params["w"]).tobytes(),
                np.asarray(params["b"]).tobytes(), live_after_epoch)

    def test_two_bucket_fit_donated_equals_undonated(self):
        w_ref, b_ref, _ = self._fit(donate=False)
        w_don, b_don, live = self._fit(donate=True)
        # (a) bit-identical fit: donation is invisible to the math
        assert w_don == w_ref and b_don == b_ref
        # (b) device memory flat across epochs: the arena is reused, not
        # re-grown (first epoch may include warmup allocations)
        assert live[-1] <= live[0] * 1.01 + 4096

    def test_donated_fit_stays_at_one_trace_per_bucket(self):
        before = dt.compile_counts().get("linear.step", 0)
        before_re = _flat(obs.registry(),
                          'dmlc_xla_recompiles_total{fn="linear.step"}')
        self._fit(donate=True)
        # (c) two nnz buckets → exactly two traces, zero recompile alarms
        assert dt.compile_counts()["linear.step"] - before == 2
        assert _flat(obs.registry(),
                     'dmlc_xla_recompiles_total{fn="linear.step"}'
                     ) == before_re


class TestH2DAccounting:
    def test_meter_bytes_and_bandwidth(self):
        reg = Registry()
        meter = dt.H2DMeter(reg, feed="f9")
        meter.note(1 << 20, 1_000_000)  # 1 MiB in 1 ms ≈ 1048.6 MB/s
        assert _flat(reg, 'dmlc_feed_h2d_bytes_total{feed="f9"}') == 1 << 20
        assert _flat(reg, 'dmlc_feed_h2d_mbps{feed="f9"}:count') == 1
        mbps = _flat(reg, 'dmlc_feed_h2d_mbps{feed="f9"}:sum')
        assert mbps == pytest.approx(1048.576)
        meter.note(0, 100)  # empty put: nothing recorded
        meter.note(5, 0)  # unmeasurable wall time: bytes only
        assert _flat(reg, 'dmlc_feed_h2d_bytes_total{feed="f9"}') == (
            (1 << 20) + 5)
        assert _flat(reg, 'dmlc_feed_h2d_mbps{feed="f9"}:count') == 1

    def test_feed_run_populates_h2d_metrics(self, tmp_path):
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.device.feed import BatchSpec, DeviceFeed
        from dmlc_tpu.io.input_split import create_input_split

        rng = np.random.RandomState(3)
        lines = []
        for i in range(256):
            feats = " ".join(
                f"{j}:{rng.rand():.3f}"
                for j in sorted(rng.choice(20, size=3, replace=False)))
            lines.append("%d %s" % (i % 2, feats))
        path = tmp_path / "t.svm"
        path.write_text("\n".join(lines) + "\n")

        def total_h2d():
            return sum(
                v for k, v in obs.registry().flat_values().items()
                if k.startswith("dmlc_feed_h2d_bytes_total"))

        before = total_h2d()
        split = create_input_split(str(path), 0, 1, "text", threaded=False)
        spec = BatchSpec(batch_size=64, layout="dense", num_features=20)
        feed = DeviceFeed(LibSVMParser(split, nthread=1), spec)
        for batch in feed:
            np.asarray(batch["label"])
        feed.close()
        assert total_h2d() > before


class TestSampleAndDetail:
    def test_sample_is_graceful_on_cpu_and_tracks_peak(self):
        reg = Registry()
        keep = jnp.ones((64, 64))  # something for the census to find
        out = dt.sample(reg)
        assert set(out) == {"hbm", "live"}
        # cpu backends report no memory_stats — the census carries the load
        assert out["live"]
        flats = reg.flat_values()
        assert any(k.startswith("dmlc_device_live_bytes") for k in flats)
        assert dt.peak_hbm_bytes() >= int(keep.nbytes)

    def test_detail_section_shapes_for_bench(self):
        reg = Registry()
        inst = dt.InstrumentedJit(lambda x: x + 1, "t.detail", reg=reg)
        keep = inst(jnp.ones(8))  # held live so the census finds something
        dt.H2DMeter(reg, feed="f0").note(1 << 20, 1_000_000)
        out = dt.detail_section(reg)
        del keep
        assert out["compiles"] == {"t.detail": 1}
        assert out["h2d_mbps"] == pytest.approx(1048.6)
        assert out.get("peak_hbm_bytes", 0) > 0  # census-backed on cpu

    def test_sentry_gates_device_keys(self):
        from dmlc_tpu.obs import sentry

        vals = sentry.record_values({
            "name": "b", "value": 100.0,
            "extra": {"device_telemetry": {
                "compiles": {"linear.step": 2},
                "peak_hbm_bytes": 4096,
                "h2d_mbps": 800.0,
            }},
        })
        assert vals["compiles.linear.step"] == 2.0
        assert vals["hbm.peak_bytes"] == 4096.0
        assert vals["h2d_mbps"] == 800.0
        assert sentry.lower_is_better("compiles.linear.step")
        assert sentry.lower_is_better("hbm.peak_bytes")
        assert not sentry.lower_is_better("h2d_mbps")


class TestCaptureProfile:
    def test_capture_writes_event_and_counter(self, tmp_path, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", None)))
        monkeypatch.setenv("DMLC_TASK_ID", "2")
        rec = flight.configure(str(tmp_path), capacity=16, rank=2,
                               install=False)
        before = sum(
            v for k, v in obs.registry().flat_values().items()
            if k.startswith("dmlc_device_profile_captures_total"))
        th = dt.capture_profile(0.01, out_dir=str(tmp_path), req_id=3,
                                block=True)
        assert th is not None and not th.is_alive()
        assert [c[0] for c in calls] == ["start", "stop"]
        assert calls[0][1].endswith("profile-rank2-req3")
        events = [r for r in rec.records() if r["kind"] == "profile.capture"]
        assert len(events) == 1
        assert events[0]["req"] == 3 and events[0]["ok"] is True
        after = sum(
            v for k, v in obs.registry().flat_values().items()
            if k.startswith("dmlc_device_profile_captures_total"))
        assert after == before + 1

    def test_overlapping_capture_is_dropped(self, tmp_path, monkeypatch):
        release = threading.Event()
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", release.wait)
        th = dt.capture_profile(0.0, out_dir=str(tmp_path), req_id=1)
        try:
            assert th is not None
            assert dt.capture_profile(0.0, out_dir=str(tmp_path),
                                      req_id=2) is None
        finally:
            release.set()
            th.join(timeout=10)
        assert not th.is_alive()


class TestProfileWire:
    def test_word_roundtrip_and_clamps(self):
        assert plane.decode_profile_word(
            plane.encode_profile_word(1, 10)) == (1, 10)
        assert plane.decode_profile_word(0) == (0, 0)
        assert plane.decode_profile_word(-7) == (0, 0)
        assert plane.encode_profile_word(1, 10 ** 9) == (
            (1 << plane.PROFILE_SHIFT) | plane.PROFILE_MAX_S)
        assert plane.NOOP_PLANE.profile_word() == 0

    def test_request_profile_advances_word(self):
        sp = plane.StatusPlane(num_workers=1)
        assert sp.profile_word() == 0
        out = sp.request_profile(7)
        assert out == {"profile_req": 1, "seconds": 7}
        assert plane.decode_profile_word(sp.profile_word()) == (1, 7)
        out = sp.request_profile(10 ** 9)  # clamped to the field width
        assert out["seconds"] == plane.PROFILE_MAX_S
        assert plane.decode_profile_word(sp.profile_word()) == (
            2, plane.PROFILE_MAX_S)

    def test_profile_endpoint(self):
        sp = plane.StatusPlane(num_workers=1)
        srv = plane.StatusServer(sp, port=0)
        srv.start()
        try:
            url = "http://127.0.0.1:%d/profile" % srv.port
            with urllib.request.urlopen(url + "?seconds=9") as resp:
                out = json.loads(resp.read())
            assert out == {"profile_req": 1, "seconds": 9}
            for bad in ("?seconds=abc", "?seconds=0", "?seconds=-4"):
                with pytest.raises(urllib.error.HTTPError) as err:
                    urllib.request.urlopen(url + bad)
                assert err.value.code == 400
            # default window when seconds is omitted
            with urllib.request.urlopen(url) as resp:
                out = json.loads(resp.read())
            assert out["seconds"] == 5 and out["profile_req"] == 2
        finally:
            srv.close()

    def test_heartbeat_carries_profile_word(self, monkeypatch):
        from dmlc_tpu.tracker.rendezvous import RabitTracker, send_heartbeat

        monkeypatch.setenv("DMLC_TPU_STATUS_PORT", "0")
        tracker = RabitTracker("127.0.0.1", num_workers=1)
        try:
            tracker.start(1)
            # original single-int contract untouched for default callers
            ack = send_heartbeat("127.0.0.1", tracker.port, rank=0, epoch=1)
            assert isinstance(ack, int)
            ack, word = send_heartbeat("127.0.0.1", tracker.port, rank=0,
                                       epoch=1, want_profile=True)
            assert word == 0  # nothing requested yet
            url = "http://127.0.0.1:%d/profile?seconds=3" % tracker.status.port
            with urllib.request.urlopen(url) as resp:
                json.loads(resp.read())
            ack, word = send_heartbeat("127.0.0.1", tracker.port, rank=0,
                                       epoch=2, want_profile=True)
            assert plane.decode_profile_word(word) == (1, 3)
        finally:
            tracker.close()

    def test_publisher_captures_once_per_request(self, monkeypatch):
        captured = []
        monkeypatch.setattr(
            dt, "capture_profile",
            lambda seconds, req_id=0, **kw: captured.append(
                (req_id, seconds)))
        pub = plane.ObsPublisher("127.0.0.1", 1, rank=0, reg=Registry())
        try:
            pub._maybe_capture(0)  # never requested
            assert captured == []
            word = plane.encode_profile_word(2, 5)
            pub._maybe_capture(word)
            pub._maybe_capture(word)  # same request id: served already
            assert captured == [(2, 5)]
            pub._maybe_capture(plane.encode_profile_word(3, 4))
            assert captured == [(2, 5), (3, 4)]
            # a lower id (tracker restart) is ignored, not replayed
            pub._maybe_capture(plane.encode_profile_word(1, 9))
            assert captured == [(2, 5), (3, 4)]
        finally:
            pub.close()


class TestObsTopParsing:
    def test_parse_and_build_rows(self):
        text = "\n".join([
            "# HELP dmlc_xla_compiles_total x",
            'dmlc_xla_compiles_total{fn="linear.step",rank="0"} 2',
            'dmlc_xla_recompiles_total{fn="linear.step",rank="0"} 1',
            'dmlc_feed_h2d_bytes_total{feed="f0",rank="0"} 1048576',
            'dmlc_feed_h2d_mbps_sum{feed="f0",rank="0"} 500',
            'dmlc_feed_h2d_mbps_count{feed="f0",rank="0"} 1',
            'dmlc_feed_consume_ns_sum{feed="f0",rank="0"} 4e6',
            'dmlc_feed_consume_ns_count{feed="f0",rank="0"} 2',
            'dmlc_device_live_bytes{device="cpu:0",rank="0"} 2097152',
            "malformed line {{{",
        ])
        from dmlc_tpu.tools import obs_top

        workers = {"world_version": 1, "workers": {
            "0": {"epoch": 3, "lag_s": 0.5, "straggler": False}}}
        rows, h2d = obs_top.build_rows(text, workers)
        assert len(rows) == 1
        row = rows[0]
        assert row["compiles"] == 2 and row["recompiles"] == 1
        assert row["step_ms"] == pytest.approx(2.0)
        assert row["h2d_mbps"] == pytest.approx(500.0)  # histogram mean seed
        assert row["hbm_mb"] == pytest.approx(2.097152)
        assert h2d == {0: 1048576.0}
        # second frame: inter-poll byte rate replaces the histogram mean
        text2 = text.replace(
            'dmlc_feed_h2d_bytes_total{feed="f0",rank="0"} 1048576',
            'dmlc_feed_h2d_bytes_total{feed="f0",rank="0"} 3145728')
        rows2, _ = obs_top.build_rows(text2, workers, prev_h2d=h2d, dt_s=2.0)
        assert rows2[0]["h2d_mbps"] == pytest.approx(1.048576)
        table = obs_top.render_table(rows2, world_version=1)
        assert "world_version=1" in table and "rank" in table
