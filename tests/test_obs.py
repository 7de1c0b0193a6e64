"""Unified observability layer (dmlc_tpu/obs): registry semantics,
thread safety, disabled-path cost, span tracing, exporters, cross-host
aggregation, tracker heartbeats, and the Timer satellite fixes.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from dmlc_tpu import obs
from dmlc_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    NOOP,
    Registry,
    escape_label_value,
)
from dmlc_tpu.utils.logging import DMLCError
from dmlc_tpu.utils.timer import Timer


class TestRegistry:
    def test_idempotent_children_and_kind_conflict(self):
        reg = Registry()
        a = reg.counter("dmlc_t_x_total", "help", feed="f0")
        b = reg.counter("dmlc_t_x_total", feed="f0")
        assert a is b
        c = reg.counter("dmlc_t_x_total", feed="f1")
        assert c is not a
        with pytest.raises(DMLCError):
            reg.gauge("dmlc_t_x_total", feed="f0")

    def test_snapshot_and_flat_values(self):
        reg = Registry()
        reg.counter("dmlc_t_c_total", "c", k="v").inc(3)
        reg.gauge("dmlc_t_g_value", "g").set(2.5)
        reg.histogram("dmlc_t_h_ns", "h").observe(5)
        snap = reg.snapshot()
        assert snap['dmlc_t_c_total{k="v"}'] == 3
        assert snap["dmlc_t_g_value"] == 2.5
        assert snap["dmlc_t_h_ns"]["count"] == 1
        assert snap["dmlc_t_h_ns"]["sum"] == 5
        flat = reg.flat_values()
        assert flat["dmlc_t_h_ns:sum"] == 5.0
        assert flat["dmlc_t_h_ns:count"] == 1.0

    def test_thread_safety_8_writers(self):
        reg = Registry()
        c = reg.counter("dmlc_t_threads_total")
        h = reg.histogram("dmlc_t_threads_ns")
        per_thread, nthreads = 5000, 8

        def work():
            for i in range(per_thread):
                c.inc()
                h.observe(i)

        threads = [threading.Thread(target=work) for _ in range(nthreads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = per_thread * nthreads
        assert c.value == total
        assert h.count == total
        assert h.sum == nthreads * per_thread * (per_thread - 1) / 2
        assert sum(h.buckets().values()) == total


class TestHistogramBuckets:
    def test_le_edge_semantics(self):
        reg = Registry()
        h = reg.histogram("dmlc_t_edges_ns", buckets=(10, 100, 1000))
        # le semantics: a value equal to a bound counts IN that bound
        for v in (1, 10, 11, 100, 1000, 1001):
            h.observe(v)
        assert h.buckets() == {"10": 2, "100": 2, "1000": 1, "+Inf": 1}
        # cumulative covers every bound plus +Inf
        assert dict(h.cumulative()) == {
            "10": 2, "100": 4, "1000": 5, "+Inf": 6}

    def test_default_buckets_log_scale(self):
        assert DEFAULT_BUCKETS[0] == 1
        assert all(b2 == b1 * 4 for b1, b2 in
                   zip(DEFAULT_BUCKETS, DEFAULT_BUCKETS[1:]))
        h = Registry().histogram("dmlc_t_default_ns")
        h.observe(0)      # below the first bound → first bucket
        h.observe(4 ** 25)  # beyond the last bound → overflow
        b = h.buckets()
        assert b["1"] == 1 and b["+Inf"] == 1

    def test_quantile_interpolates_within_bucket(self):
        h = Registry().histogram("dmlc_t_q_ns", buckets=(10, 100, 1000))
        for v in (5, 5, 5, 50):  # 3 in (0,10], 1 in (10,100]
            h.observe(v)
        # p50 lands inside the first bucket: lo=0, hi=10, 2/3 through it
        assert h.quantile(0.5) == pytest.approx(10 * (2 / 3))
        # p100 lands in the second bucket at its upper edge
        assert h.quantile(1.0) == pytest.approx(100)

    def test_quantile_edges(self):
        h = Registry().histogram("dmlc_t_qe_ns", buckets=(10, 100))
        assert h.quantile(0.5) == 0.0          # empty histogram
        h.observe(4)
        assert h.quantile(0.0) == 0.0          # q=0 → bucket lower edge
        assert h.quantile(-1.0) == 0.0         # q clamped up to 0
        h.observe(10 ** 9)                      # overflow bucket
        # overflow observations clamp to the last finite bound
        assert h.quantile(1.0) == 100
        assert h.quantile(2.0) == h.quantile(1.0)  # q clamped down

    def test_quantile_single_bucket(self):
        h = Registry().histogram("dmlc_t_q1_ns", buckets=(8,))
        assert h.quantile(1.0) == 0.0  # still empty
        for _ in range(4):
            h.observe(2)
        assert h.quantile(0.0) == 0.0  # lower edge of the only bucket
        assert h.quantile(1.0) == 8    # upper edge of the only bucket
        assert h.quantile(0.5) == pytest.approx(4.0)  # interpolated

    def test_quantile_noop_child(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS", "0")
        h = Registry().histogram("dmlc_t_qn_ns")
        h.observe(5)
        assert h.quantile(0.5) == 0.0


class TestDisabledPath:
    def test_disabled_returns_shared_noop(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_METRICS", "0")
        reg = Registry()
        c = reg.counter("dmlc_t_off_total", who="x")
        h = reg.histogram("dmlc_t_off_ns")
        assert c is NOOP and h is NOOP
        c.inc()
        h.observe(1)
        assert c.value == 0 and h.sum == 0.0
        assert reg.snapshot() == {} and reg.flat_values() == {}

    def test_disabled_overhead_under_2x_noop_call(self, monkeypatch):
        """The disabled path's contract, by what it does and not by a
        stopwatch (the machine's load is not the program's): every
        disabled handle IS the shared no-op, a disabled span records
        nothing and keeps nothing, and 10,000 disabled calls leave no
        memory allocated by a line of obs/trace.py or obs/metrics.py."""
        import tracemalloc

        from dmlc_tpu.obs import metrics as metrics_mod
        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        assert not trace_mod._listeners
        obs.clear_trace()
        live = Registry().histogram("dmlc_t_cost_live_ns")
        monkeypatch.setenv("DMLC_TPU_METRICS", "0")
        reg = Registry()
        counter = reg.counter("dmlc_t_cost_total")
        hist = reg.histogram("dmlc_t_cost_ns", who="x")
        # identity, not speed: one shared child, whose mutators are the
        # class's empty functions
        assert counter is NOOP and hist is NOOP
        assert counter.inc.__func__ is type(NOOP).inc
        assert hist.observe.__func__ is type(NOOP).observe
        # a span with nothing to observe into is the shared inert object,
        # args or not; with a live histogram it is a timer that holds no
        # name and no args, and records no event
        assert obs.span("stage") is obs.NOOP_SPAN
        assert obs.span("stage", pass_=1, batch=2) is obs.NOOP_SPAN
        assert obs.span("stage", hist=hist, pass_=1) is obs.NOOP_SPAN
        timed = obs.span("stage", hist=live, pass_=1, batch=2)
        assert timed is not obs.NOOP_SPAN and not timed.live
        assert not hasattr(timed, "args") and not hasattr(timed, "name")
        assert not hasattr(timed, "__dict__")
        with timed:
            pass
        assert live.count == 1 and live.sum == timed.dur_ns
        assert obs.trace_events() == []

        def burst(n=10_000):
            for i in range(n):
                counter.inc()
                hist.observe(i)
                with obs.span("stage", pass_=0, batch=i):
                    pass
                with obs.span("stage", hist=hist, pass_=0, batch=i):
                    pass

        burst(200)  # warm caches before measuring
        started_here = not tracemalloc.is_tracing()
        if started_here:
            tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            burst()
            after = tracemalloc.take_snapshot()
        finally:
            if started_here:
                tracemalloc.stop()
        only = [tracemalloc.Filter(True, trace_mod.__file__),
                tracemalloc.Filter(True, metrics_mod.__file__)]
        grown = [
            str(stat) for stat in after.filter_traces(only).compare_to(
                before.filter_traces(only), "lineno")
            if stat.size_diff > 0]
        assert grown == []
        assert obs.trace_events() == []
        assert reg.snapshot() == {}


class TestSpanHistogram:
    """``obs.span(name, hist=h)``: the span observes its own duration, from
    the two clock reads that give its ``ts`` and ``dur``."""

    def test_dur_and_observed_value_are_one_number(self):
        from dmlc_tpu.obs import trace as trace_mod

        hist = Registry().histogram("dmlc_t_span_ns")
        seen = []
        trace_mod.add_listener(seen.append)
        try:
            for k in range(3):
                with obs.span("put", hist=hist, batch=k) as live:
                    time.sleep(0.001)
                assert live.live and live.dur_ns > 0
        finally:
            trace_mod.remove_listener(seen.append)
        assert [e["args"] for e in seen] == [{"batch": k} for k in range(3)]
        assert hist.count == 3
        # the event's dur is the histogram's ns in us: the same reads
        assert hist.sum == sum(round(e["dur"] * 1e3) for e in seen)
        assert live.dur_ns == round(seen[-1]["dur"] * 1e3)
        for e in seen:
            assert e["ts"] > 0 and e["dur"] >= 1000.0

    def test_tracing_off_fills_the_histogram_and_records_no_event(
            self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        obs.clear_trace()
        hist = Registry().histogram("dmlc_t_span_off_ns")
        with obs.span("put", hist=hist, batch=0) as timed:
            time.sleep(0.001)
        assert timed is not obs.NOOP_SPAN and not timed.live
        assert (hist.count, hist.sum) == (1, timed.dur_ns)
        assert timed.dur_ns >= 1_000_000
        assert obs.trace_events() == []

    def test_an_exception_still_observes(self):
        hist = Registry().histogram("dmlc_t_span_exc_ns")
        with pytest.raises(StopIteration):
            with obs.span("take", hist=hist):
                raise StopIteration
        assert hist.count == 1

    @pytest.mark.parametrize("tracing", [False, True])
    def test_an_inner_span_observes_no_more_than_its_outer(self, tracing):
        """``put`` inside ``dispatch``: four clock reads in order, so the
        two counters nest as the spans do, tracing on or off."""
        from dmlc_tpu.obs import trace as trace_mod

        reg = Registry()
        outer_h = reg.histogram("dmlc_t_outer_ns")
        inner_h = reg.histogram("dmlc_t_inner_ns")
        seen = []
        if tracing:
            trace_mod.add_listener(seen.append)
        try:
            for k in range(5):
                with obs.span("dispatch", hist=outer_h, batch=k) as outer:
                    with obs.span("put", hist=inner_h, batch=k) as inner:
                        pass
                assert outer.live == inner.live == tracing
                assert 0 <= inner.dur_ns <= outer.dur_ns
        finally:
            if tracing:
                trace_mod.remove_listener(seen.append)
        assert outer_h.count == inner_h.count == 5
        assert inner_h.sum <= outer_h.sum
        assert [e["name"] for e in seen] == 5 * tracing * ["put", "dispatch"]


class TestSpans:
    def test_span_disabled_without_env(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        obs.clear_trace()
        with obs.span("nothing"):
            pass
        assert obs.trace_events() == []

    def test_nesting_ordering_and_flush(self, monkeypatch, tmp_path):
        out = tmp_path / "t.json"
        monkeypatch.setenv("DMLC_TPU_TRACE", str(out))
        obs.clear_trace()
        with obs.span("outer", epoch=0):
            with obs.span("inner_a", chunk=1):
                time.sleep(0.002)
            with obs.span("inner_b", chunk=2):
                time.sleep(0.002)
        path = obs.flush_trace()
        assert path == str(out)
        doc = json.loads(out.read_text())
        events = {e["name"]: e for e in doc["traceEvents"]}
        assert set(events) == {"outer", "inner_a", "inner_b"}
        outer, a, b = events["outer"], events["inner_a"], events["inner_b"]
        for e in (outer, a, b):
            assert e["ph"] == "X" and e["dur"] > 0
        # containment: both inners inside outer, a before b, same thread
        for inner in (a, b):
            assert inner["ts"] >= outer["ts"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
            assert inner["tid"] == outer["tid"]
        assert a["ts"] + a["dur"] <= b["ts"] + 1
        assert a["args"] == {"chunk": 1}
        obs.clear_trace()

    def test_feed_spans_emitted(self, monkeypatch, tmp_path):
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.device.feed import BatchSpec, DeviceFeed
        from dmlc_tpu.io.input_split import create_input_split

        out = tmp_path / "feed.json"
        monkeypatch.setenv("DMLC_TPU_TRACE", str(out))
        obs.clear_trace()
        rng = np.random.RandomState(0)
        lines = []
        for i in range(600):
            ids = np.sort(rng.choice(40, size=1 + i % 7, replace=False))
            feats = " ".join("%d:%.6f" % (j, rng.rand()) for j in ids)
            lines.append("%d %s" % (i % 2, feats))
        path = tmp_path / "t.svm"
        path.write_text("\n".join(lines) + "\n")
        split = create_input_split(str(path), 0, 1, "text", threaded=False)
        spec = BatchSpec(batch_size=128, layout="dense", num_features=40)
        feed = DeviceFeed(LibSVMParser(split, nthread=1), spec)
        for batch in feed:
            np.asarray(batch["label"])
        feed.close()
        names = {e["name"] for e in obs.trace_events()}
        assert {"produce", "feed_batch", "take", "dispatch", "stage", "put",
                "deliver", "consume"} <= names
        obs.flush_trace()
        json.loads(out.read_text())  # loadable Chrome trace
        obs.clear_trace()


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    made = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs, self.entered = name, kwargs, 0
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.entered -= 1
        return False


class TestJaxBridge:
    @pytest.fixture
    def bridged(self, monkeypatch):
        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.setenv("DMLC_TPU_TRACE_JAX", "1")
        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        monkeypatch.setattr(
            trace_mod, "_bridge", (_FakeAnnotation,))
        _FakeAnnotation.made = []
        seen = []
        trace_mod.add_listener(seen.append)
        yield seen
        trace_mod.remove_listener(seen.append)

    def test_span_args_reach_the_annotation(self, bridged):
        with obs.span("dispatch", pass_=2, batch=5) as live:
            assert live is not obs.NOOP_SPAN
            (annot,) = _FakeAnnotation.made
            assert annot.entered == 1
        assert annot.entered == 0
        # the name stays bare; the args travel as the annotation's kwargs
        assert annot.name == "dispatch"
        assert annot.kwargs == {"pass_": 2, "batch": 5}
        assert bridged[0]["args"] == {"pass_": 2, "batch": 5}

    def test_classes_resolved_once_not_per_span(self, monkeypatch):
        import jax.profiler

        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.setenv("DMLC_TPU_TRACE_JAX", "1")
        monkeypatch.setattr(trace_mod, "_bridge", None)
        assert trace_mod._jax_annotation_cls() is \
            jax.profiler.TraceAnnotation
        resolved = trace_mod._bridge
        assert trace_mod._jax_annotation_cls() is \
            jax.profiler.TraceAnnotation
        assert trace_mod._bridge is resolved  # no second lookup
        monkeypatch.delenv("DMLC_TPU_TRACE_JAX")
        assert trace_mod._jax_annotation_cls() is None

    def test_tracing_off_is_the_shared_noop(self, monkeypatch):
        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.setenv("DMLC_TPU_TRACE_JAX", "1")
        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        monkeypatch.setattr(
            trace_mod, "_bridge", (_FakeAnnotation,))
        _FakeAnnotation.made = []
        seen = []
        trace_mod.add_listener(seen.append)
        trace_mod.remove_listener(seen.append)  # disarmed again
        obs.clear_trace()
        assert obs.span("consume", pass_=0, batch=1) is obs.NOOP_SPAN
        with obs.span("consume", pass_=0, batch=1):
            assert obs.current_batch() == {}
        assert seen == [] and _FakeAnnotation.made == []
        assert obs.trace_events() == []

    def test_current_batch_is_thread_local(self):
        obs.set_current_batch(4, 9)
        try:
            assert obs.current_batch() == {"pass_": 4, "batch": 9}
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(obs.current_batch()))
            t.start()
            t.join()
            assert seen == [{}]
        finally:
            obs.set_current_batch(None)
        assert obs.current_batch() == {}


class TestFlow:
    def test_disabled_is_zero_and_allocation_free(self, monkeypatch):
        """What the disabled path promises, none of it a count of the
        whole interpreter's blocks (other threads of the test worker
        allocate too): flow id 0, no event built or buffered, and no
        memory left allocated by a line of obs/trace.py."""
        import tracemalloc

        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.delenv("DMLC_TPU_TRACE", raising=False)
        obs.clear_trace()
        built = []
        monkeypatch.setattr(
            trace_mod, "_flow_event", lambda *a: built.append(a))
        assert obs.new_flow() == 0

        def burst(n=2000):
            for _ in range(n):
                fid = obs.new_flow()
                assert fid == 0
                obs.flow_start(fid, "chunk")
                obs.flow_step(fid, "chunk")
                obs.flow_end(fid, "chunk")

        burst()  # warm caches before measuring
        started_here = not tracemalloc.is_tracing()
        if started_here:
            tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            burst()
            after = tracemalloc.take_snapshot()
        finally:
            if started_here:
                tracemalloc.stop()
        only = [tracemalloc.Filter(True, trace_mod.__file__)]
        grown = [
            str(stat) for stat in after.filter_traces(only).compare_to(
                before.filter_traces(only), "lineno")
            if stat.size_diff > 0]
        assert grown == []
        assert built == []
        assert obs.trace_events() == []

    def test_enabled_chain_same_id_and_bp(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DMLC_TPU_TRACE", str(tmp_path / "flow.json"))
        obs.clear_trace()
        fid = obs.new_flow()
        assert fid > 0
        assert obs.new_flow() != fid  # unique per allocation
        with obs.span("io_read", flow=fid):
            obs.flow_start(fid, "chunk")
        with obs.span("parse", flow=fid):
            obs.flow_step(fid, "chunk")
        with obs.span("consume"):
            obs.flow_end(fid, "chunk")
        flows = [e for e in obs.trace_events()
                 if e.get("cat") == "dataflow" and e.get("id") == fid]
        assert [e["ph"] for e in flows] == ["s", "t", "f"]
        for e in flows:
            assert e["name"] == "chunk" and e["ts"] >= 0
        # arrow head binds to the enclosing slice, tail/steps to theirs
        assert "bp" not in flows[0] and "bp" not in flows[1]
        assert flows[2]["bp"] == "e"
        obs.clear_trace()

    def test_flow_id_embeds_rank_and_pid(self, monkeypatch, tmp_path):
        from dmlc_tpu.obs import trace as trace_mod

        monkeypatch.setenv("DMLC_TPU_TRACE", str(tmp_path / "flow.json"))
        monkeypatch.setenv("DMLC_TASK_ID", "3")
        monkeypatch.setattr(trace_mod, "_FLOW_BASE", None)
        obs.clear_trace()
        fid = obs.new_flow()
        assert fid >> 40 == 3 + 1  # rank+1 in the high bits
        assert (fid >> 24) & 0xFFFF == os.getpid() & 0xFFFF
        obs.clear_trace()

    def test_current_flow_is_thread_local(self):
        obs.set_current_flow(7)
        try:
            assert obs.current_flow() == 7
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(obs.current_flow()))
            t.start()
            t.join()
            assert seen == [0]  # other threads see no ambient flow
        finally:
            obs.set_current_flow(0)
        assert obs.current_flow() == 0

    def test_ingest_flow_chain_end_to_end(self, monkeypatch, tmp_path):
        from dmlc_tpu.data.parsers import LibSVMParser
        from dmlc_tpu.data.pipeline import PipelinedParser
        from dmlc_tpu.device.feed import BatchSpec, DeviceFeed
        from dmlc_tpu.io.input_split import create_input_split

        monkeypatch.setenv("DMLC_TPU_TRACE", str(tmp_path / "e2e.json"))
        obs.clear_trace()
        rng = np.random.RandomState(1)
        lines = []
        for i in range(600):
            ids = np.sort(rng.choice(40, size=1 + i % 7, replace=False))
            feats = " ".join("%d:%.6f" % (j, rng.rand()) for j in ids)
            lines.append("%d %s" % (i % 2, feats))
        path = tmp_path / "flow.svm"
        path.write_text("\n".join(lines) + "\n")
        split = create_input_split(str(path), 0, 1, "text", threaded=False)
        split.hint_chunk_size(4096)  # multi-chunk, or one flow proves little
        piped = PipelinedParser(LibSVMParser(split, nthread=1), nthread=2)
        spec = BatchSpec(batch_size=128, layout="dense", num_features=40)
        feed = DeviceFeed(piped, spec)
        for batch in feed:
            np.asarray(batch["label"])
        feed.close()
        chains = {}
        for e in obs.trace_events():
            if e.get("cat") == "dataflow":
                chains.setdefault(e["id"], []).append(e["ph"])
        assert len(chains) > 1  # one flow per chunk
        # at least one chunk's full journey: io_read s → t steps → consume f
        assert any(phs[0] == "s" and phs[-1] == "f" and "t" in phs
                   for phs in chains.values())
        obs.clear_trace()


class TestExporters:
    def _reg(self):
        reg = Registry()
        reg.counter("dmlc_t_exp_total", "a counter", k="v").inc(7)
        reg.histogram("dmlc_t_exp_ns", "a hist").observe(3)
        return reg

    def test_jsonl_appends(self, tmp_path):
        reg = self._reg()
        path = tmp_path / "m.jsonl"
        obs.export_jsonl(str(path), reg)
        obs.export_jsonl(str(path), reg)
        lines = path.read_text().strip().splitlines()
        assert len(lines) == 2
        rec = json.loads(lines[-1])
        assert rec["metrics"]['dmlc_t_exp_total{k="v"}'] == 7

    def test_prometheus_textfile(self, tmp_path):
        reg = self._reg()
        path = tmp_path / "m.prom"
        obs.export_prometheus(str(path), reg)
        text = path.read_text()
        assert "# TYPE dmlc_t_exp_total counter" in text
        assert 'dmlc_t_exp_total{k="v"} 7' in text
        assert 'dmlc_t_exp_ns_bucket{le="4"} 1' in text
        assert 'dmlc_t_exp_ns_bucket{le="+Inf"} 1' in text
        assert "dmlc_t_exp_ns_count 1" in text

    def test_label_value_escaping(self):
        from dmlc_tpu.obs.exporters import prometheus_lines

        # backslash escaped first, or its own escapes would re-escape
        assert escape_label_value('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
        reg = Registry()
        reg.counter("dmlc_t_esc_total", "c", path='a"b\\c\nd').inc(1)
        lines = prometheus_lines(reg)
        assert all("\n" not in line for line in lines)  # format-valid
        assert 'dmlc_t_esc_total{path="a\\"b\\\\c\\nd"} 1' in lines
        # the flat snapshot identity uses the same escaping
        assert 'dmlc_t_esc_total{path="a\\"b\\\\c\\nd"}' in reg.snapshot()

    def test_summary_line_and_export_epoch(self, monkeypatch, tmp_path):
        reg = self._reg()
        line = obs.summary_line(reg=reg)
        assert 'dmlc_t_exp_total{k="v"}=7' in line
        assert "dmlc_t_exp_ns=p50~2.5/1" in line
        out = tmp_path / "epoch.prom"
        monkeypatch.setenv("DMLC_TPU_METRICS_EXPORT", str(out))
        got = obs.export_epoch(reg)
        assert got == line
        assert out.exists()
        # export failure degrades, never raises
        monkeypatch.setenv("DMLC_TPU_METRICS_EXPORT",
                           str(tmp_path / "no" / "dir" / "x.prom"))
        assert obs.export_epoch(reg) == line


class TestCrossHost:
    def test_single_host_snapshot_exact(self):
        from dmlc_tpu.collective.device import DeviceEngine

        reg = Registry()
        reg.counter("dmlc_t_xh_total", "c").inc(42)
        reg.histogram("dmlc_t_xh_ns", "h").observe(10)
        snap = obs.cross_host_snapshot(DeviceEngine(), reg=reg)
        assert snap["world"] == 1 and snap["rank"] == 0
        m = snap["metrics"]["dmlc_t_xh_total"]
        assert m["min"] == m["median"] == m["max"] == m["sum"] == 42.0
        assert snap["metrics"]["dmlc_t_xh_ns:count"]["max"] == 1.0

    def test_prefix_filter_and_report_skew(self):
        from dmlc_tpu.collective.device import DeviceEngine

        reg = Registry()
        reg.counter("dmlc_t_keep_total").inc(1)
        reg.counter("dmlc_other_drop_total").inc(1)
        snap = obs.report_skew(DeviceEngine(), reg=reg, prefix="dmlc_t_")
        assert list(snap["metrics"]) == ["dmlc_t_keep_total"]


class TestTimerSatellite:
    def test_exit_without_enter_raises_dmlc_error(self):
        with pytest.raises(DMLCError):
            Timer().__exit__(None, None, None)

    def test_reset_mid_timing_keeps_timing_valid(self):
        t = Timer()
        with t:
            time.sleep(0.002)
            t.reset()  # mid-flight: restarts, exit must not raise
        assert 0.0 <= t.elapsed < 0.5

    def test_accumulates_across_enters(self):
        t = Timer()
        for _ in range(2):
            with t:
                time.sleep(0.001)
        assert t.elapsed >= 0.002
        t.reset()
        assert t.elapsed == 0.0


class TestHeartbeat:
    def test_heartbeat_recorded_and_counted(self):
        from dmlc_tpu.tracker.rendezvous import RabitTracker, send_heartbeat

        before = obs.registry().counter(
            "dmlc_tracker_heartbeats_total").value
        tracker = RabitTracker("127.0.0.1", num_workers=1)
        try:
            tracker.start(1)
            send_heartbeat("127.0.0.1", tracker.port, rank=0, epoch=2,
                           metrics="loss=0.25")
            deadline = time.time() + 5
            while not tracker.heartbeats() and time.time() < deadline:
                time.sleep(0.01)
            hb = tracker.heartbeats()
            assert 0 in hb
            last_seen, line = hb[0]
            assert line == "epoch=2 loss=0.25"
            assert last_seen <= time.time()
            assert obs.registry().counter(
                "dmlc_tracker_heartbeats_total").value >= before + 1
        finally:
            tracker.close()

    def test_straggler_flagging(self, caplog):
        import logging as _logging

        from dmlc_tpu.tracker.rendezvous import RabitTracker

        tracker = RabitTracker("127.0.0.1", num_workers=2)
        try:
            tracker.heartbeat_gap = 0.01
            tracker._note_heartbeat(0, "epoch=0")
            time.sleep(0.05)
            with caplog.at_level(_logging.WARNING, "dmlc_tpu.tracker"):
                tracker._note_heartbeat(1, "epoch=0")
            assert any("straggler: rank 0" in r.getMessage()
                       for r in caplog.records)
            # flagged once: a second report from rank 1 does not re-warn
            caplog.clear()
            with caplog.at_level(_logging.WARNING, "dmlc_tpu.tracker"):
                tracker._note_heartbeat(1, "epoch=1")
            assert not caplog.records
            # rank 0 reporting again clears its flag, logs the recovery,
            # and ticks the recovery counter
            before = obs.registry().counter(
                "dmlc_tracker_straggler_recoveries_total").value
            with caplog.at_level(_logging.INFO, "dmlc_tpu.tracker"):
                tracker._note_heartbeat(0, "epoch=1")
            assert 0 not in tracker._hb_flagged
            assert any("straggler recovered: rank 0" in r.getMessage()
                       for r in caplog.records)
            assert obs.registry().counter(
                "dmlc_tracker_straggler_recoveries_total"
            ).value == before + 1
            # re-armed: the same rank going quiet again re-warns
            time.sleep(0.05)
            caplog.clear()
            with caplog.at_level(_logging.WARNING, "dmlc_tpu.tracker"):
                tracker._note_heartbeat(1, "epoch=2")
            assert any("straggler: rank 0" in r.getMessage()
                       for r in caplog.records)
        finally:
            tracker.close()
