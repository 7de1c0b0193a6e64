"""Unit tier for dmlc_tpu.resilience: the retry policy (classifier,
jitter bounds, deadline, budget, no-sleep-after-final-attempt), the
deterministic fault injector (spec grammar, per-site streams, disabled
no-op path), hedged calls, and the WebHDFS CREATE/APPEND retry split."""

import http.client
import io
import os
import random
import threading
import urllib.error

import pytest

from dmlc_tpu import resilience
from dmlc_tpu.resilience import (
    FaultSpecError,
    InjectedFault,
    RetryBudget,
    RetryPolicy,
    classify_transient,
    faults,
    hedged_call,
)
from dmlc_tpu.utils.logging import DMLCError


def _http_error(code: int) -> urllib.error.HTTPError:
    return urllib.error.HTTPError(
        "http://x/y", code, "status", {}, io.BytesIO(b"")
    )


def _policy(**kw):
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("budget", RetryBudget(0))
    kw.setdefault("deadline_s", 0)
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------------
# classifier
# ---------------------------------------------------------------------------


class TestClassifier:
    def test_5xx_transient(self):
        assert classify_transient(_http_error(500))
        assert classify_transient(_http_error(503))

    def test_throttling_transient(self):
        # the old _retry_call bug: 429/408 were fatal because code < 500
        assert classify_transient(_http_error(429))
        assert classify_transient(_http_error(408))

    def test_other_4xx_fatal(self):
        assert not classify_transient(_http_error(403))
        assert not classify_transient(_http_error(404))
        assert not classify_transient(_http_error(416))

    def test_network_shapes_transient(self):
        assert classify_transient(urllib.error.URLError("refused"))
        assert classify_transient(OSError("reset"))
        assert classify_transient(ConnectionResetError())
        assert classify_transient(http.client.IncompleteRead(b""))
        assert classify_transient(DMLCError("engine failure"))

    def test_config_errors_fatal(self):
        # OSError subclasses that mean misconfiguration, not flakiness
        assert not classify_transient(FileNotFoundError("gone"))
        assert not classify_transient(PermissionError("denied"))
        assert not classify_transient(IsADirectoryError("dir"))

    def test_injected_fault_is_transient(self):
        assert classify_transient(InjectedFault("chaos"))


# ---------------------------------------------------------------------------
# RetryPolicy.call
# ---------------------------------------------------------------------------


class TestPolicyCall:
    def test_succeeds_after_transient_failures(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("flaky")
            return "ok"

        assert _policy(max_attempts=3).call(fn, "t.site") == "ok"
        assert len(calls) == 3

    def test_fatal_error_raises_immediately(self):
        calls = []

        def fn():
            calls.append(1)
            raise _http_error(404)

        with pytest.raises(urllib.error.HTTPError):
            _policy(max_attempts=5).call(fn, "t.site")
        assert len(calls) == 1

    def test_gives_up_after_max_attempts(self):
        calls = []

        def fn():
            calls.append(1)
            raise OSError("down")

        with pytest.raises(DMLCError, match="attempts exhausted"):
            _policy(max_attempts=3).call(fn, "t.site")
        assert len(calls) == 3

    def test_no_sleep_after_final_attempt(self):
        # the second _retry_call bug: a full backoff was wasted after the
        # last failure before raising
        sleeps = []
        policy = _policy(max_attempts=3, sleep=sleeps.append)

        def fn():
            raise OSError("down")

        with pytest.raises(DMLCError):
            policy.call(fn, "t.site")
        assert len(sleeps) == 2  # 3 attempts, sleeps only between them

    def test_custom_classifier(self):
        policy = _policy(
            max_attempts=3,
            classify=lambda err: isinstance(err, ConnectionError),
        )
        with pytest.raises(DMLCError, match="bad magic"):
            policy.call(lambda: (_ for _ in ()).throw(
                DMLCError("bad magic")), "t.site")

    def test_original_error_chained(self):
        def fn():
            raise OSError("root cause")

        with pytest.raises(DMLCError) as exc:
            _policy(max_attempts=2).call(fn, "t.site")
        assert isinstance(exc.value.__cause__, OSError)


class TestJitter:
    def test_decorrelated_jitter_bounds(self):
        policy = _policy(base_s=0.1, cap_s=2.0, rng=random.Random(7))
        prev = policy.base_s
        for _ in range(200):
            delay = policy.next_sleep(prev)
            assert 0.1 <= delay <= 2.0
            assert delay <= max(prev * 3, 0.1)
            prev = delay

    def test_sleeps_vary(self):
        policy = _policy(base_s=0.01, cap_s=10.0, rng=random.Random(3))
        seen = {round(policy.next_sleep(1.0), 6) for _ in range(20)}
        assert len(seen) > 1  # jitter, not a fixed ladder


class TestDeadline:
    def test_deadline_stops_retrying(self):
        clock = [0.0]

        def sleep(s):
            clock[0] += s

        policy = RetryPolicy(
            max_attempts=1000, base_s=10.0, cap_s=10.0,
            deadline_s=25.0, sleep=sleep, budget=RetryBudget(0),
            clock=lambda: clock[0],
        )
        calls = []

        def fn():
            calls.append(1)
            raise OSError("down")

        with pytest.raises(DMLCError, match="deadline"):
            policy.call(fn, "t.site")
        # 10s jittered sleeps against a 25s deadline: at most 3 attempts
        assert len(calls) <= 3


class TestBudget:
    def test_unlimited_by_default(self):
        budget = RetryBudget(0)
        assert all(budget.take() for _ in range(10_000))

    def test_exhaustion_fails_fast(self):
        budget = RetryBudget(3, refill_s=3600.0)
        policy = _policy(max_attempts=100, budget=budget)

        def fn():
            raise OSError("outage")

        with pytest.raises(DMLCError, match="budget exhausted"):
            policy.call(fn, "t.site")

    def test_budget_shared_across_policies(self):
        budget = RetryBudget(4, refill_s=3600.0)
        for _ in range(4):
            assert budget.take()
        policy = _policy(max_attempts=5, budget=budget)
        with pytest.raises(DMLCError, match="budget exhausted"):
            policy.call(lambda: (_ for _ in ()).throw(OSError()), "t.site")

    def test_refill(self):
        budget = RetryBudget(10, refill_s=0.000001)  # instant refill
        assert all(budget.take() for _ in range(100))


class TestRetryState:
    def test_progress_refills_attempts(self):
        state = _policy(max_attempts=3).start("t.site")
        for _ in range(7):  # would exhaust max_attempts=3 without reset
            state.failed(OSError("drip"), progressed=True)
        assert state.total_attempts == 7

    def test_absolute_ceiling_bounds_progress_resets(self):
        state = _policy(max_attempts=3).start("t.site")
        with pytest.raises(DMLCError, match="ceiling"):
            for _ in range(100):
                state.failed(OSError("drip"), progressed=True)
        assert state.total_attempts == 30  # max_attempts * 10

    def test_cancelled_stops_promptly(self):
        state = _policy(max_attempts=50).start(
            "t.site", cancelled=lambda: True)
        with pytest.raises(DMLCError, match="cancelled"):
            state.failed(OSError("down"))


class TestRetryMetrics:
    def test_attempts_and_giveups_counted(self):
        from dmlc_tpu import obs

        reg = obs.registry()
        attempts = reg.counter(
            "dmlc_retry_attempts_total",
            "retries performed, by call site", site="t.metrics")
        giveups = reg.counter(
            "dmlc_retry_giveups_total",
            "operations abandoned after exhausting retries",
            site="t.metrics")
        a0, g0 = attempts.value, giveups.value
        with pytest.raises(DMLCError):
            _policy(max_attempts=3).call(
                lambda: (_ for _ in ()).throw(OSError()), "t.metrics")
        assert attempts.value == a0 + 2  # granted retries, not tries
        assert giveups.value == g0 + 1


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.reset()
    yield
    resilience.reset()


class TestFaultSpec:
    def test_parse_probabilistic(self):
        rules = faults.parse_spec("io.read:p=0.5:seed=7")
        assert rules["io.read"].p == 0.5

    def test_parse_scripted(self):
        rules = faults.parse_spec("collective.send:nth=3")
        assert rules["collective.send"].nth == 3

    def test_parse_multi_site(self):
        rules = faults.parse_spec(
            "io.read:p=0.02:seed=7;collective.send:nth=3")
        assert set(rules) == {"io.read", "collective.send"}

    def test_bad_option_raises(self):
        with pytest.raises(FaultSpecError):
            faults.parse_spec("io.read:bogus=1")
        with pytest.raises(FaultSpecError):
            faults.parse_spec("io.read:p=not-a-float")
        with pytest.raises(FaultSpecError):
            faults.parse_spec("io.read:p=0")  # no trigger configured

    def test_nth_fires_exactly_once(self):
        resilience.configure("t.site:nth=3")
        resilience.faultpoint("t.site")
        resilience.faultpoint("t.site")
        with pytest.raises(InjectedFault):
            resilience.faultpoint("t.site")
        for _ in range(50):
            resilience.faultpoint("t.site")  # never again

    def test_times_extends_nth(self):
        resilience.configure("t.site:nth=2:times=2")
        resilience.faultpoint("t.site")
        for _ in range(2):
            with pytest.raises(InjectedFault):
                resilience.faultpoint("t.site")
        resilience.faultpoint("t.site")

    def test_unarmed_site_never_fires(self):
        resilience.configure("other.site:nth=1")
        for _ in range(100):
            resilience.faultpoint("t.site")


class TestFaultDeterminism:
    def _run(self, spec, sites, passes=500):
        resilience.configure(spec)
        for i in range(passes):
            for site in sites:
                try:
                    resilience.faultpoint(site)
                except InjectedFault:
                    pass
        fired = list(resilience.injector().fired)
        resilience.reset()
        return fired

    def test_same_spec_same_schedule(self):
        spec = "t.a:p=0.05:seed=7;t.b:p=0.1:seed=7"
        one = self._run(spec, ["t.a", "t.b"])
        two = self._run(spec, ["t.a", "t.b"])
        assert one and one == two

    def test_seed_changes_schedule(self):
        one = self._run("t.a:p=0.05:seed=7", ["t.a"])
        two = self._run("t.a:p=0.05:seed=8", ["t.a"])
        assert one != two

    def test_sites_independent(self):
        """Arming a second site must not perturb the first site's
        schedule (per-site rng streams)."""
        alone = [f for f in self._run(
            "t.a:p=0.05:seed=7", ["t.a", "t.b"]) if f[0] == "t.a"]
        together = [f for f in self._run(
            "t.a:p=0.05:seed=7;t.b:p=0.5:seed=9", ["t.a", "t.b"])
            if f[0] == "t.a"]
        assert alone == together


class TestDisabledPath:
    def test_disabled_is_shared_noop(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_FAULTS", raising=False)
        resilience.reset()
        resilience.faultpoint("io.read")
        assert resilience.injector() is resilience.NOOP

    def test_disabled_path_zero_allocation(self, monkeypatch):
        """Mirrors the DMLC_TPU_METRICS=0 no-op-child guarantee: a
        disarmed faultpoint must not allocate per call."""
        import tracemalloc

        monkeypatch.delenv("DMLC_TPU_FAULTS", raising=False)
        resilience.reset()
        resilience.faultpoint("warm.up")  # trigger lazy init outside trace

        def loop(n):
            fp = resilience.faultpoint
            for _ in range(n):
                fp("io.read")

        tracemalloc.start()
        loop(1000)  # first traced pass pays tracemalloc's frame records
        before = tracemalloc.take_snapshot()
        loop(1000)
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        # what the loop's own code holds, by the file that allocated it
        # (the idiom of tests/test_obs.py): tracemalloc traces every
        # thread of the process, and an xdist worker has one no test can
        # join, execnet's receiver, which unpacks the scheduler's next
        # message whenever it comes (found in PR 41: +882 bytes from
        # xdist/remote.py and execnet/gateway_base.py inside one test of
        # 180; here 152 bytes once in two whole runs)
        only = [tracemalloc.Filter(True, os.path.join(
                    os.path.dirname(resilience.__file__), "*")),
                tracemalloc.Filter(True, __file__)]
        changed = [
            str(stat) for stat in after.filter_traces(only).compare_to(
                before.filter_traces(only), "lineno")
            if stat.size_diff != 0]
        assert changed == []

    def test_env_arms_on_first_use(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_FAULTS", "t.env:nth=1")
        resilience.reset()
        with pytest.raises(InjectedFault):
            resilience.faultpoint("t.env")

    def test_malformed_env_spec_raises(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_FAULTS", "t.env:wat")
        resilience.reset()
        with pytest.raises(FaultSpecError):
            resilience.faultpoint("t.env")


class TestFaultThreadSafety:
    def test_nth_fires_once_under_contention(self):
        resilience.configure("t.site:nth=50")
        fired = []
        barrier = threading.Barrier(8)

        def worker():
            barrier.wait()
            for _ in range(100):
                try:
                    resilience.faultpoint("t.site")
                except InjectedFault:
                    fired.append(1)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(fired) == 1


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------


class TestHedgedCall:
    def test_threshold_zero_is_inline(self):
        ident = []

        def fn():
            ident.append(threading.current_thread())
            return 5

        assert hedged_call(fn, 0) == 5
        assert ident == [threading.main_thread()]

    def test_fast_primary_no_hedge(self):
        from dmlc_tpu import obs

        hedges = obs.registry().counter(
            "dmlc_readahead_hedges_total",
            "backup requests issued after the hedge threshold",
            site="readahead.fetch")
        h0 = hedges.value
        assert hedged_call(lambda: 9, 5.0) == 9
        assert hedges.value == h0

    def test_backup_wins_over_stuck_primary(self):
        stall = threading.Event()
        calls = []
        lock = threading.Lock()

        def fn():
            with lock:
                calls.append(1)
                first = len(calls) == 1
            if first:
                stall.wait(10.0)  # primary wedged
                return "slow"
            return "fast"

        try:
            assert hedged_call(fn, 0.05, site="t.hedge") == "fast"
        finally:
            stall.set()

    def test_both_fail_raises(self):
        def fn():
            raise OSError("both down")

        with pytest.raises(OSError, match="both down"):
            hedged_call(fn, 0.01, site="t.hedge")

    def test_primary_error_backup_success(self):
        calls = []
        lock = threading.Lock()

        def fn():
            with lock:
                calls.append(1)
                first = len(calls) == 1
            if first:
                import time
                time.sleep(0.05)
                raise OSError("primary died late")
            return "rescued"

        assert hedged_call(fn, 0.01, site="t.hedge") == "rescued"


# ---------------------------------------------------------------------------
# integration: the rewired call sites
# ---------------------------------------------------------------------------


class TestWebHDFSRetrySplit:
    def _stream(self, fail_times):
        from dmlc_tpu.io import webhdfs as wh

        class FakeFS:
            _part_bytes = 1 << 20

            def __init__(self):
                self.ops = []
                self.failures = dict(fail_times)

            def _two_step_write(self, method, name, op, data, **params):
                self.ops.append((op, bytes(data)))
                left = self.failures.get(op, 0)
                if left > 0:
                    self.failures[op] = left - 1
                    raise urllib.error.URLError("datanode hiccup")

        fs = FakeFS()
        from dmlc_tpu.io.filesystem import URI

        stream = wh._WebHDFSWriteStream.__new__(wh._WebHDFSWriteStream)
        from dmlc_tpu.io.object_store import ObjectWriteStream

        ObjectWriteStream.__init__(stream, fs._part_bytes)
        stream._fs = fs
        stream._path = URI.parse("hdfs://nn:9870/tmp/out.bin")
        stream._created = False
        return fs, stream

    def test_create_retries(self, monkeypatch):
        monkeypatch.setattr(
            "dmlc_tpu.resilience.retry.time.sleep", lambda s: None)
        fs, stream = self._stream({"CREATE": 2})
        stream._upload_part(b"hello", last=False)
        assert [op for op, _ in fs.ops] == ["CREATE"] * 3
        assert stream._created

    def test_append_single_shot(self, monkeypatch):
        monkeypatch.setattr(
            "dmlc_tpu.resilience.retry.time.sleep", lambda s: None)
        fs, stream = self._stream({"APPEND": 1})
        stream._upload_part(b"first", last=False)
        with pytest.raises(urllib.error.URLError):
            stream._upload_part(b"second", last=False)
        # exactly one APPEND was attempted: a lost-ack resend could
        # duplicate committed bytes, so the policy must not retry it
        assert [op for op, _ in fs.ops] == ["CREATE", "APPEND"]


class TestNoAdhocRetryLoops:
    def test_no_surviving_ad_hoc_sleep_retry_loops(self):
        """Acceptance guard: remote-I/O/service/collective retry loops
        route through RetryPolicy — no hand-rolled time.sleep backoff
        loops survive at the known historical sites."""
        import os
        import re

        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "dmlc_tpu")
        offenders = []
        for sub in ("io", "data", "collective"):
            for dirpath, _dirs, files in os.walk(os.path.join(root, sub)):
                for fname in files:
                    if not fname.endswith(".py"):
                        continue
                    text = open(os.path.join(dirpath, fname)).read()
                    # a sleep with an attempt/retry-scaled argument is the
                    # ad-hoc backoff shape this PR removed
                    for m in re.finditer(
                        r"time\.sleep\([^)\n]*(retry|attempt)", text
                    ):
                        offenders.append((fname, m.group(0)))
        assert offenders == []


class TestRangeReadIntegration:
    def test_injected_read_faults_retried(self):
        from dmlc_tpu.io.filesystem import read_range_with_retry

        payload = b"0123456789abcdef"

        class Resp:
            def __init__(self, body):
                self._b = io.BytesIO(body)
                self.headers = {"Content-Length": str(len(body))}

            def read(self, n):
                return self._b.read(n)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        def open_ranged(start, end):
            return Resp(payload[start:end])

        resilience.configure("io.read:nth=2")
        try:
            out = read_range_with_retry(
                open_ranged, 0, len(payload), "fake", max_retry=5,
                retry_sleep_s=0.0)
        finally:
            resilience.reset()
        assert bytes(out) == payload
        assert resilience.injector is not None
