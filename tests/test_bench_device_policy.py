"""bench.py's device policy, pinned: no CPU continuation, no fake chip.

Off the TPU the device tiers do not run and the record says so
(``device_tiers: "not measured"``, no device-tier key at all); on a TPU a
device tier that raises leaves its ``*_error`` key, the line still
prints, and the process exits non-zero. Every record names the device.
Both cases drive the real ``bench.main()`` at a tiny size.
"""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# keys only a device tier writes (bench.py / bench_collective.py)
DEVICE_KEYS = (
    "feed_dense_mbps", "sgd_e2e_mbps", "sgd_e2e_cached_mbps",
    "sgd_csr_e2e_mbps", "recordio_sgd_mbps", "sgd_e2e_shard_mbps",
    "criteo_like_csr_sgd_mbps", "gbdt_fit_mrows_s", "sgd_goodput_ratio",
    "sgd_mfu", "ckpt_overhead_ratio", "psum_step_ms", "spmd_step_ms",
    "bucket_fused_ms", "engine_reduce_single_process_gbps", "parity",
)


@pytest.fixture
def small_bench(tmp_path, monkeypatch):
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.setattr(bench, "ROWS", 4000)
    monkeypatch.setattr(bench, "CRITEO_ROWS", 1000)
    monkeypatch.setattr(bench, "TRIALS", 1)
    monkeypatch.setattr(bench, "HEADLINE_TRIALS", 1)
    monkeypatch.setattr(bench, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "DATA_PATH",
                        str(tmp_path / "higgs_like_small.svm"))
    monkeypatch.setenv("DMLC_TPU_BENCH_DETAIL", str(tmp_path / "detail.json"))
    return bench


def _last_json_line(text):
    lines = [ln for ln in text.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, text
    return json.loads(lines[0])


def test_off_the_chip_device_tiers_are_not_measured(small_bench, capsys):
    small_bench.main()  # exit code 0: nothing failed, nothing was faked
    extra = _last_json_line(capsys.readouterr().out)["extra"]
    assert extra["platform"] == "cpu" and extra["device_count"] >= 1
    assert "device_kind" in extra
    assert extra["device_tiers"] == "not measured"
    assert not [k for k in DEVICE_KEYS if k in extra]
    assert not [k for k in extra if k.endswith("_error")]
    detail = json.loads(open(os.environ["DMLC_TPU_BENCH_DETAIL"]).read())
    assert not [k for k in DEVICE_KEYS if k in detail["extra"]]
    assert "harvest" not in detail["extra"]


def test_on_the_chip_a_failing_device_tier_fails_the_run(
        small_bench, monkeypatch, capsys):
    """A stand-in TPU (jax.devices() reports platform 'tpu'): the tiers
    are stubbed, one raises — the record carries its error, names the
    device, and the process exits non-zero AFTER printing its line."""
    import jax

    import bench_collective

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    for name in ("_bench_device_feed", "_bench_recordio_sgd",
                 "_bench_shard_sgd", "_bench_criteo_sgd", "_bench_multijob",
                 "_bench_snapshot"):
        monkeypatch.setattr(small_bench, name,
                            lambda *a, _n=name, **k: {_n + "_ran": 1})
    monkeypatch.setattr(small_bench, "_bench_gbdt", boom)
    from dmlc_tpu.tools import parity

    monkeypatch.setattr(parity, "run_parity", lambda **k: dict.fromkeys(
        ("single_backend", "bitexact", "max_grad_ulp", "max_loss_rel",
         "max_param_abs_diff", "criterion", "pass"), 0))
    monkeypatch.setattr(
        bench_collective, "collective_metrics",
        lambda device_tiers=True: {"psum_devices": 1} if device_tiers
        else {})
    with pytest.raises(SystemExit) as exc:
        small_bench.main()
    assert exc.value.code not in (0, None)
    assert "gbdt_error" in str(exc.value.code)
    extra = _last_json_line(capsys.readouterr().out)["extra"]
    assert extra["gbdt_error"] == "mosaic said no"
    assert extra["platform"] == "tpu"
    assert extra["device_kind"] == "TPU v5 lite"
    assert "device_tiers" not in extra  # they ran; one failed
