"""chip_smoke.py's no-fallback rule, pinned on the CPU.

The script itself only runs on the chip (through the chip tool); what the
CPU suite can and must hold is that it REFUSES to run here: preflight
exits non-zero, nothing is built or trained, and no result line is
printed — there is no CPU continuation to regress into.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_preflight_exits_nonzero_off_the_chip(capsys):
    sys.path.insert(0, REPO)
    import chip_smoke

    with pytest.raises(SystemExit) as exc:
        chip_smoke.preflight()
    assert exc.value.code not in (0, None)
    err = capsys.readouterr().err
    assert "no TPU" in err and "nothing was run" in err


def test_result_line_has_exactly_the_contract_keys():
    sys.path.insert(0, REPO)
    import chip_smoke

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_script_prints_no_result_off_the_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no phase ran, no JSON line
    assert "no TPU" in proc.stderr


def test_the_dma_row_writers_check_holds_small_in_the_interpreter():
    """The one piece of the kernels phase that has a CPU form: the FM
    step's DMA row writer against XLA's scatter, here a few dozen rows in
    Pallas' interpreter; Mosaic's compile at the cells' sizes is the
    chip's (a TPU's compiler does not belong in the CPU suite)."""
    sys.path.insert(0, REPO)
    import chip_smoke

    facts = chip_smoke.dma_row_writer(
        interpret=True, chunk=32, passes=3, height=131)
    assert list(facts) == ["dma_row_writer"]
    assert "96 slots" in facts["dma_row_writer"]


def test_the_dlrm_steps_check_holds_at_the_rehearse_size():
    """``chip_smoke.dlrm_step`` on the CPU at the ``criteo-dlrm``
    configuration's rehearse size (small tables, the published MLPs,
    batches of 1024): the same comparison with the float64 reference the
    chip makes at 33.8 M ids, under the cell's own limits."""
    sys.path.insert(0, REPO)
    import chip_smoke

    facts = chip_smoke.dlrm_step(steps=2, rehearse=True)
    assert list(facts) == ["dlrm_step"]
    assert "2 steps of 1024 rows" in facts["dlrm_step"]
    assert "row writer scatter" in facts["dlrm_step"]
