"""The control against the limits, for every cell that keeps readings.

At a size a CPU test run can hold (fixtures/<cell>.cpu.json), the reference
computed one precision below the configured bfloat16 (float8 e4m3) in the
program's place, and the half-batch fault, must read well above the sound
program on at least one compared number. At the cell's own size, the
readings bench/calibrate.py took on the chip
(fixtures/<cell>.readings.json) go through the same comparison and the
cell's limits file as a run's do: the program's must be correct on every
seed, the control's and the fault's not correct on any."""
import pytest

import calibrate
import cells
import run
from kinds.train import compare


@pytest.mark.parametrize("cell", cells.cpu_cells("train"))
def test_train_control_and_fault_separate(cell):
    run.setup_jax()
    rows, _ = calibrate.calibrate_train(cells.cpu_spec(cell), [5], out=lambda s: None)
    got = {r["reading"]: r for r in rows}
    prog = got["program"]
    keys = ("loss_gap", "grad_norm_gap", "change_norm_gap")
    for name in ("control_fp8", "fault_half_batch"):
        assert any(got[name][k] >= 3 * prog[k] for k in keys), (name, got)


@pytest.mark.parametrize("reading,correct", [
    ("program", True), ("control_fp8", False), ("fault_half_batch", False)])
@pytest.mark.parametrize("cell", cells.reading_cells())
def test_chip_readings_against_the_cell_limits(cell, reading, correct):
    saved = cells.readings(cell)
    assert saved["workload"] == cell
    limits = run.load_spec(cell).limits
    assert len(saved["readings"]) >= 12
    for seed, r in saved["readings"].items():
        nums = compare(r[reading], r["reference"])
        checks = {k: (v, limits[k]) for k, v in nums.items()}
        assert run.judge(checks) is correct, (seed, checks)
