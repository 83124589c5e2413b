"""``readings.py`` takes the readings ``control.py`` takes, at the tiny
size on the CPU, from one reference warmup branched into the reference,
the control and the fault."""
import pytest

import benchtiny


def test_readings_read_as_control_does(tmp_path):
    """Each reading is the one ``control.py`` takes, and the run's
    judgement passes."""
    import control
    import readings
    reg = benchtiny.registry(tmp_path)
    ours = readings.main(["--workload", benchtiny.CELL, "--seed", "5",
                          "--control", "--faults", "half_batch"], reg=reg,
                         require_tpu=False)
    theirs = control.main(["--workload", benchtiny.CELL, "--program-seeds",
                           "5", "--control-seeds", "5", "--faults",
                           "half_batch"], reg=reg, require_tpu=False)
    assert ours[0]["kind"] == "run" and ours[0]["correct"] is True
    assert [r["kind"] for r in ours[1:]] == [r["kind"] for r in theirs]
    for a, b in zip(ours[1:], theirs):
        for k in ("loss", "grad", "change"):
            assert a[k] == pytest.approx(b[k], rel=1e-6, abs=1e-12), (a, k)
