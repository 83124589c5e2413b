"""The comparison that decides ``correct`` catches a broken timed path:
faults planted in the program's compressed step, the one the window
times, and the control (the reference in a lower precision) in its
place, at the tiny size on the CPU; and the limits of the benchmark's
cells separate the readings they were set from."""
import json

import pytest

import benchtiny
import run
from harness import compare


def _wrap(monkeypatch, wrapper):
    """Route the timed (compressed) step program through ``wrapper``."""
    build = run.Cell.build

    def patched(self):
        build(self)
        self.step = wrapper(self.step)
    monkeypatch.setattr(run.Cell, "build", patched)


def _failed(r):
    return [k for k, c in r["checks"].items() if c["value"] > c["limit"]]


def test_step_that_returns_its_state_unchanged(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    def unchanged(fn):
        def step(params, opt, batch, lr):
            copy = jax.tree.map(jnp.copy, (params, opt))
            _, _, met = fn(*copy, batch, lr)
            return params, opt, met
        step.build = fn.build
        return step
    _wrap(monkeypatch, unchanged)
    r = benchtiny.run_tiny(tmp_path)
    assert r["correct"] is False
    assert r["checks"]["change"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(tmp_path, monkeypatch):
    def half(fn):
        def step(params, opt, batch, lr):
            m = batch["loss_mask"]
            return fn(params, opt, dict(batch, loss_mask=m.at[
                m.shape[0] // 2:].set(0.0)), lr)
        step.build = fn.build
        return step
    _wrap(monkeypatch, half)
    r = benchtiny.run_tiny(tmp_path)
    assert r["correct"] is False
    assert "grad" in _failed(r)


def test_control_and_fault_fail_and_program_passes(tmp_path):
    import control
    reg = benchtiny.registry(tmp_path)
    out = control.main(["--workload", benchtiny.CELL, "--program-seeds",
                        "5", "--control-seeds", "6", "--faults",
                        "half_batch"], reg=reg, require_tpu=False)
    limits = benchtiny.LIMITS
    kinds = {r["kind"] for r in out}
    assert kinds == {"program", "control:bfloat16", "fault:half_batch"}
    for r in out:
        read = {k: {"value": r[k]} for k in limits}
        ok, _ = compare.judge(read, limits)
        assert ok is (r["kind"] == "program"), r


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (benchtiny.ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_limits_separate_their_readings(cell):
    """Each reading a limit was set from: the program's largest passes,
    and the control's and each fault's smallest fail through the same
    judgement a run makes."""
    from harness.registry import Registry
    spec = json.loads((Registry().bench / "limits" / f"{cell}.json")
                      .read_text())
    limits = spec["limits"]
    numbers = {k: spec["readings"][k] for k in limits}
    uppers = {u for v in numbers.values() for u, x in v.items()
              if u != "program_max" and isinstance(x, (int, float))}
    assert uppers
    def value(k, upper):
        x = numbers[k].get(upper)
        return x if isinstance(x, (int, float)) else 0.0
    for upper in sorted(uppers):
        read = {k: {"value": value(k, upper)} for k in limits}
        assert compare.judge(read, limits)[0] is False, upper
    prog = {k: {"value": numbers[k]["program_max"]} for k in limits}
    assert compare.judge(prog, limits)[0] is True
