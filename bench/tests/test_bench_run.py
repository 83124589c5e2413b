"""One run end to end at the tiny size on the CPU, the result line's
contract, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import benchtiny
from harness.traffic import Stream

ROOT = benchtiny.ROOT


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "bert-base.s512.onebit", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_tiny_run_is_correct_and_well_formed(tmp_path):
    r = benchtiny.run_tiny(tmp_path)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert r["device"]["count"] == 1
    for k in ("loss", "grad", "change"):
        c = r["checks"][k]
        assert 0 <= c["value"] <= c["limit"]
    json.dumps(r)


def test_traffic_is_drawn_from_the_seed():
    big = 2**31 + 12345
    a = Stream(512, 8, 32, 2, big, cover_steps=4)
    b = Stream(512, 8, 32, 2, big, cover_steps=4)
    c = Stream(512, 8, 32, 2, big + 1, cover_steps=4)
    for k in range(3):
        for x, y in zip(a.batch(k).values(), b.batch(k).values()):
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.batch(0)["tokens"], c.batch(0)["tokens"])
    assert not np.array_equal(a.batch(0)["tokens"], a.batch(1)["tokens"])
    seen = np.concatenate([a.warmup_batch(k)["tokens"].ravel()
                           for k in range(4)])
    assert set(seen.tolist()) == set(range(512))
    w = a.warmup_batch(0)
    assert w["tokens"].dtype == np.int32 and w["loss_mask"].dtype == \
        np.float32
    # a masked position is fed the mask id and predicts its own token
    m = a.batch(0)["loss_mask"] > 0
    assert np.all(a.batch(0)["tokens"][m] == 511)


def test_cover_must_fit():
    with pytest.raises(ValueError):
        Stream(512, 2, 16, 1, 0, cover_steps=4).warmup_batch(0)
