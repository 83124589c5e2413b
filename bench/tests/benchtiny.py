"""A tiny benchmark tree for the CPU tests: one cell of the program's
``bert-base-smoke`` (2 layers, d_model 256, GQA 4/2 heads, vocab 512,
float32) at sequence 32."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.s32"

CONFIG = {"name": "tiny", "registry": "bert-base-smoke",
          "source": "https://huggingface.co/google-bert/bert-base-uncased",
          "num_hidden_layers": 2, "hidden_size": 256,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "intermediate_size": 512, "vocab_size": 512, "reduced": [],
          "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
          "compute_dtype": "float32"}

TRAFFIC = {"seq": 32, "batch_per_chip": 8, "recipe": "onebit_adam",
           "block_size": 512, "warmup_steps": 16, "cover_steps": 4,
           "warmup_lr": 1e-4, "lr": 2e-5, "followed_steps": 3,
           "trace_steps": 2, "reference_rows": 4}

# float32 on the CPU: the program and the reference agree to rounding
# (seed 5: loss 3.7e-7, grad 1.7e-6, change 1.4e-5); the bfloat16 control
# reads loss 8.8e-5, grad 6.0e-4 (seed 6)
LIMITS = {"loss": 1e-5, "grad": 1e-4, "change": 1e-3}


def make_tree(tmp: Path, chips: int = 1) -> Path:
    """``tmp`` laid out as a checkout holding only the tiny cell."""
    bench = tmp / "bench"
    for kind in ("configs", "traffic", "limits"):
        (bench / kind).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": CONFIG["source"],
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU test"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "s32",
                          "chips": chips, "why": "CPU test"}]
    for m in spec["per_layer"]:
        m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "s32.json").write_text(json.dumps(TRAFFIC))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    return tmp


def registry(tmp: Path, chips: int = 1):
    from harness.registry import Registry
    root = make_tree(tmp, chips)
    return Registry(root=root, bench=root / "bench")


def run_tiny(tmp: Path, seed: int = 3, chips: int = 1) -> dict:
    import run
    return run.run_cell(CELL, seed, 1.0, False, reg=registry(tmp, chips),
                        require_tpu=False, cache=False)
