"""Operation and byte counts against hand counts, and the peak table."""
import json

import pytest

import benchtiny
from harness import costs, peaks

CONFIGS = benchtiny.BENCH / "configs"
# the paper's other configuration, at its published widths
BERT_LARGE = {"num_hidden_layers": 24, "hidden_size": 1024,
              "num_attention_heads": 16, "intermediate_size": 4096,
              "vocab_size": 30522}


def config(name):
    if name == "bert-large":
        return BERT_LARGE
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seq,tokens,flops", [
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 30522 = 333,244,416
    # matrix parameters; 6 x that + 12 x 24 x 128 x 1024 per token, on
    # 32 x 128 tokens a chip
    ("bert-large", 128, 4096, 4096 * (6 * 333_244_416 + 37_748_736)),
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 30522 = 108,375,552;
    # attention 12 x 12 x 512 x 768 per token, on 32 x 512 tokens
    ("bert-base", 512, 16384, 16384 * (6 * 108_375_552 + 56_623_104)),
])
def test_model_flops(name, seq, tokens, flops):
    assert costs.model_flops_per_token(config(name), seq) * tokens == flops


@pytest.mark.parametrize("name,params", [
    ("bert-large", 333_244_416 + 30522 * 1024 + 49 * 1024),
    ("bert-base", 108_375_552 + 30522 * 768 + 25 * 768),
])
def test_param_count(name, params):
    assert costs.param_count(config(name)) == params


def test_least_bytes():
    d, block = 1_000_000, 4096
    pay = d / 8 + 4 * d / block
    # one worker: 32 B of state a parameter, the worker payload written
    # and read, the received (= own) payload read, the server error read
    # and written over the whole vector, its payload written and read
    one = 32 * d + 2 * pay + pay + 8 * d + pay
    assert costs.onebit_adam_least_bytes(d, 1, block) == pytest.approx(one)
    four = 32 * d + 3 * pay + 8 * d / 4 + (d / 4 / 8 + 4 * d / 4 / block)
    assert costs.onebit_adam_least_bytes(d, 4, block) == pytest.approx(four)


def test_peaks_by_device_kind():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
