"""The benchmark is data: cells, configurations, traffic, limits and
per-layer metrics are found by name, and each matches what it names."""
import json
import re

import pytest

import benchtiny
from harness import reference
from harness.registry import Registry

SPEC = json.loads((benchtiny.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_pieces_found_by_name(cell):
    reg = Registry()
    w = reg.cell(cell)
    c, t = reg.config(w["config"]), reg.traffic(w["traffic"])
    assert {"grad", "change"} <= set(reg.limits(cell)) <= {"loss", "grad",
                                                           "change"}
    assert c["name"] == w["config"] and c["reduced"] == []
    entry = next(x for x in SPEC["configs"] if x["name"] == w["config"])
    assert entry["file"] == f"bench/configs/{w['config']}.json"
    assert entry["source"] == c["source"]
    assert t["batch_per_chip"] * t["seq"] * w["chips"] >= c["vocab_size"] / \
        t["cover_steps"]
    assert {m["name"] for m in reg.metrics(cell, "end_to_end")} >= {
        "setup_s", "tokens_per_s"}
    assert reg.metrics(cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_matches_its_entry(metric):
    m = next(x for x in SPEC["per_layer"] if x["name"] == metric)
    mod = Registry().reader(metric)
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"],
                                                m["moves"])
    assert callable(mod.read)


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_config_is_the_registry_architecture(name):
    import run
    from repro.configs import get_config
    c = Registry().config(name)
    run.check_config(get_config(c["registry"]), c)
    m = reference.Model.from_config(c)
    assert reference.leaf_sizes(m) and reference.flat_length(m, 4, 4096) \
        % (4 * 4096) == 0


def test_new_pieces_are_new_files(tmp_path):
    reg = benchtiny.registry(tmp_path)
    (reg.bench / "metrics" / "extra.count.py").write_text(
        'UNIT, LAYER, MOVES = "n", "device", "tokens_per_s"\n'
        "def read(r):\n    return 7\n")
    assert reg.reader("extra.count").read(None) == 7
    with pytest.raises(KeyError):
        reg.traffic("absent")
    assert reg.cell(benchtiny.CELL)["traffic"] == "s32"
