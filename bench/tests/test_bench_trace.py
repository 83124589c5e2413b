"""The trace reduction, on one step recorded on a TPU v5e and on small
hand-made traces."""
import gzip
import json
from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts bench/ on sys.path)
from harness import intervals as I
from harness import trace as T

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "bert-base.s512.step.json.gz", "rt") as f:
        d = json.load(f)
    return T.Raw.from_json(d["raw"]), d["op_names"], d["window"]


def test_recorded_step_split(recorded):
    raw, names, (lo, hi) = recorded
    r = T.reduce(raw, names, lo, hi)
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.184100841)
    # the step program runs back to back: under a tenth of a percent idle
    assert 0 < 1 - r.busy_s / r.window_s < 1e-3
    # self times tile the busy time: nested while events are not counted
    # twice
    assert sum(r.class_s.values()) == pytest.approx(r.busy_s, rel=1e-6)
    assert r.class_s["model"] == pytest.approx(0.146884345, rel=1e-6)
    assert r.class_s["optimizer"] == pytest.approx(0.02807416, rel=1e-6)
    assert r.class_s["other"] == pytest.approx(0.009129435, rel=1e-6)
    assert r.class_s["collective"] == 0 and r.collective_s == 0
    assert r.exposed_s == 0
    top_name, top_s = r.top_ops[0]
    assert "dot_general" in top_name and top_s > 0.005
    assert len(r.top_ops) == 10 and len(r.idle_gaps) <= 10


def test_opcode_and_classes():
    names = {"fusion.1": "jit(step)/transpose(jvp())/while/body/dot_general",
             "fusion.2": "jit(step)/sub",
             "all_to_all.7": "jit(step)/shard_map/all_to_all"}
    ev = {"fusion.1": "%fusion.1 = (f32[2]{0}, bf16[3,4]{1,0}) fusion("
                      "%a), kind=kLoop",
          "fusion.2": "%fusion.2 = f32[8]{0:T(1024)} fusion(%b)",
          "all_to_all.7": "%all_to_all.7 = u8[4,8,128]{2,1,0} "
                          "all-to-all(%c), replica_groups={{0,1,2,3}}",
          "copy.3": "%copy.3 = f32[8]{0} copy(%d)"}
    assert T.opcode(ev["fusion.1"]) == "fusion"
    assert T.opcode(ev["all_to_all.7"]) == "all-to-all"
    assert T.opcode("%all-gather-start.2 = (u8[4]{0}, u8[16]{0}) "
                    "all-gather-start(%x)") == "all-gather-start"
    assert T.is_collective("%all-gather-done.2 = u8[16]{0} "
                           "all-gather-done(%y)")
    assert T.classify(ev["fusion.1"], names) == "model"
    assert T.classify(ev["fusion.2"], names) == "optimizer"
    assert T.classify(ev["all_to_all.7"], names) == "collective"
    assert T.classify(ev["copy.3"], names) == "other"
    hlo = ('  %fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, '
           'metadata={op_name="jit(step)/jvp()/mul" stack_frame_id=3}\n'
           '  ROOT %t = (f32[2]{0}) tuple(%fusion.1)\n')
    assert T.op_names(hlo) == {"fusion.1": "jit(step)/jvp()/mul"}


def test_self_times_nested():
    evs = [(0, 100, "%while.1 = () while(%a)"), (10, 30, "%f.1 = T fusion("),
           (40, 90, "%f.2 = T fusion("), (50, 60, "%f.3 = T fusion(")]
    st = {e[2]: t for e, t in T.self_times(evs)}
    assert st["%while.1 = () while(%a)"] == 30
    assert st["%f.2 = T fusion("] == 40
    assert st["%f.3 = T fusion("] == 10


def test_exposed_collective_and_idle_gaps():
    coll = "%all_to_all.1 = u8[4]{0} all-to-all(%p)"
    comp = "%fusion.1 = f32[4]{0} fusion(%q)"
    names = {"fusion.1": "jit(step)/jvp()/dot_general"}
    raw = T.Raw(
        ops={0: [(0, 40, comp), (50, 80, coll), (90, 100, comp)],
             1: [(0, 30, comp), (30, 60, coll), (60, 100, comp)]},
        async_ops={0: [(20, 45, "%all-gather-start.1 = (u8[1]{0}, u8[4]{0})"
                                 " all-gather-start(%r)")]},
        host=[(0, 100, "bench.window"), (40, 52, "bench.wait"),
              (80, 95, "bench.batch")])
    r = T.reduce(raw, names, *T.window(raw))
    assert r.window_s == pytest.approx(100e-9)
    # chip 0 busy 40 + 30 + 10, chip 1 busy 100
    assert r.busy_s == pytest.approx(90e-9)
    # chip 0: async all-gather 20-45 and all-to-all 50-80 -> 55 ns, of
    # which 40-45 and 50-80 run alone (35 ns); chip 1: 30 ns, all alone
    assert r.collective_s == pytest.approx(42.5e-9)
    assert r.exposed_s == pytest.approx(32.5e-9)
    assert r.class_s["collective"] == pytest.approx(30e-9)
    assert r.class_s["model"] == pytest.approx(60e-9)
    # chip 0 idles 40-50 (the host waits) and 80-90 (it makes a batch)
    assert sorted(r.idle_gaps) == [("bench.batch", 10e-9),
                                   ("bench.wait", 10e-9)]


def test_interval_algebra():
    a = I.merge_spans([(0, 5), (3, 8), (10, 12)])
    assert a == [(0, 8), (10, 12)]
    assert I.subtract_spans(a, [(2, 4), (11, 20)]) == [(0, 2), (4, 8),
                                                      (10, 11)]
    assert I.gaps(a, -1, 13) == [(-1, 0), (8, 10), (12, 13)]
    assert I.span_length(I.clip_spans(a, 4, 11)) == 5
