"""Device time by the program's layer scopes (``harness.scopes``) and
the readers of the metrics built on it: on small hand-made traces, on
one step of the program from before the scopes, and on a traced run of
the scoped program recorded on a TPU v5e."""
import gzip
import json
import math
from pathlib import Path

import pytest

import benchtiny
from harness import costs, peaks, scopes as S
from harness import trace as T
from harness.registry import Registry

DATA = Path(__file__).parent / "data"
CONFIG = json.loads((benchtiny.BENCH / "configs" / "bert-base.json")
                    .read_text())
NEW = ("model.attention_ms", "model.recompute_ms", "optimizer.flat_ms",
       "optimizer.stats_ms", "exchange.compress_ms")
# what the six metrics read on the recorded step of the program from
# before the scopes, through the reduction as it was then
BEFORE = {"device.idle_share": 0.0070075725509433084,
          "device.peak_hbm_gib": 3.525527000427246,
          "step.mfu": 31.933125877568855,
          "model.fwd_bwd_ms": 146.884345,
          "optimizer.update_ms": 28.07416,
          "optimizer.update_roofline": 23.22411080381755}


def load(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def reading(raw, names, span, steps):
    return T.Reading(
        trace=T.reduce(raw, names, *span), steps=steps, chips=len(raw.ops),
        flops_per_step=costs.model_flops_per_token(CONFIG, 512) * 16384,
        optimizer_least_bytes=costs.onebit_adam_least_bytes(
            costs.param_count(CONFIG), 1, 4096),
        peak=peaks.peak("TPU v5 lite"), memory_peak_bytes=3785505792)


def read_all(r, trace_dir, monkeypatch):
    monkeypatch.setattr(S, "TRACE_DIR", trace_dir)
    reg = Registry()
    return {m["name"]: reg.reader(m["name"]).read(r)
            for m in reg.spec["per_layer"]}


def keep(tmp_path, d, cell="bert-base.s512.onebit"):
    """The reduction input as ``bench/run.py`` keeps it."""
    tdir = tmp_path / "trace"
    tdir.mkdir(exist_ok=True)
    with gzip.open(tdir / f"{cell}.json.gz", "wt") as f:
        json.dump({"raw": d["raw"], "op_names": d["op_names"]}, f)
    return tdir


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp()/while/body/closed_call/obs::model::attention/"
     "bqhd,bkhd->bhqk/dot_general", ("obs::model::attention", False)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/obs::model::mlp/tanh", ("obs::model::mlp", True)),
    ("jit(step)/transpose(jvp(obs::model::head))/bsd,dv->bsv/dot_general",
     ("obs::model::head", False)),
    ("jit(step)/obs::exchange::onebit/obs::flat/onebit::s1::AllToAll~intra"
     "/all_to_all", ("obs::exchange::onebit", False)),
    ("jit(step)/obs::optimizer::stats/jit(norm)/sqrt",
     ("obs::optimizer::stats", False)),
    ("jit(step)/jvp()/while/body/dynamic_slice", None),
    ("jit(step)/obs::model::attention::x/sin", None),
    ("jit(step)/obs::trainer::loop/sin", None),
    ("", None),
])
def test_scope_of(op_name, want):
    assert S.scope_of(op_name) == want


def synthetic():
    names = {
        "fusion.1": "jit(step)/jvp()/while/body/closed_call/"
                    "obs::model::attention/dot_general",
        "fusion.2": "jit(step)/transpose(jvp())/while/body/closed_call/"
                    "checkpoint/rematted_computation/obs::model::attention/"
                    "exp",
        "fusion.3": "jit(step)/transpose(jvp())/while/body/closed_call/"
                    "checkpoint/rematted_computation/add",
        "fusion.4": "jit(step)/obs::optimizer::flatten/concatenate",
        "all-to-all.5": "jit(step)/obs::exchange::onebit/"
                        "obs::flat/onebit::s0::AllToAll~intra/all_to_all",
        "fusion.6": "jit(step)/obs::exchange::onebit/sign",
        "while.7": "jit(step)/jvp()/while",
    }
    ev = {n: f"%{n} = f32[4]{{0}} {n.split('.')[0]}(%a)" for n in names}
    # the loop holds two layer ops (its own time is the rest), then the
    # recomputed ops, the flatten, an unnamed copy, the exchange
    ops = [(0, 100, ev["while.7"]), (10, 40, ev["fusion.1"]),
           (40, 70, ev["fusion.2"]), (100, 110, ev["fusion.3"]),
           (110, 130, ev["fusion.4"]), (130, 140, "%copy.8 = f32[4]{0} "
                                                  "copy(%b)"),
           (140, 150, ev["all-to-all.5"]), (150, 170, ev["fusion.6"])]
    raw = T.Raw(ops={0: ops, 1: [(e[0], e[1], e[2]) for e in ops]},
                async_ops={}, host=[(0, 170, "bench.window")])
    return raw, names


def test_scope_s_self_time_recompute_and_unscoped():
    raw, names = synthetic()
    s = S.scope_s(raw, names, *T.window(raw))
    ns = {k: round(v * 1e9, 6) for k, v in s.items()}
    assert ns == {"obs::model::attention": 60.0,
                  "obs::model::attention:recompute": 30.0,
                  "unscoped": 60.0,            # the loop 40, the copy 10,
                  "unscoped:recompute": 10.0,  # an unscoped recompute 10
                  "obs::optimizer::flatten": 20.0,
                  "obs::exchange::onebit:collective": 10.0,
                  "obs::exchange::onebit": 20.0}
    busy = T.reduce(raw, names, *T.window(raw)).busy_s
    assert sum(v for k, v in s.items()
               if not k.endswith(":recompute")) == pytest.approx(busy)
    assert S.unscoped_share(s) == pytest.approx(60 / 170)


def test_readers_on_a_kept_trace(tmp_path, monkeypatch):
    raw, names = synthetic()
    d = {"raw": raw.to_json(), "op_names": names}
    r = reading(raw, names, T.window(raw), steps=2)
    got = read_all(r, keep(tmp_path, d), monkeypatch)
    # ns over 2 steps, in ms
    assert got["model.attention_ms"] == pytest.approx(60e-6 / 2)
    assert got["model.recompute_ms"] == pytest.approx(40e-6 / 2)
    assert got["optimizer.flat_ms"] == pytest.approx(20e-6 / 2)
    assert got["optimizer.stats_ms"] == 0.0
    assert got["exchange.compress_ms"] == pytest.approx(20e-6 / 2)
    # a kept trace of another window is not this reading's
    other = reading(raw, names, (0, 160), steps=2)
    assert read_all(other, tmp_path / "trace", monkeypatch)[
        "model.attention_ms"] is None


@pytest.mark.parametrize("metric", sorted(BEFORE))
def test_existing_metrics_read_as_before(metric, tmp_path, monkeypatch):
    d = load("bert-base.s512.step.json.gz")
    raw = T.Raw.from_json(d["raw"])
    got = read_all(reading(raw, d["op_names"], d["window"], 1),
                   tmp_path / "none", monkeypatch)
    assert got[metric] == BEFORE[metric]


def test_unscoped_program_gives_no_scope_readings(tmp_path, monkeypatch):
    # the recorded step names no layer: only the recompute reading,
    # which needs no scope, is made
    d = load("bert-base.s512.step.json.gz")
    raw = T.Raw.from_json(d["raw"])
    d["raw"]["host"] = [[*d["window"], "bench.window"]]
    r = reading(raw, d["op_names"], d["window"], 1)
    got = read_all(r, keep(tmp_path, d), monkeypatch)
    assert {k for k in NEW if got[k] is None} == set(NEW) - {
        "model.recompute_ms"}
    assert 0 < got["model.recompute_ms"] < got["model.fwd_bwd_ms"]


@pytest.fixture(scope="module")
def scoped():
    d = load("bert-base.s512.scoped.json.gz")
    raw = T.Raw.from_json(d["raw"])
    return d, raw, T.window(raw)


def test_scoped_run_covers_the_step(scoped):
    d, raw, span = scoped
    s = S.scope_s(raw, d["op_names"], *span)
    red = T.reduce(raw, d["op_names"], *span)
    assert sum(v for k, v in s.items()
               if not k.endswith(":recompute")) == pytest.approx(
        red.busy_s, rel=1e-6)
    assert S.unscoped_share(s) <= 0.10


# what the traced run printed on the chip
ON_CHIP = {"model.attention_ms": 90.62411219999998,
           "model.recompute_ms": 25.3919634,
           "optimizer.flat_ms": 5.604580400000001,
           "optimizer.stats_ms": 7.4776342,
           "exchange.compress_ms": 13.254804000000002}


@pytest.mark.parametrize("metric", NEW)
def test_new_metrics_read_the_scoped_run(metric, scoped, tmp_path,
                                         monkeypatch):
    d, raw, span = scoped
    r = reading(raw, d["op_names"], span, d["steps"])
    got = read_all(r, keep(tmp_path, d), monkeypatch)
    assert got[metric] is not None and math.isfinite(got[metric])
    assert 0 <= got[metric] < 1e3 * r.trace.window_s / r.steps
    assert got[metric] == pytest.approx(ON_CHIP[metric], rel=1e-12)
