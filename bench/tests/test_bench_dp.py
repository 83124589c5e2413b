"""The benchmark's data-parallel path at the tiny size on four forced
CPU devices, the exchange's byte count, and the readers of the
exchange's metrics on hand-made traces."""
import gzip
import json
import os
import subprocess
import sys
import textwrap

import pytest

import benchtiny
from harness import compare, exchange, peaks, scopes, wire
from harness import trace as T
from harness.registry import Registry

# sizes of the flat 1-bit exchange of bert-large.s128.onebit.dp4
LARGE_D, BLOCK = 364_576_768, 4096


@pytest.fixture(scope="module")
def dp_runs(tmp_path_factory):
    """The tiny cell on four chips: once as the program is, once with no
    exchange planted in the timed program (each worker keeps its own
    momentum)."""
    tmp = tmp_path_factory.mktemp("dp")
    code = textwrap.dedent(f"""
        import json, sys
        from pathlib import Path
        sys.path[:0] = [{str(benchtiny.BENCH)!r},
                        {str(benchtiny.BENCH / "tests")!r}]
        import benchtiny
        from repro.core import comm

        def run(name):
            (Path({str(tmp)!r}) / name).mkdir()
            r = benchtiny.run_tiny(Path({str(tmp)!r}) / name, chips=4)
            print("RESULT", name, json.dumps(r), flush=True)

        run("program")

        def no_exchange(x, errs, *args, **kw):
            return comm._concat(x) if isinstance(x, tuple) else x, errs
        comm.compressed_exchange = no_exchange
        run("no_exchange")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            _, name, body = line.split(" ", 2)
            out[name] = json.loads(body)
    return out


def test_tiny_cell_on_four_chips_matches_the_reference(dp_runs):
    r = dp_runs["program"]
    assert r["device"]["count"] == 4
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 2


def test_no_exchange_fails_the_judgement(dp_runs):
    r = dp_runs["no_exchange"]
    read = {k: {"value": c["value"]} for k, c in r["checks"].items()
            if k in benchtiny.LIMITS}
    ok, _ = compare.judge(read, benchtiny.LIMITS)
    assert ok is False and r["correct"] is False
    assert r["checks"]["grad"]["value"] > benchtiny.LIMITS["grad"]


@pytest.mark.parametrize("d,workers,hand", [
    # 49,152 elements over 4: the payload of the whole vector is 6,144
    # sign bytes and 12 scales of 4 bytes; 3/4 of it in the all-to-all,
    # 3 copies of a quarter of it in the all-gather
    (49_152, 4, 0.75 * (6_144 + 48) + 3 * (1_536 + 12)),
    (LARGE_D, 4, 2 * 0.75 * (LARGE_D / 8 + 4 * LARGE_D / BLOCK)),
    (364_564_480, 1, 0.0),
])
def test_wire_bytes_hand_count(d, workers, hand):
    assert wire.onebit_send_bytes(d, BLOCK, workers) == hand


@pytest.mark.parametrize("d,workers", [(49_152, 4), (LARGE_D, 4),
                                       (8 * BLOCK, 8)])
def test_wire_bytes_are_the_plans(d, workers):
    """The same count as the plan the program executes: its declared
    send bytes and the bandwidth term of ``plan/cost.py``'s prices."""
    from repro.optim import get_compressor
    from repro.plan.cost import op_coeffs_kind
    from repro.plan.schedules import flat_schedule
    plan = flat_schedule(get_compressor("onebit", block_size=BLOCK), d,
                         workers, ("data",))
    got = wire.onebit_send_bytes(d, BLOCK, workers)
    assert plan.wire_send_bytes() == pytest.approx(got, rel=1e-12)
    priced = sum(op_coeffs_kind(op.kind, op.n, op.payload_bytes)[2]
                 for op in plan.ops)
    assert priced == pytest.approx(got, rel=1e-12)


def test_wire_bytes_need_whole_blocks():
    with pytest.raises(ValueError):
        wire.onebit_send_bytes(3 * BLOCK, BLOCK, 4)


def test_layout_of_the_cells():
    assert exchange.layout("bert-large.s128.onebit.dp4") == (LARGE_D, BLOCK,
                                                             4)
    assert exchange.layout("bert-large.s128.onebit") == (364_564_480,
                                                         BLOCK, 1)
    assert exchange.layout("absent.cell") is None


def test_each_config_and_traffic_names_one_cell():
    cells = Registry().spec["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))


def test_dp4_traffic_is_the_one_chip_mix():
    reg = Registry()
    one, dp4 = (reg.traffic(reg.cell(c)["traffic"]) for c in
                ("bert-large.s128.onebit", "bert-large.s128.onebit.dp4"))
    assert {k: v for k, v in one.items() if k != "why"} == \
        {k: v for k, v in dp4.items() if k != "why"}


def synthetic(chips=4):
    """One step of an exchange on ``chips`` chips: the worker's compress,
    an all-to-all, the server's average and compress, an all-gather that
    runs asynchronously beside a norm, the decompress, and the metrics'
    all-reduce."""
    ex = "jit(step)/obs::exchange::onebit"
    names = {
        "fusion.1": f"{ex}/sign",
        "all-to-all.2": f"{ex}/all_to_all",
        "fusion.3": f"{ex}/obs::exchange::server/reduce_mean",
        "fusion.4": f"{ex}/obs::exchange::server/sign",
        "all-gather-start.5": f"{ex}/all_gather",
        "all-gather-done.5": f"{ex}/all_gather",
        "fusion.6": "jit(step)/obs::optimizer::stats/sqrt",
        "fusion.7": f"{ex}/mul",
        "all-reduce.8": "jit(step)/obs::exchange::metrics/pmean",
    }
    ev = {n: f"%{n} = f32[4]{{0}} {n.split('.')[0]}(%a)" for n in names}
    ops = [(0, 10, ev["fusion.1"]), (10, 30, ev["all-to-all.2"]),
           (30, 40, ev["fusion.3"]), (40, 45, ev["fusion.4"]),
           (45, 46, ev["all-gather-start.5"]), (46, 60, ev["fusion.6"]),
           (60, 62, ev["all-gather-done.5"]), (62, 70, ev["fusion.7"]),
           (70, 74, ev["all-reduce.8"])]
    async_ops = [(45, 62, ev["all-gather-start.5"])]
    raw = T.Raw(ops={c: list(ops) for c in range(chips)},
                async_ops={c: list(async_ops) for c in range(chips)},
                host=[(0, 80, "bench.window")])
    return raw, names


def reading(raw, names, steps=1):
    return T.Reading(
        trace=T.reduce(raw, names, *T.window(raw)), steps=steps,
        chips=len(raw.ops), flops_per_step=1.0, optimizer_least_bytes=1.0,
        peak=peaks.peak("TPU v5 lite"), memory_peak_bytes=1)


def read_kept(tmp_path, monkeypatch, raw, names, cell):
    tdir = tmp_path / "trace"
    tdir.mkdir(exist_ok=True)
    with gzip.open(tdir / f"{cell}.json.gz", "wt") as f:
        json.dump({"raw": raw.to_json(), "op_names": names}, f)
    monkeypatch.setattr(scopes, "TRACE_DIR", tdir)
    reg = Registry()
    r = reading(raw, names)
    return {m: reg.reader(m).read(r) for m in (
        "exchange.server_ms", "exchange.collective_ms",
        "exchange.collective_roofline", "exchange.compress_ms")}


def test_collective_time_spans_both_lines():
    raw, names = synthetic()
    # the all-to-all 20 ns, the all-gather from its start to its done
    # (17 ns, a norm ran meanwhile), the metrics' all-reduce 4 ns
    assert exchange.collective_s(raw, names, *T.window(raw)) == \
        pytest.approx(41e-9)
    # a collective under another scope is not the exchange's; one the
    # compiler made, with no source name, is
    other = dict(names, **{"all-reduce.8": "jit(step)/obs::model::mlp/psum"})
    assert exchange.collective_s(raw, other, *T.window(raw)) == \
        pytest.approx(37e-9)
    unnamed = {k: v for k, v in names.items() if k != "all-reduce.8"}
    assert exchange.collective_s(raw, unnamed, *T.window(raw)) == \
        pytest.approx(41e-9)


def test_readers_on_a_kept_dp4_trace(tmp_path, monkeypatch):
    raw, names = synthetic()
    got = read_kept(tmp_path, monkeypatch, raw, names,
                    "bert-large.s128.onebit.dp4")
    assert got["exchange.server_ms"] == pytest.approx(15e-6)
    assert got["exchange.collective_ms"] == pytest.approx(41e-6)
    least_s = 8 * wire.onebit_send_bytes(LARGE_D, BLOCK, 4) / 1.6e12
    assert got["exchange.collective_roofline"] == pytest.approx(
        100 * least_s / 41e-9)
    # the server's part stays inside what the codec's metric reads
    assert got["exchange.compress_ms"] == pytest.approx(
        (10 + 10 + 5 + 8) * 1e-6)


def test_readers_without_collectives_or_server(tmp_path, monkeypatch):
    """One chip runs no collective, and a program from before the server
    scope names none: those readings are not made."""
    raw, names = synthetic(chips=1)
    raw = T.Raw({0: [e for e in raw.ops[0] if not T.is_collective(e[2])]},
                {}, raw.host)
    names = {k: v.replace("/obs::exchange::server", "")
             for k, v in names.items()}
    got = read_kept(tmp_path, monkeypatch, raw, names,
                    "bert-large.s128.onebit")
    assert got["exchange.server_ms"] is None
    assert got["exchange.collective_ms"] is None
    assert got["exchange.collective_roofline"] is None
    assert got["exchange.compress_ms"] > 0
