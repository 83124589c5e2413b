#!/usr/bin/env python3
"""Runs one cell of the benchmark once and prints its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the TPU chips the
cell asks for.  ``BENCHMARK.json`` names the cell's configuration and
traffic mix (see ``bench/README.md``).

Set-up (``setup_s``, from process start to the first timed dispatch):
the program's own entry, ``repro.launch.train.run``, trains two steps
(one warmup, one compressed) at learning rate 0 to build the step
programs and their state layout, and hands back the jitted programs it
built (``programs=``).  The benchmark then gives that state fresh
weights made on the device from the seed and a zero optimizer state,
and drives it through the traffic's warmup steps (Adam, on batches that
together hold the whole vocabulary) and then through its followed
compressed steps with the window's own program, recording what the
reference will be compared on: their losses, the gradient the first of
them got (read from the optimizer state before and after it), and the
parameters' change over them.

Window (``--trace 0``): the compressed program back to back on fresh
batches for ``--seconds`` seconds, closed loop: after dispatching step
k it blocks on step k-1's loss and records that step's completion.
``tokens_per_s`` is every token of every step dispatched before the
time ran out over the time from the first dispatch to the last
completion; ``step_ms_p90`` is the 90th percentile of the intervals
between successive completions.

Trace (``--trace 1``): the same set-up, then ``trace_steps`` steps of
the same loop under ``jax.profiler``; ``bench/metrics/*.py`` read the
reduced trace (``harness.trace``).

Correctness: once the window has closed and the program's state is
freed, the plain reference (``harness.reference``) repeats the warmup
and followed steps from the same seed, and ``harness.compare`` holds the
followed steps' numbers to the cell's limits
(``bench/limits/<cell>.json``).

A run that finds no TPU, or fewer chips than the cell asks for, exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import compare, costs, peaks, reference, trace  # noqa: E402
from harness.registry import Registry  # noqa: E402
from harness.traffic import Stream  # noqa: E402

CACHE = ROOT / ".bench_cache"
COMPILE_EVENTS = "/jax/core/compile"


class BenchError(Exception):
    """A run that cannot give a result."""


def _seed(seed: int, tag: int) -> int:
    """A 31-bit seed derived from ``--seed`` for ``tag``."""
    return int(np.random.default_rng([seed % 2**64, tag]).integers(2**31))


def find_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU found (platform {devices[0].platform!r});"
                         " nothing is measured elsewhere")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, found "
                         f"{len(devices)}")
    return devices[:chips]


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, whatever the environment says."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_config(arch, c: dict) -> None:
    """The registry's architecture is the one the configuration file
    states, and the one the reference follows."""
    want = {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c.get("num_key_value_heads",
                                c["num_attention_heads"]),
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "norm_eps": c["rms_norm_eps"], "rope_theta": c["rope_theta"],
            "compute_dtype": c["compute_dtype"], "causal": False,
            "mlp_kind": "gelu", "family": "encoder", "window": None}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise BenchError(f"registry {c['registry']!r} differs from its "
                         f"configuration file (program, file): {diff}")


def host_flat(tree) -> np.ndarray:
    import jax
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])


class CompileCounter:
    """Counts JAX compile events while ``on``."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name.startswith(COMPILE_EVENTS):
            self.n += 1


class Cell:
    """The program driven through one cell's set-up, window and trace."""

    def __init__(self, reg: Registry, name: str, seed: int, devices):
        self.name, self.devices = name, devices
        self.spec = reg.cell(name)
        self.c = reg.config(self.spec["config"])
        self.t = reg.traffic(self.spec["traffic"])
        self.chips = self.spec["chips"]
        self.batch = self.t["batch_per_chip"] * self.chips
        self.seq = self.t["seq"]
        self.model = reference.Model.from_config(self.c)
        self.refs = {}
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        import jax
        self.seed = seed
        self.stream = Stream(self.c["vocab_size"], self.batch, self.seq,
                             self.chips, seed, self.t["cover_steps"])
        self.key = jax.random.PRNGKey(_seed(seed, 1))
        self.followed = {}

    # --- set-up --------------------------------------------------------------
    def build(self) -> None:
        """The program's step programs, through its own entry, and a
        state for them with weights from the seed."""
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.train import run as train
        check_config(get_config(self.c["registry"]), self.c)
        programs = {}
        params, opt, _ = train(
            self.c["registry"], 2, self.batch, self.seq, (self.chips, 1),
            base_lr=0.0, warmup_steps=1, block_size=self.t["block_size"],
            recipe=self.t["recipe"], seed=_seed(self.seed, 2),
            log_every=10**9, programs=programs)
        self.warm = programs[("warmup", True)]
        self.step = programs[("compressed", True)]
        shapes = jax.tree.map(lambda a: tuple(a.shape), params)
        want = reference.param_shapes(self.model)
        if shapes != want:
            raise BenchError(f"the program's parameters {shapes} are not "
                             f"the reference's {want}")
        model = self.model
        shards = (jax.tree.map(lambda a: a.sharding, params),
                  jax.tree.map(lambda a: a.sharding, opt))
        zeros = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             opt)

        def fresh(k):
            return (reference.init_params(k, model),
                    jax.tree.map(lambda z: jnp.zeros(z.shape, z.dtype),
                                 zeros))
        self._fresh = jax.jit(fresh, out_shardings=shards)
        for a in jax.tree.leaves((params, opt)):
            a.delete()
        self.reset()

    def reset(self) -> None:
        """Weights from the seed and a zero optimizer state."""
        self.params, self.opt = self._fresh(self.key)

    def put(self, batch):
        import jax
        return {k: jax.device_put(v) for k, v in batch.items()}

    def follow(self) -> None:
        """The warmup steps, then the compressed steps the reference
        follows, through the window's own program: their losses, the
        first compressed step's gradient, and the change."""
        import jax
        import jax.numpy as jnp
        t, warm_losses, losses = self.t, [], []
        lr_w, lr = jnp.float32(t["warmup_lr"]), jnp.float32(t["lr"])
        for k in range(t["warmup_steps"]):
            self.params, self.opt, met = self.warm(
                self.params, self.opt, self.put(self.stream.warmup_batch(k)),
                lr_w)
            warm_losses.append(met["loss"])
        x0 = host_flat(self.params)
        held = jax.jit(partial(held_momentum, workers=self.chips))
        before = held(self.opt)
        for k in range(t["followed_steps"]):
            self.params, self.opt, met = self.step(
                self.params, self.opt, self.put(self.stream.batch(k)), lr)
            losses.append(met["loss"])
            if k == 0:
                self.followed["grad"] = np.asarray(jax.jit(first_grad)(
                    held(self.opt), before))
                for a in jax.tree.leaves(before):
                    a.delete()
        self.followed["change"] = host_flat(self.params) - x0
        self.followed["warmup_losses"] = [float(x) for x in warm_losses]
        self.followed["losses"] = [float(x) for x in losses]
        self.next_batch, self.lr = t["followed_steps"], lr

    # --- the closed loop -----------------------------------------------------
    def loop(self, seconds: float = None, steps: int = None, mark=None):
        """Dispatch compressed steps back to back, blocking on step k-1
        after dispatching step k, until ``seconds`` have passed or
        ``steps`` were dispatched.  Returns (first dispatch time,
        completion times, losses)."""
        lr = self.lr
        mark = mark or (lambda name: contextlib.nullcontext())
        done, losses, pending = [], [], None
        n = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds if seconds is not None else None
        while True:
            more = (deadline is None or time.perf_counter() < deadline) and \
                (steps is None or n < steps)
            if more:
                with mark("bench.batch"):
                    b = self.put(self.stream.batch(self.next_batch))
                self.next_batch += 1
                with mark("bench.dispatch"):
                    self.params, self.opt, met = self.step(
                        self.params, self.opt, b, lr)
                n += 1
            if pending is not None:
                with mark("bench.wait"):
                    losses.append(float(pending["loss"]))
                done.append(time.perf_counter())
            if not more:
                break
            pending = met
        return t0, done, losses

    def memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def free(self) -> None:
        import jax
        for a in jax.tree.leaves((self.params, self.opt)):
            a.delete()
        self.params = self.opt = None

    def hlo_text(self) -> str:
        import jax.numpy as jnp
        b = self.put(self.stream.batch(0))
        return self.step.build(b).lower(self.params, self.opt, b,
                                        jnp.float32(self.t["lr"])
                                        ).compile().as_text()

    # --- the reference -------------------------------------------------------
    def reference(self, precision: str = "float32", fault: str = "",
                  stages=("warmup", "compressed")) -> dict:
        """The reference's readings of the followed steps; with
        ``precision`` or ``fault`` set, the control or a planted fault
        in its place, in the ``stages`` named."""
        t = self.t
        key = (precision, fault, tuple(stages))
        if key not in self.refs:
            self.refs[key] = reference.Reference(
                reference.Plan(self.model, self.chips, t["block_size"],
                               t["reference_rows"], precision, fault,
                               tuple(stages)), self.devices)
        ref = self.refs[key]
        st, warm_losses, losses = ref.init(self.key), [], []
        for k in range(t["warmup_steps"]):
            st, loss, g = ref.step(st, "warmup", self.stream.warmup_batch(k),
                                   t["warmup_lr"])
            warm_losses.append(loss)
            g.delete()
        x0, v = np.asarray(st["x"]), np.asarray(st["v"])
        for k in range(t["followed_steps"]):
            st, loss, g = ref.step(st, "compressed", self.stream.batch(k),
                                   t["lr"])
            losses.append(loss)
            if k == 0:
                grad = np.asarray(g)
            g.delete()
        change = np.asarray(st["x"]) - x0
        for a in st.values():
            a.delete()
        return {"warmup_losses": warm_losses, "losses": losses,
                "grad": grad, "change": change, "v": v}

    def judge(self, ref: dict, limits: dict):
        read = compare.readings(self.followed, ref,
                                reference.leaf_sizes(self.model),
                                reference.leaf_names(self.model))
        ok, checks = compare.judge(read, limits)
        return ok, checks, read


def held_momentum(opt, workers: int):
    """The momentum with the error feedback held back from it: m, plus
    the workers' mean error, plus the servers' errors gathered.  A
    compressed step keeps this sum: it grows by (1 - b1) g - (1 - b1) m,
    whatever the compression rounded."""
    return (opt["m"].reshape(-1)
            + opt["worker_err"].reshape(workers, -1).mean(0)
            + opt["server_err"].reshape(-1), opt["m"].reshape(-1))


def first_grad(after, before):
    """The gradient a compressed step got, from ``held_momentum``
    before and after it: g = (H1 - H0) / (1 - b1) + m0."""
    return (after[0] - before[0]) / (1 - reference.B1) + before[1]


def p90(xs):
    return statistics.quantiles(xs, n=10, method="inclusive")[8]


def end_to_end(cell: Cell, seconds: float, setup_s: float, counter):
    counter.on = True
    t0, done, losses = cell.loop(seconds=seconds)
    counter.on = False
    steps = len(done)
    window = done[-1] - t0
    intervals = np.diff(done)
    values = {
        "tokens_per_s": steps * cell.batch * cell.seq / window,
        "step_ms_p90": 1e3 * p90(intervals) if len(intervals) >= 2
        else float("nan"),
        "setup_s": setup_s,
    }
    info = {"steps": steps, "window_s": window,
            "step_ms_mean": 1e3 * float(np.mean(intervals)),
            "step_ms_median": 1e3 * float(np.median(intervals)),
            "compiles": counter.n,
            "loss_first": losses[0], "loss_last": losses[-1]}
    bad = sum(1 for x in losses if not math.isfinite(x))
    return values, info, steps, bad


def traced(cell: Cell, reg: Registry):
    import jax
    names = trace.op_names(cell.hlo_text())
    cell.loop(steps=2)                    # settle after the compile above
    tdir = CACHE / "trace" / cell.name
    shutil.rmtree(tdir, ignore_errors=True)
    steps = cell.t["trace_steps"]
    # Python function events would flood the host buffer: the host
    # spans the reduction reads are the benchmark's own annotations
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            _, done, losses = cell.loop(
                steps=steps, mark=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(tdir.rglob("*.xplane.pb"))
    if not paths:
        raise BenchError(f"the profiler wrote no trace under {tdir}")
    raw = trace.extract(str(paths[-1]))
    shutil.rmtree(tdir, ignore_errors=True)
    # the reduction's input stays beside the cache for a look by hand
    with gzip.open(CACHE / "trace" / f"{cell.name}.json.gz", "wt") as f:
        json.dump({"raw": raw.to_json(), "op_names": names}, f)
    span = trace.window(raw)
    if span is None:
        raise BenchError("the trace holds no bench.* span")
    red = trace.reduce(raw, names, *span)
    peak = peaks.peak(cell.devices[0].device_kind)
    tokens = cell.batch * cell.seq
    reading = trace.Reading(
        trace=red, steps=steps, chips=cell.chips,
        flops_per_step=costs.model_flops_per_token(cell.c, cell.seq) * tokens,
        optimizer_least_bytes=costs.onebit_adam_least_bytes(
            costs.param_count(cell.c), cell.chips, cell.t["block_size"]),
        peak=peak, memory_peak_bytes=cell.memory_peak())
    values = {}
    for m in reg.metrics(cell.name, "per_layer"):
        v = reg.reader(m["name"]).read(reading)
        if v is not None:
            values[m["name"]] = v
    n_class = ", ".join(f"{k} {1e3 * v / steps:.3f} ms"
                        for k, v in red.class_s.items())
    print(f"trace: {steps} steps in {red.window_s:.4f} s, busy "
          f"{red.busy_s:.4f} s per chip; per step: {n_class} (other: ops "
          f"with no source name)", flush=True)
    breakdown = {"device_ops": [[k, v] for k, v in red.top_ops],
                 "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
    bad = sum(1 for x in losses if not math.isfinite(x))
    return values, breakdown, red, steps, bad


def run_cell(workload: str, seed: int, seconds: float, trace_on: bool,
             reg: Registry = None, require_tpu: bool = True,
             cache: bool = True) -> dict:
    """One run of ``workload``; the tests call it with a registry of
    their own, no TPU and no persistent cache."""
    reg = reg or Registry()
    spec = reg.cell(workload)
    devices = find_devices(spec["chips"], require_tpu)
    if cache:
        use_cache()
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    counter = CompileCounter()
    cell = Cell(reg, workload, seed, devices)
    cell.build()
    cell.follow()
    setup_s = time.perf_counter() - T_START
    units = {m["name"]: m["unit"] for m in
             reg.spec["end_to_end"] + reg.spec["per_layer"]}
    out = {}
    if trace_on:
        values, breakdown, red, attempted, bad = traced(cell, reg)
        out["breakdown"] = breakdown
    else:
        wanted = {m["name"] for m in reg.metrics(workload, "end_to_end")}
        values, info, attempted, bad = end_to_end(cell, seconds, setup_s,
                                                  counter)
        values = {k: v for k, v in values.items() if k in wanted}
        print("window: " + ", ".join(f"{k} {v}" for k, v in info.items()),
              flush=True)
    memory = cell.memory_peak()
    cell.free()
    t_ref = time.perf_counter()
    ref = cell.reference()
    ok, checks, read = cell.judge(ref, reg.limits(workload))
    print(f"reference: {time.perf_counter() - t_ref:.1f} s; warmup losses "
          f"{cell.followed['warmup_losses']} (program), "
          f"{ref['warmup_losses']} (reference); compressed "
          f"{cell.followed['losses']} (program), {ref['losses']} "
          "(reference)", flush=True)
    checks["window_nonfinite_losses"] = {"value": bad, "limit": 0}
    ok = ok and bad == 0
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    if trace_on:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    result = {"correct": ok, "attempted": attempted, "failed": bad,
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    result.update(out)
    print(f"compared: worst grad leaf {read['grad']['leaf']}, worst change "
          f"leaf {read['change']['leaf']}; loss gap {read['loss']['value']}; "
          f"left out as quiet: {read['left_out']}", flush=True)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
