"""System-level behaviour tests: training driver, checkpointing, serving
steps, roofline parser, variance-freeze semantics."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_pytree, save_pytree
from repro.configs import SHAPES, get_config
from repro.configs.base import InputShape
from repro.core import onebit_adam as OB
from repro.core.compression import CompressionConfig
from repro.data import SyntheticStream, make_batch
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train.step import (TrainStepConfig, init_train_state,
                              make_serve_step, make_train_step)


def small_setup(arch="internlm2-1.8b", block=512):
    cfg = get_config(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    ocfg = OB.OneBitAdamConfig(compression=CompressionConfig(
        block_size=block))
    params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
    opt = init_train_state(cfg, mesh, block=block)
    return cfg, mesh, ocfg, params, opt


class TestTrainingLoop:
    def test_two_stage_converges(self):
        cfg, mesh, ocfg, params, opt = small_setup()
        shape = InputShape("t", 64, 4, "train")
        stream = SyntheticStream(cfg, shape)
        s_w = make_train_step(cfg, mesh,
                              TrainStepConfig(opt=ocfg, stage="warmup"),
                              donate=False)
        s_c = make_train_step(cfg, mesh,
                              TrainStepConfig(opt=ocfg,
                                              stage="compressed"),
                              donate=False)
        losses = []
        for t in range(40):
            fn = s_w if t < 15 else s_c
            params, opt, m = fn(params, opt, stream.batch_at(t),
                                jnp.float32(2e-3))
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.8 * losses[0]
        assert all(np.isfinite(losses))

    def test_v_frozen_in_compressed_stage(self):
        """The second moment must not change during the compression stage
        (Alg. 1: v_{T_w} is a fixed precondition)."""
        cfg, mesh, ocfg, params, opt = small_setup()
        shape = InputShape("t", 64, 4, "train")
        stream = SyntheticStream(cfg, shape)
        s_w = make_train_step(cfg, mesh,
                              TrainStepConfig(opt=ocfg, stage="warmup"),
                              donate=False)
        s_c = make_train_step(cfg, mesh,
                              TrainStepConfig(opt=ocfg,
                                              stage="compressed"),
                              donate=False)
        for t in range(5):
            params, opt, _ = s_w(params, opt, stream.batch_at(t),
                                 jnp.float32(1e-3))
        v_frozen = np.asarray(opt.v)
        for t in range(5, 10):
            params, opt, _ = s_c(params, opt, stream.batch_at(t),
                                 jnp.float32(1e-3))
        np.testing.assert_array_equal(np.asarray(opt.v), v_frozen)

    def test_warmup_is_uncompressed_adam(self):
        """Warmup metrics carry zero compression-error norms implicitly:
        worker/server errors stay zero through warmup."""
        cfg, mesh, ocfg, params, opt = small_setup()
        shape = InputShape("t", 64, 4, "train")
        stream = SyntheticStream(cfg, shape)
        s_w = make_train_step(cfg, mesh,
                              TrainStepConfig(opt=ocfg, stage="warmup"),
                              donate=False)
        for t in range(3):
            params, opt, _ = s_w(params, opt, stream.batch_at(t),
                                 jnp.float32(1e-3))
        assert float(jnp.max(jnp.abs(opt.worker_err))) == 0.0
        assert float(jnp.max(jnp.abs(opt.server_err))) == 0.0


class TestCheckpoint:
    def test_roundtrip(self):
        cfg, mesh, ocfg, params, opt = small_setup()
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "ck.npz")
            save_pytree(path, (params, opt), step=7)
            (p2, o2), step = load_pytree(path, (params, opt))
            assert step == 7
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert jax.tree.structure(o2) == jax.tree.structure(opt)

    def test_load_backfills_new_state_fields(self):
        """Checkpoints written before an optimizer-state field existed
        (e.g. pre-plan-IR, no ``outer_err``) must stay loadable with
        backfill=True (the --resume path): leaves absent from the
        archive fill from the template, with a warning. The default
        stays strict — missing keys usually mean a wrong checkpoint."""
        old = {"m": jnp.arange(4.0)}
        template = {"m": jnp.zeros(4), "outer_err": jnp.full((2,), 9.0)}
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "ck.npz")
            save_pytree(path, old, step=3)
            with pytest.warns(UserWarning, match="outer_err"):
                got, step = load_pytree(path, template, backfill=True)
            assert step == 3
            np.testing.assert_array_equal(np.asarray(got["m"]),
                                          np.arange(4.0))
            np.testing.assert_array_equal(np.asarray(got["outer_err"]),
                                          np.full((2,), 9.0))
            with pytest.raises(KeyError):
                load_pytree(path, template)

    def test_resume_continues_identically(self):
        """save -> load -> next step == uninterrupted next step."""
        cfg, mesh, ocfg, params, opt = small_setup()
        shape = InputShape("t", 64, 4, "train")
        stream = SyntheticStream(cfg, shape)
        step = make_train_step(cfg, mesh, TrainStepConfig(opt=ocfg),
                               donate=False)
        params, opt, _ = step(params, opt, stream.batch_at(0),
                              jnp.float32(1e-3))
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "ck.npz")
            save_pytree(path, (params, opt), step=1)
            (p2, o2), _ = load_pytree(path, (params, opt))
        pa, oa, _ = step(params, opt, stream.batch_at(1), jnp.float32(1e-3))
        pb, ob, _ = step(p2, o2, stream.batch_at(1), jnp.float32(1e-3))
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestServeSteps:
    def test_prefill_step_1x1(self):
        cfg = get_config("llama3.2-3b").reduced()
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = InputShape("p", 64, 2, "prefill")
        step = make_serve_step(cfg, mesh, shape)
        params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
        batch = make_batch(cfg, shape, jax.random.PRNGKey(1))
        batch.pop("labels", None)
        logits = step(params, batch)
        assert logits.shape == (2, cfg.padded_vocab(1))
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_decode_step_1x1(self):
        cfg = get_config("falcon-mamba-7b").reduced()
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = InputShape("d", 64, 2, "decode")
        step = make_serve_step(cfg, mesh, shape)
        params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
        caches = step.init_caches(dtype=jnp.float32)
        h0 = np.asarray(jax.tree.leaves(caches)[0]).copy()  # donated below
        batch = {"tokens": jnp.zeros((2, 1), jnp.int32)}
        logits, new_caches = step(params, batch, caches, jnp.int32(0))
        assert logits.shape == (2, cfg.padded_vocab(1))
        # ssm state must move
        h1 = jax.tree.leaves(new_caches)[0]
        assert not np.array_equal(h0, np.asarray(h1))


class TestRooflineParser:
    def test_scan_trip_count(self):
        from repro.analysis.roofline import analyze_compiled

        def f(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y

        s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        co = jax.jit(f).lower(s, s).compile()
        r = analyze_compiled(co)
        assert abs(r.dot_flops - 2 * 64 ** 3 * 7) / (2 * 64 ** 3 * 7) < 0.01

    def test_nested_dot(self):
        from repro.analysis.roofline import analyze_compiled

        def f(a, b, c):
            return (a @ b) @ c

        s = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        co = jax.jit(f).lower(s, s, s).compile()
        r = analyze_compiled(co)
        assert abs(r.dot_flops - 2 * 2 * 32 ** 3) < 1e-6

    def test_bottleneck_fields(self):
        from repro.analysis.roofline import RooflineReport
        r = RooflineReport(dot_flops=197e12, hbm_bytes=819e9 * 2,
                           coll_bytes=0.0, coll_by_kind={})
        assert r.t_compute == pytest.approx(1.0)
        assert r.t_memory == pytest.approx(2.0)
        assert r.bottleneck == "memory"
        assert r.step_time_lower_bound == pytest.approx(2.0)


class TestTrainDriverCLI:
    def test_driver_runs(self, tmp_path):
        from repro.launch.train import run
        log = str(tmp_path / "log.json")
        run("internlm2-1.8b-smoke", steps=12, batch=4, seq=64,
            mesh_shape=(1, 1), base_lr=2e-3, lr_warmup=4, warmup_steps=6,
            block_size=512, log_file=log, log_every=100)
        import json
        hist = json.load(open(log))
        assert len(hist) == 12
        assert hist[5]["stage"] == "warmup"
        assert hist[6]["stage"] == "compressed"
        assert np.isfinite(hist[-1]["loss"])

    @pytest.mark.parametrize("from_env", [True, False])
    def test_compile_cache_directory(self, tmp_path, from_env):
        """``JAX_COMPILATION_CACHE_DIR`` when set, and compiled programs
        land there; otherwise the fixed ``<repo>/.jax_cache``."""
        import subprocess
        import sys
        code = ("import jax, jax.numpy as jnp\n"
                "from repro.launch.train import REPO_ROOT, use_compile_cache\n"
                "path = use_compile_cache()\n"
                "assert jax.config.jax_compilation_cache_dir == path\n"
                "if jax.config.jax_compilation_cache_dir != "
                "str(REPO_ROOT / '.jax_cache'):\n"
                "    jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8)))"
                ".block_until_ready()\n"
                "print(path)\n")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(PYTHONPATH=src,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
        if from_env:
            env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        path = r.stdout.strip().splitlines()[-1]
        if from_env:
            assert path == str(tmp_path)
            assert any(tmp_path.iterdir())
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == os.path.join(repo, ".jax_cache")


class TestGradAccumulation:
    def test_accum_matches_single_batch(self):
        """accum_steps=4 over a batch == one step over the same batch
        (grads averaged identically; warmup stage is deterministic)."""
        cfg, mesh, ocfg, params, opt = small_setup()
        shape = InputShape("t", 64, 8, "train")
        batch = SyntheticStream(cfg, shape).batch_at(0)
        s1 = make_train_step(cfg, mesh, TrainStepConfig(opt=ocfg),
                             donate=False)
        s4 = make_train_step(cfg, mesh,
                             TrainStepConfig(opt=ocfg, accum_steps=4),
                             donate=False)
        p1, o1, m1 = s1(params, opt, batch, jnp.float32(1e-3))
        p4, o4, m4 = s4(params, opt, batch, jnp.float32(1e-3))
        # accumulation reorders the gradient sum; Adam's rsqrt amplifies
        # the float-association noise near v ~ 0 — tolerance reflects that
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-2, atol=1e-4)
        np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                                   rtol=1e-5)


class TestServeEngine:
    def test_generate_greedy_deterministic(self):
        from repro.serve import GenerationConfig, ServeEngine
        cfg = get_config("llama3.2-3b").reduced()
        params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
        eng = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                     cfg.vocab, jnp.int32)
        gc = GenerationConfig(max_new_tokens=8, temperature=0.0)
        out1 = eng.generate(prompts, gc)
        out2 = eng.generate(prompts, gc)
        np.testing.assert_array_equal(np.asarray(out1["tokens"]),
                                      np.asarray(out2["tokens"]))
        assert out1["tokens"].shape == (2, 8)
        assert (np.asarray(out1["tokens"]) < cfg.vocab).all()

    def test_generate_sampled_and_eos(self):
        from repro.serve import GenerationConfig, ServeEngine
        cfg = get_config("falcon-mamba-7b").reduced()
        params = T.init_params(cfg, jax.random.PRNGKey(0), tp=1)
        eng = ServeEngine(cfg, params)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 12), 0,
                                     cfg.vocab, jnp.int32)
        gc = GenerationConfig(max_new_tokens=10, temperature=1.0, top_k=8,
                              eos_id=0)
        out = eng.generate(prompts, gc, key=jax.random.PRNGKey(7))
        toks = np.asarray(out["tokens"])
        nv = np.asarray(out["n_valid"])
        assert toks.shape == (3, 10)
        # after a sequence hits eos, all later tokens are eos
        for i in range(3):
            if nv[i] < 10:
                assert (toks[i, nv[i]:] == 0).all()


class TestDCGAN:
    def test_gan_losses_finite_and_trainable(self):
        from repro.models.dcgan import (d_loss, g_loss, generator,
                                        init_discriminator, init_generator,
                                        synthetic_faces)
        kg, kd, kz, kx = jax.random.split(jax.random.PRNGKey(0), 4)
        pg = init_generator(kg)
        pd_ = init_discriminator(kd)
        z = jax.random.normal(kz, (8, 32))
        real = synthetic_faces(kx, 8)
        assert real.shape == (8, 16, 16, 3)
        fake = generator(pg, z)
        assert fake.shape == (8, 16, 16, 3)
        assert bool(jnp.all(jnp.abs(fake) <= 1.0))
        ld = d_loss(pd_, pg, real, z)
        lg = g_loss(pg, pd_, z)
        assert np.isfinite(float(ld)) and np.isfinite(float(lg))
        gd = jax.grad(d_loss)(pd_, pg, real, z)
        gg = jax.grad(g_loss)(pg, pd_, z)
        for leaf in jax.tree.leaves(gd) + jax.tree.leaves(gg):
            assert bool(jnp.all(jnp.isfinite(leaf)))
