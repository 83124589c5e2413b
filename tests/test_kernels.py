"""Per-kernel allclose tests: Pallas (interpret=True) vs ref.py oracle.

Sweeps shapes and value scales with hypothesis, as required for every
Pallas kernel in the repo.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import compression as C
from repro.kernels.fused_adam import ops as fa_ops
from repro.kernels.fused_adam import ref as fa_ref
from repro.kernels.onebit import ops as ob_ops
from repro.kernels.onebit import ref as ob_ref


def rand(d, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(d,)).astype(np.float32) * scale)


class TestOneBitKernel:
    @given(nblocks=st.integers(1, 6), seed=st.integers(0, 2**31 - 1),
           block=st.sampled_from([256, 1024, 4096]),
           scale=st.floats(1e-3, 1e3))
    @settings(max_examples=20, deadline=None)
    def test_compress_matches_ref(self, nblocks, seed, block, scale):
        x = rand(nblocks * block, seed, scale)
        pk_k, sc_k = ob_ops.compress(x, block_size=block)
        pk_r, sc_r = ob_ref.compress(x, block_size=block)
        np.testing.assert_array_equal(np.asarray(pk_k), np.asarray(pk_r))
        np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_r),
                                   rtol=1e-6)

    @given(nblocks=st.integers(1, 6), seed=st.integers(0, 2**31 - 1),
           block=st.sampled_from([256, 1024, 4096]))
    @settings(max_examples=20, deadline=None)
    def test_decompress_matches_ref(self, nblocks, seed, block):
        x = rand(nblocks * block, seed)
        pk, sc = ob_ref.compress(x, block_size=block)
        out_k = ob_ops.decompress(pk, sc, block_size=block)
        out_r = ob_ref.decompress(pk, sc, block_size=block)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6)

    @given(seed=st.integers(0, 2**31 - 1), escale=st.floats(0.0, 10.0))
    @settings(max_examples=20, deadline=None)
    def test_fused_ef_matches_ref(self, seed, escale):
        block = 1024
        x = rand(4 * block, seed)
        e = rand(4 * block, seed + 1, escale)
        pk_k, sc_k, ne_k = ob_ops.ef_compress_fused(x, e, block_size=block)
        pk_r, sc_r, ne_r = ob_ref.ef_compress_fused(x, e, block_size=block)
        np.testing.assert_array_equal(np.asarray(pk_k), np.asarray(pk_r))
        np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_r),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(ne_k), np.asarray(ne_r),
                                   rtol=1e-5, atol=1e-6 * max(escale, 1.0))

    def test_core_routes_through_kernel(self):
        """CompressionConfig(use_kernel=True) must give identical wire bytes
        as the jnp path (compression.py dispatches into kernels/onebit)."""
        x = rand(8192, 5)
        pk_j, sc_j = C.compress_onebit(x, 1024, use_kernel=False)
        pk_k, sc_k = C.compress_onebit(x, 1024, use_kernel=True)
        np.testing.assert_array_equal(np.asarray(pk_j), np.asarray(pk_k))
        np.testing.assert_allclose(np.asarray(sc_j), np.asarray(sc_k),
                                   rtol=1e-6)

    def test_ef_invariant_through_kernel(self):
        cfg = C.CompressionConfig(block_size=1024, use_kernel=True)
        x, e = rand(4096, 0), rand(4096, 1, 0.1)
        payload, new_e = C.ef_compress(x, e, cfg)
        y = C.ef_decompress(payload, cfg)
        np.testing.assert_allclose(np.asarray(y + new_e), np.asarray(x + e),
                                   rtol=1e-5, atol=1e-6)


class TestFusedAdamKernel:
    @given(seed=st.integers(0, 2**31 - 1),
           d=st.sampled_from([8192, 16384, 24576]),
           lr=st.floats(1e-5, 1e-1), wd=st.sampled_from([0.0, 0.01]))
    @settings(max_examples=15, deadline=None)
    def test_matches_ref(self, seed, d, lr, wd):
        x, m = rand(d, seed), rand(d, seed + 1, 0.1)
        v, g = jnp.abs(rand(d, seed + 2, 0.01)), rand(d, seed + 3)
        out_k = fa_ops.adam_step(x, m, v, g, lr, weight_decay=wd)
        out_r = fa_ref.adam_step(x, m, v, g, jnp.float32(lr), 0.9, 0.999,
                                 1e-8, wd)
        # tolerance: interpret-mode kernel vs jnp ref differ by fma/rsqrt
        # association at the ULP level (observed max 2.4e-7 abs)
        for a, b in zip(out_k, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=5e-7)

    def test_padding_path(self):
        """d not divisible by the tile: wrapper pads and un-pads."""
        d = 1000
        x, m = rand(d, 0), rand(d, 1, 0.1)
        v, g = jnp.abs(rand(d, 2, 0.01)), rand(d, 3)
        out_k = fa_ops.adam_step(x, m, v, g, 1e-3)
        out_r = fa_ref.adam_step(x, m, v, g, jnp.float32(1e-3), 0.9, 0.999,
                                 1e-8, 0.0)
        for a, b in zip(out_k, out_r):
            assert a.shape == (d,)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_matches_core_adam(self):
        """Kernel result == repro.core.adam.update (no bias correction)."""
        from repro.core import AdamConfig, adam_init, adam_update
        d = 8192
        x, g = rand(d, 7), rand(d, 8)
        st0 = adam_init(d)
        x_ref, st_ref = adam_update(g, st0, x, AdamConfig(), lr=1e-2)
        nx, nm, nv = fa_ops.adam_step(x, st0.m, st0.v, g, 1e-2)
        np.testing.assert_allclose(np.asarray(nx), np.asarray(x_ref),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(nm), np.asarray(st_ref.m),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(nv), np.asarray(st_ref.v),
                                   rtol=1e-6)


class TestFlashAttentionKernel:
    @given(seed=st.integers(0, 2**31 - 1),
           s=st.sampled_from([128, 256, 512]),
           d=st.sampled_from([32, 64, 128]),
           causal=st.booleans(),
           blocks=st.sampled_from([(64, 64), (128, 64), (128, 128)]))
    @settings(max_examples=12, deadline=None)
    def test_matches_ref(self, seed, s, d, causal, blocks):
        from repro.kernels.flash_attn import ops as fa_o
        from repro.kernels.flash_attn import ref as fa_r
        rng = np.random.default_rng(seed)
        shape = (1, 2, s, d)
        q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        k = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        v = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        bq, bk = blocks
        out_k = fa_o.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
        out_r = fa_r.sdpa(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=2e-6)

    @given(seed=st.integers(0, 2**31 - 1),
           window=st.sampled_from([32, 64, 128]))
    @settings(max_examples=8, deadline=None)
    def test_sliding_window(self, seed, window):
        from repro.kernels.flash_attn import ops as fa_o
        from repro.kernels.flash_attn import ref as fa_r
        rng = np.random.default_rng(seed)
        shape = (1, 2, 256, 64)
        q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        k = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        v = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        out_k = fa_o.flash_attention(q, k, v, causal=True, window=window,
                                     bq=64, bk=64)
        out_r = fa_r.sdpa(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=2e-6)

    def test_bf16(self):
        from repro.kernels.flash_attn import ops as fa_o
        from repro.kernels.flash_attn import ref as fa_r
        rng = np.random.default_rng(3)
        shape = (2, 2, 128, 64)
        q = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=shape)).astype(jnp.bfloat16)
        out_k = fa_o.flash_attention(q, k, v, bq=64, bk=64)
        out_r = fa_r.sdpa(q, k, v)
        assert out_k.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_prefill_path_uses_kernel(self):
        """attn_impl='pallas' prefill logits == default path logits."""
        import dataclasses
        from repro.configs import get_config
        from repro.models import transformer as T
        from repro.models.common import ParallelCtx
        cfg0 = get_config("llama3.2-3b").reduced()
        ctx = ParallelCtx()
        params = T.init_params(cfg0, jax.random.PRNGKey(0), tp=1)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                  cfg0.vocab, jnp.int32)
        outs = {}
        for impl in ("full", "pallas"):
            cfg = dataclasses.replace(cfg0, attn_impl=impl)
            logits, _ = T.prefill(params, {"tokens": toks}, cfg, ctx)
            outs[impl] = logits
        np.testing.assert_allclose(np.asarray(outs["pallas"]),
                                   np.asarray(outs["full"]),
                                   rtol=1e-4, atol=1e-4)


class TestPlatformChoice:
    """The interpreter is picked when a kernel is lowered for the CPU and
    only then: a TPU program holds the compiled Mosaic kernel, and no
    other platform gets a silent fallback."""

    CASES = {
        "onebit": lambda x: ob_ops.ef_compress_fused(x, x, 1024),
        "onebit_decompress": lambda x: ob_ops.decompress(
            x[:512].astype(jnp.uint8), x[:4], 1024),
        "fused_adam": lambda x: fa_ops.adam_step(x, x, x, x, 1e-3),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_interpreted_only_on_cpu(self, name):
        traced = jax.jit(self.CASES[name]).trace(rand(4096))
        cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
        tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" not in cpu
        assert "tpu_custom_call" in tpu
        with pytest.raises(NotImplementedError):
            traced.lower(lowering_platforms=("cuda",))

    def test_flash_attention_interpreted_only_on_cpu(self):
        from repro.kernels.flash_attn import ops as fl_ops
        q = jnp.zeros((1, 2, 128, 64), jnp.float32)
        traced = jax.jit(lambda a: fl_ops.flash_attention(a, a, a)).trace(q)
        assert "tpu_custom_call" not in traced.lower(
            lowering_platforms=("cpu",)).as_text()
        assert "tpu_custom_call" in traced.lower(
            lowering_platforms=("tpu",)).as_text()

    def test_import_starts_no_backend(self):
        code = ("import repro.kernels.onebit, repro.kernels.fused_adam, "
                "repro.kernels.flash_attn, repro.core.compression, "
                "repro.launch.train; "
                "from jax._src import xla_bridge; "
                "assert not xla_bridge._backends, xla_bridge._backends")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, PYTHONPATH=src)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
