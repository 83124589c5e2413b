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
from repro.kernels.onebit import kernel as ob_kernel
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


def _received(n, chunk, block, seed):
    """``n`` compressed chunks as a server receives them from the
    all_to_all: ``(n, chunk/8)`` u8 and ``(n, chunk/block)`` f32."""
    xs = jnp.stack([rand(chunk, seed + i) for i in range(n)])
    return jax.vmap(lambda x: C.compress_onebit(x, block))(xs)


class TestOneBitServerKernel:
    """``ef_server_fused`` (interpreted) against the server step it
    replaces on the chip: decompress, combine and EF-compress in jnp."""

    # 1,280 rows of 128 lanes: a grid step holds 1,024, so the last one
    # is partial at every block size
    CHUNK = 1280 * 128

    def _check(self, n, combine, block, chunk, seed=0, byte_rows=None):
        from repro.optim.compressors import Compressor, OneBitCompressor
        packed, scales = _received(n, chunk, block, seed)
        err = rand(chunk, seed + n, 0.3)
        pk_k, sc_k, ne_k = ob_kernel.ef_server_fused(
            packed, scales, err, block, combine, byte_rows=byte_rows)
        comp = OneBitCompressor(block_size=block)
        (pk_j, sc_j), ne_j = Compressor.server_ef_compress(
            comp, (packed, scales), err, combine)
        np.testing.assert_array_equal(np.asarray(pk_k), np.asarray(pk_j))
        np.testing.assert_allclose(np.asarray(sc_k), np.asarray(sc_j),
                                   rtol=1e-6)
        # the residual is relative to its block's scale, not to itself
        atol = 1e-6 * float(jnp.max(sc_j))
        np.testing.assert_allclose(np.asarray(ne_k), np.asarray(ne_j),
                                   rtol=1e-6, atol=atol)
        # error feedback: the payload sent on plus the new error is the
        # server's buffer, the combined chunks plus the old error
        buf = comp.combine_received((packed, scales), combine) + err
        np.testing.assert_allclose(
            np.asarray(comp.decompress((pk_k, sc_k)) + ne_k),
            np.asarray(buf), rtol=1e-6, atol=atol)

    @pytest.mark.parametrize("block", [128, 1024, 4096])
    @pytest.mark.parametrize("combine", ["mean", "sum"])
    @pytest.mark.parametrize("n", [1, 4])
    def test_matches_jnp_server_step(self, n, combine, block):
        self._check(n, combine, block, self.CHUNK)

    def test_one_chunk_as_byte_rows(self):
        """One chunk whose bitmap comes from the Pallas codec: rows of 128
        bytes, the view a group of more than one takes."""
        self._check(1, "mean", 4096, self.CHUNK, seed=5, byte_rows=True)

    def test_chunk_of_a_few_blocks(self):
        """Three blocks of 128: one grid step of three rows, the whole
        chunk, with no row of 1,024 elements filled."""
        self._check(2, "mean", 128, 3 * 128, seed=11)


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


def _bhsd(a):
    """(B, S, H, D) <-> (B, H, S, D), the reference's layout."""
    return a.transpose(0, 2, 1, 3)


def _attn_data(shape, seed, dtype):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=shape).astype(np.float32)
                        ).astype(dtype) for _ in range(4)]


def _ref_grads(q, k, v, do, causal, window=None):
    """o and (dq, dk, dv) of ``ref.sdpa`` in float32, (B, S, H, D)."""
    from repro.kernels.flash_attn import ref as fa_r

    def f(a, b, c):
        return _bhsd(fa_r.sdpa(_bhsd(a), _bhsd(b), _bhsd(c), causal=causal,
                               window=window))
    f32 = [a.astype(jnp.float32) for a in (q, k, v, do)]
    o, vjp = jax.vjp(f, *f32[:3])
    return (o,) + vjp(f32[3])


def _kernel_grads(q, k, v, do, causal, window=None, blocks=None):
    """o and (dq, dk, dv), (B, S, H, D), from the forward and backward
    Pallas kernels run through the interpreter; ``blocks`` (bq, bk)
    defaults to ``kernel.blocks``.  di goes in, from the forward's f32 o,
    where the kv block is not all of S, as the op gives it."""
    from repro.kernels.flash_attn import kernel as K
    b, s, h, d = q.shape
    bq, bk = blocks or K.blocks(s)
    cfg = dict(causal=causal, window=window, bq=bq, bk=bk, interpret=True)
    heads_major = lambda a: a.transpose(0, 2, 3, 1)
    packed = lambda a: a.reshape(b, s, h * d)
    o, lse, o32 = K.fwd(heads_major(q), heads_major(k), packed(v), **cfg)
    di = None
    if bk < s:
        di = jnp.sum((o32 * packed(do).astype(jnp.float32)
                      ).reshape(b, s, h, d), axis=-1)
        di = di.transpose(0, 2, 1)[:, :, None, :]
    dq, dk, dv = K.bwd(heads_major(q), heads_major(k), packed(v), packed(do),
                       lse, di, **cfg)
    return (o.reshape(b, s, h, d), dq.transpose(0, 3, 1, 2),
            dk.transpose(0, 3, 1, 2), dv.reshape(b, s, h, d))


def _op_grads(q, k, v, do, causal, window=None):
    """o and (dq, dk, dv) of the op as the CPU lowers it (its jnp rule)."""
    from repro.kernels.flash_attn import ops as fa_o
    o, vjp = jax.vjp(lambda a, b, c: fa_o.flash_attention(
        a, b, c, causal=causal, window=window), q, k, v)
    return (o,) + vjp(do)


def _assert_close(got, want, dtype):
    """o to the forward tests' own tolerances; the gradients, sums over
    S terms of a few units, to float32 rounding of such sums (2e-5
    absolute) and to bf16's where the probabilities and ds are rounded to
    bf16 before their products (the f32 reference rounds nothing)."""
    if dtype == jnp.float32:
        tols = [dict(rtol=1e-5, atol=2e-6)] + 3 * [dict(rtol=1e-5, atol=2e-5)]
    else:
        tols = 4 * [dict(rtol=2e-2, atol=2e-2)]
    for name, a, b, tol in zip(("o", "dq", "dk", "dv"), got, want, tols):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), err_msg=name,
                                   **tol)


# the kernels' blocks (bq, bk) at sequence s: their default (one kv block
# up to 1024), one kv block with 128-row query blocks, and 128 x 128
# tiles (the online softmax, di given, dq summed across kv blocks)
BLOCKS = {"default": lambda s: None, "q128": lambda s: (128, s),
          "tiled": lambda s: (128, 128)}


class TestFlashAttentionKernel:
    """The Pallas kernels through the interpreter, and the op (its CPU
    rule, as the tests lower for the CPU), against ``ref.sdpa``."""

    @given(seed=st.integers(0, 2**31 - 1),
           s=st.sampled_from([128, 256, 512]),
           d=st.sampled_from([32, 64, 128]),
           causal=st.booleans(),
           blocks=st.sampled_from(sorted(BLOCKS)))
    @settings(max_examples=12, deadline=None)
    def test_matches_ref(self, seed, s, d, causal, blocks):
        """Two lane blocks of heads: G = 4 heads of 32, 2 of 64 (the scale
        folded into q), 1 of 128."""
        q, k, v, do = _attn_data((1, s, 256 // d, d), seed, jnp.float32)
        _assert_close(_kernel_grads(q, k, v, do, causal,
                                    blocks=BLOCKS[blocks](s)),
                      _ref_grads(q, k, v, do, causal), jnp.float32)

    @given(seed=st.integers(0, 2**31 - 1),
           window=st.sampled_from([32, 64, 128]),
           blocks=st.sampled_from(sorted(BLOCKS)))
    @settings(max_examples=8, deadline=None)
    def test_sliding_window(self, seed, window, blocks):
        """Windows up to a 128-row block, in every schedule."""
        q, k, v, do = _attn_data((1, 256, 2, 64), seed, jnp.float32)
        _assert_close(_kernel_grads(q, k, v, do, True, window,
                                    blocks=BLOCKS[blocks](256)),
                      _ref_grads(q, k, v, do, True, window), jnp.float32)

    def test_bf16(self):
        q, k, v, do = _attn_data((2, 128, 2, 64), 3, jnp.bfloat16)
        got = _kernel_grads(q, k, v, do, True)
        assert all(a.dtype == jnp.bfloat16 for a in got)
        _assert_close(got, _ref_grads(q, k, v, do, True), jnp.bfloat16)

    # (causal, window, S, blocks): one kv block at S = 128 and 512, the
    # tiled online-softmax branch at S = 1024 with 256-row blocks, and a
    # sliding window over tiled 128-row blocks
    KERNEL_CASES = [(False, None, 128, None), (True, None, 128, None),
                    (False, None, 512, None), (True, None, 512, None),
                    (False, None, 1024, 256), (True, None, 1024, 256),
                    (True, 200, 512, 128)]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("causal,window,s,blk", KERNEL_CASES)
    def test_kernels_interpreted(self, causal, window, s, blk, dtype):
        """The forward and backward Pallas kernels, called directly
        through the interpreter, give o and jax.vjp's dq, dk, dv of the
        reference: two heads of 64 in a lane block."""
        self._check_kernels(causal, window, s, blk, 64, dtype)

    # (causal, window, S, blocks, head_dim): one kv block over four query
    # blocks at S = 1024; one head of 128 a lane block and four of 32, the
    # scale applied to the scores (1/sqrt(D) is no power of two), the
    # latter with a window smaller than its 128-row blocks
    SHAPE_CASES = [(False, None, 1024, None, 64),
                   (False, None, 512, None, 128), (True, None, 256, 128, 128),
                   (True, 64, 256, 128, 32), (False, None, 256, None, 32)]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("causal,window,s,blk,d", SHAPE_CASES)
    def test_kernels_interpreted_shapes(self, causal, window, s, blk, d,
                                        dtype):
        self._check_kernels(causal, window, s, blk, d, dtype)

    @staticmethod
    def _check_kernels(causal, window, s, blk, d, dtype):
        q, k, v, do = _attn_data((1, s, 128 // d, d), s + causal, dtype)
        got = _kernel_grads(q, k, v, do, causal, window,
                            blocks=(blk, blk) if blk else None)
        assert all(a.dtype == dtype for a in got)
        _assert_close(got, _ref_grads(q, k, v, do, causal, window), dtype)

    @pytest.mark.parametrize("blocks", ["default", "tiled"])
    def test_bf16_gradients_with_shared_key(self, blocks):
        """Keys and values sharing a component across positions (tokens
        sharing an embedding) amplify any bias in the row sums of ds
        along that component of dq.  di must therefore match the p and dp
        of the backward: from the tile with one kv block, from the
        forward's f32 o with kv blocks.  From the bf16 o, dq is off by
        5.6% of its norm here; the f32 arithmetic of the reference and
        the bf16 rounding of p and ds leave 0.3-0.6%."""
        rng = np.random.default_rng(11)
        shape, shared = (1, 512, 2, 64), (1, 1, 2, 64)
        q, k, v, do = [rng.normal(size=shape) for _ in range(4)]
        k = k + 1.5 * rng.normal(size=shared)
        v = v + 1.5 * rng.normal(size=shared)
        q, k, v, do = (jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                       for a in (q, k, v, do))
        got = _kernel_grads(q, k, v, do, False,
                            blocks=BLOCKS[blocks](512))
        want = _ref_grads(q, k, v, do, False)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            assert np.linalg.norm(a - b) < 0.01 * np.linalg.norm(b), name

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                               (True, 100)])
    def test_cpu_rule_matches_ref(self, causal, window, dtype):
        """The op as the CPU lowers it, value and gradients."""
        q, k, v, do = _attn_data((2, 256, 4, 32), 5, dtype)
        got = _op_grads(q, k, v, do, causal, window)
        assert all(a.dtype == dtype for a in got)
        _assert_close(got, _ref_grads(q, k, v, do, causal, window), dtype)

    @pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                               (True, 100)])
    def test_cpu_rule_matches_kernels(self, causal, window):
        """The jnp rule the op lowers to on the CPU computes what the
        kernels compute, lse included."""
        from repro.kernels.flash_attn import kernel as K
        from repro.kernels.flash_attn import ops as fa_o
        q, k, v, do = _attn_data((1, 256, 4, 32), 7, jnp.bfloat16)
        qh, kh = (a.transpose(0, 2, 3, 1) for a in (q, k))
        vp = v.reshape(1, 256, 128)
        bq, bk = K.blocks(256)
        _, lse_k, _ = K.fwd(qh, kh, vp, causal=causal, window=window, bq=bq,
                            bk=bk, interpret=True)
        _, lse_j, _ = fa_o._fwd_rule(qh, kh, vp, causal, window)
        np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_j),
                                   rtol=1e-6, atol=1e-6)
        for a, c in zip(_kernel_grads(q, k, v, do, causal, window),
                        _op_grads(q, k, v, do, causal, window)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(c, np.float32),
                                       rtol=1e-2, atol=1e-2)

    def test_supports(self):
        from repro.kernels.flash_attn import ops as fa_o
        assert fa_o.supports(512, 12, 64)          # two heads a block
        assert fa_o.supports(128, 4, 16)           # all heads in 64 lanes
        assert fa_o.supports(1024, 8, 128)
        assert not fa_o.supports(32, 12, 64)       # not whole 128 rows
        assert not fa_o.supports(512, 3, 64)       # heads do not pair up
        assert not fa_o.supports(512, 4, 96)       # 96 does not tile 128
        assert not fa_o.supports(512, 4, 256)

    @pytest.mark.parametrize("causal", [False, True])
    def test_layer_auto_matches_sdpa(self, causal):
        """attn_forward under "auto" (the op) against "full" (_sdpa) at a
        small width, in value and in gradient."""
        import dataclasses
        from repro.configs import get_config
        from repro.models import attention as A
        from repro.models.common import ParallelCtx
        # the smoke config's 64-row attn_chunk would send S = 512 chunked
        cfg0 = dataclasses.replace(get_config("bert-base-smoke"),
                                   causal=causal, compute_dtype="float32",
                                   attn_chunk=2048)
        ctx = ParallelCtx()
        p = A.init_attn(jax.random.PRNGKey(0), cfg0, tp=1)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (2, A.FLASH_MIN_SEQ, cfg0.d_model))
        out = {}
        for impl in ("auto", "full"):
            cfg = dataclasses.replace(cfg0, attn_impl=impl)
            f = lambda p, x: jnp.sum(jnp.sin(A.attn_forward(p, x, cfg, ctx)))
            out[impl] = (A.attn_forward(p, x, cfg, ctx),
                         jax.grad(f, argnums=(0, 1))(p, x))
        for a, b in zip(jax.tree.leaves(out["auto"]),
                        jax.tree.leaves(out["full"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("seq", [256, 512])
    def test_auto_takes_kernels_from_min_seq(self, seq):
        """Under "auto" the TPU program attends through the kernels from
        FLASH_MIN_SEQ up, and through XLA's _sdpa below it."""
        import dataclasses
        from repro.configs import get_config
        from repro.models import attention as A
        from repro.models.common import ParallelCtx
        cfg = dataclasses.replace(get_config("bert-base-smoke"),
                                  attn_chunk=2048)
        p = A.init_attn(jax.random.PRNGKey(0), cfg, tp=1)
        x = jnp.zeros((1, seq, cfg.d_model), jnp.bfloat16)
        text = jax.jit(lambda p, x: A.attn_forward(
            p, x, cfg, ParallelCtx())).trace(p, x).lower(
                lowering_platforms=("tpu",)).as_text()
        assert ("flash_attn_fwd" in text) == (seq >= A.FLASH_MIN_SEQ)

    def test_prefill_path_uses_kernel(self):
        """attn_impl="pallas" prefill logits == default path logits, and
        its TPU program holds the forward kernel."""
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
        # the CPU runs the op's jnp rule; the TPU program runs the kernel
        tpu = jax.jit(lambda p, t: T.prefill(p, {"tokens": t}, cfg, ctx)
                      ).trace(params, toks).lower(
                          lowering_platforms=("tpu",)).as_text()
        assert "flash_attn_fwd" in tpu
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
        "onebit_server": lambda x: ob_kernel.ef_server_fused(
            x[:1024].astype(jnp.uint8).reshape(2, 512), x[:8].reshape(2, 4),
            x, 1024),
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
        """The op's forward and backward rules: both kernels when lowered
        for a TPU, the jnp rule (no interpreter) for the CPU."""
        from repro.kernels.flash_attn import ops as fl_ops
        q = jnp.zeros((1, 128, 2, 64), jnp.float32)

        def loss(a):
            return jnp.sum(fl_ops.flash_attention(a, a, a))
        traced = jax.jit(jax.grad(loss)).trace(q)
        cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
        tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" not in cpu
        assert "pallas" not in cpu
        assert tpu.count("tpu_custom_call") == 2
        with pytest.raises(NotImplementedError):
            traced.lower(lowering_platforms=("cuda",))

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
