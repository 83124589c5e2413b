"""Pallas TPU flash attention for training: a forward and a backward kernel.

Layout: q and k (and dq, dk) are (B, H, D, S), each head a (D, S) block
with the sequence on the lanes; v, o, dO and dv are (B, S, H * D), the
heads side by side along the lanes, as the projections produce them.
Both are the layouts XLA gives these arrays around the kernels: q and k
come out of the rotary embedding's fusion with the sequence minor, so
neither side needs a transpose in HBM.  A grid step takes a group of G
heads: the lane block of v, o and dO that holds them, 128 lanes for
G = 128 // D heads (all H when H * D < 128).  Head h's products with v
and dO contract over the block's lanes with the other heads' lanes
zeroed, which costs the MXU what a D-deep contraction costs.  The row
statistics ``lse`` (log-sum-exp of the scaled scores) and ``di``
(rowsum(dO * o)) are (B, H, 1, S) f32.  Why di comes from the tile, or
from an f32 o: a bf16 o puts its rounding into every row of ds as a
bias, which dq carries along the keys' shared component, and that
component can be large (tokens share embeddings).

Precision: bf16 (the operands' dtype) into every product with f32
accumulation; the 1/sqrt(D) scale on the f32 scores; softmax in f32; the
probabilities cast to the operands' dtype before the PV product, and p
and ds before the products of the backward.  Where 1/sqrt(D) is a power
of two (D = 16, 64, 256) it is applied to q instead, which is exact.

Forward, grid (B, H / G, S / BQ): a (BQ, D) query block (q transposed
in VMEM) against the whole of K (D, S) and V (S, W), scores (BQ, BK).
  * one kv block (BK == S, every S <= ONE_BLOCK_MAX_S): a plain softmax
    of the (BQ, S) tile, normalized before the PV product;
  * otherwise the online-softmax recurrence over BK-row kv blocks, with
    causal blocks right of the diagonal and blocks left of a sliding
    window skipped.

Backward, grid (B, H / G, S / BK): a BK-row kv block against the whole
of q and dO, in transposed orientation (scores (BK, BQ), kv on the
sublanes), so lse and di broadcast as lane rows; a loop over BQ-row
query blocks recomputes p = exp(s - lse) and forms dv = p^T dO,
dk^T = q^T ds and dq^T = k^T ds^T, every product in its native
orientation.  With one kv block (BK == S) the tile holds every key of
its queries: di = rowsum(p * dp) is formed from the tile, as softmax's
own gradient forms it, so each row of ds sums to zero, and dq is whole.
Above, di = rowsum(dO * o) comes in, from the forward's o before its
rounding to the operands' dtype (a third output, f32), and dq is summed
across kv blocks in VMEM.  The S x S scores and probabilities never
leave VMEM.

Compiled when lowered for a TPU; the Pallas interpreter runs these
kernels only where a test calls them with ``interpret=True``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ONE_BLOCK_MAX_S = 1024        # up to here a query block sees all of K/V
TILE_ELEMS = 512 * 512        # one f32 score tile: 1 MiB of VMEM
NEG_INF = -1e30
VMEM_LIMIT = 64 * 2**20

# contract the last dims: a b^T
_NT = (((1,), (1,)), ((), ()))


def head_group(heads: int, head_dim: int) -> Optional[int]:
    """Heads per lane block, or None where the heads do not tile the
    lanes (head_dim not a divisor of 128, or heads not a multiple)."""
    if head_dim > LANES or LANES % head_dim:
        return None
    g = min(LANES // head_dim, heads)
    return g if heads % g == 0 else None


def _block(s: int, other: int) -> int:
    """The largest multiple of 128 that divides s and keeps a
    (block, other) f32 tile within TILE_ELEMS (128 at the least)."""
    for b in range(s - s % LANES, LANES, -LANES):
        if s % b == 0 and b * other <= TILE_ELEMS:
            return b
    return LANES


def blocks(s: int) -> Tuple[int, int]:
    """Default (BQ, BK), the query and kv blocks of both kernels.  One
    kv block (BK = S) up to ONE_BLOCK_MAX_S, 512-row kv blocks above."""
    bk = s if s <= ONE_BLOCK_MAX_S else _block(s, 512)
    return _block(s, bk), bk


class Cfg(NamedTuple):
    heads: int                # G, heads in one lane block
    head_dim: int
    causal: bool
    window: Optional[int]
    bq: int
    bk: int

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def fold(self) -> bool:
        """Whether 1/sqrt(D) is a power of two, so that scaling q (and
        dq at the end) is exact and equal to scaling the scores (and ds)."""
        return math.log2(self.scale).is_integer()


def _mask(cfg: Cfg, s, q0, k0, kv_rows: bool):
    """Mask the causal and sliding-window entries of a score tile whose
    first query is q0 and first key k0 (keys on the rows if kv_rows)."""
    if not cfg.causal and cfg.window is None:
        return s
    kdim, qdim = (0, 1) if kv_rows else (1, 0)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, qdim)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, kdim)
    ok = jnp.ones(s.shape, bool)
    if cfg.causal:
        ok = ok & (kpos <= qpos)
    if cfg.window is not None:
        ok = ok & (kpos > qpos - cfg.window)
    return jnp.where(ok, s, NEG_INF)


def _kv_range(cfg: Cfg, q0, n_kv: int):
    """[first, end) kv blocks a query block starting at q0 can see."""
    end = n_kv
    if cfg.causal:
        end = jnp.minimum((q0 + cfg.bq + cfg.bk - 1) // cfg.bk, n_kv)
    first = 0
    if cfg.window is not None:
        first = jnp.maximum((q0 - cfg.window + 1) // cfg.bk, 0)
    return first, end


def _q_range(cfg: Cfg, k0, n_q: int):
    """[first, end) query blocks that can see the kv block at k0."""
    first = 0
    if cfg.causal:
        first = k0 // cfg.bq
    end = n_q
    if cfg.window is not None:
        end = jnp.minimum((k0 + cfg.bk + cfg.window - 2) // cfg.bq + 1, n_q)
    return first, end


def _head_lanes(cfg: Cfg, w: int, h: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return (lane >= h * cfg.head_dim) & (lane < (h + 1) * cfg.head_dim)


def _fwd_kernel(cfg: Cfg, q_ref, k_ref, v_ref, o_ref, lse_ref, *o32_ref):
    # q (G, D, BQ), k (G, D, S), v (S, W) -> o (BQ, W), lse (G, 1, BQ)
    # and, with kv blocks, o before its rounding (BQ, W) f32
    bq, w = o_ref.shape
    s_kv = k_ref.shape[2]
    q0 = pl.program_id(2) * bq
    o, lses = None, []
    for h in range(cfg.heads):
        in_h = _head_lanes(cfg, w, h)
        q = q_ref[h].T                                          # (BQ, D)
        if cfg.fold:
            q = q * jnp.asarray(cfg.scale, q.dtype)

        def scores(k):
            s = jnp.dot(q, k, preferred_element_type=jnp.float32)
            return s if cfg.fold else s * cfg.scale

        if cfg.bk == s_kv:
            v = v_ref[...]
            s = _mask(cfg, scores(k_ref[h]), q0, 0, kv_rows=False)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            oh = jnp.dot((p * (1.0 / l)).astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        else:
            def body(j, carry):
                m_prev, l_prev, acc = carry
                kv = pl.ds(pl.multiple_of(j * cfg.bk, cfg.bk), cfg.bk)
                v = v_ref[kv, :]
                s = _mask(cfg, scores(k_ref[h, :, kv]), q0, j * cfg.bk,
                          kv_rows=False)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
                acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                           preferred_element_type=jnp.float32)
                return m_new, l_new, acc

            first, end = _kv_range(cfg, q0, s_kv // cfg.bk)
            m, l, acc = jax.lax.fori_loop(
                first, end, body,
                (jnp.full((bq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.zeros((bq, w), jnp.float32)))
            oh = acc / l
        o = oh if o is None else jnp.where(in_h, oh, o)
        lses.append(m + jnp.log(l))
    o_ref[...] = o.astype(o_ref.dtype)
    if o32_ref:
        o32_ref[0][...] = o
    # the (BQ, 1) columns side by side as lanes, turned into lane rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)
    cols = jnp.zeros((bq, LANES), jnp.float32)
    for h, lse in enumerate(lses):
        cols = jnp.where(lane == h, lse, cols)
    rows = cols.T
    for h in range(cfg.heads):
        lse_ref[h] = rows[h:h + 1, :]


def _bwd_kernel(cfg: Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, *refs):
    # q (G, D, S), k (G, D, BK), v (BK, W), dO (S, W), lse and, when the
    # kv block is not all of S, di (G, 1, S) -> dq (G, D, S), dk (G, D,
    # BK), dv (BK, W)
    bk, w = v_ref.shape
    s_q = q_ref.shape[2]
    bq = cfg.bq
    # every key in this block: di = rowsum(p * dp) of this tile, the rows
    # of ds sum to zero as in softmax's own gradient, and dq is whole
    one_kv = bk == s_q
    di_ref = None if one_kv else refs[0]
    dq_ref, dk_ref, dv_ref, *scratch = refs[0 if one_kv else 1:]
    kv = pl.program_id(2)
    k0 = kv * bk
    v = v_ref[...]
    dt = v.dtype
    # ds is unscaled where the scale went into q: dq takes it at the end
    dq_scale = cfg.scale if cfg.fold else 1.0
    dq_acc = scratch[0] if scratch else None
    if dq_acc is not None:
        @pl.when(kv == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    dv = None
    for h in range(cfg.heads):
        in_h = _head_lanes(cfg, w, h)
        k = k_ref[h]                                            # (D, BK)
        kt = k.T                                                # (BK, D)
        vh = jnp.where(in_h, v, 0)

        def body(j, carry):
            dk_h, dv_h = carry
            qs = pl.ds(pl.multiple_of(j * bq, bq), bq)
            q = q_ref[h, :, qs]                                 # (D, BQ)
            if cfg.fold:
                q = q * jnp.asarray(cfg.scale, q.dtype)
            do = do_ref[qs, :]
            s = jnp.dot(kt, q, preferred_element_type=jnp.float32)
            if not cfg.fold:
                s = s * cfg.scale
            s = _mask(cfg, s, j * bq, k0, kv_rows=True)
            p = jnp.exp(s - lse_ref[h, :, qs])                  # (BK, BQ)
            dp = jax.lax.dot_general(vh, do, _NT,
                                     preferred_element_type=jnp.float32)
            di = (jnp.sum(p * dp, axis=0, keepdims=True) if one_kv
                  else di_ref[h, :, qs])
            ds = p * (dp - di)
            if not cfg.fold:
                ds = ds * cfg.scale
            p, ds = p.astype(dt), ds.astype(dt)
            dv_h = dv_h + jnp.dot(p, do, preferred_element_type=jnp.float32)
            dk_h = dk_h + jax.lax.dot_general(
                q, ds, _NT, preferred_element_type=jnp.float32)
            dq = jnp.dot(k, ds, preferred_element_type=jnp.float32)
            if one_kv:
                dq_ref[h, :, qs] = (dq * dq_scale).astype(dq_ref.dtype)
            else:
                dq_acc[h, :, qs] += dq
            return dk_h, dv_h

        zero = (jnp.zeros((cfg.head_dim, bk), jnp.float32),
                jnp.zeros((bk, w), jnp.float32))
        if bq == s_q:
            dk_h, dv_h = body(0, zero)
        else:
            first, end = _q_range(cfg, k0, s_q // bq)
            dk_h, dv_h = jax.lax.fori_loop(first, end, body, zero)
        dk_ref[h] = dk_h.astype(dk_ref.dtype)
        dv = dv_h if dv is None else jnp.where(in_h, dv_h, dv)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    if dq_acc is not None:
        @pl.when(kv == pl.num_programs(2) - 1)
        def _():
            dq_ref[...] = (dq_acc[...] * dq_scale).astype(dq_ref.dtype)


def _cfg(q: jax.Array, v: jax.Array, causal: bool, window: Optional[int],
         bq: int, bk: int) -> Tuple[Cfg, int]:
    """The kernels' static configuration and the lane block width."""
    b, heads, d, s = q.shape
    g = head_group(heads, d)
    assert g is not None, f"{heads} heads of {d} do not tile {LANES} lanes"
    assert v.shape == (b, s, heads * d), (q.shape, v.shape)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    return Cfg(g, d, causal, window, bq, bk), g * d


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
        window: Optional[int], bq: int, bk: int, interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """q/k (B, H, D, S), v (B, S, H*D) -> o (B, S, H*D), lse (B, H, 1, S)
    f32, and o32, o before its rounding (B, S, H*D) f32, where bk < S
    (the backward's di = rowsum(dO * o32)), else None."""
    cfg, w = _cfg(q, v, causal, window, bq, bk)
    b, heads, d, s = q.shape
    g = cfg.heads
    grid = (b, heads // g, s // bq)
    o_blk = pl.BlockSpec((None, bq, w), lambda i, j, t: (i, t, j))
    o32 = [] if bk == s else [jax.ShapeDtypeStruct(v.shape, jnp.float32)]
    o, lse, *o32 = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, g, d, bq), lambda i, j, t: (i, j, 0, t)),
            pl.BlockSpec((None, g, d, s), lambda i, j, t: (i, j, 0, 0)),
            pl.BlockSpec((None, s, w), lambda i, j, t: (i, 0, j)),
        ],
        out_specs=[o_blk, pl.BlockSpec((None, g, 1, bq),
                                       lambda i, j, t: (i, j, 0, t)),
                   ] + [o_blk] * len(o32),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32),
                   ] + o32,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_attn_fwd",
    )(q, k, v)
    return o, lse, (o32[0] if o32 else None)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def bwd(q: jax.Array, k: jax.Array, v: jax.Array, do: jax.Array,
        lse: jax.Array, di: Optional[jax.Array], *, causal: bool,
        window: Optional[int], bq: int, bk: int, interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gradients (dq, dk, dv), in the layouts of (q, k, v), of the
    forward's o, from its inputs, its lse and dO; bq and bk are the
    forward's blocks.  di = rowsum(dO * o32), (B, H, 1, S) f32, is given
    where bk < S, and None where bk == S (the kernel forms it)."""
    cfg, w = _cfg(q, v, causal, window, bq, bk)
    b, heads, d, s = q.shape
    one_kv = bk == s
    assert (di is None) == one_kv, "di is given exactly where bk < S"
    g = cfg.heads
    grid = (b, heads // g, s // bk)
    qk_whole = pl.BlockSpec((None, g, d, s), lambda i, j, t: (i, j, 0, 0))
    qk_blk = pl.BlockSpec((None, g, d, bk), lambda i, j, t: (i, j, 0, t))
    v_whole = pl.BlockSpec((None, s, w), lambda i, j, t: (i, 0, j))
    v_blk = pl.BlockSpec((None, bk, w), lambda i, j, t: (i, t, j))
    row = pl.BlockSpec((None, g, 1, s), lambda i, j, t: (i, j, 0, 0))
    rows = (lse,) if one_kv else (lse, di)
    # dq is summed across kv blocks in VMEM unless one block holds all keys
    scratch = [] if one_kv else [pltpu.VMEM((g, d, s), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg),
        grid=grid,
        in_specs=[qk_whole, qk_blk, v_blk, v_whole] + [row] * len(rows),
        out_specs=[qk_whole, qk_blk, v_blk],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_attn_bwd",
    )(q, k, v, do, *rows)
