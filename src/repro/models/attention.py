"""GQA attention with Megatron TP sharding, causal/sliding-window masks,
the Pallas flash-attention op (``kernels.flash_attn``, forward and
backward) for training and prefill, a chunked online-softmax path for
long prefill, and KV-cached decode with optional flash-decoding-style
sequence sharding over the dp axes.

Per-rank layout (tp = ctx.tp):
  wq : (d, Hq_l * hd)   column-parallel, Hq_l = padded_heads / tp
  wk : (d, Hkv_l * hd)  column-parallel over kv heads when n_kv >= tp;
  wv :                  duplicated across groups of tp/n_kv ranks otherwise
                        (grad psum'd within the group via grouped_param)
  wo : (Hq_l * hd, d)   row-parallel, closed by f_reduce

The q-to-kv head alignment is guaranteed by contiguous sharding: rank r
holds q heads [r*Hq_l, (r+1)*Hq_l) and exactly the kv heads those map to.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.flash_attn import ops as fa
from repro.models.common import (ParallelCtx, apply_rope, dense, f_reduce,
                                 g_copy, grouped_param, init_linear,
                                 rope_tables)

NEG_INF = -1e30
# "auto" takes the flash-attention kernels from this sequence length up.
# On a v5e, model forward and backward (block remat): bert-base 32 x 512
# trains 37% faster with them, but bert-large is slower at 32 x 128
# (77.3 against 68.4 ms with _sdpa) and 16 x 256 (75.7 against 74.2 ms),
# where the S x S scores are small; bert-base 128 x 128 gains 3.5%.
FLASH_MIN_SEQ = 512


def shard_dims(cfg: ArchConfig, tp: int) -> Tuple[int, int, int]:
    """(q_heads_local, kv_heads_local, kv_dup_group_size)."""
    hq = cfg.padded_heads(tp) // tp
    if cfg.n_kv_heads >= tp:
        assert cfg.n_kv_heads % tp == 0, (cfg.n_kv_heads, tp)
        return hq, cfg.n_kv_heads // tp, 1
    assert tp % cfg.n_kv_heads == 0, (cfg.n_kv_heads, tp)
    return hq, 1, tp // cfg.n_kv_heads


def init_attn(key, cfg: ArchConfig, tp: int) -> Dict[str, jax.Array]:
    """Global parameter tensors for one attention layer.

    Global kv shape is (d, tp * Hkv_l * hd): when n_kv < tp the kv heads are
    stored duplicated (head order 0,0,1,1,...) so a contiguous model-axis
    shard lands each rank its own copy.
    """
    hd = cfg.head_dim
    hq, hkv_l, rep = shard_dims(cfg, tp)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.d_model
    wk = init_linear(kk, d, cfg.n_kv_heads * hd)
    wv = init_linear(kv, d, cfg.n_kv_heads * hd)
    if rep > 1:  # duplicate kv head columns for the group layout
        wk = jnp.repeat(wk.reshape(d, cfg.n_kv_heads, hd), rep, axis=1
                        ).reshape(d, tp * hkv_l * hd)
        wv = jnp.repeat(wv.reshape(d, cfg.n_kv_heads, hd), rep, axis=1
                        ).reshape(d, tp * hkv_l * hd)
    return {
        "wq": init_linear(kq, d, tp * hq * hd),
        "wk": wk,
        "wv": wv,
        "wo": init_linear(ko, tp * hq * hd, d),
    }


def attn_param_specs(cfg: ArchConfig, axis: str) -> Dict[str, object]:
    from jax.sharding import PartitionSpec as P
    return {"wq": P(None, axis), "wk": P(None, axis), "wv": P(None, axis),
            "wo": P(axis, None)}


def _qkv(p, x, cfg: ArchConfig, ctx: ParallelCtx, positions,
         skip_gcopy: bool = False):
    """Project + rope. x: (B, S, d) -> q (B,S,Hq_l,hd), k/v (B,S,Hkv_l,hd)."""
    hd = cfg.head_dim
    hq, hkv_l, rep = shard_dims(cfg, ctx.tp)
    xin = x if skip_gcopy else g_copy(x, ctx)
    dt = x.dtype
    q = dense(xin, p["wq"].astype(dt)).reshape(*x.shape[:-1], hq, hd)
    wk = grouped_param(p["wk"], ctx, rep).astype(dt)
    wv = grouped_param(p["wv"], ctx, rep).astype(dt)
    k = dense(xin, wk).reshape(*x.shape[:-1], hkv_l, hd)
    v = dense(xin, wv).reshape(*x.shape[:-1], hkv_l, hd)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, Hkv * n_rep, hd) by head repetition."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def _causal_mask(sq: int, skv: int, q_offset, window: Optional[int],
                 causal: bool = True):
    """(sq, skv) bool mask; q position i may see kv position j."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(skv)[None, :]
    m = (kj <= qi) if causal else jnp.ones((sq, skv), bool)
    if window is not None:
        m = m & (kj > qi - window)
    return m


def _sdpa(q, k, v, mask) -> jax.Array:
    """q: (B,Sq,H,hd), k/v: (B,Skv,H,hd), mask (Sq,Skv). f32 softmax."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (hd ** 0.5)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), v)


def _sdpa_chunked(q, k, v, q_offset, window, chunk: int) -> jax.Array:
    """Online-softmax over KV chunks (flash-attention schedule in jnp).

    Memory: O(Sq * chunk) scores instead of O(Sq * Skv). Used for long
    prefill where the full score matrix would not fit HBM.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    assert skv % chunk == 0, (skv, chunk)
    nchunk = skv // chunk
    kc = k.reshape(b, nchunk, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunk, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)

    def body(carry, kv_i):
        m_prev, l_prev, o_prev, i = carry
        kb, vb = kv_i
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        s = s / (hd ** 0.5)
        qi = jnp.arange(sq)[:, None] + q_offset
        kj = jnp.arange(chunk)[None, :] + i * chunk
        msk = kj <= qi
        if window is not None:
            msk = msk & (kj > qi - window)
        s = jnp.where(msk[None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                       # (b,h,q)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        o_new = o_prev * corr[..., None] + pv
        return (m_new, l_new, o_new, i + 1), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, o, _), _ = jax.lax.scan(body, (m0, l0, o0, 0), (kc, vc))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)    # (b,sq,h,hd)


def attn_forward(p, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx,
                 return_kv: bool = False, outer: str = "tp"):
    """Training/prefill self-attention. x: (B, S, d) -> (B, S, d).

    return_kv=True additionally returns the pre-repeat (k, v) of shape
    (B, S, Hkv_l, hd) so a prefill can seed the decode cache.
    outer="none": the caller owns the boundary collectives (sequence
    parallelism) — input is already gathered/g_copy'd; output is returned
    as the PARTIAL row-parallel sum (no f_reduce).
    """
    b, s, _ = x.shape
    hq, hkv_l, _ = shard_dims(cfg, ctx.tp)
    positions = jnp.arange(s)[None, :]
    q, k0, v0 = _qkv(p, x, cfg, ctx, positions, skip_gcopy=(outer == "none"))
    n_rep = hq // hkv_l
    k, v = _repeat_kv(k0, n_rep), _repeat_kv(v0, n_rep)
    use_chunked = (cfg.attn_impl == "chunked" or
                   (cfg.attn_impl == "auto" and s > 4 * cfg.attn_chunk))
    # the Pallas kernels, forward and backward, wherever the shape fits
    # them from FLASH_MIN_SEQ to the length at which "auto" turns chunked
    if cfg.attn_impl == "pallas" or (
            cfg.attn_impl == "auto" and not use_chunked
            and s >= FLASH_MIN_SEQ and fa.supports(s, hq, cfg.head_dim)):
        o = fa.flash_attention(q, k, v, causal=cfg.causal, window=cfg.window)
    elif use_chunked and s % cfg.attn_chunk == 0 and cfg.causal:
        o = _sdpa_chunked(q, k, v, 0, cfg.window, cfg.attn_chunk)
    else:
        o = _sdpa(q, k, v, _causal_mask(s, s, 0, cfg.window, cfg.causal))
    o = o.reshape(b, s, hq * cfg.head_dim)
    out = dense(o, p["wo"].astype(x.dtype))
    if outer != "none":
        out = f_reduce(out, ctx)
    if return_kv:
        return out, (k0, v0)
    return out


# --- decode with KV cache -----------------------------------------------


def init_kv_cache(cfg: ArchConfig, batch: int, seq_len: int, tp: int,
                  dtype=jnp.bfloat16, seq_shards: int = 1
                  ) -> Dict[str, jax.Array]:
    """KV cache for one attention layer (global shapes).

    Sliding-window archs cache only the window (ring buffer) — that is the
    sub-quadratic-memory property that qualifies them for long_500k.
    seq_shards > 1 means the cache seq axis will be sharded over dp
    (flash-decoding); shapes stay global here.
    """
    _, hkv_l, _ = shard_dims(cfg, tp)
    if cfg.window:
        s = min(seq_len, cfg.window)  # ring buffer; replicated over dp
    else:
        s = ((seq_len + seq_shards - 1) // seq_shards) * seq_shards
    shape = (batch, s, tp * hkv_l, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attn(p, x: jax.Array, cache: Dict[str, jax.Array], pos: jax.Array,
                cfg: ArchConfig, ctx: ParallelCtx,
                seq_axes: Tuple[str, ...] = ()
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, d); cache k/v: (B, S_c, Hkv_l, hd) local.

    pos: () int32 — absolute position of the new token (== #valid cache
    entries). With ``seq_axes`` the cache is sharded over those dp axes
    along the sequence; partial attention is combined with the
    flash-decoding max/logsumexp psum trick.
    """
    b = x.shape[0]
    hq, hkv_l, _ = shard_dims(cfg, ctx.tp)
    hd = cfg.head_dim
    # windowed caches are small (<= window) and always replicated over dp;
    # sequence sharding is for unbounded full-attention caches only.
    assert not (cfg.window and seq_axes), "SWA caches are not seq-sharded"
    q, k_new, v_new = _qkv(p, x, cfg, ctx, pos[None, None]
                           if pos.ndim == 0 else pos)
    s_c = cache["k"].shape[1]

    n_seq = 1
    if seq_axes:
        n_seq = jax.lax.psum(1, seq_axes)

    # -- write the new kv into the cache -------------------------------------
    if cfg.window:
        slot = pos % s_c                       # ring buffer over the window
    else:
        slot = pos
    if seq_axes:
        # global slot -> (owner shard, local slot); only the owner writes.
        shard_idx = jax.lax.axis_index(seq_axes)
        owner = slot // s_c
        local_slot = slot % s_c
        write = (owner == shard_idx)
        k_upd = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype),
            (0, local_slot, 0, 0))
        v_upd = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype),
            (0, local_slot, 0, 0))
        new_cache = {"k": jnp.where(write, k_upd, cache["k"]),
                     "v": jnp.where(write, v_upd, cache["v"])}
    else:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0)),
        }

    # -- attend over the cache ------------------------------------------------
    kc = _repeat_kv(new_cache["k"], hq // hkv_l).astype(jnp.float32)
    vc = _repeat_kv(new_cache["v"], hq // hkv_l).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, hq, hd)
    s = jnp.einsum("bhd,bkhd->bhk", qf, kc) / (hd ** 0.5)

    # validity mask over cache slots (local view when seq-sharded)
    local_pos = jnp.arange(s_c)
    if seq_axes:
        shard_idx = jax.lax.axis_index(seq_axes)
        gpos = local_pos + shard_idx * s_c
    else:
        gpos = local_pos
    if cfg.window:
        valid = (gpos <= pos) if not seq_axes else (gpos % s_c <= pos)
        # ring buffer: every slot written within the last `window` steps is
        # valid once pos >= s_c; before that only slots <= pos.
        valid = jnp.where(pos >= s_c - 1, jnp.ones_like(valid), gpos <= pos)
    else:
        valid = gpos <= pos
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    if seq_axes:
        m_loc = jnp.max(s, axis=-1)                               # (b,h)
        m_glob = jax.lax.pmax(m_loc, seq_axes)
        p_ = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(p_, axis=-1)
        o_loc = jnp.einsum("bhk,bkhd->bhd", p_, vc)
        l_glob = jax.lax.psum(l_loc, seq_axes)
        o_glob = jax.lax.psum(o_loc, seq_axes)
        o = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
    else:
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhk,bkhd->bhd", w, vc)

    o = o.astype(x.dtype).reshape(b, 1, hq * hd)
    out = f_reduce(dense(o, p["wo"].astype(x.dtype)), ctx)
    return out, new_cache
