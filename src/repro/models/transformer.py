"""Model assembly: embedding, block stacks (scanned), losses, decode.

Layer stacking uses ``lax.scan`` over parameter pytrees stacked on a
leading layer axis, so the compiled HLO contains ONE block body regardless
of depth (compile time and HLO size stay bounded even for 88-layer
granite or 72-layer jamba). Hybrid (Jamba) models scan over *superblocks*
of ``attn_every`` layers (7 Mamba + 1 attention, MoE on every second
layer), dense/MoE/SSM models scan over single blocks.

All forward code runs per-rank inside shard_map; ``init_params`` builds
GLOBAL tensors and ``param_specs`` the matching PartitionSpecs, so the
same pytree drives single-device tests (tp=1, specs ignored) and the
production mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import ssm as S
from repro.models.common import (ParallelCtx, dense, f_reduce, g_copy,
                                 rep_param, rms_norm, sp_gather, sp_scatter,
                                 sp_slice, tp_rank)
from repro.obs import trace as obs

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# layer kinds within a (super)block
# --------------------------------------------------------------------------

def _superblock_layout(cfg: ArchConfig):
    """List of (mixer_kind, ffn_kind) for one scan body.

    dense/moe/audio/vlm/encoder: one block  [("attn", ...)]
    ssm:                         one block  [("ssm", None)]
    hybrid:                      attn_every blocks (Jamba superblock)
    """
    if cfg.family == "ssm":
        return [("ssm", None)]
    if cfg.family == "hybrid":
        out = []
        for i in range(cfg.attn_every):
            mixer = "attn" if cfg.is_attn_layer(i) else "ssm"
            ffn = "moe" if cfg.is_moe_layer(i) else "dense"
            out.append((mixer, ffn))
        return out
    ffn = "moe" if cfg.n_experts else "dense"
    return [("attn", ffn)]


def n_superblocks(cfg: ArchConfig) -> int:
    per = len(_superblock_layout(cfg))
    assert cfg.n_layers % per == 0, (cfg.n_layers, per)
    return cfg.n_layers // per


def _init_layer(key, cfg: ArchConfig, tp: int, mixer: str,
                ffn: Optional[str]) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), jnp.float32)}
    p["mixer"] = (A.init_attn(k1, cfg, tp) if mixer == "attn"
                  else S.init_ssm(k1, cfg, tp))
    if ffn is not None:
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ffn"] = (M.init_moe(k2, cfg, tp) if ffn == "moe"
                    else M.init_mlp(k3, cfg, tp))
    return p


def _layer_specs(cfg: ArchConfig, axis: str, mixer: str,
                 ffn: Optional[str]) -> Params:
    p: Params = {"norm1": P(None)}
    p["mixer"] = (A.attn_param_specs(cfg, axis) if mixer == "attn"
                  else S.ssm_param_specs(cfg, axis))
    if ffn is not None:
        p["norm2"] = P(None)
        p["ffn"] = (M.moe_param_specs(cfg, axis) if ffn == "moe"
                    else M.mlp_param_specs(cfg, axis))
    return p


def _layer_fwd(p: Params, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx,
               mixer: str, ffn: Optional[str]) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm residual block. Returns (x, aux).

    With ctx.sp the residual stream x is SEQUENCE-SHARDED over the model
    axis: each block boundary is an all-gather (in) / reduce-scatter (out)
    pair — half the wire bytes of the all-reduce pair it replaces, and the
    norms/residual math runs on 1/tp of the tokens.
    """
    sp = ctx.sp and ctx.tp_axis is not None
    with obs.layer_scope("model", "norm"):
        h = rms_norm(x, rep_param(p["norm1"], ctx), cfg.norm_eps)
    with obs.layer_scope("model", "attention" if mixer == "attn" else "ssm"):
        if sp:
            h_in = sp_gather(h, ctx)
            fwd = (A.attn_forward(p["mixer"], h_in, cfg, ctx, outer="none")
                   if mixer == "attn" else
                   S.ssm_forward(p["mixer"], h_in, cfg, ctx, outer="none"))
            x = x + sp_scatter(fwd, ctx)
        elif mixer == "attn":
            x = x + A.attn_forward(p["mixer"], h, cfg, ctx)
        else:
            x = x + S.ssm_forward(p["mixer"], h, cfg, ctx)
    aux = jnp.zeros((), jnp.float32)
    if ffn is not None:
        with obs.layer_scope("model", "norm"):
            h = rms_norm(x, rep_param(p["norm2"], ctx), cfg.norm_eps)
        with obs.layer_scope("model", "moe" if ffn == "moe" else "mlp"):
            if sp:
                h_in = sp_gather(h, ctx)
                if ffn == "moe":
                    y, aux = M.moe_forward(p["ffn"], h_in, cfg, ctx,
                                           outer="none", x_shard=h)
                else:
                    y = M.mlp_forward(p["ffn"], h_in, cfg, ctx,
                                      outer="none")
                y = sp_scatter(y, ctx)
            elif ffn == "moe":
                y, aux = M.moe_forward(p["ffn"], h, cfg, ctx)
            else:
                y = M.mlp_forward(p["ffn"], h, cfg, ctx)
            x = x + y
    return x, aux


# --------------------------------------------------------------------------
# init / specs
# --------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key, tp: int = 1) -> Params:
    layout = _superblock_layout(cfg)
    nsb = n_superblocks(cfg)
    k_emb, k_out, k_blocks = jax.random.split(key, 3)
    vp = cfg.padded_vocab(tp)
    d = cfg.d_model

    def init_sb(k):
        ks = jax.random.split(k, len(layout))
        return {f"l{i}": _init_layer(ks[i], cfg, tp, mx, ff)
                for i, (mx, ff) in enumerate(layout)}

    blocks = jax.vmap(init_sb)(jax.random.split(k_blocks, nsb))
    p: Params = {
        "blocks": blocks,
        "norm_f": jnp.ones((d,), jnp.float32),
        "w_out": (jax.random.normal(k_out, (d, vp)) * (d ** -0.5)
                  ).astype(jnp.float32),
    }
    if cfg.embed_kind in ("tokens", "prefix"):
        p["embed"] = (jax.random.normal(k_emb, (vp, d)) * 0.02
                      ).astype(jnp.float32)
    return p


def param_specs(cfg: ArchConfig, axis: str = "model", tp: int = 16) -> Params:
    layout = _superblock_layout(cfg)
    sb = {f"l{i}": _layer_specs(cfg, axis, mx, ff)
          for i, (mx, ff) in enumerate(layout)}
    # stacked leading superblock axis -> prepend None to every spec
    blocks = jax.tree.map(lambda s: P(*((None,) + tuple(s))), sb,
                          is_leaf=lambda s: isinstance(s, P))
    specs: Params = {
        "blocks": blocks,
        "norm_f": P(None),
        "w_out": P(None, axis),
    }
    if cfg.embed_kind in ("tokens", "prefix"):
        specs["embed"] = P(axis, None)
    return specs


# --------------------------------------------------------------------------
# embedding + vocab-parallel loss
# --------------------------------------------------------------------------

def embed_tokens(emb_local: jax.Array, ids: jax.Array, ctx: ParallelCtx,
                 dtype, reduce: bool = True) -> jax.Array:
    """Vocab-parallel embedding lookup. ids replicated, emb sharded dim 0.

    reduce=False returns the PARTIAL (this rank's vocab-shard hits only);
    under sequence parallelism the caller closes it with sp_scatter, which
    completes the vocab psum and scatters the sequence in one collective
    (Megatron-SP's fused embedding reduce-scatter).
    """
    v_l = emb_local.shape[0]
    local = ids - tp_rank(ctx) * v_l
    valid = (local >= 0) & (local < v_l)
    x = jnp.take(emb_local, jnp.clip(local, 0, v_l - 1), axis=0)
    x = jnp.where(valid[..., None], x, 0.0)
    if reduce:
        x = f_reduce(x, ctx)
    return x.astype(dtype)


def vocab_parallel_xent(x: jax.Array, w_out_local: jax.Array,
                        labels: jax.Array, mask: jax.Array,
                        cfg: ArchConfig, ctx: ParallelCtx,
                        skip_gcopy: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over vocab-parallel logits.

    x: (B, S, d) final hidden (replicated); w_out_local: (d, V_l);
    labels (B, S) int32; mask (B, S) {0,1}. Returns (mean loss, mean acc).
    Padded vocab columns are masked to -inf before the partition function.
    skip_gcopy: set when x arrived through sp_gather, whose backward
    reduce-scatter already sums the per-rank partial cotangents — adding
    g_copy's psum on top would double-count by tp.
    """
    v_l = w_out_local.shape[-1]
    xin = x if skip_gcopy else g_copy(x, ctx)
    logits = jnp.einsum("bsd,dv->bsv", xin.astype(jnp.float32),
                        w_out_local.astype(jnp.float32))
    r = tp_rank(ctx)
    gidx = jnp.arange(v_l) + r * v_l
    logits = jnp.where(gidx[None, None, :] < cfg.vocab, logits, -1e30)

    m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    m = (jax.lax.pmax(m_loc, ctx.tp_axis) if ctx.tp_axis else m_loc)
    se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    z = f_reduce(se, ctx)
    # label logit (psum of the local piece)
    local_lab = labels - r * v_l
    valid = (local_lab >= 0) & (local_lab < v_l)
    ll = jnp.take_along_axis(
        logits, jnp.clip(local_lab, 0, v_l - 1)[..., None], axis=-1)[..., 0]
    ll = f_reduce(jnp.where(valid, ll, 0.0), ctx)
    nll = jnp.log(z) + m - ll
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    # accuracy (greedy): global argmax via max-trick
    best_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    best = jax.lax.pmax(best_loc, ctx.tp_axis) if ctx.tp_axis else best_loc
    correct = (jnp.abs(jax.lax.stop_gradient(ll) - best) < 1e-6) & (mask > 0)
    acc = jnp.sum(correct) / denom
    return loss, acc


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _inputs_to_h0(params: Params, batch: Dict[str, jax.Array],
                  cfg: ArchConfig, ctx: ParallelCtx, dtype,
                  sp: bool = False) -> jax.Array:
    """Map the modality inputs to the initial hidden states (B, S, d).

    sp=True: return only this rank's sequence chunk (B, S/tp, d).
    Vocab-parallel lookups produce PARTIAL full-sequence activations that
    sp_scatter then reduces (completing the vocab psum) and scatters along
    the sequence in ONE collective — slicing ids per rank first would make
    the vocab psum mix different ranks' token chunks.
    """
    if cfg.embed_kind == "tokens":
        if sp:
            part = embed_tokens(params["embed"], batch["tokens"], ctx,
                                dtype, reduce=False)
            return sp_scatter(part, ctx)
        return embed_tokens(params["embed"], batch["tokens"], ctx, dtype)
    if cfg.embed_kind == "embeddings":      # audio stub: frames are given
        h = batch["embeddings"].astype(dtype)
        return sp_slice(h, ctx) if sp else h
    if cfg.embed_kind == "prefix":          # VLM stub: patch prefix + text
        if sp:
            txt = embed_tokens(params["embed"], batch["tokens"], ctx,
                               dtype, reduce=False)
            # patches are replicated: pre-divide by tp so the scatter's
            # sum restores them exactly (tp is a power of two)
            patch = (batch["patch_embeds"].astype(jnp.float32)
                     / ctx.tp_size).astype(dtype)
            return sp_scatter(jnp.concatenate([patch, txt], axis=1), ctx)
        txt = embed_tokens(params["embed"], batch["tokens"], ctx, dtype)
        return jnp.concatenate(
            [batch["patch_embeds"].astype(dtype), txt], axis=1)
    raise ValueError(cfg.embed_kind)


def _run_blocks(params: Params, h: jax.Array, cfg: ArchConfig,
                ctx: ParallelCtx) -> Tuple[jax.Array, jax.Array]:
    layout = _superblock_layout(cfg)

    def sb_body(x, sb_params):
        aux = jnp.zeros((), jnp.float32)
        for i, (mx, ff) in enumerate(layout):
            x, a = _layer_fwd(sb_params[f"l{i}"], x, cfg, ctx, mx, ff)
            aux = aux + a
        return x, aux

    if cfg.remat:
        if cfg.remat_policy == "dots":
            pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            body = jax.checkpoint(sb_body, policy=pol)
        else:
            body = jax.checkpoint(sb_body)
    else:
        body = sb_body

    def scan_fn(x, sbp):
        return body(x, sbp)

    h, auxs = jax.lax.scan(scan_fn, h, params["blocks"])
    return h, jnp.sum(auxs)


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            ctx: ParallelCtx, aux_weight: float = 0.01
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Training loss (local to this rank's batch shard; replicated over tp).
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    sp = ctx.sp and ctx.tp_axis is not None
    with obs.layer_scope("model", "embed"):
        h = _inputs_to_h0(params, batch, cfg, ctx, dtype, sp=sp)
    h, aux = _run_blocks(params, h, cfg, ctx)
    with obs.layer_scope("model", "norm"):
        h = rms_norm(h, rep_param(params["norm_f"], ctx), cfg.norm_eps)
    with obs.layer_scope("model", "head"):
        if sp:
            # LM head stays vocab-parallel: gather the (norm'd) hiddens
            # back to the full sequence (Megatron-SP's final gather)
            h = sp_gather(h, ctx)
        labels = batch["labels"]
        if cfg.embed_kind == "prefix":
            h = h[:, -labels.shape[1]:, :]  # loss over text positions only
        mask = batch.get("loss_mask", jnp.ones(labels.shape, jnp.float32))
        loss, acc = vocab_parallel_xent(h, params["w_out"], labels, mask,
                                        cfg, ctx, skip_gcopy=sp)
        total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux, "acc": acc}


def prefill(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            ctx: ParallelCtx, cache_len: Optional[int] = None
            ) -> Tuple[jax.Array, Any]:
    """Prefill forward: returns last-position logits (B, V_l local) and the
    decode caches (stacked per superblock) seeded from the sequence.

    cache_len: total KV-cache capacity (>= prompt length) so subsequent
    decode steps have slots to append into; ignored for windowed (ring)
    caches and SSM state, which are fixed-size by construction.
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    h = _inputs_to_h0(params, batch, cfg, ctx, dtype)
    layout = _superblock_layout(cfg)
    s = h.shape[1]

    def sb_body(x, sb_params):
        caches = {}
        for i, (mx, ff) in enumerate(layout):
            p = sb_params[f"l{i}"]
            hn = rms_norm(x, rep_param(p["norm1"], ctx), cfg.norm_eps)
            if mx == "attn":
                y, (k, v) = A.attn_forward(p["mixer"], hn, cfg, ctx,
                                           return_kv=True)
                if cfg.window and s > cfg.window:
                    w = cfg.window
                    pos = jnp.arange(s - w, s)
                    k = jnp.zeros_like(k[:, :w]).at[:, pos % w].set(
                        k[:, s - w:])
                    v = jnp.zeros_like(v[:, :w]).at[:, pos % w].set(
                        v[:, s - w:])
                elif cache_len is not None and cache_len > s:
                    pad = ((0, 0), (0, cache_len - s), (0, 0), (0, 0))
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                caches[f"l{i}"] = {"k": k, "v": v}
            else:
                y, st = S.ssm_forward(p["mixer"], hn, cfg, ctx,
                                      return_state=True)
                caches[f"l{i}"] = st
            x = x + y
            if ff is not None:
                hn = rms_norm(x, rep_param(p["norm2"], ctx), cfg.norm_eps)
                if ff == "moe":
                    y, _ = M.moe_forward(p["ffn"], hn, cfg, ctx)
                else:
                    y = M.mlp_forward(p["ffn"], hn, cfg, ctx)
                x = x + y
        return x, caches

    h, caches = jax.lax.scan(sb_body, h, params["blocks"])
    h = rms_norm(h, rep_param(params["norm_f"], ctx), cfg.norm_eps)
    xin = g_copy(h[:, -1, :], ctx)
    logits = dense(xin, params["w_out"].astype(dtype))
    return logits, caches


def init_caches(cfg: ArchConfig, batch: int, seq_len: int, tp: int,
                dtype=jnp.bfloat16, seq_shards: int = 1) -> Any:
    """Decode caches, stacked per superblock (global shapes)."""
    layout = _superblock_layout(cfg)
    nsb = n_superblocks(cfg)

    def one_sb():
        c = {}
        for i, (mx, _) in enumerate(layout):
            if mx == "attn":
                c[f"l{i}"] = A.init_kv_cache(cfg, batch, seq_len, tp, dtype,
                                             seq_shards)
            else:
                c[f"l{i}"] = S.init_ssm_cache(cfg, batch, tp, dtype)
        return c

    sb = one_sb()
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (nsb,) + x.shape), sb)


def cache_specs(cfg: ArchConfig, axis: str, dp_axes, seq_sharded: bool
                ) -> Any:
    """PartitionSpecs for the decode caches.

    Attention KV: (nsb, B, S, H_kv_l, hd) — batch over dp (or seq over dp
    when seq_sharded, for long_500k flash-decoding), heads over model.
    SSM state: (nsb, B, di, N) — batch over dp, channels over model.
    """
    layout = _superblock_layout(cfg)
    dp = tuple(dp_axes) if not isinstance(dp_axes, str) else (dp_axes,)
    c = {}
    for i, (mx, _) in enumerate(layout):
        if mx == "attn":
            if cfg.window:
                # windowed ring caches are replicated over dp when batch
                # cannot be sharded (long_500k b=1); batch-shard otherwise
                bspec = dp if not seq_sharded else None
                c[f"l{i}"] = {"k": P(None, bspec, None, axis, None),
                              "v": P(None, bspec, None, axis, None)}
            elif seq_sharded:
                c[f"l{i}"] = {"k": P(None, None, dp, axis, None),
                              "v": P(None, None, dp, axis, None)}
            else:
                c[f"l{i}"] = {"k": P(None, dp, None, axis, None),
                              "v": P(None, dp, None, axis, None)}
        else:
            bspec = dp if not seq_sharded else None
            c[f"l{i}"] = {"h": P(None, bspec, axis, None),
                          "conv": P(None, bspec, None, axis)}
    return c


def decode_step(params: Params, batch: Dict[str, jax.Array], caches: Any,
                pos: jax.Array, cfg: ArchConfig, ctx: ParallelCtx,
                seq_axes: Tuple[str, ...] = ()
                ) -> Tuple[jax.Array, Any]:
    """One decode step: one new token per sequence against the caches.

    batch: {"tokens": (B, 1)} or {"embeddings": (B, 1, d)}.
    Returns (logits (B, V_l) local vocab shard, new caches).
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.embed_kind == "tokens" or cfg.embed_kind == "prefix":
        h = embed_tokens(params["embed"], batch["tokens"], ctx, dtype)
    else:
        h = batch["embeddings"].astype(dtype)
    layout = _superblock_layout(cfg)

    def sb_body(x, pc):
        sb_params, sb_cache = pc
        new_cache = {}
        for i, (mx, ff) in enumerate(layout):
            p = sb_params[f"l{i}"]
            hn = rms_norm(x, rep_param(p["norm1"], ctx), cfg.norm_eps)
            if mx == "attn":
                y, nc = A.decode_attn(p["mixer"], hn, sb_cache[f"l{i}"],
                                      pos, cfg, ctx, seq_axes)
            else:
                y, nc = S.decode_ssm(p["mixer"], hn, sb_cache[f"l{i}"],
                                     cfg, ctx)
            new_cache[f"l{i}"] = nc
            x = x + y
            if ff is not None:
                hn = rms_norm(x, rep_param(p["norm2"], ctx), cfg.norm_eps)
                if ff == "moe":
                    y, _ = M.moe_forward(p["ffn"], hn, cfg, ctx)
                else:
                    y = M.mlp_forward(p["ffn"], hn, cfg, ctx)
                x = x + y
        return x, new_cache

    h, new_caches = jax.lax.scan(sb_body, h, (params["blocks"], caches))
    h = rms_norm(h, rep_param(params["norm_f"], ctx), cfg.norm_eps)
    xin = g_copy(h[:, -1, :], ctx)
    logits = dense(xin, params["w_out"].astype(dtype))
    return logits, new_caches
