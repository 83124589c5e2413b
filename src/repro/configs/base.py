"""Architecture + input-shape configuration.

Every assigned architecture registers an ``ArchConfig`` with its exact
published dimensions (source cited in the module docstring of each config
file). ``reduced()`` derives the CPU-smoke variant (<=2 layers,
d_model<=512, <=4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm", "encoder")


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # one of FAMILIES
    n_layers: int
    d_model: int
    n_heads: int                   # query heads (0 for attention-free SSM)
    n_kv_heads: int
    d_ff: int
    vocab: int
    # MoE
    n_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1             # MoE FFN every k-th layer (Jamba: 2)
    capacity_factor: float = 1.25
    # "einsum": one-hot dispatch matmuls (2*t*cap*d FLOPs — MXU friendly
    #           but dominates MoE compute at large t);
    # "gather": take/scatter-add dispatch (memory-bound, no dot FLOPs)
    moe_dispatch: str = "einsum"
    # SSM (Mamba-1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    # hybrid (Jamba): one attention layer per `attn_every` layers
    attn_every: int = 0
    # attention flavour
    window: Optional[int] = None   # sliding-window size (Mixtral: 4096)
    rope_theta: float = 10_000.0
    causal: bool = True            # False for encoder-only (BERT)
    mlp_kind: str = "swiglu"       # "swiglu" | "gelu"
    # input modality: "tokens" (LM), "embeddings" (audio stub),
    # "prefix" (VLM stub: patch-embedding prefix + text tokens)
    embed_kind: str = "tokens"
    n_prefix: int = 256            # VLM: patch embeddings per sample
    # numerics / memory policy
    norm_eps: float = 1e-5
    compute_dtype: str = "bfloat16"
    remat: bool = True             # activation-checkpoint each block
    # "block": recompute everything inside the block on backward (min mem)
    # "dots":  jax.checkpoint_policies.dots_with_no_batch_dims_saveable —
    #          matmul outputs are saved, elementwise ops recomputed
    #          (trades memory for ~25% fewer backward FLOPs)
    remat_policy: str = "block"
    attn_chunk: int = 2048         # KV chunk for the online-softmax path
    # "full" | "chunked" | "pallas" (the flash-attention kernels) | "auto"
    # (the kernels from sequence 512 where the shape fits them, else full
    # or chunked; models/attention.FLASH_MIN_SEQ)
    attn_impl: str = "auto"
    source: str = ""               # citation

    def __post_init__(self):
        assert self.family in FAMILIES, self.family
        if self.n_heads:
            assert self.d_model % self.n_heads == 0

    # --- derived ------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(math.ceil(self.d_model / 16), 1)

    def padded_heads(self, tp: int) -> int:
        """Query heads padded up to a multiple of tp (llama3.2: 24->32)."""
        if not self.n_heads:
            return 0
        return ((self.n_heads + tp - 1) // tp) * tp

    def padded_vocab(self, tp: int) -> int:
        q = 8 * tp  # keep byte-alignment for the vocab-parallel shard
        return ((self.vocab + q - 1) // q) * q

    def is_attn_layer(self, i: int) -> bool:
        """Hybrid layout: within each attn_every-block, the middle layer is
        attention (Jamba: 1 attn per 8 layers), everything else Mamba."""
        if self.family != "hybrid":
            return self.n_heads > 0
        return (i % self.attn_every) == self.attn_every // 2

    def is_moe_layer(self, i: int) -> bool:
        return self.n_experts > 0 and (i % self.moe_every) == self.moe_every - 1

    @property
    def supports_long_decode(self) -> bool:
        """True if decode over a 500k context is sub-quadratic-memory:
        SSM/hybrid state or a sliding window bound the live KV."""
        return (self.family in ("ssm", "hybrid") or self.window is not None)

    def param_count(self, tp: int = 1) -> int:
        """Approximate global parameter count (exact to init, incl. pads)."""
        from repro.models import transformer
        shapes = jax.eval_shape(
            lambda k: transformer.init_params(self, k, tp=tp),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))

    def active_param_count(self, tp: int = 1) -> int:
        """Params touched per token (MoE: only top_k experts active)."""
        total = self.param_count(tp)
        if not self.n_experts:
            return total
        n_moe = sum(self.is_moe_layer(i) for i in range(self.n_layers))
        expert_params = n_moe * self.n_experts * 3 * self.d_model * self.d_ff
        active = n_moe * self.moe_top_k * 3 * self.d_model * self.d_ff
        return total - expert_params + active

    # --- reduced smoke variant ----------------------------------------------
    def reduced(self) -> "ArchConfig":
        n_heads = min(self.n_heads, 4) if self.n_heads else 0
        d_model = 256
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=2 if self.family != "hybrid" else self.attn_every,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=min(self.n_kv_heads, max(n_heads // 2, 1)),
            d_ff=512,
            vocab=512,
            n_experts=min(self.n_experts, 4),
            moe_top_k=min(self.moe_top_k, 2),
            n_prefix=16,
            window=min(self.window, 64) if self.window else None,
            compute_dtype="float32",
            attn_chunk=64,
        )


@dataclasses.dataclass(frozen=True)
class OptimSpec:
    """Named training recipe: which ``repro.optim`` optimizer/compressor/
    switch policy to run, selected by config name instead of code edits.

    ``optimizer`` / ``compressor`` are registry names
    (``repro.optim.list_optimizers()`` / ``list_compressors()``);
    ``switch_mode`` is "steps" (manual T_w) or "auto" (the paper's
    Sec. 7.1 variance-ratio freeze rule).
    """

    name: str = "onebit_adam"
    optimizer: str = "onebit_adam"
    compressor: str = "onebit"
    block_size: int = 4096
    switch_mode: str = "steps"           # "steps" | "auto"
    var_freeze_threshold: float = 0.96   # auto-mode ratio threshold
    optimizer_kwargs: Optional[dict] = None
    compressor_kwargs: Optional[dict] = None
    # collective-schedule topology: "flat" | "hier" | "auto" ("auto" lets
    # repro.plan.tune pick per cluster — see launch.train --cluster)
    topology: str = "flat"
    # bucketed pipelined exchange (repro.pipeline): "off", a bucket
    # count N, or "auto" (repro.plan.tune searches the bucket count for
    # the described cluster; resolved by launch.train)
    pipeline: object = "off"
    # fused Pallas compress path (kernels/onebit): "off", "on", or
    # "auto" (the repro.perf compute model decides — pallas wins where
    # the exchange is HBM/launch-bound on the described device)
    use_kernel: object = "off"


_OPTIM_RECIPES: Dict[str, OptimSpec] = {}


def register_optim_recipe(spec: OptimSpec) -> OptimSpec:
    _OPTIM_RECIPES[spec.name] = spec
    return spec


def get_optim_recipe(name: str) -> OptimSpec:
    if name not in _OPTIM_RECIPES:
        raise KeyError(f"unknown optim recipe {name!r}; "
                       f"registered: {sorted(_OPTIM_RECIPES)}")
    return _OPTIM_RECIPES[name]


def list_optim_recipes():
    return sorted(_OPTIM_RECIPES)


# the shipped recipes: one per registered optimizer, plus the paper's
# ablations (32-bit identity schedule, EF top-k) and the auto-warmup rule
for _spec in (
    OptimSpec(name="onebit_adam"),
    OptimSpec(name="onebit_adam_auto", switch_mode="auto"),
    OptimSpec(name="onebit_adam_32bit", compressor="identity"),
    OptimSpec(name="onebit_adam_topk", compressor="topk"),
    OptimSpec(name="zerone_adam", optimizer="zerone_adam",
              optimizer_kwargs={"var_update_interval": 16,
                                "var_freeze_step": 1000,
                                "sync_double_every": 0}),
    OptimSpec(name="zerone_adam_local", optimizer="zerone_adam",
              optimizer_kwargs={"var_update_interval": 16,
                                "var_freeze_step": 1000,
                                "sync_base_interval": 1,
                                "sync_double_every": 64,
                                "sync_max_interval": 4}),
    OptimSpec(name="onebit_lamb", optimizer="onebit_lamb"),
    # schedule topology picked by the repro.plan auto-tuner for the
    # --cluster the driver is told about (flat on uniform fabrics, hier
    # when cross-pod bandwidth is the bottleneck)
    OptimSpec(name="onebit_adam_autotopo", topology="auto"),
    # ...and the bucket count searched alongside: overlap the cross-pod
    # (DCI) legs with the next bucket's compress + intra-pod work
    OptimSpec(name="onebit_adam_pipelined", topology="auto",
              pipeline="auto"),
):
    register_optim_recipe(_spec)


_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    if name.endswith("-smoke"):
        return get_config(name[:-len("-smoke")]).reduced()
    return _REGISTRY[name]


def list_archs():
    return sorted(_REGISTRY)


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation — dry-run pattern)
# --------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one global step of the given input shape.

    train/prefill: full sequences; decode: ONE new token per sequence
    (the KV/SSM caches are separate arguments, see transformer.init_caches).
    [audio]/[vlm] carve-out: the modality frontend is stubbed — the specs
    carry precomputed frame/patch embeddings of the right shape.
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    emb_dt = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "decode":
        if cfg.embed_kind == "embeddings":
            return {"embeddings": jax.ShapeDtypeStruct((b, 1, cfg.d_model),
                                                       emb_dt)}
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    # train / prefill
    if cfg.embed_kind == "embeddings":
        specs = {"embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                    emb_dt),
                 "labels": jax.ShapeDtypeStruct((b, s), i32)}
    elif cfg.embed_kind == "prefix":
        st = s - cfg.n_prefix
        specs = {"tokens": jax.ShapeDtypeStruct((b, st), i32),
                 "patch_embeds": jax.ShapeDtypeStruct(
                     (b, cfg.n_prefix, cfg.d_model), emb_dt),
                 "labels": jax.ShapeDtypeStruct((b, st), i32)}
    else:
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                 "labels": jax.ShapeDtypeStruct((b, s), i32)}
    if shape.kind == "prefill":
        specs.pop("labels")
    return specs
