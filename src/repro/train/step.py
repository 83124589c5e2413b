"""Distributed train/serve steps: one ``shard_map`` over the full mesh.

The whole step — forward, backward, and the compressed-optimizer update
including its ``compressed_allreduce`` — runs per-rank inside a single
shard_map (check_vma=False). This is what gives the paper's exact
semantics:

  * gradients are NOT averaged over data-parallel ranks by autodiff (no dp
    collective exists in the backward pass at all);
  * the ONLY dp communication is the optimizer's own exchange — an
    uncompressed ``pmean`` in the warmup stage (== the paper's baseline
    Adam), the error-compensated compressed all_to_all/all_gather schedule
    in the compression stage (Alg. 1 / Fig. 3), or nothing at all on a
    skipped-sync ("0-bit") step;
  * tensor parallelism is explicit Megatron collectives placed by the
    model code (see repro.models.common).

The optimizer itself is pluggable: ``TrainStepConfig`` names a registered
``repro.optim`` optimizer and compressor, and the step body only ever
calls the uniform ``warmup_update`` / ``update`` interface — no
optimizer-specific branches live here (the compression-stage ``update``
is ONE path for every state layout, driven by the declared slots).
Orthogonal to the optimizer choice are:

  ``stage``     "warmup" | "compressed" (legacy values
                "compressed_zero1"/"compressed_hier" normalise onto the
                two axes below);
  ``layout``    where optimizer state lives:
                  "replicated" — m/v replicated over dp (paper layout);
                  "local"      — m/v/scale per dp rank, REQUIRED whenever
                                 the optimizer may skip syncs (local
                                 momentum diverges across dp between
                                 syncs; a replicated out-spec would
                                 silently drop it);
                  "zero1"      — v + f32 master weights dp-sharded
                                 (beyond-paper ZeRO-1 composition);
  ``topology``  "flat" | "hier" (two-level compressed allreduce across
                pods — composes with any registered optimizer).

Optimizer state is NOT spelled out here: the optimizer declares its
slots once (:meth:`repro.optim.TwoStageOptimizer.state_slots`, a tuple
of :class:`repro.state.SlotSpec`s) and this module materialises the
mesh-global zeros (:func:`init_train_state`) and ``PartitionSpec``s
(:func:`train_state_specs`) from those declarations — replicated slots
become ``(tp, L)`` / ``P("model", None)``, per-dp-rank and dp-sharded
slots gain the leading ``(*dp_sizes,)`` dims / ``P(*dp, "model",
None)``, with every length derived from the slot's extent (``d``, the
server/total chunk, the segment count, or a scalar).  Adding optimizer
state is a slot declaration, not a plumbing change.

Replicating m/v over dp is paper-faithful (DeepSpeed's 1-bit Adam does not
compose with ZeRO for the same reason: worker momentum + error state are
inherently per-worker and full-sized). The dp-sharded-state variant is a
beyond-paper extension measured in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.core import onebit_adam as OB
from repro.core.compression import padded_length
from repro.models import transformer as T
from repro.models.common import ParallelCtx
from repro.obs import trace as obs
from repro.optim import (STAT_KEYS, TwoStageOptimizer, from_config,
                         get_optimizer, segments_of)
from repro.state import (StateLayout, StateTree, init_global_state,
                         state_specs)

LAYOUTS = ("replicated", "local", "zero1")
TOPOLOGIES = ("flat", "hier")
_LEGACY_STAGES = {"compressed_zero1": ("compressed", "zero1", None),
                  "compressed_hier": ("compressed", None, "hier")}


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    optimizer: str = "onebit_adam"  # repro.optim registry name
    compressor: str = "onebit"      # repro.optim compressor registry name
    stage: str = "warmup"           # "warmup" | "compressed"
    #                                (legacy: "compressed_zero1",
    #                                 "compressed_hier" — normalised onto
    #                                 layout/topology below)
    layout: str = "replicated"      # "replicated" | "local" | "zero1"
    topology: str = "flat"          # "flat" | "hier"
    sync: bool = True               # False = 0-bit local step (requires
    #                                layout="local")
    pipeline: Any = "off"           # bucketed pipelined exchange:
    #                                "off" (serial), or an int bucket
    #                                count N (>1 overlaps cross-pod legs
    #                                with intra-pod work; repro.pipeline).
    #                                "auto" must be resolved to N by the
    #                                driver (launch.train, per --cluster)
    #                                before the step is built
    block_size: int = 4096          # compression block / padding basis
    use_kernel: Any = "off"         # fused Pallas compress path:
    #                                "off"/False (jnp), "on"/True
    #                                (kernels/onebit — requires a
    #                                compressor with has_kernel). "auto"
    #                                must be resolved by the driver
    #                                (launch.train, via the repro.perf
    #                                compute model) before steps build
    overlap_bwd: Any = "off"        # backward overlap: "off"/False keeps
    #                                the single "grads done" barrier;
    #                                "on"/True feeds the pipelined
    #                                exchange per-bucket gradient PARTS
    #                                (built from per-leaf fragments, so
    #                                each bucket depends only on its own
    #                                layers' grads) issued in ready
    #                                (reversed-bucket) order — XLA then
    #                                hides compressed comm under
    #                                backprop. Bitwise identical either
    #                                way. "auto" must be resolved by the
    #                                driver (launch.train, via the
    #                                four-stream cost model)
    opt_kwargs: Optional[dict] = None   # extra optimizer hyperparams
    comp_kwargs: Optional[dict] = None  # extra compressor kwargs
    # legacy config object; when set it defines the optimizer (onebit_adam)
    # and compressor, overriding the name fields above
    opt: Optional[OB.OneBitAdamConfig] = None
    model_axis: str = "model"
    aux_weight: float = 0.01
    seq_parallel: bool = False     # Megatron-SP residual stream (§Perf)
    accum_steps: int = 1           # gradient accumulation (microbatching):
    #                                activation/temp memory scales with the
    #                                microbatch, grads are averaged over
    #                                accum_steps before ONE optimizer step
    #                                (communication per step unchanged)

    def normalized(self) -> "TrainStepConfig":
        """Resolve legacy stage strings onto (stage, layout, topology)."""
        if self.stage in _LEGACY_STAGES:
            stage, layout, topo = _LEGACY_STAGES[self.stage]
            return dataclasses.replace(
                self, stage=stage, layout=layout or self.layout,
                topology=topo or self.topology)
        return self

    def build_optimizer(self) -> TwoStageOptimizer:
        """Materialise the registry optimizer this config names."""
        if self.opt is not None:
            o = self.opt
            return get_optimizer(
                "onebit_adam", compressor=from_config(o.compression),
                b1=o.b1, b2=o.b2, eps=o.eps,
                weight_decay=o.weight_decay,
                bias_correction=o.bias_correction,
                **(self.opt_kwargs or {}))
        comp_kwargs = dict(self.comp_kwargs or {})
        comp_kwargs.setdefault("block_size", self.block_size)
        if self.kernel_enabled:
            from repro.optim.compressors import compressor_has_kernel
            if not compressor_has_kernel(self.compressor):
                raise ValueError(
                    f"use_kernel={self.use_kernel!r}: compressor "
                    f"{self.compressor!r} has no fused Pallas path "
                    "(has_kernel=False) — use --kernels off/auto")
            comp_kwargs["use_kernel"] = True
        return get_optimizer(self.optimizer, compressor=self.compressor,
                             compressor_kwargs=comp_kwargs,
                             # the optimizer-level flag routes the WARMUP
                             # stage through kernels/fused_adam (bitwise
                             # the jnp chain; pinned in tests/test_state)
                             use_kernel=self.kernel_enabled,
                             **(self.opt_kwargs or {}))

    @property
    def kernel_enabled(self) -> bool:
        """Resolved ``use_kernel`` ("off" -> False, "on" -> True)."""
        if self.use_kernel in (None, "off", False):
            return False
        assert self.use_kernel != "auto", \
            ("use_kernel='auto' must be resolved by the driver "
             "(launch.train.resolve_schedule, via the repro.perf compute "
             "model) before building steps")
        assert self.use_kernel in ("on", True), self.use_kernel
        return True

    @property
    def n_buckets(self) -> int:
        """Effective pipeline bucket count ("off" -> 1)."""
        if self.pipeline in (None, "off"):
            return 1
        assert self.pipeline != "auto", \
            ("pipeline='auto' must be resolved to a bucket count by the "
             "driver (launch.train.resolve_pipeline) before building steps")
        n = int(self.pipeline)
        assert n >= 1, self.pipeline
        return n

    @property
    def overlap_enabled(self) -> bool:
        """Resolved ``overlap_bwd`` ("off" -> False, "on" -> True)."""
        if self.overlap_bwd in (None, "off", False):
            return False
        assert self.overlap_bwd != "auto", \
            ("overlap_bwd='auto' must be resolved by the driver "
             "(launch.train.resolve_schedule, via the four-stream "
             "pipeline cost model) before building steps")
        assert self.overlap_bwd in ("on", True), self.overlap_bwd
        return True

    @property
    def opt_block_size(self) -> int:
        if self.opt is not None:
            return self.opt.compression.block_size
        return (self.comp_kwargs or {}).get("block_size", self.block_size)


def mesh_axes(mesh: Mesh, model_axis: str = "model"):
    """(dp_axes, dp_sizes, tp) split of the mesh axes."""
    dp_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    dp_sizes = tuple(mesh.shape[a] for a in dp_axes)
    tp = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1
    return dp_axes, dp_sizes, tp


def pod_split(dp_axes, dp_sizes):
    """THE pod-axis convention, in one place: when the mesh has more
    than one dp axis, the LEADING one is the pod (cross-DCI) axis and
    the rest are intra-pod. Returns (inner_axes, outer_axes, n_inner,
    n_outer); a single-dp-axis mesh is one pod (outer empty).

    Everything that must agree on the split uses this — the step's
    hierarchical axes, the EF-state chunk sizing, and the auto-topology
    tuner's ClusterSpec (launch.train.resolve_topology)."""
    if len(dp_axes) > 1:
        n_inner = 1
        for s in dp_sizes[1:]:
            n_inner *= s
        return (tuple(dp_axes[1:]), tuple(dp_axes[:1]), n_inner,
                dp_sizes[0])
    n_inner = 1
    for s in dp_sizes:
        n_inner *= s
    return tuple(dp_axes), (), n_inner, 1


def _param_shapes(cfg: ArchConfig, tp: int):
    return jax.eval_shape(partial(T.init_params, cfg, tp=tp),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def _local_leaf_sizes(cfg: ArchConfig, tp: int):
    """Per-model-rank flat sizes of each parameter leaf, in ravel order."""
    shapes = _param_shapes(cfg, tp)
    specs = T.param_specs(cfg, "model", tp)
    sizes = []
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda s: isinstance(s, P))):
        n = 1
        for i, dim in enumerate(leaf.shape):
            ax = spec[i] if i < len(spec) else None
            n *= dim // tp if ax == "model" else dim
        sizes.append(n)
    return sizes


def _flat_dim(cfg: ArchConfig, tp: int, n_dp: int, block: int) -> int:
    """Padded per-model-rank flat parameter length."""
    return padded_length(sum(_local_leaf_sizes(cfg, tp)), max(n_dp, 1),
                         block)


def _n_segments(cfg: ArchConfig, tp: int, d_pad: int) -> int:
    sizes = _local_leaf_sizes(cfg, tp)
    return len(sizes) + (1 if d_pad > sum(sizes) else 0)


def _as_optimizer(optimizer) -> TwoStageOptimizer:
    """Resolve ``optimizer`` (instance | registry name | None) to the
    slot-declaring object; None = the base family slots (every current
    registered optimizer shares them)."""
    if optimizer is None:
        return TwoStageOptimizer()
    if isinstance(optimizer, str):
        return get_optimizer(optimizer)
    return optimizer


def state_layout_ctx(cfg: ArchConfig, mesh: Mesh,
                     model_axis: str = "model", block: int = 4096,
                     topology: str = "flat") -> StateLayout:
    """The :class:`repro.state.StateLayout` materialisation context of a
    training run: padded flat length, dp/server/pod group sizes, segment
    count — THE numbers every state consumer (init, specs, pipelined
    slot views, checkpoint canonicalisation, tuner pricing) derives
    from."""
    dp_axes, dp_sizes, tp = mesh_axes(mesh, model_axis)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    d_pad = _flat_dim(cfg, tp, n_dp, block)
    n_srv, n_outer = n_dp, 1
    if mesh_topology(mesh, topology, model_axis) == "hier":
        _, _, n_srv, n_outer = pod_split(dp_axes, dp_sizes)
    return StateLayout(d=d_pad, n_dp=n_dp, n_srv=n_srv, n_outer=n_outer,
                       n_segments=_n_segments(cfg, tp, d_pad),
                       dp_sizes=tuple(dp_sizes), tp=tp)


def mesh_topology(mesh: Mesh, topology: str = "flat",
                  model_axis: str = "model") -> str:
    """The topology the exchange runs on ``mesh``: ``hier`` needs a pod
    axis (more than one dp axis) and is ``flat`` without one."""
    dp_axes, _, _ = mesh_axes(mesh, model_axis)
    return "hier" if topology == "hier" and len(dp_axes) > 1 else "flat"


def train_slots(mesh: Mesh, model_axis: str = "model",
                layout: str = "replicated", topology: str = "flat",
                optimizer=None):
    """The optimizer's declared state slots for a run on ``mesh``."""
    return _as_optimizer(optimizer).state_slots(
        layout, mesh_topology(mesh, topology, model_axis))


def train_state_specs(mesh: Mesh, model_axis: str = "model",
                      layout: str = "replicated",
                      optimizer=None, topology: str = "flat") -> StateTree:
    """PartitionSpecs for the mesh-global optimizer state, derived from
    the optimizer's declared slots."""
    dp_axes, _, _ = mesh_axes(mesh, model_axis)
    return state_specs(train_slots(mesh, model_axis, layout, topology,
                                   optimizer), dp_axes, model_axis)


def init_train_state(cfg: ArchConfig, mesh: Mesh,
                     model_axis: str = "model", block: int = 4096,
                     abstract: bool = False, layout: str = "replicated",
                     topology: str = "flat",
                     optimizer=None) -> StateTree:
    """Mesh-global optimizer state (zeros; ``abstract=True`` ->
    ShapeDtypeStructs), built from the optimizer's declared slots.

    ``topology="hier"`` sizes the server/outer EF chunks by the INNER
    (intra-pod) dp size — the two-level compressed allreduce runs the
    paper's server stage within the pod only — and declares the outer
    ones only for an optimizer whose compressor needs them
    (``plan.schedules.needs_outer_ef``).  The padded flat length is
    always a multiple of n_dp_total * block in both topologies.
    ``layout`` selects replicated (paper) / per-dp-rank "local" /
    dp-sharded "zero1" adaptive state.
    """
    ctx = state_layout_ctx(cfg, mesh, model_axis, block, topology)
    return init_global_state(train_slots(mesh, model_axis, layout,
                                         topology, optimizer),
                             ctx, abstract=abstract)


def _ctx(mesh: Mesh, model_axis: str) -> ParallelCtx:
    dp_axes, _, tp = mesh_axes(mesh, model_axis)
    return ParallelCtx(tp_axis=model_axis if tp > 1 else None,
                       tp_size=tp, dp_axes=dp_axes)


def batch_specs(cfg: ArchConfig, shape_kind: str, dp_axes) -> Dict[str, P]:
    """Batch dim sharded over the dp super-axis; everything else replicated."""
    dp = tuple(dp_axes)
    spec: Dict[str, P] = {}
    names = {"tokens": 2, "labels": 2, "loss_mask": 2, "embeddings": 3,
             "patch_embeds": 3}
    for k, nd in names.items():
        spec[k] = P(dp, *([None] * (nd - 1)))
    return spec


def _select(spec_map: Dict[str, Any], batch: Dict[str, Any]):
    return {k: spec_map[k] for k in batch}


# --------------------------------------------------------------------------
# training step
# --------------------------------------------------------------------------

def _grad_tree(params, batch, cfg: ArchConfig, ctx: ParallelCtx,
               aux_weight: float, accum_steps: int):
    """The gradient pytree of one step (accumulation averaged in), with
    its ``(total, metrics)`` aux — NOTHING flattened yet."""
    grad_fn = jax.value_and_grad(T.loss_fn, has_aux=True)
    if accum_steps > 1:
        a = accum_steps
        micro = jax.tree.map(
            lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]),
            batch)

        def acc_body(carry, mb):
            g_acc, tot_acc, met_acc = carry
            (tot, met), g = grad_fn(params, mb, cfg, ctx, aux_weight)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            met_acc = jax.tree.map(jnp.add, met_acc, met)
            return (g_acc, tot_acc + tot, met_acc), None

        g0 = jax.tree.map(jnp.zeros_like, params)
        m0 = {"loss": 0.0, "aux": 0.0, "acc": 0.0}
        (grads, total, metrics), _ = jax.lax.scan(
            acc_body, (g0, jnp.float32(0.0),
                       jax.tree.map(jnp.float32, m0)), micro)
        grads = jax.tree.map(lambda g: g / a, grads)
        total = total / a
        metrics = jax.tree.map(lambda v: v / a, metrics)
    else:
        (total, metrics), grads = grad_fn(params, batch, cfg, ctx,
                                          aux_weight)
    return grads, total, metrics


def flat_grad_parts(grads, sizes, d_pad: int):
    """Per-bucket f32 gradient parts — the backward-overlap front end.

    ``sizes`` is the bucketer's per-bucket element counts (summing to
    ``d_pad``).  Each part is the concatenation of the RAVELED LEAF
    FRAGMENTS its element range covers (leaves in ``ravel_pytree``
    order, i.e. layer order), plus explicit zeros for any padding tail
    — so ``concatenate(parts)`` is bitwise ``flat_grads``' padded
    ravel, while part ``b`` depends ONLY on the leaves it overlaps.
    That per-bucket dependency is the whole point: fed unconcatenated
    to the pipelined exchange, a trailing bucket's compress+wire chain
    needs only the trailing layers' gradients, so XLA's scheduler can
    start it while backward still produces earlier layers."""
    leaves = [jnp.ravel(g).astype(jnp.float32)
              for g in jax.tree.leaves(grads)]
    bounds, off = [], 0
    for g in leaves:
        bounds.append((off, off + g.shape[0]))
        off += g.shape[0]
    d_r = off
    assert sum(sizes) == d_pad >= d_r, (tuple(sizes), d_pad, d_r)
    parts, lo = [], 0
    for sz in sizes:
        hi = lo + sz
        frags = [jax.lax.slice(g, (max(lo, a) - a,), (min(hi, b) - a,))
                 for (a, b), g in zip(bounds, leaves)
                 if min(hi, b) > max(lo, a)]
        n_pad = hi - max(lo, d_r)
        if n_pad > 0:
            frags.append(jnp.zeros((min(n_pad, sz),), jnp.float32))
        parts.append(frags[0] if len(frags) == 1
                     else jnp.concatenate(frags))
        lo = hi
    return tuple(parts)


def flat_grads(params, batch, cfg: ArchConfig, ctx: ParallelCtx,
               aux_weight: float, accum_steps: int, d_pad: int,
               bucket_sizes=None):
    """Per-rank flat f32 training-loss gradient padded to ``d_pad``,
    with its :class:`SegmentInfo` and the ``(total, metrics)`` aux —
    the shared front half of the train step and the
    :mod:`repro.obs.audit` probe (the probe re-runs it on the SAME
    batch, so the audited gradient is exactly the one the next step
    consumes).  Gradient accumulation averages over ``accum_steps``
    microbatches before anything is flattened.

    With ``bucket_sizes`` (backward overlap) the first return value is
    the tuple of per-bucket parts from :func:`flat_grad_parts` instead
    of one ``(d_pad,)`` vector — bitwise the same elements, without
    the whole-vector ravel every bucket would otherwise depend on."""
    grads, total, metrics = _grad_tree(params, batch, cfg, ctx,
                                       aux_weight, accum_steps)
    segs = segments_of(grads, d_pad)
    with obs.layer_scope("optimizer", "flatten"):
        if bucket_sizes is not None:
            return (flat_grad_parts(grads, bucket_sizes, d_pad), segs,
                    total, metrics)
        g_flat, _ = ravel_pytree(grads)
        d_r = g_flat.shape[0]
        g_flat = jnp.pad(g_flat.astype(jnp.float32), (0, d_pad - d_r))
    return g_flat, segs, total, metrics


def make_train_step(cfg: ArchConfig, mesh: Mesh, tsc: TrainStepConfig,
                    donate: bool = True):
    """Returns jitted fn(params, opt_state, batch, lr) -> (params, state,
    metrics). ``tsc`` names the optimizer/compressor (repro.optim
    registries) and the stage/layout/topology; the step body drives the
    uniform optimizer interface only."""
    tsc = tsc.normalized()
    assert tsc.stage in ("warmup", "compressed"), tsc.stage
    assert tsc.layout in LAYOUTS, tsc.layout
    assert tsc.topology in TOPOLOGIES, tsc.topology
    assert tsc.n_buckets >= 1  # fails fast on an unresolved "auto"
    if not tsc.sync:
        # a skipped sync leaves per-rank momentum divergent across dp;
        # replicated/zero1 out-specs would silently drop it
        assert tsc.layout == "local", \
            "sync=False (0-bit local steps) requires layout='local'"
    optimizer = tsc.build_optimizer()
    dp_axes, dp_sizes, tp = mesh_axes(mesh, tsc.model_axis)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    ctx = _ctx(mesh, tsc.model_axis)
    if tsc.seq_parallel:
        ctx = dataclasses.replace(ctx, sp=True)
    tp_axes = (tsc.model_axis,) if tp > 1 else ()
    pspecs = T.param_specs(cfg, tsc.model_axis, tp)
    osp = train_state_specs(mesh, tsc.model_axis, tsc.layout, optimizer,
                            tsc.topology)
    block = tsc.opt_block_size

    if mesh_topology(mesh, tsc.topology, tsc.model_axis) == "hier":
        inner_axes, outer_axes, _, _ = pod_split(dp_axes, dp_sizes)
    else:
        inner_axes, outer_axes = dp_axes, ()
    # padding basis: the flat vector must chunk into n_dp_total * block in
    # BOTH topologies (hier additionally sub-chunks each server chunk over
    # the outer axes — see core/comm.py); matches init_train_state
    d_pad = _flat_dim(cfg, tp, n_dp, block)

    # backward overlap: per-bucket gradient parts replace the whole-
    # vector ravel, sized by the SAME bucketer the pipelined exchange
    # lowers with (core/comm._execute) so the parts land on its buckets
    # exactly. Only a synchronous compressed pipelined exchange has
    # anything to hide comm under; everything else keeps the flat path.
    bucket_sizes = None
    if (tsc.overlap_enabled and tsc.stage == "compressed" and tsc.sync
            and tsc.n_buckets > 1):
        from repro.pipeline import Bucketer  # lazy: no cycle
        bucket_sizes = Bucketer.for_exchange(
            d_pad, n_dp, block, tsc.n_buckets).sizes

    def step(params, opt, batch, lr):
        with obs.layer_scope("optimizer", "flatten"):
            flat0, unravel = ravel_pytree(params)
        d_r = flat0.shape[0]
        g_flat, segs, total, metrics = flat_grads(
            params, batch, cfg, ctx, tsc.aux_weight, tsc.accum_steps,
            d_pad, bucket_sizes=bucket_sizes)

        # global -> per-rank views: flatten every non-scalar slot (the
        # per-rank shard of any slot is its length with singleton leads)
        with obs.layer_scope("optimizer", "flatten"):
            st = StateTree({k: (v.reshape(-1) if v.ndim else v)
                            for k, v in opt.items()})
        sharded = "master_shard" in st

        if sharded:
            x_full, st, stats = optimizer.update(
                g_flat, st, lr, dp_axes=inner_axes, pod_axes=outer_axes,
                tp_axes=tp_axes, segs=segs, sync=tsc.sync,
                n_buckets=tsc.n_buckets)
            with obs.layer_scope("optimizer", "unflatten"):
                new_params = unravel(x_full[:d_r].astype(flat0.dtype))
        else:
            with obs.layer_scope("optimizer", "flatten"):
                x = jnp.pad(flat0, (0, d_pad - d_r))
            if tsc.stage == "warmup":
                new_x, st, stats = optimizer.warmup_update(
                    g_flat, st, x, lr, dp_axes=dp_axes, tp_axes=tp_axes,
                    segs=segs)
            else:
                new_x, st, stats = optimizer.update(
                    g_flat, st, lr, x=x, dp_axes=inner_axes,
                    pod_axes=outer_axes, tp_axes=tp_axes, segs=segs,
                    sync=tsc.sync, n_buckets=tsc.n_buckets)
            with obs.layer_scope("optimizer", "unflatten"):
                new_params = unravel(new_x[:d_r])

        # per-rank -> global views, generically (scalars pass through)
        with obs.layer_scope("optimizer", "unflatten"):
            new_opt = StateTree({k: (st[k].reshape(opt[k].shape)
                                     if opt[k].ndim else st[k])
                                 for k in opt})
        # metrics: mean over dp (a no-op while replicated; the honest
        # cross-rank mean in the "local" layout); v_l1 summed over model
        # shards = the paper's fused-variance norm (Fig. 2).  Nothing
        # here but collectives: they are the exchange layer's, with the
        # optimizer's own exchange
        with obs.layer_scope("exchange", "metrics"):
            out_metrics = {k: jax.lax.pmean(v, dp_axes) if dp_axes else v
                           for k, v in metrics.items()}
            v_l1 = stats["v_l1"]
            if sharded and dp_axes:   # v sharded over dp: SUM the shards
                v_l1 = jax.lax.psum(v_l1, dp_axes)
            elif tsc.layout == "local" and dp_axes:
                v_l1 = jax.lax.pmean(v_l1, dp_axes)
            if ctx.tp_axis:
                v_l1 = jax.lax.psum(v_l1, ctx.tp_axis)
            out_metrics["v_l1"] = v_l1
            # the remaining uniform STAT_KEYS (grad/momentum/EF-residual
            # norms) are per-model-rank diagnostics: dp-meaned like the
            # loss metrics (honest across divergent local state), not
            # combined over tp (a cross-shard L2 would need the
            # squared-sum psum)
            for k, v in stats.items():
                if k != "v_l1":
                    out_metrics[k] = (jax.lax.pmean(v, dp_axes)
                                      if dp_axes else v)
            out_metrics["total"] = (jax.lax.pmean(total, dp_axes)
                                    if dp_axes else total)
        return new_params, new_opt, out_metrics

    _cache: Dict[frozenset, Any] = {}

    def build(batch_tree):
        key = frozenset(batch_tree)
        if key not in _cache:
            bspec = _select(batch_specs(cfg, "train", dp_axes), batch_tree)
            mspec = {k: P() for k in
                     ["loss", "aux", "acc", "total", *STAT_KEYS]}
            mapped = jax.shard_map(
                step, mesh=mesh,
                in_specs=(pspecs, osp, bspec, P()),
                out_specs=(pspecs, osp, mspec),
                check_vma=False)
            donate_argnums = (0, 1) if donate else ()
            _cache[key] = jax.jit(mapped, donate_argnums=donate_argnums)
        return _cache[key]

    def train_step(params, opt_state, batch, lr):
        return build(batch)(params, opt_state, batch, lr)

    # expose the pieces for lowering without real arrays (dry-run)
    train_step.build = build
    train_step.param_specs = pspecs
    train_step.opt_specs = osp
    train_step.optimizer = optimizer
    return train_step


# --------------------------------------------------------------------------
# serving steps
# --------------------------------------------------------------------------

def make_serve_step(cfg: ArchConfig, mesh: Mesh, shape: InputShape,
                    model_axis: str = "model"):
    """Prefill or decode step for the given input shape.

    decode: batch over dp when it divides (decode_32k); for long_500k
    (batch=1) full-attention KV caches are sequence-sharded over dp and
    combined flash-decoding style; SSM states / windowed ring caches are
    replicated over dp (their memory is O(1) in context length).
    Returns jitted fn + .cache_specs/.batch_specs attributes.
    """
    dp_axes, dp_sizes, tp = mesh_axes(mesh, model_axis)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    ctx = _ctx(mesh, model_axis)
    pspecs = T.param_specs(cfg, model_axis, tp)
    seq_sharded = (shape.kind == "decode"
                   and shape.global_batch < n_dp)
    seq_axes = dp_axes if seq_sharded else ()

    if shape.kind == "prefill":
        def pre(params, batch):
            logits, caches = T.prefill(params, batch, cfg, ctx)
            return logits

        _cache: Dict[frozenset, Any] = {}

        def build(batch_tree):
            key = frozenset(batch_tree)
            if key not in _cache:
                bspec = _select(batch_specs(cfg, shape.kind, dp_axes),
                                batch_tree)
                mapped = jax.shard_map(pre, mesh=mesh,
                                       in_specs=(pspecs, bspec),
                                       out_specs=P(dp_axes, model_axis),
                                       check_vma=False)
                _cache[key] = jax.jit(mapped)
            return _cache[key]

        def serve_step(params, batch):
            return build(batch)(params, batch)

        serve_step.build = build
        serve_step.param_specs = pspecs
        return serve_step

    # decode
    cspecs = T.cache_specs(cfg, model_axis, dp_axes, seq_sharded)
    nsb = T.n_superblocks(cfg)
    cspecs = jax.tree.map(lambda s: s, cspecs,
                          is_leaf=lambda s: isinstance(s, P))

    def dec(params, batch, caches, pos):
        sa = seq_axes if not cfg.window else ()
        logits, new_caches = T.decode_step(params, batch, caches, pos, cfg,
                                           ctx, seq_axes=sa)
        return logits, new_caches

    _cache: Dict[frozenset, Any] = {}

    def build(batch_tree):
        key = frozenset(batch_tree)
        if key not in _cache:
            bspec = _select(batch_specs(cfg, shape.kind, dp_axes),
                            batch_tree)
            if seq_sharded:  # batch replicated (batch < n_dp)
                bspec = jax.tree.map(
                    lambda s: P(*((None,) + tuple(s)[1:])), bspec,
                    is_leaf=lambda s: isinstance(s, P))
            logits_spec = (P(None, model_axis) if seq_sharded
                           else P(dp_axes, model_axis))
            mapped = jax.shard_map(dec, mesh=mesh,
                                   in_specs=(pspecs, bspec, cspecs, P()),
                                   out_specs=(logits_spec, cspecs),
                                   check_vma=False)
            _cache[key] = jax.jit(mapped, donate_argnums=(2,))
        return _cache[key]

    def serve_step(params, batch, caches, pos):
        return build(batch)(params, batch, caches, pos)

    serve_step.build = build
    serve_step.param_specs = pspecs
    serve_step.cache_specs = cspecs
    serve_step.seq_sharded = seq_sharded
    serve_step.init_caches = lambda batch=None, dtype=jnp.bfloat16: (
        T.init_caches(cfg, batch or shape.global_batch, shape.seq_len, tp,
                      dtype, n_dp if seq_sharded else 1))
    return serve_step
