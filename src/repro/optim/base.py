"""Two-stage compressed-optimizer interface and registry.

Every optimizer in the family (1-bit Adam, 0/1 Adam, 1-bit LAMB, ...)
shares one shape of algorithm:

  * **warmup stage** — an uncompressed adaptive step on the dp-mean
    gradient while the second moment ``v`` is tracked;
  * **compression stage** — ``v`` (effectively) frozen, local momentum
    reduced across dp via the error-compensated compressed allreduce, the
    model updated by preconditioned momentum SGD.

The base class implements that skeleton once — including the ZeRO-1
(dp-sharded state) layout and the hierarchical (two-level) topology —
and exposes four small hooks where the algorithms differ:

  ``_update_v``        variance behaviour in the compression stage
                       (frozen by default; 0/1 Adam updates on a schedule)
  ``_update_scale``    per-segment scaling state (1-bit LAMB freezes the
                       layerwise trust ratios here)
  ``_scale_per_elem``  how the scaling state multiplies the update
  ``_warmup_direction``direction shaping in warmup (LAMB trust ratio)

plus one host-side hook, ``sync_due(step)``, for optimizers that skip
synchronisation entirely on some steps (0/1 Adam's "0-bit" local steps).

State is DECLARED, not hand-built: :meth:`TwoStageOptimizer.state_slots`
names every slot once as a :class:`repro.state.SlotSpec` (extent x
replication x dtype), and the ``repro.state`` machinery materialises the
per-rank zeros (:meth:`init_state`), the mesh-global shapes and
PartitionSpecs (``repro.train.step``), the per-bucket views of the
pipelined executor, and the checkpoint zeros/migration templates from
those declarations.  One generic :class:`repro.state.StateTree` carries
every layout — the ``replicated``/``local`` layouts hold ``v``
per-param, the ``zero1`` layout declares ``v_shard``/``master_shard``
dp-sharded chunks instead, and ONE :meth:`update` path branches on
which slots the state declares rather than on a layout enum.  A new
optimizer that needs extra state (e.g. per-worker drift params for a
true-local 0/1 Adam) overrides ``state_slots`` and declares it — no
plumbing.

Per-layer information travels as a :class:`SegmentInfo` (the
``ravel_pytree`` leaf boundaries), so layerwise optimizers work on the
same flat vectors as elementwise ones.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm
from repro.obs import trace as obs
from repro.optim.compressors import Compressor, OneBitCompressor
from repro.plan.schedules import needs_outer_ef
from repro.state import (SlotSpec, StateLayout, StateTree, ef_errs,
                         init_rank_state)

LAYOUTS = ("replicated", "local", "zero1")

# every update path (warmup / compressed sync / 0-bit local) emits this
# SAME stat set, so the shard_map out-specs and the telemetry schema are
# one fixed list regardless of stage (repro.train.step, repro.obs).
# Per-model-rank scalars: the paper's fused-variance L1 norm (Fig. 2),
# the grad/momentum L2 norms, and the two EF-residual L2 norms.
STAT_KEYS = ("v_l1", "grad_norm", "momentum_norm", "worker_err_norm",
             "server_err_norm")

# the audit probe's stat set (repro.obs.audit): per-segment vectors of
# length SegmentInfo.n, then whole-model scalars.  Fixed lists for the
# same reason as STAT_KEYS — the probe's shard_map out-specs and the
# ``fidelity`` event schema are derived from them; optimizers may append
# per-family extras via ``audit_extra_keys`` / ``_audit_extra``.
AUDIT_SEG_KEYS = ("cos_sim", "sign_agree", "v_drift", "v_l1_seg",
                  "worker_err_seg", "server_err_seg")
AUDIT_SCALAR_KEYS = ("v_ratio", "grad_norm", "momentum_norm",
                     "worker_err_norm", "server_err_norm", "v_live")


@dataclasses.dataclass(frozen=True)
class SegmentInfo:
    """Per-layer segment boundaries of the flat parameter vector.

    ``sizes`` are the ``ravel_pytree`` leaf sizes in flattening order; the
    final entry is the zero-padding tail (its own segment so layerwise
    statistics never mix with padding).
    """

    sizes: Tuple[int, ...]

    @property
    def n(self) -> int:
        return len(self.sizes)

    @property
    def d(self) -> int:
        return sum(self.sizes)

    def ids(self) -> jax.Array:
        # the np array is cached; the jnp lift happens per-trace (a cached
        # device array would leak tracers across jit traces)
        return jnp.asarray(_segment_ids_np(self.sizes))


@functools.lru_cache(maxsize=64)
def _segment_ids_np(sizes: Tuple[int, ...]) -> np.ndarray:
    return np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)


def segments_of(tree, d_pad: Optional[int] = None) -> SegmentInfo:
    """SegmentInfo for a (per-rank) parameter pytree, with the padding to
    ``d_pad`` appended as a trailing segment."""
    sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(tree)]
    d = sum(sizes)
    if d_pad is not None and d_pad > d:
        sizes.append(d_pad - d)
    return SegmentInfo(tuple(sizes))


def segment_norms(x: jax.Array, seg_ids: jax.Array, n_segments: int,
                  axes: Sequence[str] = ()) -> jax.Array:
    """Per-segment L2 norms of a flat (possibly sharded) vector; squared
    sums are psummed over ``axes`` before the sqrt so sharded layouts get
    the global norm."""
    sq = jax.ops.segment_sum(jnp.square(x), seg_ids,
                             num_segments=n_segments)
    if axes:
        sq = jax.lax.psum(sq, tuple(axes))
    return jnp.sqrt(sq)


def segment_l1(x: jax.Array, seg_ids: jax.Array, n_segments: int,
               axes: Sequence[str] = ()) -> jax.Array:
    """Per-segment L1 mass (the per-layer slice of the paper's fused
    ``||v||_1``); partial sums are psummed over ``axes`` so sharded
    vectors get the global value."""
    s = jax.ops.segment_sum(jnp.abs(x), seg_ids, num_segments=n_segments)
    if axes:
        s = jax.lax.psum(s, tuple(axes))
    return s


def segment_cosine(a: jax.Array, b: jax.Array, seg_ids: jax.Array,
                   n_segments: int, axes: Sequence[str] = ()
                   ) -> jax.Array:
    """Per-segment cosine similarity ``<a,b> / (||a|| ||b||)``; the
    three inner products are psummed over ``axes`` before the division,
    so sharded vectors get the global similarity.  Segments where either
    side is all-zero report 1.0 (nothing was lost)."""
    def seg(x):
        return jax.ops.segment_sum(x, seg_ids, num_segments=n_segments)
    dots, na, nb = seg(a * b), seg(jnp.square(a)), seg(jnp.square(b))
    if axes:
        ax = tuple(axes)
        dots, na, nb = (jax.lax.psum(s, ax) for s in (dots, na, nb))
    denom = jnp.sqrt(na * nb)
    return jnp.where(denom > 0.0, dots / jnp.maximum(denom, 1e-30), 1.0)


def segment_sign_agreement(a: jax.Array, b: jax.Array,
                           seg_ids: jax.Array, n_segments: int,
                           axes: Sequence[str] = ()) -> jax.Array:
    """Per-segment fraction of coordinates where ``sign(a) == sign(b)``
    (the quantity 1-bit compression preserves by construction when EF is
    healthy); counts are psummed over ``axes``.  Empty segments report
    1.0."""
    agree = (jnp.sign(a) == jnp.sign(b)).astype(jnp.float32)
    num = jax.ops.segment_sum(agree, seg_ids, num_segments=n_segments)
    cnt = jax.ops.segment_sum(jnp.ones_like(agree), seg_ids,
                              num_segments=n_segments)
    if axes:
        ax = tuple(axes)
        num, cnt = jax.lax.psum(num, ax), jax.lax.psum(cnt, ax)
    return jnp.where(cnt > 0.0, num / jnp.maximum(cnt, 1.0), 1.0)


@dataclasses.dataclass(frozen=True)
class TwoStageOptimizer:
    """Base: exactly 1-bit Adam (Alg. 1) unless a hook is overridden."""

    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    bias_correction: bool = False       # BertAdam disables it (paper setup)
    compressor: Compressor = OneBitCompressor()
    use_kernel: bool = False            # fused Pallas warmup Adam update
    #                                     (kernels/fused_adam; the
    #                                     compressor carries its own flag)

    name: str = "?"

    # --- declared state ----------------------------------------------------
    def state_slots(self, layout: str = "replicated",
                    topology: str = "flat") -> Tuple[SlotSpec, ...]:
        """The optimizer family's state, declared once (repro.state).

        ``layout`` selects the replication of the adaptive state:
        ``replicated`` (paper), ``local`` (per-dp-rank m/v/scale —
        required when ``sync_due`` can skip), ``zero1`` (``v`` + f32
        master weights dp-sharded).  EF slots are identical across
        layouts: error state is inherently per-worker.  ``topology``
        (``flat`` or ``hier``, the exchange the state serves) adds the
        cross-pod EF slots where that schedule consumes them.
        Optimizers with extra state override this and append their
        slots.
        """
        assert layout in LAYOUTS, layout
        assert topology in ("flat", "hier"), topology
        adaptive = "per_dp_rank" if layout == "local" else "replicated"
        slots = [SlotSpec("m", "per_param", "replicated"
                          if layout != "local" else "per_dp_rank")]
        if layout == "zero1":
            slots += [SlotSpec("v_shard", "per_chunk", "dp_sharded",
                               chunk_of="dp"),
                      SlotSpec("master_shard", "per_chunk", "dp_sharded",
                               chunk_of="dp")]
        else:
            slots += [SlotSpec("v", "per_param", adaptive)]
        slots += [
            SlotSpec("worker_err", "per_param", "per_dp_rank",
                     ef="worker"),
            SlotSpec("server_err", "per_chunk", "per_dp_rank",
                     chunk_of="server", ef="server", bucket_keyed=True),
            SlotSpec("scale", "per_segment", adaptive),
            SlotSpec("count", "scalar", dtype="int32"),
            SlotSpec("v_step", "scalar", dtype="int32"),
        ]
        if topology == "hier" and needs_outer_ef(self.compressor):
            # the hierarchical schedule's cross-pod EF loops, which only
            # sparse compressors run (plan.schedules.hier_schedule);
            # checkpoints move between schemas by slot diff
            # (state.checkpoint)
            slots += [
                SlotSpec("outer_err", "per_chunk", "per_dp_rank",
                         chunk_of="server", ef="outer", bucket_keyed=True),
                SlotSpec("outer_ag_err", "per_chunk", "per_dp_rank",
                         chunk_of="total", ef="outer_ag",
                         bucket_keyed=True),
            ]
        return tuple(slots)

    def init_state(self, d: int, n_dp: int = 1, n_segments: int = 1,
                   n_inner: Optional[int] = None,
                   layout: str = "replicated") -> StateTree:
        """Zeros per-rank state for a ``d``-element exchange over
        ``n_dp`` ranks, built from :meth:`state_slots`.

        For the HIERARCHICAL topology pass ``n_inner`` (the intra-pod dp
        size): the server/outer EF chunks then follow the two-level
        schedule's groups.  ``repro.train.step`` materialises the
        mesh-GLOBAL state from the same declarations."""
        n = max(n_dp, 1)
        n_srv = max(n_inner or n, 1)
        ctx = StateLayout(d=d, n_dp=n, n_srv=n_srv,
                          n_outer=max(n // n_srv, 1),
                          n_segments=max(n_segments, 1))
        topology = "flat" if n_inner is None else "hier"
        return init_rank_state(self.state_slots(layout, topology), ctx)

    @staticmethod
    def _stats(v_l1, grad_norm, momentum_norm, state=None,
               worker_err=None, server_err=None) -> dict:
        """The uniform :data:`STAT_KEYS` dict.  EF-residual norms come
        from the freshly produced errs when given, else from ``state``
        (warmup / 0-bit steps, where the slots are carried unchanged)."""
        we = worker_err if worker_err is not None else state.worker_err
        se = server_err if server_err is not None else state.server_err
        return {"v_l1": v_l1, "grad_norm": grad_norm,
                "momentum_norm": momentum_norm,
                "worker_err_norm": jnp.linalg.norm(we),
                "server_err_norm": jnp.linalg.norm(se)}

    # --- hooks (the whole per-algorithm surface) ---------------------------
    def _update_v(self, v: jax.Array, v_step: jax.Array,
                  m_prev: jax.Array, m_bar: jax.Array, count: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
        """Compression-stage variance; returns (v, new v_step marker).
        Default: frozen (Alg. 1). Only called on SYNC steps — any
        quantity fed into ``v`` must be dp-rank-consistent, or the
        replicated parameter layout silently diverges."""
        return v, v_step

    def _update_scale(self, scale: jax.Array, x: jax.Array, upd: jax.Array,
                      seg_ids_fn: Optional[Callable[[], jax.Array]],
                      n_segments: int,
                      norm_axes: Tuple[str, ...]) -> jax.Array:
        """Per-segment scaling state. Default: untouched.

        ``seg_ids_fn`` lazily yields the per-element segment-id vector —
        only hooks that call it pay for the (D,) constant."""
        return scale

    def _scale_per_elem(self, scale: jax.Array,
                        seg_ids_fn: Optional[Callable[[], jax.Array]]
                        ) -> Optional[jax.Array]:
        """Per-element multiplier from the scaling state; None = identity
        (skipped entirely, keeping the default path bitwise-pristine)."""
        return None

    def _warmup_direction(self, upd: jax.Array, x: jax.Array,
                          seg_ids_fn: Optional[Callable[[], jax.Array]],
                          n_segments: int,
                          norm_axes: Tuple[str, ...]) -> jax.Array:
        """Warmup direction shaping. Default: plain Adam direction."""
        return upd

    def sync_due(self, step: int) -> bool:
        """Host-side: must step ``step`` of the compression stage
        synchronise across dp? Default: every step (1-bit Adam)."""
        return True

    # --- audit hooks (repro.obs.audit reads these) -------------------------
    def _audit_extra(self, state: StateTree, seg_ids: jax.Array,
                     n_segments: int, tp_axes: Tuple[str, ...]) -> dict:
        """Per-family additions to :meth:`audit_stats` (keys must match
        :attr:`audit_extra_keys` — the probe derives its static
        out-specs from them).  Default: none."""
        return {}

    @property
    def audit_extra_keys(self) -> Tuple[str, ...]:
        """Names of the extra stats :meth:`_audit_extra` returns."""
        return ()

    def _audit_v_live(self, state: StateTree) -> jax.Array:
        """1.0 while the compression-stage variance is still
        legitimately updating (0/1 Adam's interval refresh), 0.0 once
        frozen — the HealthMonitor suppresses the variance-drift
        verdict while live, since drift is then expected, not a
        violated assumption.  Default: frozen (Alg. 1)."""
        return jnp.float32(0.0)

    def with_kernels(self, enabled: bool) -> "TwoStageOptimizer":
        """This optimizer with the fused Pallas paths toggled — the
        compressor's compress/EF kernels (``kernels/onebit``) AND the
        warmup-stage fused Adam update (``kernels/fused_adam``);
        ``launch.train --kernels`` / the tuner's ``use_kernel`` axis
        land here.  The compressor kernels write the bitwise-identical
        wire format and the fused Adam matches to the ULP, so flipping
        mid-run is safe.  Raises for compressors without a kernel path
        when enabling."""
        comp = self.compressor
        if enabled and not getattr(comp, "has_kernel", False):
            raise ValueError(f"compressor {comp.name!r} has no fused "
                             "kernel path (has_kernel=False)")
        comp_state = getattr(comp, "use_kernel", False)
        if comp_state is bool(enabled) and \
                self.use_kernel is bool(enabled):
            return self
        if hasattr(comp, "use_kernel") and comp_state is not bool(enabled):
            comp = dataclasses.replace(comp, use_kernel=bool(enabled))
        return dataclasses.replace(self, compressor=comp,
                                   use_kernel=bool(enabled))

    @property
    def may_skip_sync(self) -> bool:
        """True if ``sync_due`` can ever return False — drivers must then
        use the per-dp-rank ("local") state layout."""
        return False

    @property
    def _fused_warmup_ok(self) -> bool:
        """The fused Adam kernel computes the base warmup update exactly:
        usable iff no hook reshapes the direction and bias correction is
        off (the kernel implements BertAdam)."""
        return (self.use_kernel and not self.bias_correction
                and type(self)._warmup_direction
                is TwoStageOptimizer._warmup_direction)

    # --- warmup stage ------------------------------------------------------
    def warmup_update(self, g_local: jax.Array, state: StateTree,
                      x: jax.Array, lr: jax.Array, *,
                      dp_axes: Sequence[str] = (),
                      tp_axes: Sequence[str] = (),
                      segs: Optional[SegmentInfo] = None,
                      ) -> Tuple[jax.Array, StateTree, dict]:
        """Uncompressed adaptive step on the dp-mean gradient.

        With ``use_kernel`` (and no direction-shaping hook) the whole
        elementwise update — both EMAs, the preconditioning, the axpy —
        runs as ONE fused Pallas kernel (``kernels/fused_adam``; 4 reads
        + 3 writes per element vs ~6+5 unfused).  Same math in the same
        order; kernel-vs-jnp agreement is pinned at the ULP level
        (FMA-contraction association — tests/test_state.py, matching
        the tests/test_kernels.py kernel parity tolerance).
        """
        g = comm.allreduce_mean(g_local, dp_axes)
        with obs.layer_scope("optimizer", "update"):
            count = state.count + 1
        if self._fused_warmup_ok:
            from repro.kernels.fused_adam import ops as _fa
            with obs.layer_scope("optimizer", "update"):
                new_x, m, v = _fa.adam_step(
                    x, state.m, state.v, g, lr, b1=self.b1, b2=self.b2,
                    eps=self.eps, weight_decay=self.weight_decay)
        else:
            with obs.layer_scope("optimizer", "momentum"):
                m = self.b1 * state.m + (1.0 - self.b1) * g
            with obs.layer_scope("optimizer", "update"):
                v = self.b2 * state.v + (1.0 - self.b2) * jnp.square(g)
                if self.bias_correction:
                    t = count.astype(jnp.float32)
                    m_hat = m / (1.0 - self.b1 ** t)
                    v_hat = v / (1.0 - self.b2 ** t)
                else:
                    m_hat, v_hat = m, v
                upd = m_hat / (jnp.sqrt(v_hat) + self.eps)
                if self.weight_decay:
                    upd = upd + self.weight_decay * x
                seg_ids_fn = segs.ids if segs is not None else None
                n_seg = segs.n if segs is not None else 1
                upd = self._warmup_direction(upd, x, seg_ids_fn, n_seg,
                                             tuple(tp_axes))
                new_x = x - lr * upd
        with obs.layer_scope("optimizer", "stats"):
            stats = self._stats(v_l1=jnp.sum(jnp.abs(v)),
                                grad_norm=jnp.linalg.norm(g),
                                momentum_norm=jnp.linalg.norm(m),
                                state=state)
        return new_x, state._replace(m=m, v=v, count=count), stats

    # --- compression stage (ONE path, parameterised by the slots) ----------
    def update(self, g_local, state: StateTree, lr: jax.Array,
               *,
               x: Optional[jax.Array] = None,
               dp_axes: Sequence[str] = (),
               pod_axes: Sequence[str] = (),
               tp_axes: Sequence[str] = (),
               segs: Optional[SegmentInfo] = None,
               sync: bool = True,
               n_buckets: int = 1,
               ) -> Tuple[jax.Array, StateTree, dict]:
        """Compressed (or, with ``sync=False``, purely local) momentum
        step preconditioned by the (hook-governed) second moment — the
        ONE compression-stage path for every state layout.

        The state's declared slots drive the math: a ``v`` slot means
        the replicated/local layout (``x`` required; the new full
        parameter vector is returned); ``v_shard``/``master_shard``
        slots mean ZeRO-1 (``x`` ignored — the update lands on this
        rank's f32 master chunk and the rebuilt bf16 replica is
        returned via one all_gather).  The EF slot dict handed to the
        exchange is likewise read off the declared slots (every spec
        with ``ef=`` set, via :func:`repro.state.ef_errs`), so new EF
        slots never need threading.

        With ``pod_axes`` the momentum exchange runs the hierarchical
        two-level schedule (``dp_axes`` = intra-pod, ``pod_axes`` =
        cross-pod); ``n_buckets > 1`` runs it through the bucketed
        pipelined executor (``repro.pipeline``), bitwise the serial
        schedule for every compressor.

        A ``sync=False`` ("0-bit") step moves NO bytes and applies NO
        model update: the local gradient folds into the per-rank momentum
        and the update is deferred to the next sync.  Because the dp-mean
        commutes with the momentum recursion, the next synchronised step
        applies exactly the dp-mean EMA of every gradient seen since the
        last sync — local information is never lost, and the parameters
        stay bitwise identical across dp ranks (which the replicated
        parameter layout of the shard_map step requires).  The per-rank
        momentum itself does diverge between syncs, hence the "local"
        optimizer-state layout requirement (see repro.train.step).

        ``g_local`` may be a tuple of per-bucket gradient parts
        (backward overlap, ``repro.train.step.flat_grad_parts``): the
        momentum fold then runs per part against the matching slice of
        ``state.m`` — elementwise, so bitwise the full-vector fold —
        and the UNconcatenated parts feed the exchange, keeping each
        bucket's compress+wire chain dependent only on its own
        gradient fragments.  A full-vector norm for the stats is taken
        from a separate concatenation that gates nothing.
        """
        sharded = "master_shard" in state
        all_axes = tuple(pod_axes) + tuple(dp_axes)
        parts = g_local if isinstance(g_local, (tuple, list)) else None
        if parts is not None and (not sync or n_buckets <= 1):
            # no exchange to overlap (or a serial one): fold as one
            g_local = (parts[0] if len(parts) == 1
                       else jnp.concatenate(tuple(parts)))
            parts = None
        if parts is not None:
            with obs.layer_scope("optimizer", "stats"):
                g_norm_in = jnp.concatenate(tuple(parts))
            m_send, off = [], 0
            with obs.layer_scope("optimizer", "momentum"):
                for p in parts:
                    m_prev = jax.lax.slice(state.m, (off,),
                                           (off + p.shape[0],))
                    m_send.append(self.b1 * m_prev + (1.0 - self.b1) * p)
                    off += p.shape[0]
            assert off == state.m.shape[0], (off, state.m.shape)
            m_local = tuple(m_send)
        else:
            g_norm_in = g_local
            with obs.layer_scope("optimizer", "momentum"):
                m_local = self.b1 * state.m + (1.0 - self.b1) * g_local
        if not sync:
            x_full = self._full_params(state, x, all_axes)
            with obs.layer_scope("optimizer", "stats"):
                stats = self._stats(
                    v_l1=jnp.sum(jnp.abs(state.v_shard if sharded
                                         else state.v)),
                    grad_norm=jnp.linalg.norm(g_local),
                    momentum_norm=jnp.linalg.norm(m_local), state=state)
            with obs.layer_scope("optimizer", "update"):
                count = state.count + 1
            return x_full, state._replace(m=m_local, count=count), stats

        # the declared ef= fields ARE the state-slot -> plan-slot map
        # (EF slots are layout-invariant, so any layout's declaration
        # serves; subclasses declaring extra EF slots are picked up);
        # the state holds the ones its topology declared
        ef_slots = tuple(s for s in self.state_slots(
            "zero1" if sharded else "replicated", "hier")
            if s.ef is not None and s.name in state)
        m_bar, errs = comm.compressed_exchange(
            m_local, ef_errs(state, ef_slots), dp_axes, pod_axes,
            self.compressor, n_buckets=n_buckets)
        with obs.layer_scope("optimizer", "update"):
            count = state.count + 1
            seg_ids_fn = segs.ids if segs is not None else None
            n_seg = segs.n if segs is not None else 1

            if sharded:
                n = comm.axis_size(all_axes)
                d = m_bar.shape[0]
                chunk = d // max(n, 1)
                idx = (jax.lax.axis_index(all_axes) * chunk if all_axes
                       else 0)
                my_mbar = jax.lax.dynamic_slice(m_bar, (idx,), (chunk,))
                my_mprev = jax.lax.dynamic_slice(state.m, (idx,), (chunk,))
                v, v_step = self._update_v(state.v_shard, state.v_step,
                                           my_mprev, my_mbar, count)
                upd = my_mbar / (jnp.sqrt(v) + self.eps)
                master = state.master_shard
                if seg_ids_fn is not None:
                    ids_full = seg_ids_fn
                    seg_ids_fn = lambda: jax.lax.dynamic_slice(  # noqa: E731
                        ids_full(), (idx,), (chunk,))
                # each rank holds one chunk: segment norms need the dp psum
                norm_axes = tuple(tp_axes) + all_axes
            else:
                assert x is not None, \
                    "update() needs x for the replicated/local layouts"
                v, v_step = self._update_v(state.v, state.v_step, state.m,
                                           m_bar, count)
                upd = m_bar / (jnp.sqrt(v) + self.eps)
                master = x
                norm_axes = tuple(tp_axes)

            scale = self._update_scale(state.scale, master, upd, seg_ids_fn,
                                       n_seg, norm_axes)
            pe = self._scale_per_elem(scale, seg_ids_fn)
            if pe is not None:
                upd = upd * pe
            if self.weight_decay:
                upd = upd + self.weight_decay * master
            new_master = master - lr * upd

            repl = {s.name: errs[s.ef] for s in ef_slots}
            repl.update(m=m_bar, scale=scale, count=count, v_step=v_step)
        if sharded:
            repl.update(v_shard=v, master_shard=new_master)
            x_full = self._gather_replica(new_master, all_axes)
        else:
            repl.update(v=v)
            x_full = new_master
        with obs.layer_scope("optimizer", "stats"):
            stats = self._stats(v_l1=jnp.sum(jnp.abs(v)),
                                grad_norm=jnp.linalg.norm(g_norm_in),
                                momentum_norm=jnp.linalg.norm(m_bar),
                                worker_err=errs["worker"],
                                server_err=errs["server"])
        return x_full, state._replace(**repl), stats

    # --- audit probe (observation only; repro.obs.audit builds it) ---------
    def audit_stats(self, g_local: jax.Array, state: StateTree,
                    shadow_v: jax.Array, *,
                    dp_axes: Sequence[str] = (),
                    pod_axes: Sequence[str] = (),
                    tp_axes: Sequence[str] = (),
                    segs: Optional[SegmentInfo] = None,
                    ) -> Tuple[jax.Array, dict]:
        """Per-segment compression-fidelity and frozen-variance stats of
        one WOULD-BE sync step — pure observation: the model state and
        the EF residuals are read, never written, so the probe can run
        as its own jitted fn without perturbing training (the
        telemetry-neutrality pin relies on this).

        Returns ``(new_shadow_v, stats)``:

          * ``new_shadow_v`` — the shadow second-moment EMA advanced one
            step on the dp-mean gradient: what ``v`` would be were it
            not frozen (the paper's Sec. 7.1 / Fig. 2 quantity, here per
            segment);
          * ``stats`` — the :data:`AUDIT_SEG_KEYS` per-segment vectors,
            the :data:`AUDIT_SCALAR_KEYS` scalars, and any
            ``audit_extra_keys`` the family adds.

        Fidelity is measured on EXACTLY what a sync step compresses:
        the EF-compensated local momentum ``m_local + worker_err`` vs
        its decompressed wire image.  Needs the full ``v`` slot, i.e.
        the replicated/local layouts (``launch.train`` never selects
        zero1, which shards ``v``)."""
        assert "v" in state, \
            "audit_stats needs the full 'v' slot (replicated/local)"
        all_dp = tuple(pod_axes) + tuple(dp_axes)
        tp = tuple(tp_axes)
        n_seg = segs.n if segs is not None else 1
        seg_ids = (segs.ids() if segs is not None
                   else jnp.zeros(g_local.shape[0], jnp.int32))

        # (a) frozen-variance validity: one shadow-EMA step on the
        # dp-mean gradient, compared per segment against the frozen v
        g = comm.allreduce_mean(g_local, all_dp)
        new_sv = self.b2 * shadow_v + (1.0 - self.b2) * jnp.square(g)
        sv_seg = segment_l1(new_sv, seg_ids, n_seg, tp)
        v_seg = segment_l1(state.v, seg_ids, n_seg, tp)
        # zero-mass segments (the padding tail, untouched layers) have
        # no drift to report: ratio pinned to 1.0, not 0/0
        v_drift = jnp.where(v_seg > 0.0,
                            sv_seg / jnp.maximum(v_seg, 1e-30), 1.0)
        v_tot, sv_tot = jnp.sum(v_seg), jnp.sum(sv_seg)
        v_ratio = jnp.where(v_tot > 0.0,
                            sv_tot / jnp.maximum(v_tot, 1e-30), 1.0)

        # (b) compression fidelity of the would-be momentum exchange
        m_local = self.b1 * state.m + (1.0 - self.b1) * g_local
        raw = m_local + state.worker_err
        payload, _ = self.compressor.ef_compress(m_local,
                                                 state.worker_err)
        m_hat = self.compressor.decompress(payload)
        cos = segment_cosine(raw, m_hat, seg_ids, n_seg, tp)
        sign = segment_sign_agreement(raw, m_hat, seg_ids, n_seg, tp)
        if all_dp:   # per-rank quantities: report the honest dp mean
            cos = jax.lax.pmean(cos, all_dp)
            sign = jax.lax.pmean(sign, all_dp)

        # EF-residual mass per segment: global L2 over every rank's
        # residual (squared sums psummed over tp shards AND dp ranks)
        we_seg = segment_norms(state.worker_err, seg_ids, n_seg,
                               tp + all_dp)
        # the server residual is one chunk per intra-pod rank at that
        # rank's element offset (the all_to_all partition of the server
        # stage — same indexing as the ZeRO-1 branch of update())
        inner = tuple(dp_axes)
        chunk = state.server_err.shape[0]
        off = jax.lax.axis_index(inner) * chunk if inner else 0
        ids_chunk = jax.lax.dynamic_slice(seg_ids, (off,), (chunk,))
        se_seg = segment_norms(state.server_err, ids_chunk, n_seg,
                               tp + all_dp)

        m_norm = jnp.linalg.norm(m_local)
        stats = {
            "cos_sim": cos, "sign_agree": sign, "v_drift": v_drift,
            "v_l1_seg": v_seg, "worker_err_seg": we_seg,
            "server_err_seg": se_seg,
            "v_ratio": v_ratio,
            "grad_norm": jnp.linalg.norm(g),
            "momentum_norm": (jax.lax.pmean(m_norm, all_dp) if all_dp
                              else m_norm),
            "worker_err_norm": jnp.sqrt(jnp.sum(jnp.square(we_seg))),
            "server_err_norm": jnp.sqrt(jnp.sum(jnp.square(se_seg))),
            "v_live": self._audit_v_live(state),
        }
        stats.update(self._audit_extra(state, seg_ids, n_seg, tp))
        return new_sv, stats

    @staticmethod
    def _gather_replica(master_shard: jax.Array, all_axes) -> jax.Array:
        with obs.layer_scope("exchange", "gather_replica"):
            if all_axes:
                return jax.lax.all_gather(master_shard.astype(jnp.bfloat16),
                                          all_axes, tiled=True)
            return master_shard.astype(jnp.bfloat16)

    def _full_params(self, state: StateTree, x, all_axes) -> jax.Array:
        if "master_shard" in state:
            return self._gather_replica(state.master_shard, all_axes)
        assert x is not None
        return x


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_OPTIMIZERS: Dict[str, Callable[..., TwoStageOptimizer]] = {}


def register_optimizer(name: str):
    def deco(cls):
        _OPTIMIZERS[name] = cls
        return cls
    return deco


def get_optimizer(name: str, *, compressor="onebit",
                  compressor_kwargs: Optional[dict] = None,
                  **hyper) -> TwoStageOptimizer:
    """Build a registered optimizer, resolving the compressor by name
    (or accepting a ready :class:`Compressor` / legacy config)."""
    from repro.optim.compressors import as_compressor, get_compressor
    if name not in _OPTIMIZERS:
        raise KeyError(f"unknown optimizer {name!r}; "
                       f"registered: {sorted(_OPTIMIZERS)}")
    if isinstance(compressor, str):
        comp = get_compressor(compressor, **(compressor_kwargs or {}))
    else:
        comp = as_compressor(compressor)
    return _OPTIMIZERS[name](compressor=comp, **hyper)


def list_optimizers():
    return sorted(_OPTIMIZERS)
