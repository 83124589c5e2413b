"""Compressor registry: the C_omega operators behind the optimizer family.

A compressor turns a flat float32 vector into a tuple of wire arrays (the
*payload*) plus, for error-feedback use, the exact residual:

    payload, new_err = comp.ef_compress(x, err)    # compress(x + err)
    x_hat            = comp.decompress(payload)    # x + err == x_hat + new_err

Payload contract (what lets one collective schedule serve every entry):
  * ``payload`` is a tuple of arrays, each 1-D and laid out in element
    order, so that slicing leaf ``p`` into ``n`` equal leading chunks
    slices the represented vector into its ``n`` contiguous chunks;
  * every leaf length is divisible by ``n_dp`` whenever the represented
    length is divisible by ``n_dp * block_size`` (``padded_length``
    guarantees that for all optimizer state).

``repro.core.comm`` moves payload leaves through all_to_all/all_gather and
never looks inside them; registering a new compressor here is all it takes
to run any registered optimizer over it.

Registered entries:
  ``onebit``   — sign + per-block mean-|x| scale (the paper's C_omega),
                 wrapping :mod:`repro.core.compression` (Pallas-kernel path
                 included via ``use_kernel``)
  ``identity`` — no-op (the paper's "1-bit Adam (32-bits)" ablation and
                 exactness tests)
  ``topk``     — per-block magnitude top-k with error feedback (classic
                 sparsified EF-SGD compressor; values + intra-block indices
                 on the wire)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import (CompressionConfig, DEFAULT_BLOCK,
                                    compress_onebit, decompress_onebit,
                                    onebit_residual)
from repro.perf.kernel_cost import (ComputeSpec, ZERO_COMPUTE,
                                    ef_combine_cost, elementwise_pass)
from repro.plan.ir import WireSpec, log2ceil

Payload = Tuple[jax.Array, ...]


class Compressor:
    """Uniform EF-compressor interface. Subclasses are immutable and
    hashable (they are closed over by jitted step functions)."""

    name: str = "?"
    lossless: bool = False
    # dense = every coordinate survives compression (possibly quantised);
    # sparse compressors (dense=False) drop coordinates and need error
    # feedback on EVERY lossy hop — the hierarchical schedule's cross-pod
    # legs give them the dedicated ``outer`` EF slot (see core/comm.py)
    dense: bool = True
    # True when the entry has a fused Pallas path behind ``use_kernel``
    # (the tuner only enumerates the pallas axis where this is set)
    has_kernel: bool = False

    def ef_compress(self, x: jax.Array, err: jax.Array
                    ) -> Tuple[Payload, jax.Array]:
        """Compress ``x + err``; return (payload, exact new residual)."""
        buf = x + err
        payload = self.compress(buf)
        if self.lossless:
            return payload, jnp.zeros_like(buf)
        return payload, buf - self.decompress(payload)

    def combine_received(self, recv: Payload, combine: str) -> jax.Array:
        """The ``n`` chunks a rank received (each leaf ``(n, ...)``),
        decompressed and averaged (``"mean"``) or summed (``"sum"``)."""
        vals = jax.vmap(lambda *leaves: self.decompress(tuple(leaves)))(
            *recv)
        if combine == "mean":
            return jnp.mean(vals, axis=0)
        return jnp.sum(vals, axis=0)

    def server_ef_compress(self, recv: Payload, err: jax.Array,
                           combine: str) -> Tuple[Payload, jax.Array]:
        """The flat schedule's server step: ``ef_compress`` of the
        combined received chunks with the server error ``err``."""
        return self.ef_compress(self.combine_received(recv, combine), err)

    def compress(self, x: jax.Array) -> Payload:
        raise NotImplementedError

    def decompress(self, payload: Payload) -> jax.Array:
        raise NotImplementedError

    def wire_specs(self, d: int) -> Tuple[WireSpec, ...]:
        """Declared wire format (dtype + shape per payload leaf) for a
        d-element f32 vector — the single source of truth consumed by the
        plan executor (asserted against the real ``compress`` output) and
        the α-β cost model (``repro.plan.cost``)."""
        raise NotImplementedError

    def wire_bytes(self, d: int) -> int:
        """Bytes on the wire for a d-element float32 payload (derived
        from ``wire_specs`` — override the specs, not this)."""
        return sum(ws.nbytes for ws in self.wire_specs(d))

    # --- declared compute (repro.perf), next to the declared wire format ---
    def _compress_cost(self, d: int) -> ComputeSpec:
        """Declared FLOPs/HBM bytes/kernel launches of ``compress``."""
        raise NotImplementedError

    def _decompress_cost(self, d: int) -> ComputeSpec:
        """Declared FLOPs/HBM bytes/kernel launches of ``decompress``."""
        raise NotImplementedError

    def compute_specs(self, d: int) -> Dict[str, ComputeSpec]:
        """Declared compute for a d-element f32 vector, keyed
        ``compress`` / ``decompress`` / ``ef_compress`` — the compute
        analogue of ``wire_specs`` and the single source the roofline
        coster (``repro.plan.cost``) prices; ``tests/test_perf.py`` pins
        the byte counts against the kernel/ref traffic.

        The base composition mirrors the base ``ef_compress``: an add
        pass, a compress, a decompress, and a residual pass.  Entries
        whose ``use_kernel`` path fuses those (1-bit) override this."""
        c = self._compress_cost(d)
        dc = self._decompress_cost(d)
        return {"compress": c, "decompress": dc,
                "ef_compress": ef_combine_cost(d) + c + dc}


@dataclasses.dataclass(frozen=True)
class OneBitCompressor(Compressor):
    block_size: int = DEFAULT_BLOCK
    use_kernel: bool = False
    name = "onebit"
    has_kernel = True

    def compress(self, x):
        return compress_onebit(x, self.block_size, self.use_kernel)

    def ef_compress(self, x, err):
        if self.use_kernel:
            from repro.kernels.onebit import ops as _kops
            pk, sc, new_err = _kops.ef_compress_fused(
                x + 0.0, err, block_size=self.block_size)
            return (pk, sc), new_err
        buf = x + err
        payload = self.compress(buf)
        return payload, onebit_residual(buf, payload[1], self.block_size)

    def decompress(self, payload):
        packed, scales = payload
        return decompress_onebit(packed, scales, self.block_size,
                                 self.use_kernel)

    def server_ef_compress(self, recv, err, combine):
        """Lowered for a TPU, with blocks of whole 128-lane rows, the
        server step is one Pallas sweep (``kernels/onebit``: the server
        error read and written once), whatever ``use_kernel`` says;
        elsewhere it is the base class's decompress, combine and
        ``ef_compress``.  ``use_kernel`` only tells the sweep that one
        chunk's bitmap comes from the Pallas codec."""
        def chain(packed, scales, e):
            (pk, sc), new_err = Compressor.server_ef_compress(
                self, (packed, scales), e, combine)
            return pk, sc, new_err
        if self.block_size % 128:
            pk, sc, new_err = chain(*recv, err)
        else:
            from repro.kernels.onebit import kernel as _kernel
            pk, sc, new_err = _kernel.ef_server_fused(
                *recv, err, self.block_size, combine,
                byte_rows=self.use_kernel or recv[0].shape[0] > 1,
                cpu=chain)
        return (pk, sc), new_err

    def wire_specs(self, d):
        return (WireSpec("uint8", (d // 8,)),
                WireSpec("float32", (d // self.block_size,)))

    # traffic counts pinned to kernels/onebit (module docstring there is
    # the ground truth): fused EF-compress = 2 f32 reads + 1 f32 write +
    # the wire output, ONE launch; the jnp chain re-reads the buffer per
    # pass (pack pass + scale pass) and materializes the sign vector
    def _compress_cost(self, d):
        w = self.wire_bytes(d)
        if self.use_kernel:
            return ComputeSpec(flops=2.0 * d, hbm_bytes=4 * d + w,
                               kernels=1)
        return ComputeSpec(flops=2.0 * d, hbm_bytes=8 * d + w, kernels=2)

    def _decompress_cost(self, d):
        w = self.wire_bytes(d)
        if self.use_kernel:
            return ComputeSpec(flops=2.0 * d, hbm_bytes=w + 4 * d,
                               kernels=1)
        # unpack materializes the (d,) sign vector before the scale mul
        return ComputeSpec(flops=2.0 * d, hbm_bytes=w + 12 * d, kernels=2)

    def compute_specs(self, d):
        specs = super().compute_specs(d)
        if self.use_kernel:
            # ef_compress_fused: buf, scale, pack, residual in ONE pass —
            # reads x + err, writes new_err + the wire payload
            w = self.wire_bytes(d)
            specs["ef_compress"] = ComputeSpec(
                flops=4.0 * d, hbm_bytes=12 * d + w, kernels=1)
        return specs


@dataclasses.dataclass(frozen=True)
class IdentityCompressor(Compressor):
    block_size: int = DEFAULT_BLOCK  # accepted for interface uniformity
    name = "identity"
    lossless = True

    def compress(self, x):
        return (x,)

    def decompress(self, payload):
        return payload[0]

    def wire_specs(self, d):
        return (WireSpec("float32", (d,)),)

    def _compress_cost(self, d):
        return ZERO_COMPUTE          # payload IS the buffer; no copy

    def _decompress_cost(self, d):
        return ZERO_COMPUTE

    def compute_specs(self, d):
        # lossless: ef_compress is one add pass (new_err = zeros is
        # constant-folded by XLA, not a data pass)
        return {"compress": ZERO_COMPUTE, "decompress": ZERO_COMPUTE,
                "ef_compress": elementwise_pass(d, 2, 1)}


@dataclasses.dataclass(frozen=True)
class TopKCompressor(Compressor):
    """Per-block magnitude top-k with error feedback.

    Each ``block_size`` block keeps its ``k = block_size // ratio`` largest
    |x| entries as (float32 value, intra-block index) pairs.  Intra-block
    indexing keeps the payload element-ordered and chunkable, so the same
    all_to_all/all_gather schedule as 1-bit applies — and it bounds the
    index range by ``block_size``, so indices pack into 16 bits whenever
    ``block_size <= 65536`` (uint16: int16 would overflow at 32768+),
    halving the index wire bytes; int32 is used only beyond that.
    """

    block_size: int = DEFAULT_BLOCK
    ratio: int = 32                  # keep 1/ratio of the elements
    name = "topk"
    dense = False

    def __post_init__(self):
        assert self.block_size % self.ratio == 0, (self.block_size,
                                                   self.ratio)

    @property
    def k(self) -> int:
        return max(self.block_size // self.ratio, 1)

    @property
    def index_dtype(self):
        return jnp.uint16 if self.block_size <= 65536 else jnp.int32

    def compress(self, x):
        assert x.ndim == 1 and x.shape[0] % self.block_size == 0, (
            x.shape, self.block_size)
        xb = x.reshape(-1, self.block_size)
        _, idx = jax.lax.top_k(jnp.abs(xb), self.k)          # (nb, k) i32
        vals = jnp.take_along_axis(xb, idx, axis=1)           # (nb, k) f32
        return vals.reshape(-1), idx.astype(self.index_dtype).reshape(-1)

    def decompress(self, payload):
        vals, idx = payload
        nb = vals.shape[0] // self.k
        vb = vals.reshape(nb, self.k)
        ib = idx.reshape(nb, self.k).astype(jnp.int32)
        out = jnp.zeros((nb, self.block_size), vals.dtype)
        rows = jnp.arange(nb, dtype=jnp.int32)[:, None]
        return out.at[rows, ib].set(vb).reshape(-1)

    def wire_specs(self, d):
        kept = (d // self.block_size) * self.k
        return (WireSpec("float32", (kept,)),
                WireSpec(jnp.dtype(self.index_dtype).name, (kept,)))

    def _compress_cost(self, d):
        # abs pass + per-block top_k (O(B log B) work per block) +
        # value gather; reads x twice, writes the (vals, idx) wire
        w = self.wire_bytes(d)
        return ComputeSpec(flops=float(d) * max(log2ceil(self.block_size),
                                                1),
                           hbm_bytes=8 * d + w, kernels=3)

    def _decompress_cost(self, d):
        # zeros init + scatter of the kept (value, index) pairs
        w = self.wire_bytes(d)
        return ComputeSpec(flops=float(d), hbm_bytes=4 * d + 2 * w,
                           kernels=2)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_COMPRESSORS: Dict[str, Callable[..., Compressor]] = {}


def register_compressor(name: str):
    def deco(factory):
        _COMPRESSORS[name] = factory
        return factory
    return deco


register_compressor("onebit")(OneBitCompressor)
register_compressor("identity")(IdentityCompressor)
register_compressor("topk")(TopKCompressor)


def get_compressor(name: str, **kwargs) -> Compressor:
    if name not in _COMPRESSORS:
        raise KeyError(f"unknown compressor {name!r}; "
                       f"registered: {sorted(_COMPRESSORS)}")
    return _COMPRESSORS[name](**kwargs)


def list_compressors():
    return sorted(_COMPRESSORS)


def compressor_has_kernel(name: str) -> bool:
    """True when the registered entry has a fused Pallas path behind
    ``use_kernel`` (checked WITHOUT constructing — the tuner and the
    ``--kernels`` CLI use it to gate the pallas axis)."""
    if name not in _COMPRESSORS:
        raise KeyError(f"unknown compressor {name!r}; "
                       f"registered: {sorted(_COMPRESSORS)}")
    return bool(getattr(_COMPRESSORS[name], "has_kernel", False))


def from_config(cfg: CompressionConfig) -> Compressor:
    """Adapt the legacy ``CompressionConfig`` to a registry compressor."""
    if cfg.kind == "identity":
        return IdentityCompressor(block_size=cfg.block_size)
    return OneBitCompressor(block_size=cfg.block_size,
                            use_kernel=cfg.use_kernel)


def as_compressor(obj) -> Compressor:
    """Accept a Compressor, a CompressionConfig, or a registry name."""
    if isinstance(obj, Compressor):
        return obj
    if isinstance(obj, str):
        return get_compressor(obj)
    if isinstance(obj, CompressionConfig):
        return from_config(obj)
    raise TypeError(f"not a compressor: {obj!r}")
