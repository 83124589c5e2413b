"""DeviceSpec — the single place hardware peaks live.

Every number the planning stack knows about a *device* (as opposed to a
*link* — those are :class:`repro.plan.cost.LinkSpec`) is a field here:
peak matmul FLOP/s, HBM bandwidth, per-kernel launch overhead, HBM
capacity, and the per-chip interconnect bandwidth the roofline's
collective term uses.  ``launch.mesh`` re-exports the TPU v5e constants
for its legacy names, ``analysis.roofline`` defaults its report to the
same preset, and ``plan.cost.ClusterSpec`` embeds a DeviceSpec so the
three-stream (compute/intra/cross) pipeline pricing and the tuner all
read one source — the drift this replaces was three copies of 197e12.

Two ways to get a spec:

  * ``get_device(name)`` — a preset (interconnect-free device character);
  * ``DeviceSpec.from_measured(path)`` — calibrated from a
    ``benchmarks/kernel_sweep.py`` JSON: HBM bandwidth and kernel launch
    overhead least-squares-fitted from TIMED compression/Adam kernels on
    the fabric the process actually runs on (mirror of
    ``ClusterSpec.from_measured`` / ``comm_sweep.py`` for links).

The roofline time of a kernel on a device is

    t = max(flops / peak_flops, hbm_bytes / hbm_bw) + kernels * kernel_overhead

— compute- or memory-bound, whichever ceiling binds, plus one launch
overhead per kernel dispatched (what makes an unfused 6-pass jnp chain
lose to a fused single-pass Pallas kernel even at equal byte counts).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """One accelerator's peaks (per chip)."""

    name: str
    peak_flops: float        # bf16 matmul FLOP/s
    hbm_bw: float            # HBM bytes/s
    kernel_overhead: float   # seconds per kernel launch (dispatch + sync)
    hbm_bytes: int = 16 * 1024 ** 3   # HBM capacity
    ici_bw: float = 50e9     # per-chip interconnect bytes/s (roofline term)

    def roofline_time(self, flops: float, hbm_bytes: float,
                      kernels: int = 0) -> float:
        """Seconds for a kernel sequence: the binding roofline ceiling
        plus one launch overhead per kernel."""
        return (max(flops / self.peak_flops, hbm_bytes / self.hbm_bw)
                + kernels * self.kernel_overhead)

    @property
    def hbm_capacity(self) -> Optional[int]:
        """Per-rank memory capacity in bytes — what the memory ledger
        (repro.obs.mem) and the tuner's capacity constraint price
        against.  TPU presets: the datasheet HBM size (``hbm_bytes``).
        ``cpu-host``: the machine's REAL installed RAM via psutil —
        the preset's nominal 64 GiB is a roofline fiction, not this
        host's capacity — or None when psutil is unavailable (no
        capacity constraint rather than a wrong one)."""
        if self.name == "cpu-host":
            return host_memory_bytes()
        return self.hbm_bytes

    @classmethod
    def from_measured(cls, path: str, name: Optional[str] = None,
                      base: str = "tpu-v5e") -> "DeviceSpec":
        """Build a spec from a ``benchmarks/kernel_sweep.py`` JSON — HBM
        bandwidth + kernel launch overhead CALIBRATED from timed kernels.

        Fields the sweep cannot observe (``peak_flops``: the timed
        kernels are memory-bound by design; HBM capacity) fall back to
        the ``base`` preset.  A sweep whose fit clamped a coefficient
        (its ``clamped`` list is non-empty) is a FAILED calibration —
        refused here rather than silently loaded as a ~zero-overhead /
        garbage-bandwidth device the tuner would trust."""
        import json
        with open(path) as f:
            data = json.load(f)
        if data.get("clamped"):
            raise ValueError(
                f"{path}: calibration clamped {data['clamped']} — the "
                "timings did not resolve these terms (noise or too-"
                "narrow sweep); re-run benchmarks/kernel_sweep.py on "
                "real hardware instead of loading this fit")
        fallback = get_device(base)
        return cls(
            name=str(data.get("name", "measured")) if name is None else name,
            peak_flops=float(data.get("peak_flops")
                             or fallback.peak_flops),
            hbm_bw=float(data["hbm_bw"]),
            kernel_overhead=float(data["kernel_overhead"]),
            hbm_bytes=int(data.get("hbm_bytes", fallback.hbm_bytes)),
            ici_bw=float(data.get("ici_bw", fallback.ici_bw)))


# --------------------------------------------------------------------------
# presets (public datasheet peaks; launch overheads are O(us) guesses the
# kernel_sweep calibration replaces on real hardware)
# --------------------------------------------------------------------------

DEVICES: Dict[str, DeviceSpec] = {
    "tpu-v5e": DeviceSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                          kernel_overhead=2e-6,
                          hbm_bytes=16 * 1024 ** 3, ici_bw=50e9),
    "tpu-v4": DeviceSpec("tpu-v4", peak_flops=275e12, hbm_bw=1228e9,
                         kernel_overhead=2e-6,
                         hbm_bytes=32 * 1024 ** 3, ici_bw=50e9),
    "tpu-v5p": DeviceSpec("tpu-v5p", peak_flops=459e12, hbm_bw=2765e9,
                          kernel_overhead=2e-6,
                          hbm_bytes=95 * 1024 ** 3, ici_bw=100e9),
    # a host CPU running the interpret-mode fallbacks: tiny peaks, fat
    # launch overhead — makes "latency-bound => stay serial/unfused"
    # decisions exercisable in tests without fictional numbers
    "cpu-host": DeviceSpec("cpu-host", peak_flops=2e11, hbm_bw=2e10,
                           kernel_overhead=5e-5,
                           hbm_bytes=64 * 1024 ** 3, ici_bw=1e10),
}


# ``jax.Device.device_kind`` of each TPU generation -> its preset.  A TPU
# whose kind is not here is an error, never priced as some other chip.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu-v4",
    "TPU v5 lite": "tpu-v5e",
    "TPU v5": "tpu-v5p",
}


def preset_for_kind(kind: str) -> str:
    """The preset of a TPU ``device_kind``; unknown kinds raise."""
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no device preset for TPU device_kind {kind!r}; "
                       f"known kinds: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def resolve_device(requested: Optional[str] = None, device=None) -> str:
    """The preset that prices this process's accelerator.

    On a TPU (``device``, default ``jax.devices()[0]``) the preset follows
    its ``device_kind``, and a ``requested`` preset naming another chip is
    an error.  Elsewhere (the CPU runs that test the pricing) the
    ``requested`` preset is used as given, ``tpu-v5e`` when none is."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return requested or "tpu-v5e"
    found = preset_for_kind(device.device_kind)
    if requested is not None and requested != found:
        raise ValueError(f"--device {requested!r} does not match the chip "
                         f"found ({device.device_kind!r} -> {found!r})")
    return found


def host_memory_bytes() -> Optional[int]:
    """Total installed host RAM in bytes (psutil), or None."""
    try:
        import psutil
        return int(psutil.virtual_memory().total)
    except Exception:
        return None


def get_device(name: str) -> DeviceSpec:
    if name not in DEVICES:
        raise KeyError(f"unknown device preset {name!r}; "
                       f"registered: {sorted(DEVICES)}")
    return DEVICES[name]


def list_devices():
    return sorted(DEVICES)


def as_device(obj) -> DeviceSpec:
    """Accept a DeviceSpec or a preset name."""
    if isinstance(obj, DeviceSpec):
        return obj
    if isinstance(obj, str):
        return get_device(obj)
    raise TypeError(f"not a device spec: {obj!r}")


# the TPU v5e numbers under their historical names — ``launch.mesh``
# re-exports these; everything else should take a DeviceSpec
TPU_V5E = DEVICES["tpu-v5e"]
PEAK_FLOPS_BF16 = TPU_V5E.peak_flops
HBM_BW = TPU_V5E.hbm_bw
ICI_BW = TPU_V5E.ici_bw
HBM_BYTES = TPU_V5E.hbm_bytes
