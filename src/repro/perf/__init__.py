"""repro.perf — compute as a first-class priced stream.

  * :mod:`repro.perf.device`      — DeviceSpec: the ONE place hardware
                                    peaks live (presets + calibration
                                    via ``DeviceSpec.from_measured``)
  * :mod:`repro.perf.kernel_cost` — ComputeSpec: declared FLOPs / HBM
                                    bytes / kernel-launch counts for
                                    the compress / EF / Adam hot path

``repro.plan.cost`` prices these against the cluster's DeviceSpec as a
third ("compute") stream beside the intra/cross link streams, so the
auto-tuner can see when a fused Pallas kernel, a bigger bucket, or a
cheaper compressor changes the bottleneck.  ``benchmarks/
kernel_sweep.py`` calibrates HBM bandwidth + kernel launch overhead
from timed kernels, mirroring ``comm_sweep.py`` for links.
"""
from repro.perf.device import (DEVICES, DeviceSpec, as_device, get_device,
                               host_memory_bytes, list_devices,
                               preset_for_kind, resolve_device)
from repro.perf.kernel_cost import (ComputeSpec, ZERO_COMPUTE,
                                    adam_update_cost, combine_cost,
                                    ef_combine_cost, elementwise_pass)

__all__ = [
    "DEVICES", "DeviceSpec", "ComputeSpec", "ZERO_COMPUTE",
    "adam_update_cost", "as_device", "combine_cost", "ef_combine_cost",
    "elementwise_pass", "get_device", "host_memory_bytes", "list_devices",
    "preset_for_kind", "resolve_device",
]
