"""The device check and device memory."""
from __future__ import annotations

import os
from typing import Dict


class NoChip(RuntimeError):
    """The run cannot measure here: no TPU, too few chips, or a kernel
    rerouted off its chip default."""


def check_environment() -> None:
    rerouted = sorted(k for k in os.environ if k.startswith("CLAX_KERNEL_IMPL"))
    if rerouted:
        raise NoChip(f"{rerouted} reroute kernels off their chip default")


def check_devices(chips: int) -> Dict:
    """The device JAX runs on, as the result line reports it."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` on the fullest of the cell's chips."""
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0
