"""Device/platform introspection (platform/cpu_info.* + gpu_info.* analog).

The reference exposes core counts, flops estimates, and memory budgets per
device; here the equivalents come from the PJRT device handle plus the
chip-generation peak table (utils/flops.py)."""

import os

__all__ = [
    "cpu_count",
    "device_count",
    "device_kind",
    "peak_flops",
    "device_memory_limit",
]


def cpu_count():
    return os.cpu_count() or 1


def device_count():
    import jax

    return jax.device_count()


def device_kind(place=None):
    from .memory import _device

    d = _device(place)
    return getattr(d, "device_kind", d.platform)


def peak_flops(place=None):
    """Peak bf16 FLOPs/sec of the attached chip (None on a CPU device;
    an unknown accelerator kind raises) —
    the gpu_info flops-estimate analog, used for MFU accounting."""
    from .memory import _device
    from .utils.flops import chip_peak_flops

    return chip_peak_flops(_device(place))


def device_memory_limit(place=None):
    from .memory import memory_limit

    return memory_limit(place)
