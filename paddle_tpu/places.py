"""Device places (place.h analog): CPUPlace / TPUPlace.

The reference dispatches kernels by Place (CPUPlace/CUDAPlace); here a Place
selects the JAX backend + default device for compiled blocks.  TPUPlace is
the CUDAPlace analog named by the north star (BASELINE.json).
"""

import functools


class Place:
    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(
            other, "device_id", 0
        )

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"

    def jax_device(self):
        import jax

        cpus = [d for d in jax.devices() if d.platform == "cpu"]
        if cpus:
            return cpus[0]
        return jax.devices()[0]


class TPUPlace(Place):
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id

    def jax_device(self):
        import jax

        tpus = [d for d in jax.devices() if d.platform == "tpu"]
        if not tpus:
            raise RuntimeError(
                "%r: jax sees no TPU (devices: %s) — use CPUPlace() to run "
                "on the host" % (self, jax.devices()))
        if not 0 <= self.device_id < len(tpus):
            raise RuntimeError(
                "%r: only %d TPU device(s) attached" % (self, len(tpus)))
        return tpus[self.device_id]


# CUDAPlace alias for scripts written against the reference API surface
CUDAPlace = TPUPlace


class TPUPinnedPlace(Place):
    """Host-staging place (CUDAPinnedPlace analog) — host numpy buffers."""

    def __repr__(self):
        return "TPUPinnedPlace"

    def jax_device(self):
        import jax

        cpus = [d for d in jax.devices() if d.platform == "cpu"]
        return cpus[0] if cpus else jax.devices()[0]


@functools.lru_cache(maxsize=None)
def default_place():
    """TPU if attached, else CPU — mirrors fluid's use_cuda auto-detect."""
    import jax

    platforms = {d.platform for d in jax.devices()}
    return TPUPlace(0) if "tpu" in platforms else CPUPlace()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True
