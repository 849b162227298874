"""Device-mesh helpers (the NCCLContextMap analog — nccl_helper.h:82 —
except the 'communicators' are implicit in XLA collectives over the mesh)."""

import numpy as np
import jax
from jax import shard_map
from jax.sharding import Mesh

__all__ = ["make_mesh", "default_mesh", "mesh_axis_sizes", "dp_mesh",
           "mesh_compile_options", "shard_map", "vma_of", "pcast_varying"]


def vma_of(*xs):
    """Union of the inputs' varying-mesh-axes (``jax.typeof(x).vma``)."""
    out = frozenset()
    for x in xs:
        out = out | jax.typeof(x).vma
    return out


def pcast_varying(v, axes):
    """Mark `v` device-varying over `axes` inside a shard_map body."""
    return jax.lax.pcast(v, axes, to="varying")


def make_mesh(axes, devices=None):
    """axes: dict name->size in order, e.g. {"dp": 2, "mp": 4}. Use -1 for
    one axis to absorb the remaining devices."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError("mesh %s needs %d devices, have %d" % (axes, total, n))
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))


def default_mesh(axis_name="dp"):
    return make_mesh({axis_name: -1})


def mesh_axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# What the TPU compiler is asked for when it compiles ONE step for a mesh
# of several chips (Executor._run_spmd): under its defaults an all-reduce
# is a synchronous op, and nothing else runs on the chip while the links
# carry it.  With these two, and only with both, an all-reduce may become
# an asynchronous collective fusion: its pieces ride inside the matmul
# fusions scheduled beside it (the backward's activation sums over mp
# under the deferred dW matmuls).  The same all-reduces over the same
# members in the same dtypes, on another schedule.  Kept because the step
# compiled for a described v5e:2x2 differs with them and the chip's step
# is shorter; the options that changed nothing in that HLO, or nothing on
# the chip, are named with their evidence in docs/PERFORMANCE.md ("The
# mesh step's compile options") and PERF.md section 6, PR 35.
_TPU_MESH_COMPILE_OPTIONS = {
    "xla_enable_async_all_reduce": True,
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
}


def mesh_compile_options(mesh):
    """`compiler_options` for jitting one step over `mesh`, chosen from
    what the mesh shows: the asynchronous-collective scheduling above
    where its devices are TPUs and there is more than one, nothing
    anywhere else (a CPU compile refuses an `xla_tpu_` option outright,
    and one chip has no collective to hide).  The one place the option
    names live."""
    devices = mesh.devices
    if devices.size > 1 and devices.flat[0].platform == "tpu":
        return dict(_TPU_MESH_COMPILE_OPTIONS)
    return {}


def dp_mesh(nranks, axis_name="dp"):
    """Data-parallel mesh for the collective dist backend: exactly
    `nranks` devices on one axis, spanning processes when jax.distributed
    is initialized (one device per trainer process) or local virtual
    devices for single-process CPU CI.  Fails loudly on a device deficit
    — a silent smaller mesh would hang the psum rendezvous."""
    devices = jax.devices()
    if len(devices) < nranks:
        raise ValueError(
            "collective mode needs %d devices for the %r mesh, but jax "
            "sees %d — launch %d processes (init_collective) or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=%d for a "
            "single-process CPU mesh"
            % (nranks, axis_name, len(devices), nranks, nranks))
    return make_mesh({axis_name: nranks}, devices=devices[:nranks])
