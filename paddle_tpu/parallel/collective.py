"""Distributed bootstrap + collective helpers.

The reference bootstraps NCCL with an ad-hoc gRPC broadcast of the unique id
(distributed_ops/gen_nccl_id_op.cc:31) and wires multi-node ranks through
env vars (PADDLE_TRAINER_ID etc.).  TPU-natively the whole thing is
jax.distributed.initialize over DCN; the same env-var contract is honored so
reference launch scripts keep working.
"""

import contextlib
import os
import threading

import jax

__all__ = [
    "init_distributed_env",
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "broadcast",
    "barrier",
    "trainer_id",
    "num_trainers",
    "collective_lowering",
    "lowering_axis",
]


def trainer_id():
    return int(os.environ.get("PADDLE_TRAINER_ID", os.environ.get("TRAINER_ID", 0)))


def num_trainers():
    return int(os.environ.get("PADDLE_TRAINERS", os.environ.get("TRAINERS", 1)))


def _enable_cpu_cross_process_collectives():
    """Multi-process SPMD on the CPU backend needs an explicit
    cross-process collectives implementation (gloo over TCP) — without it
    XLA rejects the computation outright ("Multiprocess computations
    aren't implemented on the CPU backend").  Must run BEFORE the backend
    initializes; the option only affects the CPU client."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init_distributed_env(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host bootstrap (gen_nccl_id + NCCLContextMap analog).

    coordinator defaults from PADDLE_PSERVER_IPS/PADDLE_CURRENT_IP-style env
    or JAX defaults; call once per host before building executors."""
    if coordinator_address is None:
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        if eps:
            coordinator_address = eps.split(",")[0]
    if num_processes is None:
        num_processes = num_trainers()
    if process_id is None:
        process_id = trainer_id()
    if num_processes <= 1:
        return  # single-process: nothing to do
    _enable_cpu_cross_process_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


# thin named wrappers so user kernels/shard_map bodies read like the
# reference's collective vocabulary
def all_reduce(x, axis_name, op="sum"):
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    if op == "mean":
        return jax.lax.pmean(x, axis_name)
    raise ValueError(op)


def all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def broadcast(x, axis_name, src=0):
    idx = jax.lax.axis_index(axis_name)
    import jax.numpy as jnp

    sel = (idx == src).astype(x.dtype)
    return jax.lax.psum(x * sel, axis_name)


def barrier(axis_name):
    jax.lax.psum(1, axis_name)


# ---- collective-lowering context ----------------------------------------
# The op registry's collective lowerings (ops/collective_ops.py
# c_allreduce_*) need to know, AT TRACE TIME, whether a mesh axis is bound
# around the traced step — psum over an unbound axis is a NameError, and a
# transpiled collective program must still degrade to single-replica
# semantics (allreduce == identity) when run on a plain executor.  The
# collective run path (executor._run_collective) enters this context while
# tracing the step under shard_map; lowering rules consult lowering_axis().
# Thread-local: pserver threads in in-process tests trace their shard
# programs concurrently with a collective trainer trace.
_lowering_state = threading.local()


@contextlib.contextmanager
def collective_lowering(axis_name, nranks):
    """Bind `axis_name` (size `nranks`) for collective op lowerings during
    a trace.  Nesting replaces (the inner trace wins, e.g. a pserver-side
    trace inside a host callback must NOT see the trainer's axis)."""
    prev = getattr(_lowering_state, "axis", None)
    _lowering_state.axis = (str(axis_name), int(nranks))
    try:
        yield
    finally:
        _lowering_state.axis = prev


def lowering_axis():
    """(axis_name, nranks) bound by the active collective trace, or None
    when tracing outside a collective run (single-replica semantics)."""
    return getattr(_lowering_state, "axis", None)
