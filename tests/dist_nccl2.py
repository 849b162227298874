"""nccl2-mode (multi-host collective DP) runner: 2 localhost processes
bootstrap via ``collective.init_distributed_env`` (the gen_nccl_id_op.cc +
NCCLContextMap re-expression — jax.distributed over DCN) and train a tiny
data-parallel linear model with grad psum over the cross-process axis.

Prints LOSSES <json> so test_dist_train.py can compare against the
single-process full-batch run (test_dist_base.py nccl2-mode parity).
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# exactly one local CPU device per process (conftest may have forced 8)
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count")
]
_flags.append("--xla_force_host_platform_device_count=1")
os.environ["XLA_FLAGS"] = " ".join(_flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import collective

    pid = int(os.environ["PADDLE_TRAINER_ID"])
    nproc = int(os.environ["PADDLE_TRAINERS"])
    collective.init_distributed_env(
        coordinator_address=os.environ["COORDINATOR"],
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.device_count() == nproc  # 1 cpu device per process

    # global batch split across processes: parity target is the LOCAL role
    # training on the full batch with mean loss
    rng = np.random.RandomState(3)
    x = rng.rand(16, 4).astype("float32")
    w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    y = x @ w_true + 0.1 * rng.rand(16, 1).astype("float32")
    shard = 16 // nproc
    xs, ys = x[pid * shard:(pid + 1) * shard], y[pid * shard:(pid + 1) * shard]

    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.parallel.mesh import shard_map

    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def step(w, xb, yb):
        # differentiate the GLOBAL loss (psum inside the grad): version-
        # robust — shard_map's autodiff auto-psums cotangents of
        # replicated inputs, so pmean-ing local grads after the fact
        # double-counts (2x grads); putting the collective inside the
        # differentiated function is correct under either semantics
        def global_loss(w):
            contrib = jnp.sum((xb @ w - yb) ** 2) / 16.0
            return collective.all_reduce(contrib, "dp", op="sum")

        loss, g = jax.value_and_grad(global_loss)(w)
        return w - 0.1 * g, loss

    sstep = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P("dp"), P("dp")),
            out_specs=(P(), P()),
        )
    )
    from jax.sharding import NamedSharding

    # build the [16, 4] GLOBAL arrays from each process's local shard
    # (host_local_array_to_global_array in this jax treats the local value
    # as already-global, silently halving the batch)
    gx = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), xs, (16, 4)
    )
    gy = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), ys, (16, 1)
    )
    w = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P()), np.zeros((4, 1), np.float32), (4, 1)
    )
    if os.environ.get("DIST_DEBUG"):
        print("DEBUG gx.shape=%s xs[0]=%s" % (gx.shape, xs[0]), flush=True)
        probe = jax.jit(
            shard_map(
                lambda xb: (
                    jnp.reshape(jnp.asarray(jax.lax.psum(1, "dp"), jnp.float32), (1,)),
                    jnp.reshape(jnp.mean(xb), (1,)),
                ),
                mesh=mesh,
                in_specs=(P("dp"),),
                out_specs=(P(), P("dp")),
            )
        )
        sz, lm = probe(gx)
        print(
            "DEBUG axis=%s localmean=%s"
            % (
                float(np.asarray(sz.addressable_data(0))[0]),
                float(np.asarray(lm.addressable_data(0))[0]),
            ),
            flush=True,
        )

    losses = []
    for _ in range(int(os.environ.get("DIST_STEPS", "4"))):
        w, lv = sstep(w, gx, gy)
        losses.append(float(np.asarray(lv.addressable_data(0)).reshape(-1)[0]))
    print("LOSSES " + json.dumps(losses), flush=True)


if __name__ == "__main__":
    main()
