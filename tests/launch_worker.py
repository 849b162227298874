"""Worker for the launcher test: bootstrap via the launcher-provided
PADDLE_* env (init_collective), then psum the ranks across processes."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count")
]
_flags.append("--xla_force_host_platform_device_count=1")
os.environ["XLA_FLAGS"] = " ".join(_flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu import distributed

    if os.environ.get("LAUNCH_WORKER_FAIL_RANK") == os.environ.get(
        "PADDLE_TRAINER_ID"
    ):
        sys.exit(3)

    distributed.init_collective()
    nproc = int(os.environ["PADDLE_TRAINERS"])
    assert jax.process_count() == nproc, jax.process_count()

    from paddle_tpu.parallel.mesh import shard_map

    from jax.sharding import NamedSharding

    mesh = Mesh(np.array(jax.devices()), ("x",))
    rank_local = np.asarray([float(jax.process_index())], np.float32)
    ranks = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("x")), rank_local, (nproc,)
    )

    f = jax.jit(
        shard_map(
            lambda r: jax.lax.psum(r, "x"),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )
    )
    # DIST_STEPS: the bench dist-smoke times N collective steps; the
    # launcher tests leave it at 1 and just check the value
    steps = max(1, int(os.environ.get("DIST_STEPS", "1")))
    for _ in range(steps):
        out = f(ranks)
    local = np.asarray(out.addressable_data(0))
    print("PSUM %.1f" % float(local[0]), flush=True)


if __name__ == "__main__":
    main()
