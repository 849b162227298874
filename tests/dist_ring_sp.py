"""Multi-PROCESS sequence parallelism runner: 2 localhost processes x 4
virtual CPU devices bootstrap ``jax.distributed`` (the DCN control plane)
and run ring attention over an sp=8 mesh that SPANS both processes — the
ppermute kv ring actually crosses the process boundary, which is the
multi-host long-context claim (SURVEY §5.7/§5.8) exercised for real
rather than on a single-process virtual mesh.

Prints CHECKS <json> with value/grad checksums; test_dist_train.py
compares them against the single-process dense reference.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

B, H, T, D = 2, 2, 64, 8


def _setup_env():
    """Process env for the runner role — called ONLY under __main__ so
    that the test process can import this module for make_qkv/constants
    without its os.environ being rewritten."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append("--xla_force_host_platform_device_count=4")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def make_qkv():
    rng = np.random.RandomState(17)
    return [rng.rand(B, H, T, D).astype("float32") for _ in range(3)]


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu import parallel
    from paddle_tpu.parallel import collective

    pid = int(os.environ["PADDLE_TRAINER_ID"])
    nproc = int(os.environ["PADDLE_TRAINERS"])
    collective.init_distributed_env(
        coordinator_address=os.environ["COORDINATOR"],
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc
    assert jax.device_count() == 4 * nproc  # 4 local devices per process

    mesh = Mesh(np.array(jax.devices()), ("sp",))
    sharding = NamedSharding(mesh, P(None, None, "sp", None))
    q_np, k_np, v_np = make_qkv()

    def to_global(x):
        # every process holds the same full array; hand jax this
        # process's local shard of the time axis
        per = T // jax.device_count()
        lo = pid * 4 * per
        hi = lo + 4 * per
        return jax.make_array_from_process_local_data(
            sharding, x[:, :, lo:hi, :], x.shape)

    q, k, v = to_global(q_np), to_global(k_np), to_global(v_np)

    def loss(q, k, v):
        out = parallel.ring.ring_attention_sharded(
            q, k, v, mesh, "sp", causal=True)
        return jnp.sum(out ** 2)

    val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)
    gsums = [float(jnp.sum(g ** 2)) for g in grads]
    print("CHECKS " + json.dumps({"val": float(val), "gsums": gsums}),
          flush=True)


if __name__ == "__main__":
    _setup_env()
    main()
