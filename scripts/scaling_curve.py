"""Distributed scaling curve on the virtual CPU mesh (VERDICT r3 item 8).

Real multi-chip hardware is unavailable in this sandbox, so this squeezes
the evidence that IS obtainable: steps/sec for the SAME global-batch
workload as the device count grows 1 -> 2 -> 4 -> 8 on the
xla_force_host_platform_device_count mesh, for

  - dp: DistributedExecutor over a {dp: n} mesh (fluid_benchmark.py's
    multi-device data-parallel leg re-expressed as one SPMD jit), and
  - pp: the gpipe schedule over a {pp: n} mesh (pipeline.py), stages
    stacked with stack_stage_params.

Also asserts the compile-count invariant per size (one traced executable
per (program, signature); `jitted._cache_size() == 1`).  Virtual CPU
devices share one host's cores, so ideal scaling is NOT expected — the
curve documents that per-step time doesn't degrade as collectives enter
the graph (the mechanism evidence), not absolute speedup.

Run:

  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/scaling_curve.py
"""

import os
import sys
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) >= 8, (
    "need >= 8 virtual devices; inherited XLA_FLAGS pinned a smaller "
    "xla_force_host_platform_device_count: %r" % os.environ.get("XLA_FLAGS"))

GLOBAL_BATCH = 256
STEPS = 20


def dp_leg(n):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.parallel.executor import DistributedExecutor
    from paddle_tpu.parallel.mesh import make_mesh

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data("img", shape=[784])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(img, 512, act="relu")
        h = layers.fc(h, 512, act="relu")
        pred = layers.fc(h, 10, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.01).minimize(loss)
    scope = scope_mod.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
    dexe = DistributedExecutor(mesh, main_program=main, scope=scope)
    rng = np.random.RandomState(0)
    x = rng.rand(GLOBAL_BATCH, 784).astype("float32")
    y = rng.randint(0, 10, (GLOBAL_BATCH, 1)).astype("int64")
    feed = {"img": x, "label": y}
    for _ in range(3):  # compile + warm
        dexe.run([loss], feed=feed)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        dexe.run([loss], feed=feed)
    dt = time.perf_counter() - t0
    assert len(dexe._cache) == 1, len(dexe._cache)
    (_, jitted), = dexe._cache.values()
    assert jitted._cache_size() == 1, jitted._cache_size()
    return STEPS / dt


def pp_leg(n):
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import (
        gpipe,
        pipeline_mlp_stages,
        stack_stage_params,
    )

    mesh = make_mesh({"pp": n}, devices=jax.devices()[:n])
    # n stages of a 512-wide MLP; microbatches = 2n
    stage_fn, init_stage = pipeline_mlp_stages(512)
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    params = stack_stage_params([init_stage(k) for k in keys])
    # gpipe returns the raw shard_map callable; jit it so steady-state
    # steps measure execution, not per-call retracing
    run = jax.jit(gpipe(stage_fn, mesh, n_microbatches=2 * n))
    x = jnp.asarray(np.random.RandomState(1).rand(
        GLOBAL_BATCH, 512).astype("float32"))
    out = run(params, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = run(params, x)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    assert run._cache_size() == 1, run._cache_size()  # no retrace per step
    return STEPS / dt


def sp_leg(n):
    """Ring attention over an {sp: n} mesh: the SAME global sequence
    (B2 H4 T1024 D64) sharded on time; grad included (fwd+bwd is the
    training-relevant path)."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.ring import ring_attention_sharded

    mesh = make_mesh({"sp": n}, devices=jax.devices()[:n])
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.rand(2, 4, 1024, 64).astype("float32"))
               for _ in range(3))

    @jax.jit
    def step(q, k, v):
        def loss(q):
            o = ring_attention_sharded(q, k, v, mesh, causal=True)
            return jnp.sum(o * o)

        return jax.grad(loss)(q)

    out = step(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = step(q, k, v)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    assert step._cache_size() == 1, step._cache_size()
    return STEPS / dt


def ep_leg(n):
    """Switch-MoE dispatch over an {ep: n} mesh: same global token batch,
    n experts (one per device)."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.moe import switch_moe

    mesh = make_mesh({"ep": n}, devices=jax.devices()[:n])
    d = 128
    rng = np.random.RandomState(3)

    def expert_fn(params, x):
        return jnp.tanh(x @ params)

    gate_w = jnp.asarray(rng.rand(d, n).astype("float32") * 0.1)
    params = jnp.asarray(rng.rand(n, d, d).astype("float32") * 0.05)
    x = jnp.asarray(rng.rand(GLOBAL_BATCH, d).astype("float32"))
    moe = switch_moe(expert_fn, mesh)
    run = jax.jit(lambda gw, p, x: moe(gw, p, x)[0])
    out = run(gate_w, params, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = run(gate_w, params, x)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    assert run._cache_size() == 1, run._cache_size()
    return STEPS / dt


def _watch_trainer(proc, steps, who):
    """Time trainer-0 stdout from its first STEP line to LOSSES (excludes
    startup + compile; measures the steady-state loop); returns
    (steps/sec, COUNTERS dict)."""
    t_first, saw_losses, counters = None, False, None
    for line in proc.stdout:
        if line.startswith("STEP ") and t_first is None:
            t_first = time.time()
        if line.startswith("COUNTERS "):
            import json

            counters = json.loads(line[len("COUNTERS "):])
        if line.startswith("LOSSES"):
            saw_losses = True
            break
    if t_first is None or not saw_losses:
        raise RuntimeError(
            "%s: trainer 0 %s (crashed mid-run?)" % (
                who,
                "emitted no STEP line" if t_first is None
                else "died before its LOSSES line"))
    dt = time.time() - t_first
    return (steps - 1) / max(dt, 1e-9), counters


def pserver_leg(n_trainers=2, n_pservers=2, steps=12):
    """REAL multi-process pserver throughput (VERDICT r4 #8): spawn
    n_pservers VarServer + n_trainers trainer subprocesses on localhost
    (tests/dist_mlp.py runner, the test_dist_base.py:34 topology /
    fluid_benchmark.py --update_method pserver analog) and measure
    wall-clock steps/sec INCLUDING rpc transport, barriers, and the
    pserver-side optimize rounds.  Returns steps/sec of the sync round
    loop (all trainers advance together)."""
    import socket
    import subprocess

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runner = os.path.join(here, "tests", "dist_mlp.py")

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    ports = [free_port() for _ in range(n_pservers)]
    eps = ",".join("127.0.0.1:%d" % p for p in ports)
    common = dict(os.environ)
    common.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": str(n_trainers),
        "DIST_SYNC_MODE": "1", "DIST_STEPS": str(steps),
    })

    def spawn(extra, capture):
        env = dict(common)
        env.update(extra)
        # only trainer 0's stdout is read; everything else goes to
        # DEVNULL so no unread PIPE can fill up and deadlock a child
        return subprocess.Popen(
            [sys.executable, runner], env=env,
            stdout=subprocess.PIPE if capture else subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, text=True)

    pservers = [spawn({"PADDLE_TRAINING_ROLE": "PSERVER",
                       "PADDLE_CURRENT_ENDPOINT": "127.0.0.1:%d" % p},
                      capture=False)
                for p in ports]
    trainers = []
    try:
        for p in ports:
            t0 = time.time()
            while time.time() - t0 < 60:
                try:
                    socket.create_connection(("127.0.0.1", p),
                                             timeout=1).close()
                    break
                except OSError:
                    time.sleep(0.2)
        trainers = [spawn({"PADDLE_TRAINING_ROLE": "TRAINER",
                           "PADDLE_TRAINER_ID": str(i)}, capture=(i == 0))
                    for i in range(n_trainers)]
        rate, counters = _watch_trainer(trainers[0], steps, "pserver_leg")
        for t in trainers:
            t.wait(timeout=120)
        for ps in pservers:
            ps.wait(timeout=90)
        return rate, counters
    finally:
        for proc in pservers + trainers:
            if proc.poll() is None:
                proc.kill()


def collective_leg(n_devices=2, steps=12):
    """Collective dense-gradient backend (DistributeTranspiler
    mode="collective") on the SAME dist MLP workload: one trainer
    process hosting an n-device virtual CPU mesh, dense grad sync as an
    in-step c_allreduce — zero RPC round trips — so the pserver and
    collective backends A/B on one curve."""
    import subprocess

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runner = os.path.join(here, "tests", "dist_mlp.py")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TRAINING_ROLE": "TRAINER",
        "DIST_MODE": "collective",
        "DIST_COLLECTIVE_DEVICES": str(n_devices),
        "DIST_STEPS": str(steps),
    })
    env.pop("PADDLE_PSERVER_EPS", None)
    env.pop("PADDLE_TRAINER_ENDPOINTS", None)
    proc = subprocess.Popen(
        [sys.executable, runner], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    try:
        rate, counters = _watch_trainer(proc, steps, "collective_leg")
        proc.wait(timeout=120)
        return rate, counters
    finally:
        if proc.poll() is None:
            proc.kill()


def main():
    print("| devices | dp steps/s (MLP bs%d) | pp steps/s (gpipe fwd) |"
          " sp steps/s (ring attn grad T1024) | ep steps/s (switch moe) |"
          % GLOBAL_BATCH)
    print("|---|---|---|---|---|")
    for n in (1, 2, 4, 8):
        dp = dp_leg(n)
        pp = pp_leg(n)
        sp = sp_leg(n)
        ep = ep_leg(n)
        print("| %d | %.2f | %.2f | %.2f | %.2f |" % (n, dp, pp, sp, ep),
              flush=True)
    ps_steps = 12
    ps_rate, counters = pserver_leg(steps=ps_steps)
    print("\npserver mode (REAL subprocesses, localhost rpc): "
          "2 pservers x 2 trainers sync = %.2f steps/s "
          "(wall-clock incl. transport + barriers)" % ps_rate, flush=True)
    if counters:
        print("pserver trainer-0 comm counters: %s" % counters, flush=True)
        # wire-compression evidence: bytes/step at the configured wire
        # dtype (FLAGS_comm_wire_dtype), incl. what compression saved
        bps = counters.get("bytes_per_step",
                           counters.get("comm_bytes_sent", 0) / ps_steps)
        print("pserver trainer-0 wire: dtype=%s %.1f KiB sent/step, "
              "%.1f KiB saved total by compression"
              % (counters.get("wire_dtype", "float32"), bps / 1024.0,
                 counters.get("comm_bytes_saved", 0) / 1024.0),
              flush=True)
    # the A/B: SAME workload, dense grads over the mesh instead of rpc
    co_rate, co_counters = collective_leg(n_devices=2, steps=ps_steps)
    print("collective mode (in-step c_allreduce over a 2-device CPU "
          "mesh): %.2f steps/s" % co_rate, flush=True)
    if co_counters:
        print("collective trainer comm: %.1f bytes/step sent, "
              "rpc_round_trips=%d (dense grads never leave the "
              "compiled step)"
              % (co_counters.get("bytes_per_step", 0.0),
                 co_counters.get("rpc_round_trips", 0)), flush=True)


if __name__ == "__main__":
    main()
