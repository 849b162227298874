"""Distributed-training runner: one process = one role (trainer/pserver).

The model file of the reference's dist test harness (test_dist_base.py:34
TestDistRunnerBase + dist_mnist.py): test_dist_train.py spawns this script
as localhost subprocesses with the PADDLE_* env contract and compares
trainer losses against a local run.

Env contract (fluid_benchmark.py:63-100 analog):
  PADDLE_TRAINING_ROLE = TRAINER | PSERVER | LOCAL
  PADDLE_PSERVER_EPS   = "127.0.0.1:p1,127.0.0.1:p2"
  PADDLE_CURRENT_ENDPOINT (pserver role)
  PADDLE_TRAINERS, PADDLE_TRAINER_ID
  DIST_SYNC_MODE = 1|0, DIST_STEPS, DIST_BATCH
  DIST_MODE = pserver (default) | collective — collective lowers dense
    grad sync into the compiled step (c_allreduce over the dp mesh, no
    pserver round trip for dense params); multi-process when launched
    with PADDLE_TRAINER_ENDPOINTS (one device per process via
    jax.distributed), else a single-process CPU mesh of
    DIST_COLLECTIVE_DEVICES (default 2) virtual devices.  With
    DIST_MODEL=sparse the run is HYBRID: embedding rows still ride the
    pserver (PADDLE_PSERVER_EPS), dense grads ride the mesh.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

_COLLECTIVE = os.environ.get("DIST_MODE") == "collective"
_TRAINER_EPS = [e for e in os.environ.get(
    "PADDLE_TRAINER_ENDPOINTS", "").split(",") if e.strip()]


def _parse_resize(spec):
    """DIST_RESIZE="step:nranks[,step:nranks]" — deterministic elastic
    collective driver: at training step `step`, resize the virtual mesh
    to `nranks` (re-trace + token drain happen inside the executor)."""
    out = []
    for part in (spec or "").split(","):
        part = part.strip()
        if part:
            a, _, b = part.partition(":")
            out.append((int(a), int(b)))
    return sorted(out)


_RESIZE_PLAN = _parse_resize(os.environ.get("DIST_RESIZE"))
if _COLLECTIVE and os.environ.get("PADDLE_TRAINING_ROLE") != "PSERVER":
    # device topology must be pinned BEFORE jax loads: multi-process runs
    # put ONE device in each trainer process (the mesh spans processes);
    # a single process hosts the whole mesh as virtual CPU devices.
    # Elastic collective (--elastic / DIST_RESIZE) pins the MAX mesh the
    # job can grow to — resizes then only re-trace, never re-boot jax.
    _n_dev = (1 if len(_TRAINER_EPS) > 1
              else int(os.environ.get("DIST_COLLECTIVE_DEVICES", "2")))
    for _, _to in _RESIZE_PLAN:
        _n_dev = max(_n_dev, _to)
    _el = os.environ.get("DIST_COLLECTIVE_ELASTIC", "")
    if _el:
        _n_dev = max(_n_dev, int(_el.split(":")[1]))
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if not f.startswith("--xla_force_host_platform_device_count")]
    _flags.append("--xla_force_host_platform_device_count=%d" % _n_dev)
    os.environ["XLA_FLAGS"] = " ".join(_flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers

SEED = 7


def build_sparse_model(distributed):
    """Distributed-lookup-table model (dist role passes distributed=True;
    LOCAL runs the plain lookup so parity compares the two paths).
    DIST_OPTIMIZER=adam_decay swaps in Adam + exponential lr decay with
    is_sparse=True, so the LOCAL reference runs the lazy SelectedRows
    adam branch — the exact rule the pserver replays per shard."""
    opt_kind = os.environ.get("DIST_OPTIMIZER", "sgd")
    lazy = opt_kind in ("adam_decay", "momentum")
    ids = layers.data("ids", shape=[1], dtype="int64")
    y = layers.data("y", shape=[1])
    emb = layers.embedding(
        ids, size=[20, 8], dtype="float32", is_sparse=lazy,
        is_distributed=distributed
    )
    emb = layers.reshape(emb, [-1, 8])
    pred = layers.fc(emb, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    if opt_kind == "adam_decay":
        lr = layers.exponential_decay(0.05, decay_steps=2, decay_rate=0.9)
        fluid.optimizer.Adam(lr).minimize(loss)
    elif opt_kind == "momentum":
        fluid.optimizer.Momentum(0.05, momentum=0.9).minimize(loss)
    else:
        fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def gen_sparse_data(n=16):
    rng = np.random.RandomState(5)
    ids = rng.randint(0, 20, (n, 1)).astype("int64")
    if os.environ.get("DIST_SPARSE_IDS") == "even":
        # every id lands on pserver 0 (id % 2 == 0): shard 1 sees
        # ROWLESS rounds only — the adam beta-pow / momentum-decay
        # advance-on-empty path end to end
        ids = (ids // 2) * 2
    y = (ids.astype("float32") / 10.0) - 1.0
    return ids, y


def build_model():
    x = layers.data("x", shape=[4])
    y = layers.data("y", shape=[1])
    # DIST_HIDDEN widens the MLP so wire-compression A/Bs can measure a
    # payload-bound step (the default 8 is framing-bound); parity tests
    # keep the default
    hidden = int(os.environ.get("DIST_HIDDEN", "8"))
    h = layers.fc(x, size=hidden, act="relu")
    # per-param lr exercises the optimize-role `scale` helper op path
    pred = layers.fc(h, size=1, param_attr=fluid.ParamAttr(learning_rate=0.5))
    loss = layers.mean(layers.square_error_cost(pred, y))
    if os.environ.get("DIST_OPTIMIZER", "sgd") == "adam_decay":
        lr = layers.exponential_decay(0.05, decay_steps=2, decay_rate=0.9)
        opt = fluid.optimizer.Adam(lr)
    else:
        opt = fluid.optimizer.SGD(0.1)
    opt.minimize(loss)
    return loss


def gen_data(n=16):
    rng = np.random.RandomState(3)
    x = rng.rand(n, 4).astype("float32")
    w = np.array([[1.0], [-2.0], [3.0], [0.5]], dtype=np.float32)
    y = x @ w + 0.1 * rng.rand(n, 1).astype("float32")
    return x, y


def main():
    role = os.environ.get("PADDLE_TRAINING_ROLE", "LOCAL")
    eps = os.environ.get("PADDLE_PSERVER_EPS", "")
    trainers = int(os.environ.get("PADDLE_TRAINERS", "1"))
    trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    sync_mode = os.environ.get("DIST_SYNC_MODE", "1") == "1"
    steps = int(os.environ.get("DIST_STEPS", "4"))
    batch = int(os.environ.get("DIST_BATCH", "16"))

    main_prog = fluid.default_main_program()
    main_prog.random_seed = SEED
    fluid.default_startup_program().random_seed = SEED
    sparse = os.environ.get("DIST_MODEL") == "sparse"
    if sparse:
        loss = build_sparse_model(distributed=(role != "LOCAL"))
        x, y = gen_sparse_data()
        feed_x = "ids"
    else:
        loss = build_model()
        x, y = gen_data()
        feed_x = "x"

    exe = fluid.Executor(fluid.CPUPlace())

    if role == "LOCAL":
        exe.run(fluid.default_startup_program())
        losses = []
        for _ in range(steps):
            (lv,) = exe.run(
                feed={feed_x: x[:batch], "y": y[:batch]}, fetch_list=[loss]
            )
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        print("LOSSES " + json.dumps(losses))
        return

    collective = os.environ.get("DIST_MODE") == "collective"
    # collective mode: one logical trainer per mesh replica — processes
    # when launched multi-process (one device each), virtual CPU devices
    # when single-process
    nranks = (len(_TRAINER_EPS) if len(_TRAINER_EPS) > 1
              else int(os.environ.get("DIST_COLLECTIVE_DEVICES", "2")))

    config = fluid.DistributeTranspilerConfig()
    config.min_block_size = 4  # tiny model: force splitting across servers
    if collective:
        config.mode = "collective"
    t = fluid.DistributeTranspiler(config=config)
    t.transpile(
        trainer_id,
        program=main_prog,
        pservers=eps,
        trainers=nranks if collective else trainers,
        sync_mode=sync_mode,
    )

    if role == "PSERVER":
        cur = os.environ["PADDLE_CURRENT_ENDPOINT"]
        if os.environ.get("PADDLE_PSERVER_ELASTIC") == "1":
            # elastic-grown server at an endpoint OUTSIDE the base set:
            # boots EMPTY and acquires shards via journaled handoff
            # (migrate_in) — docs/FAULT_TOLERANCE.md "Live shard
            # migration"
            pserver_prog = t.get_elastic_pserver_program(cur)
        else:
            pserver_prog = t.get_pserver_program(cur)
        startup = t.get_startup_program(cur, pserver_prog)
        scope = fluid.global_scope()
        exe.run(startup, scope=scope)
        print("PSERVER READY", flush=True)
        exe.run(pserver_prog, scope=scope)  # blocks until trainers complete
        print("PSERVER DONE")
        return

    # TRAINER
    trainer_prog = t.get_trainer_program()
    if collective and len(_TRAINER_EPS) > 1:
        # mesh spans processes: rank 0's endpoint coordinates
        from paddle_tpu import distributed as _dist

        _dist.init_collective()
    exe.run(fluid.default_startup_program())
    # this PROCESS's shard of the global batch (collective single-process
    # runs feed the whole batch; the executor splits it over the mesh)
    if collective:
        nproc = max(1, len(_TRAINER_EPS))
        shard = batch // nproc
        slot = trainer_id
    else:
        shard = batch // trainers
        # elastic ranks: a policy-grown trainer gets an id >= the
        # transpile-time world (PADDLE_TRAINERS) — it reuses a data slot
        # mod the original shard count (the plan epoch re-scales grads
        # for the LIVE world, so the extra contribution is weighted
        # correctly)
        slot = trainer_id % trainers
    lo, hi = slot * shard, (slot + 1) * shard
    step_sleep = float(os.environ.get("DIST_STEP_SLEEP", "0"))
    # chaos hook (tests/test_fault_tolerance.py): SIGKILL this rank after
    # step N — a real mid-training process death, no cleanup, no complete.
    # DIST_CRASH_ONCE names a marker file: the crash fires only while the
    # marker is absent (created just before the kill), so a SUPERVISED
    # relaunch of the same rank runs clean instead of crash-looping —
    # the deterministic "die once, rejoin" fence for the elastic tests.
    crash_rank = int(os.environ.get("DIST_CRASH_RANK", "-1"))
    crash_after = int(os.environ.get("DIST_CRASH_AFTER_STEP", "-1"))
    crash_once = os.environ.get("DIST_CRASH_ONCE", "")
    if crash_once and os.path.exists(crash_once):
        crash_rank = -1  # this incarnation already died once
    # elastic collective: DIST_RESIZE pins step-indexed mesh sizes;
    # DIST_COLLECTIVE_SCHEDULE (launch --elastic-schedule passthrough)
    # is the wall-clock +N/-N form, applied at step boundaries.  The
    # resize just rewrites program._collective["nranks"]: the executor
    # re-traces over the new dp mesh, and the mesh split re-shards the same
    # global batch — the mean-gradient trajectory is split-invariant.
    import time as _time

    resize_plan = list(_RESIZE_PLAN) if collective else []
    tsched, cur_n = [], nranks
    if collective and os.environ.get("DIST_COLLECTIVE_SCHEDULE"):
        lo, hi = (int(x) for x in
                  os.environ["DIST_COLLECTIVE_ELASTIC"].split(":"))
        for part in os.environ["DIST_COLLECTIVE_SCHEDULE"].split(","):
            part = part.strip()
            if part:
                t_s, _, d = part.partition(":")
                tsched.append((float(t_s), int(d)))
        tsched.sort()
        cur_n = min(max(cur_n, lo), hi)
    t0_wall = _time.monotonic()

    def maybe_resize(step_i):
        nonlocal cur_n
        new_n = cur_n
        while resize_plan and step_i >= resize_plan[0][0]:
            new_n = resize_plan.pop(0)[1]
        while tsched and _time.monotonic() - t0_wall >= tsched[0][0]:
            new_n += tsched.pop(0)[1]
            lo, hi = (int(x) for x in
                      os.environ["DIST_COLLECTIVE_ELASTIC"].split(":"))
            new_n = min(max(new_n, lo), hi)
        if new_n != cur_n:
            cur_n = new_n
            trainer_prog._collective["nranks"] = cur_n
            print("COLLECTIVE RESIZE step=%d nranks=%d" % (step_i, cur_n),
                  flush=True)

    losses = []
    for i in range(steps):
        if collective and (resize_plan or tsched):
            maybe_resize(i)
        (lv,) = exe.run(
            program=trainer_prog,
            feed={feed_x: x[lo:hi], "y": y[lo:hi]},
            fetch_list=[loss],
        )
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
        print("STEP %d" % i, flush=True)
        if trainer_id == crash_rank and i == crash_after:
            import signal

            if crash_once:
                with open(crash_once, "w") as f:
                    f.write("crashed\n")
            print("CRASHING trainer %d after step %d" % (trainer_id, i),
                  flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        if step_sleep:
            import time

            time.sleep(step_sleep)
    # comm evidence: client-side round trips / bytes plus feed-upload
    # time — deterministic counters the smoke tests read
    from paddle_tpu.distributed import rpc as _rpc

    counters = _rpc.get_comm_stats()
    counters["host_feed_ms"] = round(exe.host_feed_ms, 3)
    # wire-compression evidence: bytes on the wire per sync step (plan
    # property at fixed step count — the A/B the bf16 wire is judged on)
    counters["bytes_per_step"] = round(
        counters["comm_bytes_sent"] / max(1, steps), 1)
    if sparse and os.environ.get("DIST_DUMP_TABLE") == "1":
        # fetch EVERY row of each distributed table back from the
        # pservers (global row g lives on server g%N at local index
        # g//N) and print it exactly — the async chaos E2E asserts a
        # killed-and-restored run's table is BIT-IDENTICAL to an
        # unkilled run's (journal replay + fenced resend lose nothing)
        from paddle_tpu.distributed.rpc import RPCClient
        from paddle_tpu.ops import dist_ops

        ep_list = [e.strip() for e in eps.split(",") if e.strip()]
        # live pserver migration: shard s may have MOVED off the base
        # endpoint — route each read through the CURRENT plan (the
        # base endpoint may even be retired and gone)
        plan_st = dist_ops._plans.get(getattr(t, "plan_gid", None))
        dump = {}
        for w, info in sorted(t.sparse_tables.items()):
            n_rows = 20  # build_sparse_model's table size
            tbl = np.zeros((n_rows, info["emb_dim"]), np.float32)
            for s in range(len(ep_list)):
                ep = dist_ops._sparse_route(plan_st, s, ep_list)
                gids = np.arange(s, n_rows, len(ep_list), dtype=np.int64)
                rows = np.asarray(RPCClient.get(ep).prefetch(
                    info["shards"][s], gids // len(ep_list),
                    trainer_id=trainer_id))
                tbl[gids] = rows
            dump[w] = tbl.tolist()
        print("TABLE " + json.dumps(dump))
    exe.close()  # SendComplete to pservers
    print("COUNTERS " + json.dumps(counters))
    print("LOSSES " + json.dumps(losses))


if __name__ == "__main__":
    main()
