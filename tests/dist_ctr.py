"""Distributed CTR runner: DeepFM (models/ctr_deepfm.py) with
is_distributed embedding tables over the pserver path — the
planet-scale sparse scenario (ROADMAP item 3) at HIGH ROW-CHURN: every
step draws fresh uniform ids over the whole field range, so the sparse
stream touches new rows constantly instead of replaying a hot set.

Same env contract as dist_mlp.py (PADDLE_TRAINING_ROLE / PADDLE_* /
DIST_*); bench.py's `pserver_sparse_async_2x2` leg drives it with
--async-mode so the durable-async machinery (journal, seq fences,
clock-stamped prefetch) carries the whole stream.  Extra env:

  DIST_FIELD_DIM   rows per sparse field table   (default 1000)
  DIST_FIELDS      number of sparse id fields    (default 4)
  DIST_EPHEMERAL_CKPT=1  pserver role: checkpoint/journal into a fresh
      temp dir when PADDLE_PSERVER_CKPT_DIR is unset — arms the async
      write-ahead journal for bench legs without cross-run contamination
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.ctr_deepfm import build_deepfm_train

SEED = 11


def main():
    role = os.environ.get("PADDLE_TRAINING_ROLE", "LOCAL")
    eps = os.environ.get("PADDLE_PSERVER_EPS", "")
    trainers = int(os.environ.get("PADDLE_TRAINERS", "1"))
    trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    sync_mode = os.environ.get("DIST_SYNC_MODE", "1") == "1"
    steps = int(os.environ.get("DIST_STEPS", "4"))
    batch = int(os.environ.get("DIST_BATCH", "64"))
    field_dim = int(os.environ.get("DIST_FIELD_DIM", "1000"))
    n_fields = int(os.environ.get("DIST_FIELDS", "4"))

    main_prog = fluid.default_main_program()
    main_prog.random_seed = SEED
    fluid.default_startup_program().random_seed = SEED
    feeds, loss, _pred = build_deepfm_train(
        [field_dim] * n_fields, dense_dim=4, embed_dim=8,
        is_distributed=(role != "LOCAL"))
    fluid.optimizer.SGD(0.05).minimize(loss)

    # high row-churn stream: fresh uniform ids each step, deterministic
    rng = np.random.RandomState(SEED)
    batches = []
    for _ in range(steps):
        feed = {"C%d" % i: rng.randint(0, field_dim, (batch, 1))
                .astype("int64") for i in range(n_fields)}
        feed["dense"] = rng.rand(batch, 4).astype("float32")
        feed["click"] = (rng.rand(batch, 1) < 0.3).astype("float32")
        batches.append(feed)

    exe = fluid.Executor(fluid.CPUPlace())

    if role == "LOCAL":
        exe.run(fluid.default_startup_program())
        losses = []
        for feed in batches:
            (lv,) = exe.run(feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        print("LOSSES " + json.dumps(losses))
        return

    config = fluid.DistributeTranspilerConfig()
    t = fluid.DistributeTranspiler(config=config)
    t.transpile(trainer_id, program=main_prog, pservers=eps,
                trainers=trainers, sync_mode=sync_mode)

    if role == "PSERVER":
        if (os.environ.get("DIST_EPHEMERAL_CKPT") == "1"
                and not os.environ.get("PADDLE_PSERVER_CKPT_DIR")):
            import atexit
            import shutil
            import tempfile

            d = tempfile.mkdtemp(prefix="dist_ctr_ckpt_")
            os.environ["PADDLE_PSERVER_CKPT_DIR"] = d
            atexit.register(shutil.rmtree, d, True)
        cur = os.environ["PADDLE_CURRENT_ENDPOINT"]
        pserver_prog = t.get_pserver_program(cur)
        startup = t.get_startup_program(cur, pserver_prog)
        scope = fluid.global_scope()
        exe.run(startup, scope=scope)
        print("PSERVER READY", flush=True)
        exe.run(pserver_prog, scope=scope)
        print("PSERVER DONE")
        return

    # TRAINER
    trainer_prog = t.get_trainer_program()
    exe.run(fluid.default_startup_program())
    shard = batch // trainers
    lo, hi = trainer_id * shard, (trainer_id + 1) * shard
    losses = []
    for i, feed in enumerate(batches):
        feed = {k: v[lo:hi] for k, v in feed.items()}
        (lv,) = exe.run(program=trainer_prog, feed=feed,
                        fetch_list=[loss])
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
        print("STEP %d" % i, flush=True)
    from paddle_tpu.distributed import rpc as _rpc

    counters = _rpc.get_comm_stats()
    counters["host_feed_ms"] = round(exe.host_feed_ms, 3)
    counters["bytes_per_step"] = round(
        counters["comm_bytes_sent"] / max(1, steps), 1)
    exe.close()
    print("COUNTERS " + json.dumps(counters))
    print("LOSSES " + json.dumps(losses))


if __name__ == "__main__":
    main()
