"""Measured GPT-2 TP+DP training step on the virtual CPU mesh.

BASELINE config 5's distributed leg ("ERNIE / GPT-2 345M, TP+DP on TPU
mesh"): one real training step of GPT-2 through the DistributedExecutor
over a {dp:2, mp:4} mesh with the transformer TP rules, timed.  On this
one-chip environment the mesh is 8 VIRTUAL cpu devices sharing host
cores — the number is a step-time/compile-correctness artifact, NOT a
scaling claim.

Run under: JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8
Prints ONE json line: {"steps_per_sec": ..., "d_model": ..., ...}
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.models import gpt2

    d_model = int(os.environ.get("GPT2_TP_DMODEL", "512"))
    n_layer = int(os.environ.get("GPT2_TP_LAYERS", "4"))
    seq = int(os.environ.get("GPT2_TP_SEQ", "128"))
    bs = int(os.environ.get("GPT2_TP_BATCH", "8"))
    steps = int(os.environ.get("GPT2_TP_STEPS", "3"))

    class HP(gpt2.GPT2Config):
        vocab_size = 8192
        n_ctx = max(1024, seq)
        dropout = 0.0

    HP.d_model = d_model
    HP.n_layer = n_layer
    HP.n_head = max(4, d_model // 64)

    main_p, startup, _feeds, fetches = gpt2.gpt2_lm_program(HP, seq_len=seq)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    mesh = parallel.make_mesh({"dp": 2, "mp": 4})
    rules = parallel.transformer_tp_rules("mp")
    dexe = parallel.DistributedExecutor(mesh, rules, main_program=main_p)
    batch = gpt2.make_fake_lm_batch(bs, seq, HP, seed=0)

    out = dexe.run(fetches, feed=batch)  # compile + step 0
    loss0 = float(np.asarray(out[0]).reshape(-1)[0])
    t0 = time.time()
    for _ in range(steps):
        out = dexe.run(fetches, feed=batch)
    loss = float(np.asarray(out[0]).reshape(-1)[0])
    dt = time.time() - t0
    assert np.isfinite(loss), loss
    print(json.dumps({
        "steps_per_sec": round(steps / dt, 3),
        "tokens_per_sec": round(bs * seq * steps / dt, 1),
        "d_model": d_model, "n_layer": n_layer, "seq": seq, "batch": bs,
        "mesh": "dp=2 x mp=4 (virtual cpu)",
        "loss0": round(loss0, 4), "loss": round(loss, 4),
    }))


if __name__ == "__main__":
    main()
