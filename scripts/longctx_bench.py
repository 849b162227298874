"""Single-chip long-context attention bench (VERDICT r4 #9).

Substantiates the long-context story on ONE chip: the Pallas flash
kernels (ops/pallas_kernels.py — O(T) memory, blocked both passes) run
a fwd+bwd attention step at seq 8k/16k/32k where dense attention's
[B, H, T, T] score tensor OOMs HBM.  Prints one table row per sequence
length: tokens/sec through flash fwd+bwd, plus whether the DENSE path at
that length fits (expected: 8k marginal, 16k+ OOM at these shapes — the
dense failure point is part of the evidence).

Run on a TPU host; falls back to small seqs on CPU:
    python scripts/longctx_bench.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    # B*H=16 heads of d=64: a gpt2-small-ish attention slice; tokens/sec
    # is per-sequence tokens (B=1)
    BH, D = 16, 64
    # CPU = interpret-mode pallas (a functional smoke, not a perf number)
    seqs = [8192, 16384, 32768] if on_tpu else [256]
    steps = 5 if on_tpu else 1
    rows = []
    for T in seqs:
        q, k, v = (
            jax.device_put(
                np.random.RandomState(i).rand(BH, T, D).astype("float32")
                * 0.1, dev)
            for i in range(3)
        )

        def loss_flash(q, k, v):
            return jnp.sum(
                pk.flash_attention(q, k, v, causal=True) ** 2)

        step = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
        g = step(q, k, v)
        jax.block_until_ready(g)  # compile + warm
        t0 = time.time()
        for _ in range(steps):
            g = step(q, k, v)
        jax.block_until_ready(g)
        dt = time.time() - t0
        flash_tok = T * steps / dt

        # dense comparison at the same shape: OOM (or not) is the datum
        dense_tok, dense_err = None, None
        try:
            def loss_dense(q, k, v):
                s = jnp.einsum("bqd,bkd->bqk", q, k) * (D ** -0.5)
                mask = jnp.tril(jnp.ones((T, T), bool))
                p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), -1)
                return jnp.sum(jnp.einsum("bqk,bkd->bqd", p, v) ** 2)

            dstep = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))
            gd = dstep(q, k, v)
            jax.block_until_ready(gd)
            t0 = time.time()
            for _ in range(steps):
                gd = dstep(q, k, v)
            jax.block_until_ready(gd)
            dense_tok = T * steps / (time.time() - t0)
        except Exception as e:
            dense_err = type(e).__name__
            if "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower():
                dense_err = "OOM"
        rows.append({
            "seq": T,
            "flash_tokens_per_sec": round(flash_tok, 1),
            "dense_tokens_per_sec": (round(dense_tok, 1)
                                     if dense_tok else None),
            "dense_result": dense_err or "ok",
            "platform": dev.platform,
        })
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"longctx": rows}))


if __name__ == "__main__":
    if os.environ.get("LONGCTX_FORCE_CPU") == "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
    main()
