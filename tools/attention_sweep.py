"""The sweep behind fused_attention's engagement constants
(ops/nn_ops._FLASH_MIN_T, _FLASH_BLOCKS): forward +
backward of one attention layer alone on the chip, the blockwise kernel
at each legal block pair against the dense lowering, bf16, at the
transformer cells' attention shapes.  Run on a TPU:

    python3 tools/attention_sweep.py [--out chiprun_out/attention_sweep.json]

Prints one JSON line a (shape, lowering) and the best block pair a shape.
A lowering is judged in its cell in the end (PERF.md, PR 24); this only
orders the candidates.  T = 64 (tfm_base_train_s64) is no multiple of 128
and cannot engage, so it is not here.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (cell, B*H on a chip, T, head dim, causal, key-padding bias)
SHAPES = [
    ("gpt2_345m_train", 64, 1024, 64, True, False),
    ("gpt2_345m_train_dp2mp2", 32, 1024, 64, True, False),
    ("olmoe_1b7b_train", 32, 4096, 128, True, False),
    ("tfm_base_train.decoder", 1024, 256, 64, True, True),
    ("tfm_base_train.encoder", 1024, 256, 64, False, True),
    ("T512", 128, 512, 64, True, False),
]
BLOCKS = (128, 256, 512, 1024)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/attention_sweep.json")
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("attention_sweep: needs a TPU, jax found %s" % dev)

    def timed(fn, operands):
        f = jax.jit(jax.grad(
            lambda q, k, v, kb: jnp.sum(fn(q, k, v, kb).astype(jnp.float32)),
            argnums=(0, 1, 2)))
        jax.block_until_ready(f(*operands))
        t = time.perf_counter()
        for _ in range(args.iters):
            out = f(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / args.iters * 1e3

    rows = []
    for name, bh, t, d, causal, bias in SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.float32).astype(
            jnp.bfloat16) for kk in keys)
        kb = (jnp.where(jnp.arange(t)[None, :] < t - 7, 0.0, -1e9).astype(
            jnp.float32) * jnp.ones((bh, 1), jnp.float32)) if bias else None
        scale = d ** -0.5
        dense_ms = timed(lambda q, k, v, kb: pk._dense_attention(
            q, k, v, causal, scale, kb), (q, k, v, kb))
        row = {"shape": name, "bh": bh, "t": t, "d": d, "causal": causal,
               "kbias": bias, "dense_ms": round(dense_ms, 4), "kernel_ms": {}}
        for bq in BLOCKS:
            for bk in BLOCKS:
                if bq > t or bk > t:
                    continue
                try:
                    ms = timed(lambda q, k, v, kb: pk.flash_attention(
                        q, k, v, kb, causal, scale, bq, bk), (q, k, v, kb))
                except Exception as e:  # e.g. a tile set over the VMEM limit
                    ms = None
                    print("%s %dx%d refused: %s" % (name, bq, bk,
                                                    str(e)[:200]), flush=True)
                row["kernel_ms"]["%dx%d" % (bq, bk)] = (
                    None if ms is None else round(ms, 4))
        ok = {b: ms for b, ms in row["kernel_ms"].items() if ms is not None}
        row["best"] = min(ok, key=ok.get) if ok else None
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "iters": args.iters,
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
