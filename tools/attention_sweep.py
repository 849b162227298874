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

With --window W it sweeps the sliding-window kernel instead (PR 41), at
trinity_mini_train's shape unless --bh / --t / --d say another: square
blocks of 1024 / 512 / 256, each on the band grid the kernel takes and on
the full grid it took before (pallas_kernels._band_grid held at 0 for
that row, here in the tool: the program has no such switch), forward
alone and forward + backward, with the grid steps a head walks and the
tiles it computes, and from the two grids the cost of one skipped step;
the full causal triangle (window 0) at 1024 beside them.

    python3 tools/attention_sweep.py --window 2048 [--out chiprun_out/window_sweep.json]
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
    ap.add_argument("--window", type=int, default=0,
                    help="sweep the sliding-window kernel at this window")
    ap.add_argument("--bh", type=int, default=32)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--rehearse", action="store_true",
                    help="the window sweep's plumbing on the CPU, kernels "
                    "interpreted: the times mean nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not (args.rehearse and args.window):
        raise SystemExit("attention_sweep: needs a TPU, jax found %s" % dev)

    def timed(fn, operands, backward=True):
        f = jax.jit(jax.grad(
            lambda q, k, v, kb: jnp.sum(fn(q, k, v, kb).astype(jnp.float32)),
            argnums=(0, 1, 2)) if backward else fn)
        jax.block_until_ready(f(*operands))
        t = time.perf_counter()
        for _ in range(args.iters):
            out = f(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / args.iters * 1e3

    def window_sweep():
        """One JSON line a (block, grid): ms forward and forward + backward,
        steps walked and tiles computed a head in each pass."""
        bh, t, d, w = args.bh, args.t, args.d, args.window
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.float32).astype(
            jnp.bfloat16) for kk in keys)
        scale = d ** -0.5
        band_grid = pk._band_grid
        rows = []
        for window, blk, band in [(w, b, g) for b in (1024, 512, 256)
                                  for g in (True, False)] + [(0, 1024, False)]:
            pk._band_grid = band_grid if band else (lambda *a, **kw: 0)
            jax.clear_caches()  # the kernels' entries are jitted

            def fn(q, k, v, kb):
                return pk.flash_attention(q, k, v, kb, True, scale, blk, blk,
                                          window)

            n = t // blk
            nb_f = pk._band_grid(t, t, blk, blk, True, window) or n
            nb_b = pk._band_grid(t, t, blk, blk, True, window,
                                 transposed=True) or n
            tiles = (pk.band_grid_steps(t, blk, blk, window)[1] if window
                     else n * (n + 1) // 2)
            fwd = timed(fn, (q, k, v, None), backward=False)
            both = timed(fn, (q, k, v, None))
            rows.append({
                "bh": bh, "t": t, "d": d, "window": window, "block": blk,
                "band_grid": bool(band and window),
                "fwd_ms": round(fwd, 4), "fwd_bwd_ms": round(both, 4),
                "bwd_ms": round(both - fwd, 4), "tiles": tiles,
                "fwd_steps": n * nb_f, "bwd_steps": n * nb_b})
            print(json.dumps(rows[-1]), flush=True)
        pk._band_grid = band_grid
        for blk in (1024, 512, 256):  # a skipped step, from the two grids
            on, off = (next(r for r in rows if r["block"] == blk
                            and r["window"] and r["band_grid"] == g)
                       for g in (True, False))
            cost = {"block": blk}
            for p in ("fwd", "bwd"):
                gone = off[p + "_steps"] - on[p + "_steps"]
                cost[p + "_skipped_step_ms"] = (
                    round((off[p + "_ms"] - on[p + "_ms"]) / gone, 5)
                    if gone else None)
                cost[p + "_skipped_step_us_a_head"] = (
                    round((off[p + "_ms"] - on[p + "_ms"]) / gone / bh * 1e3,
                          4) if gone else None)
            rows.append(cost)
            print(json.dumps(cost), flush=True)
        return rows

    if args.window:
        rows = window_sweep()
        if args.out == ap.get_default("out"):
            args.out = "chiprun_out/window_sweep.json"
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "iters": args.iters,
                       "rows": rows}, f, indent=1)
        return

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
