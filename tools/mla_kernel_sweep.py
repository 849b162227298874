"""The sweep behind the flash kernel's form at latent attention's widths
(ops/nn_ops._FLASH_WIDTHS: scores 192 wide = 128 without position + 64
rotary, values 128 wide): forward + backward of one attention core alone
on the chip, bf16, causal, 32 heads, at the sequence lengths the
kanana2_30b_a3b_train cell could take.  Run on a TPU:

    python3 tools/mla_kernel_sweep.py [--out chiprun_out/mla_kernel_sweep.json]

The forms, each the SAME kernel bodies (ops/pallas_kernels.py):

  one192       the score tile is one 192-wide contraction (what the
               lowering does)
  split128_64  the score tile is a 128-wide and a 64-wide contraction
               summed (`_dot_nt` patched here, for the sweep alone)
  pad256       q and k zero-padded to 256 outside the kernel, the padding
               inside the timed function
  two_kernel_bwd  one192 with the dq and the dk/dv kernel in place of the
               one-kernel backward (what `_FUSED_BWD_DQ_BYTES`, the limit
               the narrower heads were swept under, would have chosen
               above T = 4096)
  dense        the XLA lowering (T = 4096 only: [32, T, T] f32 scores)

and, first, the kernel against the dense lowering at T = 1024 on the chip
(largest absolute difference of the result and of the three gradients).
Prints one JSON line a (T, form); a form is judged in its cell in the end.

`--width 256 --heads 16` (PR 48) sweeps ONE head width for scores and
values, Qwen3-Next's gated attention: the one-kernel backward (its [T, d]
float32 dq scratch admitted whatever `_fused_bwd_dq_limit` says)
against the two-kernel one, at the block the lowering takes and at 512, at
T = 6144 and 8192.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, D_QK, D_NOPE, D_V = 32, 192, 128, 128
LENGTHS = (4096, 6144, 8192)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/mla_kernel_sweep.json")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--width", type=int, default=None,
                    help="one head width for q, k and v (256: Qwen3-Next's)"
                         " in place of latent attention's 192 over 128")
    ap.add_argument("--heads", type=int, default=HEADS)
    args = ap.parse_args()
    square = args.width is not None
    heads = args.heads
    d_qk, d_v = (args.width, args.width) if square else (D_QK, D_V)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import nn_ops, pallas_kernels as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("mla_kernel_sweep: needs a TPU, jax found %s" % dev)
    scale = d_qk ** -0.5

    def operands(t):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(
            jax.random.normal(kk, (heads, t, w), jnp.float32).astype(
                jnp.bfloat16)
            for kk, w in zip(keys, (d_qk, d_qk, d_v)))

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                    * jnp.cos(jnp.arange(d_v))),
            argnums=(0, 1, 2)))

    def timed(fn, ops):
        jax.clear_caches()  # a form patches what the jitted calls traced
        f = grads(fn)
        jax.block_until_ready(f(*ops))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = f(*ops)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3

    def kernel(block):
        return lambda q, k, v: pk.flash_attention(
            q, k, v, None, True, scale, block, block)

    def padded(block):
        pad = ((0, 0), (0, 0), (0, 256 - D_QK))
        return lambda q, k, v: pk.flash_attention(
            jnp.pad(q, pad), jnp.pad(k, pad), v, None, True, scale, block,
            block)

    dot_nt, dq_limit = pk._dot_nt, pk._fused_bwd_dq_limit

    def split_dot_nt(a, b):
        if a.shape[-1] != D_QK:
            return dot_nt(a, b)
        return (dot_nt(a[:, :D_NOPE], b[:, :D_NOPE])
                + dot_nt(a[:, D_NOPE:], b[:, D_NOPE:]))

    # ---- the kernel against the dense lowering, on the chip
    q, k, v = operands(1024)
    got = grads(kernel(1024))(q, k, v)
    want = grads(lambda q, k, v: pk._dense_attention(
        q, k, v, True, scale))(q, k, v)
    check = {"loss_abs_diff": float(abs(got[0] - want[0])),
             "loss": float(want[0])}
    for name, g, w in zip(("dq", "dk", "dv"), got[1], want[1]):
        check[name + "_max_abs_diff"] = float(jnp.max(jnp.abs(
            g.astype(jnp.float32) - w.astype(jnp.float32))))
        check[name + "_max_abs"] = float(jnp.max(jnp.abs(
            w.astype(jnp.float32))))
    print(json.dumps({"check_T1024": check}), flush=True)

    rows = []
    fused_limit = pk._FUSED_BWD_DQ_BYTES_WIDE
    for t in (LENGTHS[1:] if square else LENGTHS):
        ops = operands(t)
        block = nn_ops._flash_block(t, window=0)
        # what one core must do forward + backward over the causal half
        flops = 3.0 * 2.0 * heads * t * t / 2.0 * (d_qk + d_v)
        one = {"_fused_bwd_dq_limit": lambda d: 2 ** 40}
        two = {"_fused_bwd_dq_limit": lambda d: 0}
        forms = [("one_kernel_bwd", kernel(block), one),
                 ("one_kernel_bwd_b512", kernel(512), one),
                 ("two_kernel_bwd", kernel(block), two),
                 ("two_kernel_bwd_b512", kernel(512), two)] if square else [("one192", kernel(block), {}),
                 ("one192_b512", kernel(512), {}),
                 ("split128_64", kernel(block), {"_dot_nt": split_dot_nt}),
                 ("pad256", padded(block), {}),
                 ("two_kernel_bwd", kernel(block),
                  {"_FUSED_BWD_DQ_BYTES_WIDE": 0}),
                 ("two_kernel_bwd_b512", kernel(512),
                  {"_FUSED_BWD_DQ_BYTES_WIDE": 0})]
        if t == 4096 and not square:
            forms.append(("dense", lambda q, k, v: pk._dense_attention(
                q, k, v, True, scale), {}))
        for name, fn, patch in forms:
            for attr, value in patch.items():
                setattr(pk, attr, value)
            try:
                ms = timed(fn, ops)
            except Exception as e:  # e.g. a tile set over the VMEM limit
                ms = None
                print("T=%d %s refused: %s" % (t, name, str(e)[:300]),
                      flush=True)
            finally:
                pk._dot_nt, pk._FUSED_BWD_DQ_BYTES_WIDE = dot_nt, fused_limit
                pk._fused_bwd_dq_limit = dq_limit
            row = {"t": t, "form": name, "block": block,
                   "one_kernel_bwd": bool(
                       (square or t * d_qk * 4 <= fused_limit)
                       and "two_kernel" not in name),
                   "ms": None if ms is None else round(ms, 4),
                   "tflops_causal_half": None if ms is None else round(
                       flops / ms / 1e9, 2)}
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "iters": args.iters,
                   "heads": heads, "widths": [d_qk, d_v],
                   "check_T1024": check, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
