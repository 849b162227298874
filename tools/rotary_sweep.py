"""The sweep behind the form of `rotary_embed`'s `interleaved` branch
(ops/nn_ops._rotary_embed: the published (2i, 2i+1) pairing de-interleaved
to (i, i + Dh/2), then rotate-half): each form alone on the chip, forward
+ backward (the result and the gradient with respect to the input, both
written), float32 and bfloat16, at the shapes `kanana2_30b_a3b_train` gives
the op: q's rotary part [1, 32, 6144, 64] and the one rotary key
[1, 1, 6144, 64].  Run on a TPU:

    python3 tools/rotary_sweep.py [--out chiprun_out/rotary_sweep.json]

(`--rehearse`: the same plumbing at a tiny shape on the CPU; no number of
it is a device number.)

  slices             the lowering before PR 43, kept here as the reference:
                     concatenate([x[..., 0::2], x[..., 1::2]]), then
                     rotate-half.  JAX 0.9 lowers such an index to a
                     `gather` along the lane axis and its transpose to a
                     `scatter` with an add
  strided_slices     the same with `lax.slice` strides: two real slices of
                     stride 2 (two pads with interior padding backward)
  op                 what ships, the lowering itself, interleaved=True:
                     x @ P, P the Dh x Dh permutation of zeros and ones
                     (nn_ops._deinterleave), then rotate-half as it is
                     (32-lane halves, a concatenate)
  fused_matmul       one product with the Dh x 2Dh constant [P | P R] (R the
                     signed rotate-half), then y[..., :Dh] * [cos, cos] +
                     y[..., Dh:] * [sin, sin]: no half slices, no
                     concatenate; its backward SUMS two non-zero terms
                     inside the product
  two_matmuls        x @ P and x @ (P R), then y * [cos, cos] + z *
                     [sin, sin]: every value Dh lanes wide; the backward's
                     two terms meet in one float32 add, as the slices' do
  reshape_transpose  reshape [..., Dh/2, 2], swap the last two axes,
                     reshape back (the same permutation, left to XLA)
  rotate_half        interleaved=False on the same input: ANOTHER result
                     (no de-interleave at all), the floor a form can reach

and, beside q alone, `in_mla`: the op where latent attention has it, q
[1, 32, 6144, 192] split into 128 + 64, the 64 rotated, the two
concatenated again (models/transformer.latent_attention's `rope` scope).

A float32 product is taken at precision HIGHEST, whose bfloat16 pieces of a
float32 value times 1 sum back to the value: every form but `rotate_half`
is compared with `slices` on the device, result and gradient, bit for bit
(`equal`, `equal_grad`; `max_diff` the largest difference of either).  The
two forms that fold the rotate-half into the product write the rotation as
y * cos + z * sin over whole rows: where a compiler contracts a product and
a sum into one rounding (XLA's CPU backend does) they round the second half
in another order than x1 * sin + x2 * cos and are NOT equal in float32.
Prints one JSON line a (shape, dtype, form): ms, the bytes of one read and
one write forward and backward, that over 819 GB/s, and the ratio.  A time
is that of one forward + backward among 16 in one executable, each fed the
one before (alone, a call takes less than its dispatch, 0.37 ms): the
compiler lays the loop's values out as it likes and need not send them
through HBM, as in a step, so `floor_ms` is arithmetic, not a bound
(`rotate_half` in bfloat16 reads half of it).  A form is judged in its cell
in the end: the cell paid for the gathers twice what this sweep reads, in
copies around them that only a whole step has (PERF.md section 6, PR 43)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASE = 1e6  # kanana-2's rope_theta
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, "TPU v5 lite"
REPEAT = 16  # forward + backward passes in one timed executable
# name -> (shape of X, width split off in front of the rotary part)
SHAPES = {"q": ((1, 32, 6144, 64), 0), "k": ((1, 1, 6144, 64), 0),
          "q_in_mla": ((1, 32, 6144, 192), 128)}
TINY = {"q": ((2, 3, 16, 8), 0), "q_in_mla": ((2, 3, 16, 24), 16)}


def forms():
    """name -> f(x [B, H, T, Dh]) -> the rotated x, rotate-half order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import nn_ops

    def op(interleaved):
        def fn(x):
            return nn_ops._rotary_embed(
                LowerCtx(), {"X": [x]},
                {"base": BASE, "interleaved": interleaved})["Out"][0]
        return fn

    rotate_half = op(False)

    def slices(x):
        return rotate_half(
            jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1))

    def strided_slices(x):
        last = x.ndim - 1
        return rotate_half(jnp.concatenate(
            [jax.lax.slice_in_dim(x, 0, None, 2, last),
             jax.lax.slice_in_dim(x, 1, None, 2, last)], -1))

    def reshape_transpose(x):
        half = x.shape[-1] // 2
        pairs = x.reshape(x.shape[:-1] + (half, 2))
        return rotate_half(jnp.swapaxes(pairs, -1, -2).reshape(x.shape))

    def signed_half(dh):  # (x1, x2) @ R = (-x2, x1)
        r = np.zeros((dh, dh), np.float32)
        half = dh // 2
        r[np.arange(half, dh), np.arange(half)] = -1
        r[np.arange(half), np.arange(half, dh)] = 1
        return r

    def times(x, m):
        return jnp.matmul(x, jnp.asarray(m, x.dtype),
                          precision=jax.lax.Precision.HIGHEST)

    def doubled(x):  # [sin, sin], [cos, cos] over [1, 1, T, Dh]
        half = x.shape[-1] // 2
        freq = BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None]
        ang = jnp.concatenate([ang, ang], -1)[None, None]
        return jnp.sin(ang).astype(x.dtype), jnp.cos(ang).astype(x.dtype)

    def fused_matmul(x):
        dh = x.shape[-1]
        p = nn_ops._pairing_permutation(dh)
        y = times(x, np.concatenate([p, p @ signed_half(dh)], 1))
        sin2, cos2 = doubled(x)
        return y[..., :dh] * cos2 + y[..., dh:] * sin2

    def two_matmuls(x):
        dh = x.shape[-1]
        p = nn_ops._pairing_permutation(dh)
        sin2, cos2 = doubled(x)
        return times(x, p) * cos2 + times(x, p @ signed_half(dh)) * sin2

    return {"slices": slices, "strided_slices": strided_slices,
            "op": op(True), "fused_matmul": fused_matmul,
            "two_matmuls": two_matmuls,
            "reshape_transpose": reshape_transpose,
            "rotate_half": rotate_half}


def in_place(form, front):
    """`form` where latent attention has the op: over the last lanes of x,
    the first `front` handed through (split, rotary_embed, concat)."""
    import jax.numpy as jnp

    if not front:
        return form
    return lambda x: jnp.concatenate(
        [x[..., :front], form(x[..., front:])], -1)


def forward_backward(fn):
    """x, g -> (fn(x), g's pull-back to x): both written."""
    import jax

    def both(x, g):
        out, back = jax.vjp(fn, x)
        return out, back(g)[0]
    return both


def sweep(shapes, dtypes, iters):
    """One dict a (shape, dtype, form)."""
    import jax
    import jax.numpy as jnp

    def timed(both, *operands):
        """ms a forward + backward: REPEAT of them in one executable, each
        fed the one before (a call alone takes less than its dispatch)."""
        fn = jax.jit(lambda *c: jax.lax.fori_loop(
            0, REPEAT, lambda _, c: both(*c), c))
        jax.block_until_ready(fn(*operands))
        best = []
        for _ in range(3):
            t0 = time.perf_counter()
            outs = [fn(*operands) for _ in range(iters)]
            jax.block_until_ready(outs)
            best.append((time.perf_counter() - t0) / (iters * REPEAT))
        return 1e3 * min(best)

    lines = []
    for shape_name, (shape, front) in shapes.items():
        for dtype in dtypes:
            kx, kg = jax.random.split(jax.random.PRNGKey(len(lines)))
            # true float32 values (all 24 bits), not cast-backs of bfloat16
            x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
            g = jax.random.normal(kg, shape, jnp.float32).astype(dtype)
            floor_bytes = 4 * x.size * x.dtype.itemsize
            floor_ms = 1e3 * floor_bytes / HBM_BYTES_PER_S
            want = None
            for name, form in forms().items():
                both = forward_backward(in_place(form, front))
                got = jax.jit(both)(x, g)
                ms = timed(both, x, g)
                line = dict(shape=shape_name, dims=list(shape),
                            dtype=jnp.dtype(dtype).name, form=name,
                            ms=round(ms, 4), bytes=floor_bytes,
                            floor_ms=round(floor_ms, 4),
                            over_floor=round(ms / floor_ms, 2))
                if name == "slices":
                    want = got
                elif name != "rotate_half":
                    line["equal"] = bool(jnp.array_equal(got[0], want[0]))
                    line["equal_grad"] = bool(
                        jnp.array_equal(got[1], want[1]))
                    line["max_diff"] = max(
                        float(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)).max())
                        for a, b in zip(got, want))
                lines.append(line)
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/rotary_sweep.json")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("rotary_sweep: needs a TPU, jax found %s" % dev)
    if args.rehearse:
        print("REHEARSAL on %s: no number below is a device number" % dev)
    lines = sweep(TINY if args.rehearse else SHAPES,
                  (jnp.float32, jnp.bfloat16), args.iters)
    for line in lines:
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(dev), "device_kind": dev.device_kind,
                   "rehearsal": args.rehearse, "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
