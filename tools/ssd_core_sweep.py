"""Time the `mamba2_scan` op alone (ops/mamba2_ops.py: the Mamba-2 selective
scan, chunkwise at 128 tokens) at the nemotron3_nano_30b_a3b_train cell's
shape, 1 x 64 heads of 64 over 8 groups at state 128, bfloat16 x, B, C and
float32 dt, A, D: the forward, and forward + backward, by the host's clock
around `block_until_ready` and, from a profiler trace of the same calls,
the device time of each of the op's three kernels (the chunk scan, the walk
that keeps the entering states, the reverse walk) and of what XLA runs
around them (the pads, dt A, D's sum).  Then the op against the token-by-
token recurrence in float32 at a length the scan finishes in seconds.  Run
on a TPU:

    python tools/ssd_core_sweep.py [--seq-len 4096,6144,8192]

(`--rehearse`: tiny sizes on the CPU, proves the plumbing.)  Prints one
JSON line a length; PERF.md (PR 57) keeps what it read.
"""

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.kda_core_sweep import _device_ops, _timed  # noqa: E402


def _inputs(jnp, np, b, h, g, t, p, n, seed):
    """As the model's initialisation leaves them: A uniform(1, 16) a head,
    dt log-uniform in (0.001, 0.1), x and B, C of the convolution's SiLU
    scale."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, h, t, p)).astype("float32")
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (b, h, t)))
    a = -rng.uniform(1.0, 16.0, (h,))
    bm, cm = (0.5 * rng.standard_normal((b, g, t, n)).astype("float32")
              for _ in range(2))
    return (jnp.asarray(x, jnp.bfloat16), jnp.asarray(dt, jnp.float32),
            jnp.asarray(a, jnp.float32), jnp.asarray(bm, jnp.bfloat16),
            jnp.asarray(cm, jnp.bfloat16), jnp.ones((h,), jnp.float32))


def _recurrence(jax, jnp, x, dt, a, bm, cm, d):
    rep = x.shape[1] // bm.shape[1]
    f32 = jnp.float32
    bm, cm = (jnp.repeat(v.astype(f32), rep, 1) for v in (bm, cm))

    def step(s, v):
        xt, dtt, bt, ct = v
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct) + d[:, None] * xt

    xs = [jnp.moveaxis(v, 2, 0) for v in (x.astype(f32), dt, bm, cm)]
    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:2] + (x.shape[-1], bm.shape[-1]), f32), xs)
    return jnp.moveaxis(y, 0, 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", default="4096,6144,8192")
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import mamba2_ops

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("ssd_core_sweep: needs a TPU (or --rehearse), jax "
                         "found %s" % jax.devices())
    b, h, g, p, n = (1, 4, 2, 16, 16) if args.rehearse else (1, 64, 8, 64,
                                                             128)
    lengths = [200] if args.rehearse else [int(t) for t in
                                           args.seq_len.split(",")]
    for t in lengths:
        ins = _inputs(jnp, np, b, h, g, t, p, n, args.seed)
        mix = jnp.asarray(np.random.default_rng(1).standard_normal(
            (b, h, t, p)), jnp.bfloat16)
        fwd = jax.jit(mamba2_ops.mamba2_scan)
        both = jax.jit(jax.value_and_grad(
            lambda *a: (mamba2_ops.mamba2_scan(*a).astype(jnp.float32)
                        * mix.astype(jnp.float32)).sum(), argnums=range(6)))
        cost = 3.0 * b * t * (g * 2.0 * 128 * n + h * (2.0 * 128 * p
                                                       + 4.0 * n * p))
        moved = 3.0 * b * t * (2.0 * (2 * h * p + 2 * g * n) + 4.0 * h)
        out = {"shape": [b, h, g, t, p, n], "chunk": mamba2_ops.CHUNK,
               "device": jax.devices()[0].device_kind,
               "forward_ms": _timed(fwd, ins, args.reps),
               "forward_backward_ms": _timed(both, ins, args.reps),
               "closed_form": {"flops_step": cost, "bytes_step": moved}}
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for _ in range(args.reps):
                jax.block_until_ready(both(*ins))
            jax.profiler.stop_trace()
            ops = _device_ops(d)
        out["device_ms_by_op"] = {
            k: round(v / args.reps, 4) for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:12]}
        out["device_ms"] = round(sum(ops.values()) / args.reps, 4)
        # against the recurrence, in float32 on the same (bfloat16-rounded)
        # inputs, at a length the scan finishes in seconds
        short = [v[:, :, :min(t, 1024)] if v.ndim > 1 else v for v in ins]
        want = jax.jit(lambda *a: _recurrence(jax, jnp, *a))(*short)
        got = fwd(*short)
        exact = fwd(*[v.astype(jnp.float32) for v in short])
        top = float(jnp.abs(want).max())
        out["max_abs_error_over_max"] = {
            "bf16_operands": float(jnp.abs(
                got.astype(jnp.float32) - want).max()) / top,
            "f32_operands": float(jnp.abs(exact - want).max()) / top}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
