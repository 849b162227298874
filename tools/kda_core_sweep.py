"""What is `kda_attention`'s time on a chip made of?  Times the op's
lowering (`ops/kda_ops.kda_chunked`) alone, forward and forward + backward,
at the kimi_linear_48b_a3b_train cell's shape (1 x 32 heads x 6,144 tokens
x 128, q / k / v bfloat16, the log-decay and beta float32, drawn as the
model's initialisation leaves them), traces one forward + backward call and
prints its device time by the op's named scopes (`intra`, `carry`; forward
or the transposed backward) and its longest device ops, and holds the
result against the token-by-token recurrence in float32 at a shorter
length.  Run on a TPU:

    python3 tools/kda_core_sweep.py [--seq-len 6144] [--block 8]
        [--heads-per-step 8]

(`--rehearse`: tiny sizes on the CPU, proves the plumbing.)  `--block`
runs the inside's kernels at another number of chunks a grid step
(`kda_ops.BLOCK`), `--heads-per-step` the carry's at another number of
heads a grid step (`kda_ops.CARRY_HEADS`; several, separated by commas,
are timed and traced in turn, one JSON line each).  `--decay head` times
the family's other member,
`gated_delta_attention` (`kda_ops.gdn_chunked`), at the
qwen3_next_80b_a3b_train cell's shape: 16 key heads under 32 value heads,
ONE log-decay a head a token (`--seq-len 8192` is the cell's).  Prints
one JSON line; PERF.md (PRs 45, 46, 48, 50) keeps what
it read.
"""

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _inputs(jnp, np, b, h, t, d, seed):
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, h, t, d)).astype("float32")
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = (0.05 * rng.standard_normal((b, h, t, d))).astype("float32")
    a = rng.uniform(1.0, 16.0, (1, h, 1, 1))
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (b, h, t, d)))
    g = (-a * dt).astype("float32")
    beta = rng.uniform(0.3, 0.7, (b, h, t)).astype("float32")
    half = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
    return half + [jnp.asarray(g), jnp.asarray(beta)]


def _recurrence(jax, jnp, q, k, v, g, beta, scale):
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt * scale, s)

    xs = [jnp.moveaxis(a.astype(jnp.float32), 2, 0)
          for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(
        step, jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 2)


def _timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def _device_ops(trace_dir):
    """{event name: summed ms} of the first device's XLA Ops line."""
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    total = collections.Counter()
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                total[ev.name] += ev.duration_ns * 1e-6
        if total:
            break
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=6144)
    ap.add_argument("--block", type=int, default=None)
    ap.add_argument("--heads-per-step", default=None,
                    help="kda_ops.CARRY_HEADS, or several: 4,8,16,32")
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--decay", choices=("channel", "head"),
                    default="channel")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import kda_ops

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("kda_core_sweep: needs a TPU, jax found %s"
                         % jax.devices())
    if args.block:
        kda_ops.BLOCK = args.block
    b, h, t, d = (1, 2, 200, 16) if args.rehearse else (1, 32, args.seq_len,
                                                        128)
    scale = d ** -0.5
    ins = _inputs(jnp, np, b, h, t, d, args.seed)
    chunked = kda_ops.kda_chunked
    if args.decay == "head":  # half the key heads, one decay a head
        chunked = kda_ops.gdn_chunked
        ins = [ins[0][:, :h // 2], ins[1][:, :h // 2], ins[2],
               ins[3][..., 0], ins[4]]
    mix = jnp.asarray(np.random.default_rng(1).standard_normal(
        (b, h, t, d)), jnp.bfloat16)

    for heads in (args.heads_per_step or "").split(","):
        if heads:
            kda_ops.CARRY_HEADS = int(heads)
        fwd = jax.jit(lambda *a: chunked(*a, scale))
        both = jax.jit(jax.value_and_grad(
            lambda *a: (chunked(*a, scale).astype(jnp.float32)
                        * mix.astype(jnp.float32)).sum(), argnums=range(5)))
        out = {"shape": [b, h, t, d], "decay": args.decay,
               "chunk": kda_ops.CHUNK,
               "block": kda_ops._block(t),
               "heads_per_step": kda_ops._carry_heads(b * h),
               "device": jax.devices()[0].device_kind,
               "forward_ms": _timed(fwd, ins, args.reps),
               "forward_backward_ms": _timed(both, ins, args.reps)}

        # against the recurrence, in float32 on the same (bfloat16-rounded)
        # inputs, at a length the scan finishes in seconds
        short = [x[:, :, :min(t, 1024)] for x in ins]
        want = jax.jit(lambda *a: _recurrence(jax, jnp, *a, scale))(*(
            short if args.decay == "channel" else
            [jnp.repeat(short[0], 2, 1), jnp.repeat(short[1], 2, 1), short[2],
             jnp.broadcast_to(short[3][..., None], short[2].shape), short[4]]))
        got = jax.jit(lambda *a: chunked(*a, scale))(*short)
        exact = jax.jit(lambda *a: chunked(*a, scale))(
            *[x.astype(jnp.float32) for x in short])
        scale_of = float(jnp.abs(want).max())
        out["max_abs_error_over_max"] = {
            "bf16_operands": float(jnp.abs(got.astype(jnp.float32)
                                           - want).max()) / scale_of,
            "f32_operands": float(jnp.abs(exact - want).max()) / scale_of}

        # one traced call: device time by named scope and by device op
        placed = {}
        for m in re.finditer(r"(%[\w.\-]+) = [^\n]*op_name=\"([^\"]*)\"",
                             both.lower(*ins).compile().as_text()):
            placed[m.group(1).lstrip("%")] = m.group(2)
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            jax.block_until_ready(both(*ins))
            jax.profiler.stop_trace()
            ops = _device_ops(tmp)
        scopes, by_scope = collections.Counter(), collections.defaultdict(list)
        for name, ms in ops.items():
            short = name.lstrip("%").split(" ")[0]
            where = placed.get(short, "")
            if re.match(r"while[.\d]*$", short):
                continue  # a loop's own event spans its body's: counted there
            part = ("intra" if re.search(r"[/(]intra[/)]", where)
                    else "carry" if re.search(r"[/(]carry[/)]", where)
                    else "other")
            part = ("backward " if "transpose(" in where else "") + part
            scopes[part] += ms
            by_scope[part].append([round(ms, 3), short, where[-60:]])
        out["traced_ms_by_scope"] = dict(scopes)
        out["traced_ms"] = sum(scopes.values())
        out["longest_device_ops_ms"] = {
            part: sorted(found, reverse=True)[:14]
            for part, found in by_scope.items()}
        print(json.dumps(out), flush=True)


    return 0


if __name__ == "__main__":
    sys.exit(main())
