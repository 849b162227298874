"""The sweep behind the form of `moe_ffn`'s row work where the op holds a
share of its experts (ops/moe_ops.py: the pieces between the sort and the
result run over the live chunks): each piece alone on the chip, bf16, at
the shapes of the two share cells, random top-k routing (a quarter / an
eighth of the N k rows live).  Run on a TPU:

    python3 tools/moe_row_sweep.py [--out chiprun_out/moe_row_sweep.json]

(`--rehearse`: the same plumbing at tiny sizes on the CPU; no number of it
is a device number.)

  combine   [N k, d] rows in expert order -> [N, d], weighted:
            whole_rows      the whole-size lowering: mask, weight and cast
                            over N k rows, gather N k rows, sum
            gather_nkd      gather N k rows, then weight, mask and sum over
                            [N, k, d]
            gather_by_slot  what the share path does: k gathers of N rows,
                            the weight and the mask in the sum's fusion
            scatter_f32     scatter-add of the live chunks into an f32
                            [N, d], then the cast
            scatter_bf16    the same into a bf16 [N, d]
  dispatch  [N, d] -> [N k, d]: whole_rows (gather N k rows, mask) against
            the live chunks at C rows
  swiglu    silu(gate) * up over [N k, 2 f]: whole_rows against the live
            chunks at C rows
  sort      the two argsorts every form shares
  op        moe_ffn forward + backward through the chip's kernels at C
            rows a chunk (the cell decides in the end: PERF.md)

Prints one JSON line a (shape, piece, form).

    python3 tools/moe_row_sweep.py --cell <cell> --chunk C -- <arguments
        of benchmark/run.py>

runs a benchmark cell in this process with C rows a chunk in place of
`moe_ops._CHUNK_ROWS` (the sweep that chose the constant)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name -> (tokens, top_k, experts, held, d, f): the share cells' layers
SHAPES = {"lfm2_8b_a1b": (16384, 4, 32, 8, 2048, 1792),
          "kanana2_30b_a3b": (6144, 6, 128, 16, 2048, 768)}
TINY = {"tiny": (512, 2, 8, 2, 128, 128)}
CHUNKS = (512, 1024, 2048, 4096)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/moe_row_sweep.json")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--cell", default=None)
    ap.add_argument("--chunk", type=int, default=None)
    args, rest = ap.parse_known_args()
    if args.cell:
        return run_cell(args.cell, args.chunk, [a for a in rest if a != "--"])

    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("moe_row_sweep: needs a TPU, jax found %s" % dev)
    if args.rehearse:
        print("REHEARSAL on %s: no number below is a device number" % dev)
    lines = []

    def timed(fn, *operands):
        """ms a call: `iters` calls dispatched back to back, blocked on
        once (a piece takes about as long as one dispatch)."""
        jax.block_until_ready(fn(*operands))
        best = []
        for _ in range(3):
            t0 = time.perf_counter()
            outs = [fn(*operands) for _ in range(args.iters)]
            jax.block_until_ready(outs)
            best.append((time.perf_counter() - t0) / args.iters)
        return 1e3 * min(best)

    def report(shape, piece, form, ms, **more):
        line = dict(shape=shape, piece=piece, form=form, ms=round(ms, 4),
                    **more)
        lines.append(line)
        print(json.dumps(line), flush=True)

    for shape, (n, k, e, held, d, f) in (TINY if args.rehearse
                                         else SHAPES).items():
        m = n * k
        chunks = [c for c in CHUNKS if m % c == 0 and c <= m] or [m]
        keys = jax.random.split(jax.random.PRNGKey(0), 8)
        top_e = jnp.argsort(jax.random.uniform(keys[0], (n, e)))[:, :k]
        top_p = jax.random.uniform(keys[1], (n, k), jnp.float32)
        local = top_e.reshape(-1)
        sort_key = jnp.where(local < held, local, held)
        order = jnp.argsort(sort_key, stable=True)
        inv, tok = jnp.argsort(order), order // k
        n_live = (local < held).sum().astype(jnp.int32)
        live = (jnp.arange(m) < n_live)[:, None]
        row_p = top_p.reshape(m, 1)[order]
        x = jax.random.normal(keys[2], (n, d), jnp.float32).astype(
            jnp.bfloat16)
        out = jax.random.normal(keys[3], (m, d), jnp.float32).astype(
            jnp.bfloat16)
        gu = jax.random.normal(keys[4], (m, 2 * f), jnp.float32).astype(
            jnp.bfloat16)
        said = dict(rows=m, live=int(n_live))

        report(shape, "sort", "two_argsorts", timed(jax.jit(
            lambda key: jnp.argsort(jnp.argsort(key, stable=True))),
            sort_key), **said)

        # --- combine -------------------------------------------------
        def whole_combine(out, row_p, inv, live):
            w = jnp.where(live, out, 0)
            w = (w.astype(jnp.float32) * row_p).astype(out.dtype)
            return moe_ops._sum_slots(w, inv, k)

        def scatter(acc_dtype, chunk):
            def fn(out, row_p, tok, n_live):
                def body(start, live, y):
                    rows = (moe_ops._rows(out, start, chunk).astype(
                        jnp.float32) * moe_ops._rows(row_p, start, chunk))
                    return y.at[moe_ops._rows(tok, start, chunk)].add(
                        jnp.where(live, rows, 0).astype(acc_dtype))
                return moe_ops._live_chunks(
                    n_live, chunk, jnp.zeros((n, d), acc_dtype),
                    body).astype(out.dtype)
            return jax.jit(fn)

        want = jax.jit(whole_combine)(out, row_p, inv, live)
        report(shape, "combine", "whole_rows",
               timed(jax.jit(whole_combine), out, row_p, inv, live), **said)
        def gather_nkd(out, top_p, inv, n_live):
            got = (out[inv].reshape(n, k, -1).astype(jnp.float32)
                   * top_p[..., None])
            return jnp.where((inv < n_live).reshape(n, k, 1), got, 0).sum(
                1).astype(out.dtype)

        for name, fn in (("gather_nkd", jax.jit(gather_nkd)),
                         ("gather_by_slot", lambda *a:
                          moe_ops._weigh_to_tokens(*a, k=k))):
            got = fn(out, top_p, inv, n_live)
            report(shape, "combine", name,
                   timed(fn, out, top_p, inv, n_live),
                   max_diff=float(jnp.abs(
                       got.astype(jnp.float32)
                       - want.astype(jnp.float32)).max()), **said)
        for chunk in chunks:
            for name, acc in (("scatter_f32", jnp.float32),
                              ("scatter_bf16", jnp.bfloat16)):
                fn = scatter(acc, chunk)
                got = fn(out, row_p, tok, n_live)
                report(shape, "combine", name,
                       timed(fn, out, row_p, tok, n_live), chunk=chunk,
                       max_diff=float(jnp.abs(
                           got.astype(jnp.float32)
                           - want.astype(jnp.float32)).max()), **said)

        # --- dispatch ------------------------------------------------
        report(shape, "dispatch", "whole_rows", timed(jax.jit(
            lambda x, tok, live: jnp.where(live, x[tok], 0)), x, tok, live),
            **said)
        for chunk in chunks:
            report(shape, "dispatch", "live_chunks", timed(
                lambda *a: moe_ops._gather_live(*a, chunk=chunk),
                x, tok, n_live), chunk=chunk, **said)

        # --- swiglu --------------------------------------------------
        report(shape, "swiglu", "whole_rows",
               timed(jax.jit(moe_ops._swiglu), gu), **said)
        for chunk in chunks:
            report(shape, "swiglu", "live_chunks", timed(
                lambda *a: moe_ops._swiglu_live(*a, chunk=chunk),
                gu, n_live), chunk=chunk, **said)

        # --- the op, forward + backward ------------------------------
        ctx = LowerCtx(platform=dev.platform)
        wr = jax.random.normal(keys[5], (d, e), jnp.float32) * 0.02
        wgu = (jax.random.normal(keys[6], (held, d, 2 * f), jnp.float32)
               * 0.02).astype(jnp.bfloat16)
        wd = (jax.random.normal(keys[7], (held, f, d), jnp.float32)
              * 0.02).astype(jnp.bfloat16)
        xf = x.astype(jnp.float32)

        def loss(x, wr, wgu, wd):
            y = moe_ops._moe_ffn(
                ctx, {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                      "DownW": [wd]},
                {"top_k": k, "router": "sigmoid", "norm_topk_prob": True,
                 "expert_offset": 0})["Y"][0]
            return y.astype(jnp.float32).sum()

        rule = moe_ops._chunk_rows
        for chunk in chunks + [m]:
            moe_ops._chunk_rows = lambda rows, chunk=chunk: chunk
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            report(shape, "op", "fwd_bwd", timed(step, xf, wr, wgu, wd),
                   chunk=chunk, **said)
        moe_ops._chunk_rows = rule

    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(lines, fh, indent=1)


def run_cell(cell, chunk, rest):
    import runpy

    from paddle_tpu.ops import moe_ops

    if chunk:
        moe_ops._CHUNK_ROWS = chunk
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.argv = [os.path.join(root, "benchmark", "run.py"),
                "--workload", cell] + rest
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
