"""The sweep behind fused_attention's engagement constants
(ops/nn_ops._FLASH_MIN_T, _FLASH_BLOCKS): forward +
backward of one attention layer alone on the chip, the blockwise kernel
at each legal block pair against the dense lowering, bf16, at the
transformer cells' attention shapes.  Run on a TPU:

    python3 tools/attention_sweep.py [--out chiprun_out/attention_sweep.json]

Prints one JSON line a (shape, lowering) and the best block pair a shape.
A lowering is judged in its cell in the end (PERF.md, PR 24); this only
orders the candidates.  The lengths under the blockwise kernel's reach are
--short's.

With --short it sweeps the one-tile kernel of the short sequences (PR 62,
pallas_kernels.short_attention) at the two Transformer-base cells' shapes
and the lengths between and beside them (SHORT_SHAPES), causal and not,
with the key-padding bias: dense | the blockwise kernel at one block of T |
the one-tile kernel at each heads-a-step G and each pack (heads side by
side in a tile's lanes), forward alone and forward + backward.  G and the
pack are held in the tool (pallas_kernels._short_plan patched for that row:
the program has no such switch; it computes both from the shapes).  Each
lowering is timed from q, k, v stored [BH, T, d]: the one-tile kernel's
transposes to [BH, d, T] and back are in its time here, where a model's step
folds them into the copies the projections' [B, T, H d] need anyway.

    python3 tools/attention_sweep.py --short [--out chiprun_out/short_sweep.json]

With --short --in-place the operands are stored as the projections write
them, [B, T, H d] (IN_PLACE_SHAPES: the two Transformer-base cells', eight
heads of 64), and every lowering is timed from there and back to there:
dense and PR 62's one-tile kernel behind the transposes to [B H, T, d] and
back (the copies XLA needs to reach their layouts are in their time), and
the in-place form (PR 63, short_attention(..., heads=H)) by "S x G": S
sequences a grid step, G heads a product (1: a static 64-lane slice a head;
2: two heads' scores side by side, the queries laid out block-diagonally, no
lane moved), and at the program's own S x G with 2 to 16 sequences held
as one loop body ("unroll").  S, G and the unroll are held in the tool
(pallas_kernels._inplace_plan patched for that row).

    python3 tools/attention_sweep.py --short --in-place [--out chiprun_out/in_place_sweep.json]

With --window W it sweeps the sliding-window kernel instead (PR 41), at
trinity_mini_train's shape unless --bh / --t / --d say another: square
blocks of 1024 / 512 / 256, each on the band grid the kernel takes and on
the full grid it took before (pallas_kernels._band_grid held at 0 for
that row, here in the tool: the program has no such switch), forward
alone and forward + backward, with the grid steps a head walks and the
tiles it computes, and from the two grids the cost of one skipped step;
the full causal triangle (window 0) at 1024 beside them.

    python3 tools/attention_sweep.py --window 2048 [--out chiprun_out/window_sweep.json]

With --band-blocks W1,W2,.. it times the band of each of those windows
(--bh / --t / --d as above) on the grid the kernel takes, at square blocks
of 1024 / 512 / 256 / 128: a window of several blocks, of ONE block and of
HALF a block and less (PR 66: the rows behind nn_ops._flash_block's answer
under a window, which --window's two blocks and more do not reach).  A row
has ms forward and backward, the tiles by class, the pairs each pass
computes over the visible ones and the bodies a kernel holds; a window's
last line names its fastest block and the block the program takes.
Laguna-XS.2's window core:

    python3 tools/attention_sweep.py --band-blocks 128,256,512,1024,2048 --bh 64 --t 6144 [--out chiprun_out/band_block_sweep.json]

With --tile-classes it times the training path's kernels at the flash
cells' shapes (TILE_SHAPES) with a tile computed by where it lies (PR 53:
`on`, what the program does) against every tile masked whole (`off`:
pallas_kernels._tile_plan held at "no plan" for that row, here in the tool:
the program has no such switch), or `both` and their ratio: ms forward and
backward, the pairs computed over the visible ones, the copies of the tile's
computation a kernel body holds, and the warm trace + lower seconds of the
forward + backward (what a model's first step pays once a kernel).
--parts 1x4,4x4 times further strip counts (forward x backward), held in the
tool alike.

    python3 tools/attention_sweep.py --tile-classes both [--out chiprun_out/tile_sweep.json]

With --dead-fetch it times the same kernels at the same shapes with the
full grid's index maps naming, at a step the causal mask skips, the block
the head's next live step reads (PR 56: `next`, what the program does)
against the plain step (`step`: every skipped step copies its block in;
pallas_kernels._band_inner held at its old form for that row, here in the
tool: the program has no such switch) and against the clamp to the row's
live range (`clamp`: as few copies, one a row exposed), or `both` (all
three): ms forward and backward, the grid steps a head walks, the tiles it
computes and the blocks it copies in each pass, and against `step` the ms
each other map saves and the us that is a skipped head-step.  The window
shape walks its band's grid (PR 41), where the maps play no part, so it is
timed on that grid once and on the FULL grid under each map (_band_grid
held at 0, as --window does): against the band grid, whose skipped steps
are gone, that is the us a skipped head-step costs under `step` and what it
STILL costs under `next` (the price of an empty grid step).  --rehearse
walks the same code on the CPU and prints the counts, no time.

    python3 tools/attention_sweep.py --dead-fetch both [--out chiprun_out/dead_fetch_sweep.json]
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
# (what, B*H, T, head dim, causal or not) of --short, with a key bias
SHORT_SHAPES = [
    ("tfm_base_train", 1024, 256, 64, (True, False)),
    ("tfm_base_train_s64", 4096, 64, 64, (True, False)),
    ("T128", 2048, 128, 64, (True,)),
    ("T384", 672, 384, 64, (True,)),
    ("T256_d128", 512, 256, 128, (True,)),
    ("T64_d128", 2048, 64, 128, (True,)),
    ("T128_d128", 1024, 128, 128, (True,)),
    ("T384_d128", 336, 384, 128, (True,)),
]
SHORT_HEADS = (1, 2, 4, 8, 16, 32, 64)
# (what, B, T, heads, head dim, causal or not) of --short --in-place
IN_PLACE_SHAPES = [
    ("tfm_base_train_s64", 512, 64, 8, 64, (True, False)),
    ("tfm_base_train", 128, 256, 8, 64, (True, False)),
]
IN_PLACE_SEQS = (2, 4, 8, 16, 32)
# (cells, B*H, T, width of Q and K, width of V, window) of --tile-classes:
# the ten flash cells' attention cores, in blocks of
# nn_ops._flash_block(T, window)
TILE_SHAPES = [
    ("gpt2_345m_train", 64, 1024, 64, 64, 0),
    ("ouro_2b6_train", 16, 4096, 128, 128, 0),
    ("olmoe_1b7b_train", 32, 4096, 128, 128, 0),
    ("kanana2_30b_a3b_train+kimi_linear_48b_a3b_train", 32, 6144, 192, 128,
     0),
    ("lfm2_8b_a1b_train", 64, 8192, 64, 64, 0),
    ("trinity_mini_train.full", 32, 8192, 128, 128, 0),
    ("trinity_mini_train.window", 32, 8192, 128, 128, 2048),
    ("qwen3_next_80b_a3b_train", 16, 8192, 256, 256, 0),
    ("laguna_xs2_33b_a3b_train.full", 48, 6144, 128, 128, 0),
    ("laguna_xs2_33b_a3b_train.window", 64, 6144, 128, 128, 512),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/attention_sweep.json")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--window", type=int, default=0,
                    help="sweep the sliding-window kernel at this window")
    ap.add_argument("--band-blocks", default="",
                    help="time the band of each of these comma-separated "
                    "windows at every square block")
    ap.add_argument("--bh", type=int, default=32)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--tile-classes", choices=("on", "off", "both"),
                    default=None,
                    help="time the kernels with a tile computed by where it "
                    "lies (on), every tile masked whole (off), or both")
    ap.add_argument("--dead-fetch", choices=("next", "step", "clamp", "both"),
                    default=None,
                    help="time the kernels with a skipped step's index maps "
                    "naming the next live block (next), the step (step), "
                    "the row's clamp (clamp), or all three (both)")
    ap.add_argument("--parts", default="",
                    help="with --tile-classes: further strip counts to time,"
                    " forward x backward, as 1x4,4x4")
    ap.add_argument("--short", action="store_true",
                    help="sweep the one-tile kernel of the short sequences "
                    "over heads a grid step and sequences a tile")
    ap.add_argument("--in-place", action="store_true",
                    help="with --short: from operands stored [B, T, H d], "
                    "the in-place form against dense and PR 62's form "
                    "behind their transposes")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--shapes", default="",
                    help="with --tile-classes or --dead-fetch: only the "
                    "shapes whose name "
                    "holds one of these comma-separated words")
    ap.add_argument("--rehearse", action="store_true",
                    help="the window or tile sweep's plumbing on the CPU, "
                    "kernels interpreted at small sizes: the times mean "
                    "nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_kernels as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not (
            args.rehearse and (args.window or args.tile_classes
                               or args.dead_fetch or args.short
                               or args.band_blocks)):
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

    def fwd_fwdbwd(fn, operands):
        """[ms forward, ms forward + backward], the least of --repeats; a
        rehearsal makes one call and times nothing."""
        if args.rehearse:
            jax.block_until_ready(jax.grad(lambda *a: jnp.sum(
                fn(*a).astype(jnp.float32)))(*operands))
            return [None, None]
        return [round(min(timed(fn, operands, backward=b)
                          for _ in range(args.repeats)), 4)
                for b in (False, True)]

    def attempt(fn, operands, what):
        try:
            return fwd_fwdbwd(fn, operands)
        except Exception as e:  # e.g. tiles over the VMEM limit
            print("%s refused: %s" % (what, str(e)[:300]), flush=True)
            return None

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

    def band_block_sweep():
        """One JSON line a (window, block), and one a window: its fastest
        block forward + backward and the block the program takes."""
        from paddle_tpu.ops import nn_ops

        bh, t, d = args.bh, args.t, args.d
        windows = [int(w) for w in args.band_blocks.split(",")]
        if args.rehearse:  # one, two and four blocks a side, interpreted
            bh, t, windows = 1, 512, [128, 256]
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.float32).astype(
            jnp.bfloat16) for kk in keys)
        scale = d ** -0.5
        rows = []
        for w in windows:
            ms = {}
            for blk in (b for b in BLOCKS[::-1] if t % b == 0):
                jax.clear_caches()  # the kernels' entries are jitted

                def fn(q, k, v, kb):
                    return pk.flash_attention(q, k, v, kb, True, scale, blk,
                                              blk, w)

                stats = pk.tile_class_stats(t, d, blk, blk, w)
                steps, tiles = pk.band_grid_steps(t, blk, blk, w)
                fwd, both = fwd_fwdbwd(fn, (q, k, v, None))
                ms[blk] = both
                rows.append({
                    "bh": bh, "t": t, "d": d, "window": w, "block": blk,
                    "fwd_ms": fwd, "fwd_bwd_ms": both,
                    "bwd_ms": both and round(both - fwd, 4),
                    "fwd_steps": steps, "tiles": tiles,
                    "tile_classes": stats["tiles"],
                    "pairs_over_visible_fwd_bwd": [
                        round(stats[p] / stats["visible"], 4)
                        for p in ("fwd_pairs", "bwd_pairs")],
                    "bodies_fwd_bwd": [stats["fwd_bodies"],
                                       stats["bwd_bodies"]]})
                print(json.dumps(rows[-1]), flush=True)
            rows.append({"window": w, "t": t,
                         "best": None if args.rehearse
                         else min(ms, key=ms.get),
                         "the_program_takes": nn_ops._flash_block(t, w)})
            print(json.dumps(rows[-1]), flush=True)
        return rows

    def tile_sweep():
        """One JSON line a (shape, variant), and with `both` one a shape
        comparing them."""
        from paddle_tpu.ops import nn_ops

        plan, fwd_parts, bwd_parts = (pk._tile_plan, pk._fwd_strip_parts,
                                      pk._strip_parts)
        variants = [v for v in ("off", "on")
                    if args.tile_classes in (v, "both")]
        variants += [tuple(int(n) for n in p.split("x"))
                     for p in args.parts.split(",") if p]
        words = [w for w in args.shapes.split(",") if w]
        rows = []
        for name, bh, t, d, dv, w in TILE_SHAPES:
            if words and not any(word in name for word in words):
                continue
            if args.rehearse:  # two blocks of 256 a side, interpreted
                bh, t, w = 1, 512, w and 256
            blk = 256 if args.rehearse else nn_ops._flash_block(t, w)
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, (bh, t, n), jnp.float32).astype(
                jnp.bfloat16) for kk, n in zip(keys, (d, d, dv)))
            scale = d ** -0.5

            def fn(q, k, v, kb):
                return pk.flash_attention(q, k, v, kb, True, scale, blk, blk,
                                          w)

            grad = jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v, None).astype(jnp.float32)), argnums=(0, 1, 2))
            # every tile _band lets run computed whole, and the pairs seen
            whole = blk * blk * sum(pk._tile_counts(t, blk, blk, w).values())
            seen = int(np.sum(np.minimum(np.arange(t) + 1, w or t)))
            said = {}
            for variant in variants:
                pk._tile_plan = plan if variant != "off" else (
                    lambda *a, **kw: None)
                pk._fwd_strip_parts, pk._strip_parts = (
                    (fwd_parts, bwd_parts) if isinstance(variant, str) else
                    (lambda t, block, n=variant[0]: min(n, block // 128),
                     lambda block, n=variant[1]: min(n, block // 128)))
                setup = []
                for _ in range(2):  # the second: imports and caches warm
                    jax.clear_caches()  # the kernels' entries are jitted
                    t0 = time.perf_counter()
                    jax.jit(grad).trace(q, k, v).lower()
                    setup.append(time.perf_counter() - t0)
                jax.clear_caches()
                fwd = min(timed(fn, (q, k, v, None), backward=False)
                          for _ in range(args.repeats))
                both = min(timed(fn, (q, k, v, None))
                           for _ in range(args.repeats))
                o = fn(q, k, v, None)
                stats = (pk.tile_class_stats(t, d, blk, blk, w)
                         if variant != "off" else None)
                row = {
                    "shape": name, "bh": bh, "t": t, "d": d, "dv": dv,
                    "window": w, "block": blk,
                    "tile_classes": variant if isinstance(variant, str)
                    else "%dx%d" % variant,
                    "fwd_ms": round(fwd, 4), "bwd_ms": round(both - fwd, 4),
                    "fwd_bwd_ms": round(both, 4),
                    "pairs_over_visible_fwd_bwd": [
                        round((stats[p] if stats else whole) / seen, 4)
                        for p in ("fwd_pairs", "bwd_pairs")],
                    "bodies_fwd_bwd": [stats["fwd_bodies"],
                                       stats["bwd_bodies"]] if stats
                    else [1, 1],
                    "trace_lower_s": round(setup[1], 3)}
                said[row["tile_classes"]] = (row, o, grad(q, k, v))
                rows.append(row)
                print(json.dumps(row), flush=True)
            if "off" in said and len(said) > 1:
                (off, o0, g0) = said.pop("off")
                for label, (on, o1, g1) in said.items():
                    cmp = {"shape": name, "tile_classes": label + "/off",
                           "max_abs_diff_o_dq_dk_dv": [
                               float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                                     - b.astype(jnp.float32))))
                               for a, b in zip((o1,) + g1, (o0,) + g0)],
                           "trace_lower_s_more": round(
                               on["trace_lower_s"] - off["trace_lower_s"], 3)}
                    for p in ("fwd_ms", "bwd_ms", "fwd_bwd_ms"):
                        cmp[p + "_ratio"] = round(on[p] / off[p], 4)
                    rows.append(cmp)
                    print(json.dumps(cmp), flush=True)
        pk._tile_plan, pk._fwd_strip_parts, pk._strip_parts = (
            plan, fwd_parts, bwd_parts)
        return rows

    def dead_fetch_sweep():
        """One JSON line a (shape, map), and with `both` one a shape: the
        ms each map saves against `step` and the us of a skipped
        head-step."""
        from paddle_tpu.ops import nn_ops

        band_inner = pk._band_inner

        def held(kind):
            """_band_inner with the full grid's maps held at `kind`."""
            def inner(band, block_q, block_k, window, n_inner,
                      transposed=False, causal=False, traced=True):
                if band or not causal or n_inner == 1 or kind == "next":
                    return band_inner(band, block_q, block_k, window,
                                      n_inner, transposed, causal, traced)
                if kind == "step":
                    return lambda o, step: step
                mx, mn = ((jnp.maximum, jnp.minimum) if traced
                          else (np.maximum, np.minimum))

                def clamp(o, step):
                    first, last = pk._band_span(o, block_q, block_k, window,
                                                n_inner, transposed, traced)
                    return mn(mx(step, first), last)

                return clamp

            return inner

        kinds = [k for k in ("step", "clamp", "next")
                 if args.dead_fetch in (k, "both")]
        band_grid = pk._band_grid
        words = [w for w in args.shapes.split(",") if w]
        rows = []
        for name, bh, t, d, dv, w in TILE_SHAPES:
            if words and not any(word in name for word in words):
                continue
            blk = nn_ops._flash_block(t, w)
            if args.rehearse:  # four blocks of 128 a side (one of 512 where
                # one block holds the cell's sequence), interpreted
                bh, t, w, blk = 1, 512, w and 256, 512 if blk == t else 128
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, (bh, t, n), jnp.float32).astype(
                jnp.bfloat16) for kk, n in zip(keys, (d, d, dv)))
            scale = d ** -0.5

            def fn(q, k, v, kb):
                return pk.flash_attention(q, k, v, kb, True, scale, blk, blk,
                                          w)

            grad = jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v, None).astype(jnp.float32)), argnums=(0, 1, 2))
            n = t // blk
            # a window's band is off the grid (PR 41: the maps play no part
            # there), so it is also timed on the full grid under each map:
            # what a skipped step costs against one the grid does not have
            variants = [(kind, False) for kind in kinds]
            if w:
                variants = [("band", False)] + [(kind, True)
                                                for kind in kinds]
            said = {}
            for kind, full in variants:
                pk._band_inner = held("next" if kind == "band" else kind)
                pk._band_grid = (lambda *a, **kw: 0) if full else band_grid
                jax.clear_caches()  # the kernels' entries are jitted
                stats = pk.tile_class_stats(t, d, blk, blk, w)
                row = {"shape": name, "bh": bh, "t": t, "d": d, "dv": dv,
                       "window": w, "block": blk, "maps": kind,
                       "steps": n * (pk._band_grid(t, t, blk, blk, True, w)
                                     or n),
                       "tiles": sum(stats["tiles"].values()),
                       "fwd_fetches": stats["fwd_fetches"],
                       "bwd_fetches": stats["bwd_fetches"]}
                if not args.rehearse:
                    fwd = min(timed(fn, (q, k, v, None), backward=False)
                              for _ in range(args.repeats))
                    both = min(timed(fn, (q, k, v, None))
                               for _ in range(args.repeats))
                    row.update(fwd_ms=round(fwd, 4),
                               bwd_ms=round(both - fwd, 4),
                               fwd_bwd_ms=round(both, 4))
                said[kind] = (row, fn(q, k, v, None), grad(q, k, v))
                rows.append(row)
                print(json.dumps(row), flush=True)
            base = "band" if w else "step"
            if base in said and len(said) > 1:
                off, o0, g0 = said.pop(base)
                for kind, (on, o1, g1) in said.items():
                    # the steps the band grid does not have, or on one grid
                    # the steps the mask skips
                    dead = abs(on["steps"] - off["steps"]) or (
                        off["steps"] - off["tiles"])
                    cmp = {"shape": name, "maps": kind + "/" + base,
                           "dead_steps_a_head": dead,
                           "bit_equal_o_dq_dk_dv": [
                               bool(jnp.all(a == b))
                               for a, b in zip((o1,) + g1, (o0,) + g0)]}
                    for p in ("fwd", "bwd") if "fwd_ms" in on and dead else ():
                        # against `step`: what the map took off a skipped
                        # step; against the band grid: what one still costs
                        less = off[p + "_ms"] - on[p + "_ms"]
                        cmp[p + "_ms_less"] = round(less, 4)
                        cmp[p + "_dead_step_us_a_head"] = round(
                            abs(less) / dead / bh * 1e3, 4)
                    rows.append(cmp)
                    print(json.dumps(cmp), flush=True)
        pk._band_grid = band_grid
        pk._band_inner = band_inner
        jax.clear_caches()
        return rows

    def short_sweep():
        """One JSON line a (shape, causal): ms forward and forward +
        backward of dense, of the blockwise kernel at one block of T, and of
        the one-tile kernel by "G x pack"; the best of those."""
        plan = pk._short_plan
        words = [w for w in args.shapes.split(",") if w]
        rows = []
        for name, bh, t, d, causals in SHORT_SHAPES:
            if words and not any(word in name for word in words):
                continue
            if args.rehearse:
                bh = 8
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.float32).astype(
                jnp.bfloat16) for kk in keys)
            kb = (jnp.where(jnp.arange(t)[None, :] < t - 7, 0.0, -1e9).astype(
                jnp.float32) * jnp.ones((bh, 1), jnp.float32))
            scale = d ** -0.5
            for causal in causals:
                row = {"shape": name, "bh": bh, "t": t, "d": d,
                       "causal": causal, "kbias": True,
                       "dense_fwd_fwdbwd_ms": fwd_fwdbwd(
                           lambda q, k, v, kb: pk._dense_attention(
                               q, k, v, causal, scale, kb), (q, k, v, kb)),
                       "blockwise_fwd_fwdbwd_ms": attempt(
                           lambda q, k, v, kb: pk.flash_attention(
                               q, k, v, kb, causal, scale, t, t),
                           (q, k, v, kb), name + " blockwise"),
                       "one_tile_fwd_fwdbwd_ms": {}}
                for p in (1, 2, 4):
                    if p > 1 and p * t > 256:
                        continue
                    for g in SHORT_HEADS:
                        # the f32 tiles a step keeps live, as _short_plan
                        # counts them, inside the 32 MiB the kernels ask for
                        if ((bh // p) % g or (args.rehearse and g > 2)
                                or g * 18 * (p * t) * max(p * t, 128)
                                > 26 * 2 ** 20):
                            continue
                        pk._short_plan = (
                            lambda *a, g=g, p=p: pk._ShortPlan(p, g))
                        ms = attempt(lambda q, k, v, kb: pk.short_attention(
                            q, k, v, kb, causal, scale), (q, k, v, kb),
                            "%s %dx%d" % (name, g, p))
                        row["one_tile_fwd_fwdbwd_ms"]["%dx%d" % (g, p)] = ms
                pk._short_plan = plan
                ok = {c: ms[1] for c, ms in
                      row["one_tile_fwd_fwdbwd_ms"].items()
                      if ms and ms[1] is not None}
                row["best"] = min(ok, key=ok.get) if ok else None
                took = plan(bh, t, d, d, 2)
                row["the_program_takes"] = "%dx%d" % (took.heads, took.pack)
                rows.append(row)
                print(json.dumps(row), flush=True)
                # a row's executables go before the next row's come (the
                # host of a one-chip machine ran out of memory over them)
                jax.clear_caches()
        return rows

    def in_place_sweep():
        """One JSON line a (shape, causal): ms forward and forward +
        backward, from q, k, v stored [B, T, H d] to o stored alike, of
        dense and of PR 62's one-tile kernel behind their transposes, and of
        the in-place form by "S x G"; the best of those."""
        plan = pk._inplace_plan
        words = [w for w in args.shapes.split(",") if w]
        rows = []
        for name, b, t, h, d, causals in IN_PLACE_SHAPES:
            if words and not any(word in name for word in words):
                continue
            if args.rehearse:
                b = 4
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(
                kk, (b, t, h * d), jnp.float32).astype(jnp.bfloat16)
                for kk in keys)
            kb = (jnp.where(jnp.arange(t)[None, :] < t - 7, 0.0, -1e9).astype(
                jnp.float32) * jnp.ones((b, 1), jnp.float32))
            scale = d ** -0.5

            def heads_first(fn):
                """fn over [B H, T, d] and a [B H, T] bias, from and to
                [B, T, H d]: what a "bthd" op does where the in-place form
                does not engage."""
                def run(q, k, v, kb):
                    flat = [x.reshape(b, t, h, d).transpose(
                        0, 2, 1, 3).reshape(b * h, t, d) for x in (q, k, v)]
                    rows_ = jnp.broadcast_to(
                        kb[:, None, :], (b, h, t)).reshape(b * h, t)
                    return fn(*flat, rows_).reshape(b, h, t, d).transpose(
                        0, 2, 1, 3).reshape(b, t, h * d)
                return run

            for causal in causals:
                took = plan(b, t, h, d, d, 2)
                row = {"shape": name, "b": b, "t": t, "h": h, "d": d,
                       "causal": causal, "kbias": True, "stored": "bthd",
                       "dense_fwd_fwdbwd_ms": fwd_fwdbwd(heads_first(
                           lambda q, k, v, kb: pk._dense_attention(
                               q, k, v, causal, scale, kb)), (q, k, v, kb)),
                       "one_tile_with_copies_fwd_fwdbwd_ms": attempt(
                           heads_first(lambda q, k, v, kb: pk.short_attention(
                               q, k, v, kb, causal, scale)), (q, k, v, kb),
                           name + " one_tile"),
                       "in_place_fwd_fwdbwd_ms": {}}
                for g, s, u in [(g, s, 1) for g in (1, 2)
                                for s in IN_PLACE_SEQS] + [
                                    (took.group, took.seqs, u)
                                    for u in (2, 4, 8, 16)]:
                    if b % s or s % u or (args.rehearse and s > 4):
                        continue
                    pk._inplace_plan = (
                        lambda *a, s=s, g=g, u=u: pk._InPlacePlan(s, g, u))
                    what = "%dx%d" % (s, g) + (" unroll %d" % u) * (u > 1)
                    row["in_place_fwd_fwdbwd_ms"][what] = attempt(
                        lambda q, k, v, kb: pk.short_attention(
                            q, k, v, kb, causal, scale, h), (q, k, v, kb),
                        "%s %s" % (name, what))
                pk._inplace_plan = plan
                ok = {c: ms[1] for c, ms in
                      row["in_place_fwd_fwdbwd_ms"].items()
                      if ms and ms[1] is not None}
                row["best"] = min(ok, key=ok.get) if ok else None
                row["the_program_takes"] = "%dx%d unroll %d" % took
                rows.append(row)
                print(json.dumps(row), flush=True)
                jax.clear_caches()
        return rows

    def save(rows, name):
        """Writes the sweep's rows to --out, or to its own file
        chiprun_out/<name>.json where --out was not given (a rehearsal's
        to <name>.rehearsal.json: it must not replace a chip's)."""
        out = (args.out if args.out != ap.get_default("out")
               else "chiprun_out/%s.json" % (
                   name + ".rehearsal" * args.rehearse))
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump({"device": dev.device_kind, "iters": args.iters,
                       "rows": rows}, f, indent=1)

    if args.short and args.in_place:
        return save(in_place_sweep(), "in_place_sweep")
    if args.short:
        return save(short_sweep(), "short_sweep")
    if args.dead_fetch:
        return save(dead_fetch_sweep(), "dead_fetch_sweep")
    if args.tile_classes:
        return save(tile_sweep(), "tile_sweep")
    if args.band_blocks:
        return save(band_block_sweep(), "band_block_sweep")
    if args.window:
        return save(window_sweep(), "window_sweep")

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
    save(rows, "attention_sweep")


if __name__ == "__main__":
    main()
