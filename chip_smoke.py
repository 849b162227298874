#!/usr/bin/env python3
"""Smallest end-to-end proof that the train path starts on a TPU.

One process, no children (a chip belongs to one process).  In order:

  train      Transformer-base at full width (bf16 AMP, fused attention)
             through Executor(TPUPlace(0)).run: startup, then 2 warm-up + 5
             steps on one fixed batch.  Checks finite and falling loss, flat
             compile count, loss on a tpu device, the tiled vocabulary head
             engaged, every fuse pass fired.
  numerics   the same program in float32 (no AMP), same seed, 2 steps: the
             bfloat16 step's losses must agree with it within LOSS_TOL.
  kernels    one compiled call (forward and backward) of every kernel in
             pallas_kernels.__all__ against its dense twin, at a shape one of
             the repo's models uses; flash_attention through
             fused_attention's default lowering.
  spmd       (--devices 4 only) the same widths over a dp=2 x mp=2 mesh
             through Executor._run_spmd.

Every phase runs even when an earlier one failed; the verdict fails if any
did.  The last stdout line of a passing run is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}.  Without a TPU the
script exits non-zero before any phase.  --rehearse runs tiny widths on the
CPU with the kernels interpreted, to debug the command before chip time is
spent; it says REHEARSAL and never prints the pass line.

This is a does-it-start check, pass or fail, and not a benchmark: it times
no step (it says how long each phase took because compilation dominates it).
"""

import argparse
import gc
import json
import os
import sys
import time
import traceback

SEED = 20260926
# the bf16 step against its float32 reference: bf16 rounding (3e-4 at the
# rehearsal's widths with dropout off) plus the two programs' dropout masks,
# which differ (3e-2 over the rehearsal's 64 tokens; less over more tokens)
LOSS_TOL = 5e-2
SPMD_LOSS_TOL = 1e-2
SPMD_STATE_RATIO = 0.55  # per-device state bytes vs unsharded at mp=2


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4 adds the dp=2 x mp=2 GSPMD leg (needs 4 chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU, kernels interpreted")
    return ap.parse_args()


class Check(Exception):
    """A named smoke check that did not hold."""


def require(ok, name, detail=""):
    if not ok:
        raise Check("%s%s" % (name, (": %s" % detail) if detail else ""))


def log(msg):
    print(msg, flush=True)


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------
def make_hp(rehearse, devices):
    from paddle_tpu.models import transformer as tfm

    class HP(tfm.ModelHyperParams):  # d_model 512, inner 2048, 8 heads,
        fused_attn = True            # 6 layers, vocab 10000 as published

    if rehearse:
        HP.d_model, HP.d_inner_hid, HP.n_head, HP.n_layer = 64, 128, 2, 1
        HP.src_vocab_size = HP.trg_vocab_size = 300
        HP.max_length = 16
    elif devices == 4:
        # depth cut, widths kept: the four-chip leg compiles the program
        # twice (one chip, then the mesh) on a machine charged four-fold
        HP.n_layer = 2
    return HP


def train(hp, batch_np, seq, place, steps, mesh=None, use_bf16=True):
    """Build, start and step the program; returns a dict of what the
    checks read.  lr: noam with a short warm-up so seven steps on one
    batch move the loss well clear of dropout noise (the builder's
    default 4000-step warm-up starts at 3.5e-7)."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.ops import kernel_tuning

    kernel_tuning.reset_attribution()
    main, startup, _feeds, fetches = tfm.wmt_transformer_program(
        hp, src_len=seq, trg_len=seq, learning_rate=1.0, warmup_steps=100,
        use_bf16=use_bf16, mesh=mesh)
    startup.random_seed = main.random_seed = SEED
    scope = fluid.Scope()
    out = {"main": main, "scope": scope, "losses": []}
    with fluid.scope_guard(scope):
        exe = out["exe"] = fluid.Executor(place)
        exe.run(startup)
        for i in range(steps):
            fetched = exe.run(main, feed=batch_np, fetch_list=fetches,
                              return_numpy=False)
            jax.block_until_ready(fetched)
            if i == 0:
                out["compiles_after_first"] = exe.compile_count
            out["losses"].append(float(np.asarray(fetched[0]).reshape(-1)[0]))
            out["loss_devices"] = sorted(
                {d.platform for d in fetched[0].devices()})
        out["compiles_end"] = exe.compile_count
        out["attribution"] = kernel_tuning.attribution()
    return out


def phase_train(ctx):
    import numpy as np

    r = ctx["train"] = train(ctx["hp"], ctx["batch"], ctx["seq"],
                             ctx["place"], steps=7)
    main, losses = r["main"], r["losses"]
    log("  losses (2 warm-up + 5): %s" % " ".join("%.4f" % v for v in losses))
    log("  compile_count after first step %d, at end %d"
        % (r["compiles_after_first"], r["compiles_end"]))
    hits = r["attribution"]["pallas_hits"]
    log("  pallas_hits %s" % json.dumps(hits, sort_keys=True))
    fused = {k: getattr(main, "_%s_fused_count" % k, 0)
             for k in ("fc", "residual_ln", "linear_xent", "smooth_xent")}
    log("  fused counts %s" % json.dumps(fused, sort_keys=True))

    require(all(np.isfinite(losses)), "finite-loss", str(losses))
    require(losses[-1] < losses[0], "loss-falls",
            "first %.4f last %.4f" % (losses[0], losses[-1]))
    require(r["compiles_end"] == r["compiles_after_first"],
            "no-recompile", "%d -> %d" % (r["compiles_after_first"],
                                          r["compiles_end"]))
    require(r["loss_devices"] == [ctx["platform"]], "loss-on-device",
            str(r["loss_devices"]))
    # at T = 256 attention lowers densely by shape, so this step holds
    # no Mosaic call (the kernels phase covers the engaged lowering)
    require(r["attribution"]["dense_vjp_hits"].get("xent", 0) > 0,
            "tiled-head-engaged", str(r["attribution"]["dense_vjp_hits"]))
    for k, n in fused.items():
        require(n > 0, "fuse-pass-fired", k)


def phase_numerics(ctx):
    require("train" in ctx and len(ctx["train"]["losses"]) >= 2,
            "numerics-needs-train", "the train phase produced no losses")
    exact = train(ctx["hp"], ctx["batch"], ctx["seq"], ctx["place"], steps=2,
                  use_bf16=False)
    got, ref = ctx["train"]["losses"][:2], exact["losses"]
    diffs = [abs(a - b) for a, b in zip(got, ref)]
    log("  bfloat16 %s" % " ".join("%.5f" % v for v in got))
    log("  float32  %s" % " ".join("%.5f" % v for v in ref))
    log("  |diff| %s (tolerance %g)"
        % (" ".join("%.2e" % d for d in diffs), LOSS_TOL))
    require(max(diffs) <= LOSS_TOL, "bf16-vs-float32-loss", str(diffs))


# --------------------------------------------------------------------------
# kernels: every member of pallas_kernels.__all__ against its dense twin
# --------------------------------------------------------------------------
def kernel_cases(rehearse):
    """name -> (kernel_fn, dense_fn, make_args, where).  Both fns map the
    same args to a tuple of arrays (outputs, then gradients where the
    kernel has a backward of its own).  make_args() draws the operands
    with jax.random, so jax.eval_shape(make_args) gives their abstract
    twins for free (tests cross-lower every case for TPU without a chip)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    bf16, f32 = jnp.bfloat16, jnp.float32
    root = jax.random.PRNGKey(0)

    def arr(i, shape, dtype, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(root, i), shape, f32)
                * scale).astype(dtype)

    def with_grads(f, n_diff):
        """f(*args) -> out (array or tuple); returns fn giving outputs
        plus d(sum of outputs)/d(first n_diff args)."""
        def scalar(*a):
            outs = f(*a)
            outs = outs if isinstance(outs, tuple) else (outs,)
            return sum(jnp.sum(o.astype(f32)) for o in outs), outs

        def fn(*a):
            (_, outs), grads = jax.value_and_grad(
                scalar, argnums=tuple(range(n_diff)), has_aux=True)(*a)
            return tuple(outs) + tuple(grads)
        return fn

    S = (lambda real, tiny: tiny) if rehearse else (lambda real, tiny: real)
    cases = {}

    D = S(64, 32)  # Transformer-base's head width
    scale = 1.0 / D ** 0.5

    # GPT-2 345M's training attention, through the fused_attention op's
    # own lowering with no flag set: platform and shape choose the
    # blockwise kernel (ops/nn_ops._flash_engages), as in the cells.  The
    # platform is stated as the chip's — this script runs nowhere else,
    # and a rehearsal rehearses the chip's path, interpreted.
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import nn_ops

    AB, AH, AT, AD = S(4, 2), S(16, 1), S(1024, 512), 64

    def attn_op(q, k, v):
        return nn_ops._fused_attention(
            LowerCtx(platform="tpu"), {"Q": [q], "K": [k], "V": [v]},
            {"causal": True})["Out"][0]

    def attn_dense(q, k, v):
        flat = [a.reshape(AB * AH, AT, AD) for a in (q, k, v)]
        return pk._dense_attention(*flat, True, AD ** -0.5).reshape(q.shape)

    cases["flash_attention"] = (
        with_grads(attn_op, 3), with_grads(attn_dense, 3),
        lambda: tuple(arr(i, (AB, AH, AT, AD), bf16) for i in range(3)),
        "GPT-2 345M causal self-attention, T %d, as fused_attention "
        "lowers it by default" % AT)

    # Transformer-base's decoder self-attention (T 256, 8 heads of 64,
    # causal with the key-padding bias), as the op lowers it under the
    # blockwise kernel's lengths: the one-tile form, several heads a step,
    # from [B, H, T, d] behind its own transposes, and (layout "bthd", what
    # the Transformer builder's Program holds since PR 63) from the
    # projections' [B, T, H, d] in place
    SB, SH, ST = S(8, 1), S(8, 2), S(256, 64)
    heads = (0, 2, 1, 3)

    def short_op(q, k, v, bias):
        def op(layout, q, k, v):
            return nn_ops._fused_attention(
                LowerCtx(platform="tpu"),
                {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
                {"causal": True, "layout": layout})["Out"][0]
        return op("bhtd", q, k, v), op(
            "bthd", *(jnp.transpose(x, heads) for x in (q, k, v)))

    def short_dense(q, k, v, bias):
        flat = [a.reshape(SB * SH, ST, AD) for a in (q, k, v)]
        kb = jnp.broadcast_to(bias[:, None, :], (SB, SH, ST)).reshape(
            SB * SH, ST)
        out = pk._dense_attention(*flat, True, AD ** -0.5, kb).reshape(
            q.shape)
        return out, jnp.transpose(out, heads)

    cases["short_attention"] = (
        with_grads(short_op, 3), with_grads(short_dense, 3),
        lambda: tuple(arr(i, (SB, SH, ST, AD), bf16) for i in range(3)) + (
            jnp.where(jnp.arange(ST)[None, :] < ST - 9, 0.0, -1e9).astype(f32)
            * jnp.ones((SB, 1), f32),),
        "Transformer-base decoder self-attention, T %d, as fused_attention "
        "lowers it by default in either layout" % ST)

    PB, PT = S(32, 2), S(256, 16)

    def piece_dense(q, k, v, qoff):
        s = jnp.einsum("bqd,bkd->bqk", q, k).astype(f32) * scale
        keep = (qoff[0] + jnp.arange(PT))[:, None] >= jnp.arange(PT)[None]
        s = jnp.where(keep[None], s, pk.NEG_INF)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse[..., None]), v)
        return o.astype(q.dtype), lse

    cases["flash_attention_piece"] = (
        with_grads(lambda q, k, v, qoff: pk.flash_attention_piece(
            q, k, v, True, scale, 128, 128, 0, qoff), 3),
        with_grads(piece_dense, 3),
        lambda: (arr(0, (PB, PT, D), bf16), arr(1, (PB, PT, D), bf16),
                 arr(2, (PB, PT, D), bf16), jnp.full((1,), PT // 2, jnp.int32)),
        "ring-attention chunk of %d with q offset %d" % (PT, PT // 2))
    return cases


def phase_kernels(ctx):
    import jax
    import numpy as np

    from paddle_tpu.ops import pallas_kernels as pk

    cases = kernel_cases(ctx["rehearse"])
    names = list(pk.__all__)
    require(sorted(cases) == sorted(names), "kernel-list-covers-__all__",
            str(sorted(set(names) ^ set(cases))))
    bad = []
    for name in names:
        kernel, dense, make_args, where = cases[name]
        t0 = time.perf_counter()
        try:
            args = make_args()
            compiled = jax.jit(kernel).lower(*args).compile()
            if not ctx["rehearse"]:
                require("tpu_custom_call" in compiled.as_text(),
                        "no Mosaic custom call in the compiled kernel")
            got = jax.block_until_ready(compiled(*args))
            with jax.default_matmul_precision("highest"):
                ref = jax.block_until_ready(jax.jit(dense)(*args))
            got = got if isinstance(got, tuple) else (got,)
            ref = ref if isinstance(ref, tuple) else (ref,)
            require(len(got) == len(ref), "output arity")
            worst = 0.0
            for a, b in zip(got, ref):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                require(a.shape == b.shape, "shape", "%s vs %s"
                        % (a.shape, b.shape))
                require(np.isfinite(a).all(), "finite")
                worst = max(worst, float(
                    np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6)))
            # relative to the twin's largest value: bf16 rounding of
            # either side is ~4e-3; a wrong mask or tile is O(1)
            require(worst <= 3e-2, "matches dense twin",
                    "max err / max |ref| = %.3g" % worst)
            log("  %-22s %s  err %.1e  %.1f s  (%s)" % (
                name, "interpreted" if ctx["rehearse"] else "compiled",
                worst, time.perf_counter() - t0, where))
        except Exception as e:
            bad.append(name)
            log("  %-22s FAILED (%s): %s" % (name, where, str(e)[:1500]))
            if not isinstance(e, Check):
                traceback.print_exc()
    require(not bad, "every-kernel-compiled", ", ".join(bad))


# --------------------------------------------------------------------------
# spmd (--devices 4)
# --------------------------------------------------------------------------
def phase_spmd(ctx):
    import jax
    import numpy as np

    from paddle_tpu.parallel.mesh import make_mesh

    require("train" in ctx and ctx["train"]["losses"],
            "spmd-needs-train", "no one-chip loss to compare with")
    one_chip_first = ctx["train"]["losses"][0]
    # the one-chip run's params and executables would sit on device 0
    # and blur the per-device memory check
    ctx.pop("train")
    gc.collect()

    devices = jax.devices()[:4]
    mesh = make_mesh({"dp": 2, "mp": 2}, devices)
    r = train(ctx["hp"], ctx["batch"], ctx["seq"], ctx["place"], steps=3,
              mesh=mesh)
    main, scope, exe = r["main"], r["scope"], r["exe"]
    log("  losses %s; one-chip first %.5f"
        % (" ".join("%.5f" % v for v in r["losses"]), one_chip_first))
    require(abs(r["losses"][0] - one_chip_first) <= SPMD_LOSS_TOL,
            "spmd-first-loss", "%.5f vs %.5f" % (r["losses"][0],
                                                 one_chip_first))
    require(r["compiles_end"] == r["compiles_after_first"], "no-recompile")

    per_device = replicated = 0
    sharded_param, sharded_bytes = None, 0
    for n in scope.all_var_names():
        v = scope.find_var(n)
        if not isinstance(v, jax.Array):
            continue
        replicated += v.nbytes
        per_device += v.dtype.itemsize * int(
            np.prod(v.sharding.shard_shape(v.shape)))
        spec = getattr(v.sharding, "spec", ())
        if ("mp" in spec and v.nbytes > sharded_bytes
                and scope.find_var(n + "_moment1_0") is not None):
            sharded_param, sharded_bytes = n, v.nbytes
    ratio = per_device / max(1, replicated)
    log("  state bytes per device / unsharded: %.4f" % ratio)
    require(ratio <= SPMD_STATE_RATIO, "spmd-state-bytes", "%.4f" % ratio)
    require(sharded_param is not None, "spmd-mp-sharded-param-exists")
    for n in (sharded_param, sharded_param + "_moment1_0",
              sharded_param + "_moment2_0"):
        v = scope.find_var(n)
        on = {s.device for s in v.addressable_shards}
        log("  %s spec %s on %d devices" % (n, v.sharding.spec, len(on)))
        require(len(on) == 4, "spmd-shards-on-four-devices", n)
        require("mp" in v.sharding.spec, "spmd-state-shards-like-param", n)

    comm = exe.spmd_comm_stats(main)
    log("  comm bytes per step %d: %s" % (comm["total_bytes"], json.dumps(
        {k: v["bytes"] for k, v in comm["per_op"].items()}, sort_keys=True)))
    require(comm["total_bytes"] > 0, "spmd-comm-stats-nonzero")
    hits = r["attribution"]["pallas_hits"]
    log("  pallas_hits %s" % json.dumps(hits, sort_keys=True))

    if ctx["rehearse"]:
        log("  per-device memory: not checked (CPU reports none)")
        return
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    log("  bytes_in_use per device: %s" % in_use)
    require(min(in_use) > 0.25 * max(in_use) and min(in_use) > 2 ** 20,
            "spmd-memory-spread-over-devices", str(in_use))


# --------------------------------------------------------------------------
def main():
    args = _parse()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % args.devices)
    t_start = time.perf_counter()
    import jax

    # persistent-cache traffic, so a warm second run can show that it
    # read what the first one wrote
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **_):
        key = name.rsplit("/", 1)[-1]
        if "/compilation_cache/" in name and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    log("devices: %s  jax %s" % (json.dumps(device), jax.__version__))
    want = "cpu" if args.rehearse else "tpu"
    if dev0.platform != want:
        log("chip_smoke: needs a %s, jax found %s" % (want, devices))
        return 3
    if len(devices) < args.devices:
        log("chip_smoke: --devices %d but jax found %d"
            % (args.devices, len(devices)))
        return 3

    import paddle_tpu as fluid  # places the compile cache on import
    from paddle_tpu.compile_cache import resolve_cache_dir
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.utils.flops import chip_peak_flops

    cache_dir, from_env = resolve_cache_dir()
    require(jax.config.jax_compilation_cache_dir == cache_dir,
            "compile-cache-placed", "%s vs %s" % (
                jax.config.jax_compilation_cache_dir, cache_dir))

    def cache_entries():
        return (len(os.listdir(cache_dir))
                if cache_dir and os.path.isdir(cache_dir) else 0)

    n_before = cache_entries()
    log("compile cache: %s (%s), %d entries at start" % (
        cache_dir, "from JAX_COMPILATION_CACHE_DIR" if from_env
        else "fixed path in the checkout" if cache_dir
        else "off: process pinned to the CPU", n_before))
    log("peak bf16 flop/s known for this device_kind: %s"
        % chip_peak_flops(dev0))

    rehearse = args.rehearse
    hp = make_hp(rehearse, args.devices)
    batch, seq = (4, 16) if rehearse else (128, 256)
    ctx = {
        "rehearse": rehearse, "platform": want, "hp": hp, "seq": seq,
        "place": fluid.CPUPlace() if rehearse else fluid.TPUPlace(0),
        "batch": tfm.make_fake_batch(batch, seq, seq, hp, seed=0),
    }
    log("model: transformer d_model %d inner %d heads %d layers %d vocab %d,"
        " batch %d x seq %d, bf16" % (
            hp.d_model, hp.d_inner_hid, hp.n_head, hp.n_layer,
            hp.trg_vocab_size, batch, seq))

    phases = [("train", phase_train)]
    if args.devices == 1:
        phases += [("numerics", phase_numerics), ("kernels", phase_kernels)]
    else:
        phases += [("spmd", phase_spmd)]
    failed = []
    for name, fn in phases:
        log("== %s" % name)
        t0 = time.perf_counter()
        before = dict(cache_events)
        try:
            fn(ctx)
            verdict = "ok"
        except Exception as e:
            failed.append(name)
            if not isinstance(e, Check):
                traceback.print_exc()
            verdict = "FAILED: %s" % str(e)[:2000]
        log("-- %s %s (%.1f s; persistent compile cache %d hits, %d misses)"
            % (name, verdict, time.perf_counter() - t0,
               cache_events["cache_hits"] - before["cache_hits"],
               cache_events["cache_misses"] - before["cache_misses"]))

    from paddle_tpu import native

    log("native library loaded by this run: %s" % (native._lib is not None))
    n_after = cache_entries()
    log("compile cache: %d entries at end (%+d); total %.1f s"
        % (n_after, n_after - n_before, time.perf_counter() - t_start))
    if failed:
        log("chip_smoke: FAILED phases: %s" % ", ".join(failed))
        return 1
    if rehearse:
        log("chip_smoke: REHEARSAL ok on %s — says nothing about the chip"
            % json.dumps(device))
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
