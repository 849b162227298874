"""fused_attention's training path: the blockwise kernel chosen from the
placed platform and the shape (ops/nn_ops._flash_engages), its numerics
against the dense lowering, and what the choice costs the host.

The CPU host steers what a chip would say in the test, never through an
option of the program: a LowerCtx that states platform "tpu" engages the
kernel (interpreted here), and `pallas_kernels._interpret` patched to
False cross-lowers it for the TPU without a chip."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.ops import kernel_tuning as kt
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = LowerCtx(platform="tpu")


def _qkv(b, h, t, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, d), jnp.float32).astype(
        jnp.bfloat16) for k in keys)


def _op(ctx, q, k, v, **attrs):
    attrs.setdefault("causal", True)
    return nn_ops._fused_attention(
        ctx, {"Q": [q], "K": [k], "V": [v]}, attrs)["Out"][0]


def _dense(q, k, v, causal=True):
    b, h, t, d = q.shape
    flat = [a.reshape(b * h, t, d) for a in (q, k, v)]
    return pk._dense_attention(*flat, causal, d ** -0.5).reshape(q.shape)


def _loss(fn):
    # a cotangent that differs by position, so a transposed or shifted
    # tile shows in every gradient
    def f(q, k, v):
        o = fn(q, k, v).astype(jnp.float32)
        w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
        return jnp.sum(o * w)
    return f


def _close(got, ref, tol=2e-2):
    """bf16 on both sides: within `tol` of the reference's largest value."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - ref)) <= tol * np.max(np.abs(ref))


# the two engaged head shapes of the cells, reduced in B*H only (and
# OLMoE's T = 4096 to 512 in 128-blocks: sixteen tiles, ten of them run)
@pytest.mark.parametrize("case", ["gpt2_t1024_d64", "olmoe_t512_d128"])
def test_chosen_path_matches_dense_forward_and_gradients(case):
    if case == "gpt2_t1024_d64":
        q, k, v = _qkv(1, 2, 1024, 64)
        before = kt.attribution()["pallas_hits"].get("attention", 0)
        chosen = lambda q, k, v: _op(TPU, q, k, v)  # noqa: E731
    else:
        q, k, v = _qkv(1, 2, 512, 128, seed=1)

        def chosen(q, k, v):
            flat = [a.reshape(2, 512, 128) for a in (q, k, v)]
            return pk.flash_attention(*flat, None, True, 128 ** -0.5, 128,
                                      128).reshape(q.shape)

    out, grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(chosen)(*a), argnums=(0, 1, 2)))(q, k, v)
    ref, ref_grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(_dense)(*a), argnums=(0, 1, 2)))(q, k, v)
    if case == "gpt2_t1024_d64":
        assert kt.attribution()["pallas_hits"]["attention"] > before
    _close(jax.jit(chosen)(q, k, v), _dense(q, k, v))
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    for g, r in zip(grads, ref_grads):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape
        _close(g, r)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_chosen_path_under_a_dp2_mp2_mesh_matches_dense():
    """Under a live mesh the same kernel runs inside shard_map, rows over
    dp and heads over mp (spmd_flash_attention): the sharding is the
    op's."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        spmd_lowering, train_partition_rules_for)

    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    rules = train_partition_rules_for("gpt2")
    q, k, v = _qkv(2, 2, 512, 64, seed=2)

    def sharded(q, k, v):
        with spmd_lowering(mesh, rules):
            return _op(TPU, q, k, v)

    f = jax.jit(jax.value_and_grad(
        lambda *a: _loss(sharded)(*a), argnums=(0, 1, 2)))
    assert "shard_map" in str(jax.make_jaxpr(f)(q, k, v))
    out, grads = f(q, k, v)
    ref, ref_grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(_dense)(*a), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    for g, r in zip(grads, ref_grads):
        _close(g, r)


def _shapes_in(jaxpr):
    """Every array shape in a jaxpr and, recursively, the jaxprs in its
    equations' parameters (jit, custom_vjp) — but not inside a
    pallas_call's body, whose arrays are VMEM tiles, not HBM arrays."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        yield tuple(getattr(v.aval, "shape", ()))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from _shapes_in(sub)


def test_no_t_by_t_array_in_the_forward_or_backward_jaxpr():
    t = 1024
    q, k, v = _qkv(1, 2, t, 64)

    def square(fn):
        jaxpr = jax.make_jaxpr(jax.grad(_loss(fn), argnums=(0, 1, 2)))(
            q, k, v)
        return [s for s in _shapes_in(jaxpr) if s.count(t) >= 2]

    assert square(_dense)  # the detector sees the dense [BH, T, T] scores
    assert square(lambda q, k, v: _op(TPU, q, k, v)) == []


@pytest.mark.parametrize("bh,t,d,bias,causal", [
    (4, 1024, 64, False, True), (2, 4096, 128, False, True),
    # the two Transformer-base cells' attentions (PR 62: the one-tile form)
    (1024, 256, 64, True, True), (1024, 256, 64, True, False),
    (1024, 256, 64, False, False), (4096, 64, 64, True, True),
    (4096, 64, 64, True, False), (4096, 64, 64, False, True),
    (672, 384, 64, True, True), (512, 256, 128, True, True)])
def test_kernel_cross_lowers_for_the_tpu_on_this_host(monkeypatch, bh, t, d,
                                                      bias, causal):
    """At the cells' engaged head shapes, blocks and tile plans as the
    lowering sets them: the Pallas -> Mosaic lowering and its block-spec
    checks, which interpret mode skips.  Two calls: one forward (the
    primal and the VJP's forward are one), one backward."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, bh, t, d), jnp.bfloat16)
    kb = jax.ShapeDtypeStruct((1, t), jnp.float32)

    def op(q, k, v, kb):
        ins = {"Q": [q], "K": [k], "V": [v]}
        if bias:
            ins["Bias"] = [kb]
        return nn_ops._fused_attention(TPU, ins, {"causal": causal})["Out"][0]

    lowered = jax.jit(jax.grad(
        lambda q, k, v, kb: _loss(lambda *a: op(*a, kb))(q, k, v),
        argnums=(0, 1, 2))).trace(x, x, x, kb).lower(
            lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 2


# (what it is, platform the step is placed on, Tq, Tk, head dim, engages)
CHOICE = [
    ("gpt2_345m_train", "tpu", 1024, 1024, 64, True),
    ("gpt2_345m_train_dp2mp2", "tpu", 1024, 1024, 64, True),
    ("olmoe_1b7b_train", "tpu", 4096, 4096, 128, True),
    ("tfm_base_train: T = 256 is under the threshold (the one-tile form's)",
     "tpu", 256, 256, 64, False),
    ("tfm_base_train_s64: 64 is no multiple of 128 (the one-tile form's)",
     "tpu", 64, 64, 64, False),
    # resnet50_train, the sixth cell, has no attention op
    ("cross-attention, Tq != Tk", "tpu", 1024, 2048, 64, False),
    ("a ragged length", "tpu", 1000, 1000, 64, False),
    ("a head dim the sweep did not cover", "tpu", 1024, 1024, 80, False),
    ("GPT-2's shape placed on the CPU", "cpu", 1024, 1024, 64, False),
]


@pytest.mark.parametrize("what,platform,tq,tk,d,engages", CHOICE,
                         ids=[c[0].split(":")[0] for c in CHOICE])
def test_choice_table(monkeypatch, what, platform, tq, tk, d, engages):
    # the placed platform decides, whatever the process's default backend
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "cpu" if platform == "tpu" else "tpu")
    assert nn_ops._flash_engages(
        LowerCtx(platform=platform), tq, tk, d) is engages, what


def _taken(ctx, t, d, tk=None, **extra):
    """Which lowering the op takes, read off the counters its engagements
    tick: the op itself is traced (abstractly), nothing is asked of the
    rule's helpers."""
    tk = tk or t
    q = jax.ShapeDtypeStruct((2, 2, t, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 2, tk, d), jnp.bfloat16)
    ins = {"QStart": jax.ShapeDtypeStruct((1,), jnp.int32),
           "SegmentIds": jax.ShapeDtypeStruct((2, t), jnp.int32),
           "Bias": jax.ShapeDtypeStruct((2, tk), jnp.float32)}
    ins = {slot: ins[slot] for slot in extra.pop("slots", ())}
    hits = lambda: dict(kt.attribution()["pallas_hits"])  # noqa: E731
    before = hits()
    jax.eval_shape(
        lambda q, k, v, ins: nn_ops._fused_attention(
            ctx, dict({"Q": [q], "K": [k], "V": [v]},
                      **{s: [a] for s, a in ins.items()}),
            dict({"causal": True}, **extra))["Out"][0], q, kv, kv, ins)
    more = {f: n - before.get(f, 0) for f, n in hits().items()
            if n != before.get(f, 0)}
    if not more:
        return "dense"
    assert more.pop("attention") == 1  # every kernel engagement counts here
    if more == {"attention_short": 1}:  # and the one-tile form once more
        return "one_tile"
    assert "attention_short" not in more
    return "blockwise"


@pytest.mark.parametrize("d", [64, 128, 192])
@pytest.mark.parametrize("t", [56, 64, 256, 384, 512, 1024])
def test_three_way_choice_by_length_and_width(t, d):
    """TPU-placed self-attention: the one-tile form under the blockwise
    kernel's lengths at the (T, head width) pairs the chip sweep has a row
    for and found it ahead at (64-wide heads at 64, two heads side by side,
    and at 256 and 384; 128-wide heads at 256 alone: dense is ahead of it
    at 64 and 128, the blockwise kernel at 384), the blockwise kernel from
    512 on at either width; dense for everything else; a CPU-placed step is
    dense whatever the shape."""
    want = ("blockwise" if t >= 512 and d != 192
            else "one_tile" if (t, d) in ((64, 64), (256, 64), (384, 64),
                                          (256, 128)) else "dense")
    assert _taken(TPU, t, d) == want
    assert _taken(LowerCtx(platform="cpu"), t, d) == "dense"


@pytest.mark.parametrize("what,kwargs,want", [
    ("the key-padding bias rides the one-tile form", {"slots": ("Bias",)},
     "one_tile"),
    ("not causal", {"causal": False}, "one_tile"),
    ("a window is the blockwise kernel's, from 512 on", {"window": 64},
     "dense"),
    ("segment ids are the blockwise kernel's", {"slots": ("SegmentIds",)},
     "dense"),
    ("QStart (cached decode) never enters the training path",
     {"slots": ("QStart",)}, "dense"),
    ("cross-attention over another length", {"tk": 128, "causal": False},
     "dense"),
], ids=lambda x: x.split(":")[0] if isinstance(x, str) else None)
def test_what_keeps_a_short_attention_off_the_one_tile_form(what, kwargs,
                                                            want):
    assert _taken(TPU, 256, 64, **dict(kwargs)) == want, what


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_under_a_live_mesh_a_short_attention_stays_dense():
    """spmd_flash_attention is the blockwise kernel's: under a live mesh the
    one-tile lengths keep the dense lowering, T = 512 the kernel in
    shard_map as before."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        spmd_lowering, train_partition_rules_for)

    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    with spmd_lowering(mesh, train_partition_rules_for("gpt2")):
        assert _taken(TPU, 256, 64) == "dense"
        assert _taken(TPU, 512, 64) == "blockwise"
    assert _taken(TPU, 256, 64) == "one_tile"


def test_one_tile_op_matches_dense_with_the_bias_forward_and_gradients():
    """The op placed on a TPU at T = 128 (the kernel, interpreted here)
    against the op placed on the CPU (dense), the key-padding bias [B, T]
    broadcast over heads: the result and dq / dk / dv."""
    q, k, v = _qkv(2, 4, 128, 64, seed=9)
    bias = jnp.where(jnp.arange(128)[None, :] < jnp.array([[100], [128]]),
                     0.0, -1e9).astype(jnp.float32)

    def op(ctx):
        return lambda q, k, v: nn_ops._fused_attention(
            ctx, {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
            {"causal": True})["Out"][0]

    before = kt.attribution()["pallas_hits"].get("attention_short", 0)
    got = jax.jit(op(TPU))(q, k, v)
    assert kt.attribution()["pallas_hits"]["attention_short"] == before + 1
    _close(got, op(LowerCtx(platform="cpu"))(q, k, v))
    grads = jax.jit(jax.grad(_loss(op(TPU)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(_loss(op(LowerCtx(platform="cpu"))),
                            argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(grads, want):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape
        _close(g, r)


def test_no_t_by_t_array_with_the_one_tile_form_either():
    t = 256
    q, k, v = _qkv(1, 2, t, 64)
    jaxpr = jax.make_jaxpr(jax.grad(
        _loss(lambda q, k, v: _op(TPU, q, k, v)), argnums=(0, 1, 2)))(q, k, v)
    assert [s for s in _shapes_in(jaxpr) if s.count(t) >= 2] == []


@pytest.mark.parametrize("t, window, block", [
    (512, 0, 512), (640, 0, 128), (1024, 0, 1024), (1536, 0, 512),
    (4096, 0, 1024), (16, 0, 16),
    (6144, 512, 512),  # Laguna-XS.2's: half a 1024-block, one of 512
    (8192, 2048, 1024),  # Trinity-Mini's: two blocks, as before PR 66
    (1024, 512, 512), (6144, 1536, 512), (6144, 3072, 1024),
    (6144, 6144, 1024), (6144, 8192, 1024),  # covers the sequence
    (6144, 1000, 1024), (640, 100, 128),  # no block divides the window
    # the measured floor: 512-blocks ahead of 256 and 128 at these windows
    (6144, 256, 512), (6144, 128, 512), (6144, 384, 512), (512, 256, 512),
    (768, 256, 256), (640, 128, 128),  # where 512 does not divide T
    (16, 8, 16),  # a length no block divides: one block
], ids=lambda v: str(v))
def test_blocks_divide_the_length_and_the_window(t, window, block):
    """_flash_block(T, window): the largest block that divides T, and under
    a window narrower than T the largest that divides the window too, not
    under the floor the chip's sweep set (nn_ops._FLASH_BAND_MIN_BLOCK): the
    band's edge lies on a block boundary and no tile is cut by the diagonal
    and the edge at once, wherever a block from the floor up divides the
    window."""
    assert nn_ops._flash_block(t, window) == block
    if not window:
        assert nn_ops._flash_block(t) == block
    band = window if window < t else 0  # what the kernels make of it
    if window % block == 0:
        assert pk._tile_counts(t, block, block, band)["both"] == 0
        if band and block >= 256:  # strips on the band's edge
            assert pk._tile_plan(t, block, block, band,
                                 pk._strip_parts(block)).edge > 1
    elif any(t % b == 0 and window % b == 0 for b in nn_ops._FLASH_BLOCKS):
        assert block == nn_ops._FLASH_BAND_MIN_BLOCK  # the floor, no less


def test_choice_falls_back_to_the_default_backend_and_skips_qstart(
        monkeypatch):
    # a caller that states no platform (parallel/ulysses.py's bare ctx)
    assert not nn_ops._flash_engages(LowerCtx(), 1024, 1024, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert nn_ops._flash_engages(LowerCtx(), 1024, 1024, 64)
    # QStart (chunked decode) at an engaged shape: not this path
    q, k, v = (jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16),) * 3
    before = kt.attribution()["pallas_hits"].get("attention", 0)
    jaxpr = jax.make_jaxpr(lambda q, k, v, s: nn_ops._fused_attention(
        TPU, {"Q": [q], "K": [k], "V": [v], "QStart": [s]},
        {"causal": True})["Out"][0])(
            q, k, v, jax.ShapeDtypeStruct((1,), jnp.int32))
    assert kt.attribution()["pallas_hits"].get("attention", 0) == before
    assert any(s.count(1024) >= 2 for s in _shapes_in(jaxpr))  # dense


def test_layer_states_its_output_without_evaluating_the_lowering(
        monkeypatch):
    """Building a program never runs fused_attention's lowering (Out is
    Q's shape and dtype): 24 evaluations of a kernel leave GPT-2's
    build."""
    def boom(*a, **k):
        raise AssertionError("the lowering was evaluated to build a program")

    monkeypatch.setattr(fluid.core.registry.get_op("fused_attention"),
                        "lower", boom)
    q = layers.data("q", shape=[2, 128, 64], dtype="float32")
    out = layers.fused_attention(q, q, q, causal=True)
    assert tuple(out.shape) == tuple(q.shape) and out.dtype == q.dtype


@pytest.mark.parametrize("t,entry,fwd_body,bwd_body,family", [
    (1024, "_flash", "_flash_fwd_kernel", "_flash_bwd_fused_kernel",
     "attention"),
    (128, "_short", "_short_fwd_kernel", "_short_bwd_kernel",
     "attention_short")], ids=["blockwise", "one_tile"])
def test_deep_program_traces_and_carries_each_kernel_once(
        monkeypatch, t, entry, fwd_body, bwd_body, family):
    """The host-cost pin: a 4-layer causal training step lowered for the
    TPU traces each kernel body once, and its StableHLO holds a Mosaic
    payload per distinct entry, not per layer — twelve call sites share
    three functions: the forward op's (jit's dead-code pass prunes its
    unused lse output, or the one-tile form's unused residuals, so it is a
    jaxpr of its own), the grad op's
    re-traced forward and the backward.  (On the device the first two are
    one instruction: same operands, same payload.)"""
    from paddle_tpu.core.trace import build_traced_function

    n_layer, heads, d = 4, 2, 64
    counts = {"fwd": 0, "bwd": 0}

    def counted(name, body):
        def wrapper(*a, **k):
            counts[name] += 1
            return body(*a, **k)
        return wrapper

    monkeypatch.setattr(pk, fwd_body, counted("fwd", getattr(pk, fwd_body)))
    monkeypatch.setattr(pk, bwd_body, counted("bwd", getattr(pk, bwd_body)))
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.clear_caches()  # an earlier test's trace of this shape would hide

    x = layers.data("x", shape=[t, heads * d], dtype="float32")
    h = x
    for _ in range(n_layer):
        qkv = [layers.transpose(layers.reshape(
            layers.fc(h, heads * d, num_flatten_dims=2),
            [-1, t, heads, d]), [0, 2, 1, 3]) for _ in range(3)]
        a = layers.fused_attention(*qkv, causal=True)
        h = h + layers.reshape(layers.transpose(a, [0, 2, 1, 3]),
                               [-1, t, heads * d])
    loss = layers.mean(h)
    fluid.optimizer.SGD(0.1).minimize(loss)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())

    traced = build_traced_function(main, 0, ("x",), [loss.name], scope,
                                   platform="tpu")
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    args = ({"x": jax.ShapeDtypeStruct((2, t, heads * d), jnp.float32)},
            {n: sds(scope.find_var(n)) for n in traced.ro_names},
            {n: sds(scope.find_var(n)) for n in traced.rw_names},
            sds(jax.random.PRNGKey(0)))
    before = kt.attribution()["pallas_hits"].get(family, 0)
    text = jax.jit(traced.fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()

    assert counts == {"fwd": 1, "bwd": 1}
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @%s_fwd_call" % entry) == 2 * n_layer
    assert text.count("call @%s_bwd_call" % entry) == n_layer
    assert text.count("func.func private @%s" % entry) == 3
    # the counter still sees every engagement: forward and grad op a layer
    assert (kt.attribution()["pallas_hits"][family] - before
            == 2 * n_layer)


def test_a_process_whose_shapes_all_say_dense_never_imports_pallas():
    """tfm_base at its rehearse preset, built, lowered and stepped in a
    process of its own: jax.experimental.pallas is not imported (1.3 s of
    every such process's set-up)."""
    code = r"""
import importlib.util, json, os, sys
root = sys.argv[1]
sys.path.insert(0, root)
import paddle_tpu as fluid

def load(*parts):
    return json.load(open(os.path.join(root, *parts)))

def merged(d):
    out = {k: v for k, v in d.items() if k != "rehearse"}
    out.update(d.get("rehearse", {}))
    return out

cfg = merged(load("benchmark", "configs", "tfm_base.json"))
work = merged(load("benchmark", "workloads", "tfm_base_train.json"))
spec = importlib.util.spec_from_file_location("adapter", os.path.join(
    root, "benchmark", "adapters", cfg["adapter"] + ".py"))
adapter = importlib.util.module_from_spec(spec)
spec.loader.exec_module(adapter)
built = adapter.build(cfg, work, mesh=None, forward_only=False)
assert any(op.type == "fused_attention"
           for op in built["main"].global_block().ops)
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(built["startup"])
    exe.run(built["main"], feed=adapter.make_batch(cfg, work, 0),
            fetch_list=[built["loss"]])
bad = sorted(m for m in sys.modules if m.startswith("jax.experimental.pallas")
             or m.startswith("jax._src.pallas"))
assert not bad, bad
print("NO_PALLAS")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code, ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "NO_PALLAS" in r.stdout, (
        r.stdout[-2000:] + r.stderr[-4000:])


# --- V of another width than Q and K (latent attention) ---------------------
def _qkv_wide(b, h, t, d=192, dv=128, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, t, w), jnp.float32).astype(
        jnp.bfloat16) for k, w in zip(keys, (d, d, dv)))


def _dense_wide(q, k, v, window=0, seg=None):
    b, h, t, d = q.shape
    flat = [a.reshape(b * h, t, a.shape[-1]) for a in (q, k, v)]
    seg = None if seg is None else jnp.broadcast_to(
        seg[:, None, :], (b, h, t)).reshape(b * h, t)
    return pk._dense_attention(*flat, True, d ** -0.5, window=window,
                               seg=seg).reshape(b, h, t, v.shape[-1])


WIDE_CHOICE = [
    ("kanana2_30b_a3b_train: scores 192 wide over 128-wide values", 6144,
     192, 128, True),
    ("one head width, as before: V's width unsaid", 1024, 128, None, True),
    ("V as wide as the scores: not swept", 6144, 192, 192, False),
    ("a narrower V under 128-wide scores: not swept", 1024, 128, 64, False),
    ("192 over 128 under the length threshold", 256, 192, 128, False),
]


@pytest.mark.parametrize("what,t,d,dv,engages", WIDE_CHOICE,
                         ids=[c[0].split(":")[0] for c in WIDE_CHOICE])
def test_choice_table_takes_the_latent_widths(what, t, d, dv, engages):
    assert nn_ops._flash_engages(TPU, t, t, d, dv) is engages, what


@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
def test_a_v_of_another_width_matches_dense_forward_and_gradients(
        monkeypatch, backward):
    """Q, K [B, H, T, 192] and V [B, H, T, 128] through the op placed on a
    TPU (the kernel, interpreted here) against the dense lowering: the
    result is [B, H, T, 128], and it and all three gradients agree, with
    the one-kernel backward (what T <= 8192 takes at 192) and with the dq
    and dk/dv kernels (what longer sequences take)."""
    if backward == "two_kernels":
        monkeypatch.setattr(pk, "_FUSED_BWD_DQ_BYTES_WIDE", 0)
        jax.clear_caches()
    q, k, v = _qkv_wide(1, 2, 512)
    before = kt.attribution()["pallas_hits"].get("attention_qk192_v128", 0)
    chosen = lambda q, k, v: _op(TPU, q, k, v)  # noqa: E731
    out, grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(chosen)(*a), argnums=(0, 1, 2)))(q, k, v)
    assert (kt.attribution()["pallas_hits"]["attention_qk192_v128"]
            > before)  # the widths the kernel engaged with, on record
    ref, ref_grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(_dense_wide)(*a), argnums=(0, 1, 2)))(q, k, v)
    got = jax.jit(chosen)(q, k, v)
    assert got.shape == (1, 2, 512, 128) and got.dtype == jnp.bfloat16
    _close(got, _dense_wide(q, k, v))
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    for g, r, like in zip(grads, ref_grads, (q, k, v)):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape == like.shape
        _close(g, r)
    if backward == "two_kernels":
        jax.clear_caches()  # the next test traces its own


def test_a_v_of_another_width_on_the_cpu_is_the_dense_lowering():
    q, k, v = _qkv_wide(2, 2, 64, d=24, dv=16)
    got = _op(LowerCtx(platform="cpu"), q, k, v)
    assert got.shape == (2, 2, 64, 16)
    _close(got, _dense_wide(q, k, v), tol=1e-6)


def test_window_and_segment_ids_stay_right_at_the_latent_widths():
    """A sliding window and packed segments do not look at a width: the
    kernel (interpreted) against the dense lowering, forward and the
    gradients, with V narrower than Q and K."""
    q, k, v = _qkv_wide(1, 2, 512, seed=4)
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 128, -1).reshape(1, 512))
    for attrs, kwargs in (({"window": 160}, {"window": 160}),
                          ({}, {"seg": seg})):
        ins = {"SegmentIds": [seg]} if "seg" in kwargs else {}

        def chosen(q, k, v):
            return nn_ops._fused_attention(
                TPU, dict({"Q": [q], "K": [k], "V": [v]}, **ins),
                dict({"causal": True}, **attrs))["Out"][0]

        dense = lambda q, k, v: _dense_wide(q, k, v, **kwargs)  # noqa: E731
        _close(jax.jit(chosen)(q, k, v), dense(q, k, v))
        grads = jax.jit(jax.grad(_loss(chosen), argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(_loss(dense), argnums=(0, 1, 2)))(q, k, v)
        for g, r in zip(grads, want):
            _close(g, r)


def test_cached_decode_and_the_mesh_path_refuse_another_v_width():
    """The QStart (cached decode) lowerings and spmd_flash_attention were
    written for one head width and say so."""
    from paddle_tpu.ops.spmd_epilogue import spmd_flash_attention

    q, k, v = _qkv_wide(1, 2, 128, d=24, dv=16)
    with pytest.raises(ValueError, match="QStart .* take V at Q's width"):
        nn_ops._fused_attention(
            TPU, {"Q": [q], "K": [k], "V": [v],
                  "QStart": [jnp.zeros((1,), jnp.int32)]}, {"causal": True})
    with pytest.raises(ValueError, match="takes V at Q's width"):
        spmd_flash_attention((None,) * 6, q, k, v, None, None, True, 1.0,
                             128, 128, 0)


def test_layer_and_infer_rule_state_the_result_at_vs_width():
    from paddle_tpu.analysis.infer import VarInfo, get_infer_rule

    q = layers.data("q", shape=[2, 128, 24], dtype="float32")
    v = layers.data("v", shape=[2, 128, 16], dtype="float32")
    out = layers.fused_attention(q, q, v, causal=True)
    assert tuple(out.shape) == (-1, 2, 128, 16) and out.dtype == q.dtype

    class Op:
        attrs = {}

    shapes = {"Q": (4, 2, 128, 24), "K": (4, 2, 128, 24),
              "V": (4, 2, 128, 16)}
    got = get_infer_rule("fused_attention").fn(
        Op, {s: [VarInfo(shape, "bfloat16")] for s, shape in shapes.items()})
    assert tuple(got["Out"][0].shape) == (4, 2, 128, 16)
    assert got["Out"][0].dtype == "bfloat16"


@pytest.mark.parametrize("t, calls", [(4096, 2), (6144, 2), (16384, 3)])
def test_latent_kernel_cross_lowers_for_the_tpu_on_this_host(monkeypatch, t,
                                                             calls):
    """32 heads of 192 over 128 at the cell's candidate lengths, blocks as
    the lowering sets them: one forward and the one-kernel backward up to
    T = 8192; the dq and dk/dv kernels beyond."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    qk = jax.ShapeDtypeStruct((1, 32, t, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 32, t, 128), jnp.bfloat16)
    lowered = jax.jit(jax.grad(
        _loss(lambda q, k, v: _op(TPU, q, k, v)), argnums=(0, 1, 2))).trace(
            qk, qk, v).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == calls


# --- a window, a head width of its own and an output gate (PR 40) ---
def test_multi_head_attention_hands_the_op_its_window_and_head_width():
    """`head_dim` 32 over d_model 64 with 4 heads (not 64 / 4): q and the
    gate project to 128, k and v to 2 x 32, the output back from 128; the
    `window` is the fused_attention op's attribute, the gate a sigmoid
    and a product before the output projection; without the new arguments
    the layer builds what it built (no gate, no window, d_model / n_head)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import transformer as tfm

    def build(**kw):
        main, startup = fluid.Program(), fluid.Program()
        with framework.program_guard(main, startup), \
                fluid.unique_name.guard():
            x = layers.data("x", shape=[2, 16, 64], append_batch_size=False)
            out = tfm.multi_head_attention(
                x, x, x, None, 64, 4, fused=True, causal=True, n_kv_head=2,
                **kw)
        block = main.global_block()
        shapes = {p.name.rsplit("_", 1)[0]: tuple(p.shape)
                  for p in block.all_parameters()}
        return block, shapes, out

    block, shapes, out = build(head_dim=32, window=5, out_gate=True,
                               scopes=True)
    assert shapes == {"mha_q.w": (64, 128), "mha_k.w": (64, 64),
                      "mha_v.w": (64, 64), "mha_gate.w": (64, 128),
                      "mha_o.w": (128, 64)}
    assert tuple(out.shape) == (2, 16, 64)
    (core,) = [op for op in block.ops if op.type == "fused_attention"]
    assert core.attrs["window"] == 5 and core.attrs["causal"]
    assert core.attrs["scale"] == 32 ** -0.5
    assert tuple(block.var(core.inputs["Q"][0]).shape) == (2, 4, 16, 32)
    assert core.attrs["op_namescope"] == "core"
    gate = [op.type for op in block.ops
            if op.attrs.get("op_namescope") == "attn_gate"]
    assert gate == ["sigmoid", "elementwise_mul"]
    types = [op.type for op in block.ops]
    assert types.index("elementwise_mul") > types.index("fused_attention")

    block, shapes, _ = build()
    assert shapes == {"mha_q.w": (64, 64), "mha_k.w": (64, 32),
                      "mha_v.w": (64, 32), "mha_o.w": (64, 64)}
    (core,) = [op for op in block.ops if op.type == "fused_attention"]
    assert core.attrs["window"] == 0
    assert not [op for op in block.ops if "op_namescope" in op.attrs]
    assert "sigmoid" not in [op.type for op in block.ops]


@pytest.mark.parametrize("kw, error", [
    ({"fused": False, "causal": True}, "fused causal training path"),
    ({"fused": True, "causal": False}, "fused causal training path"),
    ({"fused": True, "causal": False,
      "cache": {"k": None, "v": None, "pos": None}},
     "fused causal training path"),
])
def test_a_window_outside_the_fused_causal_path_is_refused(kw, error):
    from paddle_tpu.models import transformer as tfm

    x = layers.data("x", shape=[2, 16, 64], append_batch_size=False)
    with pytest.raises(ValueError, match=error):
        tfm.multi_head_attention(x, x, x, None, 64, 4, window=4, **kw)


@pytest.mark.parametrize("window", [200, 384, 512, 600])
def test_a_window_on_the_chosen_path_is_the_dense_lowerings_mask(window):
    """Heads of 128 at T = 512 through the op placed on a TPU (the kernel,
    interpreted here, in 512-blocks: one tile a head, so the band is all
    the kernel's mask) against the dense lowering, which a CPU-placed
    step takes: the same `0 <= i - j < window`, forward and gradients, at
    windows T is no multiple of, at T and beyond it, where both are full
    causal attention."""
    q, k, v = _qkv(1, 2, 512, 128, seed=6)
    chosen = lambda q, k, v: _op(TPU, q, k, v, window=window)  # noqa: E731
    dense = lambda q, k, v: _op(  # noqa: E731
        LowerCtx(platform="cpu"), q, k, v, window=window)
    _close(jax.jit(chosen)(q, k, v), dense(q, k, v))
    grads = jax.jit(jax.grad(_loss(chosen), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(_loss(dense), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(grads, want):
        _close(g, r)
    if window >= 512:
        np.testing.assert_array_equal(
            np.asarray(dense(q, k, v), np.float32),
            np.asarray(_op(LowerCtx(platform="cpu"), q, k, v), np.float32))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(chosen)(q, k, v), np.float32),
            np.asarray(jax.jit(lambda q, k, v: _op(TPU, q, k, v))(q, k, v),
                       np.float32))


# (T, window, block_q, block_k): a window under a block, one block, no
# multiple of one (200, 600), the whole length and beyond it, and blocks
# that are not square at T = 2048
BAND_TABLE = [
    (512, 50, 128, 128),
    (512, 128, 128, 128),
    (1024, 200, 128, 128),
    (1024, 600, 256, 256),
    (1024, 600, 128, 256),
    (512, 512, 128, 128),
    (512, 700, 128, 128),
    (2048, 300, 512, 1024),
    (2048, 300, 1024, 512),
]


def _band_runs(t, window, bq, bk):
    """_band's `run` by brute force: [nq, nk] of bool."""
    return np.array([[bool(pk._band(i, j, 0, bq, bk, True, window)[0])
                      for j in range(t // bk)] for i in range(t // bq)])


@pytest.mark.parametrize("t, window, bq, bk", BAND_TABLE)
def test_band_span_is_the_band_that_band_runs(t, window, bq, bk):
    """The band grid's helper against `_band` itself, both ways round: for
    every outer block the first and last inner block `_band` runs, as
    numpy and as the traced scalars an index map computes; the grid's
    width is the widest walk (0, the full grid, where that is as wide as
    the grid or the window covers the sequence); every walk visits just
    its live blocks, in order, and then repeats the last one."""
    runs = _band_runs(t, window, bq, bk)
    for transposed, table in ((False, runs), (True, runs.T)):
        n_outer, n_inner = table.shape
        assert table.any(axis=1).all()  # every walk computes something
        first = table.argmax(axis=1)
        last = n_inner - 1 - table[:, ::-1].argmax(axis=1)
        # a band is contiguous: what lies between first and last runs
        assert (table.sum(axis=1) == last - first + 1).all()
        got = pk._band_span(np.arange(n_outer), bq, bk, window, n_inner,
                            transposed, traced=False)
        np.testing.assert_array_equal(got[0], first)
        np.testing.assert_array_equal(got[1], last)
        widest = int((last - first).max()) + 1
        width = pk._band_grid(t, t, bq, bk, True, window, transposed)
        assert width == (widest if widest < n_inner and window < t else 0)
        for o in range(n_outer):
            lo, hi = pk._band_span(jnp.int32(o), bq, bk, window, n_inner,
                                   transposed)
            assert (int(lo), int(hi)) == (first[o], last[o])
            walk = [pk._band_step(jnp.int32(o), jnp.int32(s), bq, bk, window,
                                  n_inner, transposed)
                    for s in range(width)]
            live = [int(b) for b, ok in walk if ok]
            if width:
                assert live == list(range(first[o], last[o] + 1))
                assert all(int(b) == last[o] for b, ok in walk if not ok)
    walked, computed = pk.band_grid_steps(t, bq, bk, window)
    nb = pk._band_grid(t, t, bq, bk, True, window)
    assert computed == runs.sum()
    assert walked == runs.shape[0] * (nb or runs.shape[1])
    assert pk._band_grid(t, t, bq, bk, True, 0) == 0  # no window: no band
    assert pk._band_grid(t, t, bq, bk, False, 0) == 0


@pytest.mark.parametrize("extra", ["plain", "kbias", "seg"])
@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
@pytest.mark.parametrize("t, window, bq, bk", BAND_TABLE)
def test_band_grid_kernels_match_dense(monkeypatch, t, window, bq, bk,
                                       backward, extra):
    """The kernels on the band grid (and on the full grid where the table's
    row keeps it) against `_dense_attention` under the same window:
    forward, dq, dk, dv and the key bias' gradient, the one-kernel backward
    and the dq + dk/dv pair, with a key bias and with packed segments."""
    if backward == "two_kernels":
        monkeypatch.setattr(pk, "_FUSED_BWD_DQ_BYTES", 0)
    jax.clear_caches()
    rng = np.random.RandomState(t + window + bq)
    bh, d = 2, 8
    q, k, v = (jnp.asarray(rng.randn(bh, t, d).astype("float32"))
               for _ in range(3))
    kbias = (jnp.asarray(rng.randn(bh, t).astype("float32"))
             if extra == "kbias" else None)
    # segments of uneven lengths, so a boundary falls inside a block
    seg = (jnp.asarray(np.broadcast_to(np.searchsorted(
        [t // 5, t // 2 + 3], np.arange(t), side="right"), (bh, t)),
        jnp.int32) if extra == "seg" else None)
    scale = 1.0 / np.sqrt(d)
    w = jnp.cos(jnp.arange(bh * t * d, dtype=jnp.float32)).reshape(bh, t, d)

    def kernel(q, k, v, kb):
        return pk.flash_attention(q, k, v, kb, True, scale, bq, bk, window,
                                  seg)

    def dense(q, k, v, kb):
        return pk._dense_attention(q, k, v, True, scale, kb, window=window,
                                   seg=seg)

    np.testing.assert_allclose(np.asarray(kernel(q, k, v, kbias)),
                               np.asarray(dense(q, k, v, kbias)),
                               rtol=2e-4, atol=2e-5)
    argnums = (0, 1, 2, 3) if kbias is not None else (0, 1, 2)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), argnums)(
        q, k, v, kbias)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums)(
        q, k, v, kbias)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)
    jax.clear_caches()  # the patched limit must not outlive the test


def _grids(fn, *args):
    """The grid of every pallas_call under fn's jaxpr, shard_map bodies and
    nested calls included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["grid_mapping"].grid
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_a_window_under_a_dp2_mp2_mesh_walks_the_band(monkeypatch):
    """spmd_flash_attention hands the same entry the window: inside the
    shard_map each device's kernels walk the band's 3 of 4 blocks (T = 512
    in blocks of 128, said by the test, under a window of 200), forward and
    backward, and match the dense lowering under the same window."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        spmd_lowering, train_partition_rules_for)

    monkeypatch.setattr(nn_ops, "_FLASH_BLOCKS", (128,))
    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    rules = train_partition_rules_for("gpt2")
    q, k, v = _qkv(2, 2, 512, 64, seed=8)

    def sharded(q, k, v):
        with spmd_lowering(mesh, rules):
            return _op(TPU, q, k, v, window=200)

    def dense(q, k, v):
        return _op(LowerCtx(platform="cpu"), q, k, v, window=200)

    f = jax.jit(jax.value_and_grad(
        lambda *a: _loss(sharded)(*a), argnums=(0, 1, 2)))
    assert "shard_map" in str(jax.make_jaxpr(f)(q, k, v))
    grids = _grids(f, q, k, v)
    assert grids and all(g == (1, 4, 3) for g in grids)
    out, grads = f(q, k, v)
    ref, ref_grads = jax.jit(jax.value_and_grad(
        lambda *a: _loss(dense)(*a), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-2)
    for g, r in zip(grads, ref_grads):
        _close(g, r)
    assert kt.attribution()["attention_band_grid"]["steps"][
        "512x200x128x128"] == [12, 9]


def test_the_lowering_records_the_band_grid_of_a_windowed_op_only():
    """attribution()["attention_band_grid"], at trace time, by the op's
    lowering: Trinity-Mini's window layers (T 8192, window 2048, blocks of
    1024) walk 24 forward steps a head and compute 21 (the full grid's 64
    is what the kernel no longer walks); an op without a window records
    nothing; one a lowering, however often the jitted kernel is shared."""
    x = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)
    kt.reset_attribution()
    jax.eval_shape(lambda q, k, v: _op(TPU, q, k, v), x, x, x)
    assert kt.attribution()["pallas_hits"]["attention"] == 1
    assert kt.attribution()["attention_band_grid"] == {"ops": 0, "steps": {}}
    for _ in range(2):
        jax.eval_shape(lambda q, k, v: _op(TPU, q, k, v, window=2048),
                       x, x, x)
    assert kt.attribution()["attention_band_grid"] == {
        "ops": 2, "steps": {"8192x2048x1024x1024": [24, 21]}}
    # a window that covers the sequence keeps the full grid, and says so
    jax.eval_shape(lambda q, k, v: _op(TPU, q, k, v, window=8192), x, x, x)
    assert kt.attribution()["attention_band_grid"]["steps"][
        "8192x8192x1024x1024"] == [64, 36]
    # the dense lowering (a CPU-placed step) records none
    kt.reset_attribution()
    jax.eval_shape(lambda q, k, v: _op(LowerCtx(platform="cpu"), q, k, v,
                                       window=2048), x, x, x)
    assert kt.attribution()["attention_band_grid"] == {"ops": 0, "steps": {}}


def test_the_counters_report_the_block_that_ran_under_lagunas_window():
    """Laguna-XS.2's window layers (T 6144, window 512, heads of 128): the
    lowering hands the kernels and both counters the block the rule answers
    (PR 66: 512, where 1024 made every live tile a whole masked one): 24
    forward steps a head, 23 live; 12 tiles on the diagonal and 11 on the
    band's edge, none cut by both, 1.50 x the visible pairs forward and
    1.25 x backward where 1024-blocks computed 3.83 x."""
    x = jax.ShapeDtypeStruct((1, 2, 6144, 128), jnp.bfloat16)
    kt.reset_attribution()
    jax.eval_shape(lambda q, k, v: _op(TPU, q, k, v, window=512), x, x, x)
    got = kt.attribution()
    assert got["attention_band_grid"] == {
        "ops": 1, "steps": {"6144x512x512x512": [24, 23]}}
    (key, said), = got["attention_tile_classes"]["shapes"].items()
    assert key == "6144x512x512x512x128"
    assert said["tiles"] == {"whole": 0, "diag": 12, "edge": 11, "both": 0}
    assert (said["fwd_bodies"], said["bwd_bodies"]) == (4, 8)
    assert said["fwd_pairs"] / said["visible"] == pytest.approx(1.4999,
                                                                abs=1e-4)
    assert said["bwd_pairs"] / said["visible"] == pytest.approx(1.2499,
                                                                abs=1e-4)
    parent = pk.tile_class_stats(6144, 128, 1024, 1024, 512)
    assert parent["tiles"] == {"whole": 0, "diag": 0, "edge": 5, "both": 6}
    assert parent["fwd_pairs"] / parent["visible"] == pytest.approx(
        3.8258, abs=1e-4)
    kt.reset_attribution()


def test_window_grid_live_share_is_in_the_benchmark_by_name(monkeypatch):
    """BENCHMARK.json carries `window_grid_live_share` (found by name, not
    by position), its layer_metrics file names a reader that imports, and
    the reader answers None on a program that records no band grid (the
    parent commit's, or one whose windowed ops never took the kernel),
    87.5 from Trinity-Mini's record and 95.8 from Laguna-XS.2's (91.7
    before PR 66, in 1024-blocks)."""
    import importlib.util
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = [m for m in spec["per_layer"]
             if m["name"] == "window_grid_live_share"]
    assert len(entry) == 1
    assert entry[0]["workloads"] == ["trinity_mini_train",
                                     "laguna_xs2_33b_a3b_train"]
    assert (entry[0]["unit"], entry[0]["better"], entry[0]["moves"]) == (
        "%", "higher", "train_mfu")
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "window_grid_live_share.json")) as f:
        how = json.load(f)
    path = os.path.join(ROOT, "benchmark", "readers", how["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("band_grid_stat", path)
    reader = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(reader)
    ctx = {"log": lambda msg: None}
    kt.reset_attribution()
    assert reader.read(ctx, **how.get("args", {})) is None
    before = kt.attribution()
    monkeypatch.setattr(kt, "attribution", lambda: {
        k: v for k, v in before.items() if k != "attention_band_grid"})
    assert reader.read(ctx) is None  # a program from before the counter
    monkeypatch.undo()
    kt.note_band_grid(8192, 2048, 1024, 1024, 24, 21)
    assert reader.read(ctx) == 87.5
    kt.reset_attribution()
    # Laguna-XS.2's: a 512 window in the 512-blocks the rule answers (PR 66;
    # half a 1024-block before: 12 steps, 11 live): a walk is two blocks
    # wide and only the sequence's first block repeats one
    blk = nn_ops._flash_block(6144, 512)
    assert (blk, pk.band_grid_steps(6144, blk, blk, 512)) == (512, (24, 23))
    kt.note_band_grid(6144, 512, blk, blk, 24, 23)
    assert reader.read(ctx) == pytest.approx(100.0 * 23 / 24)
    kt.reset_attribution()


# --------------------------------------------------------------------------
# layout "bthd" (PR 63): the one-tile form reads [B, T, H, d] in place
# --------------------------------------------------------------------------
def _taken_bthd(ctx, t, d, tk=None, **extra):
    """`_taken` for a "bthd" op on [2, T, 2, d]: "in_place" where the
    in-place counter ticked beside the one-tile form's."""
    tk = tk or t
    q = jax.ShapeDtypeStruct((2, t, 2, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, tk, 2, d), jnp.bfloat16)
    ins = {"QStart": jax.ShapeDtypeStruct((1,), jnp.int32),
           "SegmentIds": jax.ShapeDtypeStruct((2, t), jnp.int32),
           "Bias": jax.ShapeDtypeStruct((2, tk), jnp.float32)}
    ins = {slot: ins[slot] for slot in extra.pop("slots", ())}
    hits = lambda: dict(kt.attribution()["pallas_hits"])  # noqa: E731
    before = hits()
    out = jax.eval_shape(
        lambda q, k, v, ins: nn_ops._fused_attention(
            ctx, dict({"Q": [q], "K": [k], "V": [v]},
                      **{s: [a] for s, a in ins.items()}),
            dict({"causal": True, "layout": "bthd"}, **extra))["Out"][0],
        q, kv, kv, ins)
    assert out.shape == q.shape and out.dtype == q.dtype
    more = {f: n - before.get(f, 0) for f, n in hits().items()
            if n != before.get(f, 0)}
    if not more:
        return "dense"
    assert more.pop("attention") == 1
    if more == {"attention_short": 1, "attention_short_in_place": 1}:
        return "in_place"
    assert "attention_short_in_place" not in more
    if more == {"attention_short": 1}:
        return "one_tile"
    assert "attention_short" not in more
    return "blockwise"


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [56, 64, 128, 256, 384, 512])
def test_a_bthd_op_reads_in_place_where_the_sweep_has_it_ahead(t, d):
    """TPU-placed, layout "bthd": in place at the two Transformer-base
    cells' (T, head width), the pairs `tools/attention_sweep.py --short
    --in-place` has a row for; at every other shape the op transposes in its
    lowering and takes what the "bhtd" op takes (the one-tile form behind
    its own transposes, the blockwise kernel, dense); dense on the CPU."""
    want = ("in_place" if (t, d) in ((64, 64), (256, 64))
            else _taken(TPU, t, d))
    assert _taken_bthd(TPU, t, d) == want
    assert _taken_bthd(LowerCtx(platform="cpu"), t, d) == "dense"


@pytest.mark.parametrize("what,kwargs,want", [
    ("the key-padding bias rides it", {"slots": ("Bias",)}, "in_place"),
    ("not causal", {"causal": False}, "in_place"),
    ("a window", {"window": 32}, "dense"),
    ("segment ids", {"slots": ("SegmentIds",)}, "dense"),
    ("QStart (cached decode)", {"slots": ("QStart",)}, "dense"),
    ("cross-attention over another length", {"tk": 128, "causal": False},
     "dense"),
], ids=lambda x: x if isinstance(x, str) else None)
def test_what_keeps_a_bthd_op_off_the_in_place_form(what, kwargs, want):
    assert _taken_bthd(TPU, 64, 64, **dict(kwargs)) == want, what


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_under_a_live_mesh_a_bthd_op_transposes_into_the_mesh_paths():
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        spmd_lowering, train_partition_rules_for)

    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    with spmd_lowering(mesh, train_partition_rules_for("gpt2")):
        assert _taken_bthd(TPU, 64, 64) == "dense"
        assert _taken_bthd(TPU, 512, 64) == "blockwise"
    assert _taken_bthd(TPU, 64, 64) == "in_place"


@pytest.mark.parametrize("t,causal", [(64, True), (64, False), (256, True)])
def test_in_place_op_matches_the_dense_bhtd_op_forward_and_gradients(
        t, causal):
    """A "bthd" op placed on a TPU (the in-place kernel, interpreted here)
    against the "bhtd" op placed on the CPU (dense) on the transposed
    operands, the key-padding bias [B, T] shared by the heads: the result
    and dq / dk / dv, bfloat16."""
    heads = (0, 2, 1, 3)
    q, k, v = (jnp.transpose(x, heads) for x in _qkv(2, 4, t, 64, seed=t))
    bias = jnp.where(jnp.arange(t)[None, :] < jnp.array([[t - 9], [t]]),
                     0.0, -1e9).astype(jnp.float32)

    def in_place(q, k, v):
        return nn_ops._fused_attention(
            TPU, {"Q": [q], "K": [k], "V": [v], "Bias": [bias]},
            {"causal": causal, "layout": "bthd"})["Out"][0]

    def dense(q, k, v):
        ins = {s: [jnp.transpose(x, heads)]
               for s, x in (("Q", q), ("K", k), ("V", v))}
        return jnp.transpose(nn_ops._fused_attention(
            LowerCtx(platform="cpu"), dict(ins, Bias=[bias]),
            {"causal": causal})["Out"][0], heads)

    before = kt.attribution()["pallas_hits"].get(
        "attention_short_in_place", 0)
    got = jax.jit(in_place)(q, k, v)
    assert (kt.attribution()["pallas_hits"]["attention_short_in_place"]
            == before + 1)
    assert got.shape == (2, t, 4, 64) and got.dtype == jnp.bfloat16
    _close(got, dense(q, k, v))
    grads = jax.jit(jax.grad(_loss(in_place), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(_loss(dense), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(grads, want):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape
        _close(g, r)


@pytest.mark.parametrize("b,t,h,d,bias,causal", [
    (512, 64, 8, 64, True, True), (512, 64, 8, 64, True, False),
    (512, 64, 8, 64, False, True), (128, 256, 8, 64, True, True),
    (128, 256, 8, 64, True, False)])
def test_in_place_kernel_cross_lowers_for_the_tpu_with_no_copy_around_it(
        monkeypatch, b, t, h, d, bias, causal):
    """At the two Transformer-base cells' attention shapes, from the
    projections' [B, T, H d] reshaped as the Program reshapes it and back:
    two Mosaic calls (forward, backward) and NOT ONE transpose in the
    lowered text, where the "bhtd" op between its four `transpose2` holds
    eight and the one-tile form's own on top."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16)
    kb = jax.ShapeDtypeStruct((b, t), jnp.float32)

    def op(q, k, v, kb):
        ins = {s: [a.reshape(b, t, h, d)]
               for s, a in (("Q", q), ("K", k), ("V", v))}
        if bias:
            ins["Bias"] = [kb]
        return nn_ops._fused_attention(
            TPU, ins, {"causal": causal, "layout": "bthd"})["Out"][
                0].reshape(b, t, h * d)

    text = jax.jit(jax.grad(
        lambda q, k, v, kb: _loss(lambda *a: op(*a, kb))(q, k, v),
        argnums=(0, 1, 2))).trace(x, x, x, kb).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "stablehlo.transpose" not in text


def test_deep_bthd_program_traces_and_carries_the_in_place_kernel_once(
        monkeypatch):
    """The host-cost pin of the in-place form: a 4-layer training step
    whose attentions the layout pass folded traces each kernel body once
    and holds three Mosaic payloads for twelve call sites, as the one-tile
    form it replaces does (no second set beside it), and no transpose of the
    heads."""
    from paddle_tpu.core.trace import build_traced_function
    from paddle_tpu.transpiler.pass_registry import apply_pass

    n_layer, heads, d, t = 4, 2, 64, 64
    counts = {"fwd": 0, "bwd": 0}

    def counted(name, body):
        def wrapper(*a, **k):
            counts[name] += 1
            return body(*a, **k)
        return wrapper

    for name, body in (("fwd", "_inplace_fwd_kernel"),
                       ("bwd", "_inplace_bwd_kernel")):
        monkeypatch.setattr(pk, body, counted(name, getattr(pk, body)))
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.clear_caches()

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[t, heads * d], dtype="float32")
        h = x
        for _ in range(n_layer):
            qkv = [layers.transpose(layers.reshape(
                layers.fc(h, heads * d, num_flatten_dims=2),
                [-1, t, heads, d]), [0, 2, 1, 3]) for _ in range(3)]
            a = layers.fused_attention(*qkv, causal=True)
            h = h + layers.reshape(layers.transpose(a, [0, 2, 1, 3]),
                                   [-1, t, heads * d])
        loss = layers.mean(h)
        apply_pass(main, "attention_layout_fuse_pass")
        assert main._attention_layout_fused_count == n_layer
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    traced = build_traced_function(main, 0, ("x",), [loss.name], scope,
                                   platform="tpu")
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    args = ({"x": jax.ShapeDtypeStruct((2, t, heads * d), jnp.float32)},
            {n: sds(scope.find_var(n)) for n in traced.ro_names},
            {n: sds(scope.find_var(n)) for n in traced.rw_names},
            sds(jax.random.PRNGKey(0)))
    text = jax.jit(traced.fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert counts == {"fwd": 1, "bwd": 1}
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @_inplace_fwd_call") == 2 * n_layer
    assert text.count("call @_inplace_bwd_call") == n_layer
    assert "call @_short_" not in text
    assert "dims = [0, 2, 1, 3]" not in text  # the heads' transposes
