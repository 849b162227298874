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


@pytest.mark.parametrize("bh,t,d", [(4, 1024, 64), (2, 4096, 128)])
def test_kernel_cross_lowers_for_the_tpu_on_this_host(monkeypatch, bh, t, d):
    """At the cells' engaged head shapes, blocks as the lowering sets
    them: the Pallas -> Mosaic lowering and its block-spec checks, which
    interpret mode skips.  Two calls: one forward (the primal and the
    VJP's forward are one), one fused backward."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, bh, t, d), jnp.bfloat16)
    lowered = jax.jit(jax.grad(
        _loss(lambda q, k, v: _op(TPU, q, k, v)), argnums=(0, 1, 2))).trace(
            x, x, x).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 2


# (what it is, platform the step is placed on, Tq, Tk, head dim, engages)
CHOICE = [
    ("gpt2_345m_train", "tpu", 1024, 1024, 64, True),
    ("gpt2_345m_train_dp2mp2", "tpu", 1024, 1024, 64, True),
    ("olmoe_1b7b_train", "tpu", 4096, 4096, 128, True),
    ("tfm_base_train: T = 256 is under the threshold", "tpu", 256, 256, 64,
     False),
    ("tfm_base_train_s64: 64 is no multiple of 128", "tpu", 64, 64, 64,
     False),
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


def test_blocks_divide_the_length():
    assert [nn_ops._flash_block(t) for t in (512, 640, 1024, 1536, 4096, 16)
            ] == [512, 128, 1024, 512, 1024, 16]


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


def test_deep_program_traces_and_carries_each_kernel_once(monkeypatch):
    """The host-cost pin: a 4-layer causal training step lowered for the
    TPU traces each kernel body once, and its StableHLO holds a Mosaic
    payload per distinct entry, not per layer — twelve call sites share
    three functions: the forward op's (jit's dead-code pass prunes its
    unused lse output, so it is a jaxpr of its own), the grad op's
    re-traced forward and the backward.  (On the device the first two are
    one instruction: same operands, same payload.)"""
    from paddle_tpu.core.trace import build_traced_function

    n_layer, heads, t, d = 4, 2, 1024, 64
    counts = {"fwd": 0, "bwd": 0}

    def counted(name, body):
        def wrapper(*a, **k):
            counts[name] += 1
            return body(*a, **k)
        return wrapper

    monkeypatch.setattr(pk, "_flash_fwd_kernel",
                        counted("fwd", pk._flash_fwd_kernel))
    monkeypatch.setattr(pk, "_flash_bwd_fused_kernel",
                        counted("bwd", pk._flash_bwd_fused_kernel))
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
    before = kt.attribution()["pallas_hits"].get("attention", 0)
    text = jax.jit(traced.fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()

    assert counts == {"fwd": 1, "bwd": 1}
    assert text.count("tpu_custom_call") == 3
    assert text.count("call @_flash_fwd_call") == 2 * n_layer
    assert text.count("call @_flash_bwd_call") == n_layer
    assert text.count("func.func private @_flash") == 3
    # the counter still sees every engagement: forward and grad op a layer
    assert (kt.attribution()["pallas_hits"]["attention"] - before
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
