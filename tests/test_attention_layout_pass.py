"""attention_layout_fuse_pass (PR 63): the heads' transposes around a
`fused_attention` fold into the op, which then takes the projections'
[B, T, H, d] as they are written (layout "bthd").  What the pass matches
and what it leaves, that the rewritten op computes what the chain computed
(forward and gradients, through the op and through two train steps of the
Transformer-base program), and what the infer rule says of both layouts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.models import gpt2, olmoe, transformer
from paddle_tpu.ops import nn_ops
from paddle_tpu.transpiler import pass_registry
from paddle_tpu.transpiler.pass_registry import apply_pass

PASS = "attention_layout_fuse_pass"
HEADS = [0, 2, 1, 3]


class W(transformer.ModelHyperParams):
    """Transformer-base's program shape at its cells' rehearsal widths."""
    src_vocab_size = trg_vocab_size = 512
    max_length, d_model, d_inner_hid, n_head, n_layer = 64, 128, 256, 2, 6
    fused_attn = True


def _types(program):
    return [op.type for op in program.global_block().ops]


def _attentions(program):
    return [op for op in program.global_block().ops
            if op.type == "fused_attention"]


def test_the_transformer_builder_folds_every_attention():
    """Six encoder self-attentions, six decoder self-attentions and six
    cross-attentions: 18 ops, every one "bthd" on a reshape2's [B, T, H, d],
    its result read by a reshape2, and no `transpose2` left in the Program,
    forward or backward; the fused-op counters sum 18 higher."""
    main, _, _, _ = transformer.wmt_transformer_program(
        W, src_len=16, trg_len=16, use_bf16=True)
    assert main._attention_layout_fused_count == 18
    attns = _attentions(main)
    assert len(attns) == 18
    assert all(op.attrs["layout"] == "bthd" for op in attns)
    assert not [t for t in _types(main) if t.startswith("transpose2")]
    block = main.global_block()
    producers = {n: op for op in block.ops for n in op.output_arg_names()}
    for op in attns:
        for slot in ("Q", "K", "V"):
            src = op.inputs[slot][0]
            # under AMP a cast stands between the reshape and the op
            while producers[src].type == "cast":
                src = producers[src].inputs["X"][0]
            assert producers[src].type == "reshape2"
            assert len(block._find_var_recursive(src).shape) == 4
    grads = [op for op in block.ops if op.type == "fused_attention_grad"]
    assert len(grads) == 18
    assert all(op.attrs["__fwd_attrs__"]["layout"] == "bthd" for op in grads)


def _attention_program(second_reader=False, qstart=False, bias=True,
                       protect=False):
    """reshape -> transpose x 3 -> fused_attention -> transpose -> reshape,
    as `multi_head_attention` writes it, b 2, t 8, two heads of 4."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[8, 8], dtype="float32")
        kpad = layers.data("kpad", shape=[8], dtype="float32")

        def heads(name):
            y = layers.fc(x, size=8, num_flatten_dims=2, bias_attr=False,
                          param_attr=fluid.ParamAttr(name=name))
            return layers.transpose(layers.reshape(y, [-1, 8, 2, 4]), HEADS)

        q, k, v = heads("wq"), heads("wk"), heads("wv")
        extra = []
        if second_reader:  # the transposed keys are read once more
            extra.append(layers.reduce_sum(k))
        pos = None
        if qstart:
            pos = layers.fill_constant([1], "int32", 0)
        ctx = layers.fused_attention(
            q, k, v, causal=True, bias=None if qstart or not bias else kpad,
            qstart=pos)
        out = layers.reshape(layers.transpose(ctx, HEADS), [-1, 8, 8])
        loss = layers.reduce_sum(out)
        for e in extra:
            loss = layers.elementwise_add(loss, e)
    if protect:
        main._protected_fetch_names = {ctx.name}
    return main, startup, loss


@pytest.mark.parametrize("what,kwargs,folds", [
    ("the plain chain", {}, True),
    ("without a bias", {"bias": False}, True),
    ("a transposed value with a second reader", {"second_reader": True},
     False),
    ("a QStart op (cached decode)", {"qstart": True}, False),
    ("the op's own result is a protected fetch", {"protect": True}, False),
], ids=lambda x: x if isinstance(x, str) else None)
def test_the_pass_folds_exactly_the_chains_it_should(what, kwargs, folds):
    main, _, _ = _attention_program(**kwargs)
    before = _types(main)
    apply_pass(main, PASS)
    assert main._attention_layout_fused_count == int(folds), what
    if not folds:
        assert _types(main) == before
        assert "layout" not in _attentions(main)[0].attrs
        return
    assert _types(main).count("transpose2") == 0
    (op,) = _attentions(main)
    assert op.attrs["layout"] == "bthd" and op.attrs["causal"] is True
    block = main.global_block()
    assert [tuple(block._find_var_recursive(op.inputs[s][0]).shape[1:])
            for s in ("Q", "K", "V")] == [(8, 2, 4)] * 3
    assert tuple(block._find_var_recursive(
        op.outputs["Out"][0]).shape[1:]) == (8, 2, 4)
    # a second application finds nothing left to fold
    apply_pass(main, PASS)
    assert main._attention_layout_fused_count == 0


def test_a_folded_program_computes_what_the_chain_computed():
    """The small program above, trained two SGD steps with and without the
    pass from the same weights: losses and weights bit for bit (on the CPU
    a "bthd" op transposes inside its lowering and runs the chain's own
    dense code)."""
    def run(fold):
        main, startup, loss = _attention_program()
        with fluid.program_guard(main, startup):
            if fold:
                apply_pass(main, PASS)
            fluid.optimizer.SGD(0.1).minimize(loss)
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(2, 8, 8).astype("float32"),
                "kpad": np.where(np.arange(8)[None, :] < [[6], [8]], 0.0,
                                 -1e9).astype("float32")}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = [np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0])
                      for _ in range(2)]
            return losses, [np.array(scope.get(n))
                            for n in ("wq", "wk", "wv")]

    (l0, w0), (l1, w1) = run(False), run(True)
    np.testing.assert_array_equal(l0, l1)
    for a, b in zip(w0, w1):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("builder", ["gpt2", "olmoe"])
def test_no_other_builder_applies_the_pass(builder):
    """GPT-2's and the decoder builders' Programs keep their transposes and
    their "bhtd" ops: the pass is the Transformer builder's in this PR."""
    if builder == "gpt2":
        class HP(gpt2.GPT2Config):
            vocab_size, n_ctx, d_model, n_head, n_layer = 128, 32, 32, 2, 2
            fused_attn = True

        main = gpt2.gpt2_lm_program(HP, seq_len=16)[0]
    else:
        class HP(olmoe.OLMoEConfig):
            vocab_size, hidden_size, intermediate_size = 128, 32, 16
            num_hidden_layers, num_attention_heads = 2, 2
            num_key_value_heads, num_experts, num_experts_per_tok = 2, 4, 2

        main = olmoe.olmoe_lm_program(HP, seq_len=16)[0]
    assert not hasattr(main, "_attention_layout_fused_count")
    attns = _attentions(main)
    assert attns and all("layout" not in op.attrs for op in attns)
    assert _types(main).count("transpose2") >= 4 * len(attns)


def _qkv(b, t, h, d, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), jnp.float32).astype(dtype)
                 for k in keys)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal,bias,window", [
    (False, True, 0), (True, True, 0), (True, False, 0), (True, False, 4)])
def test_a_bthd_op_equals_the_bhtd_op_on_transposed_operands(
        dtype, causal, bias, window):
    """Through the op's lowering on the CPU, forward and dq / dk / dv: bit
    for bit, since a "bthd" op that takes no in-place kernel IS the "bhtd"
    op between two transposes."""
    cpu = LowerCtx(platform="cpu")
    q, k, v = _qkv(2, 16, 2, 8, dtype)
    kb = jnp.where(jnp.arange(16)[None, :] < jnp.array([[12], [16]]), 0.0,
                   -1e9).astype(jnp.float32)

    def op(layout):
        def run(q, k, v):
            ins = {"Q": [q], "K": [k], "V": [v]}
            if bias:
                ins["Bias"] = [kb]
            attrs = {"causal": causal, "window": window}
            if layout == "bthd":
                return nn_ops._fused_attention(
                    cpu, ins, dict(attrs, layout="bthd"))["Out"][0]
            ins.update({s: [jnp.transpose(ins[s][0], HEADS)]
                        for s in ("Q", "K", "V")})
            return jnp.transpose(
                nn_ops._fused_attention(cpu, ins, attrs)["Out"][0], HEADS)
        return run

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(o * jnp.cos(jnp.arange(
                o.size, dtype=jnp.float32)).reshape(o.shape))
        return f

    got, want = op("bthd")(q, k, v), op("bhtd")(q, k, v)
    assert got.shape == (2, 16, 2, 8) and got.dtype == q.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for g, r in zip(jax.grad(loss(op("bthd")), (0, 1, 2))(q, k, v),
                    jax.grad(loss(op("bhtd")), (0, 1, 2))(q, k, v)):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


def test_a_bthd_op_takes_v_of_another_width_and_a_qstart():
    """Latent attention's V of another width, and the cached-decode path: a
    "bthd" op transposes into them as into every path but the in-place
    kernel."""
    cpu = LowerCtx(platform="cpu")
    q, k, _ = _qkv(2, 8, 2, 12, "float32")
    v = _qkv(2, 8, 2, 4, "float32", seed=1)[0]

    def both(ins, attrs):
        t = {s: [jnp.transpose(a[0], HEADS)] if s in "QKV" else a
             for s, a in ins.items()}
        return (nn_ops._fused_attention(
            cpu, ins, dict(attrs, layout="bthd"))["Out"][0],
            jnp.transpose(nn_ops._fused_attention(cpu, t, attrs)["Out"][0],
                          HEADS))

    got, want = both({"Q": [q], "K": [k], "V": [v]}, {"causal": True})
    assert got.shape == (2, 8, 2, 4)
    np.testing.assert_array_equal(got, want)
    got, want = both({"Q": [q[:, :2]], "K": [k], "V": [k],
                      "QStart": [jnp.array([3], jnp.int32)]},
                     {"causal": True})
    assert got.shape == (2, 2, 2, 12)
    np.testing.assert_array_equal(got, want)


def _infer(layout, q, k, v):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        args = [layers.data(n, shape=list(s), dtype="float32",
                            append_batch_size=False)
                for n, s in (("q", q), ("k", k), ("v", v))]
        out = layers.fused_attention(*args, causal=True, layout=layout)
    from paddle_tpu.analysis import verify_program

    return out, [d for d in verify_program(main) if d.is_error]


@pytest.mark.parametrize("layout", ["bhtd", "bthd"])
def test_the_infer_rule_and_the_verifier_accept_both_layouts(layout):
    """Out is Q's shape at V's width in either layout; the default layout
    leaves no attribute on the op (every Program built before PR 63 is the
    Program it was)."""
    shape = (2, 4, 16, 8) if layout == "bhtd" else (2, 16, 4, 8)
    out, errors = _infer(layout, shape, shape, shape[:3] + (6,))
    assert tuple(out.shape) == shape[:3] + (6,)
    assert not errors
    (op,) = [o for o in out.block.ops if o.type == "fused_attention"]
    assert op.attrs.get("layout") == (None if layout == "bhtd" else "bthd")


@pytest.mark.parametrize("layout", ["bhtd", "bthd"])
@pytest.mark.parametrize("what,q,k", [
    ("a rank-3 query", (2, 16, 32), (2, 16, 4, 8)),
    ("another head width", (2, 16, 4, 8), (2, 16, 4, 16)),
])
def test_the_verifier_rejects_a_rank_or_a_head_width_mismatch(layout, what,
                                                              q, k):
    _, errors = _infer(layout, q, k, k)
    assert errors, what
    assert any("fused_attention" in d.message for d in errors)


def test_an_unknown_layout_is_refused_where_it_is_written():
    with pytest.raises(ValueError, match="layout"):
        _infer("hbtd", (2, 4, 16, 8), (2, 4, 16, 8), (2, 4, 16, 8))
    with pytest.raises(ValueError, match="layout"):
        q = jnp.zeros((1, 2, 4, 8))
        nn_ops._fused_attention(LowerCtx(platform="cpu"),
                                {"Q": [q], "K": [q], "V": [q]},
                                {"layout": "hbtd"})


def test_two_train_steps_with_and_without_the_pass_give_the_same_loss(
        monkeypatch):
    """The rehearsal-width `tfm_base` Program (6 + 6 layers, bfloat16 under
    the AMP pass), two steps from the same weights and the same batch: the
    losses agree to the cell's `reference_tolerance` (on the CPU they are
    the same bits).  Without dropout: a dropout op draws its mask under its
    own index in the block, which the 72 transposes that go shift, so with
    it the two Programs train under different masks (6.5335 against 6.5697
    at the second step here), as any two builds that differ by an op do."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/tfm_base.json")) as f:
        tolerance = float(json.load(f)["rehearse"]["reference_tolerance"])

    def run(fold):
        if not fold:
            monkeypatch.setattr(
                pass_registry, "apply_pass",
                lambda program, name, *a, _apply=apply_pass, **kw: (
                    program if name == PASS else _apply(program, name, *a,
                                                        **kw)))
        class NoDropout(W):
            dropout = 0.0

        main, startup, _, fetches = transformer.wmt_transformer_program(
            NoDropout, src_len=16, trg_len=16, use_bf16=True)
        monkeypatch.undo()
        assert (getattr(main, "_attention_layout_fused_count", 0) == 18) \
            is fold
        batch = transformer.make_fake_batch(4, 16, 16, W)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [float(np.asarray(exe.run(
                main, feed=batch, fetch_list=fetches[:1])[0]))
                    for _ in range(2)]

    with_pass, without = run(True), run(False)
    assert np.isfinite(with_pass).all()
    np.testing.assert_allclose(with_pass, without, rtol=0, atol=tolerance)


def test_program_flops_count_a_bthd_op_as_the_chain_it_replaced():
    """utils.flops.program_flops reads B, H, Tq, d and Tk off the op's
    operands by its layout: the folded Program counts what the chain
    counted, forward and backward (T = 8 against H = 2 here: read the other
    way round the count would be a quarter)."""
    from paddle_tpu.utils.flops import program_flops

    counts = []
    for fold in (False, True):
        main, startup, loss = _attention_program()
        with fluid.program_guard(main, startup):
            if fold:
                apply_pass(main, PASS)
            fluid.optimizer.SGD(0.1).minimize(loss)
        counts.append(program_flops(main, batch_hint=4))
    assert counts[0] == counts[1] > 0
    attention = 3.0 * 2.0 * 4 * 2 * 8 * 8 * (4 + 4)
    projections = 3.0 * 3 * 2.0 * 4 * 8 * 8 * 8
    assert counts[1] == attention + projections
