"""moe_ffn: routed SwiGLU experts (top-k, dropless) against the
loop-over-experts float32 references of models/olmoe_reference.py (softmax
router) and models/lfm2_reference.py (sigmoid router with a selection
bias): forward, every gradient, the experts chosen, and the router
statistics, with a balanced router, with one so biased that one expert
takes over half the rows and one takes none, and with the op holding only
a chip's share of the experts its router chooses among."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.models import lfm2_reference, olmoe_reference as ref

N, D, F, E, K = 48, 16, 8, 8, 2
CFG = {"num_experts": E, "num_experts_per_tok": K}


def _weights(router):
    rng = np.random.RandomState(11)
    w = {
        "x": rng.randn(N, D).astype("float32"),
        "router": (rng.randn(D, E) * 0.3).astype("float32"),
        "gate_up": (rng.randn(E, D, 2 * F) * 0.3).astype("float32"),
        "down": (rng.randn(E, F, D) * 0.3).astype("float32"),
        # a fixed random weighting of Y so that the loss is no constant
        "mix": rng.uniform(0.5, 1.5, (N, D)).astype("float32"),
    }
    if router == "skewed":
        # every token leans the same way: x gets a large common component
        # along which expert 0's router column is large and the last
        # expert's very negative.  Expert 0 is then in every token's top-2
        # (all N tokens, which is half of the N k rows: the most one
        # expert can take), and the last expert in none
        w["x"][:, 0] = 4.0
        w["router"][0] = 0.0
        w["router"][0, 0] = 5.0
        w["router"][0, E - 1] = -20.0
    return w


# case -> (weights, norm_topk_prob, router, expert bias, held range
# (offset, count)).  The first three are OLMoE's.
E_ALL = (0, E)
CASE = {
    "balanced": ("balanced", False, "softmax", False, E_ALL),
    "balanced_norm": ("balanced", True, "softmax", False, E_ALL),
    "skewed": ("skewed", False, "softmax", False, E_ALL),
    "softmax_share": ("balanced", True, "softmax", False, (0, 4)),
    "sigmoid": ("balanced", False, "sigmoid", False, E_ALL),
    "sigmoid_norm_bias": ("balanced", True, "sigmoid", True, E_ALL),
    "sigmoid_share": ("balanced", True, "sigmoid", True, (4, 2)),
    "sigmoid_share_skewed": ("skewed", True, "sigmoid", True, (1, 2)),
    "sigmoid_share_no_rows": ("skewed", True, "sigmoid", True, (6, 2)),
}
CASES = list(CASE)
NAMES = ("x", "router", "gate_up", "down")


def _bias():
    """Large against the spread of the sigmoid scores, so that it changes
    which experts are chosen."""
    return np.random.RandomState(5).randn(E).astype("float32") * 0.5


def _reference(case):
    kind, norm, router, biased, (offset, held) = CASE[case]
    w = _weights(kind)
    cfg = dict(CFG, norm_topk_prob=norm, expert_offset=offset)
    # an expert the op does not hold adds nothing: for the softmax
    # reference, which knows no share, its weights are zero instead
    absent = np.ones((E, 1, 1), "float32")
    absent[offset:offset + held] = 0.0
    bias = jnp.asarray(_bias()) if biased else None
    sl = slice(offset, offset + held)

    def outputs(x, router_w, gate_up, down):
        if router == "sigmoid":
            y, top_e = lfm2_reference.moe(cfg, x, router_w, bias,
                                          gate_up[sl], down[sl])
            return y, 0.0, 0.0, top_e
        return ref.moe(cfg, x, router_w, gate_up * (1 - absent),
                       down * (1 - absent))

    def loss(*args):
        y, lb, z, _ = outputs(*args)
        return (y * w["mix"]).sum() + 0.5 * lb + 0.25 * z

    args = [jnp.asarray(w[k]) for k in NAMES]
    with jax.default_matmul_precision("highest"):
        y, lb, z, top_e = outputs(*args)
        grads = dict(zip(NAMES, jax.grad(loss, argnums=(0, 1, 2, 3))(*args)))
    grads["gate_up"], grads["down"] = grads["gate_up"][sl], grads["down"][sl]
    return {"y": y, "aux": jnp.stack([lb, z]).astype(jnp.float32),
            "top_e": top_e, "grads": grads}


@functools.lru_cache(maxsize=None)
def _run(case):
    """One program per case: Y, the statistics and all four gradients."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.initializer import NumpyArrayInitializer
    from paddle_tpu.param_attr import ParamAttr

    kind, norm, router, biased, (offset, held) = CASE[case]
    w = _weights(kind)
    init = dict(w, bias=_bias(),
                gate_up=w["gate_up"][offset:offset + held],
                down=w["down"][offset:offset + held])
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[N, D], append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=[N, D], append_batch_size=False)

        def attr(name):
            return ParamAttr(name=name,
                             initializer=NumpyArrayInitializer(init[name]))

        y, aux, counts = layers.moe_ffn(
            x, E, F, K, norm_topk_prob=norm,
            router_attr=attr("router"), gate_up_attr=attr("gate_up"),
            down_attr=attr("down"), router=router,
            expert_bias_attr=attr("bias") if biased else None,
            num_local_experts=held, expert_offset=offset)
        coef = layers.assign(np.array([0.5, 0.25], "float32"))
        coef.stop_gradient = True
        loss = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(y, mix)),
            layers.reduce_sum(layers.elementwise_mul(aux, coef)))
        fluid.backward.append_backward(loss)
    names = main._grad_names
    assert "bias" not in names  # a buffer: no gradient is built for it
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(
            main, feed={"x": w["x"], "mix": w["mix"]},
            fetch_list=[y, aux, counts] + [names[n] for n in NAMES])
    diags = analysis.verify_program(main, fetches=[loss])
    return {"y": out[0], "aux": out[1], "counts": out[2],
            "grads": dict(zip(NAMES, out[3:])),
            "errors": [d for d in diags if d.is_error]}, _reference(case)


@pytest.mark.parametrize("case", CASES)
def test_forward_matches_the_loop_over_experts(case):
    got, want = _run(case)
    np.testing.assert_allclose(got["y"], want["y"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got["aux"], want["aux"], rtol=1e-5)
    assert not got["errors"]
    if CASE[case][2] == "sigmoid":  # no auxiliary loss
        np.testing.assert_array_equal(got["aux"], [0.0, 0.0])


@pytest.mark.parametrize("wrt", NAMES)
@pytest.mark.parametrize("case", CASES)
def test_gradient_matches_the_loop_over_experts(case, wrt):
    """With a share held, the reference leaves out what the absent experts
    would add, and so must every gradient: the router's too."""
    got, want = _run(case)
    assert got["grads"][wrt].shape == want["grads"][wrt].shape
    np.testing.assert_allclose(got["grads"][wrt], want["grads"][wrt],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_no_routing_decision_is_dropped_and_choices_are_the_references(case):
    """TokensPerExpert counts exactly the reference's top-k choices over
    ALL the router's experts, held here or not, and sums to N k: dropless
    under any imbalance, an empty expert is legal."""
    got, want = _run(case)
    counts = np.asarray(got["counts"])
    assert counts.dtype == np.int32 and counts.sum() == N * K
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(want["top_e"]).reshape(-1),
                            minlength=E))
    if case == "skewed":
        assert counts[0] == N  # every token, far over half of them
        assert counts[E - 1] == 0
    if case == "sigmoid_share_skewed":
        # one held group is empty, the other takes every token
        assert counts[1] == 0 and counts[2] == N
    if case == "sigmoid_share_no_rows":
        # no row at all is live here: the result and every gradient of
        # the held experts are zero, which the reference agrees with
        assert counts[6:8].sum() == 0 and not np.asarray(got["y"]).any()


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """Four ops holding experts 0-1, 2-3, 4-5, 6-7 of the same layer: their
    outputs sum to what the op holding all eight gives (the model-level
    share test against the uncut reference is in test_lfm2_model.py)."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    w = _weights("balanced")

    def run(offset, held):
        return moe_ops._moe_ffn(LowerCtx(platform="cpu"), {
            "X": [jnp.asarray(w["x"])], "RouterW": [jnp.asarray(w["router"])],
            "ExpertBias": [jnp.asarray(_bias())],
            "GateUpW": [jnp.asarray(w["gate_up"][offset:offset + held])],
            "DownW": [jnp.asarray(w["down"][offset:offset + held])]},
            {"top_k": K, "router": "sigmoid", "norm_topk_prob": True,
             "expert_offset": offset})

    whole = run(0, E)
    parts = [run(o, 2) for o in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(p["Y"][0] for p in parts), whole["Y"][0],
                               rtol=1e-5, atol=1e-6)
    for p in parts:
        np.testing.assert_array_equal(p["TokensPerExpert"][0],
                                      whole["TokensPerExpert"][0])


def _scores(x, router_w):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(jnp.asarray(x) @ jnp.asarray(router_w))


def test_the_bias_selects_and_the_unbiased_score_weighs():
    """Experts are the top-k of s + b; a weight's numerator is s.  A bias
    that changes the chosen set changes no numerator."""
    from paddle_tpu.ops import moe_ops

    w = _weights("balanced")
    x, wr, bias = (jnp.asarray(w["x"]), jnp.asarray(w["router"]),
                   jnp.asarray(_bias()))
    s = _scores(x, wr)
    plain_p, plain_e, _, _ = moe_ops.route_sigmoid(x, wr, None, K, False)
    top_p, top_e, counts, aux = moe_ops.route_sigmoid(x, wr, bias, K, False)
    assert (np.sort(top_e, -1) != np.sort(plain_e, -1)).any()
    _, want_e = jax.lax.top_k(s + bias, K)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_allclose(top_p, jnp.take_along_axis(s, top_e, -1),
                               rtol=1e-6)
    np.testing.assert_allclose(plain_p, jax.lax.top_k(s, K)[0], rtol=1e-6)
    assert counts.sum() == N * K and not np.asarray(aux).any()


def test_sigmoid_weights_are_renormalised_with_the_published_epsilon():
    """p = s / (sum of the chosen s + 1e-6): with scores of about 6e-6
    the epsilon is a twelfth of the sum, and the weights sum to well
    under one."""
    from paddle_tpu.ops import moe_ops

    w = _weights("balanced")
    x = jnp.asarray(w["x"])
    wr = jnp.asarray(w["router"]) * 1e-3
    x = x.at[:, 0].set(1.0)
    wr = wr.at[0].set(-12.0)  # every logit about -12
    s = _scores(x, wr)
    top_p, top_e, _, _ = moe_ops.route_sigmoid(x, wr, None, K, True)
    chosen = jnp.take_along_axis(s, top_e, -1)
    total = chosen.sum(-1, keepdims=True)
    assert float(total.max()) < 2e-5
    np.testing.assert_allclose(top_p, chosen / (total + 1e-6), rtol=1e-5)
    assert float(top_p.sum(-1).max()) < 0.95


def _prims(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    _prims(sub.jaxpr, out)
                elif hasattr(sub, "eqns"):
                    _prims(sub, out)
    return out


@pytest.mark.parametrize("norm, n_prims, sha", [
    (False, 88, "c539e7a1474d8748"), (True, 91, "81bf8274ff1c3b18")])
def test_olmoes_attributes_lower_to_the_jaxpr_they_did(norm, n_prims, sha):
    """The op learnt a second router and a share by attributes whose
    defaults are OLMoE's: at OLMoE's attributes the lowering is primitive
    for primitive what PR 25's was (count and digest of the primitive
    sequence taken from the commit before the op learnt them), and saying
    the defaults changes nothing."""
    import hashlib

    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    def lowered(**more):
        def f(x, wr, wgu, wd):
            out = moe_ops._moe_ffn(
                LowerCtx(platform="cpu"),
                {"X": [x], "RouterW": [wr], "GateUpW": [wgu], "DownW": [wd]},
                dict({"top_k": K, "norm_topk_prob": norm}, **more))
            return out["Y"][0], out["TokensPerExpert"][0], out["AuxLoss"][0]

        return jax.make_jaxpr(f)(*(
            jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
                (N, D), (D, E), (E, D, 2 * F), (E, F, D))))

    unsaid = lowered()
    prims = _prims(unsaid.jaxpr, [])
    assert len(prims) == n_prims
    assert hashlib.sha256(" ".join(prims).encode()).hexdigest()[:16] == sha
    assert str(unsaid) == str(lowered(router="softmax", expert_offset=0))


def _infer(x, wr, wgu, wd, top_k=K, bias=None, **attrs):
    class Op:
        pass

    Op.attrs = dict({"top_k": top_k}, **attrs)
    ins = {"X": [VarInfo(x, "float32")], "RouterW": [VarInfo(wr, "float32")],
           "GateUpW": [VarInfo(wgu, "bfloat16")],
           "DownW": [VarInfo(wd, "bfloat16")]}
    if bias is not None:
        ins["ExpertBias"] = [VarInfo(bias, "float32")]
    return get_infer_rule("moe_ffn").fn(Op, ins)


def test_infer_rule_gives_the_three_outputs():
    out = _infer((-1, 32, D), (D, E), (E, D, 2 * F), (E, F, D))
    assert out["Y"][0].shape == (-1, 32, D)
    assert out["Y"][0].dtype == "bfloat16"  # the experts' dtype
    assert (out["TokensPerExpert"][0].shape,
            out["TokensPerExpert"][0].dtype) == ((E,), "int32")
    assert (out["AuxLoss"][0].shape, out["AuxLoss"][0].dtype) == (
        (2,), "float32")


@pytest.mark.parametrize("shapes, message", [
    (((4, D), (D, E), (E, D, F), (E, F, D)), "expert weights disagree"),
    (((4, D), (D, E), (E, D, 2 * F), (E + 1, F, D)),
     "expert weights disagree"),
    (((4, D + 1), (D, E), (E, D, 2 * F), (E, F, D)), "hidden-dim mismatch"),
])
def test_infer_rule_refuses_inconsistent_edges(shapes, message):
    with pytest.raises(InferError, match=message):
        _infer(*shapes)


def test_infer_rule_refuses_more_choices_than_experts():
    with pytest.raises(InferError, match="exceeds"):
        _infer((4, D), (D, E), (E, D, 2 * F), (E, F, D), top_k=E + 1)


def test_infer_rule_takes_a_share_and_keeps_the_routers_width():
    out = _infer((-1, 32, D), (D, E), (2, D, 2 * F), (2, F, D),
                 bias=(E,), router="sigmoid", expert_offset=6)
    assert out["Y"][0].shape == (-1, 32, D)
    assert out["TokensPerExpert"][0].shape == (E,)


@pytest.mark.parametrize("kwargs, message", [
    (dict(expert_offset=7), "holds experts"),
    (dict(bias=(E + 1,)), "ExpertBias"),
    (dict(router="tanh"), "neither softmax nor sigmoid"),
])
def test_infer_rule_refuses_a_share_or_router_that_cannot_be(kwargs, message):
    with pytest.raises(InferError, match=message):
        _infer((4, D), (D, E), (2, D, 2 * F), (2, F, D), **kwargs)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_bf16_experts_keep_a_float32_router(router):
    """Under the AMP pass the op reads X, RouterW and the expert bias in
    f32 and the experts' weights in bf16: the experts chosen are those of
    the float32 router, whatever the experts' own precision, and the
    counts stay int32 with no cast-back."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.param_attr import ParamAttr
    from paddle_tpu.transpiler.pass_registry import apply_pass

    w = _weights("balanced")
    sigmoid = router == "sigmoid"
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[N, D], append_batch_size=False)
        y, aux, counts = layers.moe_ffn(
            x, E, F, K, router=router,
            expert_bias_attr=ParamAttr(name="bias") if sigmoid else None)
        apply_pass(main, "bf16_amp_pass")
    (op,) = [o for o in main.global_block().ops if o.type == "moe_ffn"]
    block = main.global_block()
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == dict({"X": "float32", "RouterW": "float32",
                           "GateUpW": "bfloat16", "DownW": "bfloat16",
                           "Y": "bfloat16", "TokensPerExpert": "int32",
                           "AuxLoss": "float32"},
                          **({"ExpertBias": "float32"} if sigmoid else {}))
    assert op.outputs["TokensPerExpert"] == [counts.name]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        router_w = np.asarray(scope.find_var(op.inputs["RouterW"][0]))
        bias = np.asarray(scope.find_var("bias")) if sigmoid else 0.0
        got_y, got = exe.run(main, feed={"x": w["x"]},
                             fetch_list=[y, counts])
    assert got_y.dtype == np.float32  # the cast-back restores the name
    with jax.default_matmul_precision("highest"):
        logits = jnp.asarray(w["x"]) @ jnp.asarray(router_w)
        _, top_e = jax.lax.top_k(
            jax.nn.sigmoid(logits) + bias if sigmoid
            else jax.nn.softmax(logits, -1), K)
    np.testing.assert_array_equal(
        got, np.bincount(np.asarray(top_e).reshape(-1), minlength=E))


@pytest.mark.parametrize("counts, step", [
    ([8, 8, 8, 8], [0.0, 0.0, 0.0, 0.0]),      # even load: nothing moves
    ([16, 8, 8, 0], [-0.1, 0.0, 0.0, 0.1]),    # twice the mean, never chosen
    ([32, 0, 0, 0], [-0.3, 0.1, 0.1, 0.1]),    # one expert has every row
])
def test_expert_bias_update_moves_a_bias_against_its_experts_load(counts,
                                                                  step):
    """b += 0.1 * (1 - c / mean(c)), in float32, for any share held."""
    from paddle_tpu.core.registry import LowerCtx, get_op

    bias = jnp.asarray([0.05, -0.1, 0.0, 0.2], jnp.float32)
    out = get_op("expert_bias_update").lower(
        LowerCtx(), {"ExpertBias": [bias],
                     "TokensPerExpert": [jnp.asarray(counts, jnp.int32)]},
        {})["ExpertBiasOut"][0]
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out - bias), step, atol=1e-7)


@pytest.mark.parametrize("attrs, step", [
    ({"rate": 0.03}, [-0.09, 0.03, 0.03, 0.03]),   # 0.03 x (1 - 32 / 8)
    ({"rate": 0.03, "max_step": 0.03}, [-0.03, 0.03, 0.03, 0.03]),
    ({"max_step": 0.05}, [-0.05, 0.05, 0.05, 0.05]),  # the default rate
    ({"rate": 0.03, "max_step": None}, [-0.09, 0.03, 0.03, 0.03]),
])
def test_expert_bias_update_takes_a_rate_and_a_bound(attrs, step):
    """`rate` scales the proportional step, `max_step` bounds it to
    [-max_step, +max_step] (an expert that took every token comes down by
    the bound, not by E / k rates); float32 for any share held."""
    from paddle_tpu.core.registry import LowerCtx, get_op

    bias = jnp.asarray([0.05, -0.1, 0.0, 0.2], jnp.float32)
    out = get_op("expert_bias_update").lower(
        LowerCtx(), {"ExpertBias": [bias],
                     "TokensPerExpert": [jnp.asarray([32, 0, 0, 0],
                                                     jnp.int32)]},
        attrs)["ExpertBiasOut"][0]
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out - bias), step, atol=1e-7)


def test_expert_bias_update_without_attributes_is_what_it_was():
    """The op LFM2's and kanana-2's programs carry has no attribute: its
    lowering is bit-equal to b + 0.1 * (1 - c / mean(c)) as it was
    written before the attributes came back, and its jaxpr has no clamp."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    rng = np.random.RandomState(3)
    bias = jnp.asarray(rng.randn(128).astype("float32") * 0.1)
    counts = jnp.asarray(rng.randint(0, 900, 128).astype("int32"))

    def lowered(b, c):
        return moe_ops._expert_bias_update(
            LowerCtx(platform="cpu"),
            {"ExpertBias": [b], "TokensPerExpert": [c]}, {})[
                "ExpertBiasOut"][0]

    def before(b, c):
        load = c.astype(jnp.float32)
        return b + (0.1 * (1.0 - load / load.mean())).astype(b.dtype)

    np.testing.assert_array_equal(np.asarray(jax.jit(lowered)(bias, counts)),
                                  np.asarray(jax.jit(before)(bias, counts)))
    assert str(jax.make_jaxpr(lowered)(bias, counts)) == str(
        jax.make_jaxpr(before)(bias, counts))
    assert moe_ops.EXPERT_BIAS_RATE == 0.1


def test_expert_bias_update_infer_rule():
    class Op:
        attrs = {}

    rule = get_infer_rule("expert_bias_update").fn
    bias = VarInfo((E,), "float32")
    out = rule(Op, {"ExpertBias": [bias],
                    "TokensPerExpert": [VarInfo((E,), "int32")]})
    assert out["ExpertBiasOut"][0] is bias
    with pytest.raises(InferError, match="differ"):
        rule(Op, {"ExpertBias": [bias],
                  "TokensPerExpert": [VarInfo((E + 1,), "int32")]})


# --- routed_scaling_factor, norm_topk_eps (DeepSeek-V3's router) ---
def _noaux_tc(attrs, offset=4, held=2, scale_router=1.0):
    """moe_ffn's lowering as kanana2 says its attributes, and
    models/kanana2_reference.routed, on one share: (y, grads), (y, grads)
    over (x, router, gate_up, down)."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.models import kanana2_reference
    from paddle_tpu.ops import moe_ops

    w = _weights("balanced")
    sl = slice(offset, offset + held)
    bias = jnp.asarray(_bias())
    args = [jnp.asarray(w["x"]), jnp.asarray(w["router"]) * scale_router,
            jnp.asarray(w["gate_up"][sl]), jnp.asarray(w["down"][sl])]
    mix = jnp.asarray(w["mix"])

    def op(x, wr, wgu, wd):
        return moe_ops._moe_ffn(
            LowerCtx(platform="cpu"),
            {"X": [x], "RouterW": [wr], "ExpertBias": [bias],
             "GateUpW": [wgu], "DownW": [wd]},
            dict({"top_k": K, "norm_topk_prob": True, "router": "sigmoid",
                  "expert_offset": offset}, **attrs))["Y"][0]

    cfg = {"num_experts_per_tok": K, "expert_offset": offset,
           "norm_topk_prob": True,
           "routed_scaling_factor": attrs.get("routed_scaling_factor", 1.0)}

    def reference(x, wr, wgu, wd):
        return kanana2_reference.routed(cfg, x, wr, bias, wgu, wd)[0]

    out = []
    with jax.default_matmul_precision("highest"):
        for fn in (op, reference):
            y, grads = jax.value_and_grad(
                lambda *a: (fn(*a) * mix).sum(), argnums=(0, 1, 2, 3))(*args)
            out.append((fn(*args), y, grads))
    return out


def test_the_scaling_factor_and_the_families_epsilon_match_its_reference():
    """routed_scaling_factor 2.448 and norm_topk_eps 1e-20 on a chip's
    share, forward and every gradient, against the float32 loop over
    experts of models/kanana2_reference.py; and the factor is a factor."""
    attrs = {"routed_scaling_factor": 2.448, "norm_topk_eps": 1e-20}
    (y, loss, grads), (want_y, want_loss, want_grads) = _noaux_tc(attrs)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max()
    (plain, _, _), _ = _noaux_tc({"norm_topk_eps": 1e-20})
    np.testing.assert_allclose(y, 2.448 * np.asarray(plain), rtol=1e-5,
                               atol=1e-6)


def test_the_two_epsilons_differ_where_the_chosen_scores_are_tiny():
    """With every logit about -12 the chosen scores sum to ~1e-5: 1e-6 is
    a tenth of that sum and the weights fall short of one; 1e-20 is
    nothing beside it and they add up to one.  A layer built with the
    other family's epsilon is told from the reference there."""
    from paddle_tpu.ops import moe_ops

    w = _weights("balanced")
    x = jnp.asarray(w["x"]).at[:, 0].set(1.0)
    wr = (jnp.asarray(w["router"]) * 1e-3).at[0].set(-12.0)
    lfm2_p, top_e, _, _ = moe_ops.route_sigmoid(x, wr, None, K, True)
    v3_p, v3_e, _, _ = moe_ops.route_sigmoid(x, wr, None, K, True, 1e-20)
    np.testing.assert_array_equal(top_e, v3_e)
    np.testing.assert_allclose(v3_p.sum(-1), 1.0, rtol=1e-5)
    assert float(lfm2_p.sum(-1).max()) < 0.95
    chosen = jnp.take_along_axis(_scores(x, wr), top_e, -1)
    np.testing.assert_allclose(
        v3_p, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_the_new_attributes_at_their_defaults_lower_to_no_instruction(
        router):
    """routed_scaling_factor 1 and norm_topk_eps 1e-6, said or unsaid, are
    the jaxpr OLMoE's and LFM2's layers had: layers.moe_ffn now says both
    on every op it builds."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    def lowered(**more):
        def f(x, wr, b, wgu, wd):
            ins = {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                   "DownW": [wd]}
            if router == "sigmoid":
                ins["ExpertBias"] = [b]
            return moe_ops._moe_ffn(
                LowerCtx(platform="cpu"), ins,
                dict({"top_k": K, "norm_topk_prob": True, "router": router},
                     **more))["Y"][0]

        return str(jax.make_jaxpr(f)(*(
            jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
                (N, D), (D, E), (E,), (E, D, 2 * F), (E, F, D)))))

    unsaid = lowered()
    assert lowered(routed_scaling_factor=1.0, norm_topk_eps=1e-6) == unsaid
    assert lowered(routed_scaling_factor=2.448) != unsaid
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        layers.moe_ffn(layers.data("x", shape=[N, D],
                                   append_batch_size=False), E, F, K)
    (op,) = [o for o in main.global_block().ops if o.type == "moe_ffn"]
    assert (op.attrs["routed_scaling_factor"], op.attrs["norm_topk_eps"]
            ) == (1.0, 1e-6)


def test_bf16_rows_into_the_router_change_the_chosen_experts():
    """What the float32 router is for: the same rows rounded to bfloat16
    before the router choose other experts for some tokens (a top-k is
    discontinuous), and the layer's result moves by far more than
    rounding the result would."""
    from paddle_tpu.ops import moe_ops

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(16384, D).astype("float32"))
    wr = jnp.asarray(rng.randn(D, E).astype("float32"))
    bias = jnp.asarray(_bias())
    _, exact, _, _ = moe_ops.route_sigmoid(x, wr, bias, K, True, 1e-20)
    _, rounded, _, _ = moe_ops.route_sigmoid(
        x.astype(jnp.bfloat16).astype(jnp.float32),
        wr.astype(jnp.bfloat16).astype(jnp.float32), bias, K, True, 1e-20)
    flipped = (np.sort(exact, -1) != np.sort(rounded, -1)).any(-1)
    assert 0 < flipped.sum() < 0.2 * len(flipped)


@pytest.mark.parametrize("n_experts, top_k, want", [
    (32, 4, [-0.7, 0.1, 0.1]), (128, 6, [-2.0333333, 0.1, 0.1])])
def test_expert_bias_update_step_grows_with_the_routers_width(
        n_experts, top_k, want):
    """A router whose first top_k experts took every token: c / mean is
    E / k, so the proportional step is (1 - E / k) rates: -0.7 over 32
    with top-4, -2.03 over 128 with top-6, twice the range of a sigmoid
    score (what a wide router's collapse meets: PERF.md, PR 37).  An
    expert never chosen goes up by one rate whatever the width."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    counts = np.zeros(n_experts, "int32")
    counts[:top_k] = 6144
    out = moe_ops._expert_bias_update(
        LowerCtx(platform="cpu"),
        {"ExpertBias": [jnp.zeros(n_experts, jnp.float32)],
         "TokensPerExpert": [jnp.asarray(counts)]}, {})["ExpertBiasOut"][0]
    np.testing.assert_allclose(
        [out[0], out[top_k], out[n_experts - 1]], want, rtol=1e-5)


# --- a chip's share: the row work over the live chunks (PR 39) ---
# case -> (router, weights, (offset, held), rows a chunk, NaN planted)
ROWS = N * K
SHARE = {
    "softmax_router": ("softmax", "balanced", (0, 4), 16, False),
    "sigmoid_router": ("sigmoid", "balanced", (4, 2), 16, False),
    "offset_at_the_start": ("sigmoid", "balanced", (0, 2), 16, False),
    "offset_in_the_middle": ("sigmoid", "balanced", (3, 3), 16, False),
    "offset_at_the_end": ("softmax", "balanced", (6, 2), 16, False),
    "no_live_rows": ("sigmoid", "skewed", (6, 2), 16, False),
    "all_rows_live": ("softmax", "both_held", (0, 2), 16, False),
    "live_rows_no_multiple_of_a_chunk": ("softmax", "balanced", (2, 3), 32,
                                         False),
    "a_group_straddles_a_chunk": ("sigmoid", "balanced", (0, 3), 8, False),
    "nan_behind_the_live_rows": ("sigmoid", "balanced", (3, 3), 16, True),
}


def _share_weights(kind):
    w = _weights("balanced" if kind == "both_held" else kind)
    if kind == "both_held":
        # every token's two largest scores are experts 0 and 1
        w["x"][:, 0] = 4.0
        w["router"][0] = 0.0
        w["router"][0, :2] = [5.0, 4.5]
    return w


def _nan_behind(rows, n_live):
    return jnp.where((jnp.arange(rows.shape[0]) < n_live)[:, None], rows,
                     jnp.nan)


def _share_run(monkeypatch, case, chunk, plant=False):
    """Y, its gradients over (x, router, gate_up, down) and the counts, the
    share's row work in chunks of `chunk` rows."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    router, kind, (offset, held), _, _ = SHARE[case]
    w = _share_weights(kind)
    sl = slice(offset, offset + held)
    monkeypatch.setattr(moe_ops, "_chunk_rows", lambda m: chunk)
    if plant:
        # a kernel writes no row behind the last group: whatever memory
        # held stays there, forward and backward
        product, grads = moe_ops._product, moe_ops._product_grads
        monkeypatch.setattr(
            moe_ops, "_product", lambda kernel, lhs, rhs, sizes: _nan_behind(
                product(kernel, lhs, rhs, sizes), sizes.sum()))

        def planted_grads(kernel, lhs, rhs, sizes, g):
            d_lhs, d_rhs = grads(kernel, lhs, rhs, sizes, g)
            return _nan_behind(d_lhs, sizes.sum()), d_rhs

        monkeypatch.setattr(moe_ops, "_product_grads", planted_grads)
    mix = jnp.asarray(w["mix"])

    def outputs(x, wr, wgu, wd):
        out = moe_ops._moe_ffn(
            LowerCtx(platform="cpu"),
            {"X": [x], "RouterW": [wr], "GateUpW": [wgu], "DownW": [wd],
             "ExpertBias": [jnp.asarray(_bias())]},
            {"top_k": K, "router": router, "norm_topk_prob": True,
             "expert_offset": offset})
        y = out["Y"][0]
        return (y * mix).sum() + out["AuxLoss"][0].sum(), (
            y, out["TokensPerExpert"][0])

    args = [jnp.asarray(w["x"]), jnp.asarray(w["router"]),
            jnp.asarray(w["gate_up"][sl]), jnp.asarray(w["down"][sl])]
    with jax.default_matmul_precision("highest"):
        (_, (y, counts)), grads = jax.value_and_grad(
            outputs, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    return y, grads, np.asarray(counts)[sl]


@pytest.mark.parametrize("case", list(SHARE))
def test_a_shares_live_chunks_give_what_the_whole_buffer_gives(monkeypatch,
                                                              case):
    """Where the op holds a share its row work runs over the chunks that
    hold a live row: Y and all four gradients are those of one chunk as
    long as the buffers (C = N k), in float32, for both routers, a share
    at the start, in the middle and at the end of the experts, no live row
    at all, every row live, a live count that is no multiple of C, a group
    across a chunk's edge, and NaN in every row a kernel does not write."""
    _, _, _, chunk, plant = SHARE[case]
    want_y, want_grads, held_counts = _share_run(monkeypatch, case, ROWS)
    if plant:  # the layer is jitted: an unplanted trace would be found
        jax.clear_caches()
    got_y, got_grads, _ = _share_run(monkeypatch, case, chunk, plant)
    if plant:  # and a later test would find the planted one
        jax.clear_caches()
    n_live = int(held_counts.sum())
    edges = np.cumsum(held_counts)
    if case == "no_live_rows":
        assert n_live == 0 and not np.asarray(got_y).any()
    elif case == "all_rows_live":
        assert n_live == ROWS
    elif case == "live_rows_no_multiple_of_a_chunk":
        assert 0 < n_live % chunk
    elif case == "a_group_straddles_a_chunk":
        assert any(lo // chunk != (hi - 1) // chunk and lo % chunk
                   for lo, hi in zip(edges[:-1], edges[1:]))
    else:
        assert 0 < n_live < ROWS
    np.testing.assert_allclose(got_y, want_y, rtol=1e-6, atol=1e-6)
    for got, want in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_a_buffer_a_chunk_loop_fills_is_zero_behind_the_live_rows():
    """Every buffer a loop carries starts as zeros and is written in the
    live rows alone, whatever its sources hold behind them (NaN here, as
    a kernel's result may)."""
    from paddle_tpu.ops import moe_ops

    rng = np.random.RandomState(3)
    m, n_live, chunk = ROWS, 37, 16
    order = jnp.asarray(rng.permutation(m).astype("int32"))
    inv, tok = jnp.argsort(order), order // K
    x = jnp.asarray(rng.randn(N, D).astype("float32"))
    gu = _nan_behind(jnp.asarray(rng.randn(m, 2 * F).astype("float32")),
                     n_live)
    out = _nan_behind(jnp.asarray(rng.randn(m, D).astype("float32")), n_live)
    top_p = jnp.asarray(rng.rand(N, K).astype("float32"))
    rows = moe_ops._gather_live(x, tok, n_live, chunk=chunk)
    act = moe_ops._swiglu_live(gu, n_live, chunk=chunk)
    d_gu = moe_ops._swiglu_live_bwd(gu, _nan_behind(act, n_live), n_live,
                                    chunk=chunk)
    d_out, d_p = moe_ops._weigh_to_tokens_bwd(
        out, top_p, order, inv, x, n_live, k=K, chunk=chunk)
    np.testing.assert_array_equal(rows[:n_live], x[tok[:n_live]])
    np.testing.assert_allclose(act[:n_live], moe_ops._swiglu(gu[:n_live]),
                               rtol=1e-6)
    for buf in (rows, act, d_gu, d_out):
        assert np.isfinite(np.asarray(buf)).all()
        assert np.abs(np.asarray(buf[:n_live])).sum() > 0
        assert not np.asarray(buf[n_live:]).any()
    assert np.isfinite(np.asarray(d_p)).all()
    # a token's slot that is not live has no part in the weights' gradient
    assert not np.asarray(d_p)[np.asarray(inv).reshape(N, K) >= n_live].any()


@pytest.mark.parametrize("m, want", [
    (65536, 2048), (36864, 2048), (4096, 2048), (6144, 2048), (1536, 1536),
    (768, 768), (ROWS, ROWS), (1000, 1000)])
def test_rows_of_a_chunk_come_from_the_shape_alone(m, want):
    """A multiple of the kernels' 256-row tile that divides N k, the
    largest up to `_CHUNK_ROWS`; the whole buffer where there is none (the
    small shapes of these tests)."""
    from paddle_tpu.ops import moe_ops

    assert moe_ops._chunk_rows(m) == want
    assert m % want == 0


SHARE_WIDTHS = {"lfm2": (1792, 32, 8, 4), "kanana2": (768, 128, 16, 6)}


def _functions(text, name):
    """{function: its body} of the StableHLO's private functions that jit
    named `name` (a second jaxpr of one name gets a number behind it)."""
    import re

    return {m.group(1): text[m.start():text.index("\n  }\n", m.start())]
            for m in re.finditer(
                r"func\.func private @(%s(?:_\d+)?)\(" % name, text)}


@pytest.mark.parametrize("model", sorted(SHARE_WIDTHS))
def test_a_share_cross_lowers_for_the_tpu_with_six_kernels_a_layer(model):
    """The share path at LFM2's and kanana-2's expert widths (d = 2048),
    lowered for the TPU on this host: the six Mosaic calls a layer the
    whole-size lowering has (the chunk loops carry none), and every loop
    one shared function: forward the gather and the SwiGLU; backward the
    combine's transpose, the SwiGLU's, and the gather and the SwiGLU made
    again rather than kept."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    f, e, held, k = SHARE_WIDTHS[model]
    n, d = 1024, 2048
    on_chip = LowerCtx(platform="tpu")

    def loss(x, wr, wgu, wd):
        out = moe_ops._moe_ffn(
            on_chip, {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                      "DownW": [wd]},
            {"top_k": k, "router": "sigmoid", "norm_topk_prob": True,
             "expert_offset": e - held})
        return out["Y"][0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).trace(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((d, e), jnp.float32),
        jax.ShapeDtypeStruct((held, d, 2 * f), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, f, d), jnp.bfloat16),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 6
    # what the backward makes again is traced in the backward's context:
    # a function of its own
    for name, functions in (("_gather_live", 2), ("_swiglu_live", 2),
                            ("_swiglu_live_bwd", 1),
                            ("_weigh_to_tokens_bwd", 1)):
        found = _functions(text, name)
        assert len(found) == functions
        for body in found.values():
            assert body.count("stablehlo.while") == 1
            assert "tpu_custom_call" not in body
        assert sum(text.count("call @%s(" % f) for f in found) == functions


def test_a_two_layer_share_program_traces_each_chunk_loop_once(monkeypatch):
    """The host-cost pin: LFM2 at toy widths with two expert layers that
    hold 2 of 8 experts, the train step lowered as the Executor lowers it.
    A share's layer is traced once forward and once backward, whatever the
    depth and although every grad op traces its forward again, and the
    StableHLO holds the layer as functions that the layers call."""
    from paddle_tpu.core.trace import build_traced_function
    from paddle_tpu.models import lfm2
    from paddle_tpu.ops import kernel_tuning as kt, moe_ops

    class HP(lfm2.LFM2MoEConfig):
        vocab_size, hidden_size, intermediate_size = 64, 32, 32
        moe_intermediate_size, num_hidden_layers, num_dense_layers = 16, 3, 1
        layer_types = ["conv", "full_attention", "conv"]
        num_attention_heads, num_key_value_heads = 2, 1
        num_experts, num_experts_per_tok = 8, 2
        num_local_experts, expert_offset = 2, 2

    seq, traced_bodies = 24, []
    rows = 2 * seq * HP.num_experts_per_tok
    live_chunks = moe_ops._live_chunks

    def counted(n_live, chunk, carry, body):
        leaf = jax.tree.leaves(carry)[0]
        if leaf.shape[0] == rows:  # not layer_helper.infer_shape's batch
            traced_bodies.append(leaf.shape)
        return live_chunks(n_live, chunk, carry, body)

    monkeypatch.setattr(moe_ops, "_live_chunks", counted)
    monkeypatch.setattr(moe_ops, "_chunk_rows", lambda m: 16)
    jax.clear_caches()  # an earlier test's trace of these shapes would hide
    main, startup, _, fetches = lfm2.lfm2_lm_program(HP, seq_len=seq, lr=1e-3)
    scope = fluid.Scope()
    for block in (main.global_block(), startup.global_block()):
        for name, var in block.vars.items():
            if var.persistable and all(int(d) >= 0 for d in var.shape):
                scope.set(name, jax.ShapeDtypeStruct(
                    tuple(int(d) for d in var.shape),
                    jnp.dtype(str(var.dtype))))
    feeds = {"ids": jax.ShapeDtypeStruct((2, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, seq), jnp.int32),
             "loss_weight": jax.ShapeDtypeStruct((2, seq), jnp.float32)}
    traced = build_traced_function(
        main, 0, tuple(sorted(feeds)), [fetches[0].name], scope,
        platform="cpu")

    def shaped(name):
        v = scope.find_var(name)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    before = kt.attribution()["moe_live_chunks"]["ops"]
    text = jax.jit(traced.fn).trace(
        feeds, {n: shaped(n) for n in traced.ro_names},
        {n: shaped(n) for n in traced.rw_names},
        jax.eval_shape(lambda: jax.random.key(1))).lower().as_text()
    layers_ = sum(op.type == "moe_ffn" for op in main.global_block().ops)
    assert layers_ == 2
    # forward: the gather and the SwiGLU; backward: the combine's and the
    # SwiGLU's transposes, and the gather and the SwiGLU made again (jax
    # traces under another context there: a trace and a function of their
    # own), once whatever the depth
    assert len(traced_bodies) == 6
    # the layers call one forward function from their forward ops (jit
    # prunes the residuals nobody reads there: a jaxpr of its own), one
    # from their grad ops, and one backward function
    for name, functions in (("_share_fwd", 2), ("_share_bwd", 1)):
        found = _functions(text, name)
        assert len(found) == functions
        assert all(text.count("call @%s(" % f) == layers_ for f in found)
    # and those three hold every loop: one call site each
    for name, functions, calls in (
            ("_gather_live", 2, 3), ("_swiglu_live", 2, 3),
            ("_swiglu_live_bwd", 1, 1), ("_weigh_to_tokens_bwd", 1, 1)):
        found = _functions(text, name)
        assert len(found) == functions
        assert sum(text.count("call @%s(" % f) for f in found) == calls
    found = kt.attribution()["moe_live_chunks"]
    assert found["ops"] - before == 2 * layers_
    assert found["chunk_rows"][rows] == 16
    jax.clear_caches()


# --- the ungated expert: down(relu(up x)^2) (`expert_act` "relu2", PR 57) ---
def _relu2_weights():
    w = _weights("balanced")
    rng = np.random.RandomState(57)
    return dict(w, up=(rng.randn(E, D, F) * 0.3).astype("float32"))


def _relu2_loop(x, router_w, up, down, offset, scaling=2.5):
    """The plain statement: s = sigmoid(x W_r); the top-k of s + b; weights
    scaling s / (sum + 1e-20); a loop over the held experts."""
    s = jax.nn.sigmoid(x @ router_w)
    _, top_e = jax.lax.top_k(s + jnp.asarray(_bias()), K)
    top_p = jnp.take_along_axis(s, top_e, -1)
    top_p = scaling * top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for local in range(up.shape[0]):
        chosen = top_e == offset + local
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        y = y + weight * (jnp.square(jax.nn.relu(x @ up[local]))
                          @ down[local])
    return y


def _relu2_op(x, router_w, up, down, offset):
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    return moe_ops._moe_ffn(
        LowerCtx(platform="cpu"),
        {"X": [x], "RouterW": [router_w], "GateUpW": [up], "DownW": [down],
         "ExpertBias": [jnp.asarray(_bias())]},
        {"top_k": K, "router": "sigmoid", "norm_topk_prob": True,
         "norm_topk_eps": 1e-20, "routed_scaling_factor": 2.5,
         "expert_offset": offset, "expert_act": "relu2"})


RELU2 = {"whole": (0, E, None), "share": (4, 2, None),
         "share_in_chunks": (3, 3, 16), "share_at_the_start": (0, 3, 8)}


@functools.lru_cache(maxsize=None)
def _relu2_both(case):
    from paddle_tpu.ops import moe_ops

    offset, held, chunk = RELU2[case]
    w = _relu2_weights()
    sl = slice(offset, offset + held)
    args = [jnp.asarray(v) for v in (w["x"], w["router"], w["up"][sl],
                                     w["down"][sl])]
    mix = jnp.asarray(w["mix"])
    before = moe_ops._chunk_rows
    if chunk:
        moe_ops._chunk_rows = lambda m: chunk
    try:
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(
                lambda *a: (_relu2_op(*a, offset)["Y"][0] * mix).sum(),
                argnums=(0, 1, 2, 3))(*args)
            y = _relu2_op(*args, offset)["Y"][0]
            want = jax.value_and_grad(
                lambda *a: (_relu2_loop(*a, offset) * mix).sum(),
                argnums=(0, 1, 2, 3))(*args)
            want_y = _relu2_loop(*args, offset)
    finally:
        moe_ops._chunk_rows = before
    return (y, got), (want_y, want)


@pytest.mark.parametrize("case", list(RELU2))
def test_relu2_experts_match_the_plain_loop(case):
    (y, _), (want_y, _) = _relu2_both(case)
    assert np.abs(np.asarray(want_y)).max() > 0.1
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("wrt", range(4), ids=["x", "router", "up", "down"])
@pytest.mark.parametrize("case", list(RELU2))
def test_relu2_experts_every_gradient_matches_the_plain_loop(case, wrt):
    (_, (_, got)), (_, (_, want)) = _relu2_both(case)
    assert got[wrt].shape == want[wrt].shape
    np.testing.assert_allclose(got[wrt], want[wrt], rtol=1e-5, atol=1e-5)


def test_relu2_is_not_a_swiglu_of_half_the_width():
    """The same [E, d, f] weight read as (gate | up) halves is another
    function: the attribute names the body, the shape does not."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    w = _relu2_weights()
    ins = {"X": [jnp.asarray(w["x"])], "RouterW": [jnp.asarray(w["router"])],
           "GateUpW": [jnp.asarray(w["up"])],
           "DownW": [jnp.asarray(w["down"])]}
    relu2 = moe_ops._moe_ffn(LowerCtx(platform="cpu"), ins,
                             {"top_k": K, "expert_act": "relu2"})["Y"][0]
    swiglu = moe_ops._moe_ffn(
        LowerCtx(platform="cpu"),
        dict(ins, DownW=[jnp.asarray(w["down"][:, :F // 2])]),
        {"top_k": K})["Y"][0]
    assert np.abs(np.asarray(relu2) - np.asarray(swiglu)).max() > 0.01
    with pytest.raises(ValueError, match="expert_act 'gelu' is neither"):
        moe_ops._moe_ffn(LowerCtx(platform="cpu"), ins,
                         {"top_k": K, "expert_act": "gelu"})


def test_the_layer_names_the_body_and_a_swiglu_op_carries_what_it_carried():
    from paddle_tpu import framework, unique_name

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[N, D], append_batch_size=False)
        layers.moe_ffn(x, E, F, K)
        layers.moe_ffn(x, E, F, K, num_local_experts=2, expert_offset=2,
                       expert_act="relu2")
        with pytest.raises(ValueError, match="expert_act 'gelu'"):
            layers.moe_ffn(x, E, F, K, expert_act="gelu")
    block = main.global_block()
    swiglu, relu2 = [op for op in block.ops if op.type == "moe_ffn"]
    assert "expert_act" not in swiglu.attrs
    assert relu2.attrs["expert_act"] == "relu2"
    assert tuple(block.var(swiglu.inputs["GateUpW"][0]).shape) == (E, D, 2 * F)
    assert tuple(block.var(relu2.inputs["GateUpW"][0]).shape) == (2, D, F)
    assert not [d for d in analysis.verify_program(main) if d.is_error]


def test_infer_rule_reads_the_up_weight_by_the_body():
    out = _infer((N, D), (D, E), (2, D, F), (2, F, D), expert_act="relu2",
                 expert_offset=2)
    assert out["Y"][0].shape == (N, D)
    with pytest.raises(InferError, match=r"f\] under relu2"):
        _infer((N, D), (D, E), (E, D, 2 * F), (E, F, D), expert_act="relu2")
    with pytest.raises(InferError, match="2f"):
        _infer((N, D), (D, E), (E, D, F), (E, F, D))
    with pytest.raises(InferError, match="expert_act 'gelu' is neither"):
        _infer((N, D), (D, E), (E, D, 2 * F), (E, F, D), expert_act="gelu")


def test_program_flops_counts_two_matmuls_an_ungated_expert():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.utils.flops import program_flops

    def flops(**kw):
        main, startup = fluid.Program(), fluid.Program()
        with framework.program_guard(main, startup), unique_name.guard():
            x = layers.data("x", shape=[N, D], append_batch_size=False)
            layers.moe_ffn(x, E, F, K, **kw)
        return program_flops(main)

    assert flops() == 2.0 * (N * D * E + N * K * 3 * D * F)
    assert flops(expert_act="relu2") == 2.0 * (N * D * E + N * K * 2 * D * F)


# one test that ties the share to the model: Nemotron-3-Nano's expert layer
SHARES = 16


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Sixteen chips hold one expert each of one Nemotron-H expert layer
    (`models/nemotron_h._experts`: sigmoid router over 16 with a selection
    bias, top-4, scaled 2.5, relu2 experts beside a relu2 shared one).
    Each routes over all sixteen, computes its own expert's part and the
    WHOLE shared expert; the sixteen routed parts plus the shared expert
    counted ONCE are what the uncut reference gives for the layer, and
    every chip saw the same routing decisions."""
    from expert_share import share_through_the_executor
    from paddle_tpu.models import nemotron_h, nemotron_h_reference

    d, f, fs, k = 32, 16, 24, 4
    rng = np.random.RandomState(7)
    w = {"x": rng.randn(2, 24, d).astype("float32"),
         "router": (rng.randn(d, SHARES) * 0.3).astype("float32"),
         "bias": (rng.randn(SHARES) * 0.3).astype("float32"),
         "gate_up": (rng.randn(SHARES, d, f) * 0.3).astype("float32"),
         "down": (rng.randn(SHARES, f, d) * 0.3).astype("float32"),
         "shared": [(rng.randn(d, fs) * 0.3).astype("float32"),
                    (rng.randn(fs, d) * 0.3).astype("float32")]}

    hp = type("Layer", (nemotron_h.NemotronHConfig,), dict(
        hidden_size=d, moe_intermediate_size=f,
        moe_shared_expert_intermediate_size=fs, n_routed_experts=SHARES,
        num_experts_per_tok=k, num_hidden_layers=1))
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "expert_offset": 0}
    args = [jnp.asarray(w[n])
            for n in ("x", "router", "bias", "gate_up", "down")]
    with jax.default_matmul_precision("highest"):
        routed, top_e = nemotron_h_reference.routed(cfg, *args)
        shared = nemotron_h_reference.relu2_mlp(
            args[0], *map(jnp.asarray, w["shared"]))
    want_counts = np.bincount(np.asarray(top_e).reshape(-1),
                              minlength=SHARES)
    parts = [share_through_the_executor(nemotron_h._experts, hp, w, offset, 1)
             for offset in range(SHARES)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    assert sum(np.abs(part).max() > 0 for _, part, _ in parts) >= 12
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=5e-5)


# --- an expert width the kernels' tile does not divide (PR 57) --------------
def test_a_width_off_the_kernels_tile_is_padded_with_columns_that_add_nothing():
    """Nemotron-H's experts are 1856 = 14.5 x 128 wide.  Where the Pallas
    grouped matmul would engage but for that, the op pads the up weight's
    columns and the down weight's rows with zeros to the next multiple of
    128: result and every gradient are the unpadded op's (relu(0)^2 = 0;
    a SwiGLU's halves are padded each: silu(0) * 0 = 0), and a width the
    tile divides, or a placement off the chip, passes through."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    on_chip, here = LowerCtx(platform="tpu"), LowerCtx(platform="cpu")
    n, d, f, e, k = 128, 128, 96, 8, 2  # N k = 256 rows: the kernels' tile
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32)
    for act, halves in (("relu2", 1), ("swiglu", 2)):
        up = jnp.asarray(rng.randn(2, d, halves * f) * 0.3, jnp.float32)
        down = jnp.asarray(rng.randn(2, f, d) * 0.3, jnp.float32)
        wide_up, wide_down = moe_ops._kernel_widths(on_chip, n * k, up, down,
                                                    halves)
        assert wide_up.shape == (2, d, halves * 128)
        assert wide_down.shape == (2, 128, d)
        for w in moe_ops._kernel_widths(here, n * k, up, down, halves):
            assert w is up or w is down  # off the chip: as given
        fits = moe_ops._kernel_widths(on_chip, n * k, wide_up, wide_down,
                                      halves)
        assert fits[0] is wide_up and fits[1] is wide_down
        # rows the kernels' tile does not divide: ragged_dot either way
        assert moe_ops._kernel_widths(on_chip, n * k + 2, up, down,
                                      halves)[0] is up

        def loss(x, wr, wgu, wd):
            out = moe_ops._moe_ffn(
                here, {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                       "DownW": [wd]},
                {"top_k": k, "router": "sigmoid", "norm_topk_prob": True,
                 "expert_offset": 2, "expert_act": act})
            return (out["Y"][0] * jnp.cos(jnp.arange(d))).sum()

        with jax.default_matmul_precision("highest"):
            want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
                x, wr, up, down)
            got = jax.value_and_grad(
                lambda x, wr, a, b: loss(x, wr, *moe_ops._kernel_widths(
                    on_chip, n * k, a, b, halves)), argnums=(0, 1, 2, 3))(
                        x, wr, up, down)
        assert abs(float(want[0])) > 1.0
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, w in zip(got[1], want[1]):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_nemotrons_share_cross_lowers_for_the_tpu_with_six_kernels_a_layer():
    """The relu2 share at Nemotron-3-Nano's widths (d 2688, f 1856, 8 of
    128 held, top-6), lowered for the TPU on this host: the six Mosaic
    calls a layer every share has (without the padding the two products
    and their four transposes are `ragged_dot`s, none), and the body's two
    loops are the shared functions under their old names."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops

    f, e, held, k, n, d = 1856, 128, 8, 6, 1024, 2688
    on_chip = LowerCtx(platform="tpu")

    def loss(x, wr, wgu, wd):
        out = moe_ops._moe_ffn(
            on_chip, {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                      "DownW": [wd]},
            {"top_k": k, "router": "sigmoid", "norm_topk_prob": True,
             "expert_offset": 0, "expert_act": "relu2"})
        return out["Y"][0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).trace(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((d, e), jnp.float32),
        jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, f, d), jnp.bfloat16),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 6
    assert "ragged_dot" not in text
    for name, functions in (("_swiglu_live", 2), ("_swiglu_live_bwd", 1)):
        assert len(_functions(text, name)) == functions
