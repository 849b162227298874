"""moe_ffn: routed SwiGLU experts (softmax router, top-k, dropless) against
the loop-over-experts float32 reference of models/olmoe_reference.py:
forward, every gradient, the experts chosen, and the router statistics,
with a balanced router and with one so biased that one expert takes over
half the rows and one takes none."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.models import olmoe_reference as ref

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


def _reference(w, norm_topk_prob):
    cfg = dict(CFG, norm_topk_prob=norm_topk_prob)

    def loss(x, router, gate_up, down):
        y, lb, z, _ = ref.moe(cfg, x, router, gate_up, down)
        return (y * w["mix"]).sum() + 0.5 * lb + 0.25 * z

    args = [jnp.asarray(w[k]) for k in ("x", "router", "gate_up", "down")]
    with jax.default_matmul_precision("highest"):
        y, lb, z, top_e = ref.moe(cfg, *args)
        grads = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
    return {"y": y, "aux": jnp.stack([lb, z]), "top_e": top_e,
            "grads": dict(zip(("x", "router", "gate_up", "down"), grads))}


@functools.lru_cache(maxsize=None)
def _run(router, norm_topk_prob):
    """One program per case: Y, the statistics and all four gradients."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.initializer import NumpyArrayInitializer
    from paddle_tpu.param_attr import ParamAttr

    w = _weights(router)
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[N, D], append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=[N, D], append_batch_size=False)

        def attr(name):
            return ParamAttr(name=name,
                             initializer=NumpyArrayInitializer(w[name]))

        y, aux, counts = layers.moe_ffn(
            x, E, F, K, norm_topk_prob=norm_topk_prob,
            router_attr=attr("router"), gate_up_attr=attr("gate_up"),
            down_attr=attr("down"))
        coef = layers.assign(np.array([0.5, 0.25], "float32"))
        coef.stop_gradient = True
        loss = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(y, mix)),
            layers.reduce_sum(layers.elementwise_mul(aux, coef)))
        fluid.backward.append_backward(loss)
    names = main._grad_names
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(
            main, feed={"x": w["x"], "mix": w["mix"]},
            fetch_list=[y, aux, counts] + [names[n] for n in (
                "x", "router", "gate_up", "down")])
    diags = analysis.verify_program(main, fetches=[loss])
    return {"y": out[0], "aux": out[1], "counts": out[2],
            "grads": dict(zip(("x", "router", "gate_up", "down"), out[3:])),
            "errors": [d for d in diags if d.is_error]}, \
        _reference(w, norm_topk_prob)


CASES = [("balanced", False), ("balanced", True), ("skewed", False)]


@pytest.mark.parametrize("router, norm", CASES)
def test_forward_matches_the_loop_over_experts(router, norm):
    got, want = _run(router, norm)
    np.testing.assert_allclose(got["y"], want["y"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got["aux"], want["aux"], rtol=1e-5)
    assert not got["errors"]


@pytest.mark.parametrize("wrt", ["x", "router", "gate_up", "down"])
@pytest.mark.parametrize("router, norm", CASES)
def test_gradient_matches_the_loop_over_experts(router, norm, wrt):
    got, want = _run(router, norm)
    np.testing.assert_allclose(got["grads"][wrt], want["grads"][wrt],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("router, norm", CASES)
def test_no_routing_decision_is_dropped_and_choices_are_the_references(
        router, norm):
    """TokensPerExpert counts exactly the reference's top-k choices, and
    sums to N k: dropless under any imbalance, an empty expert is legal."""
    got, want = _run(router, norm)
    counts = np.asarray(got["counts"])
    assert counts.dtype == np.int32 and counts.sum() == N * K
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(want["top_e"]).reshape(-1),
                            minlength=E))
    if router == "skewed":
        assert counts[0] == N  # every token, far over half of them
        assert counts[E - 1] == 0


def _infer(x, wr, wgu, wd, top_k=K):
    class Op:
        attrs = {"top_k": top_k}

    return get_infer_rule("moe_ffn").fn(Op, {
        "X": [VarInfo(x, "float32")], "RouterW": [VarInfo(wr, "float32")],
        "GateUpW": [VarInfo(wgu, "bfloat16")],
        "DownW": [VarInfo(wd, "bfloat16")]})


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


def test_bf16_experts_keep_a_float32_router():
    """Under the AMP pass the op reads X and RouterW in f32 and the
    experts' weights in bf16: the experts chosen are those of the float32
    router, whatever the experts' own precision, and the counts stay
    int32 with no cast-back."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.transpiler.pass_registry import apply_pass

    w = _weights("balanced")
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[N, D], append_batch_size=False)
        y, aux, counts = layers.moe_ffn(x, E, F, K)
        apply_pass(main, "bf16_amp_pass")
    (op,) = [o for o in main.global_block().ops if o.type == "moe_ffn"]
    block = main.global_block()
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"X": "float32", "RouterW": "float32",
                      "GateUpW": "bfloat16", "DownW": "bfloat16",
                      "Y": "bfloat16", "TokensPerExpert": "int32",
                      "AuxLoss": "float32"}
    assert op.outputs["TokensPerExpert"] == [counts.name]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        router = np.asarray(scope.find_var(op.inputs["RouterW"][0]))
        got_y, got = exe.run(main, feed={"x": w["x"]},
                             fetch_list=[y, counts])
    assert got_y.dtype == np.float32  # the cast-back restores the name
    with jax.default_matmul_precision("highest"):
        _, top_e = jax.lax.top_k(jax.nn.softmax(
            jnp.asarray(w["x"]) @ jnp.asarray(router), -1), K)
    np.testing.assert_array_equal(
        got, np.bincount(np.asarray(top_e).reshape(-1), minlength=E))
