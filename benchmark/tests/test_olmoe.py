"""The OLMoE cell's own pieces: the adapter's copy of the reference against
paddle_tpu/models/olmoe_reference.py, its closed forms at the published
sizes, and the two new readers (router statistics from the scope, roofline
share of a named span on the recorded trace under data/)."""

import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, load_cell

from test_program_profile import _read, pp, prof  # noqa: F401 (fixtures)

CELL = "olmoe_1b7b_train"


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart: the same
    seeded weights and batch give the same loss (float32, 1e-6)."""
    from paddle_tpu.models import olmoe_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    d, f, e, v = (arch["hidden_size"], arch["intermediate_size"],
                  arch["num_experts"], arch["vocab_size"])
    layer = [(d,), (d, d), (d, d), (d, d), (d,), (d,), (d, d), (d,), (d, e),
             (e, d, 2 * f), (e, f, d)]
    shapes = [(v, d)] + layer * arch["num_hidden_layers"] + [(d,), (d, v)]
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in shapes]
    batch = adapter.make_batch(cfg, work, 4)
    mine = adapter.reference_loss(
        cfg, [("w%d" % i, w) for i, w in enumerate(weights)], batch)
    theirs, _ = olmoe_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)


def test_closed_forms_at_the_published_sizes():
    """The numbers the configuration's `why` and PERF.md quote: 9.2 TFLOP
    a step at 2 x 4096, forward 3.07 = head 1.69 + experts 0.83 + attention
    0.55 (+ router 0.002); the experts' matmuls 2.47 TFLOP and 5.2 GB a
    step, bound by operations on a v5e."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    part = adapter.forward_flops(cfg, work)
    assert part["head"] == pytest.approx(1.69e12, rel=0.01)
    assert part["experts"] == pytest.approx(0.825e12, rel=0.01)
    assert part["attention"] == pytest.approx(0.55e12, rel=0.01)
    assert sum(part.values()) == pytest.approx(3.07e12, rel=0.01)
    assert adapter.model_flops(cfg, work) == pytest.approx(9.2e12, rel=0.01)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 65536 * 2048 * 1024
    assert cost["bytes_step"] == pytest.approx(5.23e9, rel=0.01)
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (cost["flops_step"] / peak["flops_per_s"]
            > cost["bytes_step"] / peak["hbm_bytes_per_s"])


def test_configuration_carries_the_published_widths():
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {"hidden_size": 2048, "intermediate_size": 1024,
                 "max_position_embeddings": 4096, "num_attention_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
                 "rope_theta": 10000, "vocab_size": 50304,
                 "norm_topk_prob": False, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 1
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    entry = RUN.find(SPEC["configs"], "olmoe_1b7b", "config")
    assert entry["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("metric, scope, selected", [
    ("moe_time_share", "forward/moe_ffn/30", True),
    ("moe_time_share", "backward/moe_ffn_grad/57", True),
    ("moe_time_share", "forward/fused_attention/12", False),
    ("moe_time_share", "", False),
    ("attention_time_share", "forward/fused_attention/12", True),
    ("attention_time_share", "backward/fused_attention_grad/88", True),
    ("attention_time_share", "forward/fc/3", False),
    ("attention_time_share", "backward/fc_grad/7/forward/fused_attention/3",
     False),
])
def test_time_share_data_files_select_their_scopes(metric, scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _trained_olmoe():
    import paddle_tpu as fluid

    cfg, work, adapter = load_cell(CELL)
    built = adapter.build(cfg, work)
    built["startup"].random_seed = 3
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        exe.run(built["main"], feed=adapter.make_batch(cfg, work, 1),
                fetch_list=[built["loss"]])
    return {"main": built["main"], "scope": scope, "work": work, "cfg": cfg,
            "load_module": RUN.load_module}


def test_router_statistics_come_from_the_scope_and_nothing_is_dropped():
    ctx = _trained_olmoe()
    assert _read("moe_dropped_share", ctx) == 0.0
    worst = _read("moe_load_max_over_mean", ctx)
    assert 1.0 <= worst <= ctx["cfg"]["num_experts"]
    # a statistic that lost routing decisions reads as dropped
    name = next(n for n in ctx["scope"].local_var_names()
                if n.startswith("moe_tokens_per_expert"))
    counts = np.asarray(ctx["scope"].find_var(name)).copy()
    counts[counts.argmax()] -= 2
    ctx["scope"].set(name, counts)
    tokens = ctx["work"]["batch"] * ctx["work"]["seq_len"]
    sent = (tokens * ctx["cfg"]["num_experts_per_tok"]
            * ctx["cfg"]["num_hidden_layers"])
    assert _read("moe_dropped_share", ctx) == pytest.approx(100.0 * 2 / sent)


@pytest.mark.parametrize("cell", ["gpt2_345m_train", "tfm_base_train_s64"])
def test_a_program_without_experts_leaves_the_router_metrics_out(cell):
    cfg, work, adapter = load_cell(cell)
    ctx = {"main": adapter.build(cfg, work)["main"], "scope": object(),
           "work": work, "cfg": cfg, "load_module": RUN.load_module}
    assert _read("moe_dropped_share", ctx) is None
    assert _read("moe_load_max_over_mean", ctx) is None


def _fake_main(*op_types):
    ops = [types.SimpleNamespace(type=t) for t in op_types]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_span_roofline_on_the_recorded_trace(prof):  # noqa: F811
    """data/profile_trace.pbtxt: fusion.2 runs 40 us in each of the two
    steps and holds the convolution traced under backward/mul_grad/5/
    transpose(jvp())/: that is the span.  Work of 4e6 operations and 1e3
    bytes a step at 1e12 / 1e11 a second needs 4 us by operations (0.01 us
    by bytes): 10% of the roofline, bound by operations."""
    import os

    with open(os.path.join(BENCH_DIR, "tests", "data",
                           "profile_hlo.txt")) as f:
        hlo = f.read()
    logged = []
    adapter = types.SimpleNamespace(work_cost=lambda cfg, work: {
        "flops_step": 4e6, "bytes_step": 1e3})
    ctx = {"program_profile": prof, "hlo_texts": [hlo],
           "main": _fake_main("mul", "relu"), "adapter": adapter,
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": RUN.load_json(BENCH_DIR, "peaks.json")["rehearsal"],
           "load_module": RUN.load_module}
    reader = RUN.load_module("readers", "span_roofline")
    share = reader.read(ctx, op="mul", span="transpose(jvp())",
                        cost="work_cost")
    assert share == pytest.approx(10.0)
    assert len(logged) == 1 and "bound by operations" in logged[0]
    # two such ops in the program: twice the work in the same time
    ctx["main"] = _fake_main("mul", "mul")
    assert reader.read(ctx, op="mul", span="transpose(jvp())",
                       cost="work_cost") == pytest.approx(20.0)
    # a span no instruction was traced under, an op the program lacks
    assert reader.read(ctx, op="mul", span="experts",
                       cost="work_cost") is None
    assert reader.read(ctx, op="moe_ffn", span="transpose(jvp())",
                       cost="work_cost") is None


def test_span_members_places_a_fusion_by_the_matmul_inside_it():
    reader = RUN.load_module("readers", "span_roofline")
    tr = RUN.load_module("", "trace_reduce")
    text = """HloModule m

%fused.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %c = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/forward/moe_ffn/3/experts/jit(gmm)/pallas_call"}
  ROOT %m = f32[8]{0} multiply(%c, %c), metadata={op_name="jit(s)/forward/moe_ffn/3/combine/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused.1, metadata={op_name="jit(s)/forward/moe_ffn/3/combine/mul"}
  %tgmm.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/backward/moe_ffn_grad/9/transpose(backward/moe_ffn_grad/9)/jvp(experts)/jit(tgmm)/pallas_call"}
  ROOT %n = f32[8]{0} negate(%tgmm.2), metadata={op_name="jit(s)/forward/moe_ffn/3/route/neg"}
}
"""
    placed = reader.span_members([text], tr.parse_op)
    assert "/experts/" in placed["%fusion.1"]
    assert "jvp(experts)" in placed["%tgmm.2"]
    assert "/route/" in placed["%n"]
    # both spellings of the span are inside it, the others are not
    prof_ops = [(n, (1e6, "", "", "backward/moe_ffn_grad/9", set()))
                for n in ("%fusion.1", "%tgmm.2", "%n")]
    ctx = {"program_profile": {"device_ops": prof_ops, "steps": 1},
           "hlo_texts": [text], "main": _fake_main("moe_ffn"),
           "adapter": types.SimpleNamespace(cost=lambda c, w: {
               "flops_step": 1e9, "bytes_step": 1.0}),
           "cfg": {}, "work": {}, "log": [].append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    # 1 ms of work at peak over the 2 ms the two span ops took
    assert reader.read(ctx, op="moe_ffn", span="experts",
                       cost="cost") == pytest.approx(50.0)


@pytest.mark.parametrize("metric", ["moe_time_share", "attention_time_share",
                                    "expert_matmul_roofline"])
def test_without_the_programs_names_the_trace_metrics_are_left_out(metric):
    """The parent of the PR that added them has no moe_ffn and a program
    from before the scopes has no compiled_steps: None, no raise."""
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []
