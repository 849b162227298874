"""The LFM2 cell's own pieces, every registry entry looked up by name: the
adapter's copy of the reference against paddle_tpu/models/lfm2_reference.py,
its closed forms at the published sizes and against a count over the
Program, the configuration's cut, the new metrics' data files and reader,
and a rehearsal of the cell to its end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "lfm2_8b_a1b_train", "lfm2_8b_a1b"
NEW_METRICS = ("short_conv_time_share", "short_conv_roofline",
               "moe_rows_held_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order (models/lfm2_reference.py)."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    dh = d // arch["num_attention_heads"]
    kv = arch["num_key_value_heads"] * dh
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["num_experts"], arch["num_local_experts"]
    mixer = {"conv": [(d, 3 * d), (d, arch["conv_L_cache"]), (d, d)],
             "full_attention": [(d, d), (d, kv), (d, kv), (dh,), (dh,),
                                (d, d)]}
    shapes = [(v, d)]
    for i, kind in enumerate(arch["layer_types"]):
        shapes += [(d,)] + mixer[kind] + [(d,)]
        shapes += ([(d, f), (d, f), (f, d)] if i < arch["num_dense_layers"]
                   else [(d, e), (e,), (held, d, 2 * fe), (held, fe, d)])
    return shapes + [(d,)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one key/value head at a time): the same seeded weights
    and batch give the same loss (float32, 1e-6)."""
    from paddle_tpu.models import lfm2_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["num_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (8, 2, 2)
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    mine = adapter.reference_loss(
        cfg, [("w%d" % i, w) for i, w in enumerate(weights)], batch)
    theirs, _ = lfm2_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)


def test_closed_forms_at_the_published_sizes():
    """The numbers the issue and PERF.md quote: 466 M operations a forward
    token at 2 x 8192 (dense layer 122 = conv projections 34 + MLP 88; the
    attention layer's T x T 67 and projections 21; 22 a mixture layer's
    held experts; head 67), 22.9 T a step; a mixture layer's matmuls over
    the 16,384 rows its 8 held experts expect; a short_conv op's 738 MB."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = 16384.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["short_conv_projections"] == pytest.approx(4 * 33.55, rel=1e-3)
    assert part["dense_mlp"] == pytest.approx(88.08, rel=1e-3)
    assert part["attention"] == pytest.approx(67.11 + 20.97, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 22.02, rel=1e-3)
    assert part["head"] == pytest.approx(67.11, rel=1e-3)
    assert sum(part.values()) == pytest.approx(466.2, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(22.91e12, rel=1e-3)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 16384 * 2048 * 1792
    assert cost["bytes_step"] == pytest.approx(1.46e9, rel=0.01)
    conv = adapter.short_conv_cost(cfg, work)
    assert conv["bytes_step"] == 2.0 * 16384 * 2048 * 11
    assert conv["flops_forward"] == 8.0 * 16384 * 2048
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (cost["flops_step"] / peak["flops_per_s"]
            > cost["bytes_step"] / peak["hbm_bytes_per_s"])
    assert (conv["flops_step"] / peak["flops_per_s"]
            < conv["bytes_step"] / peak["hbm_bytes_per_s"])


def test_closed_forms_are_a_count_over_the_program():
    """utils.flops.program_flops walks the forward program's ops (mul, the
    fused SwiGLU, fused_attention's full T x T, moe_ffn over its share's
    expected rows, short_conv, the tied head): the adapter's forward parts
    add up to the same number, at the rehearsal's sizes."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    counted = program_flops(main, batch_hint=int(work["batch"]))
    assert sum(adapter.forward_flops(cfg, work).values()) == pytest.approx(
        counted, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("short_conv") == 4 and types_.count("moe_ffn") == 4


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 7168, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
                 "norm_eps": 1e-05, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts_per_tok": 4,
                 "num_key_value_heads": 8, "rope_theta": 1000000,
                 "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    assert cfg["share"] == {"router_experts": 32, "expert_offset": 0}
    assert "four chips share each layer" in cfg["deployment"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    # one whole period of the expert layers after a leading dense layer
    assert cfg["layer_types"][cfg["num_dense_layers"]:] == [
        "full_attention", "conv", "conv", "conv"]


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b2_s8192", 1)
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | {
        "moe_time_share", "attention_time_share", "moe_load_max_over_mean",
        "moe_dropped_share", "head_time_share", "expert_matmul_roofline"}
    assert "collective_bytes" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("scope, selected", [
    ("forward/short_conv/5", True),
    ("backward/short_conv_grad/140", True),
    ("forward/short_conv/5/gate_conv", True),
    ("forward/mul/4", False),
    ("backward/mul_grad/9/forward/short_conv/3", False),
    ("", False),
])
def test_short_conv_time_share_selects_its_scopes(scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "short_conv_time_share.json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*op_types):
    ops = [types.SimpleNamespace(type=t) for t in op_types]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_short_conv_roofline_reads_the_gate_conv_span():
    """The data file's span and cost through readers/span_roofline.py on a
    made-up step: a forward fusion under short_conv/<i>/gate_conv and a
    backward one under jvp(gate_conv) are the span, the out-projection's
    matmul is not.  2 ms in the span, work that needs 1 ms by bytes: 50%,
    bound by bytes."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/short_conv/3/gate_conv/mul"}
  %dot.2 = f32[8]{0} add(%fusion.1, %a), metadata={op_name="jit(s)/forward/mul/4/dot_general"}
  ROOT %fusion.3 = f32[8]{0} multiply(%dot.2, %a), metadata={op_name="jit(s)/backward/short_conv_grad/9/transpose(backward/short_conv_grad/9)/jvp(gate_conv)/mul"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "short_conv_roofline.json")
    assert how["reader"] == "span_roofline"
    assert how["args"] == {"op": "short_conv", "span": "gate_conv",
                           "cost": "short_conv_cost"}
    ops = [("%fusion.1", (1e6, "", "", "forward/short_conv/3", set())),
           ("%dot.2", (5e6, "", "", "forward/mul/4", set())),
           ("%fusion.3", (1e6, "", "", "backward/short_conv_grad/9", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text], "main": _fake_main("short_conv", "mul"),
           "adapter": types.SimpleNamespace(short_conv_cost=lambda c, w: {
               "flops_step": 1e3, "bytes_step": 1e8}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("short_conv_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "bound by bytes" in logged[0]
    # a program without the op: nothing to read, no raise
    ctx["main"] = _fake_main("mul")
    assert _read("short_conv_roofline", ctx) is None


def _trained(cell):
    import paddle_tpu as fluid

    cfg, work, adapter = load_cell(cell)
    built = adapter.build(cfg, work)
    built["startup"].random_seed = 3
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(built["startup"])
        exe.run(built["main"], feed=adapter.make_batch(cfg, work, 1),
                fetch_list=[built["loss"]])
    return {"main": built["main"], "scope": scope, "work": work, "cfg": cfg,
            "log": [].append, "load_module": RUN.load_module}


def test_rows_held_share_comes_from_the_scope():
    """The rehearsal holds experts 2 and 3 of 8: the metric is their rows
    over all rows, mean over the four mixture layers; the older router
    metrics keep their meaning (all N k decisions counted, none dropped)."""
    ctx = _trained(CELL)
    logged = []
    ctx["log"] = logged.append
    block = ctx["main"].global_block()
    want = []
    for op in block.ops:
        if op.type == "moe_ffn":
            counts = np.asarray(
                ctx["scope"].find_var(op.outputs["TokensPerExpert"][0]))
            assert counts.shape == (8,) and op.attrs["expert_offset"] == 2
            want.append(100.0 * counts[2:4].sum() / counts.sum())
    assert len(want) == 4
    assert _read("moe_rows_held_share", ctx) == pytest.approx(np.mean(want))
    assert len(logged) == 1 and "live rows" in logged[0]
    assert _read("moe_dropped_share", ctx) == 0.0
    assert 1.0 <= _read("moe_load_max_over_mean", ctx) <= 8.0


def test_a_program_without_experts_leaves_rows_held_share_out():
    cfg, work, adapter = load_cell("gpt2_345m_train")
    ctx = {"main": adapter.build(cfg, work)["main"], "scope": object(),
           "work": work, "cfg": cfg, "log": [].append,
           "load_module": RUN.load_module}
    assert _read("moe_rows_held_share", ctx) is None
    assert _read("moe_rows_held_share", {"log": [].append}) is None


@pytest.mark.parametrize("metric", ["short_conv_time_share",
                                    "short_conv_roofline"])
def test_without_a_trace_the_trace_metrics_are_left_out(metric):
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []


def test_the_cell_rehearses_to_its_end():
    """The real command at the data files' tiny sizes on the CPU, traced:
    correct, nothing failed, and the counters that need no device trace
    are on the line."""
    proc = _start(BENCH_DIR, "--workload", CELL, "--seed", "2147483659",
                  "--seconds", "30", "--trace", "1", "--rehearse")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-2000:]
    assert "REHEARSAL of %s ran to its end" % CELL in out
    line = json.loads(next(
        l for l in out.splitlines()
        if l.startswith("rehearsal line")).split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["moe_dropped_share"]["value"] == 0.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
