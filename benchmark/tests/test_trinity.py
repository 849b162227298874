"""The trinity cell's own pieces, every registry entry looked up by name:
the adapter's copy of the reference against
paddle_tpu/models/trinity_reference.py, its closed forms at the published
sizes and against a count by hand and over the Program, the
configuration's cut against the catalog's numbers, the new metrics' data
files and readers, and a rehearsal of the cell to its end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "trinity_mini_train", "trinity_mini"
NEW_METRICS = ("window_attention_time_share", "window_attention_roofline",
               "windowed_attention_ops")
APPENDED = ("attention_time_share", "moe_time_share",
            "moe_load_max_over_mean", "moe_dropped_share",
            "expert_matmul_roofline", "moe_rows_held_share",
            "moe_rows_run_share", "shared_expert_time_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order (models/trinity_reference.py)."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    h, kv, dh = (arch["num_attention_heads"], arch["num_key_value_heads"],
                 arch["head_dim"])
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["num_experts"], arch["num_local_experts"]
    fs = arch["num_shared_experts"] * fe
    attn = [(d,), (d, h * dh), (d, kv * dh), (d, kv * dh), (d, h * dh),
            (dh,), (dh,), (h * dh, d), (d,), (d,)]
    shapes = [(v, d)]
    for i in range(arch["num_hidden_layers"]):
        shapes += attn
        shapes += ([(d, f), (d, f), (f, d)] if i < arch["num_dense_layers"]
                   else [(d, e), (e,), (held, d, 2 * fe), (held, fe, d),
                         (d, fs), (d, fs), (fs, d)])
        shapes += [(d,)]
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same seeded weights and batch
    give the same loss (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import trinity_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["num_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (8, 2, 2)
    assert work["seq_len"] > arch["sliding_window"]
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = trinity_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_window_core_cost_is_a_count_by_hand():
    """Query i of a window layer sees min(i + 1, 2048) keys: counted one
    query at a time, 14,681,088 pairs a head at T = 8192 (the closed form
    2048 x 8192 - 2048 x 2047 / 2), 44% of a full layer's causal half;
    four operations a pair and head width forward (QK^T and PV, two a
    multiply-add), three forwards a step; at a window that reaches every
    key the full layer's convention, T^2 / 2."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    t, w, h, dh = 8192, 2048, 32, 128
    assert (work["batch"], work["seq_len"], cfg["sliding_window"],
            cfg["num_attention_heads"], cfg["head_dim"]) == (1, t, w, h, dh)
    by_hand = sum(min(i + 1, w) for i in range(t))
    assert by_hand == 14681088 == w * t - w * (w - 1) // 2
    assert adapter.core_pairs(t, w) == by_hand
    assert by_hand / (t * t / 2.0) == pytest.approx(0.4376, abs=1e-4)
    cost = adapter.window_core_cost(cfg, work)
    assert cost["flops_forward"] == 4.0 * h * by_hand * dh
    assert cost["flops_step"] == 3 * cost["flops_forward"]
    assert cost["bytes_step"] == 2.0 * h * t * 8 * dh
    assert {adapter.core_pairs(t, w) for w in (0, t, 3 * t)} == {
        t * t / 2.0}
    small = sum(min(i + 1, 8) for i in range(16))
    assert adapter.core_pairs(16, 8) == small == 100
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (cost["flops_step"] / peak["flops_per_s"]
            > 5 * cost["bytes_step"] / peak["hbm_bytes_per_s"])


def test_closed_forms_at_the_published_sizes():
    """The numbers the issue and PERF.md quote, a forward token at
    1 x 8192 in millions of operations: a layer's projections 54.5 (q and
    the gate at 4096), a window core 29.4 and the full one 67.1, the dense
    MLP 75.5, a shared expert 12.6, the held experts' 0.5 rows 6.3, a
    router 0.5, the head 102.5: 713 M, 17.5 T a step."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = 8192.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["attn_projections"] == pytest.approx(5 * 54.53, rel=1e-3)
    assert part["window_cores"] == pytest.approx(4 * 29.36, rel=1e-3)
    assert part["full_cores"] == pytest.approx(67.11, rel=1e-3)
    assert part["dense_mlp"] == pytest.approx(75.50, rel=1e-3)
    assert part["shared_expert"] == pytest.approx(4 * 12.58, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 6.291, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 0.524, rel=1e-3)
    assert part["head"] == pytest.approx(102.5, rel=1e-3)
    assert sum(part.values()) == pytest.approx(712.7, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(17.51e12, rel=1e-3)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 4096 * 2048 * 1024


def test_closed_forms_are_a_count_over_the_program_but_for_the_cores():
    """utils.flops.program_flops walks the forward program's ops and
    counts a fused_attention over Tq x Tk (Tq x window under a window);
    the adapter counts the pairs a query may see.  With the cores taken
    off both, the two are the same number."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    b, t = int(work["batch"]), int(work["seq_len"])
    h, dh, w = (cfg["num_attention_heads"], cfg["head_dim"],
                cfg["sliding_window"])
    walked_cores = 2.0 * b * h * t * (4 * w + t) * 2 * dh
    parts = adapter.forward_flops(cfg, work)
    cores = parts["window_cores"] + parts["full_cores"]
    assert cores == 2.0 * b * h * (4 * adapter.core_pairs(t, w)
                                   + t * t / 2.0) * 2 * dh
    assert sum(parts.values()) - cores == pytest.approx(
        program_flops(main, batch_hint=b) - walked_cores, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("fused_attention") == 5
    assert types_.count("moe_ffn") == 4


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    """Every number of the catalog row's `config` under the same key, but
    the five keys `reduced` names."""
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 25024,
           "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    assert cfg["share"] == {"router_experts": 128, "expert_offset": 0}
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 200192 and 16 * 8 == 128
    assert cfg["train"] == {"learning_rate": 5e-6, "use_bf16": True,
                            "expert_bias_rate": 0.03,
                            "expert_bias_max_step": 0.03}
    assert "modeling_afmoe.py" in cfg["assumed"]["the layer's equations"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")


def test_the_cells_traffic_is_the_issues():
    _, work, _ = load_cell(CELL, rehearse=False)
    assert {k: work[k] for k in ("kind", "mesh", "batch", "seq_len", "ring",
                                 "warmup_steps", "readback_every")} == {
        "kind": "train", "mesh": None, "batch": 1, "seq_len": 8192,
        "ring": 8, "warmup_steps": 32, "readback_every": 10}


def test_the_build_hands_the_balancing_step_its_rate_and_bound():
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    updates = [op for op in main.global_block().ops
               if op.type == "expert_bias_update"]
    assert len(updates) == 4
    assert all((op.attrs["rate"], op.attrs["max_step"]) == (0.03, 0.03)
               for op in updates)


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s8192", 1)
    assert len(cell["why"]) <= 200 and "16 x its share" in cell["why"]
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
        assert per_layer[name]["layer"] == "Op lowerings + kernels"
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"]
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | set(APPENDED) | {"head_time_share"}
    assert "collective_bytes" not in reports
    assert "mla_time_share" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("scope, selected", [
    ("forward/mul/7/forward/attn_window/1", True),
    ("backward/fused_attention_grad/140/backward/attn_window.core/2", True),
    ("forward/sigmoid/30/forward/attn_window.attn_gate/2", True),
    ("forward/fused_attention/80/forward/attn_full.core/2", False),
    ("forward/mul/4", False),
    ("forward/mul/4/forward/attn_windows/1", False),
    ("forward/fused_swiglu/30/forward/shared_expert/1", False),
    ("", False),
])
def test_window_attention_time_share_selects_its_scope(scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "window_attention_time_share.json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*ops):
    ops = [types.SimpleNamespace(type=t, attrs=a) for t, a in ops]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_window_attention_roofline_reads_the_window_cores_alone():
    """The data file's span and cost through readers/span_roofline_attr.py
    on a made-up step of two window layers and a full one: the kernels
    under fused_attention/<i>/forward/attn_window.core/2 and their _grad
    are the span, the full layer's core (attn_full.core) and the output
    projection are not, and the work is counted for the TWO ops that carry
    a window, not the three fused_attention ops.  4 ms in the span, work
    that needs 2 x 1 ms by operations: 50%, bound by operations."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %custom-call.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/30/forward/attn_window.core/2/jit(_flash_fwd_call)/pallas_call"}
  %custom-call.2 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/60/forward/attn_full.core/2/jit(_flash_fwd_call)/pallas_call"}
  %dot.3 = f32[8]{0} add(%custom-call.1, %a), metadata={op_name="jit(s)/forward/mul/33/forward/attn_window/1/dot_general"}
  ROOT %custom-call.4 = f32[8]{0} multiply(%dot.3, %a), metadata={op_name="jit(s)/backward/fused_attention_grad/90/backward/attn_window.core/2/jit(_flash_bwd_call)/pallas_call"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "window_attention_roofline.json")
    assert how["reader"] == "span_roofline_attr"
    assert how["args"] == {"op": "fused_attention",
                           "span": "attn_window.core",
                           "cost": "window_core_cost", "attr": "window"}
    ops = [("%custom-call.1",
            (1e6, "", "", "forward/fused_attention/30", set())),
           ("%custom-call.2",
            (7e6, "", "", "forward/fused_attention/60", set())),
           ("%dot.3", (5e6, "", "", "forward/mul/33", set())),
           ("%custom-call.4",
            (3e6, "", "", "backward/fused_attention_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main(("fused_attention", {"window": 2048}),
                              ("fused_attention", {"window": 2048}),
                              ("fused_attention", {"window": 0}),
                              ("mul", {})),
           "adapter": types.SimpleNamespace(window_core_cost=lambda c, w: {
               "flops_step": 1e9, "bytes_step": 1e3}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("window_attention_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "2 ops" in logged[0]
    assert "bound by operations" in logged[0]
    # a program whose attention carries no window (any parent's): nothing
    # to read, no raise
    ctx["main"] = _fake_main(("fused_attention", {}), ("mul", {}))
    assert _read("window_attention_roofline", ctx) is None


def test_windowed_attention_ops_reads_four_on_the_rehearsal():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("windowed_attention_ops", ctx) == 4.0
    # a program whose attention is full everywhere, and one without any
    cfg, work, adapter = load_cell("gpt2_345m_train")
    assert _read("windowed_attention_ops",
                 {"main": adapter.build(cfg, work)["main"]}) == 0
    cfg, work, adapter = load_cell("resnet50_train")
    assert _read("windowed_attention_ops",
                 {"main": adapter.build(cfg, work)["main"]}) is None
    assert _read("windowed_attention_ops", {}) is None


@pytest.mark.parametrize("metric", ["window_attention_time_share",
                                    "window_attention_roofline"])
def test_without_a_trace_the_trace_metrics_are_left_out(metric):
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []


def test_the_cell_rehearses_to_its_end():
    """The real command at the data files' tiny sizes on the CPU, traced:
    correct, nothing failed, and the counters that need no device trace
    are on the line."""
    proc = _start(BENCH_DIR, "--workload", CELL, "--seed", "3000000019",
                  "--seconds", "30", "--trace", "1", "--rehearse")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-2000:]
    assert "REHEARSAL of %s ran to its end" % CELL in out
    line = json.loads(next(
        l for l in out.splitlines()
        if l.startswith("rehearsal line")).split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["moe_dropped_share"]["value"] == 0.0
    assert line["metrics"]["windowed_attention_ops"]["value"] == 4.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
