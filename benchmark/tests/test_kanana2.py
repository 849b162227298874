"""The kanana2 cell's own pieces, every registry entry looked up by name:
the adapter's copy of the reference against
paddle_tpu/models/kanana2_reference.py, its closed forms at the published
sizes and against a count over the Program, the configuration's cut, the
new metrics' data files and reader, and a rehearsal of the cell to its
end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "kanana2_30b_a3b_train", "kanana2_30b_a3b"
NEW_METRICS = ("mla_time_share", "mla_core_roofline",
               "shared_expert_time_share", "latent_attention_ops")
APPENDED = ("attention_time_share", "moe_time_share",
            "moe_load_max_over_mean", "moe_dropped_share",
            "expert_matmul_roofline", "moe_rows_held_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order (models/kanana2_reference.py)."""
    d, v, h = (arch["hidden_size"], arch["vocab_size"],
               arch["num_attention_heads"])
    r, nope, rot, dv = (arch["kv_lora_rank"], arch["qk_nope_head_dim"],
                        arch["qk_rope_head_dim"], arch["v_head_dim"])
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["n_routed_experts"], arch["num_local_experts"]
    fs = arch["n_shared_experts"] * fe
    mla = [(d,), (d, h * (nope + rot)), (d, r + rot), (r,),
           (r, h * (nope + dv)), (h * dv, d), (d,)]
    shapes = [(v, d)]
    for i in range(arch["num_hidden_layers"]):
        shapes += mla
        shapes += ([(d, f), (d, f), (f, d)]
                   if i < arch["first_k_dense_replace"]
                   else [(d, e), (e,), (held, d, 2 * fe), (held, fe, d),
                         (d, fs), (d, fs), (fs, d)])
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same seeded weights and batch
    give the same loss (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import kanana2_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["n_routed_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (8, 2, 2)
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = kanana2_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_closed_forms_at_the_published_sizes():
    """The numbers the issue and PERF.md quote, a forward token at
    1 x 6144: latent attention's projections 52.7 M and its core over the
    causal half 62.9 (83.9 at T = 8192), the dense MLP 75.5, the shared
    expert 18.9, the held experts' 0.75 rows 7.1, the router 0.5, the head
    65.7: 825 M, 15.2 T a step."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    assert (work["batch"], work["seq_len"]) == (1, 6144)
    rows = 6144.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["mla_projections"] == pytest.approx(5 * 52.69, rel=1e-3)
    assert part["mla_core"] == pytest.approx(5 * 62.91, rel=1e-3)
    assert part["dense_mlp"] == pytest.approx(75.50, rel=1e-3)
    assert part["shared_expert"] == pytest.approx(4 * 18.87, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 7.078, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 0.524, rel=1e-3)
    assert part["head"] == pytest.approx(65.67, rel=1e-3)
    assert sum(part.values()) == pytest.approx(825.1, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(15.21e12, rel=1e-3)
    wide = dict(work, seq_len=8192)
    assert adapter.forward_flops(cfg, wide)["mla_core"] / 8192 / 5e6 == (
        pytest.approx(83.89, rel=1e-3))
    core = adapter.mla_core_cost(cfg, work)
    assert core["flops_step"] == 3 * 2.0 * 32 * 6144 * 6144 / 2 * 320
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 4608 * 2048 * 768
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (core["flops_step"] / peak["flops_per_s"]
            > 5 * core["bytes_step"] / peak["hbm_bytes_per_s"])


def test_closed_forms_are_a_count_over_the_program_but_for_the_causal_half():
    """utils.flops.program_flops walks the forward program's ops and
    counts fused_attention over the full T x T at Q's width and V's; the
    adapter counts the causal half, which is what the kernel runs.  With
    half of the walk's cores taken off, the two are the same number."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    counted = program_flops(main, batch_hint=int(work["batch"]))
    parts = adapter.forward_flops(cfg, work)
    assert sum(parts.values()) + parts["mla_core"] == pytest.approx(
        counted, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("fused_attention") == 3
    assert types_.count("moe_ffn") == 2


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16032}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    assert cfg["share"] == {"router_experts": 128, "expert_offset": 0}
    assert "eight chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 128256 and 8 * 16 == 128
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s6144", 1)
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
    for name in APPENDED:
        assert per_layer[name]["workloads"][-1] == CELL
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | set(APPENDED) | {"head_time_share"}
    assert "collective_bytes" not in reports
    assert "short_conv_time_share" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("metric, scope, selected", [
    ("mla_time_share", "forward/mul/7/forward/mla.down/2", True),
    ("mla_time_share",
     "backward/fused_attention_grad/140/backward/mla.core/2", True),
    ("mla_time_share", "forward/cast/3/forward/mla.rope/2", True),
    ("mla_time_share", "forward/mul/4", False),
    ("mla_time_share", "forward/fused_swiglu/30/forward/shared_expert/1",
     False),
    ("mla_time_share", "forward/mul/4/forward/mlab/1", False),
    ("shared_expert_time_share",
     "forward/fused_swiglu/30/forward/shared_expert/1", True),
    ("shared_expert_time_share",
     "backward/mul_grad/90/backward/shared_expert/1", True),
    ("shared_expert_time_share", "forward/moe_ffn/29", False),
    ("shared_expert_time_share", "", False),
])
def test_scope_time_shares_select_their_scopes(metric, scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*op_types):
    ops = [types.SimpleNamespace(type=t) for t in op_types]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_mla_core_roofline_reads_the_core_span():
    """The data file's span and cost through readers/span_roofline.py on a
    made-up step: the forward kernel under fused_attention/<i>/forward/
    mla.core/2 and the backward one under fused_attention_grad/... are the
    span, the output projection is not.  3 ms in the span, work that needs
    1.5 ms by operations: 50%, bound by operations."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %custom-call.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/30/forward/mla.core/2/jit(_flash_fwd_call)/pallas_call"}
  %dot.2 = f32[8]{0} add(%custom-call.1, %a), metadata={op_name="jit(s)/forward/mul/33/forward/mla.out/2/dot_general"}
  ROOT %custom-call.3 = f32[8]{0} multiply(%dot.2, %a), metadata={op_name="jit(s)/backward/fused_attention_grad/90/backward/mla.core/2/jit(_flash_bwd_call)/pallas_call"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics", "mla_core_roofline.json")
    assert how["reader"] == "span_roofline"
    assert how["args"] == {"op": "fused_attention", "span": "mla.core",
                           "cost": "mla_core_cost"}
    ops = [("%custom-call.1",
            (1e6, "", "", "forward/fused_attention/30", set())),
           ("%dot.2", (5e6, "", "", "forward/mul/33", set())),
           ("%custom-call.3",
            (2e6, "", "", "backward/fused_attention_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main("fused_attention", "mul"),
           "adapter": types.SimpleNamespace(mla_core_cost=lambda c, w: {
               "flops_step": 1.5e9, "bytes_step": 1e3}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("mla_core_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "bound by operations" in logged[0]
    # a program without the op: nothing to read, no raise
    ctx["main"] = _fake_main("mul")
    assert _read("mla_core_roofline", ctx) is None


def test_latent_attention_ops_counts_the_ops_whose_v_is_narrower():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("latent_attention_ops", ctx) == 3.0  # one a layer
    # a program whose attention is of one width, and one without any
    cfg, work, adapter = load_cell("gpt2_345m_train")
    assert _read("latent_attention_ops",
                 {"main": adapter.build(cfg, work)["main"]}) == 0
    cfg, work, adapter = load_cell("resnet50_train")
    assert _read("latent_attention_ops",
                 {"main": adapter.build(cfg, work)["main"]}) is None
    assert _read("latent_attention_ops", {}) is None


@pytest.mark.parametrize("metric", ["mla_time_share", "mla_core_roofline",
                                    "shared_expert_time_share"])
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
    assert line["metrics"]["latent_attention_ops"]["value"] == 3.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
