"""The nemotron3_nano cell's own pieces, every registry entry looked up by
name (never by position): the adapter's copy of the reference against
paddle_tpu/models/nemotron_h_reference.py, its closed forms against a count
by hand at the rehearsal's widths, at the published sizes and over the
Program, the configuration's cut against the catalog's numbers, the new
metrics' data files through the readers the benchmark has, and a rehearsal
of the cell to its end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "nemotron3_nano_30b_a3b_train", "nemotron3_nano_30b_a3b"
NEW_METRICS = ("mamba2_time_share", "mamba2_core_time_share",
               "mamba2_core_roofline", "mamba2_scan_ops")
APPENDED = ("moe_time_share", "moe_load_max_over_mean",
            "moe_load_max_over_mean_window", "moe_dropped_share",
            "expert_matmul_roofline", "moe_rows_held_share",
            "moe_rows_held_share_window", "moe_rows_held_share_range",
            "moe_rows_traced_over_expected", "moe_no_live_rows_share",
            "moe_rows_run_share", "shared_expert_time_share",
            "attention_time_share", "attention_pairs_computed_over_visible",
            "attention_block_fetches_over_tiles")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order
    (models/nemotron_h_reference.py)."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    h, p = arch["mamba_num_heads"], arch["mamba_head_dim"]
    g, n, taps = arch["n_groups"], arch["ssm_state_size"], arch["conv_kernel"]
    inner, conv = h * p, h * p + 2 * g * n
    ha, hkv, dh = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    f, fs = (arch["moe_intermediate_size"],
             arch["moe_shared_expert_intermediate_size"])
    e, held = arch["n_routed_experts"], arch["num_local_experts"]
    kind = {
        "M": [(d, 2 * inner + 2 * g * n + h), (conv, taps), (conv,), (h,),
              (h,), (h,), (g, inner // g), (inner, d)],
        "*": [(d, ha * dh), (d, hkv * dh), (d, hkv * dh), (ha * dh, d)],
        "E": [(d, e), (e,), (held, d, f), (held, f, d), (d, fs), (fs, d)]}
    shapes = [(v, d)]
    for ch in arch["hybrid_override_pattern"]:
        shapes += [(d,)] + kind[ch]
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time; both run the scan as the
    token-by-token recurrence): the same seeded weights and batch give the
    same loss (float32, 1e-6); each wrong model gives another."""
    from paddle_tpu.models import nemotron_h_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["n_routed_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (8, 2, 2)
    assert work["seq_len"] % cfg["chunk_size"]  # the op pads
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = nemotron_h_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    assert len(adapter.DEPARTURES) == 10
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > (
            1e-5 if departure in ("state_bf16", "dt_bf16") else 1e-3), (
                departure, wrong, mine)
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_ssd_core_cost_is_a_count_by_hand_at_the_rehearsals_widths():
    """One chunk of Q = 128 tokens at the rehearsal's 4 heads of 16 over 2
    groups at state 16, product by product: C B^T a group [128, 16] x
    [16, 128]: 2 x 128 x 128 x 16 = 524,288, two groups; a head the masked
    [128, 128] x [128, 16]: 524,288, the state read C S^T [128, 16] x
    [16, 16] and written (dt x)^T B [16, 128] x [128, 16]: 65,536 each,
    four heads: 3,670,016 a chunk, 28,672 a token."""
    cfg, work, adapter = load_cell(CELL)
    q, h, p, g, n = 128, 4, 16, 2, 16
    assert (cfg["chunk_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"]) == (q, h, p, g, n)
    from paddle_tpu.ops import mamba2_ops
    assert mamba2_ops.CHUNK == cfg["chunk_size"]
    chunk = g * 2 * q * q * n + h * (2 * q * q * p + 2 * 2 * q * n * p)
    assert chunk == 3670016 and chunk // q == 28672
    rows = work["batch"] * work["seq_len"]
    cost = adapter.ssd_core_cost(cfg, work)
    assert cost["flops_forward"] == rows * 28672.0
    assert cost["flops_step"] == 3 * cost["flops_forward"]
    # x and y at 4 heads of 16, B and C at 2 groups of 16 in bf16, dt f32
    assert cost["bytes_step"] == 3.0 * rows * (
        2 * (2 * h * p + 2 * g * n) + 4 * h)
    # TWO matmuls an expert: 4 rows d f forward over the expected rows
    held = rows * 3 * 2 / 8.0
    assert adapter._held_rows(cfg, work) == held
    experts = adapter.expert_matmul_cost(cfg, work)
    assert experts["flops_forward"] == 4.0 * held * 64 * 32
    assert experts["flops_step"] == 12.0 * held * 64 * 32
    assert experts["bytes_step"] == 3.0 * (
        2.0 * 2 * 2 * 64 * 32 + 2.0 * held * 2 * (64 + 32))


def test_closed_forms_at_the_published_sizes():
    """A forward token at 1 x 6144 in millions of operations: a Mamba-2
    layer's projections 77.4 and scan 3.41, the attention layer's
    projections 46.8 and core 50.3 (T = 6144; 67.1 at 8192), a shared
    expert 39.9, the held experts' 0.375 rows 7.5, a router 0.69, the head
    88.1: 701 in all (718 at 8192, as ISSUE 57 counts it)."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = float(work["seq_len"])
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["mamba_projections"] == pytest.approx(4 * 77.41, rel=1e-3)
    assert part["mamba_cores"] == pytest.approx(4 * 3.407872, rel=1e-6)
    assert part["attention_projections"] == pytest.approx(46.79, rel=1e-3)
    assert part["attention_core"] == pytest.approx(
        2.0 * 32 * rows / 2 * 256 / 1e6, rel=1e-6)
    assert part["shared_expert"] == pytest.approx(4 * 39.91, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 7.483, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 0.688, rel=1e-3)
    assert part["head"] == pytest.approx(88.08, rel=1e-3)
    at_8192 = sum(part.values()) + 2.0 * 32 * (8192 - rows) / 2 * 256 / 1e6
    assert at_8192 == pytest.approx(718.0, rel=2e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(
        3.0 * rows * 1e6 * sum(part.values()), rel=1e-9)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 12.0 * (rows * 6 * 8 / 128) * 2688 * 1856
    # the scan is bound by bytes: 0.38 GB against 62.8 GFLOP a layer a step
    scan = adapter.ssd_core_cost(cfg, work)
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (scan["bytes_step"] / peak["hbm_bytes_per_s"]
            > scan["flops_step"] / peak["flops_per_s"])


def test_closed_forms_are_a_count_over_the_program_but_for_the_full_core():
    """utils.flops.program_flops walks the forward program's ops: it counts
    a mamba2_scan and a relu2 moe_ffn as the adapter does and a
    fused_attention over the square where the adapter counts the causal
    half.  With the attention core taken off both, the two are the same
    number."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    b, t = int(work["batch"]), int(work["seq_len"])
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    walked_core = 2.0 * b * h * t * t * (dh + dh)
    parts = adapter.forward_flops(cfg, work)
    assert parts["attention_core"] == walked_core / 2
    assert sum(parts.values()) - parts["attention_core"] == pytest.approx(
        program_flops(main, batch_hint=b) - walked_core, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("mamba2_scan") == 4
    assert types_.count("fused_attention") == 1
    assert types_.count("moe_ffn") == 4


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    """Every number of the catalog row's `config` under the same key, but
    the keys `reduced` names."""
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    assert cfg["share"] == {"router_experts": 128, "expert_offset": 0}
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 131072 and 16 * 8 == 128
    assert cfg["train"] == {"learning_rate": 5e-6, "use_bf16": True,
                            "expert_bias_rate": 0.03,
                            "expert_bias_max_step": 0.03}
    assert "modeling_nemotron_h.py" in cfg["assumed"]["the layer's equations"]
    assert "applies no rotary" in cfg["assumed"]["attention without rotary"]
    assert "NOT expand" in cfg["assumed"]["d_inner"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut) and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert sum(c["file"] == entry["file"] for c in SPEC["configs"]) == 1


def test_the_cells_traffic_is_the_issues():
    """1 x 6144 by the issue's memory rule (8192 is 16.14 GiB by the
    compiler's count: PERF.md section 4), everything else to the
    letter."""
    _, work, _ = load_cell(CELL, rehearse=False)
    assert {k: work[k] for k in ("kind", "mesh", "batch", "seq_len", "ring",
                                 "warmup_steps", "readback_every",
                                 "trace_steps", "reference_rows")} == {
        "kind": "train", "mesh": None, "batch": 1, "seq_len": 6144,
        "ring": 8, "warmup_steps": 32, "readback_every": 10,
        "trace_steps": 12, "reference_rows": 1}


def test_the_build_ends_in_one_balancing_step_an_expert_layer():
    cfg, work, adapter = load_cell(CELL)
    ops = adapter.build(cfg, work)["main"].global_block().ops
    assert [(op.attrs["rate"], op.attrs["max_step"]) for op in ops
            if op.type == "expert_bias_update"] == [(0.03, 0.03)] * 4


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s6144", 1)
    assert len(cell["why"]) <= 200 and "16x their deployed share" in cell[
        "why"]
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
    assert not {"kda_time_share", "gdn_time_share", "mla_time_share",
                "fc_time_share"} & reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("scope, mixer, core", [
    ("forward/mul/7/forward/mamba2.in_proj/2", True, False),
    ("forward/causal_conv/12/forward/mamba2.conv/2", True, False),
    ("forward/mamba2_scan/30/forward/mamba2.core/2", True, True),
    ("backward/mamba2_scan_grad/140/backward/mamba2.core/2", True, True),
    ("forward/softplus/28/forward/mamba2.core/2", True, True),
    ("forward/rms_norm/33/forward/mamba2.norm/2", True, False),
    ("forward/mul/35/forward/mamba2.out_proj/2", True, False),
    ("forward/fused_attention/80/forward/attn_full.core/2", False, False),
    ("forward/mul/4", False, False),
    ("forward/mul/4/forward/mamba2s/1", False, False),
    ("forward/mul/4/forward/mamba2.cores/2", True, False),
    ("", False, False),
])
def test_the_mamba2_time_shares_select_their_scopes(scope, mixer, core):
    for metric, selected in (("mamba2_time_share", mixer),
                             ("mamba2_core_time_share", core)):
        how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
        assert how["reader"] == "scope_time_share"
        assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*ops):
    ops = [types.SimpleNamespace(type=t, attrs=a) for t, a in ops]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_the_roofline_reads_the_scan_alone():
    """The data file's span and cost through readers/span_roofline.py on a
    made-up step of two Mamba-2 layers: the device ops under
    mamba2_scan/<i>/forward/mamba2.core/2 (the chunk scan's kernel) and
    their _grad (the walk that keeps the states, the reverse walk) are the
    span; a projection and the softplus beside the op are not.  4 ms in
    the span, work that needs 2 x 1 ms by bytes: 50%, bound by bytes."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %custom-call.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/mamba2_scan/30/forward/mamba2.core/2/chunk_scan/pallas_call"}
  %fusion.2 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/softplus/28/forward/mamba2.core/2/log1p"}
  %dot.4 = f32[8]{0} add(%custom-call.1, %a), metadata={op_name="jit(s)/forward/mul/33/forward/mamba2.in_proj/2/dot_general"}
  %custom-call.5 = f32[8]{0} multiply(%dot.4, %a), metadata={op_name="jit(s)/backward/mamba2_scan_grad/90/backward/mamba2.core/2/transpose(jvp(states))/pallas_call"}
  ROOT %custom-call.6 = f32[8]{0} multiply(%dot.4, %a), metadata={op_name="jit(s)/backward/mamba2_scan_grad/90/backward/mamba2.core/2/transpose(jvp(chunk_scan))/pallas_call"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "mamba2_core_roofline.json")
    assert how["reader"] == "span_roofline"
    assert how["args"] == {"op": "mamba2_scan", "span": "mamba2.core",
                           "cost": "ssd_core_cost"}
    ops = [("%custom-call.1", (1e6, "", "", "forward/mamba2_scan/30", set())),
           ("%fusion.2", (9e6, "", "", "forward/softplus/28", set())),
           ("%dot.4", (5e6, "", "", "forward/mul/33", set())),
           ("%custom-call.5",
            (1e6, "", "", "backward/mamba2_scan_grad/90", set())),
           ("%custom-call.6",
            (2e6, "", "", "backward/mamba2_scan_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main(("mamba2_scan", {}), ("mamba2_scan", {}),
                              ("mul", {})),
           "adapter": types.SimpleNamespace(
               ssd_core_cost=lambda c, w: {"flops_step": 1e6,
                                           "bytes_step": 1e8}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("mamba2_core_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "bound by bytes" in logged[0]
    # a program without the op (any parent's): nothing to read, no raise
    ctx["main"] = _fake_main(("fused_attention", {}), ("mul", {}))
    assert _read("mamba2_core_roofline", ctx) is None


def test_mamba2_scan_ops_reads_its_count_on_the_rehearsal():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("mamba2_scan_ops", ctx) == 4
    assert _read("gdn_attention_ops", ctx) is None
    assert _read("kda_attention_ops", ctx) is None
    # Qwen3-Next's count stays its own
    cfg, work, adapter = load_cell("qwen3_next_80b_a3b_train")
    qwen = {"main": adapter.build(cfg, work)["main"]}
    assert _read("mamba2_scan_ops", qwen) is None
    assert _read("gdn_attention_ops", qwen) == 3
    assert _read("mamba2_scan_ops", {}) is None


@pytest.mark.parametrize("metric", ["mamba2_time_share",
                                    "mamba2_core_time_share",
                                    "mamba2_core_roofline"])
def test_without_a_trace_the_trace_metrics_are_left_out(metric):
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []


def test_the_cell_rehearses_to_its_end():
    """The real command at the data files' tiny sizes on the CPU, traced:
    correct, nothing failed, and the counters that need no device trace
    are on the line."""
    proc = _start(BENCH_DIR, "--workload", CELL, "--seed", "3100000043",
                  "--seconds", "30", "--trace", "1", "--rehearse")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-2000:]
    assert "REHEARSAL of %s ran to its end" % CELL in out
    line = json.loads(next(
        l for l in out.splitlines()
        if l.startswith("rehearsal line")).split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["moe_dropped_share"]["value"] == 0.0
    assert line["metrics"]["mamba2_scan_ops"]["value"] == 4.0
    assert "gdn_attention_ops" not in line["metrics"]
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
