"""The qwen3_next cell's own pieces, every registry entry looked up by
name: the adapter's copy of the reference against
paddle_tpu/models/qwen3_next_reference.py, its closed forms at the
published sizes and against a count by hand and over the Program, the
configuration's cut against the catalog's numbers, the new metrics' data
files and readers, and a rehearsal of the cell to its end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "qwen3_next_80b_a3b_train", "qwen3_next_80b_a3b"
NEW_METRICS = ("gdn_time_share", "gdn_core_time_share", "gdn_core_roofline",
               "full_attention_core_roofline", "gdn_attention_ops")
APPENDED = ("attention_time_share", "moe_time_share",
            "moe_load_max_over_mean", "moe_dropped_share",
            "expert_matmul_roofline", "moe_rows_held_share",
            "moe_rows_run_share", "shared_expert_time_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order
    (models/qwen3_next_reference.py)."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    taps, conv = arch["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv
    h, hkv, dh = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    fe, fs = (arch["moe_intermediate_size"],
              arch["shared_expert_intermediate_size"])
    e, held = arch["num_experts"], arch["num_local_experts"]
    gdn = [(d, conv), (d, hv * dv), (d, hv), (d, hv), (hv,), (conv, taps),
           (hv,), (dv,), (hv * dv, d)]
    attn = [(d, h * dh), (d, hkv * dh), (d, hkv * dh), (d, h * dh), (dh,),
            (dh,), (h * dh, d)]
    shapes = [(v, d)]
    for i in range(arch["num_hidden_layers"]):
        shapes += [(d,)] + (
            gdn if (i + 1) % arch["full_attention_interval"] else attn)
        shapes += [(d,), (d, e), (held, d, 2 * fe), (held, fe, d),
                   (d, fs), (d, fs), (fs, d), (d, 1)]
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time; both run Gated DeltaNet as the
    recurrence): the same seeded weights and batch give the same loss
    (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import qwen3_next_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["num_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (16, 4, 4)
    assert work["seq_len"] % adapter.GDN_CHUNK  # the op pads
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = qwen3_next_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    assert len(adapter.DEPARTURES) == 12
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_gdn_core_cost_is_a_count_by_hand():
    """One chunk of one VALUE head, product by product at C = 64, dk = dv =
    128: A_kk, A_qk and the inverse's W are [64, 128] x [128, 64] or
    [64, 64] x [64, 128]: 2 x 64 x 64 x 128 each; the inverse's U0 and
    A_qk U the same at dv; W S, Q S and K^T U are [64, 128] x [128, 128]:
    2 x 64 x 128 x 128 each: 11,534,336 a chunk, 180,224 a token; 32 value
    heads over 8,192 tokens three times: 141.7 GFLOP a layer a step (0.72
    ms at the chip's peak), over 0.61 GB (0.75 ms): nearly even, bound by
    bytes by a hair."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    c, dk, dv, hk, hv, t = 64, 128, 128, 16, 32, int(work["seq_len"])
    assert (adapter.GDN_CHUNK, cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], work["batch"], t) == (
                c, dk, dv, hk, hv, 1, 8192)
    from paddle_tpu.ops import kda_ops
    assert kda_ops.CHUNK == adapter.GDN_CHUNK
    chunk = (3 * 2 * c * c * dk + 2 * 2 * c * c * dv + 3 * 2 * c * dk * dv)
    assert chunk == 11534336 and chunk // c == 180224
    cost = adapter.gdn_core_cost(cfg, work)
    assert cost["flops_forward"] == hv * t * 180224.0
    assert cost["flops_step"] == 3 * cost["flops_forward"]
    assert cost["flops_step"] == pytest.approx(141.7e9, rel=1e-3)
    # q and k at 16 heads, v and o at 32 in bf16, g and beta in f32:
    # forward, and backward with the gradients
    once = t * (2 * (2 * hk * dk + 2 * hv * dv) + 2 * 4 * hv)
    assert cost["bytes_step"] == 3.0 * once
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    by_flops = cost["flops_step"] / peak["flops_per_s"]
    by_bytes = cost["bytes_step"] / peak["hbm_bytes_per_s"]
    assert by_flops == pytest.approx(0.72e-3, rel=1e-2)
    assert by_flops < by_bytes < 1.1 * by_flops


def test_full_core_cost_is_a_count_by_hand():
    """QK^T and PV over the causal half, 16 query heads of 256 over 8,192
    tokens: 2 x (8192^2 / 2) x (256 + 256) a head forward = 34.36 GFLOP,
    16 heads 549.8, three times that a step: 1.649 TFLOP (8.37 ms at the
    peak); q, k, v, o and their gradients once in bf16 at the query heads:
    0.54 GB: bound by operations."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    t, h, dh = int(work["seq_len"]), 16, 256
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (h, 2, dh)
    cost = adapter.full_core_cost(cfg, work)
    assert cost["flops_forward"] == h * 2.0 * (t * t / 2) * (dh + dh)
    assert cost["flops_forward"] == pytest.approx(549.8e9, rel=1e-3)
    assert cost["flops_step"] == 3 * cost["flops_forward"]
    assert cost["bytes_step"] == 2.0 * h * t * 8 * dh
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (cost["flops_step"] / peak["flops_per_s"]
            > 10 * cost["bytes_step"] / peak["hbm_bytes_per_s"])


def test_closed_forms_at_the_published_sizes():
    """A forward token at 1 x 8192 in millions of operations: a GDN
    layer's projections 67.4 and core 5.8, the attention layer's
    projections 54.5 and core 67.1 (T = 8192), a gated shared expert 6.3,
    the held experts' 0.625 rows 3.9, a router 2.1, the head 77.8: 468 in
    all, as ISSUE 48 counts it."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = float(work["seq_len"])
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["gdn_projections"] == pytest.approx(3 * 67.37, rel=1e-3)
    assert part["gdn_cores"] == pytest.approx(3 * 32 * 0.180224, rel=1e-6)
    assert part["attention_projections"] == pytest.approx(54.5, rel=2e-3)
    assert part["attention_core"] == pytest.approx(
        2.0 * 16 * rows / 2 * 512 / 1e6, rel=1e-6)
    assert part["shared_expert"] == pytest.approx(4 * 6.295, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 3.932, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 2.097, rel=1e-3)
    assert part["head"] == pytest.approx(77.79, rel=1e-3)
    assert sum(part.values()) == pytest.approx(468.0, rel=2e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(
        3.0 * rows * 1e6 * sum(part.values()), rel=1e-9)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * (8192 * 10 * 32 / 512) * 2048 * 512


def test_closed_forms_are_a_count_over_the_program_but_for_the_full_core():
    """utils.flops.program_flops walks the forward program's ops: it counts
    a gated_delta_attention as the adapter does (a token a VALUE head) and
    a fused_attention over the square where the adapter counts the causal
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
    assert types_.count("gated_delta_attention") == 3
    assert types_.count("fused_attention") == 1
    assert types_.count("moe_ffn") == 4


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    """Every number of the catalog row's `config` under the same key, but
    the keys `reduced` names."""
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    assert cfg["share"] == {"router_experts": 512, "expert_offset": 0}
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 151936 and 16 * 32 == 512
    assert cfg["train"] == {"learning_rate": 5e-6, "use_bf16": True}
    assert "modeling_qwen3_next.py" in cfg["assumed"][
        "the layer's equations"]
    assert "none is built" in cfg["assumed"]["multi-token prediction"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut) and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
        "blob/main/config.json")


def test_the_cells_traffic_is_the_issues():
    """1 x 8192 by the issue's memory rule (the compiler's counts are in
    PERF.md section 4), everything else to the letter."""
    _, work, _ = load_cell(CELL, rehearse=False)
    assert {k: work[k] for k in ("kind", "mesh", "batch", "seq_len", "ring",
                                 "warmup_steps", "readback_every",
                                 "trace_steps", "reference_rows")} == {
        "kind": "train", "mesh": None, "batch": 1, "seq_len": 8192,
        "ring": 8, "warmup_steps": 32, "readback_every": 10,
        "trace_steps": 12, "reference_rows": 1}


def test_the_build_has_no_balancing_step():
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    assert "expert_bias_update" not in [
        op.type for op in main.global_block().ops]


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s8192", 1)
    assert len(cell["why"]) <= 200 and "1/16 of deployed load" in cell["why"]
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
        assert per_layer[name]["layer"] == "Op lowerings + kernels"
    for name in APPENDED:
        assert per_layer[name]["workloads"][-1] == CELL
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | set(APPENDED) | {"head_time_share"}
    assert "collective_bytes" not in reports
    assert "kda_time_share" not in reports and "mla_time_share" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("scope, gdn, core", [
    ("forward/mul/7/forward/gdn.proj/2", True, False),
    ("forward/causal_conv/12/forward/gdn.conv/2", True, False),
    ("forward/gated_delta_attention/30/forward/gdn.core/2", True, True),
    ("backward/gated_delta_attention_grad/140/backward/gdn.core/2", True,
     True),
    ("forward/rms_norm/33/forward/gdn.out/2", True, False),
    ("forward/fused_attention/80/forward/attn_full.core/2", False, False),
    ("forward/mul/4", False, False),
    ("forward/mul/4/forward/gdns/1", False, False),
    ("forward/mul/4/forward/gdn.cores/2", True, False),
    ("", False, False),
])
def test_the_gdn_time_shares_select_their_scopes(scope, gdn, core):
    for metric, selected in (("gdn_time_share", gdn),
                             ("gdn_core_time_share", core)):
        how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
        assert how["reader"] == "scope_time_share"
        assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*ops):
    ops = [types.SimpleNamespace(type=t, attrs=a) for t, a in ops]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_the_two_rooflines_read_their_cores_alone():
    """The data files' spans and costs through readers/span_roofline.py on
    a made-up step of two GDN layers and an attention one: the device ops
    under gated_delta_attention/<i>/forward/gdn.core/2 (the inside's
    kernel, a product of the carry inside a while body) and their _grad
    are one span, the flash kernels under fused_attention/<i>/forward/
    attn_full.core/2 the other; a projection is in neither.  4 ms in the
    GDN span, work that needs 2 x 1 ms by bytes: 50%, bound by bytes; 7 ms
    in the attention span, work that needs 3.5 ms by operations: 50%."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/gated_delta_attention/30/forward/gdn.core/2/intra/pallas_call"}
  %dot.2 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/gated_delta_attention/30/forward/gdn.core/2/carry/while/body/dot_general"}
  %custom-call.3 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/60/forward/attn_full.core/2/jit(_flash_fwd_call)/pallas_call"}
  %dot.4 = f32[8]{0} add(%fusion.1, %a), metadata={op_name="jit(s)/forward/mul/33/forward/gdn.proj/2/dot_general"}
  ROOT %fusion.5 = f32[8]{0} multiply(%dot.4, %a), metadata={op_name="jit(s)/backward/gated_delta_attention_grad/90/backward/gdn.core/2/transpose(jvp(intra))/pallas_call"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics", "gdn_core_roofline.json")
    assert how["reader"] == "span_roofline"
    assert how["args"] == {"op": "gated_delta_attention", "span": "gdn.core",
                           "cost": "gdn_core_cost"}
    full = RUN.load_json(BENCH_DIR, "layer_metrics",
                         "full_attention_core_roofline.json")
    assert full["args"] == {"op": "fused_attention",
                            "span": "attn_full.core",
                            "cost": "full_core_cost"}
    ops = [("%fusion.1",
            (0.5e6, "", "", "forward/gated_delta_attention/30", set())),
           ("%dot.2",
            (0.5e6, "", "", "forward/gated_delta_attention/30", set())),
           ("%custom-call.3",
            (7e6, "", "", "forward/fused_attention/60", set())),
           ("%dot.4", (5e6, "", "", "forward/mul/33", set())),
           ("%fusion.5",
            (3e6, "", "", "backward/gated_delta_attention_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main(("gated_delta_attention", {}),
                              ("gated_delta_attention", {}),
                              ("fused_attention", {}), ("mul", {})),
           "adapter": types.SimpleNamespace(
               gdn_core_cost=lambda c, w: {"flops_step": 1e6,
                                           "bytes_step": 1e8},
               full_core_cost=lambda c, w: {"flops_step": 3.5e9,
                                            "bytes_step": 1e6}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("gdn_core_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "bound by bytes" in logged[0]
    assert _read("full_attention_core_roofline", ctx) == pytest.approx(50.0)
    assert "bound by operations" in logged[1]
    # a program without the op (any parent's): nothing to read, no raise
    ctx["main"] = _fake_main(("fused_attention", {}), ("mul", {}))
    assert _read("gdn_core_roofline", ctx) is None


def test_gdn_attention_ops_reads_its_count_on_the_rehearsal():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("gdn_attention_ops", ctx) == 3
    assert _read("kda_attention_ops", ctx) is None
    # Kimi-Linear's count stays its own
    cfg, work, adapter = load_cell("kimi_linear_48b_a3b_train")
    kimi = {"main": adapter.build(cfg, work)["main"]}
    assert _read("gdn_attention_ops", kimi) is None
    assert _read("kda_attention_ops", kimi) == 2
    assert _read("gdn_attention_ops", {}) is None


@pytest.mark.parametrize("metric", ["gdn_time_share", "gdn_core_time_share",
                                    "gdn_core_roofline",
                                    "full_attention_core_roofline"])
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
    assert line["metrics"]["gdn_attention_ops"]["value"] == 3.0
    assert "kda_attention_ops" not in line["metrics"]
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
