"""The kimi_linear cell's own pieces, every registry entry looked up by
name: the adapter's copy of the reference against
paddle_tpu/models/kimi_linear_reference.py, its closed forms at the
published sizes and against a count by hand and over the Program, the
configuration's cut against the catalog's numbers, the new metrics' data
files and readers, and a rehearsal of the cell to its end."""

import json
import re
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "kimi_linear_48b_a3b_train", "kimi_linear_48b_a3b"
NEW_METRICS = ("kda_time_share", "kda_core_time_share", "kda_core_roofline",
               "kda_attention_ops")
APPENDED = ("attention_time_share", "mla_time_share", "mla_core_roofline",
            "mla_rope_time_share", "latent_attention_ops", "moe_time_share",
            "moe_load_max_over_mean", "moe_dropped_share",
            "expert_matmul_roofline", "moe_rows_held_share",
            "moe_rows_run_share", "shared_expert_time_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order
    (models/kimi_linear_reference.py)."""
    d, v, la = arch["hidden_size"], arch["vocab_size"], arch[
        "linear_attn_config"]
    n, dh, taps = la["num_heads"], la["head_dim"], la[
        "short_conv_kernel_size"]
    h, r = arch["num_attention_heads"], arch["kv_lora_rank"]
    nope, rot, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                     arch["v_head_dim"])
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["num_experts"], arch["num_local_experts"]
    fs = arch["num_shared_experts"] * fe
    kda = ([(d, n * dh)] * 3 + [(d, dh), (dh, n * dh), (n * dh,), (d, dh),
                                (dh, n * dh), (d, n)]
           + [(n * dh, taps)] * 3 + [(n, 1), (dh,), (n * dh, d)])
    mla = [(d, h * (nope + rot)), (d, r + rot), (r,), (r, h * (nope + dv)),
           (h * dv, d)]
    shapes = [(v, d)]
    for i in range(arch["num_hidden_layers"]):
        shapes += [(d,)] + (kda if i + 1 in la["kda_layers"] else mla)
        shapes += [(d,)]
        shapes += ([(d, f), (d, f), (f, d)]
                   if i < arch["first_k_dense_replace"]
                   else [(d, e), (e,), (held, d, 2 * fe), (held, fe, d),
                         (d, fs), (d, fs), (fs, d)])
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    latent attention goes one head at a time; both run KDA as the
    recurrence): the same seeded weights and batch give the same loss
    (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import kimi_linear_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["num_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (8, 2, 2)
    assert work["seq_len"] % adapter.KDA_CHUNK  # the op pads
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = kimi_linear_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_kda_core_cost_is_a_count_by_hand():
    """One chunk of one head, product by product at C = 64, dk = dv = 128:
    A_kk, A_qk and the solve's W are [64, 128] x [128, 64] or [64, 64] x
    [64, 128]: 2 x 64 x 64 x 128 each; the solve's U0 and A_qk U the same
    at dv; W S, Q S and K^T U are [64, 128] x [128, 128]: 2 x 64 x 128 x
    128 each: 11,534,336 a chunk, 180,224 a token; 32 heads over 6,144
    tokens three times: 106.3 GFLOP a layer a step, over 0.9 GB: bound by
    operations."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    la = cfg["linear_attn_config"]
    c, dk, dv, h, t = 64, 128, 128, 32, int(work["seq_len"])
    assert (adapter.KDA_CHUNK, la["head_dim"], la["num_heads"],
            work["batch"], t) == (c, dk, h, 1, 6144)
    from paddle_tpu.ops import kda_ops
    assert kda_ops.CHUNK == adapter.KDA_CHUNK
    chunk = (3 * 2 * c * c * dk + 2 * 2 * c * c * dv + 3 * 2 * c * dk * dv)
    assert chunk == 11534336 and chunk // c == 180224
    cost = adapter.kda_core_cost(cfg, work)
    assert cost["flops_forward"] == h * t * 180224.0
    assert cost["flops_step"] == 3 * cost["flops_forward"]
    assert cost["flops_step"] == pytest.approx(106.3e9, rel=1e-3)
    # q, k, v, o in bf16, g in f32, beta: forward, and backward with the
    # gradients
    once = h * t * (4 * 128 * 2 + 128 * 4 + 4)
    assert cost["bytes_step"] == 3.0 * once
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert (cost["flops_step"] / peak["flops_per_s"]
            < cost["bytes_step"] / peak["hbm_bytes_per_s"])
    mla = adapter.mla_core_cost(cfg, work)
    assert mla["flops_forward"] == 2.0 * 32 * t * t / 2 * (192 + 128)


def test_closed_forms_at_the_published_sizes():
    """A forward token at 1 x 6144 in millions of operations: a KDA
    layer's projections 78.9 and core 5.8, the MLA layer's projections
    58.2 and core 62.9 (T = 6144), the dense MLP 127.4, a shared expert
    14.2, the held experts' 0.25 rows 3.5, a router 1.2, the head 94.4."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = float(work["seq_len"])
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["kda_projections"] == pytest.approx(4 * 78.92, rel=1e-3)
    assert part["kda_cores"] == pytest.approx(4 * 32 * 0.180224, rel=1e-6)
    assert part["mla_projections"] == pytest.approx(58.2, rel=2e-3)
    assert part["mla_core"] == pytest.approx(
        2.0 * 32 * rows / 2 * 320 / 1e6, rel=1e-6)
    assert part["dense_mlp"] == pytest.approx(127.4, rel=1e-3)
    assert part["shared_expert"] == pytest.approx(4 * 14.16, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 3.539, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 1.18, rel=1e-3)
    assert part["head"] == pytest.approx(94.37, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(
        3.0 * rows * 1e6 * sum(part.values()), rel=1e-9)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * (6144 * 8 * 8 / 256) * 2304 * 1024


def test_closed_forms_are_a_count_over_the_program_but_for_the_mla_core():
    """utils.flops.program_flops walks the forward program's ops: it counts
    a kda_attention as the adapter does and a fused_attention over the
    square where the adapter counts the causal half.  With the latent core
    taken off both, the two are the same number."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    b, t = int(work["batch"]), int(work["seq_len"])
    h = cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    walked_core = 2.0 * b * h * t * t * (d_qk + cfg["v_head_dim"])
    parts = adapter.forward_flops(cfg, work)
    assert parts["mla_core"] == walked_core / 2
    assert sum(parts.values()) - parts["mla_core"] == pytest.approx(
        program_flops(main, batch_hint=b) - walked_core, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("kda_attention") == 2
    assert types_.count("fused_attention") == 1
    assert types_.count("moe_ffn") == 2


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    """Every number of the catalog row's `config` under the same key, but
    the keys `reduced` names; inside linear_attn_config the widths stay."""
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4], "head_dim": 128, "kda_layers": [1, 2, 3, 5],
        "num_heads": 32, "short_conv_kernel_size": 4}
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
    assert {k: cfg[k] for k in cut} == cut
    names = set(cut) | {"linear_attn_config",
                        "linear_attn_config.kda_layers",
                        "linear_attn_config.full_attn_layers"}
    assert set(cfg["reduced"]) == names
    assert cfg["share"] == {"router_experts": 256, "expert_offset": 0}
    assert "thirty-two chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 163840 and 32 * 8 == 256
    assert cfg["train"] == {"learning_rate": 5e-6, "use_bf16": True,
                            "expert_bias_rate": 0.03,
                            "expert_bias_max_step": 0.03}
    assert "modeling_kimi.py" in cfg["assumed"]["the layer's equations"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == names
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")


def test_the_cells_traffic_is_the_issues():
    """1 x 6144 by the issue's memory rule (the compiler's counts are in
    PERF.md section 4), everything else to the letter."""
    _, work, _ = load_cell(CELL, rehearse=False)
    assert {k: work[k] for k in ("kind", "mesh", "batch", "seq_len", "ring",
                                 "warmup_steps", "readback_every")} == {
        "kind": "train", "mesh": None, "batch": 1, "seq_len": 6144,
        "ring": 8, "warmup_steps": 32, "readback_every": 10}


def test_the_build_hands_the_balancing_step_its_rate_and_bound():
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    updates = [op for op in main.global_block().ops
               if op.type == "expert_bias_update"]
    assert len(updates) == 2
    assert all((op.attrs["rate"], op.attrs["max_step"]) == (0.03, 0.03)
               for op in updates)


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s6144", 1)
    assert len(cell["why"]) <= 200 and "1/32 of deployed load" in cell["why"]
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
    assert "window_attention_time_share" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("scope, kda, core", [
    ("forward/mul/7/forward/kda.proj/2", True, False),
    ("forward/causal_conv/12/forward/kda.conv/2", True, False),
    ("forward/kda_attention/30/forward/kda.core/2", True, True),
    ("backward/kda_attention_grad/140/backward/kda.core/2", True, True),
    ("forward/rms_norm/33/forward/kda.out/2", True, False),
    ("forward/fused_attention/80/forward/mla.core/2", False, False),
    ("forward/mul/4", False, False),
    ("forward/mul/4/forward/kdas/1", False, False),
    ("forward/mul/4/forward/kda.cores/2", True, False),
    ("", False, False),
])
def test_the_kda_time_shares_select_their_scopes(scope, kda, core):
    for metric, selected in (("kda_time_share", kda),
                             ("kda_core_time_share", core)):
        how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
        assert how["reader"] == "scope_time_share"
        assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def _fake_main(*ops):
    ops = [types.SimpleNamespace(type=t, attrs=a) for t, a in ops]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_kda_core_roofline_reads_the_kda_cores_alone():
    """The data file's span and cost through readers/span_roofline.py on a
    made-up step of two KDA layers and a latent one: the device ops under
    kda_attention/<i>/forward/kda.core/2 (a fusion of the inside, a product
    of the carry inside a while body) and their _grad are the span; the
    latent core and a projection are not.  4 ms in the span, work that
    needs 2 x 1 ms by bytes: 50%, bound by bytes."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/kda_attention/30/forward/kda.core/2/while/body/intra/mul"}
  %dot.2 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/kda_attention/30/forward/kda.core/2/while/body/carry/while/body/dot_general"}
  %custom-call.3 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/60/forward/mla.core/2/jit(_flash_fwd_call)/pallas_call"}
  %dot.4 = f32[8]{0} add(%fusion.1, %a), metadata={op_name="jit(s)/forward/mul/33/forward/kda.proj/2/dot_general"}
  ROOT %fusion.5 = f32[8]{0} multiply(%dot.4, %a), metadata={op_name="jit(s)/backward/kda_attention_grad/90/backward/kda.core/2/while/body/transpose(jvp(intra))/mul"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics", "kda_core_roofline.json")
    assert how["reader"] == "span_roofline"
    assert how["args"] == {"op": "kda_attention", "span": "kda.core",
                           "cost": "kda_core_cost"}
    ops = [("%fusion.1", (0.5e6, "", "", "forward/kda_attention/30", set())),
           ("%dot.2", (0.5e6, "", "", "forward/kda_attention/30", set())),
           ("%custom-call.3",
            (7e6, "", "", "forward/fused_attention/60", set())),
           ("%dot.4", (5e6, "", "", "forward/mul/33", set())),
           ("%fusion.5",
            (3e6, "", "", "backward/kda_attention_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main(("kda_attention", {}), ("kda_attention", {}),
                              ("fused_attention", {}), ("mul", {})),
           "adapter": types.SimpleNamespace(kda_core_cost=lambda c, w: {
               "flops_step": 1e6, "bytes_step": 1e8}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("kda_core_roofline", ctx) == pytest.approx(50.0)
    assert len(logged) == 1 and "bound by bytes" in logged[0]
    # a program without the op (any parent's): nothing to read, no raise
    ctx["main"] = _fake_main(("fused_attention", {}), ("mul", {}))
    assert _read("kda_core_roofline", ctx) is None


def test_kda_attention_ops_reads_its_count_on_the_rehearsal():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("kda_attention_ops", ctx) == 2
    assert _read("latent_attention_ops", ctx) == 1
    # a program without the op, and no program
    cfg, work, adapter = load_cell("gpt2_345m_train")
    assert _read("kda_attention_ops",
                 {"main": adapter.build(cfg, work)["main"]}) is None
    assert _read("kda_attention_ops", {}) is None


@pytest.mark.parametrize("metric", ["kda_time_share", "kda_core_time_share",
                                    "kda_core_roofline"])
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
    assert line["metrics"]["kda_attention_ops"]["value"] == 2.0
    assert line["metrics"]["latent_attention_ops"]["value"] == 1.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
