"""The Laguna-XS.2 cell's own pieces, every registry entry looked up by
name: the adapter's copy of the reference against
paddle_tpu/models/laguna_reference.py, its closed forms PER LAYER KIND at
the published sizes, against a count by hand and over the Program, the
configuration's cut against the catalog's numbers, the five new metrics'
data files and the two new readers, the departures tool, and a rehearsal
of the cell to its end."""

import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT, RUN, SPEC, _start, load_cell

CELL, CONFIG = "laguna_xs2_33b_a3b_train", "laguna_xs2_33b_a3b"
NEW_METRICS = ("full_attention_time_share", "head_gate_time_share",
               "attention_rope_time_share", "scaled_rotary_ops",
               "global_attention_core_roofline")
APPENDED = ("attention_time_share", "window_attention_time_share",
            "window_attention_roofline", "windowed_attention_ops",
            "window_grid_live_share", "attention_pairs_computed_over_visible",
            "attention_block_fetches_over_tiles", "moe_time_share",
            "expert_matmul_roofline", "moe_load_max_over_mean",
            "moe_dropped_share", "moe_rows_held_share",
            "moe_rows_held_share_window", "moe_rows_held_share_range",
            "moe_rows_traced_over_expected", "moe_no_live_rows_share",
            "moe_rows_run_share", "moe_load_max_over_mean_window",
            "shared_expert_time_share", "amp_half_move_ops")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order (models/laguna_reference.py)."""
    d, v = arch["hidden_size"], arch["vocab_size"]
    kv, dh = arch["num_key_value_heads"], arch["head_dim"]
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["num_experts"], arch["num_local_experts"]
    fs = arch["shared_expert_intermediate_size"]
    shapes = [(v, d)]
    for h, mlp in zip(arch["num_attention_heads_per_layer"],
                      arch["mlp_layer_types"]):
        shapes += [(d,), (d, h * dh), (d, kv * dh), (d, kv * dh), (d, h),
                   (h * dh, d), (d,)]
        shapes += ([(d, f), (d, f), (f, d)] if mlp == "dense"
                   else [(d, e), (held, d, 2 * fe), (held, fe, d),
                         (d, fs), (d, fs), (fs, d)])
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same seeded weights and batch
    give the same loss (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import laguna_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["num_experts"], arch["num_local_experts"],
            arch["expert_offset"]) == (16, 4, 4)
    assert work["seq_len"] > arch["sliding_window"]
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = laguna_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_core_costs_are_counts_by_hand_at_each_kinds_heads():
    """Query i of a sliding layer sees min(i + 1, 512) keys: counted one
    query at a time, 3,014,912 pairs a head at T = 6144 (512 x 6144 - 512 x
    511 / 2), 16% of the causal half; a sliding core has 64 heads, a full
    one 48 over the causal half: four operations a pair and head width
    forward, three forwards a step."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    t, w, dh = 6144, 512, 128
    assert (work["batch"], work["seq_len"], cfg["sliding_window"],
            cfg["head_dim"]) == (1, t, w, dh)
    by_hand = sum(min(i + 1, w) for i in range(t))
    assert by_hand == 3014912 == w * t - w * (w - 1) // 2
    assert adapter.core_pairs(t, w) == by_hand
    assert by_hand / (t * t / 2.0) == pytest.approx(0.1597, abs=1e-4)
    window, full = (adapter.window_core_cost(cfg, work),
                    adapter.full_core_cost(cfg, work))
    assert window["flops_forward"] == 4.0 * 64 * by_hand * dh
    assert full["flops_forward"] == 4.0 * 48 * (t * t / 2.0) * dh
    for cost, heads in ((window, 64), (full, 48)):
        assert cost["flops_step"] == 3 * cost["flops_forward"]
        assert cost["bytes_step"] == 2.0 * heads * t * 8 * dh
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    for cost in (window, full):  # both bound by operations
        assert (cost["flops_step"] / peak["flops_per_s"]
                > cost["bytes_step"] / peak["hbm_bytes_per_s"])
    # a kind whose layers differ in heads has no one cost
    mixed = dict(cfg, num_attention_heads_per_layer=[48, 64, 64, 32, 48])
    with pytest.raises(ValueError, match="one number a kind"):
        adapter.window_core_cost(mixed, work)


def test_closed_forms_at_the_published_sizes():
    """A forward token at 1 x 6144 in millions of operations: a full
    layer's projections 58.9 (q and o at 6144, the gate 48 wide), a sliding
    layer's 75.8 (8192, 64), a sliding core 16.1 and a full one 75.5, the
    dense MLP 100.7, a shared expert 6.3, the held experts' 1 row 6.3, a
    router 1.0, the head 51.4: 751 M, 13.8 T a step."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    rows = 6144.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["attn_projections"] == pytest.approx(
        2 * 58.92 + 3 * 75.76, rel=1e-3)
    assert part["window_cores"] == pytest.approx(3 * 16.08, rel=1e-3)
    assert part["full_cores"] == pytest.approx(2 * 75.50, rel=1e-3)
    assert part["dense_mlp"] == pytest.approx(100.66, rel=1e-3)
    assert part["shared_expert"] == pytest.approx(4 * 6.291, rel=1e-3)
    assert part["experts"] == pytest.approx(4 * 6.291, rel=1e-3)
    assert part["router"] == pytest.approx(4 * 1.049, rel=1e-3)
    assert part["head"] == pytest.approx(51.38, rel=1e-3)
    assert sum(part.values()) == pytest.approx(750.9, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(13.84e12, rel=1e-3)
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 6144 * 2048 * 512


def test_closed_forms_are_a_count_over_the_program_but_for_the_cores():
    """utils.flops.program_flops walks the forward program's ops and
    counts a fused_attention over Tq x Tk (Tq x window under a window) at
    the op's own heads; the adapter counts the pairs a query may see.  With
    the cores taken off both, the two are the same number: every
    projection at its layer's heads, the gate [d, H_l] among them."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    b, t = int(work["batch"]), int(work["seq_len"])
    dh, w = cfg["head_dim"], cfg["sliding_window"]
    assert cfg["num_attention_heads_per_layer"] == [6, 8, 8, 8, 6]
    walked_cores = 2.0 * b * t * (3 * 8 * w + 2 * 6 * t) * 2 * dh
    parts = adapter.forward_flops(cfg, work)
    cores = parts["window_cores"] + parts["full_cores"]
    assert cores == 2.0 * b * (3 * 8 * adapter.core_pairs(t, w)
                               + 2 * 6 * t * t / 2.0) * 2 * dh
    assert sum(parts.values()) - cores == pytest.approx(
        program_flops(main, batch_hint=b) - walked_cores, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    assert types_.count("fused_attention") == 5
    assert types_.count("moe_ffn") == 4


def test_configuration_keeps_the_published_numbers_and_states_its_cut():
    """Every key of the catalog row's `config` under the same key with the
    same value (nested groups whole), but the six keys `reduced` names; no
    width among them."""
    cfg, _, _ = load_cell(CELL, rehearse=False)
    cut = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544,
           "layer_types": ["full_attention"] + ["sliding_attention"] * 3
           + ["full_attention"],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "num_attention_heads_per_layer": [48, 64, 64, 64, 48]}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Laguna-XS.2"]
        for key, value in row["config"].items():
            if key in cut:
                if isinstance(value, list):  # the lists' first five
                    assert cfg[key] == value[:5], key
            else:
                assert cfg[key] == value, key
        assert row["source_url"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["sliding_window"],
            cfg["num_key_value_heads"], cfg["num_experts_per_tok"]) == (
                2048, 128, 8192, 512, 512, 512, 8, 8)
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert cfg["share"] == {"router_experts": 256, "expert_offset": 0}
    assert "eight chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 100352 and 8 * 32 == 256
    assert cfg["train"] == {"learning_rate": 5e-6, "use_bf16": True}
    first = next(iter(cfg["assumed"].values()))
    assert "modeling_laguna.py was NOT at hand" in first
    assert {"gating", "the router's score", "norms", "activation"} <= set(
        cfg["assumed"])
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == set(cut)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert len(entry["why"]) <= 200


def test_the_state_is_what_the_cut_says():
    """691.6 M parameters at the published widths, counted over the built
    Program: 11.07 GB at 16 bytes a parameter."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    main = adapter.build(dict(cfg), dict(work, seq_len=512),
                         forward_only=True)["main"]
    count = sum(int(np.prod(p.shape))
                for p in main.global_block().all_parameters())
    assert count == 691_623_936
    assert 16 * count / 1e9 == pytest.approx(11.07, abs=0.01)


def test_the_cells_traffic_is_the_issues():
    """seq_len by ISSUE 45's rule: the largest of 8192 / 6144 / 4096 whose
    step the TPU compiler counts at or under 15.0 GiB (15.20 / 14.51 /
    12.38: tools/compile_cell_for_chip.py, PR 65)."""
    _, work, _ = load_cell(CELL, rehearse=False)
    assert {k: work[k] for k in (
        "kind", "mesh", "batch", "seq_len", "ring", "warmup_steps",
        "readback_every", "trace_steps", "reference_rows")} == {
        "kind": "train", "mesh": None, "batch": 1, "seq_len": 6144,
        "ring": 8, "warmup_steps": 32, "readback_every": 10,
        "trace_steps": 12, "reference_rows": 1}


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s6144", 1)
    assert len(cell["why"]) <= 200 and "no peers" in cell["why"]
    assert SPEC["workloads"][-1] is cell and len(SPEC["workloads"]) == 15
    assert SPEC["configs"][-1]["name"] == CONFIG
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    assert [m["name"] for m in SPEC["per_layer"][-5:]] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
        assert per_layer[name]["layer"] == "Op lowerings + kernels"
        assert set(per_layer[name]) == {"name", "unit", "better", "source",
                                        "layer", "moves", "workloads"}
    for name in APPENDED:
        assert per_layer[name]["workloads"][-1] == CELL
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | set(APPENDED) | {"head_time_share"}
    assert "collective_bytes" not in reports
    assert "mla_time_share" not in reports
    assert "full_attention_core_roofline" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("metric, scope, selected", [
    ("full_attention_time_share", "forward/fc/7/forward/attn_full/1", True),
    ("full_attention_time_share",
     "backward/fused_attention_grad/140/backward/attn_full.core/2", True),
    ("full_attention_time_share",
     "forward/rotary_embed/12/forward/attn_full.rope/2", True),
    ("full_attention_time_share",
     "forward/fc/30/forward/attn_window/1", False),
    ("full_attention_time_share", "forward/fc/4/forward/attn_fuller/1",
     False),
    ("full_attention_time_share", "forward/fc/4", False),
    ("head_gate_time_share",
     "forward/sigmoid/30/forward/attn_window.attn_gate/2", True),
    ("head_gate_time_share", "forward/fc/29/forward/attn_full.attn_gate/2",
     True),
    ("head_gate_time_share",
     "backward/elementwise_mul_grad/99/backward/attn_full.attn_gate/2", True),
    ("head_gate_time_share", "forward/fc/29/forward/attn_full/1", False),
    ("head_gate_time_share", "forward/sigmoid/3/forward/gdn.attn_gate/2",
     False),
    ("attention_rope_time_share",
     "forward/rotary_embed/12/forward/attn_window.rope/2", True),
    ("attention_rope_time_share",
     "backward/concat_grad/77/backward/attn_full.rope/2", True),
    ("attention_rope_time_share",
     "forward/rotary_embed/12/forward/mla.rope/2", False),
    ("attention_rope_time_share",
     "forward/fused_attention/12/forward/attn_full.core/2", False),
    ("attention_rope_time_share", "", False),
])
def test_scope_time_shares_select_their_scopes(metric, scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def test_the_lowered_scopes_are_what_the_matches_expect():
    """The name scopes of the rehearsal's Program, as core/trace.py joins
    them for the lowered step, against the three matches."""
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    paths = set()
    for i, op in enumerate(main.global_block().ops):
        under = op.attrs.get("op_namescope")
        if under:
            role = op.attrs.get("op_role", "forward")
            parts = under.split("/")
            paths.add("%s/%s/%d/%s/%s/%d" % (role, op.type, i, role,
                                             ".".join(parts), len(parts)))
    for metric, some in (("full_attention_time_share", "attn_full"),
                         ("head_gate_time_share", "attn_gate"),
                         ("attention_rope_time_share", "rope")):
        how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
        match = re.compile(how["args"]["match"])
        hit = [p for p in paths if match.match(p)]
        assert hit and all(some in p for p in hit), metric
        assert {p.split("/")[0] for p in hit} == {"forward", "backward"}


def _fake_main(*ops):
    ops = [types.SimpleNamespace(type=t, attrs=a) for t, a in ops]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def test_global_attention_core_roofline_reads_the_full_cores_alone():
    """The data file's span and cost through
    readers/span_roofline_unattr.py on a made-up step of two window layers
    and a full one: the kernels under fused_attention/<i>/forward/
    attn_full.core/2 and their _grad are the span, the window layers' cores
    and the output projection are not, and the work is counted for the ONE
    op that carries no window, not the three fused_attention ops.  8 ms in
    the span, work that needs 1 x 2 ms by bytes: 25%, bound by bytes."""
    text = """HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %custom-call.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/30/forward/attn_window.core/2/jit(_flash_fwd_call)/pallas_call"}
  %custom-call.2 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/forward/fused_attention/60/forward/attn_full.core/2/jit(_flash_fwd_call)/pallas_call"}
  %dot.3 = f32[8]{0} add(%custom-call.1, %a), metadata={op_name="jit(s)/forward/mul/63/forward/attn_full/1/dot_general"}
  ROOT %custom-call.4 = f32[8]{0} multiply(%dot.3, %a), metadata={op_name="jit(s)/backward/fused_attention_grad/90/backward/attn_full.core/2/jit(_flash_bwd_call)/pallas_call"}
}
"""
    how = RUN.load_json(BENCH_DIR, "layer_metrics",
                        "global_attention_core_roofline.json")
    assert how["reader"] == "span_roofline_unattr"
    assert how["args"] == {"op": "fused_attention", "span": "attn_full.core",
                           "cost": "full_core_cost", "attr": "window"}
    ops = [("%custom-call.1",
            (1e6, "", "", "forward/fused_attention/30", set())),
           ("%custom-call.2",
            (3e6, "", "", "forward/fused_attention/60", set())),
           ("%dot.3", (7e6, "", "", "forward/mul/63", set())),
           ("%custom-call.4",
            (5e6, "", "", "backward/fused_attention_grad/90", set()))]
    logged = []
    ctx = {"program_profile": {"device_ops": ops, "steps": 1},
           "hlo_texts": [text],
           "main": _fake_main(("fused_attention", {"window": 512}),
                              ("fused_attention", {"window": 512}),
                              ("fused_attention", {"window": 0}),
                              ("mul", {})),
           "adapter": types.SimpleNamespace(full_core_cost=lambda c, w: {
               "flops_step": 1e9, "bytes_step": 2e8}),
           "cfg": {}, "work": {}, "log": logged.append,
           "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "load_module": RUN.load_module}
    assert _read("global_attention_core_roofline", ctx) == pytest.approx(25.0)
    assert len(logged) == 1 and "1 ops" in logged[0]
    assert "bound by bytes" in logged[0]
    # together with span_roofline_attr's count the two cover every op
    attr = RUN.load_module("readers", "span_roofline_attr")
    assert attr.read(ctx, "fused_attention", "attn_window.core",
                     "full_core_cost", "window") == pytest.approx(
                         100.0 * 2 * 2e-3 / 1e-3)
    # a program whose every core carries a window: nothing to read, no raise
    ctx["main"] = _fake_main(("fused_attention", {"window": 8}), ("mul", {}))
    assert _read("global_attention_core_roofline", ctx) is None
    ctx["main"] = _fake_main(("mul", {}))
    assert _read("global_attention_core_roofline", ctx) is None


def test_scaled_rotary_ops_reads_four_on_the_rehearsal():
    cfg, work, adapter = load_cell(CELL)
    ctx = {"main": adapter.build(cfg, work)["main"]}
    assert _read("scaled_rotary_ops", ctx) == 4.0
    assert _read("windowed_attention_ops", ctx) == 3.0
    # a program whose rotary is plain everywhere, and two without any
    cfg, work, adapter = load_cell("olmoe_1b7b_train")
    assert _read("scaled_rotary_ops",
                 {"main": adapter.build(cfg, work)["main"]}) == 0
    cfg, work, adapter = load_cell("gpt2_345m_train")
    assert _read("scaled_rotary_ops",
                 {"main": adapter.build(cfg, work)["main"]}) is None
    assert _read("scaled_rotary_ops", {}) is None


@pytest.mark.parametrize("metric", ["full_attention_time_share",
                                    "head_gate_time_share",
                                    "attention_rope_time_share",
                                    "global_attention_core_roofline"])
def test_without_a_trace_the_trace_metrics_are_left_out(metric):
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []


def test_the_departures_tool_takes_the_cell():
    """tools/kanana2_departures.py --workload laguna_xs2_33b_a3b_train
    --rehearse: the plumbing the chip run uses (the adapter's `compare`,
    `bf16_unit`, DEPARTURES, LIMITS), at the rehearsal's sizes; its
    readings mean nothing, its lines name every departure."""
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kanana2_departures.py"),
         "--workload", CELL, "--steps", "4", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900)
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert lines, proc.stderr[-2000:]
    _, _, adapter = load_cell(CELL)
    assert set(lines[0]["passes"]) == set(adapter.DEPARTURES) | {
        "exact", "all_bfloat16"}
    assert lines[0]["passes"]["exact"]


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
    assert line["metrics"]["windowed_attention_ops"]["value"] == 3.0
    assert line["metrics"]["scaled_rotary_ops"]["value"] == 4.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
