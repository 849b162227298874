"""The JoyAI-LLM-Flash cell's own pieces, every registry entry looked up by
name: the adapter's copy of the reference against
paddle_tpu/models/joyai_flash_reference.py, its closed forms at the
published sizes and against a count over the Program, the configuration's
cut, the new metrics' data files, and a rehearsal of the cell to its
end."""

import json
import re

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT, RUN, SPEC, _start, load_cell

CELL, CONFIG = "joyai_flash_48b_a3b_train", "joyai_flash_48b_a3b"
NEW_METRICS = ("mtp_time_share", "mla_q_latent_time_share", "mtp_modules")
APPENDED = (
    "moe_time_share", "attention_time_share", "moe_load_max_over_mean",
    "moe_dropped_share", "expert_matmul_roofline", "moe_rows_held_share",
    "mla_time_share", "mla_core_roofline", "shared_expert_time_share",
    "latent_attention_ops", "moe_rows_run_share", "mla_rope_time_share",
    "moe_rows_held_share_window", "moe_rows_traced_over_expected",
    "moe_no_live_rows_share", "moe_rows_held_share_range",
    "moe_load_max_over_mean_window", "attention_pairs_computed_over_visible",
    "attention_block_fetches_over_tiles", "amp_half_move_ops",
    "shared_grad_sum_time_share")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _shapes(arch):
    """The parameters in creation order
    (models/joyai_flash_reference.py)."""
    d, v, h = (arch["hidden_size"], arch["vocab_size"],
               arch["num_attention_heads"])
    r, rq = arch["kv_lora_rank"], arch["q_lora_rank"]
    nope, rot, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                     arch["v_head_dim"])
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    e, held = arch["n_routed_experts"], arch["num_local_experts"]
    fs = arch["n_shared_experts"] * fe
    mla = [(d,), (d, rq), (rq,), (rq, h * (nope + rot)), (d, r + rot), (r,),
           (r, h * (nope + dv)), (h * dv, d), (d,)]
    moe = [(d, e), (e,), (held, d, 2 * fe), (held, fe, d), (d, fs), (d, fs),
           (fs, d)]
    shapes = [(v, d)]
    for i in range(arch["num_hidden_layers"]):
        shapes += mla + ([(d, f), (d, f), (f, d)]
                         if i < arch["first_k_dense_replace"] else moe)
    shapes += [(d,), (d,), (d,), (2 * d, d)] + mla + moe
    return shapes + [(d,), (d, v)]


def test_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same seeded weights and batch
    give the same loss (float32, 1e-6); each departure gives another."""
    from paddle_tpu.models import joyai_flash_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert (arch["n_routed_experts"], arch["num_local_experts"],
            arch["expert_offset"], arch["mtp_loss_weight"]) == (8, 2, 2, 0.3)
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
                ).astype("float32") for s in _shapes(arch)]
    batch = adapter.make_batch(cfg, work, 4)
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    mine = adapter.reference_loss(cfg, params, batch)
    theirs, _ = joyai_flash_reference.loss_and_grads(arch, weights, batch)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    for departure in adapter.DEPARTURES:
        wrong = adapter.reference_loss(cfg, params, batch, departure)
        assert abs(wrong - mine) > 1e-3, departure
    with pytest.raises(ValueError, match="unknown departure"):
        adapter.reference_loss(cfg, params, batch, "no_such_error")


def test_closed_forms_at_the_published_sizes():
    """The numbers PERF.md quotes, a forward token at 1 x 6144 over six
    blocks: the query latent 25.2 M a block, latent attention's other
    projections 27.5, its core over the causal half 62.9, the dense MLP
    88.1, the shared expert 9.4, the held experts' 0.5 rows 4.7, the
    router 1.0, the combine 16.8, the head twice 66.2: 1,007 M, 18.55 T a
    step."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    assert (work["batch"], work["seq_len"]) == (1, 6144)
    rows = 6144.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["mla_q_latent"] == pytest.approx(6 * 25.17, rel=1e-3)
    assert part["mla_projections"] == pytest.approx(6 * 27.53, rel=1e-3)
    assert part["mla_core"] == pytest.approx(6 * 62.91, rel=1e-3)
    assert part["dense_mlp"] == pytest.approx(88.08, rel=1e-3)
    assert part["shared_expert"] == pytest.approx(5 * 9.437, rel=1e-3)
    assert part["experts"] == pytest.approx(5 * 4.719, rel=1e-3)
    assert part["router"] == pytest.approx(5 * 1.049, rel=1e-3)
    assert part["mtp_combine"] == pytest.approx(16.78, rel=1e-3)
    assert part["head"] == pytest.approx(2 * 66.19, rel=1e-3)
    assert sum(part.values()) == pytest.approx(1006.9, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(18.56e12, rel=1e-3)
    # one core's work; the accepted reader is handed the trunk's five
    # spread over the six ops it counts (mla_core_cost says why)
    assert adapter._mla_core(cfg, work)["flops_step"] == (
        3 * 2.0 * 32 * 6144 * 6144 / 2 * 320)
    assert 6 * adapter.mla_core_cost(cfg, work)["flops_step"] == (
        pytest.approx(5 * adapter._mla_core(cfg, work)["flops_step"]))
    cost = adapter.expert_matmul_cost(cfg, work)
    assert cost["flops_step"] == 18.0 * 3072 * 2048 * 768
    assert adapter.work_units(adapter.make_batch(cfg, work, 1)) == 6144.0


def test_closed_forms_are_a_count_over_the_program_but_for_the_causal_half():
    """utils.flops.program_flops walks the forward program's ops (the
    module's block, combine and head rows among them) and counts
    fused_attention over the full T x T; the adapter counts the causal
    half.  With half of the walk's cores taken off, the same number."""
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
    cfg, _, adapter = load_cell(CELL, rehearse=False)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16,
           "vocab_size": 16160}
    assert {k: cfg[k] for k in cut} == cut
    assert set(cfg["reduced"]) == set(cut)
    try:
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
    except OSError:
        row = None
    if row is not None:  # every number of the catalog's config, or cut
        assert {k: v for k, v in row["config"].items()
                if k not in cut} == published
    assert cfg["share"] == {"router_experts": 256, "expert_offset": 0}
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert 8 * cfg["vocab_size"] == 129280 and 16 * 16 == 256
    for said in ("modeling code", "the state the module reads",
                 "the combine's order", "lambda", "e_score_correction_bias",
                 "learning_rate", "use_bf16", "auxiliary loss",
                 "document mask"):
        assert said in cfg["assumed"], said
    assert cfg["train"] == {
        "learning_rate": 5e-6, "use_bf16": True, "expert_bias_rate": 0.03,
        "expert_bias_max_step": 0.03, "mtp_loss_weight": 0.3}
    # the parameter count the file states is the built program's
    main = adapter.build(cfg, {"seq_len": 64})["main"]
    params = sum(int(np.prod(p.shape))
                 for p in main.global_block().all_parameters())
    assert params == 680441088
    assert "680,441,088 parameters" in cfg["reduced"]["num_hidden_layers"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert entry["reduced"] == list(cut)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    assert 1 <= len(entry["why"]) <= 200


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s6144", 1)
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"]
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | set(APPENDED) | {"head_time_share"}
    assert "collective_bytes" not in reports
    assert "kda_time_share" not in reports
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


def test_the_benchmark_holds_all_that_the_parents_held_in_its_place():
    """BENCHMARK.json against PR 61's parent's (git show, where the
    checkout is a git repository): every list starts with what the
    parent's held, entry for entry, a metric's `workloads` included; what
    PR 61 added comes after (one configuration, one cell, three per-layer
    metrics, this cell's name on the lists it joins)."""
    import subprocess

    try:
        old = json.loads(subprocess.run(
            ["git", "show", "98ac4f8:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    assert {k: v for k, v in SPEC.items() if not isinstance(v, list)} == {
        k: v for k, v in old.items() if not isinstance(v, list)}
    joined = []
    for key in ("command", "paths", "configs", "workloads", "end_to_end",
                "per_layer"):
        for was, now in zip(old[key], SPEC[key]):
            if isinstance(was, dict) and "workloads" in was:
                had = len(was["workloads"])
                assert now["workloads"][:had] == was["workloads"]
                if now["workloads"][had:had + 1] == [CELL]:
                    joined.append(now["name"])
                now = dict(now, workloads=was["workloads"])
            assert now == was
        assert len(SPEC[key]) >= len(old[key])
    assert sorted(joined) == sorted(APPENDED + ("train_tokens_per_s",))
    assert SPEC["configs"][len(old["configs"])]["name"] == CONFIG
    assert SPEC["workloads"][len(old["workloads"])]["name"] == CELL
    first = len(old["per_layer"])
    assert [m["name"] for m in SPEC["per_layer"][first:first + 3]] == list(
        NEW_METRICS)


@pytest.mark.parametrize("metric, scope, selected", [
    ("mtp_time_share", "forward/mul/90/forward/mtp.combine/2", True),
    ("mtp_time_share", "forward/lookup_table/88/forward/mtp/1", True),
    ("mtp_time_share",
     "backward/fused_attention_grad/140/backward/mtp.mla.core/3", True),
    ("mtp_time_share", "forward/moe_ffn/99/forward/mtp/1", True),
    ("mtp_time_share", "forward/mul/7/forward/mla.down/2", False),
    ("mtp_time_share", "forward/fused_linear_xent/120", False),
    ("mtp_time_share", "backward/sum/300", False),
    ("mtp_time_share", "forward/mul/4/forward/mtpx/1", False),
    ("mla_q_latent_time_share", "forward/mul/7/forward/mla.q_latent/2", True),
    ("mla_q_latent_time_share",
     "backward/rms_norm_grad/200/backward/mtp.mla.q_latent/3", True),
    ("mla_q_latent_time_share", "forward/mul/9/forward/mla.down/2", False),
    ("mla_q_latent_time_share", "forward/mul/9/forward/mtp.combine/2", False),
    # the accepted metrics read a path that STARTS at the scope: here the
    # trunk's layers, the module's being mtp_time_share's
    ("mla_time_share", "forward/mul/7/forward/mla.q_latent/2", True),
    ("mla_time_share", "forward/mul/90/forward/mtp.mla.down/3", False),
    ("shared_expert_time_share",
     "forward/fused_swiglu/95/forward/mtp.shared_expert/2", False),
    ("shared_grad_sum_time_share", "backward/sum/300", True),
    ("shared_grad_sum_time_share", "backward/sum/300/backward/mtp/1", False),
])
def test_scope_time_shares_select_their_scopes(metric, scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected


def test_the_lowered_scopes_are_what_the_matches_expect():
    """The scope paths of the built program's ops, as core/trace.py joins
    them (nested scopes with "."), hold the parts the data files match."""
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    scopes = {(op.attrs.get("op_namescope") or "").replace("/", ".")
              for op in main.global_block().ops}
    assert {"mtp", "mtp.combine", "mtp.mla.q_latent", "mla.q_latent",
            "mtp.mla.core", "mla.core", "mtp.shared_expert"} <= scopes


def test_mtp_modules_reads_the_programs_attribute():
    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work)["main"]
    assert main._mtp == {"modules": 1, "rows": int(work["seq_len"]) - 1}
    assert _read("mtp_modules", {"main": main}) == 1.0
    assert _read("latent_attention_ops", {"main": main}) == 3.0
    assert _read("amp_half_move_ops", {"main": main}) == 10.0
    # a program without a module, and no program: nothing to read
    cfg, work, adapter = load_cell("kanana2_30b_a3b_train")
    assert _read("mtp_modules",
                 {"main": adapter.build(cfg, work)["main"]}) is None
    assert _read("mtp_modules", {}) is None


@pytest.mark.parametrize("metric", ["mtp_time_share",
                                    "mla_q_latent_time_share"])
def test_without_a_trace_the_trace_metrics_are_left_out(metric):
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    assert _read(metric, ctx) is None and logged == []


def test_the_departures_tool_takes_the_cell():
    """tools/kanana2_departures.py --workload <cell> --rehearse: the
    harness's comparison against the exact reference, each wrong one and
    the all-bfloat16 one, at the rehearsal sizes (its readings mean
    nothing there: bf16 rounding at 64 lanes is a large part of the
    unit)."""
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kanana2_departures.py"),
         "--workload", CELL, "--steps", "3", "--rehearse"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert proc.stdout.strip(), proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["abs_diff"]["exact"] < 2e-3
    assert set(line["passes"]) == {"exact", "all_bfloat16"} | set(
        RUN.load_module("adapters", "joyai_flash_lm").DEPARTURES)
    assert not line["passes"]["no_mtp_loss"]
    assert not line["passes"]["mtp_loss_weight_one"]


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
    assert line["metrics"]["mtp_modules"]["value"] == 1.0
    assert 0.0 < line["metrics"]["moe_rows_held_share"]["value"] < 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
