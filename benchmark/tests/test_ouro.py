"""The Ouro cell's own pieces, every registry entry looked up by name: the
adapter's copy of the reference against paddle_tpu/models/ouro_reference.py
(the departures too), its closed forms at the published sizes and against a
count over the Program, the configuration's cut, the new metrics' data
files and reader, and a rehearsal of the cell to its end."""

import json
import re

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start, load_cell

CELL, CONFIG = "ouro_2b6_train", "ouro_2b6"
NEW_METRICS = ("looped_layers_time_share", "exit_loss_time_share",
               "shared_grad_sum_time_share", "mean_exit_step")


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _weights(arch, rng):
    """Seeded weights in creation order (models/ouro_reference.py), with a
    gate that is not zero."""
    d, f, v = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    layer = [(d,), (d, d), (d, d), (d, d), (d, d), (d,), (d,), (d, f),
             (d, f), (f, d), (d,)]
    shapes = [(v, d)] + layer * arch["num_hidden_layers"] + [
        (d,), (d, v), (d,), (1,)]
    return [(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1.0)
             ).astype("float32") for s in shapes]


@pytest.mark.parametrize("departure", [None, "three_steps", "no_entropy",
                                       "gate_before_norm",
                                       "gate_at_last_step"])
def test_adapters_reference_is_the_models_reference(departure):
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same seeded weights and batch
    give the same loss (float32, 1e-6) and the same rows, with each
    departure too."""
    from paddle_tpu.models import ouro_reference

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    assert arch["total_ut_steps"] == 4 and arch["exit_entropy_beta"] == 0.1
    assert tuple(adapter.DEPARTURES) == tuple(ouro_reference.DEPARTURES)
    weights = _weights(arch, np.random.default_rng(0))
    batch = adapter.make_batch(cfg, work, 4)
    mine, rows = adapter.reference(
        cfg, [("w%d" % i, w) for i, w in enumerate(weights)], batch,
        departure)
    theirs, _ = ouro_reference.loss_and_grads(arch, weights, batch, departure)
    assert mine == pytest.approx(float(theirs), rel=1e-6)
    _, q = ouro_reference.token_cost(arch, weights, batch["ids"],
                                     batch["labels"], departure)
    steps = 3 if departure == "three_steps" else 4
    assert rows.shape == (int(work["batch"]), 2 * steps, int(work["seq_len"]))
    np.testing.assert_allclose(np.exp(rows[:, steps:]),
                               np.asarray(q).transpose(1, 0, 2), atol=2e-5)
    # the rows stand for the loss, but for the entropy's weight
    beta = 0.0 if departure == "no_entropy" else 0.1
    cost = (np.exp(rows[:, steps:].astype("float64"))
            * (rows[:, :steps] + beta * rows[:, steps:])).sum(1)
    assert float(cost.mean()) == pytest.approx(mine, rel=1e-5)
    if departure is not None:
        exact, _ = ouro_reference.loss_and_grads(arch, weights, batch)
        assert abs(mine - float(exact)) > 1e-3


def _forward_rows(cfg, work, adapter, seed=3, gate=0.3):
    """The forward-only program run once on seeded weights (a gate that
    is not zero) in a scope of its own -> (scope, program loss, params,
    batch)."""
    import paddle_tpu as fluid

    fwd = adapter.build(cfg, work, forward_only=True)
    fwd["startup"].random_seed = seed
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    batch = adapter.make_batch(cfg, work, seed)
    with fluid.scope_guard(scope):
        exe.run(fwd["startup"])
        d = cfg["hidden_size"]
        scope.set("ouro_exit_gate.w", (gate * np.random.default_rng(
            seed).standard_normal(d)).astype("float32"))
        got = float(np.asarray(exe.run(fwd["main"], feed=batch,
                                       fetch_list=[fwd["loss"]])[0]))
        params = [(p.name, np.asarray(scope.find_var(p.name)))
                  for p in fwd["main"].global_block().all_parameters()]
    return scope, got, params, batch


@pytest.mark.parametrize("departure, reading", [
    (None, None), ("three_steps", "cost_rms"), ("no_entropy", "loss"),
    ("gate_before_norm", "log_q_rms"), ("gate_at_last_step", "log_q_rms")])
def test_the_comparison_pairs_the_programs_rows_with_the_references(
        departure, reading, capfd):
    """loops/train.py takes |program loss - reference_loss(...)| inside the
    scope the forward-only program ran in: the adapter reads the program's
    rows there, and answers NaN where a paired reading is over its limit.
    At the rehearsal's widths (a float32 program: `use_bf16` off) the exact
    reference passes and each departure fails, by the reading that is
    there for it."""
    import paddle_tpu as fluid

    cfg, work, adapter = load_cell(CELL)
    cfg = dict(cfg, train=dict(cfg["train"], use_bf16=False))
    scope, got, params, batch = _forward_rows(cfg, work, adapter)
    with fluid.scope_guard(scope):
        ref = adapter.reference_loss(cfg, params, batch, departure)
        found = adapter.readings(
            adapter.program_rows(),
            adapter.reference(cfg, params, batch, departure)[1].astype(
                "float64"))
    logged = json.loads(capfd.readouterr().err.split(
        "ouro_lm reference: ", 1)[1].splitlines()[0])
    assert {k: logged[k] for k in found} == found
    assert set(found) == set(adapter.LIMITS)
    over = {k for k in found if found[k] > adapter.LIMITS[k]}
    if reading in adapter.LIMITS:
        assert reading in over and np.isnan(ref)
    else:
        assert not over
        assert (abs(got - ref) <= adapter.TOLERANCE) == (reading is None)
    assert not abs(got - ref) <= adapter.TOLERANCE or departure is None
    # on weights alone (no program's rows in the scope) it is the loss
    with fluid.scope_guard(fluid.Scope()):
        alone = adapter.reference_loss(cfg, params, batch, departure)
    assert alone == adapter.reference(cfg, params, batch, departure)[0]
    assert ref == alone or np.isnan(ref)


def test_the_comparison_refuses_rows_of_another_batch():
    import paddle_tpu as fluid

    cfg, work, adapter = load_cell(CELL)
    cfg = dict(cfg, train=dict(cfg["train"], use_bf16=False))
    scope, _, params, batch = _forward_rows(cfg, work, adapter)
    twice = {k: np.concatenate([v, v]) for k, v in batch.items()}
    with fluid.scope_guard(scope):
        with pytest.raises(ValueError, match="not of this batch"):
            adapter.reference_loss(cfg, params, twice)


def test_one_precision_down_is_its_own_computation():
    """`dtype` bfloat16 keeps every array of the reference in bfloat16 (no
    float32 table or constant promotes one back), and moves the rows."""
    import jax.numpy as jnp

    cfg, work, adapter = load_cell(CELL)
    arch = adapter._arch(cfg)
    weights = _weights(arch, np.random.default_rng(0))
    params = [("w%d" % i, w) for i, w in enumerate(weights)]
    batch = adapter.make_batch(cfg, work, 4)
    low = adapter.reference_trunk(cfg, params, batch, "bfloat16")
    assert all(part.dtype == jnp.bfloat16 for part in low)
    _, exact = adapter.reference(cfg, params, batch)
    _, rows = adapter.reference(cfg, params, batch, dtype="bfloat16",
                                trunk=low)
    assert rows.shape == exact.shape
    assert 1e-3 < np.sqrt(np.mean(np.square(rows[:, :4] - exact[:, :4]))) < 1.0


def test_closed_forms_at_the_published_sizes():
    """The numbers the issue and PERF.md quote, at L = 5 and 1 x 4096: a
    layer-pass is 136 M operations a token forward (projections 33.6, the
    T x T 33.6, the MLP 69.2), 20 passes; the head reads 16,384 rows, 805 M
    a token; 43.4 T a step, of which the head is 22.8 %."""
    cfg, work, adapter = load_cell(CELL, rehearse=False)
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"]) == (5, 4)
    rows = 4096.0
    part = {k: v / rows / 1e6 for k, v in
            adapter.forward_flops(cfg, work).items()}
    assert part["attention"] == pytest.approx(20 * (33.55 + 33.55), rel=1e-3)
    assert part["mlp"] == pytest.approx(20 * 69.21, rel=1e-3)
    assert part["head"] == pytest.approx(4 * 201.33, rel=1e-3)
    assert adapter.model_flops(cfg, work) == pytest.approx(43.39e12, rel=1e-3)
    assert part["head"] / sum(part.values()) == pytest.approx(0.228, abs=2e-3)
    assert adapter.work_units(adapter.make_batch(cfg, work, 1)) == rows


def test_closed_forms_are_a_count_over_the_program():
    """utils.flops.program_flops walks the forward program's ops (mul, the
    fused SwiGLU, fused_attention's full T x T, the head over the four
    steps' rows): the adapter's forward parts add up to the same number,
    at the rehearsal's sizes."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(CELL)
    main = adapter.build(cfg, work, forward_only=True)["main"]
    counted = program_flops(main, batch_hint=int(work["batch"]))
    assert sum(adapter.forward_flops(cfg, work).values()) == pytest.approx(
        counted, rel=1e-9)
    types_ = [op.type for op in main.global_block().ops]
    passes = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    assert types_.count("fused_attention") == passes
    assert types_.count("fused_swiglu") == passes
    assert types_.count("fused_linear_xent") == 1


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    cfg, _, _ = load_cell(CELL, rehearse=False)
    published = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 5632, "max_position_embeddings": 65536,
                 "max_window_layers": 48, "model_type": "ouro",
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "use_sliding_window": False,
                 "vocab_size": 49152}
    assert {k: cfg[k] for k in published} == published
    depth = cfg["num_hidden_layers"]
    assert 4 <= depth <= 6
    assert cfg["layer_types"] == ["full_attention"] * depth
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert "48 ->" in cfg["reduced"]["num_hidden_layers"]
    assert "15.75G" in cfg["reduced"]["num_hidden_layers"]
    assert {"exit_entropy_beta", "final norm inside the loop", "exit gate",
            "initialisation"} <= set(cfg["assumed"])
    assert "modeling_ouro.py" in cfg["assumed"]["final norm inside the loop"]
    entry = RUN.find(SPEC["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")


def test_registry_entries_are_found_by_name():
    cell = RUN.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_b1_s4096", 1)
    _, work, _ = load_cell(CELL, rehearse=False)
    assert (work["batch"], work["seq_len"], work["ring"]) == (1, 4096, 8)
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_mfu"
    reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], CELL)}
    assert reports >= set(NEW_METRICS) | {
        "attention_time_share", "head_time_share", "peak_hbm_gib",
        "mosaic_calls", "opt_time_share"}
    assert not reports & {"collective_bytes", "moe_time_share",
                          "fc_time_share"}
    e2e = {m["name"] for m in RUN.cell_metrics(SPEC["end_to_end"], CELL)}
    assert e2e == {"train_tokens_per_s", "train_mfu", "setup_s"}
    # the older cells report none of the new metrics
    for other in SPEC["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                    other["name"])}


@pytest.mark.parametrize("metric, scope, selected", [
    ("looped_layers_time_share", "forward/mul/12/forward/ut1/1", True),
    ("looped_layers_time_share",
     "backward/fused_attention_grad/900/backward/ut4/1", True),
    ("looped_layers_time_share", "forward/cast/3/forward/ut2.layer0/2", True),
    ("looped_layers_time_share", "forward/mul/12", False),
    ("looped_layers_time_share", "forward/mul/12/forward/exit/1", False),
    ("looped_layers_time_share", "forward/while/5/forward/mul/2", False),
    ("exit_loss_time_share", "forward/cumsum/400/forward/exit/1", True),
    ("exit_loss_time_share", "backward/concat_grad/520/backward/exit/1", True),
    ("exit_loss_time_share",
     "forward/fused_linear_xent/390/forward/exit/1", False),
    ("exit_loss_time_share",
     "backward/fused_linear_xent_grad/530/backward/exit/1", False),
    ("exit_loss_time_share", "forward/cumsum/400", False),
    ("shared_grad_sum_time_share", "backward/sum/1200", True),
    ("shared_grad_sum_time_share", "backward/sum/700/backward/ut3/1", False),
    ("shared_grad_sum_time_share", "backward/sum/700/backward/exit/1", False),
    ("shared_grad_sum_time_share", "backward/summary/700", False),
    ("shared_grad_sum_time_share", "", False),
])
def test_the_scope_shares_select_their_scopes(metric, scope, selected):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "scope_time_share"
    assert bool(re.compile(how["args"]["match"]).match(scope)) == selected
    # what the older shares match, they still match with a name scope on
    for older in ("head_time_share", "attention_time_share"):
        pat = re.compile(RUN.load_json(
            BENCH_DIR, "layer_metrics", older + ".json")["args"]["match"])
        bare = "/".join(scope.split("/")[:3])
        assert bool(pat.match(scope)) == bool(pat.match(bare))


def test_scope_paths_of_the_profile_reader_carry_the_name_scope():
    """readers/program_profile.py's scope_path, which this PR did not
    touch, reads the nested part core/trace.py writes."""
    prof = RUN.load_module("readers", "program_profile")
    assert prof.scope_path(
        "jit(program_step)/forward/mul/12/forward/ut1/1/dot_general"
    ) == "forward/mul/12/forward/ut1/1"
    assert prof.scope_path(
        "jit(program_step)/backward/sum/99/backward/ut2.layer0/2/add"
    ) == "backward/sum/99/backward/ut2.layer0/2"
    assert prof.scope_path(
        "jit(program_step)/backward/sum/99/add") == "backward/sum/99"


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


def test_mean_exit_step_comes_from_the_scope():
    """One step from the zero gate the model starts at: q = 1/2, 1/4, 1/8,
    1/8 at every token, so 1.875; the sum stops at its 64 steps, so a run
    reads the same whenever it looks."""
    ctx = _trained(CELL)
    logged = []
    ctx["log"] = logged.append
    assert _read("mean_exit_step", ctx) == pytest.approx(1.875, rel=1e-5)
    assert len(logged) == 2 and "first 1 steps" in logged[0]
    assert all("[0.5, 0.25, 0.125, 0.125]" in line for line in logged)
    early = np.asarray(ctx["scope"].find_var("ouro_exit_step_mean_early"))
    full = early / early[-1] * 64.0
    ctx["scope"].set("ouro_exit_step_mean_early", full.astype("float32"))
    import paddle_tpu as fluid

    with fluid.scope_guard(ctx["scope"]):
        cfg, work, adapter = load_cell(CELL)
        fluid.Executor(fluid.CPUPlace()).run(
            ctx["main"], feed=adapter.make_batch(cfg, work, 2), fetch_list=[])
    np.testing.assert_array_equal(
        np.asarray(ctx["scope"].find_var("ouro_exit_step_mean_early")), full)


def test_a_program_without_the_statistic_leaves_mean_exit_step_out():
    cfg, work, adapter = load_cell("gpt2_345m_train")
    ctx = {"main": adapter.build(cfg, work)["main"], "scope": object(),
           "work": work, "cfg": cfg, "log": [].append,
           "load_module": RUN.load_module}
    assert _read("mean_exit_step", ctx) is None
    assert _read("mean_exit_step", {"log": [].append}) is None


@pytest.mark.parametrize("metric", NEW_METRICS[:3])
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
    # the harness's comparison found the program's rows and paired them
    paired = json.loads(err.split("ouro_lm reference: ", 1)[1].splitlines()[0])
    assert 0 < paired["cost_rms"] and 0 < paired["log_q_rms"]
    line = json.loads(next(
        l for l in out.splitlines()
        if l.startswith("rehearsal line")).split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0
    assert 1.0 < line["metrics"]["mean_exit_step"]["value"] < 4.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0
