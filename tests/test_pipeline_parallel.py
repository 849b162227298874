"""Pipeline-parallel training (docs/PERFORMANCE.md §"Pipeline
parallelism"): ``pipeline_program`` slices a built train program into S
stage sub-programs at detect_segments boundaries, drives a GPipe or
1F1B microbatch schedule as one lax.scan inside shard_map over a
dp x pp mesh, and reuses the program's own optimizer slice per stage.

Exactness contract: pp=1 returns the program UNTOUCHED (bit-identical
trajectory); pp>=2 holds rtol<=1e-5 loss parity vs the unpipelined
program over >=5 steps WITH DROPOUT LIVE (the microbatch_rows RNG
window makes per-microbatch masks bit-equal to the full-batch draw);
both schedules agree with each other; ZERO retraces after the first
step.  1F1B's stash is O(S) while GPipe's is O(M) — the activation
report must order them strictly at M > 2S-1.

Structural tests (plan slicing, reports, verifier diagnostics, the
autotune knob) ride the fast suite; everything that compiles a
schedule is @slow and runs in the ci.sh pipeline lane (-m "").
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
import paddle_tpu.framework as fw
from paddle_tpu import flags
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.models import gpt2
from paddle_tpu.parallel import make_mesh
from paddle_tpu.transpiler.pipeline import (
    build_pipeline_plan,
    pipeline_activation_report,
    pipeline_program,
    pipeline_state_report,
)

needs_four_devices = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=4")


class TinyHP(gpt2.GPT2Config):
    vocab_size = 64
    n_ctx = 16
    d_model = 32
    n_layer = 2
    n_head = 4
    d_inner = 64
    dropout = 0.1  # LIVE: the parity bar covers the RNG window
    tie_embeddings = False


class SixLayerHP(TinyHP):
    n_layer = 6


def _fresh():
    fw.switch_main_program(fluid.Program())
    fw.switch_startup_program(fluid.Program())
    scope_mod._switch_scope(scope_mod.Scope())


def _build(hp=TinyHP, seq=8, use_bf16=False):
    _fresh()
    return gpt2.gpt2_lm_program(hp, seq_len=seq, lr=3e-3,
                                use_bf16=use_bf16)


def _train(mesh=None, schedule="gpipe", M=4, steps=5, batch=8, seq=8,
           hp=TinyHP, use_bf16=False, extra_flags=None):
    """Fresh scope+programs, `steps` Adam steps on per-step-varying
    fake-LM batches; returns (losses, main, executor)."""
    _fresh()
    old = {k: flags.get_flag(k) for k in (extra_flags or {})}
    flags.set_flags(extra_flags or {})
    try:
        main, startup, feeds, fetches = gpt2.gpt2_lm_program(
            hp, seq_len=seq, lr=3e-3, use_bf16=use_bf16)
        if mesh is not None:
            main = pipeline_program(main, mesh, n_microbatches=M,
                                    schedule=schedule)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        for step in range(steps):
            fb = gpt2.make_fake_lm_batch(batch, seq, hp, seed=step)
            out = exe.run(main, feed=fb, fetch_list=fetches)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        return losses, main, exe
    finally:
        flags.set_flags(old)


def _max_rel(a, b):
    return max(abs(x - y) / abs(y) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# structural: plan slicing (fast suite)
# ---------------------------------------------------------------------------
def test_plan_slices_cover_forward_and_route_hops():
    main, _, feeds, fetches = _build()
    plan = build_pipeline_plan(main, 2, 4, "gpipe")
    assert plan.n_stages == 2 and plan.n_microbatches == 4
    # stage ranges partition the forward region exactly
    assert plan.stage_ranges[0][0] == 0
    assert plan.stage_ranges[-1][1] == plan.fwd_end
    for (a, b), (c, d) in zip(plan.stage_ranges, plan.stage_ranges[1:]):
        assert b == c
    # every cross-stage read resolves through the previous stage's hops
    assert plan.boundary_in[0] == []
    assert set(plan.boundary_in[1]) <= set(plan.boundary_out[0])
    # the loss lives on the last stage
    assert plan.loss_name
    # params partition exactly: no param on two stages, none dropped
    owned = [p for s in range(2) for p in plan.stage_params[s]]
    assert len(owned) == len(set(owned))


def test_plan_balances_by_activation_bytes_not_op_count():
    """A 6-layer model at S=4: the balancer must not put 3 segments on
    one stage just to even out op counts — per-stage state bytes stay
    within the lexicographic (max_act, max_state) optimum, which for
    this model keeps every transformer stage under 40% of the total."""
    main, _, feeds, fetches = _build(hp=SixLayerHP)
    plan = build_pipeline_plan(main, 4, 8, "1f1b")
    rep_state = plan.state_bytes
    total = sum(rep_state)
    assert max(rep_state) / total < 0.40


def test_pipeline_program_pp1_returns_program_untouched():
    main, _, feeds, fetches = _build()
    mesh = make_mesh({"pp": 1}, devices=jax.devices()[:1])
    before_version = main._version
    before_ops = [op.type for op in main.global_block().ops]
    out = pipeline_program(main, mesh, n_microbatches=4)
    assert out is main
    assert getattr(out, "_pipeline", None) is None
    # bit-identical program, bit-identical run: no mutation happened
    assert out._version == before_version
    assert [op.type for op in out.global_block().ops] == before_ops


def test_activation_report_orders_1f1b_strictly_below_gpipe():
    """The whole point of 1F1B: at M=8, S=2 the gpipe stash holds M
    microbatches per stage while 1f1b holds at most 2S-1."""
    main, _, feeds, fetches = _build()
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    main = pipeline_program(main, mesh, n_microbatches=8,
                            schedule="1f1b")
    rep = pipeline_activation_report(main)
    assert rep["1f1b"]["peak_bytes"] < rep["gpipe"]["peak_bytes"]
    # and the ratio reflects O(S) vs O(M): 2S-1=3 copies vs M=8
    assert rep["1f1b"]["peak_bytes"] <= rep["gpipe"]["peak_bytes"] * 0.5


def test_state_report_splits_params_and_opt_state_across_stages():
    main, _, feeds, fetches = _build(hp=SixLayerHP)
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    main = pipeline_program(main, mesh, n_microbatches=8)
    rep = pipeline_state_report(main)
    assert len(rep["per_stage_bytes"]) == 4
    assert sum(rep["per_stage_bytes"]) <= rep["single_device_bytes"]
    # per-device peak strictly below replicating everything everywhere
    assert rep["per_device_peak_bytes"] < rep["single_device_bytes"]
    assert rep["peak_ratio"] < 0.5


# ---------------------------------------------------------------------------
# structural: verifier stage-boundary diagnostics (fast suite)
# ---------------------------------------------------------------------------
def test_pipeline_diagnostics_clean_on_well_formed_slices():
    from paddle_tpu.analysis import pipeline_diagnostics, verify_program

    main, _, feeds, fetches = _build()
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    main = pipeline_program(main, mesh, n_microbatches=4)
    assert not any(d.is_error for d in pipeline_diagnostics(main))
    # verify_program picks the stamp up without being told
    diags = verify_program(main, check_infer=False)
    assert not any(d.code == "pipeline-slice" for d in diags)


def test_mis_sliced_program_yields_golden_stage_boundary_diagnostic():
    """Deliberately break the hop table: dropping a boundary activation
    from stage 0's hop vars must name BOTH the consuming stage and the
    boundary op that can no longer resolve its input."""
    from paddle_tpu.analysis import pipeline_diagnostics

    main, _, feeds, fetches = _build()
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    main = pipeline_program(main, mesh, n_microbatches=4)
    plan = main._pipeline["plan"]
    victim = sorted(plan.boundary_in[1])[0]
    plan.boundary_out[0] = [n for n in plan.boundary_out[0]
                            if n != victim]
    errs = [d for d in pipeline_diagnostics(main) if d.is_error]
    assert errs, "mis-slice must not verify clean"
    golden = [d for d in errs if d.code == "pipeline-slice"
              and victim in d.message and "stage 1" in d.message]
    assert golden, [str(d) for d in errs]
    # locatable: the diagnostic pins the boundary op reading the hop
    assert golden[0].op_idx is not None
    op = main.global_block().ops[golden[0].op_idx]
    assert victim in op.input_arg_names()


def test_foreign_param_read_is_a_pipeline_slice_error():
    from paddle_tpu.analysis import pipeline_diagnostics

    main, _, feeds, fetches = _build()
    plan = build_pipeline_plan(main, 2, 4, "gpipe")
    stolen = sorted(plan.stage_params[1])[0]
    plan.resolution.stage_of_param[stolen] = 0
    errs = [d for d in pipeline_diagnostics(main, plan=plan)
            if d.is_error]
    assert any(stolen in d.message and d.code == "pipeline-slice"
               for d in errs)


# ---------------------------------------------------------------------------
# structural: the autotune knob (fast suite)
# ---------------------------------------------------------------------------
def test_autotune_mesh_candidates_extend_to_pp_axis():
    from paddle_tpu.transpiler import autotune as at

    main, _, feeds, fetches = _build()
    cands = at._candidates_for("mesh_shape", lambda d: None, main)
    pp3 = [c for c in cands if len(c) == 3]
    assert (1, 1, 2) in pp3
    n = len(jax.devices())
    assert all(dp * mp * pp <= n for dp, mp, pp in pp3)


def test_n_microbatches_is_a_consult_only_knob():
    from paddle_tpu.transpiler import autotune as at

    assert at.DEFAULT_DECISION["n_microbatches"] is None
    # never searched: no candidate generator produces values for it
    assert "n_microbatches" not in at._KNOB_ORDER
    assert at.pipeline_knobs(dict(at.DEFAULT_DECISION)) == {}
    d = dict(at.DEFAULT_DECISION, n_microbatches=8)
    assert at.pipeline_knobs(d) == {"n_microbatches": 8}


def test_ci_pinned_pp_decision_consults_without_search():
    """The committed CI cache pins (mesh_shape=(1,1,4), M=8) for the
    six-layer probe program below (the cache key is the program's
    signature, so its widths are part of the pin): consult-only mode
    must return it verbatim, never timing anything
    (FLAGS_program_autotune=0 is the CI regime)."""
    from paddle_tpu.transpiler import autotune as at
    from paddle_tpu.utils import memory_analysis as ma

    class ProbeHP(gpt2.GPT2Config):
        vocab_size = 256
        n_ctx = 32
        d_model = 64
        n_layer = 6          # deep enough that 4 stages stay balanced
        n_head = 4
        d_inner = 128
        dropout = 0.0
        tie_embeddings = False

    if not str(flags.get_flag("program_tune_cache")).endswith(
            "ci_program_tune_cache.json"):
        pytest.skip("pinned program tune cache not configured "
                    "(the ci.sh transpiler lane sets it)")
    _fresh()
    at.clear_cache(forget_path=True)
    try:
        probe, _, feeds, _ = gpt2.gpt2_lm_program(ProbeHP, seq_len=16,
                                                  lr=3e-4)
        spec = ma.program_feed_specs(probe, feeds, batch_hint=8)
        d = at.tune(probe, spec)
        assert d["mesh_shape"] == (1, 1, 4)
        assert at.pipeline_knobs(d) == {"n_microbatches": 8}
        assert at.cache_stats()["stats"]["searches"] == 0
    finally:
        at.clear_cache(forget_path=True)


# ---------------------------------------------------------------------------
# runtime: schedule equivalence (ci.sh pipeline lane, -m "")
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_gpipe_and_1f1b_match_unpipelined_with_dropout_live():
    """The tentpole bar: both schedules == the unpipelined trajectory
    at rtol<=1e-5 over 5 steps with dropout LIVE and a different batch
    every step, and ZERO retraces after the first step (compile_count
    stays at startup+1 across all 5 steps)."""
    base, _, _ = _train()
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    for sched in ("gpipe", "1f1b"):
        losses, _, exe = _train(mesh=mesh, schedule=sched)
        assert _max_rel(losses, base) <= 1e-5, (sched, losses, base)
        assert exe._cache.compile_count == 2, sched


@pytest.mark.slow
@needs_four_devices
def test_dp_times_pp_matches_unpipelined():
    """(dp, pp)=(2, 2): each dp slice runs its own pipeline; the grad
    psum over dp keeps the batch-mean contract."""
    base, _, _ = _train()
    mesh = make_mesh({"dp": 2, "pp": 2}, devices=jax.devices()[:4])
    for sched in ("gpipe", "1f1b"):
        losses, _, exe = _train(mesh=mesh, schedule=sched)
        assert _max_rel(losses, base) <= 1e-5, (sched, losses, base)


@pytest.mark.slow
@needs_four_devices
def test_pp4_six_layers_matches_unpipelined():
    """(dp, pp)=(1, 4) on the 6-layer model — the bench topology."""
    base, _, _ = _train(hp=SixLayerHP, steps=3)
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    losses, main, _ = _train(hp=SixLayerHP, steps=3, mesh=mesh,
                             schedule="1f1b", M=8)
    assert _max_rel(losses, base) <= 1e-5, (losses, base)
    rep = pipeline_state_report(main)
    assert rep["peak_ratio"] < 0.5


@pytest.mark.slow
def test_pp_composes_with_remat_and_bf16_amp():
    """pp x remat x bf16 AMP: the sliced stages carry the recompute
    sub-blocks and the AMP cast chain; bf16 arithmetic widens the
    tolerance but the two programs share it exactly."""
    eflags = {"hbm_budget_bytes": 1 << 20}
    base, main_b, _ = _train(hp=SixLayerHP, steps=3, use_bf16=True,
                             extra_flags=eflags)
    assert any(op.type == "recompute"
               for op in main_b.global_block().ops)
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    losses, main, _ = _train(hp=SixLayerHP, steps=3, mesh=mesh,
                             schedule="1f1b", M=4, use_bf16=True,
                             extra_flags=eflags)
    assert any(op.type == "recompute"
               for op in main.global_block().ops)
    assert _max_rel(losses, base) <= 2e-2, (losses, base)


@pytest.mark.slow
def test_pipeline_state_stays_on_device_between_steps():
    """The packed per-stage buffers are authoritative between flushes:
    param updates persist across steps (losses must DECREASE on a
    fixed batch) and flush_pipeline_state writes them back to scope."""
    from paddle_tpu.transpiler.pipeline import flush_pipeline_state

    _fresh()
    main, startup, feeds, fetches = gpt2.gpt2_lm_program(
        TinyHP, seq_len=8, lr=3e-3)
    mesh = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    main = pipeline_program(main, mesh, n_microbatches=4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fb = gpt2.make_fake_lm_batch(8, 8, TinyHP, seed=0)
    losses = [float(np.asarray(exe.run(main, feed=fb,
                                       fetch_list=fetches)[0]).reshape(-1)[0])
              for _ in range(4)]
    assert losses[-1] < losses[0]
    scope = scope_mod.global_scope()
    plan = main._pipeline["plan"]
    p = sorted(plan.stage_params[0])[0]
    before = np.array(scope.find_var(p))
    flush_pipeline_state(main, scope)
    after = np.array(scope.find_var(p))
    assert not np.allclose(before, after)  # training moved the param
