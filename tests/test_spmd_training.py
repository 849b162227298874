"""GSPMD tensor-parallel TRAINING (docs/PERFORMANCE.md §"Sharded
training"): the train-lifted partition-rule registry drives
``Executor._run_spmd`` over a dp x mp mesh with NO model edits —
grads and Adam state shard like their param (ZeRO-style), the dp axis
keeps the collective backend's allreduce-mean semantics, and the
whole thing composes with remat, bf16 AMP, and the pallas epilogue
kernels.  Exactness contract: stamped mp=1 is BIT-identical to the
unstamped program; mp=2 on the virtual-device CI mesh holds rtol
parity; optimizer state is provably sharded (per-device bytes)."""

import contextlib
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
import paddle_tpu.framework as fw
from paddle_tpu import flags
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.models import gpt2
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.partition_rules import P, train_partition_rules_for

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
    dropout = 0.0  # determinism: the parity runs must share arithmetic
    tie_embeddings = False


def _fresh():
    fw.switch_main_program(fluid.Program())
    fw.switch_startup_program(fluid.Program())
    scope_mod._switch_scope(scope_mod.Scope())


def _train(mesh, steps=4, use_bf16=False, hp=TinyHP, extra_flags=None,
           batch=4, seq=8):
    """Fresh scope+programs, `steps` Adam steps on the fake-LM batch;
    returns (losses, scope, main_program, executor)."""
    _fresh()
    old = {k: flags.get_flag(k) for k in extra_flags or ()}
    flags.set_flags(dict(extra_flags or {}))
    try:
        main, startup, feeds, fetches = gpt2.gpt2_lm_program(
            hp, seq_len=seq, lr=3e-3, use_bf16=use_bf16, mesh=mesh)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = []
        for _ in range(steps):
            fb = gpt2.make_fake_lm_batch(batch, seq, hp, seed=0)
            out = exe.run(main, feed=fb, fetch_list=fetches)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        return losses, scope_mod.global_scope(), main, exe
    finally:
        flags.set_flags(old)


def _spec_of(scope, name):
    v = scope.find_var(name)
    assert v is not None, name
    return tuple(v.sharding.spec)


_BASE_CACHE = {}


def _base_losses(steps=3):
    """The unsharded reference trajectory, computed once per process —
    every parity test diffs against the same run (tier-1's time budget:
    one baseline compile, not one per test)."""
    if steps not in _BASE_CACHE:
        _BASE_CACHE[steps] = _train(None, steps=steps)[0]
    return _BASE_CACHE[steps]


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------
@pytest.mark.slow  # two 3-step compiles; rides ci.sh spmd lane (-m "")
def test_mp1_stamped_bit_identical_to_unstamped():
    """A (dp=1, mp=1) stamp must change NOTHING: same jaxpr shapes, no
    collectives, bit-identical losses — the registry's guards replicate
    everything and the epilogue wrappers decline single-shard meshes."""
    base = _base_losses(steps=3)
    mesh = make_mesh({"dp": 1, "mp": 1}, devices=jax.devices()[:1])
    got, _, _, _ = _train(mesh, steps=3)
    assert got == base, (got, base)


@pytest.mark.slow  # sharded + baseline compiles; rides ci.sh spmd lane
@needs_four_devices
def test_mp2_rtol_parity():
    """Pure tensor parallelism (dp=1, mp=2): losses track the unsharded
    run to rtol 1e-5 (float reassociation across shards is the only
    permitted difference)."""
    got, _, _, _ = _train(make_mesh({"dp": 1, "mp": 2},
                                    devices=jax.devices()[:2]), steps=3)
    np.testing.assert_allclose(got, _base_losses(steps=3), rtol=1e-5)


@pytest.mark.slow  # one compile per mesh shape; rides ci.sh spmd lane (-m "")
@needs_four_devices
def test_mp2_rtol_parity_across_mesh_shapes():
    """The remaining mesh shapes — pure dp and the full dp x mp grid —
    hold the same rtol 1e-5 contract as the (1, 2) tier-1 leg."""
    base = _base_losses(steps=3)
    for dp, mp in ((2, 1), (2, 2)):
        got, _, _, _ = _train(make_mesh({"dp": dp, "mp": mp}), steps=3)
        np.testing.assert_allclose(got, base, rtol=1e-5,
                                   err_msg="dp=%d mp=%d" % (dp, mp))


def _ctx_of(op_type, **slots):
    """A LowerCtx that names the op being lowered the way core/trace.py
    does (ctx.block + ctx.op_idx), so a mesh-aware lowering can resolve
    its weight's name: one op of `op_type` whose slots hold `slots`."""
    from types import SimpleNamespace

    from paddle_tpu.core.registry import LowerCtx

    ctx = LowerCtx()
    ctx.op_idx = 0
    ctx.block = SimpleNamespace(ops=[SimpleNamespace(
        type=op_type, input=lambda slot: list(slots.get(slot, ())))])
    return ctx


def _collectives(text, kind, shape):
    """The `kind` instructions of a compiled step's text whose result or
    operands carry `shape` ("[33,16]")."""
    return [ln for ln in text.splitlines()
            if (" %s(" % kind in ln or " %s-start(" % kind in ln)
            and shape in ln]


@needs_four_devices
@pytest.mark.parametrize("vocab", [32, 33])
@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("transpose_w", [False, True])
def test_linear_xent_op_under_dp2_mp2_mesh_equals_unsharded(
        transpose_w, eps, vocab, monkeypatch):
    """The vocabulary head's op traced under a live dp2 x mp2 mesh, rows
    over dp and the vocabulary over mp: loss and both gradients equal the
    unsharded op's, which here walks four row tiles; under the mesh the
    input is one tile and the trace holds no loop (a scan's dw carry
    would be all-reduced over dp once per tile).  A vocabulary mp does
    not divide (33) arrives replicated, as the divisibility guard stores
    it, and is still computed in shards: the weight's gradient crosses dp
    in halves, never whole."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import math_ops
    from paddle_tpu.parallel.partition_rules import spmd_lowering

    B, T, H, V = 4, 8, 16, vocab
    wname = "emb.w_0" if transpose_w else "softmax_out.w_0"
    monkeypatch.setattr(math_ops, "_LXENT_TILE_BYTES", 4 * V * B * 2)
    rng = np.random.RandomState(40)
    x = jnp.asarray(rng.randn(B, T, H), jnp.float32)
    w = jnp.asarray(rng.randn(*((V, H) if transpose_w else (H, V))) * 0.3,
                    jnp.float32)
    lbl = rng.randint(0, V, (B, T, 1))
    lbl.flat[1], lbl.flat[2] = -1, V + 3
    lbl = jnp.asarray(lbl, jnp.int32)
    dy = jnp.asarray(rng.rand(B, T, 1) + 0.5, jnp.float32)

    def loss_and_grads(x, w):
        loss, vjp = jax.vjp(lambda x, w: get_op("fused_linear_xent").lower(
            _ctx_of("fused_linear_xent", W=[wname]),
            {"X": [x], "W": [w], "Label": [lbl]},
            {"epsilon": eps, "transpose_w": transpose_w})["Loss"][0], x, w)
        return (loss,) + vjp(dy)

    want = jax.jit(loss_and_grads)(x, w)
    assert "scan" in str(jax.make_jaxpr(loss_and_grads)(x, w))
    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    rules = train_partition_rules_for("gpt2")
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None, None)))
    ws = jax.device_put(w, rules.sharding_for(mesh, wname, w.shape))
    assert ws.sharding.spec == (P() if vocab % 2 else
                                P("mp", None) if transpose_w
                                else P(None, "mp"))
    with spmd_lowering(mesh, rules):
        assert "scan" not in str(jax.make_jaxpr(loss_and_grads)(xs, ws))
        step = jax.jit(loss_and_grads).lower(xs, ws).compile()
    got = step(xs, ws)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    whole = "[%d,%d]" % tuple(w.shape)
    assert not _collectives(step.as_text(), "all-reduce", whole)
    assert rules.uneven_log == ([(wname, 33, "mp")] if vocab % 2 else [])


# ---------------------------------------------------------------------------
# storage and computation part where an axis does not divide a dim
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,shape,axes,want,uneven", [
    ("emb.w_0", (64, 32), {"dp": 2, "mp": 2}, P("mp", None), None),
    ("emb.w_0", (33, 32), {"dp": 2, "mp": 2}, P("mp", None), (33, "mp")),
    ("emb.w_0_moment1_0", (33, 32), {"dp": 1, "mp": 2}, P("mp", None),
     (33, "mp")),
    ("softmax_out.w_0", (32, 33), {"dp": 2, "mp": 2}, P(None, "mp"),
     (33, "mp")),
    # the guards that stay: an axis of size 1, a dim shorter than its
    # axis, rank, scalar, no rule
    ("emb.w_0", (33, 32), {"dp": 2, "mp": 1}, P(None, None), None),
    ("emb.w_0", (1, 32), {"dp": 2, "mp": 2}, P(None, None), None),
    ("emb.w_0_beta1_pow_acc_0", (1,), {"dp": 2, "mp": 2}, P(), None),
    ("ffn_in.w_0", (33,), {"dp": 2, "mp": 2}, P(), None),
    ("layer_norm_0.w_0", (33,), {"dp": 2, "mp": 2}, P(), None),
])
def test_compute_spec_lifts_the_divisibility_guard_alone(
        name, shape, axes, want, uneven):
    """`sharding_for` says how a persistable is stored (even shards or
    replicated); `compute_spec_for` how its value is computed in a step:
    the same rule with the divisibility guard lifted.  The two differ
    exactly for a dim its axis does not divide, and `uneven_log` then
    names it once."""
    n = axes["dp"] * axes["mp"]
    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)
    mesh = make_mesh(axes, jax.devices()[:n])
    rules = train_partition_rules_for("gpt2")
    got = rules.compute_spec_for(mesh, name, shape)
    rules.compute_spec_for(mesh, name, shape)  # logged once a name
    assert got == want
    stored = rules.sharding_for(mesh, name, shape).spec
    if uneven is None:
        assert rules.uneven_log == []
        assert got == stored or not any(tuple(got))
    else:
        assert stored == P() and rules.uneven_log == [(name,) + uneven]


class TiedHP(TinyHP):
    n_layer = 1
    tie_embeddings = True


def _tied_hp(vocab):
    return type("TiedHP%d" % vocab, (TiedHP,), {"vocab_size": vocab})


@needs_four_devices
@pytest.mark.parametrize("vocab", [33, 32])
def test_tied_table_step_under_dp2_mp2_mesh(vocab, monkeypatch):
    """A tied `lookup_table` + head step with its Adam update on the
    dp2 x mp2 mesh tracks the unsharded run at test_mp2_rtol_parity's
    tolerance, whatever the vocabulary.

    33 rows (mp does not divide): the table and its moments are stored
    replicated at the shape the program declares, both of its consumers
    are constrained to the rule's spec, so no all-reduce carries the
    whole table, and the summed gradient is gathered ONCE on its way to
    adam, whose outputs are not gathered.

    32 rows: storage is the computed spec, no constraint is placed and
    the step lowers to the text it lowers to without the mechanism."""
    from paddle_tpu.ops import kernel_tuning, math_ops, spmd_epilogue, \
        tensor_ops

    hp = _tied_hp(vocab)
    base, _, _, _ = _train(None, steps=3, hp=hp)
    kernel_tuning.reset_attribution()
    got, sc, main, exe = _train(make_mesh({"dp": 2, "mp": 2}), steps=3,
                                hp=hp)
    np.testing.assert_allclose(got, base, rtol=1e-5)
    rules = main._spmd["rules"]
    placed = kernel_tuning.attribution()["uneven_constraints"]
    table = sc.find_var("emb.w_0")
    assert table.shape == (vocab, hp.d_model)
    if vocab % 2:
        assert _spec_of(sc, "emb.w_0") == ()
        assert _spec_of(sc, "emb.w_0_moment1_0") == ()
        assert ("emb.w_0", 33, "mp") in rules.uneven_log
        # forward + the grad op's re-traced forward, both op types
        assert placed == {"lookup_table": 2, "fused_linear_xent": 2}
        text, = exe.compiled_hlo(main)
        assert not _collectives(text, "all-reduce", "[33,32]")
        assert _collectives(text, "all-reduce", "[17,32]")
        gathers = (_collectives(text, "all-gather", "[34,32]")
                   + _collectives(text, "all-gather", "[2,17,32]"))
        assert len(gathers) == 1 and "sum" in gathers[0], gathers
        return
    assert _spec_of(sc, "emb.w_0") == ("mp", None)
    assert placed == {} and rules.uneven_log == []
    (block, _sh), = exe._spmd_cache.values()
    jitted, avals = block.jitted, block.avals
    with_mechanism = jitted.trace(*avals).lower().as_text()
    for mod in (math_ops, tensor_ops):
        monkeypatch.setattr(mod, "rule_sharded_weight",
                            lambda ctx, op_type, slot, w: w)
    monkeypatch.setattr(spmd_epilogue, "grad_in_param_storage",
                        lambda op, ins: ins)
    jax.clear_caches()
    assert jitted.trace(*avals).lower().as_text() == with_mechanism


@needs_four_devices
@pytest.mark.parametrize("table,axes,constraints", [
    ("serving", {"mp": 2}, 0),
    ("training", {"dp": 2, "mp": 1}, 0),
    ("training", {"mp": 2}, 2),
])
def test_only_a_training_table_over_a_live_axis_constrains_the_table(
        table, axes, constraints):
    """A serving rule table names no dp axis: under it a table mp does
    not divide is read whole on every rank, as it is stored — a decode
    step's local gather from a replicated table beats a sharded gather
    and an all-reduce, and pooled == solo stays bit for bit.  Nor is
    there anything to constrain where the rule's axis has one rank.  The
    same ops under the training table over mp=2 place their constraint."""
    import jax.numpy as jnp

    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import kernel_tuning
    from paddle_tpu.parallel.partition_rules import (partition_rules_for,
                                                     spmd_lowering)

    w = jnp.zeros((33, 16), jnp.float32)
    x = jnp.zeros((4, 8, 16), jnp.float32)
    ids = jnp.zeros((4, 8), jnp.int32)

    def both(w):
        rows = get_op("lookup_table").lower(
            _ctx_of("lookup_table", W=["emb.w_0"]),
            {"W": [w], "Ids": [ids]}, {})["Out"][0]
        loss = get_op("fused_linear_xent").lower(
            _ctx_of("fused_linear_xent", W=["emb.w_0"]),
            {"X": [x], "W": [w], "Label": [ids[..., None]]},
            {"transpose_w": True})["Loss"][0]
        return rows, loss

    mesh = make_mesh(axes, jax.devices()[:int(np.prod(list(axes.values())))])
    rules = (partition_rules_for if table == "serving"
             else train_partition_rules_for)("gpt2")
    kernel_tuning.reset_attribution()
    with spmd_lowering(mesh, rules):
        text = str(jax.make_jaxpr(both)(w))
    assert text.count("sharding_constraint") == constraints
    placed = kernel_tuning.attribution()["uneven_constraints"]
    assert sum(placed.values()) == constraints
    assert len(rules.uneven_log) == (1 if constraints else 0)


# ---------------------------------------------------------------------------
# sharded optimizer state (the ZeRO-style leg)
# ---------------------------------------------------------------------------
@needs_four_devices
def test_zero_state_specs_bytes_and_comm_stats():
    """ONE dp2 x mp2 training step proves the whole ZeRO-style story
    (one compile — tier-1's time budget): every Adam moment carries its
    PARAM's PartitionSpec (the registry resolves `<p>_moment1_0` through
    base_name), the per-device param+state footprint lands under the
    0.55x acceptance bar (matrices halve; ln scales / biases / beta-pows
    stay replicated), and `spmd_comm_stats` reports the train-program
    collectives with at least the grad all-reduce visible."""
    class OneLayerHP(TinyHP):
        n_layer = 1  # tier-1 time budget: one block is enough to place
        #              every param class (emb/pos/qkvo/ffn/ln/head)
    _, sc, main, exe = _train(make_mesh({"dp": 2, "mp": 2}), steps=1,
                              hp=OneLayerHP)
    # --- moment specs follow the param ---
    moments = sorted(n for n in sc.all_var_names() if "moment" in n)
    assert moments, "no Adam state in scope"
    checked = 0
    for n in moments:
        base = train_partition_rules_for("gpt2").base_name(n)
        v = sc.find_var(n)
        if v is None or not hasattr(v, "sharding"):
            continue
        assert _spec_of(sc, n) == _spec_of(sc, base), (n, base)
        checked += 1
    assert checked >= 10
    # spot-check the load-bearing placements
    assert _spec_of(sc, "ffn_in.w_0_moment1_0") == (None, "mp")
    assert _spec_of(sc, "ffn_out.w_0_moment2_0") == ("mp", None)
    assert _spec_of(sc, "emb.w_0_moment1_0") == ("mp", None)
    # scalars (beta pows) stay replicated via the scalar guard
    rules = train_partition_rules_for("gpt2")
    assert rules.spec_for("fc_0.w_0_beta1_pow_acc_0", (1,)) == P()
    # --- per-device bytes: the acceptance floor ---
    per_device = replicated = 0
    for n in sc.all_var_names():
        v = sc.find_var(n)
        if v is None or not hasattr(v, "sharding"):
            continue
        replicated += v.nbytes
        shard = v.sharding.shard_shape(v.shape)
        nb = v.dtype.itemsize
        for d in shard:
            nb *= int(d)
        per_device += nb
    assert replicated > 0
    ratio = per_device / replicated
    assert ratio <= 0.55, (per_device, replicated, ratio)
    # --- comm attribution covers train programs ---
    stats = exe.spmd_comm_stats(main)
    assert stats["total_bytes"] > 0, stats
    assert any("all-reduce" in k for k in stats["per_op"]), stats


# ---------------------------------------------------------------------------
# composition legs
# ---------------------------------------------------------------------------
@pytest.mark.slow  # remat'd + plain compiles per leg; rides ci.sh spmd lane
@needs_four_devices
def test_remat_composes_with_mp():
    """HBM-budgeted remat under a mesh: the budget scales per-shard
    (maybe_remat multiplies by the mesh size since the estimator sees
    the GLOBAL program) and parity holds."""
    extra = {"hbm_budget_bytes": 1 << 20}
    base, _, _, _ = _train(None, extra_flags=extra)
    got, _, main, _ = _train(make_mesh({"dp": 2, "mp": 2}),
                             extra_flags=extra)
    np.testing.assert_allclose(got, base, rtol=1e-5)
    rep = getattr(main, "_remat_report", None)
    if rep is not None:
        assert rep.get("mesh_shards") == 4


@pytest.mark.slow  # two bf16 compiles; rides ci.sh spmd lane (-m "")
@needs_four_devices
def test_bf16_amp_composes_with_mp():
    """bf16 AMP under a mesh: f32 master params keep the param's spec
    (the @RAW_BF16 cast resolves through base_name) and training stays
    close to the unsharded bf16 run."""
    base, _, _, _ = _train(None, use_bf16=True)
    got, sc, _, _ = _train(make_mesh({"dp": 2, "mp": 2}), use_bf16=True)
    # bf16 rounds at 4e-3; a sharded reduction order moves the last
    # step by one rounding (1.3e-4 under jax 0.9.0's XLA)
    np.testing.assert_allclose(got, base, rtol=1e-3)
    rules = train_partition_rules_for("gpt2")
    casts = [n for n in sc.all_var_names() if "@RAW_BF16" in n
             and "ffn_in.w" in n]
    for n in casts:
        assert _spec_of(sc, n) == _spec_of(sc, rules.base_name(n)), n


@pytest.mark.slow  # an interpret-mode compile at lane-legal widths (~25 s)
@needs_four_devices
def test_sharded_train_step_lowers_for_tpu_without_chips(monkeypatch):
    """XLA cannot partition a Mosaic custom call: under a live mesh every
    kernel must sit inside a shard_map (or its op must lower densely).
    Interpret mode lowers kernels to plain HLO and never notices — the
    first four-chip run refused the unwrapped train flash_attention with
    "Mosaic kernels cannot be automatically partitioned".  Cross-lowering
    the sharded step for the TPU platform runs that check on the CPU
    host, for the dispatch site the transformer step reaches."""
    import chip_smoke
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops import pallas_kernels as pk

    # attention's training path reads no flag: say here what a TPU-placed
    # mesh and an engaged length would
    monkeypatch.setattr(nn_ops, "_flash_engages",
                        lambda ctx, tq, tk, d, dv=None: (tq == tk
                                                         and tq % 128 == 0))

    class HP(tfm.ModelHyperParams):  # small, but Mosaic-legal blocks
        d_model, d_inner_hid, n_head, n_layer = 256, 512, 2, 1
        src_vocab_size = trg_vocab_size = 1000
        max_length = 128
        fused_attn = True

    _fresh()
    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    r = chip_smoke.train(
        HP, tfm.make_fake_batch(8, 128, 128, HP, seed=0), 128,
        fluid.CPUPlace(), steps=1, mesh=mesh)
    hits = r["attribution"]["pallas_hits"]
    assert hits.get("attention", 0) > 0, hits  # dispatched, not dense
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.clear_caches()  # the interpreted trace must not be reused
    (_traced, jitted, _sh, avals), = r["exe"]._spmd_cache.values()
    text = jitted.trace(*avals[0]).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") > 0


# ---------------------------------------------------------------------------
# the mesh step's compile options (parallel.mesh.mesh_compile_options)
# ---------------------------------------------------------------------------
class _Device:
    def __init__(self, platform):
        self.platform = platform


def _mesh_of(platform, *shape):
    """What mesh_compile_options looks at, without the devices: an array
    of things that state a platform."""
    import types

    devices = np.empty(int(np.prod(shape)), dtype=object)
    devices[:] = [_Device(platform) for _ in devices]
    return types.SimpleNamespace(devices=devices.reshape(shape))


@pytest.mark.parametrize("platform,shape,engaged", [
    ("tpu", (2, 2), True),    # the benchmark's dp2 x mp2
    ("tpu", (4,), True),      # a serving mesh: mp alone, the same sums
    ("tpu", (1, 1), False),   # one chip: no collective to hide
    ("cpu", (2, 2), False),   # every tier-1 mesh: the compile would refuse
    ("cpu", (1,), False),
    ("gpu", (2, 2), False),
])
def test_mesh_compile_options_follow_the_mesh(platform, shape, engaged):
    from paddle_tpu.parallel import mesh as mesh_mod

    got = mesh_mod.mesh_compile_options(_mesh_of(platform, *shape))
    if not engaged:
        assert got == {}
        return
    assert got == mesh_mod._TPU_MESH_COMPILE_OPTIONS and got
    assert all(k.startswith("xla_") and v is True for k, v in got.items())
    got.clear()  # the caller's copy: the table itself stays whole
    assert mesh_mod.mesh_compile_options(_mesh_of(platform, *shape))


@needs_four_devices
def test_cpu_mesh_step_compiles_with_no_option_and_matches_one_device():
    """The forced-host mesh gets an empty dict (a CPU compile answers
    "No such compile option" to any xla_tpu_ name), trains as the
    one-device step does, and its trace_compile record says so."""
    from paddle_tpu import profiler
    from paddle_tpu.parallel.mesh import mesh_compile_options

    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    assert mesh_compile_options(mesh) == {}
    n_phases = len(profiler.phases())
    got, _, main, _ = _train(mesh, steps=3)
    np.testing.assert_allclose(got, _base_losses(steps=3), rtol=1e-5)
    named = [r["args"]["compiler_options"]
             for r in profiler.phases()[n_phases:]
             if r["name"] == "trace_compile"
             and r["args"].get("program") == id(main)]
    assert named == [[]]


@needs_four_devices
def test_run_path_and_compiled_hlo_compile_with_the_same_options(
        monkeypatch):
    """Whatever mesh_compile_options answers reaches the executable that
    runs, which is the one compiled_hlo reads for the readers (an option
    the CPU compiler takes stands in for the TPU's), and the trace_compile
    record names it."""
    from paddle_tpu import profiler
    from paddle_tpu.parallel import mesh as mesh_mod

    lowered_with = []
    real_jit = jax.jit

    def jit(fn, **kw):
        jitted = real_jit(fn, **kw)
        if "compiler_options" in kw:  # the mesh step, no other jit
            lowered_with.append((jitted, kw["compiler_options"]))
        return jitted

    monkeypatch.setattr(jax, "jit", jit)
    monkeypatch.setattr(mesh_mod, "mesh_compile_options",
                        lambda mesh: {"xla_embed_ir_in_executable": True})
    n_phases = len(profiler.phases())
    _, _, main, exe = _train(make_mesh({"dp": 2, "mp": 2},
                                       jax.devices()[:4]), steps=1)
    (jitted, options), = lowered_with
    assert options == {"xla_embed_ir_in_executable": True}
    step = exe.compiled_steps(main)[-1]
    assert step.path == "spmd" and step._jitted is jitted
    assert "all-reduce" in exe.compiled_hlo(main)[-1]
    named = [r["args"]["compiler_options"]
             for r in profiler.phases()[n_phases:]
             if r["name"] == "trace_compile"
             and r["args"].get("program") == id(main)]
    assert named == [["xla_embed_ir_in_executable"]]


def test_no_mesh_run_paths_pass_no_compile_option(monkeypatch):
    """One chip's steps share no line with the mesh step's options: the
    flat path's jit sites are handed no compiler_options, and the option
    names live in parallel/mesh.py alone."""
    import pathlib

    import paddle_tpu

    kwargs_seen = []
    real_jit = jax.jit

    def jit(fn, **kw):
        kwargs_seen.append(kw)
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", jit)
    losses = _train(None, steps=2)[0]
    assert np.isfinite(losses).all() and kwargs_seen
    assert not [kw for kw in kwargs_seen if "compiler_options" in kw]
    root = pathlib.Path(paddle_tpu.__file__).parent
    passing, naming = set(), set()
    for path in root.rglob("*.py"):
        text = path.read_text()
        if "compiler_options=" in text:
            passing.add(path.relative_to(root).as_posix())
        if "xla_tpu_" in text or "xla_enable_" in text:
            naming.add(path.relative_to(root).as_posix())
    assert passing == {"executor.py"} and naming == {"parallel/mesh.py"}


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described (not attached) 2 x 2 of v5e chips: the TPU compiler
    compiles for it on this host.  In a fixture, and in this file alone,
    because the process that describes it holds libtpu until it exits."""
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@contextlib.contextmanager
def _no_compilation_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip: off around such compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


class LaneHP(TinyHP):  # TinyHP at widths the TPU's tiling takes
    vocab_size = 1001  # mp does not divide it, as GPT-2's 50257
    n_ctx = 128
    d_model = 256
    n_head = 2
    d_inner = 1024
    dropout = 0.1
    tie_embeddings = True


def _described_step_hlo(topo):
    """Optimized HLO of LaneHP's dp2 x mp2 train step compiled for the
    described chips through _run_spmd's own jit site, over a scope of
    shapes (nothing can be put on a described device)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from paddle_tpu.core.trace import build_traced_function
    from paddle_tpu.executor import Executor

    _fresh()
    mesh = make_mesh({"dp": 2, "mp": 2}, list(topo.devices))
    main, startup, _feeds, fetches = gpt2.gpt2_lm_program(
        LaneHP, seq_len=128, lr=3e-3, use_bf16=True, mesh=mesh)
    rules = main._spmd["rules"]
    scope = scope_mod.Scope()
    for block in (main.global_block(), startup.global_block()):
        for name, var in block.vars.items():
            if var.persistable and all(int(d) >= 0 for d in var.shape):
                scope.set(name, jax.ShapeDtypeStruct(
                    tuple(int(d) for d in var.shape),
                    jnp.dtype(str(var.dtype))))
    batch = gpt2.make_fake_lm_batch(4, 128, LaneHP, seed=0)
    traced = build_traced_function(
        main, 0, tuple(sorted(batch)), [fetches[0].name], scope,
        spmd=(mesh, rules), platform="tpu")
    sh = {n: rules.sharding_for(mesh, n, scope.find_var(n).shape)
          for n in set(traced.ro_names) | set(traced.rw_names)
          | set(traced.updated)}
    rows = NamedSharding(mesh, P("dp"))
    jitted, options = Executor._jit_spmd_step(
        traced, mesh, {n: rows for n in batch}, sh)

    def shaped(n):
        v = scope.find_var(n)
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[n])

    key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
    hlo = jitted.lower(
        {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
         for n, a in batch.items()},
        {n: shaped(n) for n in traced.ro_names},
        {n: shaped(n) for n in traced.rw_names},
        jax.ShapeDtypeStruct(key.shape, key.dtype,
                             sharding=NamedSharding(mesh, P()))
    ).compile().as_text()
    return hlo, options


def test_tpu_mesh_step_schedules_asynchronous_reductions(v5e_2x2,
                                                         monkeypatch):
    """The mechanism, read where it acts: compiled for a described
    v5e:2x2 with the mesh's options the step's optimized HLO hands
    all-reduces to AsyncCollectiveStart (what the benchmark's
    async_collective_ops counts, by its own pattern); compiled with none,
    as before this rule, it hands none.  A compile is not a chip run:
    what the schedule pays is measured in the cell."""
    import json
    import pathlib

    from paddle_tpu.parallel import mesh as mesh_mod

    pattern = json.loads((
        pathlib.Path(__file__).parent.parent / "benchmark" / "layer_metrics"
        / "async_collective_ops.json").read_text())["args"]["pattern"]
    with _no_compilation_cache():
        with_options, options = _described_step_hlo(v5e_2x2)
        monkeypatch.setattr(mesh_mod, "mesh_compile_options", lambda m: {})
        without, none = _described_step_hlo(v5e_2x2)
    assert options == mesh_mod._TPU_MESH_COMPILE_OPTIONS and none == {}
    assert without.count(" all-reduce(") > 0
    assert without.count(pattern) == 0
    assert with_options.count(pattern) > 0


def _ffn_chain(layers):
    """`layers` of GPT-2 345M's FFN at the cell's real shape ([4, 1024,
    1024] x [1024, 4096] under gelu, [4096, 1024] back, a residual), run
    as a program runs them: every forward op, then the grad ops through
    `lower_grad_op` in reverse.  (x, then w, b of ffn_in and of ffn_out a
    layer) -> (dx, then the four parameter gradients a layer)."""
    from paddle_tpu.core.registry import LowerCtx, get_op, lower_grad_op

    def fc(ctx, x, w, b, act):
        return get_op("fc").lower(
            ctx, {"Input": [x], "W": [w], "Bias": [b]},
            {"in_num_col_dims": 2, "activation_type": act})["Out"][0]

    def fc_grad(ctx, x, w, b, act, dy):
        g = lower_grad_op(
            ctx, None,
            {"Input": [x], "W": [w], "Bias": [b], "Out@GRAD": [dy]},
            {"__fwd_type__": "fc", "__fwd_in_slots__": ["Input", "W", "Bias"],
             "__fwd_out_slots__": ["Out"],
             "__fwd_attrs__": {"in_num_col_dims": 2,
                               "activation_type": act}})
        return g["Input@GRAD"][0], g["W@GRAD"][0], g["Bias@GRAD"][0]

    def chain(x, *params):
        ctx = LowerCtx(platform="tpu")
        kept = []
        for i in range(layers):
            w1, b1, w2, b2 = params[4 * i:4 * i + 4]
            h = fc(ctx, x, w1, b1, "gelu")
            kept.append((x, h))
            x = fc(ctx, h, w2, b2, "") + x
        dx, grads = x, []  # d(sum x^2 / 2) / dx
        for i in reversed(range(layers)):
            w1, b1, w2, b2 = params[4 * i:4 * i + 4]
            x_in, h = kept[i]
            dh, dw2, db2 = fc_grad(ctx, h, w2, b2, "", dx)
            dx_in, dw1, db1 = fc_grad(ctx, x_in, w1, b1, "gelu", dh)
            dx = dx + dx_in
            grads = [dw1, db1, dw2, db2] + grads
        return (dx,) + tuple(grads)

    return chain


def test_an_engaged_fc_compiles_for_the_described_chip_with_a_fused_epilogue(
        v5e_2x2, monkeypatch, capsys):
    """ISSUE 47, compile only (nothing runs: no time, no result).  Two
    engaged `fc` + `fc_grad` layers at GPT-2's real shape, chained
    (forward, forward, backward, backward), compile for one chip of the
    described v5e:2x2 in plain XLA ops, and hold the mechanism against the
    same chain with the rule switched off HERE (the program has no such
    option): with the rule each gelu matmul's own fusion writes a pair of
    bfloat16 [4096, 4096] arrays, the output and the pre-activation, and
    without it none does (it writes the pre-activation alone, and every
    consumer computes gelu again); no matmul is added.  The temporaries of
    the two are printed: the rule pays the output's array a layer (the
    ISSUE expected fewer bytes: PERF.md section 6, PR 47, says why not),
    asserted as no more than that and a tenth."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import nn_ops

    chip = SingleDeviceSharding(v5e_2x2.devices[0])

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    layer = [s(1024, 4096), s(4096), s(4096, 1024), s(1024)]

    def compiled():
        jax.clear_caches()  # the rule is read when the chain is traced
        c = jax.jit(_ffn_chain(2)).lower(
            s(4, 1024, 1024), *(layer * 2)).compile()
        text = c.as_text()
        entry = re.sub(r"\{[^{}]*\}", "", text[text.index("\nENTRY "):])
        return (c.memory_analysis().temp_size_in_bytes, text, entry.count(
            " = (bf16[4096,4096], bf16[4096,4096]) fusion("))

    with _no_compilation_cache():
        temp, text, pairs = compiled()
        monkeypatch.setattr(nn_ops, "FC_PRODUCT_EPILOGUE_ACTS", ())
        temp_off, text_off, pairs_off = compiled()
    jax.clear_caches()
    assert "tpu_custom_call" not in text
    assert text.count(" convolution(") == text_off.count(" convolution(")
    assert (pairs, pairs_off) == (2, 0)
    with capsys.disabled():
        print("\ntwo FFN layers, temporaries for one described v5e chip: "
              "engaged %d bytes, rule off %d bytes (%+d)"
              % (temp, temp_off, temp - temp_off))
    assert temp - temp_off <= 2 * (4096 * 4096 * 2) * 1.1


def _expert_layer_step(topo):
    """One Nemotron-3-Nano expert layer at the published widths the layout
    turns on (hidden 2688, `moe_intermediate_size` 1856 = 14.5 tiles of 128
    lanes; two experts held, T = 512), its train step compiled for one chip
    of the described v5e:2x2 through the run paths' own jit
    (core/trace.jit_step), over a scope of shapes."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.core.trace import build_traced_function, jit_step
    from paddle_tpu.models import nemotron_h

    class OneExpertLayer(nemotron_h.NemotronHConfig):
        vocab_size, hidden_size, num_hidden_layers = 512, 2688, 1
        hybrid_override_pattern = "E"
        moe_intermediate_size, moe_shared_expert_intermediate_size = 1856, 256
        n_routed_experts, num_experts_per_tok = 8, 2
        num_local_experts, expert_offset = 2, 2

    _fresh()
    chip = SingleDeviceSharding(topo.devices[0])
    with fluid.unique_name.guard():
        main, startup, _, fetches = nemotron_h.nemotron_h_lm_program(
            OneExpertLayer, seq_len=512, lr=1e-3, use_bf16=True)
    scope = scope_mod.Scope()
    for block in (main.global_block(), startup.global_block()):
        for name, var in block.vars.items():
            if var.persistable and all(int(d) >= 0 for d in var.shape):
                scope.set(name, jax.ShapeDtypeStruct(
                    tuple(int(d) for d in var.shape),
                    jnp.dtype(str(var.dtype)), sharding=chip))
    feeds = {n: jax.ShapeDtypeStruct((2, 512), dt, sharding=chip)
             for n, dt in (("ids", jnp.int32), ("labels", jnp.int32),
                           ("loss_weight", jnp.float32))}
    traced = build_traced_function(
        main, 0, tuple(sorted(feeds)), [fetches[0].name], scope,
        platform="tpu")
    key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
    return jit_step(traced, {n: chip for n in traced.rw_names}).lower(
        feeds, {n: scope.find_var(n) for n in traced.ro_names},
        {n: scope.find_var(n) for n in traced.rw_names},
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)).compile()


def test_the_donated_state_is_compiled_in_the_layout_the_step_reads(
        v5e_2x2, monkeypatch):
    """ISSUE 60, compile only (nothing runs).  The device's default layout
    of a float32 [E, 2688, 1856] array puts 2688 minor, and every consumer
    of the up-projection experts and of their two Adam moments wants 1856
    minor.  Compiled as the run paths compile it, the step takes and
    returns the three arrays 1856-minor, aliased, and its entry computation
    copies no read-write matrix.  The control, with the rule switched off
    HERE (the program has no such option): the arrays come 2688-minor and
    the entry computation transposes them with `copy`s."""
    from paddle_tpu.core import trace
    from paddle_tpu.ops import pallas_kernels

    names = ("moe_up.w_0", "moe_up.w_0_moment1_0", "moe_up.w_0_moment2_0")

    def read(compiled):
        text = compiled.as_text()
        entry = text[text.index("\nENTRY "):]
        params = dict(
            (name, (layout, int(number))) for name, layout, number in
            re.findall(r"%rw_state__(\w+?)__\.\d+ = f32\[2,2688,1856\]"
                       r"(\{[0-9,]+)[^ ]* parameter\((\d+)\)", entry))
        copied = re.findall(
            r"= \w+\[\d+,[0-9,]+\]\S* copy\(%rw_state__(\w+?)__\.\d+\)",
            entry)
        aliased = set(int(n) for n in re.findall(
            r"\}: \((\d+), \{\}, (?:may|must)-alias\)",
            text[:text.index("\n")]))
        return params, copied, aliased

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    with _no_compilation_cache():
        jax.clear_caches()  # an interpreted trace of these shapes would hide
        compiled = _expert_layer_step(v5e_2x2)
        params, copied, aliased = read(compiled)
        monkeypatch.setattr(trace, "state_format",
                            lambda name, sharding: None)
        as_it_was, copied_before, _ = read(_expert_layer_step(v5e_2x2))
    jax.clear_caches()
    dotted = [n.replace(".", "_") for n in names]
    assert {n: params[n][0] for n in dotted} == dict.fromkeys(
        dotted, "{2,1,0")
    assert copied == []
    assert {params[n][1] for n in dotted} <= aliased
    formats = compiled.input_formats[0][2]
    outs = compiled.output_formats[0]
    for n in names:
        assert formats[n].layout.major_to_minor == (0, 1, 2)
        assert outs[n] == formats[n]
    assert {n: as_it_was[n][0] for n in dotted} == dict.fromkeys(
        dotted, "{1,2,0")
    assert set(copied_before) & set(dotted)


@needs_four_devices
def test_a_mesh_step_keeps_its_state_in_the_layout_it_was_compiled_for(
        monkeypatch, tmp_path):
    """The mesh path's blocks are the flat path's (core/trace.CompiledBlock
    over _jit_spmd_step's jit).  With every rank-2 Adam moment put
    column-major HERE (the CPU's compiler asks for nothing; the program
    has no such option) three dp2 x mp2 steps end on the losses of the run
    that asks for no format, to the bit: compiled in this process, and
    read back from the persistent cache, whose results JAX hands out
    mislabelled (trace.relabelled puts the labels right, shard by
    shard)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.layout import Format, Layout

    from paddle_tpu import profiler
    from paddle_tpu.core import trace

    mesh = make_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    _fresh()
    main, startup, _, _ = gpt2.gpt2_lm_program(TinyHP, seq_len=8, lr=3e-3,
                                               mesh=mesh)
    rank = {n: len(v.shape) for blk in (main.global_block(),
                                        startup.global_block())
            for n, v in blk.vars.items() if v.persistable}

    def column_major_moments(name, sharding):
        order = tuple(range(rank[name]))
        turned = rank[name] == 2 and "moment" in name
        return Format(Layout(order[::-1] if turned else order, ()), sharding)

    def train(state_format):
        jax.clear_caches()
        monkeypatch.setattr(trace, "state_format", state_format)
        losses, scope, main, exe = _train(mesh, steps=3)
        (block, _sh), = exe._spmd_cache.values()
        record = [r["args"] for r in profiler.phases()
                  if r["name"] == "trace_compile"
                  and r["args"].get("program") == id(main)][-1]
        turned = [n for n in block.traced.rw_names
                  if scope.find_var(n).format.layout.major_to_minor == (1, 0)]
        return losses, record["state_relayouts"], len(turned), block

    plain, none, _, _ = train(lambda name, sharding: None)
    configured = {n: getattr(jax.config, n) for n in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    for n, v in zip(configured, (str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    try:
        cold, moved, turned, block = train(column_major_moments)
        warm, moved_again, turned_again, block_again = train(
            column_major_moments)
    finally:
        for n, v in configured.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
        jax.clear_caches()
    assert none == 0 and moved == moved_again == turned == turned_again > 0
    assert block.mislabelled == {}
    assert len(block_again.mislabelled) in (0, moved)
    assert cold == plain and warm == plain
