"""GSPMD tensor-parallel serving pools (docs/SERVING.md §"Tensor-
parallel pools"): the partition-rule registry's resolution contracts
(precedence, scalar/rank/divisibility guards, logged replicate-by-
default) and the sharded engine's preservation of BOTH load-bearing
PR 9 contracts on a 2-virtual-device CPU mesh — every request's tokens
bit-identical to its solo run under churn, and zero retraces across
occupancy changes — plus the pool-bytes-per-device drop the sharding
exists for."""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.models import gpt2
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.partition_rules import (
    P,
    PartitionRules,
    partition_rules_for,
    registered_families,
)
from paddle_tpu.serving import Request, ServingEngine

needs_two_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=2")


# ---------------------------------------------------------------------------
# the rule registry
# ---------------------------------------------------------------------------
def test_rule_precedence_first_match_wins():
    """Rules resolve in ORDER: an earlier narrow rule shadows a later
    broad one — the family tables lean on this (pos_emb.w must hit its
    replicate rule before the emb.w vocab rule would re.search-match
    the 'emb.w' substring)."""
    r = PartitionRules([
        (r"special\.w", P("mp")),
        (r"\.w", P(None, "mp")),
    ])
    assert r.spec_for("special.w_0", (8, 8)) == P("mp")
    assert r.spec_for("plain.w_0", (8, 8)) == P(None, "mp")
    # the gpt2 family table's instance of the same contract
    fam = partition_rules_for("gpt2", mp_axis="mp")
    assert fam.spec_for("pos_emb.w_0", (32, 16)) == P()
    assert fam.spec_for("emb.w_0", (64, 16)) == P("mp", None)


def test_gpt2_family_table_covers_the_serving_persistables():
    r = partition_rules_for("gpt2", mp_axis="mp")
    assert r.spec_for("mha_q.w_3", (16, 16)) == P(None, "mp")
    assert r.spec_for("mha_o.w_1", (16, 16)) == P("mp", None)
    assert r.spec_for("ffn_gate.w_0", (16, 44)) == P(None, "mp")
    assert r.spec_for("ffn_out.w_0", (64, 16)) == P("mp", None)
    # the slot-pool persistables shard their HEADS axis
    assert (r.spec_for("gpt2_kcache_0", (4, 4, 24, 8))
            == P(None, "mp", None, None))
    assert (r.spec_for("gpt2_vcache_11", (4, 4, 24, 8))
            == P(None, "mp", None, None))
    assert "gpt2" in registered_families()
    with pytest.raises(KeyError, match="gpt2"):
        partition_rules_for("no_such_family")


def test_unmatched_name_replicates_and_logs_once():
    """Replicate-by-default is LOUD: the fallback lands in
    replicated_log exactly once per name (steady-state re-resolution
    must not grow it), and matching names never log."""
    r = PartitionRules([(r"\.w$", P("mp"))])
    assert r.spec_for("layer_norm_0.b", (8,)) == P()
    assert r.spec_for("layer_norm_0.b", (8,)) == P()
    assert r.replicated_log == [("layer_norm_0.b", "no rule matched")]
    assert r.spec_for("dense.w", (8,)) == P("mp")
    assert len(r.replicated_log) == 1


def test_scalar_and_rank_guards_replicate():
    r = PartitionRules([(r"counter|step|mha_q\.w", P("mp"))])
    # scalars/1-element values never shard — and never log (beta_pows,
    # counters are not worth surfacing)
    assert r.spec_for("counter", ()) == P()
    assert r.spec_for("step", (1,)) == P()
    assert r.replicated_log == []
    # a rank-1 value under a rank-1 spec shards fine...
    assert r.spec_for("mha_q.w_bias", (4,)) == P("mp")
    # ...but a matched rule whose spec OUTRANKS the value replicates
    # with a log
    r2 = PartitionRules([(r"x", P("a", "b"))])
    assert r2.spec_for("x", (6,)) == P()
    assert r2.replicated_log and "rank" in r2.replicated_log[0][1]


@needs_two_devices
def test_divisibility_guard_replicates_on_mesh():
    mesh = make_mesh({"mp": 2}, devices=jax.devices()[:2])
    r = PartitionRules([(r"cache", P(None, "mp", None, None))])
    ok = r.sharding_for(mesh, "cache_a", (4, 4, 24, 8))
    assert ok.spec == P(None, "mp", None, None)
    # 3 kv heads on a 2-way mesh: replicate, loudly
    bad = r.sharding_for(mesh, "cache_b", (4, 3, 24, 8))
    assert bad.spec == P()
    assert any(n == "cache_b" for n, _ in r.replicated_log)


# ---------------------------------------------------------------------------
# the sharded engine: both PR 9 contracts survive GSPMD
# ---------------------------------------------------------------------------
class TinyHP(gpt2.GPT2Config):
    vocab_size = 61
    n_ctx = 32
    d_model = 32
    n_layer = 2
    n_head = 4
    dropout = 0.0


def _churn_trace(vocab, seed=0):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(8):
        sampled = i % 2 == 1
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(1, vocab, int(rng.randint(2, 11))),
            max_new_tokens=int(rng.randint(3, 9)),
            temperature=0.8 + 0.1 * (i % 3) if sampled else 1.0,
            top_k=[0, 8, 16][i % 3] if sampled else 0,
            top_p=0.9 if sampled and i % 4 == 1 else 1.0,
            seed=1000 + i if sampled else None,
            arrival=float(i) * 0.9))
    return reqs


def _tp_engine(scope, seed=7):
    mesh = make_mesh({"mp": 2}, devices=jax.devices()[:2])
    _, lm_startup, _, _ = gpt2.gpt2_logits_program(TinyHP, seq_len=24)
    exe = fluid.Executor(fluid.CPUPlace())
    lm_startup.random_seed = seed
    exe.run(lm_startup)
    return exe, ServingEngine(exe, TinyHP, n_slots=4, width=4, t_max=24,
                              mesh=mesh)


@needs_two_devices
def test_tp_engine_churn_exactness_and_pool_bytes():
    """The tensor-parallel pool on a 2-virtual-device mp mesh: every
    request's tokens (greedy AND per-request-seeded sampled) are
    bit-identical to its solo run through the SAME sharded engine under
    admission churn, and the KV pool's per-device resident bytes drop
    to 50% of the pool (the acceptance bar is <= 60%)."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe, eng = _tp_engine(scope)
        reqs = _churn_trace(TinyHP.vocab_size)
        results, stats = eng.run(list(reqs))
        assert stats["finished"] == len(reqs) > eng.n_slots
        admits = sorted(results[r.rid]["admit_step"] for r in reqs)
        assert admits[-1] > admits[0]  # real churn happened
        for r in reqs:
            solo, _ = eng.run_solo(r)
            np.testing.assert_array_equal(
                results[r.rid]["tokens"], solo,
                err_msg="request %r sharded pooled != solo" % r.rid)
        pool = eng.kv_pool_bytes(scope)
        ratio = pool["max_device_bytes"] / pool["total_bytes"]
        assert ratio <= 0.6, pool
        # the heads-axis cache rule actually fired (not a fallback)
        assert not any("cache" in n for n, _ in
                       eng.partition_rules.replicated_log)


@needs_two_devices
def test_tp_engine_compiles_once_across_occupancy():
    """The no-retrace contract through the GSPMD path: after the warm
    run (cache startup + slot reset + step traced) every occupancy
    change — admission, eviction, reuse, drain — reuses the same
    sharded executables."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe, eng = _tp_engine(scope)
        warm = [Request(900, np.array([1, 2, 3]), 3, arrival=0.0),
                Request(901, np.array([4, 5]), 2, arrival=0.0)]
        eng.run(warm)
        baseline = exe.compile_count
        reqs = _churn_trace(TinyHP.vocab_size, seed=9)
        _, stats = eng.run(reqs)
        assert stats["finished"] == len(reqs)
        assert exe.compile_count == baseline, (
            "occupancy churn retraced the sharded serving step: %d -> %d"
            % (baseline, exe.compile_count))
