"""The generator of in-program randomness follows the place.

`Executor._rng_impl(platform)` is the one rule: a step placed on a TPU
draws from XLA's RngBitGenerator (a typed `rbg` key), any other platform
and the collective path keep the raw threefry key, bit for bit.  There is
no flag; a test that wants the TPU's generator on this host states the
rule's answer itself (monkeypatching `_rng_impl`), the way
tests/test_attention_choice.py states the platform.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import kernel_tuning as kt

P = 0.1  # the benchmark configurations' dropout probability


@pytest.fixture
def tpu_rule(monkeypatch):
    """The rule's answer for a TPU, on whatever this host is."""
    monkeypatch.setattr(fluid.Executor, "_rng_impl",
                        staticmethod(lambda platform: "rbg"))


def _is_rbg(key):
    return (jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
            and str(jax.random.key_impl(key)) == "rbg")


def _programs(build, seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.framework.program_guard(main, startup):
        fetches = build()
    return main, startup, fetches


def _run(main, startup, fetches, feed, steps=1):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        outs = [exe.run(main, feed=feed, fetch_list=list(fetches))
                for _ in range(steps)]
    return [[np.asarray(v) for v in o] for o in outs]


@pytest.mark.parametrize("platform, impl", [
    ("tpu", "rbg"), ("cpu", "threefry"), ("gpu", "threefry"),
    (None, "threefry")])
def test_platform_decides_the_generator(platform, impl):
    assert fluid.Executor._rng_impl(platform) == impl
    prog = fluid.Program()
    prog.random_seed = 11
    key = fluid.Executor(fluid.CPUPlace())._rng_base(prog, platform)
    if impl == "rbg":
        assert _is_rbg(key)
    else:  # the raw key every CPU stream has always started from
        assert key.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(key),
                                      np.asarray(jax.random.PRNGKey(11)))


def test_each_run_path_states_its_platform(monkeypatch):
    """run() (slow then fast path) and run_loop() hand the rule the
    platform of the device the step is placed on; the collective path
    hands it none and replicates a raw threefry key whatever the mesh is
    made of."""
    asked = []
    real = fluid.Executor._rng_base

    def spy(self, program, platform=None):
        key = real(self, program, platform)
        asked.append((platform, key.dtype == jnp.uint32))
        return key

    monkeypatch.setattr(fluid.Executor, "_rng_base", spy)

    def build():
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        h = layers.dropout(layers.fc(x, size=8, act="relu"), 0.5)
        loss = layers.mean(layers.square_error_cost(
            layers.fc(h, size=1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        return [loss]

    feed = {"x": np.ones((16, 4), "float32"),
            "y": np.ones((16, 1), "float32")}
    main, startup, (loss,) = _programs(build)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        del asked[:]
        exe.run(main, feed=feed, fetch_list=[loss])  # slow path
        exe.run(main, feed=feed, fetch_list=[loss])  # fast path
        exe.run_loop(2, main, feed=feed, fetch_list=[loss])
        assert asked == [("cpu", True)] * 3

        config = fluid.DistributeTranspilerConfig()
        config.mode = "collective"
        t = fluid.DistributeTranspiler(config=config)
        t.transpile(0, program=main, pservers="", trainers=2,
                    sync_mode=True, startup_program=startup)
        del asked[:]
        # even a rule that answered rbg for every platform would leave
        # this path on threefry: it names no platform
        monkeypatch.setattr(
            fluid.Executor, "_rng_impl",
            staticmethod(lambda p: "threefry" if p is None else "rbg"))
        (lv,) = exe.run(t.get_trainer_program(), feed=feed,
                        fetch_list=[loss])
        assert asked == [(None, True)] and np.isfinite(lv).all()


def test_cpu_stream_did_not_move():
    """One seeded threefry mask, pinned by hash as the parent commit drew
    it: a CPU-placed step's stream is bit-identical to what it was before
    the generator followed the place."""
    def build():
        return [layers.dropout(layers.data("x", shape=[256]), 0.4)]

    main, startup, fetches = _programs(build, seed=7)
    ((v,),) = _run(main, startup, fetches,
                   {"x": np.ones((64, 256), "float32")})
    assert hashlib.sha256((v == 0).tobytes()).hexdigest() == (
        "64b6d56d2fcbbc75226eae09925662330a0a42b58a09876358aa9aea7221cadd")


def test_rbg_mask_is_bernoulli_per_element(tpu_rule):
    """Keep rate within 4 sigma of 1 - p at p = 0.1 over 1 M elements,
    survivors scaled by exactly 1 / (1 - p)."""
    n = (1024, 1024)

    def build():
        return [layers.dropout(layers.data("x", shape=[n[1]]), P,
                               dropout_implementation="upscale_in_train")]

    before = kt.attribution()["rng_draws"]
    main, startup, fetches = _programs(build)
    ((v,),) = _run(main, startup, fetches, {"x": np.ones(n, "float32")})
    keep = float((v != 0).mean())
    sigma = (P * (1 - P) / v.size) ** 0.5
    assert abs(keep - (1 - P)) < 4 * sigma, (keep, sigma)
    np.testing.assert_allclose(v[v != 0], 1.0 / (1 - P), rtol=1e-6)
    after = kt.attribution()["rng_draws"]
    assert after["rbg"] == before["rbg"] + 1
    assert after["threefry"] == before["threefry"]


def test_rbg_masks_differ_by_site_and_step_and_follow_a_shared_seed(
        tpu_rule):
    def build():
        x = layers.data("x", shape=[512])
        return [layers.dropout(x, 0.5), layers.dropout(x, 0.5),
                layers.dropout(x, 0.5, seed=1234),
                layers.dropout(x, 0.5, seed=1234)]

    main, startup, fetches = _programs(build)
    (a1, b1, s1, t1), (a2, b2, s2, t2) = _run(
        main, startup, fetches, {"x": np.ones((64, 512), "float32")},
        steps=2)

    def same(u, v):
        return np.array_equal(u == 0, v == 0)

    assert not same(a1, b1)  # two sites
    assert not same(a1, a2) and not same(b1, b2)  # two steps
    assert same(s1, t1) and same(s2, t2)  # a shared seed attribute
    assert not same(s1, s2) and not same(s1, a1)  # ... still per step


@pytest.mark.parametrize("impl", ["upscale_in_train", "downgrade_in_infer"])
def test_rbg_backward_sees_the_forward_mask(tpu_rule, impl):
    """dropout_grad re-traces the forward with the forward's op index:
    the gradient is zero exactly where Out is zero, and carries the
    forward's scale elsewhere."""
    def build():
        w = layers.create_parameter(
            [128, 512], "float32",
            default_initializer=fluid.initializer.Constant(1.0))
        out = layers.dropout(w, P, dropout_implementation=impl)
        (_, g), = fluid.backward.append_backward(
            layers.reduce_sum(out))
        return [out, g]

    main, startup, fetches = _programs(build)
    ((out, g),) = _run(main, startup, fetches, {})
    assert 0 < (out == 0).sum() < out.size
    np.testing.assert_array_equal(out == 0, g == 0)
    scale = 1.0 / (1 - P) if impl == "upscale_in_train" else 1.0
    np.testing.assert_allclose(g[g != 0], scale, rtol=1e-6)
    np.testing.assert_allclose(out[out != 0], scale, rtol=1e-6)


def _threefry_bodies_over(text, min_elems):
    """Shapes of the threefry2x32 functions in a StableHLO module that
    hash at least `min_elems` counters (a key's fold hashes 1 or 2)."""
    big = []
    for sig in re.findall(r"func\.func private @threefry2x32[^(]*\(([^)]*)\)",
                          text):
        for dims in re.findall(r"tensor<((?:\d+x)+)ui32>", sig):
            n = int(np.prod([int(d) for d in dims.split("x") if d]))
            if n >= min_elems:
                big.append(dims)
    return big


def test_tpu_lowered_step_draws_with_the_bit_generator():
    """A training step with a dropout, traced as the Executor traces a
    TPU-placed step and cross-lowered for the TPU on this host (PR 21's
    guard): forward and backward each draw from rng_bit_generator, and no
    threefry body over anything the size of a mask: the folds of the key
    (op index, salt) are the only hashes left.  The same step under the
    raw key hashes the whole mask."""
    from paddle_tpu.core.trace import build_traced_function

    rows, width = 256, 512

    def build():
        x = layers.data("x", shape=[width])
        h = layers.dropout(layers.fc(x, size=width, act="relu"), P)
        loss = layers.mean(layers.fc(h, size=1))
        fluid.optimizer.SGD(0.1).minimize(loss)
        return [loss]

    main, startup, (loss,) = _programs(build)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        traced = build_traced_function(main, 0, ("x",), [loss.name], scope,
                                       platform="tpu")

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        def lowered(key):
            args = ({"x": jax.ShapeDtypeStruct((rows, width), jnp.float32)},
                    {n: sds(scope.find_var(n)) for n in traced.ro_names},
                    {n: sds(scope.find_var(n)) for n in traced.rw_names},
                    sds(key))
            return jax.jit(traced.fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()

        tpu_text = lowered(exe._rng_base(main, "tpu"))
        cpu_text = lowered(exe._rng_base(main, "cpu"))
    # one draw in the forward, one in the grad op's re-traced forward,
    # both through one private function that holds the generator op
    assert tpu_text.count("call @_bernoulli") == 2
    assert tpu_text.count("stablehlo.rng_bit_generator") == 1
    assert _threefry_bodies_over(tpu_text, 8) == []
    assert "rng_bit_generator" not in cpu_text
    assert _threefry_bodies_over(cpu_text, rows * width // 2)
