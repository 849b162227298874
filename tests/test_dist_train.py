"""Multi-process distributed training on localhost
(test_dist_base.py:34 TestDistBase.check_with_place analog): spawn real
pserver + trainer subprocesses, compare dist losses to a local run."""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

# multi-process / full-train-cycle integration tests: excluded from the
# default fast run (pytest.ini addopts -m "not slow"); run with -m "" 
pytestmark = pytest.mark.slow

_DIR = os.path.dirname(os.path.abspath(__file__))
_RUNNER = os.path.join(_DIR, "dist_mlp.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(env):
    full = dict(os.environ)
    full.update(env)
    full["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, _RUNNER],
        env=full,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


def _losses(proc, timeout=240):
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, "runner failed:\n%s\n%s" % (out, err)
    for line in out.splitlines():
        if line.startswith("LOSSES "):
            return json.loads(line[len("LOSSES "):])
    raise AssertionError("no LOSSES line in output:\n%s\n%s" % (out, err))


def _wait_port(port, timeout=60):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError("pserver port %d never opened" % port)


def _run_cluster(n_trainers, sync=True, steps=4, extra_env=None):
    ports = [_free_port(), _free_port()]
    eps = ",".join("127.0.0.1:%d" % p for p in ports)
    common = {
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": str(n_trainers),
        "DIST_SYNC_MODE": "1" if sync else "0",
        "DIST_STEPS": str(steps),
    }
    common.update(extra_env or {})
    pservers = [
        _spawn(
            dict(
                common,
                PADDLE_TRAINING_ROLE="PSERVER",
                PADDLE_CURRENT_ENDPOINT="127.0.0.1:%d" % p,
            )
        )
        for p in ports
    ]
    try:
        for p in ports:
            _wait_port(p)
        trainers = [
            _spawn(
                dict(
                    common,
                    PADDLE_TRAINING_ROLE="TRAINER",
                    PADDLE_TRAINER_ID=str(i),
                )
            )
            for i in range(n_trainers)
        ]
        losses = [_losses(t) for t in trainers]
        for ps in pservers:
            ps.communicate(timeout=90)
        return losses
    finally:
        for ps in pservers:
            if ps.poll() is None:
                ps.kill()


def _local_losses(steps=4, extra_env=None):
    env = {"PADDLE_TRAINING_ROLE": "LOCAL", "DIST_STEPS": str(steps)}
    env.update(extra_env or {})
    proc = _spawn(env)
    return _losses(proc)


@pytest.mark.slow
def test_dist_sync_1trainer_matches_local():
    """1 trainer + 2 pservers sync == local run exactly (same data, same
    init by construction: identical seeded startup on trainer & pservers)."""
    local = _local_losses()
    (dist,) = _run_cluster(1, sync=True)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dist_sync_2trainers_matches_local_global_batch():
    """2 trainers on half-batches, grads averaged on pservers == local
    full-batch run: mean of the two trainers' losses equals the local loss
    at every step."""
    local = _local_losses()
    l0, l1 = _run_cluster(2, sync=True)
    merged = (np.array(l0) + np.array(l1)) / 2.0
    np.testing.assert_allclose(merged, local, rtol=2e-3, atol=1e-4)


@pytest.mark.slow
def test_dist_adam_lr_decay_matches_local():
    """Adam + exponential LR decay + per-param lr: the decay chain moves to
    the pservers (lrsched role), moments are sliced per block, beta pows
    are per-block copies — dist must still match local exactly."""
    env = {"DIST_OPTIMIZER": "adam_decay"}
    local = _local_losses(steps=5, extra_env=env)
    (dist,) = _run_cluster(1, sync=True, steps=5, extra_env=env)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dist_async_trains():
    """Async mode: no barriers; loss must still go down."""
    losses = _run_cluster(2, sync=False, steps=6)
    for l in losses:
        assert l[-1] < l[0]


@pytest.mark.slow
def test_dist_sparse_lookup_table_matches_local():
    """Distributed lookup table: embedding rows sharded over pservers,
    prefetch forward + sparse SGD backward at the round barrier —
    1-trainer run matches the local plain-embedding run exactly."""
    env = {"DIST_MODEL": "sparse"}
    local = _local_losses(steps=5, extra_env=env)
    (dist,) = _run_cluster(1, sync=True, steps=5, extra_env=env)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dist_sparse_lookup_momentum_matches_local():
    """Sparse momentum on the pserver: the densified
    SparseMomentumFunctor rule per shard (every row's velocity decays
    each round, momentum_op.h:343) — dist matches the local is_sparse
    momentum run exactly."""
    env = {"DIST_MODEL": "sparse", "DIST_OPTIMIZER": "momentum"}
    local = _local_losses(steps=6, extra_env=env)
    (dist,) = _run_cluster(1, sync=True, steps=6, extra_env=env)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dist_sparse_lookup_adam_decay_matches_local():
    """VERDICT r4 #6: the sparse pserver path beyond SGD — the table's
    ADAM slot state (moments + beta pows) lives per shard on the
    pserver, the lr comes DECAYED from the pserver's lr_program, and the
    dist run matches the local lazy-adam (is_sparse) run exactly."""
    env = {"DIST_MODEL": "sparse", "DIST_OPTIMIZER": "adam_decay"}
    local = _local_losses(steps=6, extra_env=env)
    (dist,) = _run_cluster(1, sync=True, steps=6, extra_env=env)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dist_sparse_adam_skewed_shard_matches_local():
    """Code-review r5 E2E: ids chosen so EVERY row hashes to pserver 0 —
    pserver 1's shard sees only rowless rounds, whose adam beta pows
    must still advance in lockstep with the local run (the stall the
    per-round advance exists to prevent)."""
    env = {"DIST_MODEL": "sparse", "DIST_OPTIMIZER": "adam_decay",
           "DIST_SPARSE_IDS": "even"}
    local = _local_losses(steps=6, extra_env=env)
    (dist,) = _run_cluster(1, sync=True, steps=6, extra_env=env)
    np.testing.assert_allclose(dist, local, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_collective_mode_2process_matches_local():
    """Collective dense-grad backend over a REAL 2-process mesh
    (launch --mode collective + jax.distributed/gloo): every trainer
    reports the same global (pmean'd) loss trajectory, it matches the
    local full-batch run to reduction-order tolerance, and the COUNTERS
    line proves zero rpc round trips — the dense path never leaves the
    compiled step."""
    local = _local_losses()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DIST_MODE="collective", DIST_STEPS="4")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--mode", "collective", "--nproc", "2", "tests/dist_mlp.py"],
        cwd=_DIR + "/..", env=env, timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    text = r.stdout.decode()
    assert r.returncode == 0, text
    losses, counters = [], []
    for line in text.splitlines():
        pos = line.find("LOSSES ")
        if pos >= 0:
            losses.append(json.loads(line[pos + len("LOSSES "):]))
        pos = line.find("COUNTERS ")
        if pos >= 0:
            counters.append(json.loads(line[pos + len("COUNTERS "):]))
    assert len(losses) == 2 and len(counters) == 2, text
    # both replicas report the SAME allreduced trajectory
    np.testing.assert_allclose(losses[0], losses[1], rtol=0)
    np.testing.assert_allclose(losses[0], local, rtol=1e-5, atol=1e-7)
    for c in counters:
        assert c["rpc_round_trips"] == 0, c
        assert c.get("rpc_verbs") == {}, c


_NCCL2_RUNNER = os.path.join(_DIR, "dist_nccl2.py")


def _spawn_nccl2(env):
    full = dict(os.environ)
    full.update(env)
    return subprocess.Popen(
        [sys.executable, _NCCL2_RUNNER],
        env=full,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )


@pytest.mark.slow
def test_nccl2_mode_2process_matches_local():
    """nccl2 (multi-host collective DP) path: 2 localhost processes
    bootstrap jax.distributed, psum-average grads over the cross-process
    axis; losses match the 1-process full-batch run
    (test_dist_base.py:34 nccl2 coverage)."""
    port = _free_port()
    coord = "127.0.0.1:%d" % port
    common = {"COORDINATOR": coord, "DIST_STEPS": "4"}
    procs = [
        _spawn_nccl2(
            dict(common, PADDLE_TRAINERS="2", PADDLE_TRAINER_ID=str(i))
        )
        for i in range(2)
    ]
    dist = [_losses(p, timeout=180) for p in procs]
    # both replicas report the same (allreduced) loss
    np.testing.assert_allclose(dist[0], dist[1], rtol=1e-6)

    solo = _spawn_nccl2(
        {
            "COORDINATOR": "127.0.0.1:%d" % _free_port(),
            "DIST_STEPS": "4",
            "PADDLE_TRAINERS": "1",
            "PADDLE_TRAINER_ID": "0",
        }
    )
    local = _losses(solo, timeout=180)
    np.testing.assert_allclose(dist[0], local, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_pserver_checkpoint_kill_and_restart(tmp_path):
    """Fault tolerance (go/pserver service.go:346 capability): async
    pserver checkpoints every round; killing it mid-training and
    restarting recovers from the snapshot (PSERVER RESTORED) and the
    trainer — whose RPC layer retries through the outage — finishes all
    steps with finite losses."""
    port = _free_port()
    eps = "127.0.0.1:%d" % port
    ckpt = str(tmp_path / "ckpt")
    common = {
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "1",
        "DIST_SYNC_MODE": "0",
        "DIST_STEPS": "14",
        "DIST_STEP_SLEEP": "0.4",
        "PADDLE_PSERVER_CKPT_DIR": ckpt,
        "PADDLE_PSERVER_CKPT_EVERY": "1",
        "FLAGS_max_retry": "200",
    }
    ps_env = dict(
        common,
        PADDLE_TRAINING_ROLE="PSERVER",
        PADDLE_CURRENT_ENDPOINT=eps,
    )
    ps1 = _spawn(ps_env)
    try:
        _wait_port(port)
        trainer = _spawn(
            dict(common, PADDLE_TRAINING_ROLE="TRAINER", PADDLE_TRAINER_ID="0")
        )
        # wait until real progress exists: the first shard snapshot on disk
        ckpt_file = os.path.join(ckpt, "pserver_0.ckpt")
        t0 = time.time()
        while time.time() - t0 < 90 and not os.path.exists(ckpt_file):
            time.sleep(0.2)
        assert os.path.exists(ckpt_file), "no checkpoint written before kill"
        time.sleep(0.5)  # let a couple more rounds land
        ps1.kill()
        ps1.wait()
        # restart on the same endpoint; must restore from the snapshot
        ps2 = _spawn(ps_env)
        try:
            losses = _losses(trainer, timeout=360)
            assert len(losses) == 14
            assert np.isfinite(losses).all()
            # recovery, not monotonicity: the restored shard may be a
            # couple of rounds stale, so the loss can bounce right after
            # the restart — but the back half must beat the start
            assert min(losses[7:]) < losses[0], losses
            out, err = ps2.communicate(timeout=90)
            assert "PSERVER RESTORED" in out, (out, err)
        finally:
            if ps2.poll() is None:
                ps2.kill()
    finally:
        if ps1.poll() is None:
            ps1.kill()


def test_pserver_cluster_over_native_transport(tmp_path):
    """The full 2x2 pserver cluster trains over the C++ frame-server
    transport (PADDLE_TPU_NATIVE_RPC=1) with losses identical to the
    Python transport (same wire protocol, native framing/HMAC/IO)."""
    import os
    import subprocess
    import sys

    from paddle_tpu.native import get_lib

    if get_lib() is None:
        pytest.skip("native lib unavailable")

    def run(native):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DIST_STEPS="4",
                   PADDLE_TPU_NATIVE_RPC="1" if native else "0")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--mode", "pserver", "--nproc", "2", "--pservers", "2",
             "tests/dist_mlp.py"],
            cwd=_DIR + "/..", env=env, timeout=600,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        text = r.stdout.decode()
        assert r.returncode == 0, text
        return sorted(l for l in text.splitlines() if "LOSSES" in l)

    native_losses = run(True)
    python_losses = run(False)
    assert native_losses and native_losses == python_losses


_RING_SP_RUNNER = os.path.join(_DIR, "dist_ring_sp.py")


@pytest.mark.slow
def test_multiprocess_ring_attention_matches_dense():
    """Ring attention over an sp mesh SPANNING 2 processes (4 virtual
    devices each): the ppermute kv ring crosses the jax.distributed
    process boundary — the multi-host long-context path — and value +
    q/k/v grad checksums match the single-process dense reference."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("drs", _RING_SP_RUNNER)
    drs = importlib.util.module_from_spec(spec)
    # only for make_qkv/shape constants; no jax work happens at import
    spec.loader.exec_module(drs)

    port = _free_port()
    common = {"COORDINATOR": "127.0.0.1:%d" % port, "PADDLE_TRAINERS": "2"}
    procs = [
        subprocess.Popen(
            [sys.executable, _RING_SP_RUNNER],
            env=dict(os.environ, **common, PADDLE_TRAINER_ID=str(i)),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, "ring sp runner failed:\n%s\n%s" % (
                out, err)
            for line in out.splitlines():
                if line.startswith("CHECKS "):
                    outs.append(json.loads(line[len("CHECKS "):]))
                    break
            else:
                raise AssertionError("no CHECKS line:\n%s" % out)
    finally:
        # a dead coordinator must not orphan its blocked peer
        for p in procs:
            if p.poll() is None:
                p.kill()
    # both processes report the SAME global result
    np.testing.assert_allclose(outs[0]["val"], outs[1]["val"], rtol=1e-6)
    np.testing.assert_allclose(outs[0]["gsums"], outs[1]["gsums"],
                               rtol=1e-6)

    # single-process dense reference on the same arrays
    import jax
    import jax.numpy as jnp

    q, k, v = (jnp.asarray(x) for x in drs.make_qkv())
    Dh = q.shape[-1]

    def dense_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (Dh ** -0.5)
        mask = np.tril(np.ones((drs.T, drs.T), bool))
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    val_ref, grads_ref = jax.value_and_grad(
        dense_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(outs[0]["val"], float(val_ref), rtol=2e-4)
    np.testing.assert_allclose(
        outs[0]["gsums"], [float(jnp.sum(g ** 2)) for g in grads_ref],
        rtol=2e-3)
