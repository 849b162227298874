"""Launcher tests (paddle CLI / cluster_train analog): collective-mode
rank wiring + coordination bootstrap, pserver-mode role orchestration via
the existing dist_mlp runner, and fail-fast teardown."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

# multi-process / full-train-cycle integration tests: excluded from the
# default fast run (pytest.ini addopts -m "not slow"); run with -m "" 
pytestmark = pytest.mark.slow

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


def _run_launch(args, extra_env=None, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch"] + args,
        env=env,
        cwd=_REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=timeout,
    )
    return proc.returncode, proc.stdout


@pytest.mark.slow
def test_launch_collective_psum():
    """2 launched ranks bootstrap jax.distributed from the launcher's env
    and psum their ranks: both print PSUM 1.0 (0+1)."""
    rc, out = _run_launch(
        ["--nproc", "2", os.path.join(_DIR, "launch_worker.py")]
    )
    assert rc == 0, out
    psums = [l for l in out.splitlines() if "PSUM" in l]
    assert len(psums) == 2, out
    assert all(l.strip().endswith("1.0") for l in psums), psums


@pytest.mark.slow
def test_launch_pserver_mode_dist_mlp():
    """pserver mode spawns 2 pservers + 2 trainers around dist_mlp.py and
    every trainer converges (LOSSES decreasing)."""
    rc, out = _run_launch(
        ["--mode", "pserver", "--nproc", "2", "--pservers", "2",
         os.path.join(_DIR, "dist_mlp.py")],
        extra_env={"DIST_STEPS": "4"},
    )
    assert rc == 0, out
    losses = []
    for line in out.splitlines():
        if "LOSSES " in line:
            losses.append(json.loads(line.split("LOSSES ", 1)[1]))
    assert len(losses) == 2, out
    for ls in losses:
        assert np.isfinite(ls).all() and ls[-1] < ls[0], ls


@pytest.mark.slow
def test_launch_fail_fast():
    """A failing rank tears the cluster down and surfaces its exit code."""
    rc, out = _run_launch(
        ["--nproc", "2", os.path.join(_DIR, "launch_worker.py")],
        extra_env={"LAUNCH_WORKER_FAIL_RANK": "1"},
        timeout=120,
    )
    assert rc == 3, (rc, out)
