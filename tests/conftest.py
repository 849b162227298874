"""Test harness config: pin the suite (and its subprocesses) to the CPU
backend with an 8-device virtual mesh — the test topology — before JAX
initializes.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# static program verification is ON for the whole suite (the tests/CI
# regime of FLAGS_check_program): every apply_pass postcondition-checks
# its result and every program verifies once before its first compile.
# An explicit env value (e.g. a lane measuring the flag-off cost) wins.
os.environ.setdefault("FLAGS_check_program", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, scope and name counters."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod

    old_main = framework.switch_main_program(fluid.Program())
    old_startup = framework.switch_startup_program(fluid.Program())
    old_gen = unique_name.switch()
    old_scope = scope_mod._switch_scope(scope_mod.Scope())
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    scope_mod._switch_scope(old_scope)
