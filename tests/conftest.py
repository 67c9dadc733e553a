"""Test config: every test runs on an 8-device virtual CPU mesh (the analog
of the reference's multi-GPU CI boxes), with fresh programs and scope."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# no persistent compile cache under test: nothing outlives a run, and an
# entry compiled for a described TPU topology cannot be read back here
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Give every test fresh default programs + scope + name generator
    (tests build graphs into module-level singletons)."""
    from paddle_tpu.core import framework, unique_name
    from paddle_tpu.core import executor as executor_mod

    prev_main = framework.switch_main_program(framework.Program())
    prev_startup = framework.switch_startup_program(framework.Program())
    old_gen = unique_name.switch()
    scope = executor_mod.Scope()
    executor_mod._scope_stack.append(scope)
    yield
    executor_mod._scope_stack.pop()
    unique_name.switch(old_gen)
    framework.switch_main_program(prev_main)
    framework.switch_startup_program(prev_startup)


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running convergence/book tests")
