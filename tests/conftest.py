"""Test env: 8 virtual CPU devices so mesh/pjit/collective paths run in CI
without a pod (SURVEY.md §4 rebuild strategy (b)).

Both settings must be in the environment before JAX initialises its
backends: ``JAX_PLATFORMS=cpu`` keeps the suite (and every CLI a test
spawns, which inherits ``os.environ``) off an attached accelerator, and
the host-device-count flag makes the CPU backend report eight devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


def pytest_collection_modifyitems(config, items):
    """Run subprocess-spawning e2e tests after everything else: each
    child process re-imports jax and recompiles its step from scratch,
    making them the priciest items in the suite — fast unit feedback
    should not queue behind them under a tight CI time budget."""
    tail = [it for it in items if it.get_closest_marker("e2e")]
    if tail:
        tail_set = set(tail)
        items[:] = [it for it in items if it not in tail_set] + tail
