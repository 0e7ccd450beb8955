"""Test env: 8 virtual CPU devices so mesh/pjit/collective paths run in CI
without a pod (SURVEY.md §4 rebuild strategy (b)).

All settings must be in the environment before JAX initialises its
backends: ``JAX_PLATFORMS=cpu`` keeps the suite (and every CLI a test
spawns, which inherits ``os.environ``) off an attached accelerator, the
host-device-count flag makes the CPU backend report eight devices, and
the last two make XLA:CPU compile quickly. The suite is compile-bound:
XLA:CPU's backend compile was three fifths of its summed test time. At
optimisation level 1 with XLA's older fusion emitters a ResNet-18 value
and gradient compiles in 9.7 s instead of 33.9 and runs as fast (4.48 s a
step against 4.78, one core of a Xeon build box), and its loss and
gradients differ from the default's in 4 of 11,180,104 values, by 4e-9.
A test that times a program compiles it at XLA's defaults itself.

Every test runs under a time limit (``DEFAULT_TIME_LIMIT_S``, or
``@pytest.mark.time_limit(seconds)``): a test that hangs fails with the
limit's message instead of eating the whole run's clock.
"""

import os
import signal
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
# six workers share the cores: an OpenMP team per worker spins against the
# others' threads (sklearn's t-SNE of 40 points: 0.25 s alone, 94 s beside
# five busy workers)
os.environ.setdefault("OMP_NUM_THREADS", "1")
for flag in ("--xla_force_host_platform_device_count=8",
             "--xla_backend_optimization_level=1",
             "--xla_cpu_use_fusion_emitters=false"):
    flags = os.environ.get("XLA_FLAGS", "")
    if flag.split("=")[0] not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from deeplearning_tpu.core.compile_cache import enable_compile_cache  # noqa: E402,E501

jax.config.update("jax_default_matmul_precision", "highest")
# the library's persistent compile cache from the first test on: a program
# one worker (or a CLI it spawns) compiled is read back by every other
enable_compile_cache()

DEFAULT_TIME_LIMIT_S = 240.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "e2e: spawns processes that import jax; run last")
    config.addinivalue_line(
        "markers", "slow: left out of the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's limit instead of "
        f"the default {DEFAULT_TIME_LIMIT_S:.0f} s")


def pytest_collection_modifyitems(config, items):
    """Run subprocess-spawning e2e tests after everything else: each
    child process re-imports jax and recompiles its step from scratch,
    making them the priciest items in the suite — fast unit feedback
    should not queue behind them under a tight CI time budget."""
    tail = [it for it in items if it.get_closest_marker("e2e")]
    if tail:
        tail_set = set(tail)
        items[:] = [it for it in items if it not in tail_set] + tail


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """Arm a real-time timer over the test's setup, call and teardown (so
    a module fixture it is the first to build counts too); when it fires,
    the test fails where it stands with the limit's message."""
    marker = item.get_closest_marker("time_limit")
    seconds = float(marker.args[0]) if marker else DEFAULT_TIME_LIMIT_S
    if threading.current_thread() is not threading.main_thread():
        raise RuntimeError("the time limit needs the main thread")

    def expire(signum, frame):
        pytest.fail(f"{item.nodeid} ran past its time limit of {seconds:g} s",
                    pytrace=False)

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
