"""Without a chip the command prints no result and exits non-zero; a rehearsal
on the CPU carries no device metric."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_REHEARSE, ROOT


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "vit_b16_train_b128",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_cpu_rehearsal_reports_no_device_metric():
    from benchmarks import run as bench_run
    line, _ = bench_run.execute("rehearse_vit_micro", 3000000019, 0.5, True,
                                require_tpu=False, bench_file=BENCH_REHEARSE)
    json.dumps(line)
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["correct"] is True


def test_a_renamed_hook_or_a_missing_memory_figure_ends_the_run():
    """What the driver reaches into the program for fails by name, and a chip
    without ``peak_bytes_reserved`` is no 1.86 GB cell."""
    from benchmarks.drivers import train as driver
    from benchmarks.harness import spec

    class Renamed:
        pass

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    with pytest.raises(spec.SpecError, match="_aot_step"):
        driver._hook(Renamed(), "_aot_step", "for the compiled step's text")
    with pytest.raises(spec.SpecError, match="peak_bytes_reserved"):
        driver._memory_peak([Chip({"peak_bytes_in_use": 1})], on_chip=True)
    assert driver._memory_peak([Chip(None)], on_chip=False) == 0
    full = {"peak_bytes_in_use": 2, "peak_bytes_reserved": 5}
    assert driver._memory_peak([Chip(full), Chip({**full, "peak_bytes_in_use": 1})],
                               on_chip=True) == 7


def test_compile_cache_is_the_checkouts_whatever_the_machine_offers():
    """JAX reads the variable when it is first imported: the command has to set
    it before any of its imports reaches JAX (PR 24: it did not, and the runs
    were served by the chip machine's own cache)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR="/nonexistent/machine_cache")
    out = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; sys.argv = ['run.py', '--help']\n"
         "try:\n    runpy.run_path('benchmarks/run.py', run_name='__main__')\n"
         "except SystemExit:\n    pass\n"
         "from benchmarks.harness import check\n"
         "import jax; print('DIR', jax.config.jax_compilation_cache_dir, "
         "jax.config.jax_compilation_cache_max_size)"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    where = [l for l in out.stdout.splitlines() if l.startswith("DIR ")][-1].split()
    assert where[1] == os.path.join(ROOT, ".bench_cache", "jax")
    assert int(where[2]) == 16 * 2 ** 30
