"""The suite's own clock (``tests/conftest.py``): a test that runs past its
``@pytest.mark.time_limit`` fails with the limit's message and the rest of
the run goes on; and no test waits on a process without a timeout."""

import ast
import glob
import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))

TINY = '''
import time

import pytest


@pytest.mark.time_limit(1)
def test_sleeps_past_its_limit():
    time.sleep(600)


def test_after_it():
    assert True
'''


def test_a_test_past_its_limit_fails_with_the_limits_message(tmp_path):
    (tmp_path / "test_tiny.py").write_text(TINY)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [TESTS, os.path.dirname(TESTS)]))
    # were the limit not kept, the 600 s sleep would outlast this timeout
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         str(tmp_path / "test_tiny.py")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "test_sleeps_past_its_limit ran past its time limit of 1 s" \
        in out.stdout, out.stdout
    assert "1 failed, 1 passed" in out.stdout, out.stdout


def _calls_without_a_timeout(source):
    """``subprocess.run`` / ``check_output`` / ``check_call`` / ``call`` and
    any ``.wait()`` / ``.communicate()`` in ``source`` that are given no
    ``timeout`` (by keyword, or as the wait's one positional argument)."""
    missing = []
    for node in ast.walk(ast.parse(source)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        name, owner = node.func.attr, node.func.value
        if name in ("run", "check_output", "check_call", "call"):
            if not (isinstance(owner, ast.Name) and owner.id == "subprocess"):
                continue
        elif name == "wait":
            if node.args:
                continue
        elif name != "communicate":
            continue
        if not any(k.arg == "timeout" for k in node.keywords):
            missing.append(f"{node.lineno}: {ast.unparse(node)[:80]}")
    return missing


def test_every_process_a_test_waits_for_has_a_timeout():
    assert _calls_without_a_timeout(
        "subprocess.run(['x'])\np.wait()\np.communicate(b'')\n"
        "subprocess.run(['x'], timeout=5)\np.wait(5)\n") == [
        "1: subprocess.run(['x'])", "2: p.wait()", "3: p.communicate(b'')"]
    missing = {}
    for path in sorted(glob.glob(os.path.join(TESTS, "*.py"))):
        with open(path) as f:
            found = _calls_without_a_timeout(f.read())
        if found:
            missing[os.path.basename(path)] = found
    assert missing == {}
