"""dltpu-check v2 (ISSUE 13): concurrency auditor — DLT200–205 lint
rules, the static lock-order graph, the runtime thread sanitizer, and
the shared-ratchet CI plumbing.

Every rule gets a seeded synthetic violation AND a clean counterpart;
the seeded lock-order cycle is caught twice — statically by DLT201 and
live by ``threadsan`` when the same module runs both orders in one
thread (single-threaded inversion is enough: no timing lottery).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import types

import pytest

from deeplearning_tpu.analysis import concurrency as conc
from deeplearning_tpu.analysis import lint, threadsan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return [f.rule for f in findings]


def clint(src, path="deeplearning_tpu/serve/synthetic.py"):
    return conc.lint_source(textwrap.dedent(src), path)


def _clean_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


# -------------------------------------------------------- DLT200–205
class TestConcurrencyRules:
    def test_dlt200_shared_attr_thread_vs_public_unlocked(self):
        src = """
            import threading
            class Zoo:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._last = {}
                def _run(self):
                    self._last["a"] = 1
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()
                    t.join()
                def touch(self, k):
                    self._last[k] = 2
        """
        assert "DLT200" in rules_of(clint(src))

    def test_dlt200_clean_when_both_sides_locked(self):
        src = """
            import threading
            class Zoo:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._last = {}
                def _run(self):
                    with self._lock:
                        self._last["a"] = 1
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()
                    t.join()
                def touch(self, k):
                    with self._lock:
                        self._last[k] = 2
        """
        assert "DLT200" not in rules_of(clint(src))

    def test_dlt200_catches_router_refresh_race(self):
        """The ISSUE 15 satellite bug, in miniature: the router's
        health refresh rebuilt ``self._urls`` with no lock while its
        background poller wrote the same attribute — DLT200 must flag
        the unlocked public write side."""
        src = """
            import threading
            class Router:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._urls = []
                def _poll(self):
                    self._urls = ["http://a"]
                def start(self):
                    t = threading.Thread(target=self._poll,
                                         daemon=True)
                    t.start()
                    t.join()
                def refresh(self, urls):
                    self._urls = list(urls)
        """
        assert "DLT200" in rules_of(clint(src))

    def test_dlt200_clean_router_refresh_fixed(self):
        """The shipped fix: probe outside the lock, write the new set
        back UNDER the lock on every side — no finding."""
        src = """
            import threading
            class Router:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._urls = []
                def _poll(self):
                    with self._lock:
                        self._urls = ["http://a"]
                def start(self):
                    t = threading.Thread(target=self._poll,
                                         daemon=True)
                    t.start()
                    t.join()
                def refresh(self, urls):
                    probed = list(urls)
                    with self._lock:
                        self._urls = probed
        """
        assert "DLT200" not in rules_of(clint(src))

    def test_dlt201_inconsistent_lock_order(self):
        src = """
            import threading
            A = threading.Lock()
            B = threading.Lock()
            def f():
                with A:
                    with B:
                        pass
            def g():
                with B:
                    with A:
                        pass
        """
        assert "DLT201" in rules_of(clint(src))

    def test_dlt201_clean_consistent_order(self):
        src = """
            import threading
            A = threading.Lock()
            B = threading.Lock()
            def f():
                with A:
                    with B:
                        pass
            def g():
                with A:
                    with B:
                        pass
        """
        assert "DLT201" not in rules_of(clint(src))

    def test_dlt202_indefinite_block_under_lock(self):
        src = """
            import threading
            L = threading.Lock()
            def f(q, t):
                with L:
                    item = q.get()
                    t.join()
                return item
        """
        assert rules_of(clint(src)).count("DLT202") == 2

    def test_dlt202_clean_with_timeouts(self):
        src = """
            import threading
            L = threading.Lock()
            def f(q, t):
                with L:
                    item = q.get(timeout=1.0)
                    t.join(2.0)
                return item
        """
        assert "DLT202" not in rules_of(clint(src))

    def test_dlt203_non_daemon_thread_never_joined(self):
        src = """
            import threading
            def f():
                t = threading.Thread(target=print)
                t.start()
        """
        assert "DLT203" in rules_of(clint(src))

    def test_dlt203_clean_when_joined(self):
        src = """
            import threading
            def f():
                t = threading.Thread(target=print)
                t.start()
                t.join()
        """
        assert "DLT203" not in rules_of(clint(src))

    def test_dlt204_thread_outside_registry(self):
        src = """
            import threading
            def f():
                t = threading.Thread(target=print, daemon=True)
                t.start()
        """
        assert "DLT204" in rules_of(clint(src))

    def test_dlt204_registry_file_is_exempt(self):
        src = """
            import threading
            def spawn(target):
                t = threading.Thread(target=target, daemon=True)
                t.start()
                return t
        """
        findings = conc.lint_source(textwrap.dedent(src),
                                    conc.THREAD_REGISTRY)
        assert "DLT204" not in rules_of(findings)

    def test_dlt205_check_then_use_across_lock_regions(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.d = {}
                def get(self, k):
                    if k in self.d:
                        with self._lock:
                            return self.d[k]
                    return None
        """
        assert "DLT205" in rules_of(clint(src))

    def test_dlt205_clean_same_region(self):
        src = """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.d = {}
                def get(self, k):
                    with self._lock:
                        if k in self.d:
                            return self.d[k]
                    return None
        """
        assert "DLT205" not in rules_of(clint(src))

    def test_pragma_suppresses_concurrency_rule(self):
        src = """
            import threading
            def f():
                # dltpu: allow(DLT204) test harness helper
                t = threading.Thread(target=print, daemon=True)
                t.start()
        """
        assert "DLT204" not in rules_of(clint(src))

    def test_rules_table_is_complete(self):
        assert sorted(conc.RULES) == [
            "DLT200", "DLT201", "DLT202", "DLT203", "DLT204", "DLT205"]


# ------------------------------------------------- static order graph
class TestLockOrderGraph:
    def test_real_tree_graph_shape(self):
        g = conc.lock_order_graph(REPO)
        assert len(g["locks"]) > 0
        assert len(g["spawn_sites"]) > 0
        assert g["cycles"] == []          # the repo itself must be clean

    def test_seeded_cycle_is_reported(self, tmp_path):
        mod = tmp_path / "deeplearning_tpu" / "cyc.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(textwrap.dedent("""
            import threading
            A = threading.Lock()
            B = threading.Lock()
            def f():
                with A:
                    with B:
                        pass
            def g():
                with B:
                    with A:
                        pass
        """))
        g = conc.lock_order_graph(str(tmp_path))
        assert len(g["edges"]) >= 2
        assert len(g["cycles"]) == 1
        # nodes carry the file:line join key the sanitizer seeds from
        for meta in g["locks"].values():
            assert meta["path"].endswith("cyc.py")
            assert meta["line"] > 0


# ------------------------------------------------------------ threadsan
@pytest.fixture()
def sanitizer():
    """Armed sanitizer with clean state; always disarmed afterwards so
    other tests in the process see raw threading."""
    threadsan.reset()
    yield threadsan
    threadsan.disable()
    threadsan.reset()


class TestThreadsan:
    def test_proxy_patch_and_restore(self, sanitizer):
        fake = types.ModuleType("dltpu_fake_fleet")
        fake.threading = threading
        patched = sanitizer.enable([fake], seed_static=False)
        assert patched == ["dltpu_fake_fleet"]
        lk = fake.threading.Lock()
        assert isinstance(lk, threadsan.InstrumentedLock)
        assert fake.threading.current_thread() is threading.current_thread()
        sanitizer.disable()
        assert fake.threading is threading
        assert not sanitizer.enabled()

    def test_single_thread_order_inversion_raises(self, sanitizer):
        a = threadsan.InstrumentedLock()
        b = threadsan.InstrumentedLock()
        with a:
            with b:
                pass
        with pytest.raises(threadsan.LockOrderError) as exc:
            with b:
                with a:
                    pass
        report = exc.value.report
        assert report["violation"]["kind"] == "lock-order-inversion"
        assert a.site in report["violation"]["cycle"]
        assert b.site in report["violation"]["cycle"]
        assert sanitizer.status()["violations"] == 1

    def test_release_unheld_raises(self, sanitizer):
        a = threadsan.InstrumentedLock()
        a._inner.acquire()             # lock held but never recorded
        with pytest.raises(threadsan.LockOrderError,
                           match="release-unheld"):
            a.release()

    def test_rlock_reentry_is_not_an_edge(self, sanitizer):
        r = threadsan.InstrumentedLock(reentrant=True)
        with r:
            with r:
                pass
        assert sanitizer.status()["runtime_edges"] == 0

    def test_static_seed_joins_runtime_check(self, sanitizer):
        a = threadsan.InstrumentedLock()
        b = threadsan.InstrumentedLock()

        def meta(lock):
            path, line = lock.site.rsplit(":", 1)
            return {"path": path, "line": int(line), "name": "x"}

        n = sanitizer.seed_static_edges({
            "locks": {"LA": meta(a), "LB": meta(b)},
            "edges": [{"src": "LA", "dst": "LB",
                       "path": "x.py", "line": 1, "func": "f"}],
        })
        assert n == 1
        # runtime never saw a->b; the STATIC edge alone closes the cycle
        with pytest.raises(threadsan.LockOrderError):
            with b:
                with a:
                    pass

    def test_status_and_autopsy_shapes(self, sanitizer):
        lk = threadsan.InstrumentedLock()
        with lk:
            pass
        st = sanitizer.status()
        assert st["locks_instrumented"] >= 1
        assert st["ring_events"] >= 2
        rep = sanitizer.autopsy()
        assert rep["held_here"] == []
        assert lk.site in rep["locks"]


# ------------------------------- seeded cycle: static AND runtime catch
CYCLE_MODULE = """\
import threading

A = None
B = None

def init():
    global A, B
    A = threading.Lock()
    B = threading.Lock()

def f():
    with A:
        with B:
            pass

def g():
    with B:
        with A:
            pass
"""


class TestSeededCycleBothLayers:
    """Acceptance criterion: one seeded lock-order cycle is reported by
    the static analyzer AND trips the runtime sanitizer."""

    def test_static_layer_reports_dlt201(self):
        findings = conc.lint_source(CYCLE_MODULE, "pkg/cyc.py")
        assert "DLT201" in rules_of(findings)

    def test_runtime_layer_raises(self, sanitizer, tmp_path):
        import importlib.util
        path = tmp_path / "dltpu_cyc_mod.py"
        path.write_text(CYCLE_MODULE)
        spec = importlib.util.spec_from_file_location(
            "dltpu_cyc_mod", str(path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        try:
            assert sanitizer.enable([mod], seed_static=False)
            mod.init()                 # locks built through the proxy
            mod.f()                    # A -> B
            with pytest.raises(threadsan.LockOrderError):
                mod.g()                # B -> A closes the cycle
        finally:
            sys.modules.pop("dltpu_cyc_mod", None)


# ------------------------------------------------- ratchet + CI plumbing
class TestConcurrencyRatchet:
    SRC = """
        import threading
        def f():
            t = threading.Thread(target=print, daemon=True)
            t.start()
    """

    def test_dlt2_findings_ride_the_shared_baseline(self, tmp_path):
        findings = clint(self.SRC)
        assert "DLT204" in rules_of(findings)
        bl_path = tmp_path / "baseline.json"
        lint.write_baseline(findings, str(bl_path))
        baseline = lint.load_baseline(str(bl_path))
        assert lint.new_findings(findings, baseline) == []
        # one MORE violation of the same rule in the same file is NEW
        doubled = findings + findings
        assert len(lint.new_findings(doubled, baseline)) == 1

    def test_repo_tree_has_no_concurrency_debt(self):
        st = conc.ratchet_status(REPO)
        assert st["clean"], st["new"]
        assert st["baseline_findings"] == 0
        assert st["findings"] == 0

    def test_ci_warns_on_stale_baseline_entry(self, tmp_path):
        (tmp_path / "ok.py").write_text("x = 1\n")
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(
            {"counts": {"gone.py": {"DLT204": 2}}}))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--ci", "--root", str(tmp_path), "--baseline", str(bl)],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "baseline entry for missing file" in proc.stdout
        assert "gone.py" in proc.stdout

    def test_update_baseline_prunes_stale_entries(self, tmp_path):
        (tmp_path / "ok.py").write_text("x = 1\n")
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(
            {"counts": {"gone.py": {"DLT204": 2}}}))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--update-baseline", "--root", str(tmp_path),
             "--baseline", str(bl)],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "pruned" in proc.stdout
        assert "gone.py" not in json.loads(bl.read_text()).get(
            "counts", {})

    def test_ci_fails_on_seeded_concurrency_violation(self, tmp_path):
        pkg = tmp_path / "deeplearning_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(textwrap.dedent(self.SRC))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--ci", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "nope.json")],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "DLT204" in proc.stdout

    def test_rules_flag_groups_both_families(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--rules"],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "DLT100" in proc.stdout
        assert "DLT200" in proc.stdout and "DLT205" in proc.stdout

    def test_json_output_carries_lock_order_graph(self, tmp_path):
        mod = tmp_path / "deeplearning_tpu" / "nested.py"
        mod.parent.mkdir(parents=True)
        mod.write_text(textwrap.dedent("""
            import threading
            A = threading.Lock()
            B = threading.Lock()
            def f():
                with A:
                    with B:
                        pass
        """))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--json", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "nope.json")],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert len(payload["lock_order_edges"]) >= 1
        assert payload["lock_order_cycles"] == []
        assert "stale_baseline" in payload
