"""dltpu-check (ISSUE 8): AST linter rules + ratchet, jaxpr structural
auditor, runtime strict mode, and the CI gate itself.

The linter self-runs here (``TestCiGate``), so a NEW policy violation
anywhere in the tree fails the tier-1 suite — that's the tentpole's
enforcement loop. Every DLT rule also gets a seeded synthetic violation
proving the rule actually fires.
"""

import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.analysis import jaxpr as ana_jaxpr
from deeplearning_tpu.analysis import lint
from deeplearning_tpu.analysis import strict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return [f.rule for f in findings]


def lint_hot(src):
    """Lint a snippet as if it lived in a hot-path module."""
    return lint.lint_source(textwrap.dedent(src),
                            "deeplearning_tpu/train/synthetic.py")


def lint_cold(src):
    return lint.lint_source(textwrap.dedent(src), "pkg/synthetic.py")


# ---------------------------------------------------------------- linter
class TestLintRules:
    def test_dlt100_host_sync_in_hot_path(self):
        src = """
            import jax
            import numpy as np
            def f(x):
                y = jax.device_get(x)
                z = np.asarray(x)
                x.block_until_ready()
                return y, z
        """
        assert rules_of(lint_hot(src)) == ["DLT100"] * 3

    def test_dlt100_silent_outside_hot_path(self):
        src = """
            import jax
            def f(x):
                return jax.device_get(x)
        """
        assert lint_cold(src) == []

    def test_dlt101_use_after_donate(self):
        src = """
            import jax
            def run(f, state, batch):
                step = jax.jit(f, donate_argnums=(1,))
                out = step(f, state, batch)
                return state.params
        """
        found = lint_cold(src)
        assert rules_of(found) == ["DLT101"]
        assert "'state' was donated" in found[0].msg

    def test_dlt101_rebinding_clears_donation(self):
        # the hot-loop idiom: donate and rebind on the same line
        src = """
            import jax
            def run(f, state, batch):
                step = jax.jit(f, donate_argnums=(1,))
                f, state = step(f, state, batch)
                return state.params
        """
        assert lint_cold(src) == []

    def test_dlt102_scalar_closure(self):
        src = """
            import jax
            def outer(x):
                n = x.shape[0]
                def inner(y):
                    return y * n
                return jax.jit(inner)(x)
        """
        found = lint_cold(src)
        assert rules_of(found) == ["DLT102"]
        assert "static_argnums" in found[0].msg

    def test_dlt102_static_argnames_is_clean(self):
        src = """
            import jax
            def outer(x):
                n = x.shape[0]
                def inner(y):
                    return y * n
                return jax.jit(inner, static_argnames=("n",))(x)
        """
        assert lint_cold(src) == []

    def test_dlt102_jit_in_loop(self):
        src = """
            import jax
            def sweep(fns, x):
                outs = []
                for f in fns:
                    outs.append(jax.jit(f)(x))
                return outs
        """
        assert "DLT102" in rules_of(lint_cold(src))

    def test_dlt103_signal_handler(self):
        src = """
            import signal
            import time
            def handler(signum, frame):
                print("dying")
                time.sleep(1)
            signal.signal(signal.SIGTERM, handler)
        """
        assert rules_of(lint_cold(src)) == ["DLT103"] * 2

    def test_dlt103_elastic_subscribe(self):
        src = """
            from deeplearning_tpu.elastic import signals
            def on_term(signum, frame):
                print("bye")
            signals.subscribe(15, on_term)
        """
        assert rules_of(lint_cold(src)) == ["DLT103"]

    def test_dlt104_silent_swallow(self):
        src = """
            def f():
                try:
                    risky()
                except Exception:
                    pass
        """
        assert rules_of(lint_cold(src)) == ["DLT104"]

    def test_dlt104_narrow_or_handled_is_clean(self):
        src = """
            def f():
                try:
                    risky()
                except ValueError:
                    pass
                try:
                    risky()
                except Exception as e:
                    log(e)
        """
        assert lint_cold(src) == []

    def test_dlt105_io_in_traced_fn(self):
        src = """
            import jax
            import time
            @jax.jit
            def f(x):
                print("tracing")
                time.sleep(0.1)
                return x
        """
        assert rules_of(lint_cold(src)) == ["DLT105"] * 2

    def test_syntax_error_is_a_finding(self):
        found = lint.lint_source("def f(:\n", "pkg/broken.py")
        assert rules_of(found) == ["DLT000"]


class TestPragma:
    def test_pragma_on_line(self):
        src = """
            def f():
                try:
                    risky()
                except Exception:  # dltpu: allow(DLT104)
                    pass
        """
        assert lint_cold(src) == []

    def test_pragma_on_line_above(self):
        src = """
            import jax
            def f(x):
                # dltpu: allow(DLT100) designed sync
                return jax.device_get(x)
        """
        assert lint_hot(src) == []

    def test_pragma_wildcard_and_wrong_rule(self):
        base = """
            import jax
            def f(x):
                return jax.device_get(x){pragma}
        """
        ok = textwrap.dedent(base).format(
            pragma="  # dltpu: allow(*)")
        wrong = textwrap.dedent(base).format(
            pragma="  # dltpu: allow(DLT104)")
        assert lint.lint_source(
            ok, "deeplearning_tpu/train/s.py") == []
        assert rules_of(lint.lint_source(
            wrong, "deeplearning_tpu/train/s.py")) == ["DLT100"]


class TestRatchet:
    SRC = """
        def f():
            try:
                risky()
            except Exception:
                pass
    """

    def test_baseline_covers_existing_debt(self, tmp_path):
        findings = lint.lint_source(textwrap.dedent(self.SRC),
                                    "pkg/mod.py")
        path = str(tmp_path / "baseline.json")
        lint.write_baseline(findings, path)
        baseline = lint.load_baseline(path)
        assert baseline["counts"] == {"pkg/mod.py": {"DLT104": 1}}
        assert lint.new_findings(findings, baseline) == []

    def test_new_violation_breaks_the_ratchet(self, tmp_path):
        old = lint.lint_source(textwrap.dedent(self.SRC), "pkg/mod.py")
        path = str(tmp_path / "baseline.json")
        lint.write_baseline(old, path)
        grown = textwrap.dedent(self.SRC) + textwrap.dedent("""
            def g():
                try:
                    risky()
                except Exception:
                    pass
        """)
        new = lint.lint_source(grown, "pkg/mod.py")
        groups = lint.new_findings(new, lint.load_baseline(path))
        assert len(groups) == 1
        assert groups[0]["rule"] == "DLT104"
        assert groups[0]["count"] == 2 and groups[0]["budget"] == 1

    def test_fixing_debt_never_fails(self, tmp_path):
        old = lint.lint_source(textwrap.dedent(self.SRC), "pkg/mod.py")
        path = str(tmp_path / "baseline.json")
        lint.write_baseline(old, path)
        assert lint.new_findings([], lint.load_baseline(path)) == []

    def test_missing_baseline_means_zero_budget(self, tmp_path):
        findings = lint.lint_source(textwrap.dedent(self.SRC),
                                    "pkg/mod.py")
        baseline = lint.load_baseline(str(tmp_path / "nope.json"))
        assert len(lint.new_findings(findings, baseline)) == 1


# --------------------------------------------------------------- CI gate
def _clean_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


class TestCiGate:
    def test_check_ci_clean_and_fast(self):
        """The linter self-runs over the real tree: any NEW finding
        (beyond the committed baseline) fails tier-1 — and the gate
        stays under the 10s budget including interpreter startup."""
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--ci"],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        dt = time.monotonic() - t0
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "dltpu-check: clean" in proc.stdout
        assert dt < 10.0, f"--ci took {dt:.1f}s (budget 10s)"

    def test_check_ci_fails_on_seeded_violation(self, tmp_path):
        pkg = tmp_path / "deeplearning_tpu" / "train"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text(textwrap.dedent("""
            import jax
            def f(x):
                return jax.device_get(x)
        """))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check.py"),
             "--ci", "--root", str(tmp_path),
             "--baseline", str(tmp_path / "absent.json")],
            capture_output=True, text=True, timeout=60,
            env=_clean_env(), cwd=REPO)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "DLT100" in proc.stdout

    def test_update_baseline_roundtrip(self, tmp_path):
        pkg = tmp_path / "deeplearning_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(textwrap.dedent("""
            def f():
                try:
                    risky()
                except Exception:
                    pass
        """))
        base = str(tmp_path / "baseline.json")
        args = [sys.executable,
                os.path.join(REPO, "tools", "check.py"),
                "--root", str(tmp_path), "--baseline", base]
        rec = subprocess.run(args + ["--update-baseline"],
                             capture_output=True, text=True, timeout=60,
                             env=_clean_env(), cwd=REPO)
        assert rec.returncode == 0, rec.stdout + rec.stderr
        gate = subprocess.run(args + ["--ci"], capture_output=True,
                              text=True, timeout=60, env=_clean_env(),
                              cwd=REPO)
        assert gate.returncode == 0, gate.stdout + gate.stderr

    def test_repo_baseline_matches_tree(self):
        """In-process equivalent of --ci: the committed baseline covers
        today's tree."""
        status = lint.ratchet_status()
        assert status["clean"], status["new"]


# -------------------------------------------------------- jaxpr auditor
class TestJaxprAuditor:
    def test_peak_intermediate_measures_biggest_output(self):
        def f(x):
            return jnp.outer(x, x).sum()

        assert ana_jaxpr.peak_intermediate(f, jnp.ones((8,))) == 64

    def test_assert_peak_raises_over_budget(self):
        def f(x):
            return jnp.outer(x, x).sum()

        ana_jaxpr.assert_peak_intermediate_below(f, (jnp.ones((8,)),), 64)
        with pytest.raises(AssertionError):
            ana_jaxpr.assert_peak_intermediate_below(
                f, (jnp.ones((8,)),), 63)

    def test_count_transfers_on_toy_fns(self):
        def moves(x):
            return jax.device_put(x) + 1.0

        def pure(x):
            return x * 2.0

        assert ana_jaxpr.count_transfers(moves, jnp.ones((4,))) == 1
        assert ana_jaxpr.count_transfers(pure, jnp.ones((4,))) == 0

    def test_count_transfers_sees_into_jitted_fns(self):
        @jax.jit
        def nested(x):
            return jax.device_put(x) * 2.0

        assert ana_jaxpr.count_transfers(nested, jnp.ones((4,))) == 1

    def test_count_collectives_with_axis_env(self):
        def f(x):
            return jax.lax.psum(x, "i") + jax.lax.pmax(x, "i")

        got = ana_jaxpr.count_collectives(f, jnp.ones((4,)),
                                          axis_env=[("i", 2)])
        assert got == {"psum": 1, "pmax": 1}

    def test_count_collectives_empty_for_local_fn(self):
        assert ana_jaxpr.count_collectives(lambda x: x + 1,
                                           jnp.ones((3,))) == {}

    def test_builtin_audits_all_pass(self):
        rows = ana_jaxpr.run_audits()
        assert len(rows) >= 4
        bad = [r for r in rows if not r["ok"]]
        assert not bad, bad
        byname = {r["name"]: r for r in rows}
        blocked = byname["nms_blocked_n4096"]
        # bitwise the same bound as the ported test_blocked_nms assert
        assert blocked["budget_elements"] == 4 * 4096 * 256
        assert blocked["peak_elements"] <= blocked["budget_elements"]
        # the control row proves the auditor SEES an N^2 blow-up
        assert byname["nms_reference_n4096"]["peak_elements"] >= 4096 ** 2
        assert byname["train_step_mnist"]["transfers"] == 0

    def test_collective_bytes_sums_operand_sizes(self):
        def f(x):
            return jax.lax.psum(x, "i"), jax.lax.pmax(x[:2], "i")

        got = ana_jaxpr.collective_bytes(f, jnp.ones((1024,)),
                                         axis_env=[("i", 2)])
        assert got == {"psum": 1024 * 4, "pmax": 2 * 4}
        assert ana_jaxpr.collective_bytes(lambda x: x + 1,
                                          jnp.ones((3,))) == {}

    def test_hlo_collectives_parses_text_and_counts_bytes(self):
        text = """HloModule jit_step, num_partitions=8

  %ag = f32[1024]{0} all-gather(f32[128]{0} %x), dimensions={0}
  %ar = bf16[512]{0} all-reduce(bf16[512]{0} %g), to_apply=%add
"""
        got = ana_jaxpr.hlo_collectives(text)
        assert got["all_gather"] == {"count": 1, "bytes": 4096,
                                     "max_bytes": 4096}
        assert got["all_reduce"] == {"count": 1, "bytes": 1024,
                                     "max_bytes": 1024}

    def test_hlo_reclassifies_cpu_style_reduce_scatter(self):
        """XLA:CPU lowers reduce-scatter as all-reduce + 1/n
        dynamic-slice; the auditor reports that pair as reduce_scatter
        (what the same program emits on TPU), but only when the slice is
        exactly 1/num_partitions of the all-reduce output."""
        text = """HloModule jit_step, num_partitions=8

  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %g), to_apply=%add
  %shard = f32[128]{0} dynamic-slice(f32[1024]{0} %ar, s32[] %i)
"""
        got = ana_jaxpr.hlo_collectives(text)
        assert "all_reduce" not in got
        assert got["reduce_scatter"]["count"] == 1
        # opt-out restores the literal reading
        raw = ana_jaxpr.hlo_collectives(text, reclassify_scatter=False)
        assert raw["all_reduce"]["count"] == 1 and "reduce_scatter" not in raw
        # a slice that is NOT a 1/n partition does not reclassify
        other = text.replace("f32[128]{0} dynamic-slice",
                             "f32[100]{0} dynamic-slice")
        assert ana_jaxpr.hlo_collectives(other)["all_reduce"]["count"] == 1

    def test_hlo_reads_combined_all_reduces_and_bare_operands(self):
        """This XLA's spellings: the combiner's one all-reduce with a tuple
        result counts each element as the all-reduce it was; a slice inside
        a fusion names its operand bare (its shape is where the name is
        defined); an async pair counts once, at its ``-done``; a TPU
        layout's tiling nests parentheses in a tuple result."""
        text = """HloModule jit_step, num_partitions=8

%fused (param_0.3: f32[512,256]) -> f32[64,256] {
  %param_0.3 = f32[512,256]{1,0} parameter(0)
  ROOT %dynamic-slice.1 = f32[64,256]{1,0} dynamic-slice(%param_0.3, %c, %c)
}
  %ar = (f32[512,256]{1,0}, f32[4]{0}, /*index=2*/f32[]) all-reduce(%g, %h, %l), to_apply=%add
  %ags = ((bf16[32,768]{1,0}), bf16[256,768]{1,0:T(8,128)(2,1)}) all-gather-start(bf16[32,768]{1,0} %c), dimensions={0}
  %agd = bf16[256,768]{1,0:T(8,128)(2,1)} all-gather-done(%ags)
  %crs = (f32[8]{0:T(256)}, f32[8]{0:T(256)}) all-reduce-start(f32[8]{0} %x)
  %crd = (f32[8]{0:T(256)}, f32[8]{0:T(256)}) all-reduce-done(%crs)
"""
        got = ana_jaxpr.hlo_collectives(text)
        assert got["reduce_scatter"] == {"count": 1, "bytes": 512 * 256 * 4,
                                         "max_bytes": 512 * 256 * 4}
        assert got["all_reduce"] == {"count": 4, "bytes": 16 + 4 + 32 + 32,
                                     "max_bytes": 32}
        assert got["all_gather"] == {"count": 1, "bytes": 256 * 768 * 2,
                                     "max_bytes": 256 * 768 * 2}

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="zero1 audits need >= 2 devices")
    def test_zero1_audit_rows_prove_the_lowering(self):
        """The ISSUE 10 jaxpr-audit satellite: the zero1 row shows
        reduce-scatter + all-gather with no param-sized all-reduce, and
        the replicated control row shows the param-sized all-reduce the
        zero1 lowering eliminated."""
        rows = {r["name"]: r for r in ana_jaxpr.run_audits()}
        n = len(jax.devices())
        z = rows[f"train_step_zero1_dp{n}"]
        c = rows[f"train_step_replicated_dp{n}"]
        assert z["ok"] and c["ok"]
        assert z["hlo_collectives"].get("reduce_scatter", 0) >= 1
        assert z["hlo_collectives"].get("all_gather", 0) >= 1
        # the control moves strictly more all-reduce bytes than zero1
        assert (c["collective_bytes"].get("all_reduce", 0)
                > z["collective_bytes"].get("all_reduce", 0))


# ----------------------------------------------------------- strict mode
class TestStrictMode:
    def test_resolve_specs(self):
        assert strict.resolve("") == frozenset()
        assert strict.resolve("0") == frozenset()
        assert strict.resolve(False) == frozenset()
        assert strict.resolve("1") == frozenset({"transfers"})
        assert strict.resolve(True) == frozenset({"transfers"})
        assert strict.resolve("nans") == frozenset({"nans"})
        both = frozenset({"transfers", "nans"})
        assert strict.resolve("transfers,nans") == both
        assert strict.resolve("threads") == frozenset({"threads"})
        assert strict.resolve("all") == frozenset(
            {"transfers", "nans", "threads"})
        with pytest.raises(ValueError):
            strict.resolve("bogus")

    def test_resolve_env_fallback(self, monkeypatch):
        monkeypatch.setenv("DLTPU_STRICT", "nans")
        assert strict.resolve(None) == frozenset({"nans"})
        monkeypatch.delenv("DLTPU_STRICT")
        assert strict.resolve(None) == frozenset()

    def test_h2d_guard_fires_even_on_cpu(self):
        """End-to-end proof the guard MECHANISM works on this backend:
        CPU copies host→device, so the h2d guard has teeth here even
        though the zero-copy d2h direction is exempt."""
        assert strict.guard_enforced("host_to_device")
        with pytest.raises(Exception):
            with strict.no_transfers("host_to_device"):
                jnp.add(np.ones(2), 1.0)   # implicit H2D

    def test_d2h_guard_teeth_where_enforced(self):
        x = jnp.arange(4.0)
        jax.block_until_ready(x)
        if not strict.guard_enforced("device_to_host"):
            # CPU: guard is inert (zero-copy D2H) — but entering the
            # scope must still be side-effect free
            with strict.no_host_transfers():
                float(x[0])
            return
        with pytest.raises(Exception):
            with strict.no_host_transfers():
                float(x[0])

    def test_debug_nans_restores_flag(self):
        prev = jax.config.jax_debug_nans
        with strict.debug_nans():
            assert jax.config.jax_debug_nans is True
        assert jax.config.jax_debug_nans == prev

    def test_debug_nans_catches_at_the_op(self):
        with strict.debug_nans():
            with pytest.raises(FloatingPointError):
                jnp.zeros(2) / jnp.zeros(2)    # 0/0 raises at the op

    def test_strict_section_counts_nothing_when_off(self):
        with strict.strict_section(frozenset()):
            pass
        with strict.strict_section(frozenset({"transfers"})):
            pass  # d2h guard scope enters/exits cleanly on any backend
