"""PR 11 fleet telemetry plane: metrics registry units + Prometheus
line-format conformance, the MetricsServer scrape surface, serve
telemetry windowed rates, replica identity stamping (heartbeat, spans,
endpoint files), fleet rollup/SLO folds, trace_merge, the bench
overhead helper, and the multi-replica CPU e2e acceptance run
(supervise --replicas 2 -> serve replicas -> FleetScraper -> SLO
breach -> graceful drain -> merged fleet trace)."""

import json
import io
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from deeplearning_tpu.elastic import heartbeat as hb
from deeplearning_tpu.obs import flight, metrics, spans
from deeplearning_tpu.obs.fleet import (FleetScraper, SLOPolicy,
                                        compute_rollup,
                                        discover_endpoints,
                                        parse_prometheus_text,
                                        scrape_replica)
from deeplearning_tpu.obs.metrics import MetricsRegistry, MetricsServer
from deeplearning_tpu.serve.telemetry import ServeTelemetry


@pytest.fixture(autouse=True)
def _clean_obs_globals():
    """Every test starts and ends with the process-wide registry and
    tracer disabled and the default flight recorder disarmed."""
    def reset():
        metrics.disable()
        spans.disable()
        rec = flight.get_recorder()
        rec.clear()
        rec.path = None
        rec.config = None
    reset()
    yield
    reset()


# -------------------------------------------------------------- registry
class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("dltpu_x_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        assert reg.counter("dltpu_x_total") is c      # get-or-create
        g = reg.gauge("dltpu_depth")
        g.set(7.0)
        g.inc(-2.0)
        assert g.value == 5.0
        h = reg.histogram("dltpu_lat_ms", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        assert h.count == 4 and h.sum == 555.5
        cum = dict(h._cumulative())
        assert cum["+Inf"] == 4
        assert cum[repr(10.0)] == 2                   # cumulative, sorted

    def test_set_total_is_monotonic(self):
        c = MetricsRegistry().counter("dltpu_mirror_total")
        c.set_total(5.0)
        c.set_total(3.0)                              # source reset: hold
        assert c.value == 5.0
        c.set_total(9.0)
        assert c.value == 9.0

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("dltpu_x_total")
        with pytest.raises(TypeError):
            reg.gauge("dltpu_x_total")

    def test_bad_metric_name_rejected(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("bad name!")

    def test_disabled_helpers_are_inert(self):
        assert not metrics.enabled()
        metrics.inc("dltpu_never_total")
        metrics.set_gauge("dltpu_never", 1.0)
        metrics.observe("dltpu_never_ms", 1.0)
        assert metrics.get_registry() is None

    def test_enabled_helpers_write_one_registry(self):
        reg = metrics.enable()
        assert metrics.enable() is reg                # idempotent
        metrics.inc("dltpu_steps_total", 3)
        metrics.set_gauge("dltpu_step", 17.0)
        metrics.observe("dltpu_step_ms", 2.0, buckets=(1.0, 4.0))
        snap = reg.snapshot()["metrics"]
        assert snap["dltpu_steps_total"]["value"] == 3.0
        assert snap["dltpu_step"]["value"] == 17.0
        assert snap["dltpu_step_ms"]["count"] == 1

    def test_collector_errors_counted_not_raised(self):
        reg = MetricsRegistry()

        def bad(_reg):
            raise RuntimeError("boom")
        reg.register_collector(bad)
        reg.register_collector(bad)                   # identity dedup
        reg.register_collector(
            lambda r: r.gauge("dltpu_ok").set(1.0))
        snap = reg.snapshot()
        assert snap["collect_errors"] == 1
        assert snap["metrics"]["dltpu_ok"]["value"] == 1.0

    def test_dump_writes_snapshot(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("dltpu_x_total").inc()
        path = reg.dump(str(tmp_path / "metrics_registry.json"))
        doc = json.load(open(path))
        assert doc["metrics"]["dltpu_x_total"]["value"] == 1.0


# ----------------------------------------- prometheus format conformance
class TestPrometheusConformance:
    def test_text_round_trips_through_strict_parser(self, monkeypatch):
        monkeypatch.setenv(metrics.RUN_ID_VAR, "run-x")
        monkeypatch.setenv(metrics.REPLICA_VAR, "3")
        reg = MetricsRegistry()
        reg.counter("dltpu_req_total", "requests").inc(42)
        reg.gauge("dltpu_depth").set(2.5)
        h = reg.histogram("dltpu_lat_ms", "latency", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(99.0)
        text = reg.prometheus_text()
        assert "# TYPE dltpu_req_total counter" in text
        assert "# HELP dltpu_req_total requests" in text
        assert "# TYPE dltpu_lat_ms histogram" in text
        samples = parse_prometheus_text(text)   # strict: raises on bad
        flat = {(n, tuple(sorted(lab.items()))): v
                for n, lab, v in samples}
        assert flat[("dltpu_req_total", ())] == 42.0
        assert flat[("dltpu_depth", ())] == 2.5
        assert flat[("dltpu_lat_ms_bucket", (("le", "1.0"),))] == 1.0
        assert flat[("dltpu_lat_ms_bucket", (("le", "+Inf"),))] == 2.0
        assert flat[("dltpu_lat_ms_count", ())] == 2.0
        assert flat[("dltpu_lat_ms_sum", ())] == 99.5
        info = [lab for n, lab, v in samples
                if n == "dltpu_replica_info"]
        assert info == [{"run_id": "run-x", "replica": "3"}]

    def test_parser_rejects_malformed_lines(self):
        for bad in ("dltpu_x not_a_number",
                    "dltpu x 1",
                    'dltpu_x{le="1.0" 2',
                    "# TYPE dltpu_x nonsense"):
            with pytest.raises(ValueError):
                parse_prometheus_text(bad + "\n")

    def test_special_values(self):
        samples = parse_prometheus_text(
            "dltpu_a +Inf\ndltpu_b -Inf\ndltpu_c 1e3\n")
        vals = {n: v for n, _, v in samples}
        assert vals["dltpu_a"] == float("inf")
        assert vals["dltpu_b"] == float("-inf")
        assert vals["dltpu_c"] == 1000.0


# --------------------------------------------------------- scrape server
class TestMetricsServer:
    def test_routes(self):
        reg = MetricsRegistry()
        reg.counter("dltpu_x_total").inc(3)
        calls = []

        def healthz():
            calls.append(1)
            return 200, {"status": "ready", "step": 7}
        with MetricsServer(reg, port=0, healthz_fn=healthz) as srv:
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=5) as r:
                assert "version=0.0.4" in r.headers["Content-Type"]
                text = r.read().decode()
            assert ("dltpu_x_total", {}, 3.0) in \
                parse_prometheus_text(text)
            with urllib.request.urlopen(srv.url + "/metrics.json",
                                        timeout=5) as r:
                snap = json.loads(r.read())
            assert snap["metrics"]["dltpu_x_total"]["value"] == 3.0
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=5) as r:
                hz = json.loads(r.read())
            assert hz == {"status": "ready", "step": 7} and calls
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/nope", timeout=5)
            assert ei.value.code == 404

    def test_scrape_replica_reads_identity(self, monkeypatch):
        monkeypatch.setenv(metrics.RUN_ID_VAR, "run-y")
        monkeypatch.setenv(metrics.REPLICA_VAR, "1")
        reg = MetricsRegistry()
        reg.gauge("dltpu_serve_queue_depth").set(4.0)
        with MetricsServer(reg, port=0,
                           healthz_fn=lambda: (200, {"status": "ready"})
                           ) as srv:
            sample = scrape_replica(srv.url, timeout_s=5.0)
        assert sample["ok"] and sample["status"] == "ready"
        assert sample["run_id"] == "run-y" and sample["replica"] == "1"
        assert sample["metrics"]["dltpu_serve_queue_depth"] == 4.0

    def test_unreachable_replica_is_a_sample_not_a_crash(self):
        sample = scrape_replica("http://127.0.0.1:9", timeout_s=0.2)
        assert sample["ok"] is False
        assert sample["status"] == "unreachable"


# ------------------------------------------------------- telemetry rates
class TestTelemetryRates:
    def test_windowed_rates(self):
        t = ServeTelemetry()
        for _ in range(10):
            t.record_submit()
        t.record_reject()
        t.record_dispatch_latency(0.001, n=4)
        r = t.rates(window_s=10.0)
        # effective window = age of the telemetry (just born), so a
        # startup burst reads as a real rate, not one diluted by the
        # full window
        assert r["requests_per_s"] > 10.0
        assert r["rejects_per_s"] > 0.0
        assert r["completions_per_s"] > 0.0
        assert 0.0 <= r["window_s"] <= 10.0   # rounded to 3 decimals
        snap = t.snapshot()
        assert snap["submitted"] == 10.0
        assert "requests_per_s" in snap and "window_s" in snap

    def test_rates_empty(self):
        r = ServeTelemetry().rates()
        assert r["requests_per_s"] == 0.0


# ----------------------------------------------------- identity stamping
class TestIdentityStamping:
    def test_heartbeat_carries_run_and_replica(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv(hb.RUN_ID_VAR, "run-z")
        monkeypatch.setenv(hb.REPLICA_VAR, "2")
        path = str(tmp_path / "heartbeat.json")
        w = hb.HeartbeatWriter(path, hb.Heartbeat(),
                               interval_s=0.05).start()
        try:
            deadline = time.time() + 5.0
            doc = None
            while time.time() < deadline:
                if os.path.exists(path):
                    doc = json.load(open(path))
                    break
                time.sleep(0.02)
        finally:
            w.stop()
        assert doc and doc["run_id"] == "run-z" and doc["replica"] == "2"

    def test_trace_dump_carries_replica_process_row(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv(metrics.RUN_ID_VAR, "run-z")
        monkeypatch.setenv(metrics.REPLICA_VAR, "5")
        tracer = spans.enable()
        with spans.span("dispatch"):
            pass
        path = tracer.dump(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert doc["otherData"]["run_id"] == "run-z"
        assert doc["otherData"]["replica"] == "5"
        procs = [e for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert procs and procs[0]["args"]["name"] == "replica-5"

    def test_endpoint_files_and_discovery(self, tmp_path, monkeypatch):
        for i in range(2):
            d = tmp_path / f"replica-{i}"
            d.mkdir()
            monkeypatch.setenv(metrics.REPLICA_VAR, str(i))
            p = metrics.write_endpoint(f"http://127.0.0.1:900{i}",
                                       role="serve",
                                       path=str(d / "endpoint.json"))
            assert p and metrics.read_endpoint(p)["replica"] == str(i)
        # written in reverse-looking dir order still sorts by replica id
        assert discover_endpoints(str(tmp_path)) == [
            "http://127.0.0.1:9000", "http://127.0.0.1:9001"]

    def test_write_endpoint_unadvertised_is_noop(self, monkeypatch):
        monkeypatch.delenv(metrics.ENDPOINT_FILE_VAR, raising=False)
        assert metrics.write_endpoint("http://x", role="serve") is None


# ------------------------------------------------------- rollup and SLO
class TestRollupSLO:
    @staticmethod
    def _sample(i, qps=5.0, p99=4.0, status="ready", rejected=0.0):
        return {"url": f"http://r{i}", "ok": True, "status": status,
                "replica": str(i),
                "metrics": {"dltpu_serve_requests_per_s": qps,
                            "dltpu_serve_rejects_per_s": 0.0,
                            "dltpu_serve_e2e_ms_p99": p99,
                            "dltpu_serve_queue_depth": 2.0,
                            "dltpu_serve_requests_total": 100.0,
                            "dltpu_serve_completed_total": 98.0,
                            "dltpu_serve_rejected_total": rejected,
                            "dltpu_serve_timed_out_total": 0.0}}

    def test_rollup_folds(self):
        r = compute_rollup([self._sample(0, qps=5.0, p99=4.0),
                            self._sample(1, qps=7.0, p99=10.0),
                            {"url": "http://r2", "ok": False,
                             "status": "unreachable"}])
        assert r["replicas"] == 3
        assert r["replica_status"] == {"ready": 2, "unreachable": 1}
        assert r["qps_total"] == 12.0
        assert r["e2e_ms_p99_max"] == 10.0
        assert r["e2e_ms_p99_mean"] == 7.0
        assert r["queue_depth_total"] == 4.0
        assert r["requests_total"] == 200.0
        assert "slo" not in r

    def test_slo_p99_and_error_breach(self):
        slo = SLOPolicy(p99_budget_ms=5.0, error_rate_budget=0.1)
        ok = compute_rollup([self._sample(0, p99=4.0)], slo)
        assert ok["slo"]["breach"] is False
        bad = compute_rollup([self._sample(0, p99=50.0,
                                           rejected=90.0)], slo)
        assert bad["slo"]["p99_breach"] and bad["slo"]["error_breach"]
        assert bad["slo"]["breach"] is True
        assert bad["error_rate"] > 0.1

    def test_scraper_appends_and_records_breach(self, tmp_path):
        # a dead endpoint: rollup still lands, status unreachable;
        # error-rate SLO cannot breach on an empty fleet
        fleet_path = str(tmp_path / "fleet.jsonl")
        s = FleetScraper(["http://127.0.0.1:9"],
                         slo=SLOPolicy(p99_budget_ms=1.0),
                         fleet_path=fleet_path, timeout_s=0.2)
        rollup = s.scrape_once()
        assert rollup["replica_status"] == {"unreachable": 1}
        assert s.polls == 1 and s.breaches == 0
        rows = [json.loads(x) for x in open(fleet_path)]
        assert len(rows) == 1 and rows[0]["replicas"] == 1


# ------------------------------------------------------- tool self-tests
class TestToolChecks:
    def test_trace_merge_check(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "trace_merge.py"), "--check"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout


# ------------------------------------------------- multi-replica CPU e2e
@pytest.mark.e2e
class TestFleetE2E:
    def test_supervised_fleet_scrape_breach_drain_merge(self, tmp_path):
        """The ISSUE 11 acceptance run: supervise.py launches 2 serve
        replicas under one run id, load lands on both, the fleet
        scraper's rollup agrees with the per-replica /stats counters, a
        deliberately tiny p99 budget records an slo_breach flight
        event, SIGTERM drains the replicas gracefully, and trace_merge
        joins the per-replica traces into one timeline with 2 process
        rows."""
        wd = str(tmp_path / "fleet")
        env = dict(os.environ)
        env["DLTPU_TRACE"] = "1"
        env.pop("DLTPU_HEARTBEAT", None)
        cmd = [sys.executable, os.path.join(ROOT, "tools",
                                            "supervise.py"),
               "--replicas", "2", "--run-id", "fleet-test",
               "--workdir", wd,
               "--max-restarts", "0",
               # an idle serve replica only advances its activity
               # watermark per dispatched batch — a tight deadline
               # would read "idle" as "wedged"
               "--wedge-deadline", "600",
               "--startup-deadline", "600",
               "--",
               sys.executable, os.path.join(ROOT, "tools", "serve.py"),
               "--model", "mnist_fcn", "--num-classes", "10",
               "--size", "28", "--buckets", "1,4", "--max-wait-ms", "2",
               "--http", "0", "--wedge-deadline-s", "600"]
        log = open(os.path.join(str(tmp_path), "supervise.log"), "w")
        proc = subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
        pids = []
        try:
            # both replicas advertise their scrape endpoint once warm
            deadline = time.time() + 240.0
            endpoints = []
            while time.time() < deadline:
                endpoints = discover_endpoints(wd)
                if len(endpoints) >= 2:
                    break
                assert proc.poll() is None, \
                    f"supervise died rc={proc.returncode}; see " \
                    f"{log.name}"
                time.sleep(0.25)
            assert len(endpoints) == 2, endpoints
            for i in range(2):
                doc = metrics.read_endpoint(
                    os.path.join(wd, f"replica-{i}", "endpoint.json"))
                assert doc["role"] == "serve"
                assert doc["run_id"] == "fleet-test"
                assert doc["replica"] == str(i)
                pids.append(doc["pid"])

            # load on both replicas: one 4-image batch x 3 posts each
            body = io.BytesIO()
            np.save(body, np.zeros((4, 28, 28, 3), np.float32))
            for url in endpoints:
                for _ in range(3):
                    req = urllib.request.Request(
                        url + "/predict", data=body.getvalue(),
                        method="POST")
                    with urllib.request.urlopen(req, timeout=60) as r:
                        assert len(json.loads(r.read())["results"]) == 4

            # scrape: rollup must agree with the per-replica /stats
            # counters; the absurd 1e-4 ms p99 budget injects a breach
            scraper = FleetScraper(
                endpoints, slo=SLOPolicy(p99_budget_ms=1e-4),
                fleet_path=os.path.join(wd, "fleet.jsonl"),
                timeout_s=10.0)
            rollup = scraper.scrape_once()
            stats = []
            for url in endpoints:
                with urllib.request.urlopen(url + "/stats",
                                            timeout=10) as r:
                    stats.append(json.loads(r.read()))
            assert rollup["replicas"] == 2
            assert rollup["replica_status"] == {"ready": 2}
            assert rollup["requests_total"] == \
                sum(s["submitted"] for s in stats) == 24.0
            assert rollup["completed_total"] == \
                sum(s["completed"] for s in stats) == 24.0
            assert rollup["e2e_ms_p99_max"] == \
                pytest.approx(max(s["e2e_ms_p99"] for s in stats))
            assert {(p["replica"], p["run_id"])
                    for p in rollup["per_replica"]} == \
                {("0", "fleet-test"), ("1", "fleet-test")}
            # SLO breach -> flight event in the scraping process
            assert rollup["slo"]["p99_breach"] and scraper.breaches == 1
            breaches = flight.get_recorder().events("slo_breach")
            assert breaches and breaches[0]["signal"] == "p99"
            assert breaches[0]["replicas"] == 2

            # the fleet view renders the breach from fleet.jsonl alone
            view = subprocess.run(
                [sys.executable, os.path.join(ROOT, "tools",
                                              "obs_report.py"),
                 wd, "--fleet"],
                capture_output=True, text=True, timeout=120)
            assert view.returncode == 0, view.stderr
            assert "BREACH" in view.stdout, view.stdout

            # graceful drain: SIGTERM each replica -> trace dumped,
            # supervisor records completion, fleet exits 0
            for pid in pids:
                os.kill(pid, signal.SIGTERM)
            assert proc.wait(timeout=120) == 0
        finally:
            for pid in pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
            log.close()

        # one merged Perfetto timeline, one process row per replica
        out = os.path.join(str(tmp_path), "fleet_trace.json")
        merge = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools",
                                          "trace_merge.py"),
             "--out", out, wd],
            capture_output=True, text=True, timeout=60)
        assert merge.returncode == 0, merge.stderr
        doc = json.load(open(out))
        rows = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"}
        assert rows == {1: "replica-0", 2: "replica-1"}, rows
        assert doc["otherData"]["merged_from"] == 2
        spans_x = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in spans_x} == {1, 2}
        assert any(e["name"] == "serve/dispatch" for e in spans_x)
        labels = {s["label"]: s.get("run_id")
                  for s in doc["otherData"]["sources"]}
        assert labels == {"replica-0": "fleet-test",
                          "replica-1": "fleet-test"}
