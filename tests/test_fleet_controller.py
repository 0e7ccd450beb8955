"""Fleet controller (deeplearning_tpu/fleet): scaling policy hysteresis
and cooldown, rollup counter deltas, edge-triggered SLO breach events,
live-only endpoint discovery, supervisor stop/restart directives, the
replica set lifecycle, batcher drain semantics, router failover, the
loadgen per-second timeline — and the ISSUE 14 acceptance choreography:
a controller-run 3-replica CPU serve fleet under open-loop load
survives an injected wedge (drain → requeue → replacement warms → p99
recovers) and an injected preemption (exit 75 → immediate
replace-or-shed verdict), with every decision in the flight record."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from deeplearning_tpu.elastic.supervisor import (EXIT_PREEMPTED,
                                                 EXIT_WEDGED, Supervisor,
                                                 SupervisorConfig,
                                                 exit_for_outcome,
                                                 worst_outcome)
from deeplearning_tpu.fleet import (FleetController, FleetPolicy,
                                    FleetRouter, ReplicaSet,
                                    CONTROLLER_FLIGHT_FILE)
from deeplearning_tpu.obs import flight
from deeplearning_tpu.obs.fleet import (FleetScraper, discover_endpoints,
                                        rollup_delta)
from deeplearning_tpu.serve.admission import Ewma

SLEEPER = [sys.executable, "-c", "import time; time.sleep(60)"]


def _wait(cond, timeout=30.0, interval=0.05, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _rollup(p99=0.0, queue=0.0, qps=0.0, err=0.0, delta=None):
    """Minimal rollup with a healthy delta window unless overridden."""
    if delta is None:
        delta = {"dt_s": 1.0, "requests_total": qps,
                 "rejected_total": 0.0, "timed_out_total": 0.0}
    return {"e2e_ms_p99_max": p99, "queue_depth_total": queue,
            "qps_total": qps, "error_rate": err, "delta": delta}


# ----------------------------------------------------------------- ewma
class TestEwma:
    def test_first_sample_seeds(self):
        e = Ewma(alpha=0.2)
        assert e.samples == 0 and e.value == 0.0
        assert e.update(10.0) == 10.0       # seeded, not 0.8*0 + 2
        assert e.update(20.0) == pytest.approx(12.0)
        assert e.samples == 2

    def test_reset_reseeds(self):
        e = Ewma(alpha=0.5)
        e.update(100.0)
        e.reset()
        assert e.update(4.0) == 4.0

    def test_alpha_bounds(self):
        Ewma(alpha=1.0)                     # inclusive upper bound
        for bad in (0.0, -0.1, 1.5):
            with pytest.raises(ValueError):
                Ewma(alpha=bad)


# --------------------------------------------------------- rollup delta
class TestRollupDelta:
    def test_movement_and_rates(self):
        prev = {"time": 100.0, "requests_total": 10.0,
                "completed_total": 8.0, "rejected_total": 1.0,
                "timed_out_total": 0.0}
        cur = {"time": 102.0, "requests_total": 30.0,
               "completed_total": 26.0, "rejected_total": 3.0,
               "timed_out_total": 1.0}
        d = rollup_delta(prev, cur)
        assert d["dt_s"] == 2.0
        assert d["requests_total"] == 20.0
        assert d["requests_per_s"] == 10.0
        assert d["completed_total"] == 18.0
        assert d["rejected_total"] == 2.0
        assert d["timed_out_total"] == 1.0
        assert d["timed_out_per_s"] == 0.5

    def test_restart_reset_clamps_to_zero(self):
        prev = {"time": 10.0, "requests_total": 500.0,
                "completed_total": 500.0, "rejected_total": 0.0,
                "timed_out_total": 0.0}
        cur = {"time": 11.0, "requests_total": 3.0,
               "completed_total": 3.0, "rejected_total": 0.0,
               "timed_out_total": 0.0}
        d = rollup_delta(prev, cur)
        assert d["requests_total"] == 0.0       # not -497
        assert d["requests_per_s"] == 0.0

    def test_no_prev_and_no_dt(self):
        d = rollup_delta(None, {"time": 50.0, "requests_total": 5.0})
        assert d["dt_s"] == 50.0 and d["requests_total"] == 5.0
        same = {"time": 7.0, "requests_total": 9.0}
        d2 = rollup_delta(same, dict(same))
        assert d2["dt_s"] == 0.0 and d2["requests_per_s"] == 0.0


# --------------------------------------------------------------- policy
class TestFleetPolicy:
    def test_breach_streak_then_cooldown(self):
        pol = FleetPolicy(min_replicas=1, max_replicas=4,
                          p99_budget_ms=100.0, breach_polls=3,
                          idle_polls=3, cooldown_s=30.0)
        dec = [pol.observe(_rollup(p99=500.0, qps=10.0), live=2,
                           now=float(i)) for i in range(6)]
        assert [d.action for d in dec] == \
            ["hold", "hold", "scale_up", "hold", "hold", "hold"]
        assert dec[2].reason == "p99_breach"
        assert dec[5].reason == "cooldown"      # streak rebuilt in window

    def test_at_max_holds(self):
        pol = FleetPolicy(min_replicas=1, max_replicas=2,
                          p99_budget_ms=100.0, breach_polls=1)
        d = pol.observe(_rollup(p99=500.0, qps=10.0), live=2, now=0.0)
        assert d.action == "hold" and d.reason == "at_max"

    def test_below_min_bypasses_cooldown(self):
        pol = FleetPolicy(min_replicas=2, max_replicas=4,
                          p99_budget_ms=100.0, breach_polls=1,
                          cooldown_s=1000.0)
        assert pol.observe(_rollup(p99=500.0, qps=10.0), live=2,
                           now=0.0).action == "scale_up"
        # one second later, deep inside cooldown, the floor still wins
        d = pol.observe(_rollup(), live=1, now=1.0)
        assert d.action == "scale_up" and d.reason == "below_min"

    def test_idle_scale_down_and_floor(self):
        pol = FleetPolicy(min_replicas=1, max_replicas=4, idle_polls=3,
                          cooldown_s=0.0)
        dec = [pol.observe(_rollup(p99=1.0), live=2, now=float(i))
               for i in range(3)]
        assert [d.action for d in dec] == ["hold", "hold", "scale_down"]
        assert dec[2].reason == "sustained_idle"
        floor = FleetPolicy(min_replicas=1, max_replicas=4, idle_polls=2)
        for i in range(2):
            d = floor.observe(_rollup(), live=1, now=float(i))
        assert d.action == "hold" and d.reason == "at_min"

    def test_queue_breach_signal(self):
        pol = FleetPolicy(min_replicas=1, max_replicas=4,
                          queue_high=16.0, breach_polls=1)
        d = pol.observe(_rollup(queue=100.0, qps=10.0), live=2, now=0.0)
        assert d.action == "scale_up" and d.reason == "queue_breach"
        assert d.signals["queue_per_replica"] == 50.0

    def test_error_burn_uses_delta_window(self):
        # cumulative error_rate is clean but THIS window is burning —
        # the delta view must drive the decision
        pol = FleetPolicy(min_replicas=1, max_replicas=4,
                          error_rate_budget=0.05, breach_polls=1)
        burn = {"dt_s": 1.0, "requests_total": 50.0,
                "rejected_total": 50.0, "timed_out_total": 0.0}
        d = pol.observe(_rollup(qps=50.0, err=0.0, delta=burn),
                        live=2, now=0.0)
        assert d.action == "scale_up" and d.reason == "error_burn"
        assert d.signals["error_burn"] == pytest.approx(0.5)

    def test_restart_reset_does_not_mask_as_burn(self):
        # a counter reset shows cumulative error_rate noise; an empty
        # delta window with real dt means "no traffic", not "burning"
        pol = FleetPolicy(min_replicas=1, max_replicas=4,
                          error_rate_budget=0.05, breach_polls=1,
                          idle_polls=99)
        quiet = {"dt_s": 1.0, "requests_total": 0.0,
                 "rejected_total": 0.0, "timed_out_total": 0.0}
        d = pol.observe(_rollup(err=0.9, delta=quiet), live=2, now=0.0)
        assert d.action == "hold"
        assert d.signals["error_burn"] == 0.0

    def test_action_consumes_streak(self):
        pol = FleetPolicy(min_replicas=1, max_replicas=8,
                          p99_budget_ms=100.0, breach_polls=2,
                          cooldown_s=0.0)
        acts = [pol.observe(_rollup(p99=500.0, qps=10.0), live=2,
                            now=float(i)).action for i in range(4)]
        assert acts == ["hold", "scale_up", "hold", "scale_up"]

    def test_on_preemption_replace_vs_shed(self):
        pol = FleetPolicy(min_replicas=2, max_replicas=4, idle_polls=2)
        assert pol.on_preemption(3) == "replace"    # not provably idle
        # build the idle streak AT the floor: at_min holds preserve it
        # (a scale_down would consume it)
        for i in range(2):
            pol.observe(_rollup(), live=2, now=float(i))
        assert pol.idle_streak >= 2
        assert pol.on_preemption(3) == "shed"
        assert pol.on_preemption(1) == "replace"    # floor at risk

    def test_bad_bounds(self):
        with pytest.raises(ValueError):
            FleetPolicy(min_replicas=3, max_replicas=2)


# ------------------------------------------------------ exit classifier
class TestWorstOutcome:
    def test_severity_order(self):
        assert worst_outcome(["completed", "stopped"]) == "completed"
        assert worst_outcome(["completed", "preempted",
                              "stopped"]) == "preempted"
        assert worst_outcome(["preempted", "wedged"]) == "wedged"
        assert worst_outcome(["wedged", "crashed"]) == "crashed"
        # unknown labels are crash-severity, never silently clean
        assert worst_outcome(["completed", "mystery"]) == "mystery"

    def test_exit_codes(self):
        assert exit_for_outcome("completed") == 0
        assert exit_for_outcome("stopped") == 0
        assert exit_for_outcome("preempted") == EXIT_PREEMPTED == 75
        assert exit_for_outcome("wedged") == EXIT_WEDGED == 70
        assert exit_for_outcome("crashed") == 1
        assert exit_for_outcome("mystery") == 1


# -------------------------------------------------- edge-triggered SLO
class TestEdgeTriggeredBreach:
    def test_rising_refresher_clear_rearm(self):
        s = FleetScraper([], breach_cooldown_s=10.0)
        rec = flight.get_recorder()
        n0 = len(rec.events("slo_clear"))
        assert s._edge("p99", True, 100.0) is True       # rising edge
        assert s._edge("p99", True, 105.0) is False      # sustained
        assert s._edge("p99", True, 110.0) is True       # refresher
        assert s._edge("p99", True, 112.0) is False
        assert s._edge("p99", False, 115.0) is False     # falling edge
        clears = rec.events("slo_clear")
        assert len(clears) == n0 + 1
        assert clears[-1]["signal"] == "p99"
        assert s._edge("p99", True, 120.0) is True       # re-armed

    def test_signals_tracked_independently(self):
        s = FleetScraper([], breach_cooldown_s=60.0)
        assert s._edge("p99", True, 0.0) is True
        assert s._edge("error_rate", True, 0.0) is True  # own edge
        assert s._edge("p99", True, 1.0) is False


# --------------------------------------- endpoint discovery (satellite)
class TestDiscoverEndpointsLiveOnly:
    def test_stale_dead_missing_garbage(self, tmp_path):
        run = tmp_path / "run"
        for i in range(4):
            (run / f"replica-{i}").mkdir(parents=True)
        # a process that existed and is gone: its advert is stale
        dead = subprocess.Popen([sys.executable, "-c", "pass"])
        dead.wait(timeout=30)
        live_url = "http://127.0.0.1:1001"
        dead_url = "http://127.0.0.1:1002"
        nopid_url = "http://127.0.0.1:1003"
        (run / "replica-0" / "endpoint.json").write_text(json.dumps(
            {"url": live_url, "pid": os.getpid(), "replica": 0}))
        (run / "replica-1" / "endpoint.json").write_text(json.dumps(
            {"url": dead_url, "pid": dead.pid, "replica": 1}))
        # replica-2: no endpoint.json at all (still warming)
        (run / "replica-3" / "endpoint.json").write_text("not json{")
        (run / "endpoint.json").write_text(json.dumps(
            {"url": nopid_url, "replica": 4}))           # no pid field
        assert discover_endpoints(str(run)) == \
            [live_url, dead_url, nopid_url]
        # live_only: the controller must scale on live replicas ONLY —
        # dead pids and pid-less adverts are not capacity
        assert discover_endpoints(str(run), live_only=True) == [live_url]


# ------------------------------------------------ supervisor directives
@pytest.mark.e2e
class TestSupervisorDirectives:
    def _cfg(self, workdir, argv=None, **kw):
        base = dict(max_restarts=3, backoff_base_s=0.05,
                    backoff_jitter=0.0, poll_s=0.05,
                    startup_deadline_s=60.0, wedge_deadline_s=600.0,
                    kill_grace_s=2.0, seed=0)
        base.update(kw)
        return SupervisorConfig(argv or SLEEPER, workdir=str(workdir),
                                **base)

    def _start(self, cfg):
        sup = Supervisor(cfg)
        box = {}
        t = threading.Thread(target=lambda: box.update(rc=sup.run()),
                             daemon=True)
        t.start()
        return sup, t, box

    def test_stop_directive(self, tmp_path):
        sup, t, box = self._start(self._cfg(tmp_path / "s"))
        _wait(lambda: sup.launches >= 1, msg="first launch")
        time.sleep(0.2)
        sup.request_stop("test_teardown")
        t.join(30)
        assert not t.is_alive()
        assert box["rc"] == 0
        assert sup.final_outcome == "stopped"
        assert sup.outcomes[-1] == "stopped"

    def test_restart_directive_advances_attempt(self, tmp_path):
        # the child records its DLTPU_RESTART_ATTEMPT: a controller
        # requeue must move to attempt 1 (so @attempt:0 faults don't
        # re-fire on the replacement) without burning restart budget
        marks = tmp_path / "attempts.txt"
        argv = [sys.executable, "-c",
                "import os,sys,time;"
                "open(sys.argv[1],'a').write("
                "os.environ.get('DLTPU_RESTART_ATTEMPT','?')+'\\n');"
                "time.sleep(60)", str(marks)]
        sup, t, box = self._start(self._cfg(tmp_path / "s", argv=argv))

        def attempts():
            return marks.read_text().splitlines() if marks.exists() else []
        _wait(lambda: attempts() == ["0"], msg="first child's mark")
        sup.request_restart("controller_wedged")
        _wait(lambda: attempts() == ["0", "1"], msg="relaunched child's mark")
        sup.request_stop("done")
        t.join(30)
        assert not t.is_alive()
        assert box["rc"] == 0
        assert "requeued" in sup.outcomes
        assert sup.final_outcome == "stopped"
        assert marks.read_text().splitlines() == ["0", "1"]

    def test_stop_interrupts_backoff(self, tmp_path):
        # a crashing child parks the supervisor in a 30s backoff; the
        # stop directive must not wait it out
        argv = [sys.executable, "-c", "raise SystemExit(7)"]
        sup, t, box = self._start(self._cfg(
            tmp_path / "s", argv=argv, backoff_base_s=30.0,
            backoff_max_s=30.0))
        _wait(lambda: "crashed" in sup.outcomes, msg="first crash")
        t0 = time.time()
        sup.request_stop("shutdown")
        t.join(10)
        assert not t.is_alive()
        assert time.time() - t0 < 10.0
        assert box["rc"] == 0
        assert sup.final_outcome == "stopped"


# ------------------------------------------------------- replica set
@pytest.mark.e2e
class TestReplicaSet:
    def _factory(self, tmp_path, argv=None):
        def factory(i):
            return SupervisorConfig(
                argv or SLEEPER,
                workdir=str(tmp_path / f"replica-{i}"),
                max_restarts=0, backoff_base_s=0.05, poll_s=0.05,
                startup_deadline_s=60.0, wedge_deadline_s=600.0,
                kill_grace_s=2.0, seed=0, replica=i)
        return factory

    def test_spawn_stop_monotonic_indices(self, tmp_path):
        rs = ReplicaSet(self._factory(tmp_path))
        assert rs.spawn() == 0
        assert rs.spawn() == 1
        _wait(lambda: rs.live() == [0, 1], msg="both live")
        rs.stop(1, "scale_down")
        _wait(lambda: rs.live() == [0], msg="replica 1 retired")
        # a replacement NEVER reuses a dead identity
        assert rs.spawn() == 2
        _wait(lambda: rs.live() == [0, 2], msg="replacement live")
        rs.stop_all("shutdown")
        assert rs.join(timeout=30)
        assert set(rs.results()) == {0, 1, 2}
        assert all(rc == 0 for rc in rs.results().values())
        assert all(o == "stopped" for o in rs.outcomes().values())

    def test_on_outcome_hook_sees_preemption(self, tmp_path):
        calls = []

        def hook(i, sup, outcome, attempt, rc):
            calls.append((i, outcome, rc))
            return "stop"                       # shed the capacity

        argv = [sys.executable, "-c", "raise SystemExit(75)"]
        rs = ReplicaSet(self._factory(tmp_path, argv=argv),
                        on_outcome=hook)
        rs.spawn()
        assert rs.join(timeout=30)
        assert calls == [(0, "preempted", 75)]
        assert rs.results()[0] == 0             # shed is a clean stop
        assert rs.outcomes()[0] == "stopped"


# ------------------------------------- controller actuation (no HTTP)
@pytest.mark.e2e
class TestControllerActuation:
    def test_below_min_spawns_and_records(self, tmp_path):
        run_dir = tmp_path / "ctl"
        run_dir.mkdir()

        def factory(i):
            return SupervisorConfig(
                SLEEPER, workdir=str(run_dir / f"replica-{i}"),
                max_restarts=0, poll_s=0.05, startup_deadline_s=60.0,
                wedge_deadline_s=600.0, kill_grace_s=2.0, seed=0,
                replica=i)

        rs = ReplicaSet(factory)
        ctl = FleetController(
            rs, FleetPolicy(min_replicas=1, max_replicas=2),
            run_dir=str(run_dir))
        try:
            rollup = ctl.tick()                 # zero live → below_min
            assert rollup["replicas"] == 0
            assert ctl.scale_ups == 1
            _wait(lambda: rs.live() == [0], msg="spawned replica")
            path = run_dir / CONTROLLER_FLIGHT_FILE
            doc = json.loads(path.read_text())
            scales = [e for e in doc["events"]
                      if e["kind"] == "fleet_scale"]
            assert scales and scales[0]["direction"] == "up"
            assert scales[0]["reason"] == "below_min"
            assert doc["config"]["policy"]["min_replicas"] == 1
        finally:
            ctl.stop()
            rs.stop_all("test_done")
            rs.join(timeout=30)


# ------------------------------------------------- batcher drain + 503
class TestBatcherDrain:
    def test_drain_rejects_new_flushes_old(self):
        from deeplearning_tpu.serve import (InferenceEngine,
                                            MicroBatcher, Rejected)
        from deeplearning_tpu.serve.health import health
        eng = InferenceEngine("mnist_fcn", num_classes=10,
                              image_size=28, batch_buckets=(1, 4))
        img = np.zeros((28, 28, 3), np.float32)
        with MicroBatcher(eng, max_wait_ms=2.0) as mb:
            h = mb.submit(img)
            np.asarray(h.result(timeout=60.0))
            mb.drain()
            mb.drain()                          # idempotent
            assert mb.draining
            with pytest.raises(Rejected) as ei:
                mb.submit(img)
            assert ei.value.reason == "draining"
            _wait(lambda: mb.drained, msg="drain flush")
            code, payload = health(eng, mb)
            # routers must stop sending: draining is NOT a 200
            assert code == 503
            assert payload["status"] == "draining"
            assert payload["draining"] and payload["drained"]


# ------------------------------------------------------ router failover
class TestRouterFailover:
    @staticmethod
    def _mini_server(state):
        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def _send(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                status = state["status"]
                self._send(200 if status == "ready" else 503,
                           {"status": status})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                if state.get("fail_post"):
                    self._send(503, {"error": "shedding"})
                else:
                    self._send(200, {"ok": True})

            def log_message(self, *args):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def test_draining_skipped_failover_no_route(self):
        a_state = {"status": "ready"}
        b_state = {"status": "draining"}
        srv_a, url_a = self._mini_server(a_state)
        srv_b, url_b = self._mini_server(b_state)
        try:
            router = FleetRouter([url_a, url_b], health_ttl_s=0.0,
                                 timeout_s=10.0)
            assert router.routable() == [url_a]
            assert router.statuses() == {url_a: "ready",
                                         url_b: "draining"}
            code, payload, url = router.post("/predict", b"x")
            assert (code, url) == (200, url_a) and payload == {"ok": True}

            # both routable, A refusing posts: failover finds B
            a_state["fail_post"] = True
            b_state["status"] = "ready"
            oks = [router.post("/predict", b"x") for _ in range(2)]
            assert all(c == 200 and u == url_b for c, _p, u in oks)
            assert router.failovers >= 1

            # nobody routable → (0, None, None), counted
            a_state["status"] = "draining"
            b_state["status"] = "wedged"
            assert router.post("/predict", b"x") == (0, None, None)
            assert router.no_route == 1
        finally:
            srv_a.shutdown()
            srv_b.shutdown()


# ----------------------------------------------------- loadgen timeline
class TestLoadgenTimeline:
    def test_per_second_buckets(self):
        import loadgen
        tl = loadgen.Timeline()
        tl.note("submitted")
        tl.note("completed", 0.05)
        tl.t0 -= 2.0                  # shift the clock: bucket 2 next
        tl.note("completed", 0.2)
        tl.note("rejected")
        tl.note("timed_out")
        rows = tl.rows()
        assert [r["t"] for r in rows] == [0, 2]
        assert rows[0]["submitted"] == 1 and rows[0]["completed"] == 1
        assert rows[0]["p99_ms"] == pytest.approx(50.0, rel=0.01)
        assert rows[1]["rejected"] == 1 and rows[1]["timed_out"] == 1
        assert rows[1]["p99_ms"] == pytest.approx(200.0, rel=0.01)


# ------------------------------------------------- choreography CPU e2e
@pytest.mark.e2e
class TestFleetControllerE2E:
    def test_wedge_drain_requeue_preempt_recover(self, tmp_path):
        """The ISSUE 14 acceptance run: a controller-run 3-replica CPU
        serve fleet under open-loop HTTP load. DLTPU_FAULTS wedges
        replica 1 (frozen dispatch → healthz "wedged" → controller
        drains, deadline expires, supervisor requeues) and preempts
        replica 2 (exit 75 → policy verdict "replace" → requeue with no
        backoff). Traffic keeps completing throughout (the router
        reroutes), both replacements warm, a post-recovery load phase
        lands back in the pre-fault latency band, every decision is in
        flightrec_controller.json, obs_report renders the controller
        section, and SIGTERM classifies the whole fleet to exit 0."""
        import loadgen

        wd = str(tmp_path / "fleet")
        env = dict(os.environ)
        env.pop("DLTPU_HEARTBEAT", None)
        env["DLTPU_FAULTS"] = ("wedge_replica:1@step:10@attempt:0;"
                               "preempt_replica:2@step:20@attempt:0")
        cmd = [sys.executable, os.path.join(ROOT, "tools",
                                            "supervise.py"),
               "--controller", "--replicas", "3",
               "--min-replicas", "3", "--max-replicas", "5",
               "--run-id", "ctl-test", "--workdir", wd,
               "--max-restarts", "2",
               # the controller heals via /healthz; the per-replica
               # supervisor's own wedge detector stays out of the way
               # (an idle replica must never read as wedged)
               "--wedge-deadline", "600", "--startup-deadline", "600",
               "--kill-grace", "5",
               "--scale-interval", "0.5", "--drain-deadline", "3",
               # autoscaling thresholds parked out of reach: the only
               # actuations this run may take are the choreographed
               # drain/requeue/preempt ones, so the assertions below
               # are exact
               "--p99-budget", "100000", "--queue-high", "100000",
               "--error-budget", "2.0", "--breach-polls", "3",
               "--idle-polls", "100000", "--cooldown", "2",
               "--",
               sys.executable, os.path.join(ROOT, "tools", "serve.py"),
               "--model", "mnist_fcn", "--num-classes", "10",
               "--size", "28", "--buckets", "1,4", "--max-wait-ms", "2",
               "--http", "0", "--wedge-deadline-s", "2"]
        log = open(os.path.join(str(tmp_path), "supervise.log"), "w")
        proc = subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
        try:
            deadline = time.time() + 240.0
            while time.time() < deadline:
                if len(discover_endpoints(wd, live_only=True)) >= 3:
                    break
                assert proc.poll() is None, \
                    f"supervise died rc={proc.returncode}; see {log.name}"
                time.sleep(0.25)
            endpoints = discover_endpoints(wd, live_only=True)
            assert len(endpoints) >= 3, endpoints
            first_pids = {}
            for i in (1, 2):
                doc = json.loads(open(os.path.join(
                    wd, f"replica-{i}", "endpoint.json")).read())
                first_pids[i] = int(doc["pid"])

            router = FleetRouter(
                endpoints,
                refresh_fn=lambda: discover_endpoints(
                    wd, live_only=True),
                timeout_s=5.0)
            images = loadgen.make_images(16, 28)

            # phase 1: open-loop load; the faults fire a few seconds in
            # (wedge after 10 dispatched batches on replica 1, preempt
            # after 20 on replica 2), the controller drains + requeues
            res1 = loadgen.run_open_loop_http(
                router, images, rate_hz=24.0, duration_s=25.0,
                timeout_s=5.0)
            assert res1["submitted"] > 0
            # traffic survives the choreography: the fleet never goes
            # dark even while two of three replicas die mid-run
            assert res1["completed"] >= 0.5 * res1["submitted"], res1
            rows1 = res1["timeline"]
            assert rows1 and sum(r["completed"] for r in rows1) == \
                res1["completed"]
            pre_rows = [r["p99_ms"] for r in rows1
                        if r["t"] <= 2 and r["completed"] > 0]
            pre_band_ms = max(min(pre_rows) if pre_rows else 100.0,
                              50.0)

            # the controller's decisions land in its flight record:
            # wedge → drain(then=restart) → requeue; exit 75 → replace
            flight_path = os.path.join(wd, CONTROLLER_FLIGHT_FILE)

            def controller_events():
                try:
                    with open(flight_path) as f:
                        return json.load(f).get("events", [])
                except (OSError, ValueError):
                    return []

            def has_choreography():
                ev = controller_events()
                drains = [e for e in ev if e["kind"] == "fleet_drain"
                          and e.get("reason") == "wedged"]
                req = [e for e in ev if e["kind"] == "fleet_requeue"]
                pre = [e for e in ev
                       if e["kind"] == "preempt_capacity"]
                return drains and req and pre

            _wait(has_choreography, timeout=120.0, interval=0.5,
                  msg=f"choreography events in {flight_path}: "
                      f"{controller_events()}")
            ev = controller_events()
            drain = next(e for e in ev if e["kind"] == "fleet_drain"
                         and e.get("reason") == "wedged")
            assert drain["replica"] == 1 and drain["then"] == "restart"
            requeue = next(e for e in ev
                           if e["kind"] == "fleet_requeue")
            assert requeue["replica"] == 1
            pre = next(e for e in ev if e["kind"] == "preempt_capacity")
            assert pre["replica"] == 2 and pre["verdict"] == "replace"

            # both replacements warm: 3 live replicas again, fresh pids
            def relaunched(i):
                try:
                    with open(os.path.join(wd, f"replica-{i}",
                                           "endpoint.json")) as f:
                        return int(json.load(f)["pid"]) != first_pids[i]
                except (OSError, ValueError, KeyError):
                    return False

            def recovered():
                if not (relaunched(1) and relaunched(2)):
                    return False
                urls = discover_endpoints(wd, live_only=True)
                if len(urls) < 3:
                    return False
                r = FleetRouter(urls, timeout_s=5.0)
                return len(r.routable()) >= 3

            _wait(recovered, timeout=180.0, interval=1.0,
                  msg="3 routable replicas after requeues")
            for i in (1, 2):
                doc = json.loads(open(os.path.join(
                    wd, f"replica-{i}", "endpoint.json")).read())
                assert int(doc["pid"]) != first_pids[i], \
                    f"replica {i} was not relaunched"
                assert doc["run_id"] == "ctl-test"

            # phase 2: p99 back in the pre-fault band on the healed
            # fleet (generous multiplier — CI boxes are noisy; the
            # failure mode being caught is timeout-scale, ~100x off)
            res2 = loadgen.run_open_loop_http(
                router, images, rate_hz=24.0, duration_s=8.0,
                timeout_s=5.0)
            assert res2["completed"] >= 0.9 * res2["submitted"], res2
            assert res2["timed_out"] == 0, res2
            assert res2["p99_ms"] <= max(20.0 * pre_band_ms, 1000.0), \
                (res2["p99_ms"], pre_band_ms)

            # obs_report renders the fleet-controller section
            view = subprocess.run(
                [sys.executable,
                 os.path.join(ROOT, "tools", "obs_report.py"), wd],
                capture_output=True, text=True, timeout=120)
            assert view.returncode == 0, view.stderr
            assert "controller:" in view.stdout, view.stdout
            assert "drains=" in view.stdout
            assert "preempt verdicts: replace" in view.stdout

            # graceful shutdown: directives classify every replica as
            # stopped → fleet exit 0
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=120) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
            log.close()
        tail = open(log.name).read()
        # per-replica breakdown + classified fleet verdict (severity-0
        # ties — stopped vs completed — both classify to exit 0)
        assert "replica 1: stopped (rc=0)" in tail, tail[-2000:]
        assert "fleet done run_id=ctl-test" in tail, tail[-2000:]
        assert "exit=0" in tail, tail[-2000:]
