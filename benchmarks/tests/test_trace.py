"""The trace reduction, on interval arithmetic made up here and on a small
trace recorded on the chip (``data/micro.xplane.pb``: the rehearsal ViT for a
twentieth of a second, with ``data/micro.hlo.txt``, the text of its step;
``benchmarks/tests/rehearse.py ... 1 <dir>`` on a chip records another)."""
import os

import pytest

from benchmarks.harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_counts_overlap_once_and_lists_gaps():
    covered, gaps = tr.union_seconds([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert covered == pytest.approx(30e-9)
    assert gaps == [(20, 30)]


def test_window_is_the_module_events_span_averaged_over_chips():
    chip = {"ops": [("a", 1100.0, 200.0)], "modules": [("m", 1000.0, 500.0),
                                                        ("m", 3000.0, 1000.0)]}
    other = {"ops": [("a", 0.0, 1000.0), ("b", 4000.0, 1000.0)], "modules": []}
    assert tr.Trace({"/device:TPU:0": chip}, []).window_seconds() == pytest.approx(3e-6)
    assert tr.Trace({"/device:TPU:0": chip, "/device:TPU:1": other},
                    []).window_seconds() == pytest.approx(4e-6)
    assert tr.Trace({}, []).window_seconds() == 0.0


def test_op_paths_and_groups():
    text = '''ENTRY %main {
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step_fn)/transpose(jvp(VisionTransformer))/blocks_3/attn/exp" stack_frame_id=2}
  ROOT %copy.1 = f32[8]{0} copy(%fusion.7)
}'''
    paths = tr.op_paths(text)
    assert paths == {"fusion.7": "jit(step_fn)/transpose(jvp(VisionTransformer))/blocks_3/attn/exp"}
    assert tr.instruction_of("%fusion.7 = bf16[8]{0} fusion(...)") == "fusion.7"
    assert tr.group_of(paths["fusion.7"], "fusion.7") == "bwd blocks_N/attn"
    assert tr.group_of("", "copy-done.12") == "no_path copy-done"


def test_host_spans_are_placed_by_the_promptest_launch():
    t = tr.Trace({"/device:TPU:0": {"ops": [("a", 1000.0, 500.0), ("b", 9000.0, 500.0)],
                                     "modules": [("jit_step_fn(1)", 1000.0, 500.0),
                                                 ("jit_step_fn(1)", 9000.0, 500.0)]}}, [])
    events = [{"name": "dispatch", "ts": 100.0, "dur": 0.2},    # us, host clock
              {"name": "data_wait", "ts": 101.0, "dur": 6.0},
              {"name": "dispatch", "ts": 107.5, "dur": 0.2}]
    t.place_host_spans(events, ("data_wait", "dispatch"), anchor="dispatch")
    # offset: min(1000 - 100000, 9000 - 107500) = -99000 ns
    assert ("data_wait", 2000.0, 6000.0) in t.host_spans
    assert t.idle_gaps(min_ns=100) == [["data_wait", pytest.approx(7.5e-6)]]


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "micro.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_trace_reduces():
    t = tr.Trace.from_file(os.path.join(DATA, "micro.xplane.pb"))
    with open(os.path.join(DATA, "micro.hlo.txt")) as f:
        paths = tr.op_paths(f.read())
    assert list(t.devices) == ["/device:TPU:0"]
    rec = t.devices["/device:TPU:0"]
    steps = t.module_durations_ms("^jit_step_fn")
    assert len(steps) >= 3 and len(rec["ops"]) > 100 * len(steps)
    busy = t.busy_seconds()
    span = (max(s + d for _, s, d in rec["ops"]) - min(s for _, s, _ in rec["ops"])) / 1e9
    assert 0 < busy <= span                      # a share over 100 % is a wrong reduction
    # the traced window is the module events' span, and holds every op
    assert span <= t.window_seconds() <= 1.01 * span
    assert busy <= sum(d for _, _, d in rec["ops"]) / 1e9
    # ops run inside their step's module event
    assert busy <= sum(steps) / 1e3 * 1.001
    seconds, count = t.op_seconds(r"/blocks_\d+/attn/(?!qkv/|proj/)", paths)
    assert count > 0 and 0 < seconds < busy
    named = sum(1 for n, _, _ in rec["ops"] if tr.instruction_of(n) in paths)
    assert named > 0.5 * len(rec["ops"])
    assert t.top_ops(paths, 3)[0][1] > 0
