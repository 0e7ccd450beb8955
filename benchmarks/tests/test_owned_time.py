"""The readers that count a nanosecond once (``readers/owned_time.py``,
``scope_ms_per_step``, ``unplaced_pct``) on an ops line made up here: a
``while`` round three body ops and a ``conditional`` round two, as a chip's
``XLA Ops`` line holds them, and on the small trace recorded on the chip
(``data/micro.xplane.pb``)."""
import json
import os
import re
import types

import pytest

from benchmarks.harness import spec
from benchmarks.harness import trace as tr
from benchmarks.readers import owned_time, scope_ms_per_step, unplaced_pct

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
WRAPPERS = spec.load_json(os.path.join(
    spec.BENCH, "metrics", "step_unplaced_pct.json"))["params"]

# (instruction, start ns, duration ns): the loss's loop holds three ops and
# 10 ns of its own; an expert layer's conditional two ops and 5 ns; a copy
# without a path and the optimizer's add stand alone; the last two overlap
# without nesting
OPS = [("%while.1 = (f32[]) while(%t)", 0, 100),
       ("%fusion.1 = f32[8] fusion(%a)", 5, 30),
       ("%fusion.2 = f32[8] fusion(%b)", 35, 30),
       ("%fusion.3 = f32[8] fusion(%c)", 65, 30),
       ("%conditional.1 = f32[8] conditional(%p)", 200, 50),
       ("%fusion.4 = f32[8] fusion(%d)", 200, 25),
       ("%fusion.5 = f32[8] fusion(%e)", 225, 20),
       ("%copy.1 = f32[8] copy(%f)", 300, 40),
       ("%add.1 = f32[8] add(%g, %h)", 400, 60),
       ("%fusion.6 = f32[8] fusion(%i)", 500, 20),
       ("%fusion.7 = f32[8] fusion(%j)", 510, 20)]
HEAD = "jit(step_fn)/jvp(loss_head)/while"
MOE = "jit(step_fn)/jvp(CausalLM)/layers_1/moe/cond"
PATHS = {"while.1": HEAD,
         "fusion.1": HEAD + "/body/closed_call/dot_general",
         "fusion.2": HEAD + "/body/closed_call/reduce_max",
         "fusion.3": "jit(step_fn)/transpose(jvp(loss_head))/while/body/"
                     "closed_call/checkpoint/mul",
         "conditional.1": MOE,
         "fusion.4": MOE + "/branch_0_fun/moe_dispatch/gather",
         "fusion.5": MOE + "/branch_0_fun/expert_matmul/jit(gmm)/mul",
         "add.1": "jit(step_fn)/add",
         "fusion.6": "jit(step_fn)/optimizer/mul",
         "fusion.7": "jit(step_fn)/jvp(CausalLM)/add"}


def made_up(steps=2):
    trace = tr.Trace({"/device:TPU:0": {"ops": OPS, "modules": []}}, [])
    return types.SimpleNamespace(trace=trace, op_paths=PATHS,
                                 facts={"steps": steps})


def test_owned_times_sum_to_the_union():
    owned = dict(owned_time.owned_ns(OPS))
    assert owned["%while.1 = (f32[]) while(%t)"] == 10
    assert owned["%conditional.1 = f32[8] conditional(%p)"] == 5
    assert owned["%fusion.6 = f32[8] fusion(%i)"] == 10      # the later start
    assert owned["%fusion.7 = f32[8] fusion(%j)"] == 20      # owns the overlap
    trace = made_up().trace
    assert sum(owned.values()) / 1e9 == pytest.approx(trace.busy_seconds())
    assert sum(s for _, s in owned_time.owned_by_path(made_up())) \
        == pytest.approx(trace.busy_seconds())
    # in any order of the line's events
    assert dict(owned_time.owned_ns(OPS[::-1])) == owned


def test_scope_ms_counts_the_loop_once_where_op_seconds_counts_it_twice():
    run = made_up(steps=2)
    pattern = "[/(]loss_head[/)]"
    # three body ops of 30 ns and the loop's own 10, over two steps, in ms
    assert scope_ms_per_step.read(run, {"pattern": pattern}) \
        == pytest.approx(1e3 * 100e-9 / 2)
    # the regression this guards: the sum of durations holds the body twice
    seconds, count = run.trace.op_seconds(pattern, PATHS)
    assert (seconds, count) == (pytest.approx(190e-9), 4)
    assert scope_ms_per_step.read(run, {"pattern": "/moe_dispatch/"}) \
        == pytest.approx(1e3 * 25e-9 / 2)
    assert scope_ms_per_step.read(run, {"pattern": "/no_such_scope/"}) is None
    run.trace = None
    assert scope_ms_per_step.read(run, {"pattern": pattern}) is None


def test_unplaced_is_the_pathless_copy_and_the_bare_primitives():
    wrappers = re.compile(WRAPPERS["wrappers"])
    placed = {inst: unplaced_pct.placed(path, wrappers)
              for inst, path in PATHS.items()}
    # no module and no scope: the top-level add and the root module's own
    # add; the conditional's own event is its layer's
    assert [i for i, p in placed.items() if not p] == ["add.1", "fusion.7"]
    assert unplaced_pct.placed("a/b;jit(step_fn)/add", wrappers)
    assert not unplaced_pct.placed(
        "jit(step_fn)/jvp()/while/body/closed_call/checkpoint/"
        "rematted_computation/custom_jvp_call/mul", wrappers)
    # copy 40 + add 60 + the root module's 20 of 100 + 50 + 40 + 60 + 30
    assert unplaced_pct.read(made_up(), WRAPPERS) \
        == pytest.approx(100.0 * 120 / 280)
    assert unplaced_pct.read(types.SimpleNamespace(trace=None), WRAPPERS) is None
    # a program from before its step had names is not read
    bare = made_up()
    bare.op_paths = {i: p for i, p in PATHS.items()
                     if "loss_head" not in p and "optimizer" not in p}
    assert unplaced_pct.read(bare, WRAPPERS) is None


def test_new_metrics_are_found_by_name_and_only_in_their_cells():
    new = {"loss_head_ms", "attention_glue_ms", "weight_update_ms",
           "step_unplaced_pct"}
    for cell, want in (("vit_b16_train_b128", {"weight_update_ms",
                                               "step_unplaced_pct"}),
                       ("mellum2_ep4_train_2x8192", new)):
        names = {m["name"] for m in spec.load_cell(cell)["per_layer"]}
        assert names & new == want
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] in new]
    assert all(m["workloads"] and m["source"] == "device_trace"
               and m["layer"] == "harness and model step" for m in entries)


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "micro.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_trace_owned_time_is_its_busy_time():
    trace = tr.Trace.from_file(os.path.join(DATA, "micro.xplane.pb"))
    with open(os.path.join(DATA, "micro.hlo.txt")) as f:
        paths = tr.op_paths(f.read())
    steps = len(trace.module_durations_ms("^jit_step_fn"))
    run = types.SimpleNamespace(trace=trace, op_paths=paths,
                                facts={"steps": steps})
    owned = owned_time.owned_by_path(run)
    assert sum(s for _, s in owned) == pytest.approx(trace.busy_seconds(),
                                                     rel=1e-9)
    # no loop in that step: owned time is the summed durations
    pattern = r"/blocks_\d+/attn/"
    assert scope_ms_per_step.read(run, {"pattern": pattern}) == pytest.approx(
        1e3 * trace.op_seconds(pattern, paths)[0] / steps)
    # recorded before the step had scopes: none of the new metrics reads it
    # (by the rule alone its weight update is unplaced)
    assert scope_ms_per_step.read(run, {"pattern": "/optimizer/"}) is None
    assert unplaced_pct.read(run, WRAPPERS) is None
    assert 5 < unplaced_pct.read(run, {**WRAPPERS, "named_by": "jit"}) < 50
