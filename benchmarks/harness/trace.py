"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
readers need. What step 0 of PR 24 found on the v5e under JAX 0.9.0: each chip
is a plane ``/device:TPU:<n>`` with the lines ``Steps``, ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event per executed HLO instruction, named by the instruction's text,
``%fusion.12 = ...``) and ``Async XLA Ops`` (copies in flight, overlapping the
former). Op events carry no module path; the compiled program's text does
(``metadata={op_name="jit(step_fn)/.../blocks_3/attn/exp"}``), so the two are
joined on the instruction's name. Host threads are lines of ``/host:CPU``, but
recording them starves the feed (its layout transposes write some 200,000 host
events a batch), so the trace is taken with device events only and the
program's spans come from its own ring, placed on the trace's clock by
``place_host_spans``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return paths[-1]


def op_paths(hlo_text: str) -> dict:
    """instruction name -> the op_name of its metadata, from a compiled
    program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            meta = _OP_NAME.search(line)
            if meta:
                out[m.group(1)] = meta.group(1)
    return out


def union_seconds(intervals: list) -> tuple:
    """(seconds covered, gaps as (start, end)) of (start, end) pairs in ns."""
    covered, gaps, end = 0.0, [], None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s))
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    return covered / 1e9, gaps


class Trace:
    """Events of one trace, as plain tuples, read once."""

    def __init__(self, devices: dict, host_spans: list):
        # devices: plane name -> {"ops": [(name, start_ns, dur_ns)],
        #                         "modules": [(name, start_ns, dur_ns)]}
        self.devices = devices
        self.host_spans = host_spans      # [(name, start_ns, dur_ns)]

    def place_host_spans(self, events: list, names, anchor: str) -> None:
        """Put the program's spans (Chrome trace events of its own ring, ``ts``
        and ``dur`` in microseconds of the host's clock) on the trace's clock.
        The k-th ``anchor`` span (the step's dispatch) launched the k-th module
        event of the first chip, and a module cannot start before its launch,
        so the clocks' offset is the smallest start-to-start distance: right to
        the launch latency of the promptest step, well under a millisecond."""
        launches = sorted(e["ts"] * 1e3 for e in events if e["name"] == anchor)
        rec = next(iter(self.devices.values()), None)
        starts = sorted(s for _, s, _ in rec["modules"]) if rec else []
        if not launches or not starts:
            return
        offset = min(s - l for s, l in zip(starts, launches))
        wanted = set(names)
        self.host_spans = [(e["name"], e["ts"] * 1e3 + offset, e["dur"] * 1e3)
                           for e in events if e["name"] in wanted]

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        devices = {}
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                rec = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                    if key:
                        rec[key] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
                devices[plane.name] = rec
        return cls(devices, [])

    # ------------------------------------------------------------ device
    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        per_chip = [union_seconds([(s, s + d) for _, s, d in rec["ops"]])[0]
                    for rec in self.devices.values()]
        return sum(per_chip) / len(per_chip)

    def window_seconds(self) -> float:
        """The traced window on the trace's own clock: from the start of the
        first module event to the end of the last, averaged over the chips
        (op events where a plane has no module line). The profiler starts
        before the host's window opens and stops after it closes, so the host's
        clock would measure another interval than the one ``busy_seconds``
        lies in."""
        spans = []
        for rec in self.devices.values():
            events = rec["modules"] or rec["ops"]
            if events:
                spans.append(max(s + d for _, s, d in events)
                             - min(s for _, s, _ in events))
        return sum(spans) / len(spans) / 1e9 if spans else 0.0

    def module_durations_ms(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [d / 1e6 for rec in self.devices.values()
                for name, _, d in rec["modules"] if rx.search(name)]

    def op_seconds(self, pattern: str, paths: dict) -> tuple:
        """(seconds per chip of op events whose module path matches, number
        of such events per chip)."""
        rx = re.compile(pattern)
        total, count = 0.0, 0
        for rec in self.devices.values():
            for name, _, d in rec["ops"]:
                if rx.search(paths.get(instruction_of(name), "")):
                    total += d
                    count += 1
        n = max(len(self.devices), 1)
        return total / 1e9 / n, count // n

    def top_ops(self, paths: dict, k: int = 10) -> list:
        """The k groups of device operations that took most time on the first
        chip: instructions grouped by pass and module (layer numbers folded)."""
        groups = defaultdict(float)
        rec = next(iter(self.devices.values()), {"ops": []})
        for name, _, d in rec["ops"]:
            inst = instruction_of(name)
            groups[group_of(paths.get(inst, ""), inst)] += d / 1e9
        return [[g, s] for g, s in sorted(groups.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10, min_ns: float = 20e3) -> list:
        """The idle time of the first chip by what the host was doing: every
        gap between device operations longer than ``min_ns`` goes to the
        program's span that was open at its middle, else ``unattributed``."""
        rec = next(iter(self.devices.values()), {"ops": []})
        _, gaps = union_seconds([(s, s + d) for _, s, d in rec["ops"]])
        spans = sorted((s, s + d, name) for name, s, d in self.host_spans)
        starts = [s for s, _, _ in spans]
        out = defaultdict(float)
        for lo, hi in gaps:
            if hi - lo < min_ns:
                continue
            mid = (lo + hi) / 2
            name = "unattributed"
            for s, e, n in reversed(spans[max(bisect.bisect_right(starts, mid) - 4, 0):
                                          bisect.bisect_right(starts, mid)]):
                if s <= mid <= e:
                    name = n
                    break
            out[name] += (hi - lo) / 1e9
        return [[n, s] for n, s in sorted(out.items(), key=lambda kv: -kv[1])[:k]]


def instruction_of(event_name: str) -> str:
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def group_of(path: str, instruction: str) -> str:
    """``bwd blocks_N/mlp/fcN`` from an op path; the instruction's kind where
    the compiler left no path."""
    if not path:
        return "no_path " + re.sub(r"[.\d]+$", "", instruction)
    parts = [p for p in path.split("/") if not p.startswith(("jit(", "jvp(",
                                                             "transpose("))]
    where = "bwd" if "transpose(" in path else "fwd"
    module = "/".join(parts[:-1]) or parts[-1]
    return f"{where} " + re.sub(r"\d+", "N", module)
