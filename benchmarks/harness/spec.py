"""Find what belongs to a cell by the names in ``BENCHMARK.json``: its
configuration's file, its traffic mix, its limits, its per-layer metrics and
their readers, its driver and its FLOPs functions. Nothing here is keyed on a
cell's name; a later PR adds a cell by adding entries and files."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


class SpecError(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_file: str | None = None) -> dict:
    """Everything the harness needs to run one cell, as plain dicts."""
    bench = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    checks_path = os.path.join(BENCH, "checks", workload + ".json")
    if not os.path.exists(checks_path):
        raise SpecError(f"{checks_path}: a cell needs the limits of its comparison")

    def in_cell(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]
    end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in bench["per_layer"]:
        if in_cell(m) and m["moves"] in reported:
            reader = load_json(os.path.join(BENCH, "metrics", m["name"] + ".json"))
            per_layer.append({**m, **reader})
    return {"cell": cell, "config": config, "traffic": traffic,
            "checks": load_json(checks_path), "end_to_end": end_to_end,
            "per_layer": per_layer}


def module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``: a driver, a reader, a reference or a
    family's FLOPs functions, found by name."""
    if not name.replace("_", "").isalnum():
        raise SpecError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmarks.{kind}.{name}")
