"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here imports jax: the harness reads the cell before it touches a
device.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict                 # compared number -> its limit
    end_to_end: tuple            # metric entries reported with --trace 0
    per_layer: tuple             # metric entries reported with --trace 1


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", [name])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload,
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        limits=_json(BENCH / "checks" / f"{workload}.json")["limits"],
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _for_cell(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _for_cell(m, workload)),
    )


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def metric_reader(name: str):
    """The ``reduce(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.reduce


def step_kind(name: str):
    """The module ``bench/steps/<name>.py`` that builds a cell's step."""
    return importlib.import_module(f"bench.steps.{name}")
