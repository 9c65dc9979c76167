"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own:

  BENCHMARK.json                  cells, metrics, bounds
  bench/configs/<config>.json     sizes as run, source, reduced, assumed
  bench/traffic/<mix>.json        parameters for the one generator (traffic.py)
  bench/metrics/<metric>.py       ``read(run) -> float | None``

A new cell or metric is new files and new entries; no code here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or does not fit."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return _load_json(root / c["file"])
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return _load_json(bench / "traffic" / f"{name}.json")


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = _load_json(bench / "peaks.json")
    if device_kind not in table:
        raise SpecError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


def metrics_for(bm: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end ones without the trace,
    per-layer ones with it.  A metric without ``workloads`` applies to
    every cell."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, bench: Path = BENCH):
    """``read`` of ``bench/metrics/<name>.py``, loaded by path (metric names
    hold dots, so they are not importable module names)."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
