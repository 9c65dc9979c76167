"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own:

  BENCHMARK.json                  cells, metrics, bounds
  bench/configs/<config>.json     sizes as run, source, reduced, assumed, family
  bench/families/<family>.py      what differs by architecture (reference.py)
  bench/traffic/<mix>.json        parameters for the one generator (traffic.py)
  bench/metrics/<metric>.py       ``read(run) -> float | None``

A new cell or metric is new files and new entries; no code here changes.
"""

from __future__ import annotations

import functools
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


def _load(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH):
    """``read`` of ``bench/metrics/<name>.py``, loaded by path (metric names
    hold dots, so they are not importable module names)."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path}")
    return _load(path, f"bench_metric_{name}").read


def family(cfg: dict, bench: Path = BENCH):
    """The module ``bench/families/<cfg["family"]>.py``: the weights'
    leaves, the program's ModelConfig, the reference's forward pass and the
    operations per token of the configuration's architecture (what it must
    provide: ``reference``).  Loaded by path once per process, so that it
    is one object to every caller."""
    if "family" not in cfg:
        raise SpecError(f'the configuration names no family: add "family": '
                        f'"<name>" for {bench / "families"}/<name>.py')
    path = bench / "families" / f"{cfg['family']}.py"
    if not path.is_file():
        raise SpecError(f"no family {path}")
    return _family(path)


@functools.cache
def _family(path: Path):
    return _load(path, f"bench_family_{path.stem}")
