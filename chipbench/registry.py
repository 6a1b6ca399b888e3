"""Find a cell's configuration, traffic, limits and metric readers by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under ``chipbench/``:

* ``BENCHMARK.json`` (checkout root): cells, metrics, each configuration's
  file;
* ``configs/<config>.json``: the model sizes as run (``model``), the
  registry name of the program's architecture and the ``family``;
* ``families/<family>.py``: the family's seeded weights in the program's
  layout ``make_params(m, key)``, its plain float32 loss ``loss(m, params,
  batch)`` and ``flops_per_token(m, seq)``;
* ``traffic/<traffic>.json``: the hierarchy, optimizer, per-worker batch
  and executor of a training mix;
* ``limits/<workload>.json``: the limits of the numbers that decide
  ``correct`` for that cell;
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None``.

A new cell, configuration, family or metric is new files plus new entries
in ``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import types
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = "chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    family: types.ModuleType     # families/<config["family"]>.py
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # limits/<workload>.json
    end_to_end: List[dict]
    per_layer: Dict[str, dict]   # name -> BENCHMARK.json entry


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def resolve(root, workload: str) -> Cell:
    """The cell ``workload`` of the benchmark at checkout ``root``."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    base = root / BENCH_DIR
    config = _read_json(root / cfg["file"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=family(root, config["family"]),
        traffic=_read_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(base / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer={m["name"]: m for m in bench["per_layer"] if applies(m)})


def _load(path: Path, prefix: str, what: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root, name: str) -> Callable:
    """``read(ctx)`` of ``chipbench/metrics/<name>.py``."""
    path = Path(root) / BENCH_DIR / "metrics" / f"{name}.py"
    return _load(path, "chipbench_metric", f"reader for metric {name!r}").read


@functools.cache
def _family(path: Path) -> types.ModuleType:
    return _load(path, "chipbench_family", "model family")


def family(root, name: str) -> types.ModuleType:
    """The module ``chipbench/families/<name>.py``: ``make_params``,
    ``loss`` and ``flops_per_token`` of one model family."""
    return _family((Path(root) / BENCH_DIR / "families"
                    / f"{name}.py").resolve())
