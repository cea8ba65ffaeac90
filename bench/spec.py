"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is made of ``bench/configs/<config>.json`` (sizes, engine
settings, weight statistics), ``bench/traffic/<traffic>.json``
(arrivals, lengths, warm-up, pre-roll) and ``bench/cells/<cell>.json`` (the
correctness limits with the readings they were set from, and the
check's packed length).
A per-layer metric ``<name>`` is ``bench/metrics/<name>.py``, a module with
a ``read(run)`` function that returns a number or ``None``.  Adding any of
them takes new files and a new entry in ``BENCHMARK.json``, never an edit.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, bench: dict | None = None) -> dict:
    """The workload entry, its configuration and traffic files, and the
    metric entries that apply to it."""
    bench = benchmark() if bench is None else bench
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(work)}")
    w = work[name]
    cfg = config(w["config"], bench)
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    own = json.loads((BENCH / "cells" / f"{name}.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"workload": w, "config": cfg, "traffic": traffic, "cell": own,
            "end_to_end": end_to_end, "per_layer": per_layer}


def config(name: str, bench: dict | None = None) -> dict:
    bench = benchmark() if bench is None else bench
    entry = {c["name"]: c for c in bench["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return importlib.import_module(f"bench.metrics.{name}").read
