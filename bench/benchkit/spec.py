"""Finds a cell's files by name.

``BENCHMARK.json`` at the checkout's root lists the configurations, cells
and metrics.  A cell's configuration is the file its entry names; its
traffic mix is ``bench/traffic/<traffic>.json``, a data file whose
``kind`` names the general generator that reads it,
``bench/kinds/<kind>.py``; each metric is read by
``bench/metrics/<metric>.py``, a module with ``read(ctx) -> float | None``.
A later change adds a cell, a traffic kind or a metric by adding such
files and entries.

A traffic kind's module holds everything that depends on the kind:

``Load(spec)``
    The load generator of the kind, run in the load generator's process
    (which never imports JAX): ``prepare()``, ``async setup(host, port)``,
    ``async warm_round(i)``, optionally ``async start()`` (called once
    warm-up is done), ``async window(t0, t1)``, ``async finish()`` and
    its ``records`` (dicts with at least ``sent``, ``done`` and ``ok``).
``attempted(records, t0, t1)`` and ``latency(record)``
    Which requests belong to the window, and each one's latency.
``LIMITS``, ``readings(cfg, mix, seed, done, window, log)``
    The correctness comparison against the plain reference.
``control(cfg, mix, seed, seconds, minutes, log)``
    The same readings with the lower-precision reference serving.
``async serve_setup(fe, cfg, root, log)`` (optional)
    Server-side set-up the kind needs, in the process that holds the chip.
``notes(cfg, mix, log)`` (optional)
    Lines for standard error ahead of the result.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

__all__ = ["Cell", "load_cell", "metric_reader", "traffic_kind"]


@dataclass
class Cell:
    root: Path
    name: str
    entry: dict          # the workloads entry
    cfg: dict            # the configuration file
    mix: dict            # the traffic file
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    confs = {c["name"]: c for c in bm["configs"]}
    cfg = json.loads((root / confs[entry["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{entry['traffic']}.json").read_text())
    return Cell(root, name, entry, cfg, mix,
                [m for m in bm["end_to_end"] if _applies(m, name)],
                [m for m in bm["per_layer"] if _applies(m, name)])


def _module(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module(Path(root) / "bench" / "metrics" / f"{metric}.py",
                   "benchmetric_").read


_KINDS: dict = {}


def traffic_kind(root: Path, kind: str):
    """The module ``bench/kinds/<kind>.py``, loaded once per process."""
    path = (Path(root) / "bench" / "kinds" / f"{kind}.py").resolve()
    if path not in _KINDS:
        if not path.is_file():
            raise KeyError(f"no traffic kind {kind!r}: {path} is missing")
        _KINDS[path] = _module(path, "benchkind_")
    return _KINDS[path]
