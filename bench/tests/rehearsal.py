"""Helpers for the CPU rehearsals: a checkout-shaped directory holding a
copy of ``bench/``, a ``BENCHMARK.json`` of tiny cells and a link to the
program's ``src``, so that the harness runs end to end on the CPU in
seconds (Pallas kernels interpreted)."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def tiny_config(name: str) -> dict:
    """The named configuration at a tiny scale: 2 devices on 2 tenants,
    30 Hz, a quarter-hour archive; codec widths as published."""
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg["fleet"] = {"devices": 2, "tenants": 2}
    cfg["sample_rate_hz"] = 30
    if "archive" in cfg:
        cfg["archive"]["hours"] = 0.1
        # answers that never come fail their request within a second
        cfg["frontend"]["kwargs"]["request_timeout_s"] = 1.0
    return cfg


def tiny_mix(name: str) -> dict:
    mix = json.loads((REPO / "bench" / "traffic" / f"{name}.json")
                     .read_text())
    if mix["kind"] == "upload":
        mix.update(backlog_minutes=40, samples_per_feed=1800, lead_s=0.2,
                   warmup_feeds=4, warmup_rounds_max=2,
                   check={"devices": 2})
    else:
        mix.update(rate_per_s=20.0, range_minutes=[1, 3], connections=4,
                   warmup_burst=2, warmup_round_s=0.5,
                   warmup_rounds_max=2,
                   check={"requests": 6})
    return mix


def make_root(tmp: Path, cells=None) -> Path:
    """A rehearsal checkout under ``tmp``.  ``cells`` maps a cell name to
    ``(config name, config dict, traffic name, traffic dict)``; by default
    the two benchmark cells at tiny scale."""
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    if cells is None:
        cells = {}
        for w in bm["workloads"]:
            cells[w["name"]] = (w["config"], tiny_config(w["config"]),
                                w["traffic"], tiny_mix(w["traffic"]))
    bm = copy.deepcopy(bm)
    bm["configs"], bm["workloads"] = [], []
    for cell, (cname, cfg, tname, mix) in cells.items():
        f = f"bench/configs/{cname}.json"
        (root / f).write_text(json.dumps(cfg))
        (root / "bench" / "traffic" / f"{tname}.json").write_text(
            json.dumps(mix))
        if cname not in [c["name"] for c in bm["configs"]]:
            bm["configs"].append({"name": cname, "source": "rehearsal",
                                  "file": f, "reduced": [], "why": "tiny"})
        bm["workloads"].append({"name": cell, "config": cname,
                                "traffic": tname, "chips": 1,
                                "why": "tiny"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root
