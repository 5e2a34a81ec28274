"""The command line: no result off a TPU; a cell added from new files
alone runs without an edit to any file the benchmark has."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import rehearsal  # noqa: E402
from benchkit import harness  # noqa: E402


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(REPO, "--workload", "gateway-backfill", "--seed",
             str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_bench_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, "--workload", "archive-dashboard", "--seed", "3",
             "--seconds", "1", "--trace", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture
def jax_config_restored():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_cell_added_from_new_files_alone(tmp_path, jax_config_restored):
    cfg = rehearsal.tiny_config("upmu-gateway")
    cfg["name"] = "upmu-gateway-small"
    cfg["channels"] = cfg["channels"][:1] + cfg["channels"][6:7]
    mix = rehearsal.tiny_mix("backfill")
    mix["samples_per_feed"] = 900
    root = rehearsal.make_root(tmp_path, cells={
        "gateway-small": ("upmu-gateway-small", cfg, "backfill-short",
                          mix)})
    # a new end-to-end metric: a file of its own and an entry
    (root / "bench" / "metrics" / "requests_per_s.py").write_text(
        "def read(ctx):\n"
        "    n = sum(1 for r in ctx.records if r['ok'] and\n"
        "            ctx.t0 <= r['sent'] and r['done'] <= ctx.t1)\n"
        "    return n / (ctx.t1 - ctx.t0)\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["end_to_end"].append({"name": "requests_per_s", "unit": "1/s",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["gateway-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and p.suffix in (".py", ".json")}
    res = harness.run_cell(root, "gateway-small", 77, 1.0, False,
                           require_tpu=False)
    assert res["correct"], res["compared"]
    assert {"requests_per_s", "setup_s"} <= set(res["metrics"])
    assert res["metrics"]["requests_per_s"]["value"] > 0
    for p, b in before.items():
        assert p.read_bytes() == b


NEW_KIND = '''"""Traffic kind ``health_poll``: open-loop GETs of /healthz."""
import asyncio
import json
import time

from benchkit.wire import Connection, sleep_until

LIMITS = {"answers_wrong": 0, "answers_missing": 0}


class Load:
    def __init__(self, spec):
        self.mix, self.seconds = spec["traffic"], float(spec["seconds"])
        self.records = []

    def prepare(self):
        return {}

    async def setup(self, host, port):
        self.conn = await Connection(host, port).open()
        return {}

    async def warm_round(self, i):
        await self.conn.request("GET", "/healthz")

    async def window(self, t0, t1):
        n = int(self.mix["rate_per_s"] * self.seconds)
        for i in range(n):
            due = t0 + i / self.mix["rate_per_s"]
            await sleep_until(due)
            sent = time.monotonic()
            status, body = await self.conn.request("GET", "/healthz")
            self.records.append({"due": due, "sent": sent,
                                 "done": time.monotonic(),
                                 "ok": status == 200,
                                 "answer": json.loads(body)})

    async def finish(self):
        await self.conn.close()
        return {}


def attempted(records, t0, t1):
    return [r for r in records if t0 <= r["due"] < t1]


def latency(record):
    return record["done"] - record["due"]


def readings(cfg, mix, seed, done, window, log=None):
    return {"answers_wrong": sum(1 for r in window
                                 if r["answer"] != {"ok": True}),
            "answers_missing": sum(1 for r in window if not r["ok"])}


def control(cfg, mix, seed, seconds, minutes=0, log=None):
    return {"answers_wrong": int(mix["rate_per_s"] * seconds),
            "answers_missing": 0}
'''


def test_a_traffic_kind_added_from_new_files_alone(tmp_path,
                                                   jax_config_restored):
    """A new kind of traffic (its generator, window rule, comparison and
    control), a mix that uses it, a cell and a metric: new files and
    entries only."""
    cfg = rehearsal.tiny_config("upmu-gateway")
    root = rehearsal.make_root(tmp_path, cells={
        "gateway-health": ("upmu-gateway", cfg, "health",
                           {"kind": "health_poll", "rate_per_s": 20.0,
                            "warmup_rounds_max": 2})})
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and p.suffix in (".py", ".json")}
    (root / "bench" / "kinds" / "health_poll.py").write_text(NEW_KIND)
    (root / "bench" / "metrics" / "health_p50_ms.py").write_text(
        "from benchkit.harness import nearest_rank\n\n\n"
        "def read(ctx):\n"
        "    lat = [ctx.latency(r) for r in ctx.window_records()]\n"
        "    return 1e3 * nearest_rank(lat, 0.5) if lat else None\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["end_to_end"].append({"name": "health_p50_ms", "unit": "ms",
                             "better": "lower", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["gateway-health"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = harness.run_cell(root, "gateway-health", 78, 1.0, False,
                           require_tpu=False)
    assert res["correct"], res["compared"]
    assert res["compared"] == {"answers_wrong": {"value": 0, "limit": 0},
                               "answers_missing": {"value": 0, "limit": 0}}
    assert res["attempted"] == 20 and res["failed"] == 0
    assert {"health_p50_ms", "setup_s"} <= set(res["metrics"])
    for p, b in before.items():
        assert p.read_bytes() == b
