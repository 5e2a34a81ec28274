"""One run of one cell: set-up, the measured window, the correctness
comparison and the metrics, as ``bench/run.py`` prints them.

The process that runs this holds the chip and serves: it builds the
program's ``ServeFrontend`` from the cell's configuration and runs its
event loop, while the load generator (``benchkit.loadgen``, a child
process that never imports JAX) drives it over the wire.  Set-up is
everything from the start of the process until the load generator has
made its data and sent its warm-up traffic; the window then runs for the
given seconds, with nothing left to compile.  After the window the
program's state is freed and the traffic kind's comparison with the
plain reference (``bench/kinds/<kind>.py``, ``benchkit.reference``)
judges the checked answers.
"""
from __future__ import annotations

import asyncio
import gc
import json
import math
import os
import resource
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from . import roofline, server, spec as spec_mod, xplane
from .compiles import CompileLog

__all__ = ["NoAccelerator", "RunContext", "nearest_rank", "run_cell"]

READY_TIMEOUT_S = 900.0
DONE_TIMEOUT_S = 180.0
MARKS_TIMEOUT_S = 300.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by the nearest-rank rule (a value of the
    sample: the smallest with at least ``q`` of the sample at or below)."""
    v = sorted(values)
    if not v:
        raise ValueError("empty sample")
    return v[max(math.ceil(q * len(v)) - 1, 0)]


@dataclass
class RunContext:
    """What a metric's reader sees of a run.  ``counter`` and
    ``histogram`` read growth over the window, or in a traced run over
    its traced part; ``records`` cover the whole window."""

    cell: spec_mod.Cell
    kind: object          # the traffic kind's module
    seed: int
    t0: float
    t1: float
    setup_s: float
    records: list
    done: dict
    counters_before: dict
    counters_after: dict
    compiles_setup: dict
    compiles_window: dict
    peaks: dict
    trace: Optional[xplane.Trace] = None

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def mix(self) -> dict:
        return self.cell.mix

    def note(self, msg: str) -> None:
        """A line for standard error, ahead of the result."""
        log(msg)

    def window_records(self) -> list:
        """The requests of the window, by the traffic kind's rule."""
        return self.kind.attempted(self.records, self.t0, self.t1)

    def latency(self, record: dict) -> float:
        """A request's latency in seconds, by the traffic kind's rule."""
        return self.kind.latency(record)

    def _entry(self, snap: dict, name: str, labels: Optional[dict]):
        for v in snap.get(name, {}).get("values", []):
            if v["labels"] == (labels or {}):
                return v
        return None

    def counter(self, name: str, labels: Optional[dict] = None) -> float:
        """Growth of a counter over the window."""
        a = self._entry(self.counters_before, name, labels)
        b = self._entry(self.counters_after, name, labels)
        return (b["value"] if b else 0.0) - (a["value"] if a else 0.0)

    def histogram(self, name: str, labels: Optional[dict] = None):
        """``(sum, count)`` added to a histogram over the window."""
        a = self._entry(self.counters_before, name, labels)
        b = self._entry(self.counters_after, name, labels)
        s = (b["sum"] if b else 0.0) - (a["sum"] if a else 0.0)
        c = (b["count"] if b else 0) - (a["count"] if a else 0)
        return s, c


def _configure_jax(root: Path, on_tpu: bool) -> None:
    # the TPU runtime logs under /tmp unless told otherwise
    if on_tpu and "TPU_LOG_DIR" not in os.environ:
        logs = root / "artifacts" / "tpu-logs"
        logs.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(logs)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    # every executable goes to the persistent cache, however quickly it
    # compiled (the default keeps only those over one second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX's default device is {devs[0].platform!r}"
                            f", not a TPU: nothing to measure")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs


class GcLog:
    """Collections of this process's Python heap, each with its
    generation and its start and end on the host's clock (a diagnostic
    for stalls: printed, never a metric)."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic(),
                                info["generation"]))
            self._t = None

    def register(self) -> "GcLog":
        gc.callbacks.append(self)
        return self

    def unregister(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def report(self, t0: float, t1: float) -> str:
        inside = [(a, b, g) for a, b, g in self.pauses if t0 <= a < t1]
        gens = [sum(1 for *_x, g in inside if g == k) for k in range(3)]
        long = [f"{1e3 * (b - a):.1f} ms at +{a - t0:.2f} s (gen {g})"
                for a, b, g in inside if b - a >= 0.02]
        return (f"collections by generation {gens}, "
                f"{sum(b - a for a, b, _g in inside):.3f} s in all"
                + (f"; 20 ms or longer: {', '.join(long)}" if long else ""))


def _cpu_s() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


TRACE_SECONDS = 4.0      # length of the traced part of a --trace 1 window
TRACE_LEAD_S = 0.5       # the profiler starts this long before it


class LoadGenerator:
    """The load generator's process, started before this process touches
    JAX so that it makes its data while the chip is brought up.  Lines
    are read on a worker thread, so the server's event loop keeps
    serving while it waits."""

    def __init__(self, root: Path, spec: dict):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(root / "bench")] + [p for p in [env.get("PYTHONPATH")]
                                     if p])
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchkit.loadgen"], cwd=str(root),
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.send(spec)

    def send(self, doc: dict) -> None:
        self.proc.stdin.write((json.dumps(doc) + "\n").encode())
        self.proc.stdin.flush()

    async def event(self, names, timeout: float) -> dict:
        """The next line, whose ``event`` must be one of ``names``."""
        names = (names,) if isinstance(names, str) else tuple(names)
        loop = asyncio.get_running_loop()
        line = await asyncio.wait_for(
            loop.run_in_executor(None, self.proc.stdout.readline), timeout)
        if not line:
            raise RuntimeError(f"load generator exited before {names}")
        doc = json.loads(line)
        if doc.get("event") not in names:
            raise RuntimeError(f"load generator said {doc.get('event')!r}, "
                               f"expected one of {names}")
        return doc

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        for pipe in (self.proc.stdin, self.proc.stdout):
            if not pipe.closed:
                pipe.close()


def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def _window_marks(t0: float, t1: float, compiles, trace_dir) -> dict:
    """On threads of their own, so that a busy event loop cannot delay
    them: the registry snapshot and compile count at the window's start
    and end; and with ``trace_dir`` the profiler over the middle
    ``TRACE_SECONDS`` of the window, marked by the ``WINDOW_SPAN`` and by
    registry snapshots at the span's ends (the per-layer readers of a
    traced run read the counters over the traced part)."""
    import jax

    from repro import obs

    marks: dict = {}

    def window() -> None:
        _sleep_until(t0)
        marks["before"] = obs.registry().snapshot()
        marks["mark0"] = compiles.mark()
        marks["cpu0"] = _cpu_s()
        _sleep_until(t1)
        marks["cpu1"] = _cpu_s()
        marks["after"] = obs.registry().snapshot()
        marks["mark1"] = compiles.mark()

    def traced() -> None:
        length = min(TRACE_SECONDS, t1 - t0)
        ta = t0 + 0.5 * (t1 - t0 - length)
        _sleep_until(ta - TRACE_LEAD_S)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        _sleep_until(ta)
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            marks["trace_before"] = obs.registry().snapshot()
            _sleep_until(ta + length)
            marks["trace_after"] = obs.registry().snapshot()
        jax.profiler.stop_trace()

    marks["threads"] = [threading.Thread(target=fn, name=f"bench-{fn.__name__}",
                                         daemon=True)
                        for fn in ([window, traced] if trace_dir else
                                   [window])]
    for th in marks["threads"]:
        th.start()
    return marks


async def _drive(cell, kind, seed: int, seconds: float, trace_dir,
                 compiles, t_start: float, gen: LoadGenerator) -> dict:
    import jax

    root, cfg = cell.root, cell.cfg
    fe = server.make_frontend(cfg)
    await fe.start()
    try:
        if hasattr(kind, "serve_setup"):
            await kind.serve_setup(fe, cfg, root, log)
        log(f"server up after {time.monotonic() - t_start:.3f} s")
        gen.send({"host": fe.host, "port": fe.port})
        last = compiles.mark()
        while True:
            ev = await gen.event(("ready", "warm_round"), READY_TIMEOUT_S)
            if ev["event"] == "ready":
                ready = ev
                break
            # the load generator warms up in rounds until one builds no
            # executable: tell it how many the last round built
            now = compiles.mark()
            gen.send({"builds": now["compiled"] - last["compiled"]})
            last = now
        setup_s = time.monotonic() - t_start
        compiles_setup = compiles.mark()
        log(f"set-up {setup_s:.3f} s; load generator: {ready}")
        # the kind's steady load has run since "ready" for its lead
        t0 = (time.monotonic() + max(0.05, float(ready.get("lead_s", 0)))
              + (TRACE_LEAD_S if trace_dir else 0))
        marks = _window_marks(t0, t0 + seconds, compiles, trace_dir)
        gen.send({"t0": t0})
        closed = await gen.event("closed", seconds + 60.0)
        done = await gen.event("done", DONE_TIMEOUT_S)
        for th in marks["threads"]:
            await asyncio.get_running_loop().run_in_executor(
                None, th.join, MARKS_TIMEOUT_S)
            if th.is_alive():
                raise RuntimeError(f"{th.name} did not finish")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        return {"setup_s": setup_s, "t0": closed["t0"], "t1": closed["t1"],
                "done": done,
                "before": marks.get("trace_before", marks["before"]),
                "after": marks.get("trace_after", marks["after"]),
                "compiles_setup": compiles_setup,
                "compiles_window": CompileLog.since(marks["mark0"],
                                                    marks["mark1"]),
                "cpu_s": marks["cpu1"] - marks["cpu0"],
                "memory_peak_bytes": int(peak)}
    finally:
        gen.stop()
        await fe.close()


def _tenths(records: list, t0: float, t1: float, latency) -> list:
    """Median and largest latency of the requests that ended in each
    tenth of the window (a diagnostic of how a backlog grows)."""
    out = []
    for k in range(10):
        a, b = t0 + k * (t1 - t0) / 10, t0 + (k + 1) * (t1 - t0) / 10
        lat = [latency(r) for r in records if a <= r["done"] < b and r["ok"]]
        out.append(f"{1e3 * nearest_rank(lat, 0.5):.0f}/"
                   f"{1e3 * max(lat):.0f}" if lat else "-")
    return out


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: Optional[float] = None,
             require_tpu: bool = True) -> dict:
    """One run; returns the result line's object.  ``require_tpu=False``
    is for rehearsals on the CPU only: such a result names the CPU."""
    t_start = time.monotonic() if t_start is None else t_start
    root = Path(root)
    cell = spec_mod.load_cell(root, workload)
    kind = spec_mod.traffic_kind(root, cell.mix["kind"])
    gen = LoadGenerator(root, {"root": str(root), "seed": seed,
                               "seconds": seconds, "config": cell.cfg,
                               "traffic": cell.mix})
    try:
        _configure_jax(root, require_tpu)
        devs = _devices(int(cell.entry["chips"]), require_tpu)
    except BaseException:
        gen.stop()
        raise
    dev = devs[0]
    peaks = (roofline.load_peaks(root / "bench" / "peaks.json",
                                 dev.device_kind)
             if require_tpu else {})
    compiles = CompileLog().register()
    gclog = GcLog().register()
    server.import_program(root)
    trace_dir, wrapped = None, []
    try:
        if trace:
            wrapped = server.annotate_program()
            log(f"profiler spans on {len(wrapped)} program calls")
            trace_dir = root / "artifacts" / "bench-trace" / workload
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
        out = asyncio.run(_drive(cell, kind, seed, seconds, trace_dir,
                                 compiles, t_start, gen))
    finally:
        gen.stop()
        server.restore_program(wrapped)
        compiles.unregister()
        gclog.unregister()
    gc.collect()
    cs, cw = out["compiles_setup"], out["compiles_window"]
    log(f"executables built in set-up: {cs['compiled']} "
        f"({cs['compile_s']:.3f} s), {cs['cache_reads']} of them read from "
        f"the persistent cache; in the window: {cw['compiled']}, "
        f"{cw['cache_reads']} of them read from the cache")
    for t, msg in compiles.messages:
        if out["t0"] <= t < out["t1"]:
            log(f"compiled in the window: {msg[:400]}")
    log(f"CPU in the window: server process {out['cpu_s']:.3f} s, load "
        f"generator {out['done'].get('cpu_window_s', 0):.3f} s, over "
        f"{out['t1'] - out['t0']:.3f} s; server's Python heap "
        f"{gclog.report(out['t0'], out['t1'])}")

    done = out["done"]
    if done.get("jax_imported"):
        raise RuntimeError("the load generator imported JAX")
    tr = None
    if trace_dir is not None:
        tr = xplane.load(xplane.find_xplane(str(trace_dir)))
    ctx = RunContext(cell=cell, kind=kind, seed=seed, t0=out["t0"], t1=out["t1"],
                     setup_s=out["setup_s"], records=done["records"],
                     done=done, counters_before=out["before"],
                     counters_after=out["after"], compiles_setup=cs,
                     compiles_window=cw, peaks=peaks, trace=tr)
    if hasattr(kind, "notes"):
        kind.notes(cell.cfg, cell.mix, log)
    att = ctx.window_records()
    lat = [ctx.latency(r) for r in att if r["ok"]]
    if lat:
        log("latency ms over {} requests: p50 {:.3f} p90 {:.3f} p95 {:.3f} "
            "p99 {:.3f} max {:.3f}".format(
                len(lat), *(1e3 * nearest_rank(lat, q)
                            for q in (0.5, 0.9, 0.95, 0.99, 1.0))))
        log("latency ms by tenth of the window, p50/max: " + ", ".join(
            _tenths(att, ctx.t0, ctx.t1, ctx.latency)))
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec_mod.metric_reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"attempted": len(att),
              "failed": sum(1 for r in att if not r["ok"]),
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = xplane.busy_seconds(tr)
        device["window_s"] = xplane.window_seconds(tr)
        result["breakdown"] = {"device_ops": xplane.top_ops(tr),
                               "idle_gaps": xplane.idle_gaps(tr)}

    readings = kind.readings(cell.cfg, cell.mix, seed, done, att, log=log)
    compared = {k: {"value": v, "limit": kind.LIMITS[k]}
                for k, v in readings.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    for k, c in compared.items():
        log(f"compared {k} = {c['value']} (limit {c['limit']})")
    return {"correct": correct, **result, "compared": compared}
