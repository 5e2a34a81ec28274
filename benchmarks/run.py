"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Roofline rows are emitted
when dry-run artifacts exist (run scripts/run_dryrun_sweep.sh first).

``--quick`` (or ``REPRO_BENCH_QUICK=1``) is the CI smoke profile: modules
that expose a quick knob shrink their workloads, and only the fast,
dependency-light host/codec benches run.

``--json PATH`` additionally writes the rows as a machine-readable
document -- the input of the CI perf gate (``scripts/bench_gate.py``):

    {"version": 1, "quick": bool,
     "results": {name: {"us_per_call": float, "derived": str}},
     "failed": [module, ...],
     "metrics_snapshot": {...}}

``metrics_snapshot`` is the full ``repro.obs`` JSON export (metric
families + recent spans) taken after all benches ran in this process --
the nightly job uploads it as an artifact, so codec health counters
(hit rates, gate rejections, backend choices, stage latencies) ride
along with every full bench run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import traceback

QUICK_MODULES = ("stream_io", "store_decode", "decode_backends",
                 "encode_fused", "adaptive_batch", "frontier",
                 "obs_overhead")  # fast host/codec smoke set

RESULTS_VERSION = 1


def rows_to_results(rows) -> dict:
    """Parse ``name,us_per_call,derived`` rows into the JSON results map.
    Malformed rows are skipped with a warning instead of failing the run."""
    results = {}
    for row in rows:
        try:
            name, us, derived = row.split(",", 2)
            results[name] = {"us_per_call": float(us), "derived": derived}
        except ValueError:
            print(f"unparseable bench row skipped: {row!r}", file=sys.stderr)
    return results


def carry_tolerances(path: str, doc: dict) -> dict:
    """Refreshing a committed baseline in place must not drop its
    hand-embedded per-bench ``tolerances`` map (the gate's noise
    allowances): carry the existing file's over when the target already
    has one."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            old = json.load(f)
        tol = old.get("tolerances")
        if isinstance(tol, dict) and tol:
            doc["tolerances"] = tol
    except (OSError, ValueError):
        pass
    return doc


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small workloads, host/codec benches only")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as machine-readable JSON "
                         "(the perf-gate input)")
    args = ap.parse_args(argv)
    # the env var alone activates quick too, as the module docstring says
    if bool(int(os.environ.get("REPRO_BENCH_QUICK", "0") or "0")):
        args.quick = True
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    modules = [
        ("table1", "bench_table1_ratio"),
        ("table2", "bench_table2_quality"),
        ("fig3", "bench_fig3_pvalue"),
        ("fig12", "bench_fig12_spectral"),
        ("fig14", "bench_fig14_tradeoff"),
        ("fig15", "bench_fig15_speed"),
        ("limits", "bench_limits"),
        ("gradcomp", "bench_gradcomp"),
        ("stream_io", "bench_stream_io"),
        ("shard_encode", "bench_shard_encode"),
        ("store_decode", "bench_store_decode"),
        ("decode_backends", "bench_decode_backends"),
        ("encode_fused", "bench_encode_fused"),
        ("adaptive_batch", "bench_adaptive_batch"),
        ("frontier", "bench_frontier"),
        ("obs_overhead", "bench_obs_overhead"),
        # full profile only: socket latency is PR-runner noise, and the
        # quick baseline has no row for it (perf-gate contract)
        ("frontend", "bench_frontend"),
        ("roofline", "roofline"),
    ]
    if args.quick:
        modules = [(n, m) for n, m in modules if n in QUICK_MODULES]
    failed = []
    all_rows = []
    for name, modname in modules:
        try:
            # imported per bench so a missing optional dep (e.g. zstandard
            # for the baseline codecs) only fails its own rows
            mod = importlib.import_module(f"benchmarks.{modname}")
            for row in mod.run():
                all_rows.append(row)
                print(row, flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        doc = carry_tolerances(args.json, {
            "version": RESULTS_VERSION, "quick": args.quick,
            "results": rows_to_results(all_rows), "failed": failed})
        try:  # codec health from this process's bench traffic (obs layer)
            from repro import obs
            doc["metrics_snapshot"] = obs.to_json()
        except Exception:
            traceback.print_exc()
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {len(doc['results'])} results -> {args.json}",
              file=sys.stderr)
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
