import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Optional override for CPU CI tests (must still precede the jax import).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract inputs (ShapeDtypeStruct, no allocation),
jits the right step (train_step / prefill_step / serve_step) with explicit
in/out shardings on the production mesh, compiles, and records:

  - compiled.cost_analysis()   -> per-chip HLO FLOPs / bytes accessed
  - compiled.as_text() parse   -> per-chip collective wire bytes (ring model)
  - compiled.memory_analysis() -> per-chip buffer sizes (when available)

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system.  Results are JSON artifacts consumed by
benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k --mesh both --out artifacts/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --jobs 6
"""
import argparse
import functools
import json
import re
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import ALIASES, ARCHS, SHAPES, LONG_CONTEXT_OK, ShapeSpec, get_config
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch import sharding as shd
from repro.models import lm
from repro.models.common import ModelConfig, set_sharding_rules
from repro.train import init_train_state, make_train_step
from repro.serve import prefill_step

# ----------------------------------------------------------------- input specs

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    else:  # decode: one new token against a cache of length S
        specs = {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["memory"] = jax.ShapeDtypeStruct(
            (B, cfg.num_image_tokens, cfg.d_model), cfg.dtype)
    if cfg.family == "audio" and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return specs


def _memory_len(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.num_image_tokens
    if cfg.family == "audio":
        return cfg.encoder_seq
    return 0


# ------------------------------------------------------------ collective parse

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_OP_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s*(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-chip wire bytes per collective kind (ring cost model)."""
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or "-done(" in line:
            continue
        type_str, kind = m.group(1), m.group(2)
        size = _type_bytes(type_str)
        if size == 0:
            continue
        n = 1
        g = _GROUPS_BRACE_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            g = _GROUPS_IOTA_RE.search(line)
            if g:
                n = int(g.group(2))
        if n <= 1:
            continue
        if kind == "all-reduce":
            wire = 2.0 * (n - 1) / n * size
        elif kind == "all-gather":
            wire = (n - 1) / n * size  # result type is the gathered shape
        elif kind == "reduce-scatter":
            wire = float(n - 1) * size  # result is the scattered shard
        elif kind == "all-to-all":
            wire = (n - 1) / n * size
        else:  # collective-permute
            wire = float(size)
        d = out.setdefault(kind, {"count": 0, "wire_bytes": 0.0})
        d["count"] += 1
        d["wire_bytes"] += wire
    return out


# ------------------------------------------------------------------- lowering


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int = 0, cfg_override=None, smoke: bool = False):
    """Returns (lowered, meta) for one cell.  smoke=True swaps in the
    reduced config (same family/stage plan) -- used by CI to validate the
    full lowering path on the production mesh quickly."""
    cfg = cfg_override or get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    baxes = batch_axes(multi_pod)
    set_sharding_rules(shd.activation_rules(cfg, mesh, baxes))

    specs = input_specs(cfg, shape)
    batch_sh = shd.to_shardings(
        shd.batch_specs(specs, mesh, baxes), mesh)

    key = jax.random.key(0)
    if shape.kind == "train":
        mb = microbatches if microbatches else cfg.train_microbatches
        while shape.global_batch % mb or (shape.global_batch // mb) < 1:
            mb //= 2
        state_shape = jax.eval_shape(
            functools.partial(init_train_state, cfg=cfg), key)
        state_spec = shd.state_specs(state_shape, cfg, mesh)
        state_sh = shd.to_shardings(state_spec, mesh)
        step = make_train_step(cfg, lr=1e-4, microbatches=mb)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
        with jax.sharding.set_mesh(mesh):
            lowered = fn.lower(state_shape, specs)
    elif shape.kind == "prefill":
        params_shape = jax.eval_shape(
            functools.partial(lm.init_params, cfg=cfg), key)
        pspec = shd.param_specs(params_shape, cfg, mesh)
        p_sh = shd.to_shardings(pspec, mesh)
        mem_key = ("memory" if "memory" in specs
                   else "frames" if "frames" in specs else None)

        def pf(params, tokens, memory=None):
            if cfg.family == "audio":
                memory = lm.encode_frames(params, memory, cfg)
            return prefill_step(params, tokens, cfg, memory)

        if mem_key:
            fn = jax.jit(pf, in_shardings=(p_sh, batch_sh["tokens"],
                                           batch_sh[mem_key]))
            args = (params_shape, specs["tokens"], specs[mem_key])
        else:
            fn = jax.jit(pf, in_shardings=(p_sh, batch_sh["tokens"]))
            args = (params_shape, specs["tokens"])
        with jax.sharding.set_mesh(mesh):
            lowered = fn.lower(*args)
    else:  # decode
        params_shape = jax.eval_shape(
            functools.partial(lm.init_params, cfg=cfg), key)
        pspec = shd.param_specs(params_shape, cfg, mesh)
        p_sh = shd.to_shardings(pspec, mesh)
        cache_shape = jax.eval_shape(functools.partial(
            lm.init_cache, cfg, shape.global_batch, shape.seq_len,
            _memory_len(cfg)))
        shard_seq = shape.global_batch == 1
        cache_spec = shd.cache_specs(cache_shape, cfg, mesh, baxes, shard_seq)
        cache_sh = shd.to_shardings(cache_spec, mesh)

        def ds(params, cache, tokens):
            return lm.decode_step(params, cache, tokens, cfg)

        fn = jax.jit(ds, in_shardings=(p_sh, cache_sh, batch_sh["tokens"]),
                     out_shardings=(None, cache_sh), donate_argnums=(1,))
        with jax.sharding.set_mesh(mesh):
            lowered = fn.lower(params_shape, cache_shape,
                               specs["tokens"])
    set_sharding_rules(None)
    n_params = cfg.param_count()
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": shape.kind, "n_params": n_params,
            "n_active": cfg.active_param_count(),
            "chips": 512 if multi_pod else 256,
            "global_batch": shape.global_batch, "seq_len": shape.seq_len}
    return lowered, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             microbatches: int = 0, smoke: bool = False) -> dict:
    t0 = time.time()
    rec: dict = {}
    try:
        lowered, rec = build_cell(arch, shape_name, multi_pod, microbatches,
                                  smoke=smoke)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # jax<=0.4 wraps it in a list
            cost = cost[0] if cost else {}
        # XLA's analysis visits while bodies once -> undercounts scans;
        # kept for reference only. The roofline uses the trip-count-aware
        # numbers from hlo_cost.analyze.
        rec["xla_flops_body_once"] = float(cost.get("flops", -1))
        rec["xla_bytes_body_once"] = float(cost.get("bytes accessed", -1))
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)
            }
        except Exception as e:  # CPU backend may not support it
            rec["memory_analysis"] = {"error": str(e)}
        text = compiled.as_text()
        # persist the partitioned HLO (zstd) so analysis can be re-run
        # without recompiling; optional -- cost analysis proceeds without it
        try:
            import zstandard as zstd
        except ImportError:
            zstd = None
        if zstd is not None:
            os.makedirs(out_dir, exist_ok=True)
            tag0 = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
            with open(os.path.join(out_dir, tag0 + ".hlo.zst"), "wb") as f:
                f.write(zstd.ZstdCompressor(level=3).compress(text.encode()))
        from repro.launch.hlo_cost import analyze as hlo_analyze
        cost2 = hlo_analyze(text)
        rec["flops_per_chip"] = cost2["flops"]
        rec["bytes_per_chip"] = cost2["bytes"]
        rec["collectives"] = cost2["collectives"]
        rec["collective_wire_bytes_per_chip"] = cost2["collective_wire_bytes"]
        rec["trace_s"] = t1 - t0
        rec["compile_s"] = t2 - t1
        rec["status"] = "ok"
    except Exception as e:
        rec.update({"arch": arch, "shape": shape_name,
                    "mesh": "2x16x16" if multi_pod else "16x16",
                    "status": "fail", "error": f"{type(e).__name__}: {e}"})
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[{rec['status']}] {tag} "
          f"(compile {rec.get('compile_s', 0):.1f}s) "
          f"{rec.get('error', '')}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = use cfg.train_microbatches")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs on the production mesh (CI)")
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [ALIASES.get(args.arch, args.arch)]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            if shape == "long_500k" and arch not in LONG_CONTEXT_OK:
                print(f"[skip] {arch}_{shape} (full attention; DESIGN.md S5)",
                      flush=True)
                continue
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out,
                               args.microbatches, smoke=args.smoke)
                n_fail += rec["status"] != "ok"
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
