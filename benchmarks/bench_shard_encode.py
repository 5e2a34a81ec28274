"""Single-device vs sharded encode throughput (DESIGN.md Sec. 6).

Times the batched (C, nb, n) resumable encode scan on one device against
the same scan with the channel axis shard_map'd over N devices.  Devices
are forced host devices when no accelerator is attached, so the inner
measurement runs in a subprocess that owns XLA_FLAGS (same pattern as the
dry-run); on a real TPU/GPU slice the spawn is unnecessary but harmless.

Rows: ``shard_encode/<cell>/{single|sharded}`` with blocks/s and speedup.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from .common import csv_row

_DEVICES = int(os.environ.get("REPRO_BENCH_SHARD_DEVICES", "4"))


def _time_encode(fn, state, repeat: int = 5) -> float:
    import jax

    out, st = fn(state)  # warmup + compile
    jax.block_until_ready(st)
    t0 = time.time()
    for _ in range(repeat):
        out, st = fn(st)
    jax.block_until_ready(st)
    return (time.time() - t0) / repeat


def _inner(channels: int, nb: int, n: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.encoder import (encode_decisions_batched,
                                    encode_decisions_sharded, init_state)
    from repro.launch.encode_plan import make_encode_plan, shard_state

    rng = np.random.default_rng(0)
    blocks = jnp.asarray(rng.normal(size=(channels, nb, n)), jnp.float32)
    kw = dict(num_dict=255, d_crit=0.4, rel_tol=0.5)

    t_single = _time_encode(
        lambda st: encode_decisions_batched(blocks, state=st, **kw),
        init_state(255, n, channels=channels))

    plan = make_encode_plan(channels, block_size=n)
    st = shard_state(plan, init_state(255, n, channels=plan.padded_channels))
    t_sharded = _time_encode(
        lambda st: encode_decisions_sharded(
            blocks, mesh=plan.mesh, axis_name=plan.axis_name, state=st, **kw),
        st)

    print(json.dumps({
        "devices": jax.device_count(), "channels": channels, "nb": nb,
        "n": n, "t_single": t_single, "t_sharded": t_sharded,
    }))


def run(channels: int = 8, nb: int = 192, n: int = 32):
    env = dict(os.environ, PYTHONPATH="src")
    # forced host devices by design: the child stays off any accelerator so
    # it never contends with this process for the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_DEVICES}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_shard_encode", "--inner",
         str(channels), str(nb), str(n)],
        capture_output=True, text=True, timeout=1200, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stdout[-2000:] + out.stderr[-2000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    total_blocks = rec["channels"] * rec["nb"]
    cell = f"C{rec['channels']}xnb{rec['nb']}xn{rec['n']}"
    rows = []
    for kind, t in (("single", rec["t_single"]), ("sharded", rec["t_sharded"])):
        extra = (f";devices={rec['devices']}"
                 f";speedup={rec['t_single'] / rec['t_sharded']:.2f}x"
                 if kind == "sharded" else ";devices=1")
        rows.append(csv_row(
            f"shard_encode/{cell}/{kind}", t * 1e6,
            f"blocks_per_s={total_blocks / t:.0f}{extra}"))
    return rows


if __name__ == "__main__":
    if "--inner" in sys.argv:
        i = sys.argv.index("--inner")
        _inner(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
               int(sys.argv[i + 3]))
    else:
        for row in run():
            print(row)
