"""The load generator: a process of its own that never imports JAX.

It reads one JSON spec line on stdin (checkout root, seed, window length,
configuration and traffic mix) and builds the generator of the mix's
kind (``bench/kinds/<kind>.py``, found by ``spec.traffic_kind``), which
makes its data from the seed while the server brings the chip up.  It
then reads the front end's address (``{"host", "port"}``), lets the
generator open what the mix needs, and warms up in rounds: after each
round it prints ``{"event": "warm_round"}`` and reads back
``{"builds": n}``, the executables the server built meanwhile, until a
round builds none (at most ``warmup_rounds_max`` rounds).  It starts the generator's steady load if
the kind has one, prints ``{"event": "ready"}`` and waits for
``{"t0": ...}`` on stdin (a ``time.monotonic`` instant, which every
process on the host shares).  It runs the window from ``t0`` for the
spec's seconds, prints ``{"event": "closed"}`` when the window closes,
waits for every answer that is due (a minute at most), and prints
``{"event": "done", ...}`` with one record per request and what the
correctness check needs.  Progress goes to stderr; stdout carries only
these lines.

    python -m benchkit.loadgen < spec.json
"""
from __future__ import annotations

import asyncio
import json
import resource
import sys
from pathlib import Path

from . import spec as spec_mod
from .wire import sleep_until


def emit(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def _cpu_s() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


async def read_line() -> dict:
    loop = asyncio.get_running_loop()
    return json.loads(await loop.run_in_executor(None, sys.stdin.readline))


async def warm_up(gen, mix: dict) -> int:
    """Rounds of the generator's warm-up until one builds no executable;
    returns the number of rounds."""
    for rounds in range(1, int(mix["warmup_rounds_max"]) + 1):
        await gen.warm_round(rounds - 1)
        emit({"event": "warm_round", "round": rounds})
        if (await read_line())["builds"] == 0:
            break
    return rounds


async def main_async() -> None:
    spec = await read_line()
    mix = spec["traffic"]
    kind = spec_mod.traffic_kind(Path(spec["root"]), mix["kind"])
    gen = kind.Load(spec)
    info = gen.prepare()
    addr = await read_line()
    info.update(await gen.setup(addr["host"], int(addr["port"])))
    info["warmup_rounds"] = await warm_up(gen, mix)
    if hasattr(gen, "start"):
        await gen.start()
    emit({"event": "ready", "lead_s": float(mix.get("lead_s", 0.0)),
          **info})
    t0 = float((await read_line())["t0"])
    t1 = t0 + float(spec["seconds"])

    cpu = {}

    async def announce() -> None:
        await sleep_until(t0)
        cpu["t0"] = _cpu_s()
        await sleep_until(t1)
        cpu["t1"] = _cpu_s()
        emit({"event": "closed", "t0": t0, "t1": t1})

    closer = asyncio.create_task(announce())
    await gen.window(t0, t1)
    await closer
    result = await gen.finish()
    emit({"event": "done", "records": gen.records,
          "cpu_window_s": cpu["t1"] - cpu["t0"],
          "jax_imported": "jax" in sys.modules, **result})


if __name__ == "__main__":
    asyncio.run(main_async())
