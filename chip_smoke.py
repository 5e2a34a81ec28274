#!/usr/bin/env python3
"""Bring-up smoke run: the served IDEALEM path on a TPU, end to end.

  python chip_smoke.py [--seed N]        # one chip: the served path
  python chip_smoke.py --chips 4         # four chips: multi-device encode

One chip.  Starts a ``ServeFrontend`` in this process and drives it over
the real wire with ``FrontendClient``, at the paper's Table I settings
(``repro.configs.idealem_paper``): a fleet of 64 simulated micro-PMUs x 12
channels = 768 streams, 8 substation tenants of 8 devices each.  Every
device reports 6 magnitude channels (``std``, B=32, D=255) and 6 phase
angles (``delta``, B=112, D=255, range [0, 360)) as float32 at 120 Hz;
each stream receives 4 windows of 7,200 samples (one minute each).  Odd
devices' streams are coalesced, even devices' are direct; every encode
runs on ``backend="pallas"`` with the fused matcher.  Each device's 12
streams are then packed into one container, attached, and range-decoded
through ``/v1/decode`` on ``decode_backend="pallas"``.

Checks: the encode lowers to a Mosaic kernel (``tpu_custom_call``), not the
interpreter; every direct stream's wire bytes equal a ``backend="numpy"``
session fed the same chunks, and every coalesced stream decodes to the
same samples; every decoded range equals the host decode bit for bit; and
``decode_stats()`` shows device calls and no fallback.

Four chips (``--chips 4``).  Only the multi-device encode of
``repro.launch.encode_plan`` at D=255 with n=32 and n=111: channel-sharded
sessions, a D-sharded session and a planned ``StreamCoalescer``, each
compared against the single-device encode in this process
(``repro.launch.shard_check.run_check``), with the number of devices each
carry really spans.

Earlier lines report progress and the checks; the figures they print are
bring-up evidence, not a benchmark.  The last line is one JSON object::

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero, printing no such line, when the default device
is not a TPU or any phase fails.  The compilation cache follows
``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import api  # noqa: E402
from repro.configs.idealem_paper import ANG_DELTA, MAG  # noqa: E402

FLEET_DEVICES = 64     # simulated micro-PMUs
TENANTS = 8            # substations, FLEET_DEVICES // TENANTS devices each
MAG_CHANNELS = 6       # V1-V3, I1-I3 magnitudes; then as many angles
WINDOWS = 4
WINDOW = 7200          # samples: one minute at 120 Hz
DECODES_PER_STORE = 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileLog:
    """Counts executables JAX builds (compiled, or read back from the
    persistent cache) and the seconds spent building them."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self) -> str:
        return (f"{self.count} executables built in {self.seconds:.2f} s "
                f"({self.cache_hits} read from the persistent cache)")


# ------------------------------------------------------------------ fleet
@dataclasses.dataclass
class Stream:
    tenant: str
    device: int            # micro-PMU index in the fleet
    channel: int           # 0..11 within the device
    config: api.CodecConfig
    coalesce: bool
    samples: np.ndarray    # float32, windows * window
    wire: list = dataclasses.field(default_factory=list)

    @property
    def sid(self) -> str:
        return f"pmu{self.device:03d}/ch{self.channel:02d}"

    @property
    def blob(self) -> bytes:
        return b"".join(self.wire)


def make_fleet(devices: int, tenants: int, windows: int, window: int,
               seed: int, mag=MAG, ang=ANG_DELTA):
    """The simulated fleet, made from ``seed``: per device, magnitude
    channels (three voltages around 7.2 kV, three currents around 300 A)
    and phase angles ramping at slightly different frequency offsets."""
    from repro.data import synthetic

    cfg_mag = api.CodecConfig(**mag, backend="pallas", matcher="fused")
    cfg_ang = api.CodecConfig(**ang, backend="pallas", matcher="fused")
    n = windows * window
    rng = np.random.default_rng(seed)
    per_tenant = -(-devices // tenants)
    streams = []
    for dev in range(devices):
        for ch in range(2 * MAG_CHANNELS):
            s = int(rng.integers(1 << 31))
            if ch < MAG_CHANNELS:
                volts = ch < 3
                x = synthetic.pmu_magnitude(
                    n, level=7200.0 if volts else 300.0,
                    noise=1.5 if volts else 0.2,
                    tap_step=45.0 if volts else 6.0, seed=s)
                cfg = cfg_mag
            else:
                x = synthetic.pmu_angle(
                    n, slope=0.72 + 0.01 * rng.standard_normal(), seed=s)
                cfg = cfg_ang
            streams.append(Stream(
                tenant=f"substation-{dev // per_tenant:02d}", device=dev,
                channel=ch, config=cfg, coalesce=dev % 2 == 1,
                samples=x.astype(np.float32)))
    return streams


# ------------------------------------------------------------ served path
async def drive_tenant(host: str, port: int, tenant: str, streams,
                       windows: int, window: int, seed: int) -> int:
    """One tenant's connection: open, feed every window, close, then pack
    each device's streams, attach and range-decode.  Returns the number
    of decoded ranges checked."""
    from repro.serve import FrontendClient
    from repro.store import Container, decode_range, pack

    checked = 0
    async with FrontendClient(host, port, tenant) as c:
        for st in streams:
            await c.open(st.sid, st.config, coalesce=st.coalesce,
                         dtype="float32")
        for w in range(windows):
            for st in streams:
                r = await c.feed(st.sid, st.samples[w * window:
                                                    (w + 1) * window])
                st.wire.append(r.segment)
        for st in streams:
            st.wire.append((await c.close_stream(st.sid)).segment)

        rng = np.random.default_rng(seed)
        by_device = {}
        for st in streams:
            by_device.setdefault(st.device, []).append(st)
        for dev, chans in sorted(by_device.items()):
            chans.sort(key=lambda st: st.channel)
            blob = pack([st.blob for st in chans])
            store_id = f"pmu{dev:03d}"
            await c.attach(store_id, blob)
            store = Container(blob)
            for k in range(DECODES_PER_STORE):
                ch = int(rng.integers(len(chans)))
                total = store.total_blocks(ch)
                start = int(rng.integers(total))
                stop = min(total, start + 1 + int(rng.integers(64)))
                got = np.asarray((await c.decode(
                    store_id, start, stop, channel=ch,
                    request_id=f"{store_id}-{k}")).values)
                want = decode_range(store, start, stop, channel=ch,
                                    backend="numpy")
                require(got.dtype == want.dtype
                        and got.tobytes() == want.tobytes(),
                        f"{store_id} ch{ch} [{start}, {stop}) decoded on "
                        f"the device equals the host decode")
                checked += 1
    return checked


async def serve(streams, windows: int, window: int, seed: int):
    from repro.serve import FlushPolicy, ServeFrontend, TenantQuota

    tenants = {}
    for st in streams:
        tenants.setdefault(st.tenant, []).append(st)
    quota = TenantQuota(max_streams=len(streams), max_stores=len(streams),
                        max_staged_blocks=1 << 20)
    policy = FlushPolicy(max_batch_blocks=4096, max_batch_streams=64,
                         max_age_s=0.05)
    async with ServeFrontend(policy=policy, default_quota=quota,
                             decode_backend="pallas",
                             max_staged_blocks_total=1 << 22) as fe:
        log(f"front end on {fe.host}:{fe.port}: {len(tenants)} tenants, "
            f"{len(streams)} streams")
        return sum(await asyncio.gather(*(
            drive_tenant(fe.host, fe.port, t, sts, windows, window,
                         seed + i)
            for i, (t, sts) in enumerate(sorted(tenants.items())))))


def check_streams(streams, window: int) -> None:
    """Direct streams: wire bytes == a numpy-backend session fed the same
    chunks.  Coalesced streams (framed by flush cohorts): the same decoded
    samples, bit for bit."""
    from repro.core import IdealemCodec
    from repro.core.stream import decode_stream

    for st in streams:
        ref = IdealemCodec.from_config(
            dataclasses.replace(st.config, backend="numpy")).session(
                dtype=np.float32)
        parts = [ref.feed(st.samples[lo:lo + window])
                 for lo in range(0, len(st.samples), window)]
        want = b"".join(parts) + ref.finish()
        if st.coalesce:
            require(decode_stream(st.blob).tobytes()
                    == decode_stream(want).tobytes(),
                    f"coalesced {st.sid} decodes like the numpy session")
        else:
            require(st.blob == want,
                    f"direct {st.sid} bytes equal the numpy session")


def lowered_kernels(cfgs, windows_blocks) -> None:
    """The session's encode scan, lowered for this device at the smoke's
    shapes, must hold the compiled Mosaic kernel: a Pallas call that fell
    back to the interpreter lowers to plain XLA ops instead."""
    import jax
    import jax.numpy as jnp

    from repro.core import IdealemCodec
    from repro.core.encoder import (encode_decisions_batched, init_state,
                                    resolve_matcher)

    for cfg, nb in zip(cfgs, windows_blocks):
        codec = IdealemCodec.from_config(cfg)
        n = cfg.block_size - (cfg.mode != "std")
        m = resolve_matcher(cfg.matcher, num_dict=cfg.num_dict, n=n,
                            dtype=jnp.float32)
        st = init_state(cfg.num_dict, n, dtype=jnp.float32, channels=1)

        def scan(s, b, m=m, codec=codec):
            return encode_decisions_batched(
                b, num_dict=codec.num_dict, state=s,
                d_crit=float(codec.d_crit), rel_tol=float(codec.rel_tol),
                matcher=m)

        text = jax.jit(scan).lower(
            st, jax.ShapeDtypeStruct((1, nb, n), jnp.float32)).as_text()
        require("tpu_custom_call" in text,
                f"{cfg.mode} n={n} encode lowers to a Mosaic kernel")
        log(f"encode {cfg.mode} B={cfg.block_size} D={cfg.num_dict}: "
            f"matcher resolved to {m!r}, lowered to tpu_custom_call")


def one_chip(args, compiles: CompileLog) -> None:
    import jax

    from repro.core import decode as decode_mod

    cfg_mag = api.CodecConfig(**MAG, backend="pallas", matcher="fused")
    cfg_ang = api.CodecConfig(**ANG_DELTA, backend="pallas",
                              matcher="fused")
    lowered_kernels([cfg_mag, cfg_ang],
                    [WINDOW // MAG["block_size"],
                     WINDOW // ANG_DELTA["block_size"]])

    t0 = time.perf_counter()
    streams = make_fleet(FLEET_DEVICES, TENANTS, WINDOWS, WINDOW, args.seed)
    log(f"fleet made in {time.perf_counter() - t0:.2f} s: {len(streams)} "
        f"streams x {WINDOWS} windows of {WINDOW} float32 samples")

    decode_mod.reset_decode_stats()
    built0 = compiles.count
    t0 = time.perf_counter()
    ranges = asyncio.run(serve(streams, WINDOWS, WINDOW, args.seed))
    wall = time.perf_counter() - t0
    samples = sum(len(st.samples) for st in streams)
    stats = decode_mod.decode_stats()
    log(f"served {samples} samples and {ranges} range decodes over the "
        f"wire in {wall:.2f} s, {compiles.count - built0} executables "
        f"built meanwhile")
    log(f"smoke figure, not a benchmark (compilation included): "
        f"{samples / wall:.0f} samples/s")
    log(f"decode backend 'pallas': decode_stats {stats}")
    require(stats["device_calls"] > 0, "decode ran on the device")
    require(stats["fallbacks"] == 0 and stats["host_routes"] == 0,
            "no decode was served from the host")

    t0 = time.perf_counter()
    check_streams(streams, WINDOW)
    direct = sum(not st.coalesce for st in streams)
    log(f"{direct} direct streams byte-identical and "
        f"{len(streams) - direct} coalesced streams decode-identical to "
        f"the numpy session ({time.perf_counter() - t0:.2f} s)")
    ratio = samples * 4 / sum(len(st.blob) for st in streams)
    log(f"compression ratio over the fleet (float32 input): {ratio:.2f}")
    peak = jax.devices()[0].memory_stats() or {}
    log(f"peak device bytes in use: {peak.get('peak_bytes_in_use')}")


def four_chips(args) -> None:
    from repro.launch.shard_check import run_check

    for cfg in (MAG, ANG_DELTA):
        B = cfg["block_size"]
        t0 = time.perf_counter()
        rec = run_check(backend="pallas", channels=8, samples=B * 300 + 7,
                        cases=[(cfg["mode"], cfg["num_dict"],
                                cfg.get("value_range"))],
                        block_size=B, alpha=cfg["alpha"])
        log(f"{cfg['mode']} B={B} D={cfg['num_dict']} n="
            f"{B - (cfg['mode'] != 'std')}: {rec['status']} in "
            f"{time.perf_counter() - t0:.2f} s; devices spanned "
            f"{rec.get('spans')}")
        require(rec["status"] == "ok",
                f"multi-device encode matches the single-device encode: "
                f"{rec}")
        require(all(v == args.chips for v in rec["spans"].values()),
                f"every carry spans {args.chips} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the simulated fleet's samples")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device encode phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] the default device is {dev.platform!r}, not "
              f"a TPU: nothing to bring up", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device {dev.device_kind} x {len(devices)}; compilation cache "
        f"{cache}")
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args, compiles)
    log(f"compilation: {compiles}")
    log(f"total {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
