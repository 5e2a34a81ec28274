"""Multi-tenant serving front end, end to end (DESIGN.md Sec. 14).

Starts a :class:`repro.serve.ServeFrontend` on localhost, then drives it
the way a fleet of clients would -- over the real wire protocol:

1. several tenants compress uPMU-like traces concurrently, mixing
   *direct* streams (per-feed dispatch, byte-identical to a local
   ``IdealemSession``) and *coalesced* streams (staged host-side, cut as
   one padded device batch when the ``FlushPolicy`` trips);
2. one tenant sits behind a tight bytes/s quota and shows the typed 429
   ``Retry-After`` dance;
3. the compressed container is attached back and range-decoded through
   the batched decode mux;
4. finally ``/metrics`` is scraped and the p99s printed -- the numbers
   the control loop (``repro.serve.control``) steers on.

  PYTHONPATH=src python examples/serve_frontend.py --tenants 4
"""
import argparse
import asyncio

import numpy as np

from repro import api, obs
from repro.core import IdealemCodec
from repro.data import synthetic
from repro.errors import RateLimitedError
from repro.serve import (FlushPolicy, FrontendClient, ServeFrontend,
                         TenantQuota)
from repro.store import pack


async def compress_tenant(fe, i: int, samples: int) -> None:
    cfg = api.CodecConfig(mode="std", block_size=32, num_dict=127,
                          backend="numpy")
    x = synthetic.pmu_magnitude(samples, level=100 + 5 * i, noise=1.0,
                                seed=i)
    shadow = IdealemCodec.from_config(cfg).session()
    async with FrontendClient(fe.host, fe.port, f"tenant-{i}") as c:
        await c.open("pmu", cfg)
        wire, ref = [], []
        for lo in range(0, samples, 1024):
            wire.append((await c.feed("pmu", x[lo:lo + 1024])).segment)
            ref.append(shadow.feed(x[lo:lo + 1024]))
        wire.append((await c.close_stream("pmu")).segment)
        ref.append(shadow.finish())
        blob, local = b"".join(wire), b"".join(ref)
        print(f"  tenant-{i}: {samples * 8} B -> {len(blob)} B over the "
              f"wire ({samples * 8 / len(blob):.1f}x), byte-identical to "
              f"the local session: {blob == local}")

        # decode it back through the batched mux
        await c.attach("pmu-store", pack(blob))
        got = await c.decode("pmu-store", 0, 16)
        want = IdealemCodec.from_config(cfg).decode(blob)[:16 * 32]
        ok = np.allclose(np.asarray(got.values).ravel(), want)
        print(f"  tenant-{i}: range decode of 16 blocks round-trips: {ok}")


async def throttled_tenant(fe, samples: int) -> None:
    cfg = api.CodecConfig(mode="std", block_size=32, backend="numpy")
    x = synthetic.pmu_magnitude(samples, level=120.0, noise=0.5, seed=99)
    rejected = 0
    async with FrontendClient(fe.host, fe.port, "throttled") as c:
        await c.open("pmu", cfg)
        for lo in range(0, samples, 2048):
            while True:
                try:
                    await c.feed("pmu", x[lo:lo + 2048])
                    break
                except RateLimitedError as exc:
                    rejected += 1
                    await asyncio.sleep(exc.retry_after_s or 0.05)
        await c.close_stream("pmu")
    print(f"  throttled: finished after {rejected} typed 429s "
          "(each carried Retry-After)")


async def main(args) -> None:
    policy = FlushPolicy(max_batch_blocks=2048, max_batch_streams=32,
                         max_age_s=0.01)
    quotas = {"throttled": TenantQuota(max_bytes_per_s=200_000,
                                       burst_bytes=32_768)}
    async with ServeFrontend(policy=policy, quotas=quotas,
                             control_interval_s=0.05,
                             decode_backend="numpy") as fe:
        print(f"front end on {fe.host}:{fe.port}, "
              f"policy={policy.as_dict()}")
        await asyncio.gather(
            *(compress_tenant(fe, i, args.samples)
              for i in range(args.tenants)),
            throttled_tenant(fe, args.samples))

        async with FrontendClient(fe.host, fe.port, "probe") as c:
            parsed = obs.parse_prometheus(await c.metrics())
            ctl = await c.control()
    for route in ("POST /v1/feed", "POST /v1/decode"):
        p99 = obs.quantile_from_parsed(
            parsed, "repro_frontend_request_seconds", 0.99,
            {"route": route})
        if p99 is not None:
            print(f"p99 {route}: {p99 * 1e3:.2f} ms")
    print(f"control loop: {ctl['control']['ticks']} ticks, "
          f"policy now {ctl['policy']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--samples", type=int, default=32 * 512)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    asyncio.run(main(args))
