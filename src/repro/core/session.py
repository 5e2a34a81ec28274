"""Streaming codec sessions (DESIGN.md Sec. 3).

``IdealemCodec.encode`` is one-shot: dictionary built from scratch per call.
For the paper's deployment scenario -- online compression of continuous
sensor/PMU streams (Sec. I, Fig. 15) -- that destroys the hit rate the FIFO
dictionary exists to provide whenever data arrives in chunks.

``IdealemSession`` owns the persistent encoder state between chunks:

  * per-channel device ``DictState`` (or numpy ``NpDictState``), threaded
    through the resumable ``encode_decisions`` scan so chunked encoding makes
    exactly the same hit/miss decisions as one monolithic pass;
  * per-channel host tail buffers holding samples that do not yet fill a
    block;
  * segment emission: ``feed(chunk) -> bytes`` returns an append-mode stream
    segment (FLAG_MORE/FLAG_CONT framing, see repro.core.stream) and
    ``finish() -> bytes`` the final segment carrying the tail.  The
    concatenation of all returned segments decodes identically to what
    one-shot ``IdealemCodec.encode`` over the concatenated samples decodes
    to.

With ``emit_segments=False`` the session buffers host-side and ``finish``
assembles one classic single-segment stream -- byte-identical to the seed
one-shot format; ``IdealemCodec.encode`` is a thin wrapper over this mode.

Multi-channel: ``channels=C`` batches C independent streams through one
vmapped device scan (blocks stacked ``(C, nb, n)``, per-channel carry);
``feed`` then takes ``(C, m)`` chunks and returns one segment per channel.

Performance note (jax/pallas backends): the device scan compiles per
distinct per-feed block count, so live producers should feed fixed chunk
quanta (ideally a multiple of ``block_size``) to hit steady-state
throughput after the first chunk.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, List, NamedTuple, Optional, Union

import numpy as np

from . import stream as stream_mod
from .. import obs
from .stream import StreamHeader

# Session-level registry counters (ISSUE 8): process-wide aggregates over
# every session/channel; per-channel detail stays on ``SessionStats``.
# The per-(block, slot) gate attribution lives in ``npref`` (host walk).
_M = {
    key: obs.registry().counter(f"repro_encode_{key}_total", help_text)
    for key, help_text in {
        "bytes_in": "raw sample bytes accepted by sessions",
        "bytes_out": "emitted segment bytes (compressed size)",
        "segments": "stream segments emitted",
        "blocks": "blocks encoded",
        "hits": "blocks replaced by a dictionary reference",
        "mode_switches": "adaptive selector mode/scale switches applied",
    }.items()
}

# Adaptive dispatch accounting (ISSUE 9): the batched mixed scan issues one
# device dispatch per feed regardless of channel count; the fallback loop
# issues one per channel.  Tests pin the per-feed dispatch contract on
# these counters, and the cohort histogram records how many channels each
# adaptive dispatch covered.
_M_DISPATCH = {
    path: obs.registry().counter(
        "repro_encode_dispatches_total",
        "device encode-scan dispatches by path",
        labels={"path": path})
    for path in ("direct", "adaptive_batched", "adaptive_loop")
}
# The direct path's trace count, ``repro_encode_scan_traces_total``, is
# registered beside the jitted scan it counts (``core.encoder``): it stays
# flat while ``direct`` dispatches grow once every feed shape is compiled.
# Host phases of a session feed, each observed by the span of the same
# section: prepare (cut and transform), dispatch (the enqueue of the direct
# scan's cached executable), sync (its device_get), commit (stream assembly
# and framing).
_M_PHASE = {
    phase: obs.registry().histogram(
        "repro_encode_phase_seconds", "session feed host phases",
        labels={"phase": phase})
    for phase in ("prepare", "dispatch", "sync", "commit")
}
# Error-bounded direct scans: blocks the bound demoted -- some valid entry
# passed the min/max gate and the bound excluded every such entry -- counted
# on the device by the scan and fetched with its decisions.
_M_DEMOTIONS = obs.registry().counter(
    "repro_encode_bound_demotions_total",
    "blocks the error bound demoted (an entry passed min/max, none the bound)",
    labels={"path": "direct"})
_M_COHORT = obs.registry().histogram(
    "repro_encode_adaptive_cohort",
    "channels covered per adaptive encode dispatch (cohort size)",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0,
             1024.0))

# force the per-channel fallback loop (bench/debug hook; ignored for
# plan-sharded sessions, which require the batched mixed scan)
_ADAPTIVE_LOOP_ENV = "REPRO_ADAPTIVE_LOOP"

if TYPE_CHECKING:  # pragma: no cover
    from .idealem import IdealemCodec

__all__ = ["IdealemSession", "MixedCohort", "PreparedChunk", "SessionStats"]


def _mixed_matcher_name(codec):
    """The batched mixed scan's matcher for a codec config, or ``None``
    when only the per-channel loop can honor it (``"ops"``/``"auto"``/
    custom callables have no masked variant)."""
    m = getattr(codec, "matcher", None)
    if codec.backend == "pallas":
        m = m or "fused"
    if m is None or m == "reference":
        return "reference"
    if m == "fused" or (isinstance(m, tuple) and len(m) == 2
                        and m[0] == "fused"):
        return m
    from .encoder import matcher_reference
    if m is matcher_reference:
        return "reference"
    return None


class MixedCohort:
    """Shared batched carry + dispatcher for heterogeneous (mixed-mode)
    channels (DESIGN.md Sec. 13).

    Owns one ``(capacity, D, n_max)`` ``DictState`` whose lanes stay
    logically per-channel: payload widths are padded to the max across
    live lanes with ``+inf`` (``repad_state_n`` follows the max as lanes
    come and go), tail columns are masked per lane inside the scan, and a
    selector switch resets a lane in place (:meth:`reset_lane`) instead of
    rebuilding the batch.  :meth:`decide` assembles the padded cohort and
    issues ONE device dispatch + ONE host sync per feed/flush no matter
    how many lanes diverge in mode, width, threshold or error metric.
    """

    def __init__(self, num_dict: int, capacity: int, *, rel_tol: float,
                 use_minmax: bool = True, use_ks: bool = True,
                 error_bound: Optional[float] = None, matcher=None,
                 plan=None):
        if plan is not None and capacity != plan.padded_channels:
            raise ValueError(
                f"cohort capacity {capacity} != plan padded_channels "
                f"{plan.padded_channels}")
        self.num_dict = int(num_dict)
        self.capacity = int(capacity)
        self.rel_tol = float(rel_tol)
        self.use_minmax = use_minmax
        self.use_ks = use_ks
        self.error_bound = None if error_bound is None else float(error_bound)
        self.matcher = matcher
        self.plan = plan
        self.state = None  # batched DictState, width padded to _n_max
        self._n_max = 0
        self.lane_n = np.zeros(self.capacity, dtype=np.int64)
        self.dispatches = 0

    def reset_lane(self, lane: int) -> None:
        """Drop one lane's dictionary in place (selector switch, stream
        close): its rows turn ``valid=False`` and its FIFO count rewinds;
        every other lane's carry is untouched."""
        self.lane_n[lane] = 0
        if self.state is not None:
            st = self.state
            self.state = st._replace(valid=st.valid.at[lane].set(False),
                                     count=st.count.at[lane].set(0))

    def grow(self, capacity: int) -> None:
        """Extend the lane axis (coalescer capacity growth); new lanes
        start empty."""
        import jax.numpy as jnp

        add = int(capacity) - self.capacity
        if add <= 0:
            return
        if self.plan is not None:
            raise ValueError("plan-pinned cohorts cannot grow")
        self.lane_n = np.concatenate(
            [self.lane_n, np.zeros(add, dtype=np.int64)])
        if self.state is not None:
            st = self.state
            self.state = st._replace(**{
                f: jnp.pad(getattr(st, f),
                           [(0, add)] + [(0, 0)] * (getattr(st, f).ndim - 1))
                for f in st._fields})
        self.capacity = int(capacity)

    def decide(self, entries, *, nb_pad: Optional[int] = None):
        """One batched mixed-mode dispatch over ``entries``: a list of
        ``(lane, payload (nb_i, n_i), d_crit, err_cum, eb_on)`` tuples.
        Payload widths are padded to the cohort max with +inf and block
        counts to ``nb_pad`` (default: the max over entries) via the valid
        mask.  Returns ``{lane: (is_hit, slot, overwrite)}`` sliced back
        to each entry's real block count, after the single host sync."""
        import jax
        import jax.numpy as jnp
        from .encoder import (encode_decisions_mixed,
                              encode_decisions_mixed_sharded, init_state,
                              repad_state_n)

        for lane, p, *_ in entries:
            self.lane_n[lane] = p.shape[-1]
        n_max = int(self.lane_n.max())
        nb = max(p.shape[0] for _, p, *_ in entries)
        if nb_pad is not None:
            nb = max(nb, int(nb_pad))
        batch = np.full((self.capacity, nb, n_max), np.inf, dtype=np.float32)
        valid = np.zeros((self.capacity, nb), dtype=bool)
        d_crit = np.ones(self.capacity, dtype=np.float32)
        err_cum = np.zeros(self.capacity, dtype=bool)
        eb_on = np.zeros(self.capacity, dtype=bool)
        for lane, p, dc, ec, ebo in entries:
            nb_i, n_i = p.shape
            batch[lane, :nb_i, :n_i] = p
            valid[lane, :nb_i] = True
            d_crit[lane] = dc
            err_cum[lane] = ec
            eb_on[lane] = ebo
        eb = self.error_bound
        if self.state is None:
            st = init_state(self.num_dict, n_max, dtype=jnp.float32,
                            channels=self.capacity, raw=eb is not None)
        elif n_max != self._n_max:
            st = repad_state_n(self.state, n_max)
        else:
            st = self.state
        if st is not self.state and self.plan is not None:
            st = jax.device_put(st, self.plan.state_sharding())
        self._n_max = n_max
        kw = dict(num_dict=self.num_dict, n_valid=np.maximum(self.lane_n, 1),
                  d_crit=d_crit, rel_tol=self.rel_tol,
                  use_minmax=self.use_minmax, use_ks=self.use_ks,
                  error_bound=eb, error_cumulative=err_cum, eb_on=eb_on,
                  matcher=self.matcher, state=st, valid=jnp.asarray(valid))
        pj = jnp.asarray(batch)
        if self.plan is not None:
            (h, s, o), self.state = encode_decisions_mixed_sharded(
                pj, mesh=self.plan.mesh, axis_name=self.plan.axis_name, **kw)
        else:
            (h, s, o), self.state = encode_decisions_mixed(pj, **kw)
        self.dispatches += 1
        _M_DISPATCH["adaptive_batched"].inc()
        _M_COHORT.observe(float(len(entries)))
        h, s, o = jax.device_get((h, s, o))  # the one host sync per feed
        return {lane: (np.asarray(h[lane, :p.shape[0]]),
                       np.asarray(s[lane, :p.shape[0]]),
                       np.asarray(o[lane, :p.shape[0]]))
                for lane, p, *_ in entries}


class PreparedChunk(NamedTuple):
    """Host-side staging of one feed: complete blocks cut from the chunk
    (tails already re-buffered) with their transforms applied.

    ``feed`` prepares and decides in one call; the serve-layer coalescer
    prepares many sessions, batches their payloads into one padded device
    call, then ``commit``s each session's decisions back.
    """

    blocks: np.ndarray            # (C, nb, B) raw values
    payloads: np.ndarray          # (C, nb, n_lem) transformed
    bases: List[Optional[np.ndarray]]  # per channel, (nb,) or None (std)
    nb: int


@dataclass
class SessionStats:
    """Per-channel accounting of a streaming session."""

    blocks: int = 0
    hits: int = 0
    segments: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    # adaptive sessions: accepted selector switches (core.select), as dicts
    mode_switches: int = 0
    events: List[dict] = field(default_factory=list)

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.blocks, 1)

    def as_dict(self) -> dict:
        return {
            "blocks": self.blocks, "hits": self.hits,
            "hit_rate": self.hit_rate, "segments": self.segments,
            "bytes_in": self.bytes_in, "bytes_out": self.bytes_out,
            "ratio": self.bytes_in / max(self.bytes_out, 1),
            "mode_switches": self.mode_switches,
            "events": list(self.events),
        }


class IdealemSession:
    """Resumable encode session over one codec configuration.

    >>> codec = IdealemCodec(mode="std", block_size=32, num_dict=255)
    >>> s = codec.session()
    >>> parts = [s.feed(chunk) for chunk in chunks] + [s.finish()]
    >>> y = codec.decode(b"".join(parts))   # == decode of one-shot encode
    """

    def __init__(self, codec: "IdealemCodec", channels: Optional[int] = None,
                 emit_segments: bool = True, dtype=np.float64, plan=None,
                 container: bool = False):
        self.codec = codec
        self.channels = channels
        self.emit_segments = emit_segments
        self._writer = None
        if container:
            # every emitted segment is also appended to an in-memory
            # indexed container (repro.store); finish() then returns the
            # random-access packed form instead of the final segment.
            from repro.store.container import ContainerWriter
            self._writer = ContainerWriter()
        self.dtype = np.dtype(dtype)
        C = self._C = channels if channels is not None else 1
        if channels is not None and channels < 1:
            raise ValueError("channels must be >= 1")
        if plan is not None:
            if codec.backend == "numpy":
                raise ValueError("encode plans need a device backend")
            if plan.channels != C:
                raise ValueError(
                    f"plan is for {plan.channels} channels, session has {C}")
        self.plan = plan  # launch.encode_plan.EncodePlan (duck-typed)
        self._tails = [np.zeros(0, dtype=self.dtype) for _ in range(C)]
        self._started = [False] * C  # any segment emitted yet (per channel)
        self._finished = False
        self._stats = [SessionStats() for _ in range(C)]
        self._dev_state = None   # batched DictState (jax / pallas backends)
        self._np_states = None   # list[NpDictState] (numpy backend)
        # adaptive per-channel mode selection (core.select): each channel
        # carries its own current codec variant + quantized d_crit; a switch
        # resets the channel dictionary and restarts its segment chain.
        self.adaptive = bool(getattr(codec, "adaptive", False))
        self._codecs = [codec] * C
        self._d_crit = [float(codec.d_crit)] * C
        self._selectors = None
        self._adapt_states = None  # per-channel DictState list (device)
        if self.adaptive:
            if not emit_segments:
                raise ValueError(
                    "adaptive sessions require emit_segments=True (mode "
                    "switches live at segment restarts)")
            if container:
                raise ValueError(
                    "adaptive sessions do not support container output")
            if plan is not None:
                # the batched mixed scan shards the channel axis only: one
                # lane per channel, widths padded/masked per lane.
                if getattr(plan, "dict_shards", 1) > 1:
                    raise ValueError(
                        "adaptive sessions shard channels only; build the "
                        "plan with dict_shards=1")
                if _mixed_matcher_name(codec) is None:
                    raise ValueError(
                        "adaptive sessions with an encode plan need the "
                        "reference or fused matcher (the batched mixed scan "
                        f"has no masked variant of "
                        f"{getattr(codec, 'matcher', None)!r})")
            from .select import ChannelSelector
            self._selectors = [
                ChannelSelector(codec.block_size, mode=codec.mode,
                                config=getattr(codec, "selector", None))
                for _ in range(C)]
            self._adapt_states = [None] * C
        self._mixed = None           # MixedCohort (device adaptive batch)
        self._mixed_disabled = False  # matcher has no masked variant
        # host-side accumulation for emit_segments=False (one-shot assembly)
        self._buf = [
            {"raw": [], "payload": [], "bases": [], "hit": [], "slot": [],
             "ovw": []}
            for _ in range(C)
        ]

    # ------------------------------------------------------------- internals
    def _decide(self, payload_cn: np.ndarray):
        """(C, nb, n_lem) transformed blocks -> per-channel decision triples,
        threading the persistent dictionary carry."""
        cdc = self.codec
        kw = dict(
            num_dict=cdc.num_dict,
            d_crit=float(cdc.d_crit),
            rel_tol=float(cdc.rel_tol),
            use_minmax=cdc.use_minmax,
            use_ks=cdc.use_ks,
        )
        eb = getattr(cdc, "error_bound", None)
        if eb is not None:
            kw["error_bound"] = float(eb)
            kw["error_cumulative"] = cdc.mode == "delta"
        if cdc.backend == "numpy":
            from .npref import encode_decisions_np, np_init_state
            if self._np_states is None:
                self._np_states = [np_init_state(cdc.num_dict)
                                   for _ in range(self._C)]
            return [
                encode_decisions_np(payload_cn[ci],
                                    state=self._np_states[ci], **kw)[0]
                for ci in range(self._C)
            ]
        import jax
        with obs.span("scan.dispatch", histogram=_M_PHASE["dispatch"]):
            out = self._dispatch_scan(payload_cn, kw)
        _M_DISPATCH["direct"].inc()
        with obs.span("scan.sync", histogram=_M_PHASE["sync"]):
            h, s, o, *dem = jax.device_get(out)
        if dem:
            _M_DEMOTIONS.inc(int(dem[0][:self._C].sum()))
        return [(h[ci], s[ci], o[ci]) for ci in range(self._C)]

    def _dispatch_scan(self, payload_cn: np.ndarray, kw: dict):
        """Enqueue the direct session's device scan; returns its
        (unsynced) decision arrays, and with an error bound on the
        unplanned path the per-lane demotion count after them, and
        advances the carry."""
        import jax
        import jax.numpy as jnp
        from .encoder import (encode_decisions_batched,
                              encode_decisions_dsharded,
                              encode_decisions_sharded, init_state)
        cdc = self.codec
        eb = kw.get("error_bound")
        matcher = getattr(cdc, "matcher", None)
        if cdc.backend == "pallas":
            # default to the fused single-dispatch kernel (bitwise-identical
            # decisions to the composed ops matcher); an explicit codec
            # matcher ("ops", "auto", ...) overrides
            kw["matcher"] = matcher or "fused"
        elif matcher:
            kw["matcher"] = matcher
        if self.plan is not None:
            # scale-out path: channel axis sharded over the plan's mesh;
            # pad rows are masked out of the scan; _decide slices them off.
            plan = self.plan
            Cp = plan.padded_channels
            pad = Cp - self._C
            if pad:
                payload_cn = np.pad(
                    payload_cn, [(0, pad), (0, 0), (0, 0)])
            pj = jnp.asarray(payload_cn, dtype=jnp.float32)
            valid = np.ones(pj.shape[:2], dtype=bool)
            valid[self._C:] = False
            if self._dev_state is None:
                st = init_state(cdc.num_dict, pj.shape[-1],
                                dtype=jnp.float32, channels=Cp,
                                raw=eb is not None)
                self._dev_state = jax.device_put(st, plan.state_sharding())
            if getattr(plan, "dict_shards", 1) > 1:
                (h, s, o), self._dev_state = encode_decisions_dsharded(
                    pj, mesh=plan.mesh, ch_axis=plan.axis_name,
                    dict_axis=plan.dict_axis, state=self._dev_state,
                    valid=jnp.asarray(valid), **kw)
            else:
                (h, s, o), self._dev_state = encode_decisions_sharded(
                    pj, mesh=plan.mesh, axis_name=plan.axis_name,
                    state=self._dev_state, valid=jnp.asarray(valid), **kw)
        else:
            pj = jnp.asarray(payload_cn, dtype=jnp.float32)
            if self._dev_state is None:
                self._dev_state = init_state(
                    cdc.num_dict, pj.shape[-1], dtype=jnp.float32,
                    channels=self._C, raw=eb is not None)
            # the carry is donated to the scan: the old state is consumed
            (h, s, o), self._dev_state, *dem = encode_decisions_batched(
                pj, state=self._dev_state, demotions=eb is not None, **kw)
            return (h, s, o, *dem)
        return h, s, o

    # ------------------------------------------------- adaptive mode selection
    def _channel_kw(self, ci: int) -> dict:
        """Per-channel encode kwargs under the channel's current codec
        variant (adaptive sessions only)."""
        cdc0 = self.codec
        cdc = self._codecs[ci]
        kw = dict(
            num_dict=cdc0.num_dict,
            d_crit=float(self._d_crit[ci]),
            rel_tol=float(cdc0.rel_tol),
            use_minmax=cdc0.use_minmax,
            use_ks=cdc0.use_ks,
        )
        eb = getattr(cdc, "error_bound", None)
        if eb is not None:
            kw["error_bound"] = float(eb)
            kw["error_cumulative"] = cdc.mode == "delta"
        return kw

    def _decide_adaptive(self, payloads):
        """Per-channel decisions under per-channel codec variants: one
        batched masked scan when the matcher has a mixed variant (one
        device dispatch + one host sync per feed, DESIGN.md Sec. 13),
        else the per-channel loop with a single deferred sync."""
        cdc0 = self.codec
        if cdc0.backend == "numpy":
            from .npref import encode_decisions_np, np_init_state
            if self._np_states is None:
                self._np_states = [np_init_state(cdc0.num_dict)
                                   for _ in range(self._C)]
            return [
                encode_decisions_np(payloads[ci],
                                    state=self._np_states[ci],
                                    **self._channel_kw(ci))[0]
                for ci in range(self._C)
            ]
        if self._mixed is None and not self._mixed_disabled:
            force_loop = (os.environ.get(_ADAPTIVE_LOOP_ENV)
                          and self.plan is None)
            m = None if force_loop else _mixed_matcher_name(cdc0)
            if m is None:
                self._mixed_disabled = True
            else:
                eb = getattr(cdc0, "error_bound", None)
                self._mixed = MixedCohort(
                    cdc0.num_dict,
                    (self.plan.padded_channels if self.plan is not None
                     else self._C),
                    rel_tol=float(cdc0.rel_tol),
                    use_minmax=cdc0.use_minmax, use_ks=cdc0.use_ks,
                    error_bound=None if eb is None else float(eb),
                    matcher=m, plan=self.plan)
        if self._mixed is not None:
            entries = []
            for ci in range(self._C):
                cdc = self._codecs[ci]
                entries.append((ci, np.asarray(payloads[ci]),
                                float(self._d_crit[ci]),
                                cdc.mode == "delta",
                                getattr(cdc, "error_bound", None) is not None))
            dec = self._mixed.decide(entries)
            return [dec[ci] for ci in range(self._C)]
        return self._decide_adaptive_loop(payloads)

    def _decide_adaptive_loop(self, payloads):
        """Per-channel fallback for matchers without a masked variant
        ("ops"/"auto"/callables): one dispatch per channel, but all
        dispatches issue before the single ``block_until_ready`` barrier
        so device work overlaps across channels."""
        import jax
        import jax.numpy as jnp
        from .encoder import encode_decisions, init_state
        cdc0 = self.codec
        outs = []
        for ci in range(self._C):
            kw = self._channel_kw(ci)
            matcher = getattr(cdc0, "matcher", None)
            if cdc0.backend == "pallas":
                kw["matcher"] = matcher or "fused"
            elif matcher:
                kw["matcher"] = matcher
            pj = jnp.asarray(payloads[ci], dtype=jnp.float32)
            if self._adapt_states[ci] is None:
                self._adapt_states[ci] = init_state(
                    cdc0.num_dict, pj.shape[-1], dtype=jnp.float32,
                    raw="error_bound" in kw)
            out, self._adapt_states[ci] = encode_decisions(
                pj, state=self._adapt_states[ci], **kw)
            _M_DISPATCH["adaptive_loop"].inc()
            outs.append(out)
        jax.block_until_ready(outs)
        _M_COHORT.observe(float(self._C))
        return [tuple(np.asarray(v) for v in out) for out in outs]

    def _apply_switch(self, ci: int, ev) -> None:
        """Commit an accepted selector switch: swap the channel's codec
        variant, quantize its threshold, drop its dictionary and restart its
        segment chain (the next segment is cont=False, so decoders treat it
        as a fresh section)."""
        import dataclasses
        cdc = self.codec if ev.new_mode == self.codec.mode \
            else dataclasses.replace(self.codec, mode=ev.new_mode)
        self._codecs[ci] = cdc
        self._d_crit[ci] = float(cdc.d_crit) * float(ev.new_scale)
        self._started[ci] = False
        if self._np_states is not None:
            from .npref import np_init_state
            self._np_states[ci] = np_init_state(self.codec.num_dict)
        if self._adapt_states is not None:
            self._adapt_states[ci] = None
        if self._mixed is not None:
            self._mixed.reset_lane(ci)
        st = self._stats[ci]
        st.mode_switches += 1
        st.events.append(ev.as_dict())
        _M["mode_switches"].inc()
        # the selector's decision, as a structured trace event: channel +
        # the full SelectionEvent payload (rho1, var ratio, drift, scales)
        obs.event("encode.mode_switch", attrs={"channel": ci,
                                               **ev.as_dict()})

    def _feed_adaptive(self, chunk):
        if self._finished:
            raise RuntimeError("session already finished")
        arr = np.asarray(chunk)
        arr2 = arr[None, :] if self.channels is None else arr
        if arr2.ndim != 2 or arr2.shape[0] != self._C:
            raise ValueError(
                f"expected {'1-D' if self.channels is None else f'(C={self._C}, m)'}"
                f" chunk, got {arr.shape}")
        # switches apply at the feed boundary, from statistics through the
        # *previous* feeds -- a segment never changes transform mid-flight
        for ci in range(self._C):
            ev = self._selectors[ci].decide(self._stats[ci].blocks)
            if ev is not None:
                self._apply_switch(ci, ev)
        for ci in range(self._C):
            self._selectors[ci].observe(arr2[ci])
        prep = self.prepare(chunk)
        if prep is None:
            empty = [b""] * self._C
            return empty[0] if self.channels is None else empty
        outs = self.commit(prep, self._decide_adaptive(prep.payloads))
        return outs[0] if self.channels is None else outs

    def _make_header(self, ci: int, nb: int, tail: np.ndarray,
                     more: bool) -> StreamHeader:
        cdc = self._codecs[ci]
        return StreamHeader(
            mode=cdc.mode_id,
            block_size=cdc.block_size,
            num_dict=cdc.num_dict,
            max_count=cdc.max_count,
            dtype=self.dtype,
            value_range=cdc.value_range,
            n_blocks=nb,
            tail=tail,
            more=more,
            cont=self._started[ci],
            error_bounded=getattr(cdc, "error_bound", None) is not None,
        )

    def _emit(self, ci, raw, payload, bases, hit, slot, ovw, tail, more):
        header = self._make_header(ci, len(raw), tail, more)
        seg = stream_mod.assemble_stream(header, raw, payload, bases,
                                         hit, slot, ovw)
        self._started[ci] = True
        st = self._stats[ci]
        st.bytes_out += len(seg)
        st.segments += 1
        _M["bytes_out"].inc(len(seg))
        _M["segments"].inc()
        if self._writer is not None:
            self._writer.append(seg, channel=ci)
        return seg

    def _empty(self, ci: int):
        cdc = self._codecs[ci]
        B = cdc.block_size
        n_lem = cdc._lem_n()
        raw = np.zeros((0, B), dtype=self.dtype)
        payload = np.zeros((0, n_lem), dtype=self.dtype)
        bases = None if cdc.mode == "std" else np.zeros(0, self.dtype)
        z = np.zeros(0, dtype=np.int32)
        return raw, payload, bases, z.astype(bool), z, z.astype(bool)

    # ------------------------------------------------------------ public API
    def prepare(self, chunk) -> Optional[PreparedChunk]:
        """Stage a chunk host-side: buffer the sample tails, cut complete
        blocks and apply the codec transform.  Returns ``None`` when no
        full block completed.  ``feed`` is ``prepare`` + ``_decide`` +
        ``commit``; the serve-layer coalescer calls prepare/commit around
        one shared batched decide."""
        with obs.span("session.prepare", histogram=_M_PHASE["prepare"]):
            return self._prepare(chunk)

    def _prepare(self, chunk) -> Optional[PreparedChunk]:
        if self._finished:
            raise RuntimeError("session already finished")
        arr = np.asarray(chunk)
        if self.channels is None:
            if arr.ndim != 1:
                raise ValueError("single-channel session feeds 1-D chunks")
            arr = arr[None, :]
        elif arr.ndim != 2 or arr.shape[0] != self._C:
            raise ValueError(f"expected (C={self._C}, m) chunk, got {arr.shape}")
        if arr.dtype != self.dtype:
            arr = arr.astype(self.dtype)

        B = self.codec.block_size
        joined = [np.concatenate([self._tails[ci], arr[ci]])
                  for ci in range(self._C)]
        nb = len(joined[0]) // B
        self._tails = [j[nb * B:] for j in joined]
        for ci in range(self._C):
            self._stats[ci].bytes_in += arr[ci].nbytes
        _M["bytes_in"].inc(arr.nbytes)
        if nb == 0:
            return None

        blocks = np.stack([j[: nb * B].reshape(nb, B) for j in joined])
        payloads, bases = [], []
        for ci in range(self._C):
            p, b = self._codecs[ci]._transform(blocks[ci])
            payloads.append(p)
            bases.append(b)
        # adaptive channels may carry different payload widths (std vs
        # delta/residual), so they stay a ragged list; the static path keeps
        # the stacked array the batched device scan consumes
        stacked = payloads if self.adaptive else np.stack(payloads)
        return PreparedChunk(blocks, stacked, bases, nb)

    def commit(self, prep: PreparedChunk, decisions) -> List[bytes]:
        """Apply per-channel decision triples for a prepared chunk: update
        stats and emit (or buffer) each channel's segment.  Always returns
        a per-channel list; decisions may cover only ``prep.nb`` blocks."""
        with obs.span("session.commit", histogram=_M_PHASE["commit"]):
            return self._commit(prep, decisions)

    def _commit(self, prep: PreparedChunk, decisions) -> List[bytes]:
        outs = []
        total_hits = 0
        for ci in range(self._C):
            hit, slot, ovw = decisions[ci]
            st = self._stats[ci]
            st.blocks += prep.nb
            n_hits = int(np.sum(hit))
            st.hits += n_hits
            total_hits += n_hits
            if self.emit_segments:
                outs.append(self._emit(
                    ci, prep.blocks[ci], prep.payloads[ci], prep.bases[ci],
                    hit, slot, ovw, tail=np.zeros(0, dtype=self.dtype),
                    more=True))
            else:
                buf = self._buf[ci]
                buf["raw"].append(prep.blocks[ci])
                buf["payload"].append(prep.payloads[ci])
                if prep.bases[ci] is not None:
                    buf["bases"].append(prep.bases[ci])
                buf["hit"].append(hit)
                buf["slot"].append(slot)
                buf["ovw"].append(ovw)
                outs.append(b"")
        _M["blocks"].inc(prep.nb * self._C)
        _M["hits"].inc(total_hits)
        return outs

    def feed(self, chunk) -> Union[bytes, List[bytes]]:
        """Compress the next chunk; returns the emitted segment bytes (one
        ``bytes`` for single-channel sessions, a list for ``channels=C``).
        Samples not filling a block are buffered for the next feed/finish;
        an empty ``bytes`` means no full block completed yet."""
        if self.adaptive:
            return self._feed_adaptive(chunk)
        prep = self.prepare(chunk)
        if prep is None:
            empty = [b""] * self._C
            return empty[0] if self.channels is None else empty
        outs = self.commit(prep, self._decide(prep.payloads))
        return outs[0] if self.channels is None else outs

    def finish(self) -> Union[bytes, List[bytes]]:
        """Close the stream(s): emit the final segment carrying the sample
        tail (segment mode) or assemble the whole classic one-segment stream
        (``emit_segments=False``).

        With ``container=True`` the return value is instead ONE packed
        random-access container (``repro.store``) holding every segment of
        every channel -- ready for ``decode_range`` on the serving read
        path; the final per-channel segments are still emitted through the
        writer like any other."""
        if self._finished:
            raise RuntimeError("session already finished")
        self._finished = True
        outs = []
        for ci in range(self._C):
            if self.emit_segments:
                raw, payload, bases, hit, slot, ovw = self._empty(ci)
                outs.append(self._emit(ci, raw, payload, bases, hit, slot,
                                       ovw, tail=self._tails[ci], more=False))
            else:
                buf = self._buf[ci]
                if buf["raw"]:
                    raw = np.concatenate(buf["raw"])
                    payload = np.concatenate(buf["payload"])
                    bases = (np.concatenate(buf["bases"])
                             if buf["bases"] else None)
                    hit = np.concatenate(buf["hit"])
                    slot = np.concatenate(buf["slot"])
                    ovw = np.concatenate(buf["ovw"])
                else:
                    raw, payload, bases, hit, slot, ovw = self._empty(ci)
                outs.append(self._emit(ci, raw, payload, bases, hit, slot,
                                       ovw, tail=self._tails[ci], more=False))
        if self._writer is not None:
            return self._writer.finalize()
        return outs[0] if self.channels is None else outs

    @property
    def stats(self) -> Union[SessionStats, List[SessionStats]]:
        return self._stats[0] if self.channels is None else list(self._stats)
