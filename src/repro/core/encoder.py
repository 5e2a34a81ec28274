"""The IDEALEM encoder as a jit-compiled ``lax.scan`` (DESIGN.md Sec. 2).

The reference C encoder walks the dictionary and early-exits at the first
KS pass.  On TPU we compute the min/max gate (eq. 3) and the KS distance
against *all* D entries as dense masked work and select the lowest-index
passing entry -- decision-identical to the early-exit scan, but fully
vectorized (VPU) and batchable over channels with ``vmap``.

Streaming (DESIGN.md Sec. 3): ``DictState`` is a first-class resumable
carry.  ``encode_decisions(..., state=s)`` continues a scan where the last
chunk stopped and returns the updated state, so a live stream encoded in
chunks makes exactly the same hit/miss decisions as one monolithic scan.
On accelerators the incoming state buffers are donated to the jitted scan,
so resuming does not hold two copies of the dictionary in device memory.

Per-block outputs are fixed-shape decisions (is_hit, slot, overwrite); the
variable-length byte stream is assembled host-side by ``repro.core.stream``
from these decisions plus the raw blocks.

Matchers fuse the two similarity checks: ``matcher(xs_sorted, dict_sorted,
dmin, dmax, rel_tol) -> (ks (D,), mm (D,))``.  The default is the pure-jnp
oracle below; ``repro.kernels.ops.dict_match`` is the Pallas kernel with
the same signature, whose fused min/max gate is consumed directly instead
of being recomputed outside the kernel.

Beyond callables, ``matcher=`` accepts names (DESIGN.md Sec. 10):
``"reference"`` (jnp oracle), ``"ops"`` (pallas matcher + jnp step),
``"fused"`` (the single-dispatch ``kernels.encode_step`` kernel that also
applies the threshold, arg-min and FIFO overwrite), and ``"auto"`` (the
measured pick per (D, n, dtype) via the shared ``core.tuning`` machinery,
persisted under ``REPRO_ENCODE_AUTOTUNE``).
"""
from __future__ import annotations

import functools
import logging
from typing import Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .ks import ks_statistic_many, ks_statistic_many_masked
from .tuning import MeasuredTuner, best_of

__all__ = [
    "DictState",
    "EncoderParams",
    "ChanParams",
    "init_state",
    "repad_state_n",
    "matcher_reference",
    "resolve_matcher",
    "encode_decisions",
    "encode_decisions_batched",
    "encode_decisions_mixed",
    "encode_decisions_mixed_sharded",
    "encode_decisions_sharded",
    "encode_decisions_dsharded",
    "MATCHERS",
    "load_encode_autotune",
    "save_encode_autotune",
    "reset_encode_autotune",
    "encode_autotune_choices",
    "encode_autotune_cached",
]

logger = logging.getLogger("repro.core.encoder")

# "no entry passed" marker for cross-shard/cross-tile arg-min reductions;
# any real dictionary index (< 2^8) is far below it.
_SENTINEL = 2 ** 30



class DictState(NamedTuple):
    """Resumable carry of the encoder scan: the FIFO dictionary buffer.

    Thread it through chunked calls of ``encode_decisions`` to continue a
    stream.  Batched (multi-channel) states carry one leading ``(C,)`` axis
    on every field (see ``init_state(channels=...)``).
    """

    sorted_blocks: jax.Array  # (D, n) sorted source-distribution samples
    dmin: jax.Array  # (D,)
    dmax: jax.Array  # (D,)
    valid: jax.Array  # (D,) bool
    count: jax.Array  # () int32, number of inserts so far (FIFO position)
    # (D, n) raw (stream-order) payload rows, kept only for the error-bounded
    # mode's pointwise |x - x_hat| check; (0, n) when the mode is off so the
    # pytree structure (and partition specs) stay constant at zero cost.
    raw_blocks: jax.Array


class EncoderParams(NamedTuple):
    d_crit: float  # critical KS distance (from alpha via ks.critical_distance)
    rel_tol: float  # relative tolerance r for the min/max check (eq. 3)
    use_minmax: bool  # paper's new gate; False = "KS test only" mode
    use_ks: bool = True  # False = min/max check alone (ablation)
    # error-bounded mode (2404.02840 taxonomy): a would-be hit whose
    # pointwise reconstruction error exceeds the bound is demoted to a miss.
    # None disables the check; error_cumulative bounds the running cumsum of
    # the payload difference instead (delta mode, where decoded samples are
    # base + cumsum of stored diffs).
    error_bound: Optional[float] = None
    error_cumulative: bool = False


class ChanParams(NamedTuple):
    """Per-channel *traced* parameters of the masked mixed-mode scan
    (adaptive sessions, DESIGN.md Sec. 13).  Callers pass ``(C,)`` arrays;
    under the channel vmap every field is a scalar.  Built host-side by
    ``_chan_params_host`` so the float rounding matches the static paths
    exactly (``inv_n`` is the f32 rounding of the python-float ``1/n`` the
    fused kernel closes over)."""

    n: jax.Array  # () int32 logical payload width (<= padded cohort max)
    nf: jax.Array  # () f32 float(n): the reference matcher's ECDF divisor
    inv_n: jax.Array  # () f32 f32(1/n): the fused kernel's ECDF multiplier
    d_crit: jax.Array  # () f32 per-channel threshold (selector-scaled)
    err_cum: jax.Array  # () bool cumulative error metric (delta mode)
    eb_on: jax.Array  # () bool error-bound gate armed for this channel


def init_state(num_dict: int, n: int, dtype=jnp.float32,
               channels: Optional[int] = None,
               raw: bool = False) -> DictState:
    """Fresh (empty-dictionary) carry; ``channels=C`` stacks C independent
    per-channel states on a leading axis for the batched encoder.  ``raw``
    allocates the raw-payload rows the error-bounded check matches against
    (required whenever ``error_bound`` is set)."""
    lead = () if channels is None else (channels,)
    return DictState(
        sorted_blocks=jnp.zeros(lead + (num_dict, n), dtype=dtype),
        dmin=jnp.zeros(lead + (num_dict,), dtype=dtype),
        dmax=jnp.zeros(lead + (num_dict,), dtype=dtype),
        valid=jnp.zeros(lead + (num_dict,), dtype=bool),
        count=jnp.zeros(lead, dtype=jnp.int32),
        raw_blocks=jnp.zeros(lead + (num_dict if raw else 0, n),
                             dtype=dtype),
    )


def repad_state_n(state: DictState, n_new: int) -> DictState:
    """Re-pad the trailing payload-width axis of a (batched) mixed carry
    when the cohort's max live width changes.  Grown columns are ``+inf``
    (the pad value of inserted rows -- sorted rows stay sorted).  Shrinking
    slices pad columns off, which is only sound when every remaining valid
    row's logical width is <= ``n_new``; the session resets a lane before
    its width changes, so that invariant always holds."""
    n_old = state.sorted_blocks.shape[-1]
    if n_new == n_old:
        return state

    def fit(a):
        if n_new > n_old:
            pad = [(0, 0)] * (a.ndim - 1) + [(0, n_new - n_old)]
            return jnp.pad(a, pad, constant_values=jnp.inf)
        return a[..., :n_new]

    raw = state.raw_blocks
    if raw.shape[-2]:
        raw = fit(raw)
    return state._replace(sorted_blocks=fit(state.sorted_blocks),
                          raw_blocks=raw)


def _error_gate(block, raw_blocks, params: EncoderParams):
    """Per-entry pointwise error check: ``max|err| <= bound`` where err is
    the payload difference (std/residual: decoded samples differ from the
    original by exactly this) or its running cumsum (delta: decoded samples
    are base + cumsum of stored diffs).  With a value_range the bound holds
    in the circular metric (payloads are wrap-centered)."""
    diff = block[None, :] - raw_blocks
    if params.error_cumulative:
        diff = jnp.cumsum(diff, axis=-1)
    return jnp.max(jnp.abs(diff), axis=-1) <= params.error_bound


def _minmax_gate(xmin, xmax, dmin, dmax, r):
    """Eq. (3): both block extremes inside +-w*r of the stored extremes."""
    w = dmax - dmin
    t = w * r
    return (
        (xmin >= dmin - t)
        & (xmin <= dmin + t)
        & (xmax >= dmax - t)
        & (xmax <= dmax + t)
    )


def matcher_reference(xs_sorted, dict_sorted, dmin, dmax, rel_tol):
    """Default pure-jnp matcher: (ks (D,), mm (D,)) against all entries."""
    ks = ks_statistic_many(xs_sorted, dict_sorted)
    mm = _minmax_gate(xs_sorted[0], xs_sorted[-1], dmin, dmax, rel_tol)
    return ks, mm


def _step(matcher, params: EncoderParams, state: DictState, blk):
    """One scan step over ``(block, xs_sorted, block_valid)``.

    The per-block sort is hoisted out of the step: every scan entry point
    sorts the whole ``(nb, n)`` batch once (``jnp.sort(..., axis=-1)`` is
    bitwise identical to a per-step ``jnp.sort``) and threads the sorted
    rows alongside the raw ones, so the step itself is pure matching.

    ``block_valid`` is the ragged-batch padding mask: a False step is a
    no-op -- the carry passes through untouched and the decision triple is
    all-zero -- so channels with fewer real blocks than the padded batch
    (coalesced serving batches, sharded channel padding) stay
    decision-identical to an unpadded scan.
    """
    block, xs, valid = blk
    num_dict = state.sorted_blocks.shape[0]
    xmin, xmax = xs[0], xs[-1]

    ks, mm = matcher(xs, state.sorted_blocks, state.dmin, state.dmax,
                     params.rel_tol)
    ones = jnp.ones((num_dict,), dtype=bool)
    mm_ok = mm if params.use_minmax else ones
    ks_ok = (ks <= params.d_crit) if params.use_ks else ones

    ok = state.valid & mm_ok & ks_ok
    if params.error_bound is not None:
        ok = ok & _error_gate(block, state.raw_blocks, params)
    is_hit = jnp.any(ok) & valid
    first_hit = jnp.argmax(ok)  # lowest passing slot == early-exit result

    # FIFO insert slot on miss: fill 0..D-1, then overwrite oldest.
    ins_slot = jnp.mod(state.count, num_dict)
    do_ins = (~is_hit) & valid
    overwrite = do_ins & (state.count >= num_dict)
    slot = jnp.where(is_hit, first_hit, ins_slot).astype(jnp.int32)
    slot = jnp.where(valid, slot, 0)

    new_sorted = jax.lax.dynamic_update_slice(
        state.sorted_blocks, xs[None, :], (ins_slot, 0)
    )
    upd = jnp.arange(num_dict) == ins_slot
    raw_blocks = state.raw_blocks
    if params.error_bound is not None:
        new_raw = jax.lax.dynamic_update_slice(
            raw_blocks, block[None, :], (ins_slot, 0))
        raw_blocks = jnp.where(do_ins, new_raw, raw_blocks)
    new_state = DictState(
        sorted_blocks=jnp.where(do_ins, new_sorted, state.sorted_blocks),
        dmin=jnp.where(do_ins & upd, xmin, state.dmin),
        dmax=jnp.where(do_ins & upd, xmax, state.dmax),
        valid=jnp.where(do_ins & upd, True, state.valid),
        count=state.count + do_ins.astype(jnp.int32),
        raw_blocks=raw_blocks,
    )
    return new_state, (is_hit, slot, overwrite)


# ------------------------------------------------------- fused kernel step
def _is_fused(matcher) -> bool:
    """The fused matcher travels through the jit machinery as the hashable
    static value ``("fused", tile_d)`` rather than a callable."""
    return isinstance(matcher, tuple) and len(matcher) == 2 \
        and matcher[0] == "fused"


def _pad_state_d(state: DictState, pad: int) -> DictState:
    """Pad the dictionary axis with ``valid=False`` rows (tile alignment for
    the fused kernel, shard alignment for D-sharding).  Pad rows never pass
    the gate and are never inserted (FIFO slot uses the logical D)."""
    if pad == 0:
        return state
    raw = state.raw_blocks
    if raw.shape[0]:  # empty (0, n) raw stays empty: the mode is off
        raw = jnp.pad(raw, ((0, pad), (0, 0)))
    return DictState(
        sorted_blocks=jnp.pad(state.sorted_blocks, ((0, pad), (0, 0))),
        dmin=jnp.pad(state.dmin, (0, pad)),
        dmax=jnp.pad(state.dmax, (0, pad)),
        valid=jnp.pad(state.valid, (0, pad)),
        count=state.count,
        raw_blocks=raw,
    )


def _slice_state_d(state: DictState, num_dict: int) -> DictState:
    """Inverse of ``_pad_state_d``: back to the logical-D resumable carry."""
    if state.sorted_blocks.shape[0] == num_dict:
        return state
    raw = state.raw_blocks
    if raw.shape[0]:
        raw = raw[:num_dict]
    return DictState(
        sorted_blocks=state.sorted_blocks[:num_dict],
        dmin=state.dmin[:num_dict],
        dmax=state.dmax[:num_dict],
        valid=state.valid[:num_dict],
        count=state.count,
        raw_blocks=raw,
    )


def _step_fused(tile_d: int, params: EncoderParams, num_dict: int,
                state: DictState, blk):
    """Fused-kernel scan step: one pallas dispatch computes gate + masked KS
    + arg-min + FIFO overwrite and returns the updated (padded) carry.
    Decision-identical to ``_step`` with the ``ops`` matcher (bitwise: same
    kernel arithmetic) and to ``matcher_reference`` (same decisions).  Like
    ``_step`` it consumes pre-sorted rows from the batched sort stage."""
    from repro.kernels.encode_step import (DEC_COUNT, DEC_HIT, DEC_OVER,
                                           DEC_SLOT, encode_step_pallas)

    block, xs, valid = blk
    if params.error_bound is None:
        new_sorted, ndmin, ndmax, nvalid, dec = encode_step_pallas(
            xs, state.sorted_blocks, state.dmin, state.dmax, state.valid,
            state.count, valid, d_crit=params.d_crit, rel_tol=params.rel_tol,
            use_minmax=params.use_minmax, use_ks=params.use_ks,
            num_dict=num_dict, tile_d=tile_d)
        new_raw = state.raw_blocks
    else:
        new_sorted, ndmin, ndmax, nvalid, new_raw, dec = encode_step_pallas(
            xs, state.sorted_blocks, state.dmin, state.dmax, state.valid,
            state.count, valid, d_crit=params.d_crit, rel_tol=params.rel_tol,
            use_minmax=params.use_minmax, use_ks=params.use_ks,
            num_dict=num_dict, tile_d=tile_d,
            raw=block, raw_blocks=state.raw_blocks,
            error_bound=params.error_bound,
            error_cumulative=params.error_cumulative)
    new_state = DictState(new_sorted, ndmin, ndmax, nvalid, dec[DEC_COUNT],
                          new_raw)
    return new_state, (dec[DEC_HIT].astype(bool), dec[DEC_SLOT],
                       dec[DEC_OVER].astype(bool))


@functools.lru_cache(maxsize=None)
def _encode_scan():
    """Build the jitted scan lazily so importing this module never touches
    the accelerator runtime (decode-only / numpy-backend processes).

    Buffer donation of the resumable carry is a device-memory optimization;
    the CPU backend does not implement it and warns, so gate on backend.
    """
    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()

    @functools.partial(
        jax.jit,
        static_argnames=("d_crit", "rel_tol", "use_minmax", "use_ks",
                         "matcher", "error_bound", "error_cumulative"),
        donate_argnums=donate,
    )
    def scan(state: DictState, blocks, valid, *, d_crit, rel_tol, use_minmax,
             use_ks, matcher, error_bound=None, error_cumulative=False):
        params = EncoderParams(
            d_crit=d_crit, rel_tol=rel_tol, use_minmax=use_minmax,
            use_ks=use_ks, error_bound=error_bound,
            error_cumulative=error_cumulative,
        )
        xs_all = jnp.sort(blocks, axis=-1)  # hoisted out of the scan step
        if _is_fused(matcher):
            tile_d = matcher[1]
            num_dict = state.sorted_blocks.shape[0]
            pstate = _pad_state_d(state, (-num_dict) % tile_d)
            step = functools.partial(_step_fused, tile_d, params, num_dict)
            new_state, (is_hit, slot, overwrite) = jax.lax.scan(
                step, pstate, (blocks, xs_all, valid))
            new_state = _slice_state_d(new_state, num_dict)
        else:
            step = functools.partial(_step, matcher, params)
            new_state, (is_hit, slot, overwrite) = jax.lax.scan(
                step, state, (blocks, xs_all, valid))
        return (is_hit, slot, overwrite), new_state

    return scan


# ------------------------------------------- measured matcher autotuning
#
# ``matcher="auto"`` mirrors decode's ``backend="auto"`` (DESIGN.md Sec. 9):
# first use of a (D, n, dtype) combination times the reference, ops and
# fused paths (sweeping the fused kernel's tile_d) on a probe scan, routes
# the combination to the fastest, and persists the choice in the same
# versioned cache scheme under ``REPRO_ENCODE_AUTOTUNE``.

MATCHERS = ("reference", "ops", "fused")
ENCODE_AUTOTUNE_VERSION = 1
_FUSED_TILE_SWEEP = (8, 32)  # tile sizes that compile for v5e at n <= 256
_PROBE_BLOCKS = 8

_TUNER = MeasuredTuner(
    version=ENCODE_AUTOTUNE_VERSION, env_var="REPRO_ENCODE_AUTOTUNE",
    validate_entry=lambda ent: ent.get("matcher") in MATCHERS,
    log=logger, name="encode")


def _matcher_key(num_dict: int, n: int, dtype) -> str:
    import numpy as np

    return f"D={int(num_dict)}|n={int(n)}|dtype={np.dtype(dtype).str}"


def load_encode_autotune(path: str, strict: bool = True) -> int:
    """Load persisted matcher choices (see ``core.tuning``); entry count."""
    return _TUNER.load(path, strict=strict)


def save_encode_autotune(path: str) -> None:
    """Persist the in-memory matcher choices (atomic replace)."""
    _TUNER.save(path)


def reset_encode_autotune() -> None:
    """Forget every matcher choice; next ``"auto"`` re-probes.  Test hook."""
    _TUNER.reset()


def encode_autotune_choices() -> dict:
    """Current ``matcher="auto"`` routing table: key -> matcher name."""
    return _TUNER.choices("matcher")


def encode_autotune_cached(num_dict: int, n: int, dtype) -> bool:
    """Whether ``matcher="auto"`` for (D, n, dtype) resolves from cache."""
    return _TUNER.cached(_matcher_key(num_dict, n, dtype))


def _named_matcher(name: str, tile_d: Optional[int] = None):
    if name == "reference":
        return matcher_reference
    if name == "ops":
        from repro.kernels.ops import dict_match

        return dict_match
    if name == "fused":
        if tile_d is None:
            from repro.kernels.dict_match import TILE_D

            tile_d = TILE_D
        return ("fused", int(tile_d))
    raise ValueError(f"unknown matcher name {name!r}; "
                     f"expected one of {MATCHERS + ('auto',)}")


def _probe_matcher(num_dict: int, n: int, dtype) -> dict:
    """Time each matcher on a short probe scan at the real (D, n, dtype)
    operating point.  Every candidate must run: a kernel that fails to
    compile raises here instead of quietly giving way to the jnp
    reference (the tile sweep holds only sizes that compile for v5e)."""
    import numpy as np

    rng = np.random.default_rng(0)
    # mixture source: the dictionary fills, then hits and misses both occur,
    # so the fused kernel's gate-skip sees representative traffic
    blocks = jnp.asarray(np.concatenate([
        rng.normal(m, s, size=(_PROBE_BLOCKS // 2, n))
        for m, s in [(0.0, 1.0), (5.0, 0.5)]]), dtype)
    kw = dict(num_dict=num_dict, d_crit=0.35, rel_tol=0.5)

    def run(m):
        jax.block_until_ready(encode_decisions(blocks, matcher=m, **kw))

    times = {"reference": best_of(lambda: run(matcher_reference))}
    candidates = [("ops", _named_matcher("ops"))]
    candidates += [(f"fused/{td}", ("fused", td)) for td in _FUSED_TILE_SWEEP]
    for label, m in candidates:
        times[label] = best_of(lambda m=m: run(m))
    winner = min(sorted(times), key=times.get)
    if winner.startswith("fused/"):
        name, tile_d = "fused", int(winner.split("/")[1])
    else:
        name, tile_d = winner, None
    return {"matcher": name, "tile_d": tile_d,
            "times_us": {k: round(v * 1e6, 3) for k, v in times.items()}}


def resolve_matcher(matcher, *, num_dict: int, n: int, dtype):
    """Concrete matcher for an encode call.

    ``None`` -> the jnp oracle; callables and already-resolved fused tuples
    pass through (so vmapped/sharded inner calls re-resolve as no-ops);
    names pick the implementation; ``"auto"`` serves the measured choice
    for (D, n, dtype), probing (and persisting) on first use.  Resolve
    *before* entering jit/vmap tracing -- a timing probe under a tracer
    would measure tracing, not execution.
    """
    if matcher is None:
        return matcher_reference
    if callable(matcher) or _is_fused(matcher):
        return matcher
    if matcher in MATCHERS:
        return _named_matcher(matcher)
    if matcher == "auto":
        key = _matcher_key(num_dict, n, dtype)
        with _TUNER.lock:
            hit = _TUNER.cached(key)
            ent = _TUNER.resolve(
                key, lambda: _probe_matcher(int(num_dict), int(n), dtype))
            if not hit:
                logger.info("encode autotune: %s -> %s %s", key,
                            ent["matcher"], ent["times_us"])
        return _named_matcher(ent["matcher"], ent.get("tile_d"))
    raise ValueError(f"unknown matcher {matcher!r}; expected a callable "
                     f"or one of {MATCHERS + ('auto',)}")


def encode_decisions(
    blocks: jax.Array,
    *,
    num_dict: int,
    d_crit: float,
    rel_tol: float = 0.1,
    use_minmax: bool = True,
    use_ks: bool = True,
    error_bound: Optional[float] = None,
    error_cumulative: bool = False,
    matcher: Optional[Union[Callable, str, Tuple]] = None,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
):
    """Encode a (nb, n) stack of (already transformed) blocks.

    One-shot (``state=None``): returns ``(is_hit (nb,), slot (nb,),
    overwrite (nb,))`` from a fresh dictionary, as before.

    Resumable (``state=...``): continues the scan from the given carry and
    returns ``((is_hit, slot, overwrite), new_state)``.  Chunked calls that
    thread the state are decision-identical to one scan over the
    concatenated blocks.  The passed-in state is donated on accelerators --
    treat it as consumed.

    ``valid`` is an optional (nb,) padding mask: False steps leave the
    carry untouched and emit an all-zero decision, so ragged batches padded
    to a common block count stay decision-identical to unpadded scans.

    ``matcher(xs_sorted, dict_sorted, dmin, dmax, rel_tol) -> (ks, mm)``
    defaults to the pure-jnp oracle; pass ``repro.kernels.ops.dict_match``
    for the Pallas kernel (its fused min/max gate is used directly), or a
    name -- ``"reference"``/``"ops"``/``"fused"``/``"auto"`` -- resolved by
    :func:`resolve_matcher`.
    """
    matcher = resolve_matcher(matcher, num_dict=num_dict,
                              n=blocks.shape[-1], dtype=blocks.dtype)
    return_state = state is not None
    if state is None:
        state = init_state(num_dict, blocks.shape[-1], dtype=blocks.dtype,
                           raw=error_bound is not None)
    if error_bound is not None and state.raw_blocks.shape[-2] == 0:
        raise ValueError("error_bound requires a state created with "
                         "init_state(..., raw=True)")
    out, new_state = _encode_scan()(
        state, blocks,
        jnp.ones(blocks.shape[0], dtype=bool) if valid is None else valid,
        d_crit=float(d_crit), rel_tol=float(rel_tol),
        use_minmax=use_minmax, use_ks=use_ks, matcher=matcher,
        error_bound=None if error_bound is None else float(error_bound),
        error_cumulative=bool(error_cumulative),
    )
    return (out, new_state) if return_state else out


def encode_decisions_batched(
    blocks_cn: jax.Array,
    *,
    num_dict: int,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
    **kw,
):
    """Multi-channel encoder: blocks (C, nb, n) with per-channel DictState.

    One vmapped scan encodes all channels in lockstep.  One-shot
    (``state=None``) returns the (C, nb) decision triple; resumable
    (``state=init_state(..., channels=C)`` or a previous return) returns
    ``((is_hit, slot, overwrite), new_state)`` with the carry stacked on
    the leading channel axis.  ``valid`` (C, nb) masks padded blocks of
    ragged channels (coalesced serving batches).
    """
    # resolve names here, outside the vmap trace (a cold "auto" probe must
    # run eagerly); the inner per-channel resolution is then a no-op
    kw["matcher"] = resolve_matcher(
        kw.get("matcher"), num_dict=num_dict, n=blocks_cn.shape[-1],
        dtype=blocks_cn.dtype)
    return_state = state is not None
    if state is None:
        state = init_state(
            num_dict, blocks_cn.shape[-1], dtype=blocks_cn.dtype,
            channels=blocks_cn.shape[0],
            raw=kw.get("error_bound") is not None,
        )
    if valid is None:
        valid = jnp.ones(blocks_cn.shape[:2], dtype=bool)

    def one(s, b, v):
        return encode_decisions(b, num_dict=num_dict, state=s, valid=v, **kw)

    out, new_state = jax.vmap(one)(state, blocks_cn, valid)
    return (out, new_state) if return_state else out


# ------------------------------------------- masked mixed-mode (adaptive)
#
# Adaptive sessions diverge per channel: payload width (std vs
# residual/delta transforms), KS threshold (selector-scaled d_crit) and
# error metric (plain vs cumulative) all become channel-local.  Instead of
# one dispatch per channel, the mixed scan pads payloads to the cohort max
# width with +inf, masks tail columns per channel, and turns the formerly
# static kwargs into ChanParams carried through the vmap -- one dispatch
# and one host sync per feed, bitwise identical to the per-channel loop
# (DESIGN.md Sec. 13).

def _step_mixed(params: EncoderParams, chan: ChanParams, state: DictState,
                blk):
    """Masked variant of ``_step``: every width-dependent quantity uses the
    channel's logical width ``chan.n`` with the +inf tail columns masked
    out, and the KS threshold / error metric come from ``chan`` instead of
    the static params.  Bitwise-identical decisions and carry to ``_step``
    on the unpadded width."""
    block, xs, valid = blk
    num_dict = state.sorted_blocks.shape[0]
    n_max = xs.shape[0]
    col_ok = jnp.arange(n_max) < chan.n
    xmin = xs[0]
    # == xs[chan.n - 1] on sorted data; avoids a traced-index gather
    xmax = jnp.max(jnp.where(col_ok, xs, -jnp.inf))

    ks = ks_statistic_many_masked(xs, state.sorted_blocks, chan.nf, col_ok)
    mm = _minmax_gate(xmin, xmax, state.dmin, state.dmax, params.rel_tol)
    ones = jnp.ones((num_dict,), dtype=bool)
    mm_ok = mm if params.use_minmax else ones
    ks_ok = (ks <= chan.d_crit) if params.use_ks else ones

    ok = state.valid & mm_ok & ks_ok
    if params.error_bound is not None:
        diff = block[None, :] - state.raw_blocks
        diff = jnp.where(chan.err_cum, jnp.cumsum(diff, axis=-1), diff)
        # pad columns hold inf - inf = NaN: mask them before the max
        err = jnp.max(jnp.where(col_ok[None, :], jnp.abs(diff), 0.0),
                      axis=-1)
        ok = ok & ((~chan.eb_on) | (err <= params.error_bound))
    is_hit = jnp.any(ok) & valid
    first_hit = jnp.argmax(ok)

    ins_slot = jnp.mod(state.count, num_dict)
    do_ins = (~is_hit) & valid
    overwrite = do_ins & (state.count >= num_dict)
    slot = jnp.where(is_hit, first_hit, ins_slot).astype(jnp.int32)
    slot = jnp.where(valid, slot, 0)

    new_sorted = jax.lax.dynamic_update_slice(
        state.sorted_blocks, xs[None, :], (ins_slot, 0)
    )
    upd = jnp.arange(num_dict) == ins_slot
    raw_blocks = state.raw_blocks
    if params.error_bound is not None:
        new_raw = jax.lax.dynamic_update_slice(
            raw_blocks, block[None, :], (ins_slot, 0))
        raw_blocks = jnp.where(do_ins, new_raw, raw_blocks)
    new_state = DictState(
        sorted_blocks=jnp.where(do_ins, new_sorted, state.sorted_blocks),
        dmin=jnp.where(do_ins & upd, xmin, state.dmin),
        dmax=jnp.where(do_ins & upd, xmax, state.dmax),
        valid=jnp.where(do_ins & upd, True, state.valid),
        count=state.count + do_ins.astype(jnp.int32),
        raw_blocks=raw_blocks,
    )
    return new_state, (is_hit, slot, overwrite)


def _chan_block(chan: ChanParams) -> jax.Array:
    """The fused kernel's (8,) f32 channel-parameter operand (layout
    mirrored by ``kernels.encode_step.CHAN_*``; rows 5..7 are padding)."""
    z = jnp.zeros((), jnp.float32)
    return jnp.stack([chan.nf, chan.inv_n, chan.d_crit,
                      chan.err_cum.astype(jnp.float32),
                      chan.eb_on.astype(jnp.float32), z, z, z])


def _step_mixed_fused(tile_d: int, params: EncoderParams, num_dict: int,
                      chan_arr: jax.Array, state: DictState, blk):
    """Fused-kernel mixed scan step: the per-channel parameters travel as
    the kernel's ``chan`` operand, so one pallas dispatch per block still
    covers the whole heterogeneous step."""
    from repro.kernels.encode_step import (DEC_COUNT, DEC_HIT, DEC_OVER,
                                           DEC_SLOT, encode_step_pallas)

    block, xs, valid = blk
    kw = dict(d_crit=0.0, rel_tol=params.rel_tol,  # d_crit from chan
              use_minmax=params.use_minmax, use_ks=params.use_ks,
              num_dict=num_dict, tile_d=tile_d, chan=chan_arr)
    if params.error_bound is None:
        new_sorted, ndmin, ndmax, nvalid, dec = encode_step_pallas(
            xs, state.sorted_blocks, state.dmin, state.dmax, state.valid,
            state.count, valid, **kw)
        new_raw = state.raw_blocks
    else:
        new_sorted, ndmin, ndmax, nvalid, new_raw, dec = encode_step_pallas(
            xs, state.sorted_blocks, state.dmin, state.dmax, state.valid,
            state.count, valid, raw=block, raw_blocks=state.raw_blocks,
            error_bound=params.error_bound, **kw)
    new_state = DictState(new_sorted, ndmin, ndmax, nvalid, dec[DEC_COUNT],
                          new_raw)
    return new_state, (dec[DEC_HIT].astype(bool), dec[DEC_SLOT],
                       dec[DEC_OVER].astype(bool))


def _mixed_one(matcher, params: EncoderParams, num_dict: int):
    """Per-channel scan body shared by the vmapped and shard_map'd mixed
    encoders.  ``matcher`` is ``"reference"`` or a fused tuple (the only
    matchers with masked variants)."""

    def one(s, b, xsb, v, cp):
        if _is_fused(matcher):
            ps = _pad_state_d(s, (-num_dict) % matcher[1])
            step = functools.partial(_step_mixed_fused, matcher[1], params,
                                     num_dict, _chan_block(cp))
            new_s, out = jax.lax.scan(step, ps, (b, xsb, v))
            return out, _slice_state_d(new_s, num_dict)
        step = functools.partial(_step_mixed, params, cp)
        new_s, out = jax.lax.scan(step, s, (b, xsb, v))
        return out, new_s

    return one


@functools.lru_cache(maxsize=None)
def _mixed_scan():
    """Jitted mixed-mode scan, built lazily like ``_encode_scan``."""
    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()

    @functools.partial(
        jax.jit,
        static_argnames=("rel_tol", "use_minmax", "use_ks", "matcher",
                         "error_bound"),
        donate_argnums=donate,
    )
    def scan(state, blocks, valid, chan, *, rel_tol, use_minmax, use_ks,
             matcher, error_bound=None):
        params = EncoderParams(d_crit=0.0, rel_tol=rel_tol,
                               use_minmax=use_minmax, use_ks=use_ks,
                               error_bound=error_bound)
        num_dict = state.sorted_blocks.shape[-2]
        xs_all = jnp.sort(blocks, axis=-1)  # +inf pads sort to the tail
        one = _mixed_one(matcher, params, num_dict)
        out, new_state = jax.vmap(one)(state, blocks, xs_all, valid, chan)
        return out, new_state

    return scan


def _resolve_mixed_matcher(matcher):
    """Only the reference and fused matchers have masked (width-aware)
    variants; ``"ops"``/``"auto"``/callables must use the per-channel
    loop instead (the session falls back automatically)."""
    if matcher is None or matcher == "reference" \
            or matcher is matcher_reference:
        return "reference"
    if matcher == "fused":
        matcher = _named_matcher("fused")
    if _is_fused(matcher):
        return matcher
    raise ValueError(
        f"the mixed-mode scan has masked variants of the reference and "
        f"fused matchers only; got {matcher!r}")


def _chan_params_host(n_valid, d_crit, err_cum, eb_on) -> ChanParams:
    """Host-side ChanParams construction: ``inv_n`` is rounded f64 -> f32
    exactly like the static fused kernel's closed-over python float, so
    the chan-parameterized kernel is bitwise identical to the static one."""
    import numpy as np

    n = np.maximum(np.asarray(n_valid, np.int64), 1)  # inactive-lane guard
    return ChanParams(
        n=jnp.asarray(n, jnp.int32),
        nf=jnp.asarray(n, jnp.float32),
        inv_n=jnp.asarray(1.0 / n.astype(np.float64), jnp.float32),
        d_crit=jnp.asarray(np.asarray(d_crit), jnp.float32),
        err_cum=jnp.asarray(np.asarray(err_cum), bool),
        eb_on=jnp.asarray(np.asarray(eb_on), bool),
    )


def encode_decisions_mixed(
    blocks_cn: jax.Array,
    *,
    num_dict: int,
    n_valid,
    d_crit,
    rel_tol: float = 0.1,
    use_minmax: bool = True,
    use_ks: bool = True,
    error_bound: Optional[float] = None,
    error_cumulative=None,
    eb_on=None,
    matcher: Optional[Union[Callable, str, Tuple]] = None,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
):
    """Batched mixed-mode encoder for adaptive heterogeneous channels.

    ``blocks_cn`` (C, nb, n_max): per-channel payloads padded on the
    trailing width axis with ``+inf`` to the cohort max and on the block
    axis via ``valid`` (C, nb).  ``n_valid`` (C,) gives each channel's
    logical payload width, ``d_crit`` (C,) its (selector-scaled) KS
    threshold, ``error_cumulative`` (C,) bools pick the cumsum error
    metric per channel (delta mode) under the shared static
    ``error_bound``, and ``eb_on`` (C,) disarms the bound per channel.

    Decisions and the per-lane carry are bitwise identical to C separate
    ``encode_decisions`` calls on the unpadded payloads, in **one**
    dispatch (DESIGN.md Sec. 13).  Resumable exactly like
    ``encode_decisions_batched``; the carry's width axis follows the
    cohort max -- repad with :func:`repad_state_n` when it changes.
    """
    import numpy as np

    C = blocks_cn.shape[0]
    matcher = _resolve_mixed_matcher(matcher)
    return_state = state is not None
    if state is None:
        state = init_state(num_dict, blocks_cn.shape[-1],
                           dtype=blocks_cn.dtype, channels=C,
                           raw=error_bound is not None)
    if error_bound is not None and state.raw_blocks.shape[-2] == 0:
        raise ValueError("error_bound requires a state created with "
                         "init_state(..., raw=True)")
    if valid is None:
        valid = jnp.ones(blocks_cn.shape[:2], dtype=bool)
    chan = _chan_params_host(
        n_valid, d_crit,
        np.zeros(C, bool) if error_cumulative is None else error_cumulative,
        np.ones(C, bool) if eb_on is None else eb_on)
    out, new_state = _mixed_scan()(
        state, blocks_cn, valid, chan, rel_tol=float(rel_tol),
        use_minmax=use_minmax, use_ks=use_ks, matcher=matcher,
        error_bound=None if error_bound is None else float(error_bound),
    )
    return (out, new_state) if return_state else out


@functools.lru_cache(maxsize=None)
def _mixed_sharded_scan(mesh, axis_name: str):
    """shard_map'd mixed scan: channel axis split over the mesh like
    ``_sharded_scan``, with the ChanParams arrays sharded alongside."""
    from jax.sharding import PartitionSpec as P

    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
    st_spec = state_partition_spec(axis_name)
    blk_spec = P(axis_name, None, None)
    msk_spec = P(axis_name, None)
    chan_spec = ChanParams(*([P(axis_name)] * len(ChanParams._fields)))
    out_spec = (P(axis_name, None),) * 3

    @functools.partial(
        jax.jit,
        static_argnames=("rel_tol", "use_minmax", "use_ks", "matcher",
                         "error_bound"),
        donate_argnums=donate,
    )
    def scan(state, blocks, valid, chan, *, rel_tol, use_minmax, use_ks,
             matcher, error_bound=None):
        params = EncoderParams(d_crit=0.0, rel_tol=rel_tol,
                               use_minmax=use_minmax, use_ks=use_ks,
                               error_bound=error_bound)
        num_dict = state.sorted_blocks.shape[-2]
        one = _mixed_one(matcher, params, num_dict)

        def shard(s, b, v, cp):
            x = jnp.sort(b, axis=-1)
            return jax.vmap(one)(s, b, x, v, cp)

        return jax.shard_map(
            shard, mesh=mesh,
            in_specs=(st_spec, blk_spec, msk_spec, chan_spec),
            out_specs=(out_spec, st_spec),
            check_vma=False,
        )(state, blocks, valid, chan)

    return scan


def encode_decisions_mixed_sharded(
    blocks_cn: jax.Array,
    *,
    mesh,
    axis_name: str,
    num_dict: int,
    n_valid,
    d_crit,
    rel_tol: float = 0.1,
    use_minmax: bool = True,
    use_ks: bool = True,
    error_bound: Optional[float] = None,
    error_cumulative=None,
    eb_on=None,
    matcher: Optional[Union[Callable, str, Tuple]] = None,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
):
    """Channel-sharded :func:`encode_decisions_mixed`: the cohort's channel
    axis (and its ChanParams arrays) split over the 1-D ``mesh`` exactly
    like ``encode_decisions_sharded``.  C must be a mesh-axis multiple (an
    ``EncodePlan`` computes the padding; inactive pad lanes carry
    ``valid=False`` rows and a clamped width)."""
    import numpy as np

    matcher = _resolve_mixed_matcher(matcher)
    C = blocks_cn.shape[0]
    if C % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"channels={C} not divisible by mesh axis "
            f"{axis_name}={mesh.shape[axis_name]}; pad via EncodePlan")
    return_state = state is not None
    if state is None:
        state = init_state(num_dict, blocks_cn.shape[-1],
                           dtype=blocks_cn.dtype, channels=C,
                           raw=error_bound is not None)
    if valid is None:
        valid = jnp.ones(blocks_cn.shape[:2], dtype=bool)
    chan = _chan_params_host(
        n_valid, d_crit,
        np.zeros(C, bool) if error_cumulative is None else error_cumulative,
        np.ones(C, bool) if eb_on is None else eb_on)
    out, new_state = _mixed_sharded_scan(mesh, axis_name)(
        state, blocks_cn, valid, chan, rel_tol=float(rel_tol),
        use_minmax=use_minmax, use_ks=use_ks, matcher=matcher,
        error_bound=None if error_bound is None else float(error_bound),
    )
    return (out, new_state) if return_state else out


# ------------------------------------------------------- sharded scale-out
def state_partition_spec(axis_name: str):
    """``DictState``-shaped PartitionSpec pytree: every carry field split
    on its leading channel axis.  The single place that knows the field
    layout -- ``shard_map`` in_specs and the launch-layer device placement
    (``EncodePlan.state_sharding``) both derive from it."""
    from jax.sharding import PartitionSpec as P

    return DictState(
        sorted_blocks=P(axis_name, None, None),
        dmin=P(axis_name, None),
        dmax=P(axis_name, None),
        valid=P(axis_name, None),
        count=P(axis_name),
        raw_blocks=P(axis_name, None, None),
    )


@functools.lru_cache(maxsize=None)
def _sharded_scan(mesh, axis_name: str):
    """shard_map'd version of the batched scan: the channel axis is split
    across ``mesh``'s devices; each shard runs the same vmapped scan (and
    therefore the same matcher -- the pallas kernel dispatches per shard),
    so outputs are bit-identical to the single-device batched encode.

    The per-channel carry lives sharded on its device between calls and is
    donated like the single-device path."""
    from jax.sharding import PartitionSpec as P

    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
    st_spec = state_partition_spec(axis_name)
    blk_spec = P(axis_name, None, None)
    msk_spec = P(axis_name, None)
    out_spec = (P(axis_name, None),) * 3

    @functools.partial(
        jax.jit,
        static_argnames=("d_crit", "rel_tol", "use_minmax", "use_ks",
                         "matcher", "error_bound", "error_cumulative"),
        donate_argnums=donate,
    )
    def scan(state, blocks, valid, *, d_crit, rel_tol, use_minmax, use_ks,
             matcher, error_bound=None, error_cumulative=False):
        params = EncoderParams(d_crit=d_crit, rel_tol=rel_tol,
                               use_minmax=use_minmax, use_ks=use_ks,
                               error_bound=error_bound,
                               error_cumulative=error_cumulative)
        num_dict = state.sorted_blocks.shape[-2]
        if _is_fused(matcher):
            tile_d = matcher[1]
            step = functools.partial(_step_fused, tile_d, params, num_dict)
        else:
            step = functools.partial(_step, matcher, params)

        def shard(s, b, v):
            x = jnp.sort(b, axis=-1)  # hoisted out of the scan step

            def one(s1, b1, x1, v1):
                if _is_fused(matcher):
                    s1 = _pad_state_d(s1, (-num_dict) % matcher[1])
                new_s, out = jax.lax.scan(step, s1, (b1, x1, v1))
                return out, _slice_state_d(new_s, num_dict)

            return jax.vmap(one)(s, b, x, v)

        # check_vma=False: the pallas matcher has no replication rule; all
        # operands map over the channel axis anyway (no replicated outputs).
        return jax.shard_map(
            shard, mesh=mesh,
            in_specs=(st_spec, blk_spec, msk_spec),
            out_specs=(out_spec, st_spec),
            check_vma=False,
        )(state, blocks, valid)

    return scan


def encode_decisions_sharded(
    blocks_cn: jax.Array,
    *,
    mesh,
    axis_name: str,
    num_dict: int,
    d_crit: float,
    rel_tol: float = 0.1,
    use_minmax: bool = True,
    use_ks: bool = True,
    error_bound: Optional[float] = None,
    error_cumulative: bool = False,
    matcher: Optional[Union[Callable, str, Tuple]] = None,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
):
    """Scale-out variant of ``encode_decisions_batched``: the leading
    channel axis of ``blocks_cn`` (C, nb, n) is sharded over the 1-D
    ``mesh`` (see ``repro.launch.encode_plan``) and each device scans its
    channel shard with a device-resident, donated carry.

    C must be a multiple of the mesh axis size -- pad channels up and mask
    them out via ``valid`` (an ``EncodePlan`` computes the padding).
    Decisions (and therefore stream bytes) are bit-identical to the
    single-device batched encode of the same channels.
    """
    matcher = resolve_matcher(matcher, num_dict=num_dict,
                              n=blocks_cn.shape[-1], dtype=blocks_cn.dtype)
    C = blocks_cn.shape[0]
    if C % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"channels={C} not divisible by mesh axis "
            f"{axis_name}={mesh.shape[axis_name]}; pad via EncodePlan")
    return_state = state is not None
    if state is None:
        state = init_state(num_dict, blocks_cn.shape[-1],
                           dtype=blocks_cn.dtype, channels=C,
                           raw=error_bound is not None)
    if valid is None:
        valid = jnp.ones(blocks_cn.shape[:2], dtype=bool)
    out, new_state = _sharded_scan(mesh, axis_name)(
        state, blocks_cn, valid, d_crit=float(d_crit),
        rel_tol=float(rel_tol), use_minmax=use_minmax, use_ks=use_ks,
        matcher=matcher,
        error_bound=None if error_bound is None else float(error_bound),
        error_cumulative=bool(error_cumulative),
    )
    return (out, new_state) if return_state else out


# ------------------------------------------------- D-axis (dictionary) sharding
def _step_dshard(matcher, params: EncoderParams, num_dict: int,
                 dict_axis: str, state: DictState, block_valid):
    """One scan step over a *dictionary shard*: this device holds a
    contiguous slice of the (padded) dictionary rows, matches the candidate
    against them, and the lowest passing *global* index is all-reduced over
    the ``dict_axis`` mesh axis with ``pmin`` -- the reduction is exactly
    ``argmax(ok)`` of the unsharded scan, so decisions are identical.

    The FIFO insert slot ``count % num_dict`` is a global index; only the
    shard that owns it writes (the others pass their carry through).
    ``count`` is replicated across dictionary shards and advances in
    lockstep."""
    block, xs, valid = block_valid
    shard_d = state.sorted_blocks.shape[0]
    off = jax.lax.axis_index(dict_axis).astype(jnp.int32) * shard_d
    xmin, xmax = xs[0], xs[-1]

    ks, mm = matcher(xs, state.sorted_blocks, state.dmin, state.dmax,
                     params.rel_tol)
    ones = jnp.ones((shard_d,), dtype=bool)
    mm_ok = mm if params.use_minmax else ones
    ks_ok = (ks <= params.d_crit) if params.use_ks else ones
    ok = state.valid & mm_ok & ks_ok
    if params.error_bound is not None:
        ok = ok & _error_gate(block, state.raw_blocks, params)

    ids = off + jnp.arange(shard_d, dtype=jnp.int32)
    local_first = jnp.min(jnp.where(ok, ids, _SENTINEL))
    best = jax.lax.pmin(local_first, dict_axis)
    is_hit = (best < _SENTINEL) & valid

    ins = jnp.mod(state.count, num_dict)  # global FIFO slot (logical D)
    do_ins = (~is_hit) & valid
    overwrite = do_ins & (state.count >= num_dict)
    slot = jnp.where(is_hit, best, ins).astype(jnp.int32)
    slot = jnp.where(valid, slot, 0)

    lins = ins - off
    in_shard = (lins >= 0) & (lins < shard_d)
    lclip = jnp.clip(lins, 0, shard_d - 1)
    do_here = do_ins & in_shard
    new_sorted = jax.lax.dynamic_update_slice(
        state.sorted_blocks, xs[None, :], (lclip, 0))
    upd = jnp.arange(shard_d) == lclip
    raw_blocks = state.raw_blocks
    if params.error_bound is not None:
        new_raw = jax.lax.dynamic_update_slice(
            raw_blocks, block[None, :], (lclip, 0))
        raw_blocks = jnp.where(do_here, new_raw, raw_blocks)
    new_state = DictState(
        sorted_blocks=jnp.where(do_here, new_sorted, state.sorted_blocks),
        dmin=jnp.where(do_here & upd, xmin, state.dmin),
        dmax=jnp.where(do_here & upd, xmax, state.dmax),
        valid=jnp.where(do_here & upd, True, state.valid),
        count=state.count + do_ins.astype(jnp.int32),
        raw_blocks=raw_blocks,
    )
    return new_state, (is_hit, slot, overwrite)


def state_dshard_partition_spec(ch_axis: str, dict_axis: str):
    """``DictState``-shaped PartitionSpec pytree for a (channels, dict)
    2-D mesh: channels on the leading axis, dictionary rows on the second;
    ``count`` is replicated across dictionary shards."""
    from jax.sharding import PartitionSpec as P

    return DictState(
        sorted_blocks=P(ch_axis, dict_axis, None),
        dmin=P(ch_axis, dict_axis),
        dmax=P(ch_axis, dict_axis),
        valid=P(ch_axis, dict_axis),
        count=P(ch_axis),
        raw_blocks=P(ch_axis, dict_axis, None),
    )


@functools.lru_cache(maxsize=None)
def _dsharded_scan(mesh, ch_axis: str, dict_axis: str):
    """shard_map'd scan over a 2-D (channels, dict) mesh: channels split as
    in ``_sharded_scan``, and within each channel group the dictionary rows
    of every channel are split over the ``dict_axis`` devices, with the
    per-step best-match arg-min all-reduced across them.  A 1-sized channel
    axis gives pure D-sharding of fat channels."""
    from jax.sharding import PartitionSpec as P

    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
    st_spec = state_dshard_partition_spec(ch_axis, dict_axis)
    blk_spec = P(ch_axis, None, None)
    msk_spec = P(ch_axis, None)
    # decisions come out identical on every dict shard (post-pmin); declare
    # them replicated over dict_axis (check_vma=False skips verification,
    # as for the channel-sharded scan's pallas matcher)
    out_spec = (P(ch_axis, None),) * 3

    @functools.partial(
        jax.jit,
        static_argnames=("d_crit", "rel_tol", "use_minmax", "use_ks",
                         "matcher", "error_bound", "error_cumulative"),
        donate_argnums=donate,
    )
    def scan(state, blocks, valid, *, d_crit, rel_tol, use_minmax, use_ks,
             matcher, error_bound=None, error_cumulative=False):
        params = EncoderParams(d_crit=d_crit, rel_tol=rel_tol,
                               use_minmax=use_minmax, use_ks=use_ks,
                               error_bound=error_bound,
                               error_cumulative=error_cumulative)
        num_dict = state.sorted_blocks.shape[1]
        shards = mesh.shape[dict_axis]
        pad = (-num_dict) % shards
        raw = state.raw_blocks
        if raw.shape[1]:
            raw = jnp.pad(raw, ((0, 0), (0, pad), (0, 0)))
        pstate = DictState(
            sorted_blocks=jnp.pad(state.sorted_blocks,
                                  ((0, 0), (0, pad), (0, 0))),
            dmin=jnp.pad(state.dmin, ((0, 0), (0, pad))),
            dmax=jnp.pad(state.dmax, ((0, 0), (0, pad))),
            valid=jnp.pad(state.valid, ((0, 0), (0, pad))),
            count=state.count,
            raw_blocks=raw,
        )
        step = functools.partial(_step_dshard, matcher, params, num_dict,
                                 dict_axis)

        def shard(s, b, v):
            x = jnp.sort(b, axis=-1)  # hoisted out of the scan step

            def one(s1, b1, x1, v1):
                new_s, out = jax.lax.scan(step, s1, (b1, x1, v1))
                return out, new_s

            return jax.vmap(one)(s, b, x, v)

        out, new_p = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(st_spec, blk_spec, msk_spec),
            out_specs=(out_spec, st_spec),
            check_vma=False,
        )(pstate, blocks, valid)
        new_state = DictState(
            sorted_blocks=new_p.sorted_blocks[:, :num_dict],
            dmin=new_p.dmin[:, :num_dict],
            dmax=new_p.dmax[:, :num_dict],
            valid=new_p.valid[:, :num_dict],
            count=new_p.count,
            raw_blocks=(new_p.raw_blocks[:, :num_dict]
                        if new_p.raw_blocks.shape[1] else new_p.raw_blocks),
        )
        return out, new_state

    return scan


def encode_decisions_dsharded(
    blocks_cn: jax.Array,
    *,
    mesh,
    ch_axis: str,
    dict_axis: str,
    num_dict: int,
    d_crit: float,
    rel_tol: float = 0.1,
    use_minmax: bool = True,
    use_ks: bool = True,
    error_bound: Optional[float] = None,
    error_cumulative: bool = False,
    matcher: Optional[Union[Callable, str, Tuple]] = None,
    state: Optional[DictState] = None,
    valid: Optional[jax.Array] = None,
):
    """Dictionary-sharded encoder: blocks (C, nb, n) over a 2-D
    ``mesh`` (ch_axis, dict_axis).  Channels split over ``ch_axis`` exactly
    like :func:`encode_decisions_sharded`; *within* each channel the
    dictionary rows are split over ``dict_axis`` and the per-step best
    match is all-reduced, so one fat channel can use several devices.
    Decisions are bit-identical to the single-device batched encode.

    The fused single-dispatch matcher cannot run here -- its in-kernel FIFO
    overwrite would have to precede the cross-shard arg-min reduction -- so
    ``"fused"``/``"auto"``-fused resolutions fall back to the ``ops``
    pallas matcher.
    """
    matcher = resolve_matcher(matcher, num_dict=num_dict,
                              n=blocks_cn.shape[-1], dtype=blocks_cn.dtype)
    if _is_fused(matcher):
        from repro.kernels.ops import dict_match

        matcher = dict_match
    C = blocks_cn.shape[0]
    if C % mesh.shape[ch_axis] != 0:
        raise ValueError(
            f"channels={C} not divisible by mesh axis "
            f"{ch_axis}={mesh.shape[ch_axis]}; pad via EncodePlan")
    return_state = state is not None
    if state is None:
        state = init_state(num_dict, blocks_cn.shape[-1],
                           dtype=blocks_cn.dtype, channels=C,
                           raw=error_bound is not None)
    if valid is None:
        valid = jnp.ones(blocks_cn.shape[:2], dtype=bool)
    out, new_state = _dsharded_scan(mesh, ch_axis, dict_axis)(
        state, blocks_cn, valid, d_crit=float(d_crit),
        rel_tol=float(rel_tol), use_minmax=use_minmax, use_ks=use_ks,
        matcher=matcher,
        error_bound=None if error_bound is None else float(error_bound),
        error_cumulative=bool(error_cumulative),
    )
    return (out, new_state) if return_state else out
