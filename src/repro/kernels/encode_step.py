"""Pallas TPU kernel: one fused IDEALEM encode step (DESIGN.md Sec. 10).

``dict_match`` fused the two similarity checks (KS + min/max gate) into one
kernel, but the encoder's scan step remained a *composition*: the matcher
dispatch, the ``ks <= d_crit`` threshold, the arg-min over D and the FIFO
dictionary overwrite each ran as separate XLA ops with the full (D,) ks/mm
vectors materialized between them.  This kernel is the whole per-block step
in a single dispatch:

  1. min/max gate first (eq. 3), per dictionary tile -- and the gate result
     *masks the KS work*: a tile where no valid entry passes the gate skips
     its (tile_d, n, n) rank computation entirely (the paper's acceleration,
     now at the kernel level via ``@pl.when``).
  2. two-sample KS distance (eq. 1) on surviving tiles, with arithmetic
     identical to ``dict_match``.  Decisions match the composed pallas path
     for any threshold strictly between KS jump points (KS values are
     multiples of 1/n; XLA fusion choices such as FMA contraction can move
     a computed value by one ulp, so a d_crit placed *exactly* on k/n is
     undefined territory -- ``critical_distance`` thresholds never are).
  3. running arg-min of the lowest passing global index, accumulated across
     tiles in the ``dec`` output block (grid programs execute sequentially
     on TPU, so a revisited output block is a cross-program accumulator).
  4. the FIFO slot overwrite on miss, applied by the last program in the
     same dispatch -- the updated dictionary carry leaves the kernel ready
     for the next scan step.

Dictionary tiles stream through VMEM via a tiled BlockSpec, so the pallas
pipeline double-buffers them against compute; the carry-out buffers use a
constant index map and stay VMEM-resident across the whole grid.

Layout (what Mosaic accepts on the TPU): the per-entry vectors dmin, dmax
and valid travel as (Dp, 1) columns tiled (tile_d, 1), so they broadcast
against a (tile_d, n) dictionary tile and tile_d only has to be a multiple
of 8 sublanes; ``valid`` is int32 inside the kernel.  The scalars -- the
``[count, block_valid]`` meta operand, the per-channel parameters, the
error bound and the decision block -- live in SMEM as (1, k) rows: a
vmapped channel axis then adds a leading block dimension Mosaic accepts
(a 1-D SMEM operand batched to (C, k) is refused).  The candidate is a
(1, n) row.

D must be padded to a ``tile_d`` multiple with ``valid=False`` rows (the
encoder pads once at scan entry); padded rows never pass the gate and are
never inserted because the FIFO slot is ``count % num_dict`` with the
*logical* D.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dict_match import TILE_D, check_tile_divisible
from .platform import pallas_call

__all__ = ["encode_step_pallas", "encode_step_bounded", "BoundedCarry",
           "SENTINEL", "DEC_BEST", "DEC_HIT", "DEC_SLOT", "DEC_OVER",
           "DEC_COUNT", "DEC_DEMOTED", "CHAN_NF", "CHAN_INV_N", "CHAN_DCRIT",
           "CHAN_ERRCUM", "CHAN_EBON"]

# "no entry passed" marker for the running arg-min; any real global index
# (< 2^8 dictionary rows) is far below it.
SENTINEL = 2 ** 30

# layout of the (8,) int32 decision block.  With an error bound armed,
# DEC_DEMOTED flags a block the bound demoted (some valid entry passed the
# min/max gate and the bound excluded every such entry) and DEC_EB_PASS is
# the running "some entry passed both" accumulator it is finalized from;
# row 7 is padding.
DEC_BEST, DEC_HIT, DEC_SLOT, DEC_OVER, DEC_COUNT, DEC_DEMOTED, DEC_EB_PASS = \
    range(7)

# layout of the optional (8,) f32 per-channel parameter operand (mixed-mode
# adaptive cohorts, DESIGN.md Sec. 13; rows 5..7 are padding)
CHAN_NF, CHAN_INV_N, CHAN_DCRIT, CHAN_ERRCUM, CHAN_EBON = range(5)


def _max_abs_cumsum(diff, col_ok=None):
    """Row-wise ``max_j |sum_{k<=j} diff[:, k]|`` as a (rows, 1) column.

    Accumulates strictly left to right -- the order of the host oracle's
    ``np.cumsum`` -- over a static column walk (Mosaic has no cumsum
    lowering and no dynamic lane slicing).  ``col_ok(j)`` masks columns
    beyond a channel's logical width to 0 (the +inf pad columns sit at the
    tail, so they never feed a kept prefix)."""
    acc = diff[:, 0:1]
    m = jnp.abs(acc)
    for j in range(1, diff.shape[1]):
        acc = acc + diff[:, j:j + 1]
        a = jnp.abs(acc)
        if col_ok is not None:
            a = jnp.where(col_ok(j), a, jnp.zeros((), a.dtype))
        m = jnp.maximum(m, a)
    return m


def _encode_step_kernel(d_crit, rel_tol, use_minmax, use_ks, eb,
                        error_cumulative, num_dict, tile_d, chan, *refs):
    chan_ref = bound_ref = None
    if chan:
        chan_ref, *refs = refs
    if not eb:
        (meta_ref, xs_ref, dict_ref, dmin_ref, dmax_ref, valid_ref,
         new_dict_ref, new_dmin_ref, new_dmax_ref, new_valid_ref,
         dec_ref) = refs
        raw_ref = rawdict_ref = new_raw_ref = None
    else:
        (bound_ref, meta_ref, xs_ref, raw_ref, dict_ref, rawdict_ref,
         dmin_ref, dmax_ref, valid_ref, new_dict_ref, new_raw_ref,
         new_dmin_ref, new_dmax_ref, new_valid_ref, dec_ref) = refs
    i = pl.program_id(0)
    nprog = pl.num_programs(0)
    n = xs_ref.shape[1]
    off = pl.multiple_of(i * tile_d, tile_d)

    xs = xs_ref[...].astype(jnp.float32)        # (1, n) sorted candidate
    ds = dict_ref[...].astype(jnp.float32)      # (tile_d, n) dictionary tile
    dmin = dmin_ref[...].astype(jnp.float32)    # (tile_d, 1)
    dmax = dmax_ref[...].astype(jnp.float32)
    dvalid = valid_ref[...] != 0
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    if chan:
        # per-channel parameters replace the static d_crit/inv_n/err_cum;
        # tail columns beyond the channel's logical width are +inf pads,
        # masked out of every width-dependent reduction (Sec. 13)
        inv_n = chan_ref[0, CHAN_INV_N]
        nf = chan_ref[0, CHAN_NF]
        col_ok = col.astype(jnp.float32) < nf
        # == xs[n_c - 1] on sorted data: the masked max of the real columns
        xmax = jnp.max(jnp.where(col_ok, xs, -jnp.inf), axis=1,
                       keepdims=True)
    else:
        inv_n = 1.0 / n
        # == xs[n - 1] on sorted data; a reduction, because Mosaic cannot
        # broadcast a lane slice taken past the first 128 lanes
        xmax = jnp.max(xs, axis=1, keepdims=True)

    @pl.when(i == 0)
    def _init():
        for k in range(8):
            dec_ref[0, k] = jnp.int32(SENTINEL if k == DEC_BEST else 0)

    # Carry-out starts as a copy of the carry-in; the last program below
    # overwrites (at most) the one FIFO row.
    new_dict_ref[pl.ds(off, tile_d), :] = dict_ref[...]
    new_dmin_ref[pl.ds(off, tile_d), :] = dmin_ref[...]
    new_dmax_ref[pl.ds(off, tile_d), :] = dmax_ref[...]
    new_valid_ref[pl.ds(off, tile_d), :] = valid_ref[...]

    # --- min/max gate first (eq. 3): arithmetic identical to dict_match ---
    if use_minmax:
        r = jnp.float32(rel_tol)
        xmin = xs[:, 0:1]
        t = (dmax - dmin) * r
        mm = ((xmin >= dmin - t) & (xmin <= dmin + t)
              & (xmax >= dmax - t) & (xmax <= dmax + t))
        gate = dvalid & mm                       # (tile_d, 1)
    else:
        gate = dvalid

    if eb:
        # carry the raw (stream-order) rows alongside the sorted ones and
        # fold the pointwise-error demotion into the gate: a tile where no
        # entry is within the bound also skips its KS rank work.  Computed
        # in the stored dtype (no f32 cast) so the per-entry max|err| is
        # exactly what the no-permutation decode reproduces; the bound is
        # the SMEM operand, already rounded to the stored dtype (exact).
        new_raw_ref[pl.ds(off, tile_d), :] = rawdict_ref[...]
        diff = raw_ref[...] - rawdict_ref[...]  # (tile_d, n)
        bound = bound_ref[0, 0].astype(diff.dtype)
        if chan:
            # per-channel metric choice; pad columns hold inf - inf = NaN
            # and are masked out before the max
            zero = jnp.zeros((), diff.dtype)
            plain = jnp.max(jnp.where(col_ok, jnp.abs(diff), zero), axis=1,
                            keepdims=True)
            cum = _max_abs_cumsum(diff, col_ok=lambda j: j < nf)
            err = jnp.where(chan_ref[0, CHAN_ERRCUM] != 0.0, cum, plain)
            err_ok = (err <= bound) | (chan_ref[0, CHAN_EBON] == 0.0)
        elif error_cumulative:
            err_ok = _max_abs_cumsum(diff) <= bound
        else:
            err_ok = jnp.max(jnp.abs(diff), axis=1, keepdims=True) <= bound
        # demotion accounting: did any entry pass the gate before the
        # bound, and did any pass it after (accumulated over the tiles)
        dec_ref[0, DEC_DEMOTED] = jnp.maximum(
            dec_ref[0, DEC_DEMOTED], jnp.max(gate.astype(jnp.int32)))
        gate = gate & err_ok
        dec_ref[0, DEC_EB_PASS] = jnp.maximum(
            dec_ref[0, DEC_EB_PASS], jnp.max(gate.astype(jnp.int32)))

    ids = off + jax.lax.broadcasted_iota(jnp.int32, (tile_d, 1), 0)

    if use_ks:
        # KS rank work only when some valid entry survived the gate: the
        # O(tile_d * n^2) comparisons are skipped for cold tiles (and for
        # every tile while the dictionary is still empty).
        @pl.when(jnp.any(gate))
        def _ks_tile():
            # identical arithmetic to _dict_match_kernel (decision parity
            # with the composed pallas path; see module docstring)
            cmp_d_le_x = (ds[:, :, None] <= xs[None, :, :]
                          ).astype(jnp.float32)
            cnt_d = jnp.sum(cmp_d_le_x, axis=1)                 # (tile_d, n)
            # F_x at its own points counts the candidate values <= each
            # one: col + 1 unless values tie (a tied run, e.g. a constant
            # block, takes the count at its end, as the ECDF does); +inf
            # pad columns are never <= a real one
            cnt_xx = jnp.sum((xs[:, None, :] <= xs[:, :, None]
                              ).astype(jnp.float32), axis=2)    # (1, n)
            f_x_at_x = cnt_xx * inv_n
            a1 = jnp.abs(f_x_at_x - cnt_d * inv_n)
            if chan:  # zero-fill pad columns (KS >= 0) before the max
                a1 = jnp.where(col_ok, a1, 0.0)
            d1 = jnp.max(a1, axis=1, keepdims=True)

            cmp_x_le_d = (xs[None, :, :] <= ds[:, :, None]
                          ).astype(jnp.float32)
            cnt_x = jnp.sum(cmp_x_le_d, axis=2)                 # (tile_d, n)
            rank_d = jnp.sum((ds[:, None, :] <= ds[:, :, None]
                              ).astype(jnp.float32), axis=2)
            a2 = jnp.abs(cnt_x * inv_n - rank_d * inv_n)
            if chan:
                a2 = jnp.where(col_ok, a2, 0.0)
            d2 = jnp.max(a2, axis=1, keepdims=True)
            ks = jnp.maximum(d1, d2)

            thresh = chan_ref[0, CHAN_DCRIT] if chan else jnp.float32(d_crit)
            ok = gate & (ks <= thresh)
            lf = jnp.min(jnp.where(ok, ids, SENTINEL))
            dec_ref[0, DEC_BEST] = jnp.minimum(dec_ref[0, DEC_BEST], lf)
    else:
        lf = jnp.min(jnp.where(gate, ids, SENTINEL))
        dec_ref[0, DEC_BEST] = jnp.minimum(dec_ref[0, DEC_BEST], lf)

    # --- last program: finalize the decision and apply the FIFO insert ---
    @pl.when(i == nprog - 1)
    def _finalize():
        count = meta_ref[0, 0]
        bvalid = meta_ref[0, 1] != 0
        best = dec_ref[0, DEC_BEST]
        is_hit = (best < SENTINEL) & bvalid
        ins = jax.lax.rem(count, jnp.int32(num_dict))  # logical D: pad rows
        do_ins = jnp.logical_not(is_hit) & bvalid      # are never targeted
        overwrite = do_ins & (count >= num_dict)
        dec_ref[0, DEC_HIT] = is_hit.astype(jnp.int32)
        dec_ref[0, DEC_SLOT] = jnp.where(bvalid, jnp.where(is_hit, best, ins), 0)
        dec_ref[0, DEC_OVER] = overwrite.astype(jnp.int32)
        dec_ref[0, DEC_COUNT] = count + do_ins.astype(jnp.int32)
        if eb:
            demoted = (bvalid & (dec_ref[0, DEC_DEMOTED] != 0)
                       & (dec_ref[0, DEC_EB_PASS] == 0))
            dec_ref[0, DEC_DEMOTED] = demoted.astype(jnp.int32)

        @pl.when(do_ins)
        def _insert():
            new_dict_ref[pl.ds(ins, 1), :] = xs_ref[...]
            new_dmin_ref[pl.ds(ins, 1), :] = xs_ref[:, 0:1]
            new_dmax_ref[pl.ds(ins, 1), :] = xmax.astype(new_dmax_ref.dtype)
            new_valid_ref[pl.ds(ins, 1), :] = jnp.ones((1, 1), jnp.int32)
            if eb:
                new_raw_ref[pl.ds(ins, 1), :] = raw_ref[...]


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=(
    "d_crit", "rel_tol", "use_minmax", "use_ks", "num_dict", "tile_d",
    "error_cumulative"))
def encode_step_pallas(xs_sorted, sorted_blocks, dmin, dmax, valid, count,
                       block_valid, *, d_crit: float, rel_tol: float,
                       num_dict: int, use_minmax: bool = True,
                       use_ks: bool = True, tile_d: int = TILE_D,
                       raw=None, raw_blocks=None,
                       error_bound=None,
                       error_cumulative: bool = False,
                       chan=None):
    """One fused encode step.

    ``xs_sorted`` (n,) sorted candidate; ``sorted_blocks`` (Dp, n) /
    ``dmin``/``dmax``/``valid`` (Dp,) the *padded* dictionary carry (Dp a
    ``tile_d`` multiple, pad rows ``valid=False``); ``count`` () int32 FIFO
    position; ``block_valid`` () bool ragged-padding mask.  ``num_dict`` is
    the logical D.  On the TPU ``tile_d`` must be a multiple of 8.

    Returns ``(new_sorted, new_dmin, new_dmax, new_valid, dec)`` where
    ``dec`` is (8,) int32 laid out by the ``DEC_*`` constants: the winning
    global index (or SENTINEL), is_hit, slot, overwrite, updated count.

    With ``error_bound`` set, ``raw`` (n,) and ``raw_blocks`` (Dp, n) carry
    the stream-order rows, the pointwise max|err| demotion joins the gate,
    ``dec[DEC_DEMOTED]`` flags a block the bound demoted, and the return
    becomes ``(new_sorted, new_dmin, new_dmax, new_valid, new_raw, dec)``.
    The bound is a traced scalar operand, not a compile-time constant:
    whether it is set (``None`` or not) picks the program, its value does
    not, so every bound shares one executable per shape.  It is rounded to
    the stored dtype first (``jnp.asarray(bound, dtype)``, as the
    comparison always was) and travels in SMEM as f32 (f64 for f64 data,
    which only the interpreter runs), a widening that is exact.

    ``chan`` is the optional (8,) f32 per-channel parameter operand of the
    masked mixed-mode scan (``CHAN_*`` layout: logical width as f32, the
    f32-rounded ``1/n``, the channel's d_crit, the cumulative-error and
    bound-armed flags).  When set, tail columns beyond the logical width
    must be +inf pads; the static ``d_crit``/``error_cumulative`` args are
    ignored in favor of the operand, and the kernel is bitwise identical
    to the static form at the unpadded width (DESIGN.md Sec. 13).
    """
    num_dp, n = sorted_blocks.shape
    check_tile_divisible(num_dp, tile_d, "encode_step_pallas")
    if not 1 <= num_dict <= num_dp:
        raise ValueError(f"num_dict={num_dict} outside [1, Dp={num_dp}]")
    if error_bound is not None:
        if raw is None or raw_blocks is None:
            raise ValueError("error_bound requires raw and raw_blocks")
        carry = BoundedCarry(
            sorted_blocks, raw_blocks, dmin.reshape(num_dp, 1),
            dmax.reshape(num_dp, 1),
            valid.astype(jnp.int32).reshape(num_dp, 1),
            jnp.asarray(count, jnp.int32))
        c, dec = encode_step_bounded(
            carry, xs_sorted, raw, block_valid, error_bound, d_crit=d_crit,
            rel_tol=rel_tol, num_dict=num_dict, use_minmax=use_minmax,
            use_ks=use_ks, tile_d=tile_d, error_cumulative=error_cumulative,
            chan=chan)
        return (c.sorted_blocks, c.dmin.reshape(num_dp),
                c.dmax.reshape(num_dp), c.valid.reshape(num_dp) != 0,
                c.raw_blocks, dec)
    grid = (num_dp // tile_d,)
    meta = jnp.stack([jnp.asarray(count, jnp.int32),
                      jnp.asarray(block_valid).astype(jnp.int32)])[None]
    kernel = functools.partial(
        _encode_step_kernel, float(d_crit), float(rel_tol), bool(use_minmax),
        bool(use_ks), False, bool(error_cumulative), int(num_dict),
        int(tile_d), chan is not None)
    row = pl.BlockSpec((1, n), lambda i: (0, 0))           # candidate: reused
    tile = pl.BlockSpec((tile_d, n), lambda i: (i, 0))     # streamed dict tile
    col_tile = pl.BlockSpec((tile_d, 1), lambda i: (i, 0))
    # constant index maps: carry-out lives in VMEM across the grid
    carry = pl.BlockSpec((num_dp, n), lambda i: (0, 0))
    col_carry = pl.BlockSpec((num_dp, 1), lambda i: (0, 0))
    in_specs = [_smem(), row, tile, col_tile, col_tile, col_tile]
    out_specs = [carry, col_carry, col_carry, col_carry, _smem()]
    out_shape = [
        jax.ShapeDtypeStruct((num_dp, n), sorted_blocks.dtype),
        jax.ShapeDtypeStruct((num_dp, 1), dmin.dtype),
        jax.ShapeDtypeStruct((num_dp, 1), dmax.dtype),
        jax.ShapeDtypeStruct((num_dp, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    ]
    operands = [meta, xs_sorted.reshape(1, n), sorted_blocks,
                dmin.reshape(num_dp, 1), dmax.reshape(num_dp, 1),
                valid.astype(jnp.int32).reshape(num_dp, 1)]
    if chan is not None:
        # channel-parameter block leads the operand list (kernel unpack)
        in_specs.insert(0, _smem())
        operands.insert(0, chan.reshape(1, -1))
    out = pallas_call(
        kernel,
        name="encode_step",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*operands)
    new_sorted, ndmin, ndmax, nvalid, dec = out
    outs = (new_sorted, ndmin.reshape(num_dp), ndmax.reshape(num_dp),
            nvalid.reshape(num_dp) != 0)
    return outs + (dec.reshape(8),)


class BoundedCarry(NamedTuple):
    """The error-bounded step's dictionary carry in the kernel's own
    layout, so that a scan threads it from step to step with no reshape,
    cast or copy: ``sorted_blocks``/``raw_blocks`` (Dp, n), ``dmin``/
    ``dmax`` (Dp, 1), ``valid`` (Dp, 1) int32, ``count`` () int32."""

    sorted_blocks: jax.Array
    raw_blocks: jax.Array
    dmin: jax.Array
    dmax: jax.Array
    valid: jax.Array
    count: jax.Array


@functools.partial(jax.jit, static_argnames=(
    "d_crit", "rel_tol", "use_minmax", "use_ks", "num_dict", "tile_d",
    "error_cumulative"))
def encode_step_bounded(carry: BoundedCarry, xs_sorted, raw, block_valid,
                        error_bound, *, d_crit: float, rel_tol: float,
                        num_dict: int, use_minmax: bool = True,
                        use_ks: bool = True, tile_d: int = TILE_D,
                        error_cumulative: bool = False, chan=None):
    """The error-bounded fused step on a :class:`BoundedCarry`: returns
    ``(new_carry, dec)`` with ``dec`` (8,) as in
    :func:`encode_step_pallas`.  The carry-out aliases the carry-in (the
    kernel writes its carry-out blocks back only after its last grid
    program has read every input tile), so inside a scan the carry is
    updated in place.  ``error_bound`` is the traced bound (see
    :func:`encode_step_pallas`)."""
    num_dp, n = carry.sorted_blocks.shape
    check_tile_divisible(num_dp, tile_d, "encode_step_bounded")
    if not 1 <= num_dict <= num_dp:
        raise ValueError(f"num_dict={num_dict} outside [1, Dp={num_dp}]")
    dtype = carry.raw_blocks.dtype
    meta = jnp.stack([carry.count,
                      jnp.asarray(block_valid).astype(jnp.int32)])[None]
    # the bound is rounded to the stored dtype, then widened (exactly) to
    # the SMEM word
    sdt = jnp.promote_types(dtype, jnp.float32)
    bound = jnp.asarray(error_bound, dtype).astype(sdt).reshape(1, 1)
    kernel = functools.partial(
        _encode_step_kernel, float(d_crit), float(rel_tol), bool(use_minmax),
        bool(use_ks), True, bool(error_cumulative), int(num_dict),
        int(tile_d), chan is not None)
    row = pl.BlockSpec((1, n), lambda i: (0, 0))           # candidate: reused
    tile = pl.BlockSpec((tile_d, n), lambda i: (i, 0))     # streamed dict tile
    col_tile = pl.BlockSpec((tile_d, 1), lambda i: (i, 0))
    # constant index maps: carry-out lives in VMEM across the grid
    full = pl.BlockSpec((num_dp, n), lambda i: (0, 0))
    col_full = pl.BlockSpec((num_dp, 1), lambda i: (0, 0))
    # kernel unpack order: [chan], bound, meta, xs, raw row, sorted tile,
    # raw tile, dmin, dmax, valid
    in_specs = [_smem(), _smem(), row, row, tile, tile, col_tile, col_tile,
                col_tile]
    operands = [bound, meta, xs_sorted.reshape(1, n), raw.reshape(1, n),
                carry.sorted_blocks, carry.raw_blocks, carry.dmin,
                carry.dmax, carry.valid]
    out_specs = [full, full, col_full, col_full, col_full, _smem()]
    out_shape = [
        jax.ShapeDtypeStruct((num_dp, n), carry.sorted_blocks.dtype),
        jax.ShapeDtypeStruct((num_dp, n), dtype),
        jax.ShapeDtypeStruct((num_dp, 1), carry.dmin.dtype),
        jax.ShapeDtypeStruct((num_dp, 1), carry.dmax.dtype),
        jax.ShapeDtypeStruct((num_dp, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    ]
    lead = 0
    if chan is not None:
        in_specs.insert(0, _smem())
        operands.insert(0, chan.reshape(1, -1))
        lead = 1
    # the five carry operands (after the bound, meta and the two rows)
    # become the first five outputs
    aliases = {lead + 4 + k: k for k in range(5)}
    new_sorted, new_raw, ndmin, ndmax, nvalid, dec = pallas_call(
        kernel,
        name="encode_step",
        grid=(num_dp // tile_d,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
    )(*operands)
    dec = dec.reshape(8)
    return BoundedCarry(new_sorted, new_raw, ndmin, ndmax, nvalid,
                        dec[DEC_COUNT]), dec
