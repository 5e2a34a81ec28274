"""The Pallas kernels of the encode and decode path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
rank-1 blocks that are not 128-lane multiples, scalar stores to VMEM, a
kernel that outgrows scoped VMEM.  These tests compile each kernel at the
paper's Table I widths (D=255 padded to 256; n=32 for magnitude blocks,
n=111 for B=112 delta payloads) for a chip that is described, not attached,
and check that the compiled program holds the kernel (``tpu_custom_call``),
under its stable name, and not the interpreter.  Each kernel also
compiles vmapped over a channel axis, the shape batched sessions, the
coalescer and the sharded scans dispatch.  Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a fixture: only one process at a time
may load the TPU compiler library, so it must never happen at import.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import decode as decode_mod
from repro.core.encoder import _FUSED_TILE_SWEEP
from repro.kernels.dict_match import dict_match_pallas
from repro.kernels.encode_step import encode_step_pallas
from repro.kernels.seq_cumsum import seq_cumsum_pallas

NUM_DICT = 255
DP = 256          # NUM_DICT padded to a tile multiple
WIDTHS = (32, 111)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _named_kernel(text, kernel):
    """The compiled program holds the Mosaic kernel as the custom call
    named ``kernel`` (``%<kernel>.N``), the name a trace's op line shows."""
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*"
                     rf'custom_call_target="tpu_custom_call"', text), kernel


def _compile(fn, *shapes, kernel, channels=0):
    """Compile ``fn`` for the described chip, vmapped over a leading
    channel axis of every operand when ``channels``; the program must hold
    the Mosaic kernel under its stable name."""
    if channels:
        fn = jax.vmap(fn)
        shapes = [jax.ShapeDtypeStruct((channels, *a.shape), a.dtype,
                                       sharding=a.sharding) for a in shapes]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    _named_kernel(text, kernel)
    return text


def _carry(sharding, n):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    return [s((n,), jnp.float32), s((DP, n), jnp.float32),
            s((DP,), jnp.float32), s((DP,), jnp.float32),
            s((DP,), jnp.bool_), s((), jnp.int32), s((), jnp.bool_)]


@pytest.mark.parametrize("channels", [0, 4])
@pytest.mark.parametrize("tile_d", _FUSED_TILE_SWEEP)
@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("variant", ["static", "error_bound", "chan"])
def test_encode_step_compiles(one_chip, variant, n, tile_d, channels):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    kw = dict(d_crit=0.3, rel_tol=0.5, num_dict=NUM_DICT, tile_d=tile_d)
    args = _carry(one_chip, n)
    if variant == "static":
        fn = functools.partial(encode_step_pallas, **kw)
    elif variant == "error_bound":
        # delta payloads bound the cumulative error (the in-kernel column
        # walk); the plain bound is the same kernel minus that walk
        def fn(*a):
            *carry, raw, raw_blocks = a
            return encode_step_pallas(*carry, raw=raw, raw_blocks=raw_blocks,
                                      error_bound=0.1,
                                      error_cumulative=n == 111, **kw)
        args += [s((n,), jnp.float32), s((DP, n), jnp.float32)]
    else:
        def fn(*a):
            *carry, chan = a
            return encode_step_pallas(*carry, chan=chan, **kw)
        args += [s((8,), jnp.float32)]
    _compile(fn, *args, kernel="encode_step", channels=channels)


@pytest.mark.parametrize("channels", [0, 4])
@pytest.mark.parametrize("n", WIDTHS)
def test_dict_match_compiles(one_chip, n, channels):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(functools.partial(dict_match_pallas, rel_tol=0.5),
             s((n,), jnp.float32), s((DP, n), jnp.float32),
             s((DP,), jnp.float32), s((DP,), jnp.float32),
             kernel="dict_match", channels=channels)


@pytest.mark.parametrize("P", [31, 111])
def test_seq_cumsum_compiles(one_chip, P):
    _compile(seq_cumsum_pallas,
             jax.ShapeDtypeStruct((64, P), jnp.float32, sharding=one_chip),
             kernel="seq_cumsum")


@pytest.mark.parametrize("mode,block_size,value_range", [
    (decode_mod.MODE_STD, 32, None),
    (decode_mod.MODE_DELTA, 112, (0.0, 360.0))])
def test_device_decode_compiles(one_chip, mode, block_size, value_range):
    """The f32 device reconstruct as ``decode._run_device`` dispatches it
    (32-bit mode: under 64-bit mode the kernel's index maps return int64,
    which Mosaic refuses)."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    P = block_size - (mode != decode_mod.MODE_STD)
    last = (s((64, block_size), jnp.int32) if mode == decode_mod.MODE_STD
            else s((64,), jnp.float32))
    text = decode_mod._device_fn("pallas", mode, value_range).lower(
        s((128, P), jnp.float32), s((64,), jnp.int32), last
    ).compile().as_text()
    if mode == decode_mod.MODE_DELTA:
        _named_kernel(text, "seq_cumsum")  # the sequential-cumsum kernel
