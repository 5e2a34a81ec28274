"""Where a Pallas kernel runs: compiled on the accelerator, interpreted on
the CPU.

Pallas compiles kernels for TPUs (Mosaic) and GPUs; the CPU backend can only
run them through the Pallas interpreter.  :func:`pallas_call` decides when
the call is lowered, from the platform it is lowered *for*:
``lax.platform_dependent`` lowers only the branch of that platform, so a
TPU lowering always holds the compiled kernel (``tpu_custom_call``) and
never the interpreter, and CPU arrays (tests, ``JAX_PLATFORMS=cpu``) always
run interpreted.  A kernel Mosaic refuses therefore fails on the chip
instead of hiding behind the interpreter.  Importing this module touches no
backend.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

__all__ = ["pallas_call"]


def pallas_call(kernel, *, name: str, **kw):
    """``pl.pallas_call(kernel, name=name, **kw)``, interpreted only when
    lowered for the CPU backend and compiled for every other platform.
    ``name`` is the kernel's stable name: the compiled custom call is the
    HLO instruction ``<name>.N``, which is what a profiler trace's op line
    shows for it."""
    if "interpret" in kw:
        raise TypeError("interpret mode follows the lowering platform; "
                        "do not pass interpret=")
    compiled = pl.pallas_call(kernel, name=name, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, name=name, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call
