"""`pallas_call` that lowers for the device the call is placed on.

Every Pallas kernel in this package is a Mosaic (TPU) kernel. Whether it
can be compiled is a property of the platform the enclosing jit is
*lowered for* — not of the process's default backend: on a TPU host the
learner's step lowers for the TPU while actor inference is committed to
the host CPU device (runtime/loop.py `actor_device="cpu"`), and both
trace the same model code. `jax.lax.platform_dependent` makes that
choice inside the lowering itself: the compiled Mosaic kernel when the
program lowers for TPU, the interpreted kernel body (plain XLA ops,
what tier-1 exercises on CPU) anywhere else.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, interpret: bool | None = None, **kwargs):
    """`pl.pallas_call(kernel, **kwargs)`, compiled on TPU and
    interpreted elsewhere, chosen per lowering platform.

    `interpret=True/False` forces one mode (tests pin the interpreter;
    an on-chip parity check pins the compiled kernel)."""
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(
            *args, tpu=compiled, default=interpreted
        )

    return call
