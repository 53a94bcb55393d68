"""Shared helpers for the AutoGNN Pallas TPU kernels.

Every kernel goes through :func:`pallas_call`, which picks the interpreter
or Mosaic from the platform the program is lowered for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` that runs in the Pallas interpreter where the
    program is lowered for the CPU and compiles through Mosaic everywhere
    else. ``jax.lax.platform_dependent`` makes the choice per lowering,
    so one jitted function serves both and nothing is decided at import.
    """
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    compiled = pl.pallas_call(kernel, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call


def pad_pow2_1d(x: jnp.ndarray, multiple: int, fill) -> jnp.ndarray:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, (0, pad), constant_values=fill)
