"""Shared helpers for the AutoGNN Pallas TPU kernels.

TPU-adaptation notes (DESIGN.md §2):

* The UPE's prefix-sum adder network is realized as a Hillis–Steele
  log-depth shift-add scan — literally the paper's Fig. 12b hierarchy.
* The UPE's relocation router is a gather by the inverse permutation
  (``core.set_partition.gather_sources_from_counts``): a log-depth binary
  search over the monotone inclusive bucket-count columns finds the source
  of every output slot, and the move is one ``jnp.take`` — O(N·log N)
  versus the O(N²) one-hot MXU matmul it replaced.
  ``onehot_relocate_i32`` is kept as the MXU reference/benchmark router.
* Every kernel goes through :func:`pallas_call`, which picks the interpreter
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


def prefix_sum_tree(x: jnp.ndarray, axis: int = 0,
                    exclusive: bool = False) -> jnp.ndarray:
    """Hillis–Steele inclusive scan as a log-depth shift+add network.

    Static number of layers = ceil(log2(n)) — the UPE adder hierarchy.
    Pallas-TPU friendly: only static pads/slices and adds.
    """
    n = x.shape[axis]
    y = x
    d = 1
    while d < n:
        shifted = jnp.pad(y, [(d, 0) if a == axis else (0, 0)
                              for a in range(y.ndim)])
        sl = [slice(0, n) if a == axis else slice(None)
              for a in range(y.ndim)]
        y = y + shifted[tuple(sl)]
        d *= 2
    if exclusive:
        y = y - x
    return y


def onehot_relocate_i32(dest: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """out[dest[i]] = vals[i] via MXU one-hot matmul, exact for int32.

    dest: [N] int32 permutation. vals: [N] int32.
    onehot[j, i] = (dest[i] == j); out = onehot @ vals, with vals split into
    16-bit halves so the fp32 accumulate is exact.
    """
    n = dest.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)  # row index j
    onehot = (dest[None, :] == iota).astype(jnp.float32)  # [N(out), N(in)]
    lo = (vals & 0xFFFF).astype(jnp.float32)
    hi = ((vals >> 16) & 0x7FFF).astype(jnp.float32)
    sign = (vals < 0).astype(jnp.float32)
    out_lo = jax.lax.dot(onehot, lo[:, None],
                         preferred_element_type=jnp.float32)[:, 0]
    out_hi = jax.lax.dot(onehot, hi[:, None],
                         preferred_element_type=jnp.float32)[:, 0]
    out_sg = jax.lax.dot(onehot, sign[:, None],
                         preferred_element_type=jnp.float32)[:, 0]
    out = (out_lo.astype(jnp.int32) + (out_hi.astype(jnp.int32) << 16)
           + (out_sg.astype(jnp.int32) << 31))
    return out


def pad_pow2_1d(x: jnp.ndarray, multiple: int, fill) -> jnp.ndarray:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, (0, pad), constant_values=fill)
