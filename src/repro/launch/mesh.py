"""Mesh construction.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benchmarks see the 1 real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    Every sharded path in this repo is written for GSPMD propagation
    (``shard_map`` plus compiler-placed collectives around it). JAX's
    default ``Explicit`` axes put shardings into the types instead, and
    then a reshape of a sharded array, such as the cross-device merge
    rounds' ``ks.reshape(-1, 2, run)``, fails to type-check.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke/e2e tests)."""
    return make_mesh((1, 1), ("data", "model"))


# v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
