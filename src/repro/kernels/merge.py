"""Fused VMEM merge kernel — the UPE "merging" stage without HBM laps.

``core.ordering.merge_rounds`` runs log_k(n/chunk) rank-merge rounds; at the
jnp level every round is a full-array HBM round-trip (read the run group,
write the merged run). This kernel loads one super-block of
``run · prod(fan-ins)`` elements per grid step and performs all those
rounds while the runs stay VMEM-resident, writing each super-block back
exactly once — the TPU analog of the paper's w/2-per-cycle UPE merge
network chewing through a resident chunk. Each in-VMEM round merges up to
``fan_in`` runs at once (``core.ordering.merge_sorted_k``), matching the
k-ary ladder the jnp level continues for runs larger than the VMEM budget;
the mesh-sharded engine (engine/shard.py) continues the same ladder
cross-device. The merge tree refinement — and the bit-identical
stable-sort guarantee — is unchanged; only the memory traffic schedule
differs.

The per-group merge is the scatter-free rank-merge from
``core.ordering.merge_sorted_k`` (log-depth binary searches + gathers), so
the whole kernel lowers without scatters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ordering import merge_round_fan_ins, merge_sorted_k

from .common import pallas_call

# Elements of one (keys, vals) super-block held in VMEM per grid step.
# 2 arrays × in+out × 4 B × 65536 = 2 MiB — comfortably inside the ~16 MiB
# VMEM budget alongside the binary-search scratch.
DEFAULT_MAX_BLOCK = 65536


def _round_fan_ins(n: int, run: int, max_block: int,
                   fan_in: int) -> list[int]:
    """Fused-round fan-ins: the prefix of the ladder's ONE shape oracle
    (``core.ordering.merge_round_fan_ins``) whose super-block still fits
    the VMEM budget — rungs past the budget continue at the jnp level
    with exactly the rung structure the oracle (and the cost model's
    ``merge_round_count``) prescribes, so the fused and unfused halves of
    the ladder can never drift apart."""
    fans = []
    block = run
    for k in merge_round_fan_ins(n, run, fan_in):
        if block * k > max_block:
            break
        fans.append(k)
        block *= k
    return fans


def _make_kernel(run: int, fan_ins: list[int], keys_only: bool = False):
    def rounds(ks, vs):
        r = run
        for k in fan_ins:  # static fan-ins, runs stay resident
            kr = ks.reshape(-1, k, r)
            if vs is None:
                ks = jax.vmap(lambda a: merge_sorted_k(a, None)[0])(kr)
            else:
                vr = vs.reshape(-1, k, r)
                ks, vs = jax.vmap(merge_sorted_k)(kr, vr)
                vs = vs.reshape(-1)
            r *= k
            ks = ks.reshape(-1)
        return ks, vs

    if keys_only:
        def kernel(key_ref, out_key_ref):
            out_key_ref[...], _ = rounds(key_ref[...], None)

        return kernel

    def kernel(key_ref, val_ref, out_key_ref, out_val_ref):
        out_key_ref[...], out_val_ref[...] = rounds(key_ref[...],
                                                    val_ref[...])

    return kernel


def fused_merge_rounds(keys: jnp.ndarray, vals: jnp.ndarray, run: int,
                       max_block: int = DEFAULT_MAX_BLOCK,
                       fan_in: int = 2
                       ) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Merge sorted runs of length ``run`` up to length ``max_block`` with
    all intermediate rounds fused in VMEM, ``fan_in`` runs per round.

    Returns ``(keys, vals, new_run)`` — the ``merge_fn`` contract of
    ``core.ordering.merge_rounds``; ``new_run`` stays a Python int (this
    function is deliberately not jitted — callers trace it inside the
    pipeline jit, and the merge ladder's remaining-round count is static).
    No-op (rounds that don't fit a block run at the jnp level) when even
    one widening exceeds ``max_block`` or the array does not tile into
    super-blocks. ``vals=None`` fuses keys-only merge rounds (half the
    VMEM per super-block, half the HBM bytes per pass — the packed
    Ordering path).
    """
    n = keys.shape[0]
    fan_ins = _round_fan_ins(n, run, max_block, fan_in)
    if not fan_ins:
        return keys, vals, run
    block = run
    for k in fan_ins:
        block *= k
    grid = n // block
    if vals is None:
        out_k = pallas_call(
            _make_kernel(run, fan_ins, keys_only=True),
            grid=(grid,),
            in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
            out_specs=pl.BlockSpec((block,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((n,), keys.dtype),
        )(keys)
        return out_k, None, block
    out_k, out_v = pallas_call(
        _make_kernel(run, fan_ins),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), keys.dtype),
            jax.ShapeDtypeStruct((n,), vals.dtype),
        ],
    )(keys, vals)
    return out_k, out_v, block


def make_pallas_merge_fn(fan_in: int = 2):
    """merge_fn for ``core.ordering.merge_rounds`` with the ladder fan-in
    routed from ``EngineConfig.merge_fan_in`` (one knob, jnp + Pallas)."""

    def merge_fn(keys, vals, run):
        return fused_merge_rounds(keys, vals, run, fan_in=fan_in)

    return merge_fn


def pallas_merge_fn(keys, vals, run):
    """Adapter matching core.ordering.merge_rounds(merge_fn=...)."""
    return fused_merge_rounds(keys, vals, run)
