"""SCR set-count kernel (paper Fig. 13): comparators + adder tree.

Grid = (target blocks × element blocks). Each tile compares a block of
targets against a block of elements ([T, E] comparator array) and reduces
along lanes — the adder tree — accumulating int32 partial counts into the
target-block output. n_scr ↔ target block height, w_scr ↔ element block
width: the EngineConfig knobs map 1:1 onto this BlockSpec tiling.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pallas_call

# Targets run down the sublanes as a [T, 1] column and elements along the
# lanes as a [1, E] row, so each tile's comparator array is a plain 2-D
# broadcast and both blocks meet Mosaic's (8, 128) block rule.


def _target_spec(t_block):
    return pl.BlockSpec((t_block, 1), lambda i, j: (i, 0))


def _element_spec(e_block):
    return pl.BlockSpec((1, e_block), lambda i, j: (0, j))


def _count_kernel(tgt_ref, elem_ref, out_ref):
    j = pl.program_id(1)  # element-block index (minor grid dim)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tgt = tgt_ref[...]  # [T, 1]
    elem = elem_ref[...]  # [1, E]
    cmp = (elem < tgt).astype(jnp.int32)  # [T, E] comparators
    out_ref[...] += jnp.sum(cmp, axis=1, keepdims=True)  # adder tree


@partial(jax.jit, static_argnames=("t_block", "e_block"))
def set_count_less(elements: jnp.ndarray, targets: jnp.ndarray,
                   t_block: int = 256, e_block: int = 2048) -> jnp.ndarray:
    """counts[t] = |{x in elements : x < targets[t]}| (SCR Reshaper mode).

    elements [E] int32 (pad with INT32_MAX — never < any target),
    targets [T] int32 (pad arbitrarily; callers slice).
    """
    e = elements.shape[0]
    t = targets.shape[0]
    assert e % e_block == 0 and t % t_block == 0, (e, e_block, t, t_block)
    out = pallas_call(
        _count_kernel,
        grid=(t // t_block, e // e_block),
        in_specs=[_target_spec(t_block), _element_spec(e_block)],
        out_specs=_target_spec(t_block),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
    )(targets.reshape(t, 1), elements.reshape(1, e))
    return out.reshape(t)


def _filter_kernel(tgt_ref, key_ref, pay_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tgt = tgt_ref[...]  # [T, 1]
    keys = key_ref[...]  # [1, E]
    pays = pay_ref[...]  # [1, E]
    hit = keys == tgt  # [T, E] equality comparators
    enc = jnp.max(jnp.where(hit, pays + 1, 0), axis=1,
                  keepdims=True)  # OR tree
    out_ref[...] = jnp.maximum(out_ref[...], enc)


@partial(jax.jit, static_argnames=("t_block", "e_block"))
def filter_tree_lookup(keys: jnp.ndarray, payloads: jnp.ndarray,
                       targets: jnp.ndarray, t_block: int = 256,
                       e_block: int = 2048
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """SCR Reindexer mode: payload-or-miss per target via the filter tree.

    keys must be unique; pad keys with INT32_MIN (never equal to a target).
    Returns (payload, hit) — payload is -1 on miss.
    """
    e = keys.shape[0]
    t = targets.shape[0]
    assert e % e_block == 0 and t % t_block == 0
    enc = pallas_call(
        _filter_kernel,
        grid=(t // t_block, e // e_block),
        in_specs=[_target_spec(t_block), _element_spec(e_block),
                  _element_spec(e_block)],
        out_specs=_target_spec(t_block),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.int32),
    )(targets.reshape(t, 1), keys.reshape(1, e),
      payloads.reshape(1, e)).reshape(t)
    hit = enc > 0
    return jnp.where(hit, enc - 1, -1), hit


def pallas_count_fn(sorted_dst, targets):
    """Adapter for core.reshaping.build_pointer_array(count_fn=...)."""
    from .common import pad_pow2_1d
    e_block = min(2048, sorted_dst.shape[0])
    t_block = min(256, targets.shape[0])
    elems = pad_pow2_1d(sorted_dst, e_block, 0x7FFFFFFF)
    t = targets.shape[0]
    tgts = pad_pow2_1d(targets, t_block, 0)
    out = set_count_less(elems, tgts, t_block=t_block, e_block=e_block)
    return out[:t]
