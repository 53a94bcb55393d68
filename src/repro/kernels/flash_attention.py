"""Pallas TPU flash attention (forward): the LM substrate's hot spot.

The dry-run HLO showed ~3.3 TB/device/step of attention-tile traffic on the
32B train cell — every [Sq_blk, KV_blk] probability tile materialized ~8×
by XLA CPU fusion. This kernel keeps the tile pipeline entirely in VMEM:
per (batch·head, q-block) grid step, the kv-block loop runs inside the
kernel with running (m, l, acc) scratch, writing only the final [bq, dh]
output — the FlashAttention schedule tiled for the MXU (block dims multiples
of 128) and VMEM (default blocks: 512×512×128 ≈ 1.4 MB working set).

Backward uses the same tiling (see models/attention.py custom_vjp for the
schedule); the dry-run §Perf adjustment is justified by this kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .common import pallas_call

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, causal: bool, window, logit_cap, scale: float):
    j = pl.program_id(2)  # kv block (minor)
    nj = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    run = True
    if causal:
        # whole block above the diagonal → skip (guarded compute)
        run = (j * bk) <= (qi * bq + bq - 1)

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, dh]
        k = k_ref[0].astype(jnp.float32)  # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= q_pos >= kv_pos
        if window is not None:
            mask &= q_pos - kv_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)  # [bk, dh]
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "logit_cap",
                                             "bq", "bk"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int | None = None,
                        logit_cap: float | None = None, bq: int = 512,
                        bk: int = 512) -> jnp.ndarray:
    """q [BH, Sq, dh]; k, v [BH, Skv, dh] (heads pre-flattened/expanded).

    Sq % bq == 0, Skv % bk == 0; dh should be a multiple of 128 on real
    TPUs (any dh works in interpret mode).
    """
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    scale = 1.0 / math.sqrt(dh)
    grid = (bh, sq // bq, skv // bk)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, logit_cap=logit_cap,
                               scale=scale)
    return pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
    )(q, k, v)


def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         logit_cap=None, bq=512, bk=512):
    """[B,H,Sq,dh] wrapper with GQA expansion (kernel wants flat BH)."""
    b, h, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    g = h // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    out = flash_attention_fwd(
        q.reshape(b * h, sq, dh), k.reshape(b * h, skv, dh),
        v.reshape(b * h, skv, dh), causal=causal, window=window,
        logit_cap=logit_cap, bq=min(bq, sq), bk=min(bk, skv))
    return out.reshape(b, h, sq, dh)


# ---------------------------------------------------------------- backward
def _fwd_with_lse(q, k, v, *, causal, window, logit_cap, bq, bk):
    """Reference-free fwd returning (out, lse) for the backward kernels
    (jnp scan — tiny memory; only out/lse are kept)."""
    bh, sq, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    nb = k.shape[1] // bk

    def body(carry, j):
        m, l, acc = carry
        kj = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
        s = jnp.einsum("zqd,zcd->zqc", q.astype(jnp.float32) * scale,
                       kj.astype(jnp.float32))
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        qpos = jnp.arange(sq)[:, None]
        kpos = j * bk + jnp.arange(bk)[None, :]
        mask = jnp.ones((sq, bk), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, -1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "zqc,zcd->zqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((bh, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, sq), jnp.float32)
    a0 = jnp.zeros((bh, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out, lse


def _recompute_tile(q_blk, k_blk, lse_blk, *, qi, j, bq, bk, causal, window,
                    logit_cap, scale):
    """(p, mask, s_cap) for one (q-block, kv-block) tile, from saved lse."""
    s_raw = jax.lax.dot_general(q_blk * scale, k_blk,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    s_cap = (logit_cap * jnp.tanh(s_raw / logit_cap)
             if logit_cap is not None else s_raw)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= q_pos >= kv_pos
    if window is not None:
        mask &= q_pos - kv_pos < window
    s = jnp.where(mask, s_cap, NEG_INF)
    p = jnp.exp(s - lse_blk[:, None])
    return p, mask, s_cap


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_scr, *, bq, bk, causal, window, logit_cap, scale):
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (j * bk) <= (qi * bq + bq - 1) if causal else True

    @pl.when(run)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, mask, s_cap = _recompute_tile(
            q, kb, lse_ref[0], qi=qi, j=j, bq=bq, bk=bk, causal=causal,
            window=window, logit_cap=logit_cap, scale=scale)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0][:, None])
        if logit_cap is not None:
            t = s_cap / logit_cap
            ds = ds * (1.0 - t * t)
        ds = jnp.where(mask, ds, 0.0)
        acc_scr[...] += jax.lax.dot(ds, kb,
                                    preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _fin():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, bq, bk, causal, window,
                logit_cap, scale):
    i = pl.program_id(2)  # q block (minor)
    ni = pl.num_programs(2)
    j = pl.program_id(1)  # kv block

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (j * bk) <= (i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, mask, s_cap = _recompute_tile(
            q, kb, lse_ref[0], qi=i, j=j, bq=bq, bk=bk, causal=causal,
            window=window, logit_cap=logit_cap, scale=scale)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # pᵀ·do
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0][:, None])
        if logit_cap is not None:
            t = s_cap / logit_cap
            ds = ds * (1.0 - t * t)
        ds = jnp.where(mask, ds, 0.0)
        dk_scr[...] += jax.lax.dot_general(
            ds, q * scale, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # dsᵀ·(q·scale)

    @pl.when(i == ni - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "logit_cap",
                                             "bq", "bk"))
def flash_attention_bwd(q, k, v, dout, *, causal=True, window=None,
                        logit_cap=None, bq=128, bk=128):
    """Flash attention backward via two Pallas passes (FA2 split):
    pass A accumulates dq per q-block over kv-blocks; pass B accumulates
    dk/dv per kv-block over q-blocks. P is recomputed per tile from the
    saved lse — no [Sq, Skv] residual ever hits HBM.

    q/k/v/dout: [BH, S*, dh]. Returns (dq, dk, dv) in input dtypes.
    """
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0
    scale = 1.0 / math.sqrt(dh)
    out, lse = _fwd_with_lse(q, k, v, causal=causal, window=window,
                             logit_cap=logit_cap, bq=bq, bk=bk)
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)  # [BH, Sq]

    kern_a = functools.partial(_dq_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, logit_cap=logit_cap,
                               scale=scale)
    dq = pallas_call(
        kern_a,
        grid=(bh, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
    )(q, k, v, dout, lse, delta)

    kern_b = functools.partial(_dkv_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, logit_cap=logit_cap,
                               scale=scale)
    dk, dv = pallas_call(
        kern_b,
        grid=(bh, skv // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, dh), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, dh), k.dtype),
            jax.ShapeDtypeStruct((bh, skv, dh), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv
