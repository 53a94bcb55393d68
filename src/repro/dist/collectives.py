"""Sharded attention collectives (shard_map).

Two decode layouts, matching launch/steps.py's cache shardings:

* ``sharded_decode_attention`` — KV heads sharded over the ``model`` axis.
  Each shard runs dense ``decode_attention`` on its own head group (GQA
  query heads travel with their KV head), then an all-gather over ``model``
  reassembles the head dim. Zero per-step collectives besides that one
  epilogue gather — decode stays bandwidth-bound on the local cache shard.
* ``sharded_decode_attention_seq`` — long-context (B=1) flash-decoding:
  the *sequence* dim of the cache is sharded over the dp axes, every shard
  computes a partial softmax (m, l, acc) over its slice, and the shards
  combine via an LSE max/sum reduction (pmax + two psums).

Both validate bit-for-close against the dense reference in
tests/test_dist.py under 8 virtual devices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.attention import (decode_attention,
                                    decode_attention_partial,
                                    dequantize_kv)

from .sharding import _axes_size, dp_axes, model_axis_size


def sharded_decode_attention(mesh: Mesh, q: jnp.ndarray,
                             k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                             cache_len: jnp.ndarray, *,
                             window: int | None = None,
                             logit_cap: float | None = None) -> jnp.ndarray:
    """Head-sharded decode: q [B,H,1,dh], caches [B,Hkv,S,dh] with Hkv
    sharded over ``model``. Falls back to the dense path when the mesh has
    no model axis or the KV heads don't cover it."""
    b, h, _, dh = q.shape
    hkv = k_cache.shape[1]
    msz = model_axis_size(mesh)
    if msz <= 1 or hkv % msz or hkv < msz:
        return decode_attention(q, k_cache, v_cache, cache_len,
                                window=window, logit_cap=logit_cap)
    # regroup q kv-major ([B,Hkv,G,dh]) so the head shards line up with
    # their KV shards; head index h = kv * G + g matches decode_attention's
    # internal GQA grouping, so the epilogue gather restores dense order
    qg = q.reshape(b, hkv, h // hkv, dh)

    def body(qg_l, k_l, v_l, clen):
        bb, hkv_l, g, dh_l = qg_l.shape
        q_l = qg_l.reshape(bb, hkv_l * g, 1, dh_l)
        o = decode_attention(q_l, k_l, v_l, clen, window=window,
                             logit_cap=logit_cap)  # [B, H/msz, 1, dh]
        return jax.lax.all_gather(o, "model", axis=1, tiled=True)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, "model", None, None),
                             P(None, "model", None, None),
                             P(None, "model", None, None),
                             P(None)),
                   out_specs=P(None, None, None, None),
                   check_vma=False)
    return fn(qg, k_cache, v_cache, cache_len).astype(q.dtype)


def sharded_decode_attention_seq(mesh: Mesh, q: jnp.ndarray,
                                 k_cache: jnp.ndarray,
                                 v_cache: jnp.ndarray,
                                 cache_len: jnp.ndarray, *,
                                 logit_cap: float | None = None,
                                 k_scale: jnp.ndarray | None = None,
                                 v_scale: jnp.ndarray | None = None
                                 ) -> jnp.ndarray:
    """Sequence-sharded decode (flash-decoding LSE combine): caches
    [B,Hkv,S,dh] with S sharded over the dp axes. Each shard masks its
    slice by *global* position, computes partial (m, l, acc), and the
    epilogue rescales by exp(m - pmax(m)) before psum-reducing.

    When the KV heads cover the ``model`` axis they stay sharded over it
    too (query heads travel with their KV head, as in
    ``sharded_decode_attention``), so the only model-axis collective is the
    small per-step output gather — the huge cache is never replicated.
    int8 caches pass their scales through and dequantize *per local shard*
    inside the body, never materializing a widened full cache."""
    b, h, _, dh = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    dp = dp_axes(mesh)
    n = _axes_size(mesh, dp)
    if n <= 1 or s % n:
        return decode_attention(q, k_cache, v_cache, cache_len,
                                logit_cap=logit_cap, k_scale=k_scale,
                                v_scale=v_scale)
    msz = model_axis_size(mesh)
    head_sharded = msz > 1 and hkv % msz == 0 and hkv >= msz
    hspec = "model" if head_sharded else None
    # kv-major regroup so head shards line up with their KV shard
    qg = q.reshape(b, hkv, h // hkv, dh)

    def body(qg_l, k_l, v_l, clen, *scales):
        if scales:
            k_l = dequantize_kv(k_l, scales[0])
            v_l = dequantize_kv(v_l, scales[1])
        bb, hkv_l, g, dh_l = qg_l.shape
        s_l = k_l.shape[2]
        # linear shard index over the (possibly multi-axis) dp tuple,
        # row-major to match how shard_map splits the sequence dim
        start = s_l * sum(jax.lax.axis_index(a) * _trailing_size(mesh, dp, i)
                          for i, a in enumerate(dp))
        pos = start + jnp.arange(s_l)
        valid = pos[None, :] < clen[:, None]  # [B, S_l], global positions
        q_l = qg_l.reshape(bb, hkv_l * g, 1, dh_l)
        m, l, acc = decode_attention_partial(q_l, k_l, v_l, valid,
                                             logit_cap=logit_cap)
        mg = jax.lax.pmax(m, dp)
        corr = jnp.exp(m - mg)
        l_sum = jax.lax.psum(l * corr, dp)
        acc_sum = jax.lax.psum(acc * corr[..., None], dp)
        out = acc_sum / jnp.maximum(l_sum[..., None], 1e-30)
        out = out.reshape(bb, hkv_l * g, 1, dh_l)
        if head_sharded:
            out = jax.lax.all_gather(out, "model", axis=1, tiled=True)
        return out

    cache_spec = P(None, hspec, dp, None)
    in_specs = [P(None, hspec, None, None), cache_spec, cache_spec, P()]
    args = [qg, k_cache, v_cache, cache_len]
    if k_scale is not None:
        in_specs += [cache_spec, cache_spec]
        args += [k_scale, v_scale]
    fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=P(), check_vma=False)
    return fn(*args).astype(q.dtype)


def _trailing_size(mesh: Mesh, axes, i: int) -> int:
    """Product of dp-axis extents after position ``i`` (row-major linear
    index of a multi-axis dp shard)."""
    return _axes_size(mesh, axes[i + 1:])


def seq_sharded_decode_attn_fn(mesh: Mesh):
    """Adapter: an ``attn_fn`` for ``models.transformer.lm_decode_step``
    that routes cache attention through ``sharded_decode_attention_seq``.

    This is what the ``long_500k`` decode cell (launch/steps.py) injects:
    the 524288-token KV cache is sequence-sharded over the dp axes
    (``lm_cache_shardings(..., seq_sharded=True)``, heads staying on
    ``model``) and each decode step LSE-combines per-shard partial
    softmaxes instead of gathering the cache. int8 scales pass through and
    dequantize per shard; explicit-window callers fall back to the dense
    path (ring-buffer caches already bound the window, so decode passes
    None).
    """

    def attn_fn(q, k_cache, v_cache, cache_len, *, window=None,
                logit_cap=None, k_scale=None, v_scale=None):
        if window is not None:
            return decode_attention(q, k_cache, v_cache, cache_len,
                                    window=window, logit_cap=logit_cap,
                                    k_scale=k_scale, v_scale=v_scale)
        return sharded_decode_attention_seq(mesh, q, k_cache, v_cache,
                                            cache_len, logit_cap=logit_cap,
                                            k_scale=k_scale,
                                            v_scale=v_scale)

    return attn_fn
