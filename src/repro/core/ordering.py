"""Edge Ordering (paper §II-B, §V-A, Fig. 15, Algorithm 1).

Sort the COO edge array by (dst, src). The paper concatenates each pair into a
64-bit key and LSD-radix-sorts it chunk-by-chunk on UPEs, then merges sorted
chunks. JAX disables int64 by default, so two equivalent 32-bit formulations
are provided, selected by ``mode``:

* ``"packed"`` — the paper's concatenated key, shrunk to fit int32: when
  ``2 · bits(n_nodes) ≤ 31`` (all graphs ≤ 32767 nodes — always true for the
  subgraph re-conversion inside ``sample_subgraph``), pack
  ``(dst << src_bits) | src`` into ONE key with the edge id as payload and
  run a single global sort, then unpack ``(dst, src)``. Half the sort passes
  and half the merge rounds of the LSD scheme.
* ``"two_pass"`` — the LSD fallback for wide VID spaces: a stable global
  sort by src followed by a stable global sort by dst. Under ``"xla_sort"``
  (with no caller ``sort_fn``) the two passes are ONE two-key comparison
  sort over the (dst, src) columns (``xla_lex_sort``): the pair is the
  whole record, so equal keys are identical records and the unstable sort
  is exact.
* ``"auto"`` (default) — ``"packed"`` whenever the VID space allows it.

Both modes produce bit-identical output (stable sort by the lexicographic
(dst, src) key; ties keep original order either way).

Each global sort runs under a **strategy** (paper §V: the framework picks
the reduction structure per workload — ``EngineConfig.sort_strategy``,
"auto" scored by ``costmodel.resolve_sort_strategy``):

* ``"chunked_merge"`` — (a) chunk-local LSD radix sort (the UPE chunk,
  Pallas kernel in kernels/radix_sort.py) + (b) ceil(log_k(C)) parallel
  k-ary merge rounds (``fan_in``). The merge rank trick — position of an
  element is its own index plus its searchsorted rank in every sibling
  run — is the contention-free analog of the paper's w/2-per-cycle UPE
  merge network, and is itself a set-counting operation (count of sibling
  elements less-than). Relocation is a gather by the inverse merge
  permutation (no scatter in the lowered program); the fused VMEM merge
  kernel (kernels/merge.py) can collapse the first rounds into one pass
  over HBM via ``merge_fn``.
* ``"global_radix"`` — merge-free: every LSD digit pass stable-partitions
  the WHOLE array through the two-level tiled router
  (``set_partition.tiled_digit_sources``), O(digit_passes·N) with zero
  merge rounds (guarded in tests/test_perf_paths.py).
* ``"xla_sort"`` — the platform's native comparison-sort unit (one
  ``lax.sort``; see ``xla_stable_sort_by_key`` and ``xla_lex_sort``).

Sentinel handling: padded entries carry SENTINEL; keys are clipped to
``n_nodes`` (one past any valid VID) before sorting so the radix width stays
ceil(log2(n_nodes+1)) bits, and restored afterwards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .graph import COO, SENTINEL
from .set_count import rank_in_sorted
from .set_partition import (radix_sort_by_key, radix_sort_keys,
                            tiled_digit_sources)

# THE chunk-width default (UPE chunk = elements sorted fully in VMEM).
# ``EngineConfig.w_upe`` defaults to this same constant and every sorter
# entry point resolves ``chunk=None`` through it, so a caller that skips the
# config cannot silently get a different ladder depth than the engine path.
DEFAULT_CHUNK = 4096


def _bits_for(n: int) -> int:
    return max(1, int(n).bit_length())


# Keys-only contract: everywhere a (keys, vals) pair flows through the sort
# stack — merge_sorted, _chunk_sort, merge_rounds, stable_sort_by_key and
# the chunk_sort_fn / merge_fn / sort_fn hooks — ``vals=None`` selects a
# keys-only variant that routes no payload through the gathers. The packed
# Ordering uses it: the packed (dst, src) key IS the data, so the edge-id
# payload the two-pass scheme needs would be sorted and then discarded,
# roughly doubling the bytes every chunk sort and merge round moves
# (guarded by a compiled-HLO bytes-accessed test in tests/test_perf_paths.py).


def supports_packed_keys(n_nodes: int) -> bool:
    """True when (dst, src) pairs fit one non-negative int32 packed key."""
    return 2 * _bits_for(n_nodes) <= 31


def merge_sorted(a_keys, a_vals, b_keys, b_vals, unroll: bool = False):
    """Stable parallel merge of two sorted (key, val) runs.

    A-elements win ties (stability). Fully parallel and scatter-free:
    ``pos_a`` (own index + rank within the sibling run) is strictly
    increasing, so for output slot j the count ``r_a`` of a-elements placed
    at slots ≤ j is one more binary search; slot j holds ``a[r_a - 1]`` when
    that element sits exactly at j, else ``b[j - r_a]``. Relocation is two
    gathers — the inverse-permutation router — instead of four scatters.

    ``a_vals``/``b_vals`` may both be None (keys-only merge, the packed
    Ordering path); then ``out_v`` is None and no payload bytes move.
    ``unroll`` statically unrolls the two rank searches (zero while ops —
    the fused-epilogue lowering the delta-merge rung dispatches when
    ``costmodel`` prices it; the ladder's rungs keep the looped default).
    """
    la = a_keys.shape[0]
    lb = b_keys.shape[0]
    n = la + lb
    # rank_in_sorted: jnp.searchsorted's 'scan' method is sequential over
    # queries (a 65536-trip while loop at Reddit scale) and its 'sort'
    # method replicates an XLA sort per device under GSPMD; the explicit
    # log-depth binary search stays parallel AND sharded (§Perf convert).
    pos_a = jnp.arange(la, dtype=jnp.int32) + rank_in_sorted(
        b_keys, a_keys, side="left", unroll=unroll)
    j = jnp.arange(n, dtype=jnp.int32)
    r_a = rank_in_sorted(pos_a, j, side="right", unroll=unroll)
    ia = jnp.clip(r_a - 1, 0, la - 1)
    from_a = (r_a > 0) & (jnp.take(pos_a, ia, mode="clip") == j)
    ib = jnp.clip(j - r_a, 0, lb - 1)
    out_k = jnp.where(from_a, jnp.take(a_keys, ia, mode="clip"),
                      jnp.take(b_keys, ib, mode="clip"))
    if a_vals is None:
        return out_k, None
    sel = from_a.reshape((n,) + (1,) * (a_vals.ndim - 1))
    out_v = jnp.where(sel, jnp.take(a_vals, ia, axis=0, mode="clip"),
                      jnp.take(b_vals, ib, axis=0, mode="clip"))
    return out_k, out_v


def merge_sorted_k(kr: jnp.ndarray, vr: jnp.ndarray | None
                   ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Stable k-way merge of ``k`` sorted runs — one ladder rung, fan-in k.

    ``kr`` [k, run] (``vr`` [k, run] or None). Earlier runs win ties, so the
    output equals folding ``merge_sorted`` pairwise left-to-right — but in
    ONE full-array pass instead of log₂ k: the output position of element i
    of run r is its own index plus its rank in every sibling run (ties count
    against later runs only), and slot j recovers its source run by the same
    inverse-rank trick as the 2-way merge. k(k-1) cross-run rank searches +
    k slot-rank searches, all log-depth and scatter-free; ``fan_in`` in
    ``merge_rounds`` trades this extra per-round search work for
    log₂(k)-fold fewer full-array (HBM) rounds.
    """
    k, run = kr.shape
    if k == 2:  # the 2-way rank-merge needs half the searches (pos_a and
        # the slot ranks only — b-placement falls out of the inverse)
        if vr is None:
            return merge_sorted(kr[0], None, kr[1], None)
        return merge_sorted(kr[0], vr[0], kr[1], vr[1])
    n = k * run
    own = jnp.arange(run, dtype=jnp.int32)
    pos = []
    for r_i in range(k):  # static fan-in
        p = own
        for s in range(k):
            if s == r_i:
                continue
            # elements of an EARLIER run precede on ties (stability)
            p = p + rank_in_sorted(kr[s], kr[r_i],
                                   side="right" if s < r_i else "left")
        pos.append(p)
    j = jnp.arange(n, dtype=jnp.int32)
    out_k = jnp.zeros((n,), kr.dtype)
    out_v = None if vr is None else jnp.zeros((n,) + vr.shape[2:], vr.dtype)
    for r_i in range(k):
        cnt = rank_in_sorted(pos[r_i], j, side="right")
        ia = jnp.clip(cnt - 1, 0, run - 1)
        hit = (cnt > 0) & (jnp.take(pos[r_i], ia, mode="clip") == j)
        out_k = jnp.where(hit, jnp.take(kr[r_i], ia, mode="clip"), out_k)
        if vr is not None:
            sel = hit.reshape((n,) + (1,) * (vr.ndim - 2))
            out_v = jnp.where(sel, jnp.take(vr[r_i], ia, axis=0,
                                            mode="clip"), out_v)
    return out_k, out_v


def merge_round_fan_ins(n: int, run: int, fan_in: int = 2) -> list[int]:
    """Per-round fan-ins of the merge ladder for ``n`` elements in sorted
    runs of ``run`` — ``len()`` of this list is the ladder's round count
    (the ``costmodel.merge_round_count`` term and the HLO guard in
    tests/test_perf_paths.py both derive from it).

    Run counts are pow2 in practice (pow2 capacities, pow2 chunk), but the
    ladder stays well-defined off that path: a round's fan-in is the
    largest divisor of the remaining run count ≤ ``fan_in``, or the
    count's smallest factor when it has no divisor in reach (e.g. 3 runs
    under fan_in=2 merge in one 3-way rung). A chunk that does not tile
    ``n`` at all contributes no further rounds (the sorters assert
    divisibility; the cost model just needs a finite answer).
    """
    out = []
    while run < n:
        count = n // run
        if count < 2:  # chunk does not tile n — no full rounds remain
            break
        k = min(max(2, fan_in), count)
        while count % k and k > 2:
            k -= 1
        if count % k:  # no divisor ≤ fan_in: take the smallest factor
            k = next(d for d in range(2, count + 1) if count % d == 0)
        out.append(k)
        run *= k
    return out


def _chunk_sort(keys, vals, chunk: int, key_bits: int, radix_bits: int,
                map_batch: int):
    """Locally sort each chunk of ``chunk`` elements (stable LSD radix).

    ``map_batch`` = UPE lane count: chunks are processed ``map_batch`` at a
    time (lax.map batching bounds working-set memory). map_batch <= 0 means
    all lanes at once (full vmap — the distributed/sharded configuration,
    where the chunk axis is sharded over devices). ``vals=None`` sorts the
    keys alone (no payload gather per digit pass).
    """
    n = keys.shape[0]
    assert n % chunk == 0, (n, chunk)
    kc = keys.reshape(-1, chunk)
    if vals is None:
        def sort_keys(k):
            return radix_sort_keys(k, key_bits=key_bits,
                                   radix_bits=radix_bits)

        if map_batch <= 0 or map_batch >= kc.shape[0]:
            ks = jax.vmap(sort_keys)(kc)
        else:
            ks = jax.lax.map(sort_keys, kc, batch_size=map_batch)
        return ks.reshape(n), None
    vc = vals.reshape(-1, chunk)

    def sort_one(k, v):
        return radix_sort_by_key(v, k, key_bits=key_bits,
                                 radix_bits=radix_bits)

    if map_batch <= 0 or map_batch >= kc.shape[0]:
        ks, vs = jax.vmap(sort_one)(kc, vc)
    else:
        ks, vs = jax.lax.map(lambda kv: sort_one(*kv), (kc, vc),
                             batch_size=map_batch)
    return ks.reshape(n), vs.reshape(n)


def merge_rounds(ks: jnp.ndarray, vs: jnp.ndarray, run: int,
                 merge_fn=None, fan_in: int = 2
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """k-ary merge ladder: sorted runs of length ``run`` → one sorted array.

    ``fan_in`` runs are merged per rung (``merge_sorted_k``), so the ladder
    takes ceil(log_k(n/run)) full-array rounds instead of log₂ — each round
    is an HBM round-trip at the jnp level, which is exactly what the
    chunked_merge strategy pays and the global_radix strategy avoids.
    ``merge_fn(ks, vs, run) -> (ks, vs, new_run)`` optionally fuses the
    first rounds into one kernel pass over VMEM-resident run groups
    (kernels/merge.py), collapsing per-round HBM round-trips; remaining
    (large-run) rounds run at the jnp level. Shared by the single-device
    sorter below and the mesh-sharded sorter (engine/shard.py), which
    continues this exact ladder from its per-device runs — one
    implementation keeps the bit-identical guarantee honest. ``vs=None``
    merges keys alone (``merge_fn`` implementations accept and return the
    None payload).
    """
    n = ks.shape[0]
    if merge_fn is not None and run < n:
        ks, vs, run = merge_fn(ks, vs, run)
    for k in merge_round_fan_ins(n, run, fan_in):
        kr = ks.reshape(-1, k, run)
        if vs is None:
            ks = jax.vmap(lambda a: merge_sorted_k(a, None)[0])(kr)
        else:
            vr = vs.reshape(-1, k, run)
            ks, vs = jax.vmap(merge_sorted_k)(kr, vr)
            vs = vs.reshape(n)
        run *= k
        ks = ks.reshape(n)
    return ks, vs


def _global_radix_passes(keys, vals, key_bits: int, tile: int,
                         radix_bits: int, digit_pass_fn=None):
    """The merge-free digit-pass loop shared by ``global_radix_sort_by_key``
    and the per-device local sorts of ``engine.shard`` (which restore
    sentinels only after the cross-device rounds). ``digit_pass_fn(keys,
    vals, shift) -> (keys, vals)`` swaps in the Pallas tiled
    histogram/rank-gather pair (kernels/radix_sort.py); shifts are static
    (the pass loop is unrolled), so kernels compile once per digit."""
    n_buckets = 1 << radix_bits
    n_passes = max(1, -(-key_bits // radix_bits))  # ceil div
    for p in range(n_passes):  # static unroll — zero merge rounds, no carry
        shift = p * radix_bits
        if digit_pass_fn is not None:
            keys, vals = digit_pass_fn(keys, vals, shift)
            continue
        digit = (keys >> shift) & (n_buckets - 1)
        src = tiled_digit_sources(digit, n_buckets, tile)
        keys = jnp.take(keys, src, mode="clip")
        if vals is not None:
            vals = jnp.take(vals, src, axis=0, mode="clip")
    return keys, vals


def global_radix_sort_by_key(keys: jnp.ndarray, vals: jnp.ndarray,
                             key_bound: int, tile: int | None = None,
                             radix_bits: int = 4, digit_pass_fn=None
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Global stable LSD radix sort with ZERO merge rounds — the
    ``global_radix`` Ordering strategy.

    Every digit pass relocates the WHOLE array through one two-level
    gather (``set_partition.tiled_digit_sources``: per-tile partition
    ranks + rank arithmetic over the small [T, B] histogram tables), so the
    cost is O(digit_passes · N) with no log₂(N/chunk) pairwise-merge ladder
    on top — the regime where the chunked_merge strategy loses to a plain
    XLA sort at scale (BENCH_convert.json). Same sentinel contract as
    ``stable_sort_by_key``; ``vals=None`` sorts keys alone.
    """
    n = keys.shape[0]
    tile = min(DEFAULT_CHUNK if tile is None else tile, n)
    key_bits = _bits_for(key_bound)
    clipped = jnp.minimum(keys, jnp.int32(key_bound))
    ks, vs = _global_radix_passes(clipped, vals, key_bits, tile, radix_bits,
                                  digit_pass_fn=digit_pass_fn)
    ks = jnp.where(ks >= key_bound, SENTINEL, ks)
    return ks, vs


def xla_stable_sort_by_key(keys: jnp.ndarray, vals: jnp.ndarray,
                           key_bound: int
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The platform's native global sort as an Ordering strategy
    (``"xla_sort"``).

    One ``lax.sort`` — the comparison-sort *unit* the host/accelerator
    ships (std::stable_sort-class on CPU, the sort HLO on GPU) — with the
    same clip/restore sentinel contract as the radix strategies, keys-only
    when ``vals is None``. This is NOT the DGL-style baseline it gets
    benchmarked against: the baseline lexsorts the raw (src, dst) columns
    (two argsorts + payload gathers) and pays a third sort inside its
    ``searchsorted`` pointer build, while this strategy sorts the packed
    key once with no payload and the pointer build stays the rank search.
    On CPU hosts the native sort's fused compare loop beats any
    jnp-composed radix pass structure at scale — which is exactly why the
    strategy axis exists (§V: pick the reduction structure per workload
    per platform). Under GSPMD an XLA sort replicates (see
    ``set_count.rank_in_sorted``), so the mesh-sharded engine keeps its
    own sorter.
    """
    clipped = jnp.minimum(keys, jnp.int32(key_bound))
    if vals is None:
        ks, vs = jnp.sort(clipped), None
    else:
        ks, vs = jax.lax.sort([clipped, vals], num_keys=1, is_stable=True)
    ks = jnp.where(ks >= key_bound, SENTINEL, ks)
    return ks, vs


def xla_lex_sort(dst: jnp.ndarray, src: jnp.ndarray, bound: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two-pass Ordering on ``"xla_sort"`` as ONE two-key sort.

    Both columns are clipped to ``bound`` so padding sorts last, and one
    ``lax.sort`` orders the (dst, src) pair lexicographically. The pair is
    the whole record, so records with equal keys are identical and the
    result equals the two stable LSD passes bit for bit. It is unstable on
    purpose: XLA:TPU makes a sort stable by sorting an extra ``iota``
    operand, which would put a third array back through the sort network.
    Sentinels are restored as the two passes restore them.
    """
    d = jnp.minimum(dst, jnp.int32(bound))
    s = jnp.minimum(src, jnp.int32(bound))
    d, s = jax.lax.sort([d, s], num_keys=2, is_stable=False)
    d = jnp.where(d >= bound, SENTINEL, d)
    s = jnp.where((s >= bound) | (d == SENTINEL), SENTINEL, s)
    return d, s


def stable_sort_by_key(keys: jnp.ndarray, vals: jnp.ndarray, key_bound: int,
                       chunk: int | None = None, radix_bits: int = 4,
                       map_batch: int = 4, chunk_sort_fn=None,
                       merge_fn=None, strategy: str = "chunked_merge",
                       fan_in: int = 2, digit_pass_fn=None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Global stable sort under a ``strategy``:

    * ``"chunked_merge"`` — chunked UPE radix sort + k-ary merge ladder
      (``fan_in`` runs per rung; log_k(N/chunk) full-array rounds).
    * ``"global_radix"`` — merge-free global LSD radix sort
      (``global_radix_sort_by_key``; ``chunk`` becomes the histogram tile).
    * ``"xla_sort"`` — the platform's native comparison sort
      (``xla_stable_sort_by_key``; no chunk/radix knobs apply).

    ``key_bound``: exclusive upper bound of valid keys (sentinels are clipped
    to key_bound and restored). ``chunk=None`` resolves to ``DEFAULT_CHUNK``
    (= the ``EngineConfig.w_upe`` default — one routed constant, see
    DEFAULT_CHUNK). ``chunk_sort_fn`` lets the Pallas UPE kernel replace the
    jnp chunk sorter; ``merge_fn`` lets the fused Pallas merge kernel absorb
    the first merge rounds (see ``merge_rounds``); ``digit_pass_fn`` lets
    the Pallas tiled digit-pass pair replace the jnp global-radix pass.
    ``vals=None`` runs the whole stack keys-only and returns ``(keys,
    None)`` — every hook receives the None payload and must honor it.
    """
    n = keys.shape[0]
    chunk = min(DEFAULT_CHUNK if chunk is None else chunk, n)
    if strategy == "global_radix":
        return global_radix_sort_by_key(keys, vals, key_bound, tile=chunk,
                                        radix_bits=radix_bits,
                                        digit_pass_fn=digit_pass_fn)
    if strategy == "xla_sort":
        return xla_stable_sort_by_key(keys, vals, key_bound)
    if strategy != "chunked_merge":
        raise ValueError(f"unknown sort strategy {strategy!r}")
    assert n % chunk == 0, f"size {n} must be divisible by chunk {chunk}"
    key_bits = _bits_for(key_bound)
    clipped = jnp.minimum(keys, jnp.int32(key_bound))

    if chunk_sort_fn is None:
        ks, vs = _chunk_sort(clipped, vals, chunk, key_bits, radix_bits,
                             map_batch)
    else:
        ks, vs = chunk_sort_fn(clipped, vals, chunk, key_bits)

    ks, vs = merge_rounds(ks, vs, chunk, merge_fn=merge_fn, fan_in=fan_in)
    ks = jnp.where(ks >= key_bound, SENTINEL, ks)
    return ks, vs


def edge_ordering(coo: COO, chunk: int | None = None, radix_bits: int = 4,
                  map_batch: int = 4, chunk_sort_fn=None,
                  sort_fn=None, merge_fn=None, mode: str = "auto",
                  keys_only: bool = True, strategy: str = "chunked_merge",
                  fan_in: int = 2, digit_pass_fn=None) -> COO:
    """Sort edges by (dst, src) — packed single-pass or two-pass LSD.

    On ``strategy="xla_sort"`` with no ``sort_fn``, ``"two_pass"`` runs as
    one two-key comparison sort (``xla_lex_sort``) — exact, as ties are
    identical (dst, src) records. The radix strategies sort by one key a
    pass and keep the two passes.

    ``sort_fn(keys, vals, key_bound) -> (keys, vals)`` overrides the global
    stable sorter — the mesh-sharded engine passes its shard_map sorter so
    both paths share ONE copy of the packing/two-pass/sentinel-restore
    logic. ``mode``: "auto" (packed when the VID space fits), "packed", or
    "two_pass"; requesting "packed" on a too-wide VID space raises.
    ``strategy``/``fan_in``/``digit_pass_fn`` select and feed the global
    sorter's reduction structure (see ``stable_sort_by_key``) — strategy
    "auto" is resolved *above* this layer (``costmodel.resolve_sort_strategy``
    via ``pipeline.convert``), keeping Ordering itself model-free.
    ``keys_only`` (packed mode only): sort the packed key with no payload —
    the (dst, src) pair is recovered by unpacking the key itself, so the
    edge-id payload the two-pass scheme rides along would be pure waste;
    False retained for A/B bytes-moved measurement.
    """
    lex_sort = sort_fn is None and strategy == "xla_sort"
    if sort_fn is None:
        def sort_fn(k, v, bound):
            return stable_sort_by_key(k, v, bound, chunk=chunk,
                                      radix_bits=radix_bits,
                                      map_batch=map_batch,
                                      chunk_sort_fn=chunk_sort_fn,
                                      merge_fn=merge_fn, strategy=strategy,
                                      fan_in=fan_in,
                                      digit_pass_fn=digit_pass_fn)
    bound = coo.n_nodes
    if mode == "auto":
        mode = "packed" if supports_packed_keys(bound) else "two_pass"
    if mode == "packed":
        if not supports_packed_keys(bound):
            raise ValueError(
                f"packed-key ordering needs 2*bits(n_nodes) <= 31; "
                f"n_nodes={bound} does not fit — use mode='two_pass'")
        bits = _bits_for(bound)
        # clip BOTH columns to bound so sentinels stay in-radix; the packed
        # key orders by (dst, src) lexicographically in one stable sort
        d = jnp.minimum(coo.dst, jnp.int32(bound))
        s = jnp.minimum(coo.src, jnp.int32(bound))
        packed = (d << bits) | s
        if keys_only:  # the packed key IS the data — no payload to move
            payload = None
        else:  # A/B baseline: ride the (discarded) edge id along
            payload = jnp.arange(coo.capacity, dtype=jnp.int32)
        pk, _ = sort_fn(packed, payload, (bound << bits) | bound)
        # unpack; all-sentinel rows were restored to SENTINEL by the sorter
        mask = (1 << bits) - 1
        sent = pk == SENTINEL
        dst2 = jnp.where(sent, SENTINEL, pk >> bits)
        src2 = jnp.where(sent, SENTINEL, pk & mask)
        dst2 = jnp.where(dst2 >= bound, SENTINEL, dst2)
        src2 = jnp.where((src2 >= bound) | (dst2 == SENTINEL), SENTINEL,
                         src2)
        return COO(dst=dst2, src=src2, n_edges=coo.n_edges,
                   n_nodes=coo.n_nodes)
    if mode != "two_pass":
        raise ValueError(f"unknown ordering mode {mode!r}")
    if lex_sort:
        dst2, src2 = xla_lex_sort(coo.dst, coo.src, bound)
        return COO(dst=dst2, src=src2, n_edges=coo.n_edges,
                   n_nodes=coo.n_nodes)
    # pass 1: by src (secondary key), dst rides along as payload
    src1, dst1 = sort_fn(coo.src, coo.dst, bound)
    # pass 2: by dst (primary key), src rides along; stability keeps src order
    dst2, src2 = sort_fn(dst1, src1, bound)
    # restore src sentinels (payload positions that were padding)
    src2 = jnp.where(dst2 == SENTINEL, SENTINEL, src2)
    return COO(dst=dst2, src=src2, n_edges=coo.n_edges, n_nodes=coo.n_nodes)


def edge_ordering_xla(coo: COO) -> COO:
    """Comparison-sort baseline (what DGL-on-GPU effectively does)."""
    order = jnp.lexsort((coo.src, coo.dst))
    return COO(dst=coo.dst[order], src=coo.src[order],
               n_edges=coo.n_edges, n_nodes=coo.n_nodes)
