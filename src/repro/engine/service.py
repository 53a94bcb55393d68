"""PreprocService — the preprocessing engine front-end.

Subsumes ``core/reconfig.py``'s Engine/DynPre with one service object that
does what the paper's runtime does end to end:

1. **profile** the workload (<0.1 ms host-side graph metadata capture),
2. **score** the pre-compiled bitstream library with the Table-I cost model
   and switch configurations when the predicted gain amortizes the
   reconfiguration cost,
3. **shape-bucket** inputs to power-of-two capacities so the number of
   distinct compiled programs stays O(log(max_e) · log(max_b) · |library|),
4. **dispatch** to a *module-level* jit cache keyed by
   ``(EngineConfig.key, bucket)`` — the bitstreams-staged-in-DRAM analog.

The module-level entry points matter: ``core.pipeline.preprocess`` is
jitted once at import, so every service (and every legacy ``Engine`` shim)
shares one compilation cache. Re-selecting a previously used
``(config, bucket)`` pair therefore performs **zero** recompiles — asserted
via ``preprocess_cache_size()`` in tests/test_engine_service.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import pipeline
from repro.core.costmodel import (Calibration, EngineConfig, Workload,
                                  bitstream_library)
from repro.core.delta import EdgeDelta
from repro.core.graph import COO, SENTINEL, next_pow2, pad_to
from repro.core.reconfig import (RECONFIG_S_PARTIAL, ReconfigDecision,
                                 decide)
from repro.models import gnn


# ---------------------------------------------------------------------------
# Module-level jitted entry points (ONE cache per process, not per object).
# ---------------------------------------------------------------------------
# ``pipeline.preprocess`` is itself the module-level jitted program; the
# aliases below are the service's dispatch table. ``sample_jit`` / ``convert_jit``
# cover consumers that convert once and sample per step (data/sampler.py).
preprocess_jit = pipeline.preprocess
sample_jit = jax.jit(pipeline.sample_subgraph, static_argnames=("fanouts",
                                                                "cfg"))
sample_batched_jit = jax.jit(pipeline.sample_subgraph_batched,
                             static_argnames=("fanouts", "cfg"))
convert_jit = jax.jit(pipeline.convert, static_argnames=("cfg",))
apply_delta_jit = jax.jit(pipeline.apply_delta,
                          static_argnames=("cfg", "mode", "out_capacity"))
# convert → whole-graph inference: GAT logits of every node from the CSC
# ``convert_jit`` returns, (csc, features, params) -> (logits, counters)
gat_infer_jit = jax.jit(gnn.gat_infer, static_argnames=("cfg", "_sizes"))


def preprocess_cache_size() -> int:
    """Number of compiled programs behind the module-level preprocess entry
    (the compile-counter tests assert against).

    Example::

        >>> isinstance(preprocess_cache_size(), int)
        True
    """
    try:
        return int(preprocess_jit._cache_size())
    except AttributeError as e:  # private PjitFunction API (jax upgrade?)
        raise NotImplementedError(
            "jax.jit cache introspection (_cache_size) is unavailable on "
            "this JAX version — update preprocess_cache_size() to the new "
            "API") from e


def bucket_coo(coo: COO) -> COO:
    """Pad the edge buffer to its pow2 capacity bucket (SENTINEL tail).

    Example::

        >>> from repro.core.graph import COO
        >>> coo = COO.from_arrays([0, 2, 1], [1, 0, 2], n_nodes=3,
        ...                       capacity=3)
        >>> b = bucket_coo(coo)
        >>> b.capacity, int(b.n_edges)
        (4, 3)
        >>> bucket_coo(b) is b  # already-pow2 buffers pass through
        True
    """
    cap = next_pow2(coo.capacity)
    if cap == coo.capacity:
        return coo
    return COO(dst=pad_to(coo.dst, cap, SENTINEL),
               src=pad_to(coo.src, cap, SENTINEL),
               n_edges=coo.n_edges, n_nodes=coo.n_nodes)


def sample_batched_cache_size() -> int:
    """Compiled-program count behind the module-level batched-sample entry
    (serve-side zero-recompile guards assert against it).

    Example::

        >>> isinstance(sample_batched_cache_size(), int)
        True
    """
    try:
        return int(sample_batched_jit._cache_size())
    except AttributeError as e:  # private PjitFunction API (jax upgrade?)
        raise NotImplementedError(
            "jax.jit cache introspection (_cache_size) is unavailable on "
            "this JAX version — update sample_batched_cache_size() to the "
            "new API") from e


def bucket_seed_rows(seed_rows: jnp.ndarray) -> jnp.ndarray:
    """Pad [S, B] seed rows to the pow2 per-row bucket with SENTINEL (the
    same invariant as :func:`bucket_batch`, applied per slot row: padding
    seeds have degree 0 and never claim new VIDs).

    Example::

        >>> import jax.numpy as jnp
        >>> rows = bucket_seed_rows(jnp.zeros((2, 3), jnp.int32))
        >>> rows.shape
        (2, 4)
        >>> b = jnp.zeros((2, 4), jnp.int32)
        >>> bucket_seed_rows(b) is b  # already-pow2 rows pass through
        True
    """
    cap = next_pow2(seed_rows.shape[1])
    if cap == seed_rows.shape[1]:
        return seed_rows
    return jnp.pad(seed_rows, ((0, 0), (0, cap - seed_rows.shape[1])),
                   constant_values=int(SENTINEL))


def apply_delta_cache_size() -> int:
    """Compiled-program count behind the module-level delta-update entry
    (the serve-side streaming-update zero-recompile guards assert against
    it).

    Example::

        >>> isinstance(apply_delta_cache_size(), int)
        True
    """
    try:
        return int(apply_delta_jit._cache_size())
    except AttributeError as e:  # private PjitFunction API (jax upgrade?)
        raise NotImplementedError(
            "jax.jit cache introspection (_cache_size) is unavailable on "
            "this JAX version — update apply_delta_cache_size() to the "
            "new API") from e


def bucket_delta(delta: EdgeDelta) -> EdgeDelta:
    """Pad both delta streams to the pow2 delta bucket (SENTINEL tails).

    The bucket is the jit-cache axis for updates: every delta up to the
    bucket's capacity re-enters the SAME compiled ``apply_delta`` program
    (padded rows are SENTINEL in both columns, which the merge treats as
    absent).

    Example::

        >>> from repro.core.delta import EdgeDelta
        >>> d = EdgeDelta.from_arrays([0, 1, 2], [1, 2, 0], [0], [1],
        ...                           n_nodes=4)
        >>> b = bucket_delta(d)
        >>> b.capacity, int(b.n_ins), int(b.n_del)
        (4, 3, 1)
        >>> bucket_delta(b) is b  # already-pow2 buffers pass through
        True
    """
    cap = next_pow2(delta.capacity)
    if cap == delta.capacity:
        return delta
    return EdgeDelta(ins_dst=pad_to(delta.ins_dst, cap, SENTINEL),
                     ins_src=pad_to(delta.ins_src, cap, SENTINEL),
                     del_dst=pad_to(delta.del_dst, cap, SENTINEL),
                     del_src=pad_to(delta.del_src, cap, SENTINEL),
                     n_ins=delta.n_ins, n_del=delta.n_del,
                     n_nodes=delta.n_nodes)


def bucket_batch(batch_nodes: jnp.ndarray) -> jnp.ndarray:
    """Pad the seed-node list to its pow2 bucket with SENTINEL (sentinel
    seeds have degree 0 and never claim new VIDs, so real batch nodes keep
    the first new VIDs exactly as with the unpadded batch).

    Example::

        >>> import jax.numpy as jnp
        >>> b = bucket_batch(jnp.arange(3, dtype=jnp.int32))
        >>> b.shape
        (4,)
        >>> b[:3].tolist()  # real seeds unchanged, SENTINEL tail
        [0, 1, 2]
    """
    cap = next_pow2(batch_nodes.shape[0])
    if cap == batch_nodes.shape[0]:
        return batch_nodes
    return pad_to(batch_nodes, cap, SENTINEL)


@dataclasses.dataclass
class ServiceStats:
    """Dispatch counters one :class:`PreprocService` accumulates.

    Example::

        >>> s = ServiceStats()
        >>> (s.n_dispatches, s.n_reconfigs, s.n_unique_keys)
        (0, 0, 0)
    """

    n_dispatches: int = 0
    n_reconfigs: int = 0
    n_unique_keys: int = 0  # distinct (EngineConfig.key, bucket) pairs


class PreprocService:
    """The preprocessing engine as a long-lived service.

    One service instance per workload stream; all instances share the
    module-level jit caches. When constructed with a ``mesh`` whose dp
    extent is > 1, dispatches route through the sharded engine
    (``engine.shard``); otherwise through the single-device pipeline.

    Example — profile, score, dispatch (paper's DynPre mode)::

        >>> import jax, jax.numpy as jnp, numpy as np
        >>> from repro.core.graph import COO, random_coo
        >>> rng = np.random.default_rng(0)
        >>> dst, src = random_coo(rng, 64, 200)
        >>> coo = COO.from_arrays(dst, src, 64, capacity=256)
        >>> svc = PreprocService(fanouts=(2, 2))
        >>> sub = svc.preprocess(coo, jnp.arange(4, dtype=jnp.int32),
        ...                      jax.random.PRNGKey(0))
        >>> int(sub.order[0])  # seed nodes keep the first new VIDs
        0
        >>> svc.stats.n_dispatches, svc.stats.n_unique_keys
        (1, 1)
    """

    def __init__(self, fanouts: tuple[int, ...],
                 library: list[EngineConfig] | None = None,
                 cal: Calibration | None = None,
                 mesh=None,
                 switch_threshold: float = 1.5,
                 reconfig_cost_s: float = RECONFIG_S_PARTIAL):
        self.fanouts = tuple(fanouts)
        self.library = library or bitstream_library()
        self.cal = cal or Calibration()
        self.mesh = mesh
        self.threshold = switch_threshold
        self.reconfig_cost_s = reconfig_cost_s
        self.active_cfg: EngineConfig | None = None
        self.stats = ServiceStats()
        self._keys_seen: set[tuple[str, tuple[int, int]]] = set()

    # ------------------------------------------------------------- profiling
    def profile(self, coo: COO, batch_size: int,
                bucketed: bool = False) -> Workload:
        """Light-weight graph metadata capture (paper: <0.1 ms host-side).

        ``bucketed`` scores the pow2 capacity bucket instead of the exact
        edge count, making the selected config a pure function of the
        bucket — that is what bounds the number of compiled programs to
        O(log(max_e) · log(max_b)): every graph in a bucket re-selects the
        same ``(EngineConfig.key, bucket)`` pair and hits the jit cache.

        Example::

            >>> from repro.core.graph import COO
            >>> coo = COO.from_arrays([0, 1], [1, 0], n_nodes=2,
            ...                       capacity=3)
            >>> svc = PreprocService(fanouts=(2,))
            >>> svc.profile(coo, batch_size=8, bucketed=True).e
            4
            >>> svc.profile(coo, batch_size=8).e  # exact edge count
            2
        """
        e = next_pow2(coo.capacity) if bucketed else int(coo.n_edges)
        return Workload(n=coo.n_nodes, e=e, l=len(self.fanouts),
                        k=max(self.fanouts), b=batch_size)

    def decide(self, w: Workload) -> ReconfigDecision:
        """Score ``w`` against the library (Table-I cost model) and decide
        whether the predicted gain amortizes the reconfiguration cost.
        The candidate is a library entry with both dispatch axes resolved
        (``costmodel.choose_config`` pins ``sort_strategy`` AND
        ``reindex_strategy``), so the dispatched program — merge ladder,
        radix passes and the fused-vs-looped SCR epilogue alike — is the
        one the model priced.

        Example::

            >>> import dataclasses
            >>> svc = PreprocService(fanouts=(2,))
            >>> d = svc.decide(Workload(n=100, e=1000, l=1, k=2, b=16))
            >>> dataclasses.replace(d.config, sort_strategy="auto",
            ...                     reindex_strategy="auto") in svc.library
            True
            >>> d.config.sort_strategy != "auto"  # pinned by the model
            True
            >>> d.config.reindex_strategy in ("fused", "unfused")
            True
        """
        return decide(w, self.active_cfg, self.library, self.cal,
                      self.threshold, self.reconfig_cost_s)

    def select(self, coo: COO, batch_size: int) -> EngineConfig:
        """Profile + score; switch the active configuration if warranted.

        Example::

            >>> from repro.core.graph import COO
            >>> coo = COO.from_arrays([0, 1], [1, 0], n_nodes=2)
            >>> svc = PreprocService(fanouts=(2,))
            >>> svc.select(coo, batch_size=16) is svc.active_cfg
            True
        """
        d = self.decide(self.profile(coo, batch_size, bucketed=True))
        if d.reconfigure or self.active_cfg is None:
            self.active_cfg = d.config
            self.stats.n_reconfigs += 1
        return self.active_cfg

    # ------------------------------------------------------------- dispatch
    def _dp_size(self) -> int:
        from .shard import _dp
        return _dp(self.mesh)[1]

    def preprocess(self, coo: COO, batch_nodes: jnp.ndarray, key: jax.Array,
                   cfg: EngineConfig | None = None):
        """Bucket, select, dispatch. Returns the sampled ``Subgraph``.

        Passing an explicit ``cfg`` pins the configuration (the paper's
        StatPre/AutoPre modes); omitting it runs DynPre selection. See the
        class docstring for a runnable end-to-end example.
        """
        coo_b = bucket_coo(coo)
        bn_b = bucket_batch(jnp.asarray(batch_nodes, jnp.int32))
        cfg = cfg or self.select(coo_b, int(bn_b.shape[0]))
        bucket = (coo_b.capacity, int(bn_b.shape[0]))
        self.stats.n_dispatches += 1
        self._keys_seen.add((cfg.key, bucket))
        self.stats.n_unique_keys = len(self._keys_seen)
        if self._dp_size() > 1:
            from .shard import jit_shard_preprocess
            return jit_shard_preprocess(self.mesh)(
                coo_b, bn_b, fanouts=self.fanouts, key=key, cfg=cfg)
        return preprocess_jit(coo_b, bn_b, self.fanouts, key, cfg)

    def sample_batched(self, csc, seed_rows: jnp.ndarray, keys: jax.Array,
                       cfg: EngineConfig | None = None):
        """Slot-batched sampling dispatch: bucket, select, dispatch.

        The serve-side sibling of :meth:`preprocess`: ``seed_rows`` [S, B]
        is per-row SENTINEL-padded to its pow2 bucket, the configuration
        is pinned (``cfg``) or DynPre-selected on the sampling workload,
        and the dispatch is accounted under the ``(EngineConfig.key,
        (S, B_bucket))`` key — re-dispatching an already-seen pair hits
        the one module-level :data:`sample_batched_jit` cache.

        Example::

            >>> import jax, jax.numpy as jnp, numpy as np
            >>> from repro.core import pipeline
            >>> from repro.core.graph import COO, random_coo
            >>> rng = np.random.default_rng(0)
            >>> dst, src = random_coo(rng, 64, 200)
            >>> coo = COO.from_arrays(dst, src, 64, capacity=256)
            >>> csc = pipeline.convert(coo)
            >>> svc = PreprocService(fanouts=(2, 2))
            >>> rows = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
            >>> keys = jax.random.split(jax.random.PRNGKey(0), 2)
            >>> sub = svc.sample_batched(csc, rows, keys)
            >>> sub.order.shape[0]  # leading slot axis
            2
            >>> svc.stats.n_unique_keys
            1
        """
        rows = bucket_seed_rows(jnp.asarray(seed_rows, jnp.int32))
        if cfg is None:
            w = Workload(n=csc.n_nodes, e=int(csc.idx.shape[0]),
                         l=len(self.fanouts), k=max(self.fanouts),
                         b=int(rows.shape[1]))
            d = self.decide(w)
            if d.reconfigure or self.active_cfg is None:
                self.active_cfg = d.config
                self.stats.n_reconfigs += 1
            cfg = self.active_cfg
        bucket = (int(rows.shape[0]), int(rows.shape[1]))
        self.stats.n_dispatches += 1
        self._keys_seen.add((cfg.key, bucket))
        self.stats.n_unique_keys = len(self._keys_seen)
        return sample_batched_jit(csc, rows, self.fanouts, keys, cfg)

    def apply_delta(self, csc, delta: EdgeDelta,
                    cfg: EngineConfig | None = None, mode: str = "auto"):
        """Streamed graph update: bucket the delta, dispatch the
        incremental conversion, return the post-update CSC.

        The delta is padded to its pow2 bucket so repeated updates of any
        size up to the bucket hit ONE compiled program behind the
        module-level :data:`apply_delta_jit` cache; the dispatch is
        accounted under ``(EngineConfig.key, (e_cap, d_bucket, out_cap))``.
        When the surviving-edge upper bound (``n_edges + n_ins``, checked
        host-side — both counts are concrete between dispatches) would
        overflow the index buffer, the output capacity grows to the next
        pow2 bucket — a one-time recompile per growth step, exactly like
        any other bucket promotion.

        Example — update keeps the conversion warm, cache stays keyed on
        the bucket::

            >>> import jax.numpy as jnp, numpy as np
            >>> from repro.core import pipeline
            >>> from repro.core.delta import EdgeDelta
            >>> from repro.core.graph import COO, random_coo
            >>> rng = np.random.default_rng(0)
            >>> dst, src = random_coo(rng, 64, 200)
            >>> coo = COO.from_arrays(dst, src, 64, capacity=256)
            >>> csc = pipeline.convert(coo)
            >>> svc = PreprocService(fanouts=(2, 2))
            >>> d = EdgeDelta.from_arrays([3], [5], [int(dst[0])],
            ...                           [int(src[0])], n_nodes=64)
            >>> out = svc.apply_delta(csc, d)
            >>> int(out.n_edges)  # one insert, one delete
            200
            >>> out.idx.shape == csc.idx.shape
            True
            >>> svc.stats.n_unique_keys
            1
        """
        delta_b = bucket_delta(delta)
        if cfg is None:
            if self.active_cfg is None:
                w = Workload(n=csc.n_nodes, e=int(csc.idx.shape[0]),
                             l=len(self.fanouts), k=max(self.fanouts))
                self.active_cfg = self.decide(w).config
                self.stats.n_reconfigs += 1
            cfg = self.active_cfg
        e_cap = int(csc.idx.shape[0])
        need = int(csc.n_edges) + int(delta_b.n_ins)
        out_cap = e_cap if need <= e_cap else next_pow2(need)
        bucket = (e_cap, delta_b.capacity, out_cap)
        self.stats.n_dispatches += 1
        self._keys_seen.add((cfg.key, bucket))
        self.stats.n_unique_keys = len(self._keys_seen)
        return apply_delta_jit(csc, delta_b, cfg=cfg, mode=mode,
                               out_capacity=out_cap)

    @staticmethod
    def cache_size() -> int:
        """Alias for :func:`preprocess_cache_size` (all services share the
        one module-level cache).

        Example::

            >>> PreprocService.cache_size() == preprocess_cache_size()
            True
        """
        return preprocess_cache_size()
