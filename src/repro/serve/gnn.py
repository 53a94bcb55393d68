"""GnnServeEngine — batched GNN inference over the payload-agnostic slot
core.

The production scenario the paper motivates, end to end: requests carry
seed node ids; each occupied slot runs the whole request-to-prediction
dataflow — neighbor sampling (``sample_khop``) → reindex + subgraph
re-conversion (``pipeline.sample_subgraph``, reindex_strategy-dispatched
through the Table-I cost model) → feature gather → GNN forward → argmax —
as one vmap lane of ONE warm jitted step. The feeder thread pads seed rows
to the pow2 ``seed_cap`` bucket (SENTINEL, so padding seeds have degree 0
and never claim VIDs) and ``device_put``s them off the critical path,
exactly as it pads LM prompts.

What keeps batched == sequential *bit-identical* (the acceptance criterion
``tests/test_gnn_serve.py`` asserts):

* each slot is an independent ``sample_subgraph`` call — no cross-request
  VID dedup, so a request's subgraph never depends on its slot neighbours;
* the per-request PRNG key is folded from the request id, not the slot or
  step index, so the sampled frontier is a pure function of the request;
* the forward runs the pointer-based scatter-free segment reduction
  (``models.gnn`` with ``GraphBatch.ptr``) on both the batched engine and
  the sequential oracle, so even float summation order matches.

Requests retire after exactly one step (the ``max_new=1`` analog), so this
engine runs the slot core's synchronous schedule (``pipeline_steps=False``)
— emissions route immediately and cooling flushes between steps — instead
of the LM loop's one-step-in-flight overlap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.core import pipeline
from repro.core.costmodel import EngineConfig
from repro.core.delta import EdgeDelta
from repro.core.graph import CSC, SENTINEL, next_pow2
from repro.models.gnn import GNNConfig, gnn_apply, subgraph_batch

from .request import Request
from .slots import SlotEngineBase

# Control-request prompt marker: a streamed graph update enqueued by
# ``submit_update`` (its EdgeDelta rides ``Request.payload``; the row the
# feeder pads from this marker is never read).
UPDATE_MARKER = -2


def build_slot_fn(gcfg: GNNConfig, fanouts: tuple[int, ...], seed_cap: int,
                  cfg: EngineConfig):
    """One slot's whole request: sample → convert → forward → argmax.

    ``bundle`` packs everything request-independent ({"gnn": params,
    "csc": graph, "features": table}). The sequential oracle in tests and
    benchmarks jits THIS function at batch 1; the engine step is its vmap
    — bit-equality between the two is the serving acceptance criterion.
    """

    def slot_fn(bundle, seeds, key):
        sub = pipeline.sample_subgraph(bundle["csc"], seeds, fanouts, key,
                                       cfg)
        with jax.named_scope(scopes.SERVE_GATHER):
            batch = subgraph_batch(sub, bundle["features"])
        with jax.named_scope(scopes.SERVE_FORWARD):
            out = gnn_apply(gcfg, bundle["gnn"], batch)
            # first-occurrence numbering: the request's seeds own the
            # first seed_cap new VIDs, so its predictions are the first rows
            return jnp.argmax(out[:seed_cap], axis=-1).astype(jnp.int32)

    return slot_fn


def gnn_route(req: Request, emission) -> bool | None:
    """Route policy for one-shot predict requests: the emission row is
    ``[active_flag, pred_0 .. pred_cap-1]``; a flagged row retires the
    request with its first ``len(seeds)`` predictions (the tail rows
    belong to SENTINEL padding)."""
    row = np.asarray(emission)
    if int(row[0]) == 0:
        return None
    req.tokens_out.extend(int(p) for p in row[1:1 + len(req.prompt)])
    return True


def _build_step(slot_fn):
    """The one compiled program: every slot's sample→convert→forward as
    vmap lanes + the emission row assembly. Inactive slots compute on
    their stale/SENTINEL seeds (fixed shapes — no lane can be skipped)
    and are masked out by the flag column."""

    def step(params, state):
        def one_slot(seeds, key):
            return slot_fn(params, seeds, key)

        preds = jax.vmap(one_slot)(state["seeds"], state["key"])
        flag = state["active"].astype(jnp.int32)
        emitted = jnp.concatenate([flag[:, None], preds], axis=1)
        # One-shot retirement happens IN the step: every occupied slot's
        # request completes with this emission, so the step clears all
        # active flags itself and the engine's per-slot deactivation is a
        # free host no-op instead of one dispatch per retirement.
        state = {**state, "active": jnp.zeros_like(state["active"])}
        return state, emitted

    return step


def _make_admit_many(base_key, n_slots):
    """One dispatch seats a whole admission wave: seed rows, per-request
    PRNG keys (folded from the rid — inside the jit, so no host key
    derivation on the critical path) and active flags for up to
    ``n_slots`` requests at once. The lane loop is a static unroll of
    scalar row writes (dynamic-update-slice, NOT scatter — vector-indexed
    ``.at[slots].set`` would lower to the scatter op the serving contracts
    forbid); invalid lanes keep the previous state via ``where``."""

    def admit_many(state, slots, rows, rids, valid):
        keys = jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids)
        seeds, keyrow, active = state["seeds"], state["key"], state["active"]
        for i in range(n_slots):
            s = slots[i]
            seeds = jnp.where(valid[i], seeds.at[s].set(rows[i]), seeds)
            keyrow = jnp.where(valid[i], keyrow.at[s].set(keys[i]), keyrow)
            active = jnp.where(valid[i], active.at[s].set(True), active)
        return {"seeds": seeds, "key": keyrow, "active": active}

    return admit_many


class GnnServeEngine(SlotEngineBase):
    """Admission-controlled GNN inference over ``n_slots`` request slots.

    ``submit(seeds)`` enqueues one inference request for up to
    ``seed_cap`` batch nodes; ``run()`` drives sample → subgraph convert →
    forward for every occupied slot per step and retires each request with
    its per-seed class predictions in ``Request.tokens_out``. The
    preprocessing configuration (``cfg``) pins the whole dispatch stack —
    sort_strategy, reindex_strategy, Pallas routing — exactly as
    ``engine.service`` dispatches it.

    The graph itself is mutable under traffic: ``submit_update(inserts,
    deletes)`` enqueues a ``delta_cap``-bucketed edge batch on the SAME
    FIFO; the run loop holds it until in-flight requests retire, splices
    it in via the incremental conversion (O(delta) ``apply_delta``, not a
    re-convert) and resumes admissions against the updated CSC — shapes
    pinned to the serve buckets, so a whole update/inference stream runs
    on the warm step program with zero recompiles.
    """

    def __init__(self, gcfg: GNNConfig, params, csc: CSC,
                 features: jnp.ndarray, *,
                 fanouts: tuple[int, ...] | None = None, n_slots: int = 4,
                 seed_cap: int = 8, cfg: EngineConfig | None = None,
                 key_seed: int = 0, feeder_depth: int = 2,
                 delta_cap: int = 64):
        fanouts = tuple(fanouts if fanouts is not None
                        else gcfg.sample_sizes)
        if not fanouts:
            raise ValueError("fanouts required (gcfg.sample_sizes is empty)")
        seed_cap = next_pow2(seed_cap)
        n_slots = next_pow2(n_slots)
        # One-shot requests drain a full slot wave per step (the LM loop
        # admits rarely), so the feeder looks ahead a couple of waves and
        # the loop holds each wave open for a short admission window
        # rather than stepping half-empty.
        # feeder_device_put=False: admission waves stack the numpy rows
        # host-side and ship the whole [S, cap] block as ONE argument
        # transfer of the batched admit — a per-row device_put in the
        # feeder would just add transfers.
        super().__init__(n_slots=n_slots, row_cap=seed_cap,
                         route=gnn_route,
                         feeder_depth=max(feeder_depth, 4 * n_slots),
                         pipeline_steps=False, pad_value=int(SENTINEL),
                         feeder_device_put=False, admit_window=2e-3)
        self.gcfg = gcfg
        self.fanouts = fanouts
        self.seed_cap = seed_cap
        self.delta_cap = next_pow2(delta_cap)
        self.engine_cfg = cfg or EngineConfig()
        self.n_nodes = csc.n_nodes
        self.base_key = jax.random.PRNGKey(key_seed)
        self.params = {"gnn": params, "csc": csc, "features": features}
        s = self.n_slots
        self.state = {
            "seeds": jnp.full((s, seed_cap), int(SENTINEL), jnp.int32),
            "key": jnp.zeros((s,) + self.base_key.shape,
                             self.base_key.dtype),
            "active": jnp.zeros((s,), bool),
        }
        self.slot_fn = build_slot_fn(gcfg, fanouts, seed_cap,
                                     self.engine_cfg)
        # repro: allow-raw-jit — per-engine jits are deliberate: the step
        # closes over per-engine static geometry (gcfg, fanouts, seed_cap,
        # engine_cfg) and one engine serves the whole process; the
        # zero-recompile contract is enforced at runtime instead
        # (step_cache_size()==1, asserted by tests and the repro.analysis
        # gnn_serve contract).
        self._step = jax.jit(_build_step(self.slot_fn))
        # repro: allow-raw-jit — same per-engine cache argument as _step.
        self._admit_many_fn = jax.jit(
            _make_admit_many(self.base_key, self.n_slots),
            donate_argnums=(0,))
        # Not a dispatch: the step already cleared every active flag
        # (one-shot retirement), so per-slot deactivation has nothing to
        # write.
        self._deactivate_fn = lambda state, slot: state

    # ------------------------------------------------------------ admission
    def _admit_many_args(self, wave: list) -> tuple:
        """Stack one admission wave into fixed [n_slots, ...] arguments
        (slot targets, seed rows, rids, valid mask) — always n_slots lanes
        so the batched admit compiles exactly once."""
        s = self.n_slots
        slots = np.zeros((s,), np.int32)
        rows = np.full((s, self.seed_cap), int(SENTINEL), np.int32)
        rids = np.zeros((s,), np.int32)
        valid = np.zeros((s,), bool)
        for i, (slot, prep) in enumerate(wave):
            slots[i], rows[i] = slot, prep.row
            rids[i], valid[i] = prep.request.rid, True
        return (slots, rows, rids, valid)

    def submit(self, seeds) -> Request:
        """Enqueue one inference request for ``seeds`` (node ids); returns
        its Request handle. Predictions land in ``Request.tokens_out``,
        one class id per seed, in submission order."""
        seeds = [int(s) for s in seeds]
        if not 1 <= len(seeds) <= self.seed_cap:
            raise ValueError(
                f"seed count {len(seeds)} not in [1, {self.seed_cap}]")
        bad = [s for s in seeds if not 0 <= s < self.n_nodes]
        if bad:
            raise ValueError(f"seed ids out of range [0, {self.n_nodes}): "
                             f"{bad}")
        return self._enqueue(seeds, max_new=1)

    def submit_update(self, inserts, deletes=()) -> Request:
        """Enqueue one streamed graph update (edge inserts + deletes).

        ``inserts``/``deletes`` are iterables of ``(dst, src)`` pairs; both
        are bucketed to the engine's fixed ``delta_cap`` so EVERY update
        re-enters the one compiled ``apply_delta`` program (the same pow2
        discipline as seed rows). The update rides the request FIFO: it
        applies only once every earlier request retired, and every later
        request samples the post-update graph. Its Request completes with
        empty ``tokens_out`` when the update has been applied.
        """
        ins = [(int(d), int(s)) for d, s in inserts]
        dels = [(int(d), int(s)) for d, s in deletes]
        if not ins and not dels:
            raise ValueError("empty update: no inserts and no deletes")
        if max(len(ins), len(dels)) > self.delta_cap:
            raise ValueError(
                f"update size {max(len(ins), len(dels))} exceeds the "
                f"engine delta bucket {self.delta_cap} — split the batch "
                f"or construct the engine with a larger delta_cap")
        bad = [v for dd, ss in ins + dels for v in (dd, ss)
               if not 0 <= v < self.n_nodes]
        if bad:
            raise ValueError(f"update VIDs out of range [0, {self.n_nodes})"
                             f": {bad}")
        delta = EdgeDelta.from_arrays(
            [d for d, _ in ins], [s for _, s in ins],
            [d for d, _ in dels], [s for _, s in dels],
            n_nodes=self.n_nodes, capacity=self.delta_cap)
        return self._enqueue([UPDATE_MARKER], max_new=0, payload=delta)

    def _classify_prep(self, prep) -> str:
        return "apply" if isinstance(prep.request.payload, EdgeDelta) \
            else "seat"

    def _apply_control(self, prep) -> None:
        """Apply one held graph update between steps: incremental
        conversion through the module-level ``apply_delta_jit`` cache
        (``engine.service``), output capacity pinned to the serve graph's
        bucket — the post-update CSC has the exact shapes of the old one,
        so swapping it into ``params`` costs ZERO step recompiles
        (asserted by tests/test_gnn_serve.py via step_cache_size()).
        """
        from repro.engine.service import apply_delta_jit
        csc = self.params["csc"]
        cap = int(csc.idx.shape[0])
        delta = prep.request.payload
        if int(csc.n_edges) + int(delta.n_ins) > cap:
            raise RuntimeError(
                f"graph update overflows the serve index bucket ({cap} "
                f"slots): growing the bucket would recompile the step — "
                f"restart the engine with a larger graph capacity")
        self.params = {**self.params,
                       "csc": apply_delta_jit(csc, delta,
                                              cfg=self.engine_cfg,
                                              out_capacity=cap)}

    def request_key(self, rid: int) -> jax.Array:
        """The per-request PRNG key — folded from the request id alone
        (never the slot or step), which is what makes the batched engine's
        sampling bit-identical to a sequential per-request loop. The
        sequential oracle derives its keys through this same method."""
        return jax.random.fold_in(self.base_key, rid)
