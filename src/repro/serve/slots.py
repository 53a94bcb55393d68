"""Payload-agnostic slot-batching core — the machinery both serve engines
share.

``repro.serve`` started as an LM decode batcher; the scheduler, pow2 slot
buckets, feeder thread, one-cycle cooling and the zero-recompile jit-cache
discipline are not LM-specific, so they live here and the engines
(``engine.ServeEngine`` for LM decode, ``gnn.GnnServeEngine`` for GNN
inference) are clients. The contract a client implements:

* **state** — a dict of fixed-shape [n_slots, ...] device arrays with an
  ``"active"`` [S] bool row (what :func:`deactivate_update` clears).
* **_step** — ONE jitted ``(params, state) -> (state, emitted)`` program.
  ``emitted`` is a [S] or [S, ...] array routed per slot by the
  scheduler's route policy; the zero-recompile guard
  (:meth:`SlotEngineBase.step_cache_size` == 1 after heterogeneous
  traffic) is enforced against this function.
* **_admit_fn / _deactivate_fn** — jitted slot row writes; admission must
  never change a traced shape (rows are padded to the engine's pow2
  ``row_cap`` by the feeder before they reach the device).
* **route** — host-side emission routing (``scheduler.lm_token_route`` for
  greedy decode, ``gnn.gnn_route`` for one-shot predictions).

Two run-loop schedules, selected by ``pipeline_steps``: the LM loop keeps
one step in flight (host routes step ``k-1`` while the device runs ``k`` —
which is why retired slots need the scheduler's one-cycle cooling), the
GNN loop retires synchronously after each step (every request completes in
exactly one step, so a second in-flight step would recompute stale slots)
and may therefore flush cooling immediately.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import scopes

from .feeder import AdmissionFeeder
from .queue import RequestQueue
from .request import Request, RequestState
from .scheduler import Scheduler


@dataclasses.dataclass
class ServeStats:
    steps: int = 0
    admitted: int = 0
    retired: int = 0
    tokens_processed: int = 0  # payload units touched, active slots only
    tokens_generated: int = 0  # tokens (LM) / predictions (GNN) emitted
    window_waits: int = 0  # admission windows opened
    window_seated: int = 0  # requests those windows seated


def deactivate_update(state, slot):
    """Clear one slot's active flag — valid for ANY client state dict (the
    only row it touches is the shared ``"active"`` [S] bool)."""
    return {**state, "active": state["active"].at[slot].set(False)}


class SlotEngineBase:
    """Slot bookkeeping + the admission/step/retire loop, payload-free.

    Subclasses construct their params/state/jitted programs after calling
    ``super().__init__`` and expose a typed ``submit``; everything else —
    queueing, feeder lifecycle, FIFO admission into the lowest free slot,
    cooling, stats, cache introspection, stream reopen — is inherited.
    """

    def __init__(self, *, n_slots: int, row_cap: int,
                 eos_id: int | None = None, route=None,
                 feeder_depth: int = 2, pipeline_steps: bool = True,
                 pad_value: int = 0, feeder_device_put: bool = True,
                 admit_window: float = 0.0):
        self.n_slots = n_slots
        self.row_cap = row_cap
        self.queue = RequestQueue()
        self.scheduler = Scheduler(n_slots, eos_id=eos_id, route=route)
        self.stats = ServeStats()
        self._feeder_depth = feeder_depth
        self._pipeline_steps = pipeline_steps
        self._pad_value = pad_value
        self._feeder_device_put = feeder_device_put
        self._admit_window = admit_window
        self._rid = 0
        self._rid_lock = threading.Lock()
        # Set by the subclass after this constructor returns:
        self.params = None
        self.state = None
        self._step = None
        self._admit_fn = None
        self._deactivate_fn = None
        # Optional wave-batched admission: one jitted dispatch seats a
        # whole admission wave (padded to n_slots lanes with a valid
        # mask). Clients whose requests retire every step (GNN) set this —
        # per-request ``_admit_fn`` dispatches would otherwise dominate
        # their step time; the LM engine admits rarely and keeps the
        # per-slot path.
        self._admit_many_fn = None
        # Control admission (streamed graph updates): a prepared request
        # classified "apply" is HELD here until every in-flight request
        # retires, then applied between steps — and while held it blocks
        # the admission poll, so requests queued after an update see the
        # post-update state (FIFO consistency).
        self._held_prep = None

    # ----------------------------------------------------- cache discipline
    def step_cache_size(self) -> int:
        """Compiled-program count behind the slot step (the zero-recompile
        guard reads this; same ``_cache_size`` introspection as
        ``engine.service.preprocess_cache_size``)."""
        try:
            return int(self._step._cache_size())
        except AttributeError as e:
            raise NotImplementedError(
                "jax.jit cache introspection (_cache_size) is unavailable "
                "on this JAX version") from e

    def step_hlo_text(self) -> str:
        """The compiled text of the slot step at the engine's current
        params and state: what ``launch.hlo_analysis.op_scopes`` maps to
        the program's named scopes. Compiles again (or loads from the
        compile cache); call it outside any timed window."""
        return self._step.lower(self.params, self.state).compile().as_text()

    # ------------------------------------------------------------ admission
    def _enqueue(self, prompt: list[int], max_new: int,
                 payload=None) -> Request:
        """Wrap a validated payload row in a Request and queue it
        (thread-safe); subclasses validate in their typed ``submit``.
        ``payload`` rides control requests (attached BEFORE the queue put
        so the feeder thread can never see a half-built request)."""
        with self._rid_lock:
            rid = self._rid
            self._rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      payload=payload)
        self.queue.put(req)
        return req

    def close_submissions(self) -> None:
        self.queue.close()

    def reopen(self) -> None:
        """Start a new request stream after ``run()`` returned.

        ``close_submissions()`` is sticky on the queue, so callers that
        warm up and then measure (benchmarks, tests) reuse one engine —
        and its compiled programs — across streams through this method
        instead of reaching into the queue attribute.
        """
        if not self.queue.closed:
            raise RuntimeError("reopen() is only valid after the previous "
                               "stream was closed")
        self.queue = RequestQueue()

    def _admit_args(self, prep) -> tuple:
        """Extra device-side arguments ``_admit_fn`` takes after (state,
        slot); clients with per-request state (e.g. a folded PRNG key)
        extend this."""
        return (prep.row, jnp.int32(prep.plen))

    def _admit_many_args(self, wave: list) -> tuple:
        """Device-side arguments ``_admit_many_fn`` takes after ``state``
        for one admission wave (``[(slot, prep), ...]``, ≤ n_slots long);
        clients that set ``_admit_many_fn`` override this to stack the
        wave into fixed [n_slots, ...] arrays plus a valid mask."""
        raise NotImplementedError

    def _classify_prep(self, prep) -> str:
        """``"seat"`` (slot admission) or ``"apply"`` (control request the
        run loop applies between steps once the device quiesces). The base
        engine seats everything; clients with a control plane (streamed
        graph updates) override."""
        return "seat"

    def _apply_control(self, prep) -> None:
        """Apply one held control request (device is quiescent: no active
        slots, nothing in flight). Clients that classify must implement."""
        raise NotImplementedError

    def _apply_held(self, completed: list[Request]) -> None:
        prep, self._held_prep = self._held_prep, None
        req = prep.request
        with TraceAnnotation(scopes.UPDATE):
            self._apply_control(prep)
            if req.admit_t is None:
                req.admit_t = time.perf_counter()
            # finished once the updated state exists on the device, not
            # when its program was dispatched
            jax.block_until_ready(self.params)
            req.finish_t = time.perf_counter()
        req.state = RequestState.FINISHED
        self.stats.retired += 1
        completed.append(req)

    def _try_admit(self, feeder: AdmissionFeeder,
                   timeout: float | None = None) -> int:
        """Seat prepared requests while slots are free; each poll waits up
        to ``timeout`` (None = non-blocking), stopping at the first empty
        poll — the idle loop's block-for-work knob and the admission
        window's fill knob. The wave lands in ONE ``_admit_many_fn``
        dispatch when the client provides it, else one ``_admit_fn``
        dispatch per request. A control request ends the wave: it is held
        for the run loop and nothing polls past it until it applies."""
        wave = []
        while self.scheduler.has_free_slot and self._held_prep is None:
            prep = feeder.poll(timeout=timeout)
            if prep is None:
                break
            if self._classify_prep(prep) == "apply":
                self._held_prep = prep
                break
            wave.append((self.scheduler.admit(prep), prep))
        if not wave:
            return 0
        if self._admit_many_fn is not None:
            self.state = self._admit_many_fn(self.state,
                                             *self._admit_many_args(wave))
        else:
            for slot, prep in wave:
                self.state = self._admit_fn(self.state, jnp.int32(slot),
                                            *self._admit_args(prep))
        self.stats.admitted += len(wave)
        return len(wave)

    def _process(self, emitted, completed: list[Request]) -> None:
        with TraceAnnotation(scopes.WAIT):
            jax.block_until_ready(emitted)
        with TraceAnnotation(scopes.ROUTE):
            for slot, req in self.scheduler.process(np.asarray(emitted)):
                self.state = self._deactivate_fn(self.state, jnp.int32(slot))
                self.stats.retired += 1
                self.stats.tokens_generated += len(req.tokens_out)
                completed.append(req)

    # ------------------------------------------------------------- the loop
    def run(self) -> list[Request]:
        """Drive the engine until the request stream is closed and drained.

        Returns completed requests in retirement order. With
        ``pipeline_steps`` the loop keeps one step in flight: while the
        device runs step ``k``, the host routes step ``k-1``'s emissions
        and the feeder prepares admissions. Without it, emissions route
        synchronously and cooling flushes immediately (nothing is in
        flight that could emit for a stale occupant).
        """
        completed: list[Request] = []
        pending = None  # step k-1's emissions (device array)
        with AdmissionFeeder(self.queue, self.row_cap,
                             depth=self._feeder_depth,
                             device_put=self._feeder_device_put,
                             pad_value=self._pad_value) as feeder:
            while True:
                if self.scheduler.has_free_slot:
                    with TraceAnnotation(scopes.ADMIT):
                        self._try_admit(feeder)
                if (self._admit_window and self.scheduler.n_active
                        and self.scheduler.has_free_slot
                        and not feeder.done):
                    # Admission window (one-shot schedules): slots freed by
                    # the last retirement wave would otherwise ride empty —
                    # give the feeder one bounded wait to fill the wave
                    # before paying for a step.
                    with TraceAnnotation(scopes.ADMIT_WINDOW):
                        seated = self._try_admit(
                            feeder, timeout=self._admit_window)
                    self.stats.window_waits += 1
                    self.stats.window_seated += seated
                if self.scheduler.n_active == 0:
                    if pending is not None:
                        self._process(pending, completed)
                        pending = None
                        continue  # processing may have freed cooling slots
                    self.scheduler.flush_cooling()
                    if self._held_prep is not None:
                        # Quiescent: nothing active, nothing in flight —
                        # apply the held control request, then resume
                        # admitting the traffic queued behind it.
                        self._apply_held(completed)
                        continue
                    if feeder.done:
                        break
                    with TraceAnnotation(scopes.IDLE):
                        self._try_admit(feeder, timeout=0.05)
                    continue
                with TraceAnnotation(scopes.STEP):
                    self.state, emitted = self._step(self.params,
                                                     self.state)
                self.stats.steps += 1
                self.stats.tokens_processed += self.scheduler.n_active
                if self._pipeline_steps:
                    if pending is not None:
                        self._process(pending, completed)
                    pending = emitted
                else:
                    self._process(emitted, completed)
                    self.scheduler.flush_cooling()
            if pending is not None:
                self._process(pending, completed)
        return completed
