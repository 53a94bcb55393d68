"""Admission feeder — host-side tokenize/pad/upload off the decode path.

The ``engine.prefetch`` analog for serving: while the in-flight decode step
runs on device, a producer thread drains the :class:`RequestQueue`, pads
each prompt to the engine's pow2 prompt bucket and ``jax.device_put``s the
row, so that when a slot frees the admission is one cheap device-side row
write instead of a host round-trip on the critical path. Depth bounds the
lookahead exactly like ``Prefetcher(depth=...)`` — prepared admissions that
no slot can take yet don't pile up on device.

End-of-stream and producer errors travel OUT-OF-BAND (a finished event +
an error box), never through the bounded item queue: a full queue must not
be able to swallow the shutdown signal and leave the engine loop polling
forever.
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading

import jax
import numpy as np

from repro import scopes

from .queue import RequestQueue
from .request import Request, RequestState


@dataclasses.dataclass
class PreparedAdmission:
    """A request whose prompt row already lives on device."""

    request: Request
    row: jax.Array  # int32 [prompt_cap], zero-padded tail
    plen: int


def _produce(rq: RequestQueue, out: _queue.Queue, stop: threading.Event,
             prompt_cap: int, device_put: bool, err_box: list,
             finished: threading.Event, pad_value: int) -> None:
    """Producer loop (module-level for the same GC-root reason as
    ``engine.prefetch._produce``: the thread must not pin the feeder)."""
    try:
        while not stop.is_set():
            req = rq.get(timeout=0.05)
            if req is None:
                if rq.closed and len(rq) == 0:
                    return  # stream over; `finished` set in the finally
                continue
            with jax.profiler.TraceAnnotation(scopes.FEED):
                row = np.full((prompt_cap,), pad_value, np.int32)
                row[:len(req.prompt)] = np.asarray(req.prompt, np.int32)
                if device_put:
                    row = jax.device_put(row)
            req.state = RequestState.PREPARED
            item = PreparedAdmission(req, row, len(req.prompt))
            while not stop.is_set():
                try:
                    out.put(item, timeout=0.05)
                    break
                except _queue.Full:
                    continue
            else:
                return
    except BaseException as exc:  # noqa: BLE001 — relayed via the err box
        err_box.append(exc)
    finally:
        finished.set()


class AdmissionFeeder:
    """Double-buffered admission pipeline over a :class:`RequestQueue`.

    ``poll()`` returns the next :class:`PreparedAdmission` (or ``None`` when
    nothing is ready yet); once the request stream is closed and fully
    drained, ``done`` flips and ``poll()`` returns ``None`` forever. A
    producer error re-raises out of ``poll()`` after prepared items drain.
    """

    def __init__(self, rq: RequestQueue, prompt_cap: int, depth: int = 2,
                 device_put: bool = True, pad_value: int = 0):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._out: _queue.Queue = _queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._finished = threading.Event()
        self._err_box: list[BaseException] = []
        self._done = False
        # pad_value: LM rows zero-pad (0 is a harmless vocab id behind
        # prompt_len); GNN seed rows SENTINEL-pad (padding seeds must have
        # degree 0 so real seeds keep the first new VIDs).
        self._thread = threading.Thread(
            target=_produce, args=(rq, self._out, self._stop, prompt_cap,
                                   device_put, self._err_box,
                                   self._finished, pad_value),
            daemon=True, name="repro-serve-feeder")
        self._thread.start()

    @property
    def done(self) -> bool:
        return self._done

    def poll(self, timeout: float | None = None) -> PreparedAdmission | None:
        """Next prepared admission, or None (not ready / stream over)."""
        if self._done:
            return None
        try:
            return (self._out.get(timeout=timeout) if timeout
                    else self._out.get_nowait())
        except _queue.Empty:
            if self._err_box:
                self._done = True
                self.close()
                raise self._err_box[0]
            if self._finished.is_set() and self._out.empty():
                self._done = True
            return None

    def close(self) -> None:
        evt = getattr(self, "_stop", None)
        if evt is None:
            return
        evt.set()
        try:
            while True:
                self._out.get_nowait()
        except _queue.Empty:
            pass
        thread = getattr(self, "_thread", None)
        if thread is not None and thread.is_alive():
            thread.join(timeout=5.0)

    def __enter__(self) -> "AdmissionFeeder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        self.close()
