"""Async request layer.

Equivalent of the reference request objects: every collective call returns
a handle the user can wait on (with optional timeout); completion carries
the engine retcode and a duration read from the performance counter
(reference: driver/xrt/include/accl/acclrequest.hpp:39-211 BaseRequest /
FPGAQueue; driver/xrt/src/fpgadevice.cpp:24-33 finish_fpga_request).

Per-device call serialization (the reference's FPGAQueue) is preserved:
backends push requests through a `RequestQueue` so only one call is
outstanding per engine command stream at a time, while the engine itself
may interleave retried rendezvous calls internally.
"""
from __future__ import annotations

import itertools
import os
import threading
from typing import Callable, Optional

from .constants import ACCLError, OperationStatus, error_code_to_str
from .observability import trace as _trace
from .observability.record import S_QUEUED, CallRecord, mark

#: sentinel distinguishing "no timeout passed" (resolve the configurable
#: default) from an explicit None (block forever, the pre-r8 behavior)
_WAIT_DEFAULT = object()


def default_wait_timeout_s() -> float:
    """Default Request.wait budget in seconds, derived from the same
    ``ACCL_DEFAULT_TIMEOUT`` knob as the engine receive budget (µs,
    accl.default_timeout) plus generous host headroom — the driver wait
    must always fire AFTER the engine's own timeout so a stall surfaces
    as a decodable retcode first, and a bare ``wait()`` can no longer
    hang a production process forever."""
    raw = os.environ.get("ACCL_DEFAULT_TIMEOUT", "1000000")
    try:
        engine_s = float(raw) / 1e6
    except ValueError:
        engine_s = 1.0
    return engine_s + 59.0


class Request:
    """Handle for one in-flight collective call."""

    _ids = itertools.count()

    def __init__(self, description: str = "", sync: bool = False):
        self.id = next(Request._ids)
        self.description = description
        #: True when the submitter will BLOCK on this request (no
        #: run_async): the backend may then complete the call inline on
        #: the submitting thread (leader dispatch) instead of handing it
        #: to an executor — the submitter cannot issue another call
        #: until this one completes, so inline execution costs it
        #: nothing and saves the executor hop.  False (default) keeps
        #: the posted-descriptor path: the submitter wants its thread
        #: back immediately.
        self.sync = sync
        self.status = OperationStatus.QUEUED
        self.retcode: int = 0
        self.duration_ns: float = 0.0
        self._done = threading.Event()
        #: optional callback run on completion (used by the driver to sync
        #: result buffers back to the host, mirroring the async completion
        #: thread of the reference backend).
        self.on_complete: Optional[Callable[["Request"], None]] = None
        #: optional thunk run ONCE at the top of wait(), on the waiting
        #: thread, before blocking.  Backends use it to defer leader-
        #: dispatch work out of the submission path: submit() runs under
        #: the rank's RequestQueue lock, and executing a gang program
        #: there would stall concurrent submissions on the same handle —
        #: wait() runs after submit returns, lock released.
        self.pre_wait: Optional[Callable[[], None]] = None
        #: exception raised by on_complete, surfaced via check()
        self.callback_error: Optional[Exception] = None
        #: this call's lifecycle record (observability/record.py): the
        #: flight ring, the metrics registry and the tracer all read it.
        #: None only when every observer is off (ACCL_FLIGHT=0,
        #: ACCL_METRICS=0, no ACCL_TRACE) or the request predates
        #: initialize.  Set by ACCL._observe_call; each boundary is
        #: stamped in place by the queue and the backends.
        self.record: Optional[CallRecord] = None
        #: True once a wait() observed completion — the signal the
        #: collective sanitizer's leaked-request checker and
        #: ACCL.deinit() use to tell a drained async call from one
        #: whose completion (and retcode) nobody ever looked at
        self.waited = False

    def complete(self, retcode: int, duration_ns: float = 0.0) -> None:
        self.retcode = retcode
        self.duration_ns = duration_ns
        self.status = OperationStatus.COMPLETED
        try:
            if self.on_complete is not None:
                self.on_complete(self)
        except Exception as e:  # surface via check(), never lose the event
            self.callback_error = e
        finally:
            self._observe()
            self._done.set()

    def _observe(self) -> None:
        """Close this call's record: the metrics observation, then the
        complete stamp — the last event before the waiter wakes, so the
        observers' own cost lies inside the record — and, traced, the
        hand-over to the collector.  Observer failures must never lose
        the completion event."""
        rec = self.record
        if rec is None:
            return
        try:
            if rec.registry is not None:
                rec.registry.observe_call(
                    rec.collective, rec.dtype, rec.nbytes,
                    _trace.now_ns() - rec.t_submit, rec.nranks,
                    ok=self.retcode == 0, engine_ns=self.duration_ns,
                    tenant=rec.tenant)
            rec.finish(self.retcode, _trace.now_ns())
            if rec.traced:
                _trace.collector().add(rec)
        except Exception:  # pragma: no cover — observability is best-effort
            pass

    def wait(self, timeout=_WAIT_DEFAULT) -> bool:
        """Block until completion; returns False on timeout
        (reference: cclo.hpp:149-150 wait w/ timeout).

        A bare ``wait()`` uses the configurable default budget
        (:func:`default_wait_timeout_s`, driven by ACCL_DEFAULT_TIMEOUT)
        instead of blocking forever; pass ``timeout=None`` explicitly
        for an unbounded wait."""
        if timeout is _WAIT_DEFAULT:
            timeout = default_wait_timeout_s()
        thunk, self.pre_wait = self.pre_wait, None
        if thunk is not None:
            thunk()
        ok = self._done.wait(timeout)
        if ok:
            self.waited = True
        return ok

    def flight_info(self) -> str:
        """The flight-recorder view of this call, for error embedding
        ('' when the recorder is off): seq, state, lane, age."""
        rec = self.record
        if rec is None or rec.seq < 0:
            return ""
        return f" [flight: {rec.summary()}]"

    def check(self) -> None:
        """Raise if the engine reported a non-zero retcode, the
        completion callback failed, or — called after a wait() timeout —
        the call is still in flight, with the flight-recorder record
        (seq, state, lane, age) embedded so a timeout is diagnosable
        from the exception alone
        (reference: accl.cpp:1226-1250 check_return_value)."""
        if self.done:
            # checking a completed request IS observing its outcome:
            # poll-then-check drains a call as thoroughly as wait(), so
            # the sanitizer's leaked-request checker must not flag it
            self.waited = True
        if not self.done:
            raise ACCLError(
                f"{self.description or 'call'} timed out: request id "
                f"{self.id} still in flight"
                f" (status={self.status.name}){self.flight_info()}")
        if self.retcode != 0:
            raise ACCLError(
                f"{self.description or 'call'} failed: "
                f"{error_code_to_str(self.retcode)}{self.flight_info()}",
                self.retcode,
            )
        if self.callback_error is not None:
            raise ACCLError(
                f"{self.description or 'call'} completion failed: "
                f"{self.callback_error}"
            ) from self.callback_error

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def aborted(self) -> bool:
        """True when this call was finalized by a communicator abort
        (COMM_ABORTED) rather than completing or failing on its own —
        the signal recovery code branches on (shrink + re-run) without
        string-matching the error text."""
        from .constants import ErrorCode

        return self.done and bool(self.retcode & int(ErrorCode.COMM_ABORTED))

    def __repr__(self) -> str:
        return f"Request(id={self.id}, {self.description!r}, status={self.status.name})"


class RequestQueue:
    """Serializes the *submission* of calls onto a device command stream
    (the reference FPGAQueue's enqueue step, acclrequest.hpp:153-211).
    Engines accept multiple outstanding calls — retried rendezvous calls
    interleave by design — so completion ordering is backend territory;
    only the descriptor push is atomic here."""

    def __init__(self):
        self._lock = threading.Lock()

    def submit(self, request: Request, start_fn: Callable[[Request], None]) -> Request:
        with self._lock:
            request.status = OperationStatus.EXECUTING
            if request.record is not None:  # hand-off: accl.driver ends
                mark((request.record,), ("t_queue",), state=S_QUEUED,
                     stage=None)
            start_fn(request)
        return request
