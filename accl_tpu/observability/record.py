"""The per-call record: one object carries a call's whole lifecycle.

The driver builds one :class:`CallRecord` per call (``ACCL._observe_call``)
whenever an observer is on, and every layer writes its boundary into
that object: submit (driver), queue (request layer), gang-ready and
dispatch (gang scheduler, lane-tagged), launch/launched (the host call
of the compiled program), rebound (results rebound into the member
buffers) and complete (``Request._observe``, the last thing before the
waiter wakes).  The observers read the same object:

- the flight recorder's ring (``ACCL_FLIGHT``, on by default) holds it
  from submit on, so a dump mid-flight shows the stage a wedged call
  reached (observability/flight.py);
- the metrics registry (``ACCL_METRICS``) observes its collective,
  dtype, size and submit -> complete latency at completion;
- the trace collector (``ACCL_TRACE``) takes it at completion and
  exports it as Perfetto slices (observability/trace.py).

Unset stamps are None (a lane with no such stage: gang-ready on the
emulator, whose native engine matches calls below Python; launch on a
barrier, which has no program).  The flight dump writes them as 0.

Non-call records use the same class: plan replays (plans.py),
recovery-supervisor phases, lifecycle anchors (``flight.mark_event``)
and marked windows (``trace.traced_window``).

:func:`mark` is the one writer of a boundary: it stamps a list of
records at one clock read, moves their state, tags their lane and,
while tracing is on, switches the calling thread's ``accl.*`` profiler
annotation (``trace.stage``).
"""
from __future__ import annotations

from typing import Optional

from . import trace as _trace
from .trace import now_ns

# record states, in lifecycle order (ints: one attribute write per
# transition on the always-on path; names only materialize at dump time)
S_SUBMITTED = 0
S_QUEUED = 1
S_GANG_READY = 2
S_DISPATCHED = 3
S_COMPLETE = 4
S_FAILED = 5
S_ABORTED = 6  # finalized by a communicator abort (COMM_ABORTED), not
#              # an engine fault — a terminal state, never "in flight"
S_RECOVERING = 7  # a recovery-supervisor phase record (non-gang): the
#              # rank is mid detect->abort->probe->shrink/grow->resume;
#              # finish() retires it complete/failed like any record
STATE_NAMES = ("submitted", "queued", "gang_ready", "dispatched",
               "complete", "failed", "aborted", "recovering")

#: retcode bit marking an abort-finalized call (constants.ErrorCode.
#: COMM_ABORTED; kept as a literal here so the record path adds no
#: import edge)
_COMM_ABORTED_BIT = 1 << 27

#: the stamp fields, in lifecycle order
STAMPS = ("submit", "queue", "gang_ready", "dispatch", "launch",
          "launched", "rebound", "complete")

#: the stamps of a rank-local lane's dispatch (local op, p2p, emu): its
#: launch opens at dispatch, since these lanes have no gang stages
LOCAL_DISPATCH = ("t_dispatch", "t_launch")


class CallRecord:
    """One call's lifecycle record, mutated in place as the call moves
    through the stack."""

    __slots__ = ("seq", "req_id", "rank", "collective", "comm", "tag",
                 "desc", "dtype", "count", "nbytes", "nranks", "gang",
                 "gang_id", "lane", "tenant", "state", "retcode",
                 "t_submit", "t_queue", "t_gang_ready", "t_dispatch",
                 "t_launch", "t_launched", "t_rebound", "t_complete",
                 "traced", "registry", "_recorder")

    def __init__(self, collective: str, desc: str = "", rank: int = -1,
                 count: int = 0, dtype: str = "", nbytes: int = 0,
                 nranks: int = 1, comm: int = 0, tag: int = 0,
                 gang: bool = False, req_id: int = -1,
                 tenant: Optional[str] = None,
                 t_submit: Optional[int] = None):
        #: flight-ring sequence number (-1 until a recorder adds it)
        self.seq = -1
        self.req_id = req_id
        #: the issuing rank in the call's communicator (the Perfetto
        #: pid); the flight dump names the recorder's rank instead
        self.rank = rank
        self.collective = collective
        self.comm = comm
        self.tag = tag
        self.desc = desc
        self.dtype = dtype
        self.count = count
        self.nbytes = nbytes
        self.nranks = nranks
        self.gang = gang
        #: pairs the per-rank records of one gang instance in the trace
        self.gang_id: Optional[int] = None
        self.lane: Optional[str] = None
        #: tenant/lane label of the issuing communicator — records of a
        #: labeled tenant render on their own per-tenant call track
        self.tenant = tenant
        self.state = S_SUBMITTED
        self.retcode = 0
        self.t_submit = t_submit
        self.t_queue: Optional[int] = None
        self.t_gang_ready: Optional[int] = None
        self.t_dispatch: Optional[int] = None
        #: host clock immediately around the program call; on the chip
        #: the call returns at enqueue, so this is NOT device time (the
        #: profiler trace has that)
        self.t_launch: Optional[int] = None
        self.t_launched: Optional[int] = None
        self.t_rebound: Optional[int] = None
        self.t_complete: Optional[int] = None
        #: the trace collector takes this record at completion
        self.traced = False
        #: the metrics registry that observes this call at completion
        self.registry = None
        #: the flight recorder whose ring holds this record
        self._recorder = None

    @property
    def in_flight(self) -> bool:
        # a recovery-phase record is a live episode until finish()
        # retires it (it is never gang=True, so the watchdog's
        # stuck-GANG scan and the merge hang analysis both skip it)
        return self.state < S_COMPLETE or self.state == S_RECOVERING

    def age_ns(self, now: Optional[int] = None) -> int:
        """Nanoseconds since submit (in flight) or submit→complete."""
        if self.t_submit is None:
            return 0
        end = self.t_complete or (now if now is not None else now_ns())
        return max(end - self.t_submit, 0)

    def timestamps(self) -> dict:
        return {k: getattr(self, "t_" + k) for k in STAMPS}

    def finish(self, retcode: int, t: int) -> None:
        """Retire the record: retcode, complete stamp, terminal state,
        and the flight recorder's completion bookkeeping (its
        receive-timeout forensics included)."""
        self.retcode = retcode
        self.t_complete = t
        if retcode == 0:
            self.state = S_COMPLETE
        elif retcode & _COMM_ABORTED_BIT:
            self.state = S_ABORTED
        else:
            self.state = S_FAILED
        if self._recorder is not None:
            self._recorder._note_finished(self)

    def summary(self, now: Optional[int] = None) -> str:
        """One-line human rendering, used by error embedding and logs."""
        return (f"seq={self.seq} {self.collective} comm={self.comm} "
                f"state={STATE_NAMES[self.state]} lane={self.lane} "
                f"dtype={self.dtype} count={self.count} "
                f"age={self.age_ns(now) / 1e6:.1f}ms")

    def to_dict(self, now: Optional[int] = None) -> dict:
        """The flight dump's record (flight.RECORD_SCHEMA_KEYS): unset
        stamps are 0, the rank is the recorder's."""
        rec = self._recorder
        return {
            "seq": self.seq, "req_id": self.req_id,
            "rank": rec.rank if rec is not None else self.rank,
            "collective": self.collective, "comm": self.comm,
            "tag": self.tag, "dtype": self.dtype, "count": self.count,
            "nbytes": self.nbytes, "nranks": self.nranks,
            "lane": self.lane, "state": STATE_NAMES[self.state],
            "gang": self.gang, "retcode": self.retcode,
            "tenant": self.tenant,
            "age_us": round(self.age_ns(now) / 1e3, 1),
            "t_submit": self.t_submit or 0,
            "t_queue": self.t_queue or 0,
            "t_gang_ready": self.t_gang_ready or 0,
            "t_dispatch": self.t_dispatch or 0,
            "t_complete": self.t_complete or 0,
        }

    def __repr__(self) -> str:  # pragma: no cover — debug aid
        return (f"CallRecord(r{self.rank} gang={self.gang_id} "
                f"{self.summary()})")


#: :func:`mark`'s default `stage`: leave the thread's annotation alone
KEEP = object()


def mark(records, fields, t: Optional[int] = None,
         state: Optional[int] = None, lane: Optional[str] = None,
         stage=KEEP) -> None:
    """One lifecycle boundary: write each stamp of `fields`
    (``("t_dispatch",)``, ...) on every record of `records` (None
    entries skipped) at one clock read, `t` where the caller already
    read it; move each record to `state` and tag `lane` where given (a
    lane already tagged is kept).  While tracing is on, close the
    calling thread's open ``accl.*`` annotation and open `stage` (None:
    open none); the default leaves it as it is."""
    if t is None:
        t = now_ns()
    for rec in records:
        if rec is None:
            continue
        for f in fields:
            setattr(rec, f, t)
        if state is not None:
            rec.state = state
        if lane is not None and rec.lane is None:
            rec.lane = lane
    if stage is not KEEP and _trace._enabled:
        _trace.stage(stage)
