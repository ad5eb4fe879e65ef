"""Recovery supervisor: the automated detect -> resume state machine.

r8 gave the stack a watchdog that *diagnoses* (which ranks arrived,
which are missing); r10 gave it the recovery verbs (abort + epoch
fencing, ULFM shrink); this module closes the loop into a per-rank
supervisor that drives the whole episode without operator code:

    RUNNING --failure--> ABORT -> PROBE -> SHRINK --(grow policy)-->
       JOIN_WAIT -> GROW -> AGREE -> RESUME --> RUNNING

Policy knobs (env or :class:`RecoveryPolicy`):

- ``ACCL_RECOVERY`` = ``shrink`` (default) | ``grow`` | ``halt`` —
  what to do after a classified failure: finish on the survivor set,
  wait for a replacement and heal back toward full size, or give up
  and surface the error;
- ``ACCL_JOIN_WAIT_S`` — how long the grow policy waits for a
  replacement to announce itself on the membership board (default 5);
- ``ACCL_RECOVERY_MAX_ROUNDS`` — recovery episodes before the
  supervisor halts (default 4; a world dying faster than it heals
  must eventually surface, not spin);
- ``ACCL_PROBE_WINDOW_S`` — the liveness probe window (default 2).

Every transition is published three ways (the observability contract
of docs/fault_tolerance.md):

- a ``recovery/<phase>`` record in the rank's flight ring, live in the
  new ``recovering`` state until the phase retires (non-gang — the
  watchdog's stuck-gang scan and the merge hang analysis never see a
  healing world as a hang);
- the ``accl_health`` gauge reads ``recovering`` (4) for the whole
  episode (outranking a stale ``hung``/``aborted`` watchdog verdict);
- metrics: ``membership/*`` event counters and the
  ``recovery/latency_us`` + ``join_wait_us`` histograms.

``ACCL.supervise()`` constructs one; ``ACCL_SUPERVISE=1`` arms it at
``initialize`` (``accl.supervisor``).  The supervisor is loop-level,
not call-level: with it off (the default) the per-call hot path
contains ZERO supervisor code — the ≤2 % callrate gate holds by
construction (bench/results/callrate_r11_elastic_overhead.md).
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

import numpy as np

from ..constants import (
    ACCLError,
    ErrorCode,
    ReduceFunction,
    env_float,
    env_int,
)
from ..observability import flight as _flight
from ..observability import health as _health
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..observability.record import S_RECOVERING, mark
from ..utils.logging import get_logger
from .elastic import MembershipBoard, admit_pending
from .membership import probe_alive

RECOVERY_MODES = ("shrink", "grow", "halt")

#: state names, published through flight records + the state log
S_RUNNING = "running"
S_ABORT = "abort"
S_PROBE = "probe"
S_SHRINK = "shrink"
S_JOIN_WAIT = "join_wait"
S_GROW = "grow"
S_AGREE = "agree"
S_RESUME = "resume"
S_HALTED = "halted"

#: the joiner's neutral contribution to the restart agreement (an
#: allreduce MAX of negated first-incomplete iterations): a fresh
#: member has no completed work and must never raise the restart point
_FRESH_MEMBER = -float(2 ** 30)


class RecoveryPolicy:
    """Resolved supervisor policy (env -> numbers, clear-error knobs)."""

    def __init__(self, mode: Optional[str] = None,
                 join_wait_s: Optional[float] = None,
                 max_rounds: Optional[int] = None,
                 probe_window_s: Optional[float] = None):
        self.mode = (mode if mode is not None
                     else os.environ.get("ACCL_RECOVERY", "shrink"))
        if self.mode not in RECOVERY_MODES:
            raise ACCLError(
                f"ACCL_RECOVERY={self.mode!r} is not a policy — pick one "
                f"of {'/'.join(RECOVERY_MODES)}")
        self.join_wait_s = (join_wait_s if join_wait_s is not None
                            else env_float("ACCL_JOIN_WAIT_S", 5.0,
                                           minimum=0.0))
        self.max_rounds = (max_rounds if max_rounds is not None
                           else env_int("ACCL_RECOVERY_MAX_ROUNDS", 4,
                                        minimum=1))
        self.probe_window_s = (probe_window_s if probe_window_s is not None
                               else env_float("ACCL_PROBE_WINDOW_S", 2.0))
        if not self.probe_window_s > 0:
            # the same clear-error-at-bring-up contract as the sibling
            # knobs: probe_alive hard-rejects a non-positive window, so
            # a typo must fail HERE, not mid-recovery-episode
            raise ACCLError(
                f"ACCL_PROBE_WINDOW_S={self.probe_window_s!r} must be "
                f"> 0 (a zero/negative probe window can never collect "
                f"a pong)")

    def __repr__(self) -> str:
        return (f"RecoveryPolicy(mode={self.mode!r}, "
                f"join_wait_s={self.join_wait_s}, "
                f"max_rounds={self.max_rounds})")


class RecoverySupervisor:
    """One rank's automated recovery driver.

    Wrap the training/serving step in :meth:`run_loop` — the
    supervisor catches classified collective failures and runs the
    full abort -> probe -> shrink/grow -> agree -> resume episode,
    handing the (possibly new) communicator id back to the step
    function.  The step function signature is
    ``step(accl, comm_id, iteration)``; raise-through of
    non-collective exceptions is unchanged."""

    def __init__(self, accl, policy: Optional[RecoveryPolicy] = None,
                 board: Optional[MembershipBoard] = None,
                 registry=None):
        self.accl = accl
        self.policy = policy if policy is not None else RecoveryPolicy()
        self.board = board
        self._registry = (registry if registry is not None
                          else _metrics.default_registry())
        self.state = S_RUNNING
        #: (monotonic_s, state, detail) transition log — uploaded as a
        #: CI artifact by the chaos join drill
        self.state_log: list = []
        self.rounds = 0
        self.comm_id: Optional[int] = None
        self._log = get_logger("accl_tpu.supervisor",
                               rank=getattr(accl, "rank", None))
        self._note(S_RUNNING, "armed")

    # -- observability plumbing -----------------------------------------
    def _note(self, state: str, detail: str = "") -> None:
        self.state = state
        self.state_log.append((time.monotonic(), state, detail))
        self._log.info("supervisor -> %s%s", state,
                       f" ({detail})" if detail else "")

    def _phase(self, name: str, comm_id: int):
        """A flight-ring record for one supervisor phase, live in the
        ``recovering`` state until the context exits."""
        sup = self

        class _Phase:
            def __enter__(self):
                self.rec = None
                fr = sup.accl.flight_recorder
                if fr is not None and _flight.enabled():
                    t = _trace.now_ns()
                    self.rec = fr.new_record(
                        -1, f"recovery/{name}", comm_id, 0, "none", 0, 0,
                        1, False, t)
                    mark((self.rec,), ("t_dispatch",), t,
                         state=S_RECOVERING, lane="supervisor")
                sup._note(name)
                return self

            def __exit__(self, exc_type, exc, tb):
                if self.rec is not None:
                    self.rec.finish(0 if exc_type is None else
                                    int(ErrorCode.RANK_FAILED),
                                    _trace.now_ns())
                return False

        return _Phase()

    # -- the loop --------------------------------------------------------
    def run_loop(self, step: Callable, iters: int, comm_id: int = 0,
                 on_restart: Optional[Callable[[int], None]] = None,
                 start_iteration: int = 0,
                 fresh_member: bool = False) -> dict:
        """Drive ``step(accl, comm_id, it)`` for ``iters`` iterations
        with automated recovery.  ``on_restart(restart_it)`` lets the
        caller discard results at/after the agreed restart point;
        ``fresh_member=True`` marks a replacement rank that joined with
        no completed work (its vote can never raise the restart).
        Returns an episode summary dict."""
        self.comm_id = comm_id
        it = start_iteration
        restarts: list = []
        while it < iters:
            try:
                step(self.accl, self.comm_id, it)
                it += 1
                continue
            except ACCLError as e:
                code = int(getattr(e, "code", 0))
                # the classified-failure mask: abort finalizations,
                # receive-budget expiry, a wedged engine past the
                # driver budget, and seqn-stream corruption — a rank
                # killed MID-SEGMENT surfaces as PACK_SEQ on peers
                # whose NACK solicitations go unanswered
                classified = code & (
                    int(ErrorCode.COMM_ABORTED)
                    | int(ErrorCode.RANK_FAILED)
                    | int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
                    | int(ErrorCode.DMA_TIMEOUT_ERROR)
                    | int(ErrorCode.PACK_SEQ_NUMBER_ERROR))
                if not classified:
                    raise  # not a membership failure: surface as-is
                self.rounds += 1
                if self.policy.mode == "halt" \
                        or self.rounds > self.policy.max_rounds:
                    self._note(S_HALTED,
                               f"round {self.rounds}, policy "
                               f"{self.policy.mode}")
                    if _metrics.enabled():
                        self._registry.inc("recovery/halts")
                    raise
                it = self._recover(first_incomplete=it,
                                   fresh=fresh_member, cause=e)
                fresh_member = False  # recovered members have history
                restarts.append(it)
                if on_restart is not None:
                    on_restart(it)
        self._note(S_RUNNING, f"loop done at iter {iters}")
        return {"iters": iters, "rounds": self.rounds,
                "comm_id": self.comm_id, "restarts": restarts,
                "state_log": list(self.state_log)}

    # -- one recovery episode --------------------------------------------
    def _recover(self, first_incomplete: int, fresh: bool,
                 cause: ACCLError) -> int:
        accl, pol = self.accl, self.policy
        comm_id = self.comm_id
        t0 = time.monotonic()
        if _metrics.enabled():
            self._registry.inc("recovery/rounds")
        _health.note_recovering(self._registry, True)
        # recovery rides longer clocks than the data plane: members
        # reach each phase skewed by up to one receive budget, and the
        # grow policy legitimately WAITS (join budget + state sync)
        # while peers sit in the admission bcast / restart agreement.
        # Raise the engine receive budget for the episode so those
        # waits never classify as fresh failures, and restore after.
        saved_budget = getattr(accl, "engine_timeout_us",
                               1_000_000)
        saved_call_s = accl.call_timeout_s
        episode_margin_s = 2 * pol.probe_window_s + 15.0 + (
            pol.join_wait_s + 15.0 if pol.mode == "grow" else 0.0)
        accl.set_timeout(saved_budget + int(episode_margin_s * 1e6))
        accl.call_timeout_s = max(saved_call_s,
                                  saved_budget / 1e6 + episode_margin_s
                                  + 10.0)
        try:
            with self._phase(S_ABORT, comm_id):
                # idempotent: the failure that got us here may already
                # have been an abort (epochs are monotonic, re-revoking
                # a revoked comm is a no-op fan-out)
                accl.abort(comm_id, error=int(ErrorCode.RANK_FAILED))
            with self._phase(S_PROBE, comm_id):
                alive = probe_alive(accl, comm_id, pol.probe_window_s)
                deaths = alive.count(False)
                if _metrics.enabled() and deaths:
                    self._registry.inc("membership/rank_deaths", deaths)
                if sum(alive) <= 1 < len(alive):
                    # nobody else answered: THIS rank is the isolated
                    # (killed/partitioned) one — it must not "shrink"
                    # the world down to itself and carry on
                    self._note(S_HALTED, "isolated: no live peers")
                    if _metrics.enabled():
                        self._registry.inc("recovery/halts")
                    raise ACCLError(
                        f"supervisor(comm {comm_id}): no live peers in "
                        f"{pol.probe_window_s:.1f}s probe — this rank "
                        f"is isolated (original failure: {cause})",
                        int(ErrorCode.RANK_FAILED))
            with self._phase(S_SHRINK, comm_id):
                new_comm = accl.shrink_communicator(
                    comm_id, window_s=pol.probe_window_s)
            if pol.mode == "grow":
                if self.board is None:
                    self._log.warning(
                        "grow policy without a membership board — "
                        "falling back to shrink for this episode")
                else:
                    # state-log marker only (no flight record: the wait
                    # itself runs inside admit_pending, whose duration
                    # the grow phase record below covers; the pure wait
                    # portion is published as the join_wait_us
                    # histogram — a zero-length join_wait record here
                    # would misattribute the bottleneck)
                    self._note(S_JOIN_WAIT)
                    with self._phase(S_GROW, new_comm):
                        new_comm, admitted = admit_pending(
                            accl, new_comm, self.board,
                            wait_s=pol.join_wait_s,
                            window_s=pol.probe_window_s,
                            registry=self._registry)
                        self._note(S_GROW,
                                   f"admitted {admitted} joiner(s), "
                                   f"comm {new_comm}")
            self.comm_id = new_comm
            with self._phase(S_AGREE, new_comm):
                restart = self.agree_restart(first_incomplete,
                                             fresh=fresh)
            self._note(S_RESUME, f"iter {restart} on comm {new_comm}")
            return restart
        finally:
            accl.set_timeout(saved_budget)
            accl.call_timeout_s = saved_call_s
            _health.note_recovering(self._registry, False)
            if _metrics.enabled():
                self._registry.observe_value(
                    "recovery/latency_us",
                    (time.monotonic() - t0) * 1e6)

    def agree_restart(self, first_incomplete: int,
                      fresh: bool = False) -> int:
        """Collective restart-point agreement on the CURRENT comm: an
        allreduce(MAX) of each member's negated first-incomplete
        iteration = the MIN over members — nobody may skip work a
        slower survivor never finished.  Fresh members vote neutrally.
        Also the joiner's entry point: a replacement calls this (via
        run_loop's recovery or directly) as its first collective."""
        accl = self.accl
        vote = _FRESH_MEMBER if fresh else -float(first_incomplete)
        sb = accl.create_buffer(1, np.float32)
        sb.host[0] = vote
        rb = accl.create_buffer(1, np.float32)
        accl.allreduce(sb, rb, 1, ReduceFunction.MAX,
                       comm_id=self.comm_id)
        agreed = -float(rb.host[0])
        if agreed <= _FRESH_MEMBER or agreed >= -_FRESH_MEMBER:
            return 0  # every member is fresh: start from the top
        return max(0, int(agreed))
