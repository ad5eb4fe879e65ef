"""Closed-loop streams replayed from captured plans.

The same units as closed_loop.py, but each input set's unit is captured
once during set-up by ``ACCL.capture_plan`` (the capture runs its calls)
and every unit replays the captured plan of its input set: the driver's
replay path, which skips descriptor building and gang assembly.  One
plan a unit; chained units only (a replay adopts no new inputs); the
check, control and lanes are closed_loop.py's.
"""
from __future__ import annotations

import os

import harness

_base = harness.load_module(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "traffic", "closed_loop")


class Stream(_base.Stream):
    def setup(self) -> None:
        if not self.chain:
            raise ValueError("tp_plan replays chained units only: a "
                             "replay adopts no new inputs")
        super().setup()
        self.plans = [None] * self.nranks
        self.world.run(self._capture)

    def _capture(self, accl, rank: int) -> None:
        """Capture each input set's unit on this rank's thread."""
        plans = []
        for s in range(self.sets):
            plans.append(accl.capture_plan(
                lambda a, s=s: _base.Stream.issue(self, a, rank, s)))
        with self._lock:
            self.plans[rank] = plans

    def issue(self, accl, rank: int, u: int) -> list:
        self.plans[rank][u % self.sets].replay()
        return [self.recv[rank][-1].dev]

    def release(self) -> None:
        for plans in self.plans:
            for p in plans or ():
                p.close()
        self.plans = []
        super().release()
