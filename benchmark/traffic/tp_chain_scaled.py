"""Closed-loop chains of driver allreduces with a scale between calls.

The units of closed_loop.py's chained mixes, but every call's result is
multiplied by the traffic's ``scale`` (a jitted multiply on each rank,
adopted back into the result buffer) before the next call reads it: the
stand-in for the matmuls that a tensor-parallel layer runs between its
allreduces, which keeps a SUM over several ranks in range over a long
chain.  The kept outputs are the scaled results, and the reference
scales alike; the scale is a power of two, so it is exact in any
binary format and the check's bounds are closed_loop.py's.
"""
from __future__ import annotations

import os

import harness

_base = harness.load_module(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "traffic", "closed_loop")


class Stream(_base.Stream):
    def __init__(self, world, cfg: dict, traffic: dict, seed: int,
                 control: bool = False, here: str = harness.HERE):
        super().__init__(world, cfg, traffic, seed, control, here)
        if not self.chain:
            raise ValueError("tp_chain_scaled scales chained units only")
        self.scale = float(traffic["scale"])
        self._scaled = None

    def _times(self):
        if self._scaled is None:
            import jax

            scale = self.dtype.type(self.scale)
            self._scaled = jax.jit(lambda y: y * scale)
        return self._scaled

    def issue(self, accl, rank: int, u: int) -> list:
        recv = self.recv[rank]
        times = self._times()
        src = self.send[rank][u % self.sets]
        for c, n in enumerate(self.counts):
            self._call(accl, src, recv[c], n)
            recv[c].set_dev_range(0, times(recv[c].dev))
            src = recv[c]
        return [recv[-1].dev]

    def _reference(self, xs: list) -> list:
        return [(ref * self.scale, mag * self.scale)
                for ref, mag in super()._reference(xs)]
