"""Closed-loop streams of driver collectives on device-resident operands.

A traffic file names its generator, a module of this directory, by
``generator``; this one serves every mix whose units are a fixed list of
calls issued back to back on every rank thread.  A generator module
holds ``Stream(world, cfg, traffic, seed, control, here)`` with:

``warmup_units``, ``setup()``, ``issue(accl, rank, u)``
    units run before the window; inputs and buffers; issue unit `u`'s
    calls on one rank and return the arrays to block on;
``plan_checks()``, ``keep(rank, w, u, last=False)``, ``release()``
    draw from the seed which window units are kept for the reference;
    keep a rank's outputs of window unit `w`; free the buffers;
``calls()``, ``expected_lanes()``
    (collective, per-rank payload bytes) of each call of a unit; the
    gangs per unit each engine lane counter must serve;
``check()``
    ({name: (value, limit)}, outputs compared, outputs over the limit).

A configuration's ``unit`` names one unit of work (a training step, a
decoded token) as the calls it makes: the collective (``op``, a module
of ../ops/), its reduce function, the dtype and the element count of
each call, in issue order.  The traffic file says how units are driven:

``chain``
    false: every call has its own send and receive buffer, and each unit
    adopts its input set's arrays into the send buffers (a training step
    hands DDP new gradients).  true: the first call reads the unit's
    input, and every later call reads the previous call's output (the
    residual stream of a decode step).
``input_sets``
    how many different inputs the units cycle through; unit u uses set
    u mod input_sets, so an output that was not produced anew shows.
``warmup_units``
    units run before the window, on the same buffers and programs.
``check``
    which outputs are kept for the reference: ``units`` ("all", or how
    many window units to draw from the first ``units_below``),
    ``calls`` ("last", "all", "each_size": one call of each distinct
    count, or how many to draw per kept unit), ``last_unit_calls``
    (the same, for the window's last unit; absent: none), and
    ``max_rel_err``, the limit (see reference.py).
``control``
    what the control switches: ``compress_dtype`` (the program's own
    lower-precision wire lane) or ``lower_reference`` (the reference
    computed in a lower carrier, reference.CARRIERS, put in the
    program's place, where the program has no such lane).

Every call keeps operands and results on the device, and each rank
thread blocks once per unit.  The inputs are made on the device from the
seed in one jitted call.  The engine's own ring threshold decides which
lane each call is expected on.
"""
from __future__ import annotations

import threading

import numpy as np

import harness
import reference


def np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def seed_key(seed: int):
    """A JAX key from a seed of any size (more than 32 bits)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Stream:
    """One cell's traffic over one world: buffers, units, kept outputs."""

    def __init__(self, world, cfg: dict, traffic: dict, seed: int,
                 control: bool = False, here: str = harness.HERE):
        unit = cfg["unit"]
        self.world = world
        self.op_name = unit["op"]
        self.op = harness.load_module(here, "ops", self.op_name)
        self.function = unit.get("function")
        self.dtype = np_dtype(unit["dtype"])
        self.counts = [int(n) for n in unit["counts"]]
        self.nranks = world.nranks
        self.sizes = [self.op.buffers(n, self.nranks) for n in self.counts]
        self.chain = bool(traffic["chain"])
        if self.chain and any(s != r for s, r in self.sizes):
            raise ValueError(f"{self.op_name} cannot be chained: its "
                             "result is not the size of its input")
        self.sets = int(traffic["input_sets"])
        self.warmup_units = int(traffic["warmup_units"])
        self.check_spec = traffic["check"]
        self.seed = seed
        self.compress = None
        self.lower = None
        if control:
            ctl = traffic["control"]
            self.compress = ctl.get("compress_dtype")
            if "lower_reference" in ctl:
                self.lower = reference.carrier(ctl["lower_reference"])
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        #: (unit, call) -> [output array of each rank]
        self.kept: dict = {}
        self.inputs: list = []   # [rank][set][slot] device arrays
        self.send: list = []     # [rank][slot] buffers
        self.recv: list = []     # [rank][call] buffers

    # -- set-up --------------------------------------------------------
    def make_inputs(self) -> None:
        """Every rank's input sets, on the device, in one jitted call."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devices = list(self.world.engine.devices)
        mesh = Mesh(np.array(devices), ("rank",))
        sh = NamedSharding(mesh, PartitionSpec("rank"))
        slots = [s for s, _ in self.sizes]
        if self.chain:
            slots = slots[:1]
        P, dtype = self.nranks, self.dtype
        shapes = [(P * n,) for _ in range(self.sets) for n in slots]

        def make(key):
            keys = jax.random.split(key, len(shapes))
            return tuple(jax.random.normal(k, s, dtype)
                         for k, s in zip(keys, shapes))

        outs = jax.jit(make, out_shardings=tuple(sh for _ in shapes))(
            seed_key(self.seed))
        rank_of = {d: r for r, d in enumerate(devices)}
        per = [[[None] * len(slots) for _ in range(self.sets)]
               for _ in range(P)]
        for i, arr in enumerate(outs):
            s, slot = divmod(i, len(slots))
            for shard in arr.addressable_shards:
                per[rank_of[shard.device]][s][slot] = shard.data
        self.inputs = per

    def make_buffers(self, accl, rank: int) -> None:
        """This rank's send and receive buffers (run on its thread)."""
        if self.chain:  # one send buffer per input set
            first = [(self.sizes[0][0], x[0]) for x in self.inputs[rank]]
        else:           # one per call, holding set 0 until a unit adopts
            first = [(s, x) for (s, _), x in zip(self.sizes,
                                                 self.inputs[rank][0])]
        send = []
        for n, x in first:
            b = accl.create_buffer(n, self.dtype)
            b.set_dev_range(0, x)
            send.append(b)
        recv = [accl.create_buffer(r, self.dtype) for _, r in self.sizes]
        with self._lock:
            self.send[rank] = send
            self.recv[rank] = recv

    def setup(self) -> None:
        self.make_inputs()
        self.send = [None] * self.nranks
        self.recv = [None] * self.nranks
        self.world.run(self.make_buffers)

    # -- units ---------------------------------------------------------
    def _call(self, accl, src, dst, n: int) -> None:
        kw = {}
        if self.compress is not None:
            from accl_tpu.constants import DataType

            kw["compress_dtype"] = DataType[self.compress]
        if self.function is None:
            self.op.call(accl, src, dst, n, **kw)
        else:
            self.op.call(accl, src, dst, n, self.function, **kw)

    def issue(self, accl, rank: int, u: int) -> list:
        """Issue unit `u`'s calls on this rank; the arrays to block on."""
        recv = self.recv[rank]
        s = u % self.sets
        if self.chain:
            src = self.send[rank][s]
            for c, n in enumerate(self.counts):
                self._call(accl, src, recv[c], n)
                src = recv[c]
            return [recv[-1].dev]
        send, x = self.send[rank], self.inputs[rank][s]
        for c, n in enumerate(self.counts):
            send[c].set_dev_range(0, x[c])
            self._call(accl, send[c], recv[c], n)
        return [b.dev for b in recv]

    def calls(self) -> list:
        return [(self.op_name, s * self.dtype.itemsize)
                for s, _ in self.sizes]

    def expected_lanes(self) -> dict:
        """Gangs per unit that each engine lane must serve, by the
        engine's own ring threshold."""
        threshold = self.world.engine.ring_threshold_bytes
        ring = sum(1 for _, nbytes in self.calls()
                   if self.op.RING_LANE and self.nranks > 1
                   and nbytes >= threshold)
        return {"lane_ring": ring, "lane_hlo": len(self.counts) - ring}

    # -- what the reference sees ---------------------------------------
    def plan_checks(self) -> None:
        """Draw from the seed which window units are kept."""
        spec, rng = self.check_spec, self._rng
        if spec["units"] == "all":
            self._keep_units = None
        else:
            self._keep_units = set(rng.choice(
                int(spec["units_below"]), int(spec["units"]),
                replace=False).tolist())
        self._calls_for = {}

    def _draw_calls(self, how) -> list:
        C = len(self.counts)
        if how == "all":
            return list(range(C))
        if how == "last":
            return [C - 1]
        if how == "each_size":
            by_count: dict = {}
            for c, n in enumerate(self.counts):
                by_count.setdefault(n, []).append(c)
            return sorted(int(self._rng.choice(cs))
                          for cs in by_count.values())
        return sorted(self._rng.choice(C, int(how), replace=False).tolist())

    def keep(self, rank: int, w: int, u: int, last: bool = False) -> None:
        """Keep this rank's outputs of window unit `w` (unit index `u`)
        if the plan says so; ``last`` marks the window's last unit."""
        if last:
            how = self.check_spec.get("last_unit_calls")
            if how is None:
                return
        elif self._keep_units is not None and w not in self._keep_units:
            return
        key = ("last", w) if last else w
        with self._lock:
            calls = self._calls_for.get(key)
            if calls is None:
                how = self.check_spec["last_unit_calls" if last else "calls"]
                calls = self._calls_for[key] = self._draw_calls(how)
            for c in calls:
                self.kept.setdefault((u, c), [None] * self.nranks)[rank] = \
                    self.recv[rank][c].dev

    def release(self) -> None:
        """Free the buffers (the kept outputs and inputs stay)."""
        for bufs in self.send + self.recv:
            for b in bufs:
                b.free()
        self.send, self.recv = [], []

    def _reference(self, xs: list) -> list:
        return self.op.reference(xs, self.function)

    def _lowered(self, xs: list) -> list:
        """The control in the program's place: each rank's result from
        the inputs carried in the lower precision, in the cell's dtype."""
        refs = self._reference([self.lower(x) for x in xs])
        return [ref.astype(self.dtype) for ref, _ in refs]

    def check(self) -> tuple:
        """The largest rel_err over every kept output against the float64
        reference, outputs compared, outputs over the limit.  With a
        lowered reference (the control), its results are compared in
        place of the program's outputs."""
        import jax

        limit = float(self.check_spec["max_rel_err"])
        lowered = self.lower is not None
        errs: list = []
        by_set: dict = {}
        for (u, c), outs in self.kept.items():
            by_set.setdefault(u % self.sets, []).append((c, outs))

        def compare(outs, refs, lows):
            gots = lows if lowered else jax.device_get(outs)
            seen: list = []   # ranks holding bitwise the same result
            for g, pair in zip(gots, refs):   # against the same reference
                err = next((e for h, p, e in seen
                            if p is pair and np.array_equal(g, h)), None)
                if err is None:
                    err = reference.rel_err(g, *pair)
                    seen.append((g, pair, err))
                errs.append(err)

        def inputs(s, c):
            return [np.asarray(jax.device_get(self.inputs[r][s][c]),
                               np.float64) for r in range(self.nranks)]

        for s, items in sorted(by_set.items()):
            if not self.chain:
                for c, outs in items:
                    xs = inputs(s, c)
                    compare(outs, self._reference(xs),
                            lowered and self._lowered(xs))
                continue
            wanted: dict = {}
            for c, outs in items:
                wanted.setdefault(c, []).append(outs)
            cur = low = inputs(s, 0)
            for c in range(max(wanted) + 1):
                refs = self._reference(cur)
                lows = self._lowered(low) if lowered else None
                for outs in wanted.get(c, []):
                    compare(outs, refs, lows)
                cur = [ref.astype(self.dtype).astype(np.float64)
                       for ref, _ in refs]
                if lowered:
                    low = [x.astype(np.float64) for x in lows]
        worst = max(errs, default=0.0)
        checks = {"max_rel_err": (worst, limit)}
        return checks, len(errs), sum(e > limit for e in errs)
