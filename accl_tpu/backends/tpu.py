"""TPU backend: XLA HLO collectives over the device mesh.

Reference analog: `FPGADevice`, the hardware backend that dispatches call
descriptors to the CCLO offload engine over the 100G protocol-offload
engines (driver/xrt/src/fpgadevice.cpp).  On TPU the ICI mesh replaces
the POEs and XLA plays the CCLO's role (BASELINE.json north star): every
collective lowers to one jitted `shard_map` program whose body is the
matching XLA HLO collective (`psum`, `all_gather`, `psum_scatter`,
`all_to_all`, ...), compiled once per (scenario, shape, dtype, comm) and
cached.

Driver parity is preserved exactly: each rank holds a normal `ACCL`
handle and submits 15-word call descriptors; a world-level *gang
scheduler* (`TpuEngine`) pairs up the descriptors that the reference's
distributed firmware instances would have matched over the wire, then
runs the SPMD program for the whole gang.  One rank == one device of a
`jax.sharding.Mesh` axis named "rank"; sub-communicators map to
sub-meshes.  The same test corpus that drives the emulator drives this
backend unchanged (SURVEY §4: one suite, every rung).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache
from typing import Callable, Optional, Sequence

import numpy as np

from ..accl import ACCL
from ..arithconfig import ArithConfig
from ..buffer import BaseBuffer
from ..communicator import Communicator, Rank
from ..constants import (
    ACCLError,
    CCLOCall,
    CompressionFlags,
    ErrorCode,
    Operation,
    ReduceFunction,
    StreamFlags,
)
from ..observability import health as _health
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..observability.record import (LOCAL_DISPATCH, S_DISPATCHED,
                                    S_GANG_READY, mark)
from ..request import Request
from ..utils.logging import get_logger
from ..utils.platform import pallas_interpret
from .base import CCLODevice

# address space stride per buffer handle (addresses are opaque ids here,
# not memory offsets; slices advance within the stride)
_ADDR_STRIDE = 1 << 20

#: `stats` counters of the gangs each collective lane executed: the XLA
#: HLO collective, the Pallas ring kernels, the fused chunked ring
_LANE_COUNTERS = ("lane_hlo", "lane_ring", "lane_fused")


def _import_jax():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    return jax, jnp, Mesh, NamedSharding, PartitionSpec


def _global_constructor(shape: tuple, dtype, sharding) -> Callable:
    """A constructor of the global array of `shape` from its
    single-device shards, given as a list in `sharding`'s device order.
    The aval and sharding are fixed here, once, and a call skips the
    per-call validation and rearrangement that
    ``jax.make_array_from_single_device_arrays`` repeats: sound only for
    shards that are whole, of the global's dtype and shard shape and
    committed to their devices — what a plan's all-fast operands are
    (jax 0.9 ``ArrayImpl``; a missing or deleted shard still raises)."""
    from functools import partial

    from jax._src import core
    from jax._src.array import ArrayImpl

    aval = core.update_aval_with_sharding(
        core.ShapedArray(tuple(shape), np.dtype(dtype)), sharding)
    return partial(ArrayImpl, aval, sharding, committed=True,
                   _skip_checks=True)


class TpuBuffer(BaseBuffer):
    """Host numpy array paired with a single-device jax.Array resident on
    this rank's device (the FPGABuffer analog: host map + device BO).

    The device array may have any shape whose size is the buffer's
    length (its *form*): a buffer starts 1-D, and a whole-buffer write
    or an alltoallv payload rebinds it in the written array's shape —
    ``[rows, row]`` for an alltoallv's payload, so a model reads and
    writes its rows with no relayout.  Element ranges (partial writes,
    slices, the host mirror) act on a flat view of it."""

    def __init__(self, host: np.ndarray, device, jax_device, address: int):
        super().__init__(host, address)
        self._device = device
        self._jax_device = jax_device
        import jax

        # copy: on the CPU rung device_put can ALIAS the host numpy
        # (zero-copy), which would let un-synced host writes leak into
        # "device" state — behavior real TPU HBM never has.  The copy
        # keeps the emulation's sync semantics faithful (same reason
        # sync_to_device copies).
        self._dev = jax.device_put(host.copy(), jax_device)

    @property
    def dev(self):
        return self._dev

    @property
    def flat(self):
        """The device array as 1-D elements (a relayout on the device
        when the buffer holds rows)."""
        dev = self._dev
        return dev if dev.ndim == 1 else dev.reshape(-1)

    def set_dev_range(self, start: int, values) -> None:
        """Write `values` into device elements [start, start+size).  A
        write of the whole buffer adopts the array in its own shape."""
        dev = self._dev
        same = values.shape == dev.shape
        if start == 0 and values.dtype == dev.dtype and (
                same or values.size == dev.size):
            # full overwrite: adopt the array instead of dispatching a
            # device scatter (the gang path's per-rank hot path); keep
            # the buffer pinned to its rank's device — host-built values
            # land on the default device otherwise
            import jax

            if getattr(values, "device", None) != self._jax_device:
                values = jax.device_put(values, self._jax_device)
            self._dev = values
            if not same:
                self._device.reform(self)
            return
        self._dev = self.flat.at[start:start + values.size].set(
            values.reshape(-1)).reshape(dev.shape)

    def sync_to_device(self) -> None:
        import jax

        host = self._host.copy()
        if self._dev is not None:   # keep the buffer's form
            host = host.reshape(self._dev.shape)
        self._dev = jax.device_put(host, self._jax_device)

    def sync_from_device(self) -> None:
        self._host[:] = np.asarray(self._dev).reshape(-1)

    def slice(self, start: int, end: int) -> "BaseBuffer":
        return _TpuBufferSlice(self, start, end)

    def free(self) -> None:
        """Release the device array: the engine forgets the buffer and
        every cached gang plan that binds it."""
        self._device.free_buffer(self)
        self._dev = None


class _TpuBufferSlice(BaseBuffer):
    """Sub-span view used by the driver's partial sync logic."""

    def __init__(self, parent: TpuBuffer, start: int, end: int):
        super().__init__(parent.host[start:end],
                         parent.address + start * parent.host.itemsize)
        self._parent = parent
        self._start = start
        self._end = end

    def sync_to_device(self) -> None:
        import jax
        import jax.numpy as jnp

        # copy: jnp.asarray of a host numpy slice can ALIAS it on the
        # CPU rung, and set_dev_range's full-overwrite path ADOPTS the
        # array — the same fidelity hazard TpuBuffer.__init__ copies
        # against (un-synced host writes must never leak into device
        # state)
        vals = jnp.asarray(
            np.array(self._parent.host[self._start:self._end], copy=True))
        self._parent.set_dev_range(self._start, vals)

    def sync_from_device(self) -> None:
        self._parent.host[self._start:self._end] = np.asarray(
            self._parent.flat[self._start:self._end])

    def slice(self, start: int, end: int) -> "BaseBuffer":
        return _TpuBufferSlice(self._parent, self._start + start,
                               self._start + end)


def _records(gang: dict) -> list:
    """A gang's member call records (None where a request has none)."""
    return [req.record for _call, req, _krnl in gang.values()]


class PlanRing:
    """Fixed-slot submission/completion ring for one armed persistent
    plan (accl_tpu/plans.py; io_uring-style).

    Every descriptor of the captured program is pre-resolved at arm
    time into a *slot* — a pinned gang execution plan (buffers bound,
    SPMD program compiled), a pre-paired p2p move, or a local op — so
    a replay is nothing but a sequence-counter bump: the rank's
    ``gen``-th replay joins generation ``gen``; the LAST member to
    arrive executes every slot inline (it holds the whole world's
    pre-resolved state — the leader-dispatch economics applied to the
    entire program, one rendezvous per replay instead of one per call)
    while the others wait on the completion side of the ring.  No
    descriptor build, no dict lookups, no per-call allocation.

    ``invalid`` is the epoch fence: abort / membership change /
    reset_errors poisons the ring and wakes every waiter — a replay
    can raise on a fenced plan but never silently run it."""

    __slots__ = ("slots", "members", "nmembers", "comm_gens", "cv",
                 "rank_gen", "gen_count", "done_gen", "invalid",
                 "replays", "refs")

    def __init__(self, slots: list, members: frozenset,
                 comm_gens: dict):
        self.slots = slots
        self.members = members
        self.nmembers = len(members)
        #: per-rank plan handles sharing this ring (release_ring drops
        #: the pinned state only when the LAST holder dies)
        self.refs = 0
        #: comm id -> engine fence generation at arm time; any bump
        #: (abort/rebuild) makes the ring unreplayable
        self.comm_gens = comm_gens
        self.cv = threading.Condition()
        self.rank_gen: dict = {}    # rank -> replays this rank issued
        self.gen_count: dict = {}   # generation -> arrivals so far
        self.done_gen = 0           # completed replay generations
        self.invalid: Optional[str] = None
        self.replays = 0


class TpuEngine:
    """World-level gang scheduler + jitted collective executor."""

    def __init__(self, nranks: int, devices=None):
        jax, _, Mesh, _, _ = _import_jax()
        all_devices = devices if devices is not None else jax.devices()
        if len(all_devices) < nranks:
            raise ACCLError(
                f"need {nranks} devices, found {len(all_devices)} "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        self.nranks = nranks
        self.devices = list(all_devices[:nranks])
        self._dev_to_rank = {d: r for r, d in enumerate(self.devices)}
        self._lock = threading.Lock()
        # large-message (rendezvous-analog) path: payloads at or above
        # this many bytes route through the Pallas ring kernels
        # (ops/ring.py segmented drivers) instead of the XLA HLO
        # collective — the firmware's eager/rendezvous protocol switch
        # (fw send :589, set_max_eager_msg_size accl.cpp:1415-1423)
        import os as _os

        self.ring_threshold_bytes = int(
            _os.environ.get("ACCL_RING_THRESHOLD", str(4 << 20)))
        # flat-tree tuning-register hints (constants.TuningKey 0..5):
        # written through TpuDeviceView.set_tuning for parity with the
        # native engine's registers; the XLA collective owns the
        # schedule below the ring threshold so these are stored (and
        # observable) rather than consulted per dispatch
        self.tuning_registers: dict = {}
        # per-call completion barrier.  False (default): a collective
        # call completes at DISPATCH — jax arrays are async futures and
        # every consumer (the next collective's operand, sync_from_device
        # readbacks, np.asarray) forces the dependency chain, so results
        # are exact while rank threads overlap their next submission
        # with device execution (the reference fast path likewise posts
        # the descriptor and polls; fpgadevice.cpp:46-180).  True: the
        # executor blocks until the device finishes so get_duration is
        # the on-device perf-counter reading (fw :2280-2303) and any
        # async execution error surfaces in THIS call's retcode instead
        # of at the next consumer.
        self.profile_sync = (
            _os.environ.get("ACCL_PROFILE_SYNC", "0") == "1")
        # leader-dispatch fast path for blocking gangs (_dispatch_gang);
        # ACCL_LEADER_DISPATCH=0 forces every gang through the executor
        # (the pre-r6 path) — the A/B lane the callrate bench reports
        self.leader_dispatch = (
            _os.environ.get("ACCL_LEADER_DISPATCH", "1") != "0")
        # per-rank address -> buffer registry
        self._buffers: list[dict[int, TpuBuffer]] = [dict() for _ in range(nranks)]
        self._next_addr = [_ADDR_STRIDE] * nranks
        # communicators: comm_id -> list of global ranks (must agree across
        # ranks; first upload wins, later uploads validated)
        self._comms: dict[int, list[int]] = {}
        # arithmetic configs, deduplicated across per-rank uploads so ids
        # agree with the driver's table (ACCL.initialize upload order)
        self._arithcfgs: list = []
        self._arithcfg_ids: dict = {}
        # gang assembly: key -> deque of partial gangs
        self._gangs: dict = {}
        # aborted communicators (resilience): comm id -> error bits;
        # submits on them complete immediately, partial gangs drain fast
        self._aborted_comms: dict = {}
        # complete gangs awaiting execution, drained by ONE dedicated
        # executor thread (see _exec_loop): if the completing submitter
        # executed inline (r4 design), that rank thread could not
        # submit its own member of the NEXT gang, so no second gang
        # could ever complete behind a running dispatch and batches
        # never formed.  A dedicated executor lets all rank threads
        # keep submitting while a dispatch is in flight — the queue
        # depth behind it is what the batched dispatch fuses.
        self._ready: deque = deque()
        self._ready_cv = threading.Condition()
        self._shutdown = False
        # leader-dispatch fast path state (see _dispatch_gang): at most
        # ONE gang executes at any moment — either on the executor
        # thread (_exec_busy) or inline on the last-arriving rank's
        # thread (_inline_busy).  Both flags live under _ready_cv so the
        # idle check and the claim are atomic against each other.
        self._exec_busy = False
        self._inline_busy = False
        #: dispatch-lane counters live in a per-engine MetricsRegistry
        #: (observability: callrate bench lanes and the deterministic
        #: fast-path tests read these through the `stats` view).  Each
        #: key has a single writer context — leader_dispatches under the
        #: serialized inline lane, the rest on the executor thread.
        #: ``assemble_checked`` counts executions whose operands took the
        #: checked assembly (_assemble_global); the prepared share is
        #: 1 - assemble_checked / (sum of the lane counters).
        #: ``a2av_flat_operands`` counts alltoallv payload operands that
        #: arrived out of row form and were reshaped at assembly.
        self.metrics = _metrics.MetricsRegistry()
        for k in ("leader_dispatches", "executor_dispatches", "batches",
                  "batched_gangs", "plan_replays",
                  *_LANE_COUNTERS, "assemble_checked",
                  "a2av_flat_operands"):
            self.metrics.inc(k, 0)
        self._log = get_logger("accl_tpu.tpu")
        # per-link wire telemetry twin (r15): (src rank, comm, peer
        # rank) -> counter dict in the LINK_STATS_FIELDS_V2 vocabulary.
        # The gang scheduler IS this backend's wire, so the twin
        # accounts the bytes its ring/tree schedules move per rank pair
        # at dispatch time and folds the gang-assembly straggler wait
        # into seek_wait_ns (the emulator's blocked-receiver analog):
        # every non-last member's wait is attributed to the LAST-
        # arriving rank's link — the peer that actually kept it waiting.
        self._links: dict = {}
        self._link_lock = threading.Lock()
        #: hang watchdog (observability/health.py), armed by
        #: start_watchdog once the world's per-rank flight recorders
        #: exist; fires with this engine's gang-assembly snapshot
        self._watchdog: Optional[_health.Watchdog] = None
        self._exec_thread = threading.Thread(
            target=self._exec_loop, name="accl-gang-exec", daemon=True)
        self._exec_thread.start()
        # gang signature -> resolved execution plan (see _gang_plan);
        # bounded LRU — fresh buffer addresses mint fresh signatures, so
        # an unbounded dict would pin one plan (and its buffers) per
        # training step on the non-resident path
        from collections import OrderedDict

        self._gang_plans: "OrderedDict" = OrderedDict()
        self._gang_plans_cap = 256
        # persistent-plan submission rings (accl_tpu/plans.py): armed
        # rings (pinned — NOT subject to the _gang_plans LRU), the arm
        # rendezvous board pairing concurrent per-rank arms into one
        # ring, and the per-comm fence generation rings snapshot at arm
        # (abort/rebuild bump it, fencing every dependent ring)
        self._plan_rings: list = []
        self._plan_board: list = []
        self._plan_cv = threading.Condition()
        self._reform_lock = threading.Lock()   # see reform
        self._comm_gen: dict = {}
        # kernel streams: (rank, strm_id) -> deque of np arrays
        self._streams: dict[tuple[int, int], deque] = {}
        self._stream_cv = threading.Condition()
        # krnl operand queues per rank (OP0_STREAM sources)
        self._krnl_in: list[deque] = [deque() for _ in range(nranks)]

    @property
    def stats(self) -> dict:
        """Dispatch-lane counter snapshot (kept as the pre-registry
        `stats` dict shape the bench and fast-path tests read)."""
        return self.metrics.counters()

    # ------------------------------------------------------------------
    # buffers / memory
    # ------------------------------------------------------------------
    def create_buffer(self, rank: int, length: int, dtype) -> TpuBuffer:
        host = np.zeros(length, dtype=dtype)
        with self._lock:
            addr = self._next_addr[rank]
            self._next_addr[rank] += _ADDR_STRIDE
        buf = TpuBuffer(host, self, self.devices[rank], addr)
        with self._lock:
            self._buffers[rank][addr] = buf
        return buf

    def free_buffer(self, buf: TpuBuffer) -> None:
        """Drop `buf` from its rank's registry and evict the cached gang
        plans that bind it as operand or result, so nothing pins its
        device array."""
        rank = self._dev_to_rank[buf._jax_device]
        with self._lock:
            self._buffers[rank].pop(buf.address, None)
        self._evict_plans(lambda plan: any(
            o[0] == rank and (o[1] is buf or o[4] is buf)
            for o in plan["ops"] + (plan.get("count_ops") or [])))

    def _evict_plans(self, binds: Callable) -> None:
        """Drop the cached gang plans for which `binds(plan)` holds."""
        with self._lock:
            for sig in [sig for sig, plan in self._gang_plans.items()
                        if binds(plan)]:
                del self._gang_plans[sig]

    def reform(self, buf: TpuBuffer) -> None:
        """`buf`'s device array changed form: re-plan what bound the old
        one — a cached gang plan or an armed ring's slot that takes it as
        an operand of a 1-D global (any collective's but an alltoallv
        payload, which each call checks) is built anew.  Runs on a form
        change only, never on a call that keeps the form."""
        rank = self._dev_to_rank[buf._jax_device]

        def binds(plan):
            if plan is None:
                return False
            opnds = (plan["ops"] if plan["count_ops"] is None
                     else plan["count_ops"])
            return any(o[0] == rank and o[1] is buf for o in opnds)

        self._evict_plans(binds)
        with self._plan_cv:
            rings = list(self._plan_rings)
        # one re-plan at a time: a slot's new plan reads every member's
        # form, and must not be overwritten by one that read less
        with self._reform_lock:
            for ring in rings:
                for slot in ring.slots:
                    if slot["kind"] == "gang" and binds(slot["plan"]):
                        slot["plan"] = self._gang_plan(
                            slot["op"], slot["comm"], slot["gang"])

    def resolve(self, rank: int, addr: int):
        """Map a descriptor address to (buffer, element offset)."""
        if addr == 0:
            return None, 0
        base = addr - (addr % _ADDR_STRIDE)
        buf = self._buffers[rank].get(base)
        if buf is None:
            return None, 0
        off_bytes = addr - base
        return buf, off_bytes // buf.host.itemsize

    # ------------------------------------------------------------------
    # communicators / meshes
    # ------------------------------------------------------------------
    def set_comm(self, comm: Communicator) -> int:
        members = [r.session for r in comm.ranks]
        with self._lock:
            if comm.id in self._comms:
                if self._comms[comm.id] != members:
                    raise ACCLError(
                        f"communicator {comm.id} re-uploaded with different "
                        f"membership")
            else:
                self._comms[comm.id] = members
        return comm.id

    def register_arithcfg(self, cfg: ArithConfig) -> int:
        with self._lock:
            if cfg in self._arithcfg_ids:
                return self._arithcfg_ids[cfg]
            self._arithcfgs.append(cfg)
            self._arithcfg_ids[cfg] = len(self._arithcfgs) - 1
            return self._arithcfg_ids[cfg]

    def wire_dtype_for(self, arithcfg_id: int) -> str:
        """Wire (compressed) representation of an arithcfg pair: "" when
        the pair is identity, else the jnp dtype name selected by the
        compressor lane (arithconfig.py COMPRESS_* ids).  The int8
        block-scaled lane (r17) is a SPEC, not a flat dtype —
        ``int8:<block>:<ef>`` — parsed by :func:`_parse_wire_spec` and
        routed through the ops/quantized.py kernels."""
        if not (0 <= arithcfg_id < len(self._arithcfgs)):
            return ""
        from ..arithconfig import COMPRESSOR_WIRE_DTYPE

        cfg = self._arithcfgs[arithcfg_id]
        if cfg.elem_ratio_log == 0:
            return ""
        from ..arithconfig import DEFAULT_COMPRESS_BLOCK

        name = COMPRESSOR_WIRE_DTYPE.get(cfg.compressor_tdest, "")
        if name == "int8":
            return (f"int8:{cfg.block or DEFAULT_COMPRESS_BLOCK}"
                    f":{int(bool(cfg.error_feedback))}")
        return name

    @lru_cache(maxsize=64)
    def _mesh_for(self, members: tuple) -> "object":
        _, _, Mesh, _, _ = _import_jax()
        devs = np.array([self.devices[m] for m in members])
        return Mesh(devs, ("rank",))

    # ------------------------------------------------------------------
    # gang scheduling
    # ------------------------------------------------------------------
    def submit(self, rank: int, call: CCLOCall, request: Request) -> None:
        scenario = call.scenario
        if scenario in (Operation.config, Operation.nop):
            request.complete(0, 0.0)
            return
        # abort fence (resilience): calls on an aborted comm finalize
        # fast instead of assembling a gang that can never complete
        if self._aborted_comms:
            err = self._aborted_comms.get(call.comm)
            if err is not None:
                request.complete(err, 0.0)
                return
        rec = request.record
        try:
            if scenario in (Operation.copy, Operation.combine):
                if rec is not None:
                    mark((rec,), LOCAL_DISPATCH, state=S_DISPATCHED,
                         lane="local")
                if scenario == Operation.copy:
                    self._exec_copy(rank, call)
                else:
                    self._exec_combine(rank, call)
                if rec is not None:
                    mark((rec,), ("t_launched",))
                request.complete(0, 1.0)
                return
            if scenario in (Operation.send, Operation.recv):
                if rec is not None:
                    mark((rec,), LOCAL_DISPATCH, state=S_DISPATCHED,
                         lane="p2p")
                if scenario == Operation.send:
                    self._submit_send(rank, call, request)
                else:
                    self._submit_recv(rank, call, request)
                return
            self._submit_collective(rank, call, request)
        except Exception as e:  # surface as engine error, not a hang
            from ..constants import ErrorCode

            request.description += f" [{e}]"
            request.complete(int(ErrorCode.DMA_INTERNAL_ERROR), 0.0)

    # -- local ops -----------------------------------------------------
    def _exec_copy(self, rank: int, call: CCLOCall) -> None:
        n = call.count
        # stream-flagged variants (reference copy_to_stream /
        # copy_from_stream, accl.cpp:310 + stream flag algebra): OP0
        # from the local compute-kernel queue, RES into the local
        # kernel stream keyed by the descriptor tag
        if call.stream_flags & StreamFlags.OP0_STREAM:
            q_in = self._krnl_in[rank]
            vals = q_in.popleft() if q_in else None
            if vals is None or vals.shape[0] < n:
                raise ACCLError(
                    f"stream operand {0 if vals is None else vals.shape[0]}"
                    f" elems < required {n}")
            vals = vals[:n]
        else:
            src, soff = self.resolve(rank, call.addr_0)
            vals = src.flat[soff:soff + n]
        if call.stream_flags & StreamFlags.RES_STREAM:
            self._push_stream(rank, call.tag, vals)
            return
        dst, doff = self.resolve(rank, call.addr_2)
        if vals.dtype != dst.dev.dtype:  # per-operand compression: the
            vals = vals.astype(dst.dev.dtype)  # quantize/dequantize lane
        dst.set_dev_range(doff, vals)

    def _exec_combine(self, rank: int, call: CCLOCall) -> None:
        import jax.numpy as jnp

        op0, o0 = self.resolve(rank, call.addr_0)
        op1, o1 = self.resolve(rank, call.addr_1)
        res, o2 = self.resolve(rank, call.addr_2)
        n = call.count
        a, b = op0.flat[o0:o0 + n], op1.flat[o1:o1 + n]
        # mixed-precision combine: arithmetic in the widest operand dtype,
        # result cast to the result buffer's representation (the arithcfg
        # lane selection, arithconfig.py; per-operand OP0/OP1/RES flags)
        cd = a.dtype if a.dtype.itemsize >= b.dtype.itemsize else b.dtype
        a, b = a.astype(cd), b.astype(cd)
        out = jnp.maximum(a, b) if call.function == int(
            ReduceFunction.MAX) else a + b
        res.set_dev_range(o2, out.astype(res.dev.dtype))

    # -- point-to-point ------------------------------------------------
    def _submit_send(self, rank: int, call: CCLOCall, request: Request) -> None:
        import jax

        src, soff = self.resolve(rank, call.addr_0)
        n = call.count
        if call.stream_flags & StreamFlags.OP0_STREAM:
            data = self._krnl_in[rank].popleft()[:n]
        else:
            data = src.flat[soff:soff + n]
        if call.compression_flags & CompressionFlags.ETH_COMPRESSED:
            data = _wire_roundtrip(data, self.wire_dtype_for(call.arithcfg))
        members = self._comms[call.comm]
        dst_rank = members[call.root_src_dst]
        if call.stream_flags & StreamFlags.RES_STREAM:
            # stream_put: land in the destination's kernel stream
            moved = jax.device_put(data, self.devices[dst_rank])
            self._push_stream(dst_rank, call.tag, moved)
            request.complete(0, 1.0)
            return
        # buffered eager semantics: capture payload, complete the sender,
        # deliver when the matching recv arrives.  The channel key
        # carries NO tag — tags are matched at seek time so a TAG_ANY
        # recv pairs with any pending send, the same wildcard semantics
        # the emulator's rx pool implements (native/src/rxpool.hpp,
        # reference rxbuf_seek.cpp:19-78)
        gkey = ("p2p", call.comm, rank, dst_rank)
        with self._lock:
            q = self._gangs.setdefault(gkey, deque())
            q.append(("data", call.tag, data))
        self._try_deliver(gkey)
        request.complete(0, 1.0)

    def _submit_recv(self, rank: int, call: CCLOCall, request: Request) -> None:
        members = self._comms[call.comm]
        src_rank = members[call.root_src_dst]
        gkey = ("p2p", call.comm, src_rank, rank)
        with self._lock:
            q = self._gangs.setdefault(gkey, deque())
            q.append(("recv", call.tag, (rank, call, request)))
        self._try_deliver(gkey)

    def _try_deliver(self, gkey) -> None:
        import jax
        from ..constants import ErrorCode, TAG_ANY

        while True:
            seq_err = None
            with self._lock:
                q = self._gangs.get(gkey)
                if not q:
                    return
                # seek semantics shared with the emulator rung (rxpool
                # seek, native/src/rxpool.hpp:67-78; reference
                # rxbuf_seek.cpp + dma_mover seqn check :579-611): the
                # per-src sequence counter is shared across tags, so the
                # OLDEST recv pairs with the OLDEST pending send; the
                # recv's tag must equal the send's (TAG_ANY matches
                # any), and a mismatch at the head of the stream is the
                # sequence-discipline violation PACK_SEQ_NUMBER_ERROR —
                # NOT a reorder opportunity
                datas = [i for i, e in enumerate(q) if e[0] == "data"]
                recvs = [i for i, e in enumerate(q) if e[0] == "recv"]
                if not datas or not recvs:
                    return
                ri, di = recvs[0], datas[0]
                rtag, dtag = q[ri][1], q[di][1]
                if rtag != TAG_ANY and rtag != dtag:
                    # consume the recv, leave the data queued (the emu
                    # pool keeps mismatched entries for a future
                    # wildcard/same-tag seek)
                    seq_err = q[ri][2]
                    del q[ri]
                else:
                    data = q[di][2]
                    rank, call, request = q[ri][2]
                    for i in sorted((ri, di), reverse=True):
                        del q[i]
            if seq_err is not None:
                _, _, request = seq_err
                request.complete(int(ErrorCode.PACK_SEQ_NUMBER_ERROR), 0.0)
                continue
            dst, doff = self.resolve(rank, call.addr_2)
            n = call.count
            moved = jax.device_put(data[:n], self.devices[rank])
            if call.compression_flags & CompressionFlags.ETH_COMPRESSED:
                moved = _wire_roundtrip(moved,
                                        self.wire_dtype_for(call.arithcfg))
            if dst is not None and moved.dtype != dst.dev.dtype:
                # per-operand compression: land in the RES representation
                moved = moved.astype(dst.dev.dtype)
            if call.stream_flags & StreamFlags.RES_STREAM:
                self._push_stream(rank, call.tag, moved)
            else:
                dst.set_dev_range(doff, moved)
            if request.record is not None:  # delivery ends the launch
                mark((request.record,), ("t_launched",))
            request.complete(0, 1.0)

    # -- collectives ---------------------------------------------------
    def _submit_collective(self, rank: int, call: CCLOCall,
                           request: Request) -> None:
        members = self._comms[call.comm]
        P = len(members)
        # an OP0_STREAM operand is RESERVED in the submitting rank's own
        # thread, preserving the reference's call-order stream pairing —
        # popping at gang-execution time (an arbitrary member's thread)
        # would let a later local stream op on this rank steal it
        krnl = None
        if call.stream_flags & StreamFlags.OP0_STREAM:
            in_len = call.count * (
                P if Operation(call.scenario) in (
                    Operation.scatter, Operation.reduce_scatter,
                    Operation.alltoall) else 1)
            q_in = self._krnl_in[rank]
            krnl = q_in.popleft() if q_in else None
            if krnl is None or krnl.shape[0] < in_len:
                # silent truncation/zero-padding of a short stream
                # operand would corrupt the reduction with retcode 0
                request.description += (
                    f" [stream operand {0 if krnl is None else krnl.shape[0]}"
                    f" elems < required {in_len}]")
                request.complete(
                    int(ErrorCode.SEGMENTER_EXPECTED_BTT_ERROR), 0.0)
                return
        traced = _trace.enabled()
        if traced:  # accl.gang: submit through gang join
            _trace.stage("accl.gang")
        gkey = ("coll", int(call.scenario), call.comm, call.tag)
        # link twin (r15): gang-arrival stamp for straggler-wait
        # attribution (one clock read per collective submit)
        request.link_arrival_ns = _trace.now_ns()
        ready = None
        with self._lock:
            q = self._gangs.setdefault(gkey, deque())
            # find first gang this rank hasn't joined yet (FIFO per key)
            for gang in q:
                if rank not in gang:
                    gang[rank] = (call, request, krnl)
                    if len(gang) == P:
                        ready = gang
                        q.remove(gang)
                    break
            else:
                gang = {rank: (call, request, krnl)}
                q.append(gang)
                if P == 1:
                    ready = gang
                    q.remove(gang)
        if ready is None:
            if traced:  # joined: accl.gang ends
                _trace.stage(None)
            return
        t_ready = _trace.now_ns()  # last member arrived: gang exists
        # joined: accl.gang ends
        mark(_records(ready), ("t_gang_ready",), t_ready,
             state=S_GANG_READY, stage=None)
        self._account_gang_wait(call.comm, ready, t_ready)
        self._dispatch_gang(int(call.scenario), call.comm, ready, request)

    def _dispatch_gang(self, scenario: int, comm_id: int, gang: dict,
                       leader_req: Request) -> None:
        """Route one complete gang to its dispatch lane.

        Leader-dispatch fast path (the reference's post-and-poll call
        economics, fpgadevice.cpp:24-33): when every member's request
        is BLOCKING (sync-resident), the last-arriving rank runs the
        fused program inline on its own thread — no executor wakeup on
        the way in, and the leader's own completion needs no futex wait
        on the way out, so the critical path loses one full thread
        rendezvous.  Safe because every member's submitter is parked in
        Request.wait until this very gang completes: inline execution
        cannot stall anyone's next submission (the r4 inline design
        failed exactly there for ASYNC submitters, which is why the
        async lane keeps the posted-descriptor + executor path and its
        gang batching).

        The inline run is DEFERRED to the leader's Request.wait (the
        pre_wait hook): this method is reached under the leader rank's
        RequestQueue submission lock, and executing the gang program
        there would stall a concurrent submission on the same handle
        for the whole device dispatch — wait() runs microseconds later
        on the same thread, after the lock is released.  A sync gang's
        leader waits by definition, so the thunk always runs.

        The fast path requires the engine to be otherwise IDLE — no
        queued gangs and no dispatch in flight — so execution stays
        globally one-at-a-time in gang-completion order, exactly the
        executor's serialization (concurrent dispatch of two gangs
        sharing a member's buffers would race the rebind).  Any async
        member, or a busy engine at thunk-run time, falls back to the
        executor queue."""
        if self.leader_dispatch and all(
                req.sync for _c, req, _k in gang.values()):

            def run_inline() -> None:
                with self._ready_cv:
                    idle = (not self._ready and not self._exec_busy
                            and not self._inline_busy)
                    if idle:
                        self._inline_busy = True
                if not idle:
                    self._enqueue_ready(scenario, comm_id, gang)
                    return
                try:
                    self.metrics.inc("leader_dispatches")
                    self._exec_gang(scenario, comm_id, gang)
                finally:
                    with self._ready_cv:
                        self._inline_busy = False
                        if self._ready or self._shutdown:
                            self._ready_cv.notify()

            leader_req.pre_wait = run_inline
            return
        self._enqueue_ready(scenario, comm_id, gang)

    def _enqueue_ready(self, scenario: int, comm_id: int,
                       gang: dict) -> None:
        with self._ready_cv:
            self._ready.append((scenario, comm_id, gang))
            self._ready_cv.notify()

    # -- per-link wire telemetry twin (r15) ----------------------------
    def _link_add(self, src: int, comm: int, peer: int, **counts) -> None:
        with self._link_lock:
            row = self._links.setdefault((src, comm, peer), {})
            for k, v in counts.items():
                row[k] = row.get(k, 0) + int(v)

    @staticmethod
    def _wire_ratio(wire_dtype: str) -> float:
        """Wire bytes per logical byte for a wire spec ("" = 1.0): the
        cast lanes halve the payload; the int8 block-scaled lane packs
        ~4:1 plus one fp32 scale per block."""
        if not wire_dtype:
            return 1.0
        name, block, _ef = _parse_wire_spec(wire_dtype)
        if name == "int8":
            return (1.0 + 4.0 / max(block, 1)) / 4.0
        return 0.5  # float16 / bfloat16

    def _account_links(self, plan: dict) -> None:
        """Fold one execution of `plan` into the link twin and the
        compressed_tx_* counters: its precomputed deltas, under one
        lock acquisition."""
        links = plan["links"]
        if links is None:
            return
        rows, comp = links
        if comp is not None:
            self.metrics.inc("compressed_tx_logical_bytes", comp[0])
            self.metrics.inc("compressed_tx_bytes", comp[1])
        with self._link_lock:
            table = self._links
            for key, counts in rows:
                row = table.get(key)
                if row is None:
                    row = table[key] = {}
                for k, v in counts:
                    row[k] = row.get(k, 0) + v

    def _link_deltas(self, op, comm_id: int, members: list,
                     root_local: int, nbytes: int,
                     wire_dtype: str = "") -> Optional[tuple]:
        """What one dispatched gang adds to the link twin: ((rows,
        compressed), with rows ((src, comm, peer), ((field, count),
        ...)) and compressed (logical bytes, wire bytes) or None), or
        None when there is nothing to add.  It depends only on the
        gang's signature, so a plan computes it once.

        Ring collectives move ``busbw_factor × nbytes`` per rank to its
        right ring neighbor over P-1 (allgather/reduce_scatter) or
        2(P-1) (allreduce) hops — the same nccl-tests accounting the
        metrics registry derives bandwidth from, so the matrix and the
        busbw gauges agree by construction.  Rooted collectives
        attribute the payload to the root<->member links.  With a
        compressed ``wire_dtype`` the same logical traffic is also
        accounted at its compressed wire width (comp_tx_bytes per link,
        compressed_tx_* engine counters — the r17 bytes-saved plane)."""
        P = len(members)
        name = Operation(op).name
        if name == "alltoallv" and P > 1:
            # the counts live on the device: the host records one
            # message per link and no bytes (the caller tallies them)
            rows = []
            for src in members:
                for peer in members:
                    if peer != src:
                        rows.append(((src, comm_id, peer), (("tx_msgs", 1),)))
                        rows.append(((peer, comm_id, src), (("rx_msgs", 1),)))
            return tuple(rows), None
        if P < 2 or nbytes <= 0:
            return None
        ratio = self._wire_ratio(wire_dtype)
        comp_counts = None
        if ratio < 1.0:
            # nbytes is in_len * itemsize, which ALREADY carries the P
            # factor for the n*P-operand collectives — divide it back
            # out so logical = descriptor count x payload_factor, the
            # same convention the native engine and metrics use
            per_count = nbytes // (
                P if name in ("scatter", "reduce_scatter", "alltoall")
                else 1)
            logical = int(per_count * _metrics.payload_factor(name, P))
            comp_counts = (logical, int(logical * ratio))
        rows: dict = {}

        def add(src: int, peer: int, **counts) -> None:
            row = rows.setdefault((src, comm_id, peer), {})
            for k, v in counts.items():
                row[k] = row.get(k, 0) + int(v)

        if name in ("allreduce", "allgather", "reduce_scatter",
                    "alltoall"):
            # nbytes is the per-rank operand (plan in_len); the busbw
            # factors apply to the TOTAL moved payload, which for
            # allgather is P x the per-rank contribution (the
            # nccl-tests payload_factor convention)
            if name == "allgather":
                nbytes *= P
            per_link = int(nbytes * _metrics.busbw_factor(name, P))
            hops = 2 * (P - 1) if name == "allreduce" else P - 1
            comp = int(per_link * ratio) if ratio < 1.0 else 0
            for i, src in enumerate(members):
                right = members[(i + 1) % P]
                left = members[(i - 1) % P]
                add(src, right, tx_msgs=hops, tx_bytes=per_link,
                    comp_tx_bytes=comp)
                add(src, left, rx_msgs=hops, rx_bytes=per_link)
        elif name in ("bcast", "scatter", "gather", "reduce"):
            root = members[root_local] if root_local < P else members[0]
            to_root = name in ("gather", "reduce")
            # scatter's operand is the root's WHOLE input (in_len =
            # n*P); each root->member link carries only its 1/P slice
            per_link = nbytes // P if name == "scatter" else nbytes
            comp = int(per_link * ratio) if ratio < 1.0 else 0
            for m in members:
                if m == root:
                    continue
                a, b = (m, root) if to_root else (root, m)
                add(a, b, tx_msgs=1, tx_bytes=per_link, comp_tx_bytes=comp)
                add(b, a, rx_msgs=1, rx_bytes=per_link)
        return (tuple((key, tuple(row.items()))
                      for key, row in rows.items()), comp_counts)

    def _account_gang_wait(self, comm_id: int, gang: dict,
                           t_ready: int) -> None:
        """Straggler wait as the seek-latency analog: every non-last
        member's (t_last − t_own) is attributed to the LAST-arriving
        rank's link — the peer that actually kept the gang waiting."""
        arrivals = {r: getattr(req, "link_arrival_ns", None)
                    for r, (_c, req, _k) in gang.items()}
        known = {r: t for r, t in arrivals.items() if t is not None}
        if len(known) < 2:
            return
        last_rank = max(known, key=lambda r: known[r])
        t_last = known[last_rank]
        for r, t in known.items():
            if r == last_rank:
                continue
            self._link_add(r, comm_id, last_rank, seeks=1,
                           seek_wait_ns=max(t_last - t, 0))

    def link_stats_for(self, rank: int) -> list:
        """One rank's link rows in the LINK_STATS_FIELDS_V2 vocabulary
        (TpuDeviceView.link_stats body).  Peers are GLOBAL ranks — the
        gang scheduler addresses members globally; on comm 0 the two
        vocabularies coincide, which is what link_matrix folds."""
        from ..observability import telemetry as _telemetry

        rows = []
        with self._link_lock:
            for (src, comm, peer), c in sorted(self._links.items()):
                if src != rank:
                    continue
                row = {"comm": comm, "peer": peer}
                for f in _telemetry.LINK_COUNTER_FIELDS:
                    row[f] = int(c.get(f, 0))
                rows.append(row)
        return rows

    def abort_comm(self, comm_id: int, err_bits: int) -> bool:
        """Epoch-analog abort for the in-process TPU engine: mark the
        comm aborted (future submits finalize immediately) and drain
        every PARTIAL gang and pending p2p recv on it, completing their
        requests with `err_bits` — blocked waiters on every rank wake
        at once.  Complete gangs already queued for dispatch run to
        completion (they have all members; executing them is safe).
        The gang-table rebuild half of elastic recovery starts here:
        the dead comm's cached execution plans are evicted so a grown
        successor never pins the old world's buffers or meshes."""
        drained = []
        with self._lock:
            self._aborted_comms[comm_id] = err_bits
            # epoch fence for persistent plans: any ring armed against
            # the pre-abort world is now stale
            self._comm_gen[comm_id] = self._comm_gen.get(comm_id, 0) + 1
            for key in list(self._gangs):
                if key[0] == "coll" and key[2] == comm_id:
                    for gang in self._gangs.pop(key):
                        drained.extend(req for _c, req, _k in gang.values())
                elif key[0] == "p2p" and key[1] == comm_id:
                    for entry in self._gangs.pop(key):
                        if entry[0] == "recv":
                            drained.append(entry[2][2])
            for sig in [s for s in self._gang_plans if s[1] == comm_id]:
                del self._gang_plans[sig]
        self.invalidate_rings(comm_id, "communicator aborted")
        for req in drained:
            if not req.done:
                req.complete(err_bits, 0.0)
        return True

    # ------------------------------------------------------------------
    # elastic membership (r11): sponsor-side state sync + rebuild
    # ------------------------------------------------------------------
    def comm_count(self) -> int:
        """Comm slots this world-level scheduler knows (the in-process
        twin of the native engine's comm_count): the join path pads a
        late rank's driver table to this before the grown upload."""
        with self._lock:
            return (max(self._comms) + 1) if self._comms else 0

    def export_join_state(self, comm_id: int = 0) -> dict:
        """Sponsor-side state sync for an in-process joiner: the
        world's comm-slot count, the abort fence table, and the
        members of the comm being recovered — everything a replacement
        rank's driver needs to align before adopting a grown comm.
        (The wire Join/Welcome/StateSync exchange of the emulator rung
        collapses to this dict: the scheduler IS the control plane.)"""
        with self._lock:
            return {
                "comm_count": (max(self._comms) + 1) if self._comms
                else 0,
                "aborted": dict(self._aborted_comms),
                "members": list(self._comms.get(comm_id, [])),
            }

    def rebuild_gang_tables(self, comm_id: int) -> int:
        """Drop every partial gang and cached plan referencing
        ``comm_id`` (grow path: a successor comm must assemble against
        a clean table — a stale partial gang from the dead world could
        otherwise swallow a new member's first call).  Returns how many
        entries were evicted; their requests finalize with the comm's
        abort bits (or COMM_ABORTED when it was never aborted)."""
        err = None
        drained = []
        with self._lock:
            err = self._aborted_comms.get(
                comm_id, int(ErrorCode.COMM_ABORTED))
            self._comm_gen[comm_id] = self._comm_gen.get(comm_id, 0) + 1
            evicted = 0
            for key in [k for k in self._gangs
                        if (k[0] == "coll" and k[2] == comm_id)
                        or (k[0] == "p2p" and k[1] == comm_id)]:
                for gang in self._gangs.pop(key):
                    evicted += 1
                    if isinstance(gang, dict):  # coll: rank -> entry
                        drained.extend(
                            req for _c, req, _k in gang.values())
                    elif gang[0] == "recv":  # p2p pending recv tuple
                        # ("recv", tag, (rank, call, request)) — same
                        # shape abort_comm finalizes: the blocked
                        # waiter must wake NOW, not at the driver
                        # budget ("data" entries carry no request)
                        drained.append(gang[2][2])
            for sig in [s for s in self._gang_plans if s[1] == comm_id]:
                del self._gang_plans[sig]
                evicted += 1
        self.invalidate_rings(comm_id, "gang tables rebuilt (grow)")
        for req in drained:
            if not req.done:
                req.complete(err, 0.0)
        return evicted

    def reset_comm_errors(self) -> None:
        """Clear abort fencing (driver reset_errors path).  Every armed
        plan ring is invalidated too: reset_errors is a world-state
        discontinuity, and a healed world must re-capture rather than
        replay pre-reset state."""
        with self._lock:
            self._aborted_comms.clear()
        self.invalidate_rings(None, "reset_errors")

    # ------------------------------------------------------------------
    # persistent-plan submission rings (accl_tpu/plans.py)
    # ------------------------------------------------------------------
    def arm_plan(self, rank: int, calls: Sequence[CCLOCall],
                 expected: frozenset, timeout_s: float) -> PlanRing:
        """Arm one rank's captured descriptor stream.  Ranks arming
        concurrently (every member of ``expected``) rendezvous on the
        arm board; the LAST arrival lowers the whole group into one
        :class:`PlanRing` — gang pairing, buffer resolution, dtype
        widening, sharding construction and AOT compilation all paid
        here, once, instead of per call."""
        with self._plan_cv:
            group = None
            for g in self._plan_board:
                # join only a group with the IDENTICAL member union:
                # every rank of one logical capture derives the same
                # union (any shared gang guarantees it), and mere
                # overlap would fuse two distinct concurrent captures
                # that happen to share ranks into one broken ring.
                # Plans whose per-rank unions differ (pure-p2p chains
                # with asymmetric routes) arm-time out decodably —
                # include a barrier/gang to give every rank the union.
                if rank not in g["arrived"] and not g["building"] \
                        and g["expected"] == set(expected):
                    group = g
                    break
            if group is None:
                group = {"arrived": {}, "expected": set(expected),
                         "ring": None, "error": None, "building": False}
                self._plan_board.append(group)
            group["arrived"][rank] = list(calls)
            complete = set(group["arrived"]) >= group["expected"]
            if complete:
                group["building"] = True
        if complete:
            ring = err = None
            try:
                ring = self._build_ring(group["arrived"],
                                        group["expected"])
            except Exception as e:  # noqa: BLE001 — every armer must
                err = e             # see the same failure, not a hang
            with self._plan_cv:
                group["ring"], group["error"] = ring, err
                if group in self._plan_board:
                    self._plan_board.remove(group)
                if ring is not None:
                    self._plan_rings.append(ring)
                self._plan_cv.notify_all()
            if err is not None:
                raise err if isinstance(err, ACCLError) else ACCLError(
                    f"plan arm failed: {err}")
            with ring.cv:
                ring.refs += 1  # this rank's plan handle
            return ring
        deadline = time.monotonic() + timeout_s
        with self._plan_cv:
            while group["ring"] is None and group["error"] is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._plan_cv.wait(remaining):
                    if group["ring"] is not None \
                            or group["error"] is not None:
                        break
                    if group["building"]:
                        # the last rank arrived and the build (AOT
                        # compile) is in flight: it ALWAYS publishes a
                        # ring or an error — poisoning now would race
                        # the builder's overwrite and strand a ring
                        # whose member count includes this rank.  Wait
                        # for the build result instead.
                        deadline = time.monotonic() + timeout_s
                        continue
                    missing = sorted(set(group["expected"])
                                     - set(group["arrived"]))
                    err = ACCLError(
                        f"plan arm timed out after {timeout_s:.0f}s "
                        f"waiting for rank(s) {missing} to capture the "
                        f"same plan — capture_plan is collective over "
                        f"every gang/p2p peer of the captured program")
                    # poison + retire the group so a late arm can never
                    # complete it against this rank's abandoned calls
                    # (fellow waiters fail consistently; retries open a
                    # FRESH group)
                    group["error"] = err
                    if group in self._plan_board:
                        self._plan_board.remove(group)
                    self._plan_cv.notify_all()
                    raise err
            if group["error"] is not None:
                e = group["error"]
                raise e if isinstance(e, ACCLError) else ACCLError(
                    f"plan arm failed: {e}")
            ring = group["ring"]
        # refs outside the board lock: release_ring takes ring.cv then
        # _plan_cv, so taking ring.cv under _plan_cv would invert
        with ring.cv:
            ring.refs += 1  # this rank's plan handle
        return ring

    def _build_ring(self, lists: dict, expected: set) -> PlanRing:
        """Lower a complete arm group into ring slots: merge the
        per-rank call streams into one serializable schedule (the gang
        pairing the runtime scheduler would have done per call, done
        once), resolving every operand and pre-compiling every SPMD
        program."""
        from ..constants import TAG_ANY

        ranks = sorted(lists)
        comm_gens: dict = {}

        def note_comm(comm_id: int) -> list:
            members = self._comms.get(comm_id)
            if members is None:
                raise ACCLError(f"plan arm: unknown communicator "
                                f"{comm_id}")
            if comm_id in self._aborted_comms:
                raise ACCLError(
                    f"plan arm: communicator {comm_id} is aborted — "
                    f"recover first, then capture",
                    int(ErrorCode.COMM_ABORTED))
            comm_gens.setdefault(comm_id, self._comm_gen.get(comm_id, 0))
            return members

        heads = {r: 0 for r in ranks}
        total = sum(len(v) for v in lists.values())
        made = 0
        slots: list = []
        pending: dict = {}  # (comm, src, dst) -> deque of sends
        while made < total:
            progressed = False
            for r in ranks:
                i = heads[r]
                if i >= len(lists[r]):
                    continue
                call = lists[r][i]
                op = Operation(call.scenario)
                if call.stream_flags:
                    raise ACCLError(
                        "plan arm: stream-operand calls are not "
                        "replayable — keep stream traffic eager")
                if op in (Operation.config, Operation.nop):
                    heads[r] += 1
                    made += 1
                    progressed = True
                elif op in (Operation.copy, Operation.combine):
                    slots.append({"kind": "local", "rank": r,
                                  "call": call})
                    heads[r] += 1
                    made += 1
                    progressed = True
                elif op == Operation.send:
                    members = note_comm(call.comm)
                    dst = members[call.root_src_dst]
                    pending.setdefault((call.comm, r, dst),
                                       deque()).append((r, call))
                    heads[r] += 1
                    made += 1
                    progressed = True
                elif op == Operation.recv:
                    members = note_comm(call.comm)
                    src = members[call.root_src_dst]
                    q = pending.get((call.comm, src, r))
                    if not q:
                        continue  # sender not reached yet
                    s_rank, s_call = q.popleft()
                    if call.tag != TAG_ANY and call.tag != s_call.tag:
                        raise ACCLError(
                            f"plan arm: recv tag {call.tag} does not "
                            f"match the oldest pending send tag "
                            f"{s_call.tag} on route {s_rank}->{r} "
                            f"(the PACK_SEQ sequence discipline)")
                    sbuf, soff = self.resolve(s_rank, s_call.addr_0)
                    dbuf, doff = self.resolve(r, call.addr_2)
                    if sbuf is None or dbuf is None:
                        raise ACCLError(
                            "plan arm: p2p operand does not resolve "
                            "to a registered device buffer")
                    eth = ((int(s_call.compression_flags)
                            | int(call.compression_flags))
                           & int(CompressionFlags.ETH_COMPRESSED))
                    slots.append({
                        "kind": "p2p", "src_rank": s_rank,
                        "dst_rank": r, "src": sbuf, "soff": soff,
                        "dst": dbuf, "doff": doff, "n": call.count,
                        "wire": (self.wire_dtype_for(s_call.arithcfg)
                                 if eth else "")})
                    heads[r] += 1
                    made += 1
                    progressed = True
                else:  # gang collective
                    members = note_comm(call.comm)
                    ready = True
                    for m in members:
                        if m not in lists:
                            raise ACCLError(
                                f"plan arm: comm {call.comm} member "
                                f"{m} never captured this plan — "
                                f"every member must capture_plan the "
                                f"same program")
                        j = heads[m]
                        if j >= len(lists[m]) or \
                                (lists[m][j].scenario, lists[m][j].comm,
                                 lists[m][j].tag) != (call.scenario,
                                                      call.comm,
                                                      call.tag):
                            ready = False
                            break
                    if not ready:
                        continue
                    gang = {m: (lists[m][heads[m]], None, None)
                            for m in members}
                    plan = (None if op == Operation.barrier
                            else self._gang_plan(op, call.comm, gang))
                    slots.append({"kind": "gang", "op": op,
                                  "comm": call.comm, "gang": gang,
                                  "plan": plan})
                    for m in members:
                        heads[m] += 1
                    made += len(members)
                    progressed = True
            if not progressed:
                raise ACCLError(
                    "plan arm: captured steps do not form a "
                    "serializable schedule (cross-rank call order "
                    "diverges, or a recv waits on a send outside the "
                    "plan) — run scripts/accl_lint.py on the program")
        leftover = sum(len(q) for q in pending.values())
        if leftover:
            raise ACCLError(
                f"plan arm: {leftover} send(s) have no matching recv "
                f"inside the plan — p2p must pair within the captured "
                f"program")
        return PlanRing(slots, frozenset(expected), comm_gens)

    def ring_replay(self, rank: int, ring: PlanRing,
                    run_async: bool = False,
                    timeout_s: float = 60.0) -> int:
        """The replay hot path: bump this rank's sequence counter; the
        generation's LAST arrival executes every pre-resolved slot
        inline, everyone else rides the completion side.  Returns the
        generation (the async ticket's token)."""
        with ring.cv:
            if ring.invalid is not None:
                raise ACCLError(
                    f"plan replay: plan invalidated ({ring.invalid})",
                    int(ErrorCode.COMM_ABORTED))
            g = ring.rank_gen.get(rank, 0) + 1
            ring.rank_gen[rank] = g
            n = ring.gen_count.get(g, 0) + 1
            last = n == ring.nmembers
            if last:
                ring.gen_count.pop(g, None)
            else:
                ring.gen_count[g] = n
        if last:
            self._ring_execute(ring, g, timeout_s)
            return g
        if run_async:
            return g
        if not self.ring_wait(ring, g, timeout_s):
            raise ACCLError(
                f"plan replay: generation {g} never completed within "
                f"{timeout_s:.0f}s (a member rank stopped replaying?)")
        return g

    def ring_wait(self, ring: PlanRing, gen: int,
                  timeout_s: float = 60.0) -> bool:
        """Completion side of the ring: block until generation ``gen``
        finished.  False on timeout; raises when the ring was fenced."""
        deadline = time.monotonic() + timeout_s
        with ring.cv:
            while ring.done_gen < gen:
                if ring.invalid is not None:
                    raise ACCLError(
                        f"plan replay: plan invalidated "
                        f"({ring.invalid})",
                        int(ErrorCode.COMM_ABORTED))
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                ring.cv.wait(remaining)
        return True

    def _ring_execute(self, ring: PlanRing, gen: int,
                      timeout_s: float) -> None:
        # generation ordering: an async pump can trigger gen g while
        # g-1 is mid-execution on another thread — executions must
        # land in order (slots rebind buffers)
        deadline = time.monotonic() + timeout_s
        with ring.cv:
            while ring.done_gen < gen - 1 and ring.invalid is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ACCLError(
                        f"plan replay: generation {gen - 1} never "
                        f"completed within {timeout_s:.0f}s")
                ring.cv.wait(remaining)
            if ring.invalid is not None:
                raise ACCLError(
                    f"plan replay: plan invalidated ({ring.invalid})",
                    int(ErrorCode.COMM_ABORTED))
        # epoch fence: the comm generations must still match the armed
        # snapshot — a replay must never run on a fenced epoch
        for comm_id, gen0 in ring.comm_gens.items():
            if self._comm_gen.get(comm_id, 0) != gen0 \
                    or comm_id in self._aborted_comms:
                self._invalidate_ring(
                    ring, f"communicator {comm_id} fenced since arm")
                raise ACCLError(
                    f"plan replay: communicator {comm_id} was fenced "
                    f"(abort/epoch bump) since the plan was armed — "
                    f"re-capture on the recovered communicator",
                    int(ErrorCode.COMM_ABORTED))
        # claim the engine's one-gang-program-at-a-time slot (the same
        # serialization invariant the leader/executor lanes uphold)
        with self._ready_cv:
            while (self._ready or self._exec_busy
                   or self._inline_busy) and not self._shutdown:
                self._ready_cv.wait(0.05)
            if self._shutdown:
                raise ACCLError(
                    "plan replay: engine shut down while waiting for "
                    "the dispatch slot")
            self._inline_busy = True
        try:
            self.metrics.inc("plan_replays")
            for slot in ring.slots:
                self._exec_slot(slot)
        except Exception as e:
            self._invalidate_ring(ring, f"replay execution failed: {e}")
            if isinstance(e, ACCLError):
                raise
            raise ACCLError(f"plan replay failed: {e}") from e
        finally:
            with self._ready_cv:
                self._inline_busy = False
                if self._ready or self._shutdown:
                    self._ready_cv.notify()
        with ring.cv:
            ring.done_gen = gen
            ring.replays += 1
            ring.cv.notify_all()

    def _exec_slot(self, slot: dict) -> None:
        kind = slot["kind"]
        if kind == "gang":
            plan = slot["plan"]
            if plan is None:  # barrier: the replay rendezvous IS it
                return
            x = self._assemble_global(plan, slot["gang"])
            # link twin (r15): replayed collectives are the whole
            # steady-state traffic of a captured plan — without this
            # the matrix would report near-zero for exactly the lane
            # that matters (no gang-wait here: a replay rendezvouses
            # on the ring sequence, not per-member arrival)
            self._account_links(plan)
            self.metrics.inc(plan["lane"])
            y = plan["compiled"](x)
            self._scatter_back(plan, y)
        elif kind == "local":
            call = slot["call"]
            if call.scenario == Operation.copy:
                self._exec_copy(slot["rank"], call)
            else:
                self._exec_combine(slot["rank"], call)
        else:  # p2p: pre-paired direct device-to-device move
            import jax

            data = slot["src"].flat[slot["soff"]:slot["soff"]
                                    + slot["n"]]
            if slot["wire"]:
                data = _wire_roundtrip(data, slot["wire"])
            moved = jax.device_put(data, self.devices[slot["dst_rank"]])
            dst = slot["dst"]
            if moved.dtype != dst.dev.dtype:
                moved = moved.astype(dst.dev.dtype)
            dst.set_dev_range(slot["doff"], moved)

    def _invalidate_ring(self, ring: PlanRing, reason: str) -> None:
        with ring.cv:
            if ring.invalid is None:
                ring.invalid = reason
            ring.cv.notify_all()

    def invalidate_rings(self, comm_id: Optional[int],
                         reason: str) -> None:
        """Fence every armed ring touching ``comm_id`` (None = all) and
        wake their waiters — called from abort/rebuild/reset, and by
        the driver's shrink/grow plan-fencing contract."""
        with self._plan_cv:
            keep = []
            for ring in self._plan_rings:
                if comm_id is None or comm_id in ring.comm_gens:
                    self._invalidate_ring(ring, reason)
                else:
                    keep.append(ring)
            self._plan_rings = keep

    def release_ring(self, ring: PlanRing) -> None:
        """Drop one rank's handle on a ring (its plan object died or
        was closed); when the LAST holder releases, the ring is fenced
        and its pinned compiled programs/buffer bindings are dropped —
        the engine must not pin dead plans' state forever (rings are
        otherwise evicted only by a comm fence)."""
        with ring.cv:
            ring.refs -= 1
            if ring.refs > 0:
                return
        self._invalidate_ring(ring, "plan released")
        with self._plan_cv:
            if ring in self._plan_rings:
                self._plan_rings.remove(ring)
        ring.slots = []  # drop the pinned gang plans/buffers now

    def shutdown(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        with self._ready_cv:
            self._shutdown = True
            self._ready_cv.notify()

    # ------------------------------------------------------------------
    # hang diagnosis (observability/health.py watchdog integration)
    # ------------------------------------------------------------------
    def start_watchdog(self, recorders) -> Optional["object"]:
        """Arm the per-engine hang watchdog over the world's per-rank
        flight recorders (ACCL_WATCHDOG_TIMEOUT seconds; 0 disables).
        On fire, the report embeds gang_assembly_snapshot() so the
        partial gangs inside this scheduler are named directly."""
        if self._watchdog is None:
            self._watchdog = _health.Watchdog(
                recorders, introspect=self.gang_assembly_snapshot,
                name="accl-tpu").start()
        return self._watchdog

    def gang_assembly_snapshot(self) -> list:
        """Introspection hook: every PARTIAL gang still assembling in
        _gangs — which ranks arrived with what call, which members are
        missing — the engine-level truth the watchdog report pairs with
        the per-rank flight rings."""
        now = _trace.now_ns()
        out = []
        with self._lock:
            # copy under the lock: gang dicts mutate as ranks join, and
            # p2p queues hold ("data"/"recv", tag, payload) tuples
            items = [(k, ([dict(g) for g in q] if k[0] == "coll"
                          else [(e[0], e[1]) for e in q]))
                     for k, q in self._gangs.items() if q]
        for key, gangs in items:
            if key[0] == "coll":
                _kind, scenario, comm_id, tag = key
                members = self._comms.get(comm_id, [])
                for gang in gangs:
                    arrived = sorted(gang)
                    recs = [req.record for _c, req, _k in gang.values()
                            if req.record is not None]
                    out.append({
                        "kind": "collective",
                        "collective": Operation(scenario).name,
                        "comm": comm_id, "tag": tag,
                        "arrived": arrived,
                        "missing": [m for m in members
                                    if m not in gang],
                        "oldest_age_us": round(max(
                            (r.age_ns(now) for r in recs), default=0)
                            / 1e3, 1),
                    })
            elif key[0] == "p2p":
                _kind, comm_id, src, dst = key
                for kind, tag in gangs:
                    out.append({
                        "kind": kind,  # pending "data" or "recv"
                        "comm": comm_id, "src": src, "dst": dst,
                        "tag": tag,
                    })
        return out

    def _exec_loop(self) -> None:
        """Dedicated gang executor (see _ready above).  Mutually
        exclusive with the leader-dispatch lane: while an inline
        dispatch is in flight the executor parks, so at most one gang
        program runs at any moment (global completion-order
        serialization — the property both lanes rely on)."""
        while True:
            with self._ready_cv:
                while True:
                    if self._ready and not self._inline_busy:
                        break
                    if self._shutdown and not self._ready:
                        return
                    self._ready_cv.wait()
                scenario, comm_id, gang = self._ready.popleft()
                self._exec_busy = True
            try:
                items = self._extend_batch(scenario, comm_id, gang)
                if items is None:
                    self.metrics.inc("executor_dispatches")
                    self._exec_gang(scenario, comm_id, gang)
                else:
                    self.metrics.inc("batches")
                    self.metrics.inc("batched_gangs", len(items))
                    self._exec_gang_batch(items)
            except Exception as e:  # pragma: no cover — belt and braces
                self._log.error("executor gang dispatch failed: %s", e)
                for call, request, _k in gang.values():
                    request.description += f" [{e}]"
                    request.complete(int(ErrorCode.DMA_INTERNAL_ERROR),
                                     0.0)
            finally:
                with self._ready_cv:
                    self._exec_busy = False
                    # wake a plan-replay leader parked on the idle
                    # claim (the ring's one-program-at-a-time slot)
                    self._ready_cv.notify_all()

    #: max gangs fused into one dispatch (the reference's effective
    #: FPGAQueue depth; also bounds compiled-variant count per fn key)
    _BATCH_CAP = 8

    def _extend_batch(self, scenario: int, comm_id: int, gang: dict):
        """Try to extend `gang` with queued compatible gangs: same
        compiled program (fn_args) and no RAW hazard (a candidate
        reading a buffer an earlier member writes must wait for the
        rebind).  Only the drainer pops, so peeking then popping is
        race-free.  Returns a list of (op, comm, gang, plan) when a
        batch of >= 2 formed, else None."""
        op = Operation(scenario)
        if op in (Operation.barrier, Operation.alltoallv):
            return None
        if self.profile_sync:
            # exact perf-counter mode: every gang dispatches alone so
            # get_duration is THAT call's blocking on-device time, never
            # an averaged share of a fused batch's wall clock
            return None
        with self._ready_cv:
            if not self._ready:
                return None
        plan = self._gang_plan(op, comm_id, gang)
        if plan["fn_args"][8]:
            # ring=True: the Pallas ring kernels use fixed
            # collective_ids; fusing two instances
            # into one program would give data-independent rings the
            # SAME barrier/ACK semaphores, which cross-device skew can
            # alias into a double-buffer overrun on real hardware —
            # ring-path gangs always dispatch alone
            return None
        items = [(op, comm_id, gang, plan)]
        res_addrs = set(plan["res_addrs"])
        while len(items) < self._BATCH_CAP:
            with self._ready_cv:
                if not self._ready:
                    break
                nscen, ncomm, ngang = self._ready[0]
            nop = Operation(nscen)
            if nop in (Operation.barrier, Operation.alltoallv):
                break
            try:
                nplan = self._gang_plan(nop, ncomm, ngang)
            except Exception:  # noqa: BLE001 — candidate stays QUEUED:
                # its own execution turn will surface the error to its
                # own requests; raising here would drop already-popped
                # gangs with their requests never completed
                break
            if (nplan["fn_args"] != plan["fn_args"]
                    or nplan["opnd_addrs"] & res_addrs):
                break
            with self._ready_cv:
                popped = self._ready.popleft()
            # only the executor pops: the head cannot have changed
            items.append((nop, ncomm, popped[2], nplan))
            res_addrs |= nplan["res_addrs"]
        return items if len(items) > 1 else None

    def _exec_gang(self, scenario: int, comm_id: int, gang: dict) -> None:
        # NB: signature is stable API for the lock-discipline test spies
        # (tests/test_tpu_backend.py wraps it positionally).  The lane
        # follows from who runs it: a leader-dispatch run holds
        # _inline_busy throughout, and the executor never runs beside it
        lane = "leader" if self._inline_busy else "executor"
        traced = _trace.enabled()
        try:
            mark(_records(gang), ("t_dispatch",), state=S_DISPATCHED,
                 lane=lane, stage="accl.marshal")
            dt_ns = self._run_collective(Operation(scenario), comm_id,
                                         gang)
            for call, request, _krnl in gang.values():
                request.complete(0, float(dt_ns))
        except Exception as e:
            for call, request, _krnl in gang.values():
                request.description += f" [{e}]"
                request.complete(int(ErrorCode.DMA_INTERNAL_ERROR), 0.0)
        finally:
            if traced:  # accl.finish ends: every member completed
                _trace.stage(None)

    def _exec_gang_batch(self, items) -> None:
        """K same-program, RAW-independent gangs in ONE dispatch: the
        batched compiled fn takes K sharded globals and returns K
        results (inputs are all read before any rebind, which is
        exactly the sequential semantics the RAW guard preserves)."""
        traced = _trace.enabled()
        try:
            recs = [r for _op, _c, gang, _plan in items
                    for r in _records(gang)]
            mark(recs, ("t_dispatch",), state=S_DISPATCHED,
                 lane="batched", stage="accl.marshal")
            xs = [self._assemble_global(plan, gang)
                  for _op, _c, gang, plan in items]
            for _op, _c, _gang, plan_ in items:
                self._account_links(plan_)
                self.metrics.inc(plan_["lane"])
            fnb = _collective_fn(*items[0][3]["fn_args"],
                                 nbatch=len(items))
            # one fused launch shared by every batched gang — the
            # aligned cross-gang slice the timeline shows
            t0 = _trace.now_ns()
            mark(recs, ("t_launch",), t0, stage="accl.launch")
            ys = fnb(*xs)
            if self.profile_sync:
                import jax

                jax.block_until_ready(ys)
            t1 = _trace.now_ns()
            mark(recs, ("t_launched",), t1, stage="accl.finish")
            # per-call perf counter: the batch's wall time is shared by
            # K fused dispatches, so each call's duration is its share
            # (reporting the whole batch per call would inflate
            # get_duration by the batch width)
            per_call = float(t1 - t0) / len(items)
            for (op, _c, gang, plan), y in zip(items, ys):
                self._scatter_back(plan, y)
                mark(_records(gang), ("t_rebound",))
                for call, request, _krnl in gang.values():
                    request.complete(0, per_call)
        except Exception as e:
            for _op, _c, gang, _plan in items:
                for call, request, _krnl in gang.values():
                    if request.done:
                        # earlier batch members that already completed
                        # successfully must NOT be re-completed as
                        # errors (waiters may have observed success;
                        # on_complete must not run twice)
                        continue
                    request.description += f" [{e}]"
                    request.complete(int(ErrorCode.DMA_INTERNAL_ERROR),
                                     0.0)
        finally:
            if traced:  # accl.finish ends: every member completed
                _trace.stage(None)

    def _gang_plan(self, op: Operation, comm_id: int, gang: dict):
        """Resolve one gang signature into an execution plan and cache
        it: training loops repeat identical descriptors at call rate, so
        buffer resolution, dtype widening, sharding construction and the
        AOT-compile lookup are paid once per signature instead of per
        call (the hostctrl MMIO fast-path role: per-call work collapses
        to a handful of register writes, fpgadevice.cpp:46-180).
        Safe to cache: the address->buffer registry only grows, buffer
        dev dtype/shape never change, and the compiled fn is keyed on
        everything that shapes the program.

        A hit is one signature tuple and one locked lookup: whatever
        depends only on the signature (the prepared operand assembly,
        the link-twin deltas) was computed when the plan was built."""
        members = self._comms[comm_id]
        # ring_threshold_bytes is a runtime knob (tests force the ring
        # path by setting it to 0): it shapes the compiled program, so
        # it must be part of the signature or a threshold change would
        # silently keep serving the previously-compiled lowering.  The
        # comm id fixes the member order, so members need no slot.
        sig = (int(op), comm_id, self.ring_threshold_bytes, tuple([
            (c.addr_0, c.addr_1, c.addr_2, c.count, c.root_src_dst,
             c.function,
             c.compression_flags, c.arithcfg, c.stream_flags, c.tag,
             c.fused)
            for c in [gang[m][0] for m in members]]))
        # _gang_plan runs only on the dispatching context — the
        # executor thread or (leader-dispatch lane) the one inline
        # leader, never both at once — so the lock is effectively
        # uncontended here and the hit path keeps proper LRU recency
        # (an early r5 build skipped move_to_end to dodge submit-thread
        # convoying that no longer exists; past 256 live signatures
        # that cost re-compiles)
        with self._lock:
            plan = self._gang_plans.get(sig)
            if plan is not None:
                self._gang_plans.move_to_end(sig)
                return plan
        plan = self._build_plan(op, comm_id, gang, members)
        with self._lock:
            self._gang_plans[sig] = plan
            self._gang_plans.move_to_end(sig)
            while len(self._gang_plans) > self._gang_plans_cap:
                self._gang_plans.popitem(last=False)
        return plan

    def _build_plan(self, op: Operation, comm_id: int, gang: dict,
                    members: list) -> dict:
        """A gang signature's execution plan (see _gang_plan)."""
        if op == Operation.alltoallv:
            return self._build_alltoallv_plan(comm_id, gang, members)
        _jax, _jnp, _Mesh, NamedSharding, P = _import_jax()
        nranks = len(members)
        mesh = self._mesh_for(tuple(members))
        any_call = next(iter(gang.values()))[0]
        n = any_call.count
        root = any_call.root_src_dst
        func = any_call.function
        wire_dtype = (self.wire_dtype_for(any_call.arithcfg)
                      if any_call.compression_flags
                      & CompressionFlags.ETH_COMPRESSED else "")

        # operand length per rank in the global array
        in_len = {
            Operation.bcast: n,
            Operation.scatter: n * nranks,
            Operation.gather: n,
            Operation.allgather: n,
            Operation.reduce: n,
            Operation.allreduce: n,
            Operation.reduce_scatter: n * nranks,
            Operation.alltoall: n * nranks,
        }[op]

        # per-operand compression: run the collective in the widest
        # (uncompressed) representation present in the gang; narrower
        # operand shards are dequantized on the way in and results are
        # quantized back to each rank's result-buffer dtype on the way
        # out (the hp_compression lane role, driven by buffer dtypes the
        # same way ACCL._build derives OP0/RES_COMPRESSED)
        dtype = None
        for g in members:
            call = gang[g][0]
            for addr in (call.addr_0, call.addr_2):
                b, _o = self.resolve(g, addr)
                if b is not None and (dtype is None
                                      or b.host.dtype.itemsize
                                      > np.dtype(dtype).itemsize):
                    dtype = b.host.dtype
        if dtype is None:
            # stream->stream collectives address no buffer at all: the
            # dtype comes from the reserved kernel operands (np.dtype(
            # None) would silently mean float64 and corrupt f32 streams)
            for g in members:
                krnl = gang[g][2]
                if krnl is not None:
                    dtype = np.dtype(krnl.dtype)
                    break
        if dtype is None:
            raise ACCLError(
                "collective addresses no buffer and no stream operand "
                "was reserved — cannot derive the datapath dtype")

        ops = []
        for li, g in enumerate(members):
            call = gang[g][0]
            op0_stream = bool(call.stream_flags & StreamFlags.OP0_STREAM)
            res_stream = bool(call.stream_flags & StreamFlags.RES_STREAM)
            # operand: op0 for contributors; bcast non-root contributes its
            # result buffer as placeholder (engine ignores the content);
            # OP0_STREAM members contribute from their kernel queue at
            # call time (the mem<->stream reduce variants, test.cpp
            # :813-910)
            if op0_stream:
                buf, off, fast = None, 0, False
            else:
                buf, off = self.resolve(g, call.addr_0)
                if buf is None and call.addr_0:
                    # an address that names no live buffer (freed):
                    # never substitute the result buffer for it
                    raise ACCLError(
                        f"rank {g}: operand address {call.addr_0:#x} "
                        f"names no live buffer (freed?)")
                if buf is None:
                    buf, off = self.resolve(g, call.addr_2)
                fast = (buf is not None and off == 0
                        and buf.dev.shape == (in_len,)
                        and buf.dev.dtype == dtype)
            write_out = not (op in (Operation.reduce, Operation.gather)
                             and li != root)
            res, roff = self.resolve(g, call.addr_2)
            if res is None and call.addr_2 and not res_stream:
                raise ACCLError(
                    f"rank {g}: result address {call.addr_2:#x} names "
                    f"no live buffer (freed?)")
            res_tag = call.tag if (res_stream and write_out) else None
            ops.append((g, buf, off, fast,
                        res if (write_out and not res_stream) else None,
                        roff, op0_stream, res_tag))

        # large payloads ride the Pallas ring kernels (rendezvous path)
        ring = (op in (Operation.allreduce, Operation.allgather,
                       Operation.reduce_scatter)
                and nranks > 1
                and in_len * np.dtype(dtype).itemsize
                >= self.ring_threshold_bytes)

        # r18 fused lane (descriptor opt-in): the chunked pipelined ring
        # that overlaps chunk k+1's wire hop with chunk k's fold; takes
        # precedence over the threshold-selected ring/HLO paths
        fused = (bool(any_call.fused)
                 and op in (Operation.allreduce, Operation.allgather,
                            Operation.reduce_scatter)
                 and nranks > 1)

        # compiled once per (mesh, op, shape, root, func, ...) and
        # cached (no donation — see _collective_fn)
        fn_args = (mesh, op, nranks, in_len, root, func, wire_dtype,
                   str(np.dtype(dtype)), ring, fused)
        compiled = (None if op == Operation.barrier
                    else _collective_fn(*fn_args))
        sharding = NamedSharding(mesh, P("rank"))
        prepared = None
        if all(o[3] for o in ops):
            # every operand is a whole, same-dtype, resident buffer: a
            # call assembles the global from the buffers' current arrays,
            # bound here in the sharding's device order
            by_dev = {self.devices[o[0]]: o[1] for o in ops}
            prepared = (
                _global_constructor((nranks * in_len,), dtype, sharding),
                tuple(by_dev[d]
                      for d in sharding._addressable_device_assignment))
        plan = {
            "members": members,
            "nranks": nranks,
            # a second operand and result (alltoallv's counts), or None
            "count_ops": None,
            "in_len": in_len,
            "dtype": dtype,
            "sharding": sharding,
            "compiled": compiled,
            "ops": ops,
            # (global constructor, operand buffers in shard order), or None:
            # some operand needs the checked assembly (_assemble_global)
            "prepared": prepared,
            # this signature's link-twin deltas (_account_links)
            "links": self._link_deltas(
                op, comm_id, members, root,
                in_len * np.dtype(dtype).itemsize, wire_dtype),
            # batching metadata: gangs with the same fn_args can share
            # one dispatch; the address sets drive the RAW guard (a
            # candidate whose operands intersect an earlier batch
            # member's results must see the rebound value, so it ends
            # the batch).  Keyed by (rank, address): the per-rank
            # allocators are symmetric — every rank mints the same
            # numeric addresses — so a raw-address set would falsely
            # alias unrelated cross-rank buffers and end batches that
            # have no hazard at all (e.g. disjoint sub-communicator
            # gangs); only a same-rank overlap is a real RAW.
            "fn_args": fn_args,
            # the served-lane counter every execution of this plan bumps
            "lane": ("lane_fused" if fused else
                     "lane_ring" if ring else "lane_hlo"),
            "opnd_addrs": frozenset(
                (g, b.address) for g, b, _o, _f, _r, _ro, _os, _rt in ops
                if b is not None),
            "res_addrs": frozenset(
                (g, r.address) for g, _b, _o, _f, r, _ro, _os, _rt in ops
                if r is not None),
        }
        return plan

    def _build_alltoallv_plan(self, comm_id: int, gang: dict,
                              members: list) -> dict:
        """An alltoallv gang's plan: two operands (the payload and the
        int32 send counts) and two results (the payload and the receive
        counts), each a global sharded over the members.  Every buffer
        must be whole and resident, so the plan is always prepared.

        The payload globals are in row form, ``[P * rows, row]`` sharded
        on the rows: a send buffer that holds ``[rows, row]`` is its own
        shard, and the receive buffer is rebound to its ``[rows, row]``
        shard.  A send that arrives in another form (a first call, a 1-D
        write) is reshaped once at assembly, rebound in row form and
        counted under ``a2av_flat_operands``."""
        _jax, _jnp, _Mesh, NamedSharding, P = _import_jax()
        nranks = len(members)
        mesh = self._mesh_for(tuple(members))
        row = next(iter(gang.values()))[0].count
        ops, count_ops, shapes = [], [], set()
        for g in members:
            call = gang[g][0]
            if call.stream_flags or call.compression_flags:
                raise ACCLError("alltoallv takes no stream operand and no "
                                "wire compression")
            bufs = []
            for what, addr in (
                    ("send", call.addr_0), ("send count", call.addr_1),
                    ("receive", call.addr_2),
                    ("receive count", call.addr_3)):
                b, off = self.resolve(g, addr)
                if b is None or off:
                    raise ACCLError(f"rank {g}: alltoallv {what} address "
                                    f"{addr:#x} names no whole live buffer")
                bufs.append(b)
            send, scount, recv, rcount = bufs
            for b in (scount, rcount):
                if b.dev.dtype != np.int32 or b.dev.shape != (nranks,):
                    raise ACCLError(f"rank {g}: alltoallv counts must be "
                                    f"int32 buffers of {nranks}")
            shapes.add((send.dev.size, recv.dev.size,
                        str(send.dev.dtype), str(recv.dev.dtype)))
            ops.append((g, send, 0, True, recv, 0, False, None))
            count_ops.append((g, scount, 0, True, rcount, 0, False, None))
        if len(shapes) != 1:
            raise ACCLError(f"alltoallv: members' buffers differ "
                            f"(send, receive lengths and dtypes): {shapes}")
        send_len, recv_len, dtype, rdtype = shapes.pop()
        if dtype != rdtype or send_len % row or recv_len % row:
            raise ACCLError(f"alltoallv: send and receive must share a "
                            f"dtype and hold whole rows of {row}")
        sharding = NamedSharding(mesh, P("rank"))
        order = sharding._addressable_device_assignment
        by_dev = {self.devices[o[0]]: o[1] for o in ops}
        counts_by_dev = {self.devices[o[0]]: o[1] for o in count_ops}
        rows = (send_len // row, row)
        build_x = _global_constructor((nranks * rows[0], row), dtype,
                                      sharding)
        build_c = _global_constructor((nranks * nranks,), np.int32,
                                      sharding)
        sends = tuple(by_dev[d] for d in order)

        def build(devs):
            xs = devs[:nranks]
            for i, x in enumerate(xs):
                if x.shape != rows:   # out of row form: one relayout
                    self.metrics.inc("a2av_flat_operands")
                    sends[i]._dev = xs[i] = x.reshape(rows)
                    self.reform(sends[i])
            return build_x(xs), build_c(devs[nranks:])

        fn_args = (mesh, nranks, send_len // row, recv_len // row, row,
                   dtype)
        return {
            "members": members,
            "nranks": nranks,
            "count_ops": count_ops,
            "in_len": send_len,
            "dtype": np.dtype(dtype),
            "sharding": sharding,
            "compiled": _alltoallv_fn(*fn_args),
            "ops": ops,
            "prepared": (build, sends
                         + tuple(counts_by_dev[d] for d in order)),
            # the receive's row form, which _scatter_back rebinds it in
            "recv_rows": (recv_len // row, row),
            "links": self._link_deltas(Operation.alltoallv, comm_id,
                                       members, 0, 0),
            "fn_args": fn_args,
            "lane": "lane_hlo",
            "opnd_addrs": frozenset((o[0], o[1].address)
                                    for o in ops + count_ops),
            "res_addrs": frozenset((o[0], o[4].address)
                                   for o in ops + count_ops),
        }

    def _run_collective(self, op: Operation, comm_id: int,
                        gang: dict) -> tuple:
        """Assemble the gang's operands into one sharded array, execute
        the AOT-compiled SPMD collective, and scatter result shards back
        into the per-rank device buffers — everything stays jax.Arrays
        on device end to end (the reference's zero-copy device-resident
        call path, accl.cpp:796-839).  The duration is execution
        nanoseconds (dispatch + device time, compile excluded — the perf-counter
        role, fw :2280-2303).

        Hot path: the plan cache resolves everything per SIGNATURE, the
        global array is 1-D with each member's whole buffer as its
        shard, and full-length results rebind buffers — a repeated call
        costs one prepared global + one compiled dispatch, no per-member
        jax ops.

        Returns the duration in ns.  It stamps the member records'
        launch, launched and rebound and, traced, moves the dispatching
        thread's stage from accl.marshal to accl.launch to accl.finish
        (a barrier has no program: its records keep those stamps
        None)."""
        if op == Operation.barrier:
            return 0  # gang completion IS the synchronization

        plan = self._gang_plan(op, comm_id, gang)
        x = self._assemble_global(plan, gang)
        self._account_links(plan)
        self.metrics.inc(plan["lane"])

        recs = _records(gang)
        t0 = _trace.now_ns()
        mark(recs, ("t_launch",), t0, stage="accl.launch")
        y = plan["compiled"](x)
        if self.profile_sync:
            # exact perf-counter mode: duration is on-device time and
            # async errors surface here (see __init__)
            import jax

            jax.block_until_ready(y)
        t1 = _trace.now_ns()
        mark(recs, ("t_launched",), t1, stage="accl.finish")

        self._scatter_back(plan, y)
        mark(recs, ("t_rebound",))
        return t1 - t0

    def _assemble_global(self, plan: dict, gang: dict):
        """The gang's operands as one global array sharded over the
        members.  A prepared plan builds it from the bound buffers'
        current arrays; any other plan takes the checked path (slices,
        dtype widening, stream operands, padded placeholders), counted
        under ``assemble_checked``."""
        prepared = plan["prepared"]
        if prepared is not None:
            build, bufs = prepared
            return build([b._dev for b in bufs])
        self.metrics.inc("assemble_checked")
        jax, jnp, _Mesh, _NamedSharding, _P = _import_jax()
        in_len = plan["in_len"]
        dtype = plan["dtype"]

        shards = []
        for g, buf, off, fast, _res, _roff, op0_stream, _rtag in plan["ops"]:
            if fast:
                # whole-buffer operand already resident on its device:
                # the buffer IS the shard (zero-copy call path,
                # accl.cpp:796-839)
                shards.append(buf.dev)
                continue
            if op0_stream:
                # the operand was RESERVED at submit time in the
                # member's own thread (call-order stream pairing)
                shard = jnp.asarray(gang[g][2])[:in_len]
            else:
                shard = buf.flat[off:off + in_len]
            if shard.dtype != dtype:
                shard = shard.astype(dtype)
            if shard.shape[0] < in_len:  # placeholder short buffer (bcast)
                pad = jnp.zeros((in_len - shard.shape[0],), shard.dtype)
                shard = jnp.concatenate([shard, pad])
            shards.append(jax.device_put(shard, self.devices[g]))
        return jax.make_array_from_single_device_arrays(
            (plan["nranks"] * in_len,), plan["sharding"], shards)

    def _scatter_back(self, plan: dict, y) -> None:
        # scatter result shards back into per-rank result buffers without
        # leaving the device: each addressable shard is already a
        # single-device jax.Array on its gang member's chip.  The shard
        # order for a given sharding is stable across calls, so it is
        # resolved once per plan and later calls zip straight through
        # (the dict build + Device hashing was a measured slice of the
        # per-call budget at call rate).
        count_ops = plan["count_ops"]
        if count_ops is not None:   # alltoallv: (payload, counts)
            y, counts = y
        shard_list = y.addressable_shards
        order = plan.get("shard_order")
        if order is None:
            order = tuple(self._dev_to_rank[s.device] for s in shard_list)
            plan["shard_order"] = order
        out_shards = dict(zip(order, (s.data for s in shard_list)))
        if count_ops is not None:   # same sharding: same shard order
            got = dict(zip(order, (s.data for s in counts.addressable_shards)))
            for g, _b, _o, _f, res, _ro, _os, _rt in count_ops:
                res._dev = got[g]
            rows = plan["recv_rows"]
            for g, _b, _o, _f, res, _ro, _os, _rt in plan["ops"]:
                # the payload's [rows, row] shard, whatever form the
                # receive held before (or a substituted program gave)
                out = out_shards[g]
                if out.shape != rows:
                    out = out.reshape(rows)
                reformed = res._dev.shape != rows
                res._dev = out
                if reformed:
                    self.reform(res)
            return
        for g, _buf, _off, _fast, res, roff, _op0s, res_tag in plan["ops"]:
            if res_tag is not None:
                # RES_STREAM: the member's result lands in its local
                # kernel stream (uncompressed representation)
                self._push_stream(g, res_tag, out_shards[g])
                continue
            if res is None:
                continue
            out = out_shards[g]
            if (roff == 0 and out.shape == res.dev.shape
                    and out.dtype == res.dev.dtype):
                # whole-buffer result already on the right device: adopt
                # directly (the set_dev_range fast path minus its
                # per-call device probe — a result shard lives on its
                # member's device by construction)
                res._dev = out
                continue
            if out.dtype != res.dev.dtype:  # quantize to RES representation
                out = out.astype(res.dev.dtype)
            res.set_dev_range(roff, out)

    # ------------------------------------------------------------------
    # kernel streams
    # ------------------------------------------------------------------
    def push_krnl(self, rank: int, data: np.ndarray) -> None:
        import jax

        self._krnl_in[rank].append(
            jax.device_put(np.ascontiguousarray(data), self.devices[rank]))

    def _push_stream(self, rank: int, strm: int, data) -> None:
        """Deliver `data` into (rank, strm)'s kernel stream and wake
        waiters — the single delivery point for every RES_STREAM path
        (local copy, stream_put, recv landing, gang results)."""
        key = (rank, strm)
        with self._stream_cv:
            self._streams.setdefault(key, deque()).append(data)
            self._stream_cv.notify_all()

    def pop_stream(self, rank: int, strm: int, timeout_s: float):
        key = (rank, strm)
        with self._stream_cv:
            ok = self._stream_cv.wait_for(
                lambda: self._streams.get(key), timeout=timeout_s)
            if not ok:
                return None
            return np.asarray(self._streams[key].popleft())


def _parse_wire_spec(wire_dtype: str):
    """Decode a wire_dtype_for() spec: ("float16"|"bfloat16"|"", 0,
    False) for the cast lanes, ("int8", block, error_feedback) for the
    block-scaled lane."""
    if wire_dtype.startswith("int8"):
        from ..arithconfig import DEFAULT_COMPRESS_BLOCK

        parts = wire_dtype.split(":")
        block = int(parts[1]) if len(parts) > 1 else \
            DEFAULT_COMPRESS_BLOCK
        ef = len(parts) > 2 and parts[2] == "1"
        return "int8", block, ef
    return wire_dtype, 0, False


def _wire_roundtrip(x, wire_dtype: str):
    """Model one wire hop of compression: the payload crosses the link
    in the arithcfg's compressed representation and is decompressed on
    arrival — a dtype cast pair for the f16/bf16 lanes, a blockwise
    quantize/dequantize (ops/quantized.py) for the int8 block-scaled
    lane.  Idempotent: the absmax element of every quantized block maps
    to exactly ±127, so re-quantizing an already-roundtripped payload
    reproduces it bit-for-bit."""
    import jax.numpy as jnp

    if not wire_dtype:
        return x
    name, block, _ef = _parse_wire_spec(wire_dtype)
    if name == "int8":
        from ..ops.quantized import dequantize_blockwise, quantize_blockwise

        if x.dtype.itemsize <= 1:
            return x
        flat = x.reshape(-1).astype(jnp.float32)
        q, sc, n = quantize_blockwise(flat, block)
        return dequantize_blockwise(q, sc, n).reshape(x.shape).astype(x.dtype)
    wd = jnp.dtype(name)
    if x.dtype.itemsize > wd.itemsize:
        return x.astype(wd).astype(x.dtype)
    return x


def _tree_bcast(v, nranks: int, root: int):
    """Binomial-tree broadcast over ppermute: log2(P) rounds of doubling
    senders; every device receives the payload exactly once, so wire
    traffic is n*(P-1) total — vs n*(P-1) *per device* for the old
    all_gather-then-index lowering (the reference's rendezvous tree
    bcast, fw :816-869)."""
    import jax
    import jax.numpy as jnp

    idx = jax.lax.axis_index("rank")
    rel = (idx - root) % nranks
    k = 1
    while k < nranks:
        perm = [((root + j) % nranks, (root + j + k) % nranks)
                for j in range(k) if j + k < nranks]
        recvd = jax.lax.ppermute(v, "rank", perm)
        got_now = jnp.logical_and(rel >= k, rel < 2 * k)
        v = jnp.where(got_now, recvd, v)
        k *= 2
    return v


def _tree_gather(v, nranks: int, root: int):
    """Binomial-tree gather: payload sizes double each round
    (dynamic_slice/update at rel-rank offsets), so total wire traffic is
    O(P*n*log2(P)/2) and each non-root device forwards at most once —
    vs every device receiving the full (P-1)*n under all_gather.  The
    rel-ordered accumulator is rolled into global rank order at the end
    (the reference's ring-relay gather with stride bookkeeping,
    fw :1207-1295, re-shaped as a tree for ICI)."""
    import jax
    import jax.numpy as jnp

    n = v.shape[0]
    idx = jax.lax.axis_index("rank")
    rel = (idx - root) % nranks
    # accumulator padded to the next power of two so the doubling-block
    # dynamic slices never clamp at the edge for non-power-of-2 worlds
    # (clamping would silently shift a block over a neighbor's slice)
    pow2 = 1
    while pow2 < nranks:
        pow2 *= 2
    acc = jnp.zeros((pow2 * n,), v.dtype)
    acc = jax.lax.dynamic_update_slice(acc, v, (rel * n,))
    k = 1
    while k < nranks:
        # senders: rel % 2k == k; receivers: rel % 2k == 0 with rel+k < P
        perm = [((root + j + k) % nranks, (root + j) % nranks)
                for j in range(0, nranks, 2 * k) if j + k < nranks]
        # every device extracts its own k*n block (senders' payload)
        chunk = jax.lax.dynamic_slice(acc, (rel * n,), (k * n,))
        recvd = jax.lax.ppermute(chunk, "rank", perm)
        is_recv = jnp.logical_and(rel % (2 * k) == 0, rel + k < nranks)
        merged = jax.lax.dynamic_update_slice(acc, recvd, ((rel + k) * n,))
        acc = jnp.where(is_recv, merged, acc)
        k *= 2
    # acc holds rel-ordered slices; global rank j sits at rel (j-root)%P,
    # one static roll restores global order
    return jnp.roll(acc[:nranks * n], root * n)


@lru_cache(maxsize=256)
def _collective_fn(mesh, op: Operation, nranks: int, in_len: int, root: int,
                   func: int, wire_dtype: str, dtype: str,
                   ring: bool = False, fused: bool = False,
                   nbatch: int = 1) -> Callable:
    """Build + AOT-compile the SPMD program for one collective: a
    shard_map whose inner program is the XLA HLO collective (or the
    ppermute tree schedule) over ICI — or, with ``ring=True``, the
    segmented Pallas ring kernel (the rendezvous large-message path).
    Compilation happens here, once per cache key, so execution timing in
    the caller never includes compile (get_duration = the perf-counter
    role)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    n = in_len if op not in (Operation.scatter, Operation.reduce_scatter,
                             Operation.alltoall) else in_len // nranks
    is_max = func == int(ReduceFunction.MAX)
    interpret = pallas_interpret()
    red = "max" if is_max else "sum"

    def quant(v):
        # wire hop in the arithcfg's compressed representation.  NB: the
        # interior accumulate stays in the UNCOMPRESSED domain on TPU —
        # the MXU/VPU reduce natively in f32, so quantizing only at the
        # wire endpoints is both faster and strictly more accurate than
        # the emulator's reference-faithful compressed-domain lanes
        # (arith_is_compressed, arithconfig.hpp:106-119); both are within
        # the corpus's FLOAT16 tolerances (test_compression_matrix.py).
        return _wire_roundtrip(v, wire_dtype)

    def ring_body(v):
        from ..ops import ring as ring_ops

        if op == Operation.allreduce:
            return ring_ops.ring_all_reduce_segmented(
                v, "rank", op=red, interpret=interpret)
        if op == Operation.allgather:
            return ring_ops.ring_all_gather_segmented(
                v, "rank", interpret=interpret)
        return ring_ops.ring_reduce_scatter_segmented(
            v, "rank", op=red, interpret=interpret)

    # r17 quantized ring lane: with the int8 block-scaled wire spec the
    # ppermute payload IS the packed (int8, scale) block stream
    # (ops/quantized.py), with optional EQuARX error feedback carried
    # hop to hop — not a roundtrip model around a lossless ring.  SUM
    # only (the EQuARX algebra); MAX and ragged chunkings fall back to
    # the wire-roundtrip model around the plain ring below.
    wire_name, wire_block, wire_ef = _parse_wire_spec(wire_dtype)

    def q_ring_body(v):
        from ..ops import quantized as q_ops

        if op == Operation.allreduce:
            return q_ops.quantized_all_reduce(
                v, "rank", block=wire_block,
                error_feedback=wire_ef).astype(v.dtype)
        if op == Operation.allgather:
            return q_ops.quantized_ring_all_gather(
                v, "rank", block=wire_block).astype(v.dtype)
        return q_ops.quantized_ring_reduce_scatter(
            v, "rank", block=wire_block,
            error_feedback=wire_ef).astype(v.dtype)

    q_ring = (ring and wire_name == "int8" and not is_max
              and op in (Operation.allreduce, Operation.allgather,
                         Operation.reduce_scatter)
              and in_len % nranks == 0)

    # r18 fused lane twin of q_ring: the int8 quantize/dequantize runs
    # INSIDE the chunked pipeline loop (no whole-buffer pack/unpack)
    fused_q = fused and wire_name == "int8" and not is_max

    def fused_body(v):
        from ..ops import fused as fused_ops

        if fused_q:
            w = (wire_block, wire_ef)
            if op == Operation.allreduce:
                return fused_ops.chunked_ring_all_reduce(
                    v.astype(jnp.float32), "rank",
                    wire=w).astype(v.dtype)
            if op == Operation.allgather:
                return fused_ops.chunked_ring_all_gather(
                    v.astype(jnp.float32), "rank",
                    wire=w).astype(v.dtype)
            return fused_ops.chunked_ring_reduce_scatter(
                v.astype(jnp.float32), "rank", wire=w).astype(v.dtype)
        v = quant(v)
        if op == Operation.allreduce:
            out = fused_ops.chunked_ring_all_reduce(v, "rank", op=red)
        elif op == Operation.allgather:
            out = fused_ops.chunked_ring_all_gather(v, "rank")
        else:
            out = fused_ops.chunked_ring_reduce_scatter(v, "rank", op=red)
        return quant(out)

    def body(v):  # v: [in_len] block on each device (1-D global layout:
        # the per-rank shard IS the member's buffer, no reshape on the
        # way in or out — the gang hot path stays dispatch-free)
        if fused:
            # the fused lane owns its wire hops end to end (int8 inside
            # the loop body; cast lanes roundtrip at the endpoints)
            return fused_body(v)
        if q_ring:
            # the quantized kernels own the wire hops end to end — no
            # extra entry/exit roundtrip (that would double-quantize)
            return q_ring_body(v.astype(jnp.float32)).astype(v.dtype)
        v = quant(v)
        if ring:
            out = ring_body(v)
        elif op == Operation.allreduce or op == Operation.reduce:
            out = (jax.lax.pmax(v, "rank") if is_max
                   else jax.lax.psum(v, "rank"))
        elif op == Operation.bcast:
            out = _tree_bcast(v, nranks, root)
        elif op == Operation.gather:
            out = _tree_gather(v, nranks, root)
        elif op == Operation.allgather:
            out = jax.lax.all_gather(v, "rank").reshape(-1)
        elif op == Operation.scatter:
            # only the root's operand matters: mask everyone else to
            # zero and ride the bandwidth-optimal reduce-scatter ring —
            # O(n*P) total wire traffic vs O(n*P^2) for all_gather
            idx = jax.lax.axis_index("rank")
            masked = jnp.where(idx == root, v, jnp.zeros_like(v))
            out = jax.lax.psum_scatter(masked, "rank", scatter_dimension=0,
                                       tiled=True)
        elif op == Operation.reduce_scatter:
            if is_max:
                # XLA has no pmax_scatter: reduce fully, keep own chunk
                # (correct first; MAX reduce_scatter is a cold lane —
                # the SUM path keeps the bandwidth-optimal ring)
                idx = jax.lax.axis_index("rank")
                out = jax.lax.dynamic_slice_in_dim(
                    jax.lax.pmax(v, "rank"), idx * n, n)
            else:
                out = jax.lax.psum_scatter(v, "rank",
                                           scatter_dimension=0,
                                           tiled=True)
        elif op == Operation.alltoall:
            # [P, rows, 128] blocks where they divide: a bf16 [P, n]
            # all_to_all took 97 s to compile for v5e at 128 MiB
            blocks = v.reshape((nranks, n // 128, 128) if n % 128 == 0
                               else (nranks, n))
            out = jax.lax.all_to_all(blocks, "rank", split_axis=0,
                                     concat_axis=0, tiled=False)
            out = out.reshape(-1)
        else:
            raise ACCLError(f"collective {op} not lowered")
        return quant(out)

    # vma checking can't see through the Pallas remote-DMA kernels
    fn = shard_map(body, mesh=mesh, in_specs=P("rank"),
                   out_specs=P("rank"), check_vma=not ring)
    arg = jax.ShapeDtypeStruct(
        (nranks * in_len,), np.dtype(dtype),
        sharding=NamedSharding(mesh, P("rank")))
    # NO donation: the per-rank shards ARE the registered device buffers
    # on the fast path (the member may reuse its send buffer on the very
    # next call), so the input must stay alive across the dispatch
    if nbatch == 1:
        return jax.jit(fn).lower(arg).compile()
    # batched gang dispatch (the reference's queue-depth amortization,
    # FPGAQueue acclrequest.hpp:153-211): K independent same-shape
    # gangs ride ONE compiled program — K inputs, K outputs, no
    # concatenation — so the per-dispatch overhead is paid once per
    # batch instead of once per call
    def batched(*vs):
        return tuple(fn(v) for v in vs)

    return jax.jit(batched).lower(*([arg] * nbatch)).compile()


def _ragged_exchange_supported(mesh) -> bool:
    """Whether the mesh's compiler lowers ``ragged-all-to-all``: XLA:CPU
    refuses it ("not supported by XLA:CPU ThunkEmitter")."""
    return mesh.devices.flat[0].platform != "cpu"


@lru_cache(maxsize=64)
def _alltoallv_fn(mesh, nranks: int, send_rows: int, recv_rows: int,
                  row: int, dtype: str) -> Callable:
    """Build + AOT-compile one alltoallv program: the members' P x P
    count matrix is gathered on the device, the send and receive
    offsets derived from it, and the rows moved by
    ``ragged_all_to_all``.  Where the compiler lacks that op, the same
    program exchanges ``[P, send_rows, row]`` blocks with ``all_to_all``
    and packs them (both forms write zeros past the received total).
    The compiled callable takes and returns a (payload, counts) pair of
    globals; the payloads are in row form, ``[P * send_rows, row]`` in
    and ``[P * recv_rows, row]`` out, sharded on the rows, so a shard is
    a member's ``[rows, row]`` buffer with no relayout on either side."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    ragged = _ragged_exchange_supported(mesh)

    def body(xv, c):
        m = jax.lax.all_gather(c, "rank")     # m[i, j]: rows i -> j
        r = jax.lax.axis_index("rank")
        send, recv = m[r], m[:, r]
        in_off = jnp.cumsum(send) - send
        if ragged:
            # where my rows land on receiver j: after the rows that the
            # ranks before me send it
            before = jnp.arange(nranks)[:, None] < r
            out_off = jnp.sum(jnp.where(before, m, 0), axis=0)
            out = jnp.zeros((recv_rows, row), xv.dtype)
            y = jax.lax.ragged_all_to_all(xv, out, in_off, send, out_off,
                                          recv, axis_name="rank")
            return y, recv
        k = jnp.arange(send_rows)
        padded = jnp.concatenate([xv, jnp.zeros_like(xv)])
        blocks = jnp.stack([
            jnp.where((k < send[j])[:, None],
                      jax.lax.dynamic_slice_in_dim(padded, in_off[j],
                                                   send_rows), 0)
            for j in range(nranks)])
        got = jax.lax.all_to_all(blocks, "rank", 0, 0, tiled=False)
        ends = jnp.cumsum(recv)
        t = jnp.arange(recv_rows)
        src = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                          nranks - 1)
        pos = jnp.clip(t - (ends - recv)[src], 0, send_rows - 1)
        y = jnp.where((t < ends[-1])[:, None], got[src, pos], 0)
        return y, recv

    fn = shard_map(body, mesh=mesh, in_specs=(P("rank"), P("rank")),
                   out_specs=(P("rank"), P("rank")), check_vma=False)
    sh = NamedSharding(mesh, P("rank"))
    args = (jax.ShapeDtypeStruct((nranks * send_rows, row),
                                 np.dtype(dtype), sharding=sh),
            jax.ShapeDtypeStruct((nranks * nranks,), np.int32, sharding=sh))
    return jax.jit(lambda xc: fn(*xc)).lower(args).compile()


class TpuDeviceView(CCLODevice):
    """One rank's CCLO handle over the shared TpuEngine (the per-rank
    driver-facing face of the world-level backend)."""

    #: all ranks share one TpuEngine comm table keyed by comm id, so a
    #: disjoint sub-group must get a DISTINCT id world-wide; the
    #: hierarchical composer (accl_tpu/tuning/compose.py) reads this to
    #: decide whether a non-member rank pads its id space driver-side
    #: only (shared table: the members' upload covers the world) or
    #: must upload an inert pad comm (per-rank engine tables: emu)
    comm_table_is_shared = True

    def __init__(self, engine: TpuEngine, rank: int):
        self._engine = engine
        self._rank = rank
        self._mem = {}

    def start(self, call: CCLOCall, request: Request) -> None:
        self._engine.submit(self._rank, call, request)

    def sanitizer_domain(self):
        """All ranks of a TpuWorld share one in-process TpuEngine, so
        the engine's identity is the sanitizer exchange domain: a
        mismatched gang raises at submit instead of assembling two
        forever-partial gangs in the scheduler."""
        return ("tpu", id(self._engine))

    @property
    def engine_metrics(self) -> "object":
        """The shared engine's registry (ACCL.metrics() merges its
        dispatch-lane counters under engine/ keys)."""
        return self._engine.metrics

    def engine_stats(self) -> dict:
        """Engine telemetry snapshot (r14) in the same flat schema as
        the native engine's ``accl_engine_stats`` where the concepts
        map (plans/replays), plus the TPU-only dispatch-lane and
        plan-ring fields (generation = max comm fence generation,
        refcounts = per-rank handles pinning live rings).  The
        world-level sampler polls this exactly like the emu twin."""
        eng = self._engine
        counters = eng.metrics.counters()
        with eng._plan_cv:
            rings = [r for r in eng._plan_rings if r.invalid is None]
            plans_live = len(rings)
            plan_ring_refs = sum(r.refs for r in rings)
            ring_replays = sum(r.replays for r in rings)
        with eng._ready_cv:
            ready_depth = len(eng._ready)
        with eng._lock:  # _comm_gen mutates under _lock (abort/evict)
            gen = max(eng._comm_gen.values(), default=0)
        with eng._link_lock:
            link_rows = sum(1 for (src, _c, _p) in eng._links
                            if src == self._rank)
        return {
            "version": 3,
            "link_rows": link_rows,
            "compressed_tx_bytes":
                counters.get("compressed_tx_bytes", 0),
            "compressed_tx_logical_bytes":
                counters.get("compressed_tx_logical_bytes", 0),
            "plans_live": plans_live,
            "plan_ring_refs": plan_ring_refs,
            "plan_ring_generation": gen,
            "plan_ring_replays": ring_replays,
            "plan_replays": counters.get("plan_replays", 0),
            "leader_dispatches": counters.get("leader_dispatches", 0),
            "executor_dispatches": counters.get("executor_dispatches", 0),
            "batches": counters.get("batches", 0),
            "batched_gangs": counters.get("batched_gangs", 0),
            "a2av_flat_operands": counters.get("a2av_flat_operands", 0),
            "ready_depth": ready_depth,
        }

    def link_stats(self) -> list:
        """Per-(comm, peer) wire-counter rows (r15) — the TPU twin of
        EmuDevice.link_stats: ring/tree schedule bytes accounted at
        gang dispatch, gang-assembly straggler wait as seek_wait_ns.
        Peers are global ranks (== comm-local on comm 0)."""
        return self._engine.link_stats_for(self._rank)

    # memory API kept for interface completeness; TPU buffers are opaque
    # handles, not a flat address space
    def alloc_mem(self, nbytes: int, alignment: int = 64) -> int:
        raise ACCLError("TPU backend allocates via create_buffer only")

    def free_mem(self, address: int) -> None:
        pass

    def read_mem(self, address: int, nbytes: int) -> bytes:
        buf, off = self._engine.resolve(self._rank, address)
        if buf is None:
            raise ACCLError(f"read_mem: unknown address {address:#x}")
        raw = np.asarray(buf.dev).tobytes()
        start = off * buf.host.itemsize
        return raw[start:start + nbytes]

    def write_mem(self, address: int, data: bytes) -> None:
        import jax.numpy as jnp

        buf, off = self._engine.resolve(self._rank, address)
        if buf is None:
            raise ACCLError(f"write_mem: unknown address {address:#x}")
        vals = np.frombuffer(data, dtype=buf.host.dtype)
        buf.set_dev_range(off, jnp.asarray(vals))

    def create_buffer(self, length: int, dtype: np.dtype) -> BaseBuffer:
        return self._engine.create_buffer(self._rank, length, dtype)

    def setup_rx_buffers(self, n_bufs: int, buf_size: int) -> None:
        pass  # no rx pool: ICI/XLA manage buffering

    def upload_communicator(self, comm: Communicator) -> int:
        return self._engine.set_comm(comm)

    def upload_arithconfig(self, cfg: ArithConfig) -> int:
        # registered so the gang can recover each call's wire dtype
        # (f16 vs bf16 compression pair) from the descriptor's arithcfg id
        return self._engine.register_arithcfg(cfg)

    def set_tuning(self, key: int, value: int) -> None:
        """TPU twin of the engine tuning registers (clear-error
        contract, constants.TuningKey): RING_THRESHOLD_BYTES is live —
        it moves the ring/HLO crossover the gang planner compiles
        against (`_gang_plan` keys its signature on it, so a write
        recompiles affected shapes) — and the flat-tree registers are
        stored as schedule hints (the XLA collective owns the schedule
        below the ring threshold).  Unknown keys raise an ACCLError
        naming the key and the known set."""
        from ..constants import (
            TPU_TUNING_KEYS,
            TuningKey,
            unknown_tuning_key_error,
        )

        if key not in TPU_TUNING_KEYS:
            raise unknown_tuning_key_error(key, TPU_TUNING_KEYS, "tpu")
        if key == int(TuningKey.RING_THRESHOLD_BYTES):
            self._engine.ring_threshold_bytes = int(value)
        else:
            self._engine.tuning_registers[int(key)] = int(value)

    def push_krnl(self, data: np.ndarray) -> None:
        self._engine.push_krnl(self._rank, data)

    def pop_stream(self, strm: int, nbytes: int, timeout_s: float = 10.0):
        arr = self._engine.pop_stream(self._rank, strm, timeout_s)
        return None if arr is None else arr.tobytes()[:nbytes]

    # -- persistent plans (accl_tpu/plans.py): every rank shares the
    # in-process engine, so the ring IS the shared submission/
    # completion structure — arm rendezvouses the world's captures,
    # replay is a sequence-counter bump on the shared ring
    def arm_plan(self, calls, expected, timeout_s: float):
        return self._engine.arm_plan(self._rank, calls, expected,
                                     timeout_s)

    def plan_replay(self, ring, run_async: bool = False,
                    timeout_s: float = 60.0):
        return self._engine.ring_replay(self._rank, ring, run_async,
                                        timeout_s)

    def plan_wait(self, ring, token, timeout_s: float) -> bool:
        return self._engine.ring_wait(ring, token, timeout_s)

    def invalidate_plans(self, comm_id: int = -1) -> None:
        self._engine.invalidate_rings(
            None if comm_id < 0 else comm_id,
            "invalidated by the driver (shrink/grow/reset)")

    def plan_release(self, ring) -> None:
        """Release a dead plan's ring (driver finalizer path)."""
        self._engine.release_ring(ring)

    # -- resilience: every rank shares one in-process engine, so a
    # single abort covers the whole world (no wire propagation needed)
    def abort_comm(self, comm_id: int, err_bits: int) -> bool:
        return self._engine.abort_comm(comm_id, err_bits)

    # -- elastic membership (r11) -------------------------------------
    def join_sync(self, sponsor_session: int,
                  timeout_s: float = 10.0) -> int:
        """In-process join state sync: the world-level scheduler IS the
        control plane, so the wire exchange of the emulator rung
        collapses to a gang-table rebuild for any comm this view's
        driver will re-adopt — epochs/fences are already shared.
        Always succeeds (0): the sponsor cannot be deaf in-process."""
        return 0

    def comm_count(self) -> int:
        return self._engine.comm_count()

    def export_join_state(self, comm_id: int = 0) -> dict:
        return self._engine.export_join_state(comm_id)

    def rebuild_gang_tables(self, comm_id: int) -> int:
        return self._engine.rebuild_gang_tables(comm_id)

    def reset_errors(self) -> None:
        self._engine.reset_comm_errors()

    def close(self) -> None:
        pass


class TpuWorld:
    """N ranks over the TPU backend with the same harness surface as
    EmuWorld: per-rank ACCL handles and `run(fn)` concurrency."""

    def __init__(self, nranks: int, devices=None, **_ignored):
        self.nranks = nranks
        self.engine = TpuEngine(nranks, devices)
        self.devices = [TpuDeviceView(self.engine, r) for r in range(nranks)]
        self.accls = [ACCL(d) for d in self.devices]
        self._pool = ThreadPoolExecutor(max_workers=nranks)
        ranks = [Rank(ip="127.0.0.1", port=0, session=r) for r in range(nranks)]
        for r, a in enumerate(self.accls):
            a.initialize(ranks, r)
        # hang watchdog over this world's per-rank flight recorders
        # (no-op under ACCL_WATCHDOG_TIMEOUT=0 / ACCL_FLIGHT=0)
        self.engine.start_watchdog(
            [a.flight_recorder for a in self.accls
             if a.flight_recorder is not None])
        # engine telemetry sampler (r14): the shared TpuEngine is one
        # stats source — polling it per rank would just re-read the
        # same counters
        from ..observability import telemetry as _telemetry

        self.telemetry = _telemetry.sampler_from_env(
            [self.devices[0].engine_stats], name="accl-tpu",
            link_sources=[(r, d.link_stats)
                          for r, d in enumerate(self.devices)])
        # online tuner (r19): same world-level arm as EmuWorld —
        # ACCL_TUNE_ONLINE=1 starts the live retune loop, unset
        # constructs nothing (bit-identical dispatch)
        from ..tuning import online as _online

        self.online_tuner = _online.ensure_online_tuner_from_env(self)

    def run(self, fn: Callable, *args) -> list:
        futures = [self._pool.submit(fn, self.accls[r], r, *args)
                   for r in range(self.nranks)]
        return [f.result(timeout=300) for f in futures]

    def link_stats(self) -> dict:
        """Per-rank link rows (r15): rank -> (comm, peer) counter rows
        from the gang scheduler's wire twin."""
        return {r: d.link_stats() for r, d in enumerate(self.devices)}

    def link_matrix(self, comm: int = 0,
                    tenant: Optional[str] = None) -> dict:
        """World-level P×P link traffic matrix (same schema as
        EmuWorld.link_matrix — observability/telemetry.link_matrix).
        ``tenant`` (r20) slices by tenant label instead: the union of
        every communicator labeled that tenant across the drivers."""
        from ..observability import telemetry as _telemetry

        if tenant is not None:
            comms = set()
            for a in self.accls:
                comms.update(a.tenant_comm_ids(tenant))
            doc = _telemetry.link_matrix(self.link_stats(),
                                         nranks=self.nranks, comms=comms)
            doc["tenant"] = tenant
            return doc
        return _telemetry.link_matrix(self.link_stats(),
                                      nranks=self.nranks, comm=comm)

    def close(self) -> None:
        if getattr(self, "online_tuner", None) is not None:
            from ..tuning import online as _online

            if _online.online_tuner() is self.online_tuner:
                _online.stop_online_tuner()
            else:
                self.online_tuner.stop()
            self.online_tuner = None
        if self.telemetry is not None:
            self.telemetry.stop()
            self.telemetry = None
        self.engine.shutdown()
        self._pool.shutdown(wait=False)

    def __enter__(self) -> "TpuWorld":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
