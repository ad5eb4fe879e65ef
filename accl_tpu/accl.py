"""The ACCL driver: public collective API and call marshaling.

Equivalent of the reference `ACCL::ACCL` host driver class
(driver/xrt/include/accl/accl.hpp:46-1148, driver/xrt/src/accl.cpp):
every collective builds one 15-word call descriptor, syncs operand
buffers to the device, submits asynchronously through the request queue,
and on completion syncs results back and checks the engine retcode.

The collective *algorithms* do not live here — exactly as in the
reference, where the host only marshals a descriptor and the
device-resident engine decomposes it (SURVEY §1).  Here the engine is
either the native C++ emulator (backends/emu.py) or the JAX/XLA/Pallas
TPU engine (backends/tpu.py).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from . import plans as _plans
from .analysis import sanitizer as _san
from .arithconfig import DEFAULT_ARITH_CONFIG
from .backends.base import CCLODevice
from .buffer import BaseBuffer, DummyBuffer
from .communicator import Communicator, Rank
from .constants import (
    DATA_TYPE_SIZE,
    DEFAULT_EAGER_RX_BUF_SIZE,
    DEFAULT_EAGER_RX_BUFS,
    DEFAULT_MAX_EAGER_SIZE,
    DEFAULT_MAX_RENDEZVOUS_SIZE,
    GANG_OPERATIONS,
    TAG_ANY,
    ACCLError,
    CCLOCall,
    CfgFunc,
    CompressionFlags,
    DataType,
    ErrorCode,
    HostFlags,
    Operation,
    ReduceFunction,
    StreamFlags,
    TuningKey,
)
from .observability import flight as _flight
from .observability import health as _health
from .observability import metrics as _metrics
from .observability import trace as _trace
from .observability.record import CallRecord
from .request import Request, RequestQueue
from .utils.logging import get_logger

GLOBAL_COMM = 0  # id of the world communicator, like the reference's comm 0

#: scenarios that form cross-rank gangs in the engines (one instance ==
#: one gang id in the trace); p2p and local ops are single-rank spans.
#: Shared with the flight-recorder analyzer and the collective
#: sanitizer via constants.GANG_OPERATIONS.
_GANG_OPS = GANG_OPERATIONS


def default_timeout() -> int:
    """Default engine receive-timeout in emulated cycles (1 cycle = 1 µs).

    The reference bring-up writes 1e6 (accl.cpp:1112); loaded CI hosts
    need more headroom, so ACCL_DEFAULT_TIMEOUT overrides it — tests that
    temporarily shrink the budget restore to this, not the literal."""
    raw = os.environ.get("ACCL_DEFAULT_TIMEOUT", "1000000")
    try:
        return int(float(raw))  # accept "30000000" and "3e7" alike
    except ValueError as e:
        raise ACCLError(f"ACCL_DEFAULT_TIMEOUT={raw!r} is not a number") from e


class ACCL:
    """One rank's handle on the collective engine.

    Usage mirrors the reference driver: construct with a backend device,
    call :meth:`initialize` with the rank table, then issue collectives.
    """

    def __init__(self, device: CCLODevice):
        self._device = device
        self._queue = RequestQueue()
        self._communicators: list[Communicator] = []
        self._arith_ids: dict[tuple[DataType, DataType], int] = {}
        self._arith_pairs: dict[int, tuple] = {}
        #: error-feedback twins of block-scaled pairs (r17): same dtype
        #: pair, distinct engine config id whose error_feedback word
        #: arms the per-site EQuARX residual fold on egress
        self._arith_ids_ef: dict[tuple[DataType, DataType], int] = {}
        self._initialized = False
        self.max_eager_size = DEFAULT_MAX_EAGER_SIZE
        self.max_rendezvous_size = DEFAULT_MAX_RENDEZVOUS_SIZE
        #: host-side wait budget for synchronous calls; raise it alongside
        #: set_timeout for long-running collectives on slow emulator hosts
        self.call_timeout_s: float = 60.0
        #: engine receive budget (µs) as last written by set_timeout /
        #: initialize — read (and temporarily raised) by the recovery
        #: supervisor so an admission wait can't trip a peer's budget
        self.engine_timeout_us: int = default_timeout()
        self._last_request: Optional[Request] = None
        # descriptor memo: _build is a pure function of its scalar args
        # plus immutable per-buffer facts (address — never reused, the
        # registry only grows — dtype, host-only), so a training loop's
        # repeated call re-derives the same flag algebra every step;
        # the memo collapses that to one dict hit (the reference keeps
        # prepare_call cheap the same way: a handful of field writes).
        # Bounded LRU: fresh buffer addresses mint fresh keys, and a
        # descriptor-heavy workload cycling through > cap distinct
        # descriptors must evict the COLDEST entry, not wholesale-clear
        # — a clear-at-capacity memo re-derived every live call each
        # pass exactly when the memo mattered most.
        from collections import OrderedDict

        self._call_memo: "OrderedDict" = OrderedDict()
        self._call_memo_cap = 512
        #: always-on per-rank flight recorder (observability/flight.py):
        #: created at initialize (the rank is known there); None only
        #: with ACCL_FLIGHT=0
        self.flight_recorder: Optional[_flight.FlightRecorder] = None
        #: collective sanitizer state (analysis/sanitizer.py): per-comm
        #: gang instance counters for the cross-rank fingerprint
        #: exchange, and weak handles on run_async requests so deinit
        #: can name anything the caller never waited
        self._sanitize_seq: dict = {}
        self._async_pending: list = []
        #: communicator ids this driver has aborted (resilience layer):
        #: new calls on them fail fast at submit instead of reaching a
        #: fenced engine; cleared by reset_errors().  The off-path cost
        #: is one falsy check per call.
        self._aborted_comms: set = set()
        #: placeholder comm ids (elastic join protocol): dead slots a
        #: joiner minted to align its id space with the survivors' —
        #: calls on them fail fast with the same falsy-set discipline
        self._placeholder_comms: set = set()
        #: recovery supervisor, armed by supervise() / ACCL_SUPERVISE=1
        #: at initialize; None adds ZERO per-call code (loop-level, not
        #: call-level — the hot path never consults it)
        self.supervisor = None
        #: persistent collective plans (accl_tpu/plans.py): weak refs to
        #: live plans (abort/reset/shrink/grow invalidate them), the
        #: capture recorder installed by capture_plan for the duration
        #: of the captured function, and the per-driver capture group
        #: counter the pooled cross-rank validation pairs on.  Off-path
        #: cost in _execute is one falsy read per lane.
        self._plans: list = []
        self._plan_recorder = None
        #: per-(domain, member-set) capture counters: the pooled
        #: cross-rank validation pairs the K-th capture of the SAME
        #: member group across ranks, so disjoint sub-comm captures
        #: never skew each other's pairing
        self._plan_group_seq: dict = {}
        #: learned algorithm-selection policy (accl_tpu/tuning): armed
        #: at initialize from ACCL_TUNE_TABLE (ACCL_TUNE=0 disarms).
        #: None adds ONE falsy read to _execute — with the knobs unset
        #: dispatch behavior is bit-identical to the static thresholds.
        self._tune_policy = None
        #: wire-compression policy (arithconfig.CompressionPolicy; r17):
        #: armed at initialize from ACCL_COMPRESS (or set_compression /
        #: a tuned table whose cells select a compression lane).  None
        #: (the default) adds one falsy read in _build's memo-miss path
        #: — dispatch is bit-identical static with the knob unset.
        self._compress_policy = None
        #: fused compute/communication lane default (r18): per-call
        #: ``fused=`` overrides; None here resolves to the ACCL_FUSED
        #: env read once at construction.  Unset, every descriptor
        #: carries fused=False and dispatch is bit-identical to r17.
        self._fused_default = os.environ.get(
            "ACCL_FUSED", "0") not in ("", "0")
        #: transparent hierarchical dispatch (r19): memoized per-
        #: (comm, axis-split) composers serving table cells won by the
        #: "hierarchical" lane without the caller constructing one.
        #: Probed only AFTER an armed policy returned "hierarchical"
        #: for a call — no table (or no hier win) never touches it, so
        #: dispatch stays bit-identical when nothing selects the lane.
        self._hier_comms: dict = {}
        self._in_hier = False

    # ------------------------------------------------------------------
    # bring-up (reference: accl.cpp:1082-1130 initialize)
    # ------------------------------------------------------------------
    def initialize(
        self,
        ranks: Sequence[Rank],
        local_rank: int,
        n_egr_rx_bufs: int = DEFAULT_EAGER_RX_BUFS,
        egr_rx_buf_size: int = DEFAULT_EAGER_RX_BUF_SIZE,
        # NB: the reference *driver* defaults the eager threshold to the rx
        # buffer size (1 KB, accl.hpp:103-105), overriding the engine's
        # 32 KB default (ccl_offload_control.c:27-28).
        max_eager_size: Optional[int] = None,
        max_rendezvous_size: int = DEFAULT_MAX_RENDEZVOUS_SIZE,
        timeout: Optional[int] = None,
    ) -> None:
        """Full bring-up sequence (reference order, accl.cpp:1082-1130):
        soft reset, eager rx buffer pool, rendezvous spare buffers,
        communicator, arithmetic configs, tuning, thresholds, enable."""
        if self._initialized:
            raise ACCLError("ACCL already initialized")

        # 1. soft reset (reference: accl.cpp:57-69 soft_reset)
        self._config_call(CfgFunc.reset_periph)

        # 2. eager rx buffers + rendezvous spares live inside the backend
        #    engine (reference writes a table into exchange memory,
        #    accl.cpp:1147-1212; our backends own their pools).
        self._device.setup_rx_buffers(n_egr_rx_bufs, egr_rx_buf_size)

        # 3. communicator (reference: accl.cpp:1435-1443)
        comm = Communicator(list(ranks), local_rank, comm_id=GLOBAL_COMM)
        self._device.upload_communicator(comm)
        self._communicators = [comm]

        # 4. arithmetic configs (reference: accl.cpp:1132-1141), plus
        #    the r17 int8 block-scaled wire pair — registered here (not
        #    in DEFAULT_ARITH_CONFIG) so the scale-block geometry can
        #    follow ACCL_COMPRESS_BLOCK, with an error-feedback twin
        #    whose engine config arms the EQuARX residual fold
        from .arithconfig import compress_block_from_env, int8_block_config

        for key, cfg in DEFAULT_ARITH_CONFIG.items():
            self._arith_ids[key] = self._device.upload_arithconfig(cfg)
        block = compress_block_from_env()
        i8_pair = (DataType.float32, DataType.int8)
        self._arith_ids[i8_pair] = self._device.upload_arithconfig(
            int8_block_config(block))
        self._arith_ids_ef = {
            i8_pair: self._device.upload_arithconfig(
                int8_block_config(block, error_feedback=True)),
        }
        # reverse map id -> (uncompressed, compressed): observability
        # recovers each call's datapath dtype from the descriptor's
        # arithcfg id (first pair wins on backend-deduplicated ids)
        self._arith_pairs = {}
        for pair, aid in self._arith_ids.items():
            self._arith_pairs.setdefault(aid, pair)
        for pair, aid in self._arith_ids_ef.items():
            self._arith_pairs.setdefault(aid, pair)
        self._call_memo.clear()  # memoized arithcfg ids may predate this

        # 5. timeout + protocol thresholds (reference: accl.cpp:1112-1120).
        # The reference default is 1e6 cycles; on shared/loaded CI hosts a
        # 1 s receive budget fires spuriously, so the default is
        # overridable (tests that exercise timeouts pass explicit values).
        if timeout is None:
            timeout = default_timeout()
        self._config_call(CfgFunc.set_timeout, value=timeout)
        self.engine_timeout_us = int(timeout)
        if max_eager_size is None:
            max_eager_size = egr_rx_buf_size
        self.set_max_eager_msg_size(max_eager_size)
        self.set_max_rendezvous_msg_size(max_rendezvous_size)

        # 6. flat-tree tuning registers (reference
        #    configure_tuning_parameters, accl.cpp:1214-1224): gather
        #    fan-in 2 above 32 KB, bcast flat <= 3 ranks, reduce flat
        #    <= 4 ranks or <= min(rndzv/4, 32 KB)
        self.apply_static_tuning()

        # 6.5 learned selection policy (accl_tpu/tuning/autotune.py):
        #     ACCL_TUNE_TABLE names a persisted selection table and
        #     ACCL_TUNE != 0 — the policy's derived crossovers are
        #     written over the firmware-ported constants above, so
        #     Engine::set_tuning / the TPU ring threshold become the
        #     backend of the LEARNED policy.  Both knobs unset: policy
        #     is None and the static writes above stand bit-for-bit.
        from .tuning import autotune as _autotune

        self._tune_policy = _autotune.policy_from_env()
        if self._tune_policy is not None:
            self._tune_policy.install(self)

        # 6.7 wire-compression policy (arithconfig.CompressionPolicy,
        #     r17): ACCL_COMPRESS arms automatic compress_dtype
        #     selection per size/dtype/collective threshold.  The env
        #     knob wins over anything a tuned table installed above —
        #     INCLUDING an explicit ACCL_COMPRESS=0, which disarms a
        #     table-armed policy; both unset leaves dispatch
        #     bit-identical static.
        from .arithconfig import (
            COMPRESS_OFF_TOKENS,
            compression_policy_from_env,
        )

        raw_compress = os.environ.get("ACCL_COMPRESS", "").strip().lower()
        if raw_compress in COMPRESS_OFF_TOKENS:
            self.set_compression(None)
        else:
            env_compress = compression_policy_from_env()
            if env_compress is not None:
                self.set_compression(env_compress)

        # 7. enable transport engines (reference: accl.cpp:1122-1125)
        self._config_call(CfgFunc.enable_pkt)
        self._initialized = True

        # 8. observability bring-up: the always-on flight recorder (the
        #    rank is known now), the process-wide OpenMetrics endpoint
        #    when ACCL_METRICS_PORT is set, and the regression sentinel
        #    when ACCL_SENTINEL names a committed baseline (off = zero
        #    threads, zero per-call work)
        if _flight.enabled():
            self.flight_recorder = _flight.register(
                _flight.FlightRecorder(local_rank))
            # RECEIVE_TIMEOUT forensics (r20): the instant the engine
            # classifies a receive-timeout, the recorder snapshots the
            # per-peer link rows (and, where the backend exposes it,
            # the gang-assembly state) with wall-clock stamps into the
            # flight dump — the standing sub-comm allgather wedge
            # (ROADMAP item 5) ships an artifact, not a bare timeout
            sources = {}
            for attr, key in (("link_stats", "link_rows"),
                              ("engine_stats", "engine_stats")):
                fn = getattr(self._device, attr, None)
                if callable(fn):
                    sources[key] = fn
            gang_fn = getattr(self._device, "gang_assembly_snapshot",
                              None)
            if gang_fn is None:
                eng = getattr(self._device, "_engine", None)
                gang_fn = getattr(eng, "gang_assembly_snapshot", None)
            if callable(gang_fn):
                sources["gang_assembly"] = gang_fn
            if sources:
                self.flight_recorder.set_forensics_sources(sources)
        _health.ensure_exporter_from_env()
        from .observability import sentinel as _sentinel
        from .observability import slo as _slo

        _sentinel.ensure_sentinel_from_env()
        _slo.ensure_slo_from_env()

        # 9. resilience bring-up: ACCL_SUPERVISE=1 arms the recovery
        #    supervisor (resilience/supervisor.py) on this rank — a
        #    loop-level state machine, so the per-call hot path gains
        #    nothing when it is off (the default)
        if os.environ.get("ACCL_SUPERVISE", "0") == "1":
            self.supervisor = self.supervise()

    # ------------------------------------------------------------------
    # properties / config
    # ------------------------------------------------------------------
    @property
    def device(self) -> CCLODevice:
        return self._device

    @property
    def comm(self) -> Communicator:
        return self._communicators[GLOBAL_COMM]

    @property
    def rank(self) -> int:
        return self.comm.local_rank

    @property
    def size(self) -> int:
        return self.comm.size

    def communicator(self, comm_id: int) -> Communicator:
        """The communicator table for an id, or a decodable ACCLError —
        a bad id must not surface as a bare IndexError deep inside a
        collective (the lookup contract the collective sanitizer and
        accl_lint formalize)."""
        if isinstance(comm_id, int) and \
                0 <= comm_id < len(self._communicators):
            comm = self._communicators[comm_id]
            if comm.is_placeholder:
                raise ACCLError(
                    f"communicator {comm_id} is a placeholder slot on "
                    f"this rank — it marks a world this rank joined "
                    f"AFTER (elastic membership); only communicators "
                    f"minted at or after the join are usable here")
            return comm
        if not self._communicators:
            raise ACCLError(
                f"unknown communicator id {comm_id!r}: driver not "
                f"initialized (call initialize() first)")
        raise ACCLError(
            f"unknown communicator id {comm_id!r}: this rank has ids "
            f"0..{len(self._communicators) - 1} (create_communicator "
            f"must run in the same order on every member rank)")

    def arithcfg_id(self, uncompressed: DataType,
                    compressed: Optional[DataType] = None) -> int:
        """Device id of the arithmetic config for a dtype pair — what a
        device-side caller passes to :class:`~accl_tpu.device_api.
        ACCLCommand` (the exchange-memory arithcfg offset the reference's
        HLS bindings take, driver/hls/accl_hls.h:82).  `compressed`
        defaults to the uncompressed dtype (no compression lane)."""
        pair = (uncompressed,
                uncompressed if compressed is None else compressed)
        try:
            return self._arith_ids[pair]
        except KeyError:
            raise ACCLError(
                f"no arithmetic config for dtype pair {pair} — supported "
                f"pairs: {sorted(p for p in self._arith_ids)}") from None

    def create_communicator(self, indices: Sequence[int],
                            tenant: Optional[str] = None) -> int:
        """Create a sub-communicator from global-rank indices; returns its
        id (reference: accl.cpp:971-978).

        Collective and order-sensitive: every member rank must create
        its sub-communicators in the same order so the ids align across
        the group — the same discipline the reference needs for its
        exchange-memory communicator addresses (communicator.cpp:23).

        ``tenant`` labels the communicator's traffic for the per-tenant
        observability plane (r20): flight records, ``tenant/<name>``
        metric families, trace tracks and ``link_matrix(tenant=...)``
        slices all key off it.  Purely driver/telemetry-side — the
        engine ABI is untouched."""
        size = self.comm.size
        bad = [i for i in indices if not 0 <= i < size]
        if bad:
            raise ACCLError(
                f"create_communicator: rank indices {bad} outside the "
                f"world (size {size})")
        new_id = len(self._communicators)
        sub = self.comm.split(indices, new_id)
        self._device.upload_communicator(sub)
        self._communicators.append(sub)
        if tenant is not None:
            self.set_tenant(new_id, tenant)
        return new_id

    def set_tenant(self, comm_id: int, tenant: Optional[str]) -> None:
        """Label (or with ``None`` unlabel) a communicator's traffic
        with a tenant name for per-tenant telemetry.  Names are bounded
        and shell-safe (``[A-Za-z0-9_.-]{1,64}``) because they become
        metric label values and trace track names; the registry's
        ACCL_METRICS_MAX_SERIES guard bounds how many distinct names
        can mint series."""
        comm = self.communicator(comm_id)
        if tenant is not None:
            import re as _re

            if not isinstance(tenant, str) or \
                    not _re.fullmatch(r"[A-Za-z0-9_.\-]{1,64}", tenant):
                raise ACCLError(
                    f"set_tenant: invalid tenant name {tenant!r} — "
                    f"need 1-64 chars of [A-Za-z0-9_.-] (it becomes a "
                    f"metric label and trace track name)")
        comm.tenant = tenant

    def tenant_comm_ids(self, tenant: str) -> list:
        """Ids of this rank's communicators labeled ``tenant`` — the
        slice key ``link_matrix(tenant=...)`` folds over (a tenant's
        traffic is the union of its communicators' link rows)."""
        return [c.id for c in self._communicators
                if not c.is_placeholder and c.tenant == tenant]

    def reserve_communicator(self) -> int:
        """Burn one communicator id with an inert slot, so a sub-group
        this rank is NOT a member of can occupy the same id on its
        members — the :meth:`create_communicator` ordering discipline
        applied to disjoint group families (the hierarchical
        composer's per-axis sub-communicators, accl_tpu/tuning).

        On a world-shared comm table (TPU backend) the pad is
        driver-side only — the members' upload covers the world and a
        second upload with different membership would be rejected.
        Per-rank engine tables (emulator) additionally get an inert
        self-only communicator so the engine-side id spaces stay
        aligned with the wire protocol's comm ids."""
        cid = len(self._communicators)
        if getattr(self._device, "comm_table_is_shared", False):
            self._pad_communicators(cid + 1)
            return cid
        return self.create_communicator([self.rank])

    def set_max_eager_msg_size(self, nbytes: int) -> None:
        """Runtime eager↔rendezvous threshold (reference:
        accl.cpp:1415-1423; validated ≥ rx buffer size by the engine,
        ccl_offload_control.c:2432-2441)."""
        self._config_call(CfgFunc.set_max_eager_msg_size, value=nbytes)
        self.max_eager_size = nbytes

    def set_max_rendezvous_msg_size(self, nbytes: int) -> None:
        self._config_call(CfgFunc.set_max_rendezvous_msg_size, value=nbytes)
        self.max_rendezvous_size = nbytes

    def set_timeout(self, timeout: int) -> None:
        self._config_call(CfgFunc.set_timeout, value=timeout)
        #: last engine receive budget written (µs) — the recovery
        #: supervisor raises it for an episode and restores it after
        self.engine_timeout_us = int(timeout)

    # flat-tree schedule thresholds (reference exchange-memory tuning
    # registers, accl.cpp:1214-1224 / ccl_offload_control.h:86-90) —
    # aliases of the ONE authoritative table, constants.TuningKey
    BCAST_FLAT_TREE_MAX_RANKS = int(TuningKey.BCAST_FLAT_TREE_MAX_RANKS)
    REDUCE_FLAT_TREE_MAX_RANKS = int(
        TuningKey.REDUCE_FLAT_TREE_MAX_RANKS)
    GATHER_FLAT_TREE_MAX_FANIN = int(
        TuningKey.GATHER_FLAT_TREE_MAX_FANIN)
    EGRESS_PIPELINE_DEPTH = int(TuningKey.EGRESS_PIPELINE_DEPTH)
    GATHER_FLAT_TREE_MAX_COUNT = int(
        TuningKey.GATHER_FLAT_TREE_MAX_COUNT)
    REDUCE_FLAT_TREE_MAX_COUNT = int(
        TuningKey.REDUCE_FLAT_TREE_MAX_COUNT)

    def static_tuning(self) -> dict:
        """The firmware-ported static tuning-register values
        (reference configure_tuning_parameters, accl.cpp:1214-1224) —
        the ONE place they are written down: initialize applies them,
        and the autotuner's algorithm lanes restore them after a sweep
        so "static" always means exactly this."""
        return {
            int(TuningKey.GATHER_FLAT_TREE_MAX_FANIN): 2,
            int(TuningKey.GATHER_FLAT_TREE_MAX_COUNT): 32 * 1024,
            int(TuningKey.BCAST_FLAT_TREE_MAX_RANKS): 3,
            int(TuningKey.REDUCE_FLAT_TREE_MAX_RANKS): 4,
            int(TuningKey.REDUCE_FLAT_TREE_MAX_COUNT):
                min(self.max_rendezvous_size // 4, 32 * 1024),
        }

    def apply_static_tuning(self) -> None:
        """Write the static register values of :meth:`static_tuning`."""
        for key, value in self.static_tuning().items():
            self.set_tuning(key, value)

    def set_compression(self, policy) -> None:
        """Arm (or disarm, with ``None``) the wire-compression policy
        (:class:`~accl_tpu.arithconfig.CompressionPolicy`, r17): calls
        matching its collective/dtype/size thresholds get their
        ``compress_dtype`` selected automatically — int8 rides the
        block-scaled engine lane (with the EQuARX error-feedback twin
        when the policy asks), float16/bfloat16 the cast lanes.  The
        descriptor memo is dropped: cached descriptors predate the
        policy's decisions."""
        self._compress_policy = policy
        self._call_memo.clear()

    @property
    def compression_policy(self):
        return self._compress_policy

    def set_tuning(self, key: int, value: int) -> None:
        """Write one runtime tuning register (constants.TuningKey).
        Unknown keys raise an ACCLError naming the key and the known
        set — never a silent no-op (clear-error contract, r16); the
        backend additionally rejects keys it does not implement (e.g.
        RING_THRESHOLD_BYTES on the emulator engine)."""
        from .constants import TUNING_KEY_NAMES, unknown_tuning_key_error

        if key not in TUNING_KEY_NAMES:
            raise unknown_tuning_key_error(
                key, frozenset(TUNING_KEY_NAMES), "any")
        setter = getattr(self._device, "set_tuning", None)
        if setter is not None:
            setter(key, value)

    def get_duration(self, request: Optional[Request] = None) -> float:
        """Duration in ns of a completed call, from the engine's
        performance counter (reference: accl.cpp:1387 get_duration;
        simdevice.cpp:123 cycle→ns scaling).

        Raises ACCLError when no call has been issued or the request is
        still in flight — a silent 0.0 there poisoned bandwidth math
        downstream (0 ns == infinite busbw) without any signal."""
        req = request or self._last_request
        if req is None:
            raise ACCLError("get_duration: no request issued yet")
        if not req.done:
            raise ACCLError(
                f"get_duration: {req.description or 'request'} (id "
                f"{req.id}) has not completed — wait() on it first")
        return req.duration_ns

    # ------------------------------------------------------------------
    # fault tolerance (accl_tpu/resilience; docs/fault_tolerance.md) —
    # the detect -> recover bridge over the failure-detection machinery
    # (seqn discipline + receive timeouts + flight recorder + watchdog)
    # ------------------------------------------------------------------
    def abort(self, comm_id: int = GLOBAL_COMM, error: int = 0) -> None:
        """Abort a communicator (ULFM revoke analog): bump its epoch,
        propagate the abort through the control plane, and fail every
        pending request on all live ranks fast with ``COMM_ABORTED``
        (OR ``error`` in — the watchdog's abort action passes
        ``RANK_FAILED``).  Blocked :meth:`Request.wait` callers wake as
        their engine finalizes them — immediately, not after the
        ``ACCL_DEFAULT_TIMEOUT`` budget.  Stale traffic from the dead
        epoch is fenced at the pool boundary; recover with
        :meth:`shrink_communicator` (dead peer) or
        :meth:`reset_errors` (transient fault)."""
        self.communicator(comm_id)  # raises the naming error on bad ids
        err = int(error) | int(ErrorCode.COMM_ABORTED)
        self._aborted_comms.add(comm_id)
        # lifecycle anchor (r13): the fence event goes into the flight
        # ring so post-mortem dumps can order replays against it
        # (analysis.checks.check_fence_staleness)
        _flight.mark_event(self.flight_recorder, "abort", comm_id, err)
        self._invalidate_plans(comm_id, "communicator aborted")
        handled = self._device.abort_comm(comm_id, err)
        if not handled:
            # backend has no engine-side abort: fail the driver-tracked
            # pending async requests directly so waiters still wake.
            # Only THIS comm's requests — a healthy sibling comm's
            # in-flight calls must not report COMM_ABORTED (the flight
            # record carries the comm; without one — ACCL_FLIGHT=0 —
            # the comm is unknowable and the conservative choice is to
            # fail the request rather than strand its waiter forever)
            for ref in self._async_pending:
                r = ref()
                if r is not None and not r.done and \
                        (r.record is None or r.record.comm == comm_id):
                    r.complete(err)

    def shrink_communicator(self, comm_id: int = GLOBAL_COMM,
                            window_s: float = 1.0) -> int:
        """ULFM-style shrink: agree on the surviving rank set of
        ``comm_id`` (liveness from control-plane heartbeats + the
        probe window) and build a fresh communicator excluding dead
        ranks; returns the new comm id.  Collective over the survivors
        — every live rank must call it in the same order, exactly like
        :meth:`create_communicator`.  The usual recovery sequence after
        a rank death is ``abort() -> shrink_communicator() -> re-run
        the collective on the returned comm``."""
        from .resilience.membership import shrink as _shrink

        new_id = _shrink(self, comm_id, window_s)
        _flight.mark_event(self.flight_recorder, "shrink", comm_id)
        # plan fencing: a healed world must never replay a dead comm's
        # plan — fence driver-side plans AND the engine-side ring/cache
        # (the emu engine drains its plan slots here, not only on abort)
        self._invalidate_plans(comm_id, "communicator shrunk")
        inv = getattr(self._device, "invalidate_plans", None)
        if inv is not None:
            inv(comm_id)
        if _metrics.enabled():
            _metrics.default_registry().inc("membership/shrinks")
        return new_id

    def grow_communicator(self, new_ranks, comm_id: int = GLOBAL_COMM,
                          window_s: float = 1.0) -> int:
        """Elastic grow, the mirror of :meth:`shrink_communicator`:
        mint a fresh communicator over the LIVE members of ``comm_id``
        plus ``new_ranks`` (:class:`~accl_tpu.communicator.Rank` rows
        for ranks joining the world — e.g. a replacement for a killed
        member).  Collective over the survivors, in create order; each
        joiner adopts the identical table through
        :func:`accl_tpu.resilience.elastic.join_grown_world` (its
        engine state-synced from a sponsor first, so epochs, abort
        fences and comm-id spaces align).  In-flight traffic on other
        communicators is untouched — the dead world stays fenced
        behind its bumped epoch, it is never drained."""
        from .resilience.elastic import grow as _grow

        new_id = _grow(self, new_ranks, comm_id, window_s)
        _flight.mark_event(self.flight_recorder, "grow", comm_id)
        # same plan-fencing contract as shrink: membership changed, the
        # captured world no longer exists
        self._invalidate_plans(comm_id, "communicator grown")
        inv = getattr(self._device, "invalidate_plans", None)
        if inv is not None:
            inv(comm_id)
        return new_id

    def supervise(self, policy=None, board=None, registry=None):
        """Arm (and return) a recovery supervisor for this rank — the
        automated detect -> abort -> probe -> shrink-or-grow -> agree
        -> resume loop (resilience/supervisor.py; policy via
        ``ACCL_RECOVERY`` / ``ACCL_JOIN_WAIT_S`` /
        ``ACCL_RECOVERY_MAX_ROUNDS`` or an explicit RecoveryPolicy).
        Also armed automatically by ``ACCL_SUPERVISE=1`` at
        :meth:`initialize`."""
        from .resilience.supervisor import RecoverySupervisor

        self.supervisor = RecoverySupervisor(self, policy=policy,
                                             board=board,
                                             registry=registry)
        return self.supervisor

    # ------------------------------------------------------------------
    # persistent collective plans (accl_tpu/plans.py;
    # docs/performance.md "Persistent plans")
    # ------------------------------------------------------------------
    def capture_plan(self, fn, *args, validate: bool = True,
                     timeout_s: Optional[float] = None):
        """Capture ``fn(self, *args)``'s collective calls into a
        persistent plan: recorded once (the calls still execute — the
        capture iteration's results are real), validated once (the
        sanitizer checker suite; an error finding fails the capture
        naming it), lowered once into the backend's pre-resolved
        submission ring, then replayed with ``plan.replay()`` at ring
        speed.  Under ``ACCL_PLAN=0`` returns an eager fallback whose
        replay re-runs ``fn`` through the normal per-call path."""
        if not _plans.enabled():
            fn(self, *args)  # the capture iteration still executes
            return _plans.EagerPlan(self, fn, args)
        if self._plan_recorder is not None:
            raise ACCLError("capture_plan: a capture is already in "
                            "progress on this driver (no nesting)")
        recorder = _plans.PlanRecorder(self)
        self._plan_recorder = recorder
        try:
            fn(self, *args)
        finally:
            self._plan_recorder = None
        return _plans.build_plan(self, recorder, validate=validate,
                                 timeout_s=timeout_s)

    def _invalidate_plans(self, comm_id: Optional[int],
                          reason: str) -> None:
        """Fence live plans touching ``comm_id`` (None = all): part of
        the abort/reset/shrink/grow contract — a replay must raise
        after any epoch fence, never silently run the dead world's
        program."""
        live = []
        for ref in self._plans:
            p = ref()
            if p is None:
                continue
            live.append(ref)
            if comm_id is None or comm_id in p.comms:
                p._invalidate(reason)
        self._plans = live
        # the selection-policy memo keys on (scenario, arithcfg,
        # count, comm): after a membership change the same comm id can
        # mean a different size (and payload bucket), so drop the
        # cached decisions — the next call re-resolves at current size
        if self._tune_policy is not None:
            self._tune_policy._memo.clear()
        # transparent-dispatch composers (r19) memoize an axis split
        # over a specific membership epoch; a fence retires them the
        # same way it retires captured plans
        self._drop_hier_comms()

    def _drop_hier_comms(self) -> None:
        """Retire the transparent-dispatch composer memo (r19): cached
        scratch is freed; the burned sub-comm ids stay (create-order
        discipline), and a later qualifying call re-mints sub-comms in
        gang order on every rank alike."""
        if not self._hier_comms:
            return
        for h in self._hier_comms.values():
            if h:
                try:
                    h.close()
                except ACCLError:
                    pass
        self._hier_comms.clear()

    def _route_hier(self, call: CCLOCall, sync_in: list, sync_out: list,
                    run_async: bool, desc: str):
        """Serve one call through the composer the selection table
        picked for its cell (r19 transparent hierarchical dispatch).
        Returns the last staged call's completed Request, or None when
        the call does not qualify — root-dependent, async, device-
        resident, stream/compressed/fused, sub-communicator, capture
        or sanitizer active — and must ride the flat path.  First
        qualifying call per (comm, axis split) mints the composer:
        lazy construction is create-order aligned because every rank
        reaches the same first qualifying call in gang order."""
        if (run_async or call.comm != GLOBAL_COMM
                or not sync_in or not sync_out
                or call.compression_flags != CompressionFlags.NO_COMPRESSION
                or call.stream_flags != StreamFlags.NO_STREAM
                or call.host_flags != HostFlags.NO_HOST
                or call.fused
                or self._plan_recorder is not None or _san.active()):
            return None
        op = Operation(call.scenario)
        if op.name not in ("allreduce", "reduce_scatter", "allgather"):
            return None
        table = self._tune_policy.table
        meta = table.world or {}
        key = (call.comm, tuple(meta.get("shape") or ()),
               tuple(meta.get("axis_order") or ()))
        h = self._hier_comms.get(key)
        if h is None:
            from .tuning.autotune import fabric_of_table
            from .tuning.compose import HierarchicalComm

            fabric = fabric_of_table(table, self.size)
            if fabric.trivial:
                # nothing to compose across: remember the miss so the
                # next call is one dict probe, and ride the flat path
                self._hier_comms[key] = False
                return None
            h = HierarchicalComm(self, fabric)
            self._hier_comms[key] = h
        elif h is False:
            return None
        sendbuf, recvbuf = sync_in[0][0], sync_out[0][0]
        self._in_hier = True
        try:
            if op is Operation.allreduce:
                h.allreduce(sendbuf, recvbuf, call.count,
                            ReduceFunction(call.function))
            elif op is Operation.reduce_scatter:
                h.reduce_scatter(sendbuf, recvbuf, call.count,
                                 ReduceFunction(call.function))
            else:
                h.allgather(sendbuf, recvbuf, call.count)
        finally:
            self._in_hier = False
        return self._last_request

    def _install_communicator(self, comm: Communicator) -> int:
        """Append + upload an explicitly-built communicator (the elastic
        grow/join path, where rows do NOT come from world-comm indices).
        Enforces the id-alignment contract: the object's id must be the
        next slot on this rank."""
        if comm.id != len(self._communicators):
            raise ACCLError(
                f"_install_communicator: comm id {comm.id} is not the "
                f"next slot ({len(self._communicators)}) on this rank — "
                f"the group's create/grow order has diverged")
        self._device.upload_communicator(comm)
        self._communicators.append(comm)
        return comm.id

    def _pad_communicators(self, count: int) -> None:
        """Pad this driver's comm-id space with placeholder slots up to
        ``count`` (elastic join: the engine side is padded by the
        Join/Welcome/StateSync exchange; this is the driver half)."""
        while len(self._communicators) < count:
            cid = len(self._communicators)
            self._communicators.append(Communicator.placeholder(cid))
            self._placeholder_comms.add(cid)

    def reset_errors(self) -> None:
        """Recover a world poisoned by a CLASSIFIED transient fault
        (seqn skew after a drop/corruption that exhausted recovery):
        resynchronize sequence state, drain transient receive and
        retransmit state, clear driver-side abort fencing.  Collective:
        every rank of a quiesced world must call it — after which the
        next collective on the same world must succeed (the
        fixture-reuse contract in tests/test_fault_injection.py)."""
        self._aborted_comms.clear()
        _flight.mark_event(self.flight_recorder, "reset_errors", -1)
        # plan fencing: reset_errors is a world-state discontinuity —
        # every plan (driver + engine side) is invalidated; re-capture
        # on the recovered world (the emu engine drains its own plan
        # slots inside reset_errors, the TPU engine in reset below)
        self._invalidate_plans(None, "reset_errors")
        self._device.reset_errors()

    def resilience_stats(self) -> dict:
        """Engine-side recovery counters (retransmitted segments, NACKs
        sent/received, epoch-fenced drops), or an empty dict for
        backends without the retransmission lane."""
        fn = getattr(self._device, "resilience_stats", None)
        return fn() if fn is not None else {}

    # ------------------------------------------------------------------
    # session lifecycle (reference: open_port/open_con/close_con,
    # accl.hpp:1069-1083, backed by the tcp_session_handler plugin)
    # ------------------------------------------------------------------
    def open_port(self) -> None:
        """Verify the inbound endpoint is live (reference open_port).
        Connectionless backends (inproc, datagram, TPU/ICI) succeed as
        no-ops — as in the reference, where only the TCP design ships
        the session handler."""
        fn = getattr(self._device, "open_port", None)
        if fn is not None and fn() != 0:
            raise ACCLError("open_port failed: transport not listening")

    def open_con(self, comm_id: int = GLOBAL_COMM) -> None:
        """Explicitly open sessions to every peer of a communicator,
        surfacing connection failures as a distinct setup error instead
        of a mid-collective hang (reference open_con)."""
        fn = getattr(self._device, "open_con", None)
        if fn is None:
            return  # connectionless backend
        rc = fn(comm_id)
        if rc > 0:
            raise ACCLError(
                f"open_con failed: no session to peer {rc - 1} "
                f"(comm {comm_id})")
        if rc < 0:
            raise ACCLError(f"open_con: unknown communicator {comm_id}")

    def close_con(self, comm_id: int = GLOBAL_COMM) -> None:
        """Tear down the sessions of a communicator (reference
        close_con).  A later call lazily reconnects on session
        transports."""
        fn = getattr(self._device, "close_con", None)
        if fn is not None and fn(comm_id) < 0:
            raise ACCLError(f"close_con: unknown communicator {comm_id}")

    # ------------------------------------------------------------------
    # buffers
    # ------------------------------------------------------------------
    def create_buffer(self, length: int, dtype=np.float32,
                      host_only: bool = False) -> BaseBuffer:
        """Allocate a paired host+device buffer; with host_only=True the
        device residence is the engine's host-memory region instead (the
        reference's host-only buffers over the external_dma path,
        accl.hpp:774-1004 create_buffer<T> family + buffer.hpp
        is_host_only).  Backends without a distinct host region fall
        back to a normal buffer."""
        if host_only:
            try:
                return self._device.create_buffer(length, np.dtype(dtype),
                                                  host_only=True)
            except TypeError:
                pass  # backend has no host region; plain buffer below
        return self._device.create_buffer(length, np.dtype(dtype))

    def create_buffer_like(self, data: np.ndarray) -> BaseBuffer:
        buf = self.create_buffer(int(np.asarray(data).size), np.asarray(data).dtype)
        buf.host[:] = np.asarray(data).reshape(-1)
        return buf

    def create_buffer_p2p(self, length: int, dtype=np.float32) -> BaseBuffer:
        """Allocate a buffer directly addressable by peer engines without
        host staging (reference: FPGABufferP2P — PCIe-p2p-visible BO,
        fpgabufferp2p.hpp).  On this build every device buffer is already
        peer-addressable (emulator: shared device memory; TPU: HBM
        reachable over ICI), so this maps to the backend's p2p variant
        when it has one and a plain device buffer otherwise."""
        make = getattr(self._device, "create_buffer_p2p", None)
        if make is not None:
            return make(length, np.dtype(dtype))
        return self._device.create_buffer(length, np.dtype(dtype))

    # ------------------------------------------------------------------
    # collectives — each mirrors one reference entry point in accl.cpp
    # ------------------------------------------------------------------
    def send(
        self,
        srcbuf: BaseBuffer,
        count: int,
        dst: int,
        tag: int = TAG_ANY,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        stream_flags: StreamFlags = StreamFlags.NO_STREAM,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
    ):
        """Point-to-point send (reference: accl.cpp:138)."""
        call = self._build(
            Operation.send, count, comm_id, root_src_dst=dst, tag=tag,
            op0=srcbuf, stream_flags=stream_flags, compress_dtype=compress_dtype,
        )
        return self._execute(call, sync_in=[] if from_fpga else [(srcbuf, count)],
                             sync_out=[], run_async=run_async, desc=f"send(dst={dst})")

    def recv(
        self,
        dstbuf: BaseBuffer,
        count: int,
        src: int,
        tag: int = TAG_ANY,
        comm_id: int = GLOBAL_COMM,
        to_fpga: bool = False,
        stream_flags: StreamFlags = StreamFlags.NO_STREAM,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
    ):
        """Point-to-point receive (reference: accl.cpp:252)."""
        call = self._build(
            Operation.recv, count, comm_id, root_src_dst=src, tag=tag,
            res=dstbuf, stream_flags=stream_flags, compress_dtype=compress_dtype,
        )
        return self._execute(call, sync_in=[],
                             sync_out=[] if to_fpga else [(dstbuf, count)],
                             run_async=run_async, desc=f"recv(src={src})")

    def stream_put(
        self,
        srcbuf: BaseBuffer,
        count: int,
        dst: int,
        stream_id: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        run_async: bool = False,
    ):
        """Send into a remote compute stream: the payload is routed to
        stream `stream_id` on the destination instead of a memory buffer
        (reference: accl.cpp:191-250 stream_put; remote routing by header
        strm field, udp_depacketizer.cpp:136-147)."""
        if stream_id < 9:
            raise ACCLError("stream ids < 9 are reserved")  # reference: accl.cpp:197
        call = self._build(
            Operation.send, count, comm_id, root_src_dst=dst, tag=stream_id,
            op0=srcbuf, stream_flags=StreamFlags.RES_STREAM,
        )
        return self._execute(call, sync_in=[] if from_fpga else [(srcbuf, count)],
                             sync_out=[], run_async=run_async,
                             desc=f"stream_put(dst={dst}, strm={stream_id})")

    def copy(
        self,
        srcbuf: BaseBuffer,
        dstbuf: BaseBuffer,
        count: int,
        from_fpga: bool = False,
        to_fpga: bool = False,
        run_async: bool = False,
    ):
        """Local device-side copy (reference: accl.cpp:310)."""
        call = self._build(Operation.copy, count, GLOBAL_COMM, op0=srcbuf, res=dstbuf)
        return self._execute(call, sync_in=[] if from_fpga else [(srcbuf, count)],
                             sync_out=[] if to_fpga else [(dstbuf, count)],
                             run_async=run_async, desc="copy")

    def copy_to_stream(
        self,
        srcbuf: BaseBuffer,
        count: int,
        stream_id: int = 9,
        from_fpga: bool = False,
        run_async: bool = False,
    ):
        """Copy a device buffer into a local kernel stream
        (reference: accl.cpp copy_to_stream — copy with RES_STREAM; the
        result lane is routed to the external-kernel switch port)."""
        if stream_id < 9:
            raise ACCLError("stream ids < 9 are reserved")  # accl.cpp:197
        call = self._build(Operation.copy, count, GLOBAL_COMM, op0=srcbuf,
                           tag=stream_id, stream_flags=StreamFlags.RES_STREAM)
        return self._execute(call, sync_in=[] if from_fpga else [(srcbuf, count)],
                             sync_out=[], run_async=run_async,
                             desc=f"copy_to_stream({stream_id})")

    def copy_from_stream(
        self,
        dstbuf: BaseBuffer,
        count: int,
        to_fpga: bool = False,
        run_async: bool = False,
    ):
        """Copy from the local kernel input stream into a device buffer
        (reference: accl.cpp copy_from_stream — copy with OP0_STREAM)."""
        call = self._build(Operation.copy, count, GLOBAL_COMM, res=dstbuf,
                           stream_flags=StreamFlags.OP0_STREAM)
        return self._execute(call, sync_in=[],
                             sync_out=[] if to_fpga else [(dstbuf, count)],
                             run_async=run_async, desc="copy_from_stream")

    def combine(
        self,
        count: int,
        function: ReduceFunction,
        op0: BaseBuffer,
        op1: BaseBuffer,
        res: BaseBuffer,
        from_fpga: bool = False,
        to_fpga: bool = False,
        run_async: bool = False,
    ):
        """Local elementwise reduction of two device buffers
        (reference: accl.cpp:378)."""
        call = self._build(
            Operation.combine, count, GLOBAL_COMM, function=int(function),
            op0=op0, op1=op1, res=res,
        )
        sync_in = [] if from_fpga else [(op0, count), (op1, count)]
        return self._execute(call, sync_in=sync_in,
                             sync_out=[] if to_fpga else [(res, count)],
                             run_async=run_async, desc=f"combine({function.name})")

    def bcast(
        self,
        buf: BaseBuffer,
        count: int,
        root: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
    ):
        """Broadcast from root (reference: accl.cpp:418)."""
        comm = self.communicator(comm_id)
        is_root = comm.local_rank == root
        call = self._build(
            Operation.bcast, count, comm_id, root_src_dst=root,
            op0=buf if is_root else None, res=None if is_root else buf,
            compress_dtype=compress_dtype,
        )
        sync_in = [(buf, count)] if (is_root and not from_fpga) else []
        sync_out = [(buf, count)] if (not is_root and not to_fpga) else []
        return self._execute(call, sync_in=sync_in, sync_out=sync_out,
                             run_async=run_async, desc=f"bcast(root={root})")

    def scatter(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        root: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
    ):
        """Scatter `count` elements to each rank from root
        (reference: accl.cpp:464)."""
        comm = self.communicator(comm_id)
        is_root = comm.local_rank == root
        call = self._build(
            Operation.scatter, count, comm_id, root_src_dst=root,
            op0=sendbuf if is_root else None, res=recvbuf,
            compress_dtype=compress_dtype,
            op0_dtype=sendbuf.data_type if sendbuf is not None else None,
        )
        sync_in = [(sendbuf, count * comm.size)] if (is_root and not from_fpga) else []
        sync_out = [] if to_fpga else [(recvbuf, count)]
        return self._execute(call, sync_in=sync_in, sync_out=sync_out,
                             run_async=run_async, desc=f"scatter(root={root})")

    def gather(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        root: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
    ):
        """Gather `count` elements from each rank at root
        (reference: accl.cpp:513)."""
        comm = self.communicator(comm_id)
        is_root = comm.local_rank == root
        call = self._build(
            Operation.gather, count, comm_id, root_src_dst=root,
            op0=sendbuf, res=recvbuf if is_root else None,
            compress_dtype=compress_dtype,
            res_dtype=recvbuf.data_type if recvbuf is not None else None,
        )
        sync_in = [] if from_fpga else [(sendbuf, count)]
        sync_out = [(recvbuf, count * comm.size)] if (is_root and not to_fpga) else []
        return self._execute(call, sync_in=sync_in, sync_out=sync_out,
                             run_async=run_async, desc=f"gather(root={root})")

    def allgather(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
        fused: Optional[bool] = None,
    ):
        """All-gather (reference: accl.cpp:571).  ``fused``: see
        allreduce."""
        comm = self.communicator(comm_id)
        call = self._build(
            Operation.allgather, count, comm_id,
            op0=sendbuf, res=recvbuf, compress_dtype=compress_dtype,
            fused=fused,
        )
        return self._execute(call,
                             sync_in=[] if from_fpga else [(sendbuf, count)],
                             sync_out=[] if to_fpga else [(recvbuf, count * comm.size)],
                             run_async=run_async, desc="allgather")

    def reduce(
        self,
        sendbuf: Optional[BaseBuffer],
        recvbuf: Optional[BaseBuffer],
        count: int,
        root: int,
        function: ReduceFunction = ReduceFunction.SUM,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        stream_flags: StreamFlags = StreamFlags.NO_STREAM,
        stream_id: int = 9,
        run_async: bool = False,
    ):
        """Rooted reduction (reference: accl.cpp:627-794, 4 overloads).

        The mem<->stream variants (reference: test.cpp:813-910) are selected
        with `stream_flags`: OP0_STREAM takes the operand from the local
        compute-kernel stream (`sendbuf` may be None; feed bytes with
        `device.push_krnl`), RES_STREAM delivers the root's result to local
        compute stream `stream_id` (`recvbuf` may be None; read it with
        `device.pop_stream`)."""
        comm = self.communicator(comm_id)
        is_root = comm.local_rank == root
        op_stream = bool(stream_flags & StreamFlags.OP0_STREAM)
        res_stream = bool(stream_flags & StreamFlags.RES_STREAM)
        if res_stream and stream_id < 9:
            raise ACCLError("stream ids < 9 are reserved")  # accl.cpp:197
        call = self._build(
            Operation.reduce, count, comm_id, root_src_dst=root,
            function=int(function),
            tag=stream_id if res_stream else TAG_ANY,
            op0=None if op_stream else sendbuf,
            res=recvbuf if (is_root and not res_stream) else None,
            stream_flags=stream_flags, compress_dtype=compress_dtype,
            res_dtype=(recvbuf.data_type
                       if (recvbuf is not None and not res_stream) else None),
        )
        sync_in = [] if (from_fpga or op_stream) else [(sendbuf, count)]
        sync_out = ([(recvbuf, count)]
                    if (is_root and not to_fpga and not res_stream) else [])
        return self._execute(call, sync_in=sync_in,
                             sync_out=sync_out, run_async=run_async,
                             desc=f"reduce(root={root},{function.name})")

    def allreduce(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        function: ReduceFunction = ReduceFunction.SUM,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
        fused: Optional[bool] = None,
    ):
        """All-reduce (reference: accl.cpp:796).  ``fused`` opts the call
        into the r18 chunked compute/communication-overlap lane (None =
        the driver's ACCL_FUSED default)."""
        call = self._build(
            Operation.allreduce, count, comm_id, function=int(function),
            op0=sendbuf, res=recvbuf, compress_dtype=compress_dtype,
            fused=fused,
        )
        return self._execute(call, sync_in=[] if from_fpga else [(sendbuf, count)],
                             sync_out=[] if to_fpga else [(recvbuf, count)],
                             run_async=run_async, desc=f"allreduce({function.name})")

    def reduce_scatter(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        function: ReduceFunction = ReduceFunction.SUM,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        compress_dtype: Optional[DataType] = None,
        run_async: bool = False,
        fused: Optional[bool] = None,
    ):
        """Reduce-scatter: each rank ends with `count` reduced elements
        (reference: accl.cpp:844).  ``fused``: see allreduce."""
        comm = self.communicator(comm_id)
        call = self._build(
            Operation.reduce_scatter, count, comm_id, function=int(function),
            op0=sendbuf, res=recvbuf, compress_dtype=compress_dtype,
            fused=fused,
        )
        return self._execute(call,
                             sync_in=[] if from_fpga else [(sendbuf, count * comm.size)],
                             sync_out=[] if to_fpga else [(recvbuf, count)],
                             run_async=run_async, desc=f"reduce_scatter({function.name})")

    def alltoall(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: int,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        run_async: bool = False,
    ):
        """All-to-all personalized exchange (reference: accl.cpp:892)."""
        comm = self.communicator(comm_id)
        call = self._build(Operation.alltoall, count, comm_id,
                           op0=sendbuf, res=recvbuf)
        return self._execute(call,
                             sync_in=[] if from_fpga else [(sendbuf, count * comm.size)],
                             sync_out=[] if to_fpga else [(recvbuf, count * comm.size)],
                             run_async=run_async, desc="alltoall")

    def alltoallv(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        sendcounts: BaseBuffer,
        recvcounts: BaseBuffer,
        row: int = 1,
        comm_id: int = GLOBAL_COMM,
        from_fpga: bool = False,
        to_fpga: bool = False,
        run_async: bool = False,
    ):
        """All-to-all with per-peer counts held on the device.

        ``sendcounts`` is an int32 buffer of P counts, in rows of `row`
        elements: ``sendcounts[j]`` rows go to comm rank j, and the send
        layout is packed in rank order.  The call fills the int32
        buffer ``recvcounts`` with the rows received from each rank and
        packs them into ``recvbuf`` in source order; what the rows past
        their sum hold is unspecified (the TPU engine writes zeros, the
        emulator leaves them).  The counts never reach the host during a
        call, so the router that made them on the device is not
        stalled.

        ``recvbuf``'s length is the capacity: the caller sizes it for
        the worst case (every rank's rows sent here).  Nothing checks
        at run time that the received rows fit, nor that the sent rows
        fit ``sendbuf``: the sums must fit.  Same-rank rows move
        locally; zero counts and an all-to-one routing are legal.  On
        the TPU engine every member's send (and receive) buffer has one
        length.

        The descriptor's count is `row`; the count operand rides in the
        OP1 slot and the count result's address is the descriptor's
        ``addr_3`` (``CCLOCall.addr_3_words``)."""
        comm = self.communicator(comm_id)
        P = comm.size
        for name, b in (("sendcounts", sendcounts),
                        ("recvcounts", recvcounts)):
            if b.data_type != DataType.int32 or b.length != P:
                raise ACCLError(f"alltoallv: {name} must be an int32 "
                                f"buffer of {P} counts")
        if row < 1 or sendbuf.length % row or recvbuf.length % row:
            raise ACCLError(f"alltoallv: buffer lengths must be whole "
                            f"rows of {row} elements")
        call = self._build(Operation.alltoallv, row, comm_id,
                           **CCLOCall.addr_3_words(recvcounts.address),
                           op0=sendbuf, res=recvbuf, counts=sendcounts)
        return self._execute(
            call,
            sync_in=[] if from_fpga else [(sendbuf, sendbuf.length),
                                          (sendcounts, P)],
            sync_out=[] if to_fpga else [(recvbuf, recvbuf.length),
                                         (recvcounts, P)],
            run_async=run_async, desc="alltoallv")

    def barrier(self, comm_id: int = GLOBAL_COMM, run_async: bool = False):
        """Barrier over the communicator (reference: accl.cpp:947)."""
        call = self._build(Operation.barrier, 0, comm_id)
        return self._execute(call, sync_in=[], sync_out=[],
                             run_async=run_async, desc="barrier")

    def nop(self, run_async: bool = False):
        call = self._build(Operation.nop, 0, GLOBAL_COMM)
        return self._execute(call, sync_in=[], sync_out=[],
                             run_async=run_async, desc="nop")

    # ------------------------------------------------------------------
    # marshaling (reference: accl.cpp:1252-1372 prepare_call)
    # ------------------------------------------------------------------
    def _build(
        self,
        scenario: Operation,
        count: int,
        comm_id: int,
        root_src_dst: int = 0,
        function: int = 0,
        tag: int = TAG_ANY,
        op0: Optional[BaseBuffer] = None,
        op1: Optional[BaseBuffer] = None,
        res: Optional[BaseBuffer] = None,
        stream_flags: StreamFlags = StreamFlags.NO_STREAM,
        compress_dtype: Optional[DataType] = None,
        op0_dtype: Optional[DataType] = None,
        res_dtype: Optional[DataType] = None,
        fused: Optional[bool] = None,
        counts: Optional[BaseBuffer] = None,
    ) -> CCLOCall:
        """Build a call descriptor: select the arithmetic config from the
        (uncompressed, compressed) dtype pair, derive per-operand and
        on-the-wire compression flags, substitute dummies for absent
        operands — the same responsibilities as the reference prepare_call
        (accl.cpp:1252-1372).

        The full reference flag algebra is implemented: mixed-dtype
        operands mark whichever of OP0/OP1/RES holds the *compressed*
        (narrower) representation (accl.cpp:1310-1335); `compress_dtype`
        additionally requests wire compression (ETH_COMPRESSED,
        accl.cpp:1338-1367), and operands already typed as the compressed
        dtype get their per-operand bit as well.

        Cross-rank contract (same as the reference): every rank of a
        collective must derive the same arithcfg + ETH flag, since each
        engine computes the wire format from its own descriptor.  Absent
        operands therefore contribute dtype hints (op0_dtype/res_dtype,
        the reference's data_type_io_* fields) — so mixed-dtype rooted
        collectives must either pass the absent-side buffer everywhere
        (reduce/gather/scatter do this automatically when the buffer
        argument is supplied on every rank) or set compress_dtype, which
        pins the wire format regardless of per-rank operand layout
        (tests/test_compression_matrix.py ROOTED_COMBOS)."""
        # each buffer contributes (address, dtype, host-only): every
        # _build-derived field is a function of those three plus the
        # scalar args.  dtype/host-only are IN the key because emulator
        # backends free and first-fit-REUSE addresses (engine.cpp
        # free_addr) — an address-only key could serve a stale arithcfg
        # for a recycled address with a different dtype; with all three,
        # a recycled address either matches (identical descriptor) or
        # misses.
        # a bad comm id must fail HERE with a decodable error, not as a
        # backend IndexError (or a silent engine hang) later; the slow
        # path is one len() + compare, the raise is delegated.  The
        # world comm on an uninitialized driver stays permissive:
        # local-op descriptors (copy/nop) are buildable pre-bring-up
        if (comm_id < 0 or comm_id >= len(self._communicators)) and \
                (self._communicators or comm_id != GLOBAL_COMM):
            self.communicator(comm_id)  # raises the naming ACCLError

        def _bkey(b):
            return (None if b is None
                    else (b.address, b.data_type, b.is_host_only))

        # per-call fused=None resolves to the driver default HERE so the
        # memo key carries the resolved bool (two calls differing only
        # in fused must not share a descriptor)
        fused = self._fused_default if fused is None else bool(fused)
        memo_key = (scenario, count, comm_id, root_src_dst, function, tag,
                    _bkey(op0), _bkey(op1), _bkey(res),
                    stream_flags, compress_dtype, op0_dtype, res_dtype,
                    fused, _bkey(counts))
        cached = self._call_memo.get(memo_key)
        if cached is not None:
            self._call_memo.move_to_end(memo_key)
            return cached

        dummy = DummyBuffer()
        op0 = op0 if op0 is not None else dummy
        op1 = op1 if op1 is not None else dummy
        res = res if res is not None else dummy

        # absent operands still contribute their dtype so every rank of a
        # rooted collective derives the same arithcfg + wire format (the
        # reference's data_type_io_* hints, accl.cpp:1259-1281)
        present = [b for b in (op0, op1, res) if not b.is_dummy]
        dtypes = {b.data_type for b in present}
        if op0.is_dummy and op0_dtype is not None:
            dtypes.add(op0_dtype)
        if res.is_dummy and res_dtype is not None:
            dtypes.add(res_dtype)
        dtypes.discard(DataType.none)
        compression = CompressionFlags.NO_COMPRESSION

        # wire-compression policy (r17): fill in compress_dtype for
        # eligible calls when the caller left it unset.  Deterministic
        # in the memo key's fields + the (static-after-arming) policy,
        # so the descriptor memo above stays sound; stream-flagged and
        # mixed-dtype calls are never auto-compressed.  One falsy read
        # when no policy is armed — bit-identical static dispatch.
        if compress_dtype is None and self._compress_policy is not None \
                and stream_flags == StreamFlags.NO_STREAM \
                and len(dtypes) == 1:
            compress_dtype = self._compress_policy.select(
                scenario, count, comm_id, next(iter(dtypes)))

        def flag_operands(compressed_dtype: DataType) -> CompressionFlags:
            flags = CompressionFlags.NO_COMPRESSION
            if not op0.is_dummy and op0.data_type == compressed_dtype:
                flags |= CompressionFlags.OP0_COMPRESSED
            if not op1.is_dummy and op1.data_type == compressed_dtype:
                flags |= CompressionFlags.OP1_COMPRESSED
            if not res.is_dummy and res.data_type == compressed_dtype:
                flags |= CompressionFlags.RES_COMPRESSED
            return flags

        if compress_dtype is None:
            if len(dtypes) <= 1:
                # homogeneous operands: identity pair (accl.cpp:1297-1307)
                dtype = dtypes.pop() if dtypes else DataType.float32
                pair = (dtype, dtype)
                if pair not in self._arith_ids and scenario not in (
                    Operation.config, Operation.nop, Operation.barrier,
                ):
                    raise ACCLError(f"unsupported dtype {dtype!r}")
                arithcfg = self._arith_ids.get(pair, 0)
            elif len(dtypes) == 2:
                # operand compression without wire compression: the
                # narrower dtype is the compressed representation
                # (accl.cpp:1310-1335)
                d1, d2 = sorted(dtypes, key=lambda d: DATA_TYPE_SIZE[d])
                pair = (d2, d1)
                if pair not in self._arith_ids:
                    raise ACCLError(f"no arithmetic config for dtype pair {pair}")
                arithcfg = self._arith_ids[pair]
                compression = flag_operands(d1)
            else:
                raise ACCLError(f"unsupported dtype combination: {dtypes}")
        else:
            # wire compression requested (accl.cpp:1338-1367)
            operand_dtypes = dtypes - {compress_dtype}
            if len(operand_dtypes) > 1:
                raise ACCLError(f"unsupported dtype combination: {dtypes}")
            uncompressed = (operand_dtypes.pop() if operand_dtypes
                            else compress_dtype)
            if uncompressed == compress_dtype:
                # all operands already typed as the wire dtype: identity
                # config; ETH flag is set for descriptor fidelity but the
                # ratio-0 config makes it a no-op in the engine
                pair = (uncompressed, uncompressed)
                if pair not in self._arith_ids:
                    raise ACCLError(f"unsupported dtype {uncompressed!r}")
                arithcfg = self._arith_ids[pair]
                compression = CompressionFlags.ETH_COMPRESSED
            elif compress_dtype == DataType.int8:
                # block-scaled wire lane (r17): the wire form is
                # (int8, per-block fp32 scales) — it has no flat-buffer
                # residence, so per-operand int8 marking is rejected
                # and the ETH flag stands alone.  The EQuARX
                # error-feedback twin is selected per the armed policy.
                pair = (uncompressed, compress_dtype)
                if pair not in self._arith_ids:
                    raise ACCLError(f"no arithmetic config for dtype pair {pair}")
                if uncompressed != DataType.float32:
                    raise ACCLError(
                        f"int8 block-scaled wire lane supports float32 "
                        f"operands only (got {uncompressed.name})")
                if any(not b.is_dummy and b.data_type == DataType.int8
                       for b in (op0, op1, res)):
                    raise ACCLError(
                        "int8 block-scaled wire lane: operands must be "
                        "float32 — a flat int8 buffer cannot hold the "
                        "(int8, per-block scale) wire representation")
                use_ef = (self._compress_policy is not None
                          and self._compress_policy.wants_error_feedback(
                              comm_id))
                arithcfg = (self._arith_ids_ef[pair] if use_ef
                            else self._arith_ids[pair])
                compression = CompressionFlags.ETH_COMPRESSED
            else:
                pair = (uncompressed, compress_dtype)
                if pair not in self._arith_ids:
                    raise ACCLError(f"no arithmetic config for dtype pair {pair}")
                arithcfg = self._arith_ids[pair]
                compression = (CompressionFlags.ETH_COMPRESSED
                               | flag_operands(compress_dtype))

        # host-resident operand markers (reference prepare_call sets
        # OP0/OP1/RES_HOST from Buffer::is_host_only, accl.cpp:1259-1283)
        host_flags = HostFlags.NO_HOST
        if not op0.is_dummy and op0.is_host_only:
            host_flags |= HostFlags.OP0_HOST
        if not op1.is_dummy and op1.is_host_only:
            host_flags |= HostFlags.OP1_HOST
        if not res.is_dummy and res.is_host_only:
            host_flags |= HostFlags.RES_HOST

        call = CCLOCall(
            scenario=scenario,
            count=count,
            comm=comm_id,
            root_src_dst=root_src_dst,
            function=function,
            tag=tag,
            arithcfg=arithcfg,
            compression_flags=compression,
            stream_flags=stream_flags,
            host_flags=host_flags,
            addr_0=op0.address,
            # alltoallv's count operand: in the OP1 slot, outside the
            # dtype algebra (int32 counts beside a bf16 payload)
            addr_1=op1.address if counts is None else counts.address,
            addr_2=res.address,
            fused=fused,
        )
        self._call_memo[memo_key] = call
        while len(self._call_memo) > self._call_memo_cap:
            self._call_memo.popitem(last=False)
        return call

    def _config_call(self, func: CfgFunc, value: int = 0) -> None:
        """Issue an Operation.config descriptor
        (reference: accl.cpp call_config / cfgFunc dispatch fw :2413-2459)."""
        call = CCLOCall(scenario=Operation.config, count=value, function=int(func))
        req = Request(f"config({func.name})")
        self._queue.submit(req, lambda r: self._device.start(call, r))
        if not req.wait(timeout=30.0):
            raise ACCLError(f"config({func.name}) timed out")
        req.check()

    def _execute(
        self,
        call: CCLOCall,
        sync_in: list,
        sync_out: list,
        run_async: bool,
        desc: str,
    ):
        """Submit one call: sync inputs, start async, and either return the
        request handle or wait + sync outputs + check retcode
        (reference: call_async/call_sync accl.cpp:1395-1413)."""
        # abort fast-fail (resilience): a call on an aborted comm must
        # not burn a receive budget against a fenced engine — one falsy
        # set check when no abort ever happened (the off-path case)
        if self._aborted_comms and call.comm in self._aborted_comms:
            raise ACCLError(
                f"{desc}: communicator {call.comm} is aborted "
                f"(COMM_ABORTED) — shrink_communicator() or "
                f"reset_errors() to recover",
                int(ErrorCode.COMM_ABORTED))
        # placeholder fast-fail (elastic join): same falsy-set cost
        if self._placeholder_comms and call.comm in self._placeholder_comms:
            self.communicator(call.comm)  # raises the naming ACCLError
        # learned selection policy (accl_tpu/tuning): one falsy read
        # when no table is armed; armed, one memoized dict probe per
        # descriptor signature — the policy's threshold derivations
        # were written into the backend registers at install, so this
        # consult only records/serves the per-call decision (metrics
        # family tuning/selected/<algorithm>)
        if self._tune_policy is not None:
            alg = self._tune_policy.on_call(self, call)
            # the r18 fused lane is a DESCRIPTOR opt-in, not a backend
            # register: a table cell won by "fused" arms the memoized
            # call object once (idempotent — _build returns the same
            # object per signature, so every later call of this
            # signature rides the fused gang plan)
            if alg == "fused" and not call.fused:
                call.fused = True
            # transparent hierarchical dispatch (r19): a cell won by
            # the composer routes through a memoized per-(comm,
            # axis-split) HierarchicalComm — the caller never
            # constructs one.  Only the plain sync host path on the
            # global communicator qualifies; everything else falls
            # through to the flat engine call.  The _in_hier guard
            # keeps the composer's own staged sub-comm calls (which
            # re-enter _execute) on the flat path.
            elif alg == "hierarchical" and not self._in_hier:
                routed = self._route_hier(call, sync_in, sync_out,
                                          run_async, desc)
                if routed is not None:
                    return routed
        # observability gate first: one module-bool read each when all
        # are off, and t_submit marks user-call entry (operand staging
        # below is inside the submit→queue window by design).  The
        # flight recorder is in the gate because it is ON by default —
        # the always-on black box — so the no-observer fast path only
        # exists under ACCL_FLIGHT=0 + ACCL_METRICS=0 + trace off.
        # Traced, the driver stage's annotation (accl.driver) opens here
        # and closes at the queue hand-off (RequestQueue.submit).
        traced = _trace.enabled()
        if traced:
            _trace.stage("accl.driver")
        observe = ((self.flight_recorder is not None and _flight.enabled())
                   or _metrics.enabled() or traced)
        t_submit = _trace.now_ns() if observe else 0
        # size validation: the descriptor carries the full count, so a
        # short buffer would silently corrupt (the reference throws from
        # its buffer slice bounds)
        for buf, count in (*sync_in, *sync_out):
            if not buf.is_dummy and count > buf.length:
                raise ACCLError(
                    f"{desc}: count {count} exceeds buffer length {buf.length}"
                )
        for buf, count in sync_in:
            if not buf.is_dummy:
                buf.slice(0, count).sync_to_device()

        # sync=True marks a call whose submitter blocks below: backends
        # with a leader-dispatch fast path (backends/tpu.py) may then
        # execute the gang inline on the last-arriving rank's thread
        req = Request(desc, sync=not run_async)
        if observe:
            self._observe_call(call, desc, req, t_submit, traced)
        # collective sanitizer lane (analysis/sanitizer.py): off-path
        # cost is this one module-bool read; with ACCL_SANITIZE=1 the
        # call is validated (comm/root/peer/operand-overlap) and, on
        # in-process worlds, fingerprint-matched against its gang peers
        # BEFORE dispatch — raising here instead of hanging there.  A
        # shadow CaptureSession records the descriptor the same way.
        if _san.active():
            _san.on_call(self, call, desc, req, run_async)
        # plan capture (capture_plan in progress): shadow-record the
        # descriptor + staging spec; the call still executes below, so
        # the capture iteration's results are real.  One falsy read
        # when no capture is installed.
        if self._plan_recorder is not None:
            self._plan_recorder.on_call(call, sync_in, sync_out,
                                        run_async, desc, req)

        if sync_out:  # device-resident results need no completion sync
            def finish(r: Request) -> None:
                if r.retcode == 0:
                    for buf, count in sync_out:
                        if not buf.is_dummy:
                            buf.slice(0, count).sync_from_device()

            req.on_complete = finish
        self._queue.submit(req, lambda r: self._device.start(call, r))
        self._last_request = req
        if run_async:
            # weak handle only: deinit() names still-pending async
            # requests, but tracking must never extend their lifetime
            import weakref

            self._async_pending.append(weakref.ref(req))
            if len(self._async_pending) > 256:
                self._async_pending = [
                    ref for ref in self._async_pending
                    if (r := ref()) is not None and not r.done]
            return req
        if not req.wait(timeout=self.call_timeout_s):
            # disarm the result sync so a late completion can't mutate the
            # user's host buffers after this raise; the flight record
            # (seq, state, lane, age) pins WHERE the call wedged
            req.on_complete = None
            raise ACCLError(f"{desc} timed out waiting for engine "
                            f"completion{req.flight_info()}")
        req.check()
        return req

    def resolve_call_signature(self, call: CCLOCall) -> tuple:
        """(op, nranks, rank, dtype_name, nbytes) for one descriptor —
        the ONE derivation of the metrics signature, shared by the
        observability gate below and the r16 selection policy's table
        lookup (accl_tpu/tuning/autotune.SelectionPolicy.on_call), so
        the tuner always buckets a call exactly the way the metrics it
        was trained on did."""
        op = Operation(call.scenario)
        comm = (self._communicators[call.comm]
                if call.comm < len(self._communicators) else None)
        nranks = comm.size if comm else 1
        rank = comm.local_rank if comm else -1
        pair = self._arith_pairs.get(call.arithcfg)
        dtype_name = pair[0].name if pair else "none"
        # DATA_TYPE_SIZE is in BITS (reference constants.hpp:268-272)
        elem_bytes = (DATA_TYPE_SIZE.get(pair[0], 0) // 8) if pair else 0
        nbytes = (call.count * elem_bytes
                  * _metrics.payload_factor(op.name, nranks))
        return op, nranks, rank, dtype_name, nbytes

    def _observe_call(self, call: CCLOCall, desc: str, req: Request,
                      t_submit: int, traced: bool) -> None:
        """Attach the call's one record to the outgoing request: into
        the flight ring when the recorder is on, the metrics registry
        that observes it at completion, and, traced, its gang id.  The
        gang-id key matches the engines' FIFO pairing key (scenario,
        comm, tag), so rank R's Nth instance joins the same gang id
        every engine would assemble."""
        op, nranks, rank, dtype_name, nbytes = \
            self.resolve_call_signature(call)
        # tenant label (r20): rides the issuing communicator; one
        # attribute read (class-level None when unlabeled)
        tenant = (self._communicators[call.comm].tenant
                  if call.comm < len(self._communicators) else None)
        gang = op in _GANG_OPS
        rec = CallRecord(op.name, desc, rank, call.count, dtype_name,
                         nbytes, nranks, call.comm, call.tag, gang, req.id,
                         tenant, t_submit)
        if self.flight_recorder is not None and _flight.enabled():
            self.flight_recorder.add(rec)
        if _metrics.enabled():
            rec.registry = _metrics.default_registry()
        if traced:
            rec.traced = True
            if gang:
                rec.gang_id = _trace.collector().gang_id_for(
                    (int(op), call.comm, call.tag), rank)
        req.record = rec

    # ------------------------------------------------------------------
    # observability (reference: accl.cpp:980-1064 dump utilities, plus
    # the accl_tpu/observability metrics registry + trace collector)
    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        """Snapshot of the metrics registry this driver publishes into:
        per-(collective, dtype, size-bucket) call counts, fixed-bucket
        latency histograms, and derived algorithmic/bus bandwidth, plus
        this device's engine counters (dispatch-lane attribution on the
        TPU backend) merged under ``engine/``-prefixed keys.  In-process
        worlds share one registry, so the snapshot aggregates every
        rank's calls."""
        snap = _metrics.default_registry().snapshot()
        eng = getattr(self._device, "engine_metrics", None)
        if eng is not None:
            for k, v in eng.counters().items():
                snap["counters"][f"engine/{k}"] = v
        return snap

    def dump_metrics(self, as_json: bool = False) -> str:
        """Text (default) or JSON rendering of :meth:`metrics`
        (registry side only — engine counters are in the dict form)."""
        return _metrics.dump_metrics(as_json=as_json)

    def dump_flight_recorder(self, path: Optional[str] = None,
                             merged: bool = False) -> dict:
        """The always-on flight recorder's ring: this rank's last N
        collective records (seq, state, lane, timestamps) — the black
        box to read when a collective wedges.  With ``merged=True``
        returns every live rank's ring through
        :func:`observability.flight.merge_flight_dumps` (desync/hang
        analysis included); with ``path`` also writes the JSON there.
        Also reachable without code: ``SIGUSR1`` dumps all ranks to
        ``ACCL_FLIGHT_DUMP``, and a watchdog fire dumps automatically.
        """
        if self.flight_recorder is None and not merged:
            raise ACCLError(
                "flight recorder is off (ACCL_FLIGHT=0) or the driver "
                "is not initialized")
        doc = (_flight.dump_all() if merged
               else self.flight_recorder.dump())
        if path:
            import json as _json

            with open(path, "w") as f:
                _json.dump(doc, f, indent=1)
        return doc

    def dump_communicator(self, comm_id: int = GLOBAL_COMM) -> str:
        return self.communicator(comm_id).dump()

    def dump_rx_buffers(self) -> str:
        dump = getattr(self._device, "dump_rx_buffers", None)
        return dump() if dump else "<backend has no rx buffer table>"

    def deinit(self) -> None:
        """Tear down the backend.  Async requests still in flight are
        named (flight-recorder seq/state included) through the
        structured logger first — silently dropping them hid both lost
        completions and the leaked-request bug class accl_lint flags."""
        pending = [r for ref in self._async_pending
                   if (r := ref()) is not None and not r.done]
        if pending:
            rank = (self._communicators[GLOBAL_COMM].local_rank
                    if self._communicators else None)
            log = get_logger("accl_tpu.driver", rank=rank)
            log.warning(
                "deinit with %d async request(s) still pending — their "
                "completions (and any engine errors) are dropped:",
                len(pending))
            for r in pending:
                info = r.flight_info() or (
                    f" (id {r.id}, status={r.status.name})")
                log.warning("  pending: %s%s", r.description or "call",
                            info)
        self._async_pending.clear()
        self._device.close()

    def __enter__(self) -> "ACCL":
        return self

    def __exit__(self, *exc) -> None:
        self.deinit()
