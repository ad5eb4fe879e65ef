"""ABI constants for the ACCL-TPU framework.

These mirror the reference ACCL host/device ABI so that call descriptors,
error codes and flag algebra stay bit-compatible with the reference driver
(reference: driver/xrt/include/accl/constants.hpp:179-405 and
kernels/cclo/fw/sw_apps/ccl_offload_control/src/ccl_offload_control.h:25-60).
The *implementation* behind these codes is brand new and TPU-native: the
collective engine is a portable C++ library plus a JAX/XLA/Pallas backend,
not a translation of the reference firmware.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass


class Operation(enum.IntEnum):
    """Collective scenario codes carried in word 0 of a call descriptor.

    Values match the reference `operation` enum
    (driver/xrt/include/accl/constants.hpp:191-210).
    """

    config = 0
    copy = 1
    combine = 2
    send = 3
    recv = 4
    bcast = 5
    scatter = 6
    gather = 7
    reduce = 8
    allgather = 9
    allreduce = 10
    reduce_scatter = 11
    barrier = 12
    alltoall = 13
    #: all-to-all with per-peer row counts held on the device (not in the
    #: reference's enum: ACCL has no alltoallv)
    alltoallv = 14
    nop = 255


#: scenarios that form cross-rank gangs in the engines (one instance ==
#: one gang id in the trace); p2p and local ops are single-rank.  Shared
#: by the driver's observability gate (accl.py), the flight-recorder
#: analyzer and the collective sanitizer (accl_tpu/analysis).
GANG_OPERATIONS = frozenset((
    Operation.bcast, Operation.scatter, Operation.gather,
    Operation.allgather, Operation.reduce, Operation.allreduce,
    Operation.reduce_scatter, Operation.alltoall, Operation.alltoallv,
    Operation.barrier,
))


class CfgFunc(enum.IntEnum):
    """Sub-functions of Operation.config
    (reference: constants.hpp:179-185)."""

    reset_periph = 0
    enable_pkt = 1
    set_timeout = 2
    set_max_eager_msg_size = 3
    set_max_rendezvous_msg_size = 4


class ReduceFunction(enum.IntEnum):
    """On-path reduction operator (reference: constants.hpp:216-219)."""

    SUM = 0
    MAX = 1


class DataType(enum.IntEnum):
    """Wire/arithmetic datatypes (reference: constants.hpp:254-262)."""

    none = 0
    int8 = 1
    float16 = 2
    float32 = 3
    float64 = 4
    int32 = 5
    int64 = 6
    # TPU extension: the MXU's native 16-bit float (not in the reference's
    # dtype set, constants.hpp:254-262)
    bfloat16 = 7


#: Width in bits of each DataType (reference: constants.hpp:268-272).
DATA_TYPE_SIZE = {
    DataType.none: 0,
    DataType.int8: 8,
    DataType.float16: 16,
    DataType.float32: 32,
    DataType.float64: 64,
    DataType.int32: 32,
    DataType.int64: 64,
    DataType.bfloat16: 16,
}


class StreamFlags(enum.IntFlag):
    """Streamed-operand markers (reference: constants.hpp:278-282)."""

    NO_STREAM = 0
    OP0_STREAM = 1
    RES_STREAM = 2


class HostFlags(enum.IntFlag):
    """Host-resident-buffer markers (reference: constants.hpp:302-307)."""

    NO_HOST = 0
    OP0_HOST = 1
    OP1_HOST = 2
    RES_HOST = 4


class CompressionFlags(enum.IntFlag):
    """Per-operand / on-the-wire compression markers
    (reference: constants.hpp:327-333)."""

    NO_COMPRESSION = 0
    OP0_COMPRESSED = 1
    OP1_COMPRESSED = 2
    RES_COMPRESSED = 4
    ETH_COMPRESSED = 8


class ErrorCode(enum.IntFlag):
    """26-bit sticky error codes aggregated across the engine
    (reference: constants.hpp:355-387).

    Codes that named FPGA DMA engines in the reference keep their bit
    positions but describe the equivalent stage of the TPU-native engine
    (local memory movers, transport, segmenter, arithmetic lanes).
    """

    COLLECTIVE_OP_SUCCESS = 0
    DMA_MISMATCH_ERROR = 1 << 0
    DMA_INTERNAL_ERROR = 1 << 1
    DMA_DECODE_ERROR = 1 << 2
    DMA_SLAVE_ERROR = 1 << 3
    DMA_NOT_OKAY_ERROR = 1 << 4
    DMA_NOT_END_OF_PACKET_ERROR = 1 << 5
    DMA_NOT_EXPECTED_BTT_ERROR = 1 << 6
    DMA_TIMEOUT_ERROR = 1 << 7
    CONFIG_SWITCH_ERROR = 1 << 8
    DEQUEUE_BUFFER_TIMEOUT_ERROR = 1 << 9
    DEQUEUE_BUFFER_SPARE_BUFFER_STATUS_ERROR = 1 << 10
    RECEIVE_TIMEOUT_ERROR = 1 << 11
    DEQUEUE_BUFFER_SPARE_BUFFER_DMATAG_MISMATCH = 1 << 12
    DEQUEUE_BUFFER_SPARE_BUFFER_INDEX_ERROR = 1 << 13
    COLLECTIVE_NOT_IMPLEMENTED = 1 << 14
    RECEIVE_OFFCHIP_SPARE_BUFF_ID_NOT_VALID = 1 << 15
    EAGER_THRESHOLD_INVALID = 1 << 16
    RENDEZVOUS_THRESHOLD_INVALID = 1 << 17
    DMA_SIZE_ERROR = 1 << 18
    ARITH_ERROR = 1 << 19
    PACK_TIMEOUT_STS_ERROR = 1 << 20
    PACK_SEQ_NUMBER_ERROR = 1 << 21
    COMPRESSION_ERROR = 1 << 22
    KRNL_TIMEOUT_STS_ERROR = 1 << 23
    KRNL_STS_COUNT_ERROR = 1 << 24
    SEGMENTER_EXPECTED_BTT_ERROR = 1 << 25
    DMA_TAG_MISMATCH_ERROR = 1 << 26
    # fault-tolerance extension (no reference analog; mirrored in
    # native/src/common.hpp): the communicator this call ran on was
    # aborted — every pending call on all live ranks finalizes fast
    # with this bit, epoch-fenced against stragglers
    COMM_ABORTED = 1 << 27
    # the abort was triggered by a peer declared dead (watchdog
    # ACCL_WATCHDOG_ACTION=abort or a liveness probe), not by an
    # application-initiated ACCL.abort()
    RANK_FAILED = 1 << 28


#: Bits occupied by engine error codes (bit 0 .. bit 28 inclusive;
#: 27/28 are the fault-tolerance extension).
ERROR_CODE_BITS = 29

#: Internal (non-user-visible) signal used by the engine to re-queue a call
#: whose rendezvous peer has not arrived yet; mirrors the firmware's
#: NOT_READY_ERROR retry path (reference: ccl_offload_control.c:2460-2479).
NOT_READY_ERROR = 1 << 31

#: Driver-internal retcode stamped on a flight record when the collective
#: sanitizer (analysis/sanitizer.py, ACCL_SANITIZE=1) aborts the call
#: BEFORE dispatch: the record must leave the watchdog's in-flight scan
#: (the call will never complete) without claiming engine success.
SANITIZER_ABORT_ERROR = 1 << 30


class TuningKey(enum.IntEnum):
    """Runtime tuning-register keys (reference exchange-memory flat-tree
    thresholds, ccl_offload_control.h:86-90, plus the TPU-backend ring
    crossover).  The ONE authoritative name/value table: the driver
    (`ACCL.set_tuning`), the native engine twin (engine.hpp TuningKey)
    and the TPU backend twin all validate against it, so an unknown key
    raises an ACCLError naming the key and this set instead of silently
    writing nothing (the clear-error contract, r16)."""

    BCAST_FLAT_TREE_MAX_RANKS = 0
    REDUCE_FLAT_TREE_MAX_RANKS = 1
    GATHER_FLAT_TREE_MAX_FANIN = 2
    EGRESS_PIPELINE_DEPTH = 3
    GATHER_FLAT_TREE_MAX_COUNT = 4
    REDUCE_FLAT_TREE_MAX_COUNT = 5
    #: TPU-backend extension: byte threshold above which allreduce /
    #: allgather / reduce_scatter ride the Pallas ring kernels instead
    #: of the XLA HLO collective (backends/tpu.py ring_threshold_bytes,
    #: env default ACCL_RING_THRESHOLD).  The native emulator engine
    #: has no ring/flat crossover register and REJECTS this key.
    RING_THRESHOLD_BYTES = 6


#: key -> name for every tuning register any backend knows; the known
#: set quoted by the clear-error message of `set_tuning` rejections.
TUNING_KEY_NAMES = {int(k): k.name for k in TuningKey}

#: the subset the native emulator engine implements (engine.hpp
#: TuningKey 0..5; RING_THRESHOLD_BYTES is TPU-only)
EMU_TUNING_KEYS = frozenset(
    int(k) for k in TuningKey if k != TuningKey.RING_THRESHOLD_BYTES)

#: the subset the TPU backend implements (flat-tree registers are
#: stored for schedule hints/observability; RING_THRESHOLD_BYTES is
#: live — it reshapes `TpuEngine._gang_plan` signatures)
TPU_TUNING_KEYS = frozenset(int(k) for k in TuningKey)


def unknown_tuning_key_error(key: int, known: "frozenset[int]",
                             backend: str) -> "ACCLError":
    """The shared rejection message: names the offending key and the
    backend's known register set (constants.TuningKey names)."""
    names = ", ".join(f"{k}={TUNING_KEY_NAMES[k]}" for k in sorted(known))
    label = (f"{key} ({TUNING_KEY_NAMES[key]})"
             if key in TUNING_KEY_NAMES else repr(key))
    return ACCLError(
        f"set_tuning: unknown tuning key {label} for the {backend} "
        f"backend — known keys: {names}")


class OperationStatus(enum.IntEnum):
    """Lifecycle of an async request (reference: constants.hpp:226-230)."""

    QUEUED = 0
    EXECUTING = 1
    COMPLETED = 2


class MsgType(enum.IntEnum):
    """Wire message types (reference: kernels/cclo/hls/eth_intf/eth_intf.h:42-45)."""

    EGR_MSG = 0
    RNDZVS_MSG = 1
    RNDZVS_INIT = 2
    RNDZVS_WR_DONE = 3


class NetworkProtocol(enum.IntEnum):
    """Transport family of a backend.  The reference builds one of
    TCP/UDP/RDMA protocol-offload engines into the bitstream
    (constants.hpp:334-338); the TPU build replaces them with the ICI
    mesh (`ICI`) and keeps a socket transport (`SOCKET`) for the CPU
    emulator rung of the test ladder."""

    TCP = 0
    UDP = 1
    RDMA = 2
    SOCKET = 3
    ICI = 4


#: Any-source / any-tag wildcard, and the default tag value.
#: (reference: driver/xrt/include/accl/constants.hpp TAG_ANY = 0xFFFFFFFF)
TAG_ANY = 0xFFFFFFFF

#: Exchange-memory-equivalent defaults (reference: accl.hpp:103-105 and
#: ccl_offload_control.c:27-28).
DEFAULT_EAGER_RX_BUFS = 16
DEFAULT_EAGER_RX_BUF_SIZE = 1024
DEFAULT_MAX_EAGER_SIZE = 32 * 1024
DEFAULT_MAX_RENDEZVOUS_SIZE = 32 * 1024

#: Segmentation ceiling of a single transport packet and of one DMA command
#: (reference: ccl_offload_control.h:51-54).
MAX_PACKETSIZE = 4096
DMA_MAX_BTT = ((1 << 23) - 1) // 64 * 64

#: Width of the streaming datapath the reference moves per cycle; kept as a
#: segment-alignment quantum in the emulator (ccl_offload_control.h:34).
DATAPATH_WIDTH_BYTES = 64

#: Number of rendezvous scratch buffers used by tree reduce
#: (reference: accl.cpp:1190-1212, SPARE1-3).
N_SPARE_BUFFERS = 3


@dataclass
class CCLOCall:
    """The 15-word call descriptor marshalled per collective.

    Field-for-field equivalent of the reference host→device ABI
    (reference: kernels/plugins/hostctrl/hostctrl.cpp:19-63 and
    ccl_offload_control.c:2321-2356): scenario, count, comm, root_src_dst,
    function, msg_tag, arithcfg, compression_flags, stream+host flags,
    and three 64-bit operand addresses (low/high word pairs).
    """

    scenario: Operation = Operation.nop
    count: int = 0
    comm: int = 0  # communicator id
    root_src_dst: int = 0
    function: int = 0  # ReduceFunction or CfgFunc
    tag: int = TAG_ANY
    arithcfg: int = 0  # arithmetic-config table id
    compression_flags: CompressionFlags = CompressionFlags.NO_COMPRESSION
    stream_flags: StreamFlags = StreamFlags.NO_STREAM
    host_flags: HostFlags = HostFlags.NO_HOST
    addr_0: int = 0
    addr_1: int = 0
    addr_2: int = 0
    #: r18 fused-lane hint — NOT part of the 15-word wire ABI (the
    #: reference has no such field; fusion is a backend scheduling
    #: decision).  Riding on the call object keeps it visible to plan
    #: capture/replay and the gang scheduler without widening to_words.
    fused: bool = False

    @staticmethod
    def addr_3_words(addr: int) -> dict:
        """The root and function words that carry a fourth address,
        alltoallv's count result (an all-to-all has no root and no
        function): low half, high half."""
        return {"root_src_dst": addr & 0xFFFFFFFF, "function": addr >> 32}

    @property
    def addr_3(self) -> int:
        """The fourth address of :meth:`addr_3_words`."""
        return self.root_src_dst | (self.function << 32)

    def to_words(self) -> list[int]:
        """Serialize to the 15-word stream format pushed to the engine."""
        return [
            int(self.scenario),
            int(self.count),
            int(self.comm),
            int(self.root_src_dst),
            int(self.function),
            int(self.tag),
            int(self.arithcfg),
            int(self.compression_flags),
            int(self.stream_flags) | (int(self.host_flags) << 8),
            self.addr_0 & 0xFFFFFFFF,
            (self.addr_0 >> 32) & 0xFFFFFFFF,
            self.addr_1 & 0xFFFFFFFF,
            (self.addr_1 >> 32) & 0xFFFFFFFF,
            self.addr_2 & 0xFFFFFFFF,
            (self.addr_2 >> 32) & 0xFFFFFFFF,
        ]


def error_code_to_str(code: int) -> str:
    """Human-readable decode of a sticky error bitfield
    (reference: constants.hpp:393-405 error_code_to_string)."""
    if code == 0:
        return "COLLECTIVE_OP_SUCCESS"
    names = [e.name for e in ErrorCode if e.value and code & e.value]
    if code & NOT_READY_ERROR:
        names.append("NOT_READY_ERROR")
    if code & SANITIZER_ABORT_ERROR:
        names.append("SANITIZER_ABORT_ERROR")
    return " | ".join(names) if names else f"UNKNOWN_ERROR({code:#x})"


class ACCLError(RuntimeError):
    """Raised by the driver when a collective returns a non-zero retcode
    (reference: accl.cpp:1226-1250 check_return_value)."""

    def __init__(self, message: str, code: int = 0):
        super().__init__(message)
        self.code = code


def env_int(name: str, default: int, minimum: int = None) -> int:
    """Integer env knob with the decodable-error contract: a malformed
    value raises ACCLError NAMING the knob instead of a bare ValueError
    from int() deep inside bring-up.  Scientific notation is accepted
    ("3e7") since operators write budgets that way."""
    import os as _os

    raw = _os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = int(float(raw))
    except ValueError as e:
        raise ACCLError(f"{name}={raw!r} is not a number") from e
    if minimum is not None and val < minimum:
        raise ACCLError(f"{name}={raw!r} must be >= {minimum}")
    return val


def env_float(name: str, default: float, minimum: float = None) -> float:
    """Float twin of :func:`env_int` (same clear-error contract)."""
    import os as _os

    raw = _os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = float(raw)
    except ValueError as e:
        raise ACCLError(f"{name}={raw!r} is not a number") from e
    if minimum is not None and val < minimum:
        raise ACCLError(f"{name}={raw!r} must be >= {minimum}")
    return val
