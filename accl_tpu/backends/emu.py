"""Emulator backend: the native C++ collective engine over ctypes.

Reference analog: `SimDevice`, which forwards call descriptors and buffer
sync to the `cclo_emu` emulator process over ZMQ (driver/xrt/src/
simdevice.cpp:38-64, test/model/emulator/cclo_emu.cpp).  Here the
emulator is an in-process native library (`native/libacclemu.so`): a
per-rank engine thread runs the collective algorithms against a CPU
dataplane and an inproc or TCP socket transport.

`EmuWorld` is the test harness equivalent of the reference's
one-emulator-per-MPI-rank bring-up (test/host/xrt/src/utility.cpp:26-70):
it creates N ranks in one process and runs per-rank driver code on a
thread pool, so the MPI-style test corpus ports directly.
"""
from __future__ import annotations

import ctypes
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional, Sequence

import numpy as np

from ..accl import ACCL, default_timeout
from ..arithconfig import ArithConfig
from ..buffer import BaseBuffer, EmuBuffer, EmuBufferP2P
from ..communicator import Communicator, Rank
from ..constants import ACCLError, CCLOCall, ErrorCode
from ..observability import flight as _flight
from ..observability import health as _health
from ..observability.record import LOCAL_DISPATCH, S_DISPATCHED, mark
from ..request import Request
from ..utils.logging import get_logger
from .base import CCLODevice

# Sanitizer lane selection (docs/static_analysis.md "Native sanitizer
# lanes"): ACCL_SANITIZER=asan|ubsan|tsan loads the instrumented twin
# built by `ACCL_SANITIZER=<lane> make -C native`; ACCL_NATIVE_LIB
# overrides the path outright (a prebuilt artifact in CI).  NB the
# asan/tsan lanes need their runtime preloaded into the (uninstrumented)
# python — see the docs for the LD_PRELOAD line.
_SANITIZER = os.environ.get("ACCL_SANITIZER", "").strip()
_LIB_NAME = f"libacclemu_{_SANITIZER}.so" if _SANITIZER else "libacclemu.so"
_LIB_PATH = os.environ.get("ACCL_NATIVE_LIB") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    _LIB_NAME,
)

_lib = None


def _build_lib_if_stale() -> None:
    """Build (or rebuild) the native engine when the .so is missing or
    older than any of its sources, so a fresh checkout and an edited
    engine both work without a manual `make -C native` step."""
    import glob
    import subprocess

    if os.environ.get("ACCL_NATIVE_LIB"):
        return  # explicit artifact: never rebuild over it
    native_dir = os.path.dirname(_LIB_PATH)
    sources = glob.glob(os.path.join(native_dir, "src", "*.cpp")) + glob.glob(
        os.path.join(native_dir, "src", "*.hpp")) + [
        os.path.join(native_dir, "Makefile")
    ]
    if os.path.exists(_LIB_PATH):
        lib_mtime = os.path.getmtime(_LIB_PATH)
        if all(os.path.getmtime(s) <= lib_mtime for s in sources):
            return
    # serialize concurrent builders (e.g. parallel CI jobs sharing one
    # checkout) so two `make` runs can't corrupt the same .so
    lock_path = os.path.join(native_dir, ".build.lock")
    with open(lock_path, "w") as lock:
        try:
            import fcntl

            fcntl.flock(lock, fcntl.LOCK_EX)
        except ImportError:  # pragma: no cover (non-POSIX)
            pass
        # the build must not inherit a sanitizer runtime: under the ASan
        # lane LD_PRELOAD leaks into make/g++ and LeakSanitizer fails
        # the COMPILER with its own (irrelevant) leaks
        env = dict(os.environ)
        env.pop("LD_PRELOAD", None)
        env["ASAN_OPTIONS"] = "detect_leaks=0"
        try:
            proc = subprocess.run(["make", "-C", native_dir],
                                  capture_output=True, text=True, env=env)
        except FileNotFoundError as e:
            raise ACCLError(
                f"native engine not built and `make` unavailable: {e} "
                f"(build {_LIB_PATH} manually)") from e
        if proc.returncode != 0:
            raise ACCLError(
                f"native engine build failed:\n{proc.stdout}\n{proc.stderr}")


def _load_lib() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    _build_lib_if_stale()
    if not os.path.exists(_LIB_PATH):
        raise ACCLError(
            f"native engine not built: {_LIB_PATH} missing (run `make -C native`)"
        )
    lib = ctypes.CDLL(_LIB_PATH)
    u64, u32, i32 = ctypes.c_uint64, ctypes.c_uint32, ctypes.c_int
    p = ctypes.c_void_p
    lib.accl_world_create.restype = p
    lib.accl_world_create.argtypes = [i32, u64]
    lib.accl_world_create_tcp.restype = p
    lib.accl_world_create_tcp.argtypes = [i32, i32, i32, u64]
    lib.accl_world_create_dgram.restype = p
    lib.accl_world_create_dgram.argtypes = [i32, u64, u32, u32]
    lib.accl_dgram_fault.argtypes = [p, u32]
    lib.accl_world_create_rdma.restype = p
    lib.accl_world_create_rdma.argtypes = [i32, u64]
    lib.accl_dump_qps.argtypes = [p, i32, ctypes.c_char_p, i32]
    lib.accl_world_destroy.argtypes = [p]
    lib.accl_world_shutdown.argtypes = [p]
    lib.accl_cfg_rx.argtypes = [p, i32, i32, u64]
    lib.accl_set_comm.argtypes = [p, i32, ctypes.POINTER(u32), i32]
    lib.accl_set_arithcfg.argtypes = [p, i32, ctypes.POINTER(u32), i32]
    lib.accl_set_tuning.argtypes = [p, i32, u32, u32]
    lib.accl_alloc.restype = u64
    lib.accl_alloc.argtypes = [p, i32, u64, u64]
    lib.accl_alloc_host.restype = u64
    lib.accl_alloc_host.argtypes = [p, i32, u64, u64]
    lib.accl_alloc_p2p.restype = u64
    lib.accl_alloc_p2p.argtypes = [p, i32, u64, u64]
    lib.accl_free_p2p.argtypes = [p, i32, u64]
    lib.accl_mem_ptr.restype = ctypes.c_void_p
    lib.accl_mem_ptr.argtypes = [p, i32, u64, u64]
    lib.accl_tx_stats.argtypes = [p, i32, ctypes.POINTER(u64),
                                  ctypes.POINTER(u64)]
    lib.accl_open_port.restype = i32
    lib.accl_open_port.argtypes = [p, i32]
    lib.accl_open_con.restype = i32
    lib.accl_open_con.argtypes = [p, i32, i32]
    lib.accl_close_con.restype = i32
    lib.accl_close_con.argtypes = [p, i32, i32]
    lib.accl_free.argtypes = [p, i32, u64]
    lib.accl_read_mem.argtypes = [p, i32, u64, ctypes.c_void_p, u64]
    lib.accl_write_mem.argtypes = [p, i32, u64, ctypes.c_void_p, u64]
    lib.accl_start_call.restype = u64
    lib.accl_start_call.argtypes = [p, i32, ctypes.POINTER(u32)]
    lib.accl_poll_call.argtypes = [p, i32, u64, ctypes.POINTER(u32),
                                   ctypes.POINTER(ctypes.c_double)]
    lib.accl_wait_call.argtypes = [p, i32, u64, i32, ctypes.POINTER(u32),
                                   ctypes.POINTER(ctypes.c_double)]
    lib.accl_push_krnl.argtypes = [p, i32, ctypes.c_void_p, u64]
    lib.accl_pop_stream.argtypes = [p, i32, u32, ctypes.c_void_p, u64,
                                    ctypes.POINTER(u64), i32]
    lib.accl_dump_rx.argtypes = [p, i32, ctypes.c_char_p, i32]
    lib.accl_inject_fault.argtypes = [p, i32, u32]
    # resilience control plane (retransmission / abort / shrink / chaos)
    lib.accl_set_resilience.restype = i32
    lib.accl_set_resilience.argtypes = [p, i32, u32, u32]
    lib.accl_abort.restype = i32
    lib.accl_abort.argtypes = [p, i32, i32, u32]
    lib.accl_reset_errors.restype = i32
    lib.accl_reset_errors.argtypes = [p, i32]
    lib.accl_set_chaos.restype = i32
    lib.accl_set_chaos.argtypes = [p, i32, u64, u32, u32, u32, u32, u32, u32]
    lib.accl_chaos_kill.restype = i32
    lib.accl_chaos_kill.argtypes = [p, i32]
    lib.accl_probe_liveness.restype = i32
    lib.accl_probe_liveness.argtypes = [p, i32, i32, u32, ctypes.POINTER(u64)]
    lib.accl_resilience_stats.argtypes = [p, i32, ctypes.POINTER(u64),
                                          ctypes.POINTER(u64),
                                          ctypes.POINTER(u64),
                                          ctypes.POINTER(u64)]
    # elastic membership (r11): live rank join
    lib.accl_world_add_rank.restype = i32
    lib.accl_world_add_rank.argtypes = [p]
    lib.accl_join_sync.restype = i32
    lib.accl_join_sync.argtypes = [p, i32, u32, i32]
    lib.accl_comm_count.restype = i32
    lib.accl_comm_count.argtypes = [p, i32]
    lib.accl_comm_epoch.restype = u32
    lib.accl_comm_epoch.argtypes = [p, i32, i32]
    lib.accl_join_stats.argtypes = [p, i32, ctypes.POINTER(u64),
                                    ctypes.POINTER(u64)]
    # persistent collective plans (r12): pre-marshaled descriptor ring
    i64 = ctypes.c_longlong
    lib.accl_plan_create.restype = i32
    lib.accl_plan_create.argtypes = [p, i32, ctypes.POINTER(u32), i32]
    lib.accl_plan_replay.restype = i64
    lib.accl_plan_replay.argtypes = [p, i32, i32]
    lib.accl_plan_poll.restype = i32
    lib.accl_plan_poll.argtypes = [p, i32, i64, ctypes.POINTER(u32),
                                   ctypes.POINTER(ctypes.c_double)]
    lib.accl_plan_wait.restype = i32
    lib.accl_plan_wait.argtypes = [p, i32, i64, i32, ctypes.POINTER(u32),
                                   ctypes.POINTER(ctypes.c_double)]
    lib.accl_plan_invalidate.restype = i32
    lib.accl_plan_invalidate.argtypes = [p, i32, i32]
    lib.accl_plan_count.restype = i32
    lib.accl_plan_count.argtypes = [p, i32]
    lib.accl_plan_release.restype = i32
    lib.accl_plan_release.argtypes = [p, i32, i32]
    # wire-protocol correctness surface (r13): raw-frame ingest, frame
    # counters, egress frame tap (fuzz seed-corpus capture)
    lib.accl_engine_ingest_bytes.restype = i32
    lib.accl_engine_ingest_bytes.argtypes = [p, i32, ctypes.c_char_p, u64]
    lib.accl_frame_stats.argtypes = [p, i32, ctypes.POINTER(u64),
                                     ctypes.POINTER(u64)]
    lib.accl_frame_tap.restype = i32
    lib.accl_frame_tap.argtypes = [p, i32, i32]
    lib.accl_frame_tap_count.restype = i32
    lib.accl_frame_tap_count.argtypes = [p, i32]
    lib.accl_frame_tap_read.restype = i32
    lib.accl_frame_tap_read.argtypes = [p, i32, i32, ctypes.c_void_p, i32]
    lib.accl_frame_tap_drain.restype = i32
    lib.accl_frame_tap_drain.argtypes = [p, i32, ctypes.c_void_p, i32]
    # engine telemetry snapshot (r14): versioned flat-array stats plane
    lib.accl_engine_stats_version.restype = i32
    lib.accl_engine_stats_version.argtypes = []
    lib.accl_engine_stats.restype = i32
    lib.accl_engine_stats.argtypes = [p, i32, ctypes.POINTER(u64), i32]
    # per-link wire telemetry (r15): flat (comm, peer) counter rows
    lib.accl_engine_link_stats_stride.restype = i32
    lib.accl_engine_link_stats_stride.argtypes = []
    lib.accl_engine_link_stats.restype = i32
    lib.accl_engine_link_stats.argtypes = [p, i32, ctypes.POINTER(u64),
                                           i32]
    _lib = lib
    return lib


def _words(vals: Sequence[int]):
    arr = (ctypes.c_uint32 * len(vals))(*[v & 0xFFFFFFFF for v in vals])
    return arr


def _join_waiters(devices, timeout_s: float = 10.0) -> int:
    """Join every tracked waiter thread of `devices` (bounded); returns
    how many were STILL alive afterwards.  Called between world
    shutdown (which makes their FFI waits return promptly) and world
    destroy (which frees the memory they were polling)."""
    import time

    deadline = time.monotonic() + timeout_s
    stuck = 0
    for d in devices:
        for t in list(getattr(d, "_waiters", ())):
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                stuck += 1
    return stuck


# ---------------------------------------------------------------------------
# interpreter-exit safety net: close every still-open world BEFORE the
# interpreter (and then the C runtime) starts tearing the process down.
# A world leaked by test code keeps native engine threads running into
# __cxa_finalize — where the library's static destructors run out from
# under them (the r13 suite-exit segfault class).  atexit handlers run
# LIFO, so registering at import time (before any ThreadPoolExecutor
# exists) means this fires AFTER user code but BEFORE
# concurrent.futures' own exit hook joins its workers.
# ---------------------------------------------------------------------------
import atexit  # noqa: E402 — grouped with its registry on purpose
import weakref  # noqa: E402

_live_worlds: "weakref.WeakSet" = weakref.WeakSet()


def _close_live_worlds() -> None:  # pragma: no cover — exit path
    for w in list(_live_worlds):
        try:
            w.close()
        except Exception:  # noqa: BLE001 — never let cleanup raise at exit
            pass


atexit.register(_close_live_worlds)


class EmuDevice(CCLODevice):
    """One rank's handle on the native engine."""

    def __init__(self, world_handle: ctypes.c_void_p, rank: int,
                 lib: ctypes.CDLL, call_timeout_s: float = 60.0):
        self._w = world_handle
        self._rank = rank
        self._lib = lib
        self._timeout_ms = int(call_timeout_s * 1000)
        # arm the NACK retransmission lane from the env policy
        # (ACCL_RETRY_MAX / ACCL_RETRY_BASE_US; worlds may override)
        from ..resilience.retry import RetryPolicy

        pol = RetryPolicy.from_env()
        self.set_resilience(pol.max_retries, pol.base_us)
        #: True while every rank of this world lives in this process
        #: (EmuWorld); EmuRankTcp clears it — its peers are other
        #: processes (or sibling worlds) the in-process sanitizer
        #: exchange can never pair with
        self.shares_process_world = True
        #: last frame-counter values already published to the metrics
        #: registry (frame_stats publishes monotonic deltas)
        self._frames_published: dict = {}
        #: live waiter threads (one per in-flight engine call).  World
        #: close() joins these AFTER accl_world_shutdown made their FFI
        #: waits return and BEFORE accl_world_destroy frees the
        #: engines — the ordering that fixes the suite-exit segfault
        #: (a waiter scheduled late dereferencing a nulled/freed world).
        self._waiters: set = set()
        #: serializes call submission against close(): start() snapshots
        #: the world handle, submits, and registers its waiter under
        #: this lock; close() nulls the handle under the same lock, so
        #: a submission either completes registration (and is joined
        #: before destroy) or observes the nulled handle — there is no
        #: window where a stale handle outlives the world
        self._lifecycle = threading.Lock()

    def sanitizer_domain(self):
        """The native world handle identifies the in-process gang for
        the sanitizer's cross-rank fingerprint exchange (one EmuWorld ==
        one engine world == one domain)."""
        if self.shares_process_world and self._w:
            return ("emu", int(self._w))
        return None

    # -- call path ----------------------------------------------------
    def start(self, call: CCLOCall, request: Request) -> None:
        # the native engine owns the session send/recv + rendezvous
        # retry loop below this point, so the record's launch window is
        # the descriptor-post → engine-completion interval (its interior
        # breakdown is the engine's cycle-count duration, stamped on the
        # request as duration_ns)
        rec = request.record
        if rec is not None:
            mark((rec,), LOCAL_DISPATCH, state=S_DISPATCHED, lane="emu")
        # snapshot + submit + waiter registration happen atomically
        # against close() (the _lifecycle contract above): after this
        # block either the call is tracked (close joins its waiter
        # before destroying) or the handle was already None and the
        # request fails fast.  The C side additionally null-guards, so
        # even an untracked straggler gets a clean error, never a
        # dereference.
        with self._lifecycle:
            world = self._w
            if world is None:
                request.complete(int(ErrorCode.COMM_ABORTED
                                     | ErrorCode.RANK_FAILED), 0.0)
                return
            call_id = self._lib.accl_start_call(world, self._rank,
                                                _words(call.to_words()))
            t = threading.Thread(target=lambda: waiter(), daemon=True)
            self._waiters.add(t)

        def waiter():
            try:
                ret = ctypes.c_uint32(0)
                dur = ctypes.c_double(0.0)
                ok = self._lib.accl_wait_call(world, self._rank, call_id,
                                              self._timeout_ms,
                                              ctypes.byref(ret),
                                              ctypes.byref(dur))
                if rec is not None:
                    mark((rec,), ("t_launched",))
                if ok:
                    request.complete(ret.value, dur.value)
                else:
                    from ..constants import ErrorCode

                    get_logger("accl_tpu.emu", rank=self._rank).warning(
                        "engine wait timed out after %d ms: %s%s",
                        self._timeout_ms, request.description,
                        request.flight_info())
                    request.complete(int(ErrorCode.DMA_TIMEOUT_ERROR), 0.0)
            finally:
                self._waiters.discard(threading.current_thread())

        t.start()

    # -- device memory ------------------------------------------------
    def alloc_mem(self, nbytes: int, alignment: int = 64) -> int:
        addr = self._lib.accl_alloc(self._w, self._rank, nbytes, alignment)
        if addr == 0:
            raise ACCLError("emulator device memory exhausted")
        return addr

    def free_mem(self, address: int) -> None:
        self._lib.accl_free(self._w, self._rank, address)

    def free_mem_p2p(self, address: int) -> None:
        self._lib.accl_free_p2p(self._w, self._rank, address)

    def read_mem(self, address: int, nbytes: int) -> bytes:
        buf = ctypes.create_string_buffer(nbytes)
        rc = self._lib.accl_read_mem(self._w, self._rank, address, buf, nbytes)
        if rc != 0:
            raise ACCLError(f"read_mem({address:#x}, {nbytes}) out of range")
        return buf.raw

    def write_mem(self, address: int, data: bytes) -> None:
        rc = self._lib.accl_write_mem(self._w, self._rank, address, data,
                                      len(data))
        if rc != 0:
            raise ACCLError(f"write_mem({address:#x}, {len(data)}) out of range")

    # -- buffers ------------------------------------------------------
    def create_buffer(self, length: int, dtype: np.dtype,
                      host_only: bool = False) -> BaseBuffer:
        host = np.zeros(length, dtype=dtype)
        if host_only:
            addr = self._lib.accl_alloc_host(self._w, self._rank,
                                             max(host.nbytes, 64), 64)
            if addr == 0:
                raise ACCLError("emulator host-buffer region exhausted")
            return EmuBuffer(host, self, addr, host_only=True)
        addr = self.alloc_mem(max(host.nbytes, 64))
        return EmuBuffer(host, self, addr)

    def create_buffer_p2p(self, length: int, dtype: np.dtype) -> BaseBuffer:
        """Peer-addressable buffer (reference FPGABufferP2P): the host
        view is a direct MAPPING of engine devicemem (zero-copy, no
        sync), and the span is registered peer-writable — an in-process
        peer's rendezvous one-sided write lands in it by direct memcpy,
        bypassing the wire (native/src/engine.cpp rndzv_send fast
        path)."""
        nbytes = max(int(np.dtype(dtype).itemsize) * length, 64)
        addr = self._lib.accl_alloc_p2p(self._w, self._rank, nbytes, 64)
        if addr == 0:
            raise ACCLError("emulator device memory exhausted (p2p)")
        ptr = self._lib.accl_mem_ptr(self._w, self._rank, addr, nbytes)
        if not ptr:
            raise ACCLError("p2p mapping failed")
        raw = (ctypes.c_uint8 * nbytes).from_address(ptr)
        host = np.frombuffer(raw, dtype=dtype, count=length)
        return EmuBufferP2P(host, self, addr)

    # -- session lifecycle (reference open_port/open_con/close_con over
    # tcp_session_handler; accl.hpp:1069-1083).  TCP worlds really
    # connect/tear down; inproc/datagram transports succeed as no-ops. --
    def open_port(self) -> int:
        return int(self._lib.accl_open_port(self._w, self._rank))

    def open_con(self, comm_id: int) -> int:
        return int(self._lib.accl_open_con(self._w, self._rank, comm_id))

    def close_con(self, comm_id: int) -> int:
        return int(self._lib.accl_close_con(self._w, self._rank, comm_id))

    def tx_stats(self) -> tuple:
        """Egress (messages, payload_bytes) handed to the transport —
        the observable that proves the p2p path bypassed the wire."""
        msgs = ctypes.c_uint64(0)
        pay = ctypes.c_uint64(0)
        self._lib.accl_tx_stats(self._w, self._rank, ctypes.byref(msgs),
                                ctypes.byref(pay))
        return int(msgs.value), int(pay.value)

    # -- configuration ------------------------------------------------
    def setup_rx_buffers(self, n_bufs: int, buf_size: int) -> None:
        self._lib.accl_cfg_rx(self._w, self._rank, n_bufs, buf_size)

    def upload_communicator(self, comm: Communicator) -> int:
        w = comm.to_words()
        return self._lib.accl_set_comm(self._w, self._rank, _words(w), len(w))

    def upload_arithconfig(self, cfg: ArithConfig) -> int:
        w = cfg.to_words()
        return self._lib.accl_set_arithcfg(self._w, self._rank, _words(w),
                                           len(w))

    def set_tuning(self, key: int, value: int) -> None:
        """Write a flat-tree tuning register (reference:
        configure_tuning_parameters, accl.cpp:1214-1224; keys named in
        constants.TuningKey).  Unknown keys raise an ACCLError naming
        the key and the engine's known set — the engine rejects them
        instead of silently writing nothing (clear-error contract)."""
        from ..constants import EMU_TUNING_KEYS, unknown_tuning_key_error

        rc = self._lib.accl_set_tuning(self._w, self._rank, key, value)
        if rc == -2 or (rc != 0 and key not in EMU_TUNING_KEYS):
            raise unknown_tuning_key_error(key, EMU_TUNING_KEYS, "emu")
        if rc != 0:
            raise ACCLError(f"set_tuning({key}, {value}) failed (rc={rc})")

    # -- streams (PL-kernel equivalent) -------------------------------
    def push_krnl(self, data: np.ndarray) -> None:
        """Feed operand bytes into the engine's compute-kernel input
        stream (OP0_STREAM source; reference data_to_cclo port)."""
        b = np.ascontiguousarray(data).tobytes()
        self._lib.accl_push_krnl(self._w, self._rank, b, len(b))

    def pop_stream(self, strm: int, nbytes: int,
                   timeout_s: float = 10.0) -> Optional[bytes]:
        """Pull one message from a compute stream (data_from_cclo port)."""
        buf = ctypes.create_string_buffer(nbytes)
        got = ctypes.c_uint64(0)
        ok = self._lib.accl_pop_stream(self._w, self._rank, strm, buf, nbytes,
                                       ctypes.byref(got),
                                       int(timeout_s * 1000))
        return buf.raw[: got.value] if ok else None

    def dump_rx_buffers(self) -> str:
        out = ctypes.create_string_buffer(65536)
        self._lib.accl_dump_rx(self._w, self._rank, out, 65536)
        return out.value.decode()

    #: fault kinds for inject_fault (one-shot, next egress message)
    FAULT_DROP = 1
    FAULT_DUPLICATE = 2
    FAULT_CORRUPT_SEQ = 3
    FAULT_DELAY = 4

    def inject_fault(self, kind: int) -> None:
        """Arm a one-shot egress fault on this rank's engine — sugar
        over the seeded chaos funnel (forces its next draw): drop /
        duplicate / corrupt-seqn / delay, resolved in the same engine
        switch the probabilistic plan uses (SURVEY §5)."""
        rc = self._lib.accl_inject_fault(self._w, self._rank, kind)
        if rc != 0:
            raise ACCLError(f"inject_fault({kind}) failed for rank "
                            f"{self._rank}")

    # -- resilience (accl_tpu/resilience; docs/fault_tolerance.md) ----
    def set_resilience(self, retry_max: int, retry_base_us: int) -> None:
        """Configure the NACK retransmission lane (0 retries = off)."""
        self._lib.accl_set_resilience(self._w, self._rank,
                                      max(0, int(retry_max)),
                                      max(1, int(retry_base_us)))

    def abort_comm(self, comm_id: int, err_bits: int) -> bool:
        """Epoch-tagged abort of a communicator, propagated to every
        peer through the control plane; returns True (engine handled
        the fan-out and pending-call finalization)."""
        rc = self._lib.accl_abort(self._w, self._rank, comm_id,
                                  err_bits & 0xFFFFFFFF)
        if rc != 0:
            raise ACCLError(f"abort(comm {comm_id}) failed for rank "
                            f"{self._rank}")
        return True

    def reset_errors(self) -> None:
        """Seqn resync + transient-state drain after a classified fault
        (collective: every rank of a quiesced world calls it)."""
        self._lib.accl_reset_errors(self._w, self._rank)

    def set_chaos(self, seed: int, drop_ppm: int, dup_ppm: int,
                  delay_ppm: int, delay_us: int, corrupt_ppm: int,
                  slow_us: int) -> None:
        """Arm the seeded probabilistic chaos plan on this rank."""
        self._lib.accl_set_chaos(self._w, self._rank, seed, drop_ppm,
                                 dup_ppm, delay_ppm, delay_us, corrupt_ppm,
                                 slow_us)

    def kill(self) -> None:
        """Kill-rank chaos: this engine goes silent (egress dropped,
        ingress deaf) and aborts its local comms with RANK_FAILED."""
        self._lib.accl_chaos_kill(self._w, self._rank)

    def probe_liveness(self, comm_id: int, size: int,
                       window_s: float = 1.0) -> list:
        """Heartbeat-probe every peer of a communicator; returns a
        per-comm-local-rank alive list (local rank always True)."""
        bm = ctypes.c_uint64(0)
        rc = self._lib.accl_probe_liveness(
            self._w, self._rank, comm_id, int(window_s * 1e6),
            ctypes.byref(bm))
        if rc != 0:
            raise ACCLError(f"probe_liveness(comm {comm_id}) failed")
        return [bool(bm.value >> i & 1) for i in range(size)]

    def resilience_stats(self) -> dict:
        """Engine-side recovery counters: retransmitted segments, NACKs
        sent/received, epoch-fenced ingress drops."""
        vals = [ctypes.c_uint64(0) for _ in range(4)]
        self._lib.accl_resilience_stats(self._w, self._rank,
                                        *[ctypes.byref(v) for v in vals])
        keys = ("retrans_sent", "nacks_tx", "nacks_rx", "fenced_drops")
        return dict(zip(keys, (int(v.value) for v in vals)))

    def engine_stats(self) -> dict:
        """Full engine telemetry snapshot (r14): retransmit-store depth/
        evictions, NACK counters, rx-pool occupancy + high-water,
        egress/ingress queue depths, seek-miss rate inputs, plan table/
        token state, wire accept/reject, tx traffic, join counters —
        ONE FFI for the whole plane (the sampler's poll body).  Decoded
        through the versioned field schema so a newer engine's extra
        fields surface as ``unknown_field_<i>`` instead of vanishing."""
        from ..observability import telemetry as _telemetry

        if not self._w:
            raise ACCLError("engine_stats: world is closed")
        cap = max(64, len(_telemetry.ENGINE_STATS_FIELDS_V1))
        buf = (ctypes.c_uint64 * cap)()
        total = int(self._lib.accl_engine_stats(self._w, self._rank,
                                                buf, cap))
        if total < 0:
            raise ACCLError(f"engine_stats failed for rank {self._rank}")
        version = int(self._lib.accl_engine_stats_version())
        return _telemetry.decode_engine_stats(
            buf[:min(total, cap)], version=version, total_fields=total)

    def link_stats(self) -> list:
        """Per-(comm, peer) wire counters (r15): tx/rx messages+bytes,
        retransmits served, NACKs both directions, epoch-fenced drops,
        and seek count/blocked-wait per peer — ONE FFI for the whole
        link plane, decoded through the strict stride-checked schema
        (LINK_STATS_FIELDS_V2).  Returns a list of row dicts; peers are
        comm-local ranks (global ranks on comm 0)."""
        from ..observability import telemetry as _telemetry

        if not self._w:
            raise ACCLError("link_stats: world is closed")
        stride = int(self._lib.accl_engine_link_stats_stride())
        expect = len(_telemetry.LINK_STATS_FIELDS_V2)
        if stride != expect:
            # deterministic stride agreement BEFORE any slicing: the
            # decoder's whole-number-of-rows check alone would pass by
            # coincidence whenever rows * new_stride happens to divide
            # by the old one
            raise ACCLError(
                f"link_stats: engine row stride {stride} != this "
                f"build's schema ({expect} fields) — mixed-version "
                f"world; refusing to mis-slice")
        total = int(self._lib.accl_engine_link_stats(
            self._w, self._rank, None, 0))
        if total < 0:
            raise ACCLError(f"link_stats failed for rank {self._rank}")
        if total == 0:
            return []
        # headroom: rows minted between the size probe and the read
        cap = total + 16 * stride
        buf = (ctypes.c_uint64 * cap)()
        got = int(self._lib.accl_engine_link_stats(self._w, self._rank,
                                                   buf, cap))
        if got < 0:
            raise ACCLError(f"link_stats failed for rank {self._rank}")
        return _telemetry.decode_link_stats(buf[:min(got, cap)])

    # -- persistent collective plans (r12) ----------------------------
    def arm_plan(self, calls, expected=None, timeout_s: float = 30.0):
        """Pre-marshal a captured descriptor stream into the engine's
        plan storage: every 15-word descriptor is parsed ONCE here; a
        replay is a single FFI entry for the whole batch (no per-call
        Python marshaling, no per-call FFI).  Per-rank — the engine's
        own wire protocol pairs the gangs across ranks, exactly as it
        does for eager calls."""
        words: list = []
        for call in calls:
            words.extend(call.to_words())
        pid = int(self._lib.accl_plan_create(
            self._w, self._rank, _words(words), len(calls)))
        if pid < 0:
            raise ACCLError(
                "arm_plan: engine rejected the descriptor batch (a "
                "referenced communicator is aborted, or the batch is "
                "empty) — recover the world, then capture")
        return pid

    def plan_replay(self, plan_id: int, run_async: bool = False,
                    timeout_s: float = 60.0):
        """One replay of the armed batch.  Sync (default): blocks until
        every call completed and raises on a non-zero combined retcode.
        Async: returns the completion token for plan_wait."""
        token = int(self._lib.accl_plan_replay(self._w, self._rank,
                                               plan_id))
        if token == -2:
            raise ACCLError(
                "plan replay: plan invalidated by an abort/epoch "
                "fence/reset — re-capture on the recovered "
                "communicator",
                int(ErrorCode.COMM_ABORTED))
        if token < 0:
            raise ACCLError(f"plan replay: unknown plan id {plan_id}")
        if run_async:
            return token
        if not self.plan_wait(plan_id, token, timeout_s):
            raise ACCLError(
                f"plan replay timed out after {timeout_s:.0f}s")
        return None

    def plan_wait(self, plan_id: int, token: int,
                  timeout_s: float) -> bool:
        """Block until a replay token completes (False on timeout);
        raises the decoded engine error on a non-zero retcode."""
        from ..constants import error_code_to_str

        ret = ctypes.c_uint32(0)
        dur = ctypes.c_double(0.0)
        rc = int(self._lib.accl_plan_wait(
            self._w, self._rank, token, int(timeout_s * 1000),
            ctypes.byref(ret), ctypes.byref(dur)))
        if rc == 0:
            return False
        if rc < 0:
            raise ACCLError(f"plan replay: unknown token {token}")
        if ret.value != 0:
            raise ACCLError(
                f"plan replay failed: {error_code_to_str(ret.value)}",
                int(ret.value))
        return True

    def invalidate_plans(self, comm_id: int = -1) -> None:
        """Fence engine-side plans touching a comm (-1 = all) — the
        shrink/grow half of the eviction contract (abort and
        reset_errors fence inside the engine on their own)."""
        self._lib.accl_plan_invalidate(self._w, self._rank, comm_id)

    def plan_count(self) -> int:
        """Live (valid) engine-side plans — eviction introspection."""
        return int(self._lib.accl_plan_count(self._w, self._rank))

    def plan_release(self, plan_id: int) -> None:
        """Release a dead plan's engine-side storage.  Called from a
        GC finalizer, which may outlive the world — the null-handle
        guard keeps a post-teardown release a no-op instead of a
        use-after-free (EmuWorld.close nulls its devices' handles)."""
        if self._w:
            self._lib.accl_plan_release(self._w, self._rank, plan_id)

    # -- wire-protocol correctness surface (r13) ----------------------
    def ingest_bytes(self, frame: bytes) -> int:
        """Feed one raw wire frame (64-byte header + payload) through
        this engine's REAL ingress classification path, as if a peer's
        transport delivered it.  Returns 0 when the engine consumed it
        (or legally dropped it at the kill/epoch gate), 1 when it was
        rejected as malformed (counted in :meth:`frame_stats`).  The
        wire fuzzer's (scripts/fuzz_wire.py) one entry point."""
        rc = int(self._lib.accl_engine_ingest_bytes(
            self._w, self._rank, frame, len(frame)))
        if rc < 0:
            raise ACCLError(f"ingest_bytes failed for rank {self._rank}")
        return rc

    def frame_stats(self, publish: bool = True) -> dict:
        """Frames that passed structural validation vs frames rejected
        as malformed.  Each read publishes the deltas into the r8
        metrics registry (``wire/accepted_frames`` /
        ``wire/rejected_frames`` counters) so a scrape of /metrics sees
        the rejection rate without touching the FFI."""
        acc = ctypes.c_uint64(0)
        rej = ctypes.c_uint64(0)
        self._lib.accl_frame_stats(self._w, self._rank, ctypes.byref(acc),
                                   ctypes.byref(rej))
        stats = {"accepted_frames": int(acc.value),
                 "rejected_frames": int(rej.value)}
        if publish:
            from ..observability import metrics as _metrics

            reg = _metrics.default_registry()
            for key, val in stats.items():
                delta = val - self._frames_published.get(key, 0)
                if delta > 0:
                    reg.inc(f"wire/{key}", delta)
                    self._frames_published[key] = val
        return stats

    def frame_tap(self, on: bool = True) -> None:
        """Toggle the egress frame tap (bounded ring of the last 256
        staged frames, serialized wire framing)."""
        self._lib.accl_frame_tap(self._w, self._rank, 1 if on else 0)

    def tap_frames(self) -> list:
        """Drain the captured egress frames, oldest first, as raw
        bytes.  Atomic per batch (one native lock hold serializes a
        whole [len][bytes] run), so frames can never tear against live
        traffic rotating the ring; the tap is left EMPTY."""
        out: list = []
        buf = ctypes.create_string_buffer(1 << 20)
        while True:
            n = int(self._lib.accl_frame_tap_drain(self._w, self._rank,
                                                   buf, len(buf)))
            if n <= 0:
                break
            raw = buf.raw[:n]
            off = 0
            while off + 4 <= n:
                ln = int.from_bytes(raw[off:off + 4], "little")
                off += 4
                out.append(raw[off:off + ln])
                off += ln
        return out

    # -- elastic membership (r11): join control plane -----------------
    def join_sync(self, sponsor_session: int,
                  timeout_s: float = 10.0) -> int:
        """Joiner side of the Join/Welcome/StateSync exchange: sync
        per-comm epochs/abort fences + the comm-slot count from a live
        sponsor.  Returns 0, or -1 when the sponsor never answered
        (dead/killed — pick another survivor and retry)."""
        return int(self._lib.accl_join_sync(
            self._w, self._rank, sponsor_session,
            int(timeout_s * 1000)))

    def comm_count(self) -> int:
        """Comm slots (real + placeholder) this engine knows — lets the
        join path assert its id space really aligned."""
        return int(self._lib.accl_comm_count(self._w, self._rank))

    def comm_epoch(self, comm_id: int) -> int:
        """Current epoch of a comm slot (abort-fence introspection)."""
        return int(self._lib.accl_comm_epoch(self._w, self._rank,
                                             comm_id))

    def join_stats(self) -> dict:
        """Joins answered as sponsor / completed as joiner."""
        sponsored = ctypes.c_uint64(0)
        joined = ctypes.c_uint64(0)
        self._lib.accl_join_stats(self._w, self._rank,
                                  ctypes.byref(sponsored),
                                  ctypes.byref(joined))
        return {"sponsored": int(sponsored.value),
                "joined": int(joined.value)}

    def close(self) -> None:
        pass  # world teardown owns the native handle


class EmuRankTcp:
    """One rank over the TCP socket transport (one process — or thread —
    per rank; the reference's emulator-per-MPI-rank rung with ZMQ pub/sub
    replaced by length-prefixed TCP frames)."""

    def __init__(self, rank: int, nranks: int, base_port: int,
                 devmem_bytes: int = 64 << 20, n_egr_rx_bufs: int = 16,
                 egr_rx_buf_size: int = 1024,
                 max_eager_size: Optional[int] = None,
                 call_timeout_s: float = 60.0):
        self._lib = _load_lib()
        self.rank = rank
        self.nranks = nranks
        self._handle = self._lib.accl_world_create_tcp(rank, nranks, base_port,
                                                       devmem_bytes)
        if not self._handle:
            raise ACCLError(f"TCP emulator rank {rank} failed to start "
                            f"(port {base_port + rank} busy?)")
        # the driver-level sync wait gates the same calls as the engine's
        # receive timeout; the engine budget (ACCL_DEFAULT_TIMEOUT, µs)
        # must always fire FIRST so a stall surfaces as a decodable
        # RECEIVE_TIMEOUT_ERROR rather than an opaque driver wait failure
        # — clamp the driver budget above it
        call_timeout_s = max(call_timeout_s, default_timeout() / 1e6 + 5.0)
        self.device = EmuDevice(self._handle, rank, self._lib,
                                call_timeout_s=call_timeout_s)
        # one world handle per rank here (peers are separate processes
        # or sibling worlds): the in-process sanitizer exchange cannot
        # pair them — fall back to single-rank checks
        self.device.shares_process_world = False
        self.accl = ACCL(self.device)
        self.accl.call_timeout_s = call_timeout_s
        ranks = [Rank(ip="127.0.0.1", port=base_port + r, session=r,
                      max_segment_size=egr_rx_buf_size)
                 for r in range(nranks)]
        kwargs = {}
        if max_eager_size is not None:
            kwargs["max_eager_size"] = max_eager_size
        self.accl.initialize(ranks, rank, n_egr_rx_bufs=n_egr_rx_bufs,
                             egr_rx_buf_size=egr_rx_buf_size, **kwargs)
        # per-process telemetry sampler (multi-process worlds poll one
        # rank each; the scrape surface merges across processes)
        from ..observability import telemetry as _telemetry

        self.telemetry = _telemetry.sampler_from_env(
            [self.device.engine_stats], name=f"accl-tcp-r{rank}")
        _live_worlds.add(self)  # interpreter-exit safety net

    def close(self) -> None:
        if getattr(self, "telemetry", None) is not None:
            self.telemetry.stop()
            self.telemetry = None
        if self._handle:
            _flight.mark_event(self.accl.flight_recorder,
                               _flight.TEARDOWN_EVENT, -1, lane="lifecycle")
            # same shutdown -> null-under-lock -> join-waiters ->
            # destroy ordering as EmuWorld.close (the segfault fix)
            self._lib.accl_world_shutdown(self._handle)
            with self.device._lifecycle:
                self.device._w = None  # plan finalizers must no-op now
            stuck = _join_waiters([self.device])
            if stuck:
                get_logger("accl_tpu.emu").warning(
                    "tcp rank close: %d waiter thread(s) still alive "
                    "after shutdown — leaking the native world", stuck)
            else:
                self._lib.accl_world_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class EmuWorld:
    """N emulated ranks in one process.

    The MPI-replacement test harness: `run(fn)` executes `fn(accl, rank)`
    for every rank concurrently, mirroring how the reference test suite
    runs one driver per MPI rank against one emulator each.

    `transport` selects the wire rung: "inproc" (FIFO, synchronous hub),
    "dgram" (MTU fragmentation + deterministic out-of-order delivery +
    interleaved reassembly — the reference's UDP POE + depacketizer +
    rxbuf_session stack; see native/src/dgram.hpp), or "rdma" (queue
    pairs with an ordered control plane and a separate one-sided memory
    plane for rendezvous WRITEs — the CoyoteDevice rung; see
    native/src/rdma.hpp).
    """

    #: datagram fault kinds for inject_dgram_fault
    DGRAM_DROP_NEXT = 1
    DGRAM_DUP_NEXT = 2

    def __init__(self, nranks: int, devmem_bytes: int = 64 << 20,
                 n_egr_rx_bufs: int = 16, egr_rx_buf_size: int = 1024,
                 max_eager_size: Optional[int] = None,
                 max_rendezvous_size: Optional[int] = None,
                 initialize: bool = True, transport: str = "inproc",
                 mtu: int = 256, reorder_window: int = 8,
                 retry_max: Optional[int] = None,
                 retry_base_us: Optional[int] = None,
                 chaos=None):
        self._lib = _load_lib()
        self.nranks = nranks
        self._n_egr_rx_bufs = n_egr_rx_bufs
        self._egr_rx_buf_size = egr_rx_buf_size
        if transport == "dgram":
            self._handle = self._lib.accl_world_create_dgram(
                nranks, devmem_bytes, mtu, reorder_window)
        elif transport == "rdma":
            self._handle = self._lib.accl_world_create_rdma(
                nranks, devmem_bytes)
        elif transport == "inproc":
            self._handle = self._lib.accl_world_create(nranks, devmem_bytes)
        else:
            raise ACCLError(f"unknown transport {transport!r}")
        self.devices = [EmuDevice(self._handle, r, self._lib)
                        for r in range(nranks)]
        # retransmission policy: explicit args > ACCL_RETRY_* env >
        # defaults (the env policy was applied at device construction)
        if retry_max is not None or retry_base_us is not None:
            from ..resilience.retry import RetryPolicy

            env = RetryPolicy.from_env()
            rm = env.max_retries if retry_max is None else retry_max
            rb = env.base_us if retry_base_us is None else retry_base_us
            for d in self.devices:
                d.set_resilience(rm, rb)
        # seeded chaos plan: a ChaosPlan, a grammar string, or (by
        # default) whatever ACCL_CHAOS carries
        from ..resilience.chaos import ChaosPlan

        if isinstance(chaos, str):
            chaos = ChaosPlan.parse(chaos)
        if chaos is None:
            chaos = ChaosPlan.from_env()
        self.chaos_plan = chaos
        if chaos is not None:
            for r, d in enumerate(self.devices):
                chaos.apply(d, r)
        self.accls = [ACCL(d) for d in self.devices]
        self._pool = ThreadPoolExecutor(max_workers=nranks)
        if initialize:
            ranks = [
                Rank(ip="127.0.0.1", port=0, session=r,
                     max_segment_size=egr_rx_buf_size)
                for r in range(nranks)
            ]
            kwargs = {}
            if max_eager_size is not None:
                kwargs["max_eager_size"] = max_eager_size
            if max_rendezvous_size is not None:
                kwargs["max_rendezvous_size"] = max_rendezvous_size
            for r, a in enumerate(self.accls):
                a.initialize(ranks, r, n_egr_rx_bufs=n_egr_rx_bufs,
                             egr_rx_buf_size=egr_rx_buf_size, **kwargs)
        # hang watchdog over the per-rank flight recorders: the native
        # engine keeps its own gang state, so diagnosis here is purely
        # flight-ring based (which ranks have an in-flight gang call,
        # which never issued one).  Inert when ACCL_WATCHDOG_TIMEOUT=0,
        # ACCL_FLIGHT=0, or initialize was deferred (no recorders yet).
        # With ACCL_WATCHDOG_ACTION=abort a fire additionally aborts the
        # hung communicator (initiated from an arrived survivor) instead
        # of only dumping — the detect -> recover bridge.
        self.watchdog = _health.Watchdog(
            [a.flight_recorder for a in self.accls
             if a.flight_recorder is not None], name="accl-emu",
            abort_hook=self._watchdog_abort).start()
        # elastic membership (r11): the in-process join rendezvous —
        # replacement ranks announce here, the recovery supervisor's
        # grow policy discovers them (resilience/elastic.py)
        from ..resilience.elastic import MembershipBoard

        self.board = MembershipBoard()
        self.joiners: list = []
        # engine telemetry sampler (r14): polls every rank's native
        # stats snapshot into the shared registry as engine/* families.
        # None (no thread, zero work) unless ACCL_TELEMETRY_INTERVAL_MS
        # is set > 0.
        from ..observability import telemetry as _telemetry

        self.telemetry = _telemetry.sampler_from_env(
            [d.engine_stats for d in self.devices], name="accl-emu",
            link_sources=[(r, d.link_stats)
                          for r, d in enumerate(self.devices)])
        # online tuner (r19): ACCL_TUNE_ONLINE=1 closes the telemetry
        # -> tuner loop over this world (tuning/online.py); unset
        # constructs nothing and dispatch stays bit-identical
        from ..tuning import online as _online

        self.online_tuner = _online.ensure_online_tuner_from_env(self)
        _live_worlds.add(self)  # interpreter-exit safety net

    def start_watchdog(self, **kwargs) -> "_health.Watchdog":
        """Re-arm the watchdog with explicit settings (tests shrink
        timeout_s; a deferred-initialize world arms it after bring-up)."""
        self.watchdog.stop()
        kwargs.setdefault("abort_hook", self._watchdog_abort)
        self.watchdog = _health.Watchdog(
            [a.flight_recorder for a in self.accls
             if a.flight_recorder is not None],
            name="accl-emu", **kwargs).start()
        return self.watchdog

    def _watchdog_abort(self, comm_id: int, report: dict) -> None:
        """ACCL_WATCHDOG_ACTION=abort hook: abort the hung communicator
        with RANK_FAILED, initiated from a rank that actually ARRIVED
        at the stuck gang (the missing rank may be dead and unable to
        propagate anything)."""
        from ..constants import ErrorCode

        hangs = report.get("analysis", {}).get("hangs", [])
        # the hook fires once per hung comm: pick THIS comm's arrived
        # set (hangs[0] may describe a different comm whose arrived
        # ranks include the very rank that is dead here)
        arrived = next((h["arrived"] for h in hangs
                        if h.get("comm") == comm_id), [])
        for r in list(arrived) or list(range(self.nranks)):
            try:
                self.accls[r].abort(comm_id,
                                    error=int(ErrorCode.RANK_FAILED))
                return
            except Exception:  # noqa: BLE001 — try the next survivor
                continue

    def kill_rank(self, rank: int) -> None:
        """Kill-rank chaos: rank's engine goes silent mid-run (egress
        dropped, ingress deaf, local comms aborted with RANK_FAILED)."""
        self.devices[rank].kill()

    def spawn_replacement(self, announce: bool = True) -> "EmuJoiner":
        """Elastic membership: spawn a REAL replacement rank — a fresh
        native engine wired into the live world's hub at the next
        session id, with its own driver brought up on a self-world —
        and (by default) announce it on the membership board so a
        grow-policy recovery supervisor admits it.  The joiner then
        completes the handshake with :meth:`EmuJoiner.join` (engine
        state sync from a sponsor + adoption of the grown comm).
        In-flight traffic on the existing ranks is untouched: the new
        engine only ever speaks the join control plane until the grown
        communicator exists."""
        new_rank = int(self._lib.accl_world_add_rank(self._handle))
        if new_rank < 0:
            raise ACCLError(
                "spawn_replacement: this world's transport cannot grow "
                "(only inproc worlds support live join) or the join "
                "headroom is exhausted")
        device = EmuDevice(self._handle, new_rank, self._lib)
        accl = ACCL(device)
        row = Rank(ip="127.0.0.1", port=0, session=new_rank,
                   max_segment_size=self._egr_rx_buf_size)
        # the joiner brings up on a self-world: its comm 0 is a 1-rank
        # table (usable for local ops only); the join state sync then
        # pads/fences its id space to match the survivors'
        accl.initialize([row], 0, n_egr_rx_bufs=self._n_egr_rx_bufs,
                        egr_rx_buf_size=self._egr_rx_buf_size)
        joiner = EmuJoiner(self, accl, device, new_rank, row)
        if announce:
            joiner.offer = self.board.announce(new_rank, row)
        self.joiners.append(joiner)
        if accl.flight_recorder is not None:
            self.watchdog.add_recorder(accl.flight_recorder)
        return joiner

    def reset_errors(self) -> None:
        """Collective seqn resync after a classified fault: every
        rank's driver + engine state is cleared so the world is
        reusable (the fixture-reuse contract of
        tests/test_fault_injection.py)."""
        for a in self.accls:
            a.reset_errors()

    def resilience_stats(self) -> list:
        """Per-rank engine recovery counters (retransmits, NACKs,
        fenced drops) — the observability of the retransmission lane."""
        return [d.resilience_stats() for d in self.devices]

    def engine_stats(self) -> list:
        """Per-rank full engine telemetry snapshots (r14) — the same
        plane the ACCL_TELEMETRY_INTERVAL_MS sampler polls."""
        return [d.engine_stats() for d in self.devices]

    def link_stats(self) -> dict:
        """Per-rank link rows (r15): global rank -> decoded
        (comm, peer) wire-counter rows."""
        return {r: d.link_stats() for r, d in enumerate(self.devices)}

    def link_matrix(self, comm: int = 0,
                    tenant: Optional[str] = None) -> dict:
        """World-level P×P link traffic matrix over one communicator
        (observability/telemetry.link_matrix doc) — the measured
        per-link bandwidth/congestion input the topology-aware
        selection work (ROADMAP item 2) consumes.  ``tenant`` (r20)
        slices instead by tenant label: the union of every
        communicator labeled that tenant across the world's drivers."""
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

    def run(self, fn: Callable, *args) -> list:
        """Run `fn(accl, rank, *args)` on every rank concurrently and
        return per-rank results; exceptions propagate."""
        futures = [
            self._pool.submit(fn, self.accls[r], r, *args)
            for r in range(self.nranks)
        ]
        return [f.result(timeout=120) for f in futures]

    def dump_qps(self, rank: int) -> str:
        """Queue-pair counters for one rank (RDMA rung observability,
        the CoyoteDevice dump analog)."""
        out = ctypes.create_string_buffer(8192)
        n = self._lib.accl_dump_qps(self._handle, rank, out, 8192)
        if n < 0:
            raise ACCLError("world has no RDMA transport")
        return out.value.decode()

    def inject_dgram_fault(self, kind: int) -> None:
        """Arm a one-shot datagram-level fault on the shared hub (drop or
        duplicate the NEXT fragment posted by any rank); only valid for
        the "dgram" transport."""
        rc = self._lib.accl_dgram_fault(self._handle, kind)
        if rc != 0:
            raise ACCLError("world has no datagram transport")

    def close(self) -> None:
        self.watchdog.stop()
        if getattr(self, "online_tuner", None) is not None:
            from ..tuning import online as _online

            # stop the loop before engines die — a mid-teardown A/B
            # measurement would submit against a dying world
            if _online.online_tuner() is self.online_tuner:
                _online.stop_online_tuner()
            else:
                self.online_tuner.stop()
            self.online_tuner = None
        if self.telemetry is not None:
            self.telemetry.stop()  # before shutdown: no poll of a dead world
            self.telemetry = None
        self._pool.shutdown(wait=False)
        if self._handle:
            # lifecycle anchor (r13): after this record, NO successful
            # completion may publish on these ranks — the dump-side
            # invariant analysis.checks.check_teardown_completions
            # verifies (the post-mortem twin of the suite-exit fix)
            for a in self.accls + [j.accl for j in self.joiners]:
                _flight.mark_event(a.flight_recorder, _flight.TEARDOWN_EVENT,
                                   -1, lane="lifecycle")
            # Teardown ordering (the r13 suite-exit segfault fix —
            # docs/debugging.md "The suite-exit segfault"):
            # 1. shutdown: engine threads stop, every pending call
            #    finalizes, so waiter threads parked in accl_wait_call
            #    return within one poll interval;
            # 2. null the device handles UNDER each device's lifecycle
            #    lock: a submission in flight either finished
            #    registering its waiter (joined below) or now observes
            #    None and fails fast — no stale handle survives;
            # 3. join the waiter threads — after this, NO thread can
            #    be inside (or about to enter) the native world;
            # 4. destroy.  If a waiter refuses to die (pathological),
            #    LEAK the native world instead of freeing memory a
            #    live thread may still touch.
            self._lib.accl_world_shutdown(self._handle)
            devices = self.devices + [j.device for j in self.joiners]
            for d in devices:
                with d._lifecycle:
                    d._w = None
            stuck = _join_waiters(devices)
            if stuck:
                get_logger("accl_tpu.emu").warning(
                    "world close: %d waiter thread(s) still alive after "
                    "shutdown — leaking the native world rather than "
                    "freeing memory under a live thread", stuck)
            else:
                self._lib.accl_world_destroy(self._handle)
            self._handle = None

    def __enter__(self) -> "EmuWorld":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class EmuJoiner:
    """Handle on a replacement rank spawned into a live EmuWorld
    (:meth:`EmuWorld.spawn_replacement`): its own native engine,
    driver, and membership-board offer.  The world owns the native
    handle; the joiner's lifetime ends with the world's."""

    def __init__(self, world: EmuWorld, accl: ACCL, device: EmuDevice,
                 rank: int, row: Rank):
        self.world = world
        self.accl = accl
        self.device = device
        self.rank = rank  # session id == global engine index
        self.row = row
        self.offer = None

    def join(self, timeout_s: float = 30.0) -> int:
        """Complete the join handshake (blocks until a survivor's
        admit/grow round claims this offer): engine state sync from
        the sponsor, driver comm-id padding, adoption of the grown
        communicator.  Returns the grown comm id — the first
        communicator this rank can collectively use."""
        from ..resilience.elastic import join_grown_world

        if self.offer is None:
            self.offer = self.world.board.announce(self.rank, self.row)
        return join_grown_world(self.accl, self.offer, timeout_s)
