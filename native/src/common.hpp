// Common types for the ACCL-TPU native collective engine.
//
// This library is the TPU build's equivalent of the reference's on-device
// control plane + dataplane, re-hosted as portable C++ so the whole
// framework is testable without accelerator hardware — the role the
// reference's cclo_emu CPU emulator plays (test/model/emulator/cclo_emu.cpp).
// Nothing here is a translation of the reference sources; the wire header
// field set and the 15-word call ABI are kept compatible so the Python
// driver can treat the emulator and the TPU backend identically.
#pragma once

#include <array>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <optional>
#include <stdexcept>
#include <string>
#include <thread>
#include <vector>

// ---------------------------------------------------------------------------
// Compile-time lock discipline: Clang Thread Safety Analysis attributes.
//
// Every mutex-guarded field and lock-requiring function in the native
// engine is annotated with these macros; `make tsa` builds the tree
// with -Werror=thread-safety (scripts/tsa_check.py drives a real
// clang++ when one is installed, or the libclang frontend otherwise)
// so an unlocked read of a guarded field, a missing REQUIRES on a
// helper, or a lock-order inversion against the declared
// ACQUIRED_BEFORE edges fails the build.  Policy mirrors the r13
// sanitizer wall: ZERO waivers under accl:: — ACCL_NO_TSA exists for
// third-party interop only and scripts/tsa_check.py greps it banned
// from native/src.  Under gcc (plain/ASan/TSan lanes) every macro
// expands to nothing, so non-clang builds are bit-identical.
// ---------------------------------------------------------------------------
#if defined(__clang__)
#define ACCL_TSA(x) __attribute__((x))
#else
#define ACCL_TSA(x)
#endif
#define ACCL_CAPABILITY(x) ACCL_TSA(capability(x))
#define ACCL_SCOPED_CAPABILITY ACCL_TSA(scoped_lockable)
#define ACCL_GUARDED_BY(x) ACCL_TSA(guarded_by(x))
#define ACCL_PT_GUARDED_BY(x) ACCL_TSA(pt_guarded_by(x))
#define ACCL_REQUIRES(...) ACCL_TSA(requires_capability(__VA_ARGS__))
#define ACCL_ACQUIRE(...) ACCL_TSA(acquire_capability(__VA_ARGS__))
#define ACCL_RELEASE(...) ACCL_TSA(release_capability(__VA_ARGS__))
#define ACCL_TRY_ACQUIRE(...) ACCL_TSA(try_acquire_capability(__VA_ARGS__))
#define ACCL_EXCLUDES(...) ACCL_TSA(locks_excluded(__VA_ARGS__))
#define ACCL_ACQUIRED_BEFORE(...) ACCL_TSA(acquired_before(__VA_ARGS__))
#define ACCL_ACQUIRED_AFTER(...) ACCL_TSA(acquired_after(__VA_ARGS__))
#define ACCL_RETURN_CAPABILITY(x) ACCL_TSA(lock_returned(x))
// Third-party interop escape hatch.  NEVER legal under accl:: —
// scripts/tsa_check.py fails the lane if it appears in native/src.
#define ACCL_NO_TSA ACCL_TSA(no_thread_safety_analysis)

// Deterministic schedule exploration (docs/static_analysis.md): the
// ACCL_DETSCHED build routes every blocking primitive below through
// the virtual scheduler in detsched.hpp, serializing all engine
// threads onto one deterministic schedule so small-world drills can be
// model-checked exhaustively.  Plain builds never include it.
#if defined(ACCL_DETSCHED)
#include "detsched.hpp"
#include "detsched_pred.hpp"
#endif

namespace accl {

// ---------------------------------------------------------------------------
// ABI constants (kept bit-compatible with accl_tpu/constants.py and the
// reference driver; see driver/xrt/include/accl/constants.hpp:191-210).
// ---------------------------------------------------------------------------
enum class Op : uint32_t {
  Config = 0,
  Copy = 1,
  Combine = 2,
  Send = 3,
  Recv = 4,
  Bcast = 5,
  Scatter = 6,
  Gather = 7,
  Reduce = 8,
  Allgather = 9,
  Allreduce = 10,
  ReduceScatter = 11,
  Barrier = 12,
  Alltoall = 13,
  Alltoallv = 14,  // per-peer counts from the count operand
  Nop = 255,
};

enum class CfgFunc : uint32_t {
  ResetPeriph = 0,
  EnablePkt = 1,
  SetTimeout = 2,
  SetMaxEagerMsgSize = 3,
  SetMaxRendezvousMsgSize = 4,
};

// Error bits (reference: constants.hpp:355-387; bits 27/28 are this
// build's fault-tolerance extension, mirrored in accl_tpu/constants.py).
enum Err : uint32_t {
  OK = 0,
  RECEIVE_TIMEOUT_ERROR = 1u << 11,
  COLLECTIVE_NOT_IMPLEMENTED = 1u << 14,
  EAGER_THRESHOLD_INVALID = 1u << 16,
  RENDEZVOUS_THRESHOLD_INVALID = 1u << 17,
  DMA_SIZE_ERROR = 1u << 18,
  ARITH_ERROR = 1u << 19,
  PACK_SEQ_NUMBER_ERROR = 1u << 21,
  COMPRESSION_ERROR = 1u << 22,
  SEGMENTER_EXPECTED_BTT_ERROR = 1u << 25,
  // the communicator this call ran on was aborted (epoch fenced); every
  // pending call on all live ranks finalizes fast with this bit
  COMM_ABORTED = 1u << 27,
  // the abort was triggered by a peer declared dead (watchdog action or
  // liveness probe) rather than an application-initiated abort
  RANK_FAILED = 1u << 28,
};

// Wire message types (reference: eth_intf.h:42-45; types >= 4 are this
// build's resilience control plane — no reference analog).
enum class MsgType : uint8_t {
  EgrMsg = 0,
  RndzvsMsg = 1,
  RndzvsInit = 2,
  RndzvsWrDone = 3,
  // receiver -> sender: "resend eager segments of (comm, tag) from seqn"
  // (hdr.seqn = first missing sequence number); answered from the
  // sender's bounded retransmit store
  Nack = 4,
  // liveness ping/pong piggybacked on the control plane (hdr.count = 1
  // requests a reply; 0 is the reply); any ingress traffic also counts
  // as proof of life for the sending peer
  Heartbeat = 5,
  // epoch-tagged communicator abort: hdr.epoch carries the NEW epoch,
  // hdr.count the error bits every pending call must finalize with
  Abort = 6,
  // ---- elastic membership (r11): the join control plane ----
  // joiner -> sponsor: "I am session hdr.src, send me your world state"
  // (the joiner is in NO communicator table yet, so it is addressed by
  // raw session id — the one piece of addressing that predates comms)
  Join = 7,
  // sponsor -> joiner: join accepted; hdr.count = number of comm slots
  // the StateSync payload will describe
  Welcome = 8,
  // sponsor -> joiner: serialized per-comm recovery state (see
  // Engine::ingress Join handling for the word layout): comm count,
  // then per comm {size, epoch, abort_bits} + the sponsor's per-peer
  // inbound/outbound seqn rows.  The joiner adopts the epoch/abort
  // fence table (so dead-epoch traffic can never land on it and its
  // comm-id space aligns with the survivors') and records the seqn
  // rows for introspection — fresh comms it joins start with clean
  // pairwise seqn state on every member by construction.
  StateSync = 9,
};

constexpr uint32_t TAG_ANY = 0xFFFFFFFFu;
constexpr uint32_t MAX_PACKETSIZE = 4096;  // transport write-chunk quantum

// Compression flag bits of descriptor word 7 (reference:
// constants.hpp:320-325; bit-compatible with accl_tpu/constants.py) —
// shared by the engine's flag algebra and the C++ host driver's
// prepare_call marshaling.
enum CompFlag : uint32_t {
  OP0_COMPRESSED = 1,
  OP1_COMPRESSED = 2,
  RES_COMPRESSED = 4,
  ETH_COMPRESSED = 8,
};

// ---------------------------------------------------------------------------
// Wire header: 64 bytes, self-describing, field set equivalent to the
// reference's eth_header {count,tag,src,seqn,strm,dst,msg_type,host,vaddr}
// (eth_intf.h:94-151) with a comm id in previously-reserved space (the
// reference derives the communicator from the session id; carrying it
// explicitly keeps the socket transport stateless).
// ---------------------------------------------------------------------------
struct WireHeader {
  uint32_t count = 0;  // payload bytes (compressed size if compressed)
  uint32_t tag = 0;
  uint32_t src = 0;   // source rank within comm
  uint32_t seqn = 0;  // per (comm, src->dst) sequence number
  uint32_t strm = 0;  // nonzero: route to compute stream id, not memory
  uint16_t dst_session = 0;
  uint8_t msg_type = 0;
  uint8_t host = 0;
  uint64_t vaddr = 0;  // rendezvous target address
  uint32_t comm_id = 0;
  uint32_t compressed = 0;  // wire payload is in the compressed
                            // representation (diagnostic only: both ends
                            // derive the wire format from their OWN
                            // arithcfg + flags, like the reference's
                            // marker-free eth header)
  uint32_t epoch = 0;  // communicator epoch (abort fencing): ingress
                       // drops data messages whose epoch trails the
                       // receiver's, so traffic from a dead epoch can
                       // never land after an abort
  uint8_t pad[64 - 44] = {0};
};
static_assert(sizeof(WireHeader) == 64, "wire header must be 64 bytes");

// ---------------------------------------------------------------------------
// 15-word call descriptor (reference ABI: hostctrl.cpp:19-63).
// ---------------------------------------------------------------------------
struct CallDesc {
  std::array<uint32_t, 15> w{};
  uint64_t id = 0;
  uint32_t current_step = 0;  // rendezvous resume point (fw :34,:2336)
  // scratch device-memory leases that persist across retries (the role of
  // the reference's SPARE1-3 rendezvous scratch buffers, accl.cpp:1190)
  uint64_t scratch0 = 0, scratch1 = 0;
  // first time this call was attempted at its CURRENT resume step (ns
  // since steady epoch; 0 = not yet tried; reset whenever current_step
  // advances so the budget is per-receive, like the blocking eager
  // path's seek, not per-call).  The retry queue expires calls against
  // the engine's receive budget — the reference retries NOT_READY
  // forever (fw :2460-2479), which turns a dead peer into an opaque
  // host-side hang; here the same timeout register that bounds blocking
  // receives bounds the cooperative retry loop, so a stuck rendezvous
  // finalizes with RECEIVE_TIMEOUT_ERROR.
  uint64_t first_try_ns = 0;
  // (comm, src, tag, vaddr) landing records this call advertised
  // (receiver role); torn down if the call expires so a late one-sided
  // write cannot land into reused memory and a late completion cannot
  // satisfy a future call.
  std::vector<std::array<uint64_t, 4>> rndzv_posts;

  Op scenario() const { return static_cast<Op>(w[0]); }
  uint32_t count() const { return w[1]; }
  uint32_t comm() const { return w[2]; }
  uint32_t root_src_dst() const { return w[3]; }
  uint32_t function() const { return w[4]; }
  uint32_t tag() const { return w[5]; }
  uint32_t arithcfg() const { return w[6]; }
  uint32_t compression() const { return w[7]; }
  uint32_t stream_flags() const { return w[8] & 0xFF; }
  uint32_t host_flags() const { return (w[8] >> 8) & 0xFF; }
  uint64_t addr0() const { return uint64_t(w[9]) | (uint64_t(w[10]) << 32); }
  uint64_t addr1() const { return uint64_t(w[11]) | (uint64_t(w[12]) << 32); }
  uint64_t addr2() const { return uint64_t(w[13]) | (uint64_t(w[14]) << 32); }
  // alltoallv's count result: the root and function words, which an
  // all-to-all has no use for (CCLOCall.addr_3 in constants.py).
  uint64_t addr3() const { return uint64_t(w[3]) | (uint64_t(w[4]) << 32); }
};

// Thrown by a rendezvous wait-point whose peer state has not arrived;
// the engine loop re-queues the whole call with its resume step
// (reference retry path: ccl_offload_control.c:2460-2479).
struct NotReadyEx {
  uint32_t step;
};

// Thrown when a message exceeds the configured rendezvous maximum size —
// the transfer cannot be expressed by either protocol, so the call
// finalizes immediately with the accumulated error code (the reference
// stores this register but never enforces it; here it is a hard cap).
struct SizeCapEx {};

// ---------------------------------------------------------------------------
// Synchronization wrappers: the compile-time lock discipline's
// capability types AND the deterministic scheduler's hook points.
//
// accl::Mutex / MutexLock / UniqueLock / CondVar / Thread replace the
// raw std primitives everywhere under accl:: so that
//  (a) clang Thread Safety Analysis sees every acquire/release (std::
//      mutex carries no capability attributes on libstdc++), and
//  (b) the ACCL_DETSCHED build can serialize every blocking operation
//      onto the virtual scheduler (detsched.hpp) — the hooks live in
//      exactly one place, inside these wrappers.
// Plain builds compile the wrappers down to the raw std calls.
// ---------------------------------------------------------------------------
class ACCL_CAPABILITY("mutex") Mutex {
 public:
  Mutex() = default;
  Mutex(const Mutex&) = delete;
  Mutex& operator=(const Mutex&) = delete;
  void lock() ACCL_ACQUIRE() {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      det::lock_hooked(&m_);
      return;
    }
#endif
    m_.lock();
  }
  void unlock() ACCL_RELEASE() {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      det::unlock_hooked(&m_);
      return;
    }
#endif
    m_.unlock();
  }
  bool try_lock() ACCL_TRY_ACQUIRE(true) { return m_.try_lock(); }
  std::mutex& native() { return m_; }

 private:
  std::mutex m_;
};

// std::lock_guard replacement (scoped capability so the analysis
// tracks the critical section's extent).
class ACCL_SCOPED_CAPABILITY MutexLock {
 public:
  explicit MutexLock(Mutex& m) ACCL_ACQUIRE(m) : m_(m) { m_.lock(); }
  ~MutexLock() ACCL_RELEASE() { m_.unlock(); }
  MutexLock(const MutexLock&) = delete;
  MutexLock& operator=(const MutexLock&) = delete;

 private:
  Mutex& m_;
};

// std::unique_lock replacement.  Derives the std type so condition
// waits (CondVar, cv_wait_for_pred) take it unchanged; lock/unlock are
// shadowed with capability-annotated, scheduler-aware versions.
class ACCL_SCOPED_CAPABILITY UniqueLock : public std::unique_lock<std::mutex> {
 public:
  explicit UniqueLock(Mutex& m) ACCL_ACQUIRE(m)
      : std::unique_lock<std::mutex>(acquire_adopted(m)), mu_(&m) {}
  ~UniqueLock() ACCL_RELEASE() {
    if (owns_lock()) {
      std::unique_lock<std::mutex>::release();
      mu_->unlock();
    }
  }
  void unlock() ACCL_RELEASE() {
    std::unique_lock<std::mutex>::release();
    mu_->unlock();
  }
  void lock() ACCL_ACQUIRE() {
    mu_->lock();
    static_cast<std::unique_lock<std::mutex>&>(*this) =
        std::unique_lock<std::mutex>(mu_->native(), std::adopt_lock);
  }
  UniqueLock(const UniqueLock&) = delete;
  UniqueLock& operator=(const UniqueLock&) = delete;

 private:
  static std::unique_lock<std::mutex> acquire_adopted(Mutex& m)
      ACCL_ACQUIRE(m) {
    m.lock();  // capability-aware + det-aware acquire
    return std::unique_lock<std::mutex>(m.native(), std::adopt_lock);
  }
  Mutex* mu_;
};

// std::condition_variable replacement; notify and the untimed waits
// are scheduler hook points.  Untimed pthread_cond_wait is intercepted
// by every sanitizer runtime, so no TSan workaround is needed here
// (only the TIMED waits below need one — see cv_wait_for_pred).
class CondVar {
 public:
  CondVar() = default;
  CondVar(const CondVar&) = delete;
  CondVar& operator=(const CondVar&) = delete;
  void notify_all() {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      det::cv_notify(this, true);
      return;
    }
#endif
    cv_.notify_all();
  }
  void notify_one() {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      det::cv_notify(this, false);
      return;
    }
#endif
    cv_.notify_one();
  }
  // Untimed predicate wait; `g` holds the Mutex associated with the
  // guarded state the predicate reads.
  template <typename Pred>
  void wait(std::unique_lock<std::mutex>& g, Pred pred) {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      while (!det::invoke_pred(pred)) det::cv_block(this, g, det::kInf);
      return;
    }
#endif
    cv_.wait(g, pred);
  }
  void wait(std::unique_lock<std::mutex>& g) {
#if defined(ACCL_DETSCHED)
    if (det::on()) {
      det::cv_block(this, g, det::kInf);
      return;
    }
#endif
    cv_.wait(g);
  }
  std::condition_variable& native() { return cv_; }

 private:
  std::condition_variable cv_;
};

// std::thread replacement: under ACCL_DETSCHED a child spawned during
// an active run registers with the scheduler before its body runs, so
// the scheduler serializes it from its first instruction; join parks
// on the virtual scheduler instead of blocking the token.
class Thread {
 public:
  Thread() noexcept = default;
  template <typename F>
  explicit Thread(F fn) {
#if defined(ACCL_DETSCHED)
    if (det::run_active()) {
      det_id_ = det::Sched::inst().pre_spawn();
      int id = det_id_;
      t_ = std::thread([id, fn2 = std::move(fn)]() mutable {
        det::Sched::inst().child_enter(id);
        fn2();
        det::Sched::inst().child_exit();
      });
      // deterministic spawn: the child is registered (and parked for
      // its first grant) before the parent's next instruction
      det::Sched::inst().await_child_enter(det_id_);
      return;
    }
#endif
    t_ = std::thread(std::move(fn));
  }
  Thread(Thread&&) noexcept = default;
  Thread& operator=(Thread&&) noexcept = default;
  bool joinable() const { return t_.joinable(); }
  void join() {
#if defined(ACCL_DETSCHED)
    if (det_id_ >= 0 && det::on()) det::Sched::inst().join_wait_slot(det_id_);
#endif
    t_.join();
  }

 private:
  std::thread t_;
#if defined(ACCL_DETSCHED)
  int det_id_ = -1;
#endif
};

// Scheduler-aware sleep/yield (the engine loop's retry pacing, chaos
// stalls, liveness-probe polls): virtual time under ACCL_DETSCHED,
// the real thing everywhere else.
inline void det_sleep_for(std::chrono::nanoseconds d) {
#if defined(ACCL_DETSCHED)
  if (det::on()) {
    det::sleep_hooked(uint64_t(d.count() > 0 ? d.count() : 1));
    return;
  }
#endif
  std::this_thread::sleep_for(d);
}

inline void det_yield() {
#if defined(ACCL_DETSCHED)
  if (det::on()) {
    det::yield_hooked();
    return;
  }
#endif
  std::this_thread::yield();
}

// Budget clock: virtual time under an active detsched run, the real
// steady clock everywhere else.  The engine's receive budgets MUST be
// measured with this — a budget read off the real clock never expires
// inside an explored schedule (cv waits are virtual, so wall time
// barely advances), which made the whole RECEIVE_TIMEOUT classification
// class unreachable to the checker: ROADMAP item 5's "wall-clock
// ingredient the virtual clock hides".
inline std::chrono::steady_clock::time_point det_clock_now() {
#if defined(ACCL_DETSCHED)
  // Free-run (the deadlock escape hatch) freezes the virtual clock and
  // runs teardown on real primitives; budgets must switch back to the
  // real clock with it or they never expire and teardown hangs.
  if (det::on() && !det::free_running())
    return std::chrono::steady_clock::time_point(
        std::chrono::duration_cast<std::chrono::steady_clock::duration>(
            std::chrono::nanoseconds(det::now_ns())));
#endif
  return std::chrono::steady_clock::now();
}

// Resource-exhaustion hook: a modeled resource (rx pool, retransmit
// store) just saturated.  Under detsched this arms the checker's
// timeout-injection window (exhaustion-induced orderings become
// explored state); a no-op everywhere else.
inline void det_note_pressure() {
#if defined(ACCL_DETSCHED)
  if (det::on()) det::note_pressure();
#endif
}

// Liveness tokens: one per submitted engine call, returned when the
// call finalizes.  Tokens still outstanding when a drill returns are
// the stuck-progress finding; no-ops outside detsched runs.
inline void det_live_begin() {
#if defined(ACCL_DETSCHED)
  if (det::on()) det::live_begin();
#endif
}

inline void det_live_end() {
#if defined(ACCL_DETSCHED)
  if (det::on()) det::live_end();
#endif
}

// ---------------------------------------------------------------------------
// TSan-safe timed condition waits (r13).  libstdc++ (gcc 10) lowers
// every steady-clock timed CV wait to pthread_cond_clockwait, which
// this toolchain's ThreadSanitizer runtime does NOT intercept: the
// checker then never observes the mutex being released inside the wait
// and reports impossible "double lock of a mutex"/"race with mutex
// held" findings on perfectly locked queues.  Under
// __SANITIZE_THREAD__ these helpers replace the timed wait with a
// bounded unlock/sleep/relock poll (1 ms granularity — every caller
// re-checks its predicate, so the observable semantics are identical);
// all other builds use the real futex-backed wait.  Policy + rationale:
// docs/static_analysis.md "Native sanitizer lanes".
// Under ACCL_DETSCHED the deadline is VIRTUAL: the wait parks on the
// scheduler and the clock jumps when nothing is runnable, so receive
// budgets cost microseconds of wall time per explored schedule.
// ---------------------------------------------------------------------------
template <typename Pred>
inline bool cv_wait_for_pred(CondVar& cv, std::unique_lock<std::mutex>& g,
                             std::chrono::nanoseconds timeout, Pred pred) {
#if defined(ACCL_DETSCHED)
  if (det::on()) {
    uint64_t deadline =
        det::now_ns() + uint64_t(timeout.count() > 0 ? timeout.count() : 0);
    for (;;) {
      if (det::invoke_pred(pred)) return true;
      if (det::free_running()) {
        // escape hatch fired: the virtual clock is frozen, so finish the
        // wait against the REAL clock or this slice never expires and
        // teardown hangs instead of reporting the finding
        auto rdl = std::chrono::steady_clock::now() + timeout;
        for (;;) {
          if (det::invoke_pred(pred)) return true;
          if (std::chrono::steady_clock::now() >= rdl)
            return det::invoke_pred(pred);
          det::cv_block(&cv, g, 1000000);  // 1 ms real poll in free-run
        }
      }
      uint64_t now = det::now_ns();
      if (now >= deadline) return det::invoke_pred(pred);
      det::cv_block(&cv, g, deadline - now);
    }
  }
#endif
#if defined(__SANITIZE_THREAD__)
  auto deadline = std::chrono::steady_clock::now() + timeout;
  for (;;) {
    if (pred()) return true;
    if (std::chrono::steady_clock::now() >= deadline) return pred();
    g.unlock();
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
    g.lock();
  }
#else
  return cv.native().wait_for(g, timeout, pred);
#endif
}

inline std::cv_status cv_wait_until_point(
    CondVar& cv, std::unique_lock<std::mutex>& g,
    std::chrono::steady_clock::time_point deadline) {
#if defined(ACCL_DETSCHED)
  if (det::on()) {
    auto now = std::chrono::steady_clock::now();
    if (now >= deadline) return std::cv_status::timeout;
    uint64_t ns = uint64_t(
        std::chrono::duration_cast<std::chrono::nanoseconds>(deadline - now)
            .count());
    return det::cv_block(&cv, g, ns) ? std::cv_status::no_timeout
                                     : std::cv_status::timeout;
  }
#endif
#if defined(__SANITIZE_THREAD__)
  if (std::chrono::steady_clock::now() >= deadline)
    return std::cv_status::timeout;
  g.unlock();
  std::this_thread::sleep_for(std::chrono::milliseconds(1));
  g.lock();
  return std::chrono::steady_clock::now() >= deadline
             ? std::cv_status::timeout
             : std::cv_status::no_timeout;
#else
  return cv.native().wait_until(g, deadline);
#endif
}

// ---------------------------------------------------------------------------
// Bounded-ish MPMC fifo used for command/status/notification streams
// (role of the hlslib FIFOs wiring the reference emulator threads).
// ---------------------------------------------------------------------------
template <typename T>
class Fifo {
 public:
  void push(T v) {
    {
      MutexLock g(m_);
      q_.push_back(std::move(v));
    }
    cv_.notify_all();
  }

  std::optional<T> pop_wait(std::chrono::nanoseconds timeout) {
    UniqueLock g(m_);
    // the predicate runs with m_ held (cv_wait_for_pred's contract);
    // the REQUIRES annotation tells the analysis, which otherwise
    // checks lambda bodies as lock-free contexts
    if (!cv_wait_for_pred(cv_, g, timeout,
                          [&]() ACCL_REQUIRES(m_) { return !q_.empty() || closed_; }))
      return std::nullopt;
    if (q_.empty()) return std::nullopt;
    T v = std::move(q_.front());
    q_.pop_front();
    return v;
  }

  std::optional<T> try_pop() {
    MutexLock g(m_);
    if (q_.empty()) return std::nullopt;
    T v = std::move(q_.front());
    q_.pop_front();
    return v;
  }

  // Wait until pred matches an element; remove and return it.  Other
  // elements stay queued (out-of-order matching for rendezvous queues).
  // Expressed as one predicate wait so the deterministic scheduler's
  // virtual deadline applies (and the post-timeout last scan the r13
  // version did by hand falls out of cv_wait_for_pred's contract).
  std::optional<T> pop_match(std::function<bool(const T&)> pred,
                             std::chrono::nanoseconds timeout) {
    UniqueLock g(m_);
    auto find = [&]() ACCL_REQUIRES(m_) {
      for (auto it = q_.begin(); it != q_.end(); ++it)
        if (pred(*it)) return it;
      return q_.end();
    };
    cv_wait_for_pred(cv_, g, timeout,
                     [&]() ACCL_REQUIRES(m_) { return closed_ || find() != q_.end(); });
    auto it = find();
    if (it == q_.end()) return std::nullopt;
    T v = std::move(*it);
    q_.erase(it);
    return v;
  }

  bool empty() const {
    MutexLock g(m_);
    return q_.empty();
  }

  // Non-destructive scan: does any queued element satisfy pred?
  bool any(std::function<bool(const T&)> pred) const {
    MutexLock g(m_);
    for (const auto& v : q_)
      if (pred(v)) return true;
    return false;
  }

  // Non-destructive visit of every queued element.
  void for_each(std::function<void(const T&)> fn) const {
    MutexLock g(m_);
    for (const auto& v : q_) fn(v);
  }

  size_t size() const {
    MutexLock g(m_);
    return q_.size();
  }

  void close() {
    {
      MutexLock g(m_);
      closed_ = true;
    }
    cv_.notify_all();
  }

 private:
  mutable Mutex m_;
  CondVar cv_;
  std::deque<T> q_ ACCL_GUARDED_BY(m_);
  bool closed_ ACCL_GUARDED_BY(m_) = false;
};

// fp16 <-> fp32 conversion (the emulator arithmetic/compression lanes'
// scalar core; the reference uses Vitis HLS half types in
// kernels/plugins/hp_compression/hp_compression.cpp).
inline uint16_t f32_to_f16(float f) {
  uint32_t x;
  std::memcpy(&x, &f, 4);
  uint32_t sign = (x >> 16) & 0x8000u;
  int32_t exp = int32_t((x >> 23) & 0xFF) - 127 + 15;
  uint32_t mant = x & 0x7FFFFFu;
  if (((x >> 23) & 0xFF) == 0xFF) {  // inf/nan
    return uint16_t(sign | 0x7C00u | (mant ? 0x200u : 0));
  }
  if (exp >= 0x1F) return uint16_t(sign | 0x7C00u);  // overflow -> inf
  if (exp <= 0) {                                    // subnormal / zero
    if (exp < -10) return uint16_t(sign);
    mant |= 0x800000u;
    uint32_t shift = uint32_t(14 - exp);
    uint32_t half_mant = mant >> shift;
    uint32_t rem = mant & ((1u << shift) - 1);
    if (rem > (1u << (shift - 1)) ||
        (rem == (1u << (shift - 1)) && (half_mant & 1)))
      half_mant++;
    return uint16_t(sign | half_mant);
  }
  uint32_t half = sign | (uint32_t(exp) << 10) | (mant >> 13);
  uint32_t rem = mant & 0x1FFFu;
  if (rem > 0x1000u || (rem == 0x1000u && (half & 1))) half++;
  return uint16_t(half);
}

inline float f16_to_f32(uint16_t h) {
  uint32_t sign = uint32_t(h & 0x8000u) << 16;
  uint32_t exp = (h >> 10) & 0x1Fu;
  uint32_t mant = h & 0x3FFu;
  uint32_t x;
  if (exp == 0) {
    if (mant == 0) {
      x = sign;
    } else {  // subnormal
      exp = 127 - 15 + 1;
      while (!(mant & 0x400u)) {
        mant <<= 1;
        exp--;
      }
      mant &= 0x3FFu;
      x = sign | (exp << 23) | (mant << 13);
    }
  } else if (exp == 0x1F) {
    x = sign | 0x7F800000u | (mant << 13);
  } else {
    x = sign | ((exp - 15 + 127) << 23) | (mant << 13);
  }
  float f;
  std::memcpy(&f, &x, 4);
  return f;
}

}  // namespace accl
