// Deterministic-schedule drills for the native engine model checker.
//
// Each drill is a self-contained, re-runnable scenario: it builds a
// tiny in-process world INSIDE the controlled run (so every engine
// thread is serialized from its first instruction), races two
// engine-lifecycle operations against live traffic, asserts the
// drill's invariants through det::expect on EVERY explored schedule,
// and tears the world down before returning.  The explorer
// (test_detsched.cpp, driven by scripts/model_check.py) re-runs a
// drill under thousands of schedules; a failing schedule is minimized
// and dumped as a replayable hex artifact.
//
// Drills (ISSUE r14 / ROADMAP item 5's verification gate):
//   replay_vs_invalidate — persistent-plan replay racing abort/fence
//   abort_vs_traffic     — ACCL.abort racing an in-flight send/recv
//   join_vs_traffic      — elastic join racing live traffic
//   shutdown_vs_waiters  — two-phase shutdown racing blocked receivers
//   alltoallv_counts     — alltoallv's count operand and count result
//   detach_race          — InprocHub::detach vs a mid-flight delivery
//                          (sensitivity drill: the ACCL_FAULT_DETACH_RACE
//                          build reverts the r13 drain and the checker
//                          must REDISCOVER the race)
#pragma once

#if !defined(ACCL_DETSCHED)
#error "detsched_drills.hpp requires an ACCL_DETSCHED build"
#endif

#include <atomic>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <map>
#include <memory>
#include <string>
#include <vector>

#include "../src/engine.hpp"

namespace accl {
namespace drills {

// ---- tiny world builder -------------------------------------------------
// Drill rx pools are deliberately SMALL (default 4 x 256 B) so real
// multi-segment payloads exhaust them inside explored schedules —
// resource pressure is modeled state, not an accident of sizing.
// Overridable per invocation for exhaustion-gradient experiments.
inline uint32_t env_u32(const char* key, uint32_t dflt) {
  const char* v = std::getenv(key);
  if (!v || !*v) return dflt;
  char* end = nullptr;
  unsigned long x = std::strtoul(v, &end, 10);
  return (end && *end == '\0' && x > 0) ? uint32_t(x) : dflt;
}

struct DetWorld {
  std::shared_ptr<InprocHub> hub;
  std::vector<std::unique_ptr<Engine>> eng;

  explicit DetWorld(int nranks, uint64_t devmem = 1 << 20) {
    hub = std::make_shared<InprocHub>(nranks);
    for (int r = 0; r < nranks; ++r)
      eng.push_back(std::make_unique<Engine>(
          uint32_t(r), devmem, std::make_unique<InprocTransport>(hub, r)));
    for (int r = 0; r < nranks; ++r) {
      eng[size_t(r)]->cfg_rx_buffers(env_u32("ACCL_DETSCHED_RX_BUFS", 4),
                                     env_u32("ACCL_DETSCHED_RX_BUFSZ", 256));
      setup_comm(r, nranks);
      setup_arith(r);
    }
  }

  // comm 0 over every rank; session id == global rank (inproc scheme)
  void setup_comm(int r, int nranks) {
    std::vector<uint32_t> w{uint32_t(nranks), uint32_t(r)};
    for (int i = 0; i < nranks; ++i) {
      w.push_back(0);             // ip
      w.push_back(0);             // port
      w.push_back(uint32_t(i));   // session
      w.push_back(0);             // max_seg (rx buffer default)
    }
    eng[size_t(r)]->set_comm(w.data(), int(w.size()));
  }

  // plain f32, no compression, copy-only lanes (drills move bytes and
  // synchronize; they never reduce)
  void setup_arith(int r) {
    uint32_t w[7] = {32, 32, 0, 0, 0, 0, 0};
    eng[size_t(r)]->set_arithcfg(w, 7);
  }

  // 15-word descriptors ---------------------------------------------------
  static std::array<uint32_t, 15> desc(Op op, uint32_t count, uint32_t comm,
                                       uint32_t peer, uint32_t tag,
                                       uint64_t addr0, uint64_t addr2) {
    std::array<uint32_t, 15> w{};
    w[0] = uint32_t(op);
    w[1] = count;
    w[2] = comm;
    w[3] = peer;
    w[5] = tag;
    w[9] = uint32_t(addr0 & 0xFFFFFFFFu);
    w[10] = uint32_t(addr0 >> 32);
    w[13] = uint32_t(addr2 & 0xFFFFFFFFu);
    w[14] = uint32_t(addr2 >> 32);
    return w;
  }

  // poll a call to completion on the virtual clock; returns retcode.
  // A schedule where the call never finishes surfaces as a det
  // deadlock/step-budget finding, not a harness hang.
  uint32_t wait_call(int r, uint64_t id, const char* what) {
    uint32_t ret = 0;
    double dur = 0;
    for (int i = 0; i < 200000; ++i) {
      if (eng[size_t(r)]->poll_call(id, &ret, &dur)) return ret;
      det_sleep_for(std::chrono::microseconds(200));
    }
    det::expect(false, what);
    return ret;
  }
};

// mask of bits a call may legally carry after a mid-flight abort
inline bool ok_or_aborted(uint32_t ret) {
  if (ret == 0) return true;
  constexpr uint32_t fence = COMM_ABORTED | RANK_FAILED;
  // once fenced, timeout/seq classification noise from the dying epoch
  // may accompany the fence bits, but the fence itself must be there
  return (ret & fence) != 0;
}

// ---- drill: persistent-plan replay vs invalidate ------------------------
// Both ranks arm a one-call Barrier plan, prove one clean replay, then
// rank 0's replay races an abort of the underlying comm.  Invariants:
// a replay ticket either completes (clean epoch, ret==0 or abort bits)
// or the replay is refused with -2; after the fence settles a fresh
// replay MUST be refused — no schedule may let a fenced epoch replay.
inline void drill_replay_vs_invalidate() {
  DetWorld w(2);
  std::vector<long long> tok(2);
  std::vector<int> plan(2);
  for (int r = 0; r < 2; ++r) {
    auto d = DetWorld::desc(Op::Barrier, 0, 0, 0, 0, 0, 0);
    plan[size_t(r)] = w.eng[size_t(r)]->plan_create(d.data(), 1);
    det::expect(plan[size_t(r)] == 0, "plan_create failed");
  }
  // round 1: clean replay on both ranks
  for (int r = 0; r < 2; ++r) tok[size_t(r)] = w.eng[size_t(r)]->plan_replay(plan[size_t(r)]);
  for (int r = 0; r < 2; ++r) {
    det::expect(tok[size_t(r)] > 0, "clean replay refused");
    uint32_t ret = 1;
    double dur = 0;
    for (int i = 0; i < 200000; ++i) {
      int rc = w.eng[size_t(r)]->plan_poll(tok[size_t(r)], &ret, &dur);
      if (rc == 1) break;
      det::expect(rc == 0, "clean replay token vanished");
      det_sleep_for(std::chrono::microseconds(200));
    }
    det::expect(ret == 0, "clean barrier replay returned error bits");
  }
  // round 2: replays race an abort
  Thread aborter([&] { w.eng[0]->abort_comm(0, 0, true); });
  long long t0 = w.eng[0]->plan_replay(plan[0]);
  long long t1 = w.eng[1]->plan_replay(plan[1]);
  for (int r = 0; r < 2; ++r) {
    long long t = r == 0 ? t0 : t1;
    if (t == -2) continue;  // fenced before the replay queued: legal
    det::expect(t > 0, "raced replay returned bogus token");
    uint32_t ret = 0;
    double dur = 0;
    for (int i = 0; i < 200000; ++i) {
      int rc = w.eng[size_t(r)]->plan_poll(t, &ret, &dur);
      if (rc == 1) break;
      det::expect(rc == 0, "raced replay token vanished");
      det_sleep_for(std::chrono::microseconds(200));
    }
    det::expect(ok_or_aborted(ret), "raced replay: unexpected error bits");
  }
  aborter.join();
  // the fence has settled: a replay on the bumped epoch must refuse
  det::expect(w.eng[0]->plan_replay(plan[0]) == -2,
              "post-abort replay was NOT fenced");
  det::expect(w.eng[1]->plan_replay(plan[1]) == -2,
              "post-abort replay was NOT fenced on the peer");
}

// ---- drill: abort vs traffic --------------------------------------------
// An eager send/recv pair mid-flight while rank 0 aborts the comm.
// Invariants: both calls finalize (no orphaned waiter), and a non-zero
// retcode always carries the fence bits.
inline void drill_abort_vs_traffic() {
  DetWorld w(2);
  uint64_t src = w.eng[0]->alloc(64, 64);
  uint64_t dst = w.eng[1]->alloc(64, 64);
  float payload[8] = {1, 2, 3, 4, 5, 6, 7, 8};
  w.eng[0]->write_mem(src, payload, 32);
  auto sd = DetWorld::desc(Op::Send, 8, 0, 1, 7, src, 0);
  auto rd = DetWorld::desc(Op::Recv, 8, 0, 0, 7, 0, dst);
  uint64_t sid = w.eng[0]->start_call(sd.data());
  uint64_t rid = w.eng[1]->start_call(rd.data());
  Thread aborter([&] { w.eng[0]->abort_comm(0, 0, true); });
  uint32_t sret = w.wait_call(0, sid, "send never finalized under abort");
  uint32_t rret = w.wait_call(1, rid, "recv never finalized under abort");
  aborter.join();
  det::expect(ok_or_aborted(sret), "send retcode lost the fence bits");
  det::expect(ok_or_aborted(rret), "recv retcode lost the fence bits");
  // if the recv claims clean success, the payload must be intact
  if (rret == 0) {
    float got[8] = {0};
    w.eng[1]->read_mem(dst, got, 32);
    det::expect(std::memcmp(got, payload, 32) == 0,
                "recv returned OK but payload is corrupt");
  }
}

// ---- drill: join vs traffic ---------------------------------------------
// A third rank joins (Join/Welcome/StateSync against sponsor 0) while
// ranks 0<->1 run live traffic.  Invariants: the join completes, the
// joiner's comm-id space aligns with the sponsor's, and the racing
// traffic still completes bitwise.
inline void drill_join_vs_traffic() {
  DetWorld w(2);
  int jr = w.hub->add_rank();
  auto joiner = std::make_unique<Engine>(
      uint32_t(jr), 1 << 20,
      std::make_unique<InprocTransport>(w.hub, jr));
  uint64_t src = w.eng[0]->alloc(64, 64);
  uint64_t dst = w.eng[1]->alloc(64, 64);
  float payload[8] = {8, 7, 6, 5, 4, 3, 2, 1};
  w.eng[0]->write_mem(src, payload, 32);
  auto sd = DetWorld::desc(Op::Send, 8, 0, 1, 9, src, 0);
  auto rd = DetWorld::desc(Op::Recv, 8, 0, 0, 9, 0, dst);
  uint64_t sid = w.eng[0]->start_call(sd.data());
  uint64_t rid = w.eng[1]->start_call(rd.data());
  int join_rc = -7;
  Thread joiner_t([&] { join_rc = joiner->join_sync(0, 2000); });
  uint32_t sret = w.wait_call(0, sid, "send never finished under join");
  uint32_t rret = w.wait_call(1, rid, "recv never finished under join");
  joiner_t.join();
  det::expect(join_rc == 0, "join_sync failed against a live sponsor");
  det::expect(joiner->comm_count() == w.eng[0]->comm_count(),
              "joiner comm-id space misaligned with sponsor");
  det::expect(sret == 0 && rret == 0, "traffic failed under a live join");
  float got[8] = {0};
  w.eng[1]->read_mem(dst, got, 32);
  det::expect(std::memcmp(got, payload, 32) == 0,
              "join raced traffic into a corrupt payload");
  joiner->shutdown();
}

// ---- drill: shutdown vs blocked waiters ---------------------------------
// Rank 1 blocks in a receive that no peer will ever satisfy; rank 1's
// two-phase shutdown races it.  Invariants: shutdown returns, the
// blocked call finalizes fast with the fence bits (never left pending
// — the r13 suite-exit segfault class as a schedule invariant), and no
// delivery is mid-flight inside the engine once its transport detached.
inline void drill_shutdown_vs_waiters() {
  DetWorld w(2);
  uint64_t dst = w.eng[1]->alloc(64, 64);
  auto rd = DetWorld::desc(Op::Recv, 8, 0, 0, 5, 0, dst);
  uint64_t rid = w.eng[1]->start_call(rd.data());
  Thread stopper([&] { w.eng[1]->shutdown(); });
  uint32_t ret = 0;
  double dur = 0;
  bool done = false;
  for (int i = 0; i < 200000 && !done; ++i) {
    done = w.eng[1]->poll_call(rid, &ret, &dur);
    if (!done) det_sleep_for(std::chrono::microseconds(200));
  }
  stopper.join();
  det::expect(done, "blocked recv left pending across shutdown");
  det::expect((ret & (COMM_ABORTED | RANK_FAILED)) != 0,
              "shutdown finalized the blocked recv without fence bits");
  det::expect(w.eng[1]->ingress_depth() == 0,
              "a delivery is still inside the engine after shutdown");
}

// ---- sensitivity drill: InprocHub::detach vs a mid-flight delivery ------
// The r13 TSan finding as a model-checking invariant: after detach()
// returns, no delivery may still execute the detached slot's sink (the
// caller is about to destroy the engine behind it).  The fixed hub
// drains in-flight deliveries; the ACCL_FAULT_DETACH_RACE build skips
// the drain and the explorer must find a schedule that fires
// `delivery into detached slot`.
inline void drill_detach_race() {
  auto hub = std::make_shared<InprocHub>(2);
  std::atomic<bool> torn{false};
  hub->attach(1, [&](Message&&) {
    det::expect(!torn.load(), "delivery into detached slot");
  });
  Thread sender([&] {
    Message m;
    m.hdr.msg_type = uint8_t(MsgType::Heartbeat);
    hub->deliver(1, std::move(m));
  });
  hub->detach(1);
  torn.store(true);  // the engine behind the slot is now "destroyed"
  sender.join();
}

// ---- registry ------------------------------------------------------------
// ---- drill: concurrent sub-communicator allgathers ----------------------
// The ROADMAP item 2 KNOWN ISSUE's shape, scaled for exhaustive
// exploration: a 2x2 grid of 2-rank sub-comms (rows {0,1}/{2,3},
// columns {0,2}/{1,3}) over one 4-rank world; every rank allgathers on
// its row comm then on its column comm, so row completions on fast
// ranks overlap column starts on slow ones — the cross-comm
// concurrency the 8-rank emu wedge (intermittent RECEIVE_TIMEOUT)
// arises from, with all four comms contending for ONE small rx pool.
// Invariant: every allgather completes CLEAN on every schedule — a
// schedule that classifies a timeout/seq error is the wedge, minimized
// into a replayable artifact.
inline void subcomm_allgather_impl(int P) {
  DetWorld w(P);
  // sub-comm uploads in identical id order on every engine; ranks
  // outside a group upload an inert self-comm so engine-side comm ids
  // stay aligned with the wire protocol's (the driver's
  // reserve_communicator discipline)
  auto sub = [&](int r, const std::vector<int>& m) {
    auto it = std::find(m.begin(), m.end(), r);
    if (it == m.end()) {
      std::vector<uint32_t> ww{1, 0, 0, 0, uint32_t(r), 0};
      w.eng[size_t(r)]->set_comm(ww.data(), int(ww.size()));
      return;
    }
    std::vector<uint32_t> ww{uint32_t(m.size()),
                             uint32_t(it - m.begin())};
    for (int g : m) {
      ww.push_back(0);            // ip
      ww.push_back(0);            // port
      ww.push_back(uint32_t(g));  // session == global rank
      ww.push_back(0);            // max_seg
    }
    w.eng[size_t(r)]->set_comm(ww.data(), int(ww.size()));
  };
  // rows of width P/2, columns of height 2 — at P=8 exactly the
  // ROADMAP repro's comm family (two 4-rank rows, four 2-rank cols)
  const int W = P / 2;
  std::vector<std::vector<int>> rows(2);
  std::vector<std::vector<int>> cols(static_cast<size_t>(W));
  for (int r = 0; r < P; ++r) {
    rows[size_t(r / W)].push_back(r);
    cols[size_t(r % W)].push_back(r);
  }
  std::vector<uint32_t> row_comm(static_cast<size_t>(P), 0u);
  std::vector<uint32_t> col_comm(static_cast<size_t>(P), 0u);
  uint32_t cid = 1;
  for (auto& m : rows) {
    for (int r = 0; r < P; ++r) sub(r, m);
    for (int g : m) row_comm[size_t(g)] = cid;
    ++cid;
  }
  for (auto& m : cols) {
    for (int r = 0; r < P; ++r) sub(r, m);
    for (int g : m) col_comm[size_t(g)] = cid;
    ++cid;
  }
  // row allgather 128 elems (512 B = 2 rx segments per slice), column
  // 256 (4 segments) — the repro's small-then-large shape with real
  // multi-segment relay pressure on the 4 x 256 B rx pool ALL comms
  // share (the suspected wedge mechanism: cross-comm pool pinning)
  const uint32_t row_n = 128, col_n = 256;
  std::vector<Thread> ranks;
  for (int r = 0; r < P; ++r) {
    ranks.emplace_back(Thread([&w, &rows, &cols, &row_comm, &col_comm,
                               r, W, row_n, col_n] {
      Engine& e = *w.eng[size_t(r)];
      for (int phase = 0; phase < 2; ++phase) {
        uint32_t comm = phase == 0 ? row_comm[size_t(r)]
                                   : col_comm[size_t(r)];
        uint32_t n = phase == 0 ? row_n : col_n;
        uint32_t members = phase == 0 ? uint32_t(W) : 2u;
        uint64_t src = e.alloc(n * 4, 64);
        uint64_t dst = e.alloc(uint64_t(n) * members * 4, 64);
        auto d = DetWorld::desc(Op::Allgather, n, comm, 0, TAG_ANY,
                                src, dst);
        uint64_t id = e.start_call(d.data());
        uint32_t ret = w.wait_call(r, id, "sub-comm allgather never "
                                          "completed");
        // On a schedule with timeout injections a non-zero retcode can
        // be legitimate: an injected expiry IS a slow peer, and a
        // RECEIVE_TIMEOUT (or the cascade it triggers) is the correct
        // classification.  The wedge invariant lives below: a timeout
        // classified while the expected segment sat STAGED is never
        // legitimate, injected or not.
        det::expect(ret == 0 || det::timeout_injections() > 0,
                    phase == 0 ? "row allgather classified an error "
                                 "(the sub-comm wedge)"
                               : "column allgather classified an "
                                 "error (the sub-comm wedge)");
        e.free_addr(src);
        e.free_addr(dst);
      }
    }));
  }
  for (auto& t : ranks) t.join();
  // THE wedge invariant, schedule-independent: no rank may ever have
  // classified RECEIVE_TIMEOUT while the segment it was seeking sat in
  // the rx staging queue (cross-comm pool pinning — data arrived, the
  // pool never surfaced it).  The ACCL_FAULT_SUBCOMM_WEDGE build
  // reverts the staged-rescue fix and the explorer must REDISCOVER
  // this via a timeout injection under pool pressure.
  uint64_t wedged = 0;
  for (auto& e : w.eng) wedged += e->wedged_timeouts();
  det::expect(wedged == 0,
              "sub-comm wedge: RECEIVE_TIMEOUT classified while the "
              "expected segment sat staged (cross-comm rx-pool pinning)");
}

inline void drill_subcomm_allgather() { subcomm_allgather_impl(4); }
// the full ROADMAP repro scale (heavier per schedule — run with an
// explicit budget, not in the default --ci sweep)
inline void drill_subcomm_allgather8() { subcomm_allgather_impl(8); }

// ---- drill: alltoallv's descriptor shape ---------------------------------
// Three ranks run one alltoallv: the count operand in the OP1 slot, the
// count result's address in the root and function words.  Counts are
// skewed (a zero pair, an all-eager pair, a 5-row pair of multi-segment
// eager traffic on the 4 x 256 B pool) so the count trade and the rows
// interleave with pool pressure.  Invariants on every schedule: the call
// finalizes cleanly (or with a timeout an injection caused), and every
// rank holds each peer's count and rows bit for bit.
inline void drill_alltoallv_counts() {
  constexpr int P = 3;
  constexpr uint32_t row = 16;  // f32 elements (64 B a row)
  const uint32_t counts[P][P] = {{1, 0, 5}, {2, 1, 0}, {0, 3, 1}};
  DetWorld w(P);
  std::vector<Thread> ranks;
  for (int r = 0; r < P; ++r) {
    ranks.emplace_back(Thread([&w, &counts, r] {
      Engine& e = *w.eng[size_t(r)];
      uint32_t rows = 0;
      for (int j = 0; j < P; ++j) rows += counts[r][j];
      std::vector<float> payload(size_t(rows) * row);
      for (size_t k = 0; k < payload.size(); ++k)
        payload[k] = float(r * 1000 + int(k));
      uint64_t src = e.alloc(std::max<uint64_t>(payload.size(), 1) * 4, 64);
      uint64_t dst = e.alloc(uint64_t(P) * 6 * row * 4, 64);
      uint64_t sc = e.alloc(P * 4, 64);
      uint64_t rc = e.alloc(P * 4, 64);
      e.write_mem(src, payload.data(), payload.size() * 4);
      int32_t mine[P];
      for (int j = 0; j < P; ++j) mine[j] = int32_t(counts[r][j]);
      e.write_mem(sc, mine, sizeof(mine));
      auto d = DetWorld::desc(Op::Alltoallv, row, 0, uint32_t(rc), TAG_ANY,
                              src, dst);
      d[4] = uint32_t(rc >> 32);
      d[11] = uint32_t(sc & 0xFFFFFFFFu);
      d[12] = uint32_t(sc >> 32);
      uint64_t id = e.start_call(d.data());
      uint32_t ret = w.wait_call(r, id, "alltoallv never completed");
      det::expect(ret == 0 || det::timeout_injections() > 0,
                  "alltoallv classified an error");
      if (ret == 0) {
        int32_t got[P];
        e.read_mem(rc, got, sizeof(got));
        uint64_t at = 0;
        for (int i = 0; i < P; ++i) {
          det::expect(got[i] == int32_t(counts[i][r]),
                      "alltoallv receive count differs from the peer's");
          uint32_t off = 0;
          for (int j = 0; j < r; ++j) off += counts[i][j];
          std::vector<float> rows_in(size_t(counts[i][r]) * row);
          e.read_mem(dst + at * 4, rows_in.data(), rows_in.size() * 4);
          for (size_t k = 0; k < rows_in.size(); ++k)
            det::expect(rows_in[k] == float(i * 1000 + int(off * row + k)),
                        "alltoallv row landed wrong");
          at += rows_in.size();
        }
      }
      e.free_addr(src);
      e.free_addr(dst);
      e.free_addr(sc);
      e.free_addr(rc);
    }));
  }
  for (auto& t : ranks) t.join();
}

// ---- sensitivity drill: a submitted call that never finalizes -----------
// Exercises the liveness invariant directly: two workers each take a
// live token (one per "submitted call"); one finalizes, the other
// returns without handing its token back — the modeled stuck call.  On
// EVERY schedule the run must end with the stuck-progress finding
// (run it with --expect-finding).  The engine drills prove the
// negative: all five finalize paths return their token, so clean runs
// report zero leaks.
inline void drill_liveness_leak() {
  std::atomic<int> done{0};
  Thread good([&] {
    det::live_begin();
    det_sleep_for(std::chrono::microseconds(50));
    det::live_end();
    done.fetch_add(1);
  });
  Thread stuck([&] {
    det::live_begin();  // never returned
    done.fetch_add(1);
  });
  good.join();
  stuck.join();
  det::expect(done.load() == 2, "liveness workers never ran");
}

inline const std::map<std::string, std::function<void()>>& registry() {
  static const auto* m = new std::map<std::string, std::function<void()>>{
      {"replay_vs_invalidate", drill_replay_vs_invalidate},
      {"abort_vs_traffic", drill_abort_vs_traffic},
      {"join_vs_traffic", drill_join_vs_traffic},
      {"shutdown_vs_waiters", drill_shutdown_vs_waiters},
      {"detach_race", drill_detach_race},
      {"subcomm_allgather", drill_subcomm_allgather},
      {"subcomm_allgather8", drill_subcomm_allgather8},
      {"liveness_leak", drill_liveness_leak},
      {"alltoallv_counts", drill_alltoallv_counts},
  };
  return *m;
}

}  // namespace drills
}  // namespace accl
