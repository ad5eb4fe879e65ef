// Engine implementation: event loop, protocol primitives, collective
// schedules.  See engine.hpp for the reference mapping.
#include "engine.hpp"

#include <algorithm>
#include <cassert>
#include <cstdio>
#include <set>

namespace accl {

using namespace std::chrono;
using std::chrono::nanoseconds;

// Tag reserved for barrier traffic (the reference exchanges empty
// rendezvous notifications instead, fw :2077-2120; a reserved eager tag
// keeps the same synchronization with the socket transport).
static constexpr uint32_t BARRIER_TAG = 0xBA771E12u;
// Stream ids >= 9 address compute-kernel streams (reference: accl.cpp:197).
static constexpr uint32_t FIRST_KRNL_STREAM = 9;

Engine::Engine(uint32_t global_rank, uint64_t devmem_bytes,
               std::unique_ptr<Transport> transport)
    : global_rank_(global_rank),
      devicemem_(devmem_bytes),
      host_region_bytes_(devmem_bytes / 2),
      transport_(std::move(transport)) {
  free_spans_[0x1000] = devmem_bytes - 0x1000;
  // hostmem_ is committed lazily on first alloc_host: most worlds never
  // use host-only buffers and should not pay half a devmem of RSS.
  // The tables behind comms_/arithcfgs_ are heap-pinned (unique_ptr
  // slots), so growth can never move a table the engine loop holds a
  // pointer into; the reserve only avoids pointer-vector churn.
  comms_.reserve(64);
  arithcfgs_.reserve(64);
  transport_->start([this](Message&& m) { ingress(std::move(m)); });
  loop_thread_ = Thread([this] { loop(); });
  egress_thread_ = Thread([this] { egress_loop(); });
  delay_thread_ = Thread([this] { delay_loop(); });
}

Engine::~Engine() { shutdown(); }

void Engine::shutdown() {
  if (stopped_.exchange(true)) return;  // idempotent
  running_ = false;
  cmd_q_.close();
  completions_.close();
  pending_addrs_.close();
  krnl_in_.close();  // unblock drain_krnl_to/send-from-stream waits
  if (loop_thread_.joinable()) loop_thread_.join();
  {
    // chaos-delayed messages still pending at teardown are dropped (the
    // world is going away; the peer's receive machinery is too)
    MutexLock g(delay_mu_);
    delay_running_ = false;
    delayed_.clear();
  }
  delay_cv_.notify_all();
  if (delay_thread_.joinable()) delay_thread_.join();
  {
    // drain staged segments so tail messages of completed calls are not
    // lost, then stop the writer
    UniqueLock g(egress_mu_);
    cv_wait_for_pred(egress_cv_, g, std::chrono::seconds(2),
                     [&]() ACCL_REQUIRES(egress_mu_) { return egress_q_.empty(); });
    egress_running_ = false;
  }
  egress_cv_.notify_all();
  if (egress_thread_.joinable()) egress_thread_.join();
  transport_->stop();
  // unblock host-side stream readers parked in pop_stream
  {
    MutexLock g(streams_mu_);
    for (auto& [strm, fifo] : streams_)
      if (fifo) fifo->close();
  }
  // finalize every call the stopped loop left pending, so a host
  // waiter polling its id returns NOW instead of burning its full wait
  // budget against a dead engine (and then touching freed memory — the
  // suite-exit segfault)
  {
    MutexLock g(results_mu_);
    for (auto& [id, r] : results_) {
      if (!r.done) {
        r.retcode = COMM_ABORTED | RANK_FAILED;
        r.done = true;
        det_live_end();
      }
    }
  }
}

// ---------------------------------------------------------------------------
// host-facing config
// ---------------------------------------------------------------------------
void Engine::cfg_rx_buffers(uint32_t nbufs, uint64_t bufsize) {
  rx_.configure(nbufs, bufsize);
}

int Engine::set_comm(const uint32_t* words, int nwords) {
  // build the table FULLY before publication: rows of a published
  // table are immutable and may be read lock-free (see CommTable)
  auto t = std::make_unique<CommTable>();
  t->size = words[0];
  t->local = words[1];
  if (nwords < int(2 + 4 * t->size)) return -1;
  for (uint32_t i = 0; i < t->size; ++i) {
    CommTable::Row r;
    r.ip = words[2 + 4 * i];
    r.port = words[3 + 4 * i];
    r.session = words[4 + 4 * i];
    r.max_seg = words[5 + 4 * i];
    t->rows.push_back(r);
  }
  t->inbound_seq.assign(t->size, 0);
  t->outbound_seq.assign(t->size, 0);
  MutexLock g(cfg_mu_);
  comms_.push_back(std::move(t));
  return int(comms_.size()) - 1;
}

int Engine::set_arithcfg(const uint32_t* words, int nwords) {
  auto a = std::make_unique<ArithCfgN>();
  a->ubits = words[0];
  a->cbits = words[1];
  a->ratio_log = words[2];
  a->compressor = words[3];
  a->decompressor = words[4];
  a->arith_compressed = words[5];
  uint32_t nlanes = words[6];
  for (uint32_t i = 0; i < nlanes && int(7 + i) < nwords; ++i)
    a->lanes.push_back(words[7 + i]);
  // r17 append-only trailing words (arithconfig.py to_words): block
  // geometry of the int8 block-scaled wire lane + error-feedback flag.
  // Older 7+nlanes-word uploads simply leave the defaults (0 = cast).
  if (int(7 + nlanes) < nwords) a->block = words[7 + nlanes];
  if (int(8 + nlanes) < nwords) a->error_feedback = words[8 + nlanes];
  if (a->block > I8_BLOCK_MAX) return -1;
  MutexLock g(cfg_mu_);
  arithcfgs_.push_back(std::move(a));
  return int(arithcfgs_.size()) - 1;
}

// ---------------------------------------------------------------------------
// device memory (first-fit free-list allocator over the flat devicemem,
// playing the role of the reference's per-bank XRT BO allocation)
// ---------------------------------------------------------------------------
// One first-fit body for both address spaces; `tag` is OR'd into the
// recorded and returned address (0 for device, HOST_ADDR_BIT for host).
static uint64_t alloc_first_fit(std::map<uint64_t, uint64_t>& spans,
                                std::map<uint64_t, uint64_t>& sizes,
                                uint64_t nbytes, uint64_t align,
                                uint64_t tag) {
  if (align == 0) align = 64;
  if (nbytes == 0) nbytes = align;
  for (auto it = spans.begin(); it != spans.end(); ++it) {
    uint64_t base = it->first, size = it->second;
    uint64_t aligned = (base + align - 1) / align * align;
    uint64_t pad = aligned - base;
    if (size < pad + nbytes) continue;
    spans.erase(it);
    if (pad) spans[base] = pad;
    uint64_t rest = size - pad - nbytes;
    if (rest) spans[aligned + nbytes] = rest;
    sizes[aligned | tag] = nbytes;
    return aligned | tag;
  }
  return 0;  // OOM
}

uint64_t Engine::alloc(uint64_t nbytes, uint64_t align) {
  MutexLock g(mem_mu_);
  return alloc_first_fit(free_spans_, alloc_sizes_, nbytes, align, 0);
}

// Host-region allocator: same first-fit discipline over the host span
// map; returned addresses carry HOST_ADDR_BIT.
uint64_t Engine::alloc_host(uint64_t nbytes, uint64_t align) {
  MutexLock g(mem_mu_);
  if (hostmem_.empty()) {
    hostmem_.resize(host_region_bytes_);
    host_spans_[0x1000] = hostmem_.size() - 0x1000;
  }
  return alloc_first_fit(host_spans_, alloc_sizes_, nbytes, align,
                         HOST_ADDR_BIT);
}

void Engine::free_addr(uint64_t addr) {
  MutexLock g(mem_mu_);
  auto it = alloc_sizes_.find(addr);
  if (it == alloc_sizes_.end()) return;
  uint64_t size = it->second;
  alloc_sizes_.erase(it);
  auto& spans = (addr & HOST_ADDR_BIT) ? host_spans_ : free_spans_;
  addr &= ~HOST_ADDR_BIT;
  // insert + merge with neighbors
  auto next = spans.lower_bound(addr);
  if (next != spans.end() && addr + size == next->first) {
    size += next->second;
    next = spans.erase(next);
  }
  if (next != spans.begin()) {
    auto prev = std::prev(next);
    if (prev->first + prev->second == addr) {
      prev->second += size;
      return;
    }
  }
  spans[addr] = size;
}

// Host-side reads/writes take mem_mu_ like every other memory toucher
// (before r14 they ran bare — an unlocked-read class the TSA lane now
// rejects: a host read racing the hostmem_ lazy commit in alloc_host
// observed a vector mid-resize).
bool Engine::read_mem(uint64_t addr, void* dst, uint64_t n) {
  MutexLock g(mem_mu_);
  auto& region = (addr & HOST_ADDR_BIT) ? hostmem_ : devicemem_;
  addr &= ~HOST_ADDR_BIT;
  if (addr + n > region.size()) return false;
  std::memcpy(dst, region.data() + addr, n);
  return true;
}

bool Engine::write_mem(uint64_t addr, const void* src, uint64_t n) {
  MutexLock g(mem_mu_);
  auto& region = (addr & HOST_ADDR_BIT) ? hostmem_ : devicemem_;
  addr &= ~HOST_ADDR_BIT;
  if (addr + n > region.size()) return false;
  std::memcpy(region.data() + addr, src, n);
  return true;
}

uint8_t* Engine::mem(uint64_t addr, uint64_t n) {
  auto& region = (addr & HOST_ADDR_BIT) ? hostmem_ : devicemem_;
  bool host = addr & HOST_ADDR_BIT;
  addr &= ~HOST_ADDR_BIT;
  if (addr + n > region.size() || (n > 0 && addr == 0)) {
    // schedule addressing bug: flag it AND make it loud — the sticky
    // error alone surfaces at retcode-decode distance, far from the
    // faulting schedule step (round-2 review weak #6/#7).  Writes land
    // in a thread-local bitbucket so the engine stays memory-safe.
    sticky_err_ |= DMA_SIZE_ERROR;
    std::fprintf(stderr,
                 "[accl engine %u] OOB %s-mem access addr=%#llx n=%llu "
                 "(region %llu bytes) — DMA_SIZE_ERROR\n",
                 global_rank_, host ? "host" : "device",
                 (unsigned long long)addr, (unsigned long long)n,
                 (unsigned long long)region.size());
    static thread_local std::vector<uint8_t> bitbucket;
    bitbucket.assign(std::max<uint64_t>(n, 64), 0);
    return bitbucket.data();
  }
  return region.data() + addr;
}

// ---------------------------------------------------------------------------
// call path
// ---------------------------------------------------------------------------
uint64_t Engine::start_call(const uint32_t* w15) {
  CallDesc c;
  std::copy(w15, w15 + 15, c.w.begin());
  c.id = next_call_id_++;
  {
    MutexLock g(results_mu_);
    results_[c.id] = CallResult{};
  }
  det_live_begin();  // liveness token, returned when the call finalizes
  cmd_q_.push(c);
  // a submission racing shutdown(): the finalize sweep may already
  // have run, leaving this call pending forever (its waiter would burn
  // the full wait budget against a dead engine) — finalize inline
  if (stopped_.load()) {
    MutexLock g(results_mu_);
    auto& r = results_[c.id];
    if (!r.done) {
      r.retcode = COMM_ABORTED | RANK_FAILED;
      r.done = true;
      det_live_end();
    }
  }
  return c.id;
}

bool Engine::poll_call(uint64_t id, uint32_t* retcode, double* duration_ns) {
  MutexLock g(results_mu_);
  auto it = results_.find(id);
  if (it == results_.end() || !it->second.done) return false;
  if (retcode) *retcode = it->second.retcode;
  if (duration_ns) *duration_ns = it->second.duration_ns;
  results_.erase(it);
  return true;
}

// ---------------------------------------------------------------------------
// persistent collective plans (r12): parse once, replay whole batches
// ---------------------------------------------------------------------------
int Engine::plan_create(const uint32_t* words, int ncalls) {
  if (!words || ncalls <= 0) return -1;
  EnginePlan plan;
  std::set<uint32_t> comms;
  for (int i = 0; i < ncalls; ++i) {
    std::array<uint32_t, 15> w{};
    std::copy(words + i * 15, words + (i + 1) * 15, w.begin());
    Op op = static_cast<Op>(w[0]);
    if (op != Op::Config && op != Op::Nop && op != Op::Copy &&
        op != Op::Combine)
      comms.insert(w[2]);
    plan.descs.push_back(w);
  }
  for (uint32_t c : comms) {
    if (abort_err(c)) return -1;  // arming against a fenced comm
    plan.comm_epochs.emplace_back(c, epoch_of(c));
  }
  MutexLock g(plans_mu_);
  plans_.push_back(std::move(plan));
  return int(plans_.size()) - 1;
}

long long Engine::plan_replay(int plan_id) {
  std::vector<std::array<uint32_t, 15>> descs;
  {
    MutexLock g(plans_mu_);
    if (plan_id < 0 || plan_id >= int(plans_.size())) return -1;
    EnginePlan& p = plans_[size_t(plan_id)];
    if (!p.valid) return -2;
    // epoch fence: any abort/epoch bump since arm invalidates the
    // plan — a replay must never run on a fenced world
    for (auto& [comm, ep] : p.comm_epochs) {
      if (epoch_of(comm) != ep || abort_err(comm)) {
        p.valid = false;
        return -2;
      }
    }
    descs = p.descs;  // cheap: 15 words per call
  }
  std::vector<uint64_t> ids;
  ids.reserve(descs.size());
  for (auto& w : descs) ids.push_back(start_call(w.data()));
  plan_replays_.fetch_add(1);
  MutexLock g(plans_mu_);
  long long token = next_plan_token_++;
  plan_tokens_[token] = std::move(ids);
  // opportunistic reaper: tokens abandoned without a successful poll
  // (dropped async tickets, timed-out waits) would otherwise pin their
  // id vectors AND the calls' CallResults forever.  Reclaim fully-done
  // stale tokens oldest-first once the map grows past its watermark —
  // bounds the leak at ~256 in-flight/abandoned replays.
  if (plan_tokens_.size() > 256) {
    MutexLock r(results_mu_);
    for (auto it = plan_tokens_.begin();
         it != plan_tokens_.end() && plan_tokens_.size() > 256;) {
      if (it->first == token) break;  // never reap the fresh token
      bool all_done = true;
      for (uint64_t id : it->second) {
        auto rit = results_.find(id);
        if (rit != results_.end() && !rit->second.done) {
          all_done = false;
          break;
        }
      }
      if (all_done) {
        for (uint64_t id : it->second) results_.erase(id);
        it = plan_tokens_.erase(it);
      } else {
        ++it;
      }
    }
  }
  return token;
}

int Engine::plan_poll(long long token, uint32_t* retcode,
                      double* duration_ns) {
  std::vector<uint64_t> ids;
  {
    MutexLock g(plans_mu_);
    auto it = plan_tokens_.find(token);
    if (it == plan_tokens_.end()) return -1;
    ids = it->second;
  }
  uint32_t ret = 0;
  double dur = 0.0;
  {
    MutexLock g(results_mu_);
    for (uint64_t id : ids) {
      auto it = results_.find(id);
      if (it == results_.end() || !it->second.done) return 0;
    }
    for (uint64_t id : ids) {
      auto it = results_.find(id);
      ret |= it->second.retcode;
      dur += it->second.duration_ns;
      results_.erase(it);
    }
  }
  {
    MutexLock g(plans_mu_);
    plan_tokens_.erase(token);
  }
  if (retcode) *retcode = ret;
  if (duration_ns) *duration_ns = dur;
  return 1;
}

void Engine::invalidate_plans(int comm_id) {
  MutexLock g(plans_mu_);
  for (EnginePlan& p : plans_) {
    bool hit = comm_id < 0;
    for (auto& [comm, ep] : p.comm_epochs)
      if (comm_id >= 0 && comm == uint32_t(comm_id)) hit = true;
    if (hit) {
      p.valid = false;
      // an invalid plan can never replay again: free its descriptor
      // storage now (slots are vector indices, so the slot stays)
      p.descs.clear();
      p.descs.shrink_to_fit();
    }
  }
}

void Engine::plan_release(int plan_id) {
  MutexLock g(plans_mu_);
  if (plan_id < 0 || plan_id >= int(plans_.size())) return;
  EnginePlan& p = plans_[size_t(plan_id)];
  p.valid = false;
  p.descs.clear();
  p.descs.shrink_to_fit();
}

int Engine::plan_count() const {
  MutexLock g(plans_mu_);
  int n = 0;
  for (const EnginePlan& p : plans_)
    if (p.valid) ++n;
  return n;
}

// ---------------------------------------------------------------------------
// engine telemetry snapshot (r14): the versioned flat export behind
// capi accl_engine_stats.  FIELD ORDER IS THE ABI — append only, and
// keep ENGINE_STATS_FIELDS_V3 in accl_tpu/observability/telemetry.py
// in lockstep (v2 appends link_rows, r15; v3 appends the quantized
// wire accounting pair, r17).
// ---------------------------------------------------------------------------
int Engine::engine_stats(uint64_t* out, int cap) {
  uint64_t egress_depth = 0;
  {
    MutexLock g(egress_mu_);
    egress_depth = egress_q_.size();
  }
  uint64_t plans_live = 0, plan_tokens = 0;
  {
    MutexLock g(plans_mu_);
    for (const EnginePlan& p : plans_)
      if (p.valid) ++plans_live;
    plan_tokens = plan_tokens_.size();
  }
  uint64_t link_rows = 0;
  {
    MutexLock g(link_mu_);
    link_rows = links_.size();
  }
  const uint64_t fields[] = {
      // -- retransmit store --
      retrans_used_.load(),        // 0 retrans_store_depth
      retrans_evictions_.load(),   // 1 retrans_store_evictions
      retrans_sent_.load(),        // 2 retrans_sent
      nacks_tx_.load(),            // 3 nacks_tx
      nacks_rx_.load(),            // 4 nacks_rx
      fenced_drops_.load(),        // 5 fenced_drops
      // -- rx pool --
      rx_.occupancy(),             // 6 rx_occupancy
      rx_.occupancy_hwm(),         // 7 rx_occupancy_hwm
      rx_.staged(),                // 8 rx_staged
      rx_.staged_hwm(),            // 9 rx_staged_hwm
      rx_.pending(),               // 10 rx_pending
      // -- transport queues --
      egress_depth,                // 11 egress_depth
      egress_hwm_.load(),          // 12 egress_hwm
      uint64_t(std::max(ingress_depth_.load(), 0)),  // 13 ingress_depth
      // -- seek discipline --
      seeks_.load(),               // 14 seeks
      seek_misses_.load(),         // 15 seek_misses
      // -- persistent plans --
      plans_live,                  // 16 plans_live
      plan_tokens,                 // 17 plan_tokens
      plan_replays_.load(),        // 18 plan_replays
      // -- wire validation --
      frames_accepted_.load(),     // 19 wire_accepted_frames
      frames_rejected_.load(),     // 20 wire_rejected_frames
      // -- egress traffic --
      tx_msgs_.load(),             // 21 tx_msgs
      tx_payload_bytes_.load(),    // 22 tx_payload_bytes
      // -- elastic membership --
      joins_sponsored_.load(),     // 23 joins_sponsored
      joins_completed_.load(),     // 24 joins_completed
      // -- per-link wire telemetry (v2, r15) --
      link_rows,                   // 25 link_rows
      // -- quantized wire accounting (v3, r17) --
      compressed_tx_bytes_.load(),          // 26 compressed_tx_bytes
      compressed_tx_logical_bytes_.load(),  // 27 compressed_tx_logical_bytes
  };
  const int total = int(sizeof(fields) / sizeof(fields[0]));
  if (out) {
    int n = cap < total ? (cap < 0 ? 0 : cap) : total;
    for (int i = 0; i < n; ++i) out[i] = fields[i];
  }
  return total;
}

// ---------------------------------------------------------------------------
// per-link wire telemetry (r15): (comm, peer) counter rows behind capi
// accl_engine_link_stats.  ROW FIELD ORDER IS THE ABI — keep
// LINK_STATS_FIELDS_V2 in accl_tpu/observability/telemetry.py in
// lockstep.  Bump helpers are leaf-lock one-liners so the egress/
// ingress funnels pay one uncontended lock + map find per message.
// ---------------------------------------------------------------------------
void Engine::link_count(uint32_t comm, uint32_t peer,
                        uint64_t LinkCounters::*field, uint64_t add) {
  if (!link_peer_ok(comm, peer)) return;
  MutexLock g(link_mu_);
  links_[{comm, peer}].*field += add;
}

void Engine::link_tx(uint32_t comm, uint32_t peer, uint64_t bytes) {
  if (!link_peer_ok(comm, peer)) return;
  MutexLock g(link_mu_);
  LinkCounters& c = links_[{comm, peer}];
  c.tx_msgs += 1;
  c.tx_bytes += bytes;
}

void Engine::link_rx(uint32_t comm, uint32_t peer, uint64_t bytes) {
  if (!link_peer_ok(comm, peer)) return;
  MutexLock g(link_mu_);
  LinkCounters& c = links_[{comm, peer}];
  c.rx_msgs += 1;
  c.rx_bytes += bytes;
}

int Engine::link_stats(uint64_t* out, int cap) {
  MutexLock g(link_mu_);
  const int total = int(links_.size()) * kLinkStatsStride;
  if (out && cap > 0) {
    // whole rows only: a short buffer truncates at a row boundary so
    // the decoder can never mis-slice a torn row
    int rows = std::min(cap, total) / kLinkStatsStride;
    int i = 0;
    for (const auto& [key, c] : links_) {
      if (i >= rows) break;
      uint64_t* row = out + ptrdiff_t(i) * kLinkStatsStride;
      row[0] = key.first;       // comm
      row[1] = key.second;      // peer (comm-local rank)
      row[2] = c.tx_msgs;
      row[3] = c.tx_bytes;
      row[4] = c.rx_msgs;
      row[5] = c.rx_bytes;
      row[6] = c.retrans_sent;
      row[7] = c.nacks_tx;
      row[8] = c.nacks_rx;
      row[9] = c.fenced_drops;
      row[10] = c.seeks;
      row[11] = c.seek_wait_ns;
      row[12] = c.comp_tx_bytes;
      ++i;
    }
  }
  return total;
}

void Engine::push_krnl(const uint8_t* data, uint64_t n) {
  krnl_in_.push(std::vector<uint8_t>(data, data + n));
}

std::shared_ptr<Fifo<std::vector<uint8_t>>> Engine::stream_for(uint32_t strm) {
  MutexLock g(streams_mu_);
  auto& slot = streams_[strm];
  if (!slot) slot = std::make_shared<Fifo<std::vector<uint8_t>>>();
  return slot;
}

bool Engine::pop_stream(uint32_t strm, uint8_t* dst, uint64_t cap,
                        uint64_t* got, int timeout_ms) {
  auto v = stream_for(strm)->pop_wait(milliseconds(timeout_ms));
  if (!v) return false;
  uint64_t n = std::min<uint64_t>(cap, v->size());
  if (n) std::memcpy(dst, v->data(), n);
  if (got) *got = n;
  return true;
}

// ---------------------------------------------------------------------------
// egress funnel — every wire message leaves through here so the test
// harness can inject one-shot faults (drop / duplicate / seqn corruption)
// against the detection machinery (SURVEY §5 failure detection)
// ---------------------------------------------------------------------------
void Engine::send_out(uint32_t session, Message&& msg) {
  // kill-rank chaos: a dead engine transmits nothing — its peers see
  // exactly what a crashed process would leave behind
  if (killed_.load()) return;
  // egress accounting (tx_stats): proves in tests whether a payload
  // actually crossed the wire (the p2p direct path must not add here)
  tx_msgs_.fetch_add(1);
  tx_payload_bytes_.fetch_add(msg.payload.size());
  // fault resolution: the one-shot injector forces the draw for the
  // next message (legacy inject_fault semantics, any message type); the
  // seeded chaos plan draws probabilistically for eager dataplane
  // segments only — rendezvous/abort/NACK control is not a chaos target,
  // so recovery under a seeded plan stays deterministic.
  uint32_t kind = fault_.exchange(0);
  if (kind == 0 && msg.hdr.msg_type == uint8_t(MsgType::EgrMsg))
    kind = chaos_draw();
  switch (kind) {
    case 1:  // drop: the message never reaches the wire
      return;
    case 2: {  // duplicate: deliver twice with identical header/seqn
      Message dup;
      dup.hdr = msg.hdr;
      dup.payload = msg.payload;
      stage_egress(session, std::move(dup));
      break;
    }
    case 3:  // corrupt the sequence number
      msg.hdr.seqn += 7;
      break;
    case 4: {  // delay: hold the message past its siblings (reordering)
      uint32_t us;
      {
        MutexLock g(chaos_mu_);
        us = chaos_.delay_us ? chaos_.delay_us : 2000;
      }
      MutexLock g(delay_mu_);
      if (delay_running_) {
        delayed_.push_back(Delayed{
            steady_clock::now() + microseconds(us), session,
            std::move(msg)});
        delay_cv_.notify_all();
        return;
      }
      break;  // teardown already underway: deliver immediately
    }
    default:
      break;
  }
  stage_egress(session, std::move(msg));
}

// Background releaser for chaos-delayed messages: re-stages each held
// segment once its deadline passes, producing REAL reordering on the
// wire (a FIFO stall would delay everything behind it and never open a
// sequence gap for the NACK path to close).
void Engine::delay_loop() {
  UniqueLock lk(delay_mu_);
  while (delay_running_) {
    if (delayed_.empty()) {
      delay_cv_.wait(lk);
      continue;
    }
    auto it = std::min_element(
        delayed_.begin(), delayed_.end(),
        [](const Delayed& a, const Delayed& b) { return a.release < b.release; });
    auto now = steady_clock::now();
    if (it->release > now) {
      cv_wait_until_point(delay_cv_, lk, it->release);
      continue;
    }
    Delayed d = std::move(*it);
    delayed_.erase(it);
    lk.unlock();
    stage_egress(d.session, std::move(d.msg));
    lk.lock();
  }
}

uint32_t Engine::chaos_draw() {
  MutexLock g(chaos_mu_);
  if (!chaos_.armed) return 0;
  // xorshift64*: deterministic per (seed, draw index) — a seeded plan
  // replays the same fault schedule run after run
  uint64_t x = chaos_.rng;
  x ^= x >> 12;
  x ^= x << 25;
  x ^= x >> 27;
  chaos_.rng = x;
  uint32_t u = uint32_t((x * 0x2545F4914F6CDD1Dull) >> 40) % 1000000u;
  if (u < chaos_.drop_ppm) return 1;
  u -= chaos_.drop_ppm;
  if (u < chaos_.dup_ppm) return 2;
  u -= chaos_.dup_ppm;
  if (u < chaos_.corrupt_ppm) return 3;
  u -= chaos_.corrupt_ppm;
  if (u < chaos_.delay_ppm) return 4;
  return 0;
}

void Engine::set_chaos(uint64_t seed, uint32_t drop_ppm, uint32_t dup_ppm,
                       uint32_t delay_ppm, uint32_t delay_us,
                       uint32_t corrupt_ppm, uint32_t slow_us) {
  MutexLock g(chaos_mu_);
  chaos_.drop_ppm = drop_ppm;
  chaos_.dup_ppm = dup_ppm;
  chaos_.delay_ppm = delay_ppm;
  chaos_.delay_us = delay_us;
  chaos_.corrupt_ppm = corrupt_ppm;
  chaos_.rng = seed ? seed : 0x9E3779B97F4A7C15ull;
  // each rank folds its id in so per-rank streams decorrelate while the
  // whole world stays reproducible from one seed
  chaos_.rng ^= (uint64_t(global_rank_) + 1) * 0xA24BAED4963EE407ull;
  chaos_.armed = drop_ppm || dup_ppm || delay_ppm || corrupt_ppm;
  slow_us_.store(slow_us);
}

void Engine::kill() {
  killed_.store(true);
  // local abort of every comm (no propagation — a dead rank cannot
  // send): this rank's own pending calls finalize fast with RANK_FAILED
  // instead of burning their receive budget against silence
  uint32_t n = comm_count();
  for (uint32_t c = 0; c < n && c < kMaxComms; ++c) {
    comm_epoch_[c].fetch_add(1);
    comm_abort_[c].fetch_or(COMM_ABORTED | RANK_FAILED);
  }
}

// Stage one wire message into the bounded egress window; blocks while
// `pipeline_depth_` segments are already outstanding (the end_move()
// backpressure point of the reference's pipelined send).
void Engine::stage_egress(uint32_t session, Message&& msg) {
  if (tap_on_.load()) {
    // fuzz seed-corpus capture: serialize exactly the wire framing
    // (64-byte header + payload) into a bounded ring.  Taps here, not
    // in send_out, because the control plane (NACK/pong/abort/join)
    // stages directly and must be capturable too.
    std::vector<uint8_t> raw(sizeof(WireHeader) + msg.payload.size());
    std::memcpy(raw.data(), &msg.hdr, sizeof(WireHeader));
    if (!msg.payload.empty())
      std::memcpy(raw.data() + sizeof(WireHeader), msg.payload.data(),
                  msg.payload.size());
    MutexLock g(tap_mu_);
    if (tap_frames_.size() >= kTapCap) tap_frames_.pop_front();
    tap_frames_.push_back(std::move(raw));
  }
  {
    UniqueLock g(egress_mu_);
    // BOUNDED backpressure: ingress handlers send too (NACK, pong,
    // retransmit, rendezvous control) and ingress runs in the SENDER's
    // egress thread, so with every queue at depth the engines form a
    // backpressure cycle through each other — egress thread A parked in
    // B's window, B's in C's, nobody draining.  Waiting forever turns
    // that transient into a distributed deadlock (and wedges shutdown,
    // which joins the loop thread before it stops this writer).  After
    // a receive budget with no slot, overflow the window instead: the
    // deque is unbounded storage, depth is a pacing knob, and a counted
    // overflow beats a silent standstill.
    bool slot = cv_wait_for_pred(
        egress_cv_, g, timeout_budget(), [&]() ACCL_REQUIRES(egress_mu_) {
          return egress_q_.size() < pipeline_depth_.load() ||
                 !egress_running_ || !running_.load();
        });
    if (!egress_running_) return;
    if (!slot) egress_overflows_.fetch_add(1);
    egress_q_.emplace_back(session, std::move(msg));
    uint64_t d = egress_q_.size(), h = egress_hwm_.load();
    while (d > h && !egress_hwm_.compare_exchange_weak(h, d)) {
    }
  }
  egress_cv_.notify_all();
}

void Engine::egress_loop() {
  for (;;) {
    std::pair<uint32_t, Message> item;
    {
      UniqueLock g(egress_mu_);
      egress_cv_.wait(g, [&]() ACCL_REQUIRES(egress_mu_) {
        return !egress_q_.empty() || !egress_running_;
      });
      if (egress_q_.empty()) {
        if (!egress_running_) return;
        continue;
      }
      item = std::move(egress_q_.front());
      egress_q_.pop_front();
    }
    egress_cv_.notify_all();  // wake staging waiters + the drain in ~Engine
    // slow-rank chaos: stall the egress writer per message so this rank
    // lags the gang without dropping anything
    uint32_t stall = slow_us_.load();
    if (stall) det_sleep_for(microseconds(stall));
    try {
      transport_->send(item.first, std::move(item.second));
    } catch (const std::exception& e) {
      // a transport failure (connect refused, peer gone) must not
      // escape this thread — std::terminate would kill the process.
      // The message is dropped; the peer's receive timeout reports it.
      std::fprintf(stderr, "[accl engine %u] egress send failed: %s\n",
                   global_rank_, e.what());
    }
  }
}

// ---------------------------------------------------------------------------
// ingress demux — the depacketizer role: eager payloads to the rx pool,
// kernel-stream payloads to stream FIFOs, rendezvous control up to the
// engine's pending/completion queues (reference: udp_depacketizer.cpp
// strm routing :136-147, rdma_depacketizer notification routing)
// ---------------------------------------------------------------------------
// Structural frame validation — the contract every conforming sender in
// this file upholds, enforced at the receiver so a corrupted/hostile
// frame is COUNTED and DROPPED before any routing interprets it:
//  - msg_type must be a known MsgType;
//  - payload-bearing types (EgrMsg/RndzvsMsg/StateSync) must carry
//    count == payload size (their senders always stamp it so);
//  - comm-addressed types must carry comm_id < kMaxComms (the fence
//    arrays index by it; conforming comm ids are < 64 by construction);
//  - a pool-routed eager segment larger than one rx buffer cannot come
//    from a conforming sender (segmentation quantum) and would be
//    silently TRUNCATED at install — rejected instead.
// Join/Welcome are session-addressed (pre-communicator) and carry no
// payload contract; RndzvsInit's count is an element count, not bytes.
// Block-scaled segments (hdr.compressed == 2, the r17 int8 wire lane)
// additionally carry a self-describing framing header whose scale-row/
// count consistency is validated HERE — a truncated scale row, a
// count/block mismatch or an oversized block is a counted rejection
// before any routing interprets the payload.
static bool i8_segment_ok(const std::vector<uint8_t>& payload) {
  return i8_wire_elems(payload.data(), payload.size()) != UINT64_MAX;
}

bool Engine::frame_ok(const WireHeader& hdr,
                      const std::vector<uint8_t>& payload) {
  const uint64_t payload_bytes = payload.size();
  switch (static_cast<MsgType>(hdr.msg_type)) {
    case MsgType::EgrMsg:
      if (hdr.count != payload_bytes) return false;
      if (hdr.comm_id >= kMaxComms) return false;
      if (hdr.compressed == 2 && !i8_segment_ok(payload)) return false;
      if (hdr.strm < FIRST_KRNL_STREAM && rx_.buf_size() &&
          payload_bytes > rx_.buf_size())
        return false;
      if (hdr.strm >= FIRST_KRNL_STREAM) {
        // stream-route state is minted per (comm, src, strm) from
        // attacker-controlled header fields: reject BEFORE any state
        // exists once the route count or the total parked holdback
        // would exceed its bound (a conforming sender uses a handful
        // of stream ids and an out-of-order window no deeper than the
        // egress pipeline).  Checked here — not in classify() — so a
        // dropped frame is a single counted rejection and
        // ingest_bytes' return code matches the counter.
        MutexLock g(strm_seq_mu_);
        StrmKey key{hdr.comm_id, hdr.src, hdr.strm};
        auto it = strm_in_seq_.find(key);
        if (it == strm_in_seq_.end() &&
            strm_in_seq_.size() >= kMaxStrmRoutes)
          return false;
        uint32_t expect = it == strm_in_seq_.end() ? 0 : it->second;
        if (hdr.seqn > expect) {  // would park in holdback
          if (strm_holdback_.size() >= kMaxStrmHoldbackTotal)
            return false;
          if (!lossy_transport_.load()) {
            size_t held = 0;
            for (const auto& kv : strm_holdback_)
              if (kv.first.first == key) ++held;
            if (held >= kStrmHoldbackLimit) return false;
          }
        }
      }
      return true;
    case MsgType::RndzvsMsg:
      if (hdr.compressed == 2 && !i8_segment_ok(payload)) return false;
      return hdr.comm_id < kMaxComms && hdr.count == payload_bytes;
    case MsgType::RndzvsInit:
    case MsgType::RndzvsWrDone:
    case MsgType::Nack:
    case MsgType::Heartbeat:
    case MsgType::Abort:
      return hdr.comm_id < kMaxComms;
    case MsgType::Join:
    case MsgType::Welcome:
      return true;
    case MsgType::StateSync:
      return hdr.count == payload_bytes;
  }
  return false;  // unknown message type
}

// RAII depth marker for ingress_depth() (see engine.hpp): lets the
// detsched shutdown drill assert no delivery is mid-flight inside a
// detached engine.
namespace {
struct DepthGuard {
  explicit DepthGuard(std::atomic<int>& d) : d_(d) { d_.fetch_add(1); }
  ~DepthGuard() { d_.fetch_sub(1); }
  std::atomic<int>& d_;
};
}  // namespace

void Engine::ingress(Message&& msg) {
  DepthGuard depth(ingress_depth_);
  // kill-rank chaos: a dead engine hears nothing — no pongs, no
  // completions, no deposits (the peer-visible half of kill())
  if (killed_.load()) return;
  if (!frame_ok(msg.hdr, msg.payload)) {
    frames_rejected_.fetch_add(1);
    return;
  }
  frames_accepted_.fetch_add(1);
  classify(std::move(msg));
}

// Test/fuzz hook: the raw-bytes twin of a transport delivery.  Same
// gates, same validation, same routing; returns the accept/reject
// verdict the transport path only counts.
int Engine::ingest_bytes(const uint8_t* data, uint64_t nbytes) {
  if (!data || nbytes < sizeof(WireHeader)) {
    frames_rejected_.fetch_add(1);
    return 1;
  }
  Message msg;
  std::memcpy(&msg.hdr, data, sizeof(WireHeader));
  msg.payload.assign(data + sizeof(WireHeader), data + nbytes);
  if (!frame_ok(msg.hdr, msg.payload)) {
    frames_rejected_.fetch_add(1);
    return 1;
  }
  frames_accepted_.fetch_add(1);
  if (!killed_.load()) classify(std::move(msg));
  return 0;
}

int Engine::tap_read(int idx, uint8_t* out, int cap) const {
  MutexLock g(tap_mu_);
  if (idx < 0 || idx >= int(tap_frames_.size())) return -1;
  const std::vector<uint8_t>& f = tap_frames_[size_t(idx)];
  if (out && cap > 0) {
    size_t n = std::min<size_t>(f.size(), size_t(cap));
    std::memcpy(out, f.data(), n);
  }
  return int(f.size());
}

int Engine::tap_drain(uint8_t* out, int cap) {
  MutexLock g(tap_mu_);
  int off = 0;
  while (!tap_frames_.empty()) {
    const std::vector<uint8_t>& f = tap_frames_.front();
    int need = int(sizeof(uint32_t) + f.size());
    if (off + need > cap) {
      // oversized lone frame can never fit any buffer of this cap
      if (off == 0 && need > cap) tap_frames_.pop_front();
      break;
    }
    uint32_t len = uint32_t(f.size());
    std::memcpy(out + off, &len, sizeof len);
    if (len) std::memcpy(out + off + sizeof len, f.data(), len);
    off += need;
    tap_frames_.pop_front();
  }
  return off;
}

void Engine::classify(Message&& msg) {
  switch (static_cast<MsgType>(msg.hdr.msg_type)) {
    case MsgType::Nack:
      nacks_rx_.fetch_add(1);
      // per-link: hdr.src is the comm-local RECEIVER soliciting us —
      // the peer whose link the loss (and the recovery) belongs to
      link_count(msg.hdr.comm_id, msg.hdr.src, &LinkCounters::nacks_rx);
      note_alive(msg.hdr.comm_id, msg.hdr.src);
      handle_nack(msg.hdr);
      return;
    case MsgType::Heartbeat: {
      // liveness control plane: epoch-agnostic (survivors probe the
      // ABORTED comm while agreeing on the shrink set)
      note_alive(msg.hdr.comm_id, msg.hdr.src);
      if (msg.hdr.count == 1) {  // ping: pong back (count = 0)
        const CommTable* t = comm_ptr(msg.hdr.comm_id);
        if (t && msg.hdr.src < t->rows.size()) {
          Message pong;
          pong.hdr.msg_type = uint8_t(MsgType::Heartbeat);
          pong.hdr.comm_id = msg.hdr.comm_id;
          pong.hdr.src = t->local;
          pong.hdr.count = 0;
          pong.hdr.dst_session = uint16_t(t->rows[msg.hdr.src].session);
          stage_egress(t->rows[msg.hdr.src].session, std::move(pong));
        }
      }
      return;
    }
    case MsgType::Abort:
      note_alive(msg.hdr.comm_id, msg.hdr.src);
      handle_abort(msg.hdr);
      return;
    case MsgType::Join:
      // elastic membership: a joiner (addressed by raw session in
      // hdr.src — it is in no comm table yet) asks for a state sync
      handle_join(msg.hdr);
      return;
    case MsgType::Welcome:
      // informational ack; the payload-bearing StateSync is the apply
      // point (ordering vs StateSync is not guaranteed on every rung,
      // so the joiner keys on StateSync alone)
      return;
    case MsgType::StateSync: {
      std::vector<uint32_t> words(msg.payload.size() / 4);
      if (!words.empty())
        std::memcpy(words.data(), msg.payload.data(), words.size() * 4);
      join_state_.push(std::move(words));
      return;
    }
    default:
      break;
  }
  // epoch fence: data/rendezvous traffic stamped with a dead epoch is
  // dropped at the pool boundary — after an abort, stragglers from the
  // old world can neither land in memory nor satisfy a future seek
  if (msg.hdr.comm_id < kMaxComms &&
      msg.hdr.epoch != comm_epoch_[msg.hdr.comm_id].load()) {
    fenced_drops_.fetch_add(1);
    link_count(msg.hdr.comm_id, msg.hdr.src, &LinkCounters::fenced_drops);
    return;
  }
  // per-link rx accounting: hdr.src is the comm-local SENDER — the
  // peer whose link this dataplane frame crossed (the chaos-
  // attribution test pins that counters land on the true peer, never
  // the local rank)
  link_rx(msg.hdr.comm_id, msg.hdr.src, msg.payload.size());
  // NB: no note_alive here — liveness piggybacks on the CONTROL plane
  // only (Heartbeat/Nack/Abort above).  The probe actively pings, so
  // stamping every data segment would buy nothing and cost the hot
  // ingress path a mutex + map walk per message.
  switch (static_cast<MsgType>(msg.hdr.msg_type)) {
    case MsgType::EgrMsg:
      if (msg.hdr.strm >= FIRST_KRNL_STREAM) {
        // resequence per (comm, src, stream): non-FIFO transports (the
        // datagram rung) may deliver stream messages out of order, and
        // the stream FIFO has no other ordering discipline
        MutexLock g(strm_seq_mu_);
        StrmKey key{msg.hdr.comm_id, msg.hdr.src, msg.hdr.strm};
        uint32_t& expect = strm_in_seq_[key];
        if (msg.hdr.seqn == expect) {
          stream_for(msg.hdr.strm)->push(std::move(msg.payload));
          ++expect;
          for (auto it = strm_holdback_.find({key, expect});
               it != strm_holdback_.end();
               it = strm_holdback_.find({key, expect})) {
            stream_for(msg.hdr.strm)->push(std::move(it->second));
            strm_holdback_.erase(it);
            ++expect;
          }
        } else if (msg.hdr.seqn > expect) {
          // holdback growth is pre-bounded by frame_ok (route count,
          // per-route window on reliable rungs, total across routes) —
          // a frame reaching this insertion was already admitted
          strm_holdback_[{key, msg.hdr.seqn}] = std::move(msg.payload);
          // loss recovery: a hole that parks too many successors means
          // the expected message was lost on a lossy rung — resync to
          // the oldest held seqn so the stream drains (bounded memory;
          // the lost payload is simply absent from the FIFO)
          size_t held = 0;
          uint32_t oldest = 0;
          bool have_oldest = false;
          for (const auto& kv : strm_holdback_)
            if (kv.first.first == key) {
              ++held;
              if (!have_oldest ||
                  int32_t(kv.first.second - oldest) < 0) {
                oldest = kv.first.second;
                have_oldest = true;
              }
            }
          if (lossy_transport_ && held > kStrmHoldbackLimit && have_oldest) {
            expect = oldest;
            for (auto it = strm_holdback_.find({key, expect});
                 it != strm_holdback_.end();
                 it = strm_holdback_.find({key, expect})) {
              stream_for(msg.hdr.strm)->push(std::move(it->second));
              strm_holdback_.erase(it);
              ++expect;
            }
          }
        }  // else: stale duplicate, drop
      } else {
        rx_.deposit(std::move(msg));
      }
      break;
    case MsgType::RndzvsInit:
      pending_addrs_.push(RndzvAddr{msg.hdr.comm_id, msg.hdr.src, msg.hdr.tag,
                                    msg.hdr.vaddr, msg.hdr.count});
      break;
    case MsgType::RndzvsMsg:
      // one-sided write into our device memory (the RDMA WRITE landing);
      // the shared land_one_sided applies the consume-write-complete
      // discipline (also run by the direct p2p path)
      land_one_sided(msg.hdr, msg.payload.data(), msg.payload.size());
      break;
    case MsgType::RndzvsWrDone:
      completions_.push(RndzvDone{msg.hdr.comm_id, msg.hdr.src, msg.hdr.tag,
                                  msg.hdr.vaddr});
      break;
    default:  // control types handled above
      break;
  }
}

// ---------------------------------------------------------------------------
// resilience: retransmission lane (NACK-driven eager resend)
// ---------------------------------------------------------------------------
void Engine::store_retrans(uint32_t comm, uint32_t dst, const Message& msg) {
  MutexLock g(retrans_mu_);
  if (retrans_ring_.empty()) retrans_ring_.resize(kRetransCap);
  RetransSlot& s = retrans_ring_[retrans_pos_];
  retrans_pos_ = (retrans_pos_ + 1) % kRetransCap;
  if (s.used)
    retrans_evictions_.fetch_add(1);  // ring wrap over a live slot
  else
    retrans_used_.fetch_add(1);
  s.used = true;
  s.comm = comm;
  s.dst = dst;
  s.msg.hdr = msg.hdr;
  // assign() reuses the recycled slot's capacity: the steady-state
  // per-segment cost is one bounded memcpy, no allocator traffic
  s.msg.payload.assign(msg.payload.begin(), msg.payload.end());
}

void Engine::send_nack(uint32_t comm, uint32_t src, uint32_t tag,
                       uint32_t seqn) {
  const CommTable* t = comm_ptr(comm);
  if (!t || src >= t->rows.size()) return;
  Message m;
  m.hdr.msg_type = uint8_t(MsgType::Nack);
  m.hdr.comm_id = comm;
  m.hdr.tag = tag;
  m.hdr.seqn = seqn;
  m.hdr.src = t->local;
  m.hdr.epoch = epoch_of(comm);
  m.hdr.dst_session = uint16_t(t->rows[src].session);
  nacks_tx_.fetch_add(1);
  // per-link: the NACK solicits the SENDER `src` — the peer whose
  // link lost the segment
  link_count(comm, src, &LinkCounters::nacks_tx);
  // control plane: staged directly (not a chaos target, see send_out)
  stage_egress(t->rows[src].session, std::move(m));
}

void Engine::handle_nack(const WireHeader& hdr) {
  // resend every stored segment on (comm, requester, tag) from the
  // requested seqn on, in seqn order — one NACK round closes a
  // multi-segment hole (the receiver evicted its suspect window).
  // Linear ring scan: this is the fault path; the no-fault store stays
  // index-free so the hot path pays nothing for our convenience here.
  std::vector<Message> out;
  {
    MutexLock g(retrans_mu_);
    for (const RetransSlot& s : retrans_ring_) {
      // a wildcard-tag NACK (a TAG_ANY recv's seek pairs with any
      // tag, so its solicitation must too) matches the whole route —
      // tag-exact matching there would strand concretely-tagged
      // segments the receiver evicted and is now waiting for
      if (s.used && s.comm == hdr.comm_id && s.dst == hdr.src &&
          (hdr.tag == TAG_ANY || s.msg.hdr.tag == hdr.tag) &&
          int32_t(s.msg.hdr.seqn - hdr.seqn) >= 0)
        out.push_back(s.msg);  // copy: the store keeps serving NACKs
    }
  }
  std::sort(out.begin(), out.end(),
            [](const Message& a, const Message& b) {
              return int32_t(a.hdr.seqn - b.hdr.seqn) < 0;
            });
  for (auto& m : out) {
    retrans_sent_.fetch_add(1);
    // per-link: the retransmit serves requester hdr.src's link
    link_count(hdr.comm_id, hdr.src, &LinkCounters::retrans_sent);
    // clean stored copy, staged directly: a retransmit is the recovery
    // path and must not re-enter the chaos funnel
    if (!killed_.load()) stage_egress(m.hdr.dst_session, std::move(m));
  }
}

// ---------------------------------------------------------------------------
// resilience: abort + epoch fencing
// ---------------------------------------------------------------------------
int Engine::abort_comm(uint32_t comm_id, uint32_t err_bits, bool propagate) {
  const CommTable* t = comm_ptr(comm_id);
  if (!t || comm_id >= kMaxComms) return -1;
  uint32_t new_epoch = comm_epoch_[comm_id].fetch_add(1) + 1;
  comm_abort_[comm_id].fetch_or(err_bits | COMM_ABORTED);
  // reclaim pool buffers pinned by the dead epoch's traffic; fence
  // every persistent plan armed against the pre-abort epoch
  rx_.evict_comm(comm_id);
  invalidate_plans(int(comm_id));
  // stale quantization residuals must not leak into the healed world's
  // error-feedback stream (the dead epoch's error is not ours to carry)
  drop_ef_residuals(int(comm_id));
  if (propagate && !killed_.load()) {
    for (uint32_t i = 0; i < t->rows.size(); ++i) {
      if (i == t->local) continue;
      Message m;
      m.hdr.msg_type = uint8_t(MsgType::Abort);
      m.hdr.comm_id = comm_id;
      m.hdr.src = t->local;
      m.hdr.count = err_bits | COMM_ABORTED;
      m.hdr.epoch = new_epoch;
      m.hdr.dst_session = uint16_t(t->rows[i].session);
      stage_egress(t->rows[i].session, std::move(m));
    }
  }
  return 0;
}

void Engine::handle_abort(const WireHeader& hdr) {
  uint32_t comm = hdr.comm_id;
  if (comm >= kMaxComms || !comm_ptr(comm)) return;
  // adopt the highest epoch seen (monotonic: a replayed abort cannot
  // roll the fence back)
  uint32_t cur = comm_epoch_[comm].load();
  while (int32_t(hdr.epoch - cur) > 0 &&
         !comm_epoch_[comm].compare_exchange_weak(cur, hdr.epoch)) {
  }
  comm_abort_[comm].fetch_or(hdr.count | COMM_ABORTED);
  rx_.evict_comm(comm);
  invalidate_plans(int(comm));
  drop_ef_residuals(int(comm));
  // pending calls on this comm finalize on the engine loop's next
  // sweep; blocked eager seeks notice within one recovery slice
}

void Engine::reset_errors() {
  // collective recovery op on a QUIESCED world: zero both directions'
  // sequence counters (every rank does the same, so the world agrees),
  // drain transient receive/retransmit state, clear armed faults and
  // abort flags.  Epochs stay bumped: old-epoch stragglers remain
  // fenced forever.
  {
    MutexLock g(cfg_mu_);
    for (auto& t : comms_) {
      std::fill(t->inbound_seq.begin(), t->inbound_seq.end(), 0);
      std::fill(t->outbound_seq.begin(), t->outbound_seq.end(), 0);
    }
  }
  rx_.clear_pending();
  {
    MutexLock g(retrans_mu_);
    for (RetransSlot& s : retrans_ring_) s.used = false;
    retrans_pos_ = 0;
    retrans_used_.store(0);
  }
  {
    MutexLock g(strm_seq_mu_);
    strm_in_seq_.clear();
    strm_holdback_.clear();
  }
  fault_.store(0);
  for (uint32_t c = 0; c < kMaxComms; ++c) comm_abort_[c].store(0);
  // plan-cache eviction fires here too (not only on abort): a healed
  // world must re-capture, never replay pre-reset descriptor state
  invalidate_plans(-1);
  drop_ef_residuals(-1);
}

// ---------------------------------------------------------------------------
// elastic membership (r11): Join/Welcome/StateSync
// ---------------------------------------------------------------------------
// Sponsor side: serialize this engine's per-comm recovery state and send
// it to the joiner.  Word layout (all u32):
//   [ncomms, then per comm: size, epoch, abort_bits,
//    then size x {inbound_seq[i], outbound_seq[i]}]
// The epoch/abort columns are the load-bearing state (the joiner must
// fence the dead world's traffic and align its comm-id space); the seqn
// rows document the sponsor's pairwise view — a comm the joiner becomes
// a member of is always a FRESH id, whose pairwise seqn state starts at
// zero on every member by construction.
void Engine::handle_join(const WireHeader& hdr) {
  joins_sponsored_.fetch_add(1);
  uint32_t joiner = hdr.src;  // raw session id, pre-communicator
  std::vector<uint32_t> words;
  {
    MutexLock g(cfg_mu_);
    words.push_back(uint32_t(comms_.size()));
    for (uint32_t ci = 0; ci < comms_.size(); ++ci) {
      const CommTable& t = *comms_[ci];
      words.push_back(t.size);
      words.push_back(epoch_of(ci));
      words.push_back(abort_err(ci));
      for (uint32_t i = 0; i < t.size; ++i) {
        words.push_back(i < t.inbound_seq.size() ? t.inbound_seq[i] : 0);
        words.push_back(i < t.outbound_seq.size() ? t.outbound_seq[i] : 0);
      }
    }
  }
  Message wel;
  wel.hdr.msg_type = uint8_t(MsgType::Welcome);
  wel.hdr.src = global_rank_;
  wel.hdr.count = words[0];
  wel.hdr.dst_session = uint16_t(joiner);
  stage_egress(joiner, std::move(wel));
  Message ss;
  ss.hdr.msg_type = uint8_t(MsgType::StateSync);
  ss.hdr.src = global_rank_;
  ss.hdr.count = uint32_t(words.size() * 4);
  ss.hdr.dst_session = uint16_t(joiner);
  ss.payload.resize(words.size() * 4);
  std::memcpy(ss.payload.data(), words.data(), ss.payload.size());
  stage_egress(joiner, std::move(ss));
}

int Engine::join_sync(uint32_t sponsor_session, int timeout_ms) {
  if (killed_.load()) return -1;
  Message m;
  m.hdr.msg_type = uint8_t(MsgType::Join);
  m.hdr.src = global_rank_;
  m.hdr.count = 1;
  m.hdr.dst_session = uint16_t(sponsor_session);
  stage_egress(sponsor_session, std::move(m));
  auto words = join_state_.pop_wait(milliseconds(timeout_ms));
  if (!words) return -1;  // sponsor deaf/dead inside the wait budget
  apply_state_sync(*words);
  joins_completed_.fetch_add(1);
  return 0;
}

void Engine::apply_state_sync(const std::vector<uint32_t>& w) {
  if (w.empty()) return;
  uint32_t ncomms = w[0];
  size_t i = 1;
  MutexLock g(cfg_mu_);
  for (uint32_t ci = 0; ci < ncomms && ci < kMaxComms; ++ci) {
    if (i >= w.size()) break;
    uint32_t size = w[i++];
    uint32_t epoch = i < w.size() ? w[i++] : 0;
    uint32_t abort = i < w.size() ? w[i++] : 0;
    i += size_t(size) * 2;  // sponsor's pairwise seqn rows (diagnostic)
    // pad with placeholder slots so the NEXT set_comm on this engine
    // lands at the same index as the survivors' next create; a call on
    // a placeholder finalizes fast in loop() instead of scheduling
    while (comms_.size() <= ci) comms_.push_back(std::make_unique<CommTable>());
    // adopt the fence monotonically (a replayed sync cannot roll back)
    uint32_t cur = comm_epoch_[ci].load();
    while (int32_t(epoch - cur) > 0 &&
           !comm_epoch_[ci].compare_exchange_weak(cur, epoch)) {
    }
    comm_abort_[ci].fetch_or(abort);
  }
}

uint32_t Engine::comm_count() const {
  MutexLock g(cfg_mu_);
  return uint32_t(comms_.size());
}

// ---------------------------------------------------------------------------
// resilience: liveness
// ---------------------------------------------------------------------------
void Engine::note_alive(uint32_t comm, uint32_t src) {
  uint64_t now = uint64_t(
      duration_cast<nanoseconds>(steady_clock::now().time_since_epoch())
          .count());
  MutexLock g(live_mu_);
  last_heard_ns_[{comm, src}] = now;
}

uint64_t Engine::probe_liveness(uint32_t comm_id, uint32_t window_us) {
  const CommTable* t = comm_ptr(comm_id);
  if (!t) return 0;
  // rows are immutable after publication: lock-free reads (CommTable)
  uint32_t local = t->local, nranks = t->size;
  std::vector<uint32_t> sessions;
  for (const auto& r : t->rows) sessions.push_back(r.session);
  uint64_t start_ns = uint64_t(
      duration_cast<nanoseconds>(steady_clock::now().time_since_epoch())
          .count());
  uint64_t alive = nranks < 64 ? (1ull << local) : 0;
  if (killed_.load()) return alive;
  for (uint32_t i = 0; i < nranks; ++i) {
    if (i == local) continue;
    Message m;
    m.hdr.msg_type = uint8_t(MsgType::Heartbeat);
    m.hdr.comm_id = comm_id;
    m.hdr.src = local;
    m.hdr.count = 1;  // ping: reply requested
    m.hdr.dst_session = uint16_t(sessions[i]);
    stage_egress(sessions[i], std::move(m));
  }
  auto deadline = steady_clock::now() + microseconds(window_us);
  uint64_t want = nranks < 64 ? (1ull << nranks) - 1 : ~0ull;
  for (;;) {
    {
      MutexLock g(live_mu_);
      for (uint32_t i = 0; i < nranks && i < 64; ++i) {
        if (i == local) continue;
        auto it = last_heard_ns_.find({comm_id, i});
        if (it != last_heard_ns_.end() && it->second >= start_ns)
          alive |= 1ull << i;
      }
    }
    if (alive == want || steady_clock::now() >= deadline) break;
    det_sleep_for(microseconds(500));
  }
  return alive;
}

// Shared landing for one-sided writes (wire ingress AND direct p2p).
//
// The depacketizer converts the wire representation into the landing
// representation using OUR OWN posted-address record (the eager path's
// own-flag-algebra discipline; the sender's header is advisory only) —
// this is the ETH-compressed rendezvous path.
//
// The whole consume-write-complete sequence holds posted_mu_:
// retry-queue expiry tears records down under the same lock, so a
// concurrent landing either fully completes BEFORE the teardown (its
// completion is then drained) or finds no record and drops — there is
// no window where a write lands or a completion surfaces after the
// teardown decided the call is dead.
void Engine::land_one_sided(const WireHeader& hdr, const uint8_t* payload,
                            uint64_t payload_bytes) {
  MutexLock pg(posted_mu_);
  std::optional<PostedRndzv> post;
  {
    auto it =
        posted_.find(PostedKey{hdr.comm_id, hdr.src, hdr.tag, hdr.vaddr});
    if (it != posted_.end()) {
      post = it->second;
      posted_.erase(it);
    }
  }
  // Landing REQUIRES our own posted record: every legitimate write
  // answers an RNDZVS_INIT we sent, so a write with no record is a
  // stale arrival for an expired call — dropping it (and emitting no
  // completion) is what keeps reused memory safe after retry-queue
  // expiry tears the record down.
  if (!post) return;
  {
    // the landing address may be tagged host-resident (host-only
    // rendezvous buffers); resolve the region like mem() does — the
    // region reference is bound UNDER mem_mu_ (binding it outside was
    // itself an unlocked read of the lazily-committed hostmem_)
    MutexLock g(mem_mu_);
    auto& region = (hdr.vaddr & HOST_ADDR_BIT) ? hostmem_ : devicemem_;
    uint64_t vaddr = hdr.vaddr & ~HOST_ADDR_BIT;
    if (post->wire_c && post->blk) {
      // block-scaled rendezvous landing: the segment is
      // self-describing — decode/validate against our posted geometry
      // and dequantize into the fp32 landing buffer (lnd_c is always
      // false for the int8 pair; the driver rejects int8 residence).
      // A segment that fails the pinned-geometry decode (divergent
      // block size, elems beyond the posted count) must NOT surface a
      // completion: the landing buffer was never written, and a
      // completed recv over stale bytes would be silent corruption —
      // withholding RndzvDone lets the receiver's budget classify the
      // failure loudly (sticky_err_ is loop-thread-only, so the
      // ingress thread cannot stamp COMPRESSION_ERROR itself).
      uint64_t elems = i8_wire_elems(payload, payload_bytes, post->blk);
      uint64_t lnd_bytes =
          elems == UINT64_MAX ? 0 : elems * post->ub;
      if (elems == UINT64_MAX || elems > post->elems ||
          vaddr + lnd_bytes > region.size() ||
          dequantize_i8_block(payload, payload_bytes,
                              reinterpret_cast<float*>(region.data() + vaddr),
                              elems, post->blk) != OK) {
        frames_rejected_.fetch_add(1);
        return;
      }
    } else if (post->wire_c != post->lnd_c) {
      // clamp to what actually arrived: a short payload (divergent
      // arithcfg, stale posted entry) must not read past the wire
      // buffer
      uint64_t wire_eb = post->wire_c ? post->cb : post->ub;
      uint64_t elems = std::min<uint64_t>(
          post->elems, payload_bytes / std::max<uint64_t>(1, wire_eb));
      uint64_t lnd_bytes = elems * (post->lnd_c ? post->cb : post->ub);
      if (vaddr + lnd_bytes <= region.size()) {
        if (post->wire_c)
          run_decompress_lane(post->comp_kind, payload,
                              region.data() + vaddr, elems);
        else
          run_compress_lane(post->comp_kind, payload,
                            region.data() + vaddr, elems);
      }
    } else if (payload_bytes && vaddr + payload_bytes <= region.size()) {
      std::memcpy(region.data() + vaddr, payload, payload_bytes);
    }
  }
  completions_.push(RndzvDone{hdr.comm_id, hdr.src, hdr.tag, hdr.vaddr});
}

// ---------------------------------------------------------------------------
// explicit session lifecycle (reference tcp_session_handler; see engine.hpp)
// ---------------------------------------------------------------------------
int Engine::open_con(uint32_t comm_id) {
  // row reads are lock-free (immutable after publication) — holding
  // cfg_mu_ across the blocking connect attempts would stall ingress
  const CommTable* t = comm_ptr(comm_id);
  if (!t || t->rows.empty()) return -1;
  for (uint32_t i = 0; i < t->rows.size(); ++i) {
    if (i == t->local) continue;
    if (transport_->open_session(t->rows[i].session) != 0) return int(i) + 1;
  }
  return 0;
}

int Engine::close_con(uint32_t comm_id) {
  const CommTable* t = comm_ptr(comm_id);
  if (!t || t->rows.empty()) return -1;
  for (uint32_t i = 0; i < t->rows.size(); ++i) {
    if (i == t->local) continue;
    // closing a never-opened session is not a failure of the teardown
    // sweep (the lazy path may simply never have connected yet)
    transport_->close_session(t->rows[i].session);
  }
  return 0;
}

// ---------------------------------------------------------------------------
// p2p buffer windows (FPGABufferP2P analog — see engine.hpp)
// ---------------------------------------------------------------------------
void Engine::register_p2p(uint64_t addr, uint64_t bytes) {
  MutexLock g(p2p_mu_);
  p2p_spans_[addr] = bytes;
}

void Engine::unregister_p2p(uint64_t addr) {
  MutexLock g(p2p_mu_);
  p2p_spans_.erase(addr);
}

bool Engine::p2p_covers(uint64_t addr, uint64_t bytes) const {
  MutexLock g(p2p_mu_);
  auto it = p2p_spans_.upper_bound(addr);
  if (it == p2p_spans_.begin()) return false;
  --it;
  return addr >= it->first && addr + bytes <= it->first + it->second;
}

uint8_t* Engine::raw_mem(uint64_t addr, uint64_t bytes) {
  MutexLock g(mem_mu_);
  if (addr & HOST_ADDR_BIT) return nullptr;  // p2p windows are devicemem
  if (addr == 0 || addr + bytes > devicemem_.size()) return nullptr;
  return devicemem_.data() + addr;
}

void Engine::land_p2p(const WireHeader& hdr, const uint8_t* payload,
                      uint64_t payload_bytes) {
  // same gates as wire ingress: a killed rank hears nothing, and
  // dead-epoch traffic is fenced (the posted-record requirement below
  // already drops writes for torn-down calls; this keeps the two
  // ingress paths gate-for-gate identical)
  if (killed_.load()) return;
  if (hdr.comm_id < kMaxComms &&
      hdr.epoch != comm_epoch_[hdr.comm_id].load()) {
    fenced_drops_.fetch_add(1);
    link_count(hdr.comm_id, hdr.src, &LinkCounters::fenced_drops);
    return;
  }
  // per-link rx: the direct p2p landing is the same inter-rank traffic
  // as a wire delivery (gate-for-gate identical ingress discipline)
  link_rx(hdr.comm_id, hdr.src, payload_bytes);
  land_one_sided(hdr, payload, payload_bytes);
}

// ---------------------------------------------------------------------------
// engine event loop (fw run_accl :2264-2306): new calls take priority;
// retried rendezvous calls progress cooperatively in between.
// ---------------------------------------------------------------------------
void Engine::loop() {
  while (running_) {
    CallDesc c;
    bool have = false;
    if (auto o = cmd_q_.try_pop()) {
      c = *o;
      have = true;
    } else if (!retry_q_.empty()) {
      c = retry_q_.front();
      retry_q_.pop_front();
      have = true;
    } else if (auto o2 = cmd_q_.pop_wait(milliseconds(2))) {
      c = *o2;
      have = true;
    }
    if (!have) continue;

    if (c.first_try_ns == 0)
      retry_idle_sweeps_ = 0;  // new call admitted: reset retry pacing

    // abort fence: a call on an aborted communicator finalizes fast with
    // the abort's error bits — whether it was freshly admitted or came
    // back through the retry queue (this is what wakes a rendezvous
    // blocked on a dead peer within one retry sweep).  Config/Nop stay
    // executable: bring-up and soft reset must work on any comm state.
    if (c.scenario() != Op::Config && c.scenario() != Op::Nop) {
      uint32_t ab = abort_err(c.comm());
      // elastic membership: a placeholder comm slot (minted by a join
      // state sync to align comm-id spaces, size 0) carries no rank
      // table — a call on it must finalize as a fenced/dead comm, not
      // divide a collective schedule by zero.  Local ops (copy/combine)
      // never consult the table and stay executable.
      if (!ab && c.scenario() != Op::Copy && c.scenario() != Op::Combine &&
          comm_for(c).size == 0)
        ab = COMM_ABORTED | RANK_FAILED;
      if (ab) {
        teardown_call(c);
        MutexLock g(results_mu_);
        auto& r = results_[c.id];
        r.retcode = ab;
        r.duration_ns = 0.0;
        r.done = true;
        det_live_end();
        continue;
      }
    }

    auto t0 = steady_clock::now();
    // the retry budget ticks on the det-aware clock (virtual under the
    // model checker) while duration telemetry stays on the real one
    if (c.first_try_ns == 0)
      c.first_try_ns = uint64_t(
          duration_cast<nanoseconds>(det_clock_now().time_since_epoch())
              .count() +
          1);
    uint32_t step_before = c.current_step;
    sticky_err_ = 0;
    bool retry = false;
    try {
      uint32_t ret = execute(c);
      retry_idle_sweeps_ = 0;  // a call completed: the world moved
      auto dt = duration_cast<nanoseconds>(steady_clock::now() - t0).count();
      MutexLock g(results_mu_);
      auto& r = results_[c.id];
      r.retcode = ret;
      r.duration_ns = double(dt);
      r.done = true;
      det_live_end();
    } catch (NotReadyEx&) {
      retry = true;
    }
    if (retry) {
      // the budget is PER RECEIVE, like the blocking eager seek: any
      // step progress restarts the clock (+1 keeps the stamp distinct
      // from the 0 = "never tried" sentinel on the virtual clock,
      // whose epoch starts at 0)
      if (c.current_step != step_before)
        c.first_try_ns = uint64_t(
            duration_cast<nanoseconds>(det_clock_now().time_since_epoch())
                .count() +
            1);
      // expire stalled calls against the receive budget (see CallDesc
      // .first_try_ns): a peer that never arrives must surface as the
      // engine's own RECEIVE_TIMEOUT_ERROR, not as a host-side hang
      auto waited = duration_cast<nanoseconds>(
                        det_clock_now().time_since_epoch())
                        .count() -
                    int64_t(c.first_try_ns);
      if (waited > timeout_budget().count()) {
        teardown_call(c);
        MutexLock g(results_mu_);
        auto& r = results_[c.id];
        r.retcode = sticky_err_ | RECEIVE_TIMEOUT_ERROR;
        r.duration_ns = double(waited);
        r.done = true;
        det_live_end();
      } else {
        retry_q_.push_back(c);
        // cooperative pacing: the firmware round-robins between the
        // host cmd stream and the retry FIFO with no sleep at all
        // (fw :2264-2288).  A fixed sleep here puts a latency floor
        // under every contended rendezvous, so pace adaptively —
        // yield while the queue is freshly unproductive (the peer is
        // usually microseconds away), escalate to a growing bounded
        // sleep only when sweeps keep coming back empty-handed.
        if (c.current_step != step_before) {
          retry_idle_sweeps_ = 0;  // step progress: stay hot
        } else if (++retry_idle_sweeps_ <= 64) {
          det_yield();
        } else {
          det_sleep_for(microseconds(
              std::min<uint32_t>(200, retry_idle_sweeps_ - 64)));
        }
      }
    }
  }
}

// Tear down one call's rendezvous protocol state + scratch leases —
// shared by retry-budget expiry and abort finalization: erase the
// landing records it advertised (a late one-sided write must NOT land
// into memory about to be reused) and drain any completions already
// surfaced for them (a future call reusing the address must not see a
// stale success).  posted_mu_ is held across BOTH so a landing racing
// with teardown either completes fully before the drain (ingress holds
// the same lock through consume-write-complete) or finds no record and
// drops; the drain matches the exact posted vaddr so a concurrent
// healthy call's completion on the same (comm, src, tag) survives.
void Engine::teardown_call(CallDesc& c) {
  {
    MutexLock g(posted_mu_);
    for (const auto& k : c.rndzv_posts) {
      posted_.erase(PostedKey{uint32_t(k[0]), uint32_t(k[1]),
                              uint32_t(k[2]), k[3]});
      while (completions_.pop_match(
          [&](const RndzvDone& d) {
            return d.comm == uint32_t(k[0]) && d.src == uint32_t(k[1]) &&
                   d.tag == uint32_t(k[2]) && d.vaddr == k[3];
          },
          nanoseconds(0))) {
      }
    }
  }
  // release scratch leases the retries kept alive
  if (c.scratch0) { free_addr(c.scratch0); c.scratch0 = 0; }
  if (c.scratch1) { free_addr(c.scratch1); c.scratch1 = 0; }
}

int Engine::set_tuning(uint32_t key, uint32_t value) {
  switch (key) {
    case BCAST_FLAT_TREE_MAX_RANKS: bcast_flat_max_ranks_ = value; break;
    case REDUCE_FLAT_TREE_MAX_RANKS: reduce_flat_max_ranks_ = value; break;
    case GATHER_FLAT_TREE_MAX_FANIN:
      gather_flat_max_fanin_ = value ? value : 1;
      break;
    case EGRESS_PIPELINE_DEPTH:
      pipeline_depth_ = value ? value : 1;
      break;
    case GATHER_FLAT_TREE_MAX_COUNT:
      gather_flat_max_count_ = value;
      break;
    case REDUCE_FLAT_TREE_MAX_COUNT:
      reduce_flat_max_count_ = value;
      break;
    default:
      return -1;  // unknown register: reject, never silently ignore
  }
  return 0;
}

uint32_t Engine::execute(CallDesc& c) {
  Progress p(c);
  try {
    dispatch(c, p);
  } catch (SizeCapEx&) {
    // size-cap violation: finalize immediately with the sticky error
    // (NotReadyEx, by contrast, propagates to the retry queue)
  }
  // release rendezvous scratch leases (kept alive across retries)
  if (c.scratch0) {
    free_addr(c.scratch0);
    c.scratch0 = 0;
  }
  if (c.scratch1) {
    free_addr(c.scratch1);
    c.scratch1 = 0;
  }
  return sticky_err_;
}

void Engine::dispatch(CallDesc& c, Progress& p) {
  switch (c.scenario()) {
    case Op::Config: do_config(c); break;
    case Op::Nop: break;
    case Op::Copy: {
      // mem<->stream copy variants (reference: accl.cpp copy_to_stream/
      // copy_from_stream wrap copy with RES_STREAM/OP0_STREAM; the
      // dma_mover routes the lane to the external-kernel switch port)
      Dom d = dom(c);
      uint64_t elems = c.count();
      uint64_t bytes = elems * d.ub;  // streams carry uncompressed
      bool op_stream = c.stream_flags() & 0x1;   // OP0_STREAM
      bool res_stream = c.stream_flags() & 0x2;  // RES_STREAM
      // a consumer must not be handed a correctly-sized but corrupt
      // payload: push to the stream only with a clean error state (same
      // guard as the streamed-result reduce path)
      if (op_stream && res_stream) {
        // kernel input port -> named local stream, staged via scratch
        uint64_t tmp = alloc(bytes, 64);
        if (tmp && drain_krnl_to(tmp, bytes) && sticky_err_ == 0)
          push_local_stream(c.tag(), tmp, bytes);
        else if (!tmp)
          sticky_err_ |= DMA_SIZE_ERROR;
        if (tmp) free_addr(tmp);
      } else if (op_stream) {
        if (d.res) {
          // stream -> compressed result buffer: stage then compress
          uint64_t tmp = alloc(bytes, 64);
          if (tmp && drain_krnl_to(tmp, bytes))
            local_move(c, tmp, c.addr2(), elems, false, true);
          else if (!tmp)
            sticky_err_ |= DMA_SIZE_ERROR;
          if (tmp) free_addr(tmp);
        } else {
          drain_krnl_to(c.addr2(), bytes);
        }
      } else if (res_stream) {
        if (d.op0) {
          // compressed operand -> stream: decompress into scratch first
          uint64_t tmp = alloc(bytes, 64);
          if (tmp && local_move(c, c.addr0(), tmp, elems, true, false) == 0)
            push_local_stream(c.tag(), tmp, bytes);
          else if (!tmp)
            sticky_err_ |= DMA_SIZE_ERROR;
          if (tmp) free_addr(tmp);
        } else if (sticky_err_ == 0) {
          push_local_stream(c.tag(), c.addr0(), bytes);
        }
      } else {
        local_move(c, c.addr0(), c.addr2(), elems, d.op0, d.res);
      }
      break;
    }
    case Op::Combine: {
      Dom d = dom(c);
      uint64_t elems = c.count();
      MutexLock g(mem_mu_);
      uint8_t* a0 = mem(c.addr0(), elems * d.eb(d.op0));
      uint8_t* a1 = mem(c.addr1(), elems * d.eb(d.op1));
      uint8_t* r = mem(c.addr2(), elems * d.eb(d.res));
      reduce_mixed(c, a0, d.op0, a1, d.op1, r, d.res, elems);
      break;
    }
    case Op::Send: coll_send(c, p); break;
    case Op::Recv: coll_recv(c, p); break;
    case Op::Bcast: coll_bcast(c, p); break;
    case Op::Scatter: coll_scatter(c, p); break;
    case Op::Gather: coll_gather(c, p); break;
    case Op::Allgather: coll_allgather(c, p); break;
    case Op::Reduce: coll_reduce(c, p); break;
    case Op::ReduceScatter: coll_reduce_scatter(c, p); break;
    case Op::Allreduce: coll_allreduce(c, p); break;
    case Op::Alltoall: coll_alltoall(c, p); break;
    case Op::Alltoallv: coll_alltoallv(c, p); break;
    case Op::Barrier: coll_barrier(c, p); break;
    default: sticky_err_ |= COLLECTIVE_NOT_IMPLEMENTED; break;
  }
}

static uint32_t floor_log2(uint32_t v) {
  uint32_t r = 0;
  while (v >>= 1) ++r;
  return r;
}

// Binomial tree broadcast (fw :816-869): each round doubles the set of
// ranks holding the payload; position is measured from the root.
void Engine::tree_bcast(CallDesc& c, Progress& p, uint32_t root,
                        uint64_t src_addr, uint64_t dst_addr, uint64_t elems,
                        bool src_c, bool dst_c) {
  const CommTable& t = comm_for(c);
  uint32_t P = t.size;
  uint32_t pos = (t.local + P - root) % P;
  uint64_t from = src_addr;
  bool from_c = src_c;
  uint32_t k0 = 0;
  if (pos != 0) {
    uint32_t pk = floor_log2(pos);
    uint32_t parent = pos - (1u << pk);
    rndzv_recv(c, p, (root + parent) % P, c.tag(), dst_addr, elems, dst_c);
    // relay: the buffer we received with RES domain becomes the OP0
    // source of the forwarding hops (fw :1408-1411)
    from = dst_addr;
    from_c = dst_c;
    k0 = pk + 1;
  }
  for (uint32_t k = k0; (1u << k) < P; ++k) {
    uint32_t child = pos + (1u << k);
    if (child < P)
      rndzv_send(c, p, (root + child) % P, c.tag(), from, elems, from_c);
  }
}

// Binomial tree reduce (fw :1603-1728): leaves push partials up; interior
// positions fold each child's partial into an accumulator, then forward.
// tmp scratch always holds the uncompressed representation.
void Engine::tree_reduce(CallDesc& c, Progress& p, uint32_t root,
                         uint64_t src_addr, uint64_t acc_addr,
                         uint64_t tmp_addr, uint64_t elems, bool src_c,
                         bool acc_c) {
  const CommTable& t = comm_for(c);
  uint32_t P = t.size;
  uint32_t pos = (t.local + P - root) % P;
  step_local(p, [&] { local_move(c, src_addr, acc_addr, elems, src_c, acc_c); });
  for (uint32_t k = 0; (1u << k) < P; ++k) {
    uint32_t bit = 1u << k;
    if (pos & bit) {
      rndzv_send(c, p, (root + pos - bit) % P, c.tag(), acc_addr, elems,
                 acc_c);
      return;
    }
    if (pos + bit < P) {
      rndzv_recv(c, p, (root + pos + bit) % P, c.tag(), tmp_addr, elems,
                 false);
      step_local(p, [&] {
        Dom d = dom(c);
        MutexLock g(mem_mu_);
        uint8_t* acc = mem(acc_addr, elems * d.eb(acc_c && d.pair));
        uint8_t* tmp = mem(tmp_addr, elems * d.ub);
        reduce_mixed(c, acc, acc_c, tmp, false, acc, acc_c, elems);
      });
    }
  }
}

void Engine::do_config(CallDesc& c) {
  switch (static_cast<CfgFunc>(c.function())) {
    case CfgFunc::ResetPeriph: {
      // soft reset (fw HOUSEKEEP_SWRST :2420-2423): drop transient state
      retry_q_.clear();
      while (pending_addrs_.try_pop()) {}
      while (completions_.try_pop()) {}
      {
        MutexLock g(posted_mu_);
        posted_.clear();
      }
      {
        MutexLock g(strm_seq_mu_);
        strm_in_seq_.clear();
        strm_holdback_.clear();
      }
      strm_out_seq_.clear();
      {
        // the loop thread owns the seq columns, but the pointer vector
        // itself is cfg_mu_-guarded (a concurrent set_comm may grow it)
        MutexLock g(cfg_mu_);
        for (auto& t : comms_) {
          std::fill(t->inbound_seq.begin(), t->inbound_seq.end(), 0);
          std::fill(t->outbound_seq.begin(), t->outbound_seq.end(), 0);
        }
      }
      pkt_enabled_ = false;
      break;
    }
    case CfgFunc::EnablePkt: pkt_enabled_ = true; break;
    case CfgFunc::SetTimeout: timeout_ = c.count(); break;
    case CfgFunc::SetMaxEagerMsgSize:
      // must cover at least one rx buffer (fw :2432-2441)
      if (rx_.buf_size() && c.count() < rx_.buf_size())
        sticky_err_ |= EAGER_THRESHOLD_INVALID;
      else
        max_eager_ = c.count();
      break;
    case CfgFunc::SetMaxRendezvousMsgSize:
      if (c.count() < max_eager_)
        sticky_err_ |= RENDEZVOUS_THRESHOLD_INVALID;
      else
        max_rndzv_ = c.count();
      break;
  }
}

// ---------------------------------------------------------------------------
// helpers
// ---------------------------------------------------------------------------
// Stable-pointer fetch: cfg_mu_ guards the pointer vector (growth);
// the pointee tables are heap-pinned and follow CommTable's per-field
// ownership discipline, so the returned pointer is usable lock-free
// for the rest of the call.
CommTable* Engine::comm_ptr(uint32_t id) const {
  MutexLock g(cfg_mu_);
  return id < comms_.size() ? comms_[id].get() : nullptr;
}

ArithCfgN* Engine::arith_ptr(uint32_t id) const {
  MutexLock g(cfg_mu_);
  return id < arithcfgs_.size() ? arithcfgs_[id].get() : nullptr;
}

// The fallback tables are IMMORTAL by design (leaked, never destroyed):
// a world the host leaked at interpreter exit still has engine threads
// running when __cxa_finalize destroys this library's function-local
// statics — a destroyed fallback under a live loop thread is a
// use-after-free at process exit (the r13 suite-exit segfault class).
const CommTable& Engine::comm_for(const CallDesc& c) const {
  static const CommTable& empty = *new CommTable();
  const CommTable* t = comm_ptr(c.comm());
  return t ? *t : empty;
}

const ArithCfgN& Engine::arith_for(const CallDesc& c) const {
  static const ArithCfgN& dflt = *new ArithCfgN();
  const ArithCfgN* a = arith_ptr(c.arithcfg());
  return a ? *a : dflt;
}

uint64_t Engine::elem_bytes(const CallDesc& c) const {
  return arith_for(c).ubits / 8;
}

Engine::Dom Engine::dom(const CallDesc& c) const {
  const ArithCfgN& a = arith_for(c);
  Dom d;
  d.ub = a.ubits ? a.ubits / 8 : 4;
  d.cb = a.cbits ? a.cbits / 8 : d.ub;
  d.ratio_log = a.ratio_log;
  d.comp_kind = a.compressor;
  d.pair = a.ratio_log > 0;
  uint32_t f = c.compression();
  d.op0 = d.pair && (f & OP0_COMPRESSED);
  d.op1 = d.pair && (f & OP1_COMPRESSED);
  d.res = d.pair && (f & RES_COMPRESSED);
  d.eth = d.pair && (f & ETH_COMPRESSED);
  if (d.pair && a.compressor == I8_BLOCK_COMPRESSOR) {
    d.blk = a.block ? a.block : I8_BLOCK_DEFAULT;
    d.ef = a.error_feedback != 0;
    // per-operand residence is undefined for a scaled segment (the
    // driver rejects it too); only the wire bit is meaningful
    d.op0 = d.op1 = d.res = false;
  }
  return d;
}

uint32_t Engine::convert_elems(const Dom& d, const uint8_t* in, bool in_c,
                               uint8_t* out, bool out_c, uint64_t elems) {
  // zero-element moves are legal (barrier's empty messages) but the
  // pointers may then be null (an empty vector's data()) — and
  // memmove/the lanes declare their pointers nonnull (UBSan)
  if (elems == 0) return OK;
  if (in_c == out_c) {
    std::memmove(out, in, d.wbytes(elems, in_c));
    return OK;
  }
  uint32_t err;
  if (d.blk) {
    // int8 block-scaled lane: the compressed side is a self-describing
    // segment (arith.hpp framing); accumulate/operand side is fp32
    err = in_c ? dequantize_i8_block(in, d.wbytes(elems, true),
                                     reinterpret_cast<float*>(out), elems,
                                     d.blk)
               : (quantize_i8_block(reinterpret_cast<const float*>(in), out,
                                    elems, d.blk),
                  OK);
  } else {
    err = in_c ? run_decompress_lane(d.comp_kind, in, out, elems)
               : run_compress_lane(d.comp_kind, in, out, elems);
  }
  sticky_err_ |= err;
  return err;
}

// Egress quantization with optional EQuARX error feedback: the plain
// path is quantize_i8_block; with the arithcfg's error_feedback word
// set, the per-site residual (comm, dst, source address) is folded in
// and refreshed.  Sites whose element count changed (buffer reuse at a
// different size) reset their residual; the total float budget is
// bounded — saturated worlds quantize feedback-free rather than grow.
void Engine::quantize_egress(const Dom& d, bool use_ef, uint32_t comm,
                             uint32_t dst, uint64_t src_addr,
                             const float* in, uint8_t* out,
                             uint64_t elems) {
  if (!use_ef || elems == 0) {
    quantize_i8_block(in, out, elems, d.blk);
    return;
  }
  MutexLock g(ef_mu_);
  auto it = ef_residual_.find(EfKey{comm, dst, src_addr});
  if (it == ef_residual_.end()) {
    if (ef_floats_ + elems > kEfResidualCapFloats) {
      quantize_i8_block(in, out, elems, d.blk);
      return;
    }
    it = ef_residual_.emplace(EfKey{comm, dst, src_addr},
                              std::vector<float>(elems, 0.0f)).first;
    ef_floats_ += elems;
  } else if (it->second.size() != elems) {
    // same cap discipline as creation: a site regrowing past the
    // budget drops its residual and quantizes feedback-free rather
    // than blowing the bound (buffer reuse at a new size)
    uint64_t grown = ef_floats_ - uint64_t(it->second.size()) + elems;
    if (grown > kEfResidualCapFloats) {
      ef_floats_ -= uint64_t(it->second.size());
      ef_residual_.erase(it);
      quantize_i8_block(in, out, elems, d.blk);
      return;
    }
    ef_floats_ = grown;
    it->second.assign(elems, 0.0f);
  }
  quantize_i8_block(in, out, elems, d.blk, it->second.data());
}

void Engine::drop_ef_residuals(int comm_id) {
  MutexLock g(ef_mu_);
  if (comm_id < 0) {
    ef_residual_.clear();
    ef_floats_ = 0;
    return;
  }
  for (auto it = ef_residual_.begin(); it != ef_residual_.end();) {
    if (std::get<0>(it->first) == uint32_t(comm_id)) {
      ef_floats_ -= it->second.size();
      it = ef_residual_.erase(it);
    } else {
      ++it;
    }
  }
}

uint32_t Engine::reduce_mixed(const CallDesc& c, const uint8_t* a0, bool a0c,
                              const uint8_t* a1, bool a1c, uint8_t* r, bool rc,
                              uint64_t elems) {
  const ArithCfgN& a = arith_for(c);
  Dom d = dom(c);
  // the arithcfg chooses the accumulate domain: mixed-precision pairs run
  // their lanes on the compressed representation when arith_compressed
  // (reference DEFAULT_ARITH_CONFIG {f32,f16} pair, arithconfig.hpp:106-119)
  bool ac = d.pair && a.arith_compressed != 0;
  uint32_t lane =
      c.function() < a.lanes.size() ? a.lanes[c.function()] : uint32_t(NUM_LANES);
  uint64_t abytes = elems * d.eb(ac);
  if (a0c == ac && a1c == ac && rc == ac) {
    uint32_t err = run_reduce_lane(lane, a0, a1, r, abytes);
    sticky_err_ |= err;
    return err;
  }
  thread_local std::vector<uint8_t> s0, s1, sr;
  const uint8_t* p0 = a0;
  const uint8_t* p1 = a1;
  if (a0c != ac) {
    s0.resize(abytes);
    if (convert_elems(d, a0, a0c, s0.data(), ac, elems)) return sticky_err_;
    p0 = s0.data();
  }
  if (a1c != ac) {
    s1.resize(abytes);
    if (convert_elems(d, a1, a1c, s1.data(), ac, elems)) return sticky_err_;
    p1 = s1.data();
  }
  if (rc == ac) {
    uint32_t err = run_reduce_lane(lane, p0, p1, r, abytes);
    sticky_err_ |= err;
    return err;
  }
  sr.resize(abytes);
  uint32_t err = run_reduce_lane(lane, p0, p1, sr.data(), abytes);
  sticky_err_ |= err;
  if (err) return err;
  return convert_elems(d, sr.data(), ac, r, rc, elems);
}

nanoseconds Engine::timeout_budget() const {
  // 1 emulated cycle = 1us (the reference counts 4ns cycles on hardware;
  // the emulator scales so the default 1e6-cycle timeout is 1s of wall
  // clock, tolerant of CI scheduling)
  return microseconds(timeout_);
}

bool Engine::use_rendezvous(const CallDesc& c, uint64_t elems) {
  // eager if small or streamed (fw send :589, recv :669).  Unlike the
  // reference firmware — which forces eager for any nonzero compression
  // flag and leaves compressed rendezvous as a TODO (fw :589, :615-620) —
  // the rendezvous primitives here are domain-aware, so protocol
  // selection depends only on size.  The threshold is measured against
  // the WIRE payload: that is the one quantity both peers of a
  // directional pair (e.g. f16 sender / f32+compress receiver) derive
  // identically from their own arithcfg + ETH flag, so protocol choice
  // can never diverge across ranks.
  Dom d = dom(c);
  uint64_t bytes = d.wbytes(elems, d.eth);
  if (bytes <= max_eager_) return false;
  if (c.stream_flags() != 0) return false;
  // enforce the rendezvous size register as a hard cap (the reference
  // validates the register, fw :2442-2448, but never checks transfers
  // against it; transfers over the cap fail fast instead of wedging)
  if (bytes > max_rndzv_) {
    sticky_err_ |= DMA_SIZE_ERROR;
    throw SizeCapEx{};
  }
  return true;
}

bool Engine::drain_krnl_to(uint64_t addr, uint64_t bytes) {
  uint64_t off = 0;
  while (off < bytes) {
    auto v = krnl_in_.pop_wait(timeout_budget());
    if (!v) {
      sticky_err_ |= SEGMENTER_EXPECTED_BTT_ERROR;
      return false;
    }
    uint64_t n = std::min<uint64_t>(v->size(), bytes - off);
    if (v->size() > bytes - off) sticky_err_ |= SEGMENTER_EXPECTED_BTT_ERROR;
    MutexLock g(mem_mu_);
    if (n) std::memcpy(mem(addr + off, n), v->data(), n);
    off += n;
  }
  return true;
}

void Engine::push_local_stream(uint32_t strm, uint64_t addr, uint64_t bytes) {
  std::vector<uint8_t> out;
  {
    MutexLock g(mem_mu_);
    uint8_t* p = mem(addr, bytes);
    out.assign(p, p + bytes);
  }
  stream_for(strm)->push(std::move(out));
}

uint32_t Engine::local_copy(uint64_t src, uint64_t dst, uint64_t bytes) {
  MutexLock g(mem_mu_);
  uint8_t* s = mem(src, bytes);
  uint8_t* d = mem(dst, bytes);
  std::memmove(d, s, bytes);
  return sticky_err_;
}

// Domain-aware element copy: routes through the compressor/decompressor
// lane when source and destination representations differ (the role of
// the reference dma_mover's per-operand lane routing).
uint32_t Engine::local_move(const CallDesc& c, uint64_t src, uint64_t dst,
                            uint64_t elems, bool src_c, bool dst_c) {
  Dom d = dom(c);
  src_c = src_c && d.pair;
  dst_c = dst_c && d.pair;
  MutexLock g(mem_mu_);
  uint8_t* s = mem(src, elems * d.eb(src_c));
  uint8_t* t = mem(dst, elems * d.eb(dst_c));
  convert_elems(d, s, src_c, t, dst_c, elems);
  return sticky_err_;
}

uint32_t Engine::local_reduce(uint32_t lane, uint64_t a, uint64_t b,
                              uint64_t dst, uint64_t bytes) {
  MutexLock g(mem_mu_);
  uint8_t* pa = mem(a, bytes);
  uint8_t* pb = mem(b, bytes);
  uint8_t* pd = mem(dst, bytes);
  sticky_err_ |= run_reduce_lane(lane, pa, pb, pd, bytes);
  return sticky_err_;
}

// ---------------------------------------------------------------------------
// eager protocol primitives
// ---------------------------------------------------------------------------
void Engine::send_eager(CallDesc& c, uint32_t dst, uint32_t tag, uint64_t addr,
                        uint64_t elems, bool from_stream, uint32_t to_strm,
                        uint32_t comp, bool reduce_stream) {
  // loop() already finalized calls on unknown/placeholder comms, so the
  // fetch cannot miss here (same contract the old direct index relied on)
  CommTable& t = *comm_ptr(c.comm());
  Dom d = dom(c);
  bool src_c = d.pair && (comp & OP0_COMPRESSED) && !from_stream;
  bool wire_c = d.pair && (comp & ETH_COMPRESSED);
  uint64_t seg_wire = t.rows[dst].max_seg ? t.rows[dst].max_seg
                                          : (rx_.buf_size() ? rx_.buf_size()
                                                            : 1024);
  // segmentation is against the rx buffer in WIRE representation: a
  // compressed wire carries ratio-more elements per segment (fw :621-623
  // computes max_seg_count from the element size the same way); the
  // block-scaled lane additionally rounds to whole blocks so every
  // segment is a self-contained (scales, data) unit
  uint64_t seg_elems = d.seg_elems(seg_wire, wire_c);

  uint64_t off = 0;
  bool first = true;
  while (off < elems || (first && elems == 0)) {
    first = false;
    uint64_t chunk = std::min(seg_elems, elems - off);
    Message msg;
    if (from_stream) {
      // operand streamed from the local compute kernel (OP0_STREAM;
      // reference vadd_put path accl_hls.h / fw :575) — streams carry
      // the uncompressed representation
      auto v = krnl_in_.pop_wait(timeout_budget());
      if (!v || v->size() != chunk * d.ub) {
        sticky_err_ |= SEGMENTER_EXPECTED_BTT_ERROR;
        return;
      }
      msg.payload = std::move(*v);
      if (wire_c) {
        std::vector<uint8_t> packed(d.wbytes(chunk, true));
        if (convert_elems(d, msg.payload.data(), false, packed.data(), true,
                          chunk))
          return;
        msg.payload = std::move(packed);
      }
    } else {
      MutexLock g(mem_mu_);
      uint8_t* p = mem(addr + off * d.eb(src_c), chunk * d.eb(src_c));
      msg.payload.resize(d.wbytes(chunk, wire_c));
      if (wire_c && d.blk && !src_c) {
        // block-scaled egress: quantize (with the per-site EQuARX
        // residual when the arithcfg arms error feedback AND this is
        // a reduction-stream hop)
        if (sticky_err_) return;
        quantize_egress(d, d.ef && reduce_stream, c.comm(), dst,
                        addr + off * d.eb(src_c),
                        reinterpret_cast<const float*>(p),
                        msg.payload.data(), chunk);
      } else if (convert_elems(d, p, src_c, msg.payload.data(), wire_c,
                               chunk)) {
        return;
      }
    }
    if (wire_c) {
      compressed_tx_bytes_.fetch_add(msg.payload.size());
      compressed_tx_logical_bytes_.fetch_add(chunk * d.ub);
      link_count(c.comm(), dst, &LinkCounters::comp_tx_bytes,
                 msg.payload.size());
    }
    msg.hdr.compressed = wire_c ? (d.blk ? 2 : 1) : 0;
    msg.hdr.count = uint32_t(msg.payload.size());
    msg.hdr.tag = tag;
    msg.hdr.src = t.local;
    // stream-destined messages bypass the rx pool on the receiver, so
    // they must not consume the eager sequence space (seqn discipline is
    // per rx-pool route); they carry their own per-(comm,dst,strm)
    // sequence so ingress can resequence on non-FIFO transports
    // outbound counter keyed per destination (the receiver resequences
    // per source, so each src->dst stream route has its own space)
    msg.hdr.seqn =
        to_strm >= FIRST_KRNL_STREAM
            ? strm_out_seq_[StrmKey{c.comm(), dst, to_strm}]++
            : t.outbound_seq[dst]++;
    msg.hdr.strm = to_strm;
    msg.hdr.dst_session = uint16_t(t.rows[dst].session);
    msg.hdr.msg_type = uint8_t(MsgType::EgrMsg);
    msg.hdr.comm_id = c.comm();
    msg.hdr.epoch = epoch_of(c.comm());
    // retransmission lane: capture the clean copy BEFORE the chaos
    // funnel (the wire may drop/corrupt it; the source data survives).
    // Stream-destined messages bypass the rx pool and its NACK
    // machinery, so only pool-routed segments are stored.
    if (to_strm < FIRST_KRNL_STREAM && retrans_enabled())
      store_retrans(c.comm(), dst, msg);
    link_tx(c.comm(), dst, msg.payload.size());
    send_out(t.rows[dst].session, std::move(msg));
    off += chunk;
  }
}

// Seek with recovery: the receive budget is sliced so (a) an abort
// wakes a blocked receiver within one slice instead of after the whole
// budget, and (b) with retransmission enabled a miss NACKs the sender
// and backs off exponentially (base ACCL_RETRY_BASE_US, deterministic
// jitter from (rank, seqn, attempt)) up to ACCL_RETRY_MAX rounds.  The
// TOTAL budget is unchanged: a peer that never sent anything still
// classifies exactly like today, on the same clock.
std::optional<RxNotification> Engine::seek_recover(CallDesc& c, uint32_t src,
                                                   uint32_t tag,
                                                   int* evicted_out,
                                                   Message* staged_out) {
  CommTable& t = *comm_ptr(c.comm());
  seeks_.fetch_add(1);
  link_count(c.comm(), src, &LinkCounters::seeks);
  // per-link seek latency: how long THIS peer's missing data kept the
  // receiver blocked — the slow-link observable the link matrix ranks
  // (a chaos-slowed peer's links dominate seek_wait_ns).  RAII so
  // every return path (success, miss, abort, shutdown) stamps it.
  struct SeekWaitStamp {
    Engine* e;
    uint32_t comm, src;
    steady_clock::time_point t0 = steady_clock::now();
    ~SeekWaitStamp() {
      e->link_count(comm, src, &LinkCounters::seek_wait_ns,
                    uint64_t(std::chrono::duration_cast<nanoseconds>(
                                 steady_clock::now() - t0)
                                 .count()));
    }
  } seek_stamp{this, c.comm(), src};
  // budget measured on the det-aware clock: virtual time under the
  // model checker (so explored schedules can actually reach expiry —
  // the wall-clock ingredient the virtual clock used to hide), the
  // real steady clock in production builds
  auto budget = timeout_budget();
  auto deadline = det_clock_now() + budget;
  uint32_t retry_max = retrans_enabled() ? retry_max_.load() : 0;
  uint32_t attempts = 0;  // fast-phase NACK rounds consumed
  uint32_t chunks = 0;    // steady-state 50 ms slices elapsed
  for (;;) {
    // engine shutdown mid-seek: give the call back to the loop (which
    // is exiting) so shutdown's finalize sweep retires it — a blocked
    // receive must never hold the loop-thread join hostage for the
    // rest of its receive budget
    if (!running_.load()) {
      sticky_err_ |= COMM_ABORTED | RANK_FAILED;
      return std::nullopt;
    }
    uint32_t ab = abort_err(c.comm());
    if (ab) {
      sticky_err_ |= ab;
      return std::nullopt;
    }
    uint32_t expect = t.inbound_seq[src];
    auto now = det_clock_now();
    if (now >= deadline) {
#if !defined(ACCL_FAULT_SUBCOMM_WEDGE)
      // last-gasp rescue: the segment may have been staged during the
      // FINAL slice (after this iteration's seek already missed), so a
      // timeout must re-probe staging before it classifies — otherwise
      // a message that did arrive is reported as a slow peer.  Taken
      // regardless of pool idleness: the budget is gone, in-order
      // delivery via the normal drain is no longer an option.
      if (staged_out) {
        auto sm = rx_.take_staged(c.comm(), src, tag, expect);
        if (sm) {
          staged_takes_.fetch_add(1);
          *staged_out = std::move(*sm);
          RxNotification n;
          n.index = UINT32_MAX;  // sentinel: payload rides *staged_out
          n.bytes = uint32_t(staged_out->payload.size());
          n.tag = staged_out->hdr.tag;
          n.src = staged_out->hdr.src;
          n.seqn = staged_out->hdr.seqn;
          n.comm = staged_out->hdr.comm_id;
          n.compressed = staged_out->hdr.compressed;
          return n;
        }
      }
#endif
      // classifying a timeout while the expected segment sits in the
      // staging queue is NOT a slow peer — the data arrived and the
      // pool never surfaced it (cross-comm pinning).  Counted in every
      // build: the detsched drill invariant reads this to tell a
      // genuine wedge from a legitimately-injected slow-peer timeout.
      if (rx_.has_staged_match(c.comm(), src, tag, expect))
        wedged_timeouts_.fetch_add(1);
      // a genuine matching failure (timeout after the recovery budget),
      // not an abort/shutdown wake — the seek-miss telemetry observable
      seek_misses_.fetch_add(1);
      return std::nullopt;
    }
    nanoseconds slice;
    bool fast_phase = attempts < retry_max;
    if (fast_phase) {
      // exponential backoff with deterministic jitter: reproducible
      // under a seeded chaos plan, decorrelated across ranks/seqns
      uint64_t base = retry_base_us_.load();
      uint64_t us = base << attempts;
      uint64_t j = (uint64_t(global_rank_ + 1) * 2654435761u) ^
                   (uint64_t(expect + 1) * 40503u) ^ attempts;
      us += j % (base / 2 + 1);
      slice = std::min<nanoseconds>(microseconds(us), deadline - now);
    } else {
      // fast phase exhausted (or lane disabled): 50 ms slices keep the
      // abort-wake latency bounded for the rest of the budget
      slice = std::min<nanoseconds>(milliseconds(50), deadline - now);
    }
    auto note = rx_.seek(c.comm(), src, tag, expect, slice);
    if (note) return note;
#if !defined(ACCL_FAULT_SUBCOMM_WEDGE)
    // Staged-segment rescue (the 8-rank sub-comm allgather wedge fix):
    // when every buffer is RESERVED, the expected segment may be parked
    // in the staging queue with nothing left to drain it — the comm
    // pinning the pool will not release() until ITS peer progresses,
    // which can transitively wait on this very receiver (a cross-comm
    // dependency cycle through the shared pool).  Instead of burning
    // the rest of the budget into a RECEIVE_TIMEOUT, consume the
    // payload straight from staging.  Only under pressure: with an idle
    // buffer present the normal deposit->notify path is at most one
    // release() away and must keep its in-order semantics.
    if (staged_out && !rx_.has_idle()) {
      auto sm = rx_.take_staged(c.comm(), src, tag, expect);
      if (sm) {
        staged_takes_.fetch_add(1);
        *staged_out = std::move(*sm);
        RxNotification n;
        n.index = UINT32_MAX;  // sentinel: payload rides *staged_out
        n.bytes = uint32_t(staged_out->payload.size());
        n.tag = staged_out->hdr.tag;
        n.src = staged_out->hdr.src;
        n.seqn = staged_out->hdr.seqn;
        n.comm = staged_out->hdr.comm_id;
        n.compressed = staged_out->hdr.compressed;
        return n;
      }
    }
#endif
    // Solicit a retransmission: the fast phase NACKs after every miss
    // (µs-scale recovery for a drop that already happened); afterwards
    // a steady-state NACK every ~200 ms covers a segment dropped LATER
    // than the fast phase — e.g. a slow sender whose first message hit
    // the chaos funnel after our backoff rounds were spent.  Without
    // the steady phase, recovery would race sender start time.
    bool steady_nack = retry_max > 0 && !fast_phase && (++chunks % 4 == 0);
    if ((fast_phase && retry_max) || steady_nack) {
      // a same-route entry sitting in the pool while the expected seqn
      // is missing is untrustworthy once a wire fault is in play (a
      // corrupt-seqn copy must never be consumable as future data):
      // evict the route — anything legitimate comes back with the
      // retransmission the NACK is about to trigger
      if (rx_.has_route_entry(c.comm(), src, tag)) {
        int n = rx_.evict_route(c.comm(), src, tag);
        if (evicted_out) *evicted_out += n;
      }
      send_nack(c.comm(), src, tag, expect);
      if (fast_phase) ++attempts;
    }
  }
}

void Engine::recv_eager(CallDesc& c, uint32_t src, uint32_t tag, uint64_t addr,
                        uint64_t elems, RecvMode mode, uint32_t strm,
                        uint32_t comp) {
  CommTable& t = *comm_ptr(c.comm());
  Dom d = dom(c);
  bool dst_c = d.pair && (comp & RES_COMPRESSED) && mode != RecvMode::STREAM;
  bool wire_c = d.pair && (comp & ETH_COMPRESSED);
  uint64_t seg_wire = t.rows[t.local].max_seg
                          ? t.rows[t.local].max_seg
                          : (rx_.buf_size() ? rx_.buf_size() : 1024);
  // must mirror the sender's wire-domain segmentation exactly
  uint64_t seg_elems = d.seg_elems(seg_wire, wire_c);

  uint64_t off = 0;
  uint64_t consumed_chunks = 0;
  bool first = true;
  while (off < elems || (first && elems == 0)) {
    first = false;
    uint64_t chunk = std::min(seg_elems, elems - off);
    int evicted_in_recovery = 0;
    Message staged_msg;  // payload home for a staging-queue rescue
    auto note = seek_recover(c, src, tag, &evicted_in_recovery, &staged_msg);
    if (!note) {
      // abort-wake: seek_recover already stamped the abort bits; this
      // call is fenced, not timed out — no fault classification
      if (sticky_err_ & COMM_ABORTED) return;
      // distinguish "nothing arrived" from "a segment with the wrong
      // sequence number is sitting in the pool" (out-of-order /
      // corrupted wire traffic — the reference's PACK_SEQ error class).
      // Stale duplicates (seqn behind expected) can never match and are
      // evicted so the pool doesn't leak; ahead-of-sequence entries
      // stay queued — they may legally match a recv posted later in a
      // different tag order — but their presence on this route still
      // classifies the failure as a sequence error, not a bare timeout.
      // Entries the NACK recovery evicted count the same way: they WERE
      // on the route when the expected seqn went missing.
      int stale = rx_.drop_stale(c.comm(), src, tag, t.inbound_seq[src] - 1);
      bool mismatched = stale > 0 || evicted_in_recovery > 0 ||
                        rx_.has_route_entry(c.comm(), src, tag);
      // reclamation bound: if the pool is exhausted, the broken route's
      // pinned segments would starve every other route (deposit() parks
      // everything in staging with no release to drain it) — force-evict
      // the route under pressure; otherwise leave ahead entries queued
      // for a possibly differently-ordered future recv
      if (mismatched && !rx_.has_idle())
        rx_.evict_route(c.comm(), src, tag);
      // lossy-rung self-heal: the expected seqn never arrived within the
      // timeout (fragment loss on the datagram rung) and will never
      // arrive.  Advance the route cursor to the oldest queued survivor
      // so FUTURE receives on the route proceed — but THIS call always
      // fails: a queued same-tag successor is indistinguishable from
      // this recv's own message, and silently splicing it in would
      // substitute wrong data with no error (at-most-once delivery with
      // an explicit error, never silent substitution).
      // Guards: only lossy rungs resync (on reliable transports an
      // absent expected seqn is corruption, kept as a hard error for the
      // fault-injection contract); and a PRESENT expected seqn under a
      // different tag is the documented misordered-recv case (PACK_SEQ
      // error, entry kept for the correctly-ordered recv).
      if (lossy_transport_ &&
          !rx_.has_seqn(c.comm(), src, t.inbound_seq[src])) {
        // The hole sits inside THIS message, whose remaining segments
        // occupy exactly the seqn window [expected, expected+remaining).
        // Evict any survivors in that window (a stranded tail segment
        // carries this recv's tag and a future same-tag seek would
        // silently consume it as shifted data) and advance the cursor
        // past the whole message — a queued FUTURE same-tag message
        // starts after the window, survives untouched, and matches the
        // next recv, which is exactly the in-order matching contract.
        // Tradeoff: a recv that merely timed out waiting for a slow (not
        // lost) sender also skips; its late segments arrive behind the
        // cursor and are dropped as stale — loss semantics, by design,
        // on the lossy rung only.
        uint64_t total_chunks =
            elems ? (elems + seg_elems - 1) / seg_elems : 1;
        uint32_t remaining = uint32_t(total_chunks - consumed_chunks);
        rx_.evict_window(c.comm(), src, tag, t.inbound_seq[src], remaining);
        t.inbound_seq[src] += remaining;
      }
      sticky_err_ |= mismatched ? PACK_SEQ_NUMBER_ERROR
                                : RECEIVE_TIMEOUT_ERROR;
      return;
    }
    t.inbound_seq[src]++;
    // a staged rescue carries its payload in staged_msg, not the pool
    const uint8_t* data = note->index == UINT32_MAX
                              ? staged_msg.payload.data()
                              : rx_.data(note->index);
    // interpret the arriving bytes via OUR OWN flag algebra — the
    // reference eth header carries no compressed marker; each end derives
    // the wire representation from its arithcfg + ETH flag, which is what
    // makes directional pairs (f16 sender / f32+compress receiver) agree
    bool got_c = wire_c;
    uint64_t got_elems;
    if (got_c && d.blk) {
      // self-describing block-scaled segment: decode + validate the
      // framing against our own arithcfg geometry (a mismatched or
      // truncated segment is a compression error, never an OOB read)
      got_elems = i8_wire_elems(data, note->bytes, d.blk);
      if (got_elems == UINT64_MAX) {
        sticky_err_ |= COMPRESSION_ERROR;
        got_elems = 0;
      }
    } else {
      got_elems = note->bytes / std::max<uint64_t>(1, d.eb(got_c));
    }
    if (got_elems != chunk) sticky_err_ |= SEGMENTER_EXPECTED_BTT_ERROR;
    uint64_t n = std::min(got_elems, chunk);
    switch (mode) {
      case RecvMode::COPY: {
        MutexLock g(mem_mu_);
        uint8_t* dst = mem(addr + off * d.eb(dst_c), n * d.eb(dst_c));
        convert_elems(d, data, got_c, dst, dst_c, n);
        break;
      }
      case RecvMode::REDUCE: {
        // fused recv-reduce: the wire payload is OP1, the accumulator at
        // addr is OP0 and RES (mixed-precision accumulate per arithcfg;
        // ETH>>2 -> OP1_COMPRESSED shifting, fw :1953-1955)
        MutexLock g(mem_mu_);
        uint8_t* acc = mem(addr + off * d.eb(dst_c), n * d.eb(dst_c));
        reduce_mixed(c, acc, dst_c, data, got_c, acc, dst_c, n);
        break;
      }
      case RecvMode::STREAM: {
        // compute streams carry the uncompressed representation
        std::vector<uint8_t> out(n * d.ub);
        if (convert_elems(d, data, got_c, out.data(), false, n) == OK)
          stream_for(strm)->push(std::move(out));
        break;
      }
    }
    if (note->index != UINT32_MAX) rx_.release(note->index);
    // a duplicated segment's stale copy (seqn <= the one just consumed)
    // can never match a future seek; drop it now instead of letting it
    // pin a pool buffer until some later timeout runs eviction
    rx_.drop_stale(c.comm(), src, tag, note->seqn);
    off += chunk;
    ++consumed_chunks;
  }
}

// ---------------------------------------------------------------------------
// rendezvous protocol primitives (fw :142-350; SURVEY §3.5)
// ---------------------------------------------------------------------------
void Engine::rndzv_post_addr(CallDesc& c, Progress& p, uint32_t src,
                             uint32_t tag, uint64_t addr, uint64_t elems,
                             bool dst_c) {
  CommTable& t = *comm_ptr(c.comm());
  Dom d = dom(c);
  if (p.pending()) {
    // record the wire->landing conversion the depacketizer must apply
    // when the peer's one-sided write arrives; both peers derive the
    // wire representation from their own arithcfg + ETH flag
    {
      MutexLock g(posted_mu_);
      posted_[PostedKey{c.comm(), src, tag, addr}] =
          PostedRndzv{elems, d.eth, dst_c && d.pair, d.comp_kind,
                      uint32_t(d.ub), uint32_t(d.cb), d.blk};
    }
    c.rndzv_posts.push_back({c.comm(), src, tag, addr});
    // advertise our landing address to the sender (RNDZVS_INIT)
    Message msg;
    msg.hdr.count = uint32_t(elems);
    msg.hdr.tag = tag;
    msg.hdr.src = t.local;
    msg.hdr.vaddr = addr;
    msg.hdr.msg_type = uint8_t(MsgType::RndzvsInit);
    msg.hdr.comm_id = c.comm();
    msg.hdr.epoch = epoch_of(c.comm());
    send_out(t.rows[src].session, std::move(msg));
  }
  p.done();
}

void Engine::rndzv_wait_done(CallDesc& c, Progress& p, uint32_t src,
                             uint32_t tag) {
  if (p.pending()) {
    // wait for the write-done completion — matched against the address
    // THIS call advertised for (src, tag), so concurrent calls sharing
    // (comm, src, tag) can only consume their own completions
    auto done = completions_.pop_match(
        [&](const RndzvDone& d) {
          if (d.comm != c.comm() || d.src != src || d.tag != tag)
            return false;
          for (const auto& k : c.rndzv_posts)
            if (uint32_t(k[0]) == c.comm() && uint32_t(k[1]) == src &&
                uint32_t(k[2]) == tag && k[3] == d.vaddr)
              return true;
          return c.rndzv_posts.empty();  // no record: legacy tag match
        },
        milliseconds(2));
    if (!done) throw NotReadyEx{c.current_step};
  }
  p.done();
}

void Engine::rndzv_recv(CallDesc& c, Progress& p, uint32_t src, uint32_t tag,
                        uint64_t addr, uint64_t elems, bool dst_c) {
  rndzv_post_addr(c, p, src, tag, addr, elems, dst_c);
  rndzv_wait_done(c, p, src, tag);
}

void Engine::rndzv_send(CallDesc& c, Progress& p, uint32_t dst, uint32_t tag,
                        uint64_t addr, uint64_t elems, bool src_c) {
  CommTable& t = *comm_ptr(c.comm());
  Dom d = dom(c);
  src_c = src_c && d.pair;
  if (p.pending()) {
    // step: match the receiver's advertised address, then issue the
    // one-sided write (single step so the INIT can't be consumed twice)
    auto a = pending_addrs_.pop_match(
        [&](const RndzvAddr& r) {
          return r.comm == c.comm() && r.src == dst && r.tag == tag;
        },
        milliseconds(2));
    if (!a) throw NotReadyEx{c.current_step};
    // Direct p2p fast path (FPGABufferP2P role): when the receiver's
    // advertised landing address lies inside a peer-registered p2p
    // window of an engine we can reach in-process, write the payload
    // straight into the peer's devicemem — no wire message, no framing
    // copy.  Restricted to the plain domain on the SENDER side (no ETH
    // compression, uncompressed source, devicemem operand) so the
    // single copy below is the whole data movement; the receiver's own
    // posted-record conversion still runs inside land_p2p, identical
    // to the wire path.  Own mem_mu_ is NOT held across the peer call
    // (two engines direct-writing at each other would deadlock on
    // crossed mem locks); devicemem_ never reallocates, so the raw
    // pointer stays valid.
    // an armed one-shot egress fault must not be skipped (or left armed
    // for an unrelated later message) by the wire bypass — faulted sends
    // take the wire path where send_out applies the injection
    if (peer_hook_ && !d.eth && !src_c && !(addr & HOST_ADDR_BIT) &&
        fault_.load() == 0 && !killed_.load()) {
      Engine* peer = peer_hook_(t.rows[dst].session);
      uint64_t nbytes = elems * d.ub;
      if (peer && peer != this && peer->p2p_covers(a->vaddr, nbytes)) {
        uint8_t* pdata;
        {
          MutexLock g(mem_mu_);
          pdata = mem(addr, nbytes);
        }
        if (sticky_err_ == 0) {
          WireHeader hdr;
          hdr.count = uint32_t(nbytes);
          hdr.tag = tag;
          hdr.src = t.local;
          hdr.vaddr = a->vaddr;
          hdr.msg_type = uint8_t(MsgType::RndzvsMsg);
          hdr.comm_id = c.comm();
          hdr.epoch = epoch_of(c.comm());
          hdr.compressed = 0;
          // per-link: the p2p write moved `nbytes` across this rank
          // pair even though the wire (and tx_stats) never saw it
          link_tx(c.comm(), dst, nbytes);
          peer->land_p2p(hdr, pdata, nbytes);
          p.done();
          return;
        }
      }
    }
    Message msg;
    msg.hdr.tag = tag;
    msg.hdr.src = t.local;
    msg.hdr.vaddr = a->vaddr;
    msg.hdr.msg_type = uint8_t(MsgType::RndzvsMsg);
    msg.hdr.comm_id = c.comm();
    msg.hdr.epoch = epoch_of(c.comm());
    {
      // convert the operand into OUR wire representation (own arithcfg +
      // ETH flag, same rule as eager); the receiver's depacketizer
      // applies its own wire->landing conversion on arrival — this is
      // the ETH-compressed rendezvous the reference leaves as a TODO
      MutexLock g(mem_mu_);
      uint8_t* pdata = mem(addr, elems * d.eb(src_c));
      msg.payload.resize(d.wbytes(elems, d.eth));
      // on conversion failure (unknown compressor lane) fall through to
      // p.done() with the sticky error set and no wire message — an
      // early return here would desynchronize the schedule's resume
      // cursor after the RNDZVS_INIT was already consumed
      if (d.eth && d.blk && !src_c && sticky_err_ == 0) {
        // rendezvous sends: EF only for reduction scenarios (tree
        // reduce contributions) — bcast/gather/scatter one-sided
        // writes must quantize cleanly
        Op sc = c.scenario();
        bool use_ef = d.ef && (sc == Op::Reduce || sc == Op::Allreduce ||
                               sc == Op::ReduceScatter);
        quantize_egress(d, use_ef, c.comm(), dst, addr,
                        reinterpret_cast<const float*>(pdata),
                        msg.payload.data(), elems);
      } else {
        convert_elems(d, pdata, src_c, msg.payload.data(), d.eth, elems);
      }
      msg.hdr.compressed = d.eth ? (d.blk ? 2 : 1) : 0;
    }
    if (sticky_err_ == 0) {
      msg.hdr.count = uint32_t(msg.payload.size());
      if (d.eth) {
        compressed_tx_bytes_.fetch_add(msg.payload.size());
        compressed_tx_logical_bytes_.fetch_add(elems * d.ub);
        link_count(c.comm(), dst, &LinkCounters::comp_tx_bytes,
                   msg.payload.size());
      }
      link_tx(c.comm(), dst, msg.payload.size());
      send_out(t.rows[dst].session, std::move(msg));
    }
  }
  p.done();
}

// ---------------------------------------------------------------------------
// collective schedules
// ---------------------------------------------------------------------------
void Engine::coll_send(CallDesc& c, Progress& p) {
  uint64_t elems = c.count();
  uint32_t dst = c.root_src_dst();
  uint32_t comp = c.compression();
  bool from_stream = c.stream_flags() & 0x1;  // OP0_STREAM
  uint32_t to_strm =
      (c.stream_flags() & 0x2) ? c.tag() : 0;  // RES_STREAM: remote stream
  if (use_rendezvous(c, elems)) {
    rndzv_send(c, p, dst, c.tag(), c.addr0(), elems, comp & OP0_COMPRESSED);
  } else {
    send_eager(c, dst, c.tag(), c.addr0(), elems, from_stream, to_strm, comp);
  }
}

void Engine::coll_recv(CallDesc& c, Progress& p) {
  uint64_t elems = c.count();
  uint32_t src = c.root_src_dst();
  uint32_t comp = c.compression();
  if (use_rendezvous(c, elems)) {
    rndzv_recv(c, p, src, c.tag(), c.addr2(), elems, comp & RES_COMPRESSED);
  } else {
    RecvMode mode =
        (c.stream_flags() & 0x2) ? RecvMode::STREAM : RecvMode::COPY;
    recv_eager(c, src, c.tag(), c.addr2(), elems, mode, c.tag(), comp);
  }
}

// Broadcast (fw :798-990): eager = root loops over ranks; rendezvous =
// out-of-order flat tree for small worlds, binomial tree otherwise
// (threshold = BCAST_FLAT_TREE_MAX_RANKS tuning register).
void Engine::coll_bcast(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  uint64_t elems = c.count();
  uint32_t root = c.root_src_dst();
  uint32_t comp = c.compression();
  if (t.size <= 1) return;
  if (use_rendezvous(c, elems)) {
    if (t.size > bcast_flat_max_ranks_) {
      tree_bcast(c, p, root, t.local == root ? c.addr0() : 0, c.addr2(),
                 elems, comp & OP0_COMPRESSED, comp & RES_COMPRESSED);
    } else if (t.local == root) {
      for (uint32_t r = 0; r < t.size; ++r)
        if (r != root)
          rndzv_send(c, p, r, c.tag(), c.addr0(), elems,
                     comp & OP0_COMPRESSED);
    } else {
      rndzv_recv(c, p, root, c.tag(), c.addr2(), elems,
                 comp & RES_COMPRESSED);
    }
    return;
  }
  if (t.local == root) {
    for (uint32_t r = 0; r < t.size; ++r)
      if (r != root)
        send_eager(c, r, c.tag(), c.addr0(), elems, false, 0, comp);
  } else {
    recv_eager(c, root, c.tag(), c.addr2(), elems, RecvMode::COPY, 0, comp);
  }
}

// Scatter: root walks the rank-strided source (the reference's
// MOVE_INCREMENT addressing, fw :1082-1124), local chunk copied in place.
void Engine::coll_scatter(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();
  uint32_t root = c.root_src_dst();
  uint32_t comp = c.compression();
  if (t.local == root) {
    // source slices stride in the OP0 representation (MOVE_INCREMENT
    // addressing over the operand's own element width, fw :1082-1124)
    uint64_t src_stride = elems * d.eb(d.op0);
    for (uint32_t r = 0; r < t.size; ++r) {
      uint64_t src = c.addr0() + uint64_t(r) * src_stride;
      if (r == root) {
        local_move(c, src, c.addr2(), elems, d.op0, d.res);
      } else if (use_rendezvous(c, elems)) {
        rndzv_send(c, p, r, c.tag(), src, elems, d.op0);
      } else {
        send_eager(c, r, c.tag(), src, elems, false, 0, comp);
      }
    }
  } else {
    if (use_rendezvous(c, elems))
      rndzv_recv(c, p, root, c.tag(), c.addr2(), elems, d.res);
    else
      recv_eager(c, root, c.tag(), c.addr2(), elems, RecvMode::COPY, 0, comp);
  }
}

// Gather: eager ring relay — every non-root forwards toward the root,
// which receives blocks in ring order (fw :1207-1295).  Large payloads
// use direct rendezvous writes to the root (flat; fan-in control comes
// with the tuning milestone, fw :1163).
void Engine::coll_gather(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();
  uint32_t root = c.root_src_dst();
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  uint64_t res_stride = elems * d.eb(d.res);
  if (P == 1) {
    local_move(c, c.addr0(), c.addr2(), elems, d.op0, d.res);
    return;
  }
  bool rndzv = use_rendezvous(c, elems);
  uint32_t dist = (t.local + P - root) % P;  // distance to root along ring
  if (rndzv) {
    // flat tree with out-of-order address arrival (fw :1011-1081 shape):
    // the root publishes landing addresses in windows of at most
    // GATHER_FLAT_TREE_MAX_FANIN (fw :1163) and collects completions in
    // whatever order the writes land
    if (t.local == root) {
      local_move(c, c.addr0(), c.addr2() + uint64_t(root) * res_stride,
                 elems, d.op0, d.res);
      // count-based fan-in (fw :1163): small gathers publish every
      // landing address at once; above GATHER_FLAT_TREE_MAX_COUNT bytes
      // the fan-in window caps concurrent inbound writes
      // root-only decision, so cross-rank divergence is impossible, but
      // wire width keeps the threshold meaning consistent with reduce
      uint32_t fanin = (d.wbytes(elems, d.eth) > gather_flat_max_count_.load())
                           ? gather_flat_max_fanin_.load()
                           : P - 1;
      fanin = std::max(1u, fanin);
      uint32_t i = 1;
      while (i < P) {
        uint32_t w = std::min(fanin, P - i);
        for (uint32_t j = 0; j < w; ++j) {
          uint32_t r = (root + i + j) % P;
          rndzv_post_addr(c, p, r, c.tag(),
                          c.addr2() + uint64_t(r) * res_stride, elems, d.res);
        }
        for (uint32_t j = 0; j < w; ++j)
          rndzv_wait_done(c, p, (root + i + j) % P, c.tag());
        i += w;
      }
    } else {
      rndzv_send(c, p, root, c.tag(), c.addr0(), elems, d.op0);
    }
    return;
  }
  if (t.local == root) {
    local_move(c, c.addr0(), c.addr2() + uint64_t(root) * res_stride, elems,
               d.op0, d.res);
    uint32_t next = (t.local + 1) % P;
    for (uint32_t i = 0; i < P - 1; ++i) {
      uint32_t origin = (root + 1 + i) % P;
      recv_eager(c, next, c.tag(), c.addr2() + uint64_t(origin) * res_stride,
                 elems, RecvMode::COPY, 0, comp);
    }
  } else {
    uint32_t prev = (t.local + P - 1) % P;
    uint32_t next = (t.local + 1) % P;
    send_eager(c, prev, c.tag(), c.addr0(), elems, false, 0, comp);
    // relay the blocks of everyone farther from the root through an
    // uncompressed scratch staging buffer (wire -> u -> wire)
    uint64_t tmp = alloc(elems * d.ub, 64);
    // the scratch is uncompressed on both sides of the relay, so only
    // the wire bit survives the hop
    uint32_t relay = comp & ETH_COMPRESSED;
    for (uint32_t i = 0; i < P - 1 - dist; ++i) {
      recv_eager(c, next, c.tag(), tmp, elems, RecvMode::COPY, 0,
                 comp & ~uint32_t(RES_COMPRESSED));
      send_eager(c, prev, c.tag(), tmp, elems, false, 0, relay);
    }
    free_addr(tmp);
  }
}

// All-gather: ring relay with a local self-copy first (fw :1404-1502).
// The relay operates on result-buffer slices, so sends read the RES
// representation (RES->OP0 relay algebra, fw :1408-1411).
void Engine::coll_allgather(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  uint64_t res_stride = elems * d.eb(d.res);
  local_move(c, c.addr0(), c.addr2() + uint64_t(t.local) * res_stride, elems,
             d.op0, d.res);
  if (P == 1) return;
  uint32_t next = (t.local + 1) % P;
  uint32_t prev = (t.local + P - 1) % P;
  // sends read result-buffer slices, so their OP0 domain is the call's
  // RES bit (fw :1408-1411 relay algebra applied to the slice source)
  uint32_t send_comp = (d.res ? uint32_t(OP0_COMPRESSED) : 0u)
                       | (comp & ETH_COMPRESSED);
  for (uint32_t s = 0; s < P - 1; ++s) {
    uint32_t send_origin = (t.local + P - s) % P;
    uint32_t recv_origin = (t.local + P - 1 - s) % P;
    send_eager(c, next, c.tag(),
               c.addr2() + uint64_t(send_origin) * res_stride, elems, false,
               0, send_comp);
    recv_eager(c, prev, c.tag(),
               c.addr2() + uint64_t(recv_origin) * res_stride, elems,
               RecvMode::COPY, 0, comp);
  }
}

// Reduce: eager ring/daisy-chain with fused recv-reduce(-send) at the
// interior ranks (fw :1730-1743); rendezvous = flat gather-and-accumulate
// for small worlds (fw :1533-1602) or binomial tree with scratchpads
// (fw :1603-1728).
void Engine::coll_reduce(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();
  uint64_t bytes = elems * d.ub;  // scratch/stream staging is uncompressed
  uint32_t root = c.root_src_dst();
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  // mem<->stream reduce variants (reference: test.cpp:813-910): a
  // streamed operand is materialized from the kernel stream into a
  // scratch lease, and a streamed result is pushed to the local compute
  // stream addressed by the tag, after the schedule runs over buffers.
  bool op_stream = c.stream_flags() & 0x1;   // OP0_STREAM
  bool res_stream = c.stream_flags() & 0x2;  // RES_STREAM
  uint64_t op_addr = c.addr0();
  uint64_t res_addr = c.addr2();
  bool is_root = t.local == root;
  // scratch leases live in the descriptor so execute() frees them on
  // every exit path (stream-flagged calls never reach the rendezvous
  // schedules, which use the same lease slots)
  // operand/result domains: scratch staging (streams) is uncompressed
  bool op_c = d.op0;
  bool res_c = d.res;
  if (op_stream) {
    if (!c.scratch0) c.scratch0 = alloc(bytes, 64);
    if (!drain_krnl_to(c.scratch0, bytes)) return;
    op_addr = c.scratch0;
    op_c = false;
  }
  if (res_stream && is_root) {
    if (!c.scratch1) c.scratch1 = alloc(bytes, 64);
    res_addr = c.scratch1;
    res_c = false;
  }
  if (P == 1) {
    local_move(c, op_addr, res_addr, elems, op_c, res_c);
    if (res_stream && is_root && sticky_err_ == 0)
      push_local_stream(c.tag(), res_addr, bytes);
    return;
  }
  if (use_rendezvous(c, elems)) {
    // stream-flagged calls never reach rendezvous (use_rendezvous forces
    // eager for them), so the scratch slots are free for the schedules
    // count threshold measured on WIRE bytes, like use_rendezvous: the
    // rank-local uncompressed width diverges across directional arithcfg
    // pairs and a schedule-selection split would wedge the rendezvous
    // handshake (fw :1533 consults its own width, but its compression is
    // symmetric by construction — ours is not)
    uint64_t wire_bytes = d.wbytes(elems, d.eth);
    if (P <= reduce_flat_max_ranks_ || wire_bytes <= reduce_flat_max_count_) {
      // flat when the world is small OR the payload is small: tree setup
      // overhead beats the flat fan-in only for large payloads on large
      // worlds
      if (t.local == root) {
        if (!c.scratch0) c.scratch0 = alloc(bytes, 64);
        step_local(p, [&] {
          local_move(c, c.addr0(), c.addr2(), elems, d.op0, d.res);
        });
        for (uint32_t i = 1; i < P; ++i) {
          rndzv_recv(c, p, (root + i) % P, c.tag(), c.scratch0, elems, false);
          step_local(p, [&] {
            MutexLock g(mem_mu_);
            uint8_t* acc = mem(c.addr2(), elems * d.eb(d.res));
            uint8_t* tmp = mem(c.scratch0, bytes);
            reduce_mixed(c, acc, d.res, tmp, false, acc, d.res, elems);
          });
        }
      } else {
        rndzv_send(c, p, root, c.tag(), c.addr0(), elems, d.op0);
      }
    } else {
      // binomial tree: root accumulates in the result buffer, interior
      // nodes in an uncompressed scratch lease; every receiver needs a
      // landing pad
      uint64_t acc = t.local == root ? c.addr2() : 0;
      bool acc_c = t.local == root ? d.res : false;
      if (t.local != root) {
        if (!c.scratch0) c.scratch0 = alloc(bytes, 64);
        acc = c.scratch0;
      }
      if (!c.scratch1) c.scratch1 = alloc(bytes, 64);
      tree_reduce(c, p, root, c.addr0(), acc, c.scratch1, elems, d.op0,
                  acc_c);
    }
    return;
  }
  uint32_t pos = (t.local + P - root) % P;  // chain position; root = 0
  uint32_t next = (t.local + 1) % P;
  uint32_t prev = (t.local + P - 1) % P;
  if (pos == 1) {
    // head of the chain: just forward our contribution (a reduction
    // operand — the EF residual's legal habitat)
    send_eager(c, next, c.tag(), op_addr, elems, false, 0,
               (op_c ? uint32_t(OP0_COMPRESSED) : 0u) | (comp & ETH_COMPRESSED),
               /*reduce_stream=*/true);
  } else if (pos != 0) {
    // interior: receive partial, fold our contribution, forward through
    // an uncompressed scratch accumulator
    uint64_t tmp = alloc(bytes, 64);
    local_move(c, op_addr, tmp, elems, op_c, false);
    recv_eager(c, prev, c.tag(), tmp, elems, RecvMode::REDUCE, 0,
               comp & ETH_COMPRESSED);
    send_eager(c, next, c.tag(), tmp, elems, false, 0,
               comp & ETH_COMPRESSED, /*reduce_stream=*/true);
    free_addr(tmp);
  } else {
    // root: receive the chain's partial, fold our contribution into res
    local_move(c, op_addr, res_addr, elems, op_c, res_c);
    recv_eager(c, prev, c.tag(), res_addr, elems, RecvMode::REDUCE, 0,
               (res_c ? uint32_t(RES_COMPRESSED) : 0u) | (comp & ETH_COMPRESSED));
    // deliver to the compute stream only on success — a consumer must
    // not be handed a correctly-sized but partially-reduced payload
    if (res_stream && sticky_err_ == 0)
      push_local_stream(c.tag(), res_addr, bytes);
  }
}

// Ring reduce-scatter core shared by reduce_scatter and allreduce
// (fw :1782-1850, :1888-2071): step 0 sends chunk (rank-1); interior
// steps fuse recv+reduce+forward; the final step folds chunk `rank`.
void Engine::ring_reduce_scatter(CallDesc& c, uint64_t src_base,
                                 const std::vector<uint64_t>& off,
                                 const std::vector<uint64_t>& len,
                                 uint64_t own_dst) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  uint32_t r = t.local;
  uint32_t next = (r + 1) % P;
  uint32_t prev = (r + P - 1) % P;
  if (P == 1) {
    local_move(c, src_base + off[0] * d.eb(d.op0), own_dst, len[0], d.op0,
               d.res);
    return;
  }
  uint32_t first = (r + P - 1) % P;
  // per-step algebra (fw :1929-1955): sends keep OP0, replace RES by the
  // wire bit; the fused recv-reduce takes the wire payload as OP1.
  // Every send here carries a reduction partial — the EF residual's
  // legal habitat (reduce_stream=true).
  send_eager(c, next, c.tag(), src_base + off[first] * d.eb(d.op0),
             len[first], false, 0,
             (d.op0 ? uint32_t(OP0_COMPRESSED) : 0u) | (comp & ETH_COMPRESSED),
             /*reduce_stream=*/true);
  uint64_t maxlen = *std::max_element(len.begin(), len.end());
  uint64_t tmp = alloc(std::max<uint64_t>(maxlen * d.ub, 64), 64);
  for (uint32_t s = 1; s <= P - 1; ++s) {
    // chunk index arriving this step: (r - 1 - s) mod P
    uint32_t chunk =
        uint32_t(((int64_t(r) - 1 - int64_t(s)) % int64_t(P) + P) % P);
    // stage our contribution uncompressed, fold the wire partial in
    local_move(c, src_base + off[chunk] * d.eb(d.op0), tmp, len[chunk],
               d.op0, false);
    recv_eager(c, prev, c.tag(), tmp, len[chunk], RecvMode::REDUCE, 0,
               comp & ETH_COMPRESSED);
    if (chunk == r) {
      // wire-form agreement (EQuARX discipline): under an allreduce's
      // compressed wire the owner's finished chunk will be RELAYED to
      // every peer in the gather phase as quant(chunk) — consume the
      // SAME wire form locally, or ranks would disagree on exactly
      // the chunks they own by a full quantization step.  The
      // roundtrip mirrors the gather phase's SEGMENTATION (block
      // partitions are segment-relative), so owner and peers land
      // within one ulp of scale arithmetic of each other.
      // reduce_scatter keeps the exact accumulate: its chunk is
      // rank-private by contract.
      if (d.eth && d.blk && c.scenario() == Op::Allreduce &&
          sticky_err_ == 0) {
        uint64_t seg_wire = t.rows[next].max_seg
                                ? t.rows[next].max_seg
                                : (rx_.buf_size() ? rx_.buf_size() : 1024);
        uint64_t seg = d.seg_elems(seg_wire, true);
        thread_local std::vector<uint8_t> rt;
        MutexLock g(mem_mu_);
        for (uint64_t o = 0; o < len[chunk]; o += seg) {
          uint64_t n = std::min<uint64_t>(seg, len[chunk] - o);
          rt.resize(d.wbytes(n, true));
          uint8_t* p = mem(tmp + o * d.ub, n * d.ub);
          if (convert_elems(d, p, false, rt.data(), true, n) != OK) break;
          convert_elems(d, rt.data(), true, p, false, n);
        }
      }
      local_move(c, tmp, own_dst, len[chunk], false, d.res);
    } else {
      send_eager(c, next, c.tag(), tmp, len[chunk], false, 0,
                 comp & ETH_COMPRESSED, /*reduce_stream=*/true);
    }
  }
  free_addr(tmp);
}

// Ring all-gather over chunks already resident in dst (fw :1990-2066);
// slices live in the RES representation throughout.
void Engine::ring_allgather(CallDesc& c, uint64_t base,
                            const std::vector<uint64_t>& off,
                            const std::vector<uint64_t>& len) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  uint32_t r = t.local;
  if (P == 1) return;
  uint32_t next = (r + 1) % P;
  uint32_t prev = (r + P - 1) % P;
  // slices live in the RES representation; sends treat that as OP0
  uint32_t send_comp = (d.res ? uint32_t(OP0_COMPRESSED) : 0u)
                       | (comp & ETH_COMPRESSED);
  for (uint32_t s = 0; s < P - 1; ++s) {
    uint32_t send_chunk = uint32_t(((int64_t(r) - int64_t(s)) % int64_t(P) + P) % P);
    uint32_t recv_chunk = uint32_t(((int64_t(r) - 1 - int64_t(s)) % int64_t(P) + P) % P);
    send_eager(c, next, c.tag(), base + off[send_chunk] * d.eb(d.res),
               len[send_chunk], false, 0, send_comp);
    recv_eager(c, prev, c.tag(), base + off[recv_chunk] * d.eb(d.res),
               len[recv_chunk], RecvMode::COPY, 0, comp);
  }
}

void Engine::coll_reduce_scatter(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();  // per-rank result elements
  uint32_t P = t.size;
  if (P > 1 && use_rendezvous(c, elems * P)) {
    // rendezvous: tree-reduce the whole vector to rank 0 through
    // uncompressed scratch, then scatter the slices
    // (fw :1768-1781 reduce-to-0 + scatter)
    uint64_t total_u = elems * P * d.ub;
    if (!c.scratch0) c.scratch0 = alloc(total_u, 64);
    if (!c.scratch1) c.scratch1 = alloc(total_u, 64);
    tree_reduce(c, p, 0, c.addr0(), c.scratch0, c.scratch1, elems * P,
                d.op0, false);
    if (t.local == 0) {
      step_local(p, [&] {
        local_move(c, c.scratch0, c.addr2(), elems, false, d.res);
      });
      for (uint32_t r = 1; r < P; ++r)
        rndzv_send(c, p, r, c.tag(), c.scratch0 + uint64_t(r) * elems * d.ub,
                   elems, false);
    } else {
      rndzv_recv(c, p, 0, c.tag(), c.addr2(), elems, d.res);
    }
    return;
  }
  std::vector<uint64_t> off(P), len(P, elems);
  for (uint32_t i = 0; i < P; ++i) off[i] = uint64_t(i) * elems;
  ring_reduce_scatter(c, c.addr0(), off, len, c.addr2());
}

void Engine::coll_allreduce(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint32_t P = t.size;
  uint64_t total = uint64_t(c.count());
  if (P == 1) {
    local_move(c, c.addr0(), c.addr2(), total, d.op0, d.res);
    return;
  }
  if (use_rendezvous(c, total)) {
    // rendezvous: tree reduce to rank 0 accumulating directly in every
    // rank's result buffer, then tree broadcast the final value
    // (fw :1878-1887 reduce-then-bcast)
    if (!c.scratch0) c.scratch0 = alloc(total * d.ub, 64);
    tree_reduce(c, p, 0, c.addr0(), c.addr2(), c.scratch0, total, d.op0,
                d.res);
    tree_bcast(c, p, 0, c.addr2(), c.addr2(), total, d.res, d.res);
    return;
  }
  // chunk the element range across ranks (bulk/tail split for ragged
  // sizes, fw :1909-1912)
  std::vector<uint64_t> off(P), len(P);
  uint64_t base_elems = total / P, extra = total % P, cursor = 0;
  for (uint32_t i = 0; i < P; ++i) {
    uint64_t e = base_elems + (i < extra ? 1 : 0);
    off[i] = cursor;
    len[i] = e;
    cursor += e;
  }
  ring_reduce_scatter(c, c.addr0(), off, len,
                      c.addr2() + off[t.local] * d.eb(d.res));
  ring_allgather(c, c.addr2(), off, len);
}

// All-to-all: send every peer its slice, then collect ours (the
// reference's eager path is unimplemented — COLLECTIVE_NOT_IMPLEMENTED,
// fw :2213-2215 — we implement it; the rendezvous path mirrors the
// reference's fused simultaneous flat trees :2123-2218).
void Engine::coll_alltoall(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint64_t elems = c.count();
  uint32_t comp = c.compression();
  uint32_t P = t.size;
  uint64_t op_stride = elems * d.eb(d.op0);
  uint64_t res_stride = elems * d.eb(d.res);
  local_move(c, c.addr0() + uint64_t(t.local) * op_stride,
             c.addr2() + uint64_t(t.local) * res_stride, elems, d.op0, d.res);
  bool rndzv = use_rendezvous(c, elems);
  if (rndzv) {
    // fused simultaneous flat trees (fw :2123-2218): publish all landing
    // addresses, write as peer addresses arrive (out of order), then
    // drain completions
    for (uint32_t i = 1; i < P; ++i) {
      uint32_t r = (t.local + P - i) % P;
      rndzv_post_addr(c, p, r, c.tag(),
                      c.addr2() + uint64_t(r) * res_stride, elems, d.res);
    }
    for (uint32_t i = 1; i < P; ++i) {
      uint32_t r = (t.local + i) % P;
      rndzv_send(c, p, r, c.tag(), c.addr0() + uint64_t(r) * op_stride,
                 elems, d.op0);
    }
    for (uint32_t i = 1; i < P; ++i)
      rndzv_wait_done(c, p, (t.local + P - i) % P, c.tag());
    return;
  }
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (t.local + i) % P;
    send_eager(c, r, c.tag(), c.addr0() + uint64_t(r) * op_stride, elems,
               false, 0, comp);
  }
  // receive in the same relative order every peer sends (peer local+1
  // sent to us first): consuming earliest arrivals first drains the rx
  // pool instead of pinning it behind a not-yet-arrived route, which
  // matters when (P-1) x segments approaches the pool size
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (t.local + P - i) % P;  // peer for whom we are (their+i)
    recv_eager(c, r, c.tag(), c.addr2() + uint64_t(r) * res_stride, elems,
               RecvMode::COPY, 0, comp);
  }
}

// All-to-all with per-peer counts (no reference counterpart).  The count
// operand (addr1) holds P int32 counts in rows of count() elements; the
// count result's address rides in the root and function words.  Step 1
// trades each peer its count in one 8-byte slot (eager); the rows then
// move on coll_alltoall's eager or rendezvous paths with per-peer
// offsets, packed in rank order on the send side and in source order
// on the receive side.  Both peers of a pair know its count after step
// 1, so they pick the same protocol.  Every primitive is its own step,
// so a rendezvous retry resumes without resending.
void Engine::coll_alltoallv(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  Dom d = dom(c);
  uint32_t P = t.size;
  uint32_t me = t.local;
  uint64_t row = c.count();
  uint64_t slot = 8 / d.ub;  // one 8-byte count in payload elements
  uint64_t counts_addr = c.addr3();
  if (!c.scratch0) c.scratch0 = alloc(uint64_t(P) * 8, 64);  // ours
  if (!c.scratch1) c.scratch1 = alloc(uint64_t(P) * 8, 64);  // theirs
  step_local(p, [&] {
    MutexLock g(mem_mu_);
    const uint8_t* in = mem(c.addr1(), uint64_t(P) * 4);
    uint8_t* ours = mem(c.scratch0, uint64_t(P) * 8);
    uint8_t* theirs = mem(c.scratch1, uint64_t(P) * 8);
    for (uint32_t j = 0; j < P; ++j) {
      int32_t v;
      std::memcpy(&v, in + 4 * j, 4);
      uint64_t n = v < 0 ? 0 : uint64_t(v);
      std::memcpy(ours + 8 * j, &n, 8);
    }
    std::memcpy(theirs + 8 * me, ours + 8 * me, 8);
  });
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (me + i) % P;
    step_local(p, [&] {
      send_eager(c, r, c.tag(), c.scratch0 + 8 * r, slot, false, 0, 0);
    });
  }
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (me + P - i) % P;
    step_local(p, [&] {
      recv_eager(c, r, c.tag(), c.scratch1 + 8 * r, slot, RecvMode::COPY,
                 0, 0);
    });
  }
  std::vector<uint64_t> sc(P), rc(P), soff(P), roff(P);
  {
    MutexLock g(mem_mu_);
    std::memcpy(sc.data(), mem(c.scratch0, uint64_t(P) * 8), P * 8);
    std::memcpy(rc.data(), mem(c.scratch1, uint64_t(P) * 8), P * 8);
  }
  for (uint32_t j = 1; j < P; ++j) {
    soff[j] = soff[j - 1] + sc[j - 1];
    roff[j] = roff[j - 1] + rc[j - 1];
  }
  step_local(p, [&] {
    std::vector<int32_t> got(P);
    for (uint32_t j = 0; j < P; ++j) got[j] = int32_t(rc[j]);
    MutexLock g(mem_mu_);
    std::memcpy(mem(counts_addr, uint64_t(P) * 4), got.data(), P * 4);
  });
  uint64_t op_row = row * d.eb(d.op0);
  uint64_t res_row = row * d.eb(d.res);
  step_local(p, [&] {
    local_move(c, c.addr0() + soff[me] * op_row,
               c.addr2() + roff[me] * res_row, sc[me] * row, d.op0, d.res);
  });
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (me + P - i) % P;
    if (use_rendezvous(c, rc[r] * row))
      rndzv_post_addr(c, p, r, c.tag(), c.addr2() + roff[r] * res_row,
                      rc[r] * row, d.res);
  }
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (me + i) % P;
    uint64_t src = c.addr0() + soff[r] * op_row;
    if (use_rendezvous(c, sc[r] * row))
      rndzv_send(c, p, r, c.tag(), src, sc[r] * row, d.op0);
    else
      step_local(p, [&] {
        send_eager(c, r, c.tag(), src, sc[r] * row, false, 0, 0);
      });
  }
  for (uint32_t i = 1; i < P; ++i) {
    uint32_t r = (me + P - i) % P;
    if (use_rendezvous(c, rc[r] * row)) {
      rndzv_wait_done(c, p, r, c.tag());
    } else {
      step_local(p, [&] {
        recv_eager(c, r, c.tag(), c.addr2() + roff[r] * res_row,
                   rc[r] * row, RecvMode::COPY, 0, 0);
      });
    }
  }
}

// Barrier: gather-to-0 + scatter-from-0 of empty messages (fw :2077-2120).
void Engine::coll_barrier(CallDesc& c, Progress& p) {
  const CommTable& t = comm_for(c);
  uint32_t P = t.size;
  if (P == 1) return;
  if (t.local == 0) {
    for (uint32_t r = 1; r < P; ++r)
      recv_eager(c, r, BARRIER_TAG, 0, 0, RecvMode::COPY, 0, 0);
    for (uint32_t r = 1; r < P; ++r)
      send_eager(c, r, BARRIER_TAG, 0, 0, false, 0, 0);
  } else {
    send_eager(c, 0, BARRIER_TAG, 0, 0, false, 0, 0);
    recv_eager(c, 0, BARRIER_TAG, 0, 0, RecvMode::COPY, 0, 0);
  }
}

}  // namespace accl
