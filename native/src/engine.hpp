// The collective engine: control plane + dataplane for one rank.
//
// This is the TPU build's equivalent of the reference's on-device control
// plane — the MicroBlaze firmware event loop that reads 15-word call
// descriptors, decomposes collectives into data movement + arithmetic,
// and re-queues rendezvous calls whose peers aren't ready (reference:
// kernels/cclo/fw/sw_apps/ccl_offload_control/src/ccl_offload_control.c:
// run_accl :2485, dispatch :2375-2459, retry queue :2460-2479).  The
// decomposition here is expressed directly over a transport + rx pool +
// arithmetic lanes rather than the reference's DMA-mover micro-ISA; the
// observable protocol (eager segmentation against rx buffers, sequence
// numbers, rendezvous address exchange, ring/tree schedules) matches.
#pragma once

#include "arith.hpp"
#include "common.hpp"
#include "rxpool.hpp"
#include "transport.hpp"

namespace accl {

struct CommTable {
  // Ownership discipline (the TSA annotations on Engine::comms_ lean
  // on this): size/local/rows are IMMUTABLE after publication —
  // set_comm builds the whole table before the cfg_mu_-guarded push,
  // so a published row may be read lock-free from any thread.  The
  // seq columns are owned by the ENGINE LOOP thread after publication;
  // the only cross-thread writers (reset_errors, ResetPeriph) run on a
  // quiesced world by the r10 recovery contract.
  uint32_t size = 0;
  uint32_t local = 0;
  struct Row {
    uint32_t ip = 0, port = 0, session = 0, max_seg = 0;
  };
  std::vector<Row> rows;
  // Device-side per-peer sequence numbers (reference keeps these in the
  // exchange-memory communicator, communicator.hpp:34-39).
  std::vector<uint32_t> inbound_seq, outbound_seq;
};

struct ArithCfgN {
  uint32_t ubits = 32, cbits = 32, ratio_log = 0;
  uint32_t compressor = 0, decompressor = 0;
  uint32_t arith_compressed = 0;
  std::vector<uint32_t> lanes;  // indexed by ReduceFunction
  // r17 block-scaled extension (append-only serialization in
  // arithconfig.py to_words: two trailing words after the lanes):
  // elements per fp32 scale on the int8 wire (0 = cast lane), and
  // whether egress quantization folds an EQuARX error-feedback
  // residual (per (comm, dst, source-address) site) into each pass.
  uint32_t block = 0;
  uint32_t error_feedback = 0;
};

// Rendezvous bookkeeping records (reference: firmware pending queues,
// rendezvous_get_addr :154-212 / _get_completion :280).
struct RndzvAddr {
  uint32_t comm, src, tag;
  uint64_t vaddr;
  uint64_t elems;
};
struct RndzvDone {
  uint32_t comm, src, tag;
  // the landing address of the write this completion reports: lets a
  // wait match exactly ITS OWN posted address, so concurrent calls with
  // the same (comm, src, tag) can't consume each other's completions
  // and retry-expiry teardown can't drain a healthy call's success
  uint64_t vaddr = 0;
};

struct CallResult {
  uint32_t retcode = 0;
  double duration_ns = 0.0;
  bool done = false;
};

class Engine {
 public:
  Engine(uint32_t global_rank, uint64_t devmem_bytes,
         std::unique_ptr<Transport> transport);
  ~Engine();

  // Two-phase teardown (r13, the suite-exit segfault fix): stop every
  // engine thread, close the queues, and finalize every still-pending
  // call with COMM_ABORTED|RANK_FAILED — WITHOUT freeing any storage.
  // After shutdown() a host-side waiter parked in a poll/wait loop
  // returns within one poll interval, so the world can be destroyed
  // with a hard guarantee that no thread is still inside the engine
  // (the crash class: accl_world_destroy racing a waiter thread's
  // poll_call).  Idempotent; the destructor runs it first.
  void shutdown();

  // ---- host-facing config (driver bring-up path) ----
  void cfg_rx_buffers(uint32_t nbufs, uint64_t bufsize);
  int set_comm(const uint32_t* words, int nwords);
  int set_arithcfg(const uint32_t* words, int nwords);

  // ---- device memory ----
  uint64_t alloc(uint64_t nbytes, uint64_t align);
  void free_addr(uint64_t addr);
  bool read_mem(uint64_t addr, void* dst, uint64_t n);
  bool write_mem(uint64_t addr, const void* src, uint64_t n);

  // ---- host-resident memory (the reference's host-only buffers /
  // external_dma path: the engine reaches into host memory when an
  // operand carries OP0/OP1/RES_HOST, ccl_offload_control.h:128-138).
  // Host addresses are tagged with HOST_ADDR_BIT and resolve into a
  // separate host region; the same engine primitives move data to and
  // from it transparently, like the reference's host-capable movers. ----
  static constexpr uint64_t HOST_ADDR_BIT = 1ull << 62;
  uint64_t alloc_host(uint64_t nbytes, uint64_t align);

  // ---- call path ----
  uint64_t start_call(const uint32_t* w15);
  bool poll_call(uint64_t id, uint32_t* retcode, double* duration_ns);

  // ---- persistent collective plans (r12): pre-marshaled descriptor
  // ring.  A plan is an ordered batch of 15-word descriptors parsed
  // ONCE at creation; a replay re-queues the whole batch through the
  // normal engine loop with fresh call ids — one host->engine entry
  // per replay instead of one per call (the ACCL+ pre-armed command
  // sequence, arxiv 2312.11742).  Each plan snapshots the epoch of
  // every communicator it touches: a replay after any abort/epoch
  // bump (or reset_errors, which invalidates every plan) fails fast
  // with -2 instead of silently running on a fenced world. ----
  // Returns the plan id (>= 0), or -1 on malformed input.
  int plan_create(const uint32_t* words, int ncalls);
  // Queue one replay; returns a completion token (> 0), -1 for an
  // unknown plan id, or -2 when the plan was invalidated/fenced.
  long long plan_replay(int plan_id);
  // Poll a replay token: 1 = all calls done (retcode = OR of every
  // call's bits, duration = sum), 0 = still in flight, -1 = unknown.
  int plan_poll(long long token, uint32_t* retcode, double* duration_ns);
  // Fence plans touching comm_id (-1 = every plan); called from
  // abort_comm/handle_abort/reset_errors and by the driver's
  // shrink/grow plan-fencing contract.  Fencing also frees the plan's
  // descriptor storage — an invalid plan can never replay again.
  void invalidate_plans(int comm_id);
  // Release one plan's storage (the driver plan object died/closed);
  // the slot stays (ids are vector indices) but holds nothing.
  void plan_release(int plan_id);
  // Live (still-valid) plan count — eviction introspection for tests.
  int plan_count() const;

  // ---- compute-kernel streams (PL-kernel equivalent) ----
  void push_krnl(const uint8_t* data, uint64_t n);
  bool pop_stream(uint32_t strm, uint8_t* dst, uint64_t cap, uint64_t* got,
                  int timeout_ms);

  std::string dump_rx() const { return rx_.dump(); }
  uint32_t rank() const { return global_rank_; }

  // Deterministic-schedule introspection (ACCL_DETSCHED drills): how
  // many transport deliveries are executing inside this engine right
  // now.  The shutdown-vs-traffic drill asserts it is zero after the
  // transport detached — the invariant the r13 InprocHub::detach drain
  // establishes (and the ACCL_FAULT_DETACH_RACE build breaks).
  int ingress_depth() const { return ingress_depth_.load(); }

  // ---- wire-protocol correctness surface (r13) ----
  // Feed one raw frame (64-byte WireHeader + payload) through the real
  // ingress classification path, exactly as if the transport delivered
  // it.  Returns 0 when the frame was consumed (including a legal drop
  // by the kill/epoch gates) and 1 when it was REJECTED as malformed —
  // truncated header, unknown MsgType, count/payload mismatch,
  // out-of-range comm id, oversized eager segment.  The same
  // validation runs on every transport-delivered frame; rejections
  // increment the counter either way.
  int ingest_bytes(const uint8_t* data, uint64_t nbytes);
  void frame_stats(uint64_t* accepted, uint64_t* rejected) const {
    if (accepted) *accepted = frames_accepted_.load();
    if (rejected) *rejected = frames_rejected_.load();
  }

  // ---- engine telemetry snapshot (r14): the versioned flat stats
  // export behind capi accl_engine_stats.  Fills up to `cap` u64
  // fields of the current layout (field order is the ABI — APPEND
  // ONLY; the Python twin is ENGINE_STATS_FIELDS_V<n> in
  // accl_tpu/observability/telemetry.py) and returns the total field
  // count this build knows, so an older caller reads a prefix and a
  // newer caller sees exactly how much the engine filled.  Cheap by
  // construction: atomics plus three short lock holds (egress depth,
  // plan table, rx staging) — pollable at 10 Hz without touching the
  // call hot path.  v2 (r15) appends link_rows: the number of
  // (comm, peer) link rows the link plane below is tracking. ----
  // v3 (r17) appends the quantized-wire accounting pair:
  // compressed_tx_bytes (wire bytes actually sent through a compressed
  // lane) and compressed_tx_logical_bytes (their uncompressed
  // equivalent — the difference is the "bytes saved" family).
  static constexpr int kEngineStatsVersion = 3;
  int engine_stats(uint64_t* out, int cap);

  // ---- per-link wire telemetry (r15): the flat (comm, peer) counter
  // plane behind capi accl_engine_link_stats.  One row of
  // kLinkStatsStride u64s per (comm, peer comm-local rank) this engine
  // has exchanged traffic with — tx/rx message+byte counters,
  // retransmits served to that peer, NACKs exchanged with it, frames
  // dropped at an epoch fence, and the seek count/blocked-wait time
  // attributed to it (the receiver's measure of how long that peer's
  // data kept it waiting).  Row field order is the ABI twin of
  // LINK_STATS_FIELDS_V2 in accl_tpu/observability/telemetry.py:
  //   0 comm, 1 peer, 2 tx_msgs, 3 tx_bytes, 4 rx_msgs, 5 rx_bytes,
  //   6 retrans_sent, 7 nacks_tx, 8 nacks_rx, 9 fenced_drops,
  //   10 seeks, 11 seek_wait_ns, 12 comp_tx_bytes (r17: wire bytes
  //   sent to this peer through a compressed lane)
  // Only WHOLE rows are ever written (a short buffer truncates at a
  // row boundary, never mid-row); the return value is the total u64
  // count this engine holds so a caller with a small buffer can retry.
  static constexpr int kLinkStatsStride = 13;
  int link_stats(uint64_t* out, int cap);

  // Egress frame tap: bounded ring of the last kTapCap frames this
  // engine staged (serialized header + payload) — the wire fuzzer's
  // seed-corpus capture (scripts/fuzz_wire.py records one real frame
  // of every MsgType through this before mutating).
  void set_frame_tap(bool on) { tap_on_.store(on); }
  int tap_count() const {
    MutexLock g(tap_mu_);
    return int(tap_frames_.size());
  }
  // Copy frame `idx` (oldest first) into out; returns the frame's full
  // size in bytes (even if > cap — caller retries with a bigger
  // buffer), or -1 for an out-of-range index.  NB index->frame identity
  // is only stable while nothing rotates the ring: concurrent readers
  // of a LIVE tap must use tap_drain, which is atomic per batch.
  int tap_read(int idx, uint8_t* out, int cap) const;
  // Atomically drain captured frames (oldest first) into out as
  // consecutive [u32 len][frame bytes] records under one lock hold;
  // returns bytes written.  Frames that don't fit stay for the next
  // drain; a single frame larger than the whole buffer is dropped
  // (it could never fit).
  int tap_drain(uint8_t* out, int cap);

  // ---- fault injection (test harness; SURVEY §5 failure detection) ----
  // Forces the chaos funnel's NEXT egress draw: 1=drop, 2=duplicate,
  // 3=corrupt sequence number, 4=delay.  One-shot sugar over the seeded
  // chaos plan below — both resolve in the same send_out switch, so the
  // detection/recovery machinery (seqn discipline, receive timeout,
  // NACK retransmission) is exercised identically either way.
  void inject_fault(uint32_t kind) { fault_.store(kind); }

  // ---- resilience: retransmission + abort/epoch + liveness + chaos ----
  // Eager retransmission config: on a seek miss the receiver NACKs the
  // sender and retries with exponential backoff + deterministic jitter,
  // up to retry_max NACK rounds inside the unchanged receive budget
  // (ACCL_RETRY_MAX / ACCL_RETRY_BASE_US on the driver side).
  // retry_max = 0 disables the whole lane (no store, no NACKs) and
  // restores the pure detect-and-classify behavior.
  void set_resilience(uint32_t retry_max, uint32_t retry_base_us) {
    retry_max_.store(retry_max);
    if (retry_base_us) retry_base_us_.store(retry_base_us);
  }
  void resilience_stats(uint64_t* retrans_sent, uint64_t* nacks_tx,
                        uint64_t* nacks_rx, uint64_t* fenced_drops) const {
    if (retrans_sent) *retrans_sent = retrans_sent_.load();
    if (nacks_tx) *nacks_tx = nacks_tx_.load();
    if (nacks_rx) *nacks_rx = nacks_rx_.load();
    if (fenced_drops) *fenced_drops = fenced_drops_.load();
  }

  // Epoch-tagged communicator abort: bump the epoch, mark the comm
  // aborted with `err_bits` (COMM_ABORTED is always OR'd in), finalize
  // every pending call on it fast, and — when propagate — send an Abort
  // control message to every peer so THEIR pending calls fail fast too.
  // Returns 0, or -1 for an unknown comm id.
  int abort_comm(uint32_t comm_id, uint32_t err_bits, bool propagate);

  // Seqn resync + transient-state drain after a CLASSIFIED fault: zero
  // both directions' sequence counters, drain the rx pool and the
  // retransmit store, clear armed one-shot faults and abort flags
  // (epochs stay bumped — old-epoch stragglers remain fenced).  A
  // collective recovery op: every rank of a quiesced world must call it.
  void reset_errors();

  // Chaos plan (seeded, probabilistic, dataplane-targeted): each eager
  // egress segment draws drop/dup/delay/corrupt with the given
  // per-million probabilities from a deterministic xorshift stream;
  // slow_us stalls this rank's egress writer per message (slow-rank).
  void set_chaos(uint64_t seed, uint32_t drop_ppm, uint32_t dup_ppm,
                 uint32_t delay_ppm, uint32_t delay_us,
                 uint32_t corrupt_ppm, uint32_t slow_us);

  // Kill this rank (chaos kill-rank): the engine goes silent — egress
  // drops everything, ingress hears nothing — and every local comm is
  // aborted with RANK_FAILED so the rank's own pending calls finalize
  // fast instead of burning their receive budget.
  void kill();
  bool is_killed() const { return killed_.load(); }

  // Liveness probe over one communicator: ping every peer with a
  // Heartbeat and collect proof-of-life (a pong, or any control-plane
  // traffic — NACK/abort ingress also stamps last-heard; the data hot
  // path deliberately does not) for up to window_us.  Returns a bitmap
  // of alive comm-local ranks (the local rank is always alive).
  uint64_t probe_liveness(uint32_t comm_id, uint32_t window_us);

  // ---- elastic membership (r11): the join control plane ----
  // Joiner side of the Join/Welcome/StateSync exchange: ask the sponsor
  // session for its world state and apply it — adopt every comm's
  // epoch + abort fence (so dead-epoch traffic can never land here and
  // a replayed abort stays fenced) and pad the comm table with
  // placeholder slots so this engine's comm-id space aligns with the
  // survivors' before the grown communicator is uploaded.  Returns 0,
  // or -1 when the sponsor never answered inside timeout_ms (a dead or
  // killed sponsor is deaf — pick another and retry).
  int join_sync(uint32_t sponsor_session, int timeout_ms);
  // Introspection for the driver/tests: comm slots this engine knows
  // (real + placeholder) and a comm's current epoch.
  uint32_t comm_count() const;
  uint32_t comm_epoch(uint32_t comm) const { return epoch_of(comm); }
  // membership counters: joins answered as sponsor / completed as joiner
  void join_stats(uint64_t* sponsored, uint64_t* joined) const {
    if (sponsored) *sponsored = joins_sponsored_.load();
    if (joined) *joined = joins_completed_.load();
  }

  // Lossy-transport mode (set by datagram worlds): a seek timeout with
  // the expected seqn absent but later seqns queued is treated as an
  // unrecoverable loss hole and the route cursor resyncs.  On reliable
  // FIFO rungs the same signature means corruption and stays a hard
  // PACK_SEQ error (fault-injection contract).
  void set_lossy_transport(bool on) { lossy_transport_ = on; }

  // ---- explicit session lifecycle (reference open_port/open_con/
  // close_con, accl.hpp:1069-1083, backed by the tcp_session_handler
  // plugin).  Connection state lives in the transport; these surface
  // bring-up/teardown per communicator with a distinct error (the
  // index of the first peer whose session failed), so a dead peer is a
  // decodable setup failure instead of a mid-collective hang. ----
  // open_port: is the inbound endpoint live?  0 ok, -1 not listening.
  int open_port() const { return transport_ && transport_->listening() ? 0 : -1; }
  // open_con / close_con over every peer of a communicator.
  // Returns 0 on success, or (1 + peer_local_rank) of the first failure.
  int open_con(uint32_t comm_id);
  int close_con(uint32_t comm_id);

  // ---- peer-to-peer buffer windows (FPGABufferP2P analog,
  // driver/xrt/include/accl/fpgabufferp2p.hpp: a device buffer directly
  // addressable by peers without staging).  A registered span lets an
  // in-process peer engine land its rendezvous one-sided write by
  // DIRECT memcpy into this engine's devicemem — the wire is bypassed
  // entirely (the PCIe-p2p DMA of the reference).  Worlds with shared
  // address space install the peer hook; wire-only worlds leave it
  // unset and p2p buffers degrade gracefully to normal buffers. ----
  void register_p2p(uint64_t addr, uint64_t bytes);
  void unregister_p2p(uint64_t addr);
  bool p2p_covers(uint64_t addr, uint64_t bytes) const;
  void set_peer_hook(std::function<Engine*(uint32_t session)> hook) {
    peer_hook_ = std::move(hook);
  }
  // Raw pointer into devicemem for zero-copy host mapping (the
  // reference's bo.map<dtype*>() on a p2p BO).  nullptr when OOB.
  uint8_t* raw_mem(uint64_t addr, uint64_t bytes);
  // Receiver side of a direct p2p landing: same consume-write-complete
  // discipline as the wire ingress (shared land_one_sided below).
  void land_p2p(const WireHeader& hdr, const uint8_t* payload,
                uint64_t payload_bytes);
  // Egress traffic counters (message count / payload bytes actually
  // handed to the transport) — lets tests PROVE the p2p path moved no
  // payload over the wire.
  void tx_stats(uint64_t* msgs, uint64_t* payload_bytes) const {
    if (msgs) *msgs = tx_msgs_.load();
    if (payload_bytes) *payload_bytes = tx_payload_bytes_.load();
  }

 private:
  // engine loop
  void loop();
  uint32_t execute(CallDesc& c);
  struct Progress;
  void dispatch(CallDesc& c, Progress& p);

  // transport ingress demux (the depacketizer role, eth_intf routing):
  // frame validation + rejection counting in ingress(), the per-type
  // routing in classify() — ingest_bytes shares both.
  void ingress(Message&& msg);
  void classify(Message&& msg);
  // Structural validation of one frame BEFORE any routing touches it:
  // a malformed frame must be counted and dropped, never interpreted.
  // Takes the payload (not just its size): block-scaled segments
  // (hdr.compressed == 2, r17) carry a self-describing framing header
  // whose scale-row/count consistency is validated here.  Non-const:
  // the stream-route pressure checks read the resequencer maps under
  // their mutex so rejection happens BEFORE any per-route state is
  // minted from attacker-controlled header fields.
  bool frame_ok(const WireHeader& hdr, const std::vector<uint8_t>& payload);
  //: bounds on state minted from inbound stream headers (comm, src and
  //: strm are attacker-controlled): max distinct inbound stream routes,
  //: and max total parked out-of-order payloads across ALL routes
  static constexpr size_t kMaxStrmRoutes = 256;
  static constexpr size_t kMaxStrmHoldbackTotal = 1024;
  std::atomic<uint64_t> frames_accepted_{0}, frames_rejected_{0};
  std::atomic<int> ingress_depth_{0};
  std::atomic<bool> tap_on_{false};
  static constexpr size_t kTapCap = 256;
  mutable Mutex tap_mu_;
  std::deque<std::vector<uint8_t>> tap_frames_ ACCL_GUARDED_BY(tap_mu_);

  // ---- primitives (firmware primitive layer, fw :533-791) ----
  struct Progress {
    CallDesc& call;
    uint32_t cursor = 0;
    explicit Progress(CallDesc& c) : call(c) {}
    bool pending() const { return cursor >= call.current_step; }
    void done() {
      ++cursor;
      if (cursor > call.current_step) call.current_step = cursor;
    }
  };

  const CommTable& comm_for(const CallDesc& c) const;
  const ArithCfgN& arith_for(const CallDesc& c) const;
  uint64_t elem_bytes(const CallDesc& c) const;
  std::chrono::nanoseconds timeout_budget() const;

  // Per-call compression domains, decoded from the descriptor's
  // compression flags + arithmetic config (the per-operand flag algebra
  // of the reference, constants.hpp:320-325; per-step shifting
  // ccl_offload_control.c:1408-1411, :1929-1955).  Every primitive below
  // is element-based so each operand can carry its own representation.
  struct Dom {
    uint32_t ub = 4, cb = 4, ratio_log = 0;
    uint32_t comp_kind = 0;       // compressor id (arithconfig.py)
    bool pair = false;            // a real compressed representation exists
    bool op0 = false, op1 = false, res = false, eth = false;
    // r17 int8 block-scaled wire lane: block != 0 selects the
    // self-describing segment format (arith.hpp i8_* helpers) whose
    // byte size is NOT linear per element — every wire-size site must
    // go through wbytes()/welems(), never eb(), for the wire domain.
    // Per-operand compressed residence is meaningless for a scaled
    // segment (the scales don't fit a flat int8 buffer), so dom()
    // forces op0/op1/res off when blk is set.
    uint32_t blk = 0;
    bool ef = false;              // error-feedback egress quantization
    uint64_t eb(bool compressed) const { return compressed ? cb : ub; }
    // wire/operand byte size of `elems` elements in a representation
    uint64_t wbytes(uint64_t elems, bool compressed) const {
      return (compressed && blk) ? i8_wire_bytes(elems, blk)
                                 : elems * eb(compressed);
    }
    // elements per segment against a wire-byte budget
    uint64_t seg_elems(uint64_t wire_cap, bool compressed) const {
      if (compressed && blk) return i8_seg_elems(wire_cap, blk);
      return std::max<uint64_t>(1, wire_cap / eb(compressed));
    }
  };
  Dom dom(const CallDesc& c) const;

  // Egress quantization for the block-scaled lane: plain unless
  // `use_ef` (the arithcfg arms error feedback AND the send carries a
  // REDUCTION stream — relays/gathers/bcasts must quantize cleanly,
  // folding a residual into non-reduced data would corrupt it), in
  // which case the per-site residual (key = (comm, dst, source
  // address)) is folded in and refreshed — a training loop's repeated
  // collective re-quantizes the same sites every iteration, so the
  // error of pass k rides into pass k+1, EQuARX-style.
  void quantize_egress(const Dom& d, bool use_ef, uint32_t comm,
                       uint32_t dst, uint64_t src_addr, const float* in,
                       uint8_t* out, uint64_t elems);

  // Convert `elems` elements between representations (identity when the
  // domains match); returns sticky error bits on unknown compressor.
  uint32_t convert_elems(const Dom& d, const uint8_t* in, bool in_c,
                         uint8_t* out, bool out_c, uint64_t elems);
  // acc/op1/res each in their own domain; arithmetic runs in the domain
  // selected by the arithcfg's arith_is_compressed (mixed-precision
  // accumulate, reference arithconfig.hpp:106-119 {f32,f16} pair).
  uint32_t reduce_mixed(const CallDesc& c, const uint8_t* a0, bool a0c,
                        const uint8_t* a1, bool a1c, uint8_t* r, bool rc,
                        uint64_t elems);

  // Eager segmented send of `elems` elements from devicemem `addr` (or
  // the kernel stream when from_stream).  comp bits: OP0_COMPRESSED =
  // memory at addr holds the compressed representation; ETH_COMPRESSED =
  // compress payloads on the wire (fw send :575-651).
  // `reduce_stream`: this send carries a reduction partial/operand (a
  // ring reduce-scatter or reduce-chain hop) — the only sends the
  // error-feedback residual may legally fold into.
  void send_eager(CallDesc& c, uint32_t dst, uint32_t tag, uint64_t addr,
                  uint64_t elems, bool from_stream, uint32_t to_strm,
                  uint32_t comp, bool reduce_stream = false);
  // Eager segmented receive of `elems` elements into devicemem `addr`;
  // mode selects plain copy, reduce-accumulate into dst (fused
  // recv-reduce), or routing to a kernel stream.  comp bits:
  // RES_COMPRESSED = the landing buffer (or accumulator) holds the
  // compressed representation; ETH_COMPRESSED = segmentation follows the
  // compressed wire width (fw recv :655-712, fused_recv_reduce :718).
  enum class RecvMode { COPY, REDUCE, STREAM };
  void recv_eager(CallDesc& c, uint32_t src, uint32_t tag, uint64_t addr,
                  uint64_t elems, RecvMode mode, uint32_t strm, uint32_t comp);

  // Rendezvous primitives (fw :142-350, rdma_sq_handler.cpp:53-130),
  // element-based: the receiver advertises its landing representation and
  // the sender converts, so compressed operands ride rendezvous too.
  void rndzv_post_addr(CallDesc& c, Progress& p, uint32_t src, uint32_t tag,
                       uint64_t addr, uint64_t elems, bool dst_c);
  void rndzv_wait_done(CallDesc& c, Progress& p, uint32_t src, uint32_t tag);
  void rndzv_recv(CallDesc& c, Progress& p, uint32_t src, uint32_t tag,
                  uint64_t addr, uint64_t elems, bool dst_c);
  void rndzv_send(CallDesc& c, Progress& p, uint32_t dst, uint32_t tag,
                  uint64_t addr, uint64_t elems, bool src_c);

  bool use_rendezvous(const CallDesc& c, uint64_t elems);

  // Materialize a kernel-stream operand (OP0_STREAM) into device memory
  // so reduction schedules can treat it like a buffer operand.
  bool drain_krnl_to(uint64_t addr, uint64_t bytes);
  // Push a device-memory range into a local compute stream (RES_STREAM).
  void push_local_stream(uint32_t strm, uint64_t addr, uint64_t bytes);
  // Get-or-create the FIFO backing compute stream `strm`.
  std::shared_ptr<Fifo<std::vector<uint8_t>>> stream_for(uint32_t strm);

  // local ops — byte-based raw copy plus domain-aware element movers
  // (the dma_mover's compressor/decompressor lane routing, SURVEY §2.4)
  uint32_t local_copy(uint64_t src, uint64_t dst, uint64_t bytes);
  uint32_t local_move(const CallDesc& c, uint64_t src, uint64_t dst,
                      uint64_t elems, bool src_c, bool dst_c);
  uint32_t local_reduce(uint32_t lane, uint64_t a, uint64_t b, uint64_t dst,
                        uint64_t bytes);

  // ---- collective schedules (fw :793-2218) ----
  void coll_send(CallDesc& c, Progress& p);
  void coll_recv(CallDesc& c, Progress& p);
  void coll_bcast(CallDesc& c, Progress& p);
  void coll_scatter(CallDesc& c, Progress& p);
  void coll_gather(CallDesc& c, Progress& p);
  void coll_allgather(CallDesc& c, Progress& p);
  void coll_reduce(CallDesc& c, Progress& p);
  void coll_reduce_scatter(CallDesc& c, Progress& p);
  void coll_allreduce(CallDesc& c, Progress& p);
  void coll_alltoall(CallDesc& c, Progress& p);
  void coll_alltoallv(CallDesc& c, Progress& p);
  void coll_barrier(CallDesc& c, Progress& p);
  void do_config(CallDesc& c);

  // binomial tree schedules for the rendezvous protocol (fw tree bcast
  // :816-869, tree reduce :1603-1728); resume-safe via Progress.  Domain
  // bits: src_c/dst_c/acc_c describe the representation of the caller's
  // buffers (relays re-derive per the RES->OP0 algebra, fw :1408-1411).
  void tree_bcast(CallDesc& c, Progress& p, uint32_t root, uint64_t src_addr,
                  uint64_t dst_addr, uint64_t elems, bool src_c, bool dst_c);
  void tree_reduce(CallDesc& c, Progress& p, uint32_t root, uint64_t src_addr,
                   uint64_t acc_addr, uint64_t tmp_addr, uint64_t elems,
                   bool src_c, bool acc_c);
  // a local op as one resumable step (local side effects must not replay
  // when a rendezvous retry re-enters the schedule)
  template <typename F>
  void step_local(Progress& p, F&& f) {
    if (p.pending()) f();
    p.done();
  }

  // ring schedule cores shared by reduce_scatter/allreduce (fw :1782-2071);
  // off/len are in elements
  void ring_reduce_scatter(CallDesc& c, uint64_t src_base,
                           const std::vector<uint64_t>& off,
                           const std::vector<uint64_t>& len, uint64_t own_dst);
  void ring_allgather(CallDesc& c, uint64_t base,
                      const std::vector<uint64_t>& off,
                      const std::vector<uint64_t>& len);

  // Resolve an engine address to backing storage.  REQUIRES(mem_mu_):
  // every caller stages its copy/convert/reduce under the lock, so the
  // TSA lane proves no primitive ever touches devicemem/hostmem bytes
  // without it.
  uint8_t* mem(uint64_t addr, uint64_t n) ACCL_REQUIRES(mem_mu_);

  // ---- state ----
  uint32_t global_rank_;
  std::vector<uint8_t> devicemem_ ACCL_GUARDED_BY(mem_mu_);
  std::vector<uint8_t> hostmem_ ACCL_GUARDED_BY(mem_mu_);  // lazily committed
  uint64_t host_region_bytes_ = 0;  // immutable after the constructor
  // addr -> size maps for both address spaces
  std::map<uint64_t, uint64_t> free_spans_ ACCL_GUARDED_BY(mem_mu_);
  std::map<uint64_t, uint64_t> host_spans_ ACCL_GUARDED_BY(mem_mu_);
  std::map<uint64_t, uint64_t> alloc_sizes_ ACCL_GUARDED_BY(mem_mu_);
  // LOCK ORDER: mem_mu_ may be taken while holding posted_mu_ (the
  // rendezvous landing path holds posted_mu_ across its payload copy,
  // engine.cpp RndzvsMsg) — NEVER take posted_mu_ while holding mem_mu_.
  // The ACQUIRED_AFTER edge makes the TSA lane enforce this statically.
  Mutex mem_mu_ ACCL_ACQUIRED_AFTER(posted_mu_);

  // Landing-pad registry for one-sided writes: rndzv_post_addr records
  // the conversion the depacketizer must apply when the peer's write
  // lands (wire representation -> landing representation), keyed by
  // (comm, src, tag, vaddr) so a stale entry from a failed transfer
  // cannot be consumed by a later collective that reuses the address.
  // Receiver-local state — the sender's header is never trusted for
  // domain decisions, matching the eager path's own-flag-algebra
  // discipline.
  struct PostedRndzv {
    uint64_t elems;
    bool wire_c, lnd_c;
    uint32_t comp_kind;
    uint32_t ub, cb;   // bytes/element in each representation
    uint32_t blk = 0;  // block-scaled wire geometry (0 = cast lane)
  };
  using PostedKey = std::tuple<uint32_t, uint32_t, uint32_t, uint64_t>;
  std::map<PostedKey, PostedRndzv> posted_ ACCL_GUARDED_BY(posted_mu_);
  // Shared landing logic for one-sided writes: wire ingress (RndzvsMsg)
  // and the direct p2p path both run exactly this (consume posted
  // record under posted_mu_, convert/copy under mem_mu_, surface the
  // completion) so the two paths cannot diverge.
  void land_one_sided(const WireHeader& hdr, const uint8_t* payload,
                      uint64_t payload_bytes);

  // p2p window registry + peer resolution (see public section)
  mutable Mutex p2p_mu_;
  std::map<uint64_t, uint64_t> p2p_spans_ ACCL_GUARDED_BY(p2p_mu_);
  // set once at world wiring, before traffic (no guard needed)
  std::function<Engine*(uint32_t session)> peer_hook_;
  std::atomic<uint64_t> tx_msgs_{0}, tx_payload_bytes_{0};
  // r17 quantized-wire accounting: bytes that left through a
  // compressed lane (any pair — f16/bf16 cast or int8 block-scaled)
  // and their uncompressed equivalent; saved = logical - compressed.
  std::atomic<uint64_t> compressed_tx_bytes_{0};
  std::atomic<uint64_t> compressed_tx_logical_bytes_{0};

  // ---- error-feedback residuals (r17, EQuARX arxiv 2506.17615) ----
  // One fp32 residual vector per quantization site (comm, dst,
  // source address), written by quantize_egress when the arithcfg's
  // error_feedback word is set.  Leaf lock taken under mem_mu_ (the
  // egress conversion sites hold mem_mu_); total floats are bounded —
  // sites past the cap quantize without feedback rather than grow.
  static constexpr uint64_t kEfResidualCapFloats = 8ull << 20;  // 32 MiB
  using EfKey = std::tuple<uint32_t, uint32_t, uint64_t>;
  std::map<EfKey, std::vector<float>> ef_residual_ ACCL_GUARDED_BY(ef_mu_);
  uint64_t ef_floats_ ACCL_GUARDED_BY(ef_mu_) = 0;
  Mutex ef_mu_ ACCL_ACQUIRED_AFTER(mem_mu_);
  void drop_ef_residuals(int comm_id);  // -1 = all (reset_errors)
  // LOCK ORDER: posted_mu_ comes BEFORE mem_mu_ (see mem_mu_ above);
  // acquiring posted_mu_ under mem_mu_ would invert the order = deadlock.
  Mutex posted_mu_;

  std::unique_ptr<Transport> transport_;
  //: pending one-shot egress fault (0 = none); see inject_fault()
  std::atomic<uint32_t> fault_{0};
  //: egress funnel applying any injected fault before the transport
  void send_out(uint32_t session, Message&& msg);

  // ---- retransmission lane (resilience layer 1) ----
  // Bounded ring of sent eager segments keyed by (comm, dst comm-local
  // rank, tag, seqn); the clean copy is captured BEFORE the chaos
  // funnel, modeling a real sender whose source data survives a wire
  // fault.  A NACK for (comm, tag, seqn) resends every stored segment
  // on the route from that seqn on (one round recovers a multi-segment
  // hole).  Retransmits bypass the chaos funnel — the recovery path
  // stays deterministic under seeded chaos.
  // Hot-path discipline: the no-fault cost per segment is ONE payload
  // copy into a RECYCLED slot (vector::assign reuses capacity — zero
  // steady-state allocation) under an uncontended mutex; there is no
  // index structure to churn.  The NACK handler pays a linear ring
  // scan instead — it only runs on the fault path.
  struct RetransSlot {
    bool used = false;
    uint32_t comm = 0, dst = 0;
    Message msg;
  };
  static constexpr size_t kRetransCap = 1024;
  std::vector<RetransSlot> retrans_ring_ ACCL_GUARDED_BY(retrans_mu_);
  size_t retrans_pos_ ACCL_GUARDED_BY(retrans_mu_) = 0;
  Mutex retrans_mu_;
  std::atomic<uint32_t> retry_max_{4};
  std::atomic<uint32_t> retry_base_us_{200};
  std::atomic<uint64_t> retrans_sent_{0}, nacks_tx_{0}, nacks_rx_{0};
  std::atomic<uint64_t> fenced_drops_{0};
  // telemetry shadows (engine_stats): live slot count and the number
  // of times a still-used slot was overwritten by ring wrap (store
  // pressure — a NACK after an eviction can no longer be served).
  // Written under retrans_mu_, read lock-free by the sampler.
  std::atomic<uint64_t> retrans_used_{0}, retrans_evictions_{0};
  bool retrans_enabled() const {
    return retry_max_.load() > 0 && !lossy_transport_.load();
  }
  void store_retrans(uint32_t comm, uint32_t dst, const Message& msg);
  void send_nack(uint32_t comm, uint32_t src, uint32_t tag, uint32_t seqn);
  void handle_nack(const WireHeader& hdr);
  // Seek with recovery: slices the receive budget so an abort wakes a
  // blocked receiver promptly, and (retransmission on) NACKs the sender
  // with exponential backoff + deterministic jitter on each miss.
  // `evicted_out` counts suspicious same-route entries evicted during
  // recovery (they classify a final failure as PACK_SEQ, like the
  // entries themselves would have).
  // `staged_out` (when non-null) may receive a message rescued straight
  // from the rx pool's staging queue; the returned notification then
  // carries index == UINT32_MAX and the payload rides *staged_out.
  std::optional<RxNotification> seek_recover(CallDesc& c, uint32_t src,
                                             uint32_t tag, int* evicted_out,
                                             Message* staged_out = nullptr);
  // telemetry: recovered-seek entries vs final misses (timeout /
  // lossy-hole classification — NOT abort/shutdown wakes, which are
  // fencing, not matching failures).  miss/seek is the seek-miss rate.
  std::atomic<uint64_t> seeks_{0}, seek_misses_{0};
  // sub-comm wedge observables: timeouts classified while the expected
  // segment sat in staging (the cross-comm pool-pinning failure — must
  // stay 0 on a healthy engine) and staged-rescue consumptions (the fix
  // firing).  Counted in BOTH normal and ACCL_FAULT_SUBCOMM_WEDGE
  // builds so the detsched drill invariant reads the same signal.
  std::atomic<uint64_t> wedged_timeouts_{0}, staged_takes_{0};

 public:
  uint64_t wedged_timeouts() const { return wedged_timeouts_.load(); }
  uint64_t staged_takes() const { return staged_takes_.load(); }
  uint64_t egress_overflows() const { return egress_overflows_.load(); }

 private:

  // ---- abort + epoch fencing (resilience layer 2) ----
  static constexpr uint32_t kMaxComms = 64;  // comms_.reserve(64) twin
  std::array<std::atomic<uint32_t>, kMaxComms> comm_epoch_{};
  std::array<std::atomic<uint32_t>, kMaxComms> comm_abort_{};
  uint32_t epoch_of(uint32_t comm) const {
    return comm < kMaxComms ? comm_epoch_[comm].load() : 0;
  }
  uint32_t abort_err(uint32_t comm) const {
    return comm < kMaxComms ? comm_abort_[comm].load() : 0;
  }
  // rendezvous/scratch teardown shared by retry expiry and abort
  void teardown_call(CallDesc& c);
  void handle_abort(const WireHeader& hdr);

  // ---- per-link wire telemetry (r15): (comm, peer) counter rows ----
  // A leaf mutex (taken around a map bump, never while holding it is
  // any other lock acquired): the per-message cost on the egress path
  // is one uncontended lock + map find, the same discipline as the
  // retransmit store.  Peers are COMM-LOCAL ranks — the link matrix
  // aggregator on the Python side maps them through the communicator.
  struct LinkCounters {
    uint64_t tx_msgs = 0, tx_bytes = 0, rx_msgs = 0, rx_bytes = 0;
    uint64_t retrans_sent = 0, nacks_tx = 0, nacks_rx = 0;
    uint64_t fenced_drops = 0, seeks = 0, seek_wait_ns = 0;
    uint64_t comp_tx_bytes = 0;  // r17: compressed wire bytes to peer
  };
  mutable Mutex link_mu_;
  std::map<std::pair<uint32_t, uint32_t>, LinkCounters> links_
      ACCL_GUARDED_BY(link_mu_);
  // Row-mint guard: the rx-side bump sites key rows off WIRE-HEADER
  // fields (hdr.comm_id is bounded by frame_ok, hdr.src is NOT) — a
  // fuzzed/hostile src must not mint unbounded map entries, so every
  // bump validates the peer against the comm table first.  The tx
  // sites pass table-derived values and the check is a cheap true.
  bool link_peer_ok(uint32_t comm, uint32_t peer) const {
    const CommTable* t = comm_ptr(comm);
    return t && peer < t->rows.size();
  }
  // one-counter bump via pointer-to-member (the common case)
  void link_count(uint32_t comm, uint32_t peer,
                  uint64_t LinkCounters::*field, uint64_t add = 1);
  // paired msg+byte bumps for the tx / rx funnels
  void link_tx(uint32_t comm, uint32_t peer, uint64_t bytes);
  void link_rx(uint32_t comm, uint32_t peer, uint64_t bytes);

  // ---- liveness (resilience layer 3) ----
  mutable Mutex live_mu_;
  std::map<std::pair<uint32_t, uint32_t>, uint64_t> last_heard_ns_
      ACCL_GUARDED_BY(live_mu_);
  void note_alive(uint32_t comm, uint32_t src);

  // ---- elastic membership (r11): join control plane ----
  Fifo<std::vector<uint32_t>> join_state_;  // StateSync payloads (joiner)
  std::atomic<uint64_t> joins_sponsored_{0}, joins_completed_{0};
  void handle_join(const WireHeader& hdr);            // sponsor side
  void apply_state_sync(const std::vector<uint32_t>& words);  // joiner

  // ---- seeded chaos (generalized injector) ----
  struct Chaos {
    bool armed = false;
    uint32_t drop_ppm = 0, dup_ppm = 0, delay_ppm = 0, delay_us = 0;
    uint32_t corrupt_ppm = 0;
    uint64_t rng = 0x9E3779B97F4A7C15ull;
  };
  Chaos chaos_ ACCL_GUARDED_BY(chaos_mu_);
  Mutex chaos_mu_;
  std::atomic<uint32_t> slow_us_{0};
  std::atomic<bool> killed_{false};
  uint32_t chaos_draw();  // fault kind for this message (0 = none)
  // delayed-egress releaser (chaos delay = real reordering, not a stall)
  struct Delayed {
    std::chrono::steady_clock::time_point release;
    uint32_t session;
    Message msg;
  };
  std::deque<Delayed> delayed_ ACCL_GUARDED_BY(delay_mu_);
  Mutex delay_mu_;
  CondVar delay_cv_;
  bool delay_running_ ACCL_GUARDED_BY(delay_mu_) = true;
  Thread delay_thread_;
  void delay_loop();

  // ---- egress pipeline: bounded outstanding-segment window ----
  // The engine loop stages each prepared segment here and immediately
  // starts preparing the next (memory read + conversion of segment k+1
  // overlaps wire transmission of segment k); a dedicated writer thread
  // drains to the transport in FIFO order.  Staging blocks once
  // `pipeline_depth_` segments are outstanding — the reference firmware's
  // 2-3-deep eager software-pipelining discipline (its send keeps
  // expected_ack_count <= 3 moves in flight and end_move()s beyond that,
  // ccl_offload_control.c:628-649, :1981-1986).
  void egress_loop();
  void stage_egress(uint32_t session, Message&& msg);
  std::deque<std::pair<uint32_t, Message>> egress_q_ ACCL_GUARDED_BY(egress_mu_);
  Mutex egress_mu_;
  CondVar egress_cv_;
  // telemetry: egress staging high-water (depth is read live under
  // egress_mu_ by engine_stats); written at stage time under the lock
  std::atomic<uint64_t> egress_hwm_{0};
  // backpressure-cycle escape valve: stagings that overflowed the
  // pipeline window after a full receive budget with no slot (see
  // stage_egress — ingress-context senders can cycle through each
  // other's windows; a counted overflow beats a distributed deadlock)
  std::atomic<uint64_t> egress_overflows_{0};
  std::atomic<uint32_t> pipeline_depth_{3};
  bool egress_running_ ACCL_GUARDED_BY(egress_mu_) = true;
  Thread egress_thread_;
  RxPool rx_;
  Fifo<RndzvAddr> pending_addrs_;
  Fifo<RndzvDone> completions_;
  std::map<uint32_t, std::shared_ptr<Fifo<std::vector<uint8_t>>>> streams_
      ACCL_GUARDED_BY(streams_mu_);
  Mutex streams_mu_;

  // Stream-destined messages bypass the rx pool, so they carry their own
  // per-(comm, peer, stream) sequence space and ingress resequences them
  // before pushing to the stream FIFO — FIFO transports never exercise
  // this, but the datagram rung delivers out of order (closes the
  // engine.cpp seqn exemption noted in round 2's review).
  using StrmKey = std::tuple<uint32_t, uint32_t, uint32_t>;  // comm,peer,strm
  //: max out-of-order stream messages parked per route before a lossy
  //: rung declares the gap a loss hole and resyncs (bounds holdback)
  static constexpr size_t kStrmHoldbackLimit = 64;
  std::map<StrmKey, uint32_t> strm_out_seq_;  // engine loop thread only
  std::map<StrmKey, uint32_t> strm_in_seq_ ACCL_GUARDED_BY(strm_seq_mu_);
  std::map<std::pair<StrmKey, uint32_t>, std::vector<uint8_t>> strm_holdback_
      ACCL_GUARDED_BY(strm_seq_mu_);
  Mutex strm_seq_mu_;
  Fifo<std::vector<uint8_t>> krnl_in_;

  // Communicator/arithcfg tables as stable heap pointers: cfg_mu_
  // guards the pointer VECTORS (growth by set_comm / join padding);
  // the pointees are never moved, so the engine loop fetches a row
  // pointer once under the lock and then uses it lock-free for the
  // whole call under CommTable's per-field ownership discipline.
  // (Before r14 these were value vectors whose safety hung on a
  // reserve(64) never-reallocate convention the analysis could not
  // see; the pointer indirection makes the guarded structure explicit
  // AND lifts the hard 64-comm growth ceiling.)
  std::vector<std::unique_ptr<CommTable>> comms_ ACCL_GUARDED_BY(cfg_mu_);
  std::vector<std::unique_ptr<ArithCfgN>> arithcfgs_ ACCL_GUARDED_BY(cfg_mu_);
  mutable Mutex cfg_mu_;
  // stable-pointer fetch (nullptr when out of range); see comms_ above
  CommTable* comm_ptr(uint32_t id) const;
  ArithCfgN* arith_ptr(uint32_t id) const;

  std::atomic<bool> lossy_transport_{false};
  uint64_t timeout_ = 1'000'000;  // in emulated cycles; 1 cycle = 1us here
  uint64_t max_eager_ = 32 * 1024;
  uint64_t max_rndzv_ = 32 * 1024;
  bool pkt_enabled_ = false;

 public:
  // Runtime tuning registers (the reference's exchange-memory flat-tree
  // thresholds, ccl_offload_control.h:86-90, written by the driver at
  // bring-up accl.cpp:1214-1224).
  enum TuningKey : uint32_t {
    BCAST_FLAT_TREE_MAX_RANKS = 0,
    REDUCE_FLAT_TREE_MAX_RANKS = 1,
    GATHER_FLAT_TREE_MAX_FANIN = 2,
    //: outstanding eager segments per engine (1 = strictly serial; the
    //: reference pipelines 2-3 moves, fw :628-649)
    EGRESS_PIPELINE_DEPTH = 3,
    //: byte thresholds for the count-based schedule selection (the
    //: reference's *_MAX_COUNT exchange-memory registers,
    //: ccl_offload_control.h:86-90): gather caps its flat-tree fan-in
    //: above this size (fw :1163); reduce prefers the flat tree at or
    //: below it regardless of rank count (fw :1533)
    GATHER_FLAT_TREE_MAX_COUNT = 4,
    REDUCE_FLAT_TREE_MAX_COUNT = 5,
  };
  // returns 0 on success, -1 for an unknown key (the clear-error
  // contract: the Python twin raises an ACCLError naming the key and
  // the known set instead of silently writing nothing)
  int set_tuning(uint32_t key, uint32_t value);

 private:
  // tuning registers: written by the host thread (set_tuning) while
  // the engine loop reads them mid-schedule — atomics, like
  // pipeline_depth_, so the live-write is well-defined on every lane
  std::atomic<uint32_t> bcast_flat_max_ranks_{4};
  std::atomic<uint32_t> reduce_flat_max_ranks_{4};
  std::atomic<uint32_t> gather_flat_max_fanin_{64};
  // byte thresholds (accl.cpp:1216-1224)
  std::atomic<uint64_t> gather_flat_max_count_{32 * 1024};
  std::atomic<uint64_t> reduce_flat_max_count_{32 * 1024};

  // ---- persistent-plan storage (see plan_create/plan_replay) ----
  struct EnginePlan {
    std::vector<std::array<uint32_t, 15>> descs;  // pre-parsed, pinned
    std::vector<std::pair<uint32_t, uint32_t>> comm_epochs;  // at arm
    bool valid = true;
  };
  std::vector<EnginePlan> plans_ ACCL_GUARDED_BY(plans_mu_);
  // token -> call ids
  std::map<long long, std::vector<uint64_t>> plan_tokens_
      ACCL_GUARDED_BY(plans_mu_);
  long long next_plan_token_ ACCL_GUARDED_BY(plans_mu_) = 1;
  std::atomic<uint64_t> plan_replays_{0};  // telemetry: replays queued
  // LOCK ORDER: plans_mu_ before results_mu_ (the replay token reaper
  // scans results under both); never the inverse.
  mutable Mutex plans_mu_ ACCL_ACQUIRED_BEFORE(results_mu_);

  Fifo<CallDesc> cmd_q_;
  std::deque<CallDesc> retry_q_;  // firmware retry FIFO (fw :2460-2479)
  //: consecutive unproductive retry sweeps, for adaptive pacing in
  //: loop(): yield first, escalate to a bounded sleep (engine thread
  //: only — no locking needed)
  uint32_t retry_idle_sweeps_ = 0;
  std::map<uint64_t, CallResult> results_ ACCL_GUARDED_BY(results_mu_);
  Mutex results_mu_;
  std::atomic<uint64_t> next_call_id_{1};
  uint32_t sticky_err_ = 0;  // per-call error accumulator (loop thread only)

  Thread loop_thread_;
  std::atomic<bool> running_{true};
  std::atomic<bool> stopped_{false};  // shutdown() ran to completion
};

}  // namespace accl
