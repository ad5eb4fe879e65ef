"""Cross-rank critical-path attribution (observability/attribution.py)
+ the r14 torn-dump tolerance satellites.

The acceptance drills from ISSUE 12: on a 4-rank world with one rank
artificially delayed the report must name that rank as the dominant
straggler with > 90% episode share, and on a clean world the per-phase
breakdown must sum to within 5% of the measured end-to-end span — on
BOTH the emu and the tpu-interpret backends.
"""
import json
import time

import numpy as np
import pytest

from accl_tpu import ReduceFunction
from accl_tpu.observability import attribution, flight, trace
from accl_tpu.observability.record import CallRecord

NRANKS = 4
COUNT = 256
SLOW_RANK = 2
SLOW_S = 0.003


def _loop_body(iters, slow_rank=None, slow_s=SLOW_S):
    def body(accl, rank):
        send = accl.create_buffer_like(
            np.arange(COUNT, dtype=np.float32) + rank)
        recv = accl.create_buffer(COUNT, np.float32)
        for _ in range(iters):
            if rank == slow_rank:
                time.sleep(slow_s)  # the artificial compute-skew delay
            accl.allreduce(send, recv, COUNT, ReduceFunction.SUM,
                           from_fpga=True, to_fpga=True)
        return recv.host.copy()

    return body


def _emu_dump(iters, slow_rank=None):
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(NRANKS) as world:
        world.run(_loop_body(iters, slow_rank))
        # THIS world's recorders only — dump_all() sweeps every live
        # recorder in the process, and closed worlds from earlier tests
        # survive until a gc cycle collects their reference cycles
        return flight.merge_flight_dumps(
            [a.flight_recorder.dump() for a in world.accls])


def _tpu_dump(iters, slow_rank=None):
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(NRANKS) as world:
        world.run(_loop_body(iters, slow_rank))
        return flight.merge_flight_dumps(
            [a.flight_recorder.dump() for a in world.accls])


# ---------------------------------------------------------------------------
# acceptance: straggler attribution names the delayed rank
# ---------------------------------------------------------------------------
def test_straggler_attribution_emu():
    report = attribution.attribute(_emu_dump(12, slow_rank=SLOW_RANK))
    rows = [c for c in report["collectives"].values()
            if c["collective"] == "allreduce"]
    assert rows, "no allreduce group attributed"
    c = rows[0]
    d = c["dominant_straggler"]
    assert d is not None, "delayed rank not detected as straggler"
    assert d["rank"] == SLOW_RANK
    assert d["share"] > 0.9, f"episode share {d['share']} <= 0.9"
    # the injected delay is 3 ms; mean lateness must be that order
    assert d["mean_late_us"] > SLOW_S * 1e6 * 0.3


def test_straggler_attribution_tpu_interpret():
    report = attribution.attribute(_tpu_dump(10, slow_rank=SLOW_RANK))
    rows = [c for c in report["collectives"].values()
            if c["collective"] == "allreduce"]
    assert rows
    d = rows[0]["dominant_straggler"]
    assert d is not None
    assert d["rank"] == SLOW_RANK
    assert d["share"] > 0.9


# ---------------------------------------------------------------------------
# acceptance: clean-world phase breakdown partitions the span (>= 95%)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dump_fn", [_emu_dump, _tpu_dump],
                         ids=["emu", "tpu-interpret"])
def test_phase_breakdown_covers_span(dump_fn):
    report = attribution.attribute(dump_fn(10))
    assert report["gangs_analyzed"] >= 8
    for c in report["collectives"].values():
        cov = c["phase_coverage"]
        assert 0.95 <= cov <= 1.05, (
            f"{c['collective']}: phases sum to {cov * 100:.1f}% of the "
            f"end-to-end span (want within 5%) — {c['phases_us']}")
        assert c["span_us"] > 0
        # every phase is non-negative and present
        for p in attribution.PHASES:
            assert c["phases_us"].get(p, 0.0) >= 0.0


def test_clean_world_has_no_dominant_straggler():
    report = attribution.attribute(_emu_dump(10))
    for c in report["collectives"].values():
        d = c["dominant_straggler"]
        # scheduler noise may elect scattered stragglers, but no rank
        # may own >90% of episodes on a clean world with any material
        # lateness; allow small-sample blips below 1 ms
        if d is not None and d["share"] > 0.9:
            assert d["mean_late_us"] < 1000.0


# ---------------------------------------------------------------------------
# clock-skew estimation from gang-rendezvous anchors
# ---------------------------------------------------------------------------
def _synthetic_dump(skew_ns=0, nranks=2, gangs=6, late_rank=None,
                    late_ns=0):
    """Hand-built per-rank dumps: gang instance k completes at the same
    TRUE time on every rank; rank r's clock reads true + r*skew_ns.
    late_rank's arrival trails the others by late_ns (true time)."""
    base = 1_000_000_000
    ranks = []
    for r in range(nranks):
        recs = []
        for k in range(gangs):
            t0 = base + k * 1_000_000  # true submit
            arrive = t0 + (late_ns if r == late_rank else 0)
            complete = t0 + max(late_ns, 0) + 500_000  # shared point
            off = r * skew_ns
            recs.append({
                "seq": k, "req_id": k, "rank": r,
                "collective": "allreduce", "comm": 0, "tag": 0,
                "dtype": "float32", "count": COUNT,
                "nbytes": COUNT * 4, "nranks": nranks, "lane": "emu",
                "state": "complete", "gang": True, "retcode": 0,
                "age_us": 500.0,
                "t_submit": arrive + off, "t_queue": arrive + 1_000 + off,
                "t_gang_ready": 0, "t_dispatch": arrive + 2_000 + off,
                "t_complete": complete + off,
            })
        ranks.append({"rank": r, "capacity": 512,
                      "last_completed_seq": gangs - 1, "records": recs})
    return ranks


def test_clock_skew_estimated_from_gang_anchors():
    # rank 1's clock is 3 ms ahead; no real straggler exists.  Without
    # skew correction every arrival comparison would blame rank 1.
    dumps = _synthetic_dump(skew_ns=3_000_000)
    report = attribution.attribute(flight.merge_flight_dumps(dumps))
    skew = report["clock_skew_ns"]
    assert abs(skew["1"] - 3_000_000) < 1_000
    for c in report["collectives"].values():
        assert c["dominant_straggler"] is None, (
            "pure clock skew misattributed as a straggler")


def test_skewed_clock_still_catches_real_straggler():
    dumps = _synthetic_dump(skew_ns=3_000_000, late_rank=0,
                            late_ns=2_000_000)
    report = attribution.attribute(flight.merge_flight_dumps(dumps))
    c = next(iter(report["collectives"].values()))
    d = c["dominant_straggler"]
    assert d is not None and d["rank"] == 0
    assert 1_000 < d["mean_late_us"] < 3_000


def test_render_names_dominant_straggler():
    report = attribution.attribute(
        _synthetic_dump(late_rank=1, late_ns=2_000_000))
    text = attribution.render(report)
    assert "DOMINANT straggler: rank 1" in text
    assert "gang_wait" in text


# ---------------------------------------------------------------------------
# satellite: torn (crash-truncated) dumps are salvaged, not fatal
# ---------------------------------------------------------------------------
def test_merge_flight_dumps_tolerates_torn_tail(tmp_path):
    dumps = _synthetic_dump(gangs=8)
    p0 = tmp_path / "r0.json"
    p1 = tmp_path / "r1.json"
    p0.write_text(json.dumps(dumps[0], indent=1))
    text = json.dumps(dumps[1], indent=1)
    p1.write_text(text[: int(len(text) * 0.7)])  # tear mid-record
    doc = flight.merge_flight_dumps([str(p0), str(p1)])
    torn = doc["analysis"]["torn_dumps"]
    assert len(torn) == 1 and torn[0]["path"] == str(p1)
    assert torn[0]["tail_bytes_skipped"] > 0
    # the complete prefix was salvaged (not everything lost)
    assert 0 < torn[0]["records_recovered"] < 8
    # the torn rank's order analysis gates like a wrapped ring: no
    # fake desync from the missing tail
    assert doc["analysis"]["desyncs"] == []
    assert 0 in doc["analysis"]["truncated_comms"]


def test_merge_flight_dumps_tolerates_torn_merged_doc(tmp_path):
    # a MERGED doc (watchdog dump: {"ranks": [...]}) torn mid-write
    # must salvage whole per-rank entries — probing the nested
    # "records" arrays first would silently drop every rank but the
    # first (r14 review finding)
    ranks = _synthetic_dump(gangs=4, nranks=3)
    merged = flight.merge_flight_dumps(ranks)
    text = json.dumps(merged, indent=1)
    # tear inside rank 2's entry: ranks 0 and 1 are fully intact
    cut = text.rindex('"rank": 2')
    p = tmp_path / "watchdog.json"
    p.write_text(text[:cut])
    doc = flight.merge_flight_dumps([str(p)])
    assert doc["nranks"] == 2, "intact ranks were dropped in salvage"
    assert sorted(rd["rank"] for rd in doc["ranks"]) == [0, 1]
    assert all(len(rd["records"]) == 4 for rd in doc["ranks"])
    assert doc["analysis"]["torn_dumps"][0]["records_recovered"] == 8


def test_merge_trace_files_tolerates_torn_tail(tmp_path):
    coll = trace.TraceCollector()
    for k in range(6):
        span = CallRecord("allreduce", rank=0, count=16)
        span.t_submit = 1000 + k
        span.t_complete = 2000 + k
        span.gang_id = k
        coll.add(span)
    doc = coll.to_perfetto()
    p0 = tmp_path / "t0.json"
    p1 = tmp_path / "t1.json"
    p0.write_text(json.dumps(doc))
    text = json.dumps(doc)
    p1.write_text(text[: int(len(text) * 0.6)])
    merged = trace.merge_trace_files([str(p0), str(p1)])
    assert len(merged["torn_files"]) == 1
    assert merged["torn_files"][0]["tail_bytes_skipped"] > 0
    assert merged["torn_files"][0]["events_recovered"] > 0
    assert len(merged["traceEvents"]) > len(doc["traceEvents"])


def test_salvage_rejects_hopeless_text():
    with pytest.raises(ValueError):
        trace.salvage_torn_json('{"no_array_here": 1', "records")


# ---------------------------------------------------------------------------
# attribution over merged docs vs raw dump lists must agree
# ---------------------------------------------------------------------------
def test_attribute_accepts_merged_and_raw():
    dumps = _synthetic_dump(late_rank=1, late_ns=2_000_000)
    merged = flight.merge_flight_dumps(dumps)
    a = attribution.attribute(merged)
    b = attribution.attribute(dumps)
    assert a["collectives"] == b["collectives"]
    # timeline mode carries the per-gang rows
    t = attribution.attribute(merged, timeline=True)
    assert len(t["timeline"]) == t["gangs_analyzed"]
    assert all(row["last_rank"] == 1 for row in t["timeline"])


# ---------------------------------------------------------------------------
# r15: the overlap accountant (wire-exposed vs compute-overlapped)
# ---------------------------------------------------------------------------
def test_overlap_math_with_fabricated_windows():
    """Synthetic dump + trace doc: exact interval arithmetic.  Two
    ranks, one gang; rank 0's wire interval [2us, 10us) is half-covered
    by a device window [4us, 8us) -> 4us overlapped, 4us exposed."""
    def rec(rank):
        return {"seq": 1, "gang": True, "state": "complete",
                "comm": 0, "collective": "allreduce", "tag": 0,
                "count": 64, "dtype": "float32", "nbytes": 256,
                "t_submit": 1000, "t_queue": 1500, "t_dispatch": 2000,
                "t_complete": 10000}

    doc = {"ranks": [{"rank": 0, "records": [rec(0)]},
                     {"rank": 1, "records": [rec(1)]}]}
    trace_doc = {"traceEvents": [
        # device COMPUTE window on rank 0: ts/dur in us, 4us..8us (an
        # xfer-phase slice would be excluded — it IS the wire)
        {"ph": "X", "pid": 0, "tid": 5, "name": "s0:reduce",
         "ts": 4.0, "dur": 4.0,
         "args": {"device_track": True, "device_phase": "reduce"}},
        # an unrelated non-compute slice must be ignored
        {"ph": "X", "pid": 0, "tid": 1, "name": "allreduce",
         "ts": 0.0, "dur": 100.0, "args": {}},
    ]}
    report = attribution.overlap(doc, trace_doc=trace_doc)
    assert report["compute_windows"] == 1
    row = report["collectives"]["allreduce|comm0|<=256B"]
    # rank 0: wire 8us, overlap 4us; rank 1: wire 8us, overlap 0
    assert row["wire_us"] == pytest.approx(16.0)
    assert row["overlapped_us"] == pytest.approx(4.0)
    assert row["exposed_us"] == pytest.approx(12.0)
    assert row["recovered_compute_fraction"] == pytest.approx(0.25)
    # span total 9us + 9us -> exposed fraction 12/18 (report rounds
    # fractions to 4 decimals)
    assert row["exposed_fraction"] == pytest.approx(12.0 / 18.0,
                                                    abs=1e-4)
    # without the trace doc nothing is overlapped
    bare = attribution.overlap(doc)
    assert bare["collectives"]["allreduce|comm0|<=256B"][
        "overlapped_us"] == 0.0


def test_wire_exposed_fraction_drops_without_delay_emu():
    """Acceptance drill (emu): a chaos-slowed peer produces a nonzero
    wire-exposed fraction that DROPS when the delay is removed."""
    slow = attribution.overlap(_emu_dump(10, slow_rank=SLOW_RANK))
    clean = attribution.overlap(_emu_dump(10, slow_rank=None))
    s = [c for c in slow["collectives"].values()
         if c["collective"] == "allreduce"][0]
    c = [c for c in clean["collectives"].values()
         if c["collective"] == "allreduce"][0]
    assert s["exposed_fraction"] > 0
    assert c["exposed_fraction"] > 0
    # the 3ms/iteration artificial delay dominates the slow world's
    # spans; removing it must shrink the exposed wire share
    assert s["exposed_us"] > c["exposed_us"]
    assert s["exposed_fraction"] >= c["exposed_fraction"]


def test_wire_exposed_fraction_drops_without_delay_tpu_interpret():
    """Acceptance drill (tpu-interpret rung): same contract through
    the gang-scheduler backend."""
    slow = attribution.overlap(_tpu_dump(8, slow_rank=SLOW_RANK))
    clean = attribution.overlap(_tpu_dump(8, slow_rank=None))
    s = [c for c in slow["collectives"].values()
         if c["collective"] == "allreduce"][0]
    c = [c for c in clean["collectives"].values()
         if c["collective"] == "allreduce"][0]
    assert s["exposed_fraction"] > 0
    assert s["exposed_us"] > c["exposed_us"]


def test_overlap_counts_window_spans():
    """Host-marked window: spans (trace.traced_window) count as
    compute cover too — the pre-device-trace way to mark compute."""
    windows = attribution._compute_windows({"traceEvents": [
        {"ph": "X", "pid": 3, "tid": 0, "name": "window:ffn",
         "ts": 10.0, "dur": 5.0, "args": {}},
        {"ph": "X", "pid": 3, "tid": 0, "name": "window:moe",
         "ts": 30.0, "dur": 5.0, "args": {}},
    ]})
    assert windows == {3: [(10000.0, 15000.0), (30000.0, 35000.0)]}
    assert attribution._overlap_ns(12000.0, 32000.0, windows[3]) == \
        pytest.approx(5000.0)


def test_overlap_windows_merge_never_double_count():
    """Overlapping cover (a host window: span CONTAINING device stamp
    slices, the common shape) must merge to its union — summing the
    intersections per window would let recovered_compute exceed 1.0."""
    windows = attribution._compute_windows({"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 0, "name": "window:step",
         "ts": 10.0, "dur": 20.0, "args": {}},
        {"ph": "X", "pid": 0, "tid": 5, "name": "s0:reduce",
         "ts": 12.0, "dur": 4.0,
         "args": {"device_track": True, "device_phase": "reduce"}},
        {"ph": "X", "pid": 0, "tid": 5, "name": "s1:reduce",
         "ts": 28.0, "dur": 6.0,
         "args": {"device_track": True, "device_phase": "reduce"}},
        # the collective's own transfer slice is NOT compute cover
        {"ph": "X", "pid": 0, "tid": 5, "name": "s1:xfer->r1",
         "ts": 40.0, "dur": 6.0,
         "args": {"device_track": True, "device_phase": "xfer"}},
    ]})
    # 10-30 + 12-16 (contained) + 28-34 (extends) -> one 10-34 window
    assert windows == {0: [(10000.0, 34000.0)]}
    # cover can never exceed the wire interval itself
    assert attribution._overlap_ns(0.0, 100000.0, windows[0]) == \
        pytest.approx(24000.0)
