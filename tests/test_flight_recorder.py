"""Flight recorder, hang/desync watchdog and the health/OpenMetrics
surface (accl_tpu/observability/flight.py + health.py): always-on
record lifecycle on both backends, flight-embedded timeout errors,
watchdog hang diagnosis naming the missing rank, the cross-rank desync
analyzer, gang-assembly introspection, and the exporter endpoints."""
import json
import time
import urllib.request

import numpy as np
import pytest

from accl_tpu import ACCLError, ReduceFunction
from accl_tpu.observability import flight as obs_flight
from accl_tpu.observability import health as obs_health
from accl_tpu.observability import metrics as obs_metrics
from accl_tpu.observability.record import S_RECOVERING, mark
from accl_tpu.observability.trace import now_ns

COUNT = 64
NRANKS = 4


def _allreduce_all(world, reps=1):
    def fn(accl, rank):
        s = accl.create_buffer_like(
            np.arange(COUNT, dtype=np.float32) + rank)
        r = accl.create_buffer(COUNT, np.float32)
        for _ in range(reps):
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
        return r.host.copy()

    return world.run(fn)


# ---------------------------------------------------------------------------
# always-on record lifecycle
# ---------------------------------------------------------------------------
def test_flight_records_tpu_gang_lifecycle():
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(NRANKS) as w:
        _allreduce_all(w, reps=2)
        for accl in w.accls:
            rec_list = [r for r in accl.flight_recorder.records()
                        if r.collective == "allreduce"]
            assert len(rec_list) == 2
            for rec in rec_list:
                assert rec.gang and not rec.in_flight
                assert rec.state == obs_flight.S_COMPLETE
                assert rec.lane in ("leader", "executor", "batched")
                assert rec.dtype == "float32"
                assert rec.nbytes == COUNT * 4
                # full state-machine walk, stamped in order
                assert (rec.t_submit <= rec.t_queue <= rec.t_gang_ready
                        <= rec.t_dispatch <= rec.t_complete)
            # per-rank seq is monotonic and completion advanced the
            # recorder's high-water mark
            seqs = [r.seq for r in rec_list]
            assert seqs == sorted(seqs)
            assert accl.flight_recorder.last_completed_seq >= seqs[-1]


def test_flight_records_emu_lane():
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(2) as w:
        _allreduce_all(w)
        for accl in w.accls:
            (rec,) = [r for r in accl.flight_recorder.records()
                      if r.collective == "allreduce"]
            assert rec.lane == "emu"
            assert rec.state == obs_flight.S_COMPLETE
            assert rec.t_submit <= rec.t_queue <= rec.t_dispatch \
                <= rec.t_complete


def test_flight_ring_is_bounded_and_disableable():
    rec = obs_flight.FlightRecorder(rank=0, capacity=4)
    for i in range(10):
        r = rec.new_record(i, "allreduce", 0, 0, "float32", 8, 32, 2,
                           True, now_ns())
        r.finish(0, now_ns())
    assert len(rec) == 4
    assert [r.seq for r in rec.records()] == [6, 7, 8, 9]
    assert rec.last_completed_seq == 9
    # the ACCL_FLIGHT=0 switch: no records attached while off
    obs_flight.set_enabled(False)
    try:
        assert not obs_flight.enabled()
        from accl_tpu.backends.tpu import TpuWorld

        with TpuWorld(2) as w:
            _allreduce_all(w)
            assert all(a.flight_recorder is None for a in w.accls)
    finally:
        obs_flight.set_enabled(True)
    assert obs_flight.enabled()


def test_dump_schema_and_dump_flight_recorder_api(tmp_path):
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(2) as w:
        _allreduce_all(w)
        doc = w.accls[0].dump_flight_recorder(
            path=str(tmp_path / "r0.json"))
        assert doc["rank"] == 0
        for rec in doc["records"]:
            assert set(obs_flight.RECORD_SCHEMA_KEYS) <= set(rec)
        with open(tmp_path / "r0.json") as f:
            assert json.load(f)["rank"] == 0
        merged = w.accls[0].dump_flight_recorder(merged=True)
        assert merged["nranks"] >= 2
        assert merged["analysis"]["ok"]


# ---------------------------------------------------------------------------
# flight-embedded timeout errors + configurable wait default
# ---------------------------------------------------------------------------
def test_check_on_in_flight_request_embeds_flight_record():
    from accl_tpu.request import Request

    recr = obs_flight.FlightRecorder(rank=3)
    req = Request("allreduce(SUM)")
    req.record = recr.new_record(req.id, "allreduce", 0, 0, "float32",
                                 64, 256, 4, True, now_ns())
    req.record.lane = "emu"
    with pytest.raises(ACCLError) as ei:
        req.check()
    msg = str(ei.value)
    assert "seq=0" in msg and "state=submitted" in msg \
        and "lane=emu" in msg and "age=" in msg


def test_driver_timeout_error_embeds_flight_record():
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(2) as w:
        def fn(accl, rank):
            buf = accl.create_buffer(COUNT, np.float32)
            if rank == 0:
                accl.call_timeout_s = 0.2  # driver budget fires first
                with pytest.raises(ACCLError) as ei:
                    accl.recv(buf, COUNT, src=1)
                accl.call_timeout_s = 60.0
                msg = str(ei.value)
                assert "timed out" in msg and "[flight:" in msg \
                    and "recv" in msg and "lane=emu" in msg
                return msg
            # unblock rank 0's pending engine recv before teardown
            time.sleep(0.5)
            src = accl.create_buffer_like(
                np.arange(COUNT, dtype=np.float32))
            accl.send(src, COUNT, dst=0)
            return None

        w.run(fn)


def test_wait_default_configurable_via_env(monkeypatch):
    from accl_tpu import request as request_mod

    monkeypatch.setenv("ACCL_DEFAULT_TIMEOUT", "2000000")  # 2 s engine
    assert request_mod.default_wait_timeout_s() == pytest.approx(61.0)
    monkeypatch.setenv("ACCL_DEFAULT_TIMEOUT", "3e7")
    assert request_mod.default_wait_timeout_s() == pytest.approx(89.0)
    # a bare wait() resolves the default (and still times out/false on
    # an incomplete request when given a tiny explicit budget)
    req = request_mod.Request("never")
    assert req.wait(timeout=0.01) is False


# ---------------------------------------------------------------------------
# watchdog: hang detection names the missing rank
# ---------------------------------------------------------------------------
def test_watchdog_fires_and_names_missing_rank(tmp_path):
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(NRANKS) as w:
        wd = w.start_watchdog(timeout_s=0.3,
                              dump_path=str(tmp_path / "wd.json"))
        bufs = {}

        def setup(accl, rank):
            s = accl.create_buffer_like(
                np.arange(COUNT, dtype=np.float32) + rank)
            bufs[rank] = (s, accl.create_buffer(COUNT, np.float32))

        w.run(setup)
        reqs = {}

        def issue(accl, rank):
            if rank == 0:
                return None  # withheld gang member
            s, r = bufs[rank]
            reqs[rank] = accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                                        run_async=True)

        w.run(issue)
        deadline = time.time() + 15
        while wd.last_report is None and time.time() < deadline:
            time.sleep(0.02)
        assert wd.last_report is not None, "watchdog never fired"
        (hang,) = wd.last_report["analysis"]["hangs"]
        assert hang["collective"] == "allreduce"
        assert hang["arrived"] == [1, 2, 3]
        assert hang["missing"] == [0]
        assert hang["missing_blocked_on"]["0"] is None  # rank 0 idle
        assert (tmp_path / "wd.json").exists()  # automatic dump
        # the hung verdict is on the gauge the exporter serves
        snap = obs_metrics.default_registry().snapshot()
        assert snap["gauges"]["accl_health"] == obs_health.HEALTH_HUNG
        assert snap["counters"]["watchdog/fires"] >= 1

        # resolution: the missing rank joins, everything completes, and
        # the next watchdog sweep restores health
        def join(accl, rank):
            if rank != 0:
                return None
            s, r = bufs[rank]
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM)

        w.run(join)
        for rank in (1, 2, 3):
            assert reqs[rank].wait(60)
            reqs[rank].check()
        deadline = time.time() + 10
        while time.time() < deadline:
            snap = obs_metrics.default_registry().snapshot()
            if snap["gauges"]["accl_health"] == obs_health.HEALTH_OK:
                break
            time.sleep(0.05)
        assert snap["gauges"]["accl_health"] == obs_health.HEALTH_OK


def test_watchdog_degraded_after_engine_error():
    recr = obs_flight.FlightRecorder(rank=0)
    rec = recr.new_record(0, "allreduce", 0, 0, "float32", 8, 32, 2,
                          True, now_ns())
    rec.finish(5, now_ns())  # non-zero retcode
    reg = obs_metrics.MetricsRegistry()
    wd = obs_health.Watchdog([recr], timeout_s=10, registry=reg,
                             dump_path="")
    assert wd.check() is None
    assert reg.snapshot()["gauges"]["accl_health"] \
        == obs_health.HEALTH_DEGRADED


def test_watchdog_direct_check_reports_stuck_record(tmp_path):
    recr = obs_flight.FlightRecorder(rank=1)
    recr.new_record(0, "bcast", 0, 5, "float32", 8, 32, 2, True,
                    now_ns() - int(1e9))  # submitted 1 s ago
    reg = obs_metrics.MetricsRegistry()
    wd = obs_health.Watchdog([recr], timeout_s=0.2, registry=reg,
                             dump_path=str(tmp_path / "d.json"))
    report = wd.check()
    assert report is not None
    assert report["watchdog"]["stuck_records"][0]["collective"] == "bcast"
    assert reg.snapshot()["gauges"]["accl_health"] \
        == obs_health.HEALTH_HUNG
    # one fire per hang episode: a second sweep does not re-fire
    assert wd.check() is None


def test_tpu_gang_assembly_introspection():
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(2) as w:
        s0 = w.accls[0].create_buffer_like(
            np.arange(COUNT, dtype=np.float32))
        r0 = w.accls[0].create_buffer(COUNT, np.float32)
        req0 = w.accls[0].allreduce(s0, r0, COUNT, ReduceFunction.SUM,
                                    run_async=True)
        deadline = time.time() + 10
        snap = []
        while time.time() < deadline:
            snap = [g for g in w.engine.gang_assembly_snapshot()
                    if g.get("kind") == "collective"]
            if snap:
                break
            time.sleep(0.01)
        assert snap, "partial gang never visible to introspection"
        assert snap[0]["collective"] == "allreduce"
        assert snap[0]["arrived"] == [0]
        assert snap[0]["missing"] == [1]
        # second member arrives: gang dispatches, assembly table drains
        s1 = w.accls[1].create_buffer_like(
            np.arange(COUNT, dtype=np.float32))
        r1 = w.accls[1].create_buffer(COUNT, np.float32)
        w.accls[1].allreduce(s1, r1, COUNT, ReduceFunction.SUM)
        assert req0.wait(60)
        req0.check()
        assert not [g for g in w.engine.gang_assembly_snapshot()
                    if g.get("kind") == "collective"]


# ---------------------------------------------------------------------------
# cross-rank desync analyzer (merge_flight_dumps / accl_doctor)
# ---------------------------------------------------------------------------
def _mk_recorder(rank, calls, inflight=()):
    """calls: (collective, comm, tag, count) completed in order;
    inflight: same shape, left in submitted state."""
    recr = obs_flight.FlightRecorder(rank=rank)
    for i, (coll, comm, tag, count) in enumerate(calls):
        rec = recr.new_record(i, coll, comm, tag, "float32", count,
                              count * 4, 2, True, now_ns())
        rec.finish(0, now_ns())
    for coll, comm, tag, count in inflight:
        recr.new_record(99, coll, comm, tag, "float32", count,
                        count * 4, 2, True, now_ns())
    return recr


def test_desync_analyzer_flags_first_divergent_seq():
    a = _mk_recorder(0, [("allreduce", 0, -1, 64), ("bcast", 0, -1, 64)])
    b = _mk_recorder(1, [("bcast", 0, -1, 64), ("allreduce", 0, -1, 64)])
    doc = obs_flight.merge_flight_dumps([a.dump(), b.dump()])
    (d,) = doc["analysis"]["desyncs"]
    assert d["comm"] == 0 and d["index"] == 0
    assert d["per_rank"]["0"]["collective"] == "allreduce"
    assert d["per_rank"]["1"]["collective"] == "bcast"
    assert not doc["analysis"]["ok"]


def test_desync_analyzer_flags_shape_mismatch_not_matching_prefix():
    a = _mk_recorder(0, [("allreduce", 0, -1, 64), ("allgather", 0, -1, 32)])
    b = _mk_recorder(1, [("allreduce", 0, -1, 64), ("allgather", 0, -1, 16)])
    doc = obs_flight.merge_flight_dumps([a.dump(), b.dump()])
    (d,) = doc["analysis"]["desyncs"]
    assert d["index"] == 1  # the matching allreduce prefix is NOT flagged
    assert d["per_rank"]["0"]["count"] == 32
    assert d["per_rank"]["1"]["count"] == 16


def test_analyzer_reports_hang_and_cross_blocked_rank():
    # ranks 1/2 stuck in allreduce; rank 0 is itself stuck in a
    # DIFFERENT collective (the desync-shaped hang): the hang entry
    # must name rank 0 missing and show what it is blocked on
    a = _mk_recorder(0, [], inflight=[("bcast", 0, -1, 64)])
    b = _mk_recorder(1, [], inflight=[("allreduce", 0, -1, 64)])
    c = _mk_recorder(2, [], inflight=[("allreduce", 0, -1, 64)])
    doc = obs_flight.merge_flight_dumps([a.dump(), b.dump(), c.dump()])
    hangs = {h["collective"]: h for h in doc["analysis"]["hangs"]}
    h = hangs["allreduce"]
    assert h["arrived"] == [1, 2] and 0 in h["missing"]
    assert h["missing_blocked_on"]["0"]["collective"] == "bcast"


def test_analyzer_skips_order_analysis_on_wrapped_rings():
    # rank 0's ring wrapped (evicted history): positional comparison
    # against rank 1's full history would fake a desync — the analyzer
    # must skip it and say so, while hang detection stays live
    a = obs_flight.FlightRecorder(rank=0, capacity=2)
    for i, coll in enumerate(("allreduce", "bcast", "allgather")):
        rec = a.new_record(i, coll, 0, -1, "float32", 64, 256, 2, True,
                           now_ns())
        rec.finish(0, now_ns())
    b = _mk_recorder(1, [("allreduce", 0, -1, 64), ("bcast", 0, -1, 64),
                         ("allgather", 0, -1, 64)])
    doc = obs_flight.merge_flight_dumps([a.dump(), b.dump()])
    assert doc["analysis"]["desyncs"] == []
    assert doc["analysis"]["stragglers"] == []
    assert doc["analysis"]["truncated_comms"] == [0]
    assert doc["analysis"]["ok"]


def test_analyzer_reports_stragglers():
    a = _mk_recorder(0, [("allreduce", 0, -1, 64)] * 3)
    b = _mk_recorder(1, [("allreduce", 0, -1, 64)] * 1)
    doc = obs_flight.merge_flight_dumps([a.dump(), b.dump()])
    (s,) = doc["analysis"]["stragglers"]
    assert s["completed_lead"] == 3 and s["behind"] == {"1": 1}


def test_merge_accepts_paths_and_merged_docs(tmp_path):
    a = _mk_recorder(0, [("allreduce", 0, -1, 64)])
    b = _mk_recorder(1, [("allreduce", 0, -1, 64)])
    pa = tmp_path / "a.json"
    with open(pa, "w") as f:
        json.dump(a.dump(), f)
    doc = obs_flight.merge_flight_dumps(
        [str(pa), b.dump()], out_path=str(tmp_path / "m.json"))
    assert doc["nranks"] == 2 and doc["analysis"]["ok"]
    # a previous merge re-ingests wholesale (the doctor's input mode)
    again = obs_flight.merge_flight_dumps([str(tmp_path / "m.json")])
    assert again["nranks"] == 2


# ---------------------------------------------------------------------------
# OpenMetrics rendering + HTTP health surface
# ---------------------------------------------------------------------------
def test_to_openmetrics_format():
    reg = obs_metrics.MetricsRegistry()
    reg.inc("watchdog/fires", 2)
    reg.set_gauge("accl_health", obs_health.HEALTH_OK)
    for _ in range(3):
        reg.observe_call("allreduce", "float32", 1024, 100e3, nranks=4)
    text = reg.to_openmetrics()
    assert text.endswith("# EOF\n")
    assert "# TYPE accl_watchdog_fires counter" in text
    assert "accl_watchdog_fires_total 2" in text
    assert "accl_health 0" in text          # not double-prefixed
    lbl = 'collective="allreduce",dtype="float32",size_bucket="<=1KiB"'
    assert f"accl_collective_calls_total{{{lbl}}} 3" in text
    # cumulative histogram: 100us sits in le_256; every bucket >= 256
    # carries the full count, +Inf closes at 3
    assert f'accl_collective_latency_us_bucket{{{lbl},le="64"}} 0' in text
    assert f'accl_collective_latency_us_bucket{{{lbl},le="256"}} 3' in text
    assert f'accl_collective_latency_us_bucket{{{lbl},le="+Inf"}} 3' in text
    assert f"accl_collective_latency_us_count{{{lbl}}} 3" in text
    assert f"accl_collective_latency_us_sum{{{lbl}}} 300.0" in text


def test_openmetrics_membership_schema():
    # r11 exporter-consumer contract: the accl_health gauge documents
    # its new recovering=4 value, the membership-event counters and the
    # recovery-latency histogram carry HELP text, and the value-
    # histogram family renders cumulative buckets + sum/count
    reg = obs_metrics.MetricsRegistry()
    reg.set_gauge("accl_health", obs_health.HEALTH_RECOVERING)
    reg.inc("membership/joins", 1)
    reg.inc("membership/shrinks", 2)
    reg.inc("membership/grows", 1)
    reg.inc("membership/rank_deaths", 1)
    reg.inc("recovery/rounds", 1)
    reg.observe_value("recovery/latency_us", 5_000_000.0)
    text = reg.to_openmetrics()
    assert "# HELP accl_health " in text and "4=recovering" in text
    assert "accl_health 4" in text
    for fam in ("accl_membership_joins", "accl_membership_shrinks",
                "accl_membership_grows", "accl_membership_rank_deaths",
                "accl_recovery_rounds"):
        assert f"# HELP {fam} " in text, fam
        assert f"# TYPE {fam} counter" in text, fam
    assert "accl_membership_joins_total 1" in text
    assert "accl_membership_shrinks_total 2" in text
    assert "# HELP accl_recovery_latency_us " in text
    assert "# TYPE accl_recovery_latency_us histogram" in text
    # 5 s lands in le=16777216 (power-of-4 µs buckets, cumulative)
    assert 'accl_recovery_latency_us_bucket{le="4194304"} 0' in text
    assert 'accl_recovery_latency_us_bucket{le="+Inf"} 1' in text
    assert "accl_recovery_latency_us_sum 5000000.0" in text
    assert "accl_recovery_latency_us_count 1" in text
    # the gauge's code list stays in lockstep with HEALTH_NAMES
    # (r14 added 5=slow — the regression sentinel's verdict)
    assert "5=slow" in text
    assert obs_health.HEALTH_NAMES == (
        "ok", "degraded", "hung", "aborted", "recovering", "slow")


def test_flight_record_recovering_state():
    # supervisor phase records: live in the `recovering` state (in
    # flight, but non-gang — invisible to the stuck-gang scan), retired
    # by finish() like any record
    rec_ring = obs_flight.FlightRecorder(0, capacity=8)
    rec = rec_ring.new_record(-1, "recovery/shrink", 0, 0, "none", 0, 0,
                              1, False, obs_flight.now_ns())
    mark((rec,), ("t_dispatch",), state=S_RECOVERING, lane="supervisor")
    assert obs_flight.STATE_NAMES[rec.state] == "recovering"
    assert rec.in_flight and not rec.gang
    assert rec.lane == "supervisor"
    assert rec.to_dict()["state"] == "recovering"
    # a live recovering record never reads as a hang in the merge
    doc = obs_flight.merge_flight_dumps([rec_ring.dump()])
    assert doc["analysis"]["hangs"] == []
    rec.finish(0, obs_flight.now_ns())
    assert not rec.in_flight
    assert obs_flight.STATE_NAMES[rec.state] == "complete"
    assert "recovering" in obs_flight.STATE_NAMES


def test_metrics_exporter_endpoints():
    reg = obs_metrics.MetricsRegistry()
    reg.set_gauge("accl_health", obs_health.HEALTH_OK)
    reg.inc("watchdog/checks", 7)
    exp = obs_health.MetricsExporter(0, registry=reg)
    try:
        base = f"http://{exp.host}:{exp.port}"
        resp = urllib.request.urlopen(base + "/metrics", timeout=10)
        assert resp.headers["Content-Type"] \
            == obs_health.OPENMETRICS_CONTENT_TYPE
        body = resp.read().decode()
        assert "accl_health 0" in body and body.endswith("# EOF\n")
        hz = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=10).read())
        assert hz == {"health": "ok", "accl_health": 0,
                      "watchdog_fires": 0, "watchdog_checks": 7}
        fl = json.loads(urllib.request.urlopen(
            base + "/flight", timeout=10).read())
        assert "ranks" in fl and "analysis" in fl
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        exp.close()


def test_start_exporter_env_gating(monkeypatch):
    monkeypatch.delenv("ACCL_METRICS_PORT", raising=False)
    obs_health.stop_exporter()
    assert obs_health.start_exporter() is None  # unset -> no endpoint
    exp = obs_health.start_exporter(port=0)
    try:
        assert exp is obs_health.start_exporter(port=0)  # singleton
    finally:
        obs_health.stop_exporter()
