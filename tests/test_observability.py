"""Observability layer (accl_tpu/observability): span ordering
invariants, disabled-mode zero-allocation fast path, multi-rank gang-id
merge, Perfetto JSON schema validity, metrics registry content, and the
satellite fixes riding this PR (get_duration error paths, Timer/timed
unification, time_fn per-iteration sync)."""
import json
import os

import numpy as np
import pytest

from accl_tpu import ACCLError, ReduceFunction
from accl_tpu.observability import metrics as obs_metrics
from accl_tpu.observability import trace as obs_trace

COUNT = 64
NRANKS = 4


@pytest.fixture
def tracing():
    """Tracing ON with a fresh collector; restores disabled state."""
    col = obs_trace.enable()
    col.clear()
    try:
        yield col
    finally:
        obs_trace.disable()
        col.clear()


def _tpu_world(nranks=NRANKS):
    from accl_tpu.backends.tpu import TpuWorld

    return TpuWorld(nranks)


def _allreduce_all_ranks(world, reps=1):
    def fn(accl, rank):
        s = accl.create_buffer_like(
            np.arange(COUNT, dtype=np.float32) + rank)
        r = accl.create_buffer(COUNT, np.float32)
        for _ in range(reps):
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
        return r.host.copy()

    return world.run(fn)


# ---------------------------------------------------------------------------
# span ordering + gang merge (TPU backend gang scheduler)
# ---------------------------------------------------------------------------
def test_span_ordering_invariants(tracing):
    with _tpu_world() as w:
        _allreduce_all_ranks(w, reps=2)
    spans = [s for s in tracing.spans() if s.collective == "allreduce"]
    assert len(spans) == 2 * NRANKS
    for s in spans:
        ts = s.timestamps()
        # every stage stamped on the gang path
        for k in ("submit", "queue", "gang_ready", "dispatch",
                  "launch", "launched", "rebound", "complete"):
            assert ts[k] is not None, f"stage {k} missing on {s!r}"
        assert s.t_submit <= s.t_queue <= s.t_gang_ready
        assert s.t_gang_ready <= s.t_dispatch <= s.t_launch
        assert s.t_launch <= s.t_launched <= s.t_rebound <= s.t_complete
        assert s.lane in ("leader", "executor", "batched")
        assert s.dtype == "float32"
        assert s.nbytes == COUNT * 4


def test_multi_rank_gang_id_merge(tracing):
    with _tpu_world() as w:
        _allreduce_all_ranks(w, reps=3)
    spans = [s for s in tracing.spans() if s.collective == "allreduce"]
    by_gang = {}
    for s in spans:
        by_gang.setdefault(s.gang_id, []).append(s)
    # 3 instances, each merging all four ranks under one gang id
    assert len(by_gang) == 3
    for gid, members in by_gang.items():
        assert gid is not None
        assert sorted(m.rank for m in members) == list(range(NRANKS))
        # a fused gang program has ONE launch, so member slices are
        # exactly aligned
        assert len({(m.t_launch, m.t_launched) for m in members}) == 1


def test_disabled_mode_zero_allocation(tracing, monkeypatch):
    # flip OFF after the fixture armed a fresh collector: the driver
    # and backends must not allocate spans nor touch the ring buffer,
    # nor open a stage annotation
    obs_trace.disable()
    opened = []
    monkeypatch.setattr(obs_trace, "_open_annotation", opened.append)
    with _tpu_world() as w:
        def fn(accl, rank):
            s = accl.create_buffer_like(
                np.arange(COUNT, dtype=np.float32))
            r = accl.create_buffer(COUNT, np.float32)
            req = accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
            # nothing for the collector
            assert req.record is None or not req.record.traced
            return True

        w.run(fn)
    assert obs_trace.new_span("x") is None
    assert len(tracing) == 0
    assert opened == []


# ---------------------------------------------------------------------------
# one record per call, on the lanes the gang-stage test does not cover
# ---------------------------------------------------------------------------
#: per lane, the stamps its record leaves None: the stages it has not
LANE_UNSET = {
    "local": ("gang_ready", "rebound"),
    "p2p": ("gang_ready", "rebound"),
    "emu": ("gang_ready", "rebound"),
    "plan": ("queue", "gang_ready", "launch", "launched", "rebound"),
}


@pytest.mark.parametrize("lane", list(LANE_UNSET))
def test_one_record_per_call(tracing, lane):
    """The flight ring and the trace collector hold the same object for
    a call; its stamps are in lifecycle order and None only where the
    lane has no such stage."""
    from accl_tpu.backends.emu import EmuWorld
    from accl_tpu.backends.tpu import TpuWorld
    from accl_tpu.observability import flight as obs_flight

    def fn(accl, rank):
        s = accl.create_buffer_like(
            np.arange(COUNT, dtype=np.float32) + rank)
        r = accl.create_buffer(COUNT, np.float32)
        if lane == "local":
            return accl.copy(s, r, COUNT).record
        if lane == "p2p":
            if rank == 0:
                accl.send(s, COUNT, 1, tag=3)
                return None
            return accl.recv(r, COUNT, 0, tag=3).record
        if lane == "emu":
            return accl.allreduce(s, r, COUNT, ReduceFunction.SUM).record
        s.sync_to_device()
        plan = accl.capture_plan(lambda a: a.allreduce(
            s, r, COUNT, ReduceFunction.SUM, from_fpga=True,
            to_fpga=True))
        plan.replay()
        return [x for x in accl.flight_recorder.records()
                if x.collective == "plan_replay"][-1]

    with (EmuWorld(2) if lane == "emu" else TpuWorld(2)) as w:
        rec = w.run(fn)[1]
        ring = w.accls[1].flight_recorder.records()
    assert any(x is rec for x in ring)
    assert any(x is rec for x in tracing.spans())
    assert rec.lane == lane
    assert obs_flight.STATE_NAMES[rec.state] == "complete"
    ts = rec.timestamps()
    assert tuple(k for k, v in ts.items() if v is None) == LANE_UNSET[lane]
    stamped = [v for v in ts.values() if v is not None]
    assert stamped == sorted(stamped)


# ---------------------------------------------------------------------------
# Perfetto export schema
# ---------------------------------------------------------------------------
def test_perfetto_json_schema(tracing, tmp_path):
    with _tpu_world() as w:
        _allreduce_all_ranks(w)
    path = tracing.dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.loads(f.read())
    events = doc["traceEvents"]
    assert events
    for ev in events:
        for key in ("ph", "ts", "pid", "tid"):
            assert key in ev, f"{key} missing from {ev}"
        assert ev["ph"] in ("X", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    # per-rank process tracks with at least one slice each
    slice_pids = {ev["pid"] for ev in events if ev["ph"] == "X"}
    assert slice_pids == set(range(NRANKS))
    # lane track names registered via thread_name metadata
    names = {ev["args"]["name"] for ev in events if ev["ph"] == "M"
             and ev["name"] == "thread_name"}
    assert any(n.startswith("lane:") for n in names)
    assert "queue" in names and "call" in names


def test_emu_backend_spans_and_merge(tracing):
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(NRANKS) as w:
        def fn(accl, rank):
            s = accl.create_buffer_like(
                np.arange(COUNT, dtype=np.float32) + rank)
            r = accl.create_buffer(COUNT, np.float32)
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
            return r.host.copy()

        w.run(fn)
    spans = [s for s in tracing.spans() if s.collective == "allreduce"]
    assert len(spans) == NRANKS
    assert len({s.gang_id for s in spans}) == 1  # one merged gang
    assert sorted(s.rank for s in spans) == list(range(NRANKS))
    for s in spans:
        assert s.lane == "emu"
        assert s.t_submit <= s.t_queue <= s.t_dispatch
        assert s.t_dispatch <= s.t_launch <= s.t_launched
        assert s.t_launched <= s.t_complete


def test_traced_window_and_merge_files(tracing, tmp_path):
    with obs_trace.traced_window("unit"):
        pass
    spans = [s for s in tracing.spans() if s.collective == "window:unit"]
    assert len(spans) == 1 and spans[0].lane == "window"
    # merge: two single-file traces with a shared gang id align clocks
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    def mk(path, ts):
        ev = {"name": "g", "ph": "X", "ts": ts, "dur": 5.0, "pid": 0,
              "tid": 0, "args": {"gang_id": 7}}
        with open(path, "w") as f:
            json.dump({"traceEvents": [ev]}, f)
    mk(p1, 100.0)
    mk(p2, 900.0)
    doc = obs_trace.merge_trace_files([p1, p2])
    ts = [ev["ts"] for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert ts == [100.0, 100.0]  # second file shifted onto the first


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_registry_reports_calls_hist_and_bandwidth():
    reg = obs_metrics.MetricsRegistry()
    # 1 KiB allreduce over 4 ranks, 10 calls of 100 us each
    for _ in range(10):
        reg.observe_call("allreduce", "float32", 1024, 100e3, nranks=4)
    reg.observe_call("allreduce", "float32", 1024, 100e3, nranks=4,
                     ok=False)
    snap = reg.snapshot()
    (key,) = snap["calls"].keys()
    st = snap["calls"][key]
    assert st["calls"] == 11 and st["errors"] == 1
    assert st["latency_us"]["avg"] == pytest.approx(100.0)
    # 100 us lands in the le_256 bucket of the power-of-4 ladder
    assert st["hist_us"]["le_256"] == 10
    assert sum(st["hist_us"].values()) == 10  # errors not in the hist
    # algbw = bytes/ns: 1024 B / 100e3 ns; busbw = algbw * 2(P-1)/P
    # (snapshot rounds to 4 decimals)
    assert st["algbw_GBps"] == pytest.approx(1024 / 100e3, abs=1e-4)
    assert st["busbw_GBps"] == pytest.approx(
        1024 / 100e3 * 1.5, abs=1e-4)
    # text + JSON renderings both carry the row
    assert "allreduce" in reg.to_text()
    assert json.loads(reg.to_json())["calls"][key]["calls"] == 11


def test_driver_publishes_metrics_end_to_end():
    reg = obs_metrics.default_registry()
    reg.reset()
    with _tpu_world() as w:
        _allreduce_all_ranks(w, reps=2)
        accl = w.accls[0]
        snap = accl.metrics()
        text = accl.dump_metrics()
        js = json.loads(accl.dump_metrics(as_json=True))
    rows = [v for v in snap["calls"].values()
            if v["collective"] == "allreduce"]
    assert rows and rows[0]["calls"] == 2 * NRANKS
    assert rows[0]["dtype"] == "float32"
    assert rows[0]["nranks"] == NRANKS
    assert rows[0]["algbw_GBps"] > 0
    assert sum(rows[0]["hist_us"].values()) == 2 * NRANKS
    assert "allreduce" in text
    assert js["calls"]
    reg.reset()


def test_engine_stats_registry_view():
    with _tpu_world() as w:
        before = dict(w.engine.stats)
        assert set(before) >= {"leader_dispatches", "executor_dispatches",
                               "batches", "batched_gangs"}
        _allreduce_all_ranks(w)
        after = dict(w.engine.stats)
        assert (after["leader_dispatches"] + after["executor_dispatches"]
                + after["batched_gangs"]) > (
            before["leader_dispatches"] + before["executor_dispatches"]
            + before["batched_gangs"])


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------
def test_get_duration_unfinished_raises():
    from accl_tpu.accl import ACCL
    from accl_tpu.request import Request

    accl = ACCL(device=None)
    with pytest.raises(ACCLError, match="no request"):
        accl.get_duration()
    pending = Request("inflight")
    with pytest.raises(ACCLError, match="not completed"):
        accl.get_duration(pending)
    finished = Request("done")
    finished.complete(0, 123.0)
    assert accl.get_duration(finished) == 123.0


def test_get_duration_completed_path_end_to_end():
    with _tpu_world(2) as w:
        def fn(accl, rank):
            s = accl.create_buffer_like(
                np.arange(COUNT, dtype=np.float32))
            r = accl.create_buffer(COUNT, np.float32)
            req = accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                                 run_async=True)
            # in-flight request raises instead of returning 0.0
            if not req.done:
                with pytest.raises(ACCLError):
                    accl.get_duration(req)
            req.wait(60)
            return accl.get_duration(req)

        durs = w.run(fn)
    assert all(d > 0 for d in durs)


def test_timer_and_timed_unified():
    import time

    from accl_tpu.utils import profiling, timing

    # one implementation: profiling re-exports timing's
    assert profiling.timed is timing.timed
    assert profiling.Timer is timing.Timer
    t = timing.Timer()
    t.start()
    time.sleep(0.005)
    t.end()
    # ns and us agree (and the reference-shaped alias still works)
    assert t.duration_ns() == pytest.approx(t.duration_us() * 1e3)
    assert t.durationUs() == t.duration_us()
    results = {}
    with timing.timed("blk", results) as timer:
        time.sleep(0.002)
    assert isinstance(timer, timing.Timer)
    assert results["blk"][0] >= 1e6  # ns


def test_time_fn_blocks_each_iteration():
    import jax
    import jax.numpy as jnp

    from accl_tpu.utils.profiling import time_fn

    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones(256)
    per_call = time_fn(f, x, iters=3, warmup=1)
    overlapped = time_fn(f, x, iters=3, warmup=1, pipelined=True)
    assert per_call > 0 and overlapped > 0


def test_merge_dedupes_track_metadata(tmp_path):
    """r15 satellite: merging N per-process trace files must emit ONE
    thread_name/process_name declaration per (pid, tid), not one per
    input file — Perfetto renders duplicates as repeated track names."""
    paths = []
    for i in range(3):
        p = str(tmp_path / f"m{i}.json")
        events = [
            {"name": "process_name", "ph": "M", "ts": 0, "pid": 0,
             "tid": 0, "args": {"name": "rank 0"}},
            {"name": "thread_name", "ph": "M", "ts": 0, "pid": 0,
             "tid": 1, "args": {"name": "call"}},
            {"name": "g", "ph": "X", "ts": 10.0 + i, "dur": 2.0,
             "pid": 0, "tid": 1, "args": {"gang_id": 4}},
        ]
        with open(p, "w") as f:
            json.dump({"traceEvents": events}, f)
        paths.append(p)
    doc = obs_trace.merge_trace_files(paths)
    meta = [(ev["name"], ev["pid"], ev["tid"])
            for ev in doc["traceEvents"] if ev["ph"] == "M"]
    assert len(meta) == len(set(meta)), f"duplicated metadata: {meta}"
    assert ("process_name", 0, 0) in meta
    assert ("thread_name", 0, 1) in meta
    # slices all survive the dedup
    assert sum(1 for ev in doc["traceEvents"] if ev["ph"] == "X") == 3
    # and the smoke's schema checker agrees
    import importlib.util as _ilu
    spec = _ilu.spec_from_file_location(
        "trace_smoke", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "scripts", "trace_smoke.py"))
    smoke = _ilu.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.check_no_duplicate_metadata(doc["traceEvents"]) == []
    dup_doc = doc["traceEvents"] + [doc["traceEvents"][0]]
    assert smoke.check_no_duplicate_metadata(dup_doc)


def test_device_steps_render_as_perfetto_tracks(tracing):
    """r15: stamp buffers land as per-rank device:<collective> tracks
    whose slices carry the step/peer/bytes schema."""
    rows = [
        [0, 0, 0, 1, 2, 1, 3, 512, 512],
        [0, 1, 3, 4, 5, 1, 3, 512, 512],
        [1, 0, 0, 1, 2, 2, 0, 512, 512],
    ]
    obs_trace.record_device_steps("all_gather", np.array(rows, np.int32))
    assert len(tracing.device_records()) == 1
    assert tracing.device_link_bytes() == {(0, 1): 1024, (1, 2): 512}
    doc = tracing.to_perfetto()
    tracks = {(ev["pid"], ev["args"]["name"])
              for ev in doc["traceEvents"] if ev.get("ph") == "M"
              and str((ev.get("args") or {}).get("name", "")
                      ).startswith("device:")}
    assert (0, "device:all_gather") in tracks
    assert (1, "device:all_gather") in tracks
    dev = [ev for ev in doc["traceEvents"]
           if (ev.get("args") or {}).get("device_track")]
    # two slices (xfer + reduce) per stamp row
    assert len(dev) == 2 * len(rows)
    xfer = [ev for ev in dev if "xfer" in ev["name"]]
    assert all(ev["args"]["tx_bytes"] == 512 for ev in xfer)
    # clear() drops device records too
    tracing.clear()
    assert tracing.device_records() == []
