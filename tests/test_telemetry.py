"""Native-engine telemetry plane (observability/telemetry.py), the
regression sentinel (observability/sentinel.py), and the r14
observability satellites: ephemeral metrics port, OpenMetrics schema
completeness by construction, perf_doctor round-trip, doctor rendering
of unknown engine families.
"""
import io
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from accl_tpu import ReduceFunction
from accl_tpu.observability import health as obs_health
from accl_tpu.observability import metrics as obs_metrics
from accl_tpu.observability import sentinel as obs_sentinel
from accl_tpu.observability import telemetry as obs_telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_world(nranks=2, iters=4, count=64):
    from accl_tpu.backends.emu import EmuWorld

    world = EmuWorld(nranks)

    def body(accl, rank):
        send = accl.create_buffer_like(
            np.arange(count, dtype=np.float32) + rank)
        recv = accl.create_buffer(count, np.float32)
        for _ in range(iters):
            accl.allreduce(send, recv, count, ReduceFunction.SUM,
                           from_fpga=True, to_fpga=True)

    world.run(body)
    return world


# ---------------------------------------------------------------------------
# engine_stats: the versioned capi snapshot
# ---------------------------------------------------------------------------
def test_engine_stats_schema_and_traffic():
    world = _run_world()
    try:
        stats = world.engine_stats()
        assert len(stats) == world.nranks
        for st in stats:
            assert st["version"] == 3
            for field in obs_telemetry.ENGINE_STATS_FIELDS_V3:
                assert field in st, f"missing v3 field {field}"
            # no unknown fields from a same-version engine
            assert not any(k.startswith("unknown_field_") for k in st)
        # traffic really flowed through the counters
        assert all(st["tx_msgs"] > 0 for st in stats)
        assert all(st["seeks"] > 0 for st in stats)
        assert all(st["wire_accepted_frames"] > 0 for st in stats)
        # eager sends were captured into the retransmit store
        assert any(st["retrans_store_depth"] > 0 for st in stats)
        # the rx pool saw occupancy
        assert any(st["rx_occupancy_hwm"] > 0 for st in stats)
        # quiesced world: transient depths drained back to zero
        assert all(st["egress_depth"] == 0 for st in stats)
        assert all(st["seek_misses"] == 0 for st in stats)
    finally:
        world.close()


def test_engine_stats_closed_world_raises():
    from accl_tpu.constants import ACCLError

    world = _run_world(iters=1)
    dev = world.devices[0]
    world.close()
    with pytest.raises(ACCLError):
        dev.engine_stats()


def test_decode_keeps_newer_engine_fields():
    n = len(obs_telemetry.ENGINE_STATS_FIELDS_V2)
    values = list(range(n + 2))  # a newer engine returned 2 extra
    st = obs_telemetry.decode_engine_stats(values, version=2,
                                           total_fields=n + 2)
    assert st[obs_telemetry.ENGINE_STATS_FIELDS_V2[0]] == 0
    assert st[f"unknown_field_{n}"] == n
    assert st[f"unknown_field_{n + 1}"] == n + 1


@pytest.mark.parametrize("decoder_version,engine_fields,expect_known", [
    # v1 decoder over a v2 engine's array: field 25 (link_rows) must
    # surface as unknown_field_25, never silently vanish or mis-name
    (1, obs_telemetry.ENGINE_STATS_FIELDS_V2,
     obs_telemetry.ENGINE_STATS_FIELDS_V1),
    # v2 decoder over a v1 engine's (shorter) array: a clean prefix
    (2, obs_telemetry.ENGINE_STATS_FIELDS_V1,
     obs_telemetry.ENGINE_STATS_FIELDS_V1),
    # same-version both ways
    (1, obs_telemetry.ENGINE_STATS_FIELDS_V1,
     obs_telemetry.ENGINE_STATS_FIELDS_V1),
    (2, obs_telemetry.ENGINE_STATS_FIELDS_V2,
     obs_telemetry.ENGINE_STATS_FIELDS_V2),
    # v3 (r17 quantized-wire pair) both ways
    (2, obs_telemetry.ENGINE_STATS_FIELDS_V3,
     obs_telemetry.ENGINE_STATS_FIELDS_V2),
    (3, obs_telemetry.ENGINE_STATS_FIELDS_V3,
     obs_telemetry.ENGINE_STATS_FIELDS_V3),
])
def test_decode_engine_stats_version_table(decoder_version,
                                           engine_fields, expect_known):
    """Table-driven forward/backward compat: the decoder's version
    selects ITS field table; extra engine fields become
    unknown_field_<i>, missing ones are simply absent."""
    values = list(range(len(engine_fields)))
    st = obs_telemetry.decode_engine_stats(
        values, version=decoder_version,
        total_fields=len(engine_fields))
    for i, name in enumerate(expect_known):
        assert st[name] == i, name
    known = obs_telemetry.ENGINE_STATS_FIELDS_BY_VERSION[decoder_version]
    for i in range(len(known), len(engine_fields)):
        assert st[f"unknown_field_{i}"] == i
    # nothing mis-sliced: every value accounted for exactly once
    assert sorted(v for k, v in st.items() if k != "version") == \
        list(range(len(engine_fields)))


def test_decode_link_stats_strict_stride():
    """The link decoder must refuse a flat array that is not a whole
    number of rows — mis-slicing would shift every counter into the
    wrong field (the compat-hardening satellite)."""
    from accl_tpu.constants import ACCLError

    stride = len(obs_telemetry.LINK_STATS_FIELDS_V2)
    rows = obs_telemetry.decode_link_stats(list(range(2 * stride)))
    assert len(rows) == 2
    assert rows[0]["comm"] == 0 and rows[0]["peer"] == 1
    assert rows[1]["comm"] == stride
    assert obs_telemetry.decode_link_stats([]) == []
    for bad_len in (1, stride - 1, stride + 1, 2 * stride - 3):
        with pytest.raises(ACCLError, match="stride"):
            obs_telemetry.decode_link_stats(list(range(bad_len)))


# ---------------------------------------------------------------------------
# the sampler: engine/* families, counter-delta discipline, off switch
# ---------------------------------------------------------------------------
def test_sampler_publishes_engine_families():
    reg = obs_metrics.MetricsRegistry()
    world = _run_world()
    try:
        sampler = obs_telemetry.TelemetrySampler(
            [d.engine_stats for d in world.devices], registry=reg,
            interval_s=30.0)
        sampler.sample()
        snap = reg.snapshot()
        assert snap["counters"].get("engine/tx_msgs", 0) > 0
        assert snap["counters"].get("engine/seeks", 0) > 0
        assert "engine/rx_occupancy_hwm" in snap["gauges"]
        total_first = snap["counters"]["engine/tx_msgs"]
        # second sample without new traffic: counters must NOT double
        sampler.sample()
        assert reg.snapshot()["counters"]["engine/tx_msgs"] == total_first
        # counters aggregate as the SUM over ranks
        per_rank = sum(st["tx_msgs"] for st in world.engine_stats())
        assert total_first == per_rank
    finally:
        world.close()


def test_sampler_env_gate(monkeypatch):
    monkeypatch.delenv("ACCL_TELEMETRY_INTERVAL_MS", raising=False)
    assert obs_telemetry.sampler_from_env([lambda: {}]) is None
    monkeypatch.setenv("ACCL_TELEMETRY_INTERVAL_MS", "0")
    assert obs_telemetry.sampler_from_env([lambda: {}]) is None
    monkeypatch.setenv("ACCL_TELEMETRY_INTERVAL_MS", "50")
    reg = obs_metrics.MetricsRegistry()
    sampler = obs_telemetry.sampler_from_env(
        [lambda: {"tx_msgs": 3, "egress_depth": 1}], registry=reg)
    try:
        assert sampler is not None and sampler.interval_s == 0.05
        sampler.sample()
        assert reg.counter("engine/tx_msgs") == 3
        assert reg.snapshot()["gauges"]["engine/egress_depth"] == 1
    finally:
        sampler.stop()


def test_sampler_survives_dying_source():
    reg = obs_metrics.MetricsRegistry()

    def dead():
        raise RuntimeError("world closed mid-poll")

    sampler = obs_telemetry.TelemetrySampler(
        [dead, lambda: {"tx_msgs": 7}], registry=reg, interval_s=30.0)
    sampler.sample()
    assert reg.counter("engine/tx_msgs") == 7


def test_tpu_engine_stats_schema():
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(2) as world:
        def body(accl, rank):
            send = accl.create_buffer_like(
                np.arange(32, dtype=np.float32) + rank)
            recv = accl.create_buffer(32, np.float32)
            for _ in range(3):
                accl.allreduce(send, recv, 32, ReduceFunction.SUM,
                               from_fpga=True, to_fpga=True)

        world.run(body)
        st = world.devices[0].engine_stats()
        assert st["version"] == 3
        assert st["link_rows"] >= 1  # the link twin saw ring traffic
        assert st["leader_dispatches"] + st["executor_dispatches"] > 0
        for k in ("plans_live", "plan_ring_refs",
                  "plan_ring_generation", "ready_depth"):
            assert k in st
        # every field classifies cleanly (counter or known gauge HELP)
        for k in st:
            if k == "version" or k in obs_telemetry.COUNTER_FIELDS:
                continue
            assert obs_metrics.metric_help_for(f"accl_engine_{k}"), k


# ---------------------------------------------------------------------------
# satellite: metrics schema completeness, by construction
# ---------------------------------------------------------------------------
def _sanitize(name: str) -> str:
    n = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    return n if n.startswith("accl_") else f"accl_{n}"


def test_every_registered_family_has_help():
    """Grep the library tree for every literal metric family minted via
    inc/set_gauge/observe_value and require each to resolve through
    METRIC_HELP (or a registered dynamic-name prefix) — the drift class
    'new family ships without HELP' fails here, not in review."""
    pattern = re.compile(
        r"\.(?:inc|set_gauge|observe_value)\(\s*(f?)\"([^\"]+)\"")
    families: dict = {}
    root = os.path.join(REPO, "accl_tpu")
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                text = f.read()
            for m in pattern.finditer(text):
                is_f, literal = m.group(1) == "f", m.group(2)
                prefix_only = is_f and "{" in literal
                name = literal.split("{")[0] if prefix_only else literal
                families[(name, prefix_only)] = path
    assert families, "grep found no metric registrations — pattern rot?"
    missing = []
    exact_keys = list(obs_metrics.METRIC_HELP)
    prefix_keys = list(obs_metrics.METRIC_HELP_PREFIXES)
    for (name, prefix_only), path in sorted(families.items()):
        s = _sanitize(name)
        if prefix_only:
            ok = any(k.startswith(s) for k in exact_keys) or \
                any(k.startswith(s) or s.startswith(k)
                    for k in prefix_keys)
        else:
            ok = obs_metrics.metric_help_for(s) is not None
        if not ok:
            missing.append(f"{name!r} ({path})")
    assert not missing, (
        "metric families without METRIC_HELP entries (add HELP text in "
        "observability/metrics.py): " + ", ".join(missing))


def test_exporter_body_validates_as_openmetrics():
    reg = obs_metrics.MetricsRegistry()
    reg.inc("watchdog/checks", 3)
    reg.inc("engine/tx_msgs", 9)
    reg.set_gauge("accl_health", 0)
    reg.set_gauge("engine/rx_occupancy_hwm", 4)
    reg.observe_value("recovery/latency_us", 1234.5)
    reg.observe_call("allreduce", "float32", 4096, 250_000.0, 4)
    reg.observe_call("allreduce", "float32", 4096, 90_000.0, 4)
    problems = obs_metrics.validate_openmetrics(reg.to_openmetrics())
    assert problems == []


def test_validator_catches_schema_breakage():
    reg = obs_metrics.MetricsRegistry()
    reg.inc("watchdog/checks")
    body = reg.to_openmetrics()
    assert obs_metrics.validate_openmetrics(body) == []
    # a family without HELP knowledge
    reg2 = obs_metrics.MetricsRegistry()
    reg2.inc("totally/unknown")
    probs = obs_metrics.validate_openmetrics(reg2.to_openmetrics())
    assert any("METRIC_HELP" in p for p in probs)
    # missing EOF
    assert any("EOF" in p for p in obs_metrics.validate_openmetrics(
        body.replace("# EOF", "")))
    # a sample without a TYPE declaration
    probs = obs_metrics.validate_openmetrics(
        "orphan_sample 1\n# EOF\n")
    assert any("TYPE" in p for p in probs)
    # non-cumulative histogram buckets
    bad = ("# TYPE accl_recovery_latency_us histogram\n"
           'accl_recovery_latency_us_bucket{le="1"} 5\n'
           'accl_recovery_latency_us_bucket{le="4"} 3\n'
           'accl_recovery_latency_us_bucket{le="+Inf"} 5\n'
           "accl_recovery_latency_us_sum 10\n"
           "accl_recovery_latency_us_count 5\n# EOF\n")
    assert any("cumulative" in p
               for p in obs_metrics.validate_openmetrics(bad))


# ---------------------------------------------------------------------------
# satellite: ACCL_METRICS_PORT=0 binds an ephemeral port
# ---------------------------------------------------------------------------
def test_metrics_port_zero_binds_ephemeral(monkeypatch):
    import urllib.request

    obs_health.stop_exporter()
    monkeypatch.setenv("ACCL_METRICS_PORT", "0")
    try:
        exporter = obs_health.ensure_exporter_from_env()
        assert exporter is not None, "port 0 must mean ephemeral, not off"
        port = obs_health.exporter_port()
        assert port == exporter.port and port > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["health"] in (
                "ok", "degraded", "hung", "aborted", "recovering",
                "slow")
    finally:
        obs_health.stop_exporter()
    assert obs_health.exporter_port() is None


def test_metrics_port_unset_means_off(monkeypatch):
    obs_health.stop_exporter()
    monkeypatch.delenv("ACCL_METRICS_PORT", raising=False)
    assert obs_health.ensure_exporter_from_env() is None
    monkeypatch.setenv("ACCL_METRICS_PORT", "")
    assert obs_health.ensure_exporter_from_env() is None


# ---------------------------------------------------------------------------
# regression sentinel: drift detection + the `slow` health verdict
# ---------------------------------------------------------------------------
def _observe(reg, us, n=30):
    for _ in range(n):
        reg.observe_call("allreduce", "float32", 4096, us * 1e3, 4)


def test_quantile_estimate_tracks_buckets():
    hist = [0] * (len(obs_metrics.LATENCY_BUCKETS_US) + 1)
    hist[5] = 100  # everything in the <=1024us bucket (4**5)
    p50 = obs_sentinel.quantile_us(hist, 0.5)
    assert 256 <= p50 <= 1024
    assert obs_sentinel.quantile_us([0] * len(hist), 0.5) == 0.0


def test_sentinel_flags_drift_and_degrades_health():
    reg = obs_metrics.MetricsRegistry()
    _observe(reg, us=200.0)
    baseline = obs_sentinel.Baseline.from_snapshot(reg.snapshot())
    assert baseline.entries, "baseline capture produced nothing"

    live = obs_metrics.MetricsRegistry()
    _observe(live, us=9000.0)  # ~45x the baseline p50
    sen = obs_sentinel.Sentinel(baseline, registry=live, p50_ratio=2.0,
                                p99_ratio=3.0, min_calls=10)
    findings = sen.check()
    assert findings, "45x latency drift not flagged"
    f = findings[0]
    assert f["collective"] == "allreduce" and f["axis"] in ("p50_us",
                                                           "p99_us")
    assert f["ratio"] > 2.0
    assert live.snapshot()["gauges"]["accl_health"] == \
        obs_health.HEALTH_SLOW
    assert live.counter("sentinel/findings") >= 1
    # recovery: a fresh registry state below threshold clears the verdict
    live.reset()
    _observe(live, us=200.0)
    assert sen.check() == []
    assert live.snapshot()["gauges"]["accl_health"] == \
        obs_health.HEALTH_OK


def test_sentinel_slow_never_masks_stronger_verdicts():
    reg = obs_metrics.MetricsRegistry()
    obs_health.note_slow(reg, True)
    assert reg.snapshot()["gauges"]["accl_health"] == \
        obs_health.HEALTH_SLOW
    # a recovery episode outranks slow
    obs_health.note_recovering(reg, True)
    assert reg.snapshot()["gauges"]["accl_health"] == \
        obs_health.HEALTH_RECOVERING
    obs_health.note_recovering(reg, False)
    obs_health.note_slow(reg, False)
    assert reg.snapshot()["gauges"]["accl_health"] == obs_health.HEALTH_OK


def test_sentinel_min_calls_guard():
    reg = obs_metrics.MetricsRegistry()
    _observe(reg, us=100.0)
    baseline = obs_sentinel.Baseline.from_snapshot(reg.snapshot())
    live = obs_metrics.MetricsRegistry()
    _observe(live, us=9000.0, n=3)  # below min_calls
    sen = obs_sentinel.Sentinel(baseline, registry=live, min_calls=10)
    assert sen.compare_snapshot(live.snapshot()) == []


def test_baseline_loads_committed_formats(tmp_path):
    # callrate record
    cb = obs_sentinel.Baseline.load(
        os.path.join(REPO, "bench/results/callrate_r12_plan_on.json"))
    assert any(k[0] == "allreduce" for k in cb.entries)
    assert any(k[3] == "*" for k in cb.entries)
    # sweep-gate CSV
    sb = obs_sentinel.Baseline.load(
        os.path.join(REPO, "bench/results/sweep_gate_baseline_r12.csv"))
    assert any(k[0] == "allreduce" for k in sb.entries)
    # native round-trip
    p = tmp_path / "base.json"
    cb.save(str(p))
    rb = obs_sentinel.Baseline.load(str(p))
    assert rb.entries == cb.entries
    # merge: self wins on conflicts, union otherwise
    merged = cb.merge(sb)
    assert len(merged.entries) >= max(len(cb.entries), len(sb.entries))


def test_sentinel_env_gate(monkeypatch, tmp_path):
    obs_sentinel.stop_sentinel()
    monkeypatch.delenv("ACCL_SENTINEL", raising=False)
    assert obs_sentinel.ensure_sentinel_from_env() is None
    monkeypatch.setenv("ACCL_SENTINEL", "/nonexistent/base.json")
    assert obs_sentinel.ensure_sentinel_from_env() is None  # never raises
    reg = obs_metrics.MetricsRegistry()
    _observe(reg, us=100.0)
    p = tmp_path / "base.json"
    obs_sentinel.Baseline.from_snapshot(reg.snapshot()).save(str(p))
    monkeypatch.setenv("ACCL_SENTINEL", str(p))
    monkeypatch.setenv("ACCL_SENTINEL_INTERVAL_MS", "60000")
    try:
        sen = obs_sentinel.ensure_sentinel_from_env()
        assert sen is not None
        assert obs_sentinel.ensure_sentinel_from_env() is sen  # idempotent
    finally:
        obs_sentinel.stop_sentinel()


# ---------------------------------------------------------------------------
# perf_doctor CLI round-trip (+ --ci schema gate)
# ---------------------------------------------------------------------------
def test_perf_doctor_cli_roundtrip(tmp_path):
    import time as _time

    from accl_tpu.backends.emu import EmuWorld
    from accl_tpu.observability import flight

    reg = obs_metrics.default_registry()
    with EmuWorld(2) as world:
        def body(accl, rank):
            send = accl.create_buffer_like(
                np.arange(64, dtype=np.float32) + rank)
            recv = accl.create_buffer(64, np.float32)
            for _ in range(6):
                if rank == 1:
                    _time.sleep(0.002)
                accl.allreduce(send, recv, 64, ReduceFunction.SUM,
                               from_fpga=True, to_fpga=True)

        world.run(body)
        fdump = tmp_path / "flight.json"
        # THIS world's recorders only: dump_all() sweeps every live
        # recorder in the process, and closed worlds from earlier tests
        # survive until a gc cycle collects their reference cycles
        doc = flight.merge_flight_dumps(
            [a.flight_recorder.dump() for a in world.accls])
        fdump.write_text(json.dumps(doc))
    mdump = tmp_path / "metrics.json"
    mdump.write_text(json.dumps(reg.snapshot()))
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/perf_doctor.py"),
         "--ci", "--metrics", str(mdump), "--flight", str(fdump),
         "--baseline",
         os.path.join(REPO, "bench/results/callrate_r12_plan_on.json"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["schema_errors"] == []
    assert "attribution" in report and "sentinel" in report
    assert "engine_telemetry" in report
    d = next(iter(report["attribution"]["collectives"].values()))
    assert d["dominant_straggler"]["rank"] == 1
    assert "straggler" in proc.stdout


def test_perf_doctor_ci_fails_on_malformed_snapshot(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"not": "a snapshot"}))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/perf_doctor.py"),
         "--ci", "--metrics", str(bad)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert "SCHEMA ERROR" in proc.stderr


# ---------------------------------------------------------------------------
# satellite: doctor --live renders unknown engine families gracefully
# ---------------------------------------------------------------------------
def test_doctor_live_renders_unknown_engine_family():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import accl_doctor
    finally:
        sys.path.pop(0)
    reg = obs_metrics.MetricsRegistry()
    reg.inc("engine/tx_msgs", 5)
    reg.set_gauge("engine/rx_occupancy_hwm", 2)
    metrics_text = reg.to_openmetrics() + (
        "# TYPE accl_engine_zz_future_field gauge\n"
        "accl_engine_zz_future_field 42\n# EOF\n")
    scraped = {
        "healthz": {"health": "ok", "accl_health": 0,
                    "watchdog_fires": 0, "watchdog_checks": 1},
        "metrics": metrics_text,
        "flight": {"generated_ns": 0, "nranks": 0, "ranks": [],
                   "analysis": {"desyncs": [], "hangs": [],
                                "stragglers": [], "truncated_comms": [],
                                "torn_dumps": [], "ok": True}},
    }
    out = io.StringIO()
    findings = accl_doctor.report_live(scraped, out)
    text = out.getvalue()
    assert not findings
    assert "engine telemetry" in text
    assert "accl_engine_tx_msgs_total 5" in text
    assert "unrecognized (newer world?)" in text
    # the known family is NOT tagged unrecognized
    known_line = [ln for ln in text.splitlines()
                  if "accl_engine_rx_occupancy_hwm" in ln][0]
    assert "unrecognized" not in known_line


# ---------------------------------------------------------------------------
# r15 wire layer: per-link counters, the world link matrix, chaos
# attribution, and the slowest-link acceptance drills
# ---------------------------------------------------------------------------
def test_link_stats_schema_and_ring_traffic():
    world = _run_world(nranks=4)
    try:
        per_rank = world.link_stats()
        assert set(per_rank) == {0, 1, 2, 3}
        for rank, rows in per_rank.items():
            for row in rows:
                assert set(row) == set(obs_telemetry.LINK_STATS_FIELDS_V2)
                assert row["peer"] != rank  # never the local rank
        m = world.link_matrix()
        assert m["nranks"] == 4
        tx = m["fields"]["tx_bytes"]
        # the ring schedule sends to the right neighbor and receives
        # from the left: every rank's tx row names (r+1) % 4
        for r in range(4):
            assert tx[r][(r + 1) % 4] > 0
            assert m["fields"]["rx_msgs"][r][(r + 3) % 4] > 0
        # link_rows gauge agrees with the decoded row count
        for rank, st in enumerate(world.engine_stats()):
            assert st["link_rows"] == len(per_rank[rank])
    finally:
        world.close()


def _pairwise_world_matrix(chaos: str, nranks: int = 4,
                           count: int = 64, rounds: int = 4) -> dict:
    """Run independent pairwise EAGER transfers under a chaos plan and
    return the link matrix.  Pairwise — NOT a ring schedule: a ring's
    serial relay makes every late hop solicit its upstream, so only
    independent routes can pin WHICH peer a counter belongs to.  Every
    send (all rounds) stages before any recv blocks — the egress
    writer drains them independently of the blocked engine loop — so
    only routes FROM the chaos-targeted rank ever need recovery or run
    slow.  Payloads stay small enough for the eager lane (the
    rendezvous lane's in-process p2p fast path bypasses the wire and
    the chaos funnel entirely) and few enough that every outstanding
    segment fits the rx pool — recovery must never fight head-of-line
    pool exhaustion in this drill."""
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(nranks, chaos=chaos) as world:
        def body(accl, rank):
            src = accl.create_buffer_like(
                np.arange(count, dtype=np.float32) + rank)
            dst = accl.create_buffer(count, np.float32)
            reqs = [accl.send(src, count, q, tag=10 + it,
                              run_async=True)
                    for it in range(rounds)
                    for q in range(nranks) if q != rank]
            for it in range(rounds):
                for q in range(nranks):
                    if q != rank:
                        accl.recv(dst, count, q, tag=10 + it)
            for r_ in reqs:
                r_.wait()

        world.run(body)
        return world.link_matrix()


def test_chaos_attribution_to_true_peer():
    """Under a seeded drop plan targeting ONE peer's egress, >= 95% of
    the world's NACK/retransmit link counters must sit on links naming
    that peer — pinning that per-peer counters are stamped at the TRUE
    peer, not the local rank (a local-rank stamp would spread them
    across the observers' own cells instead)."""
    culprit = 1
    P = 4
    m = _pairwise_world_matrix(f"seed=11,drop_rank={culprit}:0.25",
                               nranks=P)
    nacks = m["fields"]["nacks_tx"]
    retrans = m["fields"]["retrans_sent"]
    nacks_total = sum(v for row in nacks for v in row)
    assert nacks_total > 0, "drop plan produced no NACK traffic"
    # NACKs are sent BY receivers TOWARD the losing sender: column
    # `culprit` holds them; retransmits are served BY the culprit
    # toward its requesters: row `culprit`
    nacks_at_culprit = sum(nacks[r][culprit] for r in range(P))
    retrans_total = sum(v for row in retrans for v in row)
    retrans_by_culprit = sum(retrans[culprit])
    assert nacks_at_culprit / nacks_total >= 0.95, (
        f"NACKs mis-attributed: {nacks}")
    if retrans_total:
        assert retrans_by_culprit / retrans_total >= 0.95, (
            f"retransmits mis-attributed: {retrans}")


def test_slowest_link_names_chaos_slowed_peer_emu():
    """Acceptance drill (emu): a 4-rank world with one chaos-slowed
    peer must produce a link matrix whose slowest link names that
    peer."""
    slow = 2
    m = _pairwise_world_matrix(f"seed=3,slow_rank={slow}:5000")
    link = obs_telemetry.slowest_link(m, "seek_wait_ns")
    assert link is not None
    observer, peer = link
    assert peer == slow, (
        f"slowest link {link} does not name the slowed peer {slow}: "
        f"{m['fields']['seek_wait_ns']}")
    # and the wait concentrates there: the slowed peer's column
    # dominates the world's total blocked time
    wait = m["fields"]["seek_wait_ns"]
    col = sum(wait[r][slow] for r in range(4))
    total = sum(v for row in wait for v in row)
    assert col / total >= 0.5


def test_slowest_link_names_straggler_peer_tpu():
    """Acceptance drill (tpu-interpret rung): the gang scheduler's link
    twin must attribute assembly wait to the straggling peer's links."""
    import time as _time

    from accl_tpu.backends.tpu import TpuWorld

    slow = 2
    with TpuWorld(4) as world:
        def body(accl, rank):
            send = accl.create_buffer_like(
                np.arange(32, dtype=np.float32) + rank)
            recv = accl.create_buffer(32, np.float32)
            for _ in range(4):
                if rank == slow:
                    _time.sleep(0.004)
                accl.allreduce(send, recv, 32, ReduceFunction.SUM,
                               from_fpga=True, to_fpga=True)

        world.run(body)
        m = world.link_matrix()
        # ring byte accounting: every rank's tx row names its right
        # ring neighbor with the busbw-corrected payload
        tx = m["fields"]["tx_bytes"]
        for r in range(4):
            assert tx[r][(r + 1) % 4] > 0
    link = obs_telemetry.slowest_link(m, "seek_wait_ns")
    assert link is not None and link[1] == slow, (
        f"straggler wait mis-attributed: {m['fields']['seek_wait_ns']}")


def test_sampler_publishes_link_families():
    reg = obs_metrics.MetricsRegistry()
    world = _run_world(nranks=2)
    try:
        sampler = obs_telemetry.TelemetrySampler(
            [d.engine_stats for d in world.devices], registry=reg,
            interval_s=30.0,
            link_sources=[(r, d.link_stats)
                          for r, d in enumerate(world.devices)])
        sampler.sample()
        snap = reg.snapshot()
        cells = {k: v for k, v in snap["counters"].items()
                 if k.startswith("link/")}
        assert cells.get("link/tx_bytes", 0) > 0  # world total
        assert any(k.startswith("link/tx_bytes/r") for k in cells)
        # delta discipline: a second sample with no traffic publishes 0
        total_first = snap["counters"]["link/tx_bytes"]
        sampler.sample()
        assert reg.counter("link/tx_bytes") == total_first
        # world total equals the matrix sum
        msum = sum(v for row in
                   sampler.last_link_matrix["fields"]["tx_bytes"]
                   for v in row)
        assert total_first == msum
    finally:
        world.close()


def test_link_matrix_helpers_synthetic():
    rows = {
        0: [{"comm": 0, "peer": 1, "tx_msgs": 2, "tx_bytes": 100,
             "rx_msgs": 0, "rx_bytes": 0, "retrans_sent": 0,
             "nacks_tx": 0, "nacks_rx": 0, "fenced_drops": 0,
             "seeks": 1, "seek_wait_ns": 500},
            {"comm": 7, "peer": 1, "tx_msgs": 9, "tx_bytes": 999,
             "rx_msgs": 0, "rx_bytes": 0, "retrans_sent": 0,
             "nacks_tx": 0, "nacks_rx": 0, "fenced_drops": 0,
             "seeks": 0, "seek_wait_ns": 0}],
        1: [{"comm": 0, "peer": 0, "tx_msgs": 1, "tx_bytes": 40,
             "rx_msgs": 2, "rx_bytes": 100, "retrans_sent": 3,
             "nacks_tx": 0, "nacks_rx": 0, "fenced_drops": 0,
             "seeks": 2, "seek_wait_ns": 9000}],
    }
    m = obs_telemetry.link_matrix(rows, nranks=2)
    assert m["fields"]["tx_bytes"][0][1] == 100  # comm 7 filtered out
    assert m["fields"]["tx_bytes"][1][0] == 40
    assert obs_telemetry.slowest_link(m, "seek_wait_ns") == (1, 0)
    assert obs_telemetry.slowest_link(m, "fenced_drops") is None
    # comm=None folds every comm
    m_all = obs_telemetry.link_matrix(rows, nranks=2, comm=None)
    assert m_all["fields"]["tx_bytes"][0][1] == 1099
    # imbalance over nonzero cells
    assert obs_telemetry.link_imbalance(m, "tx_bytes") == \
        pytest.approx(100 / 70)


def test_perf_doctor_link_matrix_section(tmp_path):
    """The --ci report grows a schema-validated link_matrix section
    whenever the snapshot carries link/* families."""
    reg = obs_metrics.MetricsRegistry()
    world = _run_world(nranks=2)
    try:
        sampler = obs_telemetry.TelemetrySampler(
            [d.engine_stats for d in world.devices], registry=reg,
            interval_s=30.0,
            link_sources=[(r, d.link_stats)
                          for r, d in enumerate(world.devices)])
        sampler.sample()
    finally:
        world.close()
    mdump = tmp_path / "metrics.json"
    mdump.write_text(json.dumps(reg.snapshot()))
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/perf_doctor.py"),
         "--ci", "--metrics", str(mdump), "--out", str(out)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["schema_errors"] == []
    lm = report["link_matrix"]
    P = lm["matrix"]["nranks"]
    assert P == 2
    for f in obs_telemetry.LINK_COUNTER_FIELDS:
        assert len(lm["matrix"]["fields"][f]) == P
    assert "tx_imbalance_ratio" in lm["findings"]
    assert "link matrix" in proc.stdout


def test_tpu_plan_replay_traffic_lands_in_link_matrix():
    """The plan-replay lane is the whole steady-state traffic of a
    captured plan — replayed collectives must account into the link
    twin exactly like eager gang dispatches (a matrix that goes dark
    when plans kick in would mis-model precisely the hot traffic)."""
    from accl_tpu.backends.tpu import TpuWorld

    with TpuWorld(4) as world:
        def body(accl, rank):
            send = accl.create_buffer_like(
                np.arange(32, dtype=np.float32) + rank)
            recv = accl.create_buffer(32, np.float32)
            plan = accl.capture_plan(
                lambda a: a.allreduce(send, recv, 32, ReduceFunction.SUM,
                                      from_fpga=True, to_fpga=True))
            for _ in range(3):
                plan.replay()

        base = world.link_matrix()["fields"]["tx_bytes"]
        world.run(body)
        m = world.link_matrix()
    tx = m["fields"]["tx_bytes"]
    # capture (1 eager) + 3 replays = 4 instances; allreduce of 128 B
    # at busbw 2*(P-1)/P -> 192 B per right-neighbor link each
    for r in range(4):
        assert tx[r][(r + 1) % 4] - base[r][(r + 1) % 4] == 4 * 192, tx


def test_sampler_dead_rank_keeps_world_shape():
    """A source that dies mid-poll must not shrink the matrix: live
    ranks' cells toward the dead rank keep publishing."""
    reg = obs_metrics.MetricsRegistry()

    def dead():
        raise RuntimeError("rank 3 closed mid-poll")

    rows0 = [{"comm": 0, "peer": 3, "tx_msgs": 1, "tx_bytes": 64,
              "rx_msgs": 0, "rx_bytes": 0, "retrans_sent": 0,
              "nacks_tx": 0, "nacks_rx": 0, "fenced_drops": 0,
              "seeks": 0, "seek_wait_ns": 0}]
    sampler = obs_telemetry.TelemetrySampler(
        [], registry=reg,
        link_sources=[(0, lambda: rows0), (1, lambda: []),
                      (2, lambda: []), (3, dead)])
    sampler.sample()
    m = sampler.last_link_matrix
    assert m["nranks"] == 4  # NOT shrunk to the answering ranks
    assert m["fields"]["tx_bytes"][0][3] == 64
    assert reg.counter("link/tx_bytes/r0->r3") == 64
