"""Deterministic schedule-exploration model checker (r14).

Pins the ``scripts/model_check.py`` / ``native/test/test_detsched``
contract end to end:

* a clean drill explores with ZERO findings and unique traces == runs
  (the DFS really visits distinct interleavings, not one schedule N
  times);
* the sensitivity proof — the ``ACCL_FAULT_DETACH_RACE`` build, which
  reverts the r13 ``InprocHub::detach`` drain, must REDISCOVER the
  race and the minimal failing schedule must replay bit-for-bit from
  the artifact alone (the same hex+seed contract as fuzz_wire.py);
* the artifact round-trip: explore -> artifact -> --replay reproduces
  the identical finding, and the same schedule on the FIXED build runs
  clean;
* r19 — the timeout- and resource-aware upgrade: injection branching
  is deterministic and off-by-default-identical (``--ibound 0``),
  rx-pool occupancy is explored state (tightening the pool surfaces
  more pressure decision points), trace-guided exploration refinds a
  captured failure on schedule one, and the liveness invariant (every
  submitted call finalizes) fires on a seeded leak and stays quiet on
  clean engine drills.

Builds are driven through the native Makefile once per session; the
whole module self-skips when no C++ toolchain is available.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
BIN = os.path.join(NATIVE, "test", "test_detsched")
BIN_FAULT = os.path.join(NATIVE, "test", "test_detsched_fault")
MODEL_CHECK = os.path.join(REPO, "scripts", "model_check.py")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None and shutil.which("c++") is None,
    reason="no C++ toolchain for the detsched harness",
)


@pytest.fixture(scope="module")
def harness():
    proc = subprocess.run(
        ["make", "-C", NATIVE, "detsched"], capture_output=True, text=True,
        timeout=600,
    )
    if proc.returncode != 0:
        pytest.skip(f"detsched build failed: {proc.stderr[-500:]}")
    return BIN


def run_json(binary, *args, timeout=180, env=None):
    proc = subprocess.run(
        [binary, *args], capture_output=True, text=True, timeout=timeout,
        env=env,
    )
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    out["exit_code"] = proc.returncode
    return out


def test_clean_drill_zero_findings(harness):
    # a correct engine explored across hundreds of schedules: no
    # finding, and every run is a DISTINCT interleaving (the explorer
    # is exploring, not re-running one schedule)
    res = run_json(
        harness, "--drill", "abort_vs_traffic", "--explore", "300",
        "--seed", "3",
    )
    assert res["exit_code"] == 0
    assert res["findings"] == 0
    assert res["runs"] >= 300
    assert res["unique_traces"] == res["runs"]


def test_alltoallv_drill_zero_findings(harness):
    # alltoallv's descriptor shape (count operand in OP1, count result
    # in the root/function words) under explored schedules: counts and
    # rows land bit for bit on every schedule, every run distinct
    res = run_json(
        harness, "--drill", "alltoallv_counts", "--explore", "300",
        "--seed", "5",
    )
    assert res["exit_code"] == 0
    assert res["findings"] == 0
    assert res["runs"] >= 300
    assert res["unique_traces"] == res["runs"]


def test_fault_build_rediscovers_detach_race(harness):
    # sensitivity: the seeded r13 race must be found, with a non-empty
    # minimal failing prefix naming the invariant
    res = run_json(
        BIN_FAULT, "--drill", "detach_race", "--explore", "500",
        "--seed", "3", "--expect-finding",
    )
    assert res["exit_code"] == 0
    assert res["findings"] == 1
    assert "detached slot" in res["what"]
    assert res["prefix_hex"] != ""
    # minimality: the minimized prefix is no longer than the full trace
    assert len(res["prefix_hex"]) <= len(res["trace_hex"])


def test_minimal_schedule_replays_bit_for_bit(harness):
    # artifact round-trip: the minimal failing schedule reproduces the
    # identical finding on the fault build and runs CLEAN on the fixed
    # build (the fix, not schedule luck, is what holds the invariant)
    found = run_json(
        BIN_FAULT, "--drill", "detach_race", "--explore", "500",
        "--seed", "3", "--expect-finding",
    )
    prefix = found["prefix_hex"]
    replay = run_json(
        BIN_FAULT, "--drill", "detach_race", "--schedule", prefix,
        "--seed", "3", "--expect-finding",
    )
    assert replay["exit_code"] == 0
    assert replay["failed"] is True
    assert replay["what"] == found["what"]
    fixed = run_json(
        harness, "--drill", "detach_race", "--schedule", prefix,
        "--seed", "3",
    )
    assert fixed["exit_code"] == 0
    assert fixed["failed"] is False


def test_exploration_is_deterministic(harness):
    # same (drill, seed, budget) -> identical sweep, run for run
    a = run_json(harness, "--drill", "shutdown_vs_waiters", "--explore",
                 "200", "--seed", "11")
    b = run_json(harness, "--drill", "shutdown_vs_waiters", "--explore",
                 "200", "--seed", "11")
    assert (a["runs"], a["unique_traces"], a["findings"]) == (
        b["runs"], b["unique_traces"], b["findings"])


def test_model_check_cli_artifact_roundtrip(harness, tmp_path):
    # the orchestrator end to end: a fault-build exploration through
    # scripts/model_check.py writes an artifact... by running the drill
    # WITHOUT --expect-finding so the finding is treated as a failure
    artifact = tmp_path / "model_check_failure.json"
    proc = subprocess.run(
        [sys.executable, MODEL_CHECK, "--drill", "detach_race",
         "--fault-build", "--runs", "500", "--no-build",
         "--artifact", str(artifact)],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert artifact.exists()
    art = json.loads(artifact.read_text())
    assert art["drill"] == "detach_race"
    assert art["schedule_hex"]
    assert art["fault_build"] is True
    replay = subprocess.run(
        [sys.executable, MODEL_CHECK, "--replay", str(artifact),
         "--no-build"],
        capture_output=True, text=True, timeout=300,
    )
    assert replay.returncode == 0, replay.stdout + replay.stderr
    assert "reproduced" in replay.stdout


# ---- r19: timeout- and resource-aware exploration ------------------------


def test_timeout_branch_determinism(harness):
    # same (drill, seed, ibound) -> identical sweep including the
    # injection schedule; and ibound=0 keeps the legacy explorer
    # bit-identical (no injections ever, so pre-r19 artifacts replay)
    a = run_json(harness, "--drill", "subcomm_allgather", "--explore",
                 "60", "--seed", "7", "--ibound", "1")
    b = run_json(harness, "--drill", "subcomm_allgather", "--explore",
                 "60", "--seed", "7", "--ibound", "1")
    keys = ("runs", "unique_traces", "findings", "injected_runs",
            "pressure_events")
    assert [a[k] for k in keys] == [b[k] for k in keys]
    assert a["findings"] == 0
    assert a["injected_runs"] > 0  # the injector really branched
    legacy = run_json(harness, "--drill", "subcomm_allgather", "--explore",
                      "60", "--seed", "7", "--ibound", "0")
    assert legacy["findings"] == 0
    assert legacy["injected_runs"] == 0


def test_resource_bound_exploration(harness):
    # rx-pool occupancy is modeled state: halving the pool must surface
    # MORE exhaustion decision points (pressure events arm the timeout
    # injector exactly where pinning can starve a match), and the fixed
    # engine must stay clean under the extra injected expiries
    wide = run_json(harness, "--drill", "subcomm_allgather", "--explore",
                    "40", "--seed", "3", "--ibound", "1")
    tight_env = dict(os.environ, ACCL_DETSCHED_RX_BUFS="2")
    tight = run_json(harness, "--drill", "subcomm_allgather", "--explore",
                     "40", "--seed", "3", "--ibound", "1", env=tight_env)
    assert wide["pressure_events"] > 0
    assert tight["pressure_events"] > wide["pressure_events"]
    assert tight["findings"] == 0
    assert tight["exit_code"] == 0


def test_trace_guided_exploration_roundtrip(harness):
    # seed the DFS from a captured failing trace: the fault build
    # refinds the race on schedule ONE instead of searching; the fixed
    # build explores the same guided prefix clean (the fix, not
    # schedule luck, holds the invariant)
    found = run_json(BIN_FAULT, "--drill", "detach_race", "--explore",
                     "500", "--seed", "3", "--expect-finding")
    trace = found["trace_hex"]
    assert trace
    guided = run_json(BIN_FAULT, "--drill", "detach_race", "--explore",
                      "50", "--seed", "3", "--explore-from", trace,
                      "--expect-finding")
    assert guided["runs"] == 1
    assert guided["findings"] == 1
    assert guided["what"] == found["what"]
    fixed = run_json(harness, "--drill", "detach_race", "--explore", "50",
                     "--seed", "3", "--explore-from", trace)
    assert fixed["findings"] == 0
    assert fixed["exit_code"] == 0


def test_liveness_positive_and_negative(harness):
    # positive: the seeded leak drill (a live token never handed back)
    # ends with the stuck-progress finding on its very first schedule;
    # negative: a clean engine drill with blocked-then-finalized calls
    # returns every token through the finalize paths
    leak = run_json(harness, "--drill", "liveness_leak", "--explore",
                    "50", "--seed", "3", "--expect-finding")
    assert leak["exit_code"] == 0
    assert leak["findings"] >= 1
    assert "stuck-progress" in leak["what"]
    clean = run_json(harness, "--drill", "shutdown_vs_waiters",
                     "--explore", "150", "--seed", "3")
    assert clean["findings"] == 0
    assert clean["exit_code"] == 0


def test_unknown_drill_lists_registry(harness):
    # the harness refuses with exit 2 and points at --list; the
    # orchestrator does the listing itself so a typoed --drill/--replay
    # name shows the caller what IS runnable
    proc = subprocess.run(
        [harness, "--drill", "no_such_drill", "--explore", "1"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2
    assert "unknown drill" in proc.stderr
    mc = subprocess.run(
        [sys.executable, MODEL_CHECK, "--drill", "no_such_drill",
         "--no-build"],
        capture_output=True, text=True, timeout=120,
    )
    assert mc.returncode == 2
    assert "subcomm_allgather8" in mc.stdout + mc.stderr
