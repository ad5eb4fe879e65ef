#!/usr/bin/env python3
"""Deterministic schedule-exploration model checker for the native engine.

Orchestrates the ``ACCL_DETSCHED`` harness (``native/test/test_detsched``,
scheduler in ``native/src/detsched.hpp``): builds the instrumented
binaries, explores drill interleavings (DPOR-pruned, bounded-preemption
DFS over schedule prefixes, with first-class timeout injection and
rx-pool pressure modeling), and — on a finding — writes a replayable
failing-schedule artifact (drill + minimal hex schedule prefix + seed +
injection bound, mirroring fuzz_wire.py's failing-frame artifact).
Reproduce with::

    python scripts/model_check.py --replay model_check_failure.json

Modes
-----
``--drill NAME [--runs N]``
    explore one drill (see ``--list``) on the fixed build.
``--ci``
    the CI gate: >= ``--runs`` (default 3000) schedules on EACH engine
    drill with zero findings, PLUS the sensitivity proofs — the fault
    build (``ACCL_FAULT_DETACH_RACE`` + ``ACCL_FAULT_SUBCOMM_WEDGE``,
    reverting the r13 InprocHub::detach drain AND the staged-segment
    rescue) must REDISCOVER both seeded failures, and the seeded
    ``liveness_leak`` drill must fire the stuck-progress invariant on
    the fixed build.  A checker that cannot re-find a known race
    proves nothing; this run proves sensitivity on every CI
    invocation.  Ends with a per-drill schedule/time table.
    ``--deep`` lifts the per-drill run caps for the nightly lane.
``--replay ARTIFACT``
    re-run one recorded schedule; exits 0 iff the artifact's verdict
    (failing schedule) reproduces.
``--guide ARTIFACT`` (with ``--drill``)
    trace-guided exploration: replay the artifact's recorded trace as a
    verbatim prefix and explore only the suffix decision space.

Budgets: ``ACCL_DETSCHED_BUDGET`` (seconds) overrides the default
per-drill wall budget — the nightly deep-exploration lane sets it high
and raises ``--runs``; the in-PR gate keeps the fast defaults.

Exit codes: 0 clean/as-expected, 1 findings (or sensitivity loss),
2 usage/build errors (unknown drill names list the registry and exit 2).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
BIN = os.path.join(NATIVE, "test", "test_detsched")
BIN_FAULT = os.path.join(NATIVE, "test", "test_detsched_fault")

ENGINE_DRILLS = (
    "replay_vs_invalidate",
    "abort_vs_traffic",
    "join_vs_traffic",
    "shutdown_vs_waiters",
    # r17: the ROADMAP item 2 KNOWN-ISSUE shape (concurrent sub-comm
    # allgathers over one rx pool) at its 4-rank exhaustive scale; the
    # full 8-rank repro is `--drill subcomm_allgather8` with an
    # explicit budget (heavier per schedule)
    "subcomm_allgather",
    "subcomm_allgather8",
    # alltoallv's descriptor shape: count operand and count result
    "alltoallv_counts",
)
SENSITIVITY_DRILL = "detach_race"
WEDGE_DRILL = "subcomm_allgather8"
# the seeded liveness leak: a live token never handed back — the
# stuck-progress invariant must fire on the FIXED build (the checker
# machinery itself is under test, not an engine bug)
LIVENESS_DRILL = "liveness_leak"

# Timeout-injection budget per drill.  The sub-comm drills NEED
# injections (the wedge requires a budget slice expiring while the rx
# pool is pinned); the abort/shutdown drills assert "no call fails",
# which a legitimately injected RECEIVE_TIMEOUT would false-positive,
# so they explore the pure happens-before space (ibound 0 is also
# bit-identical to the pre-injection explorer: same schedules, same
# trace hashes).
DRILL_IBOUND = {
    "subcomm_allgather": 1,
    "subcomm_allgather8": 1,
}

# Per-drill CI run caps: the 8-rank drill costs ~10x a 4-rank schedule,
# and its wedge lives shallow (fault build finds it in <100 schedules),
# so a bounded sweep keeps the gate fast without hiding coverage — the
# nightly deep lane (--deep + ACCL_DETSCHED_BUDGET) runs it uncapped.
CI_RUN_CAPS = {
    "subcomm_allgather8": 400,
}


def build(verbose: bool) -> None:
    cmd = ["make", "-C", NATIVE, "detsched"]
    proc = subprocess.run(cmd, capture_output=not verbose, text=True)
    if proc.returncode != 0:
        if proc.stdout:
            sys.stderr.write(proc.stdout)
        if proc.stderr:
            sys.stderr.write(proc.stderr)
        raise SystemExit(2)


def known_drills() -> list[str]:
    try:
        proc = subprocess.run(
            [BIN, "--list"], capture_output=True, text=True, timeout=30
        )
    except (OSError, subprocess.TimeoutExpired):
        return []
    return [ln.strip() for ln in proc.stdout.splitlines() if ln.strip()]


def reject_unknown_drill(name: str) -> None:
    """Unknown drill names are usage errors: list the registry, exit 2."""
    drills = known_drills()
    if drills and name not in drills:
        print(f"[model_check] unknown drill {name!r}; available drills:")
        for d in drills:
            print(f"  {d}")
        raise SystemExit(2)


def run_harness(binary: str, args: list[str], timeout_s: float) -> dict:
    try:
        proc = subprocess.run(
            [binary, *args], capture_output=True, text=True, timeout=timeout_s
        )
    except subprocess.TimeoutExpired as exc:
        # a wedged harness is itself a finding, not an orchestrator
        # crash: report it like a failed run so artifacts still land
        return {
            "findings": 1,
            "runs": 0,
            "what": f"harness timeout after {timeout_s:.0f}s "
                    f"(possible scheduler hang): {exc}",
            "exit_code": -1,
        }
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "{}"
    try:
        out = json.loads(line)
    except json.JSONDecodeError:
        out = {"parse_error": line}
    out["exit_code"] = proc.returncode
    if proc.stderr.strip():
        out["stderr_tail"] = proc.stderr.strip().splitlines()[-5:]
    return out


def write_artifact(path: str, drill: str, result: dict, fault_build: bool) -> None:
    art = {
        "drill": drill,
        "schedule_hex": result.get("prefix_hex", ""),
        "full_trace_hex": result.get("trace_hex", ""),
        "seed": result.get("seed", 1),
        "what": result.get("what", ""),
        "fail_step": result.get("fail_step", 0),
        "pbound": result.get("pbound", 3),
        # replay MUST present the same injection bound: choices are
        # reduced modulo (enabled + injectable), so a different ibound
        # misaligns every decision after the first armed window
        "ibound": result.get("ibound", 0),
        "max_steps": result.get("max_steps", 200000),
        "fault_build": fault_build,
        "replay": (
            f"python scripts/model_check.py --replay {os.path.basename(path)}"
        ),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(art, f, indent=2)
    print(f"[model_check] failing-schedule artifact -> {path}")


def explore_drill(
    drill: str,
    runs: int,
    seed: int,
    pbound: int,
    max_steps: int,
    budget_s: float,
    artifact: str,
    fault_build: bool = False,
    expect_finding: bool = False,
    ibound: int | None = None,
    guide_hex: str = "",
) -> tuple[bool, dict]:
    """Returns (ok, result); result carries ``elapsed_s``."""
    binary = BIN_FAULT if fault_build else BIN
    if ibound is None:
        ibound = DRILL_IBOUND.get(drill, 0)
    args = [
        "--drill", drill,
        "--explore", str(runs),
        "--seed", str(seed),
        "--pbound", str(pbound),
        "--ibound", str(ibound),
        "--max-steps", str(max_steps),
        "--budget-s", str(budget_s),
    ]
    if guide_hex:
        args += ["--explore-from", guide_hex]
    if expect_finding:
        args.append("--expect-finding")
    t0 = time.monotonic()
    res = run_harness(binary, args, timeout_s=budget_s + 120)
    res["elapsed_s"] = time.monotonic() - t0
    findings = int(res.get("findings", 0))
    label = "fault" if fault_build else "fixed"
    print(
        f"[model_check] {drill} ({label}, ibound={ibound}): "
        f"{res.get('runs', '?')} schedules, "
        f"{res.get('unique_traces', '?')} unique, "
        f"{res.get('injected_runs', 0)} injected, {findings} finding(s) "
        f"[{res['elapsed_s']:.1f}s]"
    )
    if findings and not expect_finding:
        print(f"[model_check]   FINDING: {res.get('what', '')!r} "
              f"(step {res.get('fail_step')})")
        write_artifact(artifact, drill, res, fault_build)
        return False, res
    if expect_finding and not findings:
        print(
            f"[model_check]   SENSITIVITY LOSS: the {label} build's seeded "
            f"failure was NOT rediscovered"
        )
        return False, res
    if expect_finding and findings:
        prefix = res.get("prefix_hex", "")
        shown = prefix if len(prefix) <= 64 else prefix[:64] + "..."
        print(f"[model_check]   rediscovered: {res.get('what', '')!r} "
              f"(minimal prefix {len(prefix) // 2}B {shown!r})")
        # expected findings still land an artifact: the nightly deep
        # lane uploads the minimal replayable schedule as its proof
        write_artifact(artifact, drill, res, fault_build)
    return True, res


def replay(path: str) -> int:
    with open(path, encoding="utf-8") as f:
        art = json.load(f)
    reject_unknown_drill(art["drill"])
    binary = BIN_FAULT if art.get("fault_build") else BIN
    args = [
        "--drill", art["drill"],
        "--schedule", art["schedule_hex"],
        "--seed", str(art.get("seed", 1)),
        "--pbound", str(art.get("pbound", 3)),
        "--ibound", str(art.get("ibound", 0)),
        "--max-steps", str(art.get("max_steps", 200000)),
        "--expect-finding",
    ]
    res = run_harness(binary, args, timeout_s=120)
    ok = res.get("exit_code") == 0 and res.get("failed") is True
    sched = art["schedule_hex"]
    shown = sched if len(sched) <= 64 else sched[:64] + "..."
    print(
        f"[model_check] replay {art['drill']} schedule {shown!r} "
        f"(ibound={art.get('ibound', 0)}): "
        + (f"reproduced ({res.get('what', '')!r})" if ok else "did NOT reproduce")
    )
    return 0 if ok else 1


def print_ci_table(rows: list[tuple[str, str, dict]]) -> None:
    """Per-drill schedule/time table closing every --ci sweep."""
    print("[model_check] --- CI sweep table ---")
    header = (
        f"{'drill':<24} {'build':<6} {'schedules':>9} {'unique':>7} "
        f"{'injected':>8} {'findings':>8} {'time':>7}"
    )
    print(f"[model_check] {header}")
    for drill, label, res in rows:
        print(
            "[model_check] "
            f"{drill:<24} {label:<6} {res.get('runs', 0):>9} "
            f"{res.get('unique_traces', 0):>7} "
            f"{res.get('injected_runs', 0):>8} "
            f"{res.get('findings', 0):>8} "
            f"{res.get('elapsed_s', 0.0):>6.1f}s"
        )


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--drill", help="explore one drill on the fixed build")
    ap.add_argument("--list", action="store_true", help="list drills")
    ap.add_argument("--ci", action="store_true",
                    help="CI gate: engine drills + sensitivity proofs")
    ap.add_argument("--runs", type=int, default=3000,
                    help="schedules per drill (default 3000)")
    ap.add_argument("--min-interleavings", type=int, default=10000,
                    help="--ci fails below this explored total (the "
                         "acceptance floor; no silent coverage caps)")
    ap.add_argument("--deep", action="store_true",
                    help="nightly lane: lift the per-drill CI run caps — "
                         "the wall budget (ACCL_DETSCHED_BUDGET / "
                         "--budget-s) becomes the only bound")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--pbound", type=int, default=3,
                    help="preemption bound per schedule")
    ap.add_argument("--ibound", type=int, default=None,
                    help="timeout injections per run (default: per-drill "
                         "policy — sub-comm drills 1, others 0)")
    ap.add_argument("--max-steps", type=int, default=200000,
                    help="scheduling-step budget per run (livelock guard)")
    ap.add_argument("--budget-s", type=float,
                    default=float(os.environ.get("ACCL_DETSCHED_BUDGET", 240)),
                    help="wall-clock budget per drill sweep (default 240, "
                         "or the ACCL_DETSCHED_BUDGET env — the nightly "
                         "deep lane's knob)")
    ap.add_argument("--artifact", default="model_check_failure.json",
                    help="failing-schedule artifact path")
    ap.add_argument("--replay", default="",
                    help="replay a failure artifact instead of exploring")
    ap.add_argument("--guide", default="",
                    help="with --drill: artifact whose recorded trace seeds "
                         "the DFS (replay the prefix, explore the suffix)")
    ap.add_argument("--fault-build", action="store_true",
                    help="run --drill against the seeded-fault build")
    ap.add_argument("--expect-finding", action="store_true",
                    help="with --drill: exit 0 iff a finding IS discovered")
    ap.add_argument("--no-build", action="store_true",
                    help="assume the harness binaries are current")
    ap.add_argument("--verbose", action="store_true")
    opts = ap.parse_args()

    if not opts.no_build:
        build(opts.verbose)

    if opts.list:
        subprocess.run([BIN, "--list"])
        return 0

    if opts.replay:
        return replay(opts.replay)

    if opts.drill:
        reject_unknown_drill(opts.drill)
        guide_hex = ""
        if opts.guide:
            with open(opts.guide, encoding="utf-8") as f:
                art = json.load(f)
            guide_hex = art.get("full_trace_hex") or art.get("schedule_hex", "")
        ok, _ = explore_drill(
            opts.drill, opts.runs, opts.seed, opts.pbound, opts.max_steps,
            opts.budget_s, opts.artifact, fault_build=opts.fault_build,
            expect_finding=opts.expect_finding, ibound=opts.ibound,
            guide_hex=guide_hex,
        )
        return 0 if ok else 1

    if opts.ci:
        total = 0
        all_ok = True
        rows: list[tuple[str, str, dict]] = []
        for drill in ENGINE_DRILLS:
            runs = (opts.runs if opts.deep
                    else min(opts.runs, CI_RUN_CAPS.get(drill, opts.runs)))
            ok, res = explore_drill(
                drill, runs, opts.seed, opts.pbound, opts.max_steps,
                opts.budget_s, opts.artifact, ibound=opts.ibound,
            )
            total += int(res.get("runs", 0))
            rows.append((drill, "fixed", res))
            all_ok = all_ok and ok
            if not ok:
                break
        if all_ok:
            # sensitivity, part 1: the seeded detach race must be
            # rediscovered by the fault build and hold clean on the fixed
            ok, res = explore_drill(
                SENSITIVITY_DRILL, max(opts.runs, 500), opts.seed,
                opts.pbound, opts.max_steps, opts.budget_s, opts.artifact,
                fault_build=True, expect_finding=True,
            )
            rows.append((SENSITIVITY_DRILL, "fault", res))
            all_ok = all_ok and ok
            ok, res = explore_drill(
                SENSITIVITY_DRILL, max(opts.runs, 500), opts.seed,
                opts.pbound, opts.max_steps, opts.budget_s, opts.artifact,
            )
            total += int(res.get("runs", 0))
            rows.append((SENSITIVITY_DRILL, "fixed", res))
            all_ok = all_ok and ok
        if all_ok:
            # sensitivity, part 2: the liveness invariant itself must be
            # able to fire — the seeded leak drill (a live token never
            # handed back) must end with the stuck-progress finding on
            # the FIXED build.  Cheap: the leak is schedule-independent,
            # so stop_on_first lands it on run one.
            ok, res = explore_drill(
                LIVENESS_DRILL, 50, opts.seed, opts.pbound, opts.max_steps,
                opts.budget_s, opts.artifact, expect_finding=True,
            )
            rows.append((LIVENESS_DRILL, "fixed", res))
            all_ok = all_ok and ok
        if all_ok:
            # sensitivity, part 3 (LAST, so its minimal schedule owns the
            # artifact path the deep lane uploads): the 8-rank sub-comm
            # wedge (the staged-segment rescue revert) must be
            # rediscovered under timeout injection — the timeout/resource
            # machinery itself is under test here, not just the hub drain
            ok, res = explore_drill(
                WEDGE_DRILL,
                opts.runs if opts.deep else CI_RUN_CAPS.get(WEDGE_DRILL, 400),
                opts.seed, opts.pbound, opts.max_steps, opts.budget_s,
                opts.artifact, fault_build=True, expect_finding=True,
            )
            rows.append((WEDGE_DRILL, "fault", res))
            all_ok = all_ok and ok
        if all_ok and total < opts.min_interleavings:
            # the acceptance floor is a guarantee, not a report: a
            # budget-truncated sweep must fail loudly, never pass green
            print(
                f"[model_check] CI sweep EXPLORED TOO LITTLE: {total} < "
                f"{opts.min_interleavings} interleavings (budget/runs too "
                f"low for this box)"
            )
            all_ok = False
        print_ci_table(rows)
        print(
            f"[model_check] CI sweep: {total} interleavings across the "
            f"engine drills, "
            + ("sensitivity proven, zero findings" if all_ok else "FAILED")
        )
        return 0 if all_ok else 1

    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
