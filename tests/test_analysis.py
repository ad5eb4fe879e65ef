"""Collective sanitizer tests: static checkers, record/shadow capture,
the accl_lint CLI, and the ACCL_SANITIZE runtime lane.

Layout mirrors the subsystem: LintWorld/record-mode programs feed the
static checker suite (each seeded bug class + a clean program must lint
exactly as specified), the CLI round-trips the committed fixtures, and
the runtime sanitizer turns would-hang emu programs into immediate
ACCLErrors.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from accl_tpu import ReduceFunction
from accl_tpu.analysis import LintWorld, check_programs
from accl_tpu.analysis import sanitizer
from accl_tpu.analysis.findings import ERROR, WARNING, has_errors
from accl_tpu.constants import ACCLError
from accl_tpu.observability.flight import first_divergence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")
LINT_CLI = os.path.join(REPO, "scripts", "accl_lint.py")


def codes(findings):
    return sorted({f.code for f in findings})


def lint(fn, nranks=2):
    world = LintWorld(nranks)
    world.run(fn)
    return world.check()


# ---------------------------------------------------------------------------
# static checkers: each seeded bug class
# ---------------------------------------------------------------------------
def test_clean_program_zero_findings():
    def fn(a, r):
        s = a.create_buffer(512, np.float32)
        d = a.create_buffer(512, np.float32)
        g = a.create_buffer(512 * a.size, np.float32)
        a.allreduce(s, d, 512, ReduceFunction.SUM)
        a.allgather(s, g, 512)
        a.bcast(s, 512, root=1)
        a.barrier()
        req = a.send(s, 512, dst=(r + 1) % a.size, tag=5, run_async=True)
        a.recv(d, 512, src=(r - 1) % a.size, tag=5)
        assert req.wait()
        req.check()
        g.free()  # free after last use: not a hazard

    assert lint(fn, nranks=4) == []


def test_order_desync_first_divergent_index():
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        a.allreduce(s, d, 64, ReduceFunction.SUM)  # agreeing prefix
        if r == 0:
            a.allreduce(s, d, 64, ReduceFunction.SUM)
        else:
            a.bcast(s, 64, root=0)

    findings = lint(fn)
    assert [f.code for f in findings] == ["desync-order"]
    f = findings[0]
    assert f.severity == ERROR and f.index == 1 and f.comm == 0
    assert "allreduce" in f.message and "bcast" in f.message


def test_param_mismatch_count_dtype():
    def fn(a, r):
        s = a.create_buffer(128, np.float32)
        d = a.create_buffer(128, np.float32)
        a.allreduce(s, d, 128 if r == 0 else 96, ReduceFunction.SUM)

    findings = lint(fn)
    assert [f.code for f in findings] == ["param-mismatch"]
    assert "count=128" in findings[0].message
    assert "count=96" in findings[0].message

    def fn2(a, r):
        dt = np.float32 if r == 0 else np.float64
        s = a.create_buffer(64, dt)
        d = a.create_buffer(64, dt)
        a.allreduce(s, d, 64, ReduceFunction.SUM)

    findings = lint(fn2)
    assert [f.code for f in findings] == ["param-mismatch"]
    assert "float32" in findings[0].message
    assert "float64" in findings[0].message


def test_root_mismatch_is_param_mismatch():
    def fn(a, r):
        s = a.create_buffer(32, np.float32)
        a.bcast(s, 32, root=r)  # every rank names itself root

    assert codes(lint(fn)) == ["param-mismatch"]


def test_missing_call_imbalance():
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        a.allreduce(s, d, 64, ReduceFunction.SUM)
        if r == 0:  # rank 1 returns early: its peers hang
            a.allreduce(s, d, 64, ReduceFunction.SUM)

    found = codes(lint(fn))
    assert "desync-missing-call" in found
    assert "gang-missing-member" in found  # the sim sees the hang too


def test_deadlock_cycle_head_to_head_sends():
    def fn(a, r):
        peer = 1 - r
        s = a.create_buffer(4096, np.float32)  # rendezvous-sized
        d = a.create_buffer(4096, np.float32)
        a.send(s, 4096, dst=peer, tag=0)
        a.recv(d, 4096, src=peer, tag=0)

    findings = lint(fn)
    assert [f.code for f in findings] == ["deadlock-cycle"]
    assert sorted(findings[0].ranks) == [0, 1]
    assert "send" in findings[0].message


def test_eager_send_before_recv_is_not_deadlock():
    # same head-to-head shape but the payload fits the 1 KB eager
    # threshold: the rx pool buffers it, both recvs drain — clean
    def fn(a, r):
        peer = 1 - r
        s = a.create_buffer(64, np.float32)  # 256 B: eager
        d = a.create_buffer(64, np.float32)
        a.send(s, 64, dst=peer, tag=0)
        a.recv(d, 64, src=peer, tag=0)

    assert lint(fn) == []


def test_cross_gang_p2p_deadlock():
    # rank 1 waits for a send rank 0 only issues AFTER its allreduce;
    # rank 0's allreduce waits for rank 1 — a mixed-edge cycle
    def fn(a, r):
        s = a.create_buffer(4096, np.float32)
        d = a.create_buffer(4096, np.float32)
        if r == 0:
            a.allreduce(s, d, 4096, ReduceFunction.SUM)
            a.send(s, 4096, dst=1, tag=1)
        else:
            a.recv(d, 4096, src=0, tag=1)
            a.allreduce(s, d, 4096, ReduceFunction.SUM)

    assert "deadlock-cycle" in codes(lint(fn))


def test_unmatched_send_and_recv():
    def fn(a, r):
        s = a.create_buffer(4096, np.float32)
        if r == 0:
            a.send(s, 4096, dst=1, tag=9)  # nobody ever receives

    findings = lint(fn)
    assert codes(findings) == ["p2p-unmatched"]

    def fn2(a, r):
        d = a.create_buffer(64, np.float32)
        if r == 1:
            a.recv(d, 64, src=0, tag=2)  # nobody ever sends

    findings = lint(fn2)
    assert codes(findings) == ["p2p-unmatched"]
    assert "no matching send" in findings[0].message


def test_root_and_peer_validity():
    def fn(a, r):
        s = a.create_buffer(16, np.float32)
        a.bcast(s, 16, root=7)

    assert "root-invalid" in codes(lint(fn))

    def fn2(a, r):
        s = a.create_buffer(4096, np.float32)
        if r == 0:
            a.send(s, 4096, dst=5, tag=0, run_async=True).wait()

    assert "peer-invalid" in codes(lint(fn2))


def test_sub_comm_root_is_comm_local():
    # root 2 is valid in the world but NOT in the 2-member sub-comm
    def fn(a, r):
        s = a.create_buffer(32, np.float32)
        members = [0, 2]
        if r in members:
            cid = a.create_communicator(members)
            a.bcast(s, 32, root=2, comm_id=cid)

    findings = lint(fn, nranks=4)
    assert "root-invalid" in codes(findings)
    bad = [f for f in findings if f.code == "root-invalid"]
    assert all(f.comm == 1 for f in bad)


def test_buffer_overlap_and_alias():
    def fn(a, r):
        s = a.create_buffer(128, np.float32)
        a.allreduce(s.slice(0, 64), s.slice(32, 96), 64,
                    ReduceFunction.SUM)

    findings = lint(fn)
    assert codes(findings) == ["buffer-overlap"]
    assert all(f.severity == ERROR for f in findings)

    def fn2(a, r):
        s = a.create_buffer(64, np.float32)
        a.allreduce(s, s, 64, ReduceFunction.SUM)  # exact alias

    findings = lint(fn2)
    assert codes(findings) == ["buffer-alias"]
    assert all(f.severity == WARNING for f in findings)


def test_use_after_free():
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        s.free()
        a.allreduce(s, d, 64, ReduceFunction.SUM)

    findings = lint(fn)
    assert "use-after-free" in codes(findings)
    assert all(f.severity == ERROR for f in findings
               if f.code == "use-after-free")


def test_leaked_async_request():
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        a.allreduce(s, d, 64, ReduceFunction.SUM, run_async=True)

    findings = lint(fn)
    assert codes(findings) == ["leaked-request"]
    assert all(f.severity == WARNING for f in findings)
    assert not has_errors(findings)

    def fn2(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        req = a.allreduce(s, d, 64, ReduceFunction.SUM, run_async=True)
        assert req.wait()

    assert lint(fn2) == []


def test_extent_scaling_catches_fan_overlap():
    # allgather result spans count*P elements: a result buffer placed
    # right after the source still collides through the fan-out
    def fn(a, r):
        big = a.create_buffer(64 + 64 * a.size, np.float32)
        src = big.slice(0, 64)
        res = big.slice(32, 32 + 64 * a.size)
        a.allgather(src, res, 64)

    assert "buffer-overlap" in codes(lint(fn))


def test_compressed_rooted_collective_is_not_a_mismatch():
    """Per-operand compression bits and stream flags are legitimately
    per-rank (only the ROOT of a compressed rooted collective marks its
    buffers): the documented ROOTED_COMBOS pattern must lint clean."""
    from accl_tpu.constants import DataType

    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64 * a.size, np.float32) if r == 0 else None
        a.gather(s, d, 64, root=0, compress_dtype=DataType.float16)

    assert lint(fn) == []


def test_missing_gang_member_is_not_a_deadlock_cycle():
    """Ranks co-blocked on the SAME gang instance wait together: the
    culprit is the member that never arrives, not each other."""
    def fn(a, r):
        if r != 2:
            a.barrier()

    findings = lint(fn, nranks=3)
    assert "deadlock-cycle" not in codes(findings)
    missing = [f for f in findings if f.code == "gang-missing-member"]
    assert missing and all("missing [2]" in f.message for f in missing)


def test_first_divergence_helper():
    seqs = {0: ["a", "b", "c"], 1: ["a", "x", "c"]}
    div = first_divergence(seqs, lambda s: s)
    assert div["index"] == 1 and div["per_rank"] == {0: "b", 1: "x"}
    assert first_divergence({0: ["a"], 1: ["a", "b"]}, lambda s: s) is None
    assert first_divergence({}, lambda s: s) is None


# ---------------------------------------------------------------------------
# driver satellites
# ---------------------------------------------------------------------------
def test_unknown_communicator_raises_acclerror():
    world = LintWorld(2)
    accl = world.accls[0]
    s = accl.create_buffer(8, np.float32)
    d = accl.create_buffer(8, np.float32)
    with pytest.raises(ACCLError, match="unknown communicator id 3"):
        accl.allreduce(s, d, 8, ReduceFunction.SUM, comm_id=3)
    with pytest.raises(ACCLError, match="unknown communicator id 3"):
        accl.communicator(3)
    with pytest.raises(ACCLError, match="unknown communicator"):
        accl.dump_communicator(9)


def test_create_communicator_validates_indices():
    world = LintWorld(2)
    with pytest.raises(ACCLError, match=r"\[5\]"):
        world.accls[0].create_communicator([0, 5])


def test_deinit_warns_about_pending_async(caplog):
    world = LintWorld(1)
    accl = world.accls[0]
    s = accl.create_buffer(8, np.float32)
    d = accl.create_buffer(8, np.float32)
    req = accl.allreduce(s, d, 8, ReduceFunction.SUM, run_async=True)
    # the record backend completes instantly; rewind the event so the
    # request is genuinely "still pending" at deinit
    req._done = threading.Event()
    with caplog.at_level("WARNING", logger="accl_tpu"):
        accl.deinit()
    text = caplog.text
    assert "pending" in text and "allreduce" in text
    assert "seq=" in text  # the flight record (seq/state) is listed


# ---------------------------------------------------------------------------
# cross-communicator interleave order
# ---------------------------------------------------------------------------
def _grid_program(row_first_ranks):
    """2x2 grid: row comm id 1, col comm id 2 on every rank; ranks in
    ``row_first_ranks`` enter row-then-col, the rest col-then-row."""
    def fn(a, rank):
        row, col = divmod(rank, 2)
        rc = a.create_communicator([row * 2, row * 2 + 1])
        cc = a.create_communicator([col, col + 2])
        s = a.create_buffer(64, np.float32)
        ro = a.create_buffer(128, np.float32)
        co = a.create_buffer(128, np.float32)
        order = [(rc, ro), (cc, co)]
        if rank not in row_first_ranks:
            order.reverse()
        reqs = [a.allgather(s, out, 64, comm_id=cid, run_async=True)
                for cid, out in order]
        for req in reqs:
            req.wait()
            req.check()
    return fn


def test_subcomm_interleave_divergent_pair_flagged():
    findings = lint(_grid_program(row_first_ranks={0, 1}), nranks=4)
    assert [f.code for f in findings] == ["subcomm-interleave-hazard"]
    f = findings[0]
    assert f.severity == ERROR
    assert f.ranks == [0, 2]  # one witness per direction
    assert "divergent order" in f.message


def test_subcomm_interleave_agreed_order_clean():
    # same grid, every rank row-then-col: one global order, no hazard
    assert lint(_grid_program(row_first_ranks={0, 1, 2, 3}),
                nranks=4) == []


def test_subcomm_interleave_long_cycle_flagged():
    # no pair is entered both ways, but the per-rank orders close a
    # 3-cycle in the comm-order graph: 1<2 (rank 0), 2<3 (rank 1),
    # 3<1 (rank 2) — no global order exists
    def fn(a, rank):
        members = [0, 1, 2]
        cids = [a.create_communicator(members) for _ in range(3)]
        s = a.create_buffer(64, np.float32)
        outs = [a.create_buffer(64 * 3, np.float32) for _ in range(3)]
        pair = (rank, (rank + 1) % 3)
        reqs = []
        for k in pair:
            reqs.append(a.allgather(s, outs[k], 64, comm_id=cids[k],
                                    run_async=True))
        for req in reqs:
            req.wait()
            req.check()

    findings = lint(fn, nranks=3)
    assert "subcomm-interleave-hazard" in codes(findings)
    cyc = [f for f in findings if f.code == "subcomm-interleave-hazard"]
    assert len(cyc) == 1 and "acquisition cycle" in cyc[0].message


# ---------------------------------------------------------------------------
# CLI round-trips over the committed fixtures
# ---------------------------------------------------------------------------
def run_cli(*args):
    return subprocess.run([sys.executable, LINT_CLI, *args],
                          capture_output=True, text=True, cwd=REPO)


def test_cli_clean_fixture_exits_zero():
    proc = run_cli(os.path.join(FIXTURES, "clean_fixture.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no findings" in proc.stdout


def test_cli_desync_fixture_flagged(tmp_path):
    out = str(tmp_path / "lint.json")
    proc = run_cli(os.path.join(FIXTURES, "desync_fixture.py"),
                   "--json", out)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "desync-order" in proc.stdout
    doc = json.loads(open(out).read())
    assert doc["mode"] == "record" and doc["ranks"] == 2
    assert [f["code"] for f in doc["findings"]] == ["desync-order"]
    assert doc["programs"]["0"]["calls"][0]["op"] == "allreduce"
    assert doc["programs"]["1"]["calls"][0]["op"] == "bcast"


def test_cli_deadlock_fixture_flagged():
    proc = run_cli(os.path.join(FIXTURES, "deadlock_fixture.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "deadlock-cycle" in proc.stdout


def test_cli_param_mismatch_fixture_flagged():
    proc = run_cli(os.path.join(FIXTURES, "param_mismatch_fixture.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "param-mismatch" in proc.stdout
    assert "count=256" in proc.stdout and "count=128" in proc.stdout


def test_cli_subcomm_interleave_fixture_flagged():
    proc = run_cli(os.path.join(FIXTURES, "subcomm_interleave_fixture.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "subcomm-interleave-hazard" in proc.stdout


def test_cli_strict_promotes_warnings(tmp_path):
    leaky = tmp_path / "leaky.py"
    leaky.write_text(
        "import numpy as np\n"
        "from accl_tpu import ReduceFunction\n"
        "def accl_main(a, r):\n"
        "    s = a.create_buffer(32, np.float32)\n"
        "    d = a.create_buffer(32, np.float32)\n"
        "    a.allreduce(s, d, 32, ReduceFunction.SUM, run_async=True)\n")
    assert run_cli(str(leaky)).returncode == 0
    assert run_cli(str(leaky), "--strict").returncode == 1


# ---------------------------------------------------------------------------
# runtime sanitizer lane (ACCL_SANITIZE)
# ---------------------------------------------------------------------------
@pytest.fixture
def sanitize():
    sanitizer.set_enabled(True)
    try:
        yield
    finally:
        sanitizer.set_enabled(False)
        sanitizer._reset_exchange()


@pytest.fixture
def emu_world():
    from accl_tpu.backends.emu import EmuWorld

    with EmuWorld(2) as world:
        yield world


def test_sanitize_off_by_default():
    assert not sanitizer.active()
    assert not sanitizer.enabled()


def test_sanitizer_clean_emu_program_unaffected(sanitize, emu_world):
    bufs = {}

    def fn(a, r):
        s = a.create_buffer_like(np.arange(64, dtype=np.float32) + r)
        d = a.create_buffer(64, np.float32)
        bufs[r] = (s, d)
        a.allreduce(s, d, 64, ReduceFunction.SUM)
        return d.host.copy()

    outs = emu_world.run(fn)
    expect = (np.arange(64, dtype=np.float32) * 2 + 1)
    np.testing.assert_allclose(outs[0], expect)
    np.testing.assert_allclose(outs[1], expect)


def test_sanitizer_turns_mismatch_into_error_on_both_ranks(
        sanitize, emu_world):
    """The acceptance drill: a would-hang mismatched emu program raises
    an immediate ACCLError naming BOTH divergent calls on EVERY rank —
    no watchdog timeout, no wedged gang."""
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        with pytest.raises(ACCLError) as exc:
            a.allreduce(s, d, 64 if r == 0 else 32, ReduceFunction.SUM)
        msg = str(exc.value)
        assert "cross-rank call mismatch" in msg
        assert "count=64" in msg and "count=32" in msg
        assert "flight seq" in msg
        return msg

    emu_world.run(fn)


def test_sanitizer_order_desync_raises(sanitize, emu_world):
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        with pytest.raises(ACCLError, match="cross-rank call mismatch"):
            if r == 0:
                a.allreduce(s, d, 64, ReduceFunction.SUM)
            else:
                a.bcast(s, 64, root=0)

    emu_world.run(fn)


def test_sanitizer_missing_member_times_out_with_names(
        sanitize, emu_world, monkeypatch):
    monkeypatch.setenv("ACCL_SANITIZE_TIMEOUT", "0.5")

    def fn(a, r):
        if r != 0:
            return None
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        with pytest.raises(ACCLError, match=r"missing \[1\]"):
            a.allreduce(s, d, 64, ReduceFunction.SUM)
        return True

    assert emu_world.run(fn)[0] is True


def test_sanitizer_single_rank_checks(sanitize, emu_world):
    def fn(a, r):
        s = a.create_buffer(128, np.float32)
        with pytest.raises(ACCLError, match="root 9 is outside"):
            a.bcast(s, 128, root=9)
        with pytest.raises(ACCLError, match="partially overlaps"):
            a.allreduce(s.slice(0, 64), s.slice(32, 96), 64,
                        ReduceFunction.SUM)
        with pytest.raises(ACCLError, match="unknown communicator"):
            a.allreduce(s, s, 64, ReduceFunction.SUM, comm_id=4)

    emu_world.run(fn)


def test_sanitizer_abort_retires_flight_record(sanitize, emu_world):
    """An aborted call must leave the watchdog's in-flight scan: its
    flight record is finished with the dedicated sanitizer retcode,
    never reported as a hung gang."""
    def fn(a, r):
        s = a.create_buffer(64, np.float32)
        d = a.create_buffer(64, np.float32)
        with pytest.raises(ACCLError):
            a.allreduce(s, d, 64 if r == 0 else 32, ReduceFunction.SUM)
        recs = a.flight_recorder.records()
        assert recs, "no flight record for the aborted call"
        last = recs[-1]
        assert not last.in_flight
        from accl_tpu.constants import error_code_to_str

        assert "SANITIZER_ABORT_ERROR" in error_code_to_str(last.retcode)

    emu_world.run(fn)


def test_comm_abort_retires_flight_record_like_sanitizer(emu_world):
    """COMM_ABORTED is handled exactly like SANITIZER_ABORT_ERROR by
    the observability stack: an abort-finalized call's flight record is
    TERMINAL ("aborted"), leaves the watchdog's in-flight scan, and the
    merged cross-rank analysis reports no phantom hang while the abort
    propagates (the r10 abort/epoch satellite)."""
    import time

    from accl_tpu.constants import ErrorCode, error_code_to_str
    from accl_tpu.observability import flight as obs_flight

    reqs = {}

    def issue(a, r):
        if r == 1:
            d = a.create_buffer(64, np.float32)
            reqs[r] = a.recv(d, 64, 0, tag=77, run_async=True)
        return None

    emu_world.run(issue)
    time.sleep(0.1)
    emu_world.accls[0].abort(0)
    assert reqs[1].wait(30.0)
    rec = reqs[1].record
    assert rec is not None and not rec.in_flight
    assert obs_flight.STATE_NAMES[rec.state] == "aborted"
    assert "COMM_ABORTED" in error_code_to_str(rec.retcode)
    # no phantom hang anywhere in the merged analysis during/after the
    # abort — aborted records are terminal for the hang scanner
    merged = obs_flight.merge_flight_dumps(
        [a.flight_recorder.dump() for a in emu_world.accls])
    assert merged["analysis"]["hangs"] == []
    # the world must stay usable for the remaining sanitizer tests
    # sharing this fixture (abort fencing cleared by reset_errors)
    for a in emu_world.accls:
        a.reset_errors()
    assert int(ErrorCode.COMM_ABORTED) != int(ErrorCode.RANK_FAILED)


def test_shadow_capture_session(emu_world):
    from accl_tpu.analysis.sanitizer import CaptureSession

    with CaptureSession() as cap:
        def fn(a, r):
            s = a.create_buffer(64, np.float32)
            d = a.create_buffer(64, np.float32)
            a.allreduce(s, d, 64, ReduceFunction.SUM)

        emu_world.run(fn)
    assert not sanitizer.active()  # uninstalled on exit
    assert sorted(cap.programs) == [0, 1]
    assert [c.op.name for c in cap.programs[0].calls] == ["allreduce"]
    assert cap.check() == []


def test_check_programs_empty_input():
    assert check_programs({}) == []


# ---------------------------------------------------------------------------
# r13: happens-before lifecycle checkers over merged flight dumps
# ---------------------------------------------------------------------------
def _rec(rank, seq, collective, comm=0, state="complete", retcode=0,
         gang=False, t_submit=0, t_complete=0, lane="emu"):
    """Minimal flight-record dict with the RECORD_SCHEMA_KEYS fields
    the lifecycle checkers consume."""
    return {"seq": seq, "req_id": seq, "rank": rank,
            "collective": collective, "comm": comm, "tag": 0,
            "dtype": "float32", "count": 16, "nbytes": 64, "nranks": 2,
            "lane": lane, "state": state, "gang": gang,
            "retcode": retcode, "age_us": 0.0, "t_submit": t_submit,
            "t_queue": 0, "t_gang_ready": 0, "t_dispatch": 0,
            "t_complete": t_complete}


def _dump(rank, records):
    return {"rank": rank, "capacity": 512, "last_completed_seq": -1,
            "records": records}


def test_fence_stale_replay_flagged():
    from accl_tpu.analysis.checks import check_fence_staleness

    recs = [
        _rec(0, 0, "plan_replay", state="complete", gang=True),
        _rec(0, 1, "abort", retcode=1 << 27, state="aborted",
             lane="fence"),
        # replay AFTER the fence with no re-capture: the violation
        _rec(0, 2, "plan_replay", state="complete", gang=True),
    ]
    findings = check_fence_staleness(_dump(0, recs))
    assert [f.code for f in findings] == ["fence-stale-replay"]
    assert findings[0].index == 2


def test_fence_then_recapture_then_replay_clean():
    from accl_tpu.analysis.checks import check_fence_staleness

    recs = [
        _rec(0, 0, "abort", retcode=1 << 27, state="aborted",
             lane="fence"),
        _rec(0, 1, "plan_capture", lane="plan"),
        _rec(0, 2, "plan_replay", state="complete", gang=True),
    ]
    assert check_fence_staleness(_dump(0, recs)) == []


def test_reset_errors_fences_every_existing_comm():
    from accl_tpu.analysis.checks import check_fence_staleness

    recs = [
        _rec(0, 0, "allreduce", comm=3, gang=True),
        _rec(0, 1, "reset_errors", comm=-1, lane="fence"),
        _rec(0, 2, "plan_replay", comm=3, state="complete", gang=True),
    ]
    findings = check_fence_staleness(_dump(0, recs))
    assert [f.code for f in findings] == ["fence-stale-replay"]


def test_failed_replay_after_fence_is_the_sanctioned_path():
    from accl_tpu.analysis.checks import check_fence_staleness

    recs = [
        _rec(0, 0, "abort", retcode=1 << 27, state="aborted",
             lane="fence"),
        # the fencing contract WORKING: replay raised COMM_ABORTED
        _rec(0, 1, "plan_replay", state="aborted", retcode=1 << 27,
             gang=True),
    ]
    assert check_fence_staleness(_dump(0, recs)) == []


def test_completion_after_teardown_flagged():
    from accl_tpu.analysis.checks import check_teardown_completions

    recs = [
        _rec(0, 0, "allreduce", gang=True, t_submit=10, t_complete=20),
        _rec(0, 1, "engine_teardown", comm=-1, t_submit=100,
             t_complete=100, lane="lifecycle"),
        # a success published after teardown: the segfault class
        _rec(0, 2, "allreduce", gang=True, t_submit=90, t_complete=150),
    ]
    findings = check_teardown_completions(_dump(0, recs))
    assert [f.code for f in findings] == ["completion-after-teardown"]
    assert findings[0].index == 2


def test_aborted_finalization_after_teardown_is_sanctioned():
    from accl_tpu.analysis.checks import check_teardown_completions

    recs = [
        _rec(0, 0, "engine_teardown", comm=-1, t_submit=100,
             t_complete=100, lane="lifecycle"),
        # shutdown's finalize sweep: COMM_ABORTED, state aborted — OK
        _rec(0, 1, "recv", state="aborted", retcode=(1 << 27) | (1 << 28),
             t_submit=90, t_complete=150),
    ]
    assert check_teardown_completions(_dump(0, recs)) == []


def test_lock_order_inversion_flagged_across_ranks():
    from accl_tpu.analysis.checks import check_lock_order

    # rank 0 nests comm 1 inside comm 0 (0 held while 1 submits);
    # rank 1 nests comm 0 inside comm 1 — ABBA
    r0 = [_rec(0, 0, "allreduce", comm=0, gang=True, t_submit=10,
               t_complete=0, state="dispatched"),
          _rec(0, 1, "allreduce", comm=1, gang=True, t_submit=20,
               t_complete=0, state="dispatched")]
    r1 = [_rec(1, 0, "allreduce", comm=1, gang=True, t_submit=10,
               t_complete=0, state="dispatched"),
          _rec(1, 1, "allreduce", comm=0, gang=True, t_submit=20,
               t_complete=0, state="dispatched")]
    merged = {"ranks": [_dump(0, r0), _dump(1, r1)]}
    findings = check_lock_order(merged)
    assert [f.code for f in findings] == ["lock-order-inversion"]
    assert findings[0].ranks == [0, 1]


def test_lock_order_sequential_acquisition_clean():
    from accl_tpu.analysis.checks import check_lock_order

    # both ranks run comm 0 to completion BEFORE touching comm 1 and
    # vice versa — no held-while-acquiring window, no finding
    r0 = [_rec(0, 0, "allreduce", comm=0, gang=True, t_submit=10,
               t_complete=15),
          _rec(0, 1, "allreduce", comm=1, gang=True, t_submit=20,
               t_complete=25)]
    r1 = [_rec(1, 0, "allreduce", comm=1, gang=True, t_submit=10,
               t_complete=15),
          _rec(1, 1, "allreduce", comm=0, gang=True, t_submit=20,
               t_complete=25)]
    merged = {"ranks": [_dump(0, r0), _dump(1, r1)]}
    assert check_lock_order(merged) == []


def test_stuck_progress_through_teardown_is_error():
    from accl_tpu.analysis.checks import check_stuck_progress

    recs = [
        _rec(0, 0, "allreduce", gang=True, t_complete=20),
        # a dispatched recv that never finalized, with the world torn
        # down around it: the liveness violation (the sub-comm wedge's
        # dump signature)
        _rec(0, 1, "recv", state="dispatched", comm=2, t_submit=30),
        _rec(0, 2, "engine_teardown", comm=-1, t_submit=100,
             t_complete=100, lane="lifecycle"),
    ]
    findings = check_stuck_progress(_dump(0, recs))
    assert [f.code for f in findings] == ["stuck-progress"]
    f = findings[0]
    assert f.severity == ERROR and f.index == 1 and f.comm == 2


def test_stuck_progress_midrun_snapshot_is_warning():
    from accl_tpu.analysis.checks import check_stuck_progress

    # no teardown anchor: the dump may be a live snapshot, so the
    # in-flight record downgrades to a warning
    recs = [_rec(0, 0, "allgather", gang=True, state="queued")]
    findings = check_stuck_progress(_dump(0, recs))
    assert [(f.code, f.severity) for f in findings] == \
        [("stuck-progress", WARNING)]


def test_stuck_progress_terminal_states_clean():
    from accl_tpu.analysis.checks import check_stuck_progress

    # complete, failed and ABORTED (teardown's finalize sweep) all
    # count as finalized — liveness holds
    recs = [
        _rec(0, 0, "allreduce", gang=True, t_complete=20),
        _rec(0, 1, "recv", state="failed", retcode=1 << 11,
             t_complete=30),
        _rec(0, 2, "send", state="aborted", retcode=1 << 27,
             t_complete=40),
        _rec(0, 3, "engine_teardown", comm=-1, t_submit=100,
             t_complete=100, lane="lifecycle"),
    ]
    assert check_stuck_progress(_dump(0, recs)) == []


def test_lifecycle_suite_end_to_end_on_real_world(tmp_path):
    """A real abort -> fenced replay -> re-capture -> replay flow must
    come out CLEAN, and the dump must carry the lifecycle anchors."""
    from accl_tpu.analysis.checks import check_flight_lifecycle
    from accl_tpu.backends.emu import EmuWorld
    from accl_tpu.observability.flight import merge_flight_dumps

    with EmuWorld(2) as w:

        def fn(accl, rank):
            src = accl.create_buffer(16, np.float32)
            src.host[:] = rank + 1.0
            src.sync_to_device()
            dst = accl.create_buffer(16, np.float32)
            accl.allreduce(src, dst, 16)

        w.run(fn)
        w.accls[0].abort(0)
        names = [r.collective for r in w.accls[0].flight_recorder.records()]
        assert "abort" in names
        doc = merge_flight_dumps(
            [a.flight_recorder.dump() for a in w.accls])
        findings = check_flight_lifecycle(doc)
        assert [f for f in findings if f.severity == ERROR] == []
        # round-trip through JSON like a production post-mortem would
        p = tmp_path / "dump.json"
        p.write_text(json.dumps(doc))
        assert [f for f in check_flight_lifecycle(str(p))
                if f.severity == ERROR] == []
