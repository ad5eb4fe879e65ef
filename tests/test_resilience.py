"""Fault-tolerant collectives: retransmission, abort + epoch fencing,
ULFM-style shrink, and the seeded chaos harness (accl_tpu/resilience).

Complements tests/test_fault_injection.py: that file pins which error
class each fault is DETECTED as (retransmission off); this one pins
that the same faults are RECOVERED from (retransmission on — the
default), that an abort wakes every blocked waiter fast, and that a
dead rank is survivable via shrink + re-run.
"""
import threading
import time

import numpy as np
import pytest

from accl_tpu import ACCLError, ChaosPlan, ReduceFunction, RetryPolicy
from accl_tpu.backends.emu import EmuDevice, EmuWorld
from accl_tpu.constants import ErrorCode
from accl_tpu.observability import flight as obs_flight
from accl_tpu.observability import health as obs_health

COUNT = 32


def _data(count, salt=0):
    rng = np.random.default_rng(910 + salt)
    return rng.standard_normal(count).astype(np.float32)


# ---------------------------------------------------------------------------
# layer 1: NACK retransmission (one-shot faults heal transparently)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fault", [
    EmuDevice.FAULT_DROP, EmuDevice.FAULT_DUPLICATE,
    EmuDevice.FAULT_CORRUPT_SEQ, EmuDevice.FAULT_DELAY,
], ids=["drop", "dup", "corrupt", "delay"])
def test_p2p_recovers_from_one_shot_fault(fault):
    with EmuWorld(2) as world:
        def fn(accl, rank):
            accl.set_timeout(10_000_000)
            if rank == 0:
                a = accl.create_buffer_like(_data(COUNT, salt=1))
                b = accl.create_buffer_like(_data(COUNT, salt=2))
                accl.device.inject_fault(fault)
                accl.send(a, COUNT, 1, tag=7)
                accl.send(b, COUNT, 1, tag=8)  # post-fault stream stays clean
            else:
                da = accl.create_buffer(COUNT, np.float32)
                db = accl.create_buffer(COUNT, np.float32)
                accl.recv(da, COUNT, 0, tag=7)
                accl.recv(db, COUNT, 0, tag=8)
                np.testing.assert_array_equal(da.host, _data(COUNT, salt=1))
                np.testing.assert_array_equal(db.host, _data(COUNT, salt=2))

        world.run(fn)
        # the recovery really went through the NACK lane (except dup,
        # which seqn-dedup absorbs without soliciting a resend)
        if fault in (EmuDevice.FAULT_DROP, EmuDevice.FAULT_CORRUPT_SEQ):
            stats = world.resilience_stats()
            assert sum(s["nacks_tx"] for s in stats) >= 1
            assert sum(s["retrans_sent"] for s in stats) >= 1


@pytest.mark.parametrize("fault", [
    EmuDevice.FAULT_DROP, EmuDevice.FAULT_DUPLICATE,
], ids=["drop", "dup"])
def test_allreduce_recovers_from_one_shot_fault(fault):
    with EmuWorld(2) as world:
        def fn(accl, rank):
            accl.set_timeout(10_000_000)
            s = accl.create_buffer_like(_data(COUNT, salt=rank))
            r = accl.create_buffer(COUNT, np.float32)
            if rank == 0:
                accl.device.inject_fault(fault)
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
            return r.host.copy()

        outs = world.run(fn)
        expected = _data(COUNT, salt=0) + _data(COUNT, salt=1)
        for out in outs:
            np.testing.assert_allclose(out, expected, rtol=1e-6, atol=1e-5)


def test_wildcard_recv_recovers_dropped_tagged_send():
    # regression: a TAG_ANY recv's NACK is a wildcard solicitation —
    # it must resend the concretely-tagged segment it is waiting for
    # (tag-exact NACK matching stranded this exact pairing)
    with EmuWorld(2) as world:
        def fn(accl, rank):
            accl.set_timeout(10_000_000)
            if rank == 0:
                a = accl.create_buffer_like(_data(COUNT, salt=9))
                accl.device.inject_fault(EmuDevice.FAULT_DROP)
                accl.send(a, COUNT, 1, tag=5)  # concrete tag, dropped
            else:
                da = accl.create_buffer(COUNT, np.float32)
                accl.recv(da, COUNT, 0)  # wildcard TAG_ANY recv
                np.testing.assert_array_equal(da.host, _data(COUNT, salt=9))

        world.run(fn)
        assert sum(s["retrans_sent"]
                   for s in world.resilience_stats()) >= 1


def test_retry_disabled_restores_detection():
    # retry_max=0 is the pure detect-and-classify contract
    with EmuWorld(2, retry_max=0) as world:
        def fn(accl, rank):
            accl.set_timeout(1_000_000)
            if rank == 0:
                src = accl.create_buffer_like(_data(COUNT))
                accl.device.inject_fault(EmuDevice.FAULT_DROP)
                accl.send(src, COUNT, 1, tag=1)
            else:
                dst = accl.create_buffer(COUNT, np.float32)
                with pytest.raises(ACCLError) as e:
                    accl.recv(dst, COUNT, 0, tag=1)
                assert e.value.code & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)

        world.run(fn)


def test_retry_policy_env(monkeypatch):
    monkeypatch.setenv("ACCL_RETRY_MAX", "7")
    monkeypatch.setenv("ACCL_RETRY_BASE_US", "333")
    pol = RetryPolicy.from_env()
    assert pol.max_retries == 7 and pol.base_us == 333 and pol.enabled
    # backoff: exponential envelope, deterministic jitter
    assert pol.backoff_us(3) >= 333 << 3
    assert pol.backoff_us(2, rank=1, seqn=5) == pol.backoff_us(2, rank=1,
                                                               seqn=5)
    monkeypatch.setenv("ACCL_RETRY_MAX", "0")
    assert not RetryPolicy.from_env().enabled


# ---------------------------------------------------------------------------
# seeded chaos matrix: all collectives under probabilistic drop/dup/delay
# ---------------------------------------------------------------------------
def _run_collective_matrix(world, nranks):
    """Every collective once, results asserted bitwise/allclose."""
    def fn(accl, rank):
        got = {}
        s = accl.create_buffer_like(_data(COUNT, salt=rank))
        r = accl.create_buffer(COUNT, np.float32)
        big_s = accl.create_buffer_like(
            np.concatenate([_data(COUNT, salt=100 * rank + i)
                            for i in range(nranks)]))
        big_r = accl.create_buffer(COUNT * nranks, np.float32)

        # p2p ring: rank -> rank+1
        nxt, prv = (rank + 1) % nranks, (rank - 1) % nranks
        if rank % 2 == 0:
            accl.send(s, COUNT, nxt, tag=50)
            accl.recv(r, COUNT, prv, tag=50)
        else:
            accl.recv(r, COUNT, prv, tag=50)
            accl.send(s, COUNT, nxt, tag=50)
        got["sendrecv"] = r.host.copy()

        accl.bcast(s if rank == 0 else r, COUNT, root=0)
        got["bcast"] = (s if rank == 0 else r).host.copy()

        accl.scatter(big_s, r, COUNT, root=0)
        got["scatter"] = r.host.copy()
        accl.gather(s, big_r, COUNT, root=0)
        got["gather"] = big_r.host.copy() if rank == 0 else None
        accl.allgather(s, big_r, COUNT)
        got["allgather"] = big_r.host.copy()
        accl.reduce(s, r, COUNT, root=0)
        got["reduce"] = r.host.copy() if rank == 0 else None
        accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
        got["allreduce"] = r.host.copy()
        accl.reduce_scatter(big_s, r, COUNT, ReduceFunction.SUM)
        got["reduce_scatter"] = r.host.copy()
        accl.alltoall(big_s, big_r, COUNT)
        got["alltoall"] = big_r.host.copy()
        accl.barrier()
        return got

    outs = world.run(fn)
    ranks = range(nranks)
    srcs = [_data(COUNT, salt=r) for r in ranks]
    bigs = [np.concatenate([_data(COUNT, salt=100 * r + i)
                            for i in range(nranks)]) for r in ranks]
    total = np.sum(srcs, axis=0)
    for r in ranks:
        np.testing.assert_array_equal(outs[r]["sendrecv"],
                                      srcs[(r - 1) % nranks])
        np.testing.assert_array_equal(outs[r]["bcast"], srcs[0])
        np.testing.assert_array_equal(
            outs[r]["scatter"], bigs[0][r * COUNT:(r + 1) * COUNT])
        np.testing.assert_array_equal(outs[r]["allgather"],
                                      np.concatenate(srcs))
        np.testing.assert_allclose(outs[r]["allreduce"], total, rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(
            outs[r]["reduce_scatter"],
            np.sum([bigs[i][r * COUNT:(r + 1) * COUNT] for i in ranks],
                   axis=0), rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(
            outs[r]["alltoall"],
            np.concatenate([bigs[i][r * COUNT:(r + 1) * COUNT]
                            for i in ranks]))
    np.testing.assert_array_equal(outs[0]["gather"], np.concatenate(srcs))
    np.testing.assert_allclose(outs[0]["reduce"], total, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("plan", [
    "seed=11,drop=0.05", "seed=12,dup=0.05",
    "seed=13,delay=0.08,delay_us=3000",
    "seed=14,drop=0.03,dup=0.03,delay=0.03,delay_us=2000",
], ids=["drop", "dup", "delay", "mixed"])
def test_chaos_matrix_bitwise_correct(plan):
    # deterministic seeded chaos: every collective completes with
    # correct results via the retransmission lane (fixed seeds => the
    # fault schedule replays identically run after run)
    nranks = 3
    with EmuWorld(nranks, chaos=plan) as world:
        for a in world.accls:
            a.set_timeout(15_000_000)
        _run_collective_matrix(world, nranks)
        if "drop" in plan:
            stats = world.resilience_stats()
            assert sum(s["retrans_sent"] for s in stats) >= 1


def test_chaos_plan_grammar():
    plan = ChaosPlan.parse("seed=42,drop=0.01,dup=0.02,delay=0.03,"
                           "delay_us=500,corrupt=0.004,slow_rank=2:750,"
                           "kill_rank=3")
    assert plan.seed == 42 and plan.drop == 0.01 and plan.dup == 0.02
    assert plan.delay == 0.03 and plan.delay_us == 500
    assert plan.corrupt == 0.004
    assert plan.slow == {2: 750} and plan.kills == [3]
    assert plan.probabilistic
    # spec() round-trips through parse()
    again = ChaosPlan.parse(plan.spec())
    assert again == plan
    for bad in ("drop", "drop=2.0", "wat=1", "slow_rank=x"):
        with pytest.raises(ACCLError):
            ChaosPlan.parse(bad)
    assert ChaosPlan.from_env() is None  # unset => no plan


# ---------------------------------------------------------------------------
# layer 2: abort + epoch fencing
# ---------------------------------------------------------------------------
def test_abort_wakes_blocked_waiter_immediately():
    # the bare-wait satellite: a receiver blocked on a dead peer used to
    # exit only via the ACCL_DEFAULT_TIMEOUT budget; abort must wake it
    # now (engine finalization -> Request event), not at budget expiry
    with EmuWorld(2) as world:
        def fn(accl, rank):
            accl.set_timeout(60_000_000)  # 60 s receive budget
            if rank == 1:
                dst = accl.create_buffer(COUNT, np.float32)
                t0 = time.time()
                with pytest.raises(ACCLError) as e:
                    accl.recv(dst, COUNT, 0, tag=3)  # peer never sends
                assert time.time() - t0 < 10.0  # woke early, not at 60 s
                assert e.value.code & int(ErrorCode.COMM_ABORTED)
            else:
                time.sleep(0.5)
                accl.abort(0)

        world.run(fn)


def test_abort_wakes_bare_request_wait():
    # async flavor: a bare Request.wait() parked on the completion event
    # wakes the moment the engine finalizes the aborted call
    with EmuWorld(2) as world:
        reqs = {}

        def issue(accl, rank):
            if rank == 1:
                dst = accl.create_buffer(COUNT, np.float32)
                reqs[rank] = accl.recv(dst, COUNT, 0, tag=4,
                                       run_async=True)
            return None

        world.run(issue)
        waker = threading.Timer(
            0.5, lambda: world.accls[0].abort(
                0, error=int(ErrorCode.RANK_FAILED)))
        waker.start()
        t0 = time.time()
        assert reqs[1].wait(timeout=30.0)
        assert time.time() - t0 < 10.0
        assert reqs[1].aborted
        assert reqs[1].retcode & int(ErrorCode.RANK_FAILED)
        with pytest.raises(ACCLError):
            reqs[1].check()
        waker.join()


def test_aborted_comm_fails_fast_and_fenced_epoch_drops():
    with EmuWorld(2) as world:
        # a chaos delay holds rank 0's segment in flight across the
        # abort: when it finally releases it carries the DEAD epoch and
        # must be fenced at rank 1's ingress, not delivered
        world.devices[0].set_chaos(seed=1, drop_ppm=0, dup_ppm=0,
                                   delay_ppm=0, delay_us=700_000,
                                   corrupt_ppm=0, slow_us=0)

        def fn(accl, rank):
            accl.set_timeout(2_000_000)
            if rank == 0:
                src = accl.create_buffer_like(_data(COUNT))
                accl.device.inject_fault(EmuDevice.FAULT_DELAY)
                accl.send(src, COUNT, 1, tag=6)  # held for 0.7 s
                time.sleep(0.2)
                accl.abort(0)
                # driver-side fast fail: new calls on the aborted comm
                # never reach the engine
                with pytest.raises(ACCLError) as e:
                    accl.send(src, COUNT, 1, tag=7)
                assert e.value.code & int(ErrorCode.COMM_ABORTED)
            else:
                time.sleep(1.5)  # outlive the delayed release
            return None

        world.run(fn)
        stats = world.resilience_stats()
        assert stats[1]["fenced_drops"] >= 1  # the stale-epoch segment


def test_abort_flight_record_terminal_state_and_health():
    # flight records finalized by an abort retire as "aborted" — the
    # watchdog must see a recovery action, not a phantom hang — and the
    # accl_health gauge gains the aborted value
    with EmuWorld(2) as world:
        reqs = {}

        def issue(accl, rank):
            if rank == 1:
                dst = accl.create_buffer(COUNT, np.float32)
                reqs[rank] = accl.recv(dst, COUNT, 0, tag=5,
                                       run_async=True)
            return None

        world.run(issue)
        time.sleep(0.2)
        world.accls[0].abort(0)
        assert reqs[1].wait(30.0)
        rec = reqs[1].record
        assert rec is not None
        assert obs_flight.STATE_NAMES[rec.state] == "aborted"
        assert not rec.in_flight
        # merged analysis: an aborted record is terminal, never a hang
        merged = obs_flight.merge_flight_dumps(
            [a.flight_recorder.dump() for a in world.accls])
        assert not any(
            h for h in merged["analysis"]["hangs"]
            if h["tag"] == 5), merged["analysis"]["hangs"]
        # health: the watchdog's next sweep reads aborted (3)
        wd = world.watchdog
        wd.check()
        assert wd._health == obs_health.HEALTH_ABORTED
        assert obs_health.HEALTH_NAMES[obs_health.HEALTH_ABORTED] == \
            "aborted"


def test_watchdog_action_abort_recovers_hang():
    # ACCL_WATCHDOG_ACTION=abort: the PR3 watchdog now triggers recovery
    # instead of only dumping — a withheld gang member turns into fast
    # COMM_ABORTED|RANK_FAILED failures on every arrived rank
    with EmuWorld(3) as world:
        world.start_watchdog(timeout_s=1.0, action="abort",
                             dump_path="")
        reqs = {}

        def issue(accl, rank):
            if rank == 0:
                return None  # withheld: never joins the gang
            s = accl.create_buffer_like(_data(COUNT, salt=rank))
            r = accl.create_buffer(COUNT, np.float32)
            reqs[rank] = accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                                        run_async=True)
            return None

        world.run(issue)
        deadline = time.time() + 30
        for rank in (1, 2):
            assert reqs[rank].wait(timeout=max(0.1, deadline - time.time()))
            assert reqs[rank].aborted
            assert reqs[rank].retcode & int(ErrorCode.RANK_FAILED)
        assert world.watchdog.last_report is not None


# ---------------------------------------------------------------------------
# layer 3: liveness + ULFM shrink
# ---------------------------------------------------------------------------
def test_probe_liveness_names_dead_rank():
    with EmuWorld(3) as world:
        world.kill_rank(2)

        def fn(accl, rank):
            if rank == 2:
                return None
            return accl.device.probe_liveness(0, 3, window_s=2.0)

        outs = world.run(fn)
        assert outs[0] == [True, True, False]
        assert outs[1] == [True, True, False]


def test_kill_abort_shrink_rerun():
    # the full recovery drill (the chaos_smoke acceptance path): a rank
    # dies mid-run; the failure is CLASSIFIED, the comm revoked, the
    # survivors agree on the surviving set and finish on the shrunk
    # world.
    #
    # Deflaked (r14): only rank 0 — whose ring predecessor IS the dead
    # rank — deterministically fails the first allreduce.  Ranks 1/2
    # sit downstream of live senders, and the eager ring keeps
    # forwarding after an upstream receive failure, so on some
    # interleavings they complete the schedule with relayed garbage and
    # retcode 0; asserting `raises` on EVERY survivor was the flake
    # (r12/r13 "passed this run" notes), and the rank that hit DID NOT
    # RAISE then skipped the shrink, starving the others into a
    # 6-second timeout.  This is exactly the ULFM contract: ONE rank
    # classifies and revokes; the propagated abort (or clean-looking
    # garbage) is what everyone else may legally observe.  The native
    # model checker documents the engine-level half of this contract
    # (scripts/model_check.py, drill abort_vs_traffic: a raced retcode
    # is either 0 or carries the fence bits).
    nranks = 4
    with EmuWorld(nranks) as world:
        world.kill_rank(3)

        def fn(accl, rank):
            if rank == 3:
                return "dead"
            accl.set_timeout(1_500_000)
            s = accl.create_buffer_like(_data(COUNT, salt=rank))
            r = accl.create_buffer(COUNT, np.float32)
            if rank == 0:
                # prev rank in the ring is dead: guaranteed classification
                with pytest.raises(ACCLError):
                    accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
            else:
                # downstream of live senders: may fail fast via the
                # propagated abort OR complete with relayed garbage —
                # both are legal pre-revoke observations
                try:
                    accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
                except ACCLError:
                    pass
            # ULFM pattern: whoever classifies a failure revokes; the
            # propagated abort wakes slower ranks' calls immediately
            accl.abort(0, error=int(ErrorCode.RANK_FAILED))
            new_comm = accl.shrink_communicator(0, window_s=2.0)
            assert accl.communicator(new_comm).size == nranks - 1
            # fresh clock for the rerun: the shrink agreement already
            # resynchronized the survivors, the budget only has to
            # cover the collective itself (not inherited skew)
            accl.set_timeout(5_000_000)
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                           comm_id=new_comm)
            return r.host.copy()

        outs = world.run(fn)
        expected = np.sum([_data(COUNT, salt=r) for r in range(3)], axis=0)
        for r in range(3):
            np.testing.assert_allclose(outs[r], expected, rtol=1e-6, atol=1e-5)


def test_shrink_without_deaths_is_a_fresh_comm():
    with EmuWorld(2) as world:
        def fn(accl, rank):
            nc = accl.shrink_communicator(0, window_s=1.0)
            assert accl.communicator(nc).size == 2
            s = accl.create_buffer_like(_data(8, salt=rank))
            r = accl.create_buffer(8, np.float32)
            accl.allreduce(s, r, 8, ReduceFunction.SUM, comm_id=nc)
            return r.host.copy()

        outs = world.run(fn)
        expected = _data(8, salt=0) + _data(8, salt=1)
        np.testing.assert_allclose(outs[0], expected, rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(outs[1], expected, rtol=1e-6, atol=1e-5)


# ---------------------------------------------------------------------------
# recovery edge races (r11 satellite): abort/shrink interleavings
# ---------------------------------------------------------------------------
def test_double_abort_is_idempotent():
    # two aborts of one comm (e.g. two survivors both classifying the
    # same failure, or a watchdog racing an application abort) must be
    # indistinguishable from one: epochs stay monotonic, waiters wake
    # once, the second abort neither raises nor resurrects the comm
    with EmuWorld(2) as world:
        def fn(accl, rank):
            if rank == 0:
                time.sleep(0.3)
                accl.abort(0)
                accl.abort(0)  # idempotent re-revoke
                with pytest.raises(ACCLError):
                    accl.barrier()  # still fenced after the second
            else:
                dst = accl.create_buffer(COUNT, np.float32)
                with pytest.raises(ACCLError) as e:
                    accl.recv(dst, COUNT, 0, tag=9)
                assert e.value.code & int(ErrorCode.COMM_ABORTED)
                accl.abort(0)  # cross-rank double abort, same contract

        world.run(fn)
        # both ranks re-aborting bumped epochs monotonically — no
        # wraparound/rollback (the handle_abort CAS adopts max only)
        assert world.devices[0].comm_epoch(0) >= 1
        assert world.devices[1].comm_epoch(0) >= 1


def test_shrink_concurrent_with_watchdog_abort():
    # a watchdog-triggered abort (action=abort) landing WHILE the
    # survivors are already inside shrink_communicator must not corrupt
    # the shrink: the probe runs on the control plane (epoch-agnostic)
    # and the fresh comm id is minted identically everywhere
    with EmuWorld(3) as world:
        world.start_watchdog(timeout_s=1.0, action="abort", dump_path="")

        def fn(accl, rank):
            accl.set_timeout(1_500_000)
            if rank == 0:
                # withheld from the gang: the watchdog will abort comm 0
                # while ranks 1-2 are mid-recovery
                time.sleep(2.0)
                accl.abort(0, error=int(ErrorCode.RANK_FAILED))
                nc = accl.shrink_communicator(0, window_s=2.0)
                return nc
            s = accl.create_buffer_like(_data(COUNT, salt=rank))
            r = accl.create_buffer(COUNT, np.float32)
            with pytest.raises(ACCLError):
                accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
            accl.abort(0, error=int(ErrorCode.RANK_FAILED))
            nc = accl.shrink_communicator(0, window_s=2.0)
            return nc

        outs = world.run(fn)
        assert len(set(outs)) == 1, f"shrink ids diverged: {outs}"

        def verify(accl, rank, comm_id):
            s = accl.create_buffer_like(_data(8, salt=rank))
            r = accl.create_buffer(8, np.float32)
            accl.allreduce(s, r, 8, ReduceFunction.SUM, comm_id=comm_id)
            return r.host.copy()

        post = world.run(verify, outs[0])
        expected = np.sum([_data(8, salt=q) for q in range(3)], axis=0)
        for out in post:
            np.testing.assert_allclose(out, expected, rtol=1e-6,
                                       atol=1e-5)


def test_all_alive_shrink_mints_identical_ids_every_rank():
    # repeated all-alive shrinks are pure comm mints: every rank must
    # observe the SAME fresh id at every step (the create-order
    # discipline), and the last comm must still collectively work
    with EmuWorld(3) as world:
        def fn(accl, rank):
            ids = [accl.shrink_communicator(0, window_s=1.0)
                   for _ in range(3)]
            s = accl.create_buffer_like(_data(8, salt=rank))
            r = accl.create_buffer(8, np.float32)
            accl.allreduce(s, r, 8, ReduceFunction.SUM, comm_id=ids[-1])
            return ids, r.host.copy()

        outs = world.run(fn)
        ids = {tuple(o[0]) for o in outs}
        assert len(ids) == 1, f"per-rank shrink id sequences: {ids}"
        assert list(ids.pop()) == [1, 2, 3]
        expected = np.sum([_data(8, salt=q) for q in range(3)], axis=0)
        for _ids, out in outs:
            np.testing.assert_allclose(out, expected, rtol=1e-6,
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# probe validation + env knob clear errors (r11 satellites)
# ---------------------------------------------------------------------------
def test_probe_alive_rejects_bad_window_and_overlong_result():
    from accl_tpu.resilience.membership import probe_alive

    with EmuWorld(2) as world:
        accl = world.accls[0]
        with pytest.raises(ACCLError, match=r"window_s.*> 0"):
            probe_alive(accl, 0, window_s=0.0)
        with pytest.raises(ACCLError, match="comm 0"):
            probe_alive(accl, 0, window_s=-1.0)

        # a backend handing back liveness for a DIFFERENT world must be
        # refused, not truncated (a shrink built from it could exclude
        # the wrong ranks); a short answer still pads with dead
        real = accl.device.probe_liveness
        try:
            accl.device.probe_liveness = \
                lambda c, n, w: [True, True, True, False]
            with pytest.raises(ACCLError, match="refusing to truncate"):
                probe_alive(accl, 0, window_s=0.5)
            accl.device.probe_liveness = lambda c, n, w: [True]
            assert probe_alive(accl, 0, window_s=0.5) == [True, False]
        finally:
            accl.device.probe_liveness = real


def test_env_knobs_raise_naming_errors(monkeypatch):
    from accl_tpu.observability.flight import FlightRecorder
    from accl_tpu.observability.health import watchdog_timeout_s

    monkeypatch.setenv("ACCL_RETRY_MAX", "lots")
    with pytest.raises(ACCLError, match="ACCL_RETRY_MAX"):
        RetryPolicy.from_env()
    monkeypatch.setenv("ACCL_RETRY_MAX", "-3")
    with pytest.raises(ACCLError, match="ACCL_RETRY_MAX"):
        RetryPolicy.from_env()
    monkeypatch.delenv("ACCL_RETRY_MAX")
    monkeypatch.setenv("ACCL_RETRY_BASE_US", "fast")
    with pytest.raises(ACCLError, match="ACCL_RETRY_BASE_US"):
        RetryPolicy.from_env()
    monkeypatch.delenv("ACCL_RETRY_BASE_US")
    monkeypatch.setenv("ACCL_WATCHDOG_TIMEOUT", "five minutes")
    with pytest.raises(ACCLError, match="ACCL_WATCHDOG_TIMEOUT"):
        watchdog_timeout_s()
    monkeypatch.delenv("ACCL_WATCHDOG_TIMEOUT")
    monkeypatch.setenv("ACCL_FLIGHT_CAP", "big")
    with pytest.raises(ACCLError, match="ACCL_FLIGHT_CAP"):
        FlightRecorder(0)
    monkeypatch.delenv("ACCL_FLIGHT_CAP")
    from accl_tpu.resilience.supervisor import RecoveryPolicy

    monkeypatch.setenv("ACCL_RECOVERY", "pray")
    with pytest.raises(ACCLError, match="ACCL_RECOVERY"):
        RecoveryPolicy()
    monkeypatch.setenv("ACCL_RECOVERY", "grow")
    monkeypatch.setenv("ACCL_JOIN_WAIT_S", "soon")
    with pytest.raises(ACCLError, match="ACCL_JOIN_WAIT_S"):
        RecoveryPolicy()


def test_chaos_plan_join_rank_grammar():
    plan = ChaosPlan.parse("seed=5,kill_rank=2,join_rank=2")
    assert plan.kills == [2] and plan.joins == [2]
    assert ChaosPlan.parse(plan.spec()) == plan  # round-trips
    with pytest.raises(ACCLError):
        ChaosPlan.parse("join_rank=x")


# ---------------------------------------------------------------------------
# elastic membership (r11 tentpole): join + grow + supervisor
# ---------------------------------------------------------------------------
def test_spawn_replacement_grow_healthy_world():
    # grow without any death: a 2-rank world admits a third live rank;
    # the grown comm works collectively and the old comm is untouched
    from accl_tpu.resilience.elastic import admit_pending

    with EmuWorld(2) as world:
        joiner = world.spawn_replacement()
        out = {}

        def joiner_thread():
            cid = out["comm"] = joiner.join(timeout_s=20.0)
            s = joiner.accl.create_buffer_like(np.full(8, 4.0,
                                                       np.float32))
            r = joiner.accl.create_buffer(8, np.float32)
            joiner.accl.allreduce(s, r, 8, ReduceFunction.SUM,
                                  comm_id=cid)
            out["result"] = r.host.copy()

        jt = threading.Thread(target=joiner_thread, daemon=True)
        jt.start()

        def fn(accl, rank):
            new_comm, n = admit_pending(accl, 0, world.board,
                                        wait_s=5.0, window_s=1.0)
            assert n == 1
            s = accl.create_buffer_like(
                np.full(8, float(rank + 1), np.float32))
            r = accl.create_buffer(8, np.float32)
            accl.allreduce(s, r, 8, ReduceFunction.SUM,
                           comm_id=new_comm)
            # the ORIGINAL comm still works: growing drained nothing
            accl.barrier(comm_id=0)
            return new_comm, r.host.copy()

        res = world.run(fn)
        jt.join(timeout=30)
        assert not jt.is_alive()
        assert res[0][0] == res[1][0] == out["comm"] == 1
        np.testing.assert_array_equal(res[0][1], np.full(8, 7.0,
                                                         np.float32))
        np.testing.assert_array_equal(out["result"],
                                      np.full(8, 7.0, np.float32))
        # engine-level join handshake really ran (Join/Welcome/
        # StateSync): the sponsor answered, the joiner completed
        stats = joiner.device.join_stats()
        assert stats["joined"] == 1
        assert sum(world.devices[r].join_stats()["sponsored"]
                   for r in range(2)) == 1


def test_placeholder_comms_fail_fast_on_joiner():
    # a joiner's padded id space: calls on a placeholder slot raise a
    # decodable error in the driver, and the engine fences strays
    from accl_tpu.resilience.elastic import admit_pending

    with EmuWorld(2) as world:
        joiner = world.spawn_replacement()
        out = {}

        def joiner_thread():
            # make the id space interesting: survivors mint one extra
            # comm before the admission, so the joiner pads TWO slots
            out["comm"] = joiner.join(timeout_s=20.0)

        jt = threading.Thread(target=joiner_thread, daemon=True)
        jt.start()

        def fn(accl, rank):
            accl.create_communicator([0, 1])  # id 1 (joiner never saw)
            new_comm, n = admit_pending(accl, 0, world.board,
                                        wait_s=5.0, window_s=1.0)
            return new_comm

        res = world.run(fn)
        jt.join(timeout=30)
        assert res[0] == out["comm"] == 2
        # comm 1 is a placeholder on the joiner: decodable fast-fail
        with pytest.raises(ACCLError, match="placeholder"):
            joiner.accl.communicator(1)
        s = joiner.accl.create_buffer_like(np.ones(4, np.float32))
        r = joiner.accl.create_buffer(4, np.float32)
        with pytest.raises(ACCLError, match="placeholder"):
            joiner.accl.allreduce(s, r, 4, ReduceFunction.SUM,
                                  comm_id=1)


def test_supervised_kill_shrink_join_grow_resume():
    # the tier-1 twin of the CI join drill (scripts/chaos_smoke.py
    # drill 3), smaller: the per-rank supervisors drive kill -> abort
    # -> probe -> shrink -> admit -> grow -> agree -> resume; the world
    # returns to full size and the replacement participates
    from accl_tpu.resilience.supervisor import RecoveryPolicy

    nranks, iters, count = 3, 4, 16
    victim = 1

    def local_data(accl, comm_id, it):
        comm = accl.communicator(comm_id)
        rng = np.random.default_rng(70 * comm.local_rank + it)
        return rng.standard_normal(count).astype(np.float32), comm.size

    with EmuWorld(nranks) as world:
        for a in world.accls:
            a.set_timeout(1_500_000)
        policy = dict(mode="grow", join_wait_s=8.0, probe_window_s=1.0,
                      max_rounds=2)
        join_out = {}

        def replacement():
            time.sleep(0.8)
            j = world.spawn_replacement()
            cid = j.join(timeout_s=30.0)
            j.accl.set_timeout(30_000_000)
            sup = j.accl.supervise(policy=RecoveryPolicy(**policy),
                                   board=world.board)
            sup.comm_id = cid
            restart = sup.agree_restart(0, fresh=True)
            outs = {}

            def step(a, c, it):
                data, size = local_data(a, c, it)
                s = a.create_buffer_like(data)
                r = a.create_buffer(count, np.float32)
                a.allreduce(s, r, count, ReduceFunction.SUM, comm_id=c)
                outs[it] = (size, r.host.copy())

            sup.run_loop(step, iters, comm_id=cid,
                         start_iteration=restart)
            join_out.update(outs=outs, restart=restart)

        jt = threading.Thread(target=replacement, daemon=True)
        jt.start()

        def supervised(accl, rank):
            from accl_tpu.resilience.supervisor import RecoveryPolicy

            sup = accl.supervise(policy=RecoveryPolicy(**policy),
                                 board=world.board)
            outs = {}

            def step(a, comm_id, it):
                if rank == victim and it == 1:
                    world.kill_rank(victim)
                data, size = local_data(a, comm_id, it)
                s = a.create_buffer_like(data)
                r = a.create_buffer(count, np.float32)
                a.allreduce(s, r, count, ReduceFunction.SUM,
                            comm_id=comm_id)
                outs[it] = (size, r.host.copy())

            try:
                summary = sup.run_loop(
                    step, iters, comm_id=0,
                    on_restart=lambda i: [outs.pop(k) for k in
                                          list(outs) if k >= i])
            except ACCLError as e:
                assert rank == victim, f"survivor {rank} died: {e}"
                # the victim halts ISOLATED, never shrinks to itself
                assert "isolated" in str(e)
                return ("dead", sup.state_log)
            return ("alive", outs, summary)

        res = world.run(supervised)
        jt.join(timeout=60)
        assert not jt.is_alive() and "outs" in join_out
        assert res[victim][0] == "dead"
        survivors = [r for r in range(nranks) if r != victim]
        for r in survivors:
            state, outs, summary = res[r]
            assert state == "alive"
            assert sorted(outs) == list(range(iters))
            # the supervisor drove the whole episode
            states = [s for _t, s, _d in summary["state_log"]]
            for needed in ("abort", "probe", "shrink", "grow",
                           "agree", "resume"):
                assert needed in states, (needed, states)
            # world back at original size for every post-recovery iter
            assert {outs[k][0] for k in outs} == {nranks}
        # replacement fully participated at full size
        assert {v[0] for v in join_out["outs"].values()} == {nranks}
        # every member agrees on the result values per iteration
        for it in range(iters):
            vals = [res[r][1][it][1] for r in survivors]
            if it in join_out["outs"]:
                vals.append(join_out["outs"][it][1])
            for v in vals[1:]:
                np.testing.assert_array_equal(v, vals[0])
        # observability: membership counters moved and the flight rings
        # carry retired recovery/<phase> records
        from accl_tpu.observability import metrics as obs_metrics

        snap = obs_metrics.default_registry().snapshot()
        assert snap["counters"].get("membership/joins", 0) >= 1
        assert snap["counters"].get("membership/shrinks", 0) >= 1
        assert snap["counters"].get("membership/grows", 0) >= 1
        assert snap["counters"].get("recovery/rounds", 0) >= 1
        assert snap["values"].get("recovery/latency_us",
                                  {}).get("count", 0) >= 1
        recs = [rec for a in world.accls
                for rec in a.flight_recorder.records()
                if rec.collective.startswith("recovery/")]
        assert recs, "no recovery phase records in the flight rings"
        assert all(not rec.in_flight for rec in recs)


def test_supervisor_shrink_policy_finishes_smaller():
    # default policy (shrink): a killed rank's world finishes at the
    # smaller size with no join machinery involved
    from accl_tpu.resilience.supervisor import RecoveryPolicy

    nranks, iters, count = 3, 3, 16
    with EmuWorld(nranks) as world:
        for a in world.accls:
            a.set_timeout(1_500_000)

        def supervised(accl, rank):
            sup = accl.supervise(
                policy=RecoveryPolicy(mode="shrink",
                                      probe_window_s=1.0),
                board=world.board)
            outs = {}

            def step(a, comm_id, it):
                if rank == 2 and it == 1:
                    world.kill_rank(2)
                comm = a.communicator(comm_id)
                s = a.create_buffer_like(
                    _data(count, salt=comm.local_rank + 7 * it))
                r = a.create_buffer(count, np.float32)
                a.allreduce(s, r, count, ReduceFunction.SUM,
                            comm_id=comm_id)
                outs[it] = (comm.size, r.host.copy())

            try:
                sup.run_loop(step, iters, comm_id=0,
                             on_restart=lambda i: [outs.pop(k) for k in
                                                   list(outs) if k >= i])
            except ACCLError:
                assert rank == 2
                return "dead"
            return outs

        res = world.run(supervised)
        assert res[2] == "dead"
        for r in (0, 1):
            outs = res[r]
            assert sorted(outs) == list(range(iters))
            # post-recovery iterations ran on the 2-rank survivor comm
            assert outs[iters - 1][0] == 2


def test_supervisor_health_gauge_recovering():
    from accl_tpu.observability import health as oh
    from accl_tpu.observability import metrics as om

    reg = om.MetricsRegistry()
    oh.note_recovering(reg, True)
    assert reg.snapshot()["gauges"]["accl_health"] == \
        oh.HEALTH_RECOVERING
    assert oh.HEALTH_NAMES[oh.HEALTH_RECOVERING] == "recovering"
    oh.note_recovering(reg, False)
    assert reg.snapshot()["gauges"]["accl_health"] == oh.HEALTH_OK


# ---------------------------------------------------------------------------
# soak (slow-marked: excluded from tier-1, run by the nightly lane)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_soak_60s():
    # 60 s of mixed seeded chaos over a 3-rank allreduce/bcast loop:
    # every iteration must stay bitwise correct; any hang fails via the
    # receive budget
    nranks = 3
    plan = "seed=777,drop=0.02,dup=0.02,delay=0.03,delay_us=2000"
    with EmuWorld(nranks, chaos=plan) as world:
        for a in world.accls:
            a.set_timeout(20_000_000)
        deadline = time.time() + 60

        def fn(accl, rank):
            it = 0
            while time.time() < deadline:
                s = accl.create_buffer_like(_data(COUNT, salt=rank + it))
                r = accl.create_buffer(COUNT, np.float32)
                accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
                expected = np.sum([_data(COUNT, salt=q + it)
                                   for q in range(nranks)], axis=0)
                np.testing.assert_allclose(r.host, expected, rtol=1e-6, atol=1e-5)
                accl.bcast(s if rank == 0 else r, COUNT, root=0)
                np.testing.assert_array_equal(
                    (s if rank == 0 else r).host, _data(COUNT, salt=it))
                it += 1
            return it

        iters = world.run(fn)
        assert min(iters) >= 3  # the loop really looped under chaos
