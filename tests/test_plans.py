"""Persistent collective plans (accl_tpu/plans.py): capture/replay
bitwise fidelity, capture-time validation, invalidation fencing and the
ACCL_PLAN=0 kill switch.

The bitwise contract: a captured plan replayed N times must produce
exactly the byte streams the same N iterations produce through the
eager per-call driver path — on both the emulator engine (native C plan
ring, one FFI per replay) and the TPU backend (PlanRing, one rendezvous
per replay).
"""

import numpy as np
import pytest

from accl_tpu import ACCLError, ReduceFunction
from accl_tpu import plans as plans_mod
from accl_tpu.backends.emu import EmuWorld
from accl_tpu.backends.tpu import TpuWorld

NRANKS = 4
COUNT = 64
SCATTER = COUNT // NRANKS


def _data(rank):
    rng = np.random.default_rng(100 + rank)
    return rng.standard_normal(COUNT).astype(np.float32)


def _chain_eager(accl, rank, iters):
    """The reference loop: allreduce + reduce_scatter + a sendrecv ring
    hop, through the normal per-call path."""
    s = accl.create_buffer_like(_data(rank))
    r = accl.create_buffer(COUNT, np.float32)
    rs = accl.create_buffer(SCATTER, np.float32)
    pr = accl.create_buffer(COUNT, np.float32)
    outs = []
    for _ in range(iters):
        accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
        accl.reduce_scatter(s, rs, SCATTER, ReduceFunction.SUM)
        if rank % 2 == 0:
            accl.send(s, COUNT, (rank + 1) % NRANKS)
            accl.recv(pr, COUNT, (rank - 1) % NRANKS)
        else:
            accl.recv(pr, COUNT, (rank - 1) % NRANKS)
            accl.send(s, COUNT, (rank + 1) % NRANKS)
        outs.append((r.host.copy(), rs.host.copy(), pr.host.copy()))
    return outs


def _chain_planned(accl, rank, iters, plans_out):
    s = accl.create_buffer_like(_data(rank))
    r = accl.create_buffer(COUNT, np.float32)
    rs = accl.create_buffer(SCATTER, np.float32)
    pr = accl.create_buffer(COUNT, np.float32)

    def body(a):
        a.allreduce(s, r, COUNT, ReduceFunction.SUM)
        a.reduce_scatter(s, rs, SCATTER, ReduceFunction.SUM)
        if rank % 2 == 0:
            a.send(s, COUNT, (rank + 1) % NRANKS)
            a.recv(pr, COUNT, (rank - 1) % NRANKS)
        else:
            a.recv(pr, COUNT, (rank - 1) % NRANKS)
            a.send(s, COUNT, (rank + 1) % NRANKS)

    plan = accl.capture_plan(body)
    plans_out[rank] = plan
    outs = [(r.host.copy(), rs.host.copy(), pr.host.copy())]  # capture it
    for _ in range(iters - 1):
        plan.replay()
        outs.append((r.host.copy(), rs.host.copy(), pr.host.copy()))
    return outs


@pytest.mark.parametrize("world_cls", [EmuWorld, TpuWorld],
                         ids=["emu", "tpu-interpret"])
def test_capture_replay_bitwise_equals_eager(world_cls):
    """allreduce/reduce_scatter/sendrecv chains: replay == eager,
    bit for bit, iteration by iteration, on both engines."""
    iters = 3
    with world_cls(NRANKS) as w:
        ref = w.run(_chain_eager, iters)
    plans: dict = {}
    with world_cls(NRANKS) as w:
        got = w.run(_chain_planned, iters, plans)
    for rank in range(NRANKS):
        assert plans[rank].stats["replays"] == iters - 1
        for it in range(iters):
            for k, name in enumerate(("allreduce", "reduce_scatter",
                                      "sendrecv")):
                assert np.array_equal(got[rank][it][k],
                                      ref[rank][it][k]), \
                    f"{name} diverged at rank {rank} iter {it}"


def test_plan_async_replay_bitwise():
    """Async replay (ticket wait/check) produces the same results as
    sync replay on the TPU ring."""
    with TpuWorld(NRANKS) as w:
        store: dict = {}
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            s.sync_to_device()
            r = accl.create_buffer(COUNT, np.float32)
            store[rank] = (s, r)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM, from_fpga=True,
                to_fpga=True))

        w.run(cap)

        def rep(accl, rank):
            tickets = [plans[rank].replay(run_async=True)
                       for _ in range(4)]
            for t in tickets:
                assert t.wait(30)
                t.check()
            s, r = store[rank]
            r.sync_from_device()
            return r.host.copy()

        outs = w.run(rep)
    expected = sum(_data(rank) for rank in range(NRANKS))
    for rank in range(NRANKS):
        assert np.allclose(outs[rank], expected, atol=1e-4)


def test_replay_after_abort_raises_never_runs():
    """The invalidation contract: a replay after abort raises with the
    plan named invalid — it never silently runs on the fenced epoch."""
    with EmuWorld(NRANKS) as w:
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            r = accl.create_buffer(COUNT, np.float32)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM))

        w.run(cap)
        assert w.devices[0].plan_count() == 1
        w.accls[0].abort(0)

        def rep(accl, rank):
            with pytest.raises(ACCLError) as ei:
                plans[rank].replay()
            return str(ei.value)

        msgs = w.run(rep)
        for rank in range(NRANKS):
            assert "plan" in msgs[rank] or "aborted" in msgs[rank]
            assert plans[rank].invalidated or rank != 0
        # engine-side eviction: the aborted comm's plans are fenced
        assert w.devices[0].plan_count() == 0


def test_replay_after_shrink_raises_and_engine_evicts():
    """Satellite: plan-cache eviction fires on shrink_communicator for
    the emu backend too (not only on abort) — a healed world never
    replays a dead comm's plan."""
    with EmuWorld(NRANKS) as w:
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            r = accl.create_buffer(COUNT, np.float32)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM))

        w.run(cap)
        assert w.devices[0].plan_count() == 1

        def shrink_then_replay(accl, rank):
            new_id = accl.shrink_communicator(0, window_s=1.0)
            with pytest.raises(ACCLError):
                plans[rank].replay()
            return new_id

        ids = w.run(shrink_then_replay)
        assert len(set(ids)) == 1
        assert all(plans[r].invalidated for r in range(NRANKS))
        assert w.devices[0].plan_count() == 0


def test_replay_after_reset_errors_raises():
    """Satellite: eviction fires on reset_errors() too."""
    with EmuWorld(NRANKS) as w:
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            r = accl.create_buffer(COUNT, np.float32)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM))

        w.run(cap)
        w.reset_errors()
        assert w.devices[0].plan_count() == 0
        for rank in range(NRANKS):
            assert plans[rank].invalidated
            with pytest.raises(ACCLError):
                plans[rank].replay()


def test_tpu_ring_fenced_by_rebuild_gang_tables():
    """The grow path (rebuild_gang_tables) fences TPU plan rings."""
    with TpuWorld(2) as w:
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            s.sync_to_device()
            r = accl.create_buffer(COUNT, np.float32)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM, from_fpga=True,
                to_fpga=True))

        w.run(cap)
        w.engine.rebuild_gang_tables(0)

        def rep(accl, rank):
            with pytest.raises(ACCLError) as ei:
                plans[rank].replay()
            assert "invalidated" in str(ei.value) \
                or "fenced" in str(ei.value)

        w.run(rep)


def test_capture_time_sanitizer_finding_fails_capture():
    """A hazardous captured program fails capture_plan NAMING the
    finding (here: partial operand overlap, the buffer-overlap
    checker) — validated once at build time, not corrupted at
    iteration 10^6."""
    with TpuWorld(1) as w:
        accl = w.accls[0]
        buf = accl.create_buffer(COUNT, np.float32)
        shifted = buf.slice(8, COUNT // 2 + 8)
        with pytest.raises(ACCLError) as ei:
            accl.capture_plan(lambda a: a.allreduce(
                buf, shifted, COUNT // 2, ReduceFunction.SUM))
        msg = str(ei.value)
        assert "sanitizer finding" in msg
        assert "buffer-overlap" in msg


def test_capture_requires_collective_calls():
    with TpuWorld(1) as w:
        with pytest.raises(ACCLError) as ei:
            w.accls[0].capture_plan(lambda a: None)
        assert "no collective calls" in str(ei.value)


def test_plan_kill_switch_eager_lane():
    """ACCL_PLAN=0: capture_plan degrades to the eager fallback — same
    results through the unchanged per-call path, no engine plans."""
    plans_mod.set_enabled(False)
    try:
        with EmuWorld(NRANKS) as w:
            store: dict = {}

            def run(accl, rank):
                s = accl.create_buffer_like(_data(rank))
                r = accl.create_buffer(COUNT, np.float32)
                store[rank] = r
                plan = accl.capture_plan(lambda a: a.allreduce(
                    s, r, COUNT, ReduceFunction.SUM))
                assert plan.is_eager
                first = r.host.copy()
                plan.replay()
                assert np.array_equal(r.host, first)
                t = plan.replay(run_async=True)
                assert t.wait() and t.done
                t.check()
                return r.host.copy()

            outs = w.run(run)
            assert w.devices[0].plan_count() == 0
        with EmuWorld(NRANKS) as w:
            def eager(accl, rank):
                s = accl.create_buffer_like(_data(rank))
                r = accl.create_buffer(COUNT, np.float32)
                accl.allreduce(s, r, COUNT, ReduceFunction.SUM)
                return r.host.copy()

            ref = w.run(eager)
        for rank in range(NRANKS):
            assert np.array_equal(outs[rank], ref[rank])
    finally:
        plans_mod.set_enabled(True)


def test_plan_metrics_family():
    """plans/{captures,replays,invalidations} land in the metrics
    registry when metrics are enabled."""
    from accl_tpu.observability import metrics as _metrics

    if not _metrics.enabled():
        pytest.skip("metrics disabled in this environment")
    reg = _metrics.default_registry()
    before = {k: reg.counters().get(k, 0)
              for k in ("plans/captures", "plans/replays",
                        "plans/invalidations")}
    with EmuWorld(2) as w:
        plans: dict = {}

        def cap(accl, rank):
            s = accl.create_buffer_like(_data(rank))
            r = accl.create_buffer(COUNT, np.float32)
            plans[rank] = accl.capture_plan(lambda a: a.allreduce(
                s, r, COUNT, ReduceFunction.SUM))
            plans[rank].replay()

        w.run(cap)
        w.accls[0].abort(0)
    after = reg.counters()
    assert after["plans/captures"] >= before["plans/captures"] + 2
    assert after["plans/replays"] >= before["plans/replays"] + 2
    assert after["plans/invalidations"] >= \
        before["plans/invalidations"] + 1
