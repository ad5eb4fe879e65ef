"""Prepared operand assembly in the TPU engine's gang plans.

A plan whose every operand is a whole, same-dtype, resident buffer
builds the global array from the buffers' current arrays with an aval
and sharding fixed when the plan was built; any other plan takes the
checked assembly and counts it under ``assemble_checked``.  The link
twin's deltas are likewise computed once per plan.  One path serves
one-member and four-member communicators alike.
"""
import jax
import numpy as np
import pytest

from accl_tpu import DataType, ReduceFunction
from accl_tpu.backends.tpu import TpuEngine, TpuWorld
from accl_tpu.constants import (
    ACCLError,
    CCLOCall,
    Operation,
    StreamFlags,
)
from accl_tpu.observability import metrics as obs_metrics
from accl_tpu.request import Request

COUNT = 96


def _data(count, rank, salt=0, dtype=np.float32):
    rng = np.random.default_rng(1000 + 17 * rank + salt)
    return rng.standard_normal(count).astype(dtype)


@pytest.fixture(scope="module")
def world4():
    with TpuWorld(4) as w:
        yield w


@pytest.fixture(scope="module")
def world1():
    with TpuWorld(1) as w:
        yield w


def _world(request, nranks):
    return request.getfixturevalue("world1" if nranks == 1 else "world4")


def _plan_reading(eng, rank, buf):
    """The cached plan whose operands include (rank, buf)."""
    plans = [p for p in eng._gang_plans.values()
             if (rank, buf.address) in p["opnd_addrs"]]
    assert len(plans) == 1, len(plans)
    return plans[0]


def _checked(eng):
    return eng.stats["assemble_checked"]


@pytest.mark.parametrize("nranks,members", [
    (1, None),
    (4, None),
    (4, [2, 0, 3, 1]),   # members out of device order
], ids=["P1", "P4", "P4_permuted"])
def test_prepared_global_matches_checked(request, nranks, members):
    w = _world(request, nranks)
    eng = w.engine
    order = members or list(range(nranks))
    salt = 3 if members else 1

    def fn(accl, rank):
        cid = (accl.create_communicator(members) if members else 0)
        send = accl.create_buffer_like(_data(COUNT, rank, salt))
        recv = accl.create_buffer(COUNT, np.float32)
        accl.allreduce(send, recv, COUNT, ReduceFunction.SUM, comm_id=cid)
        return send, recv

    bufs = w.run(fn)
    want = np.sum([_data(COUNT, r, salt) for r in range(nranks)], axis=0)
    for _send, recv in bufs:
        np.testing.assert_allclose(recv.host, want, rtol=1e-5, atol=1e-5)

    plan = _plan_reading(eng, order[0], bufs[order[0]][0])
    assert plan["members"] == order
    assert plan["prepared"] is not None
    before = _checked(eng)
    x = eng._assemble_global(plan, None)
    assert _checked(eng) == before
    # the checked construction from the members' arrays, in member order
    shards = [bufs[g][0].dev for g in order]
    ref = jax.make_array_from_single_device_arrays(
        (nranks * COUNT,), plan["sharding"], shards)
    assert x.sharding == ref.sharding
    assert x.aval == ref.aval
    assert x.committed and ref.committed
    xs, rs = x.addressable_shards, ref.addressable_shards
    assert [s.device for s in xs] == [s.device for s in rs]
    assert [s.index for s in xs] == [s.index for s in rs]
    # shard identity: the global aliases each member's own buffer
    for got, exp in zip(xs, rs):
        assert (got.data.unsafe_buffer_pointer()
                == exp.data.unsafe_buffer_pointer())
    for g, s in zip(order, xs):
        assert s.device == eng.devices[g]
        assert (s.data.unsafe_buffer_pointer()
                == bufs[g][0].dev.unsafe_buffer_pointer())
    np.testing.assert_array_equal(np.asarray(x), np.asarray(ref))
    np.testing.assert_array_equal(
        np.asarray(x), np.concatenate([_data(COUNT, g, salt)
                                       for g in order]))


@pytest.mark.parametrize("nranks", [1, 4])
def test_hit_path_never_calls_make_array(request, monkeypatch, nranks):
    w = _world(request, nranks)
    eng = w.engine
    n = 64

    def make(accl, rank):
        send = accl.create_buffer_like(_data(n, rank, salt=5))
        recv = accl.create_buffer(n, np.float32)
        accl.allreduce(send, recv, n, ReduceFunction.SUM,
                       from_fpga=False, to_fpga=True)
        return send, recv

    bufs = w.run(make)   # builds the plans (send -> recv, recv -> recv2)

    def refuse(*_a, **_k):
        raise AssertionError("make_array_from_single_device_arrays on a "
                             "prepared plan's hit")

    monkeypatch.setattr(jax, "make_array_from_single_device_arrays", refuse)
    before = dict(eng.stats)

    def chain(accl, rank):
        send, recv = bufs[rank]
        for _ in range(3):
            accl.allreduce(send, recv, n, ReduceFunction.SUM,
                           from_fpga=True, to_fpga=True)
        recv.sync_from_device()
        return recv.host.copy()

    outs = w.run(chain)
    want = np.sum([_data(n, r, salt=5) for r in range(nranks)], axis=0)
    for got in outs:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    after = eng.stats
    assert after["lane_hlo"] - before["lane_hlo"] == 3
    assert after["assemble_checked"] == before["assemble_checked"]


def _sliced(accl, rank):
    """Operand at an element offset inside a larger buffer."""
    full = np.concatenate([np.zeros(8, np.float32), _data(COUNT, rank, 7)])
    big = accl.create_buffer_like(full)
    recv = accl.create_buffer(COUNT, np.float32)
    accl.allreduce(big.slice(8, 8 + COUNT), recv, COUNT,
                   ReduceFunction.SUM)
    want = np.sum([_data(COUNT, r, 7) for r in range(4)], axis=0)
    np.testing.assert_allclose(recv.host, want, rtol=1e-5, atol=1e-5)


def _widened(accl, rank):
    """A float16 operand widened to the float32 result's datapath."""
    x = _data(COUNT, rank, 9, np.float16)
    send = accl.create_buffer_like(x)
    recv = accl.create_buffer(COUNT, np.float32)
    accl.allreduce(send, recv, COUNT, ReduceFunction.SUM)
    want = np.sum([_data(COUNT, r, 9, np.float16).astype(np.float32)
                   for r in range(4)], axis=0)
    np.testing.assert_allclose(recv.host, want, rtol=1e-3, atol=1e-3)


def _stream(accl, rank):
    """Every member's operand from its kernel stream."""
    accl.device.push_krnl(_data(COUNT, rank, 11))
    recv = accl.create_buffer(COUNT, np.float32)
    accl.reduce(None, recv, COUNT, 1, ReduceFunction.SUM,
                stream_flags=StreamFlags.OP0_STREAM)
    if rank == 1:
        want = np.sum([_data(COUNT, r, 11) for r in range(4)], axis=0)
        np.testing.assert_allclose(recv.host, want, rtol=1e-5, atol=1e-5)


def _placeholder(accl, rank):
    """bcast: a non-root contributes its (longer) result buffer as a
    placeholder, sliced to the count."""
    root = 2
    x = _data(COUNT + 16, rank, 13)
    buf = accl.create_buffer_like(x)
    accl.bcast(buf, COUNT, root)
    np.testing.assert_array_equal(buf.host[:COUNT],
                                  _data(COUNT + 16, root, 13)[:COUNT])


@pytest.mark.parametrize("case", [_sliced, _widened, _stream, _placeholder],
                         ids=["sliced", "widened", "stream", "placeholder"])
def test_checked_path_taken_and_counted(world4, case):
    eng = world4.engine
    before = dict(eng.stats)
    world4.run(case)
    after = eng.stats
    lanes = sum(after[k] - before[k]
                for k in ("lane_hlo", "lane_ring", "lane_fused"))
    assert lanes == 1
    assert after["assemble_checked"] - before["assemble_checked"] == 1


@pytest.mark.parametrize("nranks", [1, 4])
def test_freed_buffer_completes_with_error_engine_live(request, nranks):
    w = _world(request, nranks)
    n = 32

    def fn(accl, rank):
        send = accl.create_buffer_like(_data(n, rank, salt=21))
        recv = accl.create_buffer(n, np.float32)
        accl.allreduce(send, recv, n, ReduceFunction.SUM,
                       from_fpga=True, to_fpga=True)
        send.free()
        with pytest.raises(ACCLError):
            accl.allreduce(send, recv, n, ReduceFunction.SUM,
                           from_fpga=True, to_fpga=True)
        # the engine stays live: a fresh call on new buffers succeeds
        again = accl.create_buffer_like(_data(n, rank, salt=22))
        accl.allreduce(again, recv, n, ReduceFunction.SUM)
        return recv.host.copy()

    outs = w.run(fn)
    want = np.sum([_data(n, r, salt=22) for r in range(nranks)], axis=0)
    for got in outs:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_freed_buffer_in_pinned_plan_raises(world1):
    """A plan still bound to a freed buffer (a pinned plan-ring slot)
    fails its assembly with an error instead of reading a dead array."""
    eng = world1.engine
    n = 16

    def fn(accl, rank):
        send = accl.create_buffer_like(_data(n, rank, salt=23))
        recv = accl.create_buffer(n, np.float32)
        accl.allreduce(send, recv, n, ReduceFunction.SUM)
        return send

    send = world1.run(fn)[0]
    plan = _plan_reading(eng, 0, send)
    send.free()
    assert not any(p is plan for p in eng._gang_plans.values())
    with pytest.raises(TypeError):
        eng._assemble_global(plan, None)


def test_ring_threshold_change_builds_new_plan(world4):
    eng = world4.engine
    n = 64
    bufs = world4.run(lambda accl, rank: (
        accl.create_buffer_like(_data(n, rank, salt=31)),
        accl.create_buffer(n, np.float32)))

    def call(accl, rank):
        send, recv = bufs[rank]
        accl.allreduce(send, recv, n, ReduceFunction.SUM)
        return recv.host.copy()

    saved = eng.ring_threshold_bytes
    try:
        world4.run(call)
        hlo = _plan_reading(eng, 0, bufs[0][0])
        eng.ring_threshold_bytes = 0
        before = dict(eng.stats)
        outs = world4.run(call)
        after = eng.stats
    finally:
        eng.ring_threshold_bytes = saved
    plans = [p for p in eng._gang_plans.values()
             if (0, bufs[0][0].address) in p["opnd_addrs"]]
    assert len(plans) == 2 and any(p is hlo for p in plans)
    ring = next(p for p in plans if p is not hlo)
    assert (hlo["lane"], ring["lane"]) == ("lane_hlo", "lane_ring")
    assert ring["prepared"] is not None
    assert after["lane_ring"] - before["lane_ring"] == 1
    assert after["assemble_checked"] == before["assemble_checked"]
    want = np.sum([_data(n, r, salt=31) for r in range(4)], axis=0)
    for got in outs:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_batched_lane_bit_equal_to_sequential(world4):
    """K independent gangs in one batched dispatch give bit for bit what
    the same gangs give one at a time."""
    eng = world4.engine
    K, n = 3, 80
    create = eng.create_buffer
    srcs = [[create(r, n, np.float32) for r in range(4)] for _ in range(K)]
    for k in range(K):
        for r in range(4):
            srcs[k][r].set_dev_range(
                0, jax.device_put(_data(n, r, 40 + k), eng.devices[r]))
    dst_b = [[create(r, n, np.float32) for r in range(4)] for _ in range(K)]
    dst_s = [[create(r, n, np.float32) for r in range(4)] for _ in range(K)]

    def gang(k, dst):
        return {r: (CCLOCall(scenario=Operation.allreduce, count=n,
                             function=int(ReduceFunction.SUM),
                             addr_0=srcs[k][r].address,
                             addr_2=dst[k][r].address),
                    Request(sync=True), None)
                for r in range(4)}

    before = dict(eng.stats)
    items = []
    for k in range(K):
        g = gang(k, dst_b)
        items.append((Operation.allreduce, 0, g,
                      eng._gang_plan(Operation.allreduce, 0, g)))
    assert all(it[3]["prepared"] is not None for it in items)
    eng._exec_gang_batch(items)
    for k in range(K):
        eng._exec_gang(int(Operation.allreduce), 0, gang(k, dst_s))
    for it in items:
        for _c, req, _k in it[2].values():
            assert req.done and req.retcode == 0
    assert eng.stats["assemble_checked"] == before["assemble_checked"]
    assert eng.stats["lane_hlo"] - before["lane_hlo"] == 2 * K
    for k in range(K):
        want = np.sum([_data(n, r, 40 + k) for r in range(4)], axis=0)
        for r in range(4):
            got_b = np.asarray(dst_b[k][r].dev)
            assert np.array_equal(got_b, np.asarray(dst_s[k][r].dev))
            np.testing.assert_allclose(got_b, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# link twin: per-plan deltas against the per-member accounting
# ---------------------------------------------------------------------------
_LINK_FIELDS = ("tx_msgs", "tx_bytes", "comp_tx_bytes", "rx_msgs",
                "rx_bytes")


def _per_member_accounting(eng, plan, gang, links, counters):
    """The link accounting as every member's two link updates per
    execution: the reference the precomputed deltas must equal."""
    op = plan["fn_args"][1]
    members = plan["members"]
    P = len(members)
    nbytes = plan["in_len"] * np.dtype(plan["dtype"]).itemsize
    if P < 2 or nbytes <= 0:
        return

    def add(src, peer, **counts):
        row = links.setdefault((src, peer), {})
        for k, v in counts.items():
            row[k] = row.get(k, 0) + int(v)

    name = Operation(op).name
    ratio = eng._wire_ratio(plan["fn_args"][6])
    if ratio < 1.0:
        per_count = nbytes // (
            P if name in ("scatter", "reduce_scatter", "alltoall") else 1)
        logical = int(per_count * obs_metrics.payload_factor(name, P))
        counters["compressed_tx_logical_bytes"] += logical
        counters["compressed_tx_bytes"] += int(logical * ratio)
    if name in ("allreduce", "allgather", "reduce_scatter", "alltoall"):
        if name == "allgather":
            nbytes *= P
        per_link = int(nbytes * obs_metrics.busbw_factor(name, P))
        hops = 2 * (P - 1) if name == "allreduce" else P - 1
        comp = int(per_link * ratio) if ratio < 1.0 else 0
        for i, src in enumerate(members):
            add(src, members[(i + 1) % P], tx_msgs=hops, tx_bytes=per_link,
                comp_tx_bytes=comp)
            add(src, members[(i - 1) % P], rx_msgs=hops, rx_bytes=per_link)
    elif name in ("bcast", "scatter", "gather", "reduce"):
        root_local = next(iter(gang.values()))[0].root_src_dst
        root = members[root_local] if root_local < P else members[0]
        to_root = name in ("gather", "reduce")
        per_link = nbytes // P if name == "scatter" else nbytes
        comp = int(per_link * ratio) if ratio < 1.0 else 0
        for m in members:
            if m == root:
                continue
            a, b = (m, root) if to_root else (root, m)
            add(a, b, tx_msgs=1, tx_bytes=per_link, comp_tx_bytes=comp)
            add(b, a, rx_msgs=1, rx_bytes=per_link)


def test_link_totals_match_per_member_accounting(monkeypatch):
    seen = []
    orig_run = TpuEngine._run_collective

    def run_collective(self, op, comm_id, gang):
        if op != Operation.barrier:
            seen.append((self._gang_plan(op, comm_id, gang), gang))
        return orig_run(self, op, comm_id, gang)

    monkeypatch.setattr(TpuEngine, "_run_collective", run_collective)
    n = 128
    with TpuWorld(4) as w:
        eng = w.engine

        def seq(accl, rank):
            a = accl.create_buffer_like(_data(n * 4, rank, 51))
            b = accl.create_buffer(n * 4, np.float32)
            c = accl.create_buffer(n, np.float32)
            for _ in range(2):
                accl.allreduce(a, b, n * 4, ReduceFunction.SUM)
            accl.allgather(c, b, n)
            accl.reduce_scatter(a, c, n, ReduceFunction.SUM)
            accl.bcast(c, n, 2)
            accl.reduce(c, b, n, 1, ReduceFunction.SUM)
            accl.scatter(a, c, n, 3)
            accl.gather(c, b, n, 0)
            for _ in range(2):
                accl.allreduce(a, b, n * 4, ReduceFunction.SUM,
                               compress_dtype=DataType.int8)
            accl.allreduce(a, b, n * 4, ReduceFunction.SUM,
                           compress_dtype=DataType.bfloat16)
            accl.barrier()
            return True

        assert all(w.run(seq))
        assert len(seen) == 11
        links: dict = {}
        counters = {"compressed_tx_logical_bytes": 0,
                    "compressed_tx_bytes": 0}
        for plan, gang in seen:
            _per_member_accounting(eng, plan, gang, links, counters)
        assert counters["compressed_tx_bytes"] > 0
        got = {}
        for (src, _comm, peer), row in eng._links.items():
            kept = {f: row[f] for f in _LINK_FIELDS if f in row}
            if kept:
                got[(src, peer)] = kept
        assert got == links
        stats = eng.stats
        for k, v in counters.items():
            assert stats[k] == v
