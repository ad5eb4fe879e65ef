"""ACCL.alltoallv with device-resident per-peer counts, on both
backends, against numpy: the TPU engine on four CPU devices (the packed
all_to_all form: XLA:CPU has no ragged-all-to-all) and the emulator's
native engine.  Received rows must match bit for bit; rows past their
sum are unspecified and not compared."""
import ml_dtypes
import numpy as np
import pytest

P = 4

#: name -> (row, send capacity in rows, dtype, P x P counts: rows i -> j)
CASES = {
    "skewed": (3, 10, np.float32, [[1, 3, 0, 2], [4, 0, 1, 1],
                                   [0, 2, 2, 5], [3, 3, 3, 0]]),
    "zeros": (2, 6, np.float32, [[0] * 4, [0, 2, 0, 0], [0] * 4,
                                 [0] * 4]),
    "all_to_one": (5, 8, np.float32, [[0, 0, 8, 0]] * 4),
    "bf16_rows": (64, 12, ml_dtypes.bfloat16, [[2, 0, 7, 3], [1, 1, 1, 1],
                                               [0, 0, 0, 12], [5, 4, 2, 1]]),
    "int32": (7, 9, np.int32, [[9, 0, 0, 0], [0, 1, 2, 3], [2, 2, 2, 2],
                               [0, 0, 0, 0]]),
    # large rows: the emulator's per-peer rendezvous path
    "large": (1024, 4, np.float32, [[1, 2, 0, 1], [0, 0, 4, 0],
                                    [2, 1, 1, 0], [1, 1, 1, 1]]),
}


def _sends(row, cap, dtype, seed):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.int32:
        return [rng.integers(-1000, 1000, cap * row).astype(np.int32)
                for _ in range(P)]
    return [rng.standard_normal(cap * row).astype(dtype) for _ in range(P)]


def _expected(sends, counts, row):
    """Each rank's received rows and counts, with numpy alone."""
    out = []
    for j in range(P):
        parts = [sends[i].reshape(-1, row)[counts[i, :j].sum():
                                           counts[i, :j + 1].sum()]
                 for i in range(P)]
        out.append((np.concatenate(parts), counts[:, j]))
    return out


def _world(backend):
    if backend == "tpu":
        from accl_tpu.backends.tpu import TpuWorld

        return TpuWorld(P)
    from accl_tpu.backends.emu import EmuWorld

    return EmuWorld(nranks=P)


@pytest.mark.parametrize("backend", ["tpu", "emu"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_alltoallv_matches_numpy(backend, case):
    row, cap, dtype, counts = CASES[case]
    counts = np.asarray(counts, np.int32)
    sends = _sends(row, cap, dtype, seed=len(case))

    def fn(a, r):
        s = a.create_buffer(cap * row, dtype)
        s.host[:] = sends[r]
        recv = a.create_buffer(P * cap * row, dtype)
        sc = a.create_buffer(P, np.int32)
        sc.host[:] = counts[r]
        rc = a.create_buffer(P, np.int32)
        a.alltoallv(s, recv, sc, rc, row=row)
        return recv.host.copy(), rc.host.copy()

    with _world(backend) as world:
        got = world.run(fn)
    for r, (want, want_counts) in enumerate(_expected(sends, counts, row)):
        recv, rc = got[r]
        assert rc.tolist() == want_counts.tolist()
        n = want.shape[0]
        assert np.array_equal(recv.reshape(-1, row)[:n].view(np.uint8),
                              want.view(np.uint8)), (backend, case, r)


@pytest.mark.parametrize("case", sorted(CASES))
def test_alltoallv_row_form_equals_flat(case):
    """On the XLA:CPU fallback, the same exchange from row-form sends
    ([rows, row] device arrays) and from flat host-written ones: the
    receives hold the same rows and are rebound in row form."""
    import jax.numpy as jnp

    from accl_tpu.backends.tpu import TpuWorld

    row, cap, dtype, counts = CASES[case]
    counts = np.asarray(counts, np.int32)
    sends = _sends(row, cap, dtype, seed=len(case))

    def fn(a, r):
        sc = a.create_buffer(P, np.int32)
        sc.host[:] = counts[r]
        sc.sync_to_device()
        got = []
        for rows in (False, True):
            s = a.create_buffer(cap * row, dtype)
            if rows:
                s.set_dev_range(0, jnp.asarray(sends[r].reshape(cap, row)))
            else:
                s.host[:] = sends[r]
                s.sync_to_device()
            recv = a.create_buffer(P * cap * row, dtype)
            rc = a.create_buffer(P, np.int32)
            a.alltoallv(s, recv, sc, rc, row=row, from_fpga=True,
                        to_fpga=True)
            got.append((np.asarray(recv.dev), np.asarray(rc.dev),
                        s.dev.shape))
        return got

    with TpuWorld(P) as world:
        got = world.run(fn)
        assert world.engine.stats["a2av_flat_operands"] == P
    for r, (want, want_counts) in enumerate(_expected(sends, counts, row)):
        n = want.shape[0]
        (flat, flat_rc, _), (rows, rows_rc, _) = got[r]
        assert flat.shape == rows.shape == (P * cap, row)
        assert flat_rc.tolist() == rows_rc.tolist() == want_counts.tolist()
        assert np.array_equal(rows[:n].view(np.uint8),
                              flat[:n].view(np.uint8)), (case, r)
        assert np.array_equal(rows[:n].view(np.uint8), want.view(np.uint8))
        assert got[r][0][2] == got[r][1][2] == (cap, row)


def test_flat_send_is_reshaped_once_and_counted():
    """A send written flat ends in row form after its exchange and
    counts one ``a2av_flat_operands``; a second call on it counts none,
    and a flat write counts again."""
    import jax.numpy as jnp

    from accl_tpu.backends.tpu import TpuWorld

    row, cap = 4, 6
    counts = np.array([[1, 2, 3, 0]] * P, np.int32)

    def fn(a, r):
        s = a.create_buffer(cap * row, np.float32)
        s.set_dev_range(0, jnp.arange(cap * row, dtype=np.float32) + r)
        recv = a.create_buffer(P * cap * row, np.float32)
        sc = a.create_buffer(P, np.int32)
        sc.set_dev_range(0, jnp.asarray(counts[r]))
        rc = a.create_buffer(P, np.int32)
        kw = dict(row=row, from_fpga=True, to_fpga=True)
        seen = [s.dev.shape]
        for write in (False, False, True):
            if write:
                s.set_dev_range(0, jnp.arange(cap * row, dtype=np.float32))
            a.alltoallv(s, recv, sc, rc, **kw)
            a.barrier()
            seen.append((s.dev.shape,
                         world.engine.stats["a2av_flat_operands"]))
            a.barrier()
        return seen

    with TpuWorld(P) as world:
        got = world.run(fn)
    for seen in got:
        assert seen == [(cap * row,), ((cap, row), P), ((cap, row), P),
                        ((cap, row), 2 * P)]


#: name -> what is done to a buffer of 12 float32 that holds [3, 4] rows
ROW_FORM_OPS = ("partial_write", "host_round_trip", "slice",
                "allreduce_after_reform", "replay_after_reform",
                "flat_write")


@pytest.mark.parametrize("what", ROW_FORM_OPS)
def test_row_form_buffer(what):
    """A buffer whose device array holds rows: element writes, the host
    mirror and slices act on its flat view and keep the rows, and a
    collective's cached plan that bound it flat is built anew."""
    import jax.numpy as jnp

    from accl_tpu.backends.tpu import TpuWorld
    from accl_tpu.constants import ReduceFunction

    base = np.arange(12, dtype=np.float32)

    def fn(a, r):
        b = a.create_buffer(12, np.float32)
        b.set_dev_range(0, jnp.asarray(base.reshape(3, 4)))
        assert b.dev.shape == (3, 4)
        want = base.copy()
        if what == "partial_write":
            b.set_dev_range(5, jnp.asarray([-1.0, -2.0], np.float32))
            want[5:7] = [-1, -2]
        elif what == "host_round_trip":
            b.sync_from_device()
            assert b.host.tolist() == base.tolist()
            b.host[2] = 40.0
            b.sync_to_device()
            want[2] = 40
        elif what == "slice":
            sl = b.slice(4, 8)
            sl.host[:] = [7, 7, 7, 7]
            sl.sync_to_device()
            sl.host[:] = 0
            sl.sync_from_device()
            assert sl.host.tolist() == [7, 7, 7, 7]
            want[4:8] = 7
        elif what == "allreduce_after_reform":
            flat = a.create_buffer(12, np.float32)
            out = a.create_buffer(12, np.float32)
            kw = dict(from_fpga=True, to_fpga=True)
            flat.set_dev_range(0, jnp.asarray(base))
            a.allreduce(flat, out, 12, ReduceFunction.SUM, **kw)
            a.barrier()
            checked = world.engine.stats["assemble_checked"]
            a.barrier()
            # the cached plan bound `flat` as a 1-D operand; now it holds
            # rows: the plan is built anew, and reads it through the
            # checked assembly's flat view
            flat.set_dev_range(0, jnp.asarray(2 * base).reshape(3, 4))
            a.allreduce(flat, out, 12, ReduceFunction.SUM, **kw)
            a.barrier()
            assert world.engine.stats["assemble_checked"] == checked + 1
            assert np.asarray(out.dev).reshape(-1).tolist() == \
                (2 * P * base).tolist()
            a.allreduce(b, out, 12, ReduceFunction.SUM, **kw)
            want = P * base
            b = out
        elif what == "replay_after_reform":
            flat = a.create_buffer(12, np.float32)
            out = a.create_buffer(12, np.float32)
            flat.set_dev_range(0, jnp.asarray(base))
            plan = a.capture_plan(lambda x: x.allreduce(
                flat, out, 12, ReduceFunction.SUM, from_fpga=True,
                to_fpga=True))
            plan.replay()
            a.barrier()
            checked = world.engine.stats["assemble_checked"]
            # an armed ring's slot that bound `flat` flat is re-planned
            flat.set_dev_range(0, jnp.asarray(3 * base).reshape(3, 4))
            a.barrier()
            plan.replay()
            a.barrier()
            assert world.engine.stats["assemble_checked"] == checked + 1
            b, want = out, 3 * P * base
        elif what == "flat_write":
            b.set_dev_range(0, jnp.asarray(base + 1))
            assert b.dev.shape == (12,)
            want = base + 1
        if what in ("partial_write", "host_round_trip", "slice"):
            assert b.dev.shape == (3, 4)
        b.sync_from_device()
        return b.host.tolist() == want.tolist()

    with TpuWorld(P) as world:
        assert all(world.run(fn))


def test_alltoallv_counts_stay_on_device_and_links_carry_no_bytes():
    """Device-resident operands: the host copies of the counts are never
    written, and the link twin records one message per peer pair with
    no bytes (the host does not know them)."""
    from accl_tpu.backends.tpu import TpuWorld

    row, cap = 4, 6
    counts = np.array([[1, 2, 3, 0]] * P, np.int32)

    def fn(a, r):
        import jax

        s = a.create_buffer(cap * row, np.float32)
        s.set_dev_range(0, jax.numpy.arange(cap * row, dtype=np.float32))
        recv = a.create_buffer(P * cap * row, np.float32)
        sc = a.create_buffer(P, np.int32)
        sc.set_dev_range(0, jax.numpy.asarray(counts[r]))
        rc = a.create_buffer(P, np.int32)
        a.alltoallv(s, recv, sc, rc, row=row, from_fpga=True, to_fpga=True)
        return rc.host.copy(), np.asarray(rc.dev)

    with TpuWorld(P) as world:
        got = world.run(fn)
        assert world.engine.stats["assemble_checked"] == 0
        links = world.link_stats()
    for r, (host, dev) in enumerate(got):
        assert host.tolist() == [0] * P           # not synced back
        assert dev.tolist() == counts[:, r].tolist()
    for r in range(P):
        rows = {row["peer"]: row for row in links[r]}
        for peer in range(P):
            if peer == r:
                continue
            assert rows[peer]["tx_msgs"] == 1 and rows[peer]["rx_msgs"] == 1
            assert rows[peer]["tx_bytes"] == 0 and rows[peer]["rx_bytes"] == 0


def test_alltoallv_rejects_bad_counts_buffers():
    from accl_tpu.backends.tpu import TpuWorld
    from accl_tpu.constants import ACCLError

    def fn(a, r):
        s = a.create_buffer(8, np.float32)
        recv = a.create_buffer(32, np.float32)
        wrong = a.create_buffer(P, np.float32)
        rc = a.create_buffer(P, np.int32)
        with pytest.raises(ACCLError, match="int32"):
            a.alltoallv(s, recv, wrong, rc, row=2)
        with pytest.raises(ACCLError, match="whole rows"):
            a.alltoallv(s, recv, rc, rc, row=3)
        return True

    with TpuWorld(P) as world:
        assert all(world.run(fn))


@pytest.mark.parametrize("addr", [0, 0x1234, 0xFFFFFFFF, 0x7_0000_0040,
                                  (1 << 64) - 64])
def test_count_result_address_round_trips_the_descriptor(addr):
    from accl_tpu.constants import CCLOCall, Operation

    call = CCLOCall(scenario=Operation.alltoallv, count=3,
                    **CCLOCall.addr_3_words(addr))
    assert call.addr_3 == addr
    words = call.to_words()
    assert words[3] | (words[4] << 32) == addr
    assert all(0 <= w < 1 << 32 for w in words)
