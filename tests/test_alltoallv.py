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
