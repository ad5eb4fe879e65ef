"""Pallas kernel tests (interpret mode on the CPU rung; the same code
compiles for TPU hardware).  Reference plugin coverage: reduce_ops,
hp_compression, ring schedules, vadd_put fusion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from accl_tpu.ops import (
    compress_cast,
    decompress_cast,
    fused_matmul_allreduce,
    pallas_add,
    pallas_max,
    ring_all_gather_pallas,
    ring_all_reduce_pallas,
    ring_reduce_scatter_pallas,
)
from accl_tpu.ops.fused import pallas_matmul
from accl_tpu.parallel import make_mesh
from accl_tpu.utils.platform import pallas_interpret

INTERP = pallas_interpret()
ON_TPU = not INTERP


def _rand(shape, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


# ---------------------------------------------------------------------------
# reduce_ops lanes (reference: reduce_ops.cpp:31-107)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_pallas_add_max(dtype):
    a = (_rand(1000, np.float32, 1) * 100).astype(dtype)
    b = (_rand(1000, np.float32, 2) * 100).astype(dtype)
    out = pallas_add(jnp.asarray(a), jnp.asarray(b), interpret=INTERP)
    np.testing.assert_allclose(np.asarray(out), a + b, rtol=1e-6)
    out = pallas_max(jnp.asarray(a), jnp.asarray(b), interpret=INTERP)
    np.testing.assert_array_equal(np.asarray(out), np.maximum(a, b))


def test_pallas_add_ragged_tail():
    # non-multiple of the 8x128 tile (segmentation boundary analog)
    a, b = _rand(1031, seed=3), _rand(1031, seed=4)
    out = pallas_add(jnp.asarray(a), jnp.asarray(b), interpret=INTERP)
    np.testing.assert_allclose(np.asarray(out), a + b, rtol=1e-6)


# ---------------------------------------------------------------------------
# compression lanes (reference: hp_compression.cpp:70-144)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_compress_roundtrip(dtype):
    x = _rand(4096, seed=5)
    c = compress_cast(jnp.asarray(x), dtype, interpret=INTERP)
    assert c.dtype == dtype
    d = decompress_cast(c, jnp.float32, interpret=INTERP)
    tol = 2e-3 if dtype == jnp.float16 else 2e-2
    np.testing.assert_allclose(np.asarray(d), x, rtol=tol, atol=tol)


def test_fp16_lane_bit_exact():
    # the fp16 lane converts in integer arithmetic (no f16 vectors on
    # v5e): every f16 value, every halfway point between neighbours
    # (round to nearest even), subnormals, overflow and non-finites
    # must match numpy's cast bit for bit
    h = np.arange(0, 0x7C00, dtype=np.uint16).view(np.float16)
    h32 = h.astype(np.float32)
    mid = ((h32[:-1].astype(np.float64) + h32[1:]) / 2).astype(np.float32)
    specials = np.array([np.inf, -np.inf, 65519.99, 65520.0, 1e30,
                         2.0 ** -25, 3 * 2.0 ** -26, -0.0], np.float32)
    x = np.concatenate([h32, -h32, mid, -mid, specials])
    got = np.asarray(compress_cast(jnp.asarray(x), jnp.float16,
                                   interpret=INTERP))
    with np.errstate(over="ignore"):
        want = x.astype(np.float16)
    np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))
    assert np.isnan(np.asarray(compress_cast(
        jnp.full((4,), np.nan, jnp.float32), jnp.float16,
        interpret=INTERP))).all()

    allh = np.arange(0, 0x10000, dtype=np.uint16).view(np.float16)
    back = np.asarray(decompress_cast(jnp.asarray(allh), jnp.float32,
                                      interpret=INTERP))
    want = allh.astype(np.float32)
    fin = ~np.isnan(want)
    np.testing.assert_array_equal(back[fin].view(np.uint32),
                                  want[fin].view(np.uint32))
    assert np.isnan(back[~fin]).all()


@pytest.mark.skipif(not ON_TPU, reason="stochastic rounding needs the TPU PRNG")
def test_stochastic_round_tpu():
    x = jnp.full((4096,), 1.0 + 2.0 ** -12, jnp.float32)
    c = compress_cast(x, jnp.bfloat16, stochastic=True, seed=7)
    vals = np.unique(np.asarray(c.astype(jnp.float32)))
    assert len(vals) == 2  # rounds both ways


# ---------------------------------------------------------------------------
# fused compute + collective (reference: vadd_put.cpp:23-86)
# ---------------------------------------------------------------------------
def test_pallas_matmul():
    x, w = _rand((256, 128), seed=6), _rand((128, 256), seed=7)
    out = pallas_matmul(jnp.asarray(x), jnp.asarray(w), interpret=INTERP)
    np.testing.assert_allclose(np.asarray(out), x @ w, rtol=1e-4, atol=1e-4)


def test_fused_matmul_allreduce():
    P_ = 4
    if len(jax.devices()) < P_:
        pytest.skip("needs a 4-device mesh")
    mesh = make_mesh(tp=P_)
    x = _rand((8, P_ * 16), seed=8)
    w = _rand((P_ * 16, 32), seed=9)
    xs = x.reshape(8, P_, 16).transpose(1, 0, 2)  # K-shards
    ws = w.reshape(P_, 16, 32)

    def body(xb, wb):
        return fused_matmul_allreduce(xb[0], wb[0], axis="tp",
                                      use_pallas=False)[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("tp", None, None),) * 2,
                  out_specs=P("tp", None, None))
    out = jax.jit(f)(jnp.asarray(xs), jnp.asarray(ws))
    np.testing.assert_allclose(np.asarray(out)[0], x @ w, rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# ring collectives over remote DMA (reference ring schedules; run under
# the Pallas TPU interpreter on CPU)
# ---------------------------------------------------------------------------
NR = 4


def _ring_mesh():
    if len(jax.devices()) < NR:
        pytest.skip("needs a 4-device mesh")
    return make_mesh(dp=NR)


def test_ring_all_gather_pallas():
    mesh = _ring_mesh()
    d = _rand((NR, 8, 128), seed=10)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None, None)))

    def body(xb):
        return ring_all_gather_pallas(xb[0], "dp", interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None, None),
                  out_specs=P("dp", None, None, None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    for r in range(NR):
        np.testing.assert_array_equal(out[r], d)


def test_ring_reduce_scatter_pallas():
    mesh = _ring_mesh()
    d = _rand((NR, NR, 8, 128), seed=11)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None, None, None)))

    def body(xb):
        return ring_reduce_scatter_pallas(xb[0], "dp", interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None, None, None),
                  out_specs=P("dp", None, None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    exp = d.sum(axis=0)
    for r in range(NR):
        np.testing.assert_allclose(out[r], exp[r], rtol=1e-4, atol=1e-4)


def test_ring_all_reduce_pallas():
    mesh = _ring_mesh()
    d = _rand((NR, NR * 8, 128), seed=12)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None, None)))

    def body(xb):
        return ring_all_reduce_pallas(xb[0], "dp", interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None, None),
                  out_specs=P("dp", None, None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    exp = d.sum(axis=0)
    for r in range(NR):
        np.testing.assert_allclose(out[r], exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", [96, 1000])  # multi-segment + ragged tail
def test_ring_all_reduce_segmented(n):
    from accl_tpu.ops.ring import ring_all_reduce_segmented

    mesh = _ring_mesh()
    d = _rand((NR, n), seed=13)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None)))

    def body(xb):
        return ring_all_reduce_segmented(xb[0], "dp", seg_elems=32,
                                         interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    exp = d.sum(axis=0)
    for r in range(NR):
        np.testing.assert_allclose(out[r], exp, rtol=1e-4, atol=1e-4)


def test_ring_all_gather_segmented_interleaving():
    from accl_tpu.ops.ring import ring_all_gather_segmented

    mesh = _ring_mesh()
    n = 50  # 2 even segments of 25
    d = _rand((NR, n), seed=14)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None)))

    def body(xb):
        return ring_all_gather_segmented(xb[0], "dp", seg_elems=32,
                                         interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    exp = d.reshape(-1)  # rank-major whole-payload layout
    for r in range(NR):
        np.testing.assert_array_equal(out[r], exp)


def test_ring_reduce_scatter_segmented():
    from accl_tpu.ops.ring import ring_reduce_scatter_segmented

    mesh = _ring_mesh()
    n = 70  # ragged: 3 even segments of 24 per chunk, 2 padded
    d = _rand((NR, NR * n), seed=15)
    x = jax.device_put(d, NamedSharding(mesh, P("dp", None)))

    def body(xb):
        return ring_reduce_scatter_segmented(xb[0], "dp", seg_elems=32,
                                             interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None), check_vma=False)
    out = np.asarray(jax.jit(f)(x))
    exp = d.reshape(NR, NR, n).sum(axis=0)  # [rank chunk, n]
    for r in range(NR):
        np.testing.assert_allclose(out[r], exp[r], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nbytes", [9 << 20, (16 << 20) + 4, 128 << 20])
def test_segmented_ring_padding_under_a_tile_per_segment(nbytes):
    # a payload just past a segment boundary is split evenly, not
    # padded to a second whole segment (9 MiB fp32 on 4 ranks: two
    # 4.5 MiB segments, not two 8 MiB ones)
    from accl_tpu.ops import ring as R

    P4, n = 4, nbytes // 4
    seg_max = P4 * R.RING_CHUNK_BYTES // 4
    seg = R._seg_len(n, seg_max)
    nseg = -(-n // seg)
    width = R._round_up(seg, P4 * R._tile(np.float32))
    assert nseg == -(-n // seg_max) and seg <= seg_max
    assert nseg * width - n < nseg * P4 * R._tile(np.float32)


# ---------------------------------------------------------------------------
# single-device virtual self-ring (ring_size override): the compiled
# semaphore/remote-DMA code path executable on ONE chip — the
# reference's execute-the-artifact rung (cclo_sim.cpp:57-559).  On the
# CPU rung these run under the interpreter; on the bench chip they run
# COMPILED (bench.py's selfring stage and the chip worker's test leg).
# ---------------------------------------------------------------------------
def _one_dev_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("r",))


def _smap1(f):
    mesh = _one_dev_mesh()
    return jax.jit(shard_map(f, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))


def test_selfring_all_gather():
    V = 4
    d = _rand((8, 128), seed=20)
    f = _smap1(lambda v: ring_all_gather_pallas(v, "r", ring_size=V,
                                                interpret=INTERP))
    out = np.asarray(f(jnp.asarray(d)))
    # every virtual rank is this device: out = x tiled V times
    np.testing.assert_array_equal(out, np.broadcast_to(d, (V, 8, 128)))


def test_selfring_reduce_scatter():
    V = 4
    d = _rand((V, 8, 128), seed=21)
    f = _smap1(lambda v: ring_reduce_scatter_pallas(v, "r", ring_size=V,
                                                    interpret=INTERP))
    out = np.asarray(f(jnp.asarray(d)))
    # each hop's incoming partial is our own accumulator: full fold
    np.testing.assert_allclose(out, d.sum(axis=0), rtol=1e-4, atol=1e-4)


def test_selfring_all_reduce():
    V = 4
    d = _rand((V * 8, 128), seed=22)
    f = _smap1(lambda v: ring_all_reduce_pallas(v, "r", ring_size=V,
                                                interpret=INTERP))
    out = np.asarray(f(jnp.asarray(d)))
    exp = np.broadcast_to(d.reshape(V, 8, 128).sum(axis=0),
                          (V, 8, 128)).reshape(V * 8, 128)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)


def test_selfring_requires_single_member_axis():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = make_mesh(dp=2)
    d = _rand((8, 128), seed=23)

    def body(xb):
        return ring_all_gather_pallas(xb[0], "dp", ring_size=4,
                                      interpret=INTERP)[None]

    f = shard_map(body, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None, None, None), check_vma=False)
    with pytest.raises(ValueError, match="ring_size"):
        jax.jit(f)(jax.device_put(
            np.broadcast_to(d, (2, 8, 128)).copy(),
            NamedSharding(mesh, P("dp", None, None))))
