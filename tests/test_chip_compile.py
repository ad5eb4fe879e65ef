"""Compile rehearsal: the main path's kernels compiled for a described
v5e:2x2 topology with no chip attached.

The TPU compiler refuses what interpret mode accepts — a row slice of a
VMEM buffer that is not tile-aligned, a kernel whose scratch overflows
VMEM — so each test compiles a kernel at the size the driver or its SPMD
users run it, asserts the Mosaic kernel is in the program
(``tpu_custom_call``) and that the program fits a v5e chip's 16 GB.

Only one process may load the TPU library, and it keeps it until it
exits: the topology is described inside a module-scoped fixture (never
at import), every compile runs in this process, and these tests stay in
this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip
MI = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring4(topo):
    return Mesh(topo.devices, ("rank",))


def _compile(fn, *args, kernel=True):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    if kernel:
        assert "tpu_custom_call" in text, text[:2000]
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem
    return text


def _sharded(ring4, n, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((4 * n,), dtype,
                                sharding=NamedSharding(ring4, P("rank")))


def _smap4(ring4, body):
    return jax.shard_map(body, mesh=ring4, in_specs=P("rank"),
                         out_specs=P("rank"), check_vma=False)


def test_pallas_add_64mi(one_chip):
    from accl_tpu.ops.reduce_ops import pallas_add

    x = jax.ShapeDtypeStruct((64 * MI // 128, 128), jnp.float32,
                             sharding=one_chip)
    _compile(lambda a, b: pallas_add(a, b), x, x)


@pytest.mark.parametrize("lane", ["float16", "bfloat16", "stochastic"])
def test_compression_casts_64mi(one_chip, lane):
    # v5e has no f16 vector unit: the fp16 lane converts in integer
    # arithmetic, which must fit VMEM too
    from accl_tpu.ops.compression import compress_cast, decompress_cast

    def roundtrip(v):
        if lane == "stochastic":
            return compress_cast(v, jnp.bfloat16, stochastic=True, seed=7)
        return decompress_cast(compress_cast(v, jnp.dtype(lane)),
                               jnp.float32)

    _compile(roundtrip, jax.ShapeDtypeStruct((64 * MI,), jnp.float32,
                                             sharding=one_chip))


@pytest.mark.parametrize("op", ["all_gather", "all_reduce"])
def test_selfring_8_ranks(topo, one_chip, op):
    from accl_tpu.ops import ring as R

    V, rows = 8, 4096  # 2 MiB fp32 chunks
    mesh = Mesh(np.array(topo.devices[:1]), ("r",))
    kern = {"all_gather": R.ring_all_gather_pallas,
            "all_reduce": R.ring_all_reduce_pallas}[op]
    shape = (rows, 128) if op == "all_gather" else (V * rows, 128)
    fn = jax.shard_map(lambda v: kern(v, "r", ring_size=V), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
    _compile(fn, jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("n", [4104, MI, 4 * MI, 9 * MI // 4],
                         ids=["ragged", "1Mi", "4Mi", "9MiB"])
@pytest.mark.parametrize("op", ["allreduce", "allgather", "reduce_scatter"])
def test_segmented_ring_4_chips(ring4, op, n):
    # the lowering the TPU backend picks for these collectives at or
    # above the ring threshold on more than one chip
    from accl_tpu.ops import ring as R

    body = {"allreduce": R.ring_all_reduce_segmented,
            "allgather": R.ring_all_gather_segmented,
            "reduce_scatter": R.ring_reduce_scatter_segmented}[op]
    _compile(_smap4(ring4, lambda v: body(v, "rank")), _sharded(ring4, n))


def test_int8_quantized_allreduce_4_chips(ring4):
    from accl_tpu.ops.quantized import quantized_all_reduce

    text = _compile(_smap4(ring4, lambda v: quantized_all_reduce(
        v, "rank", error_feedback=True)), _sharded(ring4, 4 * MI),
        kernel=False)
    assert "collective-permute" in text


def test_chunked_ring_all_reduce_4_chips(ring4):
    from accl_tpu.ops.fused import chunked_ring_all_reduce

    text = _compile(_smap4(ring4, lambda v: chunked_ring_all_reduce(
        v, "rank")), _sharded(ring4, 4 * MI), kernel=False)
    assert "collective-permute" in text


def test_fused_matmul_allreduce_pallas_4096(ring4):
    # tensor-parallel contraction: x [4096, 4096] bf16 with K sharded
    # over the 4 chips, w [4096, 4096] with its K rows sharded
    from accl_tpu.ops.fused import fused_matmul_allreduce_pallas

    fn = jax.shard_map(
        lambda x, w: fused_matmul_allreduce_pallas(x, w, "rank"),
        mesh=ring4, in_specs=(P(None, "rank"), P("rank", None)),
        out_specs=P(), check_vma=False)
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16,
                             sharding=NamedSharding(ring4, P(None, "rank")))
    w = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16,
                             sharding=NamedSharding(ring4, P("rank", None)))
    _compile(fn, x, w)


@pytest.mark.parametrize("op", ["bcast", "scatter", "gather", "reduce",
                                "allreduce", "reduce_scatter", "allgather",
                                "alltoall"])
def test_driver_hlo_program_128mib_bf16_4_chips(ring4, op):
    # the TPU backend's HLO-lane program at the smoke's largest size; it
    # compiles inside the driver call, so a slow compile fails the call
    # (a bf16 [P, n] all_to_all took 97 s, past the 60 s call wait)
    import time

    from accl_tpu.backends.tpu import _collective_fn
    from accl_tpu.constants import Operation

    t0 = time.perf_counter()
    compiled = _collective_fn(ring4, Operation[op], 4, 64 * MI, 0, 0, "",
                              "bfloat16")
    assert time.perf_counter() - t0 < 30
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("send_rows,recv_rows", [(49152, 196608),
                                                  (196608, 49152)])
def test_driver_alltoallv_moonlight_4_chips(ring4, send_rows, recv_rows):
    # ep_alltoall.4chip's dispatch and combine: bf16 rows of 2048, the
    # receive sized for the dropless worst case; the chip's compiler
    # takes the ragged-all-to-all form, inside the 60 s call wait
    import time

    from accl_tpu.backends.tpu import _alltoallv_fn

    t0 = time.perf_counter()
    compiled = _alltoallv_fn(ring4, 4, send_rows, recv_rows, 2048,
                             "bfloat16")
    assert time.perf_counter() - t0 < 30
    text = compiled.as_text()
    assert "ragged-all-to-all" in text
    # row form: a chip's program takes and returns its [rows, 2048] shard
    entry = next(l for l in text.splitlines() if l.startswith("ENTRY"))
    assert f"bf16[{send_rows},2048]" in entry.split("->")[0], entry
    assert f"-> (bf16[{recv_rows},2048]" in entry, entry
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_moe_experts_moonlight_one_chip(one_chip):
    # ep_alltoall.4chip's expert layer on one chip's dropless receive
    # (4 x 8192 x 6 rows of 2048): the grouped matmuls run on the rows
    # where they landed, with no sort or row index over the capacity,
    # and read and write them in the exchange's row form with no
    # relayout of a capacity-sized array
    import re

    from accl_tpu.models import moe_ep

    cfg = moe_ep.EPMoEConfig(hidden=2048, n_experts=64, top_k=6,
                             expert_width=1408, shared_width=2816,
                             scaling=2.446, eps=1e-6)
    cap, held = 4 * 8192 * 6, 16

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    p = {"w1": shape(held, 2048, 1408), "w3": shape(held, 2048, 1408),
         "w2": shape(held, 1408, 2048)}
    compiled = moe_ep._experts.lower(
        cfg, False, shape(cap, 2048), shape(4 * held, dtype=jnp.int32),
        p).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " sort(" not in text and f"s32[{cap}]" not in text
    relayouts = [l for l in text.splitlines()
                 if re.search(r"= bf16\[(196608|24576),[^=]* (reshape|copy)\(",
                              l)]
    assert not relayouts, relayouts
    entry = next(l for l in text.splitlines() if l.startswith("ENTRY"))
    assert f"-> bf16[{cap},2048]" in entry, entry
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def _longcat_decode(held=8, tokens=256):
    from accl_tpu.models import scmoe

    return scmoe.ScMoEConfig.from_hf({
        "hidden_size": 6144, "n_routed_experts": 512, "moe_topk": 12,
        "expert_ffn_hidden_size": 2048, "ffn_hidden_size": 12288,
        "zero_expert_num": 256, "zero_expert_type": "identity",
        "routed_scaling_factor": 6, "rms_norm_eps": 1e-5},
        (tuple(range(held)),)), tokens * held


@pytest.mark.parametrize("cap", [2048, 8192])
def test_moe_experts_longcat_decode_one_chip(one_chip, cap):
    # scmoe_decode.1chip's expert layer: 8 held [6144, 2048] experts on a
    # 256-token decode capacity (128-row tiles, whole weights in VMEM),
    # and on a 4x capacity (512-row tiles: the weight blocked along N)
    from accl_tpu.models import moe_ep

    cfg, _ = _longcat_decode()

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    p = {"w1": shape(8, 6144, 2048), "w3": shape(8, 6144, 2048),
         "w2": shape(8, 2048, 6144)}
    compiled = moe_ep._experts.lower(
        cfg.moe, False, shape(cap, 6144), shape(8, dtype=jnp.int32),
        p).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert moe_ep._tiles(cap, 6144, 2048, 8)[0] == (128 if cap == 2048
                                                    else 512)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_scmoe_route_and_dense_decode_one_chip(one_chip):
    # the ScMoE layer's softmax router over 768 experts (top-12, f32) and
    # its two dense [6144, 12288] FFNs on a 256-token step
    from accl_tpu.models import moe_ep, scmoe

    cfg, _ = _longcat_decode()
    H, D = 6144, 12288

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    p = {"norm": shape(H), "norm4": shape(H),
         "router": shape(768, H, dtype=jnp.float32),
         "bias": shape(768, dtype=jnp.float32),
         "ffn1_w1": shape(H, D), "ffn1_w3": shape(H, D),
         "ffn1_w2": shape(D, H), "ffn2_w1": shape(H, D),
         "ffn2_w3": shape(H, D), "ffn2_w2": shape(D, H)}
    x = shape(256, H)
    for compiled in (moe_ep._route.lower(cfg.moe, 1, x, p).compile(),
                     scmoe._dense.lower(cfg, x, p).compile()):
        mem = compiled.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_d128(one_chip, direction):
    from accl_tpu.ops.flash import flash_attention

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def fa_bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fa(*a)), argnums=(0, 1, 2))(
            q, k, v)

    x = jax.ShapeDtypeStruct((4, 2048, 4, 128), jnp.float32,
                             sharding=one_chip)
    text = _compile(fa if direction == "fwd" else fa_bwd, x, x, x)
    if direction == "bwd":  # forward rerun + dq + dkv kernels
        assert text.count("tpu_custom_call") >= 3
