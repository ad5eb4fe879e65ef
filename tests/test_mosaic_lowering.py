"""Mosaic lowering rung for the Pallas kernels.

The reference test ladder has an RTL/XSI rung that exercises the
*synthesized* artifact without a cluster (test/model/simulator/
cclo_sim.cpp:57-559).  The analog here: lower the ring and flash
kernels through the REAL TPU lowering pipeline (Pallas -> Mosaic MLIR,
serialized into the tpu_custom_call) via cross-platform jax.export —
no TPU devices needed, so a Mosaic lowering regression (bad block
shapes, semaphore misuse, unsupported ops) fails in CI instead of
hiding behind interpret mode.  Machine-code generation still happens
on hardware (bench.py's worker compiles and runs these kernels on the
real chip); this rung pins the compiler-frontend contract.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

RANKS = 8


def _export_sharded(body, n_elems, dtype=jnp.float32):
    mesh = AbstractMesh((RANKS,), ("rank",),
                        axis_types=(jax.sharding.AxisType.Explicit,))
    fn = jax.shard_map(body, mesh=mesh, in_specs=P("rank"),
                       out_specs=P("rank"), check_vma=False)
    x = jax.ShapeDtypeStruct((n_elems,), dtype,
                             sharding=NamedSharding(mesh, P("rank")))
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x)
    return exp.mlir_module()


def _assert_mosaic(text):
    # the serialized Mosaic kernel rides a tpu_custom_call; its absence
    # means the Pallas path silently fell back or was elided
    assert "tpu_custom_call" in text, text[:1500]


@pytest.mark.parametrize("kernel", ["allreduce", "allgather",
                                    "reduce_scatter"])
def test_ring_kernels_lower_through_mosaic(kernel):
    from accl_tpu.ops import ring as R

    body = {
        "allreduce": lambda v: R.ring_all_reduce_segmented(
            v, "rank", interpret=False),
        "allgather": lambda v: R.ring_all_gather_segmented(
            v, "rank", interpret=False),
        "reduce_scatter": lambda v: R.ring_reduce_scatter_segmented(
            v, "rank", op="sum", interpret=False),
    }[kernel]
    # the driver's exact shape regime: flat per-member shards over the
    # ring threshold, ragged against the segment size (bulk/tail path)
    _assert_mosaic(_export_sharded(body, RANKS * 4096 + RANKS * 8))


def test_ring_compressed_lowers_through_mosaic(phased=None):
    # the quantized (int8 block-scaled) ring variant has its own Pallas
    # usage via the wire-compression path
    from accl_tpu.ops import ring as R

    _assert_mosaic(_export_sharded(
        lambda v: R.ring_all_reduce_segmented(v, "rank", interpret=False),
        RANKS * 1024, dtype=jnp.bfloat16))


@pytest.mark.parametrize("kern,opts", [
    ("resident", {}),
    ("grid", {}),
    # the chip-tuned resident schedule options (bench candidates)
    ("resident", {"q_tiles": 2}),
    ("resident", {"fuse_denom": True}),
    ("resident", {"q_tiles": 2, "fuse_denom": True}),
    # the software-pipelined score-carry schedule (kept selectable;
    # see its docstring for the measured result)
    ("resident_skew", {"q_tiles": 1}),
])
def test_flash_kernels_lower_through_mosaic(kern, opts):
    from accl_tpu.ops.flash import flash_attention_packed

    N, T, D = 4, 2048, 128  # the bench shape (MXU-native head dim)
    args = tuple(jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16)
                 for _ in range(3))
    exp = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, causal=True, kernel=kern, **opts)),
        platforms=["tpu"])(*args)
    _assert_mosaic(exp.mlir_module())


def test_flash_sliding_window_lowers_through_mosaic():
    # windowed liveness/masks ride the grid schedule's predication —
    # the banded long-context path must lower for the real target
    from accl_tpu.ops.flash import flash_attention_packed

    N, T, D = 4, 4096, 128
    a = jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16)
    exp = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, causal=True, window=1024, kernel="grid")),
        platforms=["tpu"])(a, a, a)
    _assert_mosaic(exp.mlir_module())


@pytest.mark.parametrize("kern", ["resident", "grid"])
def test_flash_gqa_lowers_through_mosaic(kern):
    # GQA: the grouped K/V index maps (b // group) must lower — a map
    # regression would strand the Llama-family layout in interpret mode
    from accl_tpu.ops.flash import flash_attention_packed

    N, Nk, T, D = 8, 2, 2048, 128
    q = jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((Nk, T, D), jnp.bfloat16)
    exp = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, causal=True, kernel=kern)),
        platforms=["tpu"])(q, kv, kv)
    _assert_mosaic(exp.mlir_module())


@pytest.mark.parametrize("opts", [
    # fused-denominator scratch build (f32 -> bf16 K cast + ones-V)
    {"q_tiles": 2, "fuse_denom": True},
    # the two-buffer one-shot K/V cast scratch branch (the _cast sweep
    # candidates) — distinct scratch path from fuse_denom
    {"kv_cast_scratch": True},
    {"kv_cast_scratch": True, "q_tiles": 2},
])
def test_flash_scratch_paths_lower_through_mosaic(opts):
    # f32 inputs + bf16 MXU dtype: every VMEM scratch branch of the
    # resident kernel must lower, or live-chip sweep candidates fail on
    # the chip
    from accl_tpu.ops.flash import flash_attention_packed

    N, T, D = 4, 2048, 128
    args = tuple(jax.ShapeDtypeStruct((N, T, D), jnp.float32)
                 for _ in range(3))
    exp = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, causal=True, kernel="resident", **opts)),
        platforms=["tpu"])(*args)
    _assert_mosaic(exp.mlir_module())


def test_reduce_lane_lowers_through_mosaic():
    from accl_tpu.ops.reduce_ops import pallas_add

    x = jax.ShapeDtypeStruct((1 << 16, 128), jnp.float32)
    exp = jax.export.export(
        jax.jit(lambda a, b: pallas_add(a, b, interpret=False)),
        platforms=["tpu"])(x, x)
    _assert_mosaic(exp.mlir_module())


def test_flash_backward_lowers_through_mosaic():
    # the custom-VJP backward (dq and dk/dv kernels) must lower for the
    # real TPU target too — training on hardware runs exactly this
    from accl_tpu.ops.flash import flash_attention_packed

    N, T, D = 4, 2048, 128
    arg = jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention_packed(
            q, k, v, causal=True, kernel="resident").astype(jnp.float32))

    exp = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(arg, arg, arg)
    text = exp.mlir_module()
    _assert_mosaic(text)


@pytest.mark.parametrize("which", ["allgather", "reduce_scatter",
                                   "allreduce"])
def test_selfring_lowers_through_mosaic(which):
    """The single-device VIRTUAL self-ring (ring_size override — the
    execute-the-artifact rung bench.py runs compiled on the chip) must
    lower through Mosaic on a 1-member axis: real remote-DMA ops with
    device_id = self, the extended V-step hop loop, and the ACK-window
    semaphores all survive the TPU pipeline."""
    from accl_tpu.ops import ring as R

    V = 8
    n = 512
    mesh = AbstractMesh((1,), ("r",),
                        axis_types=(jax.sharding.AxisType.Explicit,))
    body = {
        "allgather": lambda v: R.ring_all_gather_pallas(
            v, "r", ring_size=V),
        "reduce_scatter": lambda v: R.ring_reduce_scatter_pallas(
            v, "r", ring_size=V),
        "allreduce": lambda v: R.ring_all_reduce_pallas(
            v, "r", ring_size=V),
    }[which]
    shape = {"allgather": (n, 128), "reduce_scatter": (V, n, 128),
             "allreduce": (V * n, 128)}[which]
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(x)
    _assert_mosaic(exp.mlir_module())


def test_flash_gqa_backward_lowers_through_mosaic():
    """The r5 expansion-free GQA backward: grouped K/V via b//G index
    maps (dq) and the G-extended accumulation axis with divmod q
    row/block index maps (dkv) must survive the real TPU lowering."""
    from accl_tpu.ops.flash import flash_attention_packed

    N, G, T, D = 8, 2, 1024, 128
    q = jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((G, T, D), jnp.bfloat16)

    def loss(q, k, v):
        # GQA is shape-driven on the packed entry: k/v carry G rows
        return jnp.sum(flash_attention_packed(
            q, k, v, causal=True,
            kernel="resident").astype(jnp.float32))

    exp = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(q, kv, kv)
    _assert_mosaic(exp.mlir_module())


def test_flash_static_max_lowers_through_mosaic():
    """The r5 static-max resident schedule (pinned softmax shift, no
    max/alpha VPU passes) must lower for the real TPU target."""
    from accl_tpu.ops.flash import flash_attention_packed

    arg = jax.ShapeDtypeStruct((4, 2048, 128), jnp.float32)
    exp = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention_packed(
            q, k, v, causal=True, kernel="resident", static_max=40.0)),
        platforms=["tpu"])(arg, arg, arg)
    _assert_mosaic(exp.mlir_module())
