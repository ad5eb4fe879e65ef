"""Wire-compression lanes as Pallas TPU kernels.

Equivalent of the reference hp_compression plugin — streaming fp32↔fp16
casts at a 2:1 width ratio, instantiated three times for the op0/op1/res
lanes (kernels/plugins/hp_compression/hp_compression.cpp:70-144;
emulator wiring cclo_emu.cpp:396-399).  The TPU build generalizes the
target to {float16, bfloat16} (bf16 is the native TPU half type) and
adds optional stochastic rounding via the on-core PRNG — the technique
EQuARX-style quantized allreduce uses to stop bias accumulating over
ring hops (PAPERS.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


# On-chip sweep (scripts/kernel_tune.py compress, 64 Mi f32 roundtrip,
# in-jit chained interleaved-window methodology): 512-lane rows dominate
# every other width by >2x, and 1024-row (2 MB) blocks edge out 256-row
# in shared windows, landing at/above the barriered XLA convert-pair
# ceiling measured in the same run.
_BLOCK_ROWS = 1024
_LANES = 512


def _f32_to_f16_bits(x):
    """IEEE binary16 bit patterns (int32) of fp32 `x`, rounded to
    nearest even: the conversion in integer arithmetic, because the v5e
    vector unit has no f16 (Mosaic refuses an f32 -> f16 pack there)."""
    b = pltpu.bitcast(x, jnp.int32)
    sign = (b >> 16) & 0x8000
    a = b & 0x7FFFFFFF
    # normal range: rebias the exponent (127 -> 15) and round the 13
    # dropped mantissa bits to nearest even; overflow saturates to inf
    normal = jnp.minimum(
        (a - 0x38000000 + 0xFFF + ((a >> 13) & 1)) >> 13, 0x7C00)
    # subnormal range (|x| < 2^-14): adding 0.5 puts the f16 subnormal
    # step at the fp32 ulp, so the FPU does the rounding
    sub = pltpu.bitcast(jnp.abs(x) + 0.5, jnp.int32) - 0x3F000000
    h = jnp.where(a < 0x38800000, sub, normal)
    h = jnp.where(a >= 0x7F800000,
                  jnp.where(a > 0x7F800000, 0x7E00, 0x7C00), h)
    return sign | h


def _f16_bits_to_f32(h):
    """fp32 values of binary16 bit patterns `h` (int32, low 16 bits)."""
    h = h & 0xFFFF
    sign = (h & 0x8000) << 16
    e = (h >> 10) & 0x1F
    m = h & 0x3FF
    normal = pltpu.bitcast(sign | ((e + 112) << 23) | (m << 13),
                           jnp.float32)
    special = pltpu.bitcast(sign | 0x7F800000 | (m << 13), jnp.float32)
    sub = m.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))


def _cast_kernel(dtype):
    def kernel(x_ref, o_ref):
        if dtype == jnp.float16:  # fp16 travels as int16 bit patterns
            o_ref[:] = _f32_to_f16_bits(
                x_ref[:].astype(jnp.float32)).astype(jnp.int16)
        elif x_ref.dtype == jnp.int16:
            o_ref[:] = _f16_bits_to_f32(
                x_ref[:].astype(jnp.int32)).astype(dtype)
        else:
            o_ref[:] = x_ref[:].astype(dtype)

    return kernel


def _stochastic_kernel(dtype):
    def kernel(seed_ref, x_ref, o_ref):
        from jax.experimental import pallas as pl
    
        # fold the grid position into the seed: every block would
        # otherwise draw the SAME bit pattern and the rounding noise
        # would correlate block-to-block instead of averaging out
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
        o_ref[:] = pltpu.stochastic_round(x_ref[:], bits, target_dtype=dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("dtype", "stochastic", "interpret"))
def _cast_2d(x, seed, dtype, stochastic: bool, interpret: bool):
    from jax.experimental import pallas as pl

    rows, cols = x.shape
    f16 = jnp.float16 in (x.dtype, dtype)
    # the integer fp16 conversion keeps several block-sized temporaries
    # live, so its blocks are a quarter as deep to fit VMEM
    block_rows = min(_BLOCK_ROWS // 4 if f16 else _BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    if x.dtype == jnp.float16:
        x = jax.lax.bitcast_convert_type(x, jnp.int16)
    out_shape = jax.ShapeDtypeStruct(
        x.shape, jnp.int16 if dtype == jnp.float16 else dtype)
    # every block is independent: parallel semantics let Mosaic overlap
    # the next block's DMA with the current cast
    params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    if stochastic:
        # scalar-prefetch index maps receive the prefetch ref as a
        # trailing argument — the specs need their own index lambdas
        pspec = pl.BlockSpec((block_rows, cols), lambda i, *_: (i, 0),
                             memory_space=pltpu.VMEM)
        return pl.pallas_call(
            _stochastic_kernel(dtype),
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[pspec],
                out_specs=pspec,
            ),
            compiler_params=params,
            interpret=interpret,
        )(seed, x)
    out = pl.pallas_call(
        _cast_kernel(dtype),
        out_shape=out_shape,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        compiler_params=params,
        interpret=interpret,
    )(x)
    return (jax.lax.bitcast_convert_type(out, jnp.float16)
            if dtype == jnp.float16 else out)


def _tiles(x):
    n = x.size
    flat = x.reshape(-1)
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, x.dtype)])
    return flat.reshape(rows, _LANES), n


# The public lanes are jitted whole — pad/reshape/kernel/unpad fuse into
# ONE dispatch instead of ~4 extra host round-trips for the reshapes.
@functools.partial(jax.jit,
                   static_argnames=("dtype", "stochastic", "interpret"))
def compress_cast(x, dtype=jnp.bfloat16, stochastic: bool = False,
                  seed: int = 0, interpret: bool = False):
    """Compress lane (hp_compression TDEST 0): fp32 → fp16/bf16.

    `stochastic=True` rounds with PRNG bits instead of
    round-to-nearest-even (TPU-only; requires the Mosaic PRNG).  `seed`
    is traced, so stepping it per call (to decorrelate ring hops) does
    NOT retrace."""
    x2, n = _tiles(x)
    seed_arr = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
    out = _cast_2d(x2, seed_arr, jnp.dtype(dtype), stochastic, interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def decompress_cast(x, dtype=jnp.float32, interpret: bool = False):
    """Decompress lane (hp_compression TDEST 1): fp16/bf16 → fp32."""
    x2, n = _tiles(x)
    out = _cast_2d(x2, jnp.array([0], jnp.int32), jnp.dtype(dtype), False,
                   interpret)
    return out.reshape(-1)[:n].reshape(x.shape)
