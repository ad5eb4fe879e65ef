"""Parallelism strategies built on the collective layer.

SURVEY §2.8 maps the reference's collectives onto the ML-parallelism
vocabulary; this module provides each strategy as a composable function
meant to run inside shard_map/pjit over the mesh axes from
:mod:`accl_tpu.parallel.mesh`:

- data parallel        ← allreduce          (fw :1855-2075)
- ZeRO/FSDP            ← reduce_scatter + all_gather (fw :1748, :1299)
- tensor parallel      ← psum / all_gather  (fw :1855, :1299)
- pipeline parallel    ← tagged send/recv shifts (fw :575-712; async
                         requests + multi-communicator in the driver)
- expert parallel      ← all_to_all         (fw :2123-2218)
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax



# ---------------------------------------------------------------------------
# data parallel
# ---------------------------------------------------------------------------
def _pad_to_multiple(flat, size: int):
    """Zero-pad a flat array so its length divides `size`."""
    pad = (-flat.shape[0]) % size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    return flat


def sync_gradients(grads, axis: str = "dp", compress: Optional[str] = None,
                   mean: bool = True, error_feedback: bool = False,
                   stochastic: bool = False, seed: int = 0):
    """All-reduce a gradient pytree across the data-parallel axis.

    `compress="bf16"|"f16"` models the reference's on-the-wire fp16
    compression (ETH_COMPRESSED) for gradient sync: payloads cross the
    link in half precision, accumulate in fp32.  `compress="int8"` goes
    one tier further than the reference's lane set: the leaf rides a
    quantized ring allreduce (int8 wire + per-block fp32 scales, 4:1 —
    ops/quantized.py).  `error_feedback`/`stochastic`/`seed` forward to
    the quantized ring's per-hop requantization error carry (EQuARX);
    they only apply to the int8 lane."""
    size = lax.axis_size(axis)

    def sync_leaf(g):
        orig = g.dtype
        if compress == "int8":
            from ..ops.quantized import quantized_all_reduce

            flat = _pad_to_multiple(g.astype(jnp.float32).reshape(-1), size)
            out = quantized_all_reduce(flat, axis,
                                       error_feedback=error_feedback,
                                       stochastic=stochastic, seed=seed)
            if mean:
                out = out / size
            n = g.size
            return out[:n].reshape(g.shape).astype(orig)
        if compress == "bf16":
            g = g.astype(jnp.bfloat16).astype(jnp.float32)
        elif compress == "f16":
            g = g.astype(jnp.float16).astype(jnp.float32)
        out = lax.pmean(g, axis) if mean else lax.psum(g, axis)
        return out.astype(orig)

    return jax.tree_util.tree_map(sync_leaf, grads)


def zero_shard_gradients(grads, axis: str = "dp"):
    """ZeRO-1 style: reduce-scatter each flat gradient so every member
    owns 1/P of the reduced values (optimizer-state sharding)."""
    size = lax.axis_size(axis)

    def shard_leaf(g):
        flat = _pad_to_multiple(g.reshape(-1), size)
        return lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)

    return jax.tree_util.tree_map(shard_leaf, grads)


def zero_unshard_params(shards, shapes, axis: str = "dp"):
    """Inverse of :func:`zero_shard_gradients`: all-gather the owned
    shards back into full parameters (shapes: matching pytree of
    jnp.shape tuples)."""

    def gather_leaf(s, shape):
        full = lax.all_gather(s, axis, tiled=True)
        n = 1
        for d in shape:
            n *= d
        return full[:n].reshape(shape)

    return jax.tree_util.tree_map(gather_leaf, shards, shapes)


# ---------------------------------------------------------------------------
# tensor parallel
# ---------------------------------------------------------------------------
def column_parallel(x, w_shard, axis: str = "tp", gather_output: bool = False):
    """y_shard = x @ W[:, shard]; optionally all-gather the columns.
    (Megatron column-parallel linear; comm only if gather_output.)"""
    y = jnp.dot(x, w_shard, preferred_element_type=jnp.float32).astype(x.dtype)
    if gather_output:
        y = lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel(x_shard, w_shard, axis: str = "tp"):
    """y = sum_over_shards(x[shard] @ W[shard, :]) — the partial products
    all-reduce over the tp ring (the fused matmul+allreduce pattern)."""
    partial = jnp.dot(x_shard, w_shard,
                      preferred_element_type=jnp.float32)
    return lax.psum(partial, axis).astype(x_shard.dtype)


# ---------------------------------------------------------------------------
# pipeline parallel
# ---------------------------------------------------------------------------
def pipeline_apply(stage_fn: Callable, params, x_microbatches,
                   axis: str = "pp"):
    """GPipe-style pipeline over the `axis` ring.

    Every member holds one stage's `params`.  `x_microbatches`
    [M, ...batch...] enters stage 0; outputs [M, ...] emerge from the
    last stage (other members return zeros).  The schedule runs
    M + P - 1 ticks; activations shift stage→stage each tick via
    ppermute — the reference's tagged send/recv between pipeline
    neighbors (async requests + per-stage communicators in the driver).
    """
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    fwd = [(i, i + 1) for i in range(P - 1)]  # no wraparound

    def tick(carry, t):
        act = carry
        mb = jnp.clip(t, 0, M - 1)
        x_in = jnp.where(idx == 0,
                         x_microbatches[mb].astype(jnp.float32),
                         act)
        y = stage_fn(params, x_in)
        act_next = lax.ppermute(y, axis, fwd)
        # last stage's output for microbatch (t - (P-1)) appears at tick t
        return act_next, y

    # derive the initial carry from a real stage output so its
    # varying-axes type matches the loop body under shard_map (the
    # inputs may vary over other mesh axes besides `axis`)
    zeros = jnp.zeros_like(
        lax.ppermute(stage_fn(params, x_microbatches[0].astype(jnp.float32)),
                     axis, fwd))
    _, ys = lax.scan(tick, zeros, jnp.arange(M + P - 1))
    # member P-1 produced microbatch m at tick m + P - 1
    outs = ys[P - 1:P - 1 + M]
    return jnp.where(idx == P - 1, outs, jnp.zeros_like(outs))


# ---------------------------------------------------------------------------
# expert parallel (MoE)
# ---------------------------------------------------------------------------
def expert_dispatch(x, expert_idx, axis: str = "ep", capacity: int = 0):
    """Route tokens to the member hosting their expert via all-to-all
    (one expert per member).  x: [N, D], expert_idx: [N] in [0, P).
    Returns (expert_inputs [P*cap, D], combine_info) — dropped tokens
    (over capacity) combine to zero, mirroring standard MoE capacity
    semantics."""
    P = lax.axis_size(axis)
    N, D = x.shape
    cap = capacity or -(-N // P)
    # slot each token within its expert bucket
    onehot = jax.nn.one_hot(expert_idx, P, dtype=jnp.int32)  # [N, P]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot - 1  # [N, P]
    slot = jnp.sum(pos_in_expert * onehot, axis=1)  # [N]
    keep = slot < cap
    # buckets[e, c] = token index destined for expert e at slot c
    buckets = jnp.zeros((P, cap, D), x.dtype)
    buckets = buckets.at[expert_idx, jnp.clip(slot, 0, cap - 1)].add(
        jnp.where(keep[:, None], x, 0.0))
    # exchange buckets: member e receives every member's bucket e
    recv = lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0,
                          tiled=False)  # [P, cap, D] from each source
    return recv.reshape(P * cap, D), (expert_idx, slot, keep, cap)


def expert_combine(y, combine_info, axis: str = "ep"):
    """Inverse of dispatch: return expert outputs to their source member
    and scatter back into token order.  y: [P*cap, D]."""
    P = lax.axis_size(axis)
    expert_idx, slot, keep, cap = combine_info
    D = y.shape[-1]
    back = lax.all_to_all(y.reshape(P, cap, D), axis, split_axis=0,
                          concat_axis=0, tiled=False)  # [P, cap, D]
    gathered = back[expert_idx, jnp.clip(slot, 0, cap - 1)]
    return jnp.where(keep[:, None], gathered, 0.0)
