"""Train the flagship transformer on a dp x tp x sp device mesh.

The distributed-training tour: an 8-device mesh (virtual CPU devices
here — the same code runs unchanged on a TPU slice over ICI) carved
into data, tensor, and sequence axes; parameters sharded by
PartitionSpec; the train step jitted once over the mesh with gradient
sync, tensor-parallel matmuls, and zigzag ring attention over the
sequence axis all compiled into one SPMD program.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_transformer_3d.py

Set ACCL_FUSED=1 to route the tensor-parallel allreduces through the
r18 fused lane (chunked collectives drained under the MXU — bitwise
vs the default schedule; see docs/performance.md).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from accl_tpu.utils.platform import ensure_host_device_count

ensure_host_device_count(8)

import jax

# virtual CPU devices stand in for chips; ACCL_EXAMPLE_ON_TPU=1 runs on
# the TPU instead
if not os.environ.get("ACCL_EXAMPLE_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from accl_tpu.models.transformer import ModelConfig, init_params, make_train_step, shard_params
from accl_tpu.parallel.mesh import make_mesh
from accl_tpu.parallel.ring_attention import zigzag_indices

B, T = 4, 64
STEPS = int(os.environ.get("ACCL_EXAMPLE_STEPS", "5"))
FUSED = os.environ.get("ACCL_FUSED", "0") not in ("", "0")


def main():
    mesh = make_mesh(dp=2, tp=2, sp=2)
    # n_kv_heads=2: grouped-query attention (the Llama-family layout).
    # On TPU the flash ring reads the grouped layout without expansion
    # and rotates half-size K/V shards; this CPU demo's dense ring
    # expands per q head first (the reference-path contract)
    cfg = ModelConfig(vocab=256, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_head=16, d_ff=128,
                      sp_schedule="zigzag")
    params = init_params(np.random.default_rng(0), cfg)

    step, (param_specs, tok_spec) = make_train_step(mesh, cfg, lr=1e-2,
                                                    fused=FUSED)
    params = shard_params(params, mesh, cfg)

    # zigzag: feed tokens in the load-balanced causal layout (rank i
    # holds sequence chunk i and its mirror — every ring hop does
    # identical causal work on every rank)
    perm = np.asarray(zigzag_indices(T, 2))
    rng = np.random.default_rng(1)

    for i in range(STEPS):
        tokens = rng.integers(0, cfg.vocab, (B, T))[:, perm]
        tokens = jax.device_put(jnp.asarray(tokens),
                                NamedSharding(mesh, tok_spec))
        params, loss = step(params, tokens)
        print(f"step {i}: loss {float(loss):.4f}")

    lane = "fused (r18 chunked overlap)" if FUSED else "default"
    print(f"train_transformer_3d: {STEPS} steps on dp=2 x tp=2 x sp=2 "
          f"({len(jax.devices())} devices, {lane} tp collectives): OK")


if __name__ == "__main__":
    main()
