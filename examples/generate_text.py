"""Serve the flagship transformer: train briefly, then generate with
the KV cache.

The inference tour: a GQA + RoPE + swiglu model (the Llama-family
dialect) takes a few training steps, then `generate` runs one
jit-compiled program — prefill banks the prompt's K/V in the grouped
cache, and a lax.scan of decode steps extends it one token at a time.
Teacher-forced parity with the training forward is the tested contract
(tests/test_decode.py); this tour shows the user-facing surface.

    python examples/generate_text.py

Set ACCL_FUSED=1 to route any tensor-parallel collectives in the
forward/decode path through the r18 fused lane (no-op on this
single-device demo, but the flag plumbs through `generate`/`prefill`
the same way it does on a tp-sharded serving mesh).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# virtual CPU devices stand in for chips; ACCL_EXAMPLE_ON_TPU=1 runs on
# the TPU instead
if not os.environ.get("ACCL_EXAMPLE_ON_TPU"):
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from accl_tpu.models import ModelConfig, forward, init_params
from accl_tpu.models.decode import decode_step, generate, init_kv_cache, prefill
from accl_tpu.models.transformer import loss_fn


def main() -> None:
    fused = os.environ.get("ACCL_FUSED", "0") not in ("", "0")
    cfg = ModelConfig(vocab=64, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_head=16, d_ff=128,
                      mlp="swiglu", rope=True)
    rng = np.random.default_rng(0)
    params = init_params(rng, cfg)

    # a few SGD steps on a toy copy task so generation is not pure noise
    data = jnp.asarray(rng.integers(0, cfg.vocab, size=(4, 32),
                                    dtype=np.int32))
    def mean_loss(p, t):  # loss_fn returns (sum, count) per device
        s, c = loss_fn(p, t, cfg)
        return s / c

    grad_fn = jax.jit(jax.grad(mean_loss))
    n_steps = int(os.environ.get("ACCL_EXAMPLE_STEPS", "3"))
    for _ in range(n_steps):
        grads = grad_fn(params, data)
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    print(f"trained {n_steps} steps")

    prompt = data[:2, :8]
    out = generate(params, prompt, cfg, max_new=6, fused=fused)
    print("generated:", np.asarray(out).tolist())

    # the cache contract, demonstrated: teacher-forced decode logits
    # equal the training forward's, position for position
    tokens = data[:2, :12]
    want = np.asarray(forward(params, tokens, cfg))
    cache = init_kv_cache(cfg, 2, tokens.shape[1])
    lg, cache = prefill(params, tokens[:, :6], cache, cfg)
    np.testing.assert_allclose(np.asarray(lg), want[:, :6], rtol=3e-5,
                               atol=3e-5)
    step_fn = jax.jit(decode_step, static_argnames=("cfg",))
    for t in range(6, tokens.shape[1]):
        lg, cache = step_fn(params, tokens[:, t], cache, cfg)
        np.testing.assert_allclose(np.asarray(lg), want[:, t],
                                   rtol=3e-5, atol=3e-5)
    print("decode parity OK")
    print("OK")


if __name__ == "__main__":
    main()
