"""Plain reference of one DeepSeek-V3 MoE layer (Moonlight-16B-A3B's
``DeepseekV3MoE`` with its ``post_attention_layernorm``), for the tests
of :mod:`accl_tpu.models.moe_ep`.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: every routed expert on
every token, masked by its gate, no exchange, no kernels, no batching.
With x the residual stream and h = RMSNorm(x):

    s = sigmoid(h W_g^T); S = top-k of s + b; g_i = scaling s_i / sum_S s
    y = x + sum_{i in S} g_i W2_i(silu(W1_i h) * W3_i h) + shared(h)

Departures from the published model:

- attention and the dense first layer are left out: the layer stands
  alone, as under data-parallel attention the MoE block does;
- the selection bias ``b`` (``e_score_correction_bias``) is an input
  (the benchmark's traffic sets it), not a trained value;
- h enters the router in float32 (the published code rounds it to the
  model's bf16 first); ``n_group`` = ``topk_group`` = 1, so the group
  stage of ``noaux_tc`` selects every expert and is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ffn(h, w1, w3, w2):
    a = h @ w1
    return (jax.nn.silu(a) * (h @ w3)) @ w2


def routing(x, p: dict, cfg) -> tuple:
    """(selected experts [T, k], gates [T, k], selection scores [T, E])
    in float32."""
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x.astype(jnp.float32), p["norm"].astype(jnp.float32),
                     cfg.eps)
        s = jax.nn.sigmoid(h @ p["router"].astype(jnp.float32).T)
        score = s + p["bias"].astype(jnp.float32)
        _, sel = jax.lax.top_k(score, cfg.top_k)
        w = jnp.take_along_axis(s, sel, axis=1)
        return sel, cfg.scaling * w / jnp.sum(w, -1, keepdims=True), score


def moe_layer(x, p: dict, cfg):
    """The layer's output in float32.  `p` holds every expert: w1, w3
    [E, H, F], w2 [E, F, H]; the shared expert s1, s3 [H, Fs], s2 [Fs, H];
    norm [H], router [E, H], bias [E]."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        x = x.astype(f32)
        h = _rmsnorm(x, p["norm"].astype(f32), cfg.eps)
        sel, gates, _ = routing(x, p, cfg)
        y = x + _ffn(h, p["s1"].astype(f32), p["s3"].astype(f32),
                     p["s2"].astype(f32))
        for e in range(cfg.n_experts):
            g = jnp.sum(jnp.where(sel == e, gates, 0.0), axis=1)
            y = y + g[:, None] * _ffn(h, p["w1"][e].astype(f32),
                                      p["w3"][e].astype(f32),
                                      p["w2"][e].astype(f32))
        return y
