"""Plain reference of one LongCat-Flash ScMoE layer, for the tests of
:mod:`accl_tpu.models.scmoe`.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: every held routed expert
on every token, masked by its gate, no exchange, no kernels, no
batching.  With x the layer's input, u = n2(x) and E routed experts::

    s = softmax(u W_g^T)            over the E routed and Z zero experts
    S = top-k of s + b;  g_i = scaling * s_i     (not renormalised)
    m = sum_{i in S, i >= E} g_i u + sum_{i in S, i held} g_i FFN_i(u)
    h2 = x + FFN1(u)
    y = h2 + FFN2(n4(h2)) + m

Departures from the published layer:

- attention is left out (h1 = x, h3 = h2; n1 and n3 unused): under
  data-parallel attention it makes no collective;
- only the experts in ``held`` compute: the picks of the others, which
  live on other chips of the expert-parallel deployment, are left out
  (with every routed expert held it is the whole layer);
- the selection bias ``b`` (``e_score_correction_bias``) is an input;
- u enters the router in float32 (the published code rounds it to the
  model's bf16 first).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ffn(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1.astype(F32)) * (h @ w3.astype(F32))) \
        @ w2.astype(F32)


def routing(x, p: dict, cfg) -> tuple:
    """(selected experts [T, k], gates [T, k], selection scores [T,
    E + Z]) in float32; `cfg` an ``EPMoEConfig``."""
    with jax.default_matmul_precision("highest"):
        u = _rmsnorm(x.astype(F32), p["norm"].astype(F32), cfg.eps)
        s = jax.nn.softmax(u @ p["router"].astype(F32).T, axis=-1)
        score = s + p["bias"].astype(F32)
        _, sel = jax.lax.top_k(score, cfg.top_k)
        return sel, cfg.scaling * jnp.take_along_axis(s, sel, 1), score


def parts(x, p: dict, cfg, held) -> tuple:
    """(x plus the dense path, the zero-compute experts' term, the held
    routed experts' term), each [T, H] float32.  `p` holds the router,
    the dense path and the held experts' weights in `held`'s order (w1,
    w3 [len(held), H, F], w2 [len(held), F, H])."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(F32)
        u = _rmsnorm(x, p["norm"].astype(F32), cfg.moe.eps)
        h2 = x + _ffn(u, p["ffn1_w1"], p["ffn1_w3"], p["ffn1_w2"])
        base = h2 + _ffn(_rmsnorm(h2, p["norm4"].astype(F32), cfg.moe.eps),
                         p["ffn2_w1"], p["ffn2_w3"], p["ffn2_w2"])
        sel, gates, _ = routing(x, p, cfg.moe)
        zero = jnp.sum(jnp.where(sel >= cfg.moe.n_experts, gates, 0.0),
                       axis=1)[:, None] * u
        routed = jnp.zeros_like(x)
        for i, e in enumerate(held):
            g = jnp.sum(jnp.where(sel == e, gates, 0.0), axis=1)
            routed = routed + g[:, None] * _ffn(u, p["w1"][i], p["w3"][i],
                                                p["w2"][i])
        return base, zero, routed


def layer(x, p: dict, cfg, held):
    """The layer's output in float32 (see :func:`parts`)."""
    base, zero, routed = parts(x, p, cfg, held)
    return base + zero + routed
