"""The benchmark's plain reference of one LongCat-Flash ScMoE layer,
computed on the chip in float32 after the window.

It imports nothing of the program.  With x the layer's input, u its
first post-attention norm n2(x), E routed and Z zero-compute experts::

    s = softmax(u W_g^T)            over all E + Z experts
    S = top-k of s + b;  g_i = scaling * s_i     (not renormalised)
    m = sum_{i in S, i >= E} g_i u + sum_{i in S, i held} g_i FFN_i(u)
    h2 = x + FFN1(u)
    y = h2 + FFN2(n4(h2)) + m

with every FFN a SwiGLU, ``W2 (silu(W1 h) * W3 h)``.  Plain
``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")``, the held experts one after another (a loop, not a batch),
no exchange, no kernels.  Departures from the published layer
(arXiv:2509.01322, transformers' ``modeling_longcat_flash.py``):
attention is left out (h1 = x, h3 = h2); only the chip's held experts
compute, the picks of the others (on other chips of the deployment) are
left out; the selection bias is set by the traffic; u enters the router
in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def ffn(h, w1, w3, w2):
    a = h @ w1.astype(F32)
    return (jax.nn.silu(a) * (h @ w3.astype(F32))) @ w2.astype(F32)


def normed(x, p: dict, eps: float):
    """u = n2(x), float32."""
    return _rmsnorm(x.astype(F32), p["norm"], eps)


def routing(u, p: dict, top_k: int, scaling: float) -> tuple:
    """(selected [T, k], gates [T, k], selection scores [T, E + Z])."""
    s = jax.nn.softmax(u @ p["router"].astype(F32).T, axis=-1)
    score = s + p["bias"].astype(F32)
    _, sel = jax.lax.top_k(score, top_k)
    return sel, scaling * jnp.take_along_axis(s, sel, axis=1), score


def tie_gap(score, top_k: int):
    """Per token, the k-th selection score less the (k+1)-th."""
    top = jax.lax.top_k(score, top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k]


def dense(x, u, p: dict, eps: float):
    """x + FFN1(u) + FFN2(n4(x + FFN1(u)))."""
    h2 = x.astype(F32) + ffn(u, p["ffn1_w1"], p["ffn1_w3"], p["ffn1_w2"])
    return h2 + ffn(_rmsnorm(h2, p["norm4"], eps), p["ffn2_w1"],
                    p["ffn2_w3"], p["ffn2_w2"])


def zero_part(u, sel, gates, n_experts: int):
    """The zero-compute (identity) experts' term."""
    return jnp.sum(jnp.where(sel >= n_experts, gates, 0.0), axis=1)[:, None] \
        * u


def experts_part(u, sel, gates, held, w1, w3, w2):
    """Sum over the held experts (ids `held`, weights in that order) of
    each token's gate times the expert's output, one expert after
    another."""
    def one(y, e_w):
        e, a, b, c = e_w
        g = jnp.sum(jnp.where(sel == e, gates, 0.0), axis=1)
        return y + g[:, None] * ffn(u, a, b, c), None

    y, _ = jax.lax.scan(one, jnp.zeros(u.shape, F32),
                        (jnp.asarray(held), w1, w3, w2))
    return y


def layer(x, p: dict, held, n_experts: int, top_k: int, scaling: float,
          eps: float):
    """The whole layer in float32, the experts `held` in `p` (w1, w3
    [len(held), H, F], w2 [len(held), F, H])."""
    with jax.default_matmul_precision("highest"):
        u = normed(x, p, eps)
        sel, gates, _ = routing(u, p, top_k, scaling)
        return (dense(x, u, p, eps) + zero_part(u, sel, gates, n_experts)
                + experts_part(u, sel, gates, held, p["w1"], p["w3"],
                               p["w2"]))
