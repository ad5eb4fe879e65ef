"""The benchmark's plain reference of one DeepSeek-V3 MoE layer
(Moonlight-16B-A3B), computed on the chip in float32 after the window.

It imports nothing of the program.  With x the residual stream and
h = RMSNorm(x):

    s = sigmoid(h W_g^T); S = top-k of s + b; g_i = scaling s_i / sum_S s
    y = x + sum_{i in S} g_i W2_i(silu(W1_i h) * W3_i h) + shared(h)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")``: every routed expert on every token of a block, masked by
its gate, one expert after another, no exchange, no kernels.  The
benchmark evaluates a layer in token blocks on one chip, from
:func:`normed`, :func:`routing`, :func:`shared` and
:func:`experts_part`; :func:`layer` is all of it at once.  Departures from the published model: attention and
the dense first layer are left out, the selection bias is set by the
traffic, h enters the router in float32, and ``n_group`` = 1 makes the
group stage of ``noaux_tc`` select every expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ffn(h, w1, w3, w2):
    a = h @ w1.astype(F32)
    return (jax.nn.silu(a) * (h @ w3.astype(F32))) @ w2.astype(F32)


def normed(x, p: dict, eps: float):
    return _rmsnorm(x.astype(F32), p["norm"].astype(F32), eps)


def routing(h, p: dict, top_k: int, scaling: float) -> tuple:
    """(selected [T, k], gates [T, k], selection scores [T, E])."""
    s = jax.nn.sigmoid(h @ p["router"].astype(F32).T)
    score = s + p["bias"].astype(F32)
    _, sel = jax.lax.top_k(score, top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    return sel, scaling * w / jnp.sum(w, -1, keepdims=True), score


def tie_gap(score, top_k: int):
    """Per token, the k-th selection score less the (k+1)-th."""
    top = jax.lax.top_k(score, top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k]


def experts_part(h, sel, gates, first, w1, w3, w2):
    """Sum over the experts ``first, first + 1, ...`` (w1, w3, w2 hold
    them in that order) of each token's gate times the expert's output,
    one expert after another (a loop, not a batch)."""
    def one(y, e_w):
        e, a, b, c = e_w
        g = jnp.sum(jnp.where(sel == e, gates, 0.0), axis=1)
        return y + g[:, None] * _ffn(h, a, b, c), None

    ids = first + jnp.arange(w1.shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, F32), (ids, w1, w3, w2))
    return y


def shared(h, p: dict):
    return _ffn(h, p["s1"], p["s3"], p["s2"])


def layer(x, p: dict, top_k: int, scaling: float, eps: float):
    """The whole layer, every expert in `p` (w1, w3 [E, H, F], w2 [E, F,
    H]), in float32."""
    with jax.default_matmul_precision("highest"):
        h = normed(x, p, eps)
        sel, gates, _ = routing(h, p, top_k, scaling)
        return (x.astype(F32) + shared(h, p)
                + experts_part(h, sel, gates, 0, p["w1"], p["w3"],
                               p["w2"]))
