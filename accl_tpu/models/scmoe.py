"""LongCat-Flash's shortcut-connected MoE (ScMoE) layer, its expert
branch through the driver.

The published layer (LongCat-Flash, arXiv:2509.01322; transformers'
``modeling_longcat_flash.py``), with n1..n4 its RMSNorms
(``input_layernorm[0/1]``, ``post_attention_layernorm[0/1]``)::

    h1  = x  + Attn1(n1(x))
    u   = n2(h1)
    m   = MoE(u)                  # the shortcut branch
    h2  = h1 + FFN1(u)
    h3  = h2 + Attn2(n3(h2))
    out = h3 + FFN2(n4(h3)) + m

with FFN1 and FFN2 dense SwiGLU FFNs and MoE the softmax-routed block
with zero-compute experts of :mod:`accl_tpu.models.moe_ep` (its ``norm``
is n2).  Attention is left out, as under data-parallel attention it
makes no collective: h1 = x and h3 = h2, and n1 and n3 go unused.

The branch overlaps the dense path: a layer call issues the MoE's route
and its three driver calls (:meth:`EPMoELayer.send`), then the two dense
FFNs, then the combine that adds m (:meth:`EPMoELayer.finish`); nothing
in a layer makes the host wait on the device, so the driver's host path
runs while the device works.  Profiler annotations ``scmoe.branch`` and
``scmoe.dense`` mark the two issues when ``ACCL_TRACE`` is on; the
branch's ``moe.*`` annotations sit inside the first.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..accl import GLOBAL_COMM
from ..observability import trace as _trace
from .moe_ep import EPMoEConfig, EPMoELayer, _silu_glu


@dataclass(frozen=True)
class ScMoEConfig:
    moe: EPMoEConfig
    #: the dense FFNs' width (``ffn_hidden_size``)
    ffn_width: int

    @classmethod
    def from_hf(cls, cfg: dict, held: tuple | None = None) -> "ScMoEConfig":
        """From a LongCat-Flash ``config.json``; `held` as
        :class:`EPMoEConfig`'s."""
        return cls(moe=EPMoEConfig.from_hf(cfg, held),
                   ffn_width=cfg["ffn_hidden_size"])


def _rmsnorm(xf, w, eps):
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def _ffn(h, w1, w3, w2):
    """A SwiGLU FFN on f32 `h`: bf16 operands, f32 accumulation."""
    hb = h.astype(jnp.bfloat16)
    a = _silu_glu(jnp.dot(hb, w1, preferred_element_type=jnp.float32),
                  jnp.dot(hb, w3, preferred_element_type=jnp.float32))
    return jnp.dot(a.astype(jnp.bfloat16), w2,
                   preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnums=(0,))
def _dense(cfg: ScMoEConfig, x, p):
    """h2 + FFN2(n4(h2)) with h2 = x + FFN1(n2(x)), [T, H] f32."""
    eps = cfg.moe.eps
    xf = x.astype(jnp.float32)
    h2 = xf + _ffn(_rmsnorm(xf, p["norm"], eps), p["ffn1_w1"],
                   p["ffn1_w3"], p["ffn1_w2"])
    return h2 + _ffn(_rmsnorm(h2, p["norm4"], eps), p["ffn2_w1"],
                     p["ffn2_w3"], p["ffn2_w2"])


class ScMoEBlock:
    """One rank's ScMoE layer over `tokens` tokens a call: the expert
    branch is an :class:`EPMoELayer` on `accl`'s communicator `comm_id`.
    ``block(x, p)`` runs one layer with that layer's parameters: the
    MoE's (``norm`` is n2, see :class:`EPMoELayer`), ``ffn1_w1``,
    ``ffn1_w3`` [H, F] and ``ffn1_w2`` [F, H], ``norm4`` [H], and FFN2's
    alike, all bf16."""

    def __init__(self, accl, cfg: ScMoEConfig, tokens: int,
                 comm_id: int = GLOBAL_COMM):
        self.cfg = cfg
        self.moe = EPMoELayer(accl, cfg.moe, tokens, comm_id)

    def __call__(self, x, p):
        with _trace.region("scmoe.branch"):
            pending = self.moe.send(x, p)
        with _trace.region("scmoe.dense"):
            base = _dense(self.cfg, x, p)
        return self.moe.finish(pending, base)

    def stats(self) -> dict:
        return self.moe.stats()

    def close(self) -> None:
        self.moe.close()
