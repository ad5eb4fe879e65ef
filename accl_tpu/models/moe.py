"""Mixture-of-Experts transformer — the expert-parallel model family.

The reference enables expert parallelism purely through its alltoall
collective (SURVEY §2.8: EP "enabled via alltoall",
ccl_offload_control.c:2123-2218); this model is the family built on that
enablement: a switch-style (top-1) MoE transformer whose expert FFNs
shard one-per-member over the ``ep`` mesh axis, with token routing done
by the alltoall dispatch/combine pair in
accl_tpu.parallel.strategies (expert_dispatch/expert_combine).

Dense fallback (``ep_axis=None``) computes every expert locally — the
correctness reference for the distributed path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.ring_attention import _dense_attention
from .transformer import _rmsnorm, sum_count_device_step


@dataclass(frozen=True)
class MoEConfig:
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_head: int = 32
    d_ff: int = 256
    n_experts: int = 4          # == ep axis size when sharded
    capacity_factor: float = 1.25
    router_aux_weight: float = 1e-2
    dtype: str = "float32"

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng: np.random.Generator, cfg: MoEConfig) -> dict:
    def g(*shape, scale=0.02):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    blocks = []
    for _ in range(cfg.n_layers):
        blocks.append({
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "wq": g(cfg.d_model, cfg.n_heads, cfg.d_head),
            "wk": g(cfg.d_model, cfg.n_heads, cfg.d_head),
            "wv": g(cfg.d_model, cfg.n_heads, cfg.d_head),
            "wo": g(cfg.n_heads, cfg.d_head, cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "router": g(cfg.d_model, cfg.n_experts),
            # expert FFN banks, leading dim = expert id (sharded over ep)
            "we1": g(cfg.n_experts, cfg.d_model, cfg.d_ff),
            "we2": g(cfg.n_experts, cfg.d_ff, cfg.d_model),
        })
    return {
        "embed": g(cfg.vocab, cfg.d_model),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
    }


def param_specs(cfg: MoEConfig, ep: Optional[str] = "ep") -> dict:
    """Expert banks shard over `ep`; everything else is replicated."""
    specs = {
        "embed": P(),
        "ln_f": P(),
        "blocks": [],
    }
    for _ in range(cfg.n_layers):
        specs["blocks"].append({
            "ln1": P(), "wq": P(), "wk": P(), "wv": P(), "wo": P(),
            "ln2": P(), "router": P(),
            "we1": P(ep), "we2": P(ep),
        })
    return specs


def _moe_ffn(h, blk, cfg: MoEConfig, ep_axis: Optional[str],
             capacity: Optional[int] = None, fused: bool = False):
    """Top-1 routed FFN.  h: [B, T, D] -> [B, T, D] + aux loss scalar.

    `capacity` overrides the training-time per-expert budget (ceil of
    B*T*capacity_factor/E).  Serving callers pass the full token count:
    at decode the per-call token count is tiny, so the training formula
    would drop (zero out) any token beyond ~B/E routed to one expert —
    a silent divergence from the dense reference (moe_decode.py).

    ``fused=True`` (r18, ep path only) splits the capacity dimension
    into chunks and pipelines the dispatch/combine alltoalls under the
    expert FFN compute (ops.fused.fused_expert_ffn) — the chunked
    routing is bitwise-equal to dispatch → FFN → combine."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    logits = jnp.einsum("nd,de->ne", x, blk["router"].astype(cfg.jdtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]

    # switch-transformer load-balance aux: E * sum_e frac_tokens_e * mean_prob_e
    onehot = jax.nn.one_hot(expert_idx, cfg.n_experts, dtype=jnp.float32)
    aux = cfg.n_experts * jnp.sum(
        jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))

    if ep_axis is None:
        # dense reference: run every expert, select by routing one-hot
        y_all = jnp.einsum("nd,edf->enf", x, blk["we1"].astype(cfg.jdtype))
        y_all = jax.nn.gelu(y_all)
        y_all = jnp.einsum("enf,efd->end", y_all,
                           blk["we2"].astype(cfg.jdtype))
        y = jnp.einsum("end,ne->nd", y_all, onehot.astype(cfg.jdtype))
    else:
        from ..parallel.strategies import expert_combine, expert_dispatch
        cap = (capacity if capacity is not None else
               int(np.ceil(B * T * cfg.capacity_factor / cfg.n_experts)))
        # this member's expert bank slice: [1, D, F] under ep sharding
        w1 = blk["we1"].astype(cfg.jdtype)[0]
        w2 = blk["we2"].astype(cfg.jdtype)[0]

        def expert_body(t):
            z = jnp.einsum("nd,df->nf", t, w1)
            z = jax.nn.gelu(z)
            return jnp.einsum("nf,fd->nd", z, w2)

        if fused:
            from ..ops.fused import fused_expert_ffn
            y = fused_expert_ffn(x, expert_idx, expert_body, ep_axis,
                                 capacity=cap)
        else:
            inputs, info = expert_dispatch(x, expert_idx, ep_axis,
                                           capacity=cap)
            y = expert_combine(expert_body(inputs), info, ep_axis)

    y = y * gate.astype(cfg.jdtype)[:, None]
    return y.reshape(B, T, D), aux




def moe_block_qkv(h, blk, cfg: MoEConfig):
    """q/k/v projections of one MoE block — shared by the training
    forward and the serving path (moe_decode.py) so the math cannot
    drift between them (same contract as transformer.block_qkv)."""
    q = jnp.einsum("btd,dhk->bthk", h, blk["wq"].astype(cfg.jdtype))
    k = jnp.einsum("btd,dhk->bthk", h, blk["wk"].astype(cfg.jdtype))
    v = jnp.einsum("btd,dhk->bthk", h, blk["wv"].astype(cfg.jdtype))
    return q, k, v


def moe_block_attn_out(x, attn, blk, cfg: MoEConfig):
    """Attention-out projection + residual (shared with moe_decode)."""
    return x + jnp.einsum("bthk,hkd->btd", attn,
                          blk["wo"].astype(cfg.jdtype))


def forward(params, tokens, cfg: MoEConfig, ep_axis: Optional[str] = None,
            fused: bool = False):
    """Token ids [B, T] -> (logits [B, T, vocab], total aux loss)."""
    x = params["embed"][tokens].astype(cfg.jdtype)
    aux_total = jnp.zeros((), jnp.float32)
    for blk in params["blocks"]:
        h = _rmsnorm(x, blk["ln1"])
        q, k, v = moe_block_qkv(h, blk, cfg)
        attn = _dense_attention(q, k, v, causal=True)
        x = moe_block_attn_out(x, attn, blk, cfg)
        h = _rmsnorm(x, blk["ln2"])
        m, aux = _moe_ffn(h, blk, cfg, ep_axis, fused=fused)
        aux_total = aux_total + aux
        x = x + m
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(cfg.jdtype))
    return logits, aux_total


def loss_fn(params, tokens, cfg: MoEConfig, ep_axis: Optional[str] = None,
            fused: bool = False):
    """Next-token cross entropy + router load-balance aux.

    Returns ``(loss_sum, count)`` local to the device — the same
    sum-and-count discipline as transformer.loss_fn, so the train step
    can psum both and scale once.  The aux term is count-weighted
    (``aux * count``) so that after global division by total count the
    result is the token-weighted mean of per-device aux losses."""
    B, T = tokens.shape
    logits, aux = forward(params, tokens, cfg, ep_axis, fused=fused)
    logits = logits.astype(jnp.float32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    valid = jnp.ones((B, T), bool).at[:, -1].set(False)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    count = jnp.sum(valid.astype(jnp.float32))
    loss_sum = jnp.sum(nll) + cfg.router_aux_weight * aux * count
    return loss_sum, count


def make_train_step(mesh, cfg: MoEConfig, lr: float = 1e-3,
                    dp: Optional[str] = "dp", ep: Optional[str] = "ep",
                    fused: bool = False):
    """Jitted SPMD train step: tokens shard over dp, expert banks over
    ep; routing rides the ep alltoall inside the step.

    Returns (step_fn, (param_specs, token_spec))."""
    axes = set(mesh.axis_names)
    dp = dp if dp in axes else None
    ep = ep if ep in axes else None
    if ep is not None and mesh.shape[ep] != cfg.n_experts:
        raise ValueError(
            f"ep axis size {mesh.shape[ep]} != n_experts {cfg.n_experts}")

    specs = param_specs(cfg, ep)
    # tokens shard over BOTH data axes: ep members are data-parallel for
    # the non-expert params, and the ep alltoall exchanges their shards
    tok_spec = P(tuple(a for a in (dp, ep) if a) or None)
    data_axes = tuple(a for a in (dp, ep) if a)

    def device_step(params, tokens):
        # ep-sharded expert banks keep per-shard grads (psummed over dp
        # only by the vma transpose); everything else follows the shared
        # sum-and-count discipline
        return sum_count_device_step(
            lambda p: loss_fn(p, tokens, cfg, ep, fused=fused),
            params, data_axes, lr)

    step = jax.shard_map(device_step, mesh=mesh,
                         in_specs=(specs, tok_spec),
                         out_specs=(specs, P()))
    return jax.jit(step), (specs, tok_spec)


def shard_params(params, mesh, cfg: MoEConfig, ep: Optional[str] = "ep"):
    ep = ep if ep in set(mesh.axis_names) else None
    specs = param_specs(cfg, ep)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_s = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    placed = [jax.device_put(p, NamedSharding(mesh, s))
              for p, s in zip(flat_p, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, placed)
