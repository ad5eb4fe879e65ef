"""Flagship transformer LM exercising the framework end-to-end.

Parallel layout (axes from accl_tpu.parallel.mesh):
- ``dp``: batch sharded; gradients all-reduce (sync_gradients)
- ``tp``: attention heads + MLP hidden sharded; row-parallel psum
- ``sp``: sequence sharded; ring attention rotates K/V over the ring

Pure-pytree parameters (no framework dependency); the train step is
built per-mesh with `shard_map` and jits end-to-end, so XLA schedules
every collective over ICI.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.ring_attention import _dense_attention, ring_attention
from ..utils.platform import pallas_interpret


@dataclass(frozen=True)
class ModelConfig:
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    #: K/V heads (grouped-query attention, the Llama-family layout):
    #: None = n_heads (plain MHA).  Must divide n_heads; each K/V head
    #: serves n_heads/n_kv_heads query heads.  The flash path consumes
    #: the grouped layout expansion-free (ops/flash.py GQA index maps);
    #: dense and ring-SP paths expand K/V per q head.
    n_kv_heads: int | None = None
    d_head: int = 32
    d_ff: int = 512
    dtype: str = "float32"  # compute dtype; bf16 on real TPU
    #: local attention implementation: "dense" (materialized scores) or
    #: "flash" (the Pallas tiled online-softmax kernel, ops/flash.py).
    #: flash requires the local sequence length to divide its blocks.
    attn: str = "dense"
    #: causal SP ring schedule: "contiguous" (natural shards) or
    #: "zigzag" (rank i holds chunk i + mirror 2P-1-i; exact per-hop
    #: load balance — feed tokens permuted by
    #: parallel.ring_attention.zigzag_indices)
    sp_schedule: str = "contiguous"
    #: sliding-window attention (the Mistral-family long-context
    #: tool): each position attends only its trailing `attn_window`
    #: tokens.  flash bounds the grid schedules (forward AND both
    #: backward kernels) to the visible blocks — out-of-window K/V is
    #: never fetched (ops/flash.py); dense applies the band mask.
    #: Under sequence parallelism (contiguous schedule; window <=
    #: T_local) the attention collapses to the local windowed block
    #: plus ONE neighbor hop — O(1) in the ring size
    #: (parallel.ring_attention window= path); zigzag + window raises.
    attn_window: int | None = None
    #: MLP flavor: "gelu" (plain two-matrix) or "swiglu" (the
    #: Llama-family gated unit: silu(x W1) * (x W3) W2 — a third
    #: projection whose gate multiplies elementwise before the down
    #: projection; same tp sharding, hidden dim sharded on both)
    mlp: str = "gelu"
    #: rotary position embeddings (RoPE, the Llama-family positional
    #: scheme): rotate q/k per GLOBAL token position before attention.
    #: Off by default (the parity baselines predate it); under
    #: sequence parallelism each shard rotates by its own global
    #: positions — including the zigzag layout's split chunks — so
    #: distributed and single-device runs agree exactly.
    rope: bool = False
    rope_theta: float = 10000.0
    #: rematerialize each transformer block on the backward pass
    #: (jax.checkpoint): only the block-input residuals stay live; the
    #: per-layer intermediates (d_ff activations, attention
    #: probabilities) are recomputed, at ~1/3 more compute — the
    #: long-context memory lever
    remat: bool = False

    def __post_init__(self):
        if self.attn not in ("dense", "flash"):
            raise ValueError(f"unknown attn implementation {self.attn!r}")
        if self.sp_schedule not in ("contiguous", "zigzag"):
            raise ValueError(f"unknown sp schedule {self.sp_schedule!r}")
        if self.n_kv_heads is not None and (
                self.n_kv_heads <= 0
                or self.n_heads % self.n_kv_heads != 0):
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must divide "
                f"n_heads={self.n_heads}")
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(f"attn_window={self.attn_window} must be "
                             f">= 1")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(f"unknown mlp flavor {self.mlp!r}")
        if self.rope and self.d_head % 2 != 0:
            raise ValueError(
                f"rope rotates feature PAIRS; d_head={self.d_head} "
                f"must be even")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng: np.random.Generator, cfg: ModelConfig) -> dict:
    """Plain-pytree parameters.  TP-shardable leaves carry the head /
    hidden dimension explicitly so PartitionSpecs address it."""
    def g(*shape, scale=0.02):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff
    G = cfg.kv_heads
    blocks = []
    for _ in range(cfg.n_layers):
        blocks.append({
            "ln1": np.ones(D, np.float32),
            "wq": g(D, H, Dh), "wk": g(D, G, Dh), "wv": g(D, G, Dh),
            "wo": g(H, Dh, D),
            "ln2": np.ones(D, np.float32),
            "w1": g(D, F), "w2": g(F, D),
            **({"w3": g(D, F)} if cfg.mlp == "swiglu" else {}),
        })
    params = {
        "embed": g(cfg.vocab, D, scale=0.02),
        "blocks": blocks,
        "ln_f": np.ones(D, np.float32),
    }
    return jax.tree_util.tree_map(jnp.asarray, params)


def param_specs(cfg: ModelConfig, tp: Optional[str] = "tp") -> dict:
    """PartitionSpec pytree: head/hidden dims sharded over `tp`, the
    rest replicated (None specs).  Under GQA the K/V projections shard
    their (smaller) head axis over the same `tp` — the mesh's tp extent
    must divide n_kv_heads for tensor parallelism to apply."""
    t = tp
    block = {
        "ln1": P(None),
        "wq": P(None, t, None), "wk": P(None, t, None),
        "wv": P(None, t, None),
        "wo": P(t, None, None),
        "ln2": P(None),
        "w1": P(None, t), "w2": P(t, None),
    }
    if cfg.mlp == "swiglu":
        block["w3"] = P(None, t)  # gate shards like w1
    return {
        "embed": P(None, None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "ln_f": P(None),
    }


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def _rope(x, positions, theta: float):
    """Rotary position embedding on [B, T, h, Dh] (h = that tensor's
    heads; Dh must be even).  Rotates feature pairs (i, i + Dh/2) by
    position-dependent angles — the Llama convention — in f32, cast
    back to the input dtype."""
    B, T, h, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]       # [1, T, 1, half]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _global_positions(Tl: int, cfg: ModelConfig, sp_axis: Optional[str]):
    """Global token positions of this member's local sequence shard:
    arange outside SP; shard-offset arange for contiguous shards; the
    split (chunk idx, mirror chunk 2P-1-idx) positions for zigzag."""
    if sp_axis is None:
        return jnp.arange(Tl)
    idx = lax.axis_index(sp_axis)
    if cfg.sp_schedule == "zigzag":
        P_ = lax.axis_size(sp_axis)
        C = Tl // 2
        a = jnp.arange(C)
        return jnp.concatenate([idx * C + a, (2 * P_ - 1 - idx) * C + a])
    return idx * Tl + jnp.arange(Tl)




def block_qkv(h, blk, cfg: ModelConfig, positions):
    """q/k/v projections of one block's normed input (+ RoPE when
    `positions` is given) — ONE definition shared by the training
    forward and the serving path (models/decode.py), so a projection
    change cannot silently break the decode parity contract."""
    q = jnp.einsum("btd,dhk->bthk", h, blk["wq"].astype(cfg.jdtype))
    k = jnp.einsum("btd,dhk->bthk", h, blk["wk"].astype(cfg.jdtype))
    v = jnp.einsum("btd,dhk->bthk", h, blk["wv"].astype(cfg.jdtype))
    if positions is not None:
        # rotate BEFORE any GQA expansion (k carries its own head
        # count; the rotation broadcasts over heads)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _fused_row_combine(h, w, tp_axis, out_shape, jdtype):
    """r18 fused lane for the row-parallel projections: the matmul and
    the tp allreduce pipeline each other (chunk k+1's wire hop hides
    under chunk k's MXU pass) instead of serializing matmul → psum.
    `h` [..., K] against `w` [K, D]; reduces over `tp_axis`."""
    from ..ops.fused import fused_chunks, fused_matmul_allreduce
    out = fused_matmul_allreduce(h.reshape(-1, h.shape[-1]), w,
                                 axis=tp_axis, use_pallas=False,
                                 chunks=fused_chunks())
    return out.reshape(out_shape).astype(jdtype)


def block_attn_out(x, attn, blk, cfg: ModelConfig, tp_axis,
                   fused: bool = False):
    """Attention-out projection + row-parallel combine + residual
    (shared with models/decode.py).  ``fused=True`` overlaps the tp
    combine with the projection matmul (r18); default is the
    sequential einsum + psum, bit-identical to r17."""
    wo = blk["wo"].astype(cfg.jdtype)
    if fused and tp_axis is not None:
        B, T, H, K = attn.shape
        o = _fused_row_combine(attn.reshape(B * T, H * K),
                               wo.reshape(H * K, -1), tp_axis,
                               (B, T, wo.shape[-1]), cfg.jdtype)
        return x + o
    o = jnp.einsum("bthk,hkd->btd", attn, wo)
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)  # row-parallel combine
    return x + o


def block_mlp(x, blk, cfg: ModelConfig, tp_axis, fused: bool = False):
    """Post-attention MLP (gelu or the Llama-family swiglu) + residual
    (shared with models/decode.py).  ``fused=True`` overlaps the tp
    combine with the down projection (r18)."""
    h = _rmsnorm(x, blk["ln2"])
    m = jnp.einsum("btd,df->btf", h, blk["w1"].astype(cfg.jdtype))
    if cfg.mlp == "swiglu":
        gate = jnp.einsum("btd,df->btf", h,
                          blk["w3"].astype(cfg.jdtype))
        m = jax.nn.silu(m) * gate
    else:
        m = jax.nn.gelu(m)
    w2 = blk["w2"].astype(cfg.jdtype)
    if fused and tp_axis is not None:
        B, T, F = m.shape
        m = _fused_row_combine(m.reshape(B * T, F), w2, tp_axis,
                               (B, T, w2.shape[-1]), cfg.jdtype)
        return x + m
    m = jnp.einsum("btf,fd->btd", m, w2)
    if tp_axis is not None:
        m = lax.psum(m, tp_axis)
    return x + m


def forward(params, tokens, cfg: ModelConfig, tp_axis: Optional[str] = None,
            sp_axis: Optional[str] = None, fused: bool = False):
    """Token ids [B, T_local] → logits [B, T_local, vocab].

    Inside shard_map: `tp_axis` marks head/hidden shards (row-parallel
    psum after attention-out and MLP-down), `sp_axis` marks sequence
    shards (ring attention).  Outside shard_map pass None for both.
    ``fused=True`` pipelines the row-parallel combines under the
    projection matmuls (r18 fused lane; no-op without a tp axis).
    """
    if cfg.sp_schedule == "zigzag" and sp_axis is None:
        # the zigzag layout is only meaningful under sequence
        # parallelism; without it the dense causal mask would silently
        # treat the permuted sequence as natural order
        raise ValueError("sp_schedule='zigzag' requires an sp axis "
                         "(tokens are in zigzag order)")
    x = params["embed"][tokens].astype(cfg.jdtype)  # [B, Tl, D]
    rope_pos = (_global_positions(tokens.shape[1], cfg, sp_axis)
                if cfg.rope else None)

    def block(x, blk):
        h = _rmsnorm(x, blk["ln1"])
        q, k, v = block_qkv(h, blk, cfg, rope_pos)
        if (k.shape[2] != q.shape[2] and sp_axis is None
                and cfg.attn != "flash"):
            # only the local dense path consumes one K/V head per q
            # head; the flash kernel reads the grouped layout in place
            # (K/V index maps share rows across the group) and the ring
            # layer rotates the grouped shards, expanding internally
            # only on its dense reference rung
            from ..parallel.ring_attention import expand_gqa_kv
            k, v = expand_gqa_kv(k, v, q.shape[2])
        if sp_axis is not None:
            if cfg.attn_window is not None and cfg.sp_schedule != \
                    "contiguous":
                raise ValueError(
                    "attn_window under sequence parallelism requires "
                    "the contiguous schedule (the zigzag layout's "
                    "split chunks break the one-neighbor-hop bound)")
            if cfg.attn == "flash":
                raise ValueError(
                    "attn='flash' is the single-shard attention kernel; "
                    "with sequence parallelism the ring layer owns the "
                    "attention schedule — use attn='dense' when sp is on")
            attn = ring_attention(q, k, v, axis=sp_axis, causal=True,
                                  schedule=cfg.sp_schedule,
                                  window=cfg.attn_window)
        elif cfg.attn == "flash":
            from ..ops.flash import flash_attention
            # MXU input format follows the model's activation dtype:
            # bf16 activations get the fast native-rate matmuls, f32
            # configs keep exact f32 numerics (dense-parity contract)
            mxu_dt = (q.dtype if q.dtype in (jnp.bfloat16, jnp.float16)
                      else jnp.float32)
            attn = flash_attention(q, k, v, causal=True,
                                   mxu_dtype=mxu_dt,
                                   window=cfg.attn_window,
                                   interpret=pallas_interpret())
        else:
            attn = _dense_attention(q, k, v, causal=True,
                                    window=cfg.attn_window)
        x = block_attn_out(x, attn, blk, cfg, tp_axis, fused=fused)
        return block_mlp(x, blk, cfg, tp_axis, fused=fused)

    if cfg.remat:
        # rematerialize each block on the backward pass: only the
        # block-input residuals stay live across layers; the per-layer
        # intermediates (d_ff activations, attention probabilities —
        # the bulky part) recompute at ~1/3 more FLOPs (jax.checkpoint
        # over the layer, the knob the big training stacks expose)
        block = jax.checkpoint(block)
    for blk in params["blocks"]:
        x = block(x, blk)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("btd,vd->btv", x,
                        params["embed"].astype(cfg.jdtype))
    return logits


def loss_fn(params, tokens, cfg: ModelConfig, tp_axis: Optional[str] = None,
            sp_axis: Optional[str] = None, fused: bool = False):
    """Next-token cross entropy.  With sequence parallelism, the label
    for a shard's last position lives on the next shard — fetched with
    one ppermute hop (the pipeline-neighbor send/recv pattern); the
    global last position is masked.  Returns (sum_loss, count) local to
    the device."""
    B, Tl = tokens.shape
    logits = forward(params, tokens, cfg, tp_axis, sp_axis,
                     fused=fused).astype(jnp.float32)
    if sp_axis is not None and cfg.sp_schedule == "zigzag":
        # zigzag layout: the local row is [chunk idx ; chunk 2P-1-idx].
        # Each chunk's last label is its GLOBAL successor's first token:
        #   lo chunk idx    -> chunk idx+1   = rank idx+1's lo-first,
        #                      except idx==P-1 whose successor (chunk P)
        #                      is its OWN hi chunk's first token;
        #   hi chunk 2P-1-idx -> chunk 2P-idx = rank idx-1's hi-first,
        #                      except idx==0 (the global end, masked).
        Pn = lax.axis_size(sp_axis)
        idx = lax.axis_index(sp_axis)
        C = Tl // 2
        lo, hi = tokens[:, :C], tokens[:, C:]
        from_next_lo = lax.ppermute(  # rank i receives rank i+1's lo[0]
            lo[:, :1], sp_axis, [(i, (i - 1) % Pn) for i in range(Pn)])
        from_prev_hi = lax.ppermute(  # rank i receives rank i-1's hi[0]
            hi[:, :1], sp_axis, [(i, (i + 1) % Pn) for i in range(Pn)])
        lo_end = jnp.where(idx == Pn - 1, hi[:, :1], from_next_lo)
        labels = jnp.concatenate(
            [lo[:, 1:], lo_end, hi[:, 1:], from_prev_hi], axis=1)
        valid = jnp.ones((B, Tl), bool).at[:, -1].set(idx != 0)
    elif sp_axis is not None:
        Pn = lax.axis_size(sp_axis)
        idx = lax.axis_index(sp_axis)
        nxt_first = lax.ppermute(tokens[:, :1], sp_axis,
                                 [(i, (i - 1) % Pn) for i in range(Pn)])
        labels = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
        is_last_shard = idx == Pn - 1
        valid = jnp.ones((B, Tl), bool).at[:, -1].set(
            jnp.logical_not(is_last_shard))
    else:
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
        valid = jnp.ones((B, Tl), bool).at[:, -1].set(False)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return jnp.sum(nll), jnp.sum(valid.astype(jnp.float32))


def _mean_grads(loss_closure, params, data_axes):
    """Per-device mean gradients for loss functions returning a LOCAL
    ``(loss_sum, count)`` pair (the sum-and-count discipline).

    Gradients of replicated parameters come back from ``value_and_grad``
    already psummed over the axes they are unvarying on (jax's
    replication-aware vma transpose), and sharded leaves keep per-shard
    grads — so re-reducing here would multiply the gradient by the mesh
    size.  The only remaining work is the global count/loss psum and
    the 1/total normalization.  Returns ``(g_mean, mean_loss)``."""
    (loss_sum, count), grads = jax.value_and_grad(
        loss_closure, has_aux=True)(params)
    total, loss_tot = count, loss_sum
    for a in data_axes:
        total = lax.psum(total, a)
        loss_tot = lax.psum(loss_tot, a)
    denom = jnp.maximum(total, 1.0)
    g_mean = jax.tree_util.tree_map(lambda g: g / denom, grads)
    return g_mean, loss_tot / denom


def sum_count_device_step(loss_closure, params, data_axes, lr):
    """Plain-SGD per-device step over :func:`_mean_grads`.
    Returns ``(new_params, mean_loss)``."""
    g_mean, mean_loss = _mean_grads(loss_closure, params, data_axes)
    new_params = jax.tree_util.tree_map(
        lambda p_, g_: p_ - lr * g_, params, g_mean)
    return new_params, mean_loss


def make_train_step(mesh, cfg: ModelConfig, lr: float = 1e-3,
                    dp: Optional[str] = "dp", tp: Optional[str] = "tp",
                    sp: Optional[str] = "sp", optimizer=None,
                    params=None, check_vma: Optional[bool] = None,
                    fused: bool = False):
    """Build the jitted SPMD train step over `mesh`.

    `check_vma` defaults per backend: on the CPU rung with
    cfg.attn="flash" the Pallas HLO interpreter inside shard_map trips
    jax's vma/dynamic_slice limitation (same caveat as ring_attention's
    flash impl), so the check is disabled there automatically; compiled
    TPU execution keeps it on.  Pass an explicit bool to override.

    Axes not present in the mesh are dropped automatically.  Gradient
    synchronization (the fw allreduce role) happens through jax's
    replication-aware (vma) transposes: parameters enter unvarying over
    dp/sp, so their gradients come back already all-reduced across those
    axes, and tp-sharded leaves keep per-shard gradients — exactly the
    Megatron discipline.  For explicitly compressed gradient sync use
    strategies.sync_gradients in a custom step.

    Default (``optimizer=None``): plain SGD at `lr`; returns
    (step_fn, (param_specs, token_spec)) with
    step_fn(params, tokens) -> (new_params, mean_loss).

    With an optax ``optimizer`` (requires `params` for state-spec
    derivation): optimizer states shard exactly like the parameters
    they mirror (tp-sharded moments stay sharded), and the returned
    bundle is (step_fn, (param_specs, opt_state_specs, token_spec),
    init_opt) with step_fn(params, opt_state, tokens) ->
    (new_params, new_opt_state, mean_loss) and init_opt(params) placing
    a fresh state on the mesh.

    The update runs PER SHARD inside shard_map, so the transform must
    be parameter-local/elementwise (adam, adamw, sgd, momentum, ...).
    Transforms that take cross-parameter statistics — e.g.
    ``clip_by_global_norm`` — would compute them from local tp shards
    and diverge from the single-device result; apply those to the mean
    gradients in a custom step instead."""
    axes = set(mesh.axis_names)
    dp = dp if dp in axes else None
    tp = tp if tp in axes else None
    sp = sp if sp in axes else None
    if cfg.sp_schedule == "zigzag" and sp is None:
        raise ValueError("ModelConfig(sp_schedule='zigzag') needs an 'sp' "
                         "axis in the mesh — zigzag-ordered tokens train "
                         "on wrong labels without the zigzag ring")

    specs = param_specs(cfg, tp)
    tok_spec = P(dp, sp)
    data_axes = tuple(a for a in (dp, sp) if a)
    if check_vma is None:
        check_vma = not (cfg.attn == "flash" and pallas_interpret())

    if optimizer is None:
        def device_step(params, tokens):
            return sum_count_device_step(
                lambda p: loss_fn(p, tokens, cfg, tp, sp, fused=fused),
                params, data_axes, lr)

        step = jax.shard_map(device_step, mesh=mesh,
                             in_specs=(specs, tok_spec),
                             out_specs=(specs, P()),
                             check_vma=check_vma)
        return jax.jit(step), (specs, tok_spec)

    if params is None:
        raise ValueError("optimizer path needs `params` (a host or "
                         "sharded pytree) to derive optimizer-state "
                         "PartitionSpecs")
    # optimizer states carry whole param-shaped subtrees (adam's mu/nu
    # are literally params-structured trees): substitute the param spec
    # tree for every state node with the params' treedef, replicate the
    # rest (step counts etc.)
    p_treedef = jax.tree_util.tree_structure(params)

    def _params_like(node):
        return jax.tree_util.tree_structure(node) == p_treedef

    state_shapes = jax.eval_shape(optimizer.init, params)
    st_leaves, st_def = jax.tree_util.tree_flatten(
        state_shapes, is_leaf=_params_like)
    opt_specs = jax.tree_util.tree_unflatten(
        st_def, [specs if _params_like(leaf) else P()
                 for leaf in st_leaves])

    import optax as _optax

    def device_step(params, opt_state, tokens):
        g_mean, mean_loss = _mean_grads(
            lambda p: loss_fn(p, tokens, cfg, tp, sp, fused=fused),
            params, data_axes)
        updates, new_state = optimizer.update(g_mean, opt_state, params)
        new_params = _optax.apply_updates(params, updates)
        return new_params, new_state, mean_loss

    step = jax.shard_map(device_step, mesh=mesh,
                         in_specs=(specs, opt_specs, tok_spec),
                         out_specs=(specs, opt_specs, P()),
                         check_vma=check_vma)

    def init_opt(p):
        return _place(optimizer.init(
            jax.tree_util.tree_map(lambda x: jnp.asarray(x), p)),
            opt_specs, mesh)

    return jax.jit(step), (specs, opt_specs, tok_spec), init_opt


def shard_params(params, mesh, cfg: ModelConfig, tp: Optional[str] = "tp"):
    """Place a host param pytree on the mesh per param_specs."""
    tp = tp if tp in set(mesh.axis_names) else None
    if tp is not None:
        ext = mesh.shape[tp]
        if cfg.kv_heads % ext != 0:
            # fail with the config-level story, not jax's generic
            # "dimension not divisible" from device_put
            raise ValueError(
                f"tensor-parallel extent {ext} must divide "
                f"n_kv_heads={cfg.kv_heads} (the grouped K/V "
                f"projections shard their head axis over {tp!r})")
    specs = param_specs(cfg, tp)
    return _place(params, specs, mesh)


def _place(params, specs, mesh):
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_s = treedef.flatten_up_to(specs)
    placed = [jax.device_put(x, NamedSharding(mesh, s))
              for x, s in zip(flat_p, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, placed)
