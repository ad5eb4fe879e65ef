"""Expert-parallel MoE block of the DeepSeek-V3 kind, through the driver.

The block (DeepSeek-V3 / Moonlight ``DeepseekV3MoE``), with x the
residual stream and h = RMSNorm(x):

    s = sigmoid(h W_g^T)                       (f32)
    S = top-k of s + b                         (b: selection-only bias)
    g_i = scaling * s_i / sum_{j in S} s_j     (i in S)
    x <- x + sum_{i in S} g_i W2_i(silu(W1_i h) * W3_i h) + shared(h)

One rank holds ``n_experts / P`` consecutive routed experts (``held``),
a replica of the router and of the shared expert.  A layer call is:

- route: the router over all experts; the (token, slot) rows sorted by
  expert id, which is destination rank then local expert; the shared
  expert beside it;
- three driver calls: the ``[P, experts per rank]`` row counts by
  ``alltoall`` (int32), the rows by ``alltoallv`` (dispatch), and after
  the experts the rows back by ``alltoallv`` (combine);
- experts: one grouped matmul (a Pallas kernel) per projection on the
  received rows where they landed (f32 accumulation, bf16 storage):
  the groups are ``(source, local expert)`` in received order, each
  with its local expert's weights, so no row is permuted in either
  direction;
- combine: the rows un-permuted, the gate-weighted sum, the shared
  expert and the residual.

The counts stay on the device from the router to the exchange: the
host reads none of them.  The payloads stay in row form, ``[rows,
hidden]``, from the router through both exchanges to the combine: the
driver keeps an alltoallv buffer in its call's row form, so no stage
relayouts them.  The receive side is sized for the dropless
worst case (every rank's every row to this one), so no token is dropped
at any skew.  A device-side tally of the rows moved is read on request
(:meth:`EPMoELayer.stats`), never per call.

Host stages are profiler annotations ``moe.route``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine`` when ``ACCL_TRACE`` is on
(:func:`accl_tpu.observability.trace.region`).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..accl import GLOBAL_COMM
from ..observability import trace as _trace
from ..utils.platform import pallas_interpret

#: tally fields (rows): dispatch rows sent off-chip, dispatch rows
#: received, combine rows sent off-chip
TALLY_FIELDS = ("dispatch_offchip_rows", "dispatch_recv_rows",
                "combine_offchip_rows")


@dataclass(frozen=True)
class EPMoEConfig:
    hidden: int
    n_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    scaling: float
    eps: float

    @classmethod
    def from_hf(cls, cfg: dict) -> "EPMoEConfig":
        """From a DeepSeek-V3 style ``config.json``."""
        return cls(hidden=cfg["hidden_size"],
                   n_experts=cfg["n_routed_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   expert_width=cfg["moe_intermediate_size"],
                   shared_width=(cfg["n_shared_experts"]
                                 * cfg["moe_intermediate_size"]),
                   scaling=float(cfg["routed_scaling_factor"]),
                   eps=float(cfg["rms_norm_eps"]))


def _inverse(perm):
    """The inverse of a permutation, so that rows move back by a gather
    (a scatter of int32 indices, not of rows)."""
    return jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[0], dtype=perm.dtype))


def _silu_glu(a, b):
    a = a.astype(jnp.float32)
    return a * jax.nn.sigmoid(a) * b.astype(jnp.float32)


def _router(cfg: EPMoEConfig, x, p) -> tuple:
    """(h, selected experts [T, k], gates [T, k]) in float32: the norm
    and the router over every expert.  x: [T, H] bf16."""
    xf = x.astype(jnp.float32)
    h = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + cfg.eps)
    h = h * p["norm"].astype(jnp.float32)
    logits = jnp.dot(h, p["router"].T, precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + p["bias"], cfg.top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    return h, sel, cfg.scaling * w / jnp.sum(w, -1, keepdims=True)


@partial(jax.jit, static_argnums=(0,))
def select(cfg: EPMoEConfig, x, p):
    """The experts the layer routes each token of `x` to, [T, k]."""
    return _router(cfg, x, p)[1]


@partial(jax.jit, static_argnums=(0, 1))
def _route(cfg: EPMoEConfig, nranks: int, x, p):
    """Router, sorted send rows [T*k, H], per-expert and per-rank
    counts, the shared expert.  x: [T, H] bf16."""
    k = cfg.top_k
    xf = x.astype(jnp.float32)
    h, sel, gates = _router(cfg, x, p)
    eid = sel.reshape(-1)
    order = jnp.argsort(eid, stable=True).astype(jnp.int32)   # [T*k]
    hb = h.astype(jnp.bfloat16)
    rows = hb[order // k]
    ecounts = jnp.bincount(eid, length=cfg.n_experts).astype(jnp.int32)
    pcounts = ecounts.reshape(nranks, -1).sum(1).astype(jnp.int32)
    a = _silu_glu(jnp.dot(hb, p["s1"], preferred_element_type=jnp.float32),
                  jnp.dot(hb, p["s3"], preferred_element_type=jnp.float32))
    shared = jnp.dot(a.astype(jnp.bfloat16), p["s2"],
                     preferred_element_type=jnp.float32)
    return rows, ecounts, pcounts, order, gates, xf + shared


#: rows of a grouped-matmul tile; the receive holds whole tiles
_TILE_ROWS = 512
#: VMEM for one tile of rows, a whole [K, N] weight and the output
#: tile, each double-buffered (about 23 MB at Moonlight's widths)
_VMEM_BYTES = 64 * 1024 * 1024


def _grouped_matmul(x, w, sizes, interpret: bool):
    """``x[rows of group g] @ w[g % E]`` for consecutive groups of
    `sizes` rows, in bf16 with f32 accumulation; rows past the last
    group are not written.  x: [M, K], M a multiple of ``_TILE_ROWS``;
    w: [E, K, N]; sizes: [G] int32; `interpret` runs the kernel under
    the Pallas TPU interpreter.

    A Pallas kernel on megablox's tile schedule
    (``make_group_metadata``): one grid step per (row tile, group) that
    meet, so a tile holding rows of several groups is visited once per
    group and stored under that group's row mask, and a step picks its
    weight by ``group % E`` (no copy of the weights per group).  Tiles
    past the last group are never visited, and consecutive steps of one
    group keep their weight in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    (M, K), (E, _, N) = x.shape, w.shape
    tm = _TILE_ROWS
    meta, steps = make_group_metadata(
        group_sizes=sizes, m=M, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=False)

    def kernel(offsets, groups, tiles, x_ref, w_ref, o_ref):
        i = pl.program_id(0)
        g = groups[i]
        row = tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1),
                                                       0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        y = jnp.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, o, g, t: (t[i], 0)),
                pl.BlockSpec((None, K, N),
                             lambda i, o, g, t: (g[i] % E, 0, 0))],
            out_specs=pl.BlockSpec((tm, N), lambda i, o, g, t: (t[i], 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(*meta, x, w)


@partial(jax.jit, static_argnums=(0, 1))
def _experts(cfg: EPMoEConfig, interpret: bool, rows, counts, p):
    """The held experts on the received rows, where they landed.  rows:
    [cap, H] bf16 in source order, each source's rows grouped by local
    expert; counts: [P * held] int32, rows from source i for local
    expert e at ``i * held + e``.  Group ``g`` is (source ``g // held``,
    local expert ``g % held``) and meets that expert's weights, so the
    output, [cap, H], is in the received order the combine sends back;
    no row is permuted or relayouted.  Rows past the received total are
    unspecified."""
    mm = partial(_grouped_matmul, sizes=counts, interpret=interpret)
    a = _silu_glu(mm(rows, p["w1"]), mm(rows, p["w3"])).astype(jnp.bfloat16)
    return mm(a, p["w2"])


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(8,))
def _combine(cfg: EPMoEConfig, rank: int, back, order, gates, base,
             sent, recvd, tally):
    """Un-permute the returned rows (``back``: [T*k, H]), weight and sum
    them, add the shared expert and the residual (``base``); fold the
    layer into the tally."""
    T = base.shape[0]
    y = back[_inverse(order)]
    y = y.reshape(T, cfg.top_k, cfg.hidden).astype(jnp.float32)
    out = base + jnp.sum(gates[..., None] * y, axis=1)
    moved = jnp.stack([jnp.sum(sent) - sent[rank], jnp.sum(recvd),
                       jnp.sum(recvd) - recvd[rank]])
    return out.astype(jnp.bfloat16), tally + moved


class EPMoELayer:
    """One rank's expert-parallel MoE block over `tokens` tokens a call,
    its exchanges served by `accl`'s ``alltoall`` and ``alltoallv`` on
    device-resident buffers.  The layer holds its buffers, not its
    weights: ``layer(x, params)`` runs one MoE layer of the model with
    that layer's parameters on this rank's device: ``norm`` [H] bf16,
    ``router`` [E, H] f32, ``bias`` [E] f32, the held experts ``w1``,
    ``w3`` [E/P, H, F] and ``w2`` [E/P, F, H] bf16 in id order, the
    shared expert ``s1``, ``s3`` [H, Fs] and ``s2`` [Fs, H] bf16."""

    def __init__(self, accl, cfg: EPMoEConfig, tokens: int,
                 comm_id: int = GLOBAL_COMM):
        comm = accl.communicator(comm_id)
        P, r = comm.size, comm.local_rank
        if cfg.n_experts % P:
            raise ValueError(f"{cfg.n_experts} experts do not divide "
                             f"over {P} ranks")
        self.accl, self.cfg, self.tokens = accl, cfg, tokens
        self.comm_id, self.nranks, self.rank = comm_id, P, r
        self.per_rank = cfg.n_experts // P
        self.held = range(self.per_rank * r, self.per_rank * (r + 1))
        rows = tokens * cfg.top_k
        H = cfg.hidden
        bf16 = jnp.bfloat16
        mk = accl.create_buffer
        self.ecount_send = mk(cfg.n_experts, np.int32)
        self.ecount_recv = mk(cfg.n_experts, np.int32)
        self.dispatch_send = mk(rows * H, bf16)
        self.dispatch_count = mk(P, np.int32)
        # dropless: every rank's every row may come here; whole tiles of
        # the expert kernel
        cap = -(-P * rows // _TILE_ROWS) * _TILE_ROWS
        self.dispatch_recv = mk(cap * H, bf16)
        self.dispatch_recv_count = mk(P, np.int32)
        self.combine_send = mk(cap * H, bf16)
        self.combine_recv = mk(rows * H, bf16)
        self.combine_recv_count = mk(P, np.int32)
        self.calls = 0
        # the expert kernels compile on the TPU, run interpreted on the CPU
        self.interpret = pallas_interpret()
        self._tally = jax.device_put(jnp.zeros(len(TALLY_FIELDS),
                                               jnp.int32),
                                     self.dispatch_send.dev.device)

    def __call__(self, x, p):
        cfg, accl, P = self.cfg, self.accl, self.nranks
        kw = dict(comm_id=self.comm_id, from_fpga=True, to_fpga=True)
        with _trace.region("moe.route"):
            rows, ecounts, pcounts, order, gates, base = _route(
                cfg, P, x, p)
            self.dispatch_send.set_dev_range(0, rows)
            self.ecount_send.set_dev_range(0, ecounts)
            self.dispatch_count.set_dev_range(0, pcounts)
        with _trace.region("moe.dispatch"):
            accl.alltoall(self.ecount_send, self.ecount_recv,
                          self.per_rank, **kw)
            accl.alltoallv(self.dispatch_send, self.dispatch_recv,
                           self.dispatch_count, self.dispatch_recv_count,
                           row=cfg.hidden, **kw)
        with _trace.region("moe.experts"):
            out = _experts(cfg, self.interpret, self.dispatch_recv.dev,
                           self.ecount_recv.dev, p)
            self.combine_send.set_dev_range(0, out)
        with _trace.region("moe.combine"):
            # each source's rows go back to it, as many as it sent
            accl.alltoallv(self.combine_send, self.combine_recv,
                           self.dispatch_recv_count,
                           self.combine_recv_count, row=cfg.hidden, **kw)
            y, self._tally = _combine(
                cfg, self.rank, self.combine_recv.dev, order, gates, base,
                self.dispatch_count.dev, self.dispatch_recv_count.dev,
                self._tally)
        self.calls += 1
        return y

    def stats(self) -> dict:
        """The device tally, read once: rows and bytes moved since the
        layer was made, and the layer calls."""
        rows = dict(zip(TALLY_FIELDS,
                        (int(v) for v in jax.device_get(self._tally))))
        row_bytes = self.cfg.hidden * 2
        out = {"calls": self.calls, **rows}
        for f in TALLY_FIELDS:
            out[f.replace("_rows", "_bytes")] = rows[f] * row_bytes
        return out

    def close(self) -> None:
        for b in (self.ecount_send, self.ecount_recv, self.dispatch_send,
                  self.dispatch_count, self.dispatch_recv,
                  self.dispatch_recv_count, self.combine_send,
                  self.combine_recv, self.combine_recv_count):
            b.free()
