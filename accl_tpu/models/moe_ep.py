"""Expert-parallel MoE block of the DeepSeek-V3 and LongCat-Flash kinds,
through the driver.

The block, with x the residual stream and h = RMSNorm(x), under one of
two routers (``EPMoEConfig.router``):

- ``sigmoid_norm`` (DeepSeek-V3 / Moonlight ``DeepseekV3MoE``)::

      s = sigmoid(h W_g^T)                       (f32)
      S = top-k of s + b                         (b: selection-only bias)
      g_i = scaling * s_i / sum_{j in S} s_j     (i in S)

- ``softmax`` (LongCat-Flash ``LongcatFlashMoE``): s = softmax(h W_g^T)
  over every expert, zero-compute ones included; S = top-k of s + b;
  g_i = scaling * s_i, not renormalised;

then ``x <- x + sum_{i in S} g_i E_i(h) + shared(h)``, where a routed
expert is E_i(h) = W2_i(silu(W1_i h) * W3_i h) and a zero-compute
(identity) expert, ids ``n_experts ..``, is E_i(h) = h.

Each rank holds a share of the routed experts (``EPMoEConfig.held``:
by default ``n_experts / P`` consecutive ids by rank), a replica of the
router and of the shared expert.  Experts that no member of the
communicator holds are absent: they live on chips outside it, and their
picks are left out here.  A layer call is:

- route: the router over all experts; the (token, slot) rows sorted by
  destination rank then local expert, the picks of zero-compute and of
  absent experts last and never sent; the zero-compute picks' share
  (gate times h) and the shared expert beside it;
- three driver calls: the ``[P, experts per rank]`` row counts by
  ``alltoall`` (int32), the rows by ``alltoallv`` (dispatch), and after
  the experts the rows back by ``alltoallv`` (combine);
- experts: one grouped matmul (a Pallas kernel) per projection on the
  received rows where they landed (f32 accumulation, bf16 storage):
  the groups are ``(source, local expert)`` in received order, each
  with its local expert's weights, so no row is permuted in either
  direction;
- combine: the rows un-permuted, the gate-weighted sum, the local
  share and the residual (or a base the caller gives:
  :meth:`EPMoELayer.send` issues all but the combine, and
  :meth:`EPMoELayer.finish` adds the result to any base).

The counts stay on the device from the router to the exchange: the
host reads none of them.  The payloads stay in row form, ``[rows,
hidden]``, from the router through both exchanges to the combine: the
driver keeps an alltoallv buffer in its call's row form, so no stage
relayouts them.  The receive side is sized for the dropless
worst case (every rank's every row that can go to one rank's experts),
so no token is dropped at any skew.  A device-side tally of the rows
moved is read on request (:meth:`EPMoELayer.stats`), never per call.

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
#: received, combine rows sent off-chip (each also read in bytes); the
#: zero-compute picks served locally by identity, and the picks left out
#: because no member of the communicator holds their expert
TALLY_FIELDS = ("dispatch_offchip_rows", "dispatch_recv_rows",
                "combine_offchip_rows", "zero_rows", "absent_rows")
#: the tally fields of rows that move, read in bytes too
MOVED_FIELDS = TALLY_FIELDS[:3]
ROUTERS = ("sigmoid_norm", "softmax")


@dataclass(frozen=True)
class EPMoEConfig:
    hidden: int
    n_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    scaling: float
    eps: float
    #: the routed expert ids that each member of the communicator holds,
    #: member by member, one sequence each, all of one length, a
    #: member's weights in its sequence's order; experts in none are
    #: absent.  None: ``n_experts / P`` consecutive ids by rank
    held: tuple | None = None
    #: zero-compute (identity) experts, ids ``n_experts`` onwards
    zero_experts: int = 0
    #: one of ``ROUTERS`` (see the module's docstring)
    router: str = "sigmoid_norm"

    def __post_init__(self):
        if self.router not in ROUTERS:
            raise ValueError(f"router {self.router!r}: one of {ROUTERS}")

    @classmethod
    def from_hf(cls, cfg: dict, held: tuple | None = None) -> "EPMoEConfig":
        """From a DeepSeek-V3 or a LongCat-Flash style ``config.json``;
        `held` as the field of that name."""
        common = dict(hidden=cfg["hidden_size"],
                      n_experts=cfg["n_routed_experts"],
                      scaling=float(cfg["routed_scaling_factor"]),
                      eps=float(cfg["rms_norm_eps"]), held=held)
        if "moe_topk" in cfg:   # LongCat-Flash: no shared expert
            kind = cfg.get("zero_expert_type", "identity")
            if kind != "identity":
                raise ValueError(f"zero_expert_type {kind!r}: only "
                                 "identity zero-compute experts are known")
            return cls(top_k=cfg["moe_topk"],
                       expert_width=cfg["expert_ffn_hidden_size"],
                       shared_width=0,
                       zero_experts=int(cfg.get("zero_expert_num", 0)),
                       router="softmax", **common)
        return cls(top_k=cfg["num_experts_per_tok"],
                   expert_width=cfg["moe_intermediate_size"],
                   shared_width=(cfg["n_shared_experts"]
                                 * cfg["moe_intermediate_size"]),
                   **common)

    @property
    def router_width(self) -> int:
        """The router's outputs: routed and zero-compute experts."""
        return self.n_experts + self.zero_experts

    def shares(self, nranks: int) -> tuple:
        """The ids each of the `nranks` members holds, member by
        member."""
        if self.held is None:
            if self.n_experts % nranks:
                raise ValueError(f"{self.n_experts} experts do not divide "
                                 f"over {nranks} ranks")
            per = self.n_experts // nranks
            return tuple(range(per * r, per * (r + 1))
                         for r in range(nranks))
        ids = [e for share in self.held for e in share]
        if (len(self.held) != nranks or not self.held[0]
                or len({len(s) for s in self.held}) != 1
                or len(set(ids)) != len(ids)
                or not all(0 <= e < self.n_experts for e in ids)):
            raise ValueError(f"held: {nranks} shares of distinct routed "
                             f"expert ids, one length each: {self.held}")
        return tuple(self.held)

    def all_routed(self, nranks: int) -> bool:
        """Whether every pick goes to a member's expert: no zero-compute
        expert, and the members hold every routed one."""
        return self.zero_experts == 0 and sum(
            len(s) for s in self.shares(nranks)) == self.n_experts


def _slots(cfg: EPMoEConfig, nranks: int) -> np.ndarray:
    """Each router output's slot, ``member * held + local index``; a
    zero-compute or absent expert's slot is ``P * held``, after every
    member's, so its picks sort last and are never sent."""
    shares = cfg.shares(nranks)
    per = len(shares[0])
    slot = np.full(cfg.router_width, nranks * per, np.int32)
    for m, share in enumerate(shares):
        slot[list(share)] = m * per + np.arange(per)
    return slot


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
    if cfg.router == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, sel = jax.lax.top_k(s + p["bias"], cfg.top_k)
        return h, sel, cfg.scaling * jnp.take_along_axis(s, sel, axis=1)
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
    """Router; sorted send rows [T*k, H]; per-expert and per-rank
    counts; the un-permute order and the gates; which picks went to a
    member (None where all do); the local share, [T, H] f32: the shared
    expert and the zero-compute picks' gate times h; and the picks
    served by identity and left out as absent.  x: [T, H] bf16."""
    k = cfg.top_k
    h, sel, gates = _router(cfg, x, p)
    eid = sel.reshape(-1)
    slots = _slots(cfg, nranks)
    held = nranks * len(cfg.shares(nranks)[0])
    if np.array_equal(slots, np.arange(slots.size)):
        slot = eid   # the ids are the slots
    else:
        slot = jnp.asarray(slots)[eid]
    routed = None
    picks = jnp.zeros(2, jnp.int32)
    if not cfg.all_routed(nranks):
        routed = (slot < held).reshape(sel.shape)
        zero = jnp.sum(eid >= cfg.n_experts, dtype=jnp.int32)
        picks = jnp.stack([zero, jnp.sum(~routed, dtype=jnp.int32) - zero])
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)   # [T*k]
    hb = h.astype(jnp.bfloat16)
    rows = hb[order // k]
    ecounts = jnp.bincount(slot, length=held).astype(jnp.int32)
    pcounts = ecounts.reshape(nranks, -1).sum(1).astype(jnp.int32)
    local = []
    if cfg.shared_width:
        a = _silu_glu(
            jnp.dot(hb, p["s1"], preferred_element_type=jnp.float32),
            jnp.dot(hb, p["s3"], preferred_element_type=jnp.float32))
        local.append(jnp.dot(a.astype(jnp.bfloat16), p["s2"],
                             preferred_element_type=jnp.float32))
    if cfg.zero_experts:
        g0 = jnp.sum(jnp.where(sel >= cfg.n_experts, gates, 0.0), axis=1)
        local.append(g0[:, None] * h)
    local = sum(local[1:], local[0]) if local else jnp.zeros_like(h)
    return rows, ecounts, pcounts, order, gates, routed, local, picks


#: rows of a grouped-matmul tile at most; the receive holds whole tiles
_TILE_ROWS = 512
#: rows of a tile at least, the MXU's
_MIN_TILE_ROWS = 128
#: VMEM for one tile of rows, a [K, tn] weight block and the output
#: tile, each double-buffered, and the f32 product
_VMEM_BYTES = 64 * 1024 * 1024


def _vmem_bytes(tm: int, K: int, tn: int) -> int:
    return 2 * 2 * (tm * K + K * tn + tm * tn) + 4 * tm * tn


def _tiles(M: int, K: int, N: int, groups: int) -> tuple:
    """(rows, columns) of a grouped-matmul tile for rows [M, K] in
    `groups` groups and [K, N] weights, from the shapes alone.

    Each group may leave up to a tile less a row of padding, computed
    as if it held rows: the row tile halves from 512 (to 128) until that
    padding, over every group, is at most half the capacity `M`.  At
    Moonlight's prefill (196,608 rows, 64 groups) it stays 512; at a
    decode capacity of 2,048 rows in 8 groups it is 128, where a padded
    step stays under the v5e ridge (about 240 rows), so the kernel's
    time is its weights' read.  The weight is blocked along N, halved
    while it and the row tiles do not fit ``_VMEM_BYTES``."""
    tm = _TILE_ROWS
    while tm > _MIN_TILE_ROWS and 2 * groups * tm > M:
        tm //= 2
    tn = N
    while _vmem_bytes(tm, K, tn) > _VMEM_BYTES and tn % 256 == 0:
        tn //= 2
    return tm, tn


def _grouped_matmul(x, w, sizes, interpret: bool):
    """``x[rows of group g] @ w[g % E]`` for consecutive groups of
    `sizes` rows, in bf16 with f32 accumulation; rows past the last
    group are not written.  x: [M, K], M a multiple of ``_TILE_ROWS``;
    w: [E, K, N]; sizes: [G] int32; `interpret` runs the kernel under
    the Pallas TPU interpreter.  The tiles follow the shapes
    (:func:`_tiles`).

    A Pallas kernel on megablox's tile schedule
    (``make_group_metadata``): one grid step per (row tile, group) that
    meet, so a tile holding rows of several groups is visited once per
    group and stored under that group's row mask, and a step picks its
    weight by ``group % E`` (no copy of the weights per group).  Tiles
    past the last group are never visited, and consecutive steps of one
    group keep their weight in VMEM.  A weight blocked along N adds an
    outer grid axis over its column blocks, so the steps that revisit
    an output tile stay consecutive."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    (M, K), (E, _, N) = x.shape, w.shape
    tm, tn = _tiles(M, K, N, sizes.shape[0])
    meta, steps = make_group_metadata(
        group_sizes=sizes, m=M, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=False)
    blocked = tn < N

    def at(a):
        """(column block, step, prefetched refs) of an index map's
        arguments."""
        return (a[0], a[1], a[2:]) if blocked else (0, a[0], a[1:])

    def kernel(offsets, groups, tiles, x_ref, w_ref, o_ref):
        i = pl.program_id(1 if blocked else 0)
        g = groups[i]
        row = tiles[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1),
                                                       0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        y = jnp.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])

    def x_map(*a):
        _j, i, (_o, _g, t) = at(a)
        return t[i], 0

    def w_map(*a):
        j, i, (_o, g, _t) = at(a)
        return g[i] % E, 0, j

    def o_map(*a):
        j, i, (_o, _g, t) = at(a)
        return t[i], j

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, steps) if blocked else (steps,),
            in_specs=[pl.BlockSpec((tm, K), x_map),
                      pl.BlockSpec((None, K, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (2 if blocked else 1),
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


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(11,))
def _combine(cfg: EPMoEConfig, rank: int, back, order, gates, routed, base,
             local, sent, recvd, picks, tally):
    """Un-permute the returned rows (``back``: [T*k, H]), weight and sum
    those of picks sent to a member (``routed``; None: all), add the
    local share and ``base`` (the residual, or what the caller adds the
    block to); fold the layer into the tally."""
    T = base.shape[0]
    y = back[_inverse(order)]
    y = y.reshape(T, cfg.top_k, cfg.hidden).astype(jnp.float32)
    if routed is not None:   # an unsent pick's row is unspecified
        y = jnp.where(routed[..., None], y, 0.0)
    out = base.astype(jnp.float32) + local + jnp.sum(gates[..., None] * y,
                                                     axis=1)
    moved = jnp.stack([jnp.sum(sent) - sent[rank], jnp.sum(recvd),
                       jnp.sum(recvd) - recvd[rank]])
    return (out.astype(jnp.bfloat16),
            tally + jnp.concatenate([moved, picks]))


class EPMoELayer:
    """One rank's expert-parallel MoE block over `tokens` tokens a call,
    its exchanges served by `accl`'s ``alltoall`` and ``alltoallv`` on
    device-resident buffers.  The layer holds its buffers, not its
    weights: ``layer(x, params)`` runs one MoE layer of the model with
    that layer's parameters on this rank's device: ``norm`` [H] bf16,
    ``router`` [E + Z, H] f32 and ``bias`` [E + Z] f32 over the routed
    and zero-compute experts, the held experts ``w1``, ``w3`` [held, H,
    F] and ``w2`` [held, F, H] bf16 in ``held``'s order, and where the
    configuration has one the shared expert ``s1``, ``s3`` [H, Fs] and
    ``s2`` [Fs, H] bf16."""

    def __init__(self, accl, cfg: EPMoEConfig, tokens: int,
                 comm_id: int = GLOBAL_COMM):
        comm = accl.communicator(comm_id)
        P, r = comm.size, comm.local_rank
        shares = cfg.shares(P)
        self.accl, self.cfg, self.tokens = accl, cfg, tokens
        self.comm_id, self.nranks, self.rank = comm_id, P, r
        self.per_rank = len(shares[0])
        self.held = shares[r]
        rows = tokens * cfg.top_k
        H = cfg.hidden
        bf16 = jnp.bfloat16
        mk = accl.create_buffer
        self.ecount_send = mk(P * self.per_rank, np.int32)
        self.ecount_recv = mk(P * self.per_rank, np.int32)
        self.dispatch_send = mk(rows * H, bf16)
        self.dispatch_count = mk(P, np.int32)
        # dropless: every rank's every pick of a held expert may come
        # here (a token picks each expert once); whole tiles of the
        # expert kernel
        most = P * tokens * min(cfg.top_k, self.per_rank)
        cap = -(-most // _TILE_ROWS) * _TILE_ROWS
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
        return self.finish(self.send(x, p), x)

    def send(self, x, p) -> tuple:
        """Issue the layer's route, its three driver calls and its
        experts on `x`, none of which waits on the device; the pending
        combine, for :meth:`finish`."""
        cfg, accl, P = self.cfg, self.accl, self.nranks
        kw = dict(comm_id=self.comm_id, from_fpga=True, to_fpga=True)
        with _trace.region("moe.route"):
            rows, ecounts, pcounts, order, gates, routed, local, picks = \
                _route(cfg, P, x, p)
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
        return (self.combine_recv.dev, order, gates, routed, local, picks,
                self.dispatch_count.dev, self.dispatch_recv_count.dev)

    def finish(self, pending: tuple, base):
        """The layer's output in bf16: `base` ([T, H], the residual or
        what the caller adds the block to) plus the pending call's
        routed experts and local share."""
        back, order, gates, routed, local, picks, sent, recvd = pending
        with _trace.region("moe.combine"):
            y, self._tally = _combine(
                self.cfg, self.rank, back, order, gates, routed, base,
                local, sent, recvd, picks, self._tally)
        self.calls += 1
        return y

    def stats(self) -> dict:
        """The device tally, read once: rows (and for the rows that
        move, bytes) since the layer was made, and the layer calls."""
        rows = dict(zip(TALLY_FIELDS,
                        (int(v) for v in jax.device_get(self._tally))))
        row_bytes = self.cfg.hidden * 2
        out = {"calls": self.calls, **rows}
        for f in MOVED_FIELDS:
            out[f.replace("_rows", "_bytes")] = rows[f] * row_bytes
        return out

    def close(self) -> None:
        for b in (self.ecount_send, self.ecount_recv, self.dispatch_send,
                  self.dispatch_count, self.dispatch_recv,
                  self.dispatch_recv_count, self.combine_send,
                  self.combine_recv, self.combine_recv_count):
            b.free()
