"""Expert-parallel MoE prefill: the program's expert layer
(``accl_tpu.models.moe_ep``) over a configuration's MoE layers, its
exchanges through the driver, chained layer to layer.

A unit is one prefill pass over the layers: each rank thread takes its
input set's hidden states ``[tokens, hidden]`` (bf16) and runs every
layer on the previous one's output, three driver calls a layer (the
per-expert counts by ``alltoall``, dispatch and combine by
``alltoallv``), one wait a unit.  The interface is closed_loop.py's.

The seed draws the weights and the inputs, on the device.  Routed
expert e of layer l is drawn from its own key, so a rank makes the
experts it holds and the reference remakes any of them alike.  The
traffic file fixes the routing skew: the selection bias, a Zipf profile
over a fixed expert order, is the same for every seed.

The check (all on the chip, after the window):

``a2av_max_abs_err``
    every rank's receive of the last unit's last layer's three exchanges
    (counts, dispatch, combine) against ops/alltoallv_counts.py's numpy
    reference, payload and counts: limit 0, bit exactness;
``moe_rel_err``
    for kept (unit, layer) pairs, the largest per-token error of the
    layer's MoE output, ||y - y_ref|| / ||y_ref - x||, with y the
    program's output and y_ref moe_reference.py's float32 layer on the
    program's own kept input x, tokens in ``routing_ties`` left out.
    The residual x is left out of the denominator: it grows layer by
    layer, and measured against it the bf16 rounding of y would hide a
    wrong MoE output.  A planned pair that the window did not reach
    fails the check;
``routing_ties``
    share of tokens whose reference k-th and (k+1)-th selection scores
    differ by less than the traffic's ``tie_gap``;
``routing_mismatch``
    tokens outside the ties whose selected experts, by the program's
    own router (``moe_ep.select``) on the kept input, differ from the
    reference's.

The control is the nearest precision below the configuration's bf16:
the routed and shared experts' weights carried through float8 e4m3 with
one scale per 128 x 128 block (``carry_fp8``, the weight format of
DeepSeek-V3's fp8 release) in the program.
"""
from __future__ import annotations

import os
import threading

import numpy as np

import harness
import moe_reference

_base = harness.load_module(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "traffic", "closed_loop")


#: largest finite float8 e4m3fn value, and its exponent range and
#: mantissa bits
E4M3_MAX, E4M3_MIN_EXP, E4M3_MANT = 448.0, -6, 3


def round_e4m3(v):
    """float32 `v` (|v| <= 448) rounded to the nearest float8 e4m3fn
    value, ties to even, as float32: the mantissa cut to 3 bits, below
    2**-6 a fixed step of 2**-9 (the subnormals)."""
    import jax.numpy as jnp

    _m, k = jnp.frexp(v)
    e = jnp.maximum(k - 1, E4M3_MIN_EXP)
    step = jnp.ldexp(jnp.ones_like(v), e - E4M3_MANT)
    return jnp.round(v / step) * step


def carry_fp8(w, block: int = 128):
    """`w` (its last two axes a matrix) carried as float8 e4m3 with one
    float32 scale, amax / 448, per `block` x `block` tile, and back, in
    its dtype; a partial tile at an edge is scaled alone."""
    import jax.numpy as jnp

    x = w.astype(jnp.float32)
    *lead, R, C = x.shape
    pad = [(0, 0)] * len(lead) + [(0, -R % block), (0, -C % block)]
    xp = jnp.pad(x, pad)
    Rp, Cp = xp.shape[-2:]
    b = xp.reshape(*lead, Rp // block, block, Cp // block, block)
    amax = jnp.max(jnp.abs(b), axis=(-3, -1), keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / E4M3_MAX)
    q = (round_e4m3(b / scale) * scale).reshape(xp.shape)
    return q[..., :R, :C].astype(w.dtype)


#: the control's carriers of the experts' weights, by the traffic's name
CARRIERS = {"fp8_e4m3_block128": carry_fp8}


class Stream(_base.Stream):
    """One cell's MoE prefill over one world."""

    def __init__(self, world, cfg: dict, traffic: dict, seed: int,
                 control: bool = False, here: str = harness.HERE):
        from accl_tpu.models import moe_ep

        self.world = world
        self.cfg = cfg
        self.moe = moe_ep.EPMoEConfig.from_hf(cfg)
        self.layers = int(cfg["num_hidden_layers"])
        self.tokens = int(cfg["assumed"]["prompt_tokens_per_chip"])
        self.nranks = world.nranks
        self.per_rank = self.moe.n_experts // self.nranks
        self.op = harness.load_module(here, "ops", cfg["unit"]["op"])
        self.sets = int(traffic["input_sets"])
        self.warmup_units = int(traffic["warmup_units"])
        self.check_spec = traffic["check"]
        self.bias = np.asarray(traffic["routing"]["selection_bias"],
                               np.float32)
        self.seed = seed
        self.control = control
        self.carry = None
        if control:
            name = traffic["control"]["lower_expert_weights"]
            if name not in CARRIERS:
                raise ValueError(f"no carrier {name!r} for the control")
            self.carry = CARRIERS[name]
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        self._keep: dict = {}       # window unit -> kept layers
        self.kept: dict = {}        # (unit, layer) -> [(x, y) per rank]
        self.exchanges: list = []   # [rank] -> the last layer's buffers
        self.tally: dict = {}
        self._last: list = [None] * self.nranks
        self._makers: dict = {}

    # -- seeded weights and inputs ---------------------------------------
    def _keys(self):
        return _base.seed_key(self.seed)

    def _layer_fn(self, control: bool = False):
        """A jitted maker of one layer's parameters over the world's
        mesh, from the layer's key: the routed experts sharded over the
        ranks in id order (rank r holds its ``per_rank`` experts), the
        rest replicated.  One program for every rank and layer."""
        if control not in self._makers:
            self._makers[control] = self._new_layer_fn(
                self.carry if control else None)
        return self._makers[control]

    def _new_layer_fn(self, carry):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        m = self.moe
        H, F, Fs, E = m.hidden, m.expert_width, m.shared_width, m.n_experts
        bf16 = jnp.bfloat16
        bias = self.bias

        def normal(k, shape, fan_in, dtype=bf16):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)

        def expert(key_l, e):
            k = jax.random.split(jax.random.fold_in(key_l, 1 + e), 3)
            w = (normal(k[0], (H, F), H), normal(k[1], (H, F), H),
                 normal(k[2], (F, H), F))
            return tuple(carry(x) for x in w) if carry else w

        def make(key_l):
            k = jax.random.split(jax.random.fold_in(key_l, 0), 5)
            w1, w3, w2 = jax.vmap(lambda e: expert(key_l, e))(
                jnp.arange(E))
            shared = (normal(k[2], (H, Fs), H), normal(k[3], (H, Fs), H),
                      normal(k[4], (Fs, H), Fs))
            if carry:
                shared = tuple(carry(x) for x in shared)
            return {"norm": (1.0 + 0.1 * jax.random.normal(
                        k[0], (H,), jnp.float32)).astype(bf16),
                    "router": normal(k[1], (E, H), H, jnp.float32),
                    "bias": jnp.asarray(bias),
                    "w1": w1, "w3": w3, "w2": w2,
                    "s1": shared[0], "s3": shared[1], "s2": shared[2]}

        split = NamedSharding(self.mesh, PartitionSpec("rank"))
        whole = NamedSharding(self.mesh, PartitionSpec())
        shardings = {k: split if k in ("w1", "w3", "w2") else whole
                     for k in ("norm", "router", "bias", "w1", "w3", "w2",
                               "s1", "s3", "s2")}
        return jax.jit(make, out_shardings=shardings)

    def _layer_key(self, layer: int):
        import jax

        return jax.random.fold_in(self._keys(), layer)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devices = list(self.world.engine.devices)
        self.mesh = Mesh(np.array(devices), ("rank",))
        make = self._layer_fn(self.control)
        self.params = [[None] * self.layers for _ in devices]
        for l in range(self.layers):
            full = make(self._layer_key(l))
            for name, arr in full.items():
                for shard in arr.addressable_shards:
                    r = devices.index(shard.device)
                    self.params[r][l] = self.params[r][l] or {}
                    self.params[r][l][name] = shard.data
        T, H, P = self.tokens, self.moe.hidden, self.nranks
        xs = jax.jit(lambda k: jax.random.normal(
            k, (P, self.sets, T, H), jnp.float32).astype(jnp.bfloat16),
            out_shardings=NamedSharding(self.mesh, PartitionSpec("rank")))(
            jax.random.fold_in(self._keys(), 1_000_000))
        self.inputs = [None] * P
        for shard in xs.addressable_shards:
            self.inputs[devices.index(shard.device)] = [
                shard.data[0, s] for s in range(self.sets)]
        self.layer_objs = [None] * self.nranks
        self.world.run(self._make_layer)

    def _make_layer(self, accl, rank: int) -> None:
        from accl_tpu.models import moe_ep

        layer = moe_ep.EPMoELayer(accl, self.moe, self.tokens)
        with self._lock:
            self.layer_objs[rank] = layer

    # -- units -----------------------------------------------------------
    def issue(self, accl, rank: int, u: int) -> list:
        layer, params = self.layer_objs[rank], self.params[rank]
        kept = self._keep.get(u - self.warmup_units, ())
        x = self.inputs[rank][u % self.sets]
        ahead: list = []
        for l in range(self.layers):
            y = layer(x, params[l])
            # at most two layers in flight: the device always has the
            # next layer queued, and no more layers' buffers are live
            ahead.append(y)
            if len(ahead) > 1:
                ahead.pop(0).block_until_ready()
            if l in kept:
                with self._lock:
                    self.kept.setdefault(
                        (u, l), [None] * self.nranks)[rank] = (x, y)
            if l == self.layers - 1:
                self._last[rank] = (x, y)
            x = y
        return [x]

    def calls(self) -> list:
        """(collective, per-rank payload bytes) of a unit's calls: the
        counts' alltoall, then dispatch and combine at the mean off-chip
        payload of an alltoallv call (the layers' device tally)."""
        t = self.tally
        n = 2 * t.get("calls", 0)
        per = ((t.get("dispatch_offchip_bytes", 0)
                + t.get("combine_offchip_bytes", 0)) / n) if n else 0.0
        counts = self.moe.n_experts * 4
        return [c for _ in range(self.layers) for c in (
            ("alltoall", counts), ("alltoallv", per), ("alltoallv", per))]

    def expected_lanes(self) -> dict:
        return {"lane_ring": 0, "lane_hlo": 3 * self.layers}

    # -- what the reference sees -----------------------------------------
    def plan_checks(self) -> None:
        spec, rng = self.check_spec, self._rng
        units = rng.choice(int(spec["units_below"]), int(spec["units"]),
                           replace=False)
        self._keep = {int(w): set(rng.choice(
            self.layers, int(spec["layers_per_unit"]),
            replace=False).tolist()) for w in units}

    def keep(self, rank: int, w: int, u: int, last: bool = False) -> None:
        """The last unit's last layer: its input and output, and the
        buffers of its three exchanges as they stand."""
        if not last:
            return
        layer = self.layer_objs[rank]
        with self._lock:
            self.kept.setdefault((u, self.layers - 1),
                                 [None] * self.nranks)[rank] = \
                self._last[rank]
            self.exchanges.append((rank, {
                "counts": (layer.ecount_send.dev, None,
                           layer.ecount_recv.dev, None),
                "dispatch": (layer.dispatch_send.dev,
                             layer.dispatch_count.dev,
                             layer.dispatch_recv.dev,
                             layer.dispatch_recv_count.dev),
                "combine": (layer.combine_send.dev,
                            layer.dispatch_recv_count.dev,
                            layer.combine_recv.dev,
                            layer.combine_recv_count.dev)}))

    def release(self) -> None:
        """Read the tally, free the buffers and the routed and shared
        weights (the kept arrays and the kept layers' routers stay)."""
        tally: dict = {}
        for layer in self.layer_objs:
            for k, v in layer.stats().items():
                tally[k] = tally.get(k, 0) + v
            layer.close()
        self.tally = tally
        kept_layers = {l for _u, l in self.kept}
        self.params = [[{k: p[k] for k in ("norm", "router", "bias")}
                        if l in kept_layers else None
                        for l, p in enumerate(ps)] for ps in self.params]
        self.inputs = []

    def check(self) -> tuple:
        spec = self.check_spec
        a2av, n_a2av = self._check_exchanges()
        self.exchanges = []   # their device memory, before the reference
        rel, ties, mismatch, n_tok, bad = self._check_layers()
        checks = {
            "a2av_max_abs_err": (a2av, float(spec["a2av_max_abs_err"])),
            "moe_rel_err": (rel, float(spec["moe_rel_err"])),
            "routing_ties": (ties / max(n_tok, 1),
                             float(spec["routing_ties"])),
            "routing_mismatch": (mismatch, int(spec["routing_mismatch"])),
        }
        bad += int(a2av > checks["a2av_max_abs_err"][1])
        return checks, n_a2av + n_tok, bad

    def _check_exchanges(self) -> tuple:
        """Largest |got - reference| over every rank's receive of the
        kept exchanges (payload and counts), and receives compared."""
        import jax

        by_rank = dict(self.exchanges)
        if len(by_rank) != self.nranks:
            return float("inf"), 0
        H, P = self.moe.hidden, self.nranks
        worst, compared = 0.0, 0
        for name in ("counts", "dispatch", "combine"):
            got = [jax.device_get(by_rank[r][name]) for r in range(P)]
            if name == "counts":   # an alltoall: P equal blocks
                counts = [np.full(P, self.per_rank, np.int32)] * P
                row = 1
            else:
                counts = [g[1] for g in got]
                row = H
            sends = [g[0] for g in got]
            refs = self.op.reference(sends, counts, row, got[0][2].size)
            for g, (want, want_counts) in zip(got, refs):
                worst = max(worst, self.op.max_abs_err(g[2], want))
                if g[3] is not None:
                    worst = max(worst, self.op.max_abs_err(g[3],
                                                           want_counts))
                compared += 1
        return worst, compared

    def _ref_fns(self):
        """Jitted reference parts on one device: routing, tie gaps, the
        shared expert and the residual of a block; every routed expert
        on it."""
        import jax

        k, sc, eps = self.moe.top_k, self.moe.scaling, self.moe.eps

        def base(x, p):
            with jax.default_matmul_precision("highest"):
                h = moe_reference.normed(x, p, eps)
                sel, gates, score = moe_reference.routing(h, p, k, sc)
                y0 = x.astype(np.float32) + moe_reference.shared(h, p)
                return h, sel, gates, moe_reference.tie_gap(score, k), y0

        def part(h, sel, gates, p):
            with jax.default_matmul_precision("highest"):
                return moe_reference.experts_part(h, sel, gates, 0, p["w1"],
                                                  p["w3"], p["w2"])

        return jax.jit(base), jax.jit(part)

    def _check_layers(self) -> tuple:
        """(largest per-token rel err outside the ties, tied tokens,
        mismatched tokens, tokens compared, tokens over the limit).  The
        reference runs on the first chip, every layer's experts gathered
        there: one program, whatever rank the tokens came from."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        from accl_tpu.models import moe_ep

        limit = float(self.check_spec["moe_rel_err"])
        gap_lim = float(self.check_spec["tie_gap"])
        block = int(self.check_spec["token_block"])
        here = SingleDeviceSharding(self.world.engine.devices[0])
        base, part = self._ref_fns()
        make = self._layer_fn(False)
        worst, ties, mismatch, n_tok, bad = 0.0, 0, 0, 0, 0
        planned = {(self.warmup_units + w, l)
                   for w, ls in self._keep.items() for l in ls}
        missing = planned - set(self.kept)
        if missing:   # the window ended before a planned unit
            worst, bad = float("inf"), len(missing)
        for (u, l), pairs in sorted(self.kept.items()):
            if any(p is None for p in pairs):
                worst, bad = float("inf"), bad + 1
                continue
            true = jax.device_put(make(self._layer_key(l)), here)
            for r, (x, y) in enumerate(pairs):
                sel_prog = moe_ep.select(self.moe, x, self.params[r][l])
                x, y, sel_prog = jax.device_put((x, y, sel_prog), here)
                for b0 in range(0, x.shape[0], block):
                    h, sel, gates, gap, y_ref = base(x[b0:b0 + block], true)
                    y_ref = y_ref + part(h, sel, gates, true)
                    yb = y[b0:b0 + block].astype(jnp.float32)
                    xb = x[b0:b0 + block].astype(jnp.float32)
                    err = (jnp.linalg.norm(yb - y_ref, axis=1)
                           / jnp.linalg.norm(y_ref - xb, axis=1))
                    tie = gap < gap_lim
                    same = jnp.all(jnp.sort(sel, 1) == jnp.sort(
                        sel_prog[b0:b0 + block], 1), axis=1)
                    err, tie, same = jax.device_get((err, tie, same))
                    err = np.where(tie, 0.0, np.nan_to_num(err, nan=np.inf))
                    worst = max(worst, float(err.max()))
                    bad += int((err > limit).sum())
                    ties += int(tie.sum())
                    mismatch += int((~same & ~tie).sum())
                    n_tok += err.size
            del true
        return worst, ties, mismatch, n_tok, bad
