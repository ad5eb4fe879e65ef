"""LongCat-Flash ScMoE decode on one chip: the program's ScMoE layer
(``accl_tpu.models.scmoe``) over a configuration's layers, its expert
branch's exchanges through the driver, chained layer to layer.

A unit is one decode step: the rank thread takes its input set's hidden
states ``[tokens, hidden]`` (bf16) and runs every layer on the previous
one's output, three driver calls a layer (the per-expert counts by
``alltoall``, dispatch and combine by ``alltoallv``, on the
communicator of the cell's chips), one wait a unit.  The interface is
closed_loop.py's.

The chip holds the routed experts that the configuration's deployment
names (``held_experts``), and routes over all of them and the
zero-compute ones.  The seed draws the weights and the inputs, on the
device; routed expert e of layer l is drawn from its own key, so the
reference remakes the held ones alike.  The traffic file fixes the
routing skew: the selection bias (a Zipf profile over the routed
experts in a fixed order, and one bias for the zero-compute experts) is
the same for every seed.

The check (all on the chip, after the window):

``scmoe_rel_err``
    for kept (unit, layer) pairs, the largest per-token error of the
    layer, ||y - y_ref|| / ||y_ref - x||, with y the program's output
    and y_ref scmoe_reference.py's float32 layer on the program's own
    kept input x, tokens in ``routing_ties`` left out; the residual x
    is left out of the denominator, as in ep_moe.py.  A planned pair
    that the window did not reach fails the check;
``experts_rel_err``
    the last unit's last layer: every received row's expert output, as
    the combine sends it back, against the reference FFN of its expert
    on that row, the largest ||out - ref|| / ||ref|| over the rows; the
    layer's error above holds the routed experts at a few percent of
    ||y_ref - x||, this one holds them alone;
``routing_ties``, ``routing_mismatch``
    as in ep_moe.py, by the program's own router (``moe_ep.select``).

The control is the nearest precision below the configuration's bf16:
the routed experts' and the dense FFNs' weights carried through float8
e4m3 with one scale per 128 x 128 block (ep_moe.py's ``carry_fp8``).
"""
from __future__ import annotations

import json
import os

import numpy as np

import harness
import scmoe_reference

_here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_base = harness.load_module(_here, "traffic", "closed_loop")
_ep = harness.load_module(_here, "traffic", "ep_moe")

#: the dense path's weights (the control carries them too)
DENSE = ("ffn1_w1", "ffn1_w3", "ffn1_w2", "ffn2_w1", "ffn2_w3", "ffn2_w2")


def selection_bias(routing: dict, n_experts: int, zero: int) -> np.ndarray:
    """The traffic's selection bias over the routed then the zero-compute
    experts: ``bias_scale * (log p_e - mean log p)`` for routed expert
    e, p_e proportional to its place in ``expert_order`` to the power
    ``-zipf_exponent``, and ``zero_bias`` for every zero-compute one."""
    order = expert_order(routing, n_experts)
    place = np.empty(n_experts)
    place[order] = np.arange(1, n_experts + 1)
    logp = -float(routing["zipf_exponent"]) * np.log(place)
    bias = float(routing["bias_scale"]) * (logp - logp.mean())
    return np.r_[bias, np.full(zero, float(routing["zero_bias"]))].astype(
        np.float32)


def expert_order(routing: dict, n_experts: int) -> np.ndarray:
    """The routed experts, most loaded first."""
    return np.random.default_rng(
        int(routing["permutation_seed"])).permutation(n_experts)


class Stream(_base.Stream):
    """One cell's ScMoE decode over a one-chip world."""

    def __init__(self, world, cfg: dict, traffic: dict, seed: int,
                 control: bool = False, here: str = harness.HERE):
        from accl_tpu.models import scmoe

        if world.nranks != 1:
            raise ValueError("scmoe_decode runs one chip's share of the "
                             "deployment: a one-chip world")
        self.world = world
        self.cfg = cfg
        self.held = tuple(int(e) for e in cfg["deployment"]["held_experts"])
        published = {**cfg, **cfg["published"]}
        self.block_cfg = scmoe.ScMoEConfig.from_hf(published, (self.held,))
        self.moe = self.block_cfg.moe
        self.layers = int(cfg["num_layers"])
        self.tokens = int(cfg["assumed"]["decode_tokens_per_chip"])
        self.nranks = 1
        self.sets = int(traffic["input_sets"])
        self.warmup_units = int(traffic["warmup_units"])
        self.check_spec = traffic["check"]
        self.bias = selection_bias(traffic["routing"], self.moe.n_experts,
                                   self.moe.zero_experts)
        self.seed = seed
        self.carry = None
        if control:
            name = traffic["control"]["lower_weights"]
            if name not in _ep.CARRIERS:
                raise ValueError(f"no carrier {name!r} for the control")
            self.carry = _ep.CARRIERS[name]
        self._rng = np.random.default_rng(seed)
        self._keep: dict = {}     # window unit -> kept layers
        self.kept: dict = {}      # (unit, layer) -> (x, y)
        self.experts_kept = None  # the last layer's expert rows
        self.tally: dict = {}
        self._last = None
        self._makers: dict = {}

    # -- seeded weights and inputs ---------------------------------------
    def _keys(self):
        return _base.seed_key(self.seed)

    def _layer_key(self, layer: int):
        import jax

        return jax.random.fold_in(self._keys(), layer)

    def _layer_fn(self, carry=None):
        """A jitted maker of one layer's parameters on the chip, from the
        layer's key, with the held experts' weights in ``held``'s
        order."""
        if carry not in self._makers:
            self._makers[carry] = self._new_layer_fn(carry)
        return self._makers[carry]

    def _new_layer_fn(self, carry):
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        m = self.moe
        H, F, D = m.hidden, m.expert_width, self.block_cfg.ffn_width
        bf16 = jnp.bfloat16
        bias, held = self.bias, jnp.asarray(self.held)
        lower = carry or (lambda w: w)

        def normal(k, shape, fan_in, dtype=bf16):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)

        def expert(key_l, e):
            k = jax.random.split(jax.random.fold_in(key_l, 1 + e), 3)
            return (lower(normal(k[0], (H, F), H)),
                    lower(normal(k[1], (H, F), H)),
                    lower(normal(k[2], (F, H), F)))

        def make(key_l):
            k = jax.random.split(jax.random.fold_in(key_l, 0), 9)
            w1, w3, w2 = jax.vmap(lambda e: expert(key_l, e))(held)
            dense = dict(zip(DENSE, (
                normal(k[3], (H, D), H), normal(k[4], (H, D), H),
                normal(k[5], (D, H), D), normal(k[6], (H, D), H),
                normal(k[7], (H, D), H), normal(k[8], (D, H), D))))

            def norm(key):
                return (1.0 + 0.1 * jax.random.normal(
                    key, (H,), jnp.float32)).astype(bf16)

            return {"norm": norm(k[0]), "norm4": norm(k[2]),
                    "router": normal(k[1], (m.router_width, H), H,
                                     jnp.float32),
                    "bias": jnp.asarray(bias),
                    "w1": w1, "w3": w3, "w2": w2,
                    **{n: lower(w) for n, w in dense.items()}}

        here = SingleDeviceSharding(self.world.engine.devices[0])
        return jax.jit(make, out_shardings=here)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        make = self._layer_fn(self.carry)
        self.params = [make(self._layer_key(l)) for l in range(self.layers)]
        T, H = self.tokens, self.moe.hidden
        here = SingleDeviceSharding(self.world.engine.devices[0])
        xs = jax.jit(lambda k: jax.random.normal(
            k, (self.sets, T, H), jnp.float32).astype(jnp.bfloat16),
            out_shardings=here)(jax.random.fold_in(self._keys(), 1_000_000))
        self.inputs = [xs[s] for s in range(self.sets)]
        self.blocks = [None]
        self.world.run(self._make_block)

    def _make_block(self, accl, rank: int) -> None:
        from accl_tpu.models import scmoe

        self.blocks[rank] = scmoe.ScMoEBlock(accl, self.block_cfg,
                                             self.tokens)

    # -- units -----------------------------------------------------------
    def issue(self, accl, rank: int, u: int) -> list:
        block = self.blocks[rank]
        kept = self._keep.get(u - self.warmup_units, ())
        x = self.inputs[u % self.sets]
        for l in range(self.layers):
            y = block(x, self.params[l])
            if l in kept:
                self.kept[(u, l)] = (x, y)
            if l == self.layers - 1:
                self._last = (x, y)
            x = y
        return [x]

    def calls(self) -> list:
        """(collective, per-rank payload bytes) of a unit's calls: the
        counts' alltoall, then dispatch and combine at the mean rows a
        layer received (the layers' device tally)."""
        t = self.tally
        n = t.get("calls", 0)
        per = t.get("dispatch_recv_bytes", 0) / n if n else 0.0
        counts = len(self.held) * 4
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
        """The last unit's last layer: its input and output, and its
        expert program's received rows, counts and output rows."""
        if not last:
            return
        moe = self.blocks[rank].moe
        self.kept[(u, self.layers - 1)] = self._last
        self.experts_kept = (moe.dispatch_recv.dev, moe.ecount_recv.dev,
                             moe.combine_send.dev)

    def release(self) -> None:
        """Read the tally, free the buffers and the weights (the kept
        arrays and the kept layers' routers stay)."""
        self.tally = self.blocks[0].stats()
        self.blocks[0].close()
        kept_layers = {l for _u, l in self.kept}
        self.params = [{k: p[k] for k in ("norm", "router", "bias")}
                       if l in kept_layers else None
                       for l, p in enumerate(self.params)]
        self.inputs = []

    def check(self) -> tuple:
        # the layers' tally (zero_rows, absent_rows beside the dispatch
        # fields), a line of its own before the result line
        print(json.dumps({"tally": self.tally}), flush=True)
        spec = self.check_spec
        experts, rows = self._check_experts()
        self.experts_kept = None
        rel, ties, mismatch, n_tok, bad = self._check_layers()
        checks = {
            "scmoe_rel_err": (rel, float(spec["scmoe_rel_err"])),
            "experts_rel_err": (experts, float(spec["experts_rel_err"])),
            "routing_ties": (ties / max(n_tok, 1),
                             float(spec["routing_ties"])),
            "routing_mismatch": (mismatch, int(spec["routing_mismatch"])),
        }
        bad += int(experts > checks["experts_rel_err"][1])
        return checks, n_tok + rows, bad

    def _true_layer(self, layer: int):
        """The layer's parameters as the configuration states them (the
        control's weights are carried, these are not)."""
        return self._layer_fn(None)(self._layer_key(layer))

    def _check_experts(self) -> tuple:
        """(largest per-row error of the kept expert output, rows
        compared): each received row of local expert e against the
        reference FFN of e on that row."""
        import jax
        import jax.numpy as jnp

        if self.experts_kept is None:
            return float("inf"), 0
        rows, counts, out = self.experts_kept
        counts = np.asarray(jax.device_get(counts))
        total = int(counts.sum())
        if total == 0:
            return 0.0, 0
        per = len(self.held)
        group = np.repeat(np.arange(counts.size) % per, counts)
        true = self._true_layer(self.layers - 1)
        x = rows[:total]

        @jax.jit
        def ref(x, w1, w3, w2):
            with jax.default_matmul_precision("highest"):
                return scmoe_reference.ffn(x.astype(jnp.float32), w1, w3, w2)

        want = np.zeros((total, self.moe.hidden), np.float32)
        for e in range(per):
            mine = group == e
            if mine.any():
                want[mine] = np.asarray(ref(x, true["w1"][e], true["w3"][e],
                                            true["w2"][e]))[mine]
        del true
        got = np.asarray(jax.device_get(out[:total]), np.float32)
        err = (np.linalg.norm(got - want, axis=1)
               / np.linalg.norm(want, axis=1))
        return float(np.nan_to_num(err, nan=np.inf).max()), total

    def _check_layers(self) -> tuple:
        """(largest per-token rel err outside the ties, tied tokens,
        mismatched tokens, tokens compared, tokens over the limit)."""
        import jax
        import jax.numpy as jnp
        from accl_tpu.models import moe_ep

        spec = self.check_spec
        limit, gap_lim = float(spec["scmoe_rel_err"]), float(spec["tie_gap"])
        m = self.moe
        k, sc, eps, E = m.top_k, m.scaling, m.eps, m.n_experts

        @jax.jit
        def reference(x, p):
            with jax.default_matmul_precision("highest"):
                u = scmoe_reference.normed(x, p, eps)
                sel, gates, score = scmoe_reference.routing(u, p, k, sc)
                y = (scmoe_reference.dense(x, u, p, eps)
                     + scmoe_reference.zero_part(u, sel, gates, E)
                     + scmoe_reference.experts_part(
                         u, sel, gates, self.held, p["w1"], p["w3"],
                         p["w2"]))
                return y, sel, scmoe_reference.tie_gap(score, k)

        worst, ties, mismatch, n_tok, bad = 0.0, 0, 0, 0, 0
        planned = {(self.warmup_units + w, l)
                   for w, ls in self._keep.items() for l in ls}
        missing = planned - set(self.kept)
        if missing:   # the window ended before a planned unit
            worst, bad = float("inf"), len(missing)
        for (u, l), (x, y) in sorted(self.kept.items()):
            sel_prog = moe_ep.select(m, x, self.params[l])
            y_ref, sel, gap = reference(x, self._true_layer(l))
            xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
            err = (jnp.linalg.norm(yf - y_ref, axis=1)
                   / jnp.linalg.norm(y_ref - xf, axis=1))
            same = jnp.all(jnp.sort(sel, 1) == jnp.sort(sel_prog, 1), axis=1)
            err, tie, same = jax.device_get((err, gap < gap_lim, same))
            err = np.where(tie, 0.0, np.nan_to_num(err, nan=np.inf))
            worst = max(worst, float(err.max()))
            bad += int((err > limit).sum())
            ties += int(tie.sum())
            mismatch += int((~same & ~tie).sum())
            n_tok += err.size
        return worst, ties, mismatch, n_tok, bad
