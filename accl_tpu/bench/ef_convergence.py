"""EF-convergence lane: does error feedback close the int8 wire gap?

The r17 quantized lane ships gradients as int8 + per-block fp32 scales
(4:1 wire compression); the EQuARX-style error feedback carries each
hop's requantization error into the next hop's quantization input
(ops/quantized.py).  The sweep records whether that per-hop carry
matters where it counts — the LOSS TRAJECTORY of a real training run:

- three lanes train the flagship transformer LM under data parallelism
  with IDENTICAL init, data order, and learning rate — only the
  gradient all-reduce differs:

  * ``fp32``     — exact ``lax.pmean`` (the reference trajectory)
  * ``int8``     — quantized ring, no error carry
  * ``int8_ef``  — quantized ring + per-hop error feedback

- everything is deterministic (no stochastic rounding, fixed seeds),
  so the recorded divergence is pure quantization arithmetic, not
  noise: a re-run reproduces the CSV bit-for-bit on the same jax.

The committed record (bench/results/ef_convergence_rNN.csv/.md) is the
evidence behind the "int8 wire lane tracks fp32" claim in the docs;
the summary gates that EVERY quantized lane's mean |loss - fp32| stays
under TRACK_TOL.  EF vs raw is reported as data, not gated: with
deterministic round-to-nearest the per-hop error carry redistributes
requantization error rather than strictly shrinking it, so at healthy
scales both lanes sit at the same ~1e-4 noise floor — EF's guarantee
(bias that dithers out instead of growing linearly in P) only
separates from raw int8 at large ring sizes or biased rounding.

Run via ``scripts/run_sweep.py --ef-convergence`` (spawns host-platform
virtual devices; no accl world needed — the lanes are jax-level
collectives inside shard_map, the same route sync_gradients takes in
the 3D example).
"""
from __future__ import annotations

import csv
from typing import Optional, Sequence

#: lane -> (compress, error_feedback) for sync_gradients
LANES = {
    "fp32": (None, False),
    "int8": ("int8", False),
    "int8_ef": ("int8", True),
}

#: gate: a quantized lane's mean |loss - fp32| over the run must stay
#: under this (the trajectories at these scales agree to ~1e-4; 5e-3
#: leaves an order of magnitude of slack before "diverged")
TRACK_TOL = 5e-3


def _make_step(mesh, cfg, lane: str, lr: float):
    """One jitted SGD step for a lane.

    Params and tokens enter pre-stacked on a leading dp dim with
    P("dp") specs (every shard holds its own copy/slice and indexes
    [0]) — the repo-wide idiom for driving sync_gradients on old-jax
    shard_map, where replicated-input grads would otherwise be
    auto-psummed by the transpose (no lax.pvary on 0.4.37).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..models.transformer import loss_fn
    from ..parallel.strategies import sync_gradients

    compress, ef = LANES[lane]

    def body(params_stacked, tokens):
        params = jax.tree_util.tree_map(lambda x: x[0], params_stacked)
        toks = tokens[0]

        def local_loss(p):
            s, c = loss_fn(p, toks, cfg)
            return s / c

        loss, grads = jax.value_and_grad(local_loss)(params)
        grads = sync_gradients(grads, axis="dp", compress=compress,
                               mean=True, error_feedback=ef)
        loss = lax.pmean(loss, "dp")
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        # re-stack so the outputs ride the same P("dp") layout in
        return (jax.tree_util.tree_map(lambda x: x[None], new_params),
                loss[None])

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                       out_specs=(P("dp"), P("dp")))
    return jax.jit(fn)


def run_ef_convergence(writer, steps: int = 40, dp: int = 4,
                       batch: int = 4, seq: int = 32, lr: float = 0.2,
                       seed: int = 0,
                       lanes: Sequence[str] = ("fp32", "int8", "int8_ef"),
                       log=lambda s: None) -> dict:
    """Train one small LM per lane on identical data; write the wide
    per-step loss CSV (step, <lane>...) to `writer` and return the
    summary dict (final losses + deviations vs fp32)."""
    import jax
    import numpy as np

    from ..models.transformer import ModelConfig, init_params
    from ..parallel.mesh import MeshConfig, make_mesh

    devices = jax.devices()
    if len(devices) < dp:
        raise RuntimeError(
            f"need {dp} devices for the dp axis, have {len(devices)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={dp}")
    mesh = make_mesh(MeshConfig(dp=dp), devices=devices[:dp])

    cfg = ModelConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                      d_head=16, d_ff=256)
    rng = np.random.default_rng(seed)
    params0 = init_params(rng, cfg)
    # the whole run's token stream up front: [steps, dp, batch, seq] —
    # every lane consumes the exact same bytes in the same order.  A
    # noisy successor chain (next = prev + 1 mod vocab, 10% resets)
    # gives the LM something learnable so the trajectories DESCEND and
    # real gradient signal flows through the quantized ring.
    tokens = np.empty((steps, dp, batch, seq), np.int32)
    tokens[..., 0] = rng.integers(0, cfg.vocab,
                                  size=(steps, dp, batch))
    for t in range(1, seq):
        succ = (tokens[..., t - 1] + 1) % cfg.vocab
        noise = rng.integers(0, cfg.vocab, size=(steps, dp, batch))
        keep = rng.random(size=(steps, dp, batch)) < 0.9
        tokens[..., t] = np.where(keep, succ, noise)

    import jax.numpy as jnp
    traj: dict = {}
    for lane in lanes:
        step = _make_step(mesh, cfg, lane, lr)
        params = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * dp), params0)
        losses = []
        for i in range(steps):
            params, loss = step(params, jnp.asarray(tokens[i]))
            losses.append(float(loss[0]))
        traj[lane] = losses
        log(f"[ef] lane {lane:8s} loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f} over {steps} steps")

    w = csv.writer(writer)
    w.writerow(["step"] + list(lanes))
    for i in range(steps):
        w.writerow([i] + [f"{traj[lane][i]:.6f}" for lane in lanes])

    summary = {"steps": steps, "dp": dp, "batch": batch, "seq": seq,
               "lr": lr, "seed": seed,
               "final": {lane: traj[lane][-1] for lane in lanes}}
    if "fp32" in traj:
        ref = np.asarray(traj["fp32"])
        for lane in lanes:
            if lane == "fp32":
                continue
            dev = np.abs(np.asarray(traj[lane]) - ref)
            summary[f"{lane}_mean_abs_dev"] = float(dev.mean())
            summary[f"{lane}_max_abs_dev"] = float(dev.max())
            log(f"[ef] {lane} vs fp32: mean |dloss| {dev.mean():.3e}, "
                f"max {dev.max():.3e}")
    return summary


def write_summary_md(path: str, summary: dict,
                     csv_name: Optional[str] = None) -> None:
    """The committed .md companion: run shape, final losses, and the
    EF-vs-raw deviation verdict."""
    final = summary["final"]
    lines = [
        "# int8 error-feedback convergence record",
        "",
        f"- run: {summary['dp']} dp ranks x {summary['batch']} "
        f"batch x {summary['seq']} seq, {summary['steps']} SGD steps, "
        f"lr {summary['lr']}, seed {summary['seed']} (deterministic — "
        f"no stochastic rounding)",
    ]
    if csv_name:
        lines.append(f"- trajectory: {csv_name} (per-step loss, one "
                     f"column per lane)")
    lines += [
        "",
        "| lane | final loss | mean \\|loss - fp32\\| | "
        "max \\|loss - fp32\\| |",
        "|---|---|---|---|",
    ]
    for lane in final:
        mean_d = summary.get(f"{lane}_mean_abs_dev")
        max_d = summary.get(f"{lane}_max_abs_dev")
        fmt = (lambda v: "—" if v is None else f"{v:.3e}")
        lines.append(f"| {lane} | {final[lane]:.6f} | {fmt(mean_d)} | "
                     f"{fmt(max_d)} |")
    devs = {k[:-len("_mean_abs_dev")]: v for k, v in summary.items()
            if k.endswith("_mean_abs_dev")}
    if devs:
        worst = max(devs.values())
        verdict = "PASS" if worst <= TRACK_TOL else "FAIL"
        lines += [
            "",
            f"- gate ({verdict}): every quantized lane must track the "
            f"fp32 trajectory within mean |dloss| <= {TRACK_TOL:g} "
            f"(worst lane: {worst:.3e})",
            "- EF vs raw int8 is reported, not gated: with "
            "round-to-nearest the per-hop error carry redistributes "
            "requantization error rather than strictly shrinking it — "
            "its bias bound only separates from raw at large ring "
            "sizes or biased rounding",
        ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
