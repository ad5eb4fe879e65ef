#!/usr/bin/env python
"""Run ACCL-TPU's main path on the TPU once and check every result.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip host

One chip: (a) the driver — ``initialize_world(Design.TPU, nranks=1)``
with one thread per rank, every collective at 4 KiB, 1 MiB, 16 MiB and
128 MiB per rank in fp32 and bf16, plus allreduce on the float16 and
int8 wire lanes; (b) the compiled kernels the driver and its SPMD users
dispatch — pallas_add/pallas_max, the compression casts (stochastic
rounding included), the virtual 8-rank self-ring collectives and flash
attention forward and backward.

``--chips 4``: only the 4-rank driver phase — every collective at the
same sizes, allreduce on the int8 and fused lanes, the calls at or above
the ring threshold re-run on the XLA HLO lane and compared, and each
rank's buffers placed on its own device.

Every result is checked against a plain reference (numpy on the same
inputs, or jnp outside Pallas); a phase that raises or mismatches makes
the script exit non-zero.  Driver inputs are small integers, so every
lossless lane must match its reference exactly.  The last stdout line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; with no
TPU the script exits non-zero and prints no result.  Everything runs in
this one process, which holds the chip(s).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

#: bytes per rank of each collective's send buffer: a latency size,
#: then sizes straddling the 4 MiB ring threshold
SIZES = (4 << 10, 1 << 20, 16 << 20, 128 << 20)
COLLECTIVES = ("sendrecv", "bcast", "scatter", "gather", "reduce",
               "allreduce", "reduce_scatter", "allgather", "alltoall",
               "barrier")
#: collectives the TPU backend serves on the Pallas ring lane at or above
#: its threshold on more than one rank
RING_OPS = ("allreduce", "reduce_scatter", "allgather")
#: ops whose count is the per-rank block, the send buffer holding P
PER_BLOCK = ("scatter", "reduce_scatter", "alltoall")
ROOT = 0
#: period of the driver input pattern: odd, so no collective's block
#: boundary lines up with it and a misplaced block cannot match
_PERIOD = (1 << 20) - 3

_OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chiprun_out")


def log(rec: dict, sink: list | None = None) -> None:
    print(" ".join(f"{k}={v}" for k, v in rec.items()), flush=True)
    if sink is not None:
        sink.append(rec)


class JsonlSink(list):
    """The records, each also written to `path` as it lands, so a run
    cut short still leaves what it checked."""

    def __init__(self, path: str):
        super().__init__()
        self._f = open(path, "w")

    def append(self, rec: dict) -> None:
        super().append(rec)
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def _check(rec: dict, err: float, tol: float) -> None:
    if not err <= tol:
        raise AssertionError(f"mismatch: {rec} max_err={err} > tol={tol}")


# ---------------------------------------------------------------------------
# (a) the driver
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _base(seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        -16, 17, _PERIOD).astype(np.float32)


def rank_data(n: int, dtype, rank: int, seed: int = 0) -> np.ndarray:
    """Small-integer input of rank `rank`: exact in bf16, and its sums
    over four ranks stay exact too (|x| <= 31)."""
    reps = -(-n // _PERIOD)
    out = np.tile(_base(seed), (reps, 1))
    out += (np.arange(reps, dtype=np.float32) % 7 + 3 * rank)[:, None]
    return out.reshape(-1)[:n].astype(dtype)


@functools.lru_cache(maxsize=1)
def _inputs(n: int, dtype, P: int) -> tuple:
    """Every rank's input; cached so the HLO re-run of a ring case
    reuses them."""
    return tuple(rank_data(n, dtype, r) for r in range(P))


def _references(op: str, inputs, count: int) -> list:
    """What each rank must hold after `op` (None: nothing to check),
    built once per case and shared between ranks."""
    P = len(inputs)
    if op == "sendrecv":
        return [inputs[(r - 1) % P] for r in range(P)]
    if op == "bcast":
        return [inputs[ROOT]] * P
    if op == "scatter":
        return [inputs[ROOT][r * count:(r + 1) * count] for r in range(P)]
    if op in ("gather", "allgather"):
        cat = np.concatenate(inputs)
        return [cat if op == "allgather" or r == ROOT else None
                for r in range(P)]
    if op == "alltoall":
        return [np.concatenate([x[r * count:(r + 1) * count]
                                for x in inputs]) for r in range(P)]
    if op in ("reduce", "allreduce", "reduce_scatter"):
        # integer inputs: the fp32 sum is exact
        total = inputs[0].astype(np.float32)
        for x in inputs[1:]:
            total += x.astype(np.float32)
        total = total.astype(inputs[0].dtype)
        if op == "reduce_scatter":
            return [total[r * count:(r + 1) * count] for r in range(P)]
        return [total if op == "allreduce" or r == ROOT else None
                for r in range(P)]
    return [None] * P


def _max_err(got: np.ndarray, ref: np.ndarray) -> float:
    bits = np.dtype(f"u{got.dtype.itemsize}")
    if got.dtype == ref.dtype and np.array_equal(got.view(bits),
                                                 ref.view(bits)):
        return 0.0  # the lossless lanes: one cheap pass over the bits
    return float(np.max(np.abs(got.astype(np.float32)
                               - ref.astype(np.float32))))


def _lane_counts(engine) -> dict:
    return {k: v for k, v in engine.stats.items() if k.startswith("lane_")}


def _lane(before: dict, after: dict) -> str:
    """The collective lane(s) whose engine counter the case moved."""
    moved = [k[len("lane_"):] for k in before if after[k] != before[k]]
    return "+".join(moved) or "p2p"


def _call(accl, op, count, send, recv, rank, P, wire, fused):
    from accl_tpu.constants import ReduceFunction

    if op == "sendrecv":
        req = accl.send(send, count, (rank + 1) % P, tag=5, run_async=True)
        accl.recv(recv, count, (rank - 1) % P, tag=5)
        if not req.wait(300):
            raise TimeoutError("send did not complete")
        req.check()
    elif op == "bcast":
        accl.bcast(send, count, ROOT)
    elif op == "scatter":
        accl.scatter(send, recv, count, ROOT)
    elif op == "gather":
        accl.gather(send, recv, count, ROOT)
    elif op == "reduce":
        accl.reduce(send, recv, count, ROOT, ReduceFunction.SUM)
    elif op == "allreduce":
        accl.allreduce(send, recv, count, ReduceFunction.SUM,
                       compress_dtype=wire, fused=fused)
    elif op == "reduce_scatter":
        accl.reduce_scatter(send, recv, count, ReduceFunction.SUM)
    elif op == "allgather":
        accl.allgather(send, recv, count)
    elif op == "alltoall":
        accl.alltoall(send, recv, count)
    elif op == "barrier":
        accl.barrier()
    else:
        raise ValueError(op)


def run_case(world, op: str, nbytes: int, dtype, wire=None,
             fused=None, keep: bool = False) -> dict:
    """One collective on every rank of `world`, called twice (the first
    call compiles): checks both results against numpy and returns the
    record.  ``keep`` also returns each rank's result array."""
    P = world.nranks
    dtype = np.dtype(dtype)
    elems = max(1, nbytes // dtype.itemsize)
    count = elems // P if op in PER_BLOCK else elems
    send_len = count * P if op in PER_BLOCK else count
    recv_len = count * P if op in ("gather", "allgather", "alltoall") \
        else count
    inputs = () if op == "barrier" else _inputs(send_len, dtype, P)
    refs = _references(op, inputs, count) if inputs else [None] * P

    def fn(accl, rank):
        send = (None if op == "barrier"
                else accl.create_buffer_like(inputs[rank]))
        recv = (None if op in ("bcast", "barrier")
                else accl.create_buffer(recv_len, dtype))
        out = send if op == "bcast" else recv
        times, errs = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            _call(accl, op, count, send, recv, rank, P, wire, fused)
            times.append(time.perf_counter() - t0)
            if refs[rank] is not None:
                errs.append(_max_err(out.host, refs[rank]))
        dev = (None if out is None
               else [d.id for d in out.dev.devices()][0])
        got = out.host if keep and out is not None else None
        for b in (send, recv):
            if b is not None:
                b.free()
        return times, errs, dev, got

    before = _lane_counts(world.engine)
    res = world.run(fn)
    lane = _lane(before, _lane_counts(world.engine))
    first = max(r[0][0] for r in res)
    warm = max(r[0][1] for r in res)
    errs = [e for r in res for e in r[1]]
    rec = {"ranks": P, "op": op,
           "bytes": 0 if op == "barrier" else send_len * dtype.itemsize,
           "dtype": dtype.name, "lane": lane,
           "max_err": max(errs) if errs else "n/a",
           "first_s": round(first, 4), "run_s": round(warm, 4),
           # the first call compiles; the warm call does not
           "compile_s": round(max(first - warm, 0.0), 4)}
    rec["devices"] = [r[2] for r in res]
    rec["_got"] = [r[3] for r in res] if keep else None
    if wire is not None:
        rec["wire"] = wire.name
        rec["_inputs_amax"] = max(float(np.max(np.abs(
            x.astype(np.float32)))) for x in inputs)
    return rec


def _emit(rec: dict, tol: float, sink: list) -> dict:
    shown = {k: v for k, v in rec.items() if not k.startswith("_")}
    log(dict({"phase": "driver"}, **shown), sink)
    if rec["max_err"] != "n/a":
        _check(shown, rec["max_err"], tol)
    return rec


def driver_phase(world, sizes=SIZES, dtypes=("float32", "bfloat16"),
                 sink: list | None = None) -> list:
    """Every collective at every size and dtype on `world`'s ranks, plus
    allreduce on the float16 and int8 wire lanes (fp32 operands)."""
    import ml_dtypes

    from accl_tpu.constants import DataType

    sink = [] if sink is None else sink
    for size in sizes:
        for dt in dtypes:
            dtype = ml_dtypes.bfloat16 if dt == "bfloat16" else np.float32
            for op in COLLECTIVES:
                _emit(run_case(world, op, size, dtype), 0.0, sink)
        _emit(run_case(world, "allreduce", size, np.float32,
                       wire=DataType.float16), 0.0, sink)
        rec = run_case(world, "allreduce", size, np.float32,
                       wire=DataType.int8)
        # block-scaled int8: one quantization step (amax/127 of the
        # block, half of it per rounding) per rank's contribution and
        # per hop — the test_fused_overlap bound
        _emit(rec, world.nranks * rec["_inputs_amax"] / 254 * 2, sink)
    return sink


def four_chip_phase(world, sizes=SIZES, dtypes=("float32", "bfloat16"),
                    sink: list | None = None) -> list:
    """The 4-rank driver: every collective against numpy; the calls at
    or above the engine's ring threshold must be served by the ring lane
    and equal the HLO lane's result; allreduce on the int8 and fused
    lanes; every rank's buffers on its own device."""
    import ml_dtypes

    from accl_tpu.constants import DataType

    sink = [] if sink is None else sink
    eng = world.engine
    threshold = eng.ring_threshold_bytes
    for size in sizes:
        for dt in dtypes:
            dtype = ml_dtypes.bfloat16 if dt == "bfloat16" else np.float32
            for op in COLLECTIVES:
                ring = op in RING_OPS and size >= threshold
                rec = _emit(run_case(world, op, size, dtype, keep=ring),
                            0.0, sink)
                if rec["devices"][0] is not None and \
                        len(set(rec["devices"])) != world.nranks:
                    raise AssertionError(f"ranks share a device: {rec}")
                if not ring:
                    continue
                if rec["lane"] != "ring":
                    raise AssertionError(f"ring lane not taken: {rec}")
                eng.ring_threshold_bytes = 1 << 62  # HLO lane
                try:
                    hlo = run_case(world, op, size, dtype, keep=True)
                finally:
                    eng.ring_threshold_bytes = threshold
                if hlo["lane"] != "hlo":
                    raise AssertionError(f"HLO lane not taken: {hlo}")
                diff = max(_max_err(a, b)
                           for a, b in zip(rec["_got"], hlo["_got"]))
                cmp = {"phase": "ring_vs_hlo", "op": op,
                       "bytes": rec["bytes"], "dtype": rec["dtype"],
                       "ring_lane": rec["lane"], "hlo_lane": hlo["lane"],
                       "hlo_compile_s": hlo["compile_s"],
                       "hlo_run_s": hlo["run_s"], "max_diff": diff}
                log(cmp, sink)
                _check(cmp, diff, 0.0)
            _emit(run_case(world, "allreduce", size, dtype, fused=True),
                  0.0, sink)
        rec = run_case(world, "allreduce", size, np.float32,
                       wire=DataType.int8)
        _emit(rec, world.nranks * rec["_inputs_amax"] / 254 * 2, sink)
    log({"phase": "engine_stats", **eng.stats,
         "devices": [d.id for d in eng.devices]}, sink)
    return sink


# ---------------------------------------------------------------------------
# (b) compiled kernels
# ---------------------------------------------------------------------------
def _timed(fn, *args):
    """(output, compile seconds, run seconds) of jit(fn)(*args)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _kernel_rec(sink, name, shape, dtype, err, tol, c, r, **extra):
    rec = {"phase": "kernel", "kernel": name, "shape": "x".join(
        str(d) for d in shape), "dtype": dtype, "max_err": err,
        "compile_s": round(c, 4), "run_s": round(r, 4), **extra}
    log(rec, sink)
    _check(rec, err, tol)


def kernels_phase(interpret: bool, lane_elems: int = 64 << 20,
                  ring_rows: int = 4096, flash=(4, 2048, 4, 128),
                  sink: list | None = None) -> list:
    """The Pallas kernels with ``interpret`` (False on the chip), each
    against jnp outside Pallas or the self-ring closed form."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from accl_tpu.ops.compression import compress_cast, decompress_cast
    from accl_tpu.ops.flash import flash_attention
    from accl_tpu.ops.reduce_ops import pallas_add, pallas_max
    from accl_tpu.ops.ring import (ring_all_gather_pallas,
                                   ring_all_reduce_pallas,
                                   ring_reduce_scatter_pallas)
    from accl_tpu.parallel.ring_attention import _dense_attention

    sink = [] if sink is None else sink
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    shape = (lane_elems // 128, 128)
    a = jax.random.normal(keys[0], shape, jnp.float32)
    b = jax.random.normal(keys[1], shape, jnp.float32)
    for name, kern, ref in (("pallas_add", pallas_add, jnp.add),
                            ("pallas_max", pallas_max, jnp.maximum)):
        out, c, r = _timed(lambda x, y, k=kern: k(x, y, interpret=interpret),
                           a, b)
        err = float(jnp.max(jnp.abs(out - ref(a, b))))
        _kernel_rec(sink, name, shape, "float32", err, 0.0, c, r)

    for dt in (jnp.bfloat16, jnp.float16):
        out, c, r = _timed(lambda x, d=dt: decompress_cast(
            compress_cast(x, d, interpret=interpret), jnp.float32,
            interpret=interpret), a)
        err = float(jnp.max(jnp.abs(out - a.astype(dt).astype(jnp.float32))))
        _kernel_rec(sink, "compress_cast", shape, jnp.dtype(dt).name, err,
                    0.0, c, r)
    if not interpret:  # the stochastic lane needs the TPU's PRNG
        x = jnp.full(shape, 1.0 + 2.0 ** -12, jnp.float32)
        out, c, r = _timed(lambda v: compress_cast(
            v, jnp.bfloat16, stochastic=True, seed=7), x)
        vals = np.unique(np.asarray(out.astype(jnp.float32)))
        up = float(jnp.mean((out > 1.0).astype(jnp.float32)))
        if set(vals.tolist()) != {1.0, 1.0 + 2.0 ** -7}:
            raise AssertionError(f"stochastic rounding values {vals}")
        # P(round up) = 2^-12 / 2^-7 = 1/32
        _kernel_rec(sink, "compress_cast_stochastic", shape, "bfloat16",
                    abs(up - 1 / 32), 2e-3, c, r, up_fraction=up)

    V = 8
    mesh = Mesh(np.array(jax.devices()[:1]), ("r",))

    def smap(f):
        return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)

    x = jax.random.normal(keys[2], (ring_rows, 128), jnp.float32)
    xs = jax.random.normal(keys[3], (V, ring_rows, 128), jnp.float32)
    out, c, r = _timed(smap(lambda v: ring_all_gather_pallas(
        v, "r", ring_size=V, interpret=interpret)), x)
    err = float(jnp.max(jnp.abs(out - jnp.broadcast_to(x, out.shape))))
    _kernel_rec(sink, "selfring_all_gather", (V, ring_rows, 128),
                "float32", err, 0.0, c, r)
    out, c, r = _timed(smap(lambda v: ring_reduce_scatter_pallas(
        v, "r", ring_size=V, interpret=interpret)), xs)
    want = np.asarray(xs).astype(np.float64).sum(axis=0)
    err = float(np.max(np.abs(np.asarray(out) - want)))
    _kernel_rec(sink, "selfring_reduce_scatter", (V, ring_rows, 128),
                "float32", err, 1e-4, c, r)
    flat = xs.reshape(V * ring_rows, 128)
    out, c, r = _timed(smap(lambda v: ring_all_reduce_pallas(
        v, "r", ring_size=V, interpret=interpret)), flat)
    err = float(np.max(np.abs(np.asarray(out).reshape(V, ring_rows, 128)
                              - want[None])))
    _kernel_rec(sink, "selfring_all_reduce", (V * ring_rows, 128),
                "float32", err, 1e-4, c, r)

    B, T, H, D = flash
    q, k, v, g = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
                  for kk in keys[4:8])

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=True, mxu_dtype=jnp.float32,
                               interpret=interpret)

    def dense(q, k, v):
        with jax.default_matmul_precision("float32"):
            return _dense_attention(q, k, v, causal=True)

    def grads(attn):
        return lambda q, k, v: jax.grad(
            lambda *a: jnp.sum(attn(*a) * g), argnums=(0, 1, 2))(q, k, v)

    out, c, r = _timed(fa, q, k, v)
    ref = dense(q, k, v)
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    _kernel_rec(sink, "flash_attention_fwd", (B, T, H, D), "float32", err,
                1e-2, c, r, err_kind="rel")
    gout, c, r = _timed(grads(fa), q, k, v)
    gref = jax.jit(grads(dense))(q, k, v)
    err = max(float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
              for x, y in zip(gout, gref))
    _kernel_rec(sink, "flash_attention_bwd", (B, T, H, D), "float32", err,
                2e-2, c, r, err_kind="rel")
    return sink


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-rank driver phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found platform {devices[0].platform!r}, "
              "not a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from accl_tpu.utils.compile_cache import enable
    from accl_tpu.utils.bringup import Design, initialize_world
    from accl_tpu.utils.platform import pallas_interpret

    log({"phase": "devices", "platform": devices[0].platform,
         "kind": repr(devices[0].device_kind),
         "ids": [d.id for d in devices], "compile_cache": enable()})
    os.makedirs(_OUT_DIR, exist_ok=True)
    sink = JsonlSink(os.path.join(_OUT_DIR,
                                  f"chip_smoke_{args.chips}chip.jsonl"))
    t0 = time.perf_counter()
    world = initialize_world(Design.TPU, nranks=args.chips)
    try:
        if args.chips == 4:
            four_chip_phase(world, sink=sink)
        else:
            driver_phase(world, sink=sink)
            kernels_phase(pallas_interpret(), sink=sink)
    finally:
        world.close()
        sink.close()
    log({"phase": "done", "wall_s": round(time.perf_counter() - t0, 1)})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
