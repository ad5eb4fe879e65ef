"""Collective benchmark sweep — the reference bench harness.

Equivalent of the reference ACCLSweepBenchmark: parameterized sweep over
2^4..2^19 elements for every collective, timing via the engine's
performance counter, CSV rows out (test/host/xrt/src/bench.cpp:25-61;
csv fixture.hpp:75-85,126-133; parse_bench_results.py).

Works against any world object exposing `accls` + `run` (EmuWorld or
TpuWorld), so the same sweep runs on the emulator rung and the TPU
backend — and the busbw column is directly comparable to the
allreduce-busbw metric of record (BASELINE.md).
"""
from __future__ import annotations

import contextlib
import csv
import io
import time
from dataclasses import dataclass
from typing import Iterable, Optional

import numpy as np

from ..constants import ReduceFunction
from ..observability import metrics as _metrics


COLLECTIVES = ("sendrecv", "bcast", "scatter", "gather", "allgather",
               "reduce", "allreduce", "reduce_scatter", "alltoall")


@dataclass
class SweepConfig:
    collectives: tuple = COLLECTIVES
    count_pows: Iterable[int] = tuple(range(4, 20))  # 2^4 .. 2^19 elements
    dtype: str = "float32"
    repetitions: int = 3
    root: int = 0


def _resolve_dtype(name) -> np.dtype:
    """np.dtype, accepting accelerator dtypes (bfloat16 via ml_dtypes)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(name)))


# bandwidth conventions (nccl-tests): one implementation, shared with
# the metrics registry the driver publishes into.  The payload factor
# matters: r4's CSVs recorded count*itemsize for all collectives, which
# made the x P collectives read as super-linear against byte-equal
# allreduce rows when the real per-byte cost was BETTER (VERDICT r4
# weak #4 — an accounting artifact, not a lowering cost).
_busbw_factor = _metrics.busbw_factor
_payload_factor = _metrics.payload_factor


def run_sweep(world, config: SweepConfig = SweepConfig(),
              writer: Optional[io.TextIOBase] = None) -> list[dict]:
    """Run the sweep; returns rows and optionally streams CSV."""
    rows: list[dict] = []
    csv_writer = None
    if writer is not None:
        csv_writer = csv.DictWriter(writer, fieldnames=[
            "collective", "count", "bytes", "duration_us", "algbw_GBps",
            "busbw_GBps", "repetition"])
        # only emit the header at the start of the stream, so several
        # sweeps (e.g. one per dtype) can append to one CSV
        try:
            at_start = writer.tell() == 0
        except (OSError, AttributeError):
            at_start = True
        if at_start:
            csv_writer.writeheader()

    P = world.nranks
    dtype = _resolve_dtype(config.dtype)

    for coll in config.collectives:
        for pw in config.count_pows:
            count = 1 << pw
            # one untimed warmup per (collective, size): on the
            # TPU-backend rung the first call pays the jit compile
            # (observed 6-30x the steady-state time), which would
            # dominate the recorded curve
            _run_once(world, coll, count, dtype, config.root)
            for rep in range(config.repetitions):
                dur_s = _run_once(world, coll, count, dtype, config.root)
                nbytes = count * _payload_factor(coll, P) * dtype.itemsize
                algbw = nbytes / dur_s / 1e9 if dur_s > 0 else 0.0
                row = {
                    "collective": coll,
                    "count": count,
                    "bytes": nbytes,
                    "duration_us": round(dur_s * 1e6, 2),
                    "algbw_GBps": round(algbw, 4),
                    "busbw_GBps": round(algbw * _busbw_factor(coll, P), 4),
                    "repetition": rep,
                }
                rows.append(row)
                if csv_writer:
                    csv_writer.writerow(row)

    # publish per-collective peak bandwidth into the process metrics
    # registry so `dump_metrics()` after a sweep reports the same
    # busbw-of-record numbers the CSV carries
    reg = _metrics.default_registry()
    best: dict = {}
    for row in rows:
        best[row["collective"]] = max(best.get(row["collective"], 0.0),
                                      row["busbw_GBps"])
    for coll, bw in best.items():
        reg.set_gauge(f"sweep/{coll}/busbw_peak_GBps", bw)
    return rows


# ---------------------------------------------------------------------------
# compression-lane sweep (r17): bandwidth vs exactness per wire lane
# ---------------------------------------------------------------------------

#: measurable wire lanes: the lossless baseline, the cast pairs, and
#: the int8 block-scaled lane with and without EQuARX error feedback
COMPRESSION_LANES = ("lossless", "float16", "bfloat16", "int8", "int8_ef")


def _lane_compress_dtype(lane: str):
    from ..constants import DataType

    return {"float16": DataType.float16, "bfloat16": DataType.bfloat16,
            "int8": DataType.int8, "int8_ef": None,
            "lossless": None}[lane]


def run_compression_sweep(world, collectives=("allreduce",
                                              "reduce_scatter"),
                          count_pows=range(12, 18), repetitions: int = 3,
                          writer: Optional[io.TextIOBase] = None,
                          log=None) -> list[dict]:
    """Sweep the wire-compression lanes: per (lane, collective, size),
    best-of-reps bus bandwidth PLUS the exactness columns — max
    absolute error and max ULP distance vs the fp64-accumulated
    reference.  The lossless lane comes back within summation-order
    noise (a few ULP — the engine's ring sums f32 sequentially; the
    BITWISE lossless gate runs on integer-valued data in
    tests/test_quantized_wire.py); the int8 lanes trade bounded error
    for ~4:1 wire width (the bandwidth-vs-exactness record
    scripts/check_bench_delta.py --quantized gates).  ``int8_ef`` runs
    through an armed
    CompressionPolicy (error feedback is a per-comm policy property,
    not a per-call flag)."""
    from ..arithconfig import CompressionPolicy
    from ..constants import DataType

    P = world.nranks
    dtype = np.dtype(np.float32)
    rows: list[dict] = []
    csv_writer = None
    if writer is not None:
        csv_writer = csv.DictWriter(writer, fieldnames=[
            "lane", "collective", "count", "bytes", "duration_us",
            "algbw_GBps", "busbw_GBps", "max_abs_err", "max_ulp"])
        csv_writer.writeheader()

    def arm(lane):
        pol = None
        if lane == "int8_ef":
            pol = CompressionPolicy(dtype=DataType.int8, min_bytes=0,
                                    error_feedback=True)
        for a in world.accls:
            a.set_compression(pol)

    def body_factory(coll, count, lane):
        cd = _lane_compress_dtype(lane)

        def body(accl, rank):
            made = []

            def mk(factory, *a):
                buf = factory(*a)
                made.append(buf)
                return buf

            data = (np.random.default_rng(rank)
                    .standard_normal(count * (P if coll ==
                                              "reduce_scatter" else 1))
                    .astype(np.float32))
            try:
                src = mk(accl.create_buffer_like, data)
                recv_n = count
                dst = mk(accl.create_buffer, recv_n, dtype)
                t0 = time.perf_counter()
                if coll == "allreduce":
                    accl.allreduce(src, dst, count, ReduceFunction.SUM,
                                   compress_dtype=cd)
                else:
                    accl.reduce_scatter(src, dst, count,
                                        ReduceFunction.SUM,
                                        compress_dtype=cd)
                dur = time.perf_counter() - t0
                dst.sync_from_device()
                return dur, data, dst.host.copy()
            finally:
                for buf in made:
                    free = getattr(buf, "free", None)
                    if free is not None:
                        free()

        return body

    try:
        for coll in collectives:
            for pw in count_pows:
                count = 1 << pw
                bodies = {}
                for lane in COMPRESSION_LANES:
                    arm(lane)
                    bodies[lane] = body_factory(coll, count, lane)
                    world.run(bodies[lane])  # warmup (jit/path setup)
                # INTERLEAVED rep rounds (the r16 compare() discipline):
                # every round measures every lane once, best-of per
                # lane, so box drift hits all lanes alike instead of
                # skewing whichever lane ran in the slow phase
                best: dict = {}
                for _ in range(repetitions):
                    for lane in COMPRESSION_LANES:
                        arm(lane)
                        out = world.run(bodies[lane])
                        dur = max(d for d, _i, _g in out)
                        if lane not in best or dur < best[lane][0]:
                            best[lane] = (dur, out)
                for lane in COMPRESSION_LANES:
                    dur, out = best[lane]
                    inputs = [i for _d, i, _g in out]
                    exact = np.sum(inputs, axis=0, dtype=np.float64) \
                        .astype(np.float32)
                    max_err = max_ulp = 0.0
                    for rank, (_d, _i, got) in enumerate(out):
                        exp = (exact if coll == "allreduce"
                               else exact.reshape(P, count)[rank])
                        err = np.abs(got.astype(np.float64)
                                     - exp.astype(np.float64))
                        max_err = max(max_err, float(err.max()))
                        ulp = err / np.spacing(np.abs(exp) + 1e-30)
                        max_ulp = max(max_ulp, float(ulp.max()))
                    nbytes = count * _payload_factor(coll, P) \
                        * dtype.itemsize
                    algbw = nbytes / dur / 1e9 if dur > 0 else 0.0
                    row = {
                        "lane": lane,
                        "collective": coll,
                        "count": count,
                        "bytes": nbytes,
                        "duration_us": round(dur * 1e6, 2),
                        "algbw_GBps": round(algbw, 4),
                        "busbw_GBps": round(
                            algbw * _busbw_factor(coll, P), 4),
                        "max_abs_err": float(f"{max_err:.6g}"),
                        "max_ulp": float(f"{max_ulp:.6g}"),
                    }
                    rows.append(row)
                    if csv_writer:
                        csv_writer.writerow(row)
                    if log:
                        log(f"  {lane:>9} {coll:<14} {count:>8} elems "
                            f"{row['busbw_GBps']:>8.3f} GB/s  "
                            f"err {row['max_abs_err']:.3g} "
                            f"ulp {row['max_ulp']:.3g}")
    finally:
        arm("lossless")
    return rows


# ---------------------------------------------------------------------------
# fused-overlap A/B lane (r18): exposed wire vs compute cover per cell
# ---------------------------------------------------------------------------

#: wire lanes the fused A/B measures: lossless fp32 and the r17 int8
#: block-scaled lane fused into the chunk loop (no whole-buffer pack)
FUSED_WIRE_LANES = ("fp32", "int8")


@contextlib.contextmanager
def _rank_window(rank: int, label: str):
    """Per-RANK compute window span (trace.traced_window stamps the
    host pseudo-rank 9999; the overlap accountant intersects wire
    intervals with compute windows on the SAME rank, so the A/B lane
    needs the span pinned to the calling rank's pid)."""
    from ..observability import trace as _trace

    span = _trace.new_span(f"window:{label}", rank=rank)
    if span is not None:
        span.t_submit = span.t_queue = span.t_dispatch = _trace.now_ns()
        span.lane = "window"
    try:
        yield
    finally:
        if span is not None:
            span.t_launch = span.t_submit
            span.t_launched = span.t_complete = _trace.now_ns()
            _trace.collector().add(span)


def _flight_marks() -> dict:
    """Per-recorder flight-ring seq watermark — records landed after
    this mark belong to the current cell (same discipline as the
    autotuner's overlap column, tuning/autotune._overlap_marks)."""
    from ..observability import flight as _flight

    return {id(r): (r, max((rec.seq for rec in r.records()),
                           default=-1))
            for r in _flight.recorders()}


def _exposed_since(marks: dict) -> Optional[float]:
    """Measured ``attribution.overlap`` exposed-wire fraction
    (exposed_us / wire_us summed over collectives) of the flight
    records landed since ``marks``, against the trace collector's
    current compute cover (host ``window:`` spans + device stamp
    slices).  None when nothing completed."""
    from ..constants import ACCLError
    from ..observability import attribution as _attr
    from ..observability import flight as _flight
    from ..observability import trace as _trace

    docs = []
    for rec, mark in marks.values():
        d = rec.dump()
        d["records"] = [r for r in d["records"] if r["seq"] > mark]
        docs.append(d)
    if not docs:
        return None
    try:
        rep = _attr.overlap(_flight.merge_flight_dumps(docs),
                            trace_doc=_trace.collector().to_perfetto())
    except (ACCLError, ValueError, KeyError):
        return None
    wire = sum(c["wire_us"] for c in rep["collectives"].values())
    exposed = sum(c["exposed_us"] for c in rep["collectives"].values())
    return round(exposed / wire, 4) if wire > 0 else None


def run_fused_overlap_sweep(world, collectives=("allreduce",
                                                "reduce_scatter"),
                            count_pows=range(14, 17),
                            repetitions: int = 3, mm_dim: int = 256,
                            mm_loops: int = 2,
                            writer: Optional[io.TextIOBase] = None,
                            log=None) -> list[dict]:
    """A/B the r18 fused compute/communication lane against the
    sequential schedule, per (wire lane, collective, size) cell.

    Both arms run the SAME matmul workload and the SAME collective:

    - ``sequential`` — compute first, then issue the collective
      synchronously: zero cover, the wire is fully exposed (the
      measured exposed-wire fraction sits at ~1.0).
    - ``fused`` — dispatch the chunked fused collective async
      (``fused=True, run_async=True``) and run the matmul while the
      wire drains, then wait: the wire interval intersects the
      rank's compute window and the exposed fraction drops by the
      covered share.

    Columns per row: best-of-reps step time, busbw of the collective
    payload, and the measured ``attribution.overlap`` exposed-wire
    fraction over the cell's timed reps (host ``window:mxu`` spans as
    compute cover — the same accountant scripts/perf_doctor.py and the
    autotuner's overlap column run).  Sizes default to 64-256 KiB
    fp32 payloads (the ISSUE's >= 64 KiB floor)."""
    import jax.numpy as jnp

    from ..constants import DataType
    from ..observability import trace as _trace

    if not _trace.enabled():
        _trace.enable()
    P = world.nranks
    dtype = np.dtype(np.float32)
    rows: list[dict] = []
    csv_writer = None
    if writer is not None:
        csv_writer = csv.DictWriter(writer, fieldnames=[
            "wire", "collective", "count", "bytes", "mode",
            "duration_us", "busbw_GBps", "exposed_wire_fraction"])
        csv_writer.writeheader()

    def body_factory(coll, count, cd, mode):
        fused = mode == "fused"

        def compute(rank):
            # fixed per-rank matmul chain — the "MXU work" both arms
            # pay identically; block_until_ready keeps the window span
            # honest (jax would otherwise return before the FLOPs)
            with _rank_window(rank, "mxu"):
                a = jnp.full((mm_dim, mm_dim), (rank + 1) / mm_dim,
                             jnp.float32)
                for _ in range(mm_loops):
                    a = (a @ a) * (1.0 / mm_dim)
                a.block_until_ready()

        def body(accl, rank):
            made = []

            def mk(factory, *a):
                buf = factory(*a)
                made.append(buf)
                return buf

            data = np.full(count * (P if coll == "reduce_scatter"
                                    else 1), rank + 1, dtype)
            try:
                src = mk(accl.create_buffer_like, data)
                dst = mk(accl.create_buffer, count, dtype)

                def issue(run_async):
                    if coll == "allreduce":
                        return accl.allreduce(
                            src, dst, count, ReduceFunction.SUM,
                            compress_dtype=cd, run_async=run_async,
                            fused=fused)
                    return accl.reduce_scatter(
                        src, dst, count, ReduceFunction.SUM,
                        compress_dtype=cd, run_async=run_async,
                        fused=fused)

                t0 = time.perf_counter()
                if mode == "sequential":
                    compute(rank)
                    issue(run_async=False)
                else:
                    req = issue(run_async=True)
                    compute(rank)
                    req.wait(60)
                return time.perf_counter() - t0
            finally:
                for buf in made:
                    free = getattr(buf, "free", None)
                    if free is not None:
                        free()

        return body

    for coll in collectives:
        for pw in count_pows:
            count = 1 << pw
            for wire in FUSED_WIRE_LANES:
                cd = DataType.int8 if wire == "int8" else None
                for mode in ("sequential", "fused"):
                    body = body_factory(coll, count, cd, mode)
                    world.run(body)  # warmup: jit + gang plan
                    # isolate the cell's cover windows + flight records
                    _trace.collector().clear()
                    marks = _flight_marks()
                    dur = min(max(world.run(body))
                              for _ in range(repetitions))
                    exposed = _exposed_since(marks)
                    nbytes = count * _payload_factor(coll, P) \
                        * dtype.itemsize
                    algbw = nbytes / dur / 1e9 if dur > 0 else 0.0
                    row = {
                        "wire": wire,
                        "collective": coll,
                        "count": count,
                        "bytes": nbytes,
                        "mode": mode,
                        "duration_us": round(dur * 1e6, 2),
                        "busbw_GBps": round(
                            algbw * _busbw_factor(coll, P), 4),
                        "exposed_wire_fraction": exposed,
                    }
                    rows.append(row)
                    if csv_writer:
                        csv_writer.writerow(row)
                    if log:
                        ex = ("-" if exposed is None
                              else f"{exposed:.3f}")
                        log(f"  {wire:>5} {coll:<14} {count:>8} elems "
                            f"{mode:>10} {row['duration_us']:>10.1f} us"
                            f"  exposed {ex}")
    return rows


def _run_once(world, coll: str, count: int, dtype, root: int,
              compress=None, fused=None) -> float:
    """One timed collective across all ranks; returns max duration (s).
    ``compress`` optionally selects a wire-compression dtype
    (constants.DataType) for the collectives that take one — the r17
    compression lanes of the autotuner sweep through here.  ``fused``
    opts the call into the r18 chunked fused lane (allreduce /
    reduce_scatter / allgather only); None leaves the driver default
    (ACCL_FUSED env) in charge."""
    P = world.nranks

    def body(accl, rank):
        made = []

        def mk(factory, *a):
            buf = factory(*a)
            made.append(buf)
            return buf

        try:
            return _timed_body(accl, rank, mk)
        finally:
            # the emulator rungs have a real device-memory allocator:
            # a full 2^4..2^19 sweep leaks gigabytes without this and
            # starves the engine's own scratch allocations mid-schedule
            for buf in made:
                free = getattr(buf, "free", None)
                if free is not None:
                    free()

    def _timed_body(accl, rank, mk):
        data = np.full(count, rank + 1, dtype)
        if coll == "sendrecv":
            src = mk(accl.create_buffer_like, data)
            dst = mk(accl.create_buffer, count, dtype)
            t0 = time.perf_counter()
            nxt, prv = (rank + 1) % P, (rank - 1) % P
            sreq = accl.send(src, count, nxt, tag=1, run_async=True,
                             compress_dtype=compress)
            accl.recv(dst, count, prv, tag=1, compress_dtype=compress)
            sreq.wait(60)
            return time.perf_counter() - t0
        if coll == "bcast":
            buf = mk(accl.create_buffer_like, data)
            t0 = time.perf_counter()
            accl.bcast(buf, count, root, compress_dtype=compress)
            return time.perf_counter() - t0
        if coll == "scatter":
            send = mk(accl.create_buffer_like, np.tile(data, P))
            recv = mk(accl.create_buffer, count, dtype)
            t0 = time.perf_counter()
            accl.scatter(send, recv, count, root,
                         compress_dtype=compress)
            return time.perf_counter() - t0
        if coll == "gather":
            send = mk(accl.create_buffer_like, data)
            recv = mk(accl.create_buffer, count * P, dtype)
            t0 = time.perf_counter()
            accl.gather(send, recv, count, root,
                        compress_dtype=compress)
            return time.perf_counter() - t0
        if coll == "allgather":
            send = mk(accl.create_buffer_like, data)
            recv = mk(accl.create_buffer, count * P, dtype)
            t0 = time.perf_counter()
            accl.allgather(send, recv, count, compress_dtype=compress,
                           fused=fused)
            return time.perf_counter() - t0
        if coll == "reduce":
            send = mk(accl.create_buffer_like, data)
            recv = mk(accl.create_buffer, count, dtype)
            t0 = time.perf_counter()
            accl.reduce(send, recv, count, root, ReduceFunction.SUM,
                        compress_dtype=compress)
            return time.perf_counter() - t0
        if coll == "allreduce":
            send = mk(accl.create_buffer_like, data)
            recv = mk(accl.create_buffer, count, dtype)
            t0 = time.perf_counter()
            accl.allreduce(send, recv, count, ReduceFunction.SUM,
                           compress_dtype=compress, fused=fused)
            return time.perf_counter() - t0
        if coll == "reduce_scatter":
            send = mk(accl.create_buffer_like, np.tile(data, P))
            recv = mk(accl.create_buffer, count, dtype)
            t0 = time.perf_counter()
            accl.reduce_scatter(send, recv, count, ReduceFunction.SUM,
                                compress_dtype=compress, fused=fused)
            return time.perf_counter() - t0
        if coll == "alltoall":
            send = mk(accl.create_buffer_like, np.tile(data, P))
            recv = mk(accl.create_buffer, count * P, dtype)
            t0 = time.perf_counter()
            accl.alltoall(send, recv, count)
            return time.perf_counter() - t0
        raise ValueError(f"unknown collective {coll!r}")

    durations = world.run(body)
    return max(durations)
