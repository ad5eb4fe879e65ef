#!/usr/bin/env python
"""accl_tune: measure, persist, and verify a collective selection table.

The r16 autotuner CLI (accl_tpu/tuning): sweeps (collective, dtype,
size-bucket, algorithm) lanes through the bench sweep harness on an emu
or TPU world, writes the versioned JSON selection table
``ACCL.initialize`` consumes via ``ACCL_TUNE_TABLE``, and (--record)
re-measures static-vs-tuned per cell — interleaved, best-of, with
unreproducible selections pruned back to static — emitting the
``sweep_rNN_tuned_vs_static`` CSV/MD record the perf gate validates.

Usage:
  python scripts/accl_tune.py --ranks 4 --shape 2x2 --out tune_table.json
  python scripts/accl_tune.py --backend tpu --ranks 4 \\
      --out tune_table.json --record bench/results/sweep_r16_tuned_vs_static

The TPU rung runs on the TPU that JAX finds and fails without one.
"""
import argparse
import csv
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--backend", choices=("emu", "tpu"), default="emu")
    ap.add_argument("--shape", default="",
                    help="fabric axis layout, e.g. 2x2 (default: "
                         "ACCL_FABRIC env / near-square factorization)")
    ap.add_argument("--collectives", default="",
                    help="comma list (default: the composable set + "
                         "reduce)")
    ap.add_argument("--pows", default="",
                    help="comma list of log2 element counts "
                         "(default 6,8,10,12,14,16)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--dtypes", default="",
                    help="comma list of dtypes to sweep into ONE merged "
                         "per-dtype table, e.g. float32,bfloat16,float16 "
                         "(default: just --dtype; unswept dtypes are "
                         "served the float32 row at dispatch)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="tune_table.json",
                    help="selection-table JSON path")
    ap.add_argument("--record", default="",
                    help="path PREFIX for the tuned-vs-static "
                         "verification record (.csv + .md written)")
    ap.add_argument("--no-demotion", action="store_true",
                    help="skip measured link-matrix axis demotion")
    args = ap.parse_args()

    # loaded/1-core boxes stall ranks past the reference 1 s receive
    # budget on big many-rank cells — widen the default like
    # tests/conftest.py (explicit env still wins)
    os.environ.setdefault("ACCL_DEFAULT_TIMEOUT", "30000000")

    from accl_tpu.tuning import TuneConfig, autotune
    from accl_tpu.utils.topology import parse_shape

    shape = parse_shape(args.shape) if args.shape else None
    kwargs = {}
    if args.collectives:
        kwargs["collectives"] = tuple(args.collectives.split(","))
    pows = (tuple(int(p) for p in args.pows.split(","))
            if args.pows else (6, 8, 10, 12, 14, 16))
    dtypes = (tuple(d.strip() for d in args.dtypes.split(",") if d.strip())
              if args.dtypes else (args.dtype,))
    cfg = TuneConfig(count_pows=pows, dtype=dtypes[0],
                     repetitions=args.reps, shape=shape,
                     measured_demotion=not args.no_demotion, **kwargs)

    if args.backend == "tpu":
        import jax

        if jax.default_backend() != "tpu":
            sys.exit(f"--backend tpu: JAX found platform "
                     f"{jax.default_backend()!r}")
        from accl_tpu.backends.tpu import TpuWorld

        world = TpuWorld(args.ranks)
    else:
        from accl_tpu.backends.emu import EmuWorld

        world = EmuWorld(args.ranks, devmem_bytes=256 << 20,
                         n_egr_rx_bufs=64, max_eager_size=16384,
                         max_rendezvous_size=64 << 20)

    t0 = time.perf_counter()
    try:
        print(f"[accl_tune] tuning {args.ranks} ranks on "
              f"{args.backend} ({len(pows)} sizes x "
              f"{len(cfg.collectives)} collectives x "
              f"{len(dtypes)} dtypes)")
        table = None
        from dataclasses import replace
        for d in dtypes:
            cfg_d = replace(cfg, dtype=d)
            if len(dtypes) > 1:
                print(f"[accl_tune] dtype lane: {d}")
            t = autotune.tune(world, cfg_d, log=print)
            if table is None:
                table = t
            else:
                # merged per-dtype table: one artifact, one cell per
                # (collective, dtype, bucket) — dispatch falls back to
                # the float32 row for dtypes never swept here
                table.entries.update(t.entries)
                table._dtypes = None
        table.world["dtypes"] = list(dtypes)
        rows = []
        if args.record:
            print("[accl_tune] verifying tuned vs static (interleaved, "
                  "pruning unreproducible selections)")
            for d in dtypes:
                rows.extend(autotune.compare(
                    world, table, replace(cfg, dtype=d), log=print))
    finally:
        world.close()

    table.save(args.out)
    print(f"[accl_tune] table: {args.out} ({len(table.entries)} cells, "
          f"{time.perf_counter() - t0:.0f}s)")

    if args.record:
        csv_path = f"{args.record}.csv"
        with open(csv_path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=[
                "collective", "dtype", "size_bucket", "count", "bytes",
                "algorithm", "static_busbw_GBps", "tuned_busbw_GBps",
                "ratio"])
            w.writeheader()
            w.writerows(rows)
        wins = sum(1 for r in rows if r["ratio"] >= 1.15)
        slow = [r for r in rows if r["ratio"] < 1.0 / 1.05]
        tuned_cells = sum(1 for r in rows if r["algorithm"] != "static")
        with open(f"{args.record}.md", "w") as f:
            f.write(
                f"# Tuned vs static sweep record\n\n"
                f"- world: {args.ranks} ranks, {args.backend} backend, "
                f"fabric {table.world.get('shape')}, dtypes "
                f"{','.join(dtypes)}\n"
                f"- table: {os.path.basename(args.out)} "
                f"({len(table.entries)} cells, "
                f"{tuned_cells} non-static selections after "
                f"verification pruning)\n"
                f"- wins >= 1.15x busbw vs static: {wins} cells\n"
                f"- cells > 1.05x slower than static: {len(slow)} "
                f"(gate: must be 0)\n\n"
                f"| collective | dtype | bucket | algorithm | "
                f"static GB/s | tuned GB/s | ratio |\n"
                f"|---|---|---|---|---|---|---|\n")
            for r in rows:
                f.write(f"| {r['collective']} | {r['dtype']} | "
                        f"{r['size_bucket']} | "
                        f"{r['algorithm']} | {r['static_busbw_GBps']} "
                        f"| {r['tuned_busbw_GBps']} | {r['ratio']}x "
                        f"|\n")
        print(f"[accl_tune] record: {csv_path} ({wins} wins >= 1.15x, "
              f"{len(slow)} cells slower than 1/1.05)")
        if slow:
            print("[accl_tune] FAIL: the verified record still has "
                  "slower-than-static cells", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
