"""Read a cell's compared numbers over many seeds, for the program and
for its control, in one process on the cell's chips.

    python3 benchmark/control.py --workload <name> --seconds <s> \\
        --seeds 1 2 3 ... --control-seeds 101 102 103

Each seed is one whole run of the cell (inputs, buffers, warm-up, a
window of ``--seconds``, the reference) on one world that is built once.
The program's runs give a limit's lower reading (the largest), the
control's its upper one (the smallest); see traffic/<traffic>.json for
what the control switches.  The benchmark's own runs never run the
control.  One JSON line per run, then a summary line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    harness.pin_cpus()
    harness.prepare_env(False)

    import jax

    if jax.devices()[0].platform != "tpu" or \
            len(jax.devices()) < cell["chips"]:
        print("control: no TPU with the cell's chips here", file=sys.stderr)
        return 2
    from accl_tpu.utils.bringup import Design, initialize_world

    world = initialize_world(Design.TPU, nranks=cell["chips"])
    readings: dict = {"program": {}, "control": {}}
    try:
        for control, seeds in ((False, args.seeds),
                               (True, args.control_seeds)):
            for seed in seeds:
                run_args = harness.parse_args([
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace", "0"])
                out = harness.run_cell(run_args, time.perf_counter(),
                                       control=control, world=world)
                side = "control" if control else "program"
                checks = {k: c["value"] for k, c in out["checks"].items()}
                readings[side][seed] = checks
                print(json.dumps({"side": side, "seed": seed,
                                  "correct": out["correct"],
                                  "checks": checks,
                                  "metrics": out["metrics"]}), flush=True)
    finally:
        world.close()
    summary = {}
    for side, runs in readings.items():
        if runs:
            errs = [c["max_rel_err"] for c in runs.values()]
            summary[side] = {"seeds": len(errs), "max_rel_err_max": max(errs),
                             "max_rel_err_min": min(errs)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
