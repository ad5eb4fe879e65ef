"""One benchmark run: set up a cell, warm it, measure a window, check it.

Everything is found by name from BENCHMARK.json at the checkout's root:
the cell's configuration in its ``file``; its traffic mix in
``traffic/<traffic>.json``, a file of parameters read by the generator
module it names, ``traffic/<generator>.py`` (see traffic/closed_loop.py
for the interface); the collective of the configuration's unit in
``ops/<op>.py``, which holds the call and its plain reference; and each
metric's reader in ``metrics/<metric>.py``, a module with ``read(run)``
that returns the value or None when it finds nothing to read.

A run is one process: it loads, warms up, measures ``--seconds``, checks
the window's outputs against reference.py, and prints one JSON line.
With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced (``ACCL_TRACE`` spans and the JAX
profiler) and the line carries its per-layer metrics.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: JAX's persistent compilation cache: one fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
PHASE_ISSUE, PHASE_WAIT = "bench.issue", "bench.wait"
#: CPUs a run keeps to (see pin_cpus)
CPUS = 4


@dataclass
class Run:
    """What a metric reader sees of one run (a later reader may need the
    cell, its configuration or its traffic, hence all of them)."""
    cell: dict
    config: dict
    traffic: dict
    nranks: int
    device_kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    unit_s: list = field(default_factory=list)
    #: (collective, per-rank payload bytes) of each call of one unit
    calls: list = field(default_factory=list)
    spans: list | None = None
    trace: object | None = None   # trace_reduce.TraceSummary

    @property
    def units(self) -> int:
        return len(self.unit_s)


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(spec: dict, name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, find(spec["configs"], name,
                                             "config")["file"]))


def load_traffic(name: str, here: str = HERE) -> dict:
    return load_json(os.path.join(here, "traffic", name + ".json"))


def load_module(here: str, kind: str, name: str):
    """The module <here>/<kind>/<name>.py, loaded by its path."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} module {name!r}: {path} is missing")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, here: str = HERE):
    """The ``read`` function of metrics/<name>.py."""
    return load_module(here, "metrics", name).read


def load_generator(traffic: dict, here: str = HERE):
    """The generator module that a traffic file names."""
    return load_module(here, "traffic", traffic["generator"])


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metrics a run of `cell` reports: end-to-end untraced,
    per-layer traced; a metric with ``workloads`` only in those cells."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec[key]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# the process
# ---------------------------------------------------------------------------
def prepare_env(trace: bool) -> None:
    """The program runs its defaults: no ``ACCL_*`` knob but
    ``ACCL_TRACE`` in a traced run; the compile cache in the checkout."""
    for k in [k for k in os.environ if k.startswith("ACCL_")]:
        del os.environ[k]
    if trace:
        os.environ["ACCL_TRACE"] = "1"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # it holds one cell's few programs: no size cap, so no eviction
    # bookkeeping (a machine's preset cap made every write fail there)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def pin_cpus(n: int = CPUS) -> None:
    """Keep the process, and every thread it starts, on the first `n`
    CPUs it may use: on a 13-core one-chip host this removed the
    multi-second stalls that unpinned runs showed now and then."""
    cpus = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, cpus[:n])


class CompileCounter:
    """Counts JAX compile and compile-cache events while armed."""

    def __init__(self):
        self.armed = False
        self.events: list = []

    def _on(self, name: str, *_a, **_kw) -> None:
        if self.armed and "compil" in name:
            self.events.append(name)

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_listener(self._on)
        monitoring.register_event_duration_secs_listener(self._on)


def drive(world, stream, seconds: float, trace_dir: str | None,
          counter: CompileCounter) -> tuple:
    """Warm up, then run units back to back for `seconds` on every rank
    thread.  Returns (window start, [unit end times], lane deltas)."""
    import jax

    P = world.nranks
    w0 = stream.warmup_units

    def warm(accl, rank):
        for u in range(w0):
            jax.block_until_ready(stream.issue(accl, rank, u))

    world.run(warm)
    gc.collect()
    gc.freeze()
    stream.plan_checks()
    state = {"t0": 0.0, "ends": [], "stop": False}

    def start():
        state["t0"] = time.perf_counter()

    def unit_done():
        t = time.perf_counter()
        state["ends"].append(t)
        state["stop"] = t - state["t0"] >= seconds

    begin = threading.Barrier(P, action=start)
    done = threading.Barrier(P, action=unit_done)

    def window(accl, rank):
        begin.wait()
        w = 0
        while True:
            u = w0 + w
            if trace_dir is None:
                outs = stream.issue(accl, rank, u)
                jax.block_until_ready(outs)
            else:
                with jax.profiler.TraceAnnotation(PHASE_ISSUE):
                    outs = stream.issue(accl, rank, u)
                with jax.profiler.TraceAnnotation(PHASE_WAIT):
                    jax.block_until_ready(outs)
            stream.keep(rank, w, u)
            done.wait()
            if state["stop"]:
                stream.keep(rank, w, u, last=True)
                return
            w += 1

    before = dict(world.engine.stats)
    if trace_dir is not None:
        from accl_tpu.observability import trace as accl_trace

        accl_trace.enable()
        accl_trace.collector().clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.armed = True
    try:
        world.run(window)
    finally:
        counter.armed = False
        gc.unfreeze()
        if trace_dir is not None:
            jax.profiler.stop_trace()
    after = world.engine.stats
    lanes = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("lane_")}
    return state["t0"], state["ends"], lanes


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run_cell(args, t_start: float, require_tpu: bool = True,
             root: str = ROOT, here: str = HERE, control: bool = False,
             world=None) -> dict | None:
    """One run of ``args.workload``; the result line's object, or None
    (and a message on stderr) where no chip of the cell's kind is here.
    ``world``, where given, is used instead of a new one and left open."""
    spec = load_spec(root)
    cell = find(spec["workloads"], args.workload, "workload")
    cfg = load_config(spec, cell["config"], root)
    traffic = load_traffic(cell["traffic"], here)
    trace = bool(args.trace)
    prepare_env(trace)

    import jax

    phases = {}
    t = time.perf_counter()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"benchmark: JAX found {devices[0].platform!r}, not a TPU",
              file=sys.stderr)
        return None
    if len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return None
    kind = devices[0].device_kind
    import peaks

    if require_tpu:
        peaks.peaks(kind)  # an unknown chip is an error, not a default
    from accl_tpu.utils.compile_cache import enable

    enable()
    counter = CompileCounter()
    counter.install()
    phases["jax_init_s"] = time.perf_counter() - t

    from accl_tpu.utils.bringup import Design, initialize_world

    generator = load_generator(traffic, here)

    t = time.perf_counter()
    own_world = world is None
    if own_world:
        world = initialize_world(Design.TPU, nranks=cell["chips"])
    phases["world_s"] = time.perf_counter() - t
    try:
        t = time.perf_counter()
        stream = generator.Stream(world, cfg, traffic, args.seed,
                                  control=control, here=here)
        stream.setup()
        phases["buffers_s"] = time.perf_counter() - t
        trace_dir = (tempfile.mkdtemp(prefix="bench_trace_") if trace
                     else None)
        t = time.perf_counter()
        t0, ends, lanes = drive(world, stream, args.seconds, trace_dir,
                                counter)
        phases["warmup_s"] = t0 - t
        used = world.engine.devices
        mem = memory_peak(used)
        spans = None
        if trace:
            from accl_tpu.observability import trace as accl_trace

            spans = accl_trace.collector().spans()
        stats = dict(world.engine.stats)
        expected = stream.expected_lanes()
        stream.release()
    finally:
        if own_world:
            world.close()
    summary = None
    if trace_dir is not None:
        summary = reduce_trace(trace_dir)
    unit_s = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    run = Run(cell=cell, config=cfg, traffic=traffic, nranks=world.nranks,
              device_kind=kind, setup_s=t0 - t_start,
              window_s=ends[-1] - t0, unit_s=unit_s,
              calls=stream.calls(), spans=spans, trace=summary)
    t = time.perf_counter()
    checks, compared, bad = stream.check()
    print(json.dumps({"setup_phases": phases, "window": {
        "units": run.units, "window_s": run.window_s,
        "compile_events": len(counter.events)}, "check_s":
        time.perf_counter() - t, "engine_stats": stats}), flush=True)
    lane_misses = sum(abs(lanes.get(k, 0) - v * run.units)
                      for k, v in expected.items())
    checks.update(lane_misses=(lane_misses, 0),
                  window_compiles=(len(counter.events), 0))
    correct = all(v <= lim for v, lim in checks.values()) and compared > 0
    metrics = {}
    for m in cell_metrics(spec, args.workload, trace):
        value = load_reader(m["name"], here)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct,
           "attempted": run.units * len(run.calls) * run.nranks,
           "failed": bad, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [list(x) for x in summary.ops[:10]],
            "idle_gaps": [list(x) for x in summary.gaps[:10]]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["checks"]["outputs_compared"] = {"value": compared, "limit": 1,
                                         "at_least": True}
    return out


def reduce_trace(trace_dir: str):
    """The trace's summary; the trace itself is deleted."""
    import glob

    import trace_reduce

    try:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return trace_reduce.reduce_file(files[0]) if files else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def print_result(out: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for k, c in out["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {k} = {c['value']!r} (limit {rel} {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    pin_cpus()
    out = run_cell(args, t_start)
    if out is None:
        return 2
    print_result(out)
    return 0
