"""BENCHMARK.json against the benchmark's contract, and discovery by
name: a new configuration, traffic mix and metric are new files and new
entries, with no existing file edited."""
import hashlib
import json
import os
import re
import time

import pytest
from conftest import BENCH, ROOT

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _spec():
    return harness.load_spec(ROOT)


def test_keys_names_and_limits():
    spec = _spec()
    assert set(spec) == KEYS["top"]
    assert spec["paths"] == ["benchmark"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[section]]
        assert len(names) == len(set(names))
        for e in spec[section]:
            extra = {"workloads"} if section in ("end_to_end",
                                                 "per_layer") else set()
            assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
            assert NAME.match(e["name"]), e["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    cells = spec["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 2)
    for c in cells:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        for m in spec["end_to_end"]:
            # a metric moved by a per-layer one is reported where it is
            for pm in spec["per_layer"]:
                if pm["moves"] == m["name"] and c["name"] in pm["workloads"]:
                    assert c["name"] in m.get("workloads", [c["name"]])
        reported = [m["name"] for m in harness.cell_metrics(spec, c["name"],
                                                            False)]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(spec, c["name"], True)
    assert len(json.dumps(spec)) < 64 << 10


def test_every_name_resolves_to_its_file():
    spec = _spec()
    for cfg in spec["configs"]:
        assert cfg["file"].startswith("benchmark/")
        data = harness.load_config(spec, cfg["name"], ROOT)
        assert data["source"] == cfg["source"]
        assert sorted(data["reduced"]) == sorted(cfg["reduced"])
        assert any(c["config"] == cfg["name"] for c in spec["workloads"])
    for c in spec["workloads"]:
        harness.load_traffic(c["traffic"], BENCH)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(m["name"], BENCH))


def _hashes(root):
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


ALLGATHER_OP = """import numpy as np

RING_LANE = True


def buffers(count, nranks):
    return count, count * nranks


def call(accl, src, dst, count, **kw):
    return accl.allgather(src, dst, count, from_fpga=True, to_fpga=True,
                          **kw)


def reference(inputs, function=None):
    ref = np.concatenate([np.asarray(x, np.float64) for x in inputs])
    return [(ref, np.abs(ref))] * len(inputs)
"""

PER_CALL_WAIT = """import os

import jax

import harness

_here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_base = harness.load_module(_here, "traffic", "closed_loop")


class Stream(_base.Stream):
    def _call(self, accl, src, dst, n):
        super()._call(accl, src, dst, n)
        jax.block_until_ready(dst.dev)
"""

#: (cell, chips, unit, generator, files its code adds)
NEW_CELLS = {
    # data only: a reduce function the existing generator and reference
    # have not run in any cell
    "data": ("tiny_max.1chip", 1,
             {"op": "allreduce", "function": "MAX", "dtype": "float32",
              "counts": [1000, 3000]}, "closed_loop", {}),
    # a new collective and a new driving pattern, each a file of its own
    "code": ("tiny_ag.4chip", 4,
             {"op": "allgather", "dtype": "float32", "counts": [1000, 3000]},
             "per_call_wait", {"ops/allgather.py": ALLGATHER_OP,
                               "traffic/per_call_wait.py": PER_CALL_WAIT}),
}


@pytest.mark.parametrize("case", sorted(NEW_CELLS))
def test_new_cell_is_new_files_only(tiny_bench, case):
    """A later change adds a cell with its own configuration, traffic
    mix, metric, and where it needs them its collective and generator,
    by adding files and BENCHMARK.json entries only."""
    from accl_tpu.utils.bringup import Design, initialize_world

    cell, chips, unit, generator, code = NEW_CELLS[case]
    root, spec = tiny_bench
    bench = root / "benchmark"
    before = _hashes(bench)
    for rel, text in code.items():
        (bench / rel).write_text(text)
    (bench / "configs" / "tiny-new.json").write_text(json.dumps({
        "name": "tiny-new", "source": "https://example.org/tiny",
        "reduced": [], "unit": dict(unit, name="call")}))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps({
        "generator": generator, "chain": False, "input_sets": 3,
        "warmup_units": 1,
        "check": {"units": "all", "calls": "all", "last_unit_calls": "all",
                  "max_rel_err": 0}, "control": {}}))
    (bench / "metrics" / "units_per_s.tiny.py").write_text(
        "def read(run):\n    return run.units / run.window_s\n")
    spec["configs"].append({"name": "tiny-new",
                            "source": "https://example.org/tiny",
                            "file": "benchmark/configs/tiny-new.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": cell, "config": "tiny-new",
                              "traffic": "tiny_mix", "chips": chips,
                              "why": "a test"})
    spec["end_to_end"].append({"name": "units_per_s.tiny", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    args = harness.parse_args(["--workload", cell, "--seed", "9",
                               "--seconds", "0.3", "--trace", "0"])
    world = initialize_world(Design.TPU, nranks=chips)
    try:
        out = harness.run_cell(args, time.perf_counter(), require_tpu=False,
                               root=str(root), here=str(bench), world=world)
    finally:
        world.close()
    assert out["correct"], out
    assert out["checks"]["outputs_compared"]["value"] >= 2 * chips
    assert set(out["metrics"]) == {"units_per_s.tiny", "setup_s"}
    after = _hashes(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/tiny-new.json", "traffic/tiny_mix.json",
        "metrics/units_per_s.tiny.py"} | set(code)


def test_unknown_function_or_op_fails_loudly():
    allreduce = harness.load_module(BENCH, "ops", "allreduce")
    with pytest.raises(ValueError, match="PROD"):
        allreduce.reference([[1.0], [2.0]], "PROD")
    with pytest.raises(SystemExit, match="alltoallv"):
        harness.load_module(BENCH, "ops", "alltoallv")
