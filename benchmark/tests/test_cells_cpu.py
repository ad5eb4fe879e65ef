"""Each cell's whole run on the CPU at a tiny size: a sound run is
correct; the control and every fault the cell can have are not.

The faults break the timed path underneath the harness, in the engine
that serves the driver's calls:

- stale: a call leaves its result buffer as it was (a step that returns
  its state unchanged);
- half: the second half of each result is the mean of the first half's
  elements (half of the batch left out, the mean taken over the rest);
- local: each rank's result is its own input times the rank count (the
  exchange between chips left out; four-chip cell only);
- altered: one element of each result is changed where it is produced.
"""
import math

import jax.numpy as jnp
import pytest

from accl_tpu.backends import tpu as tpu_backend

CELLS = ("dp_gradsync.4chip", "tp_decode.1chip")


def _wrap_program(monkeypatch, fault):
    """Replace each gang plan's compiled program by fault(program, P)."""
    orig = tpu_backend.TpuEngine._gang_plan

    def gang_plan(self, op, comm_id, gang):
        plan = orig(self, op, comm_id, gang)
        if not plan.get("_fault"):
            plan["compiled"] = fault(plan["compiled"], plan["nranks"])
            plan["_fault"] = True
        return plan

    monkeypatch.setattr(tpu_backend.TpuEngine, "_gang_plan", gang_plan)


def _half(prog, P):
    def run(x):
        y = prog(x).reshape(P, -1)
        h = y.shape[1] // 2
        mean = jnp.mean(y[:, :h].astype(jnp.float32), axis=1, keepdims=True)
        rest = jnp.broadcast_to(mean, (P, y.shape[1] - h)).astype(y.dtype)
        return jnp.concatenate([y[:, :h], rest], axis=1).reshape(-1)
    return run


def _local(prog, P):
    return lambda x: x * P


def _altered(prog, P):
    def run(x):
        y = prog(x)
        return y.at[0].add(jnp.asarray(1, y.dtype))
    return run


FAULTS = {"half": _half, "local": _local, "altered": _altered}


def test_sound_runs_are_correct(run_tiny):
    for cell in CELLS:
        out = run_tiny(cell, seed=2**31 + 17)
        assert out["correct"], out
        checks = out["checks"]
        assert checks["lane_misses"]["value"] == 0
        assert checks["window_compiles"]["value"] == 0
        assert checks["outputs_compared"]["value"] > 0
        assert out["attempted"] > 0 and out["failed"] == 0
        assert "setup_s" in out["metrics"]
        assert list(out)[-1] == "checks"


def test_ring_lane_served_the_large_calls(run_tiny):
    out = run_tiny("dp_gradsync.4chip", seed=3)
    assert out["correct"], out
    # lane_misses counts gangs off their expected lane: the tiny step's
    # 96 KiB and ragged 117 KiB calls must be served by the ring
    assert out["checks"]["lane_misses"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(run_tiny, cell):
    out = run_tiny(cell, seed=5, control=True)
    assert not out["correct"]
    err = out["checks"]["max_rel_err"]
    assert err["limit"] < err["value"] < math.inf


@pytest.mark.parametrize("cell", CELLS)
def test_stale_result_is_not_correct(run_tiny, monkeypatch, cell):
    monkeypatch.setattr(tpu_backend.TpuEngine, "_scatter_back",
                        lambda self, plan, y: None)
    assert not run_tiny(cell, seed=7)["correct"]


#: a one-member communicator has no exchange to leave out
CELL_FAULTS = [(c, f) for c in CELLS for f in sorted(FAULTS)
               if not (f == "local" and c == "tp_decode.1chip")]


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_fault_is_not_correct(run_tiny, monkeypatch, cell, fault):
    _wrap_program(monkeypatch, FAULTS[fault])
    out = run_tiny(cell, seed=11)
    assert not out["correct"], out
