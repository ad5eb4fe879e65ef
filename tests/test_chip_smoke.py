"""Rehearsal of chip_smoke.py on the virtual CPU mesh: its phase
functions at tiny sizes, with the Pallas kernels in interpret mode.
The program's own entry point refuses any platform but the TPU."""
import importlib.util
import os

import numpy as np
import pytest

from accl_tpu.backends.tpu import TpuWorld

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_rank_driver_phase(smoke):
    with TpuWorld(1) as w:
        recs = smoke.driver_phase(w, sizes=(4 << 10,))
    ops = {(r["op"], r["dtype"], r.get("wire")) for r in recs}
    assert len(recs) == 2 * len(smoke.COLLECTIVES) + 2
    assert ("allreduce", "float32", "int8") in ops
    assert all(r["lane"] in ("hlo", "p2p") for r in recs)


def test_four_rank_phase_ring_against_hlo(smoke):
    with TpuWorld(4) as w:
        # tiny sizes on both sides of a lowered ring threshold
        w.engine.ring_threshold_bytes = 8 << 10
        recs = smoke.four_chip_phase(w, sizes=(4 << 10, 16 << 10),
                                     dtypes=("float32",))
    cmp = [r for r in recs if r.get("phase") == "ring_vs_hlo"]
    assert {r["op"] for r in cmp} == set(smoke.RING_OPS)
    assert all(r["max_diff"] == 0.0 for r in cmp)
    ring = [r for r in recs if r.get("lane") == "ring"]
    # the three lossless ring ops and the int8 allreduce
    assert all(r["bytes"] >= 8 << 10 for r in ring) and len(ring) == 4
    assert any(r.get("lane") == "fused" for r in recs)
    assert all(len(set(r["devices"])) == 4 for r in recs
               if r.get("phase") == "driver" and r["op"] != "barrier")


def test_kernels_phase_interpret(smoke):
    recs = smoke.kernels_phase(True, lane_elems=8 << 10, ring_rows=8,
                               flash=(1, 128, 2, 128))
    assert {r["kernel"] for r in recs} >= {
        "pallas_add", "pallas_max", "compress_cast", "selfring_all_gather",
        "selfring_reduce_scatter", "selfring_all_reduce",
        "flash_attention_fwd", "flash_attention_bwd"}


def test_driver_inputs_exact_in_bf16(smoke):
    import ml_dtypes

    x = smoke.rank_data(3 << 20, np.float32, 3)
    assert np.abs(x).max() <= 31
    np.testing.assert_array_equal(
        x.astype(ml_dtypes.bfloat16).astype(np.float32), x)


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
