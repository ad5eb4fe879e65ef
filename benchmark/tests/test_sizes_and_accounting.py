"""The configurations' call lists against their sources' sizes, and the
bus-bandwidth arithmetic."""
import pytest

from conftest import ROOT

import ddp_buckets
import harness
from accounting import bus_bytes, busbw_factor

MIB = 1 << 20


def _cfg(name):
    return harness.load_config(harness.load_spec(ROOT), name, ROOT)


def test_ddp_buckets_hold_the_stage_parameters():
    cfg = _cfg("ouro-2.6b-dp-gradsync")
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    per_layer = 4 * h * q + 3 * h * inter + 4 * h
    assert per_layer == 51_388_416
    counts = cfg["unit"]["counts"]
    assert counts == ddp_buckets.bucket_counts(cfg)
    assert sum(counts) == cfg["num_hidden_layers"] * per_layer == 616_660_992
    assert sum(counts) * 4 == pytest.approx(2.297 * (1 << 30), rel=1e-3)
    # the last layer's four norms are reduced first (32 KiB, HLO lane);
    # every other bucket holds at least 16 MiB, the largest 60 MiB
    assert counts[0] * 4 == 32 << 10
    assert min(counts[1:]) * 4 == 16 * MIB and max(counts) * 4 == 60 * MIB
    assert len(counts) == 61
    # the order DDP keeps only with find_unused_parameters=True; without
    # it DDP rebuilds its buckets in gradient-ready order
    assert cfg["deployment"]["find_unused_parameters"] is True


def test_ddp_rule_small_first_bucket_then_cap():
    # 1 MiB first limit, then 25 MiB; the open tail is closed at the end
    sizes = [MIB // 2, MIB // 2, 10 * MIB, 10 * MIB, 10 * MIB, 3]
    assert ddp_buckets.bucket_assignment(sizes, [MIB, 25 * MIB]) == \
        [[5], [2, 3, 4], [0, 1]]


def test_tp_calls_are_two_per_layer_of_the_activation():
    cfg = _cfg("brumby-14b-tp-decode")
    batch = cfg["assumed"]["decode_batch"]
    counts = cfg["unit"]["counts"]
    assert len(counts) == 2 * cfg["num_hidden_layers"] == 80
    assert set(counts) == {batch * cfg["hidden_size"]}
    assert counts[0] * 2 == 640 << 10   # bf16
    # the deployment's 4-way communicator is cut to the one chip
    assert cfg["tensor_parallel"] == 1 and "tensor_parallel" in cfg["reduced"]
    assert cfg["deployment"]["tensor_parallel"] == 4


def test_allreduce_reference_by_function():
    import numpy as np

    allreduce = harness.load_module(harness.HERE, "ops", "allreduce")
    xs = [np.array([1.0, -4.0]), np.array([-2.0, 3.0])]
    (ref, mag), (ref1, _) = allreduce.reference(xs, "SUM")
    assert ref.tolist() == [-1.0, -1.0] and mag.tolist() == [3.0, 7.0]
    assert ref1 is ref
    assert allreduce.reference(xs, "MAX")[0][0].tolist() == [1.0, 3.0]


def test_busbw_factor_nccl_tests():
    assert busbw_factor("allreduce", 4) == 1.5
    assert busbw_factor("allreduce", 1) == 1.0
    assert busbw_factor("allgather", 4) == 0.75
    assert bus_bytes("allreduce", 8, 1000) == 1750


def test_busbw_reader():
    run = harness.Run(cell={}, config={}, traffic={}, nranks=4,
                      device_kind="TPU v5 lite", window_s=2.0,
                      unit_s=[0.2] * 10,
                      calls=[("allreduce", 100e6), ("allreduce", 20e6)])
    # 120 MB a step x 1.5 x 10 steps over 2 s
    assert harness.load_reader("busbw")(run) == pytest.approx(0.9)
    assert harness.load_reader("token_ms")(run) == pytest.approx(200.0)
    assert harness.load_reader("token_p95_ms")(run) == pytest.approx(200.0)
