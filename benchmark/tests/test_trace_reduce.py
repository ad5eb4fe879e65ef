"""The trace reduction, on a hand-built trace whose numbers are known and
on a small trace recorded on one TPU v5e chip."""
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: tp_decode.1chip traced for 0.2 s (6 tokens of 80 calls) on a TPU v5e
RECORDED = os.path.join(DATA, "tp_decode_1chip_6tokens.xplane.pb")


def _events(meta: dict, evs: list) -> str:
    """Line events (name, start_ns, dur_ns) as XLine text."""
    return "".join(
        f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}\n" for n, s, d in evs)


def _plane(pid: int, name: str, lines: dict) -> str:
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = "".join(
        f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0\n"
        f"{_events(meta, evs)}}}\n"
        for i, (ln, evs) in enumerate(lines.items()))
    md = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                 f"name: \"{n}\" }} }}\n" for n, i in meta.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}{md}}}\n"


@pytest.fixture
def synthetic():
    """Window [100, 1100] ns set by the host phases; two devices.

    TPU:0 ops: a [100, 300], b [250, 400], a [900, 1000] -> busy 400;
    gaps [400, 900] (issue covers [100, 600], wait [600, 1100]: 200 vs
    300, so wait) and [1000, 1100] (wait).  TPU:1 ops: a [150, 250] ->
    busy 100; gaps [100, 150] (issue), [250, 1100] (wait covers 500 of
    850, issue 350: wait).  An op outside the window is left out."""
    from jax.profiler import ProfileData

    host = {"python3": [("bench.issue", 100, 500),
                        ("bench.wait", 600, 500),
                        ("other", 0, 2000)]}
    d0 = {"XLA Ops": [("a", 100, 200), ("b", 250, 150), ("a", 900, 100),
                      ("a", 5000, 100)],
          "XLA Modules": [("jit_body", 100, 300), ("jit_body", 900, 100)]}
    d1 = {"XLA Ops": [("a", 150, 100)],
          "XLA Modules": [("jit_body", 150, 100)]}
    text = (_plane(1, "/host:CPU", host) + _plane(2, "/device:TPU:0", d0)
            + _plane(3, "/device:TPU:1", d1))
    return ProfileData.from_text_proto(text).planes


def test_synthetic_trace(synthetic):
    s = trace_reduce.reduce_planes(synthetic)
    assert s.devices == 2
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((400 + 100) / 2 * 1e-9)
    assert s.program_s == pytest.approx((400 + 100) / 2 * 1e-9)
    assert s.programs == pytest.approx(1.5)
    assert dict(s.ops) == pytest.approx({"a": 200e-9, "b": 75e-9})
    gaps = dict(s.gaps)
    assert gaps == pytest.approx({"bench.wait": (500 + 100 + 850) / 2e9,
                                  "bench.issue": 50 / 2e9})
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)


def test_no_device_plane_reads_nothing(synthetic):
    host_only = [p for p in synthetic if not p.name.startswith("/device")]
    assert trace_reduce.reduce_planes(host_only) is None


def test_recorded_chip_trace():
    s = trace_reduce.reduce_file(RECORDED)
    assert s.devices == 1
    # 6 tokens x 80 calls, one program execution per call
    assert s.programs == 480
    assert s.window_s == pytest.approx(0.205175404)
    assert s.busy_s == pytest.approx(0.001197292)
    assert s.program_s == pytest.approx(0.001198857)
    assert len(s.ops) == 1 and "copy" in s.ops[0][0]
    gaps = dict(s.gaps)
    assert set(gaps) == {"bench.issue", "bench.wait"}
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)


def test_interval_helpers():
    assert trace_reduce.union([[5, 6], [1, 3], [2, 4]]) == [[1, 4], [5, 6]]
    assert trace_reduce.gaps([[1, 4], [5, 6]], 0, 8) == \
        [[0, 1], [4, 5], [6, 8]]
    m = [[1, 4], [5, 6]]
    assert trace_reduce.overlap(m, [1, 5], 3, 5.5) == pytest.approx(1.5)
