"""The engine's stage readers (metrics/engine_*_us.tp.py) on synthetic
spans: each is the median per call of its two stamps, in us, and None
where no span carries both, as with the spans of a program that has no
stage stamps."""
from types import SimpleNamespace

import pytest

import harness

STAGES = {"engine_gang_us.tp": ("queue", "dispatch"),
          "engine_marshal_us.tp": ("dispatch", "launch"),
          "engine_launch_us.tp": ("launch", "launched"),
          "engine_finish_us.tp": ("launched", "complete")}


def _span(queue, dispatch, launch, launched, complete):
    return SimpleNamespace(t_submit=queue - 1000, t_queue=queue,
                           t_dispatch=dispatch, t_launch=launch,
                           t_launched=launched, t_rebound=launched,
                           t_complete=complete)


def _run(spans):
    return SimpleNamespace(spans=spans)


def test_readers_take_the_median_of_their_stage():
    # per call (ns): gang 10/30/20 us, marshal 5/7/6, launch 200/180/
    # 220, finish 40/60/50; a local-lane span lacks the gang stamps
    spans = [_span(0, 10_000, 15_000, 215_000, 255_000),
             _span(0, 30_000, 37_000, 217_000, 277_000),
             _span(0, 20_000, 26_000, 246_000, 296_000)]
    local = SimpleNamespace(t_queue=0, t_dispatch=None, t_launch=None,
                            t_launched=None, t_complete=5_000)
    want = {"engine_gang_us.tp": 20.0, "engine_marshal_us.tp": 6.0,
            "engine_launch_us.tp": 200.0, "engine_finish_us.tp": 50.0}
    run = _run(spans + [local])
    got = {name: harness.load_reader(name)(run) for name in STAGES}
    assert got == pytest.approx(want)
    # the engine's reading is the median of the four stages' sums,
    # which the sum of their medians comes near but need not equal
    engine = harness.load_reader("engine_us.tp")(_run(spans))
    assert engine == pytest.approx(277.0)
    assert sum(got.values()) == pytest.approx(engine, rel=0.1)


@pytest.mark.parametrize("name", sorted(STAGES))
def test_reader_finds_nothing_without_its_stamps(name):
    read = harness.load_reader(name)
    assert read(_run(None)) is None
    assert read(_run([])) is None
    # spans of a program without the launch stamps: the fields are
    # missing altogether, or present and never stamped
    older = SimpleNamespace(t_submit=0, t_queue=1, t_complete=9)
    unstamped = SimpleNamespace(t_submit=0, t_queue=1, t_dispatch=None,
                                t_launch=None, t_launched=None,
                                t_complete=None)
    assert read(_run([older, unstamped])) is None
