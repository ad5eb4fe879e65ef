"""Stage stamps of the TPU engine's dispatch path (observability/record.py
``mark``): on the leader, executor and batched lanes every gang call's
record is stamped queue <= gang_ready <= dispatch <= launch <= launched <=
rebound <= complete, and its four stages (gang, marshal, launch,
finish) sum to queue -> complete; under ``jax.profiler`` the same
stages are ``accl.*`` annotations on the profiler's clock, and each
``accl.launch`` encloses its call's ``PjitFunction`` event.  The
tracing-off path (no span, no annotation) is pinned in
tests/test_observability.py."""
import glob

import numpy as np
import pytest

from accl_tpu import ReduceFunction
from accl_tpu.observability import trace as obs_trace
from accl_tpu.observability.record import CallRecord, mark

COUNT = 64
STAGES = ("accl.driver", "accl.gang", "accl.marshal", "accl.launch",
          "accl.finish")
ORDER = ("queue", "gang_ready", "dispatch", "launch", "launched",
         "rebound", "complete")


@pytest.fixture
def tracing():
    col = obs_trace.enable()
    col.clear()
    try:
        yield col
    finally:
        obs_trace.disable()
        col.clear()


def _buffers(accl, rank):
    s = accl.create_buffer_like(np.full(COUNT, float(rank + 1), np.float32))
    s.sync_to_device()
    return s, accl.create_buffer(COUNT, np.float32)


def _sync_calls(world, reps):
    def fn(accl, rank):
        s, r = _buffers(accl, rank)
        for _ in range(reps):
            accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                           from_fpga=True, to_fpga=True)
        return True

    assert all(world.run(fn))


def _batched_calls(world, reps):
    """`reps` independent async calls queued while the executor is held
    off, so that it takes them as one batch."""
    eng = world.engine
    with eng._ready_cv:
        eng._inline_busy = True   # the executor parks while this is set

    def fn(accl, rank):
        s, r = _buffers(accl, rank)
        return [accl.allreduce(s, r, COUNT, ReduceFunction.SUM,
                               from_fpga=True, to_fpga=True,
                               run_async=True) for _ in range(reps)]

    try:
        reqs = [q for qs in world.run(fn) for q in qs]
    finally:
        with eng._ready_cv:
            eng._inline_busy = False
            eng._ready_cv.notify_all()
    for q in reqs:
        assert q.wait(30.0)
        q.check()


def _check_spans(spans, lane, n):
    spans = [s for s in spans if s.collective == "allreduce"]
    assert len(spans) == n
    for s in spans:
        assert s.lane == lane
        ts = [getattr(s, "t_" + k) for k in ORDER]
        assert None not in ts, s.timestamps()
        assert ts == sorted(ts), s.timestamps()
        stages = [s.t_dispatch - s.t_queue, s.t_launch - s.t_dispatch,
                  s.t_launched - s.t_launch, s.t_complete - s.t_launched]
        assert sum(stages) == s.t_complete - s.t_queue
        assert s.t_submit <= s.t_queue


@pytest.mark.parametrize("nranks", [1, 4])
@pytest.mark.parametrize("lane", ["leader", "executor", "batched"])
def test_stage_stamps_monotone_and_sum(tracing, nranks, lane):
    from accl_tpu.backends.tpu import TpuWorld

    reps = 3
    with TpuWorld(nranks) as w:
        if lane == "executor":
            w.engine.leader_dispatch = False
        _sync_calls(w, 1)   # compile outside the checked calls
        tracing.clear()
        if lane == "batched":
            _batched_calls(w, reps)
        else:
            _sync_calls(w, reps)
        stats = dict(w.engine.stats)
    _check_spans(tracing.spans(), lane, reps * nranks)
    if lane == "batched":
        assert stats["batches"] >= 1


def _xplane_lines(logdir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            yield [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]


def test_profiler_holds_one_stage_per_call(tracing, tmp_path):
    import jax

    from accl_tpu.backends.tpu import TpuWorld

    calls = 5
    with TpuWorld(1) as w:
        _sync_calls(w, 1)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _sync_calls(w, calls)
        finally:
            jax.profiler.stop_trace()
    counts = dict.fromkeys(STAGES, 0)
    enclosed = 0
    for evs in _xplane_lines(tmp_path):
        pjit = [(s, e) for n, s, e in evs if n.startswith("PjitFunction")]
        for name, s, e in evs:
            if name in counts:
                counts[name] += 1
            if name == "accl.launch":
                assert any(s <= ps and pe <= e for ps, pe in pjit), \
                    "accl.launch holds no PjitFunction on its line"
                enclosed += 1
    assert counts == dict.fromkeys(STAGES, calls)
    assert enclosed == calls


def test_stage_helper_stamps_and_switches(monkeypatch):
    """record.mark(): one clock read stamps every record, and, tracing
    on, the thread's open annotation closes before the next opens, None
    opens nothing, the default leaves it alone."""
    log = []

    class Ann:
        def __init__(self, name):
            log.append(("open", name))
            self.name = name

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(obs_trace, "_open_annotation", Ann)
    monkeypatch.setattr(obs_trace, "_enabled", True)
    a, b = CallRecord("x"), CallRecord("y")
    mark([a, None, b], ("t_dispatch",), t=7, stage="accl.marshal")
    mark([a, b], ("t_launch",), stage="accl.launch")
    mark([a, b], ("t_rebound",), t=9)
    mark([], (), stage=None)
    assert a.t_dispatch == b.t_dispatch == 7
    assert a.t_launch == b.t_launch and a.t_launch > 0
    assert a.t_rebound == b.t_rebound == 9
    assert log == [("open", "accl.marshal"), ("close", "accl.marshal"),
                   ("open", "accl.launch"), ("close", "accl.launch")]
