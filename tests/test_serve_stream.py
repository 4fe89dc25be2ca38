"""Continuous batching: scheduler policies (FIFO parity, SLO slack/EDF,
priority, shedding), deadline admission and accounting, adaptive pipeline
depth bounds, the poll/stream open-loop pump, and the serve.schedule span.
"""
import numpy as np
import pytest

from repro import gcv, obs
from repro.core import CompileOptions
from repro.core.runtime.cache import clear_caches
from repro.gnncv.tasks import build_task, request_inputs
from repro.serve import FIFOScheduler, Scheduler, SLOScheduler
from repro.serve.scheduler import resolve_scheduler

OPTS = CompileOptions(target="fpga")
TASKS = ("b1", "b6")


@pytest.fixture(scope="module")
def graphs():
    return {t: build_task(t, small=True) for t in TASKS}


def make_engine(graphs, **kw):
    kw.setdefault("options", OPTS)
    kw.setdefault("max_batch", 4)
    return gcv.serve(graphs, **kw)


def submit_n(eng, task, n, seed0=0, **kw):
    return [eng.submit(task, **request_inputs(eng.plans[task],
                                              seed=seed0 + s), **kw)
            for s in range(n)]


# ------------------------------------------------------- policy resolution --
def test_scheduler_resolution_and_defaults(graphs):
    clear_caches()
    assert isinstance(resolve_scheduler(None, slo_ms=None), FIFOScheduler)
    assert isinstance(resolve_scheduler(None, slo_ms=50.0), SLOScheduler)
    assert isinstance(resolve_scheduler("slo", slo_ms=None), SLOScheduler)
    custom = FIFOScheduler()
    assert resolve_scheduler(custom, slo_ms=50.0) is custom
    with pytest.raises(AssertionError, match="unknown scheduler"):
        resolve_scheduler("lifo", slo_ms=None)
    with pytest.raises(TypeError):
        resolve_scheduler(42, slo_ms=None)
    eng = make_engine(graphs)
    assert eng.stats()["scheduler"] == "fifo"
    assert eng.max_pipeline_depth == eng.pipeline_depth   # fixed by default
    slo = make_engine(graphs, slo_ms=200.0)
    assert slo.stats()["scheduler"] == "slo"
    assert slo.max_pipeline_depth >= 4                    # SLO headroom
    with pytest.raises(AssertionError, match="max_pipeline_depth"):
        make_engine(graphs, pipeline_depth=3, max_pipeline_depth=2)
    with pytest.raises(AssertionError, match="slo_ms"):
        make_engine(graphs, slo_ms=0)


# ------------------------------------------------------------ FIFO parity --
def test_fifo_run_matches_explicit_scheduler_bitwise(graphs):
    """run() under the default engine and under an explicitly-named FIFO
    scheduler must be output-identical — the closed-batch path is the
    degenerate schedule, not a parallel implementation."""
    clear_caches()
    streams = []
    for scheduler in (None, "fifo"):
        eng = make_engine(graphs, scheduler=scheduler)
        reqs = []
        for s in range(5):
            reqs += submit_n(eng, TASKS[s % 2], 1, seed0=s)
        assert eng.run() == 5
        assert eng.stats()["steps"] == eng.steps
        streams.append(reqs)
    for a, b in zip(*streams):
        assert a.task == b.task and a.rid == b.rid
        for xa, xb in zip(a.result, b.result):
            assert np.array_equal(xa, xb)


def test_fifo_pick_is_oldest_head_first(graphs):
    clear_caches()
    eng = make_engine(graphs)
    submit_n(eng, "b6", 3)                 # older head, longer queue
    submit_n(eng, "b1", 1, seed0=3)
    d = eng.scheduler.pick(eng)
    assert (d.task, d.take, d.bucket) == ("b6", 3, 4)


# ------------------------------------------------- deadlines & admission --
def test_submit_records_deadline_and_priority(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=250.0)
    r = submit_n(eng, "b1", 1)[0]          # deadline defaults to slo_ms
    assert r.deadline_s == pytest.approx(r.t_submit + 0.250, abs=5e-3)
    r2 = submit_n(eng, "b1", 1, seed0=1, deadline_ms=50, priority=3)[0]
    assert r2.deadline_s == pytest.approx(r2.t_submit + 0.050, abs=5e-3)
    assert r2.priority == 3
    nolimit = make_engine(graphs)
    r3 = submit_n(nolimit, "b1", 1)[0]     # no SLO -> no implicit deadline
    assert r3.deadline_s is None


def test_deadline_expired_at_submit_is_admission_rejected(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=500.0)
    r = submit_n(eng, "b1", 1, deadline_ms=0)[0]
    assert r.done and r.shed and r.missed_deadline and r.result is None
    s = eng.stats()
    assert s["pending"] == 0               # never entered a queue
    assert s["expired_at_submit"] == 1 and s["deadline_misses"] == 1
    assert s["submitted"] == 1 and s["completed"] == 0
    assert s["deadline_miss_rate"] == 1.0
    assert eng.run() == 0                  # nothing to serve


def test_expired_queued_requests_are_shed_not_served(graphs):
    import time
    clear_caches()
    eng = make_engine(graphs, slo_ms=500.0)
    doomed = submit_n(eng, "b1", 2, deadline_ms=1)
    live = submit_n(eng, "b6", 1, seed0=2)[0]
    time.sleep(0.02)                       # let the tight deadlines lapse
    assert eng.run() == 1                  # only the live request executes
    assert live.done and not live.missed_deadline
    for r in doomed:
        assert r.done and r.shed and r.result is None
    s = eng.stats()
    assert s["shed"] == 2 and s["deadline_misses"] == 2
    assert s["goodput"] == 1
    assert s["deadline_miss_rate"] == pytest.approx(2 / 3)


def test_late_completion_counts_as_miss_without_shedding(graphs):
    import time
    clear_caches()
    eng = make_engine(graphs, slo_ms=500.0,
                      scheduler=SLOScheduler(shed_expired=False))
    r = submit_n(eng, "b1", 1, deadline_ms=1)[0]
    time.sleep(0.02)
    assert eng.run() == 1                  # served anyway, late
    assert r.done and r.missed_deadline and not r.shed
    assert r.result is not None
    s = eng.stats()
    assert s["shed"] == 0 and s["deadline_misses"] == 1 and s["goodput"] == 0


# ------------------------------------------------------- SLO scheduling --
def test_slo_pick_prefers_tighter_service_corrected_slack(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=10_000.0)
    submit_n(eng, "b6", 3, deadline_ms=9_000)      # older but loose
    submit_n(eng, "b1", 2, seed0=3, deadline_ms=100)   # newer, urgent
    d = eng.scheduler.pick(eng)
    assert (d.task, d.take, d.bucket) == ("b1", 2, 2)
    assert d.slack_ms is not None and d.reason == "min-slack"


def test_slo_pick_mixed_queue_bucket_choice(graphs):
    """Bucket quantization under the SLO policy: take is the whole queue
    window, bucket the next power of two."""
    clear_caches()
    eng = make_engine(graphs, slo_ms=10_000.0, max_batch=8)
    submit_n(eng, "b6", 5)
    d = eng.scheduler.pick(eng)
    assert (d.task, d.take, d.bucket) == ("b6", 5, 8)
    submit_n(eng, "b1", 1, seed0=5, deadline_ms=10)    # urgent singleton
    d2 = eng.scheduler.pick(eng)
    assert (d2.task, d2.take, d2.bucket) == ("b1", 1, 1)


def test_priority_trumps_slack(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=10_000.0)
    submit_n(eng, "b1", 1, deadline_ms=50)             # urgent, prio 0
    submit_n(eng, "b6", 1, seed0=1, deadline_ms=9_000, priority=5)
    d = eng.scheduler.pick(eng)
    assert d.task == "b6"                              # priority first


def test_deadline_free_traffic_under_slo_policy_keeps_fifo_order(graphs):
    clear_caches()
    eng = make_engine(graphs, scheduler="slo")         # no slo_ms: no
    submit_n(eng, "b6", 1)                             # implicit deadlines
    submit_n(eng, "b1", 2, seed0=1)
    d = eng.scheduler.pick(eng)
    assert d.task == "b6" and d.reason == "no-deadline"
    assert eng.run() == 3                              # drains fully


# --------------------------------------------------------- estimation --
def test_estimator_cold_start_then_measured(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=1_000.0)
    cold = eng.estimate_batch_seconds("b1", 4)
    assert cold > 0                                    # analytic plan cost
    assert cold == pytest.approx(4 * eng.estimate_batch_seconds("b1", 1),
                                 rel=1e-6)             # scales with bucket
    submit_n(eng, "b1", 4)
    assert eng.run() == 4
    warm = eng.estimate_batch_seconds("b1", 4)
    h = eng.metrics.histogram("service_ms.b1.b4")
    assert h.count >= 1
    assert warm == pytest.approx(h.recent_mean() / 1e3)


def test_histogram_recent_mean_window():
    h = obs.MetricsRegistry().histogram("x")
    assert h.recent_mean() is None
    for v in range(100):
        h.observe(float(v))
    assert h.recent_mean(4) == pytest.approx((96 + 97 + 98 + 99) / 4)
    assert h.recent_mean(1000) == pytest.approx(np.mean(range(100)))


# ------------------------------------------------------ adaptive depth --
def test_adaptive_depth_never_below_one(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=100.0, pipeline_depth=2,
                      max_pipeline_depth=4)
    for _ in range(50):                    # p95 far beyond the SLO
        eng._h_sojourn_recent.observe(1e6)
        eng._adapt_depth()
    assert eng._depth == 1
    assert eng.stats()["pipeline_depth"] == 1


def test_adaptive_depth_never_above_max(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=10_000.0, pipeline_depth=1,
                      max_pipeline_depth=3)
    submit_n(eng, "b1", 8)                 # backlog > depth * max_batch
    submit_n(eng, "b6", 8, seed0=8)        # at every depth below the cap
    for _ in range(50):
        eng._adapt_depth()
    assert eng._depth == 3
    assert eng.run() == 16                 # depth change serves correctly
    assert eng.stats()["max_pipeline_depth"] == 3


def test_fixed_depth_engine_never_adapts(graphs):
    clear_caches()
    eng = make_engine(graphs)              # no SLO, max == pipeline_depth
    submit_n(eng, "b1", 4)
    submit_n(eng, "b6", 4, seed0=4)
    for _ in range(10):
        eng._adapt_depth()
    assert eng._depth == eng.pipeline_depth == 2


# ------------------------------------------------------- poll / stream --
def test_stats_idle_and_mid_stream(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=1_000.0)
    s = eng.stats()                        # idle: all zero-safe
    assert s["goodput"] == 0 and s["deadline_miss_rate"] is None
    assert s["goodput_req_per_s"] is None and s["pipeline_depth"] >= 1
    submit_n(eng, "b1", 2)
    assert eng.dispatch() == 2
    mid = eng.stats()                      # mid-stream: dispatched, not
    assert mid["inflight"] == 2            # yet harvested
    assert mid["completed"] == 0 and mid["pending"] == 0
    assert mid["req_per_s"] is None and mid["deadline_miss_rate"] is None
    assert eng.harvest() == 2
    done = eng.stats()
    assert done["goodput"] == 2 and done["deadline_miss_rate"] == 0.0
    assert done["goodput_req_per_s"] > 0


def test_poll_pumps_without_blocking_until_window_full(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=5_000.0, pipeline_depth=2,
                      max_pipeline_depth=2)
    assert eng.poll() == (0, 0)            # idle poll is a no-op
    submit_n(eng, "b1", 8)
    dispatched, _ = eng.poll()
    assert dispatched == 8                 # two depth-bounded batches
    assert len(eng._inflight) == 2
    total = 0
    for _ in range(100):
        total += eng.poll(draining=True)[1]
        if total == 8 and not eng._inflight:
            break
    assert total == 8


def test_stream_replays_open_loop_schedule(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=5_000.0)
    arrivals = []
    for i in range(8):
        task = TASKS[i % 2]
        arrivals.append((i * 0.002, task,
                         request_inputs(eng.plans[task], seed=i)))
    reqs = eng.stream(arrivals, max_wall_s=30.0)
    assert len(reqs) == 8
    assert all(r.done and r.result is not None for r in reqs)
    s = eng.stats()
    assert s["goodput"] == 8 and s["deadline_misses"] == 0
    assert s["pending"] == 0 and s["inflight"] == 0
    # arrival order preserved per task (FIFO within a queue)
    b1 = [r.rid for r in reqs if r.task == "b1"]
    assert b1 == sorted(b1)


def test_stream_accepts_deadline_and_priority_tuples(graphs):
    clear_caches()
    eng = make_engine(graphs, slo_ms=5_000.0)
    arrivals = [
        (0.0, "b1", request_inputs(eng.plans["b1"], seed=0), 2_000),
        (0.001, "b6", request_inputs(eng.plans["b6"], seed=1), None, 4),
    ]
    reqs = eng.stream(arrivals, max_wall_s=30.0)
    assert reqs[0].deadline_s == pytest.approx(reqs[0].t_submit + 2.0,
                                               abs=5e-3)
    assert reqs[1].priority == 4
    # None falls back to the engine's slo_ms default
    assert reqs[1].deadline_s == pytest.approx(reqs[1].t_submit + 5.0,
                                               abs=5e-3)
    assert all(r.done for r in reqs)


# ------------------------------------------------------- observability --
def test_dispatch_emits_schedule_span(graphs, tmp_path):
    clear_caches()
    eng = make_engine(graphs, slo_ms=5_000.0)
    submit_n(eng, "b1", 2)
    path = tmp_path / "trace.json"
    with gcv.trace_to(path):
        assert eng.run() == 2
    import json
    import sys
    events = json.loads(path.read_text())["traceEvents"]
    sched = [e for e in events if e["name"] == "serve.schedule"]
    # one per dispatch() with work queued: run()'s later calls find every
    # queue empty and return before opening a span
    assert len(sched) == 1
    hit = sched[0]
    assert hit["args"]["policy"] == "slo"
    assert (hit["args"]["task"], hit["args"]["take"],
            hit["args"]["bucket"]) == ("b1", 2, 2)
    sys.path.insert(0, "tools")
    try:
        import check_trace
    finally:
        sys.path.pop(0)
    assert check_trace.check(str(path), ["serve.schedule"]) == []


def test_custom_scheduler_instance_drives_dispatch(graphs):
    """The management-plane seam: a user policy decides, the engine
    executes — no engine subclassing required."""
    clear_caches()

    class OnlyB6(Scheduler):
        name = "only-b6"

        def pick(self, engine, *, draining=False):
            from repro.serve.scheduler import Decision
            q = engine.queues["b6"]
            if not q:
                return FIFOScheduler().pick(engine, draining=draining)
            take = min(len(q), engine.max_batch)
            return Decision("b6", take,
                            engine._bucket(take, engine.max_batch))

    eng = make_engine(graphs, scheduler=OnlyB6())
    submit_n(eng, "b1", 1)
    submit_n(eng, "b6", 2, seed0=1)
    assert eng.dispatch() == 2             # b6 first despite older b1
    assert eng.stats()["scheduler"] == "only-b6"
    assert eng.run() == 3                  # harvests the in-flight b6 too
