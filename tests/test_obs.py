"""Observability layer: span nesting and Chrome-trace schema, metrics
backing the stats() surfaces, per-op profiling against the cost model, and
the telemetry-off zero-impact contract."""
import json

import numpy as np
import pytest

from repro import gcv, obs
from repro.core import CompileOptions
from repro.core.runtime.cache import clear_caches
from repro.gnncv.tasks import build_task, request_inputs
from repro.serve import GNNCVServeEngine

OPTS = CompileOptions(target="fpga")


@pytest.fixture(autouse=True)
def clean_tracer():
    """Every test starts and ends with the tracer off and empty — the
    default state the rest of the suite (and production) relies on."""
    obs.get_tracer().disable()
    obs.clear()
    yield
    obs.get_tracer().disable()
    obs.clear()


# ------------------------------------------------------------- span core --
def test_disabled_tracer_hands_out_shared_noop():
    assert not obs.enabled()
    sp = obs.span("anything", cat="x", k=1)
    assert sp is obs.NOOP_SPAN
    with sp as s:
        s.set(more=2)                       # absorbed, never recorded
    assert obs.get_tracer().spans == []


def test_span_nesting_tracks_parents():
    t = obs.get_tracer()
    t.enable()
    with obs.span("outer", cat="c"):
        with obs.span("middle", cat="c"):
            with obs.span("inner", cat="c"):
                pass
    parents = {s.name: s.parent for s in t.spans}
    assert parents == {"inner": "middle", "middle": "outer", "outer": None}
    # spans accumulate in finish order: inner closes first
    assert [s.name for s in t.spans] == ["inner", "middle", "outer"]


def test_span_set_attaches_attributes_mid_flight():
    t = obs.get_tracer()
    t.enable()
    with obs.span("work", cat="c", n_in=3) as sp:
        sp.set(n_out=7)
    (span,) = t.spans
    assert span.args == {"n_in": 3, "n_out": 7}


def test_chrome_trace_schema_round_trip(tmp_path):
    t = obs.get_tracer()
    t.enable()
    with obs.span("outer", cat="compile", graph="g"):
        with obs.span("inner", cat="compile"):
            pass
    obs.instant("marker", cat="serve", rid=1)
    t0 = obs.now()
    obs.complete("request", t0 - 0.010, t0, cat="serve", rid=2)
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(path)
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert sorted(e["ph"] for e in events) == ["X", "X", "X", "i"]
    for e in events:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= e.keys()
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # events are exported in start-time order; outer started first
    complete = [e for e in events if e["ph"] == "X"]
    assert complete[0]["name"] == "request"          # started 10ms early
    req = next(e for e in events if e["name"] == "request")
    assert 9e3 < req["dur"] < 12e3                   # ~10ms in us
    assert req["args"] == {"rid": 2}


def test_telemetry_context_restores_prior_state():
    with obs.telemetry(True):
        assert obs.enabled()
    assert not obs.enabled()
    with obs.telemetry(False):
        assert not obs.enabled()


# --------------------------------------------------------------- metrics --
def test_histogram_is_zero_safe_and_counter_monotonic():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    assert h.percentile(50) is None and h.percentile(95) is None
    assert h.mean is None
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.percentile(50) == pytest.approx(3.0)
    assert h.percentile(95) == pytest.approx(4.0)
    c = reg.counter("done")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("done") is c                  # get-or-create


def test_registry_rejects_kind_mismatch():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(AssertionError):
        reg.gauge("x")


def test_compile_pipeline_emits_pass_spans():
    clear_caches()
    g = build_task("b6", small=True)
    with obs.telemetry(True):
        gcv.compile(g, options=OPTS)
    names = {s.name for s in obs.get_tracer().spans}
    assert {"compile", "pass.fusion", "pass.lower", "pass.tiling",
            "pass.select", "pass.select_kernels", "pass.schedule",
            "pass.liveness"} <= names
    parents = {s.name: s.parent for s in obs.get_tracer().spans}
    assert parents["pass.fusion"] == "compile"
    top = next(s for s in obs.get_tracer().spans if s.name == "compile")
    assert top.args["ops"] > 0                       # set() after the passes


# ----------------------------------------------------- engine stats/spans --
def test_engine_stats_safe_with_zero_requests():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=2)
    s = eng.stats()
    assert s["completed"] == 0 and s["submitted"] == 0
    assert s["p50_sojourn_ms"] is None
    assert s["p95_sojourn_ms"] is None
    assert s["req_per_s"] is None
    assert s["per_task"]["b6"] == {"submitted": 0, "completed": 0,
                                   "deadline_misses": 0,
                                   "req_per_s": None}
    assert s["deadline_miss_rate"] is None
    assert s["goodput_req_per_s"] is None
    # the whole dict must serialize (CI writes stats into JSON records)
    json.dumps(s)


def test_engine_stats_read_from_metrics_registry():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=4)
    for s in range(5):
        eng.submit("b6", **request_inputs(eng.plans["b6"], seed=s))
    assert eng.run() == 5
    st = eng.stats()
    assert st["completed"] == 5 == eng.metrics.counter("completed").value
    assert st["per_task"]["b6"]["completed"] == 5
    assert st["p50_sojourn_ms"] > 0 and st["p95_sojourn_ms"] > 0
    assert st["req_per_s"] > 0
    assert st["padded"] == eng.metrics.counter("padded").value
    assert eng.metrics.histogram("sojourn_ms").count == 5


def test_two_engines_do_not_share_request_counters():
    clear_caches()
    g = build_task("b6", small=True)
    a = GNNCVServeEngine({"b6": g}, options=OPTS, max_batch=2)
    b = GNNCVServeEngine({"b6": g}, options=OPTS, max_batch=2)
    a.submit("b6", **request_inputs(a.plans["b6"], seed=0))
    assert a.run() == 1
    assert a.stats()["completed"] == 1
    assert b.stats()["completed"] == 0


def test_serving_lifecycle_emits_batch_and_request_spans():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=4)
    for s in range(3):
        eng.submit("b6", **request_inputs(eng.plans["b6"], seed=s))
    with obs.telemetry(True):
        assert eng.run() == 3
    doc = obs.get_tracer().to_chrome()
    by_name = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["serve.dispatch"]) == 1
    assert len(by_name["serve.harvest"]) == 1
    assert len(by_name["request"]) == 3
    d = by_name["serve.dispatch"][0]["args"]
    assert d["bucket"] == 4 and d["n"] == 3 and d["pad"] == 1
    for r in by_name["request"]:
        assert r["args"]["task"] == "b6"
        assert r["args"]["batch_id"] == d["batch_id"]


# ------------------------------------------------------------- profiling --
@pytest.mark.parametrize("task", ["b1", "b6"])
def test_profile_covers_every_plan_op(task):
    clear_caches()
    model = gcv.compile(build_task(task, small=True), options=OPTS)
    prof = model.profile(repeats=1)
    assert set(prof) == {op.name for op in model.plan.ops}
    for op in model.plan.ops:
        row = prof[op.name]
        assert row["s"] > 0
        assert row["kernel"] == op.kernel


def test_profile_report_agreement_rate_on_b6():
    clear_caches()
    model = gcv.compile(build_task("b6", small=True), options=OPTS)
    rep = model.profile_report(repeats=1)
    ag = rep["agreement"]
    assert ag["considered"] >= 1           # b6 has dense multi-candidate ops
    assert 0 <= ag["agree"] <= ag["considered"]
    assert ag["rate"] is None or 0.0 <= ag["rate"] <= 1.0
    assert "cost-model agreement" in rep["text"]
    # every row lines measured seconds up against the plan's kernel binding
    by_op = {op.name: op for op in model.plan.ops}
    for row in rep["rows"]:
        assert row["kernel"] == by_op[row["op"]].kernel
        assert row["measured_s"] > 0


# -------------------------------------------------------- off-by-default --
def test_telemetry_off_outputs_bit_identical_and_no_spans():
    clear_caches()
    g = build_task("b6", small=True)
    inputs = request_inputs(gcv.compile(g, options=OPTS).plan, seed=0)
    out_off = gcv.compile(g, options=OPTS).run(**inputs)
    with obs.telemetry(True):
        out_on = gcv.compile(
            g, options=CompileOptions(target="fpga", telemetry=True)
        ).run(**inputs)
    for a, b in zip(out_off, out_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    obs.clear()
    out_again = gcv.compile(g, options=OPTS).run(**inputs)
    assert obs.get_tracer().spans == []    # tracing off: nothing recorded
    for a, b in zip(out_off, out_again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trace_to_writes_file_and_disables(tmp_path):
    clear_caches()
    path = tmp_path / "t.json"
    with gcv.trace_to(path):
        assert obs.enabled()
        gcv.compile(build_task("b6", small=True),
                    options=CompileOptions(target="fpga", telemetry=True))
    assert not obs.enabled()
    names = {e["name"]
             for e in json.loads(path.read_text())["traceEvents"]}
    assert {"compile", "pass.fusion", "pass.liveness"} <= names


def test_check_trace_tool_validates_artifacts(tmp_path):
    import sys
    sys.path.insert(0, "tools")
    try:
        import check_trace
    finally:
        sys.path.pop(0)
    path = tmp_path / "t.json"
    with gcv.trace_to(path):
        gcv.compile(build_task("b6", small=True),
                    options=CompileOptions(target="fpga", telemetry=True))
    assert check_trace.check(str(path), ["compile", "pass.fusion"]) == []
    problems = check_trace.check(str(path), ["no.such.span"])
    assert problems and "no.such.span" in problems[0]
    assert check_trace.check(str(tmp_path / "missing.json"), ["x"]) \
        == [f"{tmp_path / 'missing.json'}: missing"]


# ------------------------------------------------- profiler bridge ------
def _profiled(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` trace; return the host
    plane's events as ``(name, start_ns, end_ns)``."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_live_spans_land_on_the_profiler_trace_nested(tmp_path):
    def body():
        obs.get_tracer().enable()
        with obs.span("serve.outer", cat="serve", k=1):
            with obs.span("serve.inner", cat="serve"):
                pass
        t0 = obs.now()
        obs.complete("serve.after", t0, t0 + 1e-3)     # retroactive
    host = _profiled(tmp_path, body)
    by_name = {name: (a, b) for name, a, b in host
               if name.startswith("serve.")}
    # plain names (no args folded in), retroactive events not bridged
    assert set(by_name) == {"serve.outer", "serve.inner"}
    (o0, o1), (i0, i1) = by_name["serve.outer"], by_name["serve.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert {s.name for s in obs.get_tracer().spans} == set(by_name)


def test_disabled_tracer_makes_no_profiler_annotation(monkeypatch):
    from repro.obs import trace
    made = []
    monkeypatch.setattr(trace, "_annotation",
                        lambda name: made.append(name) or obs.NOOP_SPAN)
    assert obs.span("serve.launch") is obs.NOOP_SPAN
    assert obs.get_tracer().span("serve.launch") is obs.NOOP_SPAN
    with obs.span("serve.launch"):
        pass
    assert made == []
    obs.get_tracer().enable()
    with obs.span("serve.launch"):
        pass
    assert made == ["serve.launch"]


def _abs(e):
    t = obs.get_tracer()
    return t.epoch + e["ts"] / 1e6, t.epoch + (e["ts"] + e["dur"]) / 1e6


def test_traced_run_splits_dispatch_and_harvest_into_live_spans():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=4)
    for s in range(6):
        eng.submit("b6", **request_inputs(eng.plans["b6"], seed=s))
    with obs.telemetry(True):
        assert eng.run() == 6                     # batches of 4 and 2
    tracer = obs.get_tracer()
    events = sorted(tracer.events, key=lambda e: e["ts"])
    live = sorted(tracer.spans, key=lambda s: s.t0)
    eps = 1e-6

    def inside(names, parent):
        spans = {n: [s for s in live if s.name == n] for n in names}
        parents = [e for e in events if e["name"] == parent]
        assert len(parents) == 2
        for n in names:
            assert len(spans[n]) == len(parents), n
            for sp, e in zip(spans[n], parents):
                a, b = _abs(e)
                assert a - eps <= sp.t0 and sp.t0 + sp.dur <= b + eps, n
        return parents

    inside(("serve.stack", "serve.transfer", "serve.launch"),
           "serve.dispatch")
    harvests = inside(("serve.wait", "serve.copy"), "serve.harvest")
    # unpack follows the copy: the harvest event keeps its end at the copy
    unpack = [s for s in live if s.name == "serve.unpack"]
    assert len(unpack) == 2
    for sp, e in zip(unpack, harvests):
        assert sp.t0 >= _abs(e)[1] - eps
    # the retroactive events keep their args
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    # (a cold runner's first launch compiles, which may count a stall)
    assert set(by) - {"serve.stall"} == {"serve.dispatch", "serve.harvest",
                                         "request"}
    assert set(by["serve.dispatch"][0]["args"]) == {
        "task", "bucket", "batch_id", "n", "pad", "device", "shard_n",
        "shard_pad"}
    assert set(by["serve.harvest"][0]["args"]) == {
        "task", "batch_id", "bucket", "n", "device", "shard_n"}
    assert len(by["request"]) == 6
    assert set(by["request"][0]["args"]) == {
        "rid", "task", "batch_id", "bucket", "pad", "device", "queued_ms"}
    # a request ends where its harvest event ends
    first = by["serve.harvest"][0]
    assert {_abs(r)[1] for r in by["request"]
            if r["args"]["batch_id"] == first["args"]["batch_id"]} \
        == {_abs(first)[1]}


def test_empty_dispatch_returns_before_the_schedule_span():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=2)
    with obs.telemetry(True):
        assert eng.dispatch() == 0
        assert eng.poll() == (0, 0)
    assert obs.get_tracer().spans == []


class _StubOut:
    """A batch output whose readiness probe or host copy takes ``sleep``
    seconds."""

    def __init__(self, rows, call, sleep):
        self.rows, self.call, self.sleep = rows, call, sleep

    def _maybe_sleep(self, call):
        if call == self.call:
            import time
            time.sleep(self.sleep)

    def is_ready(self):
        self._maybe_sleep("ready")
        return True

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        self._maybe_sleep("copy")
        return np.zeros((self.rows, 3), np.float32)


@pytest.mark.parametrize("call,sleep_s,stalls", [
    ("ready", 0.060, 1), ("copy", 0.060, 1),
    ("ready", 0.005, 0), ("copy", 0.005, 0)])
def test_a_slow_runtime_call_counts_one_stall(call, sleep_s, stalls):
    from repro.serve.gnncv import RUNTIME_CALLS, STALL_MS
    assert sleep_s * 1e3 > STALL_MS if stalls else sleep_s * 1e3 < STALL_MS
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=2)
    eng._runner = lambda task, bucket: (
        lambda **inputs: (_StubOut(bucket, call, sleep_s),))
    eng.submit("b6", **request_inputs(eng.plans["b6"], seed=0))
    with obs.telemetry(True):
        assert eng.dispatch() == 1
        assert eng.poll() == (0, 1)
    st = eng.stats()
    assert st["stalls"] == stalls
    assert st["stalls_by_call"] == {
        c: (stalls if c == call else 0) for c in RUNTIME_CALLS}
    stall_events = [e for e in obs.get_tracer().events
                    if e["name"] == "serve.stall"]
    assert len(stall_events) == stalls
    if stalls:
        assert st["stall_ms_total"] >= sleep_s * 1e3
        assert stall_events[0]["args"] == {"call": call, "batch_id": 0,
                                           "bucket": 1}
        assert stall_events[0]["dur"] >= sleep_s * 1e6
    else:
        assert st["stall_ms_total"] == 0.0


def test_stats_report_stalls_and_no_queue_depth_gauges():
    clear_caches()
    eng = GNNCVServeEngine({"b6": build_task("b6", small=True)},
                           options=OPTS, max_batch=2)
    st = eng.stats()
    assert st["stalls"] == 0 and st["stall_ms_total"] == 0.0
    assert set(st["stalls_by_call"]) == {"transfer", "launch", "ready",
                                         "wait", "copy"}
    for s in range(3):
        eng.submit("b6", **request_inputs(eng.plans["b6"], seed=s))
    assert eng.run() == 3
    assert eng.stats()["per_task"]["b6"]["completed"] == 3
    assert not [n for n in eng.metrics.snapshot() if "queue_depth" in n]
