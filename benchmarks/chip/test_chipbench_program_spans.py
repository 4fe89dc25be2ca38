"""CPU tests of the program's serving spans on the profiler trace and of
the per-layer metrics that read them."""
from __future__ import annotations

import pathlib
import types

import pytest

import harness
import programtrace
import tracecalc

HERE = pathlib.Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "b6-dyn.saturate.events.json"


def _reader(name):
    return harness.load_module(HERE / "metrics" / f"{name}.py")


def _events():
    # window 0..1000 ns; device 0 idle 100-300 and 400-700, device 1 busy
    # throughout; one dispatch (90-320) and one harvest (390-690)
    return {
        "devices": {
            "/device:TPU:0": [("fusion", 0, 100), ("knn", 300, 400),
                              ("fusion", 700, 1000)],
            "/device:TPU:1": [("fusion", 0, 1000)]},
        "host": [("bench.window", 0, 1000), ("bench.dispatch", 90, 320),
                 ("bench.stack_inputs", 120, 200),
                 ("bench.harvest", 390, 690), ("bench.gc", 450, 470)],
        "program": [("serve.schedule", 95, 110), ("serve.stack", 125, 150),
                    ("serve.transfer", 150, 190),
                    ("serve.launch", 200, 310), ("serve.wait", 395, 600),
                    ("serve.copy", 600, 650), ("serve.unpack", 650, 680)],
    }


def _ctx(events=None, spans=(), window_s=1.0):
    return types.SimpleNamespace(
        events=_events() if events is None else events, spans=list(spans),
        window_s=window_s)


def _event(name, dur_us=0.0, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us,
            "args": args}


def test_idle_goes_to_the_innermost_program_span():
    got = programtrace.idle_by_span(_ctx())
    # device 0 alone holds idle time; averaged over the two devices.  The
    # bench.gc inside serve.wait keeps its 20 ns; bench.stack_inputs and
    # bench.dispatch keep what no serve.* span covers.
    want = {"serve.schedule": 10, "serve.stack": 25, "serve.transfer": 40,
            "serve.launch": 100, "serve.wait": 180, "serve.copy": 50,
            "serve.unpack": 30}
    assert got == {k: v / 2 for k, v in want.items()}


def test_grouped_charge_equals_one_charge_over_all_spans():
    ev = _events()
    ev["program"] += [("serve.inner", 210, 250)]     # nested in launch
    ev["host"] += [("bench.late", 640, 720)]          # starts inside copy
    lo, hi = tracecalc.window(ev)
    want = {}
    for ops in ev["devices"].values():
        whole = tracecalc.charge_gaps(tracecalc.gaps(ops, lo, hi),
                                      ev["host"] + ev["program"])
        for k, v in whole.items():
            if k.startswith("serve."):
                want[k] = want.get(k, 0.0) + v / 2
    assert programtrace.idle_by_span(_ctx(ev)) == pytest.approx(want)
    assert want["serve.inner"] == 20 and want["serve.copy"] == 20


def test_dispatch_idle_ms_is_per_dispatch_event_of_device_0():
    spans = [_event("serve.dispatch", device=0),
             _event("serve.dispatch", device=1)]
    got = _reader("dispatch_idle_ms.lat").read(_ctx(spans=spans))
    assert got == pytest.approx((10 + 25 + 40 + 100) / 2 / 1e6)


def test_harvest_idle_ms_is_per_harvest_event_of_device_0():
    spans = [_event("serve.harvest", device=0)] * 2
    got = _reader("harvest_idle_ms.lat").read(_ctx(spans=spans))
    assert got == pytest.approx((180 + 50 + 30) / 2 / 2 / 1e6)


@pytest.mark.parametrize("name", ["dispatch_idle_ms.lat",
                                  "harvest_idle_ms.lat"])
def test_idle_readers_read_nothing_without_program_spans(name):
    spans = [_event("serve.dispatch", device=0),
             _event("serve.harvest", device=0)]
    ev = _events()
    ev["program"] = []
    assert _reader(name).read(_ctx(ev, spans)) is None


@pytest.mark.parametrize("name", ["stall_ms.lat", "stall_ms.tput"])
def test_stall_ms_is_stall_time_over_the_window(name):
    read = _reader(name).read
    spans = [_event("serve.stall", 120e3, call="wait"),
             _event("serve.stall", 60e3, call="ready"),
             _event("serve.dispatch", 5e3, device=0)]
    assert read(_ctx(spans=spans, window_s=50.0)) == pytest.approx(3.6)
    assert read(_ctx(spans=spans[2:], window_s=50.0)) == 0.0


@pytest.mark.parametrize("name", ["stall_ms.lat", "stall_ms.tput"])
def test_stall_ms_reads_nothing_from_a_program_without_the_counter(
        name, monkeypatch):
    from repro.serve import gnncv
    monkeypatch.delattr(gnncv, "STALL_MS")
    assert _reader(name).read(_ctx(window_s=1.0)) is None


def test_program_spans_come_from_the_trace_of_the_window(tmp_path,
                                                         monkeypatch):
    import jax

    from repro import obs
    trace_dir = tmp_path / harness.TRACES / "cell"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        with jax.profiler.TraceAnnotation(tracecalc.WINDOW):
            with obs.span("serve.launch", cat="serve"):
                with obs.span("serve.transfer", cat="serve"):
                    pass
            with obs.span("compile", cat="compile"):
                pass
    finally:
        tracer.disable()
        tracer.clear()
        jax.profiler.stop_trace()
    events = tracecalc.read_xplane(tracecalc.find_xplane(trace_dir))
    monkeypatch.setattr(programtrace, "ROOT", tmp_path)
    spans = programtrace.program(_ctx(events))
    assert [name for name, _, _ in spans] == ["serve.launch",
                                              "serve.transfer"]
    (_, a, b), (_, c, d) = spans
    assert a <= c <= d <= b
    lo, hi = tracecalc.window(events)
    assert lo <= a and b <= hi
    # the host reduction keeps the bench.* spans alone
    assert [name for name, _, _ in events["host"]] == [tracecalc.WINDOW]
    # a trace of another window gives nothing
    other = {"devices": events["devices"],
             "host": [(tracecalc.WINDOW, lo - 1, hi)]}
    assert programtrace.program(_ctx(other)) == []


def test_recorded_chip_trace_has_no_program_spans(tmp_path, monkeypatch):
    # recorded before the program's spans existed: the reduction is as it
    # was, and the readers built on program spans read nothing
    monkeypatch.setattr(programtrace, "ROOT", tmp_path)
    ev = tracecalc.load_events(RECORDED)
    assert "program" not in ev
    ctx = _ctx(ev, [_event("serve.dispatch", device=0)])
    assert _reader("dispatch_idle_ms.lat").read(ctx) is None
    assert ev["program"] == []
    s = tracecalc.summarize(ev)
    assert s["busy_s"] == pytest.approx(0.059324761)
