"""CPU tests of the reduction from a profiler trace to device numbers."""
from __future__ import annotations

import pathlib

import pytest

import tracecalc

HERE = pathlib.Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "b6-dyn.saturate.events.json"


def _events():
    # window 0..100; device A busy 10-30 and 20-40 (overlap) and 60-70;
    # device B busy 0-100
    return {
        "devices": {
            "/device:TPU:0": [("conv", 10, 30), ("knn_kernel", 20, 40),
                              ("conv", 60, 70), ("conv", 150, 160)],
            "/device:TPU:1": [("fusion", 0, 100)]},
        "host": [("bench.window", 0, 100), ("bench.harvest", 40, 50),
                 ("bench.poll", 40, 80), ("bench.stack_inputs", 75, 78)],
    }


def test_busy_is_the_union_clipped_to_the_window():
    ev = _events()
    ops = ev["devices"]["/device:TPU:0"]
    assert tracecalc.union(ops, 0, 100) == [(10, 40), (60, 70)]
    assert tracecalc.busy_ns(ops, 0, 100) == 40
    assert tracecalc.gaps(ops, 0, 100) == [(0, 10), (40, 60), (70, 100)]


def test_idle_gaps_go_to_the_innermost_host_span():
    ev = _events()
    ops = ev["devices"]["/device:TPU:0"]
    got = tracecalc.charge_gaps(tracecalc.gaps(ops, 0, 100), ev["host"])
    assert got == {"outside_bench_calls": 10 + 20, "bench.harvest": 10,
                   "bench.poll": 10 + 5 + 2, "bench.stack_inputs": 3}
    assert sum(got.values()) == 100 - 40


def test_summary_averages_over_devices():
    s = tracecalc.summarize(_events())
    assert s["devices"] == 2 and s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((40 + 100) / 2 * 1e-9)
    assert dict(s["device_ops"])["fusion"] == pytest.approx(50e-9)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(30e-9)


def test_kernel_time_and_roofline():
    ops = _events()["devices"]["/device:TPU:0"]
    assert tracecalc.kernel_ns(ops, "KNN", 0, 100) == (20.0, 1)
    share, bound = tracecalc.roofline_share(
        ops=2e12, nbytes=1e9, seconds=0.02, peak_flops=200e12,
        peak_bytes=1e12)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = tracecalc.roofline_share(
        ops=1.0, nbytes=4e9, seconds=0.01, peak_flops=200e12,
        peak_bytes=800e9)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_a_window_is_required():
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracecalc.window(ev)


def test_recorded_chip_trace_reduces_to_its_numbers():
    # 60 ms of b6-dyn.saturate on one TPU v5 lite (full batches of 8)
    ev = tracecalc.load_events(RECORDED)
    s = tracecalc.summarize(ev)
    assert s["devices"] == 1 and s["window_s"] == pytest.approx(0.06)
    assert s["busy_s"] == pytest.approx(0.059324761)
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-12)
    top, seconds = s["device_ops"][0]
    assert top == "%vmap_jit_knn_graph__.2 = s32[8,1024,20]"
    lo, hi = tracecalc.window(ev)
    ns, calls = tracecalc.kernel_ns(ev["devices"]["/device:TPU:0"], "knn",
                                    lo, hi)
    assert calls == 8 and ns / 1e9 == pytest.approx(seconds)
    assert 0.3 < ns / 1e9 / s["busy_s"] < 0.5
