"""CPU tests of the chip benchmark's harness: traffic, the numbers a run
reports, the shape of its result line, and finding cells by name."""
from __future__ import annotations

import json
import math
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench_tiny
import harness
import loadgen
import workcount

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def _traffic(name):
    return harness.load_json(HERE / "traffic" / f"{name}.json")


def _arrivals(name):
    return harness.load_module(HERE / "arrivals" / f"{name}.py")


def test_periodic_schedule_is_each_streams_period_from_its_phase():
    t = _traffic("streams8x30hz")
    due = _arrivals("periodic").due_times(t, 10.0)
    assert np.all(np.diff(due) >= 0)
    assert due.min() >= 0 and due.max() < 10.0
    period = 1 / t["rate_hz"]
    want = sorted(p / 1e3 + n * period for p in t["phases_ms"]
                  for n in range(400) if p / 1e3 + n * period < 10.0)
    np.testing.assert_allclose(due, want, rtol=0, atol=1e-12)
    # 8 sensors at 30 Hz: 240 frames a second
    assert len(due) == pytest.approx(240 * 10, abs=8)


def test_periodic_schedule_rejects_a_phase_outside_the_period():
    t = dict(_traffic("streams8x30hz"), phases_ms=[40.0] * 8)
    with pytest.raises(ValueError):
        _arrivals("periodic").due_times(t, 1.0)


@pytest.mark.parametrize("kind,mix", [
    ("poisson", {"rate_hz": 200.0, "arrival_seed": 5}),
    ("bursty", {"rate_hz": 100.0, "burst_hz": 1000.0, "burst_ms": 50.0,
                "every_ms": 500.0, "arrival_seed": 5})])
def test_random_arrivals_come_from_the_mix_alone(kind, mix):
    due = _arrivals(kind).due_times(mix, 20.0)
    np.testing.assert_array_equal(due, _arrivals(kind).due_times(mix, 20.0))
    assert np.all(np.diff(due) >= 0) and 0 <= due.min() and due.max() < 20
    if kind == "poisson":
        assert len(due) == pytest.approx(200 * 20, rel=0.05)
    else:      # 40 bursts of 50 ms at 1000/s, 450 ms at 100/s between
        assert len(due) == pytest.approx(40 * (50 + 45), rel=0.1)
        in_burst = (due % 0.5) < 0.05
        assert in_burst.sum() == pytest.approx(2000, rel=0.1)
    other = dict(mix, arrival_seed=6)
    assert not np.array_equal(due, _arrivals(kind).due_times(other, 20.0))


def test_pool_and_order_follow_the_seed_alone():
    inputs = {"points": {"shape": [16, 3], "fill": "normal"},
              "mask": {"shape": [16], "fill": "ones"}}
    big = 2**31 + 17
    a = loadgen.make_pool(inputs, 8, big)
    b = loadgen.make_pool(inputs, 8, big)
    c = loadgen.make_pool(inputs, 8, big + 1)
    assert a["points"].shape == (8, 16, 3) and a["points"].dtype == np.float32
    np.testing.assert_array_equal(a["points"], b["points"])
    assert not np.array_equal(a["points"], c["points"])
    assert (a["mask"] == 1).all()
    np.testing.assert_array_equal(loadgen.pool_order(8, 100, big),
                                  loadgen.pool_order(8, 100, big))
    # every seed offers the same arrivals
    t = _traffic("streams8x30hz")
    np.testing.assert_array_equal(_arrivals("periodic").due_times(t, 2.0),
                                  _arrivals("periodic").due_times(t, 2.0))


def test_task_shares_come_from_the_mix_not_the_seed():
    mix = {"tasks": {"a": 3, "b": 1}, "arrival_seed": 1}
    seq = loadgen.task_sequence(mix, ["a", "b"], 4000)
    np.testing.assert_array_equal(seq, loadgen.task_sequence(
        mix, ["a", "b"], 4000))
    assert (seq == 0).mean() == pytest.approx(0.75, abs=0.03)
    assert (loadgen.task_sequence({}, ["only"], 5) == 0).all()
    with pytest.raises(ValueError):
        loadgen.task_sequence({"tasks": {"c": 1}}, ["a", "b"], 5)


def test_warm_buckets_cover_what_each_mix_can_dispatch():
    closed = _arrivals("closed")
    assert closed.warm(_traffic("closed32"), [1, 2, 4, 8], 2) == [8]
    assert closed.warm({"clients": 128}, [4, 8, 16, 32], 2) == [32]
    assert closed.warm({"clients": 12}, [1, 2, 4, 8], 2) == [1, 2, 4, 8]
    # an open-loop process has no ``warm``: every bucket is warmed
    assert not hasattr(_arrivals("periodic"), "warm")


class _Req:
    def __init__(self):
        self.done, self.result = False, None


class _Engine:
    """Answers up to ``per_poll`` of the oldest requests per poll and
    records how many were outstanding when it was polled."""

    def __init__(self, per_poll):
        self.per_poll, self.live, self.seen = per_poll, [], []

    def submit(self):
        r = _Req()
        self.live.append(r)
        return r

    def poll(self, draining=False):
        self.seen.append(len(self.live))
        for r in self.live[:self.per_poll]:
            r.done, r.result = True, (0,)
        self.live = self.live[self.per_poll:]
        return 0, 0


def test_closed_loop_keeps_every_client_busy():
    eng = _Engine(per_poll=8)

    def send(t_due):
        now = harness.clock()
        return harness.Rec(eng.submit(), "t", 0, now, now)

    closed = _arrivals("closed")
    done = harness.Answers({"t": 4}, seed=1)
    loop = harness.Loop(eng, send, harness.clock(), 0.05, None, done)
    live = closed.drive(loop, {"clients": 32})
    assert eng.seen and set(eng.seen) == {32}
    assert len(live) == 32 and len(done.lat) >= 8
    # a bounded, seeded sample of the answers is all that is kept
    assert len(done.kept["t"]) == 4 and done.n["t"] == len(done.lat)


def _answers(pairs):
    """``Answers`` of requests given as (due, answered) times."""
    done = harness.Answers({"t": 8}, seed=3)
    for t_due, t_ans in pairs:
        r = harness.Rec(_Req(), "t", 0, t_due, t_due)
        r.req.result, r.t_ans = (0,), t_ans
        done.add(r)
    return done


def test_rate_counts_all_answers_back_by_the_close_over_the_window():
    done = _answers([(0.0, 0.1 * i) for i in range(1, 11)]   # 0.1 .. 1.0
                    + [(0.9, 1.2)])                           # after close
    v = harness.end_to_end(done.lat + [math.inf], done.t_ans, t_close=1.0,
                           window_s=1.0, setup_s=3.0)
    assert v["throughput_rps"] == pytest.approx(10.0)
    assert v["setup_s"] == 3.0


def test_p95_is_over_all_requests_from_their_due_time():
    # 100 requests due every 10 ms; each answered 5 ms after it was due,
    # except the last six, answered 100 ms late
    done = _answers([(0.01 * i, 0.01 * i + (0.1 if i >= 94 else 0.005))
                     for i in range(100)])
    v = harness.end_to_end(done.lat, done.t_ans, t_close=2.0, window_s=1.0,
                           setup_s=0.0)
    assert v["p50_ms"] == pytest.approx(5.0)
    assert v["p95_ms"] == pytest.approx(100.0)
    # a request never answered counts as infinitely late
    v = harness.end_to_end(done.lat[6:] + [math.inf] * 6, done.t_ans[6:],
                           t_close=2.0, window_s=1.0, setup_s=0.0)
    assert v["p95_ms"] == math.inf


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        workcount.peaks("TPU v99 imaginary")
    p = workcount.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_knn_work_counts_pairs_and_bytes():
    ops, nbytes = workcount.knn_work(n=1024, d=3, k=20)
    assert ops == 1024 * 1024 * 10
    assert nbytes == 4 * (1024 * 3 + 1024 + 1024 * 20)


def _args(workload, seconds=1.0, trace=0, seed=2**31 + 99):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.sat", {"throughput_rps", "setup_s"}),
    ("tiny.streams", {"p50_ms", "p95_ms", "setup_s"})])
def test_run_line_holds_the_contract_keys_and_the_cells_metrics(
        tmp_path, cell, e2e):
    root, bench = chipbench_tiny.make(tmp_path)
    with chipbench_tiny.restore_jax():
        out = harness.run(_args(cell), bench, repo=root,
                          here=root / "bench", require_tpu=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["checks"]["median_rel_err"]["limit"] == \
        chipbench_tiny.TINY_LIMIT
    assert list(out["checks"]) == ["median_rel_err", "misplaced",
                                   "failed"]
    json.dumps(out)


def test_run_py_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "dgcnn.saturate", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cells_configs_mixes_and_metrics_are_found_by_name(tmp_path):
    root, bench = chipbench_tiny.make(tmp_path)
    b = root / "bench"
    cfg = dict(chipbench_tiny.CONFIG, name="newcfg")
    (b / "configs" / "newcfg.json").write_text(json.dumps(cfg))
    (b / "traffic" / "newmix.json").write_text(json.dumps(
        {"arrivals": "every_50ms", "pool": 2}))
    (b / "arrivals" / "every_50ms.py").write_text(
        "def drive(loop, mix):\n"
        "    return loop.open([0.05 * i for i in range(20)])\n")
    (b / "metrics" / "new_metric.tput.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.answered\n")
    bench["configs"].append({"name": "newcfg", "source": "test",
                             "file": "bench/configs/newcfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "newcfg.x", "config": "newcfg",
                               "traffic": "newmix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "throughput_rps":
            m["workloads"].append("newcfg.x")
    bench["per_layer"].append({"name": "new_metric.tput", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving",
                               "moves": "throughput_rps"})
    ns = harness.resolve(bench, "newcfg.x", root, b)
    assert ns.arrivals == b / "arrivals" / "every_50ms.py"
    assert list(ns.tasks) == ["newcfg"]
    assert ns.tasks["newcfg"]["reference_file"] == b / "configs" / "dgcnn.py"
    assert ns.tasks["newcfg"]["served_file"] == b / "models" / "dgcnn.py"
    assert [m["name"] for m in ns.e2e] == ["throughput_rps", "setup_s"]
    # a metric without a workloads key goes to every cell that reports
    # the end-to-end metric it moves
    assert "new_metric.tput" in ns.readers
    reader = harness.load_module(ns.readers["new_metric.tput"])
    assert reader.read(types.SimpleNamespace(answered=4)) == 8.0
    streams = harness.resolve(bench, "tiny.streams", root, b)
    assert "new_metric.tput" not in streams.readers
    # and the new cell runs through its new arrival process
    with chipbench_tiny.restore_jax():
        out = harness.run(_args("newcfg.x", seconds=1.0), bench, repo=root,
                          here=b, require_tpu=False)
    assert out["correct"] is True and out["attempted"] == 20


def test_a_configuration_of_several_tasks_serves_and_checks_each(tmp_path):
    root, bench = chipbench_tiny.make(tmp_path)
    b = root / "bench"
    small = dict(chipbench_tiny.CONFIG,
                 sizes=dict(chipbench_tiny.CONFIG["sizes"], n_points=32),
                 inputs={"points": {"shape": [32, 3], "fill": "normal"}})
    two = {"name": "two", "serve": chipbench_tiny.CONFIG["serve"],
           "tasks": {"big": chipbench_tiny.CONFIG, "small": small}}
    (b / "configs" / "two.json").write_text(json.dumps(two))
    (b / "traffic" / "skewed.json").write_text(json.dumps(
        {"arrivals": "closed", "clients": 16, "pool": 8,
         "tasks": {"big": 3, "small": 1}, "arrival_seed": 4}))
    bench["configs"].append({"name": "two", "source": "test",
                             "file": "bench/configs/two.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "two.skewed", "config": "two",
                               "traffic": "skewed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "throughput_rps":
            m["workloads"].append("two.skewed")
    with chipbench_tiny.restore_jax():
        out = harness.run(_args("two.skewed", seconds=1.0), bench,
                          repo=root, here=b, require_tpu=False)
    assert out["correct"] is True
    assert list(out["checks"]) == [
        "big.median_rel_err", "big.misplaced", "small.median_rel_err",
        "small.misplaced", "failed"]


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_json(REPO / "BENCHMARK.json")
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in bench["workloads"]:
        ns = harness.resolve(bench, w["name"])
        assert ns.arrivals.is_file()
        for spec in ns.tasks.values():
            assert spec["served_file"].is_file()
            assert spec["reference_file"].is_file()
        assert all(p.is_file() for p in ns.readers.values())
        assert ns.e2e and ns.per_layer
        assert "setup_s" in {m["name"] for m in ns.e2e}
