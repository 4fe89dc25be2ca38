"""Multi-device (batch-sharded) serving tests.

The main pytest process keeps 1 device (dry-run contract), so anything
needing a real mesh runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — same idiom as
``test_distributed.py``.

Parity note: each device runs a *per-shard* program, whose fusion and
vectorization on CPU can reorder float accumulation at the last ulp on
some tasks (observed ~4e-7 on b4).  The parity matrix therefore asserts
``allclose(rtol=1e-5, atol=1e-6)`` — the documented tolerance the
benchmark's sweep also gates on — not bitwise equality.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# ----------------------------------------------------- in-process guards --
def test_compile_devices_1_falls_back_to_single_device():
    """A one-device mesh must resolve to the plain single-device runner
    path (mesh=None) — no sharding machinery on the default host."""
    from repro import gcv
    from repro.core import CompileOptions
    from repro.gnncv.tasks import build_task
    cm = gcv.compile(build_task("b6", small=True),
                     options=CompileOptions(target="fpga"), devices=1)
    assert cm.mesh is None
    assert cm.stats()["devices"] == 1


# ---------------------------------------------------------- subprocess ----
def test_sharded_parity_all_tasks_devices_1_2_4_8():
    """Per-request results served at devices=2/4/8 match devices=1 within
    the documented tolerance, across all seven tasks b1-b7."""
    out = run_sub("""
        import numpy as np
        from repro import gcv
        from repro.core import CompileOptions
        from repro.gnncv.jax_tasks import build_traced_task
        from repro.gnncv.tasks import build_task, request_inputs

        OPTS = CompileOptions(target="fpga")
        graphs = {t: build_task(t, small=True)
                  for t in ("b1", "b2", "b3-r50", "b4", "b5", "b6")}
        graphs["b7"] = build_traced_task("b7", small=True)

        def serve_all(ndev):
            eng = gcv.serve(graphs, options=OPTS, max_batch=8,
                            devices=ndev)
            reqs = []
            for task in graphs:
                for seed in range(2):
                    reqs.append(eng.submit(
                        task, **request_inputs(eng.plans[task],
                                               seed=seed)))
            assert eng.run() == len(reqs)
            assert eng.stats()["devices"] == ndev
            return reqs

        ref = serve_all(1)
        for ndev in (2, 4, 8):
            got = serve_all(ndev)
            for a, b in zip(ref, got):
                assert a.task == b.task
                for x, y in zip(a.result, b.result):
                    if np.issubdtype(np.asarray(x).dtype, np.integer):
                        assert np.array_equal(x, y), (a.task, ndev)
                    else:
                        np.testing.assert_allclose(
                            x, y, rtol=1e-5, atol=1e-6,
                            err_msg=f"{a.task} devices={ndev}")
            print(f"devices={ndev}: parity ok over {len(got)} requests")
        print("PARITY_OK")
        """)
    assert "PARITY_OK" in out


def test_sharded_engine_pipelining_pads_and_frozen_misses():
    """devices=4 engine: bucket floor at the device count, round-robin pad
    accounting, per-device in-flight queues bounded by pipeline_depth,
    and runner_misses frozen under mixed traffic after warmup."""
    out = run_sub("""
        from repro import gcv
        from repro.core import CompileOptions
        from repro.gnncv.tasks import build_task, request_inputs

        OPTS = CompileOptions(target="fpga")
        graphs = {t: build_task(t, small=True) for t in ("b4", "b6")}
        # engine guards: every bucket must shard evenly, and sharding
        # needs jitted programs
        try:
            gcv.serve(graphs, options=OPTS, max_batch=2, devices=4)
            raise SystemExit("expected divisibility AssertionError")
        except AssertionError as e:
            assert "divisible" in str(e)
        try:
            gcv.serve(graphs, options=OPTS, max_batch=8, devices=4,
                      jit=False)
            raise SystemExit("expected jit AssertionError")
        except AssertionError as e:
            assert "single-device" in str(e)

        eng = gcv.serve(graphs, options=OPTS, max_batch=8, devices=4,
                        pipeline_depth=2)
        assert eng.buckets() == [4, 8]
        warmed = eng.warmup()
        assert warmed == {(t, b) for t in graphs for b in (4, 8)}
        pre = eng.stats()["runner_misses"]

        # 5 requests -> bucket 8, 3 pads spread round-robin over devices
        for s in range(5):
            eng.submit("b4", **request_inputs(eng.plans["b4"], seed=s))
        assert eng.dispatch() == 5
        assert eng.inflight_per_device() == [1, 1, 1, 1]
        assert eng.harvest() == 5
        assert eng.inflight_per_device() == [0, 0, 0, 0]
        s = eng.stats()
        # positions 5, 6, 7 of the 8-bucket pad devices 1, 2, 3
        assert s["pad_per_device"] == [0, 1, 1, 1], s["pad_per_device"]
        assert s["padded"] == 3

        # pipelined mixed traffic: depth bounds each device queue
        for seed in range(16):
            task = ("b4", "b6")[seed % 2]
            eng.submit(task, **request_inputs(eng.plans[task], seed=seed))
        assert eng.run() == 16
        s = eng.stats()
        assert s["runner_misses"] == pre, "live traffic recompiled"
        assert sum(s["pad_per_device"]) == s["padded"]
        print("ENGINE_OK")
        """)
    assert "ENGINE_OK" in out


def test_sharded_trace_has_per_device_tracks():
    """Every dispatch/harvest emits one span per device; the Chrome export
    routes them to per-device tids with thread_name metadata."""
    out = run_sub("""
        import json
        from repro import gcv, obs
        from repro.core import CompileOptions
        from repro.gnncv.tasks import build_task, request_inputs

        OPTS = CompileOptions(target="fpga")
        graphs = {"b6": build_task("b6", small=True)}
        with gcv.trace_to("/tmp/trace_sharded.json"):
            eng = gcv.serve(graphs, options=OPTS, max_batch=4, devices=2,
                            warmup=True)
            for s in range(3):
                eng.submit("b6", **request_inputs(eng.plans["b6"],
                                                  seed=s))
            assert eng.run() == 3

        doc = json.load(open("/tmp/trace_sharded.json"))
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        disp = [e for e in evs if e["name"] == "serve.dispatch"]
        harv = [e for e in evs if e["name"] == "serve.harvest"]
        reqs = [e for e in evs if e["name"] == "request"]
        assert len(disp) == 2 and len(harv) == 2   # 1 batch x 2 devices
        assert {e["args"]["device"] for e in disp} == {0, 1}
        assert sorted(e["tid"] for e in disp) == [1000, 1001]
        # global batch identity identical on both tracks; shard split sums
        # to the bucket
        assert all(e["args"]["bucket"] == 4 and e["args"]["n"] == 3
                   and e["args"]["pad"] == 1 for e in disp)
        assert sum(e["args"]["shard_n"] + e["args"]["shard_pad"]
                   for e in disp) == 4
        assert len(reqs) == 3
        assert all(e["args"]["device"] in (0, 1) for e in reqs)
        meta = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert meta[1000] == "device 0" and meta[1001] == "device 1"
        print("TRACE_OK")
        """)
    assert "TRACE_OK" in out


def test_sharded_residency_replicates_per_device():
    """Weights upload once per device: the replicated store reports
    ndev x the single-device footprint, and stats() splits it."""
    out = run_sub("""
        from repro import gcv
        from repro.core import CompileOptions
        from repro.gnncv.tasks import build_task

        OPTS = CompileOptions(target="fpga")
        g = build_task("b1", small=True)
        one = gcv.compile(g, options=OPTS, devices=1)
        four = gcv.compile(g, options=OPTS, devices=4)
        one.batched(4); four.batched(4)
        s1, s4 = one.stats(), four.stats()
        assert s4["devices"] == 4
        assert s4["resident_bytes_per_device"] == s1["resident_bytes"]
        assert s4["resident_bytes"] == 4 * s1["resident_bytes"]
        run = four.batched(4)
        assert run.mesh is not None and run.mesh.size == 4
        print("RESIDENCY_OK")
        """)
    assert "RESIDENCY_OK" in out
