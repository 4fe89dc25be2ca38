"""Throughput of the GNN-CV serving engine across three serving modes over
a mixed request stream of *builder* models (b1/b4/b6) and *traced*
user-defined JAX models (b2/b4/b7 via ``gcv.compile``'s tracing path):

  one_at_a_time     the seed serving story: every request dispatches its
                    own jit'd per-sample runner;
  engine_baseline   the PR-3 engine: synchronous step (dispatch + block),
                    legacy per-call weight staging (``residency=False``);
  engine_kernels_xla  the pipelined engine with every op forced onto its
                    XLA realization (``kernels="xla"``) — the prior
                    all-XLA configuration, the reference the kernel
                    selector must not regress;
  engine_pipelined  the full hot path with per-op kernel selection
                    (``kernels="auto"``): device-resident weights threaded
                    through jit as arguments, ``warmup()`` AOT-compiling
                    every (task, bucket) runner before traffic, and
                    pipelined dispatch/harvest overlapping host batching
                    with device execution.

All engine modes are fully warmed before timing, so the delta is pure
steady-state serving.  The run asserts ``runner_misses`` stays frozen
during pipelined traffic (no live request ever compiles) and writes the
machine-readable ``BENCH_serve_gnncv.json`` perf record (p50/p95 request
sojourn, req/s per mode, per-task residency footprint — including the b7
ViG baseline the paper has no latency target for).  A final *traced* pass
re-runs compile -> warmup -> serving under the tracer and emits
``TRACE_serve_gnncv.json`` (Chrome/Perfetto trace-event JSON: compiler
passes, per-(task, bucket) warmups, per-batch dispatch/harvest, one span
per request) — traced outside the timed passes, so telemetry cost never
touches the reported numbers.

After the mode comparison, an **open-loop Poisson pass** measures
continuous batching for deadline goodput: the same mixed stream arrives
on a Poisson schedule at ~1.25x the closed-loop capacity just measured,
every request carrying an SLO deadline (3x the closed-loop p95 sojourn),
and the SLO-aware scheduler (service-corrected EDF, shedding, adaptive
pipeline depth) is compared against the static FIFO baseline on the
*identical* arrival schedule — goodput-under-SLO, raw req/s, p50/p95
sojourn and deadline-miss rate per policy land in the JSON
(``goodput_under_slo``/``deadline_miss_rate`` are top-level fields, gated
by CI).

Then a **batch-sharded device sweep**
(``--devices 1,2,4,8``) serves the same stream through
``gcv.serve(..., devices=N)`` — batch axis sharded over a 1-D data mesh,
weights replicated per device — recording req/s, p50/p95 sojourn, pad
overhead per device count, and per-request parity against the
single-device engine as a ``devices`` axis in the JSON.  On a CPU host,
force devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the curve then measures sharding overhead, not speedup — one physical
core).  Counts the host cannot satisfy are skipped with a printed note.

    PYTHONPATH=src python -m benchmarks.serve_gnncv [--requests N]
                                                    [--max-batch B]
                                                    [--repeats R]
                                                    [--devices 1,2,4,8]
                                                    [--quick]

Each mode is timed over R passes of the same stream and the best pass is
reported — steady-state serving throughput, robust to noisy hosts.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import gcv, obs
from repro.core import CompileOptions
from repro.core.runtime.residency import plan_param_bytes
from repro.gnncv.jax_tasks import (TRACED_SMALL_CONFIGS, TRACED_TASKS,
                                   build_traced_task)
from repro.gnncv.tasks import SMALL_CONFIGS, build_task, request_inputs
from repro.serve import GNNCVServeEngine

from benchmarks.common import emit, percentile_ms, write_bench_json

BUILDER_MIX = ("b1", "b4", "b6")
TRACED_MIX = ("b2", "b4", "b7")             # served as "<task>@traced"
MIX = BUILDER_MIX + tuple(f"{t}@traced" for t in TRACED_MIX)

# Variable-topology pass: b6-dyn point clouds served over these graph-size
# buckets, mixed with dynamic-graph b7 ViG requests through one engine.
DYN_SIZES = [32, 64]


def b6dyn_factory(n_points):
    cfg = dict(TRACED_SMALL_CONFIGS["b6-dyn"])
    cfg["n_points"] = n_points
    return TRACED_TASKS["b6-dyn"](**cfg)


def dyn_request(n, seed=0):
    rng = np.random.default_rng(seed)
    return dict(points=np.asarray(rng.standard_normal((n, 3)), np.float32),
                mask=np.ones(n, np.float32))


def make_stream(plans, n):
    return [(MIX[i % len(MIX)], request_inputs(plans[MIX[i % len(MIX)]],
                                               seed=i))
            for i in range(n)]


def poisson_stream(plans, n, rate_per_s, seed=7):
    """Open-loop Poisson arrivals over the task mix: exponential
    inter-arrival times at ``rate_per_s``, independent of service (the
    generator keeps its schedule even when the engine falls behind —
    the honest way to load a server past capacity)."""
    rng = np.random.default_rng(seed)
    t, arrivals = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.0 / rate_per_s))
        task = MIX[i % len(MIX)]
        arrivals.append((t, task, request_inputs(plans[task], seed=i)))
    return arrivals


def bench_open_loop(graphs, options, plans, max_batch, *, requests,
                    repeats, closed_req_per_s, closed_p95_ms,
                    load_factor=1.25):
    """SLO-aware continuous batching vs the static FIFO baseline at equal
    offered load: both engines replay the *same* Poisson arrival schedule
    (rate = ``load_factor`` x the measured closed-loop capacity, so the
    server runs hot) under the same per-request deadline
    (3 x closed-loop p95 sojourn, floored at 20 ms).  The FIFO engine is
    the pre-stream configuration — arrival order, fixed pipeline depth,
    no shedding; the SLO engine schedules by service-corrected slack,
    sheds hopeless work and adapts its depth.  Goodput-under-SLO
    (deadline-met completions per second) is the headline; raw req/s,
    p50/p95 sojourn, miss rate and shed counts are recorded per policy.
    Best-of-``repeats`` per policy by goodput (fresh engine per pass —
    runner caches stay warm, engine state does not)."""
    rate = max(1.0, closed_req_per_s * load_factor)
    slo_ms = max(20.0, 3.0 * closed_p95_ms)
    arrivals = poisson_stream(plans, requests, rate)
    span_s = arrivals[-1][0]
    records = {}
    for policy in ("fifo", "slo"):
        best = None
        for _ in range(repeats):
            eng = gcv.serve(graphs, options=options, max_batch=max_batch,
                            pipeline_depth=2, residency=True,
                            slo_ms=slo_ms, scheduler=policy,
                            max_pipeline_depth=(2 if policy == "fifo"
                                                else 8))
            eng.warmup()
            reqs = eng.stream(arrivals, max_wall_s=120.0)
            s = eng.stats()
            wall = max(r.t_done for r in reqs) - min(r.t_submit
                                                     for r in reqs)
            rec = {
                "scheduler": policy,
                "goodput_under_slo": round(s["goodput"] / wall, 2),
                "req_per_s": round(s["completed"] / wall, 2),
                "goodput_fraction": round(s["goodput"] / len(reqs), 4),
                "deadline_miss_rate": round(s["deadline_miss_rate"] or 0.0,
                                            4),
                "p50_sojourn_ms": round(s["p50_sojourn_ms"] or 0.0, 3),
                "p95_sojourn_ms": round(s["p95_sojourn_ms"] or 0.0, 3),
                "shed": s["shed"],
                "expired_at_submit": s["expired_at_submit"],
                "dispatches": s["steps"],
                "final_pipeline_depth": s["pipeline_depth"],
            }
            if best is None or rec["goodput_under_slo"] \
                    > best["goodput_under_slo"]:
                best = rec
        records[policy] = best
    emit([[r["scheduler"], r["goodput_under_slo"], r["req_per_s"],
           r["goodput_fraction"], r["deadline_miss_rate"],
           r["p50_sojourn_ms"], r["p95_sojourn_ms"], r["shed"],
           r["final_pipeline_depth"]]
          for r in records.values()],
         ["scheduler", "goodput/s", "req_per_s", "goodput_frac",
          "miss_rate", "p50_ms", "p95_ms", "shed", "depth"])
    ratio = (records["slo"]["goodput_under_slo"]
             / max(records["fifo"]["goodput_under_slo"], 1e-9))
    print(f"open loop @ {rate:.0f} req/s offered "
          f"(~{load_factor:.2f}x capacity, slo {slo_ms:.1f} ms, "
          f"{span_s * 1e3:.0f} ms arrival span): "
          f"slo-aware vs fifo goodput {ratio:.2f}x")
    return {"offered_req_per_s": round(rate, 2),
            "load_factor": load_factor, "slo_ms": round(slo_ms, 3),
            "requests": requests, "schedulers": records,
            "slo_vs_fifo_goodput": round(ratio, 3)}


def bench_dynamic(options, max_batch, requests, repeats):
    """Variable-topology serving: mixed-size b6-dyn point clouds (graph
    rebuilt per request by the compiled ``knn_graph`` op, node counts
    bucketed to ``DYN_SIZES``) interleaved with dynamic-graph b7 ViG
    requests, all through one warmed engine.  Asserts ``runner_misses``
    stays frozen (one compile per graph bucket x batch bucket, all paid
    by warmup) and that Step 4b recorded a KNN-kernel decision for every
    dynamic plan; records req/s overall and per dynamic task plus the
    per-graph-bucket pad-node accounting."""
    models = {"b6-dyn": b6dyn_factory,
              "b7-dyn": build_traced_task("b7-dyn", small=True)}
    eng = gcv.serve(models, options=options, max_batch=max_batch,
                    pipeline_depth=2, residency=True,
                    graph_buckets={"b6-dyn": DYN_SIZES})
    eng.warmup()
    pre = eng.stats()["runner_misses"]
    knn_kernels = {}
    for task, plan in eng.plans.items():
        for op, c in plan.meta.get("kernel_choices", {}).items():
            if c.get("kind") == "knn_graph":
                knn_kernels[f"{task}.{op}"] = c["kernel"]
    assert knn_kernels, "no knn_graph kernel decision in any dynamic plan"
    rng = np.random.default_rng(13)
    stream = []
    for i in range(requests):
        if i % 2:
            stream.append(("b7-dyn",
                           request_inputs(eng.plans["b7-dyn"], seed=i)))
        else:
            n = int(rng.integers(8, DYN_SIZES[-1] + 1))
            stream.append(("b6-dyn", dyn_request(n, seed=i)))
    best, best_lats = float("inf"), []
    for _ in range(repeats):
        reqs = [eng.submit(t, **inp) for t, inp in stream]
        t0 = obs.now()
        served = eng.run()
        dt = obs.now() - t0
        assert served == len(stream)
        if dt < best:
            best, best_lats = dt, [r.t_done - t0 for r in reqs]
    post = eng.stats()
    assert post["runner_misses"] == pre, \
        "a live dynamic request paid a runner compile after warmup()"
    n_b7 = sum(1 for t, _ in stream if t == "b7-dyn")
    gb = post["graph_buckets"]["b6-dyn"]
    rec = {
        "graph_buckets": {"b6-dyn": list(DYN_SIZES)},
        "requests": requests,
        "req_per_s": round(requests / best, 2),
        "dynamic_b7_req_per_s": round(n_b7 / best, 2),
        "dynamic_b6_req_per_s": round((requests - n_b7) / best, 2),
        "p50_ms": round(percentile_ms(best_lats, 50), 3),
        "p95_ms": round(percentile_ms(best_lats, 95), 3),
        "per_graph_bucket": {str(g): gb[g] for g in DYN_SIZES},
        "knn_kernels": knn_kernels,
        "runner_misses_frozen": True,
    }
    emit([[t, rec[f"dynamic_{k}_req_per_s"]]
          for t, k in (("b6-dyn", "b6"), ("b7-dyn", "b7"))]
         + [["dynamic total", rec["req_per_s"]]],
         ["dynamic task", "req_per_s"])
    pads = {g: v["pad_nodes"] for g, v in rec["per_graph_bucket"].items()}
    print(f"variable topology: {requests} requests over graph buckets "
          f"{DYN_SIZES}, pad nodes {pads}, knn kernels {knn_kernels}")
    return rec


class PR3BaselineEngine(GNNCVServeEngine):
    """Faithful reconstruction of the PR-3 serving hot path, so the delta
    this PR reports is against what actually shipped: synchronous steps
    (``pipeline_depth=1``), per-call weight staging (``residency=False``),
    device-side batch stacking (N per-sample device puts + ``jnp.stack``)
    and per-request output slices at harvest."""

    def __init__(self, graphs, **kw):
        super().__init__(graphs, pipeline_depth=1, residency=False, **kw)

    def _stack(self, samples, info):
        keys = samples[0].keys()
        return {k: jnp.stack([jnp.asarray(s[k]) for s in samples])
                for k in keys}

    def harvest(self) -> int:
        if not self._inflight:
            return 0
        reqs, outs, _ = self._inflight.popleft()
        for dq in self._dev_inflight:
            if dq:
                dq.popleft()
        for i, req in enumerate(reqs):
            req.result = tuple(np.asarray(o[i]) for o in outs)
            req.done = True
            req.t_done = obs.now()
        self._c_completed.inc(len(reqs))
        return len(reqs)


def bench_one_at_a_time(graphs, options, stream, repeats):
    models = {t: gcv.compile(graphs[t], options=options) for t in graphs}
    for task, inputs in stream[:len(MIX)]:          # warm compiles
        models[task].run(**inputs)
    best, best_lats = float("inf"), []
    for _ in range(repeats):
        t0 = obs.now()
        lats = []
        for task, inputs in stream:
            # materialize each response, like a server answering a request
            _ = [np.asarray(o) for o in models[task].run(**inputs)]
            lats.append(obs.now() - t0)
        dt = obs.now() - t0
        if dt < best:
            best, best_lats = dt, lats
    return best, best_lats


def bench_engine(graphs, options, stream, max_batch, *, pipelined: bool,
                 repeats: int):
    """One engine mode, warmed before timing, best of ``repeats`` passes
    over the stream (steady-state serving on a possibly noisy host).
    ``pipelined=False`` is the PR-3 baseline: synchronous steps, per-call
    weight staging."""
    kw = dict(options=options, max_batch=max_batch)
    if pipelined:
        eng = gcv.serve(graphs, pipeline_depth=2, residency=True, **kw)
        warmed = eng.warmup()                       # AOT: trace+compile now
        assert warmed == {(t, b) for t in graphs for b in eng.buckets()}, \
            "warmup left (task, bucket) runners uncompiled"
    else:
        eng = PR3BaselineEngine(graphs, **kw)
        warm = PR3BaselineEngine(graphs, **kw)
        for bucket in eng.buckets():                # warm by traffic
            for task in MIX:
                for s in range(bucket):
                    warm.submit(task,
                                **request_inputs(eng.plans[task], seed=s))
            warm.run()
    pre = eng.stats()
    best, best_lats, best_dispatches = float("inf"), [], 0
    for _ in range(repeats):
        steps_before = eng.steps
        reqs = [eng.submit(task, **inputs) for task, inputs in stream]
        t0 = obs.now()
        served = eng.run()
        dt = obs.now() - t0
        assert served == len(stream)
        if dt < best:
            best = dt
            best_lats = [r.t_done - t0 for r in reqs]
            best_dispatches = eng.steps - steps_before
    post = eng.stats()
    if pipelined:
        assert post["runner_misses"] == pre["runner_misses"], \
            "a live request paid a runner compile after warmup()"
    return best, best_lats, best_dispatches, post


def bench_kernel_modes(graphs, options, stream, max_batch, repeats):
    """Pipelined engines for kernels="xla" and kernels="auto", warmed
    together and timed in *alternating* passes — on CPU the two modes
    compile identical dispatch, so timing them in separate back-to-back
    blocks would just measure which block got the warmer host slot."""
    engines = {}
    for mode in ("xla", "auto"):
        opts = dataclasses.replace(options, kernels=mode)
        eng = gcv.serve(graphs, pipeline_depth=2, residency=True,
                        options=opts, max_batch=max_batch)
        warmed = eng.warmup()
        assert warmed == {(t, b) for t in graphs for b in eng.buckets()}, \
            "warmup left (task, bucket) runners uncompiled"
        engines[mode] = eng
    pre = {m: e.stats()["runner_misses"] for m, e in engines.items()}
    best = {m: (float("inf"), [], 0) for m in engines}
    for _ in range(repeats):
        for mode, eng in engines.items():
            steps_before = eng.steps
            reqs = [eng.submit(task, **inputs) for task, inputs in stream]
            t0 = obs.now()
            served = eng.run()
            dt = obs.now() - t0
            assert served == len(stream)
            if dt < best[mode][0]:
                best[mode] = (dt, [r.t_done - t0 for r in reqs],
                              eng.steps - steps_before)
    for mode, eng in engines.items():
        assert eng.stats()["runner_misses"] == pre[mode], \
            "a live request paid a runner compile after warmup()"
    return best, {m: e.stats() for m, e in engines.items()}


def bench_devices(graphs, options, stream, max_batch, counts, repeats):
    """Batch-sharded serving sweep: one pipelined engine per device count,
    all sharing ONE max_batch (``max(max_batch, max(counts))``) so every
    engine sees the same request stream and comparable buckets.  Counts
    the host cannot satisfy are skipped with a printed note — never
    silently served at a smaller mesh.  Each count's per-request results
    are compared against the devices=1 engine's; the per-shard program can
    reorder float accumulation at the last ulp on some tasks, so parity is
    a recorded max|diff| under a 1e-5 gate rather than a bitwise claim.
    """
    import jax
    avail = len(jax.devices())
    usable = [c for c in counts if c <= avail]
    for c in counts:
        if c not in usable:
            print(f"devices={c}: skipped, host exposes only {avail} "
                  f"device(s) (set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count=N to force)")
    if not usable:
        return [], avail
    mb = max(max_batch, max(usable))
    records, ref_results = [], None
    for ndev in usable:
        eng = gcv.serve(graphs, pipeline_depth=2, residency=True,
                        options=options, max_batch=mb, devices=ndev)
        warmed = eng.warmup()
        assert warmed == {(t, b) for t in graphs for b in eng.buckets()}, \
            "warmup left (task, bucket) runners uncompiled"
        pre = eng.stats()
        best, best_lats, results = float("inf"), [], None
        for _ in range(repeats):
            reqs = [eng.submit(task, **inputs) for task, inputs in stream]
            t0 = obs.now()
            served = eng.run()
            dt = obs.now() - t0
            assert served == len(stream)
            results = [r.result for r in reqs]
            if dt < best:
                best, best_lats = dt, [r.t_done - t0 for r in reqs]
        post = eng.stats()
        assert post["runner_misses"] == pre["runner_misses"], \
            "a live request paid a runner compile after warmup()"
        parity = None
        if ref_results is None:
            ref_results = results
        else:
            parity = 0.0
            for want, got in zip(ref_results, results):
                for a, b in zip(want, got):
                    parity = max(parity, float(np.max(np.abs(
                        np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)))))
            assert parity < 1e-5, \
                f"devices={ndev} diverged from devices=1 by {parity:.3e}"
        n = len(stream)
        records.append({
            "devices": ndev, "max_batch": mb,
            "wall_ms": round(best * 1e3, 2),
            "req_per_s": round(n / best, 2),
            "p50_ms": round(percentile_ms(best_lats, 50), 3),
            "p95_ms": round(percentile_ms(best_lats, 95), 3),
            "padded": post["padded"],
            "pad_per_device": post["pad_per_device"],
            "parity_max_abs_diff_vs_1dev": (
                None if parity is None else float(f"{parity:.3e}")),
        })
    return records, avail


def mode_record(name, wall_s, lats, n, extra=None):
    return {"mode": name, "wall_ms": round(wall_s * 1e3, 2),
            "req_per_s": round(n / wall_s, 2),
            "p50_ms": round(percentile_ms(lats, 50), 3),
            "p95_ms": round(percentile_ms(lats, 95), 3),
            **(extra or {})}


def trace_pass(graphs, options, stream, max_batch, path, devices=1):
    """One fully-traced serve lifecycle, emitted as a Chrome-trace
    artifact: compile (telemetry options force a fresh plan-cache entry,
    so all six passes run inside the tracer), AOT warmup of every (task,
    bucket), then a short request stream with per-batch dispatch/harvest
    and per-request spans.  With ``devices > 1`` the engine serves batch-
    sharded and every dispatch/harvest/request span carries its device —
    the exporter routes them to per-device Perfetto tracks.  Runs after
    the timed passes — the reported numbers never include tracer
    overhead."""
    opts = dataclasses.replace(options, telemetry=True)
    with gcv.trace_to(path):
        eng = gcv.serve(graphs, pipeline_depth=2, residency=True,
                        options=opts, max_batch=max(max_batch, devices),
                        devices=devices, warmup=True)
        for task, inputs in stream:
            eng.submit(task, **inputs)
        eng.run()
        # variable-topology tail: a graph-size-bucketed engine serves a
        # few mixed-size point clouds inside the same trace, so the
        # artifact carries ``graph.build`` spans (bucket routing + node
        # padding) next to the dispatch/harvest lifecycle
        dyn = gcv.serve({"b6-dyn": b6dyn_factory}, options=opts,
                        graph_buckets={"b6-dyn": DYN_SIZES},
                        max_batch=max(2, devices), devices=devices,
                        pipeline_depth=2, residency=True, warmup=True)
        for i, n in enumerate((20, 32, 48, DYN_SIZES[-1])):
            dyn.submit("b6-dyn", **dyn_request(n, seed=i))
        dyn.run()
    s = eng.stats()
    print(f"traced pass ({s['devices']} device(s)): "
          f"{s['completed']} requests (+{dyn.stats()['completed']} "
          f"variable-topology), "
          f"p50 {s['p50_sojourn_ms']:.2f} ms, "
          f"p95 {s['p95_sojourn_ms']:.2f} ms -> {path}")


def run(requests: int = 96, max_batch: int = 8, repeats: int = 5,
        trace: str = "TRACE_serve_gnncv.json",
        devices: tuple = (1, 2, 4, 8)):
    options = CompileOptions(target="fpga")
    all_graphs = {t: build_task(t, small=True) for t in sorted(SMALL_CONFIGS)}
    graphs = {t: all_graphs[t] for t in BUILDER_MIX}
    # traced user-defined JAX models registered *next to* builder models —
    # the engine (and the plan/runner cache) cannot tell them apart.  b7
    # (ViG) exists only through the tracing frontend.
    graphs.update({f"{t}@traced": build_traced_task(t, small=True)
                   for t in TRACED_MIX})
    plans = {t: gcv.compile(g, options=options).plan
             for t, g in graphs.items()}
    stream = make_stream(plans, requests)

    loop_s, loop_lats = bench_one_at_a_time(graphs, options, stream,
                                            repeats)
    base_s, base_lats, base_disp, base_stats = bench_engine(
        graphs, options, stream, max_batch, pipelined=False,
        repeats=repeats)
    # the prior all-XLA config vs kernels="auto" — same pipelined engine,
    # only Step-4b selection differs, so auto_vs_xla isolates the kernel
    # selector's effect on the hot path
    kern_best, kern_stats = bench_kernel_modes(
        graphs, options, stream, max_batch, repeats)
    xla_s, xla_lats, xla_disp = kern_best["xla"]
    pipe_s, pipe_lats, pipe_disp = kern_best["auto"]
    pipe_stats = kern_stats["auto"]

    modes = [
        mode_record("one_at_a_time", loop_s, loop_lats, requests),
        mode_record("engine_baseline", base_s, base_lats, requests,
                    {"dispatches": base_disp}),
        mode_record("engine_kernels_xla", xla_s, xla_lats, requests,
                    {"dispatches": xla_disp, "kernels": "xla"}),
        mode_record("engine_pipelined", pipe_s, pipe_lats, requests,
                    {"dispatches": pipe_disp, "kernels": options.kernels,
                     "warmed": pipe_stats["warmed"]}),
    ]
    emit([[m["mode"], m["wall_ms"], m["req_per_s"], m["p50_ms"],
           m["p95_ms"]] for m in modes],
         ["mode", "wall_ms", "req_per_s", "p50_ms", "p95_ms"])
    # cache effectiveness (cumulative since process start): misses are the
    # warmup compiles (one per task x bucket x mode); every timed dispatch
    # is a hit
    emit([[pipe_stats["runner_hits"], pipe_stats["runner_misses"],
           pipe_stats["plan_hits"], pipe_stats["plan_misses"]]],
         ["runner_hits", "runner_misses", "plan_hits", "plan_misses"])

    rows, task_records = [], {}
    for task, g in {**all_graphs,
                    **{t: graphs[t] for t in MIX if "@" in t}}.items():
        plan = gcv.compile(g, options=options).plan
        freed = plan.peak_live_bytes(free_dead=True)
        kept = plan.peak_live_bytes(free_dead=False)
        resident = plan_param_bytes(plan)
        rows.append([task, plan.meta["frontend"], freed, kept,
                     f"{kept / freed:.2f}x", resident])
        task_records[task] = {"frontend": plan.meta["frontend"],
                              "peak_live_bytes_freed": freed,
                              "peak_live_bytes_kept": kept,
                              "resident_param_bytes": resident,
                              "kernel_counts": plan.kernel_counts()}
    emit(rows, ["task", "frontend", "peak_live_bytes_freed",
                "peak_live_bytes_kept", "reduction",
                "resident_param_bytes"])

    speedup = (requests / pipe_s) / (requests / base_s)
    auto_vs_xla = (requests / pipe_s) / (requests / xla_s)
    print(f"pipelined+residency vs PR-3 baseline: {speedup:.2f}x req/s")
    print(f"kernels=auto vs all-XLA pipelined:    {auto_vs_xla:.2f}x req/s")

    # open-loop continuous batching: offered load / SLO derived from the
    # closed-loop measurement just taken, so "1.25x capacity" tracks the
    # host instead of a hardcoded rate
    open_loop = bench_open_loop(
        graphs, options, plans, max_batch, requests=requests,
        repeats=repeats, closed_req_per_s=requests / pipe_s,
        closed_p95_ms=pipe_stats["p95_sojourn_ms"] or 1.0)

    # variable-topology serving: dynamic graph construction (b6-dyn point
    # clouds across graph-size buckets + dynamic-graph b7 ViG) through one
    # warmed engine
    dynamic = bench_dynamic(options, max_batch, requests, repeats)

    dev_records, dev_avail = bench_devices(
        graphs, options, stream, max_batch, sorted(set(devices)), repeats)
    if dev_records:
        emit([[d["devices"], d["max_batch"], d["wall_ms"], d["req_per_s"],
               d["p50_ms"], d["p95_ms"], d["padded"],
               d["parity_max_abs_diff_vs_1dev"]] for d in dev_records],
             ["devices", "max_batch", "wall_ms", "req_per_s", "p50_ms",
              "p95_ms", "padded", "parity_vs_1dev"])

    if trace:
        multi = [d["devices"] for d in dev_records if d["devices"] > 1]
        trace_pass(graphs, options, stream[:min(len(stream), 2 * len(MIX))],
                   max_batch, trace, devices=max(multi) if multi else 1)
    write_bench_json("serve_gnncv", {
        "requests": requests, "max_batch": max_batch,
        "repeats": repeats, "mix": list(MIX),
        "jax_devices_visible": dev_avail,
        "devices": dev_records,
        "modes": modes, "baseline_req_per_s": round(requests / base_s, 2),
        "pipelined_req_per_s": round(requests / pipe_s, 2),
        "pipelined_vs_baseline": round(speedup, 3),
        "kernels_xla_req_per_s": round(requests / xla_s, 2),
        "kernels_auto_req_per_s": round(requests / pipe_s, 2),
        "auto_vs_xla": round(auto_vs_xla, 3),
        "runner_misses_frozen_under_traffic": True,
        # goodput-under-SLO next to raw req/s: the open-loop headline
        # numbers (SLO-aware policy) surface at the top level, the full
        # per-policy comparison under "open_loop"
        "goodput_under_slo":
            open_loop["schedulers"]["slo"]["goodput_under_slo"],
        "deadline_miss_rate":
            open_loop["schedulers"]["slo"]["deadline_miss_rate"],
        "open_loop": open_loop,
        # variable-topology headline fields surface at the top level (the
        # JSON gate checks them); the full pass record sits under
        # "dynamic"
        "graph_buckets": dynamic["graph_buckets"],
        "dynamic_b7_req_per_s": dynamic["dynamic_b7_req_per_s"],
        "dynamic": dynamic,
        "tasks": task_records,
    })
    return modes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed passes per mode; best is reported")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small stream, small buckets")
    ap.add_argument("--trace", default="TRACE_serve_gnncv.json",
                    help="Chrome-trace artifact path ('' to disable)")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts for the batch-"
                         "sharded sweep; counts the host cannot satisfy "
                         "are skipped with a note")
    args = ap.parse_args()
    devices = tuple(int(d) for d in args.devices.split(",") if d)
    if args.quick:
        run(requests=24, max_batch=2, repeats=2, trace=args.trace,
            devices=devices)
    else:
        run(requests=args.requests, max_batch=args.max_batch,
            repeats=args.repeats, trace=args.trace, devices=devices)


if __name__ == "__main__":
    gcv.use_compile_cache()
    main()
