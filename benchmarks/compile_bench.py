"""Compile-path latency: graph construction -> six passes -> weight upload
-> first run, for all seven tasks (b1-b6 through *both* frontends, the
traced-only b7 ViG through the JAX tracer — its own recorded baseline,
since the paper publishes no latency target for ViG).

    PYTHONPATH=src python -m benchmarks.compile_bench [--small] [--iters N]
        [--quick] [--kernels auto|xla|pallas|measured] [--tasks b1,b6]

``--quick`` is the CI smoke mode: one iteration, skip the first-run jit
phase (by far the slowest), keep the full seven-task frontend sweep — a
regression anywhere in trace/canonicalize (new unsupported primitive,
broken pattern match) still fails fast.

``--kernels`` picks the Step-4b realization mode; every record carries the
per-op kernel decisions (``kernel_counts`` + the choice map), so the
uploaded ``BENCH_compile.json`` doubles as the kernel-choice report.
``--kernels measured`` additionally populates/reads the autotune cache
(``.autotune_cache.json`` or ``$REPRO_AUTOTUNE_CACHE``), which CI uploads
as an artifact.  ``--tasks`` restricts the sweep (comma-separated).

Four phases per (task, frontend):

  build_ms    builder: GraphBuilder construction; tracer: jax.make_jaxpr
              interpretation + canonicalization (the new frontend cost)
  compile_ms  the six passes (identical plans either way — parity is
              pinned by tests/test_frontend_parity.py)
  upload_ms   device-resident weight planning: one deduplicated device_put
              sweep over the plan's weights/ELL/COO arrays
              (core/runtime/residency.py) — paid once per runner, shared
              by every serving bucket
  first_ms    first runner call (jit trace + execute) — the cold-start a
              serving process pays once per (graph, options, batch), or
              ahead of traffic via ``run.aot_compile()``

Regressions in the trace/canonicalize path show up as build_ms drift
against this trajectory without touching steady-state numbers; every run
also writes the machine-readable ``BENCH_compile.json`` record CI uploads.
The record includes ``cost_model_agreement`` — Step-4b's analytic
predictions validated against per-op stopwatch measurements on b1 and b6
(``obs.profile_report``) — and the run emits a ``TRACE_compile.json``
Chrome-trace artifact covering one fully-traced compile per task.
"""
from __future__ import annotations

import argparse
import math

from benchmarks.common import emit, write_bench_json
from repro import gcv, obs
from repro.core import CompileOptions
from repro.core.runtime.cache import clear_caches
from repro.core.runtime.residency import collect_params
from repro.gnncv.jax_tasks import build_traced_task
from repro.gnncv.tasks import build_task

TASKS = ("b1", "b2", "b3-r50", "b4", "b5", "b6")
TRACED_ONLY = ("b7",)                 # ViG exists only through the tracer
OPTS = CompileOptions(target="fpga")
# Tasks whose plans get the per-op predicted-vs-measured treatment: one
# dense-dominated CNN pipeline and one sparse message-passing workload —
# the two cost-model regimes.
AGREEMENT_TASKS = ("b1", "b6")


def _time_ms(fn, iters: int):
    best = float("inf")
    result = None
    for _ in range(iters):
        t0 = obs.now()
        result = fn()
        best = min(best, (obs.now() - t0) * 1e3)
    return best, result


def bench(task: str, use_tracer: bool, *, small: bool, iters: int,
          first_run: bool = True, options: CompileOptions = OPTS):
    builder = build_traced_task if use_tracer else build_task
    build_ms, graph = _time_ms(lambda: builder(task, small=small), iters)

    def compile_cold():
        # clear the plan cache so every iteration times the six passes,
        # not a cache hit — the cold path a server pays once per graph
        clear_caches()
        return gcv.compile(graph, options=options)

    compile_ms, model = _time_ms(compile_cold, iters)
    plan = model.plan

    def upload():
        params = collect_params(plan)
        for a in params.arrays.values():
            a.block_until_ready()
        return params

    upload_ms, params = _time_ms(upload, iters)
    if not first_run:
        return (build_ms, compile_ms, upload_ms, float("nan"),
                len(plan.ops), params, plan)
    ins = model.random_inputs(seed=0)
    t0 = obs.now()
    out = model.run(**ins)
    _ = [o.block_until_ready() for o in out]
    first_ms = (obs.now() - t0) * 1e3
    return (build_ms, compile_ms, upload_ms, first_ms, len(plan.ops),
            params, plan)


def cost_model_agreement(options: CompileOptions, *, small: bool,
                         tasks=AGREEMENT_TASKS, repeats: int = 2) -> dict:
    """Predicted-vs-measured validation of the Step-4b cost model on the
    agreement tasks: per-op stopwatch profile, rival-kernel
    micro-benchmarks, and the pooled agreement rate over every op where
    the analytic model actually had a choice to make."""
    per_task, agree, considered = {}, 0, 0
    for task in tasks:
        plan = gcv.compile(build_task(task, small=small),
                           options=options).plan
        rep = obs.profile_report(plan, repeats=repeats)
        per_task[task] = rep["agreement"]
        agree += rep["agreement"]["agree"]
        considered += rep["agreement"]["considered"]
        print(rep["text"])
        print()
    return {"per_task": per_task, "agree": agree,
            "considered": considered,
            "rate": agree / considered if considered else None}


def run(small: bool = True, iters: int = 3, first_run: bool = True,
        kernels: str = "auto", tasks=None, trace="TRACE_compile.json",
        agreement: bool = True):
    import dataclasses
    options = dataclasses.replace(OPTS, kernels=kernels)
    rows, records = [], []
    sweep = [(t, use_tracer) for t in TASKS
             for use_tracer in (False, True)]
    sweep += [(t, True) for t in TRACED_ONLY]
    if tasks is not None:
        sweep = [(t, u) for t, u in sweep if t in tasks]
    for task, use_tracer in sweep:
        frontend_name = "tracer" if use_tracer else "builder"
        b, c, u, f, n_ops, params, plan = bench(
            task, use_tracer, small=small, iters=iters,
            first_run=first_run, options=options)
        rows.append((task, frontend_name, n_ops, f"{b:.1f}", f"{c:.1f}",
                     f"{u:.1f}", f"{f:.1f}", f"{b + c + u + f:.1f}"))
        records.append({"task": task, "frontend": frontend_name,
                        "ops": n_ops, "build_ms": round(b, 2),
                        "compile_ms": round(c, 2),
                        "upload_ms": round(u, 2),
                        "first_run_ms": None if math.isnan(f)
                        else round(f, 2),
                        "resident_param_bytes": params.nbytes(),
                        "value_deduped_bytes": params.value_dedup_bytes,
                        "kernel_counts": plan.kernel_counts(),
                        "kernel_choices": {
                            name: ch["kernel"] for name, ch in
                            plan.meta.get("kernel_choices", {}).items()},
                        "autotune": plan.meta.get("autotune")})
    emit(rows, ["task", "frontend", "ops", "build_ms", "compile_ms",
                "upload_ms", "first_run_ms", "total_ms"])
    cma = None
    if agreement:
        cma = cost_model_agreement(options, small=small,
                                   repeats=max(1, min(iters, 3)))
    if trace:
        # one fully-traced compile per swept task: clear the plan cache so
        # the six passes re-run inside the tracer, then export the
        # Chrome-trace artifact CI uploads next to BENCH_compile.json
        with gcv.trace_to(trace):
            clear_caches()
            for task, use_tracer in sweep:
                builder = build_traced_task if use_tracer else build_task
                gcv.compile(builder(task, small=small), options=options)
    write_bench_json("compile", {"small": small, "iters": iters,
                                 "first_run": first_run,
                                 "kernels": kernels, "tasks": records,
                                 "cost_model_agreement": cma})
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true", default=True)
    ap.add_argument("--full", dest="small", action="store_false",
                    help="paper-scale graphs (slow)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 1 iteration, skip the first-run phase")
    ap.add_argument("--kernels", default="auto",
                    choices=("auto", "xla", "pallas", "measured"),
                    help="Step-4b kernel selection mode")
    ap.add_argument("--tasks", default=None,
                    help="comma-separated task subset (e.g. b1,b6)")
    ap.add_argument("--trace", default="TRACE_compile.json",
                    help="Chrome-trace artifact path ('' to disable)")
    ap.add_argument("--no-agreement", dest="agreement",
                    action="store_false", default=True,
                    help="skip the predicted-vs-measured profile pass")
    args = ap.parse_args()
    task_filter = args.tasks.split(",") if args.tasks else None
    if args.quick:
        run(small=True, iters=1, first_run=False, kernels=args.kernels,
            tasks=task_filter, trace=args.trace, agreement=args.agreement)
    else:
        run(small=args.small, iters=args.iters, kernels=args.kernels,
            tasks=task_filter, trace=args.trace, agreement=args.agreement)
