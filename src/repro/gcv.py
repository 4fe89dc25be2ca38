"""One-call public API: ``gcv.compile`` / ``gcv.serve`` (paper §V-A).

The paper's compiler pillar "takes a user-defined model as input ... and
produces optimized code for hardware execution".  After PRs 1-4 that
promise was spread over five disjoint surfaces (``GraphBuilder`` /
``frontend.compile_model`` / ``compile_graph`` / ``build_runner`` +
``aot_compile``/``resident.swap`` / ``GNNCVServeEngine``); this module is
the single ``torch.compile``-style entry point over all of them:

    from repro import gcv

    model = gcv.compile(fn, {"x": example})     # plain JAX callable
    model = gcv.compile(graph)                  # GraphBuilder graph
    model = gcv.compile(plan)                   # pre-compiled ExecutionPlan

    out = model.run(x=sample)                   # per-sample execution
    runb = model.batched(8)                     # cached per-batch runner
    model.warmup(batches=[1, 2, 4])             # AOT trace+compile now
    model.swap_weights({"linear_1": {"w": w2}}) # hot-swap, no retrace
    model.stats() / model.lint() / model.input_specs / model.plan

    eng = gcv.serve({"b6": model, "b4": graph}, max_batch=8)

``compile`` dispatches on the input type and routes everything through the
same internals (trace -> canonicalize -> six passes -> plan/runner cache ->
device-resident weight planning -> serving engine); callers never stitch
those stages together by hand again.

Batched example inputs (ROADMAP item): users who only hold *batched*
reference arrays don't need to slice them — ``gcv.compile(fn, batched,
batch=8)`` notices every example carries the leading batch axis and strips
it before tracing, with a ``UserWarning`` naming the interpretation
(``example_batched=True`` declares it and silences the warning, ``False``
forbids stripping for models whose genuine per-sample leading dim equals
the batch size).
"""
from __future__ import annotations

import contextlib
import os
import pathlib
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.compiler import CompileOptions
from repro.core.executor import build_runner, random_inputs, stack_inputs
from repro.core.ir import Graph
from repro.core.plan import ExecutionPlan
from repro.core.runtime.cache import cached_plan, cached_runner
from repro.core.runtime.residency import (collect_params, plan_param_bytes,
                                          plan_slots)

__all__ = ["CompiledModel", "compile", "serve", "stack_inputs", "trace_to",
           "use_compile_cache"]

# The persistent compile cache's home when the environment names none: a
# fixed directory of the checkout (listed in .gitignore).  The path is part
# of every cache key, so it never derives from a temp name, pid or time.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this changes nothing; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``.  Entry points (``chip_smoke.py``, benchmarks,
    examples) call this once at start-up; importing the library never
    does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def _resolve_options(options, overrides) -> CompileOptions:
    if options is None:
        return CompileOptions(**overrides)
    assert not overrides, \
        f"pass either options= or keyword overrides, not both: " \
        f"{sorted(overrides)}"
    return options


def _resolve_mesh(devices, mesh):
    """``devices=``/``mesh=`` -> a 1-D data mesh, or None for the
    single-device path (including the one-device-mesh fallback)."""
    if mesh is not None:
        assert devices is None, "pass devices= or mesh=, not both"
        from repro.launch.mesh import as_data_mesh
        mesh = as_data_mesh(mesh)
    elif devices is not None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(devices)
    if mesh is not None and mesh.size == 1:
        return None          # one device: the existing runner is optimal
    return mesh


@contextlib.contextmanager
def trace_to(path: str):
    """Record every span inside the block and write a Chrome/Perfetto
    trace-event JSON file on exit:

        with gcv.trace_to("trace.json"):
            model = gcv.compile(task, telemetry=True)
            model.warmup(batches=[1, 8])
            model.run(**model.random_inputs())

    The file opens in ``chrome://tracing`` / https://ui.perfetto.dev and
    shows the compile passes, residency uploads, AOT warmups, and (for a
    serving engine driven inside the block) per-batch dispatch/harvest
    plus one span per request.  The tracer starts from a clean buffer and
    is disabled again on exit, so the block is self-contained; compiles
    that should re-run their passes inside the block (rather than hit the
    plan cache) want ``telemetry=True``, which is also a distinct
    plan-cache key.  The file is written even when the block raises —
    partial traces are exactly what you want when debugging the failure.
    """
    tracer = obs.get_tracer()
    obs.clear()
    tracer.enable()
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.export_chrome_trace(path)


def _example_shapes(example_inputs: Mapping[str, Any]) -> dict[str, tuple]:
    return {k: tuple(v.shape) if isinstance(v, jax.ShapeDtypeStruct)
            else tuple(np.shape(v))
            for k, v in example_inputs.items()}


def _strip_leading_axis(example_inputs: Mapping[str, Any]):
    """Per-sample specs from batched examples (drop each leading axis)."""
    out = {}
    for k, v in example_inputs.items():
        if isinstance(v, jax.ShapeDtypeStruct):
            out[k] = jax.ShapeDtypeStruct(tuple(v.shape)[1:], v.dtype)
        else:
            arr = np.asarray(v)
            out[k] = jax.ShapeDtypeStruct(arr.shape[1:], arr.dtype)
    return out


class CompiledModel:
    """The full lifecycle of one compiled model, owned in one object.

    Construct via ``gcv.compile`` — not directly.  Runners (per-sample and
    per-batch) are built lazily and cached; when the model was compiled
    from a ``Graph`` they come from the process-wide plan/runner cache
    (``core.runtime.cache``), so a serving engine and a notebook holding
    the same graph share compiled programs.
    """

    def __init__(self, plan: ExecutionPlan, *, graph: Graph | None = None,
                 options: CompileOptions, residency: bool = True,
                 batch: int | None = None, mesh=None):
        self.plan = plan
        self.graph = graph
        self.options = options
        self.residency = residency
        self.batch = batch                   # default batch for .run()
        # 1-D data mesh for batch-axis sharding (gcv.compile(devices=));
        # None = single-device. Batched runners shard their leading axis
        # over it; per-sample runners always stay single-device.
        self.mesh = mesh
        self._runners: dict[tuple, Callable] = {}
        # Runners come from the shared cache until weights diverge from the
        # plan's (swap_weights): from then on this model builds private
        # runners so its swapped weights never leak into other holders of
        # the same graph.
        self._private = graph is None
        self._swaps: dict[tuple[str, str], Any] = {}
        self._sizing = None          # memoized host-side ResidentParams

    # ------------------------------------------------------------ runners --
    def runner(self, batch: int | None = None, *, jit: bool | None = None):
        """The underlying runner for ``batch`` (``run(**inputs)`` callable
        with ``aot_compile``/``resident``/``trace_count`` attached).

        ``jit=None`` keeps ``build_runner``'s batch-aware default
        (whole-program jit per-sample, bit-stable per-op dispatch batched);
        the serving engine passes ``jit=True`` for throughput.

        On a model compiled with ``devices=``/``mesh=``, batched runners
        shard the batch axis over the mesh (``jit`` resolves to True —
        SPMD executes through whole-program jit) and ``batch`` must be
        divisible by the device count; per-sample runners stay
        single-device."""
        mesh = self.mesh if batch is not None else None
        if mesh is not None:
            if jit is None:
                jit = True
            assert jit, \
                "a mesh-sharded batched runner executes through " \
                "whole-program jit; jit=False is single-device only"
            assert batch % mesh.size == 0, \
                f"batch {batch} must be divisible by the mesh's " \
                f"{mesh.size} devices (buckets stay powers of two and " \
                f"divisible by the device count)"
        key = (batch, jit)
        if not self._private:
            # Always resolve through the process-wide cache so its
            # hit/miss effectiveness counters keep meaning something
            # (the lookup is two dict probes); the local record only
            # feeds introspection and swap bookkeeping.
            run = cached_runner(self.graph, self.options, batch=batch,
                                jit=jit, residency=self.residency,
                                mesh=mesh)
            self._runners[key] = run
            return run
        run = self._runners.get(key)
        if run is None:
            run = build_runner(self.plan, jit=jit, batch=batch,
                               residency=self.residency, mesh=mesh)
            self._apply_swaps(run)
            self._runners[key] = run
        return run

    def run(self, **inputs) -> tuple:
        """Execute the model (per-sample, or batched when the model was
        compiled with ``batch=N`` — inputs then carry the leading axis)."""
        return self.runner(self.batch)(**inputs)

    __call__ = run

    def batched(self, n: int, *, jit: bool | None = None):
        """Cached runner expecting every input stacked on a leading axis of
        size ``n`` (``gcv.stack_inputs`` builds that from samples)."""
        assert n >= 1, f"batch must be >= 1, got {n}"
        return self.runner(n, jit=jit)

    # ------------------------------------------------------------- warmup --
    def aot_compile(self, *, explicit: bool = False):
        """Pay the default runner's jit trace + XLA compile now (the
        single-model warmup hook); see ``build_runner``'s ``aot_compile``."""
        return self.runner(self.batch).aot_compile(explicit=explicit)

    def warmup(self, batches=None) -> set:
        """AOT-compile runners ahead of traffic.

        ``batches=None`` warms the default ``run()`` runner; otherwise each
        listed batch size is warmed through the serving configuration
        (``jit=True`` — what ``gcv.serve`` dispatches through).  Returns
        the set of batch sizes actually compiled (eager runners have
        nothing to warm)."""
        warmed = set()
        if batches is None:
            if self.aot_compile() is not None:
                warmed.add(self.batch)
            return warmed
        for b in batches:
            if self.batched(b, jit=True).aot_compile() is not None:
                warmed.add(b)
        return warmed

    # ----------------------------------------------------------- hot swap --
    def swap_weights(self, updates: Mapping) -> None:
        """Replace compile-time weights without recompiling.

        ``updates`` maps ``op_name -> {slot: value}`` (or flat
        ``(op_name, slot) -> value``); op names and slots are the
        ``ExecutionPlan``'s (``model.plan.ops``).  Runners that thread
        weights through jit as arguments (batched/serving) are hot-swapped
        in place with zero retrace; per-sample whole-program runners bake
        weights in as trace constants, so they are rebuilt lazily on next
        use.  After the first swap the model's runners are private — other
        holders of the same graph keep the original weights."""
        assert self.residency, \
            "swap_weights requires residency=True (the device-resident " \
            "weight store is what gets swapped)"
        flat: dict[tuple[str, str], Any] = {}
        for key, value in updates.items():
            if isinstance(key, tuple):
                flat[key] = value
            else:
                for slot, v in value.items():
                    flat[(key, slot)] = v
        known = plan_slots(self.plan)      # structural: no store, no hash
        missing = [k for k in flat if k not in known]
        assert not missing, \
            f"unknown weight slots {missing}; known op/slot pairs come " \
            f"from the plan's ops"
        self._swaps.update(flat)
        if not self._private:
            # shared-cache runners must keep the original weights for
            # other holders of the graph; go private, rebuild lazily
            self._private = True
            self._runners.clear()
            return
        for key, run in list(self._runners.items()):
            res = run.resident
            if res is not None and res.trace_constants \
                    and run.trace_count() == 0:
                self._apply_swaps(run)       # not yet traced: host swap
            elif res is not None and not res.trace_constants:
                self._apply_swaps(run)       # arg-threaded: zero retrace
            else:
                self._runners.pop(key)       # constants already traced

    def _apply_swaps(self, run) -> None:
        if not self._swaps:
            return
        res = run.resident
        assert res is not None, \
            "swap_weights requires residency=True runners"
        for (op_name, slot), value in self._swaps.items():
            # trace-constants stores are only ever swapped before their
            # program first traces (callers rebuild otherwise) — the
            # _pre_trace mode keeps one validated mutation path
            res.swap(op_name, slot, value,
                     _pre_trace=res.trace_constants)

    # -------------------------------------------------------- introspection
    @property
    def input_specs(self) -> dict[str, jax.ShapeDtypeStruct]:
        """Per-sample input specs (name -> ShapeDtypeStruct), from the
        plan's recorded shapes.  ``run()`` on a ``batch=N`` model expects
        each with an extra leading axis of N."""
        shapes = self.plan.meta.get("input_shapes", {})
        return {n: jax.ShapeDtypeStruct(tuple(shapes[n]), jnp.float32)
                for n in self.plan.input_names}

    def lint(self) -> str:
        """Trace-provenance report (which jaxpr equations produced each
        layer) for traced models, followed by the Step-4b kernel-choice
        report (per-op realization, decision source, predicted/measured
        cost)."""
        from repro.core.passes import kernel_report
        from repro.frontend.lint import lint
        head = (f"plan {self.plan.name!r}: compiled from an "
                f"ExecutionPlan — no layer graph to lint"
                if self.graph is None else lint(self.graph))
        return head + "\n\n" + kernel_report(self.plan)

    # ----------------------------------------------------------- profiling
    def profile(self, inputs: Mapping[str, Any] | None = None, *,
                repeats: int = 3) -> dict:
        """Measured wall-clock seconds per MatOp (``op_name -> row``).

        Executes the plan op by op with ``jax.block_until_ready`` between
        ops — real per-op costs, not async dispatch latencies — best of
        ``repeats`` after a warmup pass.  Each row carries the op's
        Step-4b kernel binding and, where the cost model scored it, the
        analytic prediction (``plan.meta['kernel_choices']``), so measured
        and predicted line up per op.  ``inputs=None`` profiles on random
        inputs matching the plan's recorded shapes."""
        return obs.profile_plan(self.plan, inputs, repeats=repeats)

    def profile_report(self, inputs: Mapping[str, Any] | None = None, *,
                       repeats: int = 3) -> dict:
        """``profile()`` plus the predicted-vs-measured verdict: per-op
        rows with both costs, and the **cost-model agreement rate** — on
        ops where Step 4b had multiple candidates, how often the analytic
        argmin matches the measured argmin (``agreement.rate`` is None
        when no op had competing candidates).  ``result['text']`` is the
        rendered table."""
        return obs.profile_report(self.plan, inputs, repeats=repeats)

    def stats(self) -> dict:
        """One dict over the whole lifecycle: plan shape, primitive mix,
        memory planning, residency footprint (incl. bytes folded by
        value-based dedup), runner/trace state, and the process
        plan/runner cache effectiveness counters (hits/misses from the
        ``obs.metrics()`` registry)."""
        from repro.core.runtime.cache import cache_stats
        stores = [r.resident for r in self._runners.values()
                  if r.resident is not None]
        # prefer the store whose replication matches the model's mesh
        # (a devices=N model may also hold a per-sample single-device
        # runner; resident_bytes should report the N-replica footprint)
        want = self.mesh.size if self.mesh is not None else 1
        resident = next((s for s in stores if s.replicas == want),
                        stores[0] if stores else None)
        if resident is None and self.residency:
            if self._sizing is None:      # hash once, not per stats() call
                self._sizing = collect_params(self.plan, device=False,
                                              mesh=self.mesh)
            resident = self._sizing
        out = {
            "name": self.plan.name,
            "frontend": self.plan.meta.get("frontend"),
            "ops": len(self.plan.ops),
            "primitives": self.plan.primitive_counts(),
            "kernels": self.plan.kernel_counts(),
            "kernels_mode": self.plan.meta.get("kernels_mode"),
            "peak_live_bytes": self.plan.peak_live_bytes(),
            "param_bytes": plan_param_bytes(self.plan),
            "runners_built": len(self._runners),
            "default_batch": self.batch,
            "swapped_slots": len(self._swaps),
            "devices": want,
        }
        if resident is not None:
            # total across replicas ("one upload per device"); the
            # per-device figure is the single-chip footprint
            out["resident_bytes"] = resident.nbytes()
            out["resident_bytes_per_device"] = \
                resident.nbytes() // resident.replicas
            out["value_deduped_bytes"] = resident.value_dedup_bytes
        out["cache"] = cache_stats()
        return out

    def random_inputs(self, seed: int = 0, *,
                      batch: int | None = "default") -> dict:
        """Random inputs matching ``input_specs`` (convenience for demos
        and benchmarks); ``batch`` defaults to the model's."""
        b = self.batch if batch == "default" else batch
        return random_inputs(self.plan, seed=seed, batch=b)

    def __repr__(self) -> str:
        return (f"CompiledModel({self.plan.name!r}, "
                f"frontend={self.plan.meta.get('frontend')!r}, "
                f"ops={len(self.plan.ops)}, batch={self.batch})")


def compile(model, example_inputs: Mapping[str, Any] | None = None, *,
            batch: int | None = None, options: CompileOptions | None = None,
            residency: bool = True,
            example_batched: bool | None = None, name: str | None = None,
            devices=None, mesh=None,
            **option_overrides) -> CompiledModel:
    """Compile anything the pipeline can ingest into a ``CompiledModel``.

    ``model`` is one of:

      * a plain JAX callable — ``example_inputs`` (arrays or
        ``ShapeDtypeStruct``s) names the model inputs; the tracing
        frontend recovers the layer graph (``frontend.to_graph``);
      * a layer ``Graph`` (from ``GraphBuilder`` or a prior trace);
      * an already-compiled ``ExecutionPlan``.

    ``batch=N`` makes ``run()`` expect/return a leading batch axis of N
    (per-batch runners for other sizes via ``.batched(n)``).  When tracing
    a callable with ``batch=N`` and every example input carrying that
    leading axis, the axis is stripped before tracing (batched reference
    inputs "just work"); ``example_batched`` forces (``True``) or forbids
    (``False``) the stripping for ambiguous shapes.

    Compile options come either as ``options=CompileOptions(...)`` or as
    keyword overrides (``gcv.compile(g, target="fpga")``).  Kernel
    realization is ``kernels=`` ("auto" | "xla" | "pallas" | "measured",
    a ``CompileOptions`` field, so it works both ways).
    ``telemetry=True`` records one span per compiler pass (and is a
    distinct plan-cache key, so the passes genuinely re-run) — pair with
    ``gcv.trace_to(path)`` to capture them to a file.

    ``devices=``/``mesh=`` turn on batch-axis data parallelism:
    ``devices`` is an int (the first N ``jax.devices()``) or a device
    sequence, ``mesh`` a pre-built 1-D ``("data",)`` mesh.  Every
    ``.batched(n)`` runner then shards its leading axis over the mesh
    (``n`` divisible by the device count) with the resident weights
    replicated once per device; a one-device mesh falls back to the
    existing single-device runner.  Outputs are bit-for-bit identical to
    the single-device runner at the same batch size.
    """
    opts = _resolve_options(options, option_overrides)
    dmesh = _resolve_mesh(devices, mesh)
    if isinstance(model, ExecutionPlan):
        assert example_inputs is None, \
            "an ExecutionPlan is already compiled; example_inputs are " \
            "only for tracing a callable"
        if model.meta.get("kernels_mode") != opts.kernels:
            # re-bind realizations in place: kernel selection is the only
            # pass whose inputs (shapes/nnz) are already on the plan
            from repro.core.passes import select_kernels
            select_kernels(model, kernels=opts.kernels,
                           autotune_cache=opts.autotune_cache)
        return CompiledModel(model, graph=None, options=opts,
                             residency=residency, batch=batch, mesh=dmesh)
    if isinstance(model, Graph):
        assert example_inputs is None, \
            "a layer Graph declares its own inputs; example_inputs are " \
            "only for tracing a callable"
        plan = cached_plan(model, opts)
        return CompiledModel(plan, graph=model, options=opts,
                             residency=residency, batch=batch, mesh=dmesh)
    assert callable(model), \
        f"cannot compile {type(model).__name__}: expected a JAX " \
        f"callable, a Graph, or an ExecutionPlan"
    assert example_inputs is not None, \
        "compiling a callable requires example_inputs (arrays or " \
        "jax.ShapeDtypeStruct per named input)"
    shapes = _example_shapes(example_inputs)
    strip = example_batched
    if strip is None:
        strip = batch is not None and all(
            len(s) >= 1 and s[0] == batch for s in shapes.values())
        if strip:
            # auto-detect is a guess: a genuine per-sample leading dim
            # that happens to equal `batch` would be mis-stripped, so say
            # what was decided and how to override it
            import warnings
            warnings.warn(
                f"gcv.compile: every example input leads with axis "
                f"{batch} == batch, so it is being interpreted as the "
                f"batch axis and stripped before tracing; pass "
                f"example_batched=True to silence this, or "
                f"example_batched=False if {batch} is a genuine model "
                f"dimension", UserWarning, stacklevel=2)
    if strip:
        leads = {s[0] for s in shapes.values() if len(s) >= 1}
        assert len(leads) == 1 and all(len(s) >= 1
                                       for s in shapes.values()), \
            f"example_batched expects one shared leading batch axis, " \
            f"got shapes {shapes}"
        (lead,) = leads
        assert batch is None or batch == lead, \
            f"batch={batch} does not match the examples' leading " \
            f"axis {lead}"
        batch = lead if batch is None else batch
        example_inputs = _strip_leading_axis(example_inputs)
    from repro import frontend
    graph = frontend.to_graph(
        model, example_inputs,
        name=name or getattr(model, "__name__", None) or "traced")
    plan = cached_plan(graph, opts)
    return CompiledModel(plan, graph=graph, options=opts,
                         residency=residency, batch=batch, mesh=dmesh)


def serve(models: Mapping[str, Any], *,
          options: CompileOptions | None = None, max_batch: int = 8,
          jit: bool = True,
          pipeline_depth: int = 2, residency: bool = True, warmup=False,
          devices=None, mesh=None, slo_ms: float | None = None,
          scheduler=None, max_pipeline_depth: int | None = None,
          graph_buckets: Mapping[str, Any] | None = None,
          **option_overrides):
    """Build the micro-batching serving engine from models, not plumbing.

    ``models`` maps task name -> anything ``gcv.compile`` accepts (a
    ``CompiledModel``, a layer ``Graph``, an ``ExecutionPlan``, or a
    ``(fn, example_inputs)`` pair for plain JAX callables).  Pre-compiled
    models keep their own kernel/residency settings; everything else is
    compiled with this call's (``kernels=`` picks the realization mode).
    ``warmup=True`` AOT-compiles every (task, bucket) runner before
    returning — no live request ever traces.  The engine's ``stats()``
    reads from its own ``obs.MetricsRegistry``; run it inside
    ``gcv.trace_to(path)`` to capture per-batch and per-request spans.

    ``devices=``/``mesh=`` serve over a device mesh: every bucketed
    runner shards its batch axis across the 1-D data mesh (weights
    replicated once per device), buckets stay powers of two but must be
    divisible by the device count, and the engine keeps its pipeline
    accounting per device.  Migration: ``gcv.serve(models, devices=N)``
    is the whole change — submit/dispatch/harvest/stats keep their
    single-device contract, and a one-device mesh falls back to exactly
    the old engine.

    ``slo_ms=`` configures continuous batching for deadline goodput: it
    is the default per-request deadline (``submit`` may override with
    ``deadline_ms=``/``priority=``), switches the default scheduling
    policy to the SLO-aware one (``scheduler=`` names ``"fifo"``/
    ``"slo"`` or passes a custom ``serve.Scheduler``), and turns on
    adaptive pipeline depth within ``[1, max_pipeline_depth]`` — deepen
    under queue growth, shrink when recent p95 sojourn nears the SLO.
    Drive an open-loop arrival schedule with ``engine.stream(...)`` or
    pump ``engine.poll()`` yourself.  Migration: ``engine.run()`` on a
    pre-submitted list without ``slo_ms`` is unchanged — the FIFO policy
    at fixed depth is bit-for-bit the closed-batch engine.

    ``graph_buckets=`` serves *variable-topology* tasks (dynamic graph
    construction): map a task name to the node counts it should serve at
    and make its ``models`` entry a factory ``n_nodes -> model spec``
    (e.g. ``lambda n: TRACED_TASKS["b6-dyn"](n_points=n)``).  The engine
    compiles one plan per size, ``submit`` routes each request to the
    smallest bucket that fits (zero-padding the node-indexed inputs —
    the model's validity mask keeps padded nodes inert) and raises
    ``ValueError`` at admission for requests over the largest bucket.
    """
    from repro.serve.gnncv import GNNCVServeEngine
    opts = _resolve_options(options, option_overrides)
    eng = GNNCVServeEngine(dict(models), options=opts, max_batch=max_batch,
                           jit=jit, pipeline_depth=pipeline_depth,
                           residency=residency, devices=devices, mesh=mesh,
                           slo_ms=slo_ms, scheduler=scheduler,
                           max_pipeline_depth=max_pipeline_depth,
                           graph_buckets=graph_buckets)
    if warmup:
        eng.warmup()
    return eng
