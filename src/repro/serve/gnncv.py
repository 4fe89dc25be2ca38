"""Micro-batching request engine for the GNN-CV task family (b1-b7).

The LM ``ServeEngine`` batches homogeneous decode steps over slots; GNN-CV
inference is the opposite shape of problem — each request is one
whole-program execution of a *heterogeneous* task (b1-b7), so the batching
axis is requests-per-compiled-plan, not tokens-per-slot:

  * requests queue per task; each dispatch serves the task whose front
    request has waited longest, draining everything queued behind it
    through that task's batched runner (``CompiledModel.batched(N)``);
  * batch sizes are quantized to power-of-two buckets (short batches are
    padded by repeating the tail request), so the plan/runner cache
    (``core.runtime.cache``) holds at most log2(max_batch)+1 compiled
    runners per task — the paper's fixed-latency argument (§VII-D2)
    carried to serving: after warmup, no step ever recompiles;
  * ``warmup()`` goes further and AOT-compiles every (task, bucket)
    runner before traffic arrives (``run.aot_compile()`` — one trace +
    XLA compile each, priming the jit dispatch fast path), so no live
    request ever pays a jit trace — ``stats()['runner_misses']`` freezes;
  * serving is **pipelined**: ``dispatch()`` launches a batch and leaves
    its outputs as in-flight device arrays (JAX async dispatch), so batch
    k+1 is assembled and launched while batch k executes; ``harvest()``
    blocks on the oldest in-flight batch and materializes results.
    ``pipeline_depth`` bounds in-flight batches (depth 1 = the old
    synchronous step);
  * with ``devices=``/``mesh=`` the engine serves over a 1-D ``data``
    mesh: every bucketed runner shards its batch axis across the devices
    (weights replicated once per device by the residency layer), buckets
    stay powers of two but never drop below the device count, and padded
    positions are placed round-robin (position j -> device j % ndev) so
    pad waste spreads evenly — ``stats()['pad_per_device']`` accounts for
    it per device.  Each SPMD batch occupies a row-block on every device,
    so the per-device in-flight queues advance in lockstep and
    ``pipeline_depth`` bounds each device's queue.  A one-device mesh
    falls back to exactly the single-device engine;
  * **variable topology** — a task constructed with ``graph_buckets=``
    serves requests whose *graph size* varies too: the engine compiles
    one plan per configured node count (virtual tasks ``task@g{size}``,
    bounded at len(sizes) x log2(max_batch)+1 runners), ``submit`` pads
    each request's node-indexed inputs up to the smallest bucket that
    fits (``graph.build`` span; the model's validity mask keeps padded
    nodes out of the dynamic KNN graph) and rejects requests over the
    largest bucket with a ``ValueError`` at admission; the scheduler's
    service estimator is keyed on the combined (graph bucket, batch
    bucket), and ``stats()['graph_buckets']`` accounts submissions and
    padded nodes per graph bucket;
  * the Step-6 liveness annotations bound the per-sample activation
    working set; ``plan.peak_live_bytes() x batch`` is the planner's
    sizing model for a server (under jit, XLA's own buffer reuse — which
    the annotations mirror — is what realizes it).  Weights are
    device-resident plan state shared across every bucket of a task
    (``core.runtime.residency``), not per-bucket trace constants.

Serving is **continuous**, not closed-batch: ``submit()`` timestamps
arrivals and accepts ``deadline_ms=``/``priority=``; a pluggable scheduler
(``repro.serve.scheduler`` — the management plane, split from the
dispatch/harvest execution backend) picks each next ``(task, bucket)``
dispatch, by arrival order (``"fifo"``) or by service-corrected deadline
slack built from the Step-4b cost model plus live per-(task, bucket)
service-time histograms (``"slo"``); ``poll()`` is the non-blocking pump
(opportunistic harvest of finished batches via ``jax.Array.is_ready``,
dispatch up to the current depth) and ``stream()`` replays an open-loop
arrival schedule against the wall clock.  Under a configured ``slo_ms``
the pipeline depth adapts: it deepens while the queue outgrows the
in-flight window and shrinks when recent p95 sojourn approaches the SLO
(deep pipelines buy throughput at the price of sojourn — exactly the
wrong trade near a deadline).  Expired requests are rejected at submit
and shed from the queues before they can waste a dispatch; ``stats()``
reports goodput (completions within deadline) and deadline-miss rate next
to raw req/s.  The legacy closed-batch path is a degenerate schedule:
``run()`` on a pre-submitted list under the default FIFO policy is
bit-for-bit the pre-stream engine.

The engine is observable end to end (``repro.obs``): every lifecycle
counter, gauge and latency percentile ``stats()`` reports is read from the
engine's own ``MetricsRegistry`` (per-task request counters, sojourn
histogram — zero-safe: percentiles are ``None`` until a request has been
harvested), and with tracing on (``gcv.trace_to(path)``) each dispatch and
harvest is a span carrying batch id / bucket / pad count, plus one
retroactive span per request from submit to harvest — a serve run opens in
``chrome://tracing``.  Inside them live spans split the host work —
``serve.schedule``, ``serve.stack``, ``serve.transfer``, ``serve.launch``
at dispatch; ``serve.wait``, ``serve.copy``, ``serve.unpack`` at harvest —
and land on a ``jax.profiler`` trace's host plane too, on the device
operations' clock.  Tracing is off by default and costs one attribute
read per span.  Always on: every call into the runtime (transfer, launch,
the readiness probe, wait, copy) is timed, and one slower than
``STALL_MS`` counts as a stall (``stats()['stalls']``; a ``serve.stall``
event when tracing).
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.compiler import CompileOptions
from repro.core.executor import stack_host
from repro.core.ir import Graph
from repro.core.plan import ExecutionPlan

# A runtime call slower than this is a stall: about 3x the device time of
# the longest batch the chip benchmark serves (8 DGCNN clouds, ~17 ms).
STALL_MS = 50.0
# the serving loop's calls into the runtime, each timed against STALL_MS
RUNTIME_CALLS = ("transfer", "launch", "ready", "wait", "copy")


@dataclasses.dataclass
class TaskRequest:
    rid: int
    task: str
    inputs: dict                       # per-sample input arrays, unstacked
    result: tuple | None = None        # tuple of np outputs once done
    done: bool = False
    t_submit: float = 0.0              # obs.now() at intake
    t_dispatch: float = 0.0            # obs.now() when its batch launched
    t_done: float = 0.0                # obs.now() when harvested
    deadline_s: float | None = None    # absolute obs.now() deadline
    priority: int = 0                  # higher dispatches first (SLO policy)
    missed_deadline: bool = False      # finished after deadline_s (or shed)
    shed: bool = False                 # dropped unserved (result stays None)


@dataclasses.dataclass
class _BatchInfo:
    """Identity of one in-flight dispatch, carried to harvest (and into
    the trace) so per-request spans can say which batch served them."""
    batch_id: int
    task: str
    bucket: int
    pad: int
    t_dispatch: float
    devices: int = 1
    # row placement under sharding: padded position j sits at stacked row
    # rows[j]; empty tuple = identity (single device)
    rows: tuple = ()
    shard_n: tuple = ()                # real requests per device
    pad_per_dev: tuple = ()            # pad rows per device


class GNNCVServeEngine:
    """Queue heterogeneous task requests, drain them in per-plan batches.

    Constructed by (and from) the ``repro.gcv`` façade: ``models`` maps
    task name -> anything ``gcv.compile`` accepts — a ``CompiledModel``, a
    layer ``Graph``, an ``ExecutionPlan``, or a ``(fn, example_inputs)``
    pair for plain JAX callables.  Everything not already compiled is run
    through ``gcv.compile`` with this engine's options; pre-compiled
    models keep their own.  Kernel realizations are per-op compile-time
    plan state (``options.kernels``).

    ``devices=``/``mesh=`` select the batch-sharded serving path (see the
    module docstring); models the engine compiles itself inherit the
    mesh, and pre-compiled models must have been compiled over the *same*
    mesh — a model sharded differently from the engine's dispatch
    placement would silently misattribute rows to devices.
    """

    def __init__(self, models=None, *,
                 options: CompileOptions = CompileOptions(),
                 max_batch: int = 8, jit: bool = True,
                 pipeline_depth: int = 2, residency: bool = True,
                 devices=None, mesh=None, slo_ms: float | None = None,
                 scheduler=None, max_pipeline_depth: int | None = None,
                 graph_buckets=None):
        from repro import gcv                  # late: gcv builds engines
        from repro.serve.scheduler import resolve_scheduler
        assert models, "GNNCVServeEngine needs at least one model"
        models = dict(models)
        # Variable-topology tasks: graph_buckets maps a task name to the
        # node counts it serves at.  The task's ``models`` entry must be a
        # *factory* ``n_nodes -> model spec``; each size compiles under a
        # virtual task key ``task@g{size}`` and ``submit(task, ...)``
        # routes each request to the smallest bucket that fits it (see
        # ``_pad_to_graph_bucket``).  Bucket count stays bounded:
        # len(sizes) graph buckets x log2(max_batch)+1 batch buckets.
        self.graph_buckets: dict[str, list[int]] = {
            t: sorted({int(s) for s in ss})
            for t, ss in dict(graph_buckets or {}).items()}
        for task, sizes in self.graph_buckets.items():
            assert task in models, \
                f"graph_buckets names unknown task {task!r}"
            assert sizes and sizes[0] >= 1, \
                f"task {task!r}: graph bucket sizes must be >= 1, " \
                f"got {sizes}"
            factory = models.pop(task)
            assert callable(factory) \
                and not isinstance(factory, (tuple, Graph, ExecutionPlan,
                                             gcv.CompiledModel)), \
                f"task {task!r} has graph_buckets — its models entry " \
                f"must be a factory n_nodes -> model spec, got " \
                f"{type(factory).__name__}"
            for g in sizes:
                models[f"{task}@g{g}"] = factory(g)
        self.options = options
        self.mesh = gcv._resolve_mesh(devices, mesh)
        ndev = self.mesh.size if self.mesh is not None else 1
        self._ndev = ndev
        # power of two keeps _bucket's doubling landing on the cap and the
        # runner cache on its log2(max_batch)+1 contract; rejecting other
        # values beats silently serving at a different capacity
        assert max_batch >= 1 and max_batch & (max_batch - 1) == 0, \
            f"max_batch must be a power of two, got {max_batch}"
        # every bucket must shard evenly; divisors of a power of two are
        # powers of two, so this also pins the device count to 1, 2, 4, ...
        assert max_batch % ndev == 0, \
            f"max_batch={max_batch} must be divisible by the device " \
            f"count ({ndev}) so every bucket shards evenly"
        assert jit or ndev == 1, \
            "multi-device serving shards through jitted programs — " \
            "jit=False is single-device only"
        assert pipeline_depth >= 1, \
            f"pipeline_depth must be >= 1, got {pipeline_depth}"
        assert slo_ms is None or slo_ms > 0, \
            f"slo_ms must be positive, got {slo_ms}"
        self.max_batch = max_batch
        self.jit = jit
        self.pipeline_depth = pipeline_depth   # configured starting depth
        self.slo_ms = slo_ms
        self.scheduler = resolve_scheduler(scheduler, slo_ms=slo_ms)
        # adaptive-depth ceiling: a fixed-depth engine by default (the
        # closed-batch contract), headroom to deepen once an SLO makes the
        # throughput/sojourn trade measurable
        if max_pipeline_depth is None:
            max_pipeline_depth = pipeline_depth if slo_ms is None \
                else max(pipeline_depth, 4)
        assert max_pipeline_depth >= pipeline_depth, \
            f"max_pipeline_depth={max_pipeline_depth} must be >= " \
            f"pipeline_depth={pipeline_depth}"
        self.max_pipeline_depth = max_pipeline_depth
        self._depth = pipeline_depth           # current adaptive depth
        self.residency = residency
        self.models: dict[str, gcv.CompiledModel] = {}
        for task, model in dict(models).items():
            if isinstance(model, gcv.CompiledModel):
                assert model.mesh == self.mesh, \
                    f"task {task!r}: pre-compiled model mesh " \
                    f"{model.mesh} does not match the engine's " \
                    f"{self.mesh} — compile it with the same devices=/" \
                    f"mesh=, or hand the engine its graph/plan instead"
                self.models[task] = model
            else:
                fn, example = model if isinstance(model, tuple) \
                    else (model, None)
                assert isinstance(fn, (Graph, ExecutionPlan)) \
                    or example is not None, \
                    f"task {task!r}: a plain callable needs example " \
                    f"inputs — pass (fn, example_inputs) or a " \
                    f"pre-compiled model"
                self.models[task] = gcv.compile(
                    fn, example, options=options,
                    residency=residency, name=task, mesh=self.mesh)
        self.plans = {t: m.plan for t, m in self.models.items()}
        # Back-compat view (pre-façade engines were keyed on raw graphs);
        # plan-only models have no graph to expose.
        self.graphs = {t: m.graph for t, m in self.models.items()}
        self.queues: dict[str, deque] = {t: deque() for t in self.models}
        self._rid = itertools.count()
        self._inflight: deque[tuple[list[TaskRequest], tuple,
                                    _BatchInfo]] = deque()
        # per-device dispatch queues: every SPMD batch occupies a row-block
        # on every device, so each deque mirrors the master _inflight and
        # pipeline_depth bounds each device's queue (== the master's depth)
        self._dev_inflight: list[deque] = [deque() for _ in range(ndev)]
        self._warmed: set[tuple[str, int]] = set()
        # Engine-owned instruments — stats() reads these, never its own
        # tallies.  Owned (not process-global) so two engines in one
        # process never mix their request counts.
        self.metrics = obs.MetricsRegistry()
        self._c_submitted = self.metrics.counter("submitted")
        self._c_completed = self.metrics.counter("completed")
        self._c_dispatches = self.metrics.counter("dispatches")
        self._c_padded = self.metrics.counter("padded")
        self._c_pad_dev = [self.metrics.counter(f"padded.device{d}")
                           for d in range(ndev)]
        self._h_sojourn = self.metrics.histogram("sojourn_ms")
        self._h_queue = self.metrics.histogram("queue_ms")
        # short window for depth adaptation: the all-history histogram is
        # sticky (an early overload would depress p95 reactions forever)
        self._h_sojourn_recent = self.metrics.histogram(
            "sojourn_recent_ms", maxlen=256)
        self._c_goodput = self.metrics.counter("goodput")
        self._c_misses = self.metrics.counter("deadline_misses")
        self._c_shed = self.metrics.counter("shed")
        self._c_expired = self.metrics.counter("expired_at_submit")
        self._c_stalls = self.metrics.counter("stalls")
        self._h_stall = self.metrics.histogram("stall_ms")
        self.metrics.gauge("pipeline_depth").set(self._depth)
        self._plan_cost: dict[str, float] = {}
        self._t_first_dispatch: float | None = None
        self._t_last_harvest: float | None = None

    # back-compat counter views (pre-obs engines kept plain attributes)
    @property
    def completed(self) -> int:
        return self._c_completed.value

    @property
    def steps(self) -> int:
        return self._c_dispatches.value

    # ------------------------------------------------- graph-size buckets --
    def _node_inputs(self, task: str) -> list[str]:
        """Input names carrying the graph's node axis, by convention the
        inputs whose leading dimension equals the graph-bucket size in the
        compiled plan (for ``b6-dyn``: ``points (N, 3)`` and ``mask
        (N,)``).  These are the inputs ``_pad_to_graph_bucket`` zero-pads;
        a model served this way should take a validity mask so padded
        nodes are inert (``knn_graph(mask=)`` never selects them)."""
        g0 = self.graph_buckets[task][0]
        shapes = self.plans[f"{task}@g{g0}"].meta["input_shapes"]
        names = [n for n, s in shapes.items() if s and s[0] == g0]
        assert names, \
            f"task {task!r}: no input has the graph-size leading axis"
        return names

    def _pad_to_graph_bucket(self, task: str, inputs: dict
                             ) -> tuple[str, dict]:
        """Route one variable-size request to its graph bucket: read the
        node count off the node-indexed inputs, zero-pad them up to the
        smallest bucket that fits, and return the virtual task key the
        request queues under.  Padding is a ``graph.build`` span (the
        serving-side cost of dynamic graph construction) and per-bucket
        ``graph.{task}.g{size}`` counters feed ``stats()``."""
        sizes = self.graph_buckets[task]
        node_inputs = self._node_inputs(task)
        ns = {int(np.shape(inputs[name])[0])
              for name in node_inputs if name in inputs}
        if len(ns) != 1:
            raise ValueError(
                f"task {task!r}: node-indexed inputs {node_inputs} "
                f"disagree on the node count ({sorted(ns)})")
        n = ns.pop()
        if n < 1:
            raise ValueError(f"task {task!r}: request has {n} nodes")
        if n > sizes[-1]:
            raise ValueError(
                f"task {task!r}: request has {n} nodes but the largest "
                f"graph bucket is {sizes[-1]} (buckets: {sizes}) — "
                f"serve it with a larger graph_buckets entry or split "
                f"the request")
        g = next(s for s in sizes if s >= n)
        with obs.span("graph.build", cat="serve", task=task, n_nodes=n,
                      graph_bucket=g, pad_nodes=g - n):
            if g != n:
                padded = dict(inputs)
                for name in node_inputs:
                    if name not in inputs:
                        continue       # submit reports the missing input
                    v = np.asarray(inputs[name])
                    padded[name] = np.concatenate(
                        [v, np.zeros((g - n,) + v.shape[1:], v.dtype)])
                inputs = padded
        self.metrics.counter(f"graph.{task}.g{g}.submitted").inc()
        if g != n:
            self.metrics.counter(f"graph.{task}.g{g}.pad_nodes").inc(g - n)
        return f"{task}@g{g}", inputs

    # ------------------------------------------------------------ intake --
    def submit(self, task: str, *, deadline_ms: float | None = None,
               priority: int = 0, **inputs) -> TaskRequest:
        """Validated intake: a malformed request is rejected here, where it
        can only hurt its own caller — inside ``dispatch`` it would take a
        whole popped batch down with it.

        ``deadline_ms`` is relative to now (defaulting to the engine's
        ``slo_ms`` when one is configured); ``priority`` breaks scheduling
        ties under the SLO policy (higher first).  A request whose
        deadline has already passed at submit is *admission-rejected*:
        returned ``done`` with ``result=None``, ``missed_deadline`` set,
        counted under ``expired_at_submit`` — it never enters a queue, so
        a flood of hopeless work cannot displace servable requests.

        A task with ``graph_buckets`` accepts *variable-size* requests:
        the node count is read off the node-indexed inputs, the request
        is zero-padded up to the smallest graph bucket that fits (a
        ``graph.build`` span), and it queues under that bucket's virtual
        task ``task@g{size}``.  A request larger than the biggest bucket
        is a ``ValueError`` here, at admission — not a shape assert
        inside a dispatched batch."""
        if task in self.graph_buckets:
            task, inputs = self._pad_to_graph_bucket(task, inputs)
        assert task in self.models, f"unknown task {task!r}"
        plan = self.plans[task]
        missing = set(plan.input_names) - inputs.keys()
        extra = inputs.keys() - set(plan.input_names)
        assert not missing and not extra, \
            f"task {task!r}: missing inputs {sorted(missing)}, " \
            f"unexpected inputs {sorted(extra)}"
        shapes = plan.meta["input_shapes"]
        for name, value in inputs.items():
            got = tuple(np.shape(value))
            want = tuple(shapes[name])
            assert got == want, \
                f"task {task!r}, input {name!r}: expected per-sample " \
                f"shape {want}, got {got}"
        t = obs.now()
        if deadline_ms is None:
            deadline_ms = self.slo_ms
        deadline_s = None if deadline_ms is None else t + deadline_ms / 1e3
        req = TaskRequest(next(self._rid), task, inputs, t_submit=t,
                          deadline_s=deadline_s, priority=priority)
        self._c_submitted.inc()
        self.metrics.counter(f"task.{task}.submitted").inc()
        if deadline_s is not None and deadline_s <= t:
            self._c_expired.inc()
            self._finish_unserved(req, t)
            return req
        self.queues[task].append(req)
        return req

    def _finish_unserved(self, req: TaskRequest, now: float) -> None:
        """Terminal state for a request dropped without execution (expired
        at submit, or shed from a queue): done, no result, a miss."""
        req.done = True
        req.shed = True
        req.missed_deadline = True
        req.t_done = now
        self._c_misses.inc()
        self.metrics.counter(f"task.{req.task}.deadline_misses").inc()

    def shed_expired(self, now: float | None = None) -> int:
        """Drop queued requests whose deadline has already passed — they
        would consume a dispatch slot only to be counted late.  Called by
        the SLO scheduler before each pick; a no-op on deadline-free
        queues.  Returns the number shed."""
        now = obs.now() if now is None else now
        shed = 0
        for task, q in self.queues.items():
            if not q or not any(r.deadline_s is not None
                                and r.deadline_s <= now for r in q):
                continue
            keep: deque = deque()
            for r in q:
                if r.deadline_s is not None and r.deadline_s <= now:
                    self._finish_unserved(r, now)
                    self._c_shed.inc()
                    shed += 1
                else:
                    keep.append(r)
            self.queues[task] = keep
        return shed

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def inflight(self) -> int:
        return sum(len(reqs) for reqs, _, _ in self._inflight)

    def inflight_per_device(self) -> list[int]:
        """In-flight batches per device track (lockstep under SPMD — each
        batch occupies every device — so these only differ transiently)."""
        return [len(dq) for dq in self._dev_inflight]

    def stats(self) -> dict:
        """One read over the engine's metrics registry plus the process
        plan/runner-cache effectiveness counters.

        Always safe: on an engine that has harvested zero requests the
        percentiles and ``req_per_s`` are explicit ``None`` (never NaN,
        never a ZeroDivisionError) and every counter is an explicit zero.
        After ``warmup`` a healthy engine shows ``runner_hits`` growing
        and ``runner_misses`` frozen at one per (task, bucket).
        """
        from repro.core.runtime.cache import cache_stats
        completed = self._c_completed.value
        elapsed = (self._t_last_harvest - self._t_first_dispatch
                   if completed and self._t_first_dispatch is not None
                   and self._t_last_harvest is not None else None)
        per_task = {}
        for task in self.models:
            per_task[task] = {
                "submitted": self.metrics.counter(
                    f"task.{task}.submitted").value,
                "completed": self.metrics.counter(
                    f"task.{task}.completed").value,
                "deadline_misses": self.metrics.counter(
                    f"task.{task}.deadline_misses").value,
                "req_per_s": (self.metrics.counter(
                    f"task.{task}.completed").value / elapsed
                    if elapsed else None),
            }
        self.metrics.gauge("pending").set(self.pending())
        self.metrics.gauge("inflight").set(self.inflight())
        goodput = self._c_goodput.value
        misses = self._c_misses.value
        # every terminal request lands in exactly one of goodput/misses
        # (shed and expired-at-submit requests are misses), so the miss
        # rate denominator is all finished work
        finished = goodput + misses
        graph_stats = {
            task: {g: {
                "submitted": self.metrics.counter(
                    f"graph.{task}.g{g}.submitted").value,
                "pad_nodes": self.metrics.counter(
                    f"graph.{task}.g{g}.pad_nodes").value,
            } for g in sizes}
            for task, sizes in self.graph_buckets.items()}
        return {"completed": completed, "steps": self.steps,
                "graph_buckets": graph_stats,
                "submitted": self._c_submitted.value,
                "pending": self.pending(), "inflight": self.inflight(),
                "tasks": len(self.models), "warmed": len(self._warmed),
                "padded": self._c_padded.value,
                "devices": self._ndev,
                "pad_per_device": [c.value for c in self._c_pad_dev],
                "inflight_per_device": self.inflight_per_device(),
                "scheduler": self.scheduler.name,
                "slo_ms": self.slo_ms,
                "pipeline_depth": self._depth,
                "max_pipeline_depth": self.max_pipeline_depth,
                "goodput": goodput,
                "deadline_misses": misses,
                "shed": self._c_shed.value,
                "expired_at_submit": self._c_expired.value,
                "stalls": self._c_stalls.value,
                "stall_ms_total": self._h_stall.total,
                "stalls_by_call": {
                    call: self.metrics.counter(f"stalls.{call}").value
                    for call in RUNTIME_CALLS},
                "deadline_miss_rate": (misses / finished if finished
                                       else None),
                "goodput_req_per_s": (goodput / elapsed if elapsed
                                      else None),
                "p50_sojourn_ms": self._h_sojourn.percentile(50),
                "p95_sojourn_ms": self._h_sojourn.percentile(95),
                "p50_queue_ms": self._h_queue.percentile(50),
                "p95_queue_ms": self._h_queue.percentile(95),
                "req_per_s": (completed / elapsed if elapsed else None),
                "per_task": per_task,
                **cache_stats()}

    def _bucket(self, n: int, cap: int) -> int:
        b = self._ndev            # floor: at least one row per device
        while b < n and b < cap:
            b *= 2
        return min(b, cap)

    def buckets(self) -> list[int]:
        """Every batch size the engine can dispatch: powers of two from
        the device count (each device needs at least one row) up to
        ``max_batch``."""
        out, b = [], self._ndev
        while b <= self.max_batch:
            out.append(b)
            b *= 2
        return out

    # --------------------------------------------------------- estimation --
    def _plan_cost_seconds(self, task: str) -> float:
        """Per-sample analytic cost of one task: the Step-4b predicted
        seconds of every op's *chosen* kernel, summed over the plan
        (``plan.meta['kernel_choices']``, measured timing when the plan was
        compiled in measured mode).  The scheduler's cold-start estimate;
        clamped positive so ranking never divides through zero."""
        cached = self._plan_cost.get(task)
        if cached is None:
            total = 0.0
            for c in self.plans[task].meta.get("kernel_choices",
                                               {}).values():
                src = c.get("measured_s") or c.get("predicted_s") or {}
                total += src.get(c.get("kernel"), 0.0)
            cached = self._plan_cost[task] = max(total, 1e-9)
        return cached

    def estimate_batch_seconds(self, task: str, bucket: int) -> float:
        """Marginal-latency estimate for one (task, bucket) dispatch: the
        recent mean of that bucket's *measured* service times once it has
        served traffic, the analytic plan cost scaled by the bucket before
        that.  This is what the SLO scheduler corrects deadlines by."""
        h = self.metrics.histogram(f"service_ms.{task}.b{bucket}")
        recent = h.recent_mean(32)
        if recent is not None:
            return recent / 1e3
        return self._plan_cost_seconds(task) * bucket

    def _adapt_depth(self) -> int:
        """One adaptive-depth step, bounded to [1, max_pipeline_depth]:
        deepen while the backlog outgrows the in-flight window (queue
        growth means the device is the bottleneck — more overlap helps);
        under an SLO, shrink when *recent* p95 sojourn nears it (in-flight
        batches are latency a new arrival must wait out) and refuse to
        deepen once past half of it.  Fixed-depth engines
        (``max_pipeline_depth == pipeline_depth``, the default without an
        SLO) never move."""
        if self.max_pipeline_depth > 1:
            grow = self.pending() > self._depth * self.max_batch
            p95 = self._h_sojourn_recent.percentile(95)
            if self.slo_ms is not None and p95 is not None \
                    and p95 >= 0.8 * self.slo_ms:
                self._depth = max(1, self._depth - 1)
            elif grow and (self.slo_ms is None or p95 is None
                           or p95 < 0.5 * self.slo_ms):
                self._depth = min(self.max_pipeline_depth, self._depth + 1)
            self.metrics.gauge("pipeline_depth").set(self._depth)
        return self._depth

    def _runner(self, task: str, bucket: int):
        return self.models[task].batched(bucket, jit=self.jit)

    def _stack(self, samples: list[dict], info: _BatchInfo) -> dict:
        """Batch assembly hook: one host-side ``np.stack`` per input name
        (``serve.stack``), then one device transfer each
        (``serve.transfer``, a timed runtime call); benchmarks override it
        to reconstruct legacy serving paths."""
        with obs.span("serve.stack", cat="serve"):
            host = stack_host(samples)
        t0 = obs.now()
        with obs.span("serve.transfer", cat="serve"):
            stacked = {k: jnp.asarray(v) for k, v in host.items()}
        self._runtime_call("transfer", t0, info)
        return stacked

    def _runtime_call(self, call: str, t0: float, info: _BatchInfo
                      ) -> float:
        """Close the timing of one call into the runtime begun at ``t0``
        (an ``obs.now()`` reading) and return now: a call slower than
        ``STALL_MS`` counts under ``stalls`` and ``stalls.<call>``, is
        observed by the ``stall_ms`` histogram, and with tracing on is a
        ``serve.stall`` event naming the call and the batch."""
        t1 = obs.now()
        ms = (t1 - t0) * 1e3
        if ms > STALL_MS:
            self._c_stalls.inc()
            self.metrics.counter(f"stalls.{call}").inc()
            self._h_stall.observe(ms)
            obs.complete("serve.stall", t0, t1, cat="serve", call=call,
                         batch_id=info.batch_id, bucket=info.bucket)
        return t1

    # ------------------------------------------------------------ warmup --
    def warmup(self, tasks=None, buckets=None) -> set[tuple[str, int]]:
        """AOT-compile every (task, bucket) runner before traffic arrives.

        Each runner is built (populating the plan/runner cache — the only
        ``runner_misses`` a healthy server ever records) and its jitted
        program traced + XLA-compiled from the plan's recorded input
        shapes (``run.aot_compile()``), so no live request pays tracing
        or compilation.  Returns the set of (task, bucket) pairs now
        compiled; with ``jit=False`` there is nothing to compile and the
        set stays empty.
        """
        tasks = list(self.models) if tasks is None else list(tasks)
        buckets = self.buckets() if buckets is None else list(buckets)
        for task in tasks:
            assert task in self.models, f"unknown task {task!r}"
            for bucket in buckets:
                with obs.span("serve.warmup", cat="serve", task=task,
                              bucket=bucket):
                    run = self._runner(task, bucket)
                    if run.aot_compile() is not None:
                        self._warmed.add((task, bucket))
        return set(self._warmed)

    # ---------------------------------------------------------- dispatch --
    def dispatch(self, *, draining: bool = False) -> int:
        """Launch one batch without blocking on its results; returns the
        number of requests dispatched (0 when the scheduler has nothing to
        run — every queue empty, or a deferring policy waiting).

        *What* to launch is the scheduler's decision (one ``Decision`` per
        call, traced as a ``serve.schedule`` span): oldest-head-first
        under the default FIFO policy — same-task requests coalesce into
        one batched launch, no task starves under sustained load on
        another — or service-corrected earliest-deadline-first under the
        SLO policy.  ``draining=True`` tells a deferring policy no more
        arrivals are coming.

        Outputs stay as in-flight device arrays — JAX's async dispatch
        means the host returns here immediately and can assemble the next
        batch while the device executes this one.

        Under a mesh, requests are placed round-robin across the device
        shards: padded position ``j`` lands on device ``j % ndev``, and
        since ``NamedSharding(P("data"))`` splits dim 0 into contiguous
        blocks of ``bucket // ndev`` rows, ``j``'s stacked row is
        ``(j % ndev) * (bucket // ndev) + j // ndev``.  Pad positions
        (``take..bucket-1``) thereby spread (near-)evenly across devices
        instead of piling onto the last shard."""
        if not self.pending():
            return 0           # no span: poll spins here between arrivals
        with obs.span("serve.schedule", cat="serve",
                      policy=self.scheduler.name, pending=self.pending(),
                      inflight=len(self._inflight),
                      depth=self._depth) as sp:
            d = self.scheduler.pick(self, draining=draining)
            if d is not None:
                sp.set(task=d.task, take=d.take, bucket=d.bucket,
                       reason=d.reason)
                if d.slack_ms is not None:
                    sp.set(slack_ms=round(d.slack_ms, 3))
        if d is None:
            return 0
        task, take, bucket = d.task, d.take, d.bucket
        queue = self.queues[task]
        assert 1 <= take <= len(queue) and take <= bucket <= self.max_batch, \
            f"scheduler decision {d} invalid for queue of {len(queue)}"
        reqs = [queue.popleft() for _ in range(take)]
        padded = reqs + [reqs[-1]] * (bucket - take)
        ndev = self._ndev
        rows = tuple((j % ndev) * (bucket // ndev) + j // ndev
                     for j in range(bucket))      # identity when ndev == 1
        samples: list = [None] * bucket
        for j, r in enumerate(rows):
            samples[r] = padded[j].inputs
        shard_n = tuple(sum(1 for j in range(take) if j % ndev == d)
                        for d in range(ndev))
        pad_per_dev = tuple(sum(1 for j in range(take, bucket)
                                if j % ndev == d) for d in range(ndev))
        t0 = obs.now()
        info = _BatchInfo(self._c_dispatches.value, task, bucket,
                          bucket - take, t0, devices=ndev, rows=rows,
                          shard_n=shard_n, pad_per_dev=pad_per_dev)
        run = self._runner(task, bucket)
        stacked = self._stack(samples, info)
        t_launch = obs.now()
        with obs.span("serve.launch", cat="serve"):
            outs = run(**stacked)
        t1 = self._runtime_call("launch", t_launch, info)
        if obs.enabled():
            # one retroactive dispatch span per device track (exactly one
            # on a single-device engine): the global batch identity plus
            # this shard's real-row/pad split
            for d in range(ndev):
                obs.complete("serve.dispatch", t0, t1, cat="serve",
                             task=task, bucket=bucket,
                             batch_id=info.batch_id, n=take, pad=info.pad,
                             device=d, shard_n=shard_n[d],
                             shard_pad=pad_per_dev[d])
        if self._t_first_dispatch is None:
            self._t_first_dispatch = info.t_dispatch
        for r in reqs:
            r.t_dispatch = info.t_dispatch
        self._inflight.append((reqs, outs, info))
        for dq in self._dev_inflight:
            dq.append(info)
        self._c_dispatches.inc()
        self._c_padded.inc(info.pad)
        for d in range(ndev):
            if pad_per_dev[d]:
                self._c_pad_dev[d].inc(pad_per_dev[d])
        return len(reqs)

    def harvest(self) -> int:
        """Materialize the oldest in-flight batch (blocks until the device
        finishes it); returns requests completed, 0 if nothing in flight.

        Each batched output transfers to the host *once* and is sliced
        per-request there (copies, so results don't pin the padded batch
        buffers) — per-request ``np.asarray(o[i])`` device slices cost
        O(batch) transfers per output name."""
        if not self._inflight:
            return 0
        reqs, outs, info = self._inflight.popleft()
        for dq in self._dev_inflight:
            if dq:
                dq.popleft()
        t0 = obs.now()
        with obs.span("serve.wait", cat="serve"):
            jax.block_until_ready(outs)    # waiting, told apart from copying
        t_copy = self._runtime_call("wait", t0, info)
        with obs.span("serve.copy", cat="serve"):
            mats = [np.asarray(o) for o in outs]
        done = self._runtime_call("copy", t_copy, info)
        traced = obs.enabled()
        if traced:
            # one retroactive harvest span per device track (exactly one
            # on a single-device engine)
            for d in range(info.devices):
                obs.complete("serve.harvest", t0, done, cat="serve",
                             task=info.task, batch_id=info.batch_id,
                             bucket=info.bucket, n=len(reqs), device=d,
                             shard_n=(info.shard_n[d] if info.shard_n
                                      else len(reqs)))
        with obs.span("serve.unpack", cat="serve"):
            # measured service time of this (task, bucket) — the scheduler's
            # warm estimate (estimate_batch_seconds) reads its recent mean
            self.metrics.histogram(
                f"service_ms.{info.task}.b{info.bucket}").observe(
                (done - info.t_dispatch) * 1e3)
            rows = info.rows
            for i, req in enumerate(reqs):
                row = rows[i] if rows else i    # undo the shard placement
                req.result = tuple(np.array(m[row]) for m in mats)
                req.done = True
                req.t_done = done
                sojourn_ms = (done - req.t_submit) * 1e3
                self._h_sojourn.observe(sojourn_ms)
                self._h_sojourn_recent.observe(sojourn_ms)
                self._h_queue.observe((req.t_dispatch - req.t_submit) * 1e3)
                if req.deadline_s is not None and done > req.deadline_s:
                    req.missed_deadline = True
                    self._c_misses.inc()
                    self.metrics.counter(
                        f"task.{req.task}.deadline_misses").inc()
                else:
                    self._c_goodput.inc()   # deadline-free completions count
                if traced:
                    # retroactive per-request span: the whole sojourn, from
                    # enqueue through this harvest
                    obs.complete("request", req.t_submit, done, cat="serve",
                                 rid=req.rid, task=req.task,
                                 batch_id=info.batch_id, bucket=info.bucket,
                                 pad=info.pad, device=i % info.devices,
                                 queued_ms=round(
                                     (req.t_dispatch - req.t_submit) * 1e3, 3))
            self._c_completed.inc(len(reqs))
            self.metrics.counter(f"task.{info.task}.completed").inc(len(reqs))
            self._t_last_harvest = done
        return len(reqs)

    # -------------------------------------------------------------- step --
    def step(self) -> int:
        """Synchronous serving step (dispatch one batch, harvest everything
        in flight); returns requests dispatched.  The pipelined path is
        ``run`` — ``step`` keeps the old blocking contract for callers that
        need results materialized before the next submit."""
        n = self.dispatch()
        while self._inflight:
            self.harvest()
        return n

    def run(self, max_steps: int = 10_000) -> int:
        """Drain every queue (the closed-batch path); returns requests
        served.  Under the default FIFO policy this is bit-for-bit the
        pre-stream engine — continuous batching degenerates to batch
        draining; under the SLO policy the scheduler reorders (and sheds)
        within the same loop.

        Pipelined: keeps up to the current adaptive depth of batches in
        flight (``== pipeline_depth`` unless ``max_pipeline_depth``/SLO
        configured otherwise), so host-side batch assembly overlaps device
        execution of the previous batch."""
        served = 0
        for _ in range(max_steps):
            n = self.dispatch(draining=True)
            if n == 0 and not self._inflight:
                break          # dispatch()==0 means every queue is empty
            if n == 0 or max(len(dq) for dq in self._dev_inflight) \
                    >= self._depth:
                served += self.harvest()
                self._adapt_depth()
        while self._inflight:
            served += self.harvest()
        return served

    # -------------------------------------------------------- stream pump --
    def _oldest_ready(self) -> bool:
        """True when the oldest in-flight batch has finished on device —
        harvesting it will not block.  ``jax.Array.is_ready`` is the async
        completion probe; outputs without it (jit=False numpy paths) count
        as ready, which only costs an early materialize."""
        if not self._inflight:
            return False
        _, outs, info = self._inflight[0]
        t0 = obs.now()
        ready = all(getattr(o, "is_ready", lambda: True)() for o in outs)
        self._runtime_call("ready", t0, info)
        return ready

    def poll(self, *, draining: bool = False) -> tuple[int, int]:
        """One non-blocking pump of the continuous-batching loop; returns
        ``(dispatched, harvested)`` request counts.

        Opportunistically harvests every in-flight batch the device has
        already finished, dispatches while the scheduler has work and the
        in-flight window has room (the current adaptive depth), and only
        blocks on the oldest batch when the window is full (or the stream
        is draining) with nothing else to do — exactly when blocking is
        the only way to make progress.  One ``_adapt_depth`` step per
        call keeps the window tracking queue growth and SLO headroom."""
        harvested = 0
        while self._oldest_ready():
            harvested += self.harvest()
        dispatched = 0
        while max(len(dq) for dq in self._dev_inflight) < self._depth:
            n = self.dispatch(draining=draining)
            if n == 0:
                break
            dispatched += n
        if not dispatched and not harvested and self._inflight \
                and (draining or
                     max(len(dq) for dq in self._dev_inflight)
                     >= self._depth):
            harvested += self.harvest()
        self._adapt_depth()
        return dispatched, harvested

    def stream(self, arrivals, *, max_wall_s: float | None = None) -> list:
        """Replay an open-loop arrival schedule against the wall clock;
        returns one ``TaskRequest`` per arrival (all terminal: served, or
        shed with ``result=None``).

        ``arrivals`` is an iterable of ``(at_s, task, inputs)`` tuples —
        optionally ``(at_s, task, inputs, deadline_ms)`` or
        ``(..., deadline_ms, priority)`` — with ``at_s`` relative to the
        stream start.  Open-loop means arrivals are not gated on service
        (the generator keeps its schedule even when the engine falls
        behind — the honest way to measure an overloaded server);
        ``submit`` happens when the wall clock reaches ``at_s``, the loop
        pumps ``poll()`` between arrivals, and returns once every request
        is terminal (or ``max_wall_s`` elapses, a hang stop for tests)."""
        import time
        sched = sorted(arrivals, key=lambda a: a[0])
        reqs: list[TaskRequest] = []
        t0 = obs.now()
        i, n = 0, len(sched)
        while True:
            rel = obs.now() - t0
            while i < n and sched[i][0] <= rel:
                at, task, inputs, *rest = sched[i]
                deadline_ms = rest[0] if len(rest) >= 1 else None
                priority = rest[1] if len(rest) >= 2 else 0
                reqs.append(self.submit(task, deadline_ms=deadline_ms,
                                        priority=priority, **inputs))
                i += 1
            draining = i >= n
            dispatched, harvested = self.poll(draining=draining)
            if draining and not self.pending() and not self._inflight:
                break
            if max_wall_s is not None and obs.now() - t0 > max_wall_s:
                break
            if not dispatched and not harvested and i < n:
                wait = sched[i][0] - (obs.now() - t0)
                if wait > 0:           # idle until the next arrival
                    time.sleep(min(wait, 1e-3))
        return reqs
