"""Plan executor — a thin driver over the op-registry runtime.

The software analogue of the GCV-Turbo APU: it walks the ``ExecutionPlan``
instruction sequence and dispatches every op through
``repro.core.runtime.run_op`` (per-kind handlers registered with
``@register_op``; each op executes the realization Step 4b bound to it —
``op.kernel`` — so one plan can mix Pallas and XLA kernels op by op.  The
``use_pallas`` argument survives only as the legacy dispatch for
kernel-less plans).  Weights and compile-time ELL structures are
**device-resident plan state** (``runtime/residency.py``): collected and
uploaded once per runner, deduplicated by array identity, and threaded
through ``jax.jit`` as an *argument* pytree — the paper's parameters
resident in on-chip buffers, rather than constants re-embedded into every
traced bucket program.  ``residency=False`` restores the legacy
closure-constant behaviour.

Runtime behaviours the seed executor lacked:

  * **liveness freeing** — Step 6 annotates each op with the env entries it
    kills; the driver drops them as soon as they die (``free_dead=True``).
    Under eager execution (``jit=False``) this genuinely releases buffers,
    so the working set follows ``ExecutionPlan.peak_live_bytes()`` instead
    of growing monotonically.  Under ``jax.jit``/``vmap`` the pops happen
    at trace time — they release tracer references, and XLA's own buffer
    liveness (which the Step-6 annotations mirror) governs actual memory;
    ``peak_live_bytes()`` is the planner's model of that working set, not
    a measurement of the compiled program;
  * **batched execution** — ``build_runner(plan, batch=N)`` vmaps the whole
    per-sample program over a new leading axis.  Compile-time weights and
    COO/ELL structures broadcast; only activations gain the batch axis.
    This is the paper's whole-task execution argument applied to serving:
    one compiled program amortized over N requests;
  * **AOT warmup** — ``run.aot_compile()`` traces and compiles the jitted
    program from the plan's recorded input shapes, so a serving process can
    pay every trace/compile *before* traffic arrives and no live request
    ever blocks on compilation (the §VII-D2 fixed-latency argument).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plan import ExecutionPlan
from repro.core.runtime import run_op
from repro.core.runtime.context import batched_execution
from repro.core.runtime.residency import collect_params

# Back-compat alias: tests and notebooks poke single ops through the old
# executor entry point; dispatch now lives in the registry.
_run_op = run_op


def build_runner(plan: ExecutionPlan, *, use_pallas: bool = False,
                 jit: bool | None = None, batch: int | None = None,
                 free_dead: bool = True, residency: bool = True,
                 weights_as_args: bool | None = None,
                 mesh=None) -> Callable[..., tuple]:
    """Returns ``run(**inputs) -> tuple(outputs)``.

    ``use_pallas`` is a legacy shim: compiled plans carry per-op kernel
    bindings (``op.kernel``, Step 4b) that fully determine dispatch; the
    flag only affects kernel-less ops (hand-built plans, old pickles),
    reconstructing the pre-selection global-flag behaviour.

    ``batch=None`` preserves the per-sample contract; ``batch=N`` expects
    every input stacked on a new leading axis of size N and returns outputs
    with the same leading axis.

    ``jit=None`` resolves to whole-program jit for per-sample runners and
    per-op dispatch for batched ones: XLA's whole-program fusion reorders
    float accumulation differently per batch size, so only the per-op path
    is bit-for-bit identical across ``batch`` values.  Serving passes
    ``jit=True`` explicitly — throughput over bit-stability.

    ``residency=True`` (default) collects the plan's weights/ELL/COO arrays
    into one deduplicated device-resident pytree at build time, so handlers
    never re-stage host arrays per call; ``residency=False`` restores the
    legacy per-call ``jnp.asarray`` staging.

    ``weights_as_args`` controls how the resident pytree enters a *jitted*
    program.  ``None`` resolves to ``batch is not None``:

      * serving/batched runners pass it as a jit **argument** — tracing no
        longer embeds per-bucket weight constants (trace time and program
        size stop scaling with parameter bytes) and ``resident.swap`` takes
        effect without retracing;
      * per-sample whole-program runners keep weights as trace
        **constants**: XLA folds and fuses constant weights differently
        from parameters, and the ``tests/golden/`` numerics are pinned to
        the constant-weights program.  Eager (``jit=False``) runners always
        read the resident store live, so the flag only matters under jit.

    The returned ``run`` carries runner-level plan state:

      ``run.resident``      the ``ResidentParams`` (None when residency off)
      ``run.aot_compile()`` trace+compile ahead of traffic (jit only);
                            non-None once warm — ``explicit=True`` for the
                            standalone lowered executable
      ``run.trace_count()`` how many times the program body was traced
      ``run.mesh``          the data mesh the batch axis is sharded over
                            (None for single-device runners)

    ``mesh`` (a 1-D ``("data",)`` mesh) shards the **batch axis** across
    the mesh's devices: inputs/outputs carry a
    ``NamedSharding(mesh, P("data"))``, the resident weight pytree is
    replicated (one upload per device), and each device runs the batched
    program on its ``batch / devices`` rows (``shard_map``).  Requires
    ``batch`` divisible by the device count; a one-device mesh falls back
    to the plain single-device runner.  Per-sample math is untouched, so
    outputs match the single-device runner at the same batch up to float
    accumulation order.
    """
    if mesh is not None and mesh.size == 1:
        mesh = None                      # the existing single-device path
    if mesh is not None:
        assert batch is not None, \
            "mesh= shards the batch axis; build with batch=N"
        assert batch % mesh.size == 0, \
            f"batch {batch} must be divisible by the mesh's " \
            f"{mesh.size} devices (the serving engine's bucket rule)"
        assert jit is not False, \
            "sharded runners execute through whole-program jit; " \
            "mesh= is incompatible with jit=False"
        jit = True
        assert weights_as_args is not False, \
            "sharded runners thread the replicated weight store through " \
            "jit as an argument; mesh= is incompatible with " \
            "weights_as_args=False"
    if jit is None:
        jit = batch is None
    if weights_as_args is None:
        weights_as_args = batch is not None
    # When the jitted program bakes weights in as constants, a device-side
    # store would hold a second, never-read copy of every parameter — keep
    # host references instead (the trace embeds values either way) and
    # refuse hot-swaps, which could only return stale results there.
    bakes_constants = jit and not weights_as_args
    with obs.span("build_runner", cat="runtime", plan=plan.name,
                  batch=batch, jit=bool(jit), residency=residency,
                  devices=(mesh.size if mesh is not None else 1)) as sp:
        resident = collect_params(plan, device=not bakes_constants,
                                  mesh=mesh) \
            if residency else None
        if resident is not None:
            sp.set(resident_bytes=resident.nbytes())
    if resident is not None and bakes_constants:
        resident.trace_constants = True
    traces = {"n": 0}

    def run_single(env: dict, arrays):
        params = resident.bind(arrays) if resident is not None else None
        for op in plan.ops:
            env[op.name] = run_op(op, env, use_pallas, params)
            if free_dead:
                for name in op.frees:
                    env.pop(name, None)
        return tuple(env[o] for o in plan.outputs)

    def run_impl(arrays, env):
        traces["n"] += 1
        if batch is None:
            return run_single(env, arrays)
        with batched_execution():
            return jax.vmap(run_single, in_axes=(0, None))(env, arrays)

    if weights_as_args:
        if mesh is not None:
            # Batch sharding: the resident pytree replicates (one copy per
            # device), every input/output shards its leading batch axis
            # over the 1-D data mesh, and each device runs the batched
            # program on its own rows.  shard_map, not automatic
            # partitioning: XLA cannot partition a Mosaic (Pallas) kernel.
            # Specs are pytree prefixes over run_impl's (arrays, env).
            whole, rows = (jax.sharding.PartitionSpec(),
                           jax.sharding.PartitionSpec("data"))
            staged = jax.jit(jax.shard_map(
                run_impl, mesh=mesh, in_specs=(whole, rows),
                out_specs=rows, check_vma=False))
        else:
            staged = jax.jit(run_impl) if jit else run_impl
    else:
        # Closure-bind the resident store: under jit the device arrays
        # become trace constants (the golden-pinned program); eager reads
        # the store live either way.
        def run_const(env):
            arrays = resident.arrays if resident is not None else {}
            return run_impl(arrays, env)

        staged = jax.jit(run_const) if jit else run_const
    aot = {"primed": None, "exe": None}

    def input_specs() -> dict:
        shapes = plan.meta.get("input_shapes", {})
        spec = {}
        for name in plan.input_names:
            shape = shapes.get(name)
            assert shape is not None, \
                f"no recorded input shape for {name!r}; cannot AOT-compile"
            if batch is not None:
                shape = (batch,) + tuple(shape)
            spec[name] = jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        return spec

    def aot_compile(explicit: bool = False):
        """Pay the jit trace + XLA compile now, from the plan's recorded
        input shapes — the serving warmup hook.  No-op (returns None) for
        eager runners.

        The default primes the jitted function's C++ fast-path dispatch
        cache with one zeros-filled call (one trace + one XLA compile) —
        that cache is what live traffic dispatches through, and it is the
        cheapest warmup (the standalone ``Compiled`` wrapper's Python call
        path is measurably slower per dispatch, and this jax version keeps
        the AOT and dispatch caches separate).  ``explicit=True``
        additionally materializes the ``lower().compile()`` executable —
        the inspectable AOT artifact (cost analysis, serialization) — at
        the cost of a second XLA compile of the same program."""
        if not jit:
            return None
        with obs.span("aot_compile", cat="runtime", plan=plan.name,
                      batch=batch, explicit=explicit,
                      cached=aot["primed"] is not None):
            arrays = resident.arrays if resident is not None else {}
            if aot["primed"] is None:
                spec = input_specs()
                zeros = {n: jnp.zeros(s.shape, s.dtype)
                         for n, s in spec.items()}
                warm = staged(arrays, zeros) if weights_as_args \
                    else staged(zeros)
                for o in warm:
                    o.block_until_ready()
                aot["primed"] = staged
            if explicit and aot["exe"] is None:
                spec = input_specs()
                aot["exe"] = (staged.lower(arrays, spec).compile()
                              if weights_as_args
                              else staged.lower(spec).compile())
            return aot["exe"] if explicit else aot["primed"]

    def run(**inputs):
        env = {k: jnp.asarray(v) for k, v in inputs.items()}
        missing = [k for k in plan.input_names if k not in env]
        assert not missing, f"missing inputs: {missing}"
        if batch is not None:
            for k, v in env.items():
                assert v.shape[:1] == (batch,), \
                    f"input {k!r}: expected leading batch axis {batch}, " \
                    f"got shape {v.shape}"
        if weights_as_args:
            arrays = resident.arrays if resident is not None else {}
            return staged(arrays, env)
        return staged(env)

    run.resident = resident
    run.aot_compile = aot_compile
    run.trace_count = lambda: traces["n"]
    run.input_specs = input_specs
    run.mesh = mesh
    return run


def random_inputs(plan: ExecutionPlan, seed: int = 0,
                  input_shapes: dict[str, tuple] | None = None,
                  batch: int | None = None) -> dict:
    """Convenience: dense random inputs for every plan input.

    ``batch=N`` prepends a batch axis (matching ``build_runner(batch=N)``).
    """
    rng = np.random.default_rng(seed)
    out = {}
    shapes = input_shapes or {}
    for op_name in plan.input_names:
        shape = shapes.get(op_name)
        if shape is None:
            # find the input layer's recorded shape via ops that consume it
            shape = plan.meta.get("input_shapes", {}).get(op_name)
        assert shape is not None, f"no shape for input {op_name}"
        if batch is not None:
            shape = (batch,) + tuple(shape)
        out[op_name] = rng.standard_normal(shape).astype(np.float32)
    return out


def stack_host(samples: list[dict]) -> dict:
    """Stack per-sample input dicts into one batched dict of host (NumPy)
    arrays, one ``np.stack`` per input name."""
    assert samples, "empty batch"
    return {k: np.stack([np.asarray(s[k]) for s in samples])
            for k in samples[0]}


def stack_inputs(samples: list[dict]) -> dict:
    """Stack per-sample input dicts into one batched input dict.

    Stacking happens on the host (``stack_host``) so each input name costs
    one device transfer for the whole batch — the previous form staged N
    per-sample device puts and stacked on device, paying N dispatches per
    input name per batch."""
    return {k: jnp.asarray(v) for k, v in stack_host(samples).items()}
