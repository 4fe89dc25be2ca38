"""The unified ``gcv.compile``/``gcv.serve`` façade (ISSUE 5).

A seven-task matrix (b1-b6 via the declarative builder, the traced-only
b7 ViG) pins the façade to the legacy ``build_runner`` path *bit-for-bit*
(per-sample and batched), plus: input-type dispatch (callable / Graph /
ExecutionPlan), batched-example tracing (the ROADMAP tracer-ergonomics
item), lifecycle methods (warmup / aot / swap_weights / stats / lint /
input_specs), engine construction from models, and the deprecation shims
kept for one PR.
"""
import functools

import jax
import numpy as np
import pytest

from repro import gcv
from repro.core import CompileOptions, build_runner, compile_graph
from repro.core.executor import random_inputs, stack_inputs
from repro.core.ir import Graph, GraphBuilder
from repro.core.plan import ExecutionPlan
from repro.core.runtime.cache import cache_stats, clear_caches
from repro.gnncv.jax_tasks import build_traced_task
from repro.gnncv.tasks import build_task

OPTS = CompileOptions(target="fpga")
SEED = 7
TASKS = ["b1", "b2", "b3-r50", "b4", "b5", "b6", "b7"]


@functools.lru_cache(maxsize=None)
def _graph(task) -> Graph:
    # b7 exists only through the tracing frontend
    if task == "b7":
        return build_traced_task(task, small=True)
    return build_task(task, small=True)


@functools.lru_cache(maxsize=None)
def _legacy_plan(task) -> ExecutionPlan:
    return compile_graph(_graph(task), OPTS)


# --------------------------------------------- seven-task parity matrix ----
@pytest.mark.parametrize("task", TASKS)
def test_gcv_compile_matches_legacy_per_sample(task):
    """gcv.compile(graph).run == build_runner(compile_graph(graph)),
    bit-for-bit."""
    model = gcv.compile(_graph(task), options=OPTS)
    ins = random_inputs(model.plan, seed=SEED)
    legacy = build_runner(_legacy_plan(task))(**ins)
    new = model.run(**ins)
    assert len(new) == len(legacy)
    for a, b in zip(new, legacy):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("task", TASKS)
def test_gcv_compile_matches_legacy_batched(task):
    """The façade's batched runners reproduce build_runner(plan, batch=N)
    bit-for-bit, both through .batched(n) and a batch= default."""
    model = gcv.compile(_graph(task), options=OPTS, batch=2)
    samples = [random_inputs(model.plan, seed=s) for s in range(2)]
    stacked = stack_inputs(samples)
    legacy = build_runner(_legacy_plan(task), batch=2)(**stacked)
    via_run = model.run(**stacked)               # batch=2 is the default
    via_batched = model.batched(2)(**stacked)
    for a, b, c in zip(via_run, legacy, via_batched):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------------------- input-type dispatch -----
def _tiny_fn():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 4)).astype(np.float32)

    def fn(x):
        return jax.nn.relu(x @ w)

    return fn, {"x": jax.ShapeDtypeStruct((6, 8), np.float32)}


def test_compile_accepts_plain_jax_callable():
    fn, example = _tiny_fn()
    model = gcv.compile(fn, example)
    assert model.plan.meta["frontend"] == "tracer"
    x = np.random.default_rng(1).standard_normal((6, 8)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(model.run(x=x)[0]),
                               np.asarray(fn(x)), rtol=1e-5, atol=1e-6)


def test_compile_accepts_execution_plan():
    plan = _legacy_plan("b6")
    model = gcv.compile(plan)
    assert model.plan is plan and model.graph is None
    ins = random_inputs(plan, seed=SEED)
    legacy = build_runner(plan)(**ins)
    for a, b in zip(model.run(**ins), legacy):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "ExecutionPlan" in model.lint()       # nothing to lint, says so


def test_compile_rejects_examples_for_graph_and_plan():
    with pytest.raises(AssertionError, match="example_inputs"):
        gcv.compile(_graph("b6"), {"points": np.zeros((64, 3))})
    with pytest.raises(AssertionError, match="already compiled"):
        gcv.compile(_legacy_plan("b6"), {"points": np.zeros((64, 3))})
    with pytest.raises(AssertionError, match="requires example_inputs"):
        gcv.compile(lambda x: x)
    with pytest.raises(AssertionError, match="cannot compile"):
        gcv.compile(42)


def test_compile_options_as_keywords():
    model = gcv.compile(_graph("b6"), target="fpga", sparsity_aware=False)
    assert model.options == CompileOptions(target="fpga",
                                           sparsity_aware=False)
    assert model.plan.meta["sparsity_aware"] is False
    with pytest.raises(AssertionError, match="not both"):
        gcv.compile(_graph("b6"), options=OPTS, target="fpga")


# ------------------------------------------- batched example tracing -------
def test_batched_example_tracing_parity():
    """Tracing from a *batched* example (leading batch axis on every
    input) strips the axis and compiles the same per-sample plan — the
    ROADMAP tracer-ergonomics item."""
    fn, example = _tiny_fn()
    rng = np.random.default_rng(2)
    xb = rng.standard_normal((4, 6, 8)).astype(np.float32)
    per_sample = gcv.compile(fn, example)
    # auto-detect announces the interpretation (a genuine per-sample
    # leading dim equal to batch would be mis-stripped silently otherwise)
    with pytest.warns(UserWarning, match="batch axis"):
        batched = gcv.compile(fn, {"x": xb}, batch=4)
    assert batched.plan.meta["input_shapes"] == \
        per_sample.plan.meta["input_shapes"]
    # outputs: batch=4 run == 4 independent per-sample runs, bit-for-bit
    outs = np.asarray(batched.run(x=xb)[0])
    legacy = build_runner(per_sample.plan, batch=4)(x=xb)
    np.testing.assert_array_equal(outs, np.asarray(legacy[0]))
    for i in range(4):
        np.testing.assert_array_equal(
            outs[i], np.asarray(per_sample.run(x=xb[i])[0]))


def test_batched_example_explicit_flag():
    fn, _ = _tiny_fn()
    xb = np.zeros((3, 6, 8), np.float32)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # explicit flag: no warning
        model = gcv.compile(fn, {"x": xb}, example_batched=True)
    assert model.batch == 3
    assert model.plan.meta["input_shapes"]["x"] == (6, 8)
    # example_batched=False keeps the leading axis as a model dimension
    kept = gcv.compile(lambda x: jax.nn.relu(x),
                       {"x": np.zeros((3, 6), np.float32)},
                       example_batched=False)
    assert kept.plan.meta["input_shapes"]["x"] == (3, 6)
    with pytest.raises(AssertionError, match="does not match"):
        gcv.compile(fn, {"x": xb}, batch=5, example_batched=True)


# ------------------------------------------------------ lifecycle ----------
def test_warmup_and_aot_freeze_tracing():
    model = gcv.compile(_graph("b6"), options=OPTS)
    assert model.warmup(batches=[1, 2]) == {1, 2}
    run = model.batched(2, jit=True)
    traces = run.trace_count()
    samples = [random_inputs(model.plan, seed=s) for s in range(2)]
    run(**stack_inputs(samples))
    assert run.trace_count() == traces           # warm: no live trace
    assert model.aot_compile() is not None       # default per-sample runner


def test_swap_weights_hot_swaps_without_retrace():
    b = GraphBuilder("swap_me")
    rng = np.random.default_rng(0)
    x = b.input((4, 8), name="x")
    w1 = rng.standard_normal((8, 8)).astype(np.float32)
    w2 = rng.standard_normal((8, 2)).astype(np.float32)
    h = b.linear(x, w1, name="l1")
    h = b.act(h, "relu")
    h = b.linear(h, w2, name="l2")
    model = gcv.compile(b.output(h), options=OPTS)

    samples = [{"x": rng.standard_normal((4, 8)).astype(np.float32)}
               for _ in range(2)]
    stacked = stack_inputs(samples)
    before = np.asarray(model.batched(2, jit=True)(**stacked)[0])

    model.swap_weights({"l1": {"w": w1 * 2.0}})  # first swap: goes private
    run = model.batched(2, jit=True)
    swapped = np.asarray(run(**stacked)[0])
    assert not np.array_equal(before, swapped)
    traces = run.trace_count()
    model.swap_weights({"l1": {"w": w1}})        # second swap: in place
    assert model.batched(2, jit=True) is run     # same compiled program
    restored = np.asarray(run(**stacked)[0])
    np.testing.assert_array_equal(restored, before)
    assert run.trace_count() == traces           # zero retrace

    # per-sample runners bake constants; they rebuild with the new weights
    one = np.asarray(model.run(**samples[0])[0])
    ref = np.asarray(gcv.compile(model.plan).run(**samples[0])[0])
    np.testing.assert_array_equal(one, ref)
    model.swap_weights({("l2", "w"): w2 * 3.0})  # flat-key spelling
    assert not np.array_equal(one, np.asarray(model.run(**samples[0])[0]))


def test_swap_weights_does_not_leak_into_shared_cache():
    """Two CompiledModels over the same graph: a swap on one must not
    change the other's results (the shared runner cache stays pristine)."""
    clear_caches()
    g = _graph("b6")
    a = gcv.compile(g, options=OPTS)
    bm = gcv.compile(g, options=OPTS)
    ins = random_inputs(a.plan, seed=SEED)
    stacked = stack_inputs([ins, ins])
    ref = np.asarray(bm.batched(2, jit=True)(**stacked)[0])
    target = next(op for op in a.plan.ops
                  if op.weights.get("w") is not None)
    a.swap_weights({target.name: {"w": np.asarray(target.weights["w"]) * 5}})
    changed = np.asarray(a.batched(2, jit=True)(**stacked)[0])
    assert not np.array_equal(ref, changed)
    unchanged = np.asarray(bm.batched(2, jit=True)(**stacked)[0])
    np.testing.assert_array_equal(ref, unchanged)


def test_swap_weights_rejects_unknown_slots_and_no_residency():
    model = gcv.compile(_graph("b6"), options=OPTS)
    with pytest.raises(AssertionError, match="unknown weight slots"):
        model.swap_weights({"nope": {"w": np.zeros(1, np.float32)}})
    off = gcv.compile(_graph("b6"), options=OPTS, residency=False)
    with pytest.raises(AssertionError, match="residency"):
        off.swap_weights({"anything": {"w": np.zeros(1, np.float32)}})


def test_input_specs_and_stats_and_lint():
    model = gcv.compile(_graph("b6"), options=OPTS)
    specs = model.input_specs
    assert set(specs) == {"points"}
    assert specs["points"].shape == (64, 3)
    s = model.stats()
    assert s["frontend"] == "builder" and s["ops"] == len(model.plan.ops)
    assert s["resident_bytes"] > 0
    assert "value_deduped_bytes" in s            # the dedup report
    assert s["peak_live_bytes"] == model.plan.peak_live_bytes()
    traced = gcv.compile(_graph("b7"), options=OPTS)
    assert "jaxpr" in traced.lint()              # provenance report
    assert "GraphBuilder" in model.lint()


def test_compiled_model_uses_shared_plan_and_runner_cache():
    clear_caches()
    g = _graph("b6")
    m1 = gcv.compile(g, options=OPTS)
    m2 = gcv.compile(g, options=OPTS)
    assert m1.plan is m2.plan                    # one compile per graph
    assert m1.batched(2, jit=True) is m2.batched(2, jit=True)
    stats = cache_stats()
    assert stats["runner_misses"] == 1 and stats["runner_hits"] == 1


def test_gcv_random_inputs_match_specs():
    model = gcv.compile(_graph("b4"), options=OPTS, batch=3)
    ins = model.random_inputs(seed=0)
    assert ins["skeleton"].shape[0] == 3         # default batch prepended
    per_sample = model.random_inputs(seed=0, batch=None)
    assert per_sample["skeleton"].shape == model.input_specs[
        "skeleton"].shape


# ------------------------------------------------------- gcv.serve ---------
def test_serve_from_mixed_model_inputs():
    """The engine is built from models — a pre-compiled CompiledModel, a
    raw Graph, and a (fn, example) JAX callable — and serves them through
    one queue, with results matching direct runs."""
    fn, example = _tiny_fn()
    pre = gcv.compile(_graph("b6"), options=OPTS)
    eng = gcv.serve({"b6": pre, "b4": _graph("b4"), "user": (fn, example)},
                    options=OPTS, max_batch=2)
    assert set(eng.models) == {"b6", "b4", "user"}
    reqs = []
    for s in range(6):
        task = ("b6", "b4", "user")[s % 3]
        reqs.append(eng.submit(
            task, **random_inputs(eng.plans[task], seed=s)))
    assert eng.run() == 6
    for req in reqs:
        direct = eng.models[req.task].run(**req.inputs)
        for got, want in zip(req.result, direct):
            np.testing.assert_allclose(got, np.asarray(want),
                                       rtol=1e-4, atol=1e-5)


def test_serve_warmup_flag_compiles_every_bucket():
    eng = gcv.serve({"b6": _graph("b6")}, options=OPTS, max_batch=4,
                    warmup=True)
    assert eng.stats()["warmed"] == 3            # buckets 1, 2, 4


def test_serve_rejects_bare_callable_without_examples():
    with pytest.raises(AssertionError, match="example"):
        gcv.serve({"user": lambda x: x}, options=OPTS)


# ------------------------------------------------- deprecation shims -------
def test_pre_facade_shims_are_gone():
    """The one-PR shims ``frontend.compile_model`` and
    ``GNNCVServeEngine(graphs=...)`` are deleted, not deprecated."""
    from repro import frontend
    from repro.serve import GNNCVServeEngine
    assert not hasattr(frontend, "compile_model")
    with pytest.raises(TypeError):
        GNNCVServeEngine(graphs={"b6": _graph("b6")}, options=OPTS)


def test_use_pallas_shim_is_gone():
    """The one-PR ``use_pallas=`` deprecation shim is deleted: the flag is
    now an unknown keyword on every public surface (kernels= is the only
    spelling), caught as an unexpected CompileOptions override on the
    facade and a TypeError on the engine."""
    from repro.serve import GNNCVServeEngine
    g = _graph("b6")
    with pytest.raises(TypeError):
        gcv.compile(g, use_pallas=False)     # not a CompileOptions field
    with pytest.raises(TypeError):
        gcv.serve({"b6": g}, use_pallas=True)
    with pytest.raises(TypeError):
        GNNCVServeEngine({"b6": g}, options=OPTS, max_batch=2,
                         use_pallas=False)


def test_no_deprecated_entry_points_in_repo():
    """The CI grep gate, enforced from tier-1 too: library code, examples
    and benchmarks must go through gcv, not the pre-façade entry points
    (tests are exempt — they pin the legacy path for parity)."""
    import importlib.util
    import pathlib
    tool = pathlib.Path(__file__).parent.parent / "tools" / \
        "lint_deprecated.py"
    spec = importlib.util.spec_from_file_location("lint_deprecated", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.offences() == []


def test_use_compile_cache_env_first_then_fixed_checkout_dir(
        monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; unset, the
    cache goes to one fixed directory at the root of the checkout."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert gcv.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = gcv.use_compile_cache()
        assert path == str(gcv.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        root = gcv.COMPILE_CACHE_DIR.parent
        assert (root / "src" / "repro" / "gcv.py").is_file()
        assert ".jax_cache/" in (root / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
