"""Chip smoke test: the main path, gcv.compile -> gcv.serve, on a TPU.

    python chip_smoke.py             # one chip: b4, b3-r50 and b6-dyn
    python chip_smoke.py --chips 4   # four chips: b3-r50 batch-sharded

One chip.  The three paper tasks are built at their published size
(b4: 150 frames x 25 joints ST-GCN; b3-r50: 224x224 ResNet-50 DualGCN;
b6-dyn: 1024 points, k=20, graph built per request) from their plain-JAX
definitions in ``gnncv/jax_tasks.py`` with seeded random weights, and
compiled with the defaults (``target="tpu"``, ``kernels="auto"``).  Between
them they take every Pallas kernel that ``auto`` binds on a TPU, and the
script checks that it does: the kernel counts, and a ``tpu_custom_call``
per Pallas op in each compiled batch-8 program (an interpreted kernel has
none).  One engine serves the three (``max_batch=8``, ``warmup=True``);
seeded requests are stepped through it until each is answered, with no
compile after warmup (``runner_misses`` frozen).  Each answer is compared
with the same plain-JAX function run under ``jax.jit``.

Both sides run at HIGHEST matmul precision, so the comparison measures
how the system realizes the model (kernels, fusion, batching, padding)
and not the chip's single-pass bf16 rounding, which is the default
precision's speed trade.  The gate is the relative error
``|served - reference|_F / |reference|_F`` over a task's requests, below
``TOL`` — fp32 reassociation and an occasional reordered KNN tie stay
orders of magnitude under it; a wrong kernel or a misplaced row lands
near 1.

Four chips (``--chips 4``).  Only the sharded path runs: b3-r50 served by
``gcv.serve(devices=4)`` at batch 8, against the single-device batch-8
runner on the same inputs, within ``SHARD_TOL``.  On fewer than four
chips it fails, since a data mesh never serves on fewer devices than asked.

The script exits non-zero, with no result line, unless JAX's backend is a
TPU.  Its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import jax
import numpy as np

TOL = 1e-2          # served vs plain-JAX reference, relative Frobenius
SHARD_TOL = 1e-4    # devices=4 vs single device, same batch, relative
N_REQUESTS = 6      # per task: one batch of 8 with two padded rows
# Pallas kernels that kernels="auto" binds on a TPU at published size,
# with the number of ops each task binds them to
PALLAS = {"b4": {"pallas_ell_spdmm": 9},
          "b3-r50": {"pallas_sddmm": 1},
          "b6-dyn": {"pallas_knn": 1}}
TASKS = tuple(PALLAS)


def log(*args) -> None:
    print(*args, flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check ends the run (an ``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(msg)


def request_inputs(task: str, specs: dict, seed: int) -> dict:
    """Seeded per-request inputs; b6-dyn clouds vary in their valid count
    (the mask zeroes the padded points)."""
    rng = np.random.default_rng(seed)
    out = {name: rng.standard_normal(s.shape).astype(np.float32)
           for name, s in specs.items()}
    if "mask" in out:
        n = out["mask"].shape[0]
        valid = n - n // 16 * (seed % 4)
        out["mask"] = (np.arange(n) < valid).astype(np.float32)
    return out


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def build(gcv, jax_tasks, task: str):
    fn, example = jax_tasks.TRACED_TASKS[task]()
    return fn, example, gcv.compile(fn, example, name=task)


def check_kernels(task: str, model) -> None:
    counts = model.stats()["kernels"]
    pallas = {k: v for k, v in counts.items() if k.startswith("pallas_")}
    log(f"{task}: kernels {dict(sorted(counts.items()))}")
    require(pallas == PALLAS[task],
            f"{task}: Pallas bindings {pallas}, expected {PALLAS[task]}")
    hlo = model.batched(8, jit=True).aot_compile(explicit=True).as_text()
    n_calls = hlo.count("tpu_custom_call")
    want = sum(PALLAS[task].values())
    log(f"{task}: batch-8 program holds {n_calls} tpu_custom_call "
        f"(Pallas ops: {want})")
    require(n_calls >= want,
            f"{task}: {n_calls} tpu_custom_call for {want} Pallas ops")


def serve_all(gcv, obs, engine_models: dict, inputs: dict, **kw):
    """Serve every task's requests, stepping until each is answered;
    returns (engine, {task: [first output per request]}, warmup seconds)."""
    t0 = obs.now()
    eng = gcv.serve(engine_models, max_batch=8, warmup=True, **kw)
    t_warm = obs.now() - t0
    misses = eng.stats()["runner_misses"]
    reqs = {task: [eng.submit(task, **x) for x in xs]
            for task, xs in inputs.items()}
    pending = [r for rs in reqs.values() for r in rs]
    for _ in range(100):
        if all(r.done for r in pending):
            break
        eng.step()
    require(all(r.done and r.result is not None for r in pending),
            "engine left requests unanswered")
    after = eng.stats()["runner_misses"]
    require(after == misses,
            f"runner_misses moved from {misses} to {after} after warmup")
    return eng, {t: [r.result[0] for r in rs] for t, rs in reqs.items()}, \
        t_warm


def one_chip(gcv, obs, jax_tasks) -> None:
    t0 = obs.now()
    built = {task: build(gcv, jax_tasks, task) for task in TASKS}
    t_compile = obs.now() - t0
    models = {task: m for task, (_, _, m) in built.items()}
    inputs = {task: [request_inputs(task, m.input_specs, seed)
                     for seed in range(N_REQUESTS)]
              for task, m in models.items()}
    eng, served, t_warm = serve_all(gcv, obs, models, inputs)
    log(f"cold compile: gcv.compile of {len(TASKS)} tasks "
        f"{t_compile:.1f} s, serve warmup of {eng.stats()['warmed']} "
        f"runners (buckets {eng.buckets()}) {t_warm:.1f} s")
    for task, model in models.items():
        check_kernels(task, model)
    for task, (fn, example, _) in built.items():
        ref = jax.jit(fn)
        want = [np.asarray(ref(**x)) for x in inputs[task]]
        got = served[task]
        require(all(g.shape == w.shape for g, w in zip(got, want)),
                f"{task}: served shape {got[0].shape}, reference "
                f"{want[0].shape}")
        require(all(np.isfinite(g).all() for g in got),
                f"{task}: non-finite served output")
        err = rel_err(np.stack(got), np.stack(want))
        max_abs = max(float(np.abs(g - w).max())
                      for g, w in zip(got, want))
        log(f"{task}: {len(got)} requests, output {got[0].shape}, rel err "
            f"{err:.3e} (tol {TOL:.0e}), max abs err {max_abs:.3e}")
        require(err < TOL, f"{task}: rel err {err:.3e} >= {TOL:.0e}")


def four_chips(gcv, obs, jax_tasks) -> None:
    task = "b3-r50"
    fn, example = jax_tasks.TRACED_TASKS[task]()
    sharded = gcv.compile(fn, example, name=task, devices=4)
    single = gcv.compile(fn, example, name=task)
    xs = [request_inputs(task, single.input_specs, seed)
          for seed in range(8)]
    eng, served, t_warm = serve_all(gcv, obs, {task: sharded}, {task: xs},
                                    devices=4)
    log(f"cold compile: serve warmup (devices=4, buckets {eng.buckets()})"
        f" {t_warm:.1f} s")
    require(eng.stats()["devices"] == 4, "engine is not on 4 devices")
    (want,) = single.batched(8, jit=True)(**gcv.stack_inputs(xs))
    err = rel_err(np.stack(served[task]), np.asarray(want))
    log(f"{task}: devices=4 vs single device at batch 8: rel err "
        f"{err:.3e} (tol {SHARD_TOL:.0e})")
    require(err < SHARD_TOL,
            f"sharded rel err {err:.3e} >= {SHARD_TOL:.0e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro import gcv, obs
    from repro.gnncv import jax_tasks
    dev = jax.devices()[0]
    log(f"jax {jax.__version__}, {len(jax.devices())} x {dev.device_kind}, "
        f"compile cache {gcv.use_compile_cache()}")
    with jax.default_matmul_precision("highest"):
        if args.chips == 4:
            four_chips(gcv, obs, jax_tasks)
        else:
            one_chip(gcv, obs, jax_tasks)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
