"""A tiny benchmark for the CPU tests: DGCNN at 64 points and narrow
widths, served and checked through the same harness as the chip cells.

``make(tmp_path)`` writes a checkout-like directory (``BENCHMARK.json``,
a configuration with its served model and reference, two mixes, the real
arrival processes and metric readers, and ``src`` linked to the program)
and returns it with the loaded ``BENCHMARK.json``.  ``restore_jax()`` keeps the persistent compile cache
that a run turns on off, so that other tests in the same process compile
as they would alone.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
TINY_LIMIT = 1e-5

CONFIG = {
    "name": "tiny",
    "sizes": {"n_points": 64, "k": 5, "dims": [8, 16], "emb_dims": 32,
              "hidden": [16, 8], "classes": 10},
    "served": "models/dgcnn.py",
    "reference": "configs/dgcnn.py",
    "inputs": {"points": {"shape": [64, 3], "fill": "normal"}},
    "serve": {"max_batch_per_chip": 4, "pipeline_depth": 2,
              "scheduler": "fifo", "kernels": "auto",
              "matmul_precision": "highest"},
    "work": {"flops_per_request": 1.0e6,
             "knn": [{"n": 64, "d": 3, "k": 5}, {"n": 64, "d": 8, "k": 5}]},
    "check": {"sample": 16, "chunk": 4, "median_rel_err": TINY_LIMIT,
              "misplaced": 0},
}


def make(tmp_path) -> tuple[pathlib.Path, dict]:
    root = pathlib.Path(tmp_path)
    b = root / "bench"
    (b / "configs").mkdir(parents=True)
    (b / "traffic").mkdir()
    for sub in ("metrics", "arrivals", "models"):
        shutil.copytree(HERE / sub, b / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    shutil.copy(HERE / "configs" / "dgcnn.py", b / "configs" / "dgcnn.py")
    (b / "traffic" / "closed.json").write_text(json.dumps(
        {"arrivals": "closed", "clients": 16, "pool": 8}))
    (b / "traffic" / "streams.json").write_text(json.dumps(
        {"arrivals": "periodic", "streams": 4, "rate_hz": 50,
         "phases_ms": [0.0, 1.0, 5.0, 13.0], "pool": 8}))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.sat", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "test"},
        {"name": "tiny.streams", "config": "tiny", "traffic": "streams",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            tput = m["name"] == "throughput_rps" or ".tput" in m["name"]
            m["workloads"] = ["tiny.sat"] if tput else ["tiny.streams"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench


@contextlib.contextmanager
def restore_jax():
    """Runs inside keep JAX's persistent compile cache off, and leave the
    settings as they found them."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
