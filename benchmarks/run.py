"""Benchmark driver — one section per paper table/figure plus the LM-cell
roofline summary. ``PYTHONPATH=src python -m benchmarks.run``"""
from __future__ import annotations

import sys
import time

from repro import gcv

SECTIONS = [
    ("Table II / Fig.9 — GNN-CV tasks b1-b6 (modelled latency)",
     "benchmarks.table2_tasks"),
    ("Fig.2 / Fig.10 / Table VII — portion breakdown + DM elimination",
     "benchmarks.fig2_breakdown"),
    ("Table VIII / XI — standalone CNNs c1-c5",
     "benchmarks.table8_cnns"),
    ("Table IX / XII — standalone GNNs g1-g3",
     "benchmarks.table9_gnns"),
    ("§VII-C — layer-fusion ablation", "benchmarks.ablation_fusion"),
    ("§VII-C — sparsity-aware-mapping ablation",
     "benchmarks.ablation_sparsity"),
    ("Beyond-paper — 40-cell LM roofline (from dry-run artifacts)",
     "benchmarks.lm_cells"),
    ("Beyond-paper — micro-batched GNN-CV serving throughput + liveness "
     "memory planning", "benchmarks.serve_gnncv"),
]


def main() -> None:
    import importlib
    t00 = time.time()
    failures = 0
    for title, mod_name in SECTIONS:
        print(f"==== {title} ====")
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            mod.run()
        except Exception as e:                       # noqa: BLE001
            failures += 1
            print(f"FAILED: {type(e).__name__}: {e}\n")
        print(f"[{time.time()-t0:.1f}s]\n")
    print(f"benchmarks done in {time.time()-t00:.1f}s, failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    gcv.use_compile_cache()
    main()
