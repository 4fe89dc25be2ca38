"""Beyond-paper: the 40-cell LM roofline summary from the dry-run artifacts
(EXPERIMENTS.md §Roofline reads the same data)."""
from __future__ import annotations

import os

from benchmarks.common import emit
from repro import gcv
from repro.launch import roofline


def run(indir="experiments/dryrun_opt"):
    if not os.path.isdir(indir) or not os.listdir(indir):
        fallback = "experiments/dryrun"
        if os.path.isdir(fallback) and os.listdir(fallback):
            indir = fallback
        else:
            print(f"(no dry-run artifacts under {indir} — run "
                  "`python -m repro.launch.dryrun --all` first)\n")
            return []
    rows = []
    for r in roofline.load(indir):
        if r.get("status") == "n/a":
            rows.append((r["arch"], r["shape"], "-", "-", "-", "n/a", "-"))
            continue
        rows.append((r["arch"], r["shape"], f"{r['compute_s']:.3e}",
                     f"{r['memory_s']:.3e}", f"{r['collective_s']:.3e}",
                     r["dominant"], f"{r['roofline_fraction']:.4f}"))
    emit(rows, ["arch", "shape", "compute_s", "memory_s", "collective_s",
                "dominant", "roofline_fraction"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
