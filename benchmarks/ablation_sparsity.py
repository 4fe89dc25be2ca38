"""Paper §VII-C sparsity-aware-mapping ablation: GNN-portion speedup from
Step-4 DDMM-vs-SpDMM selection. Paper: 5.2%, 330%, 356%, 356%, 2.3%,
2.3%/20.5%, 0% for b1..b6 (b6 = 0: its GNN is Linear-only)."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, portion_latency_s
from benchmarks.table2_tasks import build_all
from repro import gcv

PAPER = {"b1": "5.2%", "b2": "330%", "b3_r50": "356%", "b3_r101": "356%",
         "b4": "2.3%", "b5": "2.3-20.5%", "b6": "0%"}


def run():
    rows = []
    for name, g in build_all().items():
        off = portion_latency_s(
            compile_task(g, target="fpga", sparsity_aware=False))
        on = portion_latency_s(
            compile_task(g, target="fpga", sparsity_aware=True))
        g_off = off.get("gnn", 0.0)
        g_on = on.get("gnn", 0.0)
        speedup = (g_off - g_on) / g_on * 100.0 if g_on else 0.0
        rows.append((name, f"{g_off*1e3:.3f}", f"{g_on*1e3:.3f}",
                     f"{speedup:.1f}%", PAPER[name]))
    emit(rows, ["task", "gnn_dense_ms", "gnn_sparsity_aware_ms",
                "gnn_speedup", "paper"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
