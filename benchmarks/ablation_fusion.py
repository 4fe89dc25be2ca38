"""Paper §VII-C layer-fusion ablation: speedup from Step-1 fusion across
b1–b6. Paper reports 11.8%–48.9%."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, plan_latency_s
from benchmarks.table2_tasks import build_all
from repro import gcv


def run():
    rows = []
    for name, g in build_all().items():
        off = plan_latency_s(compile_task(g, target="fpga", fuse=False))
        on = plan_latency_s(compile_task(g, target="fpga", fuse=True))
        speedup = (off - on) / on * 100.0
        rows.append((name, f"{off*1e3:.3f}", f"{on*1e3:.3f}",
                     f"{speedup:.1f}%", "11.8%-48.9%"))
    emit(rows, ["task", "no_fusion_ms", "fusion_ms", "speedup",
                "paper_range"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
