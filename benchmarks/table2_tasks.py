"""Paper Table II / Fig. 9 analogue: end-to-end modelled latency of the six
GNN-based CV tasks b1–b6 under the GCV-Turbo execution model, plus the
paper's claimed speedup context."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, plan_latency_s
from repro import gcv
from repro.gnncv import tasks

# paper Fig. 9: GCV-Turbo speedup over GPU (RTX A5000), batch-1
PAPER_GPU_SPEEDUP = {"b1": 5.1, "b2": 1.3, "b3_r50": 1.2, "b3_r101": 1.2,
                     "b4": 3.6, "b5": 4.6, "b6": 15.2}


def build_all():
    return {
        "b1": tasks.b1_fewshot(),
        "b2": tasks.b2_mlgcn(),
        "b3_r50": tasks.b3_dualgcn(depth=50),
        "b3_r101": tasks.b3_dualgcn(depth=101),
        "b4": tasks.b4_stgcn(),
        "b5": tasks.b5_sar(),
        "b6": tasks.b6_pointcloud(),
    }


def run():
    rows = []
    for name, g in build_all().items():
        plan = compile_task(g, target="fpga")
        lat = plan_latency_s(plan) * 1e3
        implied_gpu = lat * PAPER_GPU_SPEEDUP[name]
        rows.append((name, f"{lat:.3f}", f"{PAPER_GPU_SPEEDUP[name]}",
                     f"{implied_gpu:.3f}",
                     plan.meta.get("weights_resident", "-")))
    emit(rows, ["task", "modelled_latency_ms", "paper_speedup_vs_gpu",
                "implied_gpu_ms", "weights_on_chip"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
