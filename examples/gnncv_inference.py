"""Run all six of the paper's GNN-based CV tasks end to end through the
compiler + executor, with the §VII-C optimizations toggled, reproducing the
structure of the paper's evaluation on CPU.

    PYTHONPATH=src python examples/gnncv_inference.py
"""
import numpy as np

from repro import gcv
from repro.core.perf_model import FPGA
from repro.gnncv import tasks


def latency_ms(plan):
    return sum(FPGA.op_seconds(op.cycles, op.bytes_moved)
               for op in plan.ops) * 1e3


def main():
    builders = {
        "b1 few-shot": lambda: tasks.b1_fewshot(),
        "b2 ML-GCN": lambda: tasks.b2_mlgcn(input_hw=64),
        "b4 ST-GCN": lambda: tasks.b4_stgcn(frames=32),
        "b5 SAR": lambda: tasks.b5_sar(input_hw=64),
        "b6 point-cloud": lambda: tasks.b6_pointcloud(n_points=256),
    }
    print(f"{'task':15s} {'out':>8s} {'opt ms':>9s} {'no-opt ms':>10s} "
          f"{'live KB':>8s} {'kept KB':>8s}")
    for name, build in builders.items():
        g = build()
        model = gcv.compile(g, target="fpga")
        base = gcv.compile(g, target="fpga", fuse=False,
                           sparsity_aware=False)
        out = model.run(**model.random_inputs())
        shape = np.asarray(out[0]).shape
        plan = model.plan
        print(f"{name:15s} {str(shape):>8s} {latency_ms(plan):9.3f} "
              f"{latency_ms(base.plan):10.3f} "
              f"{plan.peak_live_bytes() / 1024:8.0f} "
              f"{plan.peak_live_bytes(free_dead=False) / 1024:8.0f}")
    print("\n(optimized = six-pass compile with DM fusion, sparsity-aware "
          "mapping and\n liveness memory planning, per paper §V-C; 'live' "
          "vs 'kept' = peak activation\n working set with/without freeing "
          "dead intermediates)")


if __name__ == "__main__":
    gcv.use_compile_cache()
    main()
