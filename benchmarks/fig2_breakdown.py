"""Paper Fig. 2 / Fig. 10 / Table VII analogue: CNN vs GNN vs layout-
transformation share of each GNN-CV task, before/after DM fusion.

GCV-Turbo's claim (Table VII): the DM/layout overhead is fully eliminated
('∞' speedup). Here: dm share with dm_fusion=False vs True."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, portion_latency_s
from benchmarks.table2_tasks import build_all
from repro import gcv


def run():
    rows = []
    for name, g in build_all().items():
        base = compile_task(g, target="fpga", dm_fusion=False)
        opt = compile_task(g, target="fpga", dm_fusion=True)
        pb = portion_latency_s(base)
        po = portion_latency_s(opt)
        tot_b = sum(pb.values()) or 1.0
        tot_o = sum(po.values()) or 1.0
        rows.append((
            name,
            f"{pb.get('cnn', 0) / tot_b:.3f}",
            f"{pb.get('gnn', 0) / tot_b:.3f}",
            f"{pb.get('dm', 0) / tot_b:.3f}",
            f"{po.get('dm', 0) / tot_o:.3f}",
        ))
    emit(rows, ["task", "cnn_share", "gnn_share", "dm_share_unfused",
                "dm_share_fused(paper:0)"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
