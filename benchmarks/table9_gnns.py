"""Paper Table IX / XII analogue: standalone GNNs g1–g3 across citation /
recommendation graphs. Modelled hardware-execution latency vs the paper's
reported GCV-Turbo latencies (Table XII, GCN row)."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, plan_latency_s
from repro import gcv
from repro.gnncv import gnn_zoo

# Table XII GCV-Turbo hardware latency (ms): CO, CI, PU, FL
PAPER_GCN_MS = {"cora": 0.48, "citeseer": 1.47, "pubmed": 1.25,
                "flickr": 6.09}


def run():
    rows = []
    for model_name, fn in (("g1_gcn", gnn_zoo.gcn),
                           ("g2_sage", gnn_zoo.graphsage),
                           ("g3_gat", gnn_zoo.gat)):
        for ds in ("cora", "citeseer", "pubmed", "flickr"):
            g = fn(ds)
            plan = compile_task(g, target="fpga")
            lat = plan_latency_s(plan) * 1e3
            paper = PAPER_GCN_MS.get(ds) if model_name == "g1_gcn" else None
            rows.append((model_name, ds, f"{lat:.3f}",
                         f"{paper:.2f}" if paper else "-",
                         f"{lat/paper:.2f}" if paper else "-"))
    emit(rows, ["model", "dataset", "modelled_ms", "paper_ms",
                "ratio_model/paper"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
