"""Quickstart: the paper's pipeline in 40 lines.

Build a small CNN+GNN model as a layer graph, compile it with the five-pass
GCV-Turbo compiler, execute the plan, and print the modelled latency split.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import gcv
from repro.core import GraphBuilder
from repro.core.perf_model import FPGA

gcv.use_compile_cache()

rng = np.random.default_rng(0)

# -- a tiny GNN-CV model: conv stack -> patch-to-node DM -> message passing
b = GraphBuilder("quickstart")
b.portion = "cnn"
x = b.input((3, 32, 32), name="image")
h = b.conv(x, rng.standard_normal((3, 3, 3, 16)).astype(np.float32) * 0.1)
h = b.act(h, "relu")
h = b.pool(h, window=2)
h = b.conv(h, rng.standard_normal((3, 3, 16, 16)).astype(np.float32) * 0.1)
h = b.act(h, "relu")
h = b.pool(h, window=2)
b.portion = "gnn"
h = b.dm(h, "patch_to_node")                     # 8x8 patches -> 64 nodes
adj = (rng.random((64, 64)) < 0.1).astype(np.float32)
h = b.mp(h, adj=adj)                             # sparse -> SpDMM (Step 4)
h = b.linear(h, rng.standard_normal((16, 10)).astype(np.float32) * 0.1)
h = b.globalpool(h, kind="avg")
g = b.output(h)

# -- compile (six passes) and run through the one-call facade
model = gcv.compile(g, target="fpga")
out = model.run(**model.random_inputs())
print("output:", np.asarray(out[0]).round(3))
print("primitives used:", model.plan.primitive_counts())
lat = sum(FPGA.op_seconds(op.cycles, op.bytes_moved)
          for op in model.plan.ops)
print(f"modelled batch-1 latency: {lat*1e6:.1f} us")
