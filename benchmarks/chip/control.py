"""Readings of the comparison that decides ``correct``, for the control.

    python3 benchmarks/chip/control.py --workload dgcnn.saturate \\
        --seeds 11,12,13

The control is the plain reference put in the program's place and
computed one step below the precision the configuration states: the
three-pass bfloat16 scheme (``precision="high"``) where the
configuration states float32 at ``highest``.  For each seed it builds the
cell's input pool and weights as a run does, takes the requests a run's
comparison would sample (drawn from the seed's sending order), answers
them with the control in the run's chunks, and prints the numbers a run
compares (``median_rel_err`` and ``misplaced`` against the reference
at the stated precision) with the cell's limits.  It needs no measured window: the control serves nothing.
Exit code 1 when any seed's control passes the limit.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import loadgen  # noqa: E402


def readings(ns, seed: int, precision: str = "high") -> dict:
    """The numbers a run compares, with the control answering."""
    import jax
    out = {}
    for ti, (task, spec) in enumerate(ns.tasks.items()):
        pool = loadgen.make_pool(spec["inputs"], ns.traffic["pool"], seed,
                                 ti)
        order = loadgen.pool_order(ns.traffic["pool"],
                                   spec["check"]["sample"], seed)
        recs = [types.SimpleNamespace(pool_i=int(i)) for i in order]
        rel, misplaced = harness.reference_errors(
            spec, ns.serve["matmul_precision"], pool, recs, seed, jax,
            precision, task_i=ti)
        pre = f"{task}." if len(ns.tasks) > 1 else ""
        out[pre + "median_rel_err"] = (float(np.median(rel)),
                                       spec["check"]["median_rel_err"])
        out[pre + "misplaced"] = (int(misplaced.sum()),
                                  spec["check"]["misplaced"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    ns = harness.resolve(harness.load_json(harness.REPO / "BENCHMARK.json"),
                         args.workload)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        v = readings(ns, seed % 2**63)
        failed_all &= any(x > lim for x, lim in v.values())
        print(json.dumps({"workload": ns.name, "seed": seed,
                          "control": {k: x for k, (x, _) in v.items()},
                          "limits": {k: lim for k, (_, lim) in v.items()}}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
