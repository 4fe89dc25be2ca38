"""A run whose timed path is broken underneath must come out not correct.

Each case drives a whole run of the tiny CPU cell (no look for a chip)
with one fault planted in the engine's harvest, where answers are
produced, and checks that ``correct`` is false.
"""
from __future__ import annotations

import types

import pytest

import chipbench_tiny
import harness


def _plant(kind):
    def hook(eng):
        inner = eng.harvest
        last = {}

        def harvest():
            reqs = eng._inflight[0][0] if eng._inflight else []
            n = inner()
            outs = [r.result for r in reqs]
            if kind == "altered" and reqs:
                r = reqs[0]
                r.result = (-r.result[0],)
            elif kind == "rows_swapped" and len(reqs) > 1:
                for r, o in zip(reqs, outs[1:] + outs[:1]):
                    r.result = o
            elif kind == "half_left_out" and len(reqs) > 1:
                half = len(reqs) // 2
                for r, o in zip(reqs[half:], outs):
                    r.result = o
            elif kind == "stale" and reqs:
                if "outs" in last:
                    for r, o in zip(reqs, last["outs"]):
                        r.result = o
                last["outs"] = outs
            return n
        eng.harvest = harvest
    return hook


@pytest.mark.parametrize("kind", ["altered", "rows_swapped",
                                  "half_left_out", "stale"])
def test_a_planted_fault_makes_the_run_not_correct(tmp_path, kind):
    root, bench = chipbench_tiny.make(tmp_path)
    args = types.SimpleNamespace(workload="tiny.sat", seed=2**31 + 7,
                                 seconds=0.5, trace=0)
    with chipbench_tiny.restore_jax():
        out = harness.run(args, bench, repo=root, here=root / "bench",
                          require_tpu=False, engine_hook=_plant(kind))
    assert out["correct"] is False
    assert out["checks"]["misplaced"]["value"] \
        > out["checks"]["misplaced"]["limit"]
