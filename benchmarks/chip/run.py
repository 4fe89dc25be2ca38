"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit); diagnostics go
to standard error, whose last lines are the same checks.  Exits non-zero
with no result line when JAX finds no TPU or fewer chips than the cell
asks for.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

if __name__ == "__main__":
    import harness
    sys.exit(harness.main())
