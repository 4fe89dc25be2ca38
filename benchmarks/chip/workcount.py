"""Work counts and peaks, kept with the benchmark so that no change to
the program can change them.

``peaks`` reads the table in ``peaks.json`` by JAX's ``device_kind``; a
kind that is not there is an error, never a default.  ``knn_work`` counts
what a k-nearest-neighbour graph needs at its shapes, whatever kernel
computes it.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """Peak rates of one chip of ``device_kind``."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path}; it holds {sorted(table)}")
    return table[device_kind]


def knn_work(n: int, d: int, k: int) -> tuple[float, float]:
    """Operations and bytes of one ``k``-NN graph over ``n`` points of
    ``d`` coordinates (float32 in, int32 indices out).

    Operations: every pair's squared distance by the norm expansion,
    ``|x_i|^2 - 2 x_i.x_j + |x_j|^2`` (``2d`` for the product, 3 more to
    combine), and at least one comparison per pair to select the ``k``
    smallest: ``n * n * (2d + 4)``.  Bytes: the points and the validity
    mask read once, the ``(n, k)`` indices written once."""
    ops = float(n) * n * (2 * d + 4)
    nbytes = 4.0 * (n * d + n + n * k)
    return ops, nbytes
