"""Fused KNN graph construction — tiled pairwise distance + online top-k.

Dynamic-graph GNNs (ViG patch graphs, point-cloud EdgeConv) rebuild their
adjacency per input: for every point, the k nearest neighbors under squared
L2 distance.  The naive realization materializes the full (N, N) distance
matrix and runs ``lax.top_k`` over it — O(N^2) HBM traffic that dominates
the un-accelerated graph-build stage (Ramachandran et al., PAPERS.md).
This kernel fuses the two: distances are produced tile by tile in VMEM and
consumed immediately by an online k-selection, so nothing O(N^2) ever
touches HBM.

Block layout:
  grid = (N/bm, N/bn), the candidate dimension innermost and sequential.
  x row block (bm, F) and candidate block (bn, F) with F fully resident;
  scratch keeps the running best (bm, k) distances + indices across
  candidate tiles; the int32 (bm, k) neighbor-index block is written on
  the last tile.  Per tile, the (bm, bn) distance block
  ``|xi|^2 - 2 xi.xj + |xj|^2`` comes off the MXU and k min/knock-out
  sweeps merge it into the running best — O(k * (bn + k)) VPU work per
  tile, no gather, no sort.

**Pinned KNN semantics** — every realization (this kernel, the
materialized ``knn_ref`` oracle below via ``lax.top_k``, and the numpy
``gnncv.graphs.knn_indices`` oracle) must agree exactly:

  * neighbors are the ``k`` *smallest* squared-L2 distances, computed
    in fp32 (``HIGHEST`` matmul precision, also on TPU);
  * output order: ascending distance, ties broken toward the **lower
    candidate index** (matching ``lax.top_k`` and stable argsort);
  * a point is never its own neighbor unless ``self_loops=True``;
  * candidates with ``mask == 0`` are never selected; rows with
    ``mask == 0`` still emit indices (callers mask downstream features,
    not the index matrix);
  * fewer than ``k`` selectable candidates (over-masking) leaves the
    trailing slots deterministic but unspecified — keep ``k`` below the
    valid-candidate count.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, pad_to

# Index sentinel for exhausted candidate slots: larger than any real
# column index, so min-over-achievers never picks it while real
# candidates remain.  (Plain int — a jnp scalar here would be captured
# as a constant by the Pallas kernel tracer.)
_BIG_IDX = 2**30
# Distances are fp32 products on every backend: a TPU's default
# single-pass bf16 matmul would rank neighbors by rounded distances and
# select a different graph than the pinned semantics.
_PRECISION = jax.lax.Precision.HIGHEST


def _knn_kernel(xi_ref, xj_ref, mj_ref, o_ref, bd_ref, bi_ref, *,
                k: int, n: int, bn: int, nn: int, self_loops: bool):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        bd_ref[...] = jnp.full(bd_ref.shape, jnp.inf, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, _BIG_IDX, jnp.int32)

    bm = xi_ref.shape[0]
    xi = xi_ref[...].astype(jnp.float32)                       # (bm, F)
    xj = xj_ref[...].astype(jnp.float32)                       # (bn, F)
    d = (jnp.sum(xi * xi, axis=1, keepdims=True)
         - 2.0 * jnp.dot(xi, xj.T, precision=_PRECISION,
                         preferred_element_type=jnp.float32)
         + jnp.sum(xj * xj, axis=1)[None, :])                  # (bm, bn)
    j = pl.program_id(1)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    valid = (col < n) & (mj_ref[...].reshape(1, bn) > 0)
    if not self_loops:
        row = (pl.program_id(0) * bm
               + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0))
        valid &= col != row
    d = jnp.where(valid, d, jnp.inf)

    # Merge the tile into the running best: k sweeps of min + knock-out.
    # Ties resolve by the *lower global index* among distance achievers —
    # the pinned semantics — so merge order never matters.
    cand_d = jnp.concatenate([bd_ref[...], d], axis=1)         # (bm, k+bn)
    cand_i = jnp.concatenate([bi_ref[...], col], axis=1)
    sel_d, sel_i = [], []
    for _ in range(k):
        dmin = jnp.min(cand_d, axis=1, keepdims=True)          # (bm, 1)
        imin = jnp.min(jnp.where(cand_d == dmin, cand_i, _BIG_IDX),
                       axis=1, keepdims=True)
        sel_d.append(dmin)
        sel_i.append(imin)
        hit = (cand_d == dmin) & (cand_i == imin)
        cand_d = jnp.where(hit, jnp.inf, cand_d)
    bd_ref[...] = jnp.concatenate(sel_d, axis=1)
    bi_ref[...] = jnp.concatenate(sel_i, axis=1)

    @pl.when(j == nn - 1)
    def _finalize():
        o_ref[...] = bi_ref[...]


def knn(x: jax.Array, *, k: int, mask: jax.Array | None = None,
        self_loops: bool = False, bm: int = 128, bn: int = 128,
        interpret: bool | None = None) -> jax.Array:
    """Fused distance + top-k: ``(N, F)`` points -> int32 ``(N, k)``
    neighbor indices, no O(N^2) materialization.

    ``mask``: optional ``(N,)`` / ``(N, 1)`` validity — zero entries are
    never selected as neighbors.  Semantics pinned in the module
    docstring.
    """
    assert x.ndim == 2, f"knn expects (N, F) points, got {x.shape}"
    n, _ = x.shape
    assert 1 <= k <= n, f"k={k} out of range for {n} points"
    interpret = default_interpret(interpret)
    bm = min(bm, max(8, pl.next_power_of_2(n)))
    bn = min(bn, max(128, pl.next_power_of_2(n)))
    # rows must tile evenly under *both* block shapes — padding to a
    # multiple of bm alone would truncate the candidate grid when bn > bm
    # (nn = rows // bn), silently skipping candidate tiles
    xp = pad_to(x, (math.lcm(bm, bn), 128))
    if bn > xp.shape[0]:        # bn never exceeds the padded row count
        bn = xp.shape[0]
    m = jnp.ones((n, 1), jnp.float32) if mask is None \
        else mask.reshape(n, 1).astype(jnp.float32)
    mp = pad_to(m, (bn, 1))
    nm = xp.shape[0] // bm
    nn = xp.shape[0] // bn

    out = pl.pallas_call(
        functools.partial(_knn_kernel, k=k, n=n, bn=bn, nn=nn,
                          self_loops=self_loops),
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((bm, xp.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, xp.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], k), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32),
                        pltpu.VMEM((bm, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, xp, mp)
    return out[:n]


def knn_ref(x: jax.Array, *, k: int, mask: jax.Array | None = None,
            self_loops: bool = False) -> jax.Array:
    """Materialized oracle: full (N, N) distance matrix + ``lax.top_k``.

    This is also the ``xla_knn`` realization — XLA fuses the distance
    expression but still materializes N^2 scores for the top-k.
    ``lax.top_k`` breaks ties toward the lower index, matching the pinned
    semantics.
    """
    n = x.shape[0]
    assert 1 <= k <= n, f"k={k} out of range for {n} points"
    xf = x.astype(jnp.float32)
    sq = jnp.sum(xf * xf, axis=1)
    d = sq[:, None] - 2.0 * jnp.dot(xf, xf.T, precision=_PRECISION) \
        + sq[None, :]
    if not self_loops:
        d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d)
    if mask is not None:
        d = jnp.where(mask.reshape(1, n) > 0, d, jnp.inf)
    return jax.lax.top_k(-d, k)[1].astype(jnp.int32)
