"""SpDMM — sparse-dense matrix multiplication (GCV-Turbo primitive 2, §IV-A).

GCV-Turbo executes SpDMM with scatter-gather pipelines over CSR-style
``(src, dst, val)`` tuples, routed per-nonzero by the B2P network —
fine-grained dynamic routing that has no TPU analogue. The TPU-native
adaptation (DESIGN.md §2) is **ELL format**: every row of the sparse matrix X
is padded to a fixed ``L = max_nnz_per_row`` slots of ``(col_idx, val)``.
The kernel then becomes a *regular* gather of Y rows plus a dense
multiply-accumulate — predictable, shape-static latency, which is exactly the
determinism property the paper targets for autonomous driving.

  Z[i, :] = sum_l val[i, l] * Y[idx[i, l], :]

Cost model analogue: paper ``l_SpDMM = ceil(nnz/(p_ca/2)) * ceil(s3/p_ca)``;
here cost ∝ ``S1*L*N`` (padded-nnz × row width), so primitive selection
(passes/select.py) compares ``S1*L*N`` (SpDMM) against ``S1*S2*N`` (DDMM).

Block layout:
  grid = (S1/bm, N/bn).  The (bm, L) ``idx``/``val`` blocks sit in SMEM,
  where the kernel reads them as scalars; the Y block (S2, bn) keeps the
  full row dimension resident in VMEM.  Each output row is the sum of L
  single-row loads ``Y[idx[i, l], :]`` at a dynamic sublane offset, scaled
  by the scalar ``val[i, l]`` on the VPU and accumulated in fp32 — the
  scatter-gather pipeline as a loop over the ELL slots.  ``bn`` shrinks
  for large S2 so the resident Y block stays within ``_Y_BLOCK_BYTES``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, pad_to, round_up, unpad

# VMEM budget for one buffer of the resident (S2, bn) fp32 Y block.
_Y_BLOCK_BYTES = 4 << 20


def _spdmm_kernel(idx_ref, val_ref, y_ref, o_ref):
    bm, nl = idx_ref.shape
    s2, bn = y_ref.shape

    def row(r, carry):
        def slot(l, acc):
            # padding slots carry val == 0; clamping keeps their (ignored)
            # index inside the resident block
            j = jnp.clip(idx_ref[r, l], 0, s2 - 1)
            return acc + val_ref[r, l] * y_ref[pl.ds(j, 1), :]

        # Mosaic lowers a loop either fully unrolled or not at all
        acc = jax.lax.fori_loop(0, nl, slot,
                                jnp.zeros((1, bn), jnp.float32),
                                unroll=nl if nl <= 16 else 1)
        o_ref[pl.ds(r, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, bm, row, 0)


def spdmm(idx: jax.Array, val: jax.Array, y: jax.Array, *,
          bm: int = 32, bn: int = 512,
          out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """ELL sparse (S1, L) @ dense (S2, N) -> (S1, N).

    ``idx[i, l]`` is the column (= row of ``y``) of the l-th nonzero of row i;
    padding slots must have ``val == 0`` (their ``idx`` is ignored).
    Accumulation is fp32 whatever the input dtype.
    """
    assert idx.shape == val.shape and idx.ndim == 2
    interpret = default_interpret(interpret)
    out_dtype = out_dtype or y.dtype
    S1, L = idx.shape
    S2, N = y.shape
    s2p = round_up(S2, 8)
    bm = min(bm, round_up(S1, 8))
    bn = min(bn, round_up(N, 128),
             max(128, _Y_BLOCK_BYTES // (4 * s2p) // 128 * 128))
    idxp = pad_to(idx.astype(jnp.int32), (bm, 1))
    valp = pad_to(val.astype(jnp.float32), (bm, 1))
    yp = pad_to(y.astype(jnp.float32), (8, bn))
    grid = (idxp.shape[0] // bm, yp.shape[1] // bn)

    out = pl.pallas_call(
        _spdmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, L), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, L), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((s2p, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((idxp.shape[0], yp.shape[1]),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(idxp, valp, yp)
    return unpad(out, (S1, N)).astype(out_dtype)


def dense_to_ell(x: np.ndarray | jax.Array,
                 max_nnz: int | None = None) -> tuple[jax.Array, jax.Array]:
    """Convert a dense sparse-valued matrix to ELL ``(idx, val)`` arrays.

    Offline (compile-time) conversion — mirrors the paper's compiler preparing
    the three-tuple representation of the adjacency/weight matrix.
    """
    x = np.asarray(x)
    S1, _ = x.shape
    nnz_per_row = (x != 0).sum(axis=1)
    L = int(max_nnz if max_nnz is not None else max(1, nnz_per_row.max()))
    idx = np.zeros((S1, L), np.int32)
    val = np.zeros((S1, L), x.dtype)
    for i in range(S1):
        cols = np.nonzero(x[i])[0][:L]
        idx[i, : len(cols)] = cols
        val[i, : len(cols)] = x[i, cols]
    return jnp.asarray(idx), jnp.asarray(val)
