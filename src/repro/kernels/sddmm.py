"""SDDMM — sampled dense-dense matrix multiplication (GCV-Turbo primitive 3).

Paper: ``Z = A ⊙ (X @ Y)`` where A is a 0/1 sampling matrix; adder-tree
pipelines compute only the sampled inner products
(``l_SDDMM = ceil(nnz(A)/(p_ca/2)) * ceil(s2/p_ca)``).

TPU adaptation: per-element sampling is hostile to a systolic MXU, so the
sampling is done at **block granularity** — the compiler rounds A up to a
(bm, bn) block mask, and the kernel skips the matmul for all-zero blocks
(``pl.when`` on an SMEM-resident mask; a skipped block costs one control
cycle, the analogue of the paper's one-cycle primitive switch). Element-level
residual masking within a live block is applied in the epilogue. This is the
same dense/sparse trade the paper's Step-4 makes, at MXU-tile resolution.

Used by: VIP layers (GAT edge scores) and as the score stage of attention
(causal mask = lower-triangular block mask — see flash_attention.py for the
fused realization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, pad_to, unpad


def _sddmm_kernel(bmask_ref, x_ref, y_ref, emask_ref, o_ref, acc_ref, *,
                  nk: int, elementwise: bool):
    i, j = pl.program_id(0), pl.program_id(1)
    live = bmask_ref[i, j] != 0

    @pl.when(live & (pl.program_id(2) == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finalize():
        out = jnp.where(live, acc_ref[...], 0.0)
        if elementwise:
            out = out * emask_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def sddmm(x: jax.Array, y: jax.Array, mask: jax.Array, *,
          bm: int = 128, bk: int = 128, bn: int = 128,
          elementwise: bool = True, out_dtype=None,
          interpret: bool | None = None) -> jax.Array:
    """``mask ⊙ (x @ y)`` computing only blocks where ``mask`` has support.

    x: (M, K), y: (K, N), mask: (M, N) 0/1 sampling matrix.
    ``elementwise=False`` keeps full values inside live blocks (block-sampled
    output, used when the consumer re-masks anyway, e.g. softmax with -inf).
    """
    assert mask.shape == (x.shape[0], y.shape[1])
    interpret = default_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    M, K = x.shape
    N = y.shape[1]
    bm = min(bm, max(8, pl.next_power_of_2(M)))
    bk = min(bk, max(128, pl.next_power_of_2(K)))
    bn = min(bn, max(128, pl.next_power_of_2(N)))
    xp, yp = pad_to(x, (bm, bk)), pad_to(y, (bk, bn))
    maskp = pad_to(mask.astype(jnp.float32), (bm, bn))
    Mp, Kp = xp.shape
    Np = yp.shape[1]
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)
    # Block mask (compile-time in the GCV compiler; here reduced on device).
    bmask = (maskp.reshape(Mp // bm, bm, Np // bn, bn).sum((1, 3)) > 0)
    bmask = bmask.astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_sddmm_kernel, nk=nk, elementwise=elementwise),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # block mask, whole
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bmask, xp, yp, maskp)
    return unpad(out, (M, N))
