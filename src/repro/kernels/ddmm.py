"""DDMM — dense-dense matrix multiplication (GCV-Turbo primitive 1, paper §IV-A).

GCV-Turbo realizes DDMM on a ``p_ca x p_ca`` (16x16) systolic array at fp16.
On TPU the systolic resource is the 128x128 MXU; this kernel tiles
``(M, K) @ (K, N)`` into MXU-aligned VMEM blocks with fp32 accumulation and an
optional fused epilogue (bias add / activation / residual) — the kernel-level
realization of the paper's Step-1 layer fusion (norm/act folded into the
adjacent matmul).

Block layout:
  grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary"; M,N "parallel").
  x block (bm, bk), y block (bk, bn), out block (bm, bn) revisited across K,
  fp32 accumulator in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, pad_to, unpad

_ACTS = {
    None: lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _ddmm_kernel(x_ref, y_ref, *rest, nk: int, act, has_bias: bool,
                 has_res: bool):
    """rest = [bias_ref?, res_ref?, o_ref, acc_ref]."""
    idx = 0
    bias_ref = rest[idx] if has_bias else None
    idx += int(has_bias)
    res_ref = rest[idx] if has_res else None
    idx += int(has_res)
    o_ref, acc_ref = rest[idx], rest[idx + 1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _finalize():
        out = acc_ref[...]
        if has_bias:
            out = out + bias_ref[...].astype(jnp.float32)
        out = _ACTS[act](out)
        if has_res:
            out = out + res_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def ddmm(x: jax.Array, y: jax.Array, *, bias: jax.Array | None = None,
         residual: jax.Array | None = None, act: str | None = None,
         bm: int = 128, bk: int = 128, bn: int = 128,
         out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """``act(x @ y + bias) + residual`` with fp32 accumulation.

    x: (M, K), y: (K, N), bias: (N,), residual: (M, N).
    """
    assert x.ndim == 2 and y.ndim == 2 and x.shape[1] == y.shape[0], (
        x.shape, y.shape)
    interpret = default_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    M, K = x.shape
    N = y.shape[1]
    # Shrink blocks for small problems, keeping TPU-friendly (8, 128) floors.
    bm = min(bm, max(8, pl.next_power_of_2(M)))
    bk = min(bk, max(128, pl.next_power_of_2(K)))
    bn = min(bn, max(128, pl.next_power_of_2(N)))
    xp = pad_to(x, (bm, bk))
    yp = pad_to(y, (bk, bn))
    Mp, Kp = xp.shape
    Np = yp.shape[1]
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [xp, yp]
    if bias is not None:
        assert bias.shape == (N,)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(pad_to(bias.reshape(1, N), (1, bn)))
    if residual is not None:
        assert residual.shape == (M, N)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        args.append(pad_to(residual, (bm, bn)))

    out = pl.pallas_call(
        functools.partial(_ddmm_kernel, nk=nk, act=act,
                          has_bias=bias is not None,
                          has_res=residual is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return unpad(out, (M, N))
