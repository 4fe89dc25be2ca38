"""Flash attention — the fused SDDMM + softmax + SpDMM of the paper's
primitive vocabulary, specialized for the LM-framework hot path.

In GCV-Turbo terms, masked attention scores are an SDDMM
(``A ⊙ (Q Kᵀ)`` with A the causal/validity sampling matrix) and the
probability-weighted value reduction is an SpDMM (row-normalized sparse
weights × dense V). The paper computes these as two primitives through RB;
on TPU the memory roofline demands the *fused, tiled, online-softmax*
realization so the (Sq, Sk) score matrix never leaves VMEM — this is the
sparsity-aware Step-4 decision applied to the causal mask: blocks strictly
above the diagonal are skipped exactly like SDDMM's dead sampling blocks.

  grid = (B, Hq, Sq/bq, Sk/bk), Sk innermost; GQA via head-index map
  (kv head = q head // group). fp32 running (m, l, acc) in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, pad_to, unpad

NEG_INF = float("-inf")


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, nkb: int, bq: int, bk: int,
               sk_valid: int, offset: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal block-sparsity: skip blocks entirely above the diagonal
    # (the SDDMM dead-block skip).
    if causal:
        live = ki * bk <= qi * bq + (bq - 1) + offset
    else:
        live = ki * bk < sk_valid

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)     # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)     # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk_valid                  # key padding
        if causal:
            qpos = (qi * bq + offset
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # Rows with no live key yet keep m = -inf; exp must not see inf-inf.
        p = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                          jnp.exp(m_prev - m_new))
        l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nkb - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0.

    Causal alignment: query i attends keys j with ``j <= i + (Sk - Sq)``
    (decode/prefill-continuation convention).
    """
    interpret = default_interpret(interpret)
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0 and k.shape == v.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(bq, max(8, pl.next_power_of_2(Sq)))
    bk = min(bk, max(128, pl.next_power_of_2(Sk)))
    qp = pad_to(q, (1, 1, bq, 128))
    kp = pad_to(k, (1, 1, bk, 128))
    vp = pad_to(v, (1, 1, bk, 128))
    Dp = qp.shape[-1]
    nkb = kp.shape[2] // bk
    grid = (B, Hq, qp.shape[2] // bq, nkb)
    offset = Sk - Sq

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal, nkb=nkb,
                          bq=bq, bk=bk, sk_valid=Sk, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dp),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dp),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dp),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return unpad(out, (B, Hq, Sq, D))
