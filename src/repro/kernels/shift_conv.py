"""Shift-add convolution — the paper's Fig. 7 Conv-layer mapping, fused.

GCV-Turbo maps a Conv layer to matrix operations by rearranging the kernel
tensor W (c_out, c_in, k1, k2) into k1*k2 submatrices KM_i of shape
(c_in, c_out), multiplying each with the IFM matrix (c_in, h*w), and merging
the k1*k2 partial OFMs with shift-add. The payoff is layout-centric: IFM/OFM
stay in ``channels x pixels`` layout across consecutive Conv layers AND across
CNN->GNN transitions (channel-to-node DM becomes a no-op; patch-to-node
becomes a transpose folded into the next matmul).

This kernel fuses all k1*k2 matmuls and the shift-add merge into one pass
over the *flattened* plane: the IFM is laid out as (c_in, H*W), so tap
(dy, dx) is a lane shift of the whole pixel axis by ``dy*W + dx`` (scaled
by the dilation) — one ``pltpu.roll`` on the XLU feeding one MXU matmul,
with no 3-D reshapes or unaligned slices.  Shifting the flat axis wraps
row ends into the next row, but only into columns the VALID crop drops.

  grid = (c_out/bm, HW/bp, c_in/bk), c_in innermost (reduction);
  every pixel tile of bp lanes also reads the following tile, so the taps
  see their halo (bp is at least the largest tap offset); fp32 scratch
  accumulates the taps across the c_in blocks.

The kernel computes the VALID correlation; the jit wrapper realizes SAME by
explicit input pre-padding and stride by output subsampling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._util import default_interpret, round_up


def _shift_conv_kernel(xa_ref, xb_ref, w_ref, o_ref, acc_ref, *, nk: int,
                       offsets: tuple[int, ...]):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bp = xa_ref.shape[1]
    x = jnp.concatenate([xa_ref[...], xb_ref[...]], axis=1)   # (bk, 2*bp)
    for t, off in enumerate(offsets):         # statically unrolled taps
        shifted = x if off == 0 else pltpu.roll(x, 2 * bp - off, 1)
        acc_ref[...] += jnp.dot(w_ref[t], shifted[:, :bp],
                                preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _shift_conv_valid(x: jax.Array, w: jax.Array, *, bm: int, bk: int,
                      out_dtype, interpret: bool,
                      dilation: tuple = (1, 1)) -> jax.Array:
    """VALID correlation, x: (c_in, H, W), w: (k1, k2, c_in, c_out);
    ``dilation`` scales the tap offsets (effective extent (k-1)*d+1)."""
    c_in, H, W = x.shape
    k1, k2, _, c_out = w.shape
    dh, dw = dilation
    ke1, ke2 = (k1 - 1) * dh + 1, (k2 - 1) * dw + 1
    offsets = tuple(dy * dh * W + dx * dw
                    for dy in range(k1) for dx in range(k2))
    bp = max(512, round_up(offsets[-1] + 1, 128))
    bm = min(bm, max(8, pl.next_power_of_2(c_out)))
    bk = min(bk, max(8, pl.next_power_of_2(c_in)))
    cin_p, cout_p = round_up(c_in, bk), round_up(c_out, bm)
    P = H * W
    n_p = -(-P // bp)
    # one extra tile of zeros so the last pixel tile has a "next" tile
    xf = jnp.pad(x.reshape(c_in, P),
                 ((0, cin_p - c_in), (0, (n_p + 1) * bp - P)))
    wt = jnp.pad(w.reshape(k1 * k2, c_in, c_out).transpose(0, 2, 1),
                 ((0, 0), (0, cout_p - c_out), (0, cin_p - c_in)))
    nk = cin_p // bk
    out = pl.pallas_call(
        functools.partial(_shift_conv_kernel, nk=nk, offsets=offsets),
        grid=(cout_p // bm, n_p, nk),
        in_specs=[
            pl.BlockSpec((bk, bp), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bp), lambda i, j, k: (k, j + 1)),
            pl.BlockSpec((k1 * k2, bm, bk), lambda i, j, k: (0, i, k)),
        ],
        out_specs=pl.BlockSpec((bm, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((cout_p, n_p * bp), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xf, xf, wt)
    out = out[:c_out, :P].reshape(c_out, H, W)
    return out[:, : H - ke1 + 1, : W - ke2 + 1]


def shift_conv2d(x: jax.Array, w: jax.Array, *, stride=1,
                 padding: str = "SAME", groups: int = 1, dilation=(1, 1),
                 bm: int = 128, bk: int = 128,
                 out_dtype=None, interpret: bool | None = None) -> jax.Array:
    """2-D convolution via the Fig. 7 shift-add mapping.

    x: (c_in, H, W) single image (vmap for batch),
    w: (k1, k2, c_in // groups, c_out).  ``stride``/``dilation`` may be an
    int or a pair.  Returns (c_out, H_out, W_out).

    ``dilation`` needs no new data movement: the statically-unrolled tap
    loop just shifts by (dy*dh, dx*dw) instead of (dy, dx).  ``groups``
    runs one shift-GEMM per group over its channel slices — each group is
    an independent (c_in/g -> c_out/g) conv, merged by channel concat.
    """
    interpret = default_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    k1, k2 = w.shape[0], w.shape[1]
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    dh, dw = (dilation, dilation) if isinstance(dilation, int) \
        else tuple(dilation)
    ke1, ke2 = (k1 - 1) * dh + 1, (k2 - 1) * dw + 1
    c_in, c_out = x.shape[0], w.shape[3]
    assert c_in == w.shape[2] * groups and c_out % groups == 0, \
        f"groups={groups} must divide c_in={c_in} (w expects " \
        f"{w.shape[2]} per group) and c_out={c_out}"
    if padding == "SAME":
        H, W = x.shape[1:]
        # SAME for stride s: total pad = max((ceil(H/s)-1)*s + ke - H, 0),
        # with ke the effective (dilated) kernel extent
        ph = max((-(-H // sh) - 1) * sh + ke1 - H, 0)
        pw = max((-(-W // sw) - 1) * sw + ke2 - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2)))
    elif padding != "VALID":
        raise ValueError(padding)
    kw = dict(bm=bm, bk=bk, out_dtype=out_dtype, interpret=interpret,
              dilation=(dh, dw))
    if groups == 1:
        out = _shift_conv_valid(x, w, **kw)
    else:
        cg, og = c_in // groups, c_out // groups
        out = jnp.concatenate(
            [_shift_conv_valid(x[g * cg:(g + 1) * cg],
                               w[..., g * og:(g + 1) * og], **kw)
             for g in range(groups)], axis=0)
    if sh > 1 or sw > 1:
        out = out[:, ::sh, ::sw]
    return out
