"""Plain reference of DGCNN, the point-cloud classifier of Wang et al.,
TOG 2019, "Dynamic Graph CNN for Learning on Point Clouds", as the
authors' code defines it (github.com/WangYueFt/dgcnn, pytorch/model.py,
class ``DGCNN``), in its ModelNet40 setting.

For one cloud of N points, four EdgeConv layers.  Each builds the k=20
nearest-neighbour graph in the space of its own input (the coordinates,
then the previous layer's features: the graph is rebuilt at every layer),
forms for every edge (i, j) the feature ``[x_j - x_i, x_i]``, applies a
linear map with batch norm and LeakyReLU(0.2), and takes the max over the
k neighbours.  A point is one of its own k neighbours, as ``topk`` over
the negated distances gives it there.  Widths 64, 64, 128, 256; their
outputs are concatenated (512) and mapped to 1024 (``emb_dims``) with
batch norm and LeakyReLU; a max pool and a mean pool over the points are
concatenated (2048); then 512 and 256 with batch norm and LeakyReLU
(dropout is the identity at inference), and 40 logits.

Inference batch norm is an affine map per channel; the weights here are
random, drawn from the seed with the fold already in them, so every layer
is a linear map with a bias.

Straightforward ``jax.numpy`` in float32, one cloud at a time (vmapped
over a batch); it imports nothing of the system under test.
``precision`` is ``"highest"`` (float32 products) or ``"high"``: the
three-pass bfloat16 scheme, emulated explicitly, the benchmark's control.
Neighbours are the k smallest distances, ties to the lower index
(``lax.top_k``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
SLOPE = 0.2


def _lin(rng, fin, fout):
    w = (rng.standard_normal((fin, fout)) * np.sqrt(2.0 / fin))
    b = rng.standard_normal(fout) * 0.1
    return w.astype(np.float32), b.astype(np.float32)


def init_params(seed: int, *, n_points: int = 1024, k: int = 20,
                dims=(64, 64, 128, 256), emb_dims: int = 1024,
                hidden=(512, 256), classes: int = 40) -> dict:
    """Host float32 weights from ``seed``; they do not depend on
    ``n_points``.  An EdgeConv weight is ``(2 * C_in, C_out)``: its first
    ``C_in`` rows act on ``x_j - x_i``, the rest on ``x_i``."""
    rng = np.random.default_rng(seed)
    edge, cin = [], 3
    for d in dims:
        edge.append(_lin(rng, 2 * cin, d))
        cin = d
    emb = _lin(rng, sum(dims), emb_dims)
    head, fin = [], 2 * emb_dims
    for d in (*hidden, classes):
        head.append(_lin(rng, fin, d))
        fin = d
    return {"edge": edge, "emb": emb, "head": head, "k": k}


def _split(x):
    """bfloat16 head and tail of a float32 array, kept in float32.
    ``reduce_precision`` rounds as a cast to bfloat16 would, and unlike a
    cast pair no compiler may drop it as excess precision."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _matmul(precision: str):
    mm = functools.partial(jnp.matmul, precision=_HI)
    if precision == "highest":
        return mm
    if precision == "high":
        def three_pass(a, b):
            ah, al = _split(a)
            bh, bl = _split(b)
            return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
        return three_pass
    raise ValueError(f"precision must be 'highest' or 'high', "
                     f"got {precision!r}")


def _lrelu(x):
    return jnp.where(x >= 0, x, SLOPE * x)


def knn(x, k: int, mm):
    """``(N, k)`` indices of the k nearest points, the point included."""
    sq = (x * x).sum(axis=1)
    d = sq[:, None] - 2.0 * mm(x, x.T) + sq[None, :]
    return jax.lax.top_k(-d, k)[1]


def forward(params: dict, points, *, k: int, precision: str = "highest",
            factored: bool = False):
    """One ``(N, 3)`` cloud -> logits.  ``factored`` computes each
    EdgeConv as ``LeakyReLU((W_c - W_d) x_i + b + max_j W_d x_j)``, the
    same function with k times fewer products: the form whose operations
    ``flops.py`` counts as what a request needs."""
    mm = _matmul(precision)
    x, outs = points, []
    for w, b in params["edge"]:
        idx = knn(x, k, mm)
        c = x.shape[1]
        if factored:
            x = _lrelu(mm(x, w[c:] - w[:c]) + b
                       + mm(x, w[:c])[idx].max(axis=1))
        else:
            nbr = x[idx]                                   # (N, k, C)
            ctr = jnp.broadcast_to(x[:, None, :], nbr.shape)
            e = jnp.concatenate([nbr - ctr, ctr], axis=-1)  # (N, k, 2C)
            x = _lrelu(mm(e, w) + b).max(axis=1)           # (N, C_out)
        outs.append(x)
    w, b = params["emb"]
    h = _lrelu(mm(jnp.concatenate(outs, axis=-1), w) + b)   # (N, 1024)
    h = jnp.concatenate([h.max(axis=0), h.mean(axis=0)])
    *hidden, (w, b) = params["head"]
    for wh, bh in hidden:
        h = _lrelu(mm(h, wh) + bh)
    return mm(h, w) + b


def batched(params: dict, *, precision: str = "highest",
            factored: bool = False):
    """``f(points (B, N, 3)) -> (B, classes)``, jitted, with the weights
    as arguments so one program serves every seed."""
    k = params["k"]
    arrays = {key: v for key, v in params.items() if key != "k"}
    fn = jax.jit(lambda p, x: jax.vmap(lambda a: forward(
        p, a, k=k, precision=precision, factored=factored))(x))
    dev = jax.device_put(arrays)
    return lambda points: fn(dev, points)
