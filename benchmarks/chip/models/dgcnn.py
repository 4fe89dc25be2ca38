"""DGCNN as a user hands it to ``gcv.compile``: a plain-JAX function over
one cloud, written with the program's graph primitives.

The weights are the reference's (``configs/dgcnn.py``, drawn from the
seed); this file only rewrites each EdgeConv into the form a gather-and-
max kernel serves.  With ``W = [W_d; W_c]`` acting on ``[x_j - x_i,
x_i]``, ``W_d (x_j - x_i) + W_c x_i = W_d x_j + (W_c - W_d) x_i``, and
LeakyReLU is increasing, so the max over the neighbours of the activated
edge features is the activation of ``(W_c - W_d) x_i + b`` plus the max
over the neighbours of ``W_d x_j``: the same function, without
materialising the ``(N, k, 2C)`` edge features.  The graph of each layer
is ``nn.knn_graph`` over that layer's input, the point itself included.
"""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np

SLOPE = 0.2
_REF = pathlib.Path(__file__).resolve().parents[1] / "configs" / "dgcnn.py"


def build(seed: int, *, n_points: int, k: int = 20,
          dims=(64, 64, 128, 256), emb_dims: int = 1024,
          hidden=(512, 256), classes: int = 40):
    """``(fn, example)`` for ``gcv.compile``/``gcv.serve``: ``fn(points
    (N, 3)) -> (classes,)`` with the seed's weights."""
    import harness
    from repro.frontend import nn
    p = harness.load_module(_REF).init_params(
        seed, n_points=n_points, k=k, dims=dims, emb_dims=emb_dims,
        hidden=hidden, classes=classes)
    edge = []
    for w, b in p["edge"]:
        c = w.shape[0] // 2
        edge.append((w[:c].copy(), w[c:] - w[:c], b))
    w_emb, b_emb = p["emb"]
    *head, (w_out, b_out) = p["head"]

    def lrelu(x):
        return jax.nn.leaky_relu(x, SLOPE)

    def model(points):
        x, outs = points, []
        for w_nbr, w_self, b in edge:
            idx = nn.knn_graph(x, k=k, self_loops=True)
            x = lrelu(x @ w_self + b
                      + nn.message_passing(idx, x @ w_nbr, reduce="max"))
            outs.append(x)
        h = lrelu(jnp.concatenate(outs, axis=1) @ w_emb + b_emb)
        h = jnp.concatenate([h.max(axis=0), h.mean(axis=0)])
        for w, b in head:
            h = lrelu(h @ w + b)
        return h @ w_out + b_out

    example = {"points": jax.ShapeDtypeStruct((n_points, 3), np.float32)}
    return model, example
