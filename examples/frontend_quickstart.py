"""Frontend quickstart: compile a *user-defined JAX model* (paper §V-A).

The declarative ``GraphBuilder`` path (examples/quickstart.py) requires
re-expressing a model layer by layer.  This is the other ingestion path —
the paper's "takes a user-defined model as input" promise: write an
ordinary JAX function (convs, matmuls, pooling as plain ``jax``/``jnp``;
GNN aggregation through ``repro.frontend.nn``) and hand it to
``gcv.compile``, which traces it, runs the six-pass compiler, and returns
a ``CompiledModel`` owning the whole lifecycle.

    PYTHONPATH=src python examples/frontend_quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import gcv
from repro.frontend import nn

gcv.use_compile_cache()

rng = np.random.default_rng(0)

# -- model weights: ordinary numpy arrays closed over by the function
w_conv1 = rng.standard_normal((3, 3, 1, 8)).astype(np.float32) * 0.3
b_conv1 = rng.standard_normal(8).astype(np.float32) * 0.1
w_conv2 = rng.standard_normal((3, 3, 8, 8)).astype(np.float32) * 0.2
w_embed = rng.standard_normal((8, 16)).astype(np.float32) * 0.3
w_out = rng.standard_normal((32, 10)).astype(np.float32) * 0.3


def conv2d(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NCHW", "HWIO", "NCHW"))


def model(images):
    """A user-defined CNN+GNN: conv embedding per image, then one graph
    block over the set of images (b1-style learned affinity)."""
    h = jax.nn.relu(conv2d(images, w_conv1) + b_conv1[None, :, None, None])
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                              (1, 1, 2, 2), (1, 1, 2, 2), "SAME")
    h = jax.nn.relu(conv2d(h, w_conv2))
    h = h.mean((2, 3))                        # (n_images, 8)
    h = jax.nn.relu(h @ w_embed)              # (n_images, 16)
    affinity = jax.nn.softmax(nn.vip(h), axis=-1)
    agg = nn.message_passing(affinity, h)     # runtime adjacency -> DDMM
    h = jnp.concatenate([h, agg], axis=1)     # (n_images, 32)
    return h @ w_out


# -- one call: trace -> canonicalize -> six passes -> runner lifecycle
images = rng.standard_normal((6, 1, 12, 12)).astype(np.float32)
compiled = gcv.compile(model, {"images": images}, target="fpga",
                       name="user_model")
print("recovered layers:", [f"{l.name}:{l.kind}" for l in
                            compiled.graph.toposorted()])

out = np.asarray(compiled.run(images=images)[0])
direct = np.asarray(model(jnp.asarray(images)))
print("primitives used:", compiled.plan.primitive_counts())
print("max |compiled - direct jax|:", float(np.abs(out - direct).max()))
print("logits[0]:", out[0].round(3))
print("lifecycle stats:", compiled.stats())
