"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For topologies where inter-pod ICI/DCN bandwidth makes tensor-parallel
collectives across pods unattractive, layers are partitioned into S stages
over a mesh axis; microbatches stream through with the classic
(n_micro + S - 1)-tick schedule. The only inter-stage communication is a
point-to-point ``collective_permute`` of one microbatch's activations per
tick — bandwidth ~ activations/microbatch, independent of model size.

``pipeline_apply`` is deliberately minimal (forward streaming; training
composes it under ``jax.grad`` — collective_permute is differentiable, the
backward pass streams in reverse automatically).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, xs, *, mesh, axis: str = "stage",
                   param_specs=None):
    """Run ``stage_fn(params_i, x) -> x`` for stages i = 0..S-1 over
    microbatches ``xs`` (n_micro, mb, ...).

    stage_params: pytree with leading stage dim S (sharded over ``axis``).
    Returns ys (n_micro, mb, ...) — outputs of the final stage.
    """
    S = mesh.shape[axis]
    n_micro = xs.shape[0]
    if param_specs is None:
        param_specs = jax.tree.map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params)

    def local(params, xs_local):
        # params: leading dim 1 (this stage); xs replicated
        p = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        T = n_micro + S - 1
        mb_shape = xs_local[0].shape

        def tick(t, state):
            recv, ys = state
            # stage 0 injects microbatch t (or zeros after the last one)
            x_in = jnp.where(
                t < n_micro,
                jax.lax.dynamic_index_in_dim(
                    xs_local, jnp.minimum(t, n_micro - 1), 0, False),
                jnp.zeros(mb_shape, xs_local.dtype))
            x = jnp.where(idx == 0, x_in, recv)
            y = stage_fn(p, x)
            # last stage writes its result at slot t-(S-1)
            slot = t - (S - 1)
            ys = jax.lax.cond(
                (idx == S - 1) & (slot >= 0),
                lambda ys: jax.lax.dynamic_update_index_in_dim(
                    ys, y, jnp.maximum(slot, 0), 0),
                lambda ys: ys, ys)
            # shift activations one stage to the right
            recv = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return recv, ys

        recv0 = jnp.zeros(mb_shape, xs_local.dtype)
        ys0 = jnp.zeros_like(xs_local)
        _, ys = jax.lax.fori_loop(0, T, tick, (recv0, ys0))
        # everyone returns ys; only the last stage's copy is real — psum
        # after masking yields the result replicated
        mask = (idx == S - 1).astype(xs_local.dtype)
        return jax.lax.psum(ys * mask, axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xs)
