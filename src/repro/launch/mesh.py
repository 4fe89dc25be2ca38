"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests see 1 device).

The LM builders degrade gracefully when the host has fewer devices than the
requested shape: the largest fitting mesh is built instead (later axes —
the model/TP axes — keep their extent first, since those shard actual
tensors; leading DP axes give way), a ``UserWarning`` names the
substitution, and with tracing on an ``obs.instant("mesh.degraded")``
marker records it in the timeline.  A 1-host smoke test therefore gets a
(1, 1) mesh from ``make_host_mesh((2, 4))`` rather than a ``reshape``
error.  The serving data mesh (``make_data_mesh``) does not degrade: a
server asked for N devices either gets N or fails.
"""
from __future__ import annotations

import warnings

import jax
import numpy as np

from repro import obs


def fit_shape(shape, available: int) -> tuple:
    """Largest mesh shape elementwise <= ``shape`` whose product fits in
    ``available`` devices.  Later axes are satisfied first (innermost =
    model/TP, where extent matters most); each axis takes what it can and
    leaves the integer remainder for the axes before it."""
    assert available >= 1, f"need at least one device, got {available}"
    out = []
    remaining = available
    for size in reversed(tuple(shape)):
        take = min(int(size), remaining)
        out.append(take)
        remaining //= take
    return tuple(reversed(out))


def _build(shape, axes, *, requested=None):
    """Mesh over the first ``prod(shape)`` host devices, degrading to the
    largest fitting shape when fewer exist."""
    devices = jax.devices()
    want = tuple(int(s) for s in shape)
    n = int(np.prod(want))
    if n > len(devices):
        got = fit_shape(want, len(devices))
        warnings.warn(
            f"mesh shape {want} needs {n} devices but only "
            f"{len(devices)} exist; degrading to {got} "
            f"(axes {tuple(axes)})", UserWarning, stacklevel=3)
        obs.instant("mesh.degraded", cat="launch",
                    requested=list(requested if requested is not None
                                   else want),
                    got=list(got), devices=len(devices))
        want, n = got, int(np.prod(got))
    grid = np.asarray(devices[:n]).reshape(want)
    return jax.sharding.Mesh(grid, tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = one v5e pod (256 chips); (2, 16, 16) = 2 pods.

    The 'pod' axis is pure DP (+ FSDP spill); 'data' is FSDP/DP within a
    pod; 'model' is TP/EP/SP. The same rule-set generalizes to more pods —
    nothing below assumes pod == 2.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _build(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over whatever host devices exist (distributed tests)."""
    return _build(shape, axes)


def make_data_mesh(devices=None):
    """1-D ``("data",)`` mesh for batch-axis data parallelism — what
    ``gcv.compile(devices=)`` / ``gcv.serve(devices=)`` shard over.

    ``devices`` is ``None`` (every visible device), an int (the first N;
    asking for more than are visible raises ``ValueError``), or an explicit
    sequence of ``jax.Device``s.  A pre-built ``Mesh`` goes through
    ``as_data_mesh`` instead.
    """
    if devices is None:
        devs = list(jax.devices())
    elif isinstance(devices, int):
        assert devices >= 1, f"devices must be >= 1, got {devices}"
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(
                f"requested {devices} devices but only {len(avail)} "
                f"{avail[0].platform} device(s) are visible")
        devs = list(avail[:devices])
    else:
        devs = list(devices)
        assert devs, "empty device sequence"
    return jax.sharding.Mesh(np.asarray(devs), ("data",))


def as_data_mesh(mesh) -> "jax.sharding.Mesh":
    """Validate a user-supplied mesh for the batch-sharded serving path:
    1-D with a ``data`` axis (what the runners' ``PartitionSpec("data")``
    names)."""
    assert isinstance(mesh, jax.sharding.Mesh), \
        f"mesh= expects a jax.sharding.Mesh, got {type(mesh).__name__}"
    assert tuple(mesh.axis_names) == ("data",), \
        f"batch sharding needs a 1-D ('data',) mesh, got axes " \
        f"{tuple(mesh.axis_names)} — build one with " \
        f"launch.mesh.make_data_mesh(...)"
    return mesh


def mesh_axes(mesh):
    """(dp_axes, model_axis, fsdp_axes) conventions for a mesh."""
    names = mesh.axis_names
    model = "model" if "model" in names else names[-1]
    dp = tuple(n for n in names if n != model)
    return dp, model, dp
