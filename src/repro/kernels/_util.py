"""Shared helpers for the Pallas kernel package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def default_interpret(interpret: bool | None) -> bool:
    """Kernels run in interpret mode automatically off-TPU (CPU container)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_to(x: jax.Array, multiples: tuple[int, ...]) -> jax.Array:
    """Zero-pad each dim of ``x`` up to a multiple of ``multiples``."""
    assert x.ndim == len(multiples)
    pads = [(0, round_up(s, m) - s) for s, m in zip(x.shape, multiples)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def unpad(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    if tuple(x.shape) == tuple(shape):
        return x
    return x[tuple(slice(0, s) for s in shape)]
