"""Shared Pallas kernel helpers: alignment, padding, interpret-mode plumbing."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def use_interpret() -> bool:
    """Kernels are TPU-targeted: they compile for the chip on a TPU backend
    and run through the Pallas interpreter elsewhere. Callers that want the
    interpreter on a TPU pass ``interpret=True`` to the kernel."""
    return jax.default_backend() != "tpu"


LANE = 128  # TPU vector lane width
SUBLANE = {4: 8, 2: 16, 1: 32}  # sublane count per dtype itemsize (VREG geometry)


def sublane_for(dtype) -> int:
    return SUBLANE.get(jnp.dtype(dtype).itemsize, 8)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_block(extent: int, target: int, align: int = 1) -> int:
    """Largest block <= target that is a multiple of ``align`` (or the whole extent
    if it is smaller). Keeps MXU/VREG dims hardware-aligned when possible."""
    if extent <= target:
        return extent
    b = (target // align) * align
    return max(b, align)


def pad_to(x: jax.Array, shape) -> jax.Array:
    pads = [(0, s - xs) for xs, s in zip(x.shape, shape)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)
