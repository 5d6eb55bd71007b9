"""Shared kernel utilities."""

from __future__ import annotations

import jax

__all__ = ["CompiledRouteUnsupported", "carry_full", "default_interpret",
           "pad_to", "cdiv"]


class CompiledRouteUnsupported(NotImplementedError):
    """A kernel variant that Mosaic cannot lower was asked for on the
    compiled (``interpret=False``) route.  Raised at trace time, so the
    call fails instead of silently taking another route."""

    def __init__(self, what: str):
        super().__init__(
            f"{what} has no compiled TPU kernel; run it on the "
            f"'pallas_interpret' or 'jax' backend")


def default_interpret() -> bool:
    """Pallas kernels target TPU; everywhere else run the kernel body in
    interpret mode (Python/XLA emulation) for correctness validation."""
    return jax.default_backend() != "tpu"


def carry_full(shape, value):
    """``jnp.full(shape, value)`` for the initial carry of an in-kernel
    loop over a 2-D register.  Mosaic lays a splat constant out replicated,
    a layout the loop's updated carry cannot be converted back to, so the
    fill is a select on a 2-D iota that is laid out like ordinary data."""
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(rows + cols >= 0, jnp.float32(value), jnp.float32(0))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x, multiple: int, axis: int = 0, value=0):
    """Pad ``x`` along ``axis`` up to the next multiple of ``multiple``."""
    import jax.numpy as jnp
    n = x.shape[axis]
    pad = cdiv(n, multiple) * multiple - n
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
