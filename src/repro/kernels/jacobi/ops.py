"""Jit'd wrapper for the Jacobi sweep kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.jacobi.kernel import jacobi_sweep_kernel


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def jacobi_sweep(u: jax.Array, left_halo: jax.Array, right_halo: jax.Array,
                 *, tile: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """One sweep of ``u: (rows, W)`` between its halo columns ``(rows, 1)``.

    The tile comes from the shape (``kernel.sweep_tile``); ``tile``
    overrides it, for tests.
    """
    if interpret is None:
        interpret = _is_cpu()
    return jacobi_sweep_kernel(u, left_halo, right_halo, tile=tile,
                               interpret=interpret)
