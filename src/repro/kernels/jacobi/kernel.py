"""Pallas TPU kernel: 5-point Jacobi stencil sweep (the paper's application).

The domain is tall-and-narrow exactly as in the paper's evaluation (§5.4:
vertical dimension 8, horizontal up to 2^30, column-partitioned across
devices). Rows therefore stay resident per block and the kernel tiles the
wide column dimension: grid ``(W // TILE,)`` with three input views of the
halo-extended operand (left/center/right neighbour columns), each a
``(rows, TILE)`` VMEM block. TILE is a multiple of 128 to keep the lane
dimension MXU/VPU-aligned; vertical neighbours are row shifts inside the
block (rows are global — the column split means block edges are the true
domain boundary, handled with Dirichlet zeros).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 512


def _jacobi_kernel(l_ref, c_ref, r_ref, o_ref):
    c = c_ref[...]
    rows = c.shape[0]
    zero = jnp.zeros((1, c.shape[1]), c.dtype)
    up = jnp.concatenate([zero, c[:-1, :]], axis=0)      # Dirichlet top
    down = jnp.concatenate([c[1:, :], zero], axis=0)     # Dirichlet bottom
    o_ref[...] = 0.25 * (l_ref[...] + r_ref[...] + up + down)


def jacobi_sweep_kernel(ext: jax.Array, *, tile: int = TILE,
                        interpret: bool = True) -> jax.Array:
    """One sweep over a halo-extended block ``ext: (rows, W + 2)``.

    Returns the updated interior ``(rows, W)``. The three shifted views are
    materialized outside the kernel, under the scope ``jacobi.views`` (XLA
    fuses the slices into one copy). The ``pallas_call`` opens no scope of
    its own: the innermost scope names the kernel's HLO instruction
    (``jacobi_sweep.1`` under the ``ops`` wrapper's jit).
    """
    rows, wp2 = ext.shape
    w = wp2 - 2
    with jax.named_scope("jacobi.views"):
        left, center, right = ext[:, :-2], ext[:, 1:-1], ext[:, 2:]
    tile = min(tile, w)
    grid = (pl.cdiv(w, tile),)
    spec = pl.BlockSpec((rows, tile), lambda i: (0, i))
    return pl.pallas_call(
        _jacobi_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, w), ext.dtype),
        interpret=interpret,
    )(left, center, right)
