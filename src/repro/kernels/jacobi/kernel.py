"""Pallas TPU kernel: 5-point Jacobi stencil sweep (the paper's application).

The domain is tall-and-narrow exactly as in the paper's evaluation (§5.4:
vertical dimension 8, horizontal up to 2^30, column-partitioned across
devices). Rows therefore stay resident per block and the kernel tiles the
wide column dimension: grid ``(cdiv(W, T),)`` over the block ``u`` itself,
each step reading its ``(rows, T)`` tile and the 128-lane blocks of ``u``
just before and just after it. Horizontal neighbours are lane rolls of the
tile in VMEM; where a roll wraps, the neighbour block's edge lane (or, at
the block's first and last column, the halo column) takes its place.
Vertical neighbours are row shifts inside the tile (rows are global — the
column split means block edges are the true domain boundary, handled with
Dirichlet zeros). So a sweep reads the grid once and writes it once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Lanes of a TPU vector register: the width of the neighbour blocks, and
#: the unit a tile's width is a multiple of.
LANES = 128
#: Bytes of one ``(rows, T)`` tile. The kernel's blocks (tile and output,
#: each double-buffered) and its in-VMEM temporaries then stay inside the
#: 16 MiB of scoped VMEM a kernel gets by default on v5e.
TILE_BYTES = 1 << 20
#: Fewest grid steps a sweep is cut into where the width allows, so that
#: reading one tile overlaps computing and writing the one before.
MIN_STEPS = 8


def sweep_tile(rows: int, w: int, dtype) -> int:
    """The tile width for a ``(rows, w)`` sweep: the widest multiple of
    :data:`LANES` whose tile holds at most :data:`TILE_BYTES` (rows padded
    to the dtype's sublane tiling), cut so that the grid keeps
    :data:`MIN_STEPS` steps."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    padded_rows = pl.cdiv(rows, sublanes) * sublanes
    widest = TILE_BYTES // (padded_rows * itemsize) // LANES * LANES
    per_step = pl.cdiv(pl.cdiv(w, MIN_STEPS), LANES) * LANES
    return max(LANES, min(widest, per_step))


def _roll_lanes(x: jax.Array, shift: int) -> jax.Array:
    """``x`` rolled by ``shift`` lanes. Mosaic rotates 32-bit data only, so
    a narrower dtype rolls as float32, which holds its values exactly."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, 1)
    return pltpu.roll(x.astype(jnp.float32), shift, 1).astype(x.dtype)


def _jacobi_kernel(c_ref, lb_ref, rb_ref, lh_ref, rh_ref, o_ref, *, w):
    c = c_ref[...]
    rows, t = c.shape
    i = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    col = i * t + lane                                    # global column
    # Column 0 of the tile has the last lane of the block before it (the
    # halo in the first tile); the last column has the first lane of the
    # block after it, and the block's last column, wherever it falls in a
    # ragged tile, has the halo.
    before = jnp.where(i == 0, lh_ref[...], lb_ref[:, lb_ref.shape[1] - 1:])
    after = rb_ref[:, :1]
    left = jnp.where(lane == 0, before, _roll_lanes(c, 1))
    right = jnp.where(lane == t - 1, after, _roll_lanes(c, t - 1))
    right = jnp.where(col == w - 1, rh_ref[...], right)
    zero = jnp.zeros((1, t), c.dtype)
    up = jnp.concatenate([zero, c[:-1, :]], axis=0)      # Dirichlet top
    down = jnp.concatenate([c[1:, :], zero], axis=0)     # Dirichlet bottom
    o_ref[...] = 0.25 * (left + right + up + down)


def jacobi_sweep_kernel(u: jax.Array, left_halo: jax.Array,
                        right_halo: jax.Array, *, tile: int | None = None,
                        interpret: bool = True) -> jax.Array:
    """One sweep over the block ``u: (rows, W)``, whose neighbour columns
    are ``left_halo`` and ``right_halo`` (each ``(rows, 1)``).

    Returns the updated ``(rows, W)``. Nothing else as wide as ``u`` is
    made: the kernel reads ``u`` in place. ``tile`` overrides
    :func:`sweep_tile` (for tests). The ``pallas_call`` opens no scope of
    its own: the innermost scope names the kernel's HLO instruction
    (``jacobi_sweep.1`` under the ``ops`` wrapper's jit).
    """
    rows, w = u.shape
    if tile is None:
        tile = sweep_tile(rows, w, u.dtype)
    tile = min(tile, w)
    if tile < w and tile % LANES:
        raise ValueError(f"tile {tile} is not a multiple of {LANES} lanes")
    nb = min(LANES, w)                  # neighbour blocks: one vreg wide
    per_tile = tile // nb
    last = pl.cdiv(w, nb) - 1
    halo = pl.BlockSpec((rows, 1), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, w=w),
        grid=(pl.cdiv(w, tile),),
        in_specs=[
            pl.BlockSpec((rows, tile), lambda i: (0, i)),
            pl.BlockSpec((rows, nb),
                         lambda i: (0, jnp.maximum(i * per_tile - 1, 0))),
            pl.BlockSpec((rows, nb),
                         lambda i: (0, jnp.minimum((i + 1) * per_tile,
                                                   last))),
            halo, halo,
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, w), u.dtype),
        interpret=interpret,
    )(u, u, u, left_halo, right_halo)
