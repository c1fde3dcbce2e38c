"""Pipeline parallelism with multi-path stage-boundary transfers.

The stage-to-stage activation send in pipeline parallelism is exactly the
point-to-point transfer the paper accelerates: each microbatch handoff is a
large contiguous buffer moving between neighbouring devices while the
diagonal links idle. ``pipeline_apply`` implements a GPipe schedule under
``shard_map`` over the ``pipe`` axis; with ``multipath=True`` every handoff
is striped across the direct ring link and a 2-hop staged route through the
next-next stage (the Fig. 2(b) pattern), using the same split the core
engine plans.

The schedule runs ``M + P − 1`` ticks (fill + drain); activations for
microbatch *m* exit stage *P−1* at tick ``m + P − 1``. Correctness is
validated against sequential stage application in ``tests/test_pipeline.py``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

AXIS = "pipe"


def _shift_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


def send_next_stage(h: jax.Array, num_stages: int, *,
                    multipath: bool = False,
                    axis_name: str = AXIS) -> jax.Array:
    """Move activations one stage forward (stage boundary P2P)."""
    if not multipath or num_stages < 3:
        return lax.ppermute(h, axis_name, _shift_perm(num_stages, 1))
    half = h.shape[-1] // 2
    direct = lax.ppermute(h[..., :half], axis_name,
                          _shift_perm(num_stages, 1))
    staged = lax.ppermute(h[..., half:], axis_name,
                          _shift_perm(num_stages, 2))       # hop-1: skip
    staged = lax.ppermute(staged, axis_name,
                          _shift_perm(num_stages, -1))      # hop-2: back
    return jnp.concatenate([direct, staged], axis=-1)


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   mesh: Mesh, *, microbatches: int,
                   multipath: bool = False) -> jax.Array:
    """GPipe forward over the ``pipe`` mesh axis.

    ``stage_params``: pytree with leading stage dim (sharded over pipe).
    ``x``: (microbatches, mb, d) global inputs. Returns (microbatches, mb,
    d_out) — the last stage's outputs (other stages' slots are zero and the
    result is psum-gathered so every device returns the full output).
    """
    num_stages = mesh.shape[AXIS]
    m = microbatches

    def local(params_l, x_l):
        # params_l: stage-local params (leading dim 1); x_l: (M, mb, d) full
        # (replicated input stream — stage 0 consumes it).
        params_l = jax.tree.map(lambda p: p[0], params_l)
        sid = lax.axis_index(AXIS)
        mb_shape = x_l.shape[1:]
        h = jnp.zeros(mb_shape, x_l.dtype)
        outs = jnp.zeros((m,) + mb_shape, x_l.dtype)
        for t in range(m + num_stages - 1):
            # stage 0 ingests microbatch t during the fill phase
            feed = x_l[min(t, m - 1)]
            h_in = jnp.where(sid == 0,
                             jnp.where(t < m, feed, jnp.zeros_like(feed)),
                             h)
            h_out = stage_fn(params_l, h_in)
            # microbatch index flowing out of this stage at tick t
            mb_idx = t - sid
            emit = (sid == num_stages - 1) & (mb_idx >= 0) & (mb_idx < m)
            outs = lax.cond(
                emit,
                lambda o: lax.dynamic_update_slice(
                    o, h_out[None], (jnp.clip(mb_idx, 0, m - 1),) +
                    (0,) * len(mb_shape)),
                lambda o: o, outs)
            h = send_next_stage(h_out, num_stages, multipath=multipath)
        # surface the last stage's outputs everywhere
        return lax.psum(jnp.where(sid == num_stages - 1, outs, 0.0), AXIS)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(AXIS), P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)
