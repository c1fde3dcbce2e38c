"""Multipath (bidirectional-ring) collectives vs jax.lax references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.collectives import (bidir_ring_all_gather,
                                    bidir_ring_reduce_scatter,
                                    multipath_all_reduce,
                                    multipath_all_to_all,
                                    psum_via_multipath)


def _run(fn, x, mesh, in_spec, out_spec):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec, check_vma=False))(x)


@pytest.mark.parametrize("shape", [(8, 4), (8, 16), (16, 7), (8, 1)])
def test_all_gather(dev_mesh, shape):
    x = jnp.asarray(np.random.RandomState(0).randn(*shape), jnp.float32)
    got = _run(lambda v: bidir_ring_all_gather(v, "dev"), x, dev_mesh,
               P("dev"), P(None))
    ref = _run(lambda v: jax.lax.all_gather(v, "dev", tiled=True), x,
               dev_mesh, P("dev"), P(None))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("shape", [(8, 4), (16, 8), (64, 6), (8, 1)])
def test_reduce_scatter(dev_mesh, shape):
    x = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
    got = _run(lambda v: bidir_ring_reduce_scatter(v, "dev"), x, dev_mesh,
               P(None), P("dev"))
    ref = _run(lambda v: jax.lax.psum_scatter(v, "dev", tiled=True), x,
               dev_mesh, P(None), P("dev"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("shape", [(8, 4), (32, 8)])
def test_all_reduce(dev_mesh, shape):
    x = jnp.asarray(np.random.RandomState(2).randn(*shape), jnp.float32)
    got = _run(lambda v: multipath_all_reduce(v, "dev"), x, dev_mesh,
               P(None), P(None))
    ref = _run(lambda v: jax.lax.psum(v, "dev"), x, dev_mesh,
               P(None), P(None))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_all_to_all(dev_mesh):
    n = 8
    x = jnp.asarray(np.random.RandomState(3).randn(n * n, 4), jnp.float32)
    got = _run(lambda v: multipath_all_to_all(v.reshape(n, 1, 4), "dev"
                                              ).reshape(n, 4),
               x, dev_mesh, P("dev"), P("dev"))
    ref = _run(lambda v: jax.lax.all_to_all(v.reshape(n, 1, 4), "dev", 0, 0
                                            ).reshape(n, 4),
               x, dev_mesh, P("dev"), P("dev"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("shape", [(5, 3), (16,), (3, 3, 3)])
def test_psum_arbitrary_shapes(dev_mesh, shape):
    x = jnp.asarray(np.random.RandomState(4).randn(*shape), jnp.float32)
    got = _run(lambda v: psum_via_multipath(v, "dev"), x, dev_mesh,
               P(*([None] * len(shape))), P(*([None] * len(shape))))
    ref = x * 8.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


def test_collective_uses_both_directions(dev_mesh):
    """Structural check: the bidirectional AG emits ppermutes in both ring
    directions (this is the multipath property — 2 links per step)."""
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    lowered = jax.jit(shard_map(
        lambda v: bidir_ring_all_gather(v, "dev"), mesh=dev_mesh,
        in_specs=P("dev"), out_specs=P(None), check_vma=False)).lower(x)
    txt = lowered.as_text().replace(" ", "")
    perm_lines = [l for l in txt.splitlines() if "collective_permute" in l
                  or "collective-permute" in l]
    assert perm_lines, "no collective-permutes found"
    # at least one cw (0->1) and one ccw (1->0) permutation must appear
    has_cw = any("[0,1]" in l or "{0,1}" in l for l in perm_lines)
    has_ccw = any("[0,7]" in l or "[1,0]" in l or "{1,0}" in l
                  for l in perm_lines)
    assert has_cw and has_ccw


def test_psum_uses_both_directions(dev_mesh):
    """Regression: a single-column operand silently degraded psum to the
    one-directional ring; the (N*s, 2) packing must engage both."""
    x = jax.ShapeDtypeStruct((5, 3), jnp.float32)
    lowered = jax.jit(shard_map(
        lambda v: psum_via_multipath(v, "dev"), mesh=dev_mesh,
        in_specs=P(None, None), out_specs=P(None, None),
        check_vma=False)).lower(x)
    txt = lowered.as_text().replace(" ", "")
    perm_lines = [l for l in txt.splitlines() if "collective_permute" in l
                  or "collective-permute" in l]
    assert perm_lines, "no collective-permutes found"
    has_cw = any("[0,1]" in l or "{0,1}" in l for l in perm_lines)
    has_ccw = any("[1,0]" in l or "{1,0}" in l for l in perm_lines)
    assert has_cw and has_ccw
