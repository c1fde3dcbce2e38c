"""Halo exchange + distributed Jacobi (the paper's application, §5.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.halo import halo_exchange_ring, jacobi_step
from repro.kernels.jacobi import ref as j_ref


def _global_jacobi_ref(u: np.ndarray) -> np.ndarray:
    """Single-device reference sweep with Dirichlet-zero boundary."""
    ext = np.pad(u, ((0, 0), (1, 1)))
    return np.asarray(j_ref.jacobi_sweep_ref(jnp.asarray(ext)))


@pytest.mark.parametrize("multipath", [False, True])
def test_halo_exchange(dev_mesh, multipath):
    n = 8
    rng = np.random.RandomState(0)
    left = jnp.asarray(rng.randn(n, 4, 6), jnp.float32)
    right = jnp.asarray(rng.randn(n, 4, 6), jnp.float32)

    def body(l, r):
        lh, rh = halo_exchange_ring(l[0], r[0], "dev",
                                    multipath=multipath)
        return lh[None], rh[None]

    f = jax.jit(shard_map(body, mesh=dev_mesh,
                          in_specs=(P("dev"), P("dev")),
                          out_specs=(P("dev"), P("dev")),
                          check_vma=False))
    lh, rh = f(left, right)
    # device i's left halo == right boundary of device i-1
    np.testing.assert_array_equal(np.asarray(lh),
                                  np.roll(np.asarray(right), 1, axis=0))
    np.testing.assert_array_equal(np.asarray(rh),
                                  np.roll(np.asarray(left), -1, axis=0))


@pytest.mark.parametrize("multipath", [False, True])
def test_jacobi_step_matches_global(dev_mesh, multipath):
    rows, w_local, n = 8, 32, 8
    rng = np.random.RandomState(1)
    u_global = rng.randn(rows, w_local * n).astype(np.float32)
    # column partition across devices: (rows, W) -> (n, rows, w_local)
    u_parts = jnp.asarray(
        np.stack(np.split(u_global, n, axis=1)))  # (n, rows, w_local)

    def body(u):
        return jacobi_step(u[0], "dev", multipath=multipath)[None]

    f = jax.jit(shard_map(body, mesh=dev_mesh, in_specs=P("dev"),
                          out_specs=P("dev"), check_vma=False))
    got_parts = np.asarray(f(u_parts))
    got = np.concatenate(list(got_parts), axis=1)
    ref = _global_jacobi_ref(u_global)
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_jacobi_converges(dev_mesh):
    """Paper §5.4 obs. 6: numerical convergence is unaffected by the
    pipelined/multi-path transfers."""
    rows, w_local, n = 8, 16, 8
    u = jnp.asarray(np.random.RandomState(2).randn(n, rows, w_local),
                    jnp.float32)

    def sweep(u, multipath):
        def body(ul):
            return jacobi_step(ul[0], "dev", multipath=multipath)[None]
        return jax.jit(shard_map(body, mesh=dev_mesh,
                                 in_specs=P("dev"), out_specs=P("dev"),
                                 check_vma=False))(u)

    u_sp, u_mp = u, u
    for _ in range(60):
        u_sp = sweep(u_sp, False)
        u_mp = sweep(u_mp, True)
    np.testing.assert_allclose(np.asarray(u_sp), np.asarray(u_mp),
                               atol=1e-6)
    # Dirichlet-zero problem: the iteration contracts toward zero
    assert float(jnp.max(jnp.abs(u_sp))) < float(jnp.max(jnp.abs(u)))


def test_halo_exchange_group_matches_ring():
    """The driver-level group halo exchange (one fused launch for all 2n
    boundary messages) matches the ring-shift semantics."""
    from repro.comm import CommConfig, CommSession
    from repro.core import Topology
    from repro.core.halo import halo_exchange_group

    n = 8
    sess = CommSession(CommConfig(multipath_threshold=64),
                       topology=Topology.full_mesh(n, with_host=False))
    blocks = jnp.asarray(np.random.RandomState(3).randn(n, 4, 6), jnp.float32)
    before = sess.stats()
    lh, rh = halo_exchange_group(sess, blocks)
    after = sess.stats()
    assert after["dispatches"] - before["dispatches"] == 1   # ONE launch
    right_b, left_b = np.asarray(blocks[:, :, -1:]), np.asarray(
        blocks[:, :, :1])
    np.testing.assert_array_equal(np.asarray(lh), np.roll(right_b, 1, axis=0))
    np.testing.assert_array_equal(np.asarray(rh), np.roll(left_b, -1, axis=0))
