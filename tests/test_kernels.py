"""Per-kernel shape/dtype sweeps against the pure-jnp oracles.

Every Pallas kernel runs in TPU-interpret mode on CPU; tolerances follow
dtype (f32 tight, bf16 loose per long-reduction error)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PathPlanner, Topology

# ------------------------------ multipath DMA ------------------------------
from repro.kernels.multipath_dma import kernel as dma_kernel
from repro.kernels.multipath_dma import ops as dma_ops
from repro.kernels.multipath_dma import ref as dma_ref


@pytest.fixture(scope="module")
def mesh4():
    devs = jax.devices()[:4]
    return jax.sharding.Mesh(np.array(devs), ("dev",))


@pytest.mark.parametrize("nelems,paths,chunks", [
    (1024, 1, 1), (2048, 2, 2), (12288, 3, 4), (9216, 3, 3), (16384, 2, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dma_kernel_sweep(mesh4, nelems, paths, chunks, dtype):
    topo = Topology.full_mesh(4)
    planner = PathPlanner(topo, multipath_threshold=4)
    itemsize = jnp.dtype(dtype).itemsize
    plan = planner.plan(0, 1, nelems * itemsize,
                        granularity=dma_kernel.dma_granularity(dtype),
                        max_paths=paths, num_chunks=chunks)
    x = np.random.RandomState(0).randn(4, nelems).astype(dtype)
    got = np.asarray(dma_ops.multipath_dma_transfer(jnp.asarray(x), plan,
                                                    mesh4))
    ref = dma_ref.multipath_transfer_ref(np.asarray(x, np.float64), plan)
    np.testing.assert_array_equal(got.astype(np.float64), ref)


def test_dma_kernel_rejects_unaligned_chunks():
    """A chunk that does not fall on the 1024-element DMA tiling is
    refused before Mosaic sees it (Mosaic refuses such a slice on v5e)."""
    planner = PathPlanner(Topology.full_mesh(4), multipath_threshold=4)
    plan = planner.plan(0, 3, 768 * 4, granularity=4, max_paths=3,
                        num_chunks=3)
    with pytest.raises(ValueError, match="DMA tiling"):
        dma_kernel.build_multipath_dma(plan, 768, jnp.float32, 4)


def test_dma_kernel_rejects_3hop(mesh4):
    topo = Topology.torus2d(2, 2)
    planner = PathPlanner(topo, multipath_threshold=4)
    plan = planner.plan(0, 1, 1024, granularity=4, max_paths=3)
    if any(p.route.num_hops > 2 for p in plan.paths):
        from repro.kernels.multipath_dma.kernel import build_multipath_dma
        with pytest.raises(NotImplementedError):
            build_multipath_dma(plan, 256, jnp.float32, 4)


# ------------------------------ flash attention ----------------------------
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 2, 256, 64), (2, 4, 4, 128, 32), (1, 8, 2, 200, 64),
    (1, 2, 1, 384, 128),
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (True, 64), (False, None),
])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, window):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, hq, s, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32))
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    ref = fa_ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-5, rtol=1e-4)


def test_flash_attention_bf16():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 4, 128, 64), jnp.bfloat16) * 0.3
    k = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16) * 0.3
    v = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
    got = fa_ops.flash_attention(q, k, v, causal=True)
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    err = np.max(np.abs(np.asarray(got, np.float32)
                        - np.asarray(ref, np.float32)))
    assert err < 2e-2


# -------------------------------- jacobi -----------------------------------
from repro.kernels.jacobi import ops as j_ops
from repro.kernels.jacobi import ref as j_ref


@pytest.mark.parametrize("rows,w,tile", [
    (8, 1024, 512), (8, 700, 512), (16, 256, 128), (8, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_jacobi_sweep(rows, w, tile, dtype):
    ext = jnp.asarray(
        np.random.RandomState(2).randn(rows, w + 2), dtype)
    got = j_ops.jacobi_sweep(ext[:, 1:-1], ext[:, :1], ext[:, -1:],
                             tile=tile)
    ref = j_ref.jacobi_sweep_ref(ext)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("rows,w,tile,dtype", [
    (8, 700, 256, jnp.float32),      # ragged: not a multiple of 128 or T
    (8, 1024, 1024, jnp.float32),    # exactly one tile
    (8, 4096, 512, jnp.float32),     # neighbour blocks cross tile edges
    (16, 512, 128, jnp.float32),     # one-vreg tiles
    (8, 100, None, jnp.float32),     # narrower than a vreg
    (8, 1 << 14, None, jnp.float32),  # the tile the shape picks
    (16, 1000, 256, jnp.bfloat16),
], ids=["ragged", "one_tile", "many_tiles", "vreg_tiles", "narrow",
        "shape_tile", "bf16"])
def test_jacobi_sweep_halos_bit_equal(rows, w, tile, dtype):
    """Nonzero halos on both sides, bit for bit against the reference on
    the halo-extended block."""
    rng = np.random.RandomState(5)
    u, lh, rh = (jnp.asarray(rng.randn(rows, n), dtype)
                 for n in (w, 1, 1))
    got = j_ops.jacobi_sweep(u, lh, rh, tile=tile)
    ref = j_ref.jacobi_sweep_ref(jnp.concatenate([lh, u, rh], axis=1))
    assert got.shape == u.shape and got.dtype == u.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))


def test_jacobi_sweep_tile_rule():
    """The tile fills the VMEM budget at the widths the benchmark sweeps,
    and leaves a small grid its steps."""
    from repro.kernels.jacobi.kernel import sweep_tile
    assert sweep_tile(8, 1 << 26, jnp.float32) == 1 << 15
    assert sweep_tile(8, 1 << 26, jnp.bfloat16) == 1 << 15
    assert sweep_tile(8, 1 << 16, jnp.float32) == 1 << 13
    assert sweep_tile(8, 100, jnp.float32) == 128
    with pytest.raises(ValueError, match="multiple of 128"):
        j_ops.jacobi_sweep(jnp.zeros((8, 1024)), jnp.zeros((8, 1)),
                           jnp.zeros((8, 1)), tile=200)


# ------------------------------- rwkv6 scan --------------------------------
from repro.kernels.rwkv6_scan import ops as r_ops
from repro.kernels.rwkv6_scan import ref as r_ref


@pytest.mark.parametrize("bh,s,dk,dv,chunk", [
    (2, 128, 32, 32, 32), (1, 200, 64, 64, 64), (4, 64, 16, 32, 16),
    (1, 96, 8, 8, 32),
])
def test_rwkv6_sweep(bh, s, dk, dv, chunk):
    rng = np.random.RandomState(3)
    r = jnp.asarray(rng.randn(bh, s, dk).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(bh, s, dk).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(bh, s, dv).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.85, 0.999, (bh, s, dk)).astype(np.float32))
    u = jnp.asarray(rng.randn(bh, dk).astype(np.float32)) * 0.3
    got = r_ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    ref = r_ref.rwkv6_scan_ref(r, k, v, w, u)
    scale = np.max(np.abs(np.asarray(ref))) + 1e-9
    err = np.max(np.abs(np.asarray(got) - np.asarray(ref))) / scale
    assert err < 1e-4


# --------------------------- ring all-gather -------------------------------
from repro.kernels.ring_allgather import ops as ag_ops


@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("rows,f", [(8, 128), (4, 64), (8, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_allgather_sweep(n, rows, f, dtype):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("dev",))
    x = jnp.asarray(np.random.RandomState(0).randn(n * rows, f), dtype)
    got = np.asarray(ag_ops.ring_allgather(x, mesh))
    np.testing.assert_array_equal(got, np.asarray(x))
