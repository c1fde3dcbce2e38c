"""Compiles for a described TPU v5e, at the sizes the chip smoke runs.

Nothing here runs on a chip: the TPU compiler, which is installed with JAX,
compiles each program for a v5e:2x2 topology that is described, not
attached. A program or kernel the chip's compiler would refuse (a slice off
the Mosaic tiling, a scoped-VMEM overrun, a program larger than HBM) fails
here. The topology is described only inside the ``topo`` fixture: a test
worker that never runs this file never loads the TPU library.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm import MultiPathTransfer
from repro.core import PathPlanner, Topology

MiB = 1 << 20
GiB = 1 << 30
#: HBM of one TPU v5e chip.
V5E_HBM_BYTES = 16 * GiB
#: The chip smoke's 512 MiB f32 messages and its one-chip Jacobi grid.
MSG_ELEMS = 512 * MiB // 4
JACOBI_ROWS, JACOBI_COLS = 8, 1 << 25
#: The width of the benchmark's 2 GiB Jacobi cell (``jacobi.1chip.2G``).
JACOBI_CELL_COLS = 1 << 26
#: A diagonal message whose remote-DMA staging fits the 16 MiB of scoped
#: VMEM a kernel gets by default (24 MiB still compiles, 32 MiB does not).
DMA_BYTES = 16 * MiB


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("dev",))


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("pairs", [((0, 3),), ((0, 3), (3, 0))],
                         ids=["send_0_3", "exchange_0_3"])
def test_engine_group_program_512mib(mesh4, pairs):
    """The engine's program for 512 MiB f32 messages on a v5e:2x2 mesh:
    multipath plans, one ppermute per copy node, within one chip's HBM."""
    eng = MultiPathTransfer(mesh4,
                            topology=Topology.full_mesh(4, with_host=False))
    specs = [(s, d, MSG_ELEMS, jnp.float32) for s, d in pairs]
    plan_cp, group = eng.compiled_for_group(specs)
    assert all(p.num_paths > 1 for p in group.plans)
    text = plan_cp.compiled.as_text()
    assert text.count("collective-permute-start") >= sum(
        p.num_nodes for p in group.plans)
    _fits_one_chip(plan_cp.compiled)


@pytest.mark.parametrize("dst", [1, 3])
def test_extraction_program_512mib(mesh4, dst):
    """``comm_extract`` on a 512 MiB transfer output: the message comes
    back replicated on the four chips through one all-reduce and no other
    collective, as the eager ``y[0, dst]`` (a gather) did, and needs no
    temporary."""
    from repro.comm.engine import comm_extract
    y = jax.ShapeDtypeStruct((1, 4, MSG_ELEMS), jnp.float32,
                             sharding=NamedSharding(mesh4, P(None, "dev")))
    compiled = comm_extract.lower(y, dst).compile()
    text = compiled.as_text()
    collectives = re.findall(r"\b(all-reduce|collective-permute|all-gather|"
                             r"all-to-all|reduce-scatter)(?:-start)?\(",
                             text)
    assert collectives == ["all-reduce"]
    assert compiled.output_shardings.is_fully_replicated
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_jacobi_kernel_at_smoke_size(one_chip):
    from repro.kernels.jacobi.kernel import jacobi_sweep_kernel
    u = jax.ShapeDtypeStruct((JACOBI_ROWS, JACOBI_COLS), jnp.float32,
                             sharding=one_chip)
    halo = jax.ShapeDtypeStruct((JACOBI_ROWS, 1), jnp.float32,
                                sharding=one_chip)
    fn = jax.jit(functools.partial(jacobi_sweep_kernel, interpret=False))
    compiled = fn.lower(u, halo, halo).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_jacobi_sweep_one_chip_at_cell_size(topo, monkeypatch):
    """The whole one-chip sweep as the benchmark jits it, at the 2 GiB
    cell's 8 x 2^26: the kernel at the tile the shape picks passes the
    scoped-VMEM check, and reads the grid in place. No op but the kernel
    makes anything as wide as the grid (no halo-extended block, no shifted
    copy), and the temporaries are small."""
    from repro.core.halo import jacobi_step
    from repro.kernels.jacobi import ops as jacobi_ops
    # The kernel's wrapper interprets on a CPU backend; compile it.
    monkeypatch.setattr(jacobi_ops, "_is_cpu", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]), ("dev",))
    fn = jax.jit(jax.shard_map(
        lambda x: jacobi_step(x[0], "dev", multipath=True,
                              use_kernel=True)[None],
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    u = jax.ShapeDtypeStruct((1, JACOBI_ROWS, JACOBI_CELL_COLS), jnp.float32,
                             sharding=NamedSharding(mesh, P("dev")))
    compiled = fn.lower(u).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    wide = re.findall(rf"%\S+ = \w+\[[\d,]*{JACOBI_CELL_COLS}\]\S* "
                      rf"([\w-]+)\(", text)
    assert set(wide) <= {"parameter", "bitcast", "custom-call"}, wide
    assert wide.count("custom-call") == 1
    grid_bytes = JACOBI_ROWS * JACOBI_CELL_COLS * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < grid_bytes // 16, mem
    _fits_one_chip(compiled)


def test_multipath_dma_kernel_compiles(mesh4):
    """The remote-DMA kernel, with chunks planned on its DMA tiling,
    compiles for the chip (not interpreted) on the diagonal pair."""
    from repro.kernels.multipath_dma.kernel import (build_multipath_dma,
                                                    dma_granularity)
    planner = PathPlanner(Topology.full_mesh(4, with_host=False))
    plan = planner.plan(0, 3, DMA_BYTES,
                        granularity=dma_granularity(jnp.float32))
    assert plan.num_paths > 1
    nelems = DMA_BYTES // 4
    inner = build_multipath_dma(plan, nelems, jnp.float32, 4,
                                interpret=False)
    fn = jax.jit(jax.shard_map(lambda x: inner(x[0])[None], mesh=mesh4,
                               in_specs=P("dev"), out_specs=P("dev"),
                               check_vma=False))
    x = jax.ShapeDtypeStruct((4, nelems), jnp.float32,
                             sharding=NamedSharding(mesh4, P("dev")))
    compiled = fn.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    shape = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16,
                                 sharding=one_chip)
    fn = jax.jit(functools.partial(flash_attention_kernel, causal=True,
                                   interpret=False))
    compiled = fn.lower(shape, shape, shape).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
