"""Pallas TPU kernel: plan-driven multi-path chunked remote-DMA transfer.

This is the TPU-native realization of the paper's CUDA Graph (Fig. 5): the
:class:`~repro.core.paths.TransferPlan` is compiled into ONE kernel whose
DMA ops are the graph's copy nodes and whose semaphore waits are its
dependency edges:

* a **direct path** chunk is one ``make_async_remote_copy`` src→dst
  (= one ``PeerToPeerCopy`` node, Alg. 2),
* a **staged path** chunk is hop-1 src→staging-VMEM-on-via plus hop-2
  via→dst, where hop-2 waits only on its own hop-1 recv semaphore
  (= ``StageGPUCopy`` with the Alg. 2 line-19 dependency),
* per-path semaphore pairs play the role of the paper's per-path CUDA
  streams: chunks on different paths proceed fully independently.

The kernel body is SPMD over the mesh axis: every device executes it, and
``pl.when(my_id == …)`` selects the src/via/dst roles (senders start DMAs,
receivers wait on recv semaphores). A global barrier after the local
init-copy guarantees no remote write lands before the destination buffer is
initialized (§4.5 final-synchronization analogue).

Adaptation note (DESIGN.md §2): the paper's host path has no executable TPU
analogue and is rejected; staging buffers live in the via-chip's VMEM,
sized per-chunk — hop-granular flow control comes from the per-chunk
staging slots (a production kernel would credit-signal to reuse two slots;
we allocate ``num_chunks`` slots which bounds VMEM by the path share).

Alignment: Mosaic tiles a 1-D HBM or VMEM buffer in runs of
:data:`DMA_TILE_ELEMS` elements and refuses a DMA slice that does not start
and end on that tiling. Plans for this kernel are therefore made at
:func:`dma_granularity` bytes, and each staging buffer is one flat run of
equal slots so that every slot starts on a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.comm.plan import TransferPlan
from repro.core.topology import HOST


#: Elements per tile of a 1-D buffer under Mosaic, for every dtype the
#: kernel moves (f32, bf16 and int8 alike, checked by compiling for v5e).
DMA_TILE_ELEMS = 1024


def dma_granularity(dtype) -> int:
    """Chunk granularity in bytes for plans this kernel executes: pass it
    as ``granularity=`` to the planner."""
    return DMA_TILE_ELEMS * jnp.dtype(dtype).itemsize


def _element_bounds(plan: TransferPlan, itemsize: int):
    """Static (path -> [(offset_elems, size_elems)]) chunk table."""
    gran = DMA_TILE_ELEMS * itemsize
    table = []
    for pa in plan.paths:
        if pa.route.via == HOST:
            raise ValueError("host-staged path not executable on TPU mesh")
        chunks = []
        for off_b, size_b in pa.chunk_bounds():
            if off_b % gran or size_b % gran:
                raise ValueError(
                    f"chunk ({off_b}, {size_b}) is not aligned to the DMA "
                    f"tiling of {DMA_TILE_ELEMS} elements; plan with "
                    f"granularity=dma_granularity(dtype)")
            chunks.append((off_b // itemsize, size_b // itemsize))
        table.append(chunks)
    return table


def _multipath_dma_kernel(x_ref, o_ref, *scratch, plan: TransferPlan,
                          chunk_table, num_devices: int, axis_name: str):
    npaths = len(plan.paths)
    stage_refs = scratch[:npaths]
    (init_sem, h1_send, h1_recv, h2_send, h2_recv) = scratch[npaths:]
    my = lax.axis_index(axis_name)
    src, dst = plan.src, plan.dst

    # 1) local init: every device's output starts as its input, so the
    #    transfer is an identity for non-participants and the destination
    #    region is defined before remote chunks land.
    init = pltpu.make_async_copy(x_ref, o_ref, init_sem)
    init.start()
    init.wait()

    # 2) global barrier: no remote write may precede any init completion.
    bar = pltpu.get_barrier_semaphore()
    for d in range(num_devices):
        pltpu.semaphore_signal(bar, 1, device_id=(d,),
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(bar, num_devices)

    # 3) the transfer graph. Python loops are static — each iteration emits
    #    one copy node, exactly like the explicit CUDA Graph API in Alg. 2.
    for p, (pa, chunks) in enumerate(zip(plan.paths, chunk_table)):
        via = pa.route.via
        if via is None:
            # ---- direct path: one node per chunk --------------------------
            for c, (off, size) in enumerate(chunks):
                node = pltpu.make_async_remote_copy(
                    src_ref=x_ref.at[pl.ds(off, size)],
                    dst_ref=o_ref.at[pl.ds(off, size)],
                    send_sem=h1_send.at[p, c], recv_sem=h1_recv.at[p, c],
                    device_id=(dst,),
                    device_id_type=pltpu.DeviceIdType.MESH)

                @pl.when(my == src)
                def _(node=node):
                    node.start()

                @pl.when(my == dst)
                def _(node=node):
                    node.wait_recv()

            @pl.when(my == src)
            def _(p=p, chunks=chunks):
                for c, (off, size) in enumerate(chunks):
                    pltpu.make_async_remote_copy(
                        src_ref=x_ref.at[pl.ds(off, size)],
                        dst_ref=o_ref.at[pl.ds(off, size)],
                        send_sem=h1_send.at[p, c], recv_sem=h1_recv.at[p, c],
                        device_id=(dst,),
                        device_id_type=pltpu.DeviceIdType.MESH).wait_send()
        else:
            # ---- staged path: hop-1 into via's staging slot, hop-2 out ----
            stage = stage_refs[p]
            slot = max(size for _, size in chunks)   # 1-D slot stride
            for c, (off, size) in enumerate(chunks):
                h1 = pltpu.make_async_remote_copy(
                    src_ref=x_ref.at[pl.ds(off, size)],
                    dst_ref=stage.at[pl.ds(c * slot, size)],
                    send_sem=h1_send.at[p, c], recv_sem=h1_recv.at[p, c],
                    device_id=(via,),
                    device_id_type=pltpu.DeviceIdType.MESH)
                h2 = pltpu.make_async_remote_copy(
                    src_ref=stage.at[pl.ds(c * slot, size)],
                    dst_ref=o_ref.at[pl.ds(off, size)],
                    send_sem=h2_send.at[p, c], recv_sem=h2_recv.at[p, c],
                    device_id=(dst,),
                    device_id_type=pltpu.DeviceIdType.MESH)

                @pl.when(my == src)
                def _(h1=h1):
                    h1.start()

                @pl.when(my == via)
                def _(h1=h1, h2=h2):
                    h1.wait_recv()   # dependency edge (Alg. 2 line 19)
                    h2.start()

                @pl.when(my == dst)
                def _(h2=h2):
                    h2.wait_recv()

            @pl.when(my == src)
            def _(p=p, chunks=chunks, via=via, stage=stage, slot=slot):
                for c, (off, size) in enumerate(chunks):
                    pltpu.make_async_remote_copy(
                        src_ref=x_ref.at[pl.ds(off, size)],
                        dst_ref=stage.at[pl.ds(c * slot, size)],
                        send_sem=h1_send.at[p, c], recv_sem=h1_recv.at[p, c],
                        device_id=(via,),
                        device_id_type=pltpu.DeviceIdType.MESH).wait_send()

            @pl.when(my == via)
            def _(p=p, chunks=chunks, stage=stage, slot=slot):
                for c, (off, size) in enumerate(chunks):
                    pltpu.make_async_remote_copy(
                        src_ref=stage.at[pl.ds(c * slot, size)],
                        dst_ref=o_ref.at[pl.ds(off, size)],
                        send_sem=h2_send.at[p, c], recv_sem=h2_recv.at[p, c],
                        device_id=(dst,),
                        device_id_type=pltpu.DeviceIdType.MESH).wait_send()


def build_multipath_dma(plan: TransferPlan, nelems: int, dtype,
                        num_devices: int, *, axis_name: str = "dev",
                        interpret: bool = True, collective_id: int = 7):
    """Return ``fn(x_local) -> y_local`` executing ``plan``, for use inside
    ``jax.shard_map`` over ``axis_name``. ``x_local`` shape ``(nelems,)``."""
    dtype = jnp.dtype(dtype)
    for pa in plan.paths:
        if pa.route.num_hops > 2:
            raise NotImplementedError(
                "the DMA kernel implements direct and 2-hop staged routes "
                "(paper Alg. 2); 3-hop torus detours run on the ppermute "
                "engine (repro.core.multipath)")
    chunk_table = _element_bounds(plan, dtype.itemsize)
    npaths = len(plan.paths)
    max_chunks = max(len(c) for c in chunk_table)

    scratch = []
    for pa, chunks in zip(plan.paths, chunk_table):
        max_size = max((s for _, s in chunks), default=1)
        # staging slots only used on staged paths; direct paths get a
        # minimal placeholder so scratch indices stay aligned with paths.
        slots = len(chunks) if pa.route.via is not None else 1
        size = max_size if pa.route.via is not None else 8
        scratch.append(pltpu.VMEM((slots * size,), dtype))
    scratch += [
        pltpu.SemaphoreType.DMA,                        # init
        pltpu.SemaphoreType.DMA((npaths, max_chunks)),  # h1 send
        pltpu.SemaphoreType.DMA((npaths, max_chunks)),  # h1 recv
        pltpu.SemaphoreType.DMA((npaths, max_chunks)),  # h2 send
        pltpu.SemaphoreType.DMA((npaths, max_chunks)),  # h2 recv
    ]

    kernel = functools.partial(
        _multipath_dma_kernel, plan=plan, chunk_table=chunk_table,
        num_devices=num_devices, axis_name=axis_name)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nelems,), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )
