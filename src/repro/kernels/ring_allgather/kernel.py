"""Pallas TPU kernel: bidirectional-ring all-gather over remote DMA.

Kernel-level realization of the beyond-paper multipath collectives
(EXPERIMENTS.md §Perf N4): every step drives BOTH directional ICI links —
the clockwise chain carries the first half of each shard, the
counter-clockwise chain the second half — so the busiest-link bytes halve
vs a unidirectional ring (`core/collectives.py` is the XLA-level
equivalent; this is the hand-scheduled DMA version).

Structure per device (N-1 steps):

* init: local DMA of the own shard into output slot ``i``; global barrier,
* step s: send slot ``(i−s) mod N`` [:half] right and slot ``(i+s) mod N``
  [half:] left — two concurrent remote DMAs on distinct links with
  independent semaphore pairs (the paper's per-path streams) — then wait
  the two incoming slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _ring_ag_kernel(x_ref, o_ref, init_sem, cw_send, cw_recv, ccw_send,
                    ccw_recv, *, num_devices: int, axis_name: str,
                    half: int):
    n = num_devices
    me = lax.axis_index(axis_name)
    right = lax.rem(me + 1, n)
    left = lax.rem(me + n - 1, n)

    # own shard into own slot, then barrier before any remote write
    init = pltpu.make_async_copy(x_ref, o_ref.at[me], init_sem)
    init.start()
    init.wait()
    bar = pltpu.get_barrier_semaphore()
    for d in range(n):
        pltpu.semaphore_signal(bar, 1, device_id=(d,),
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(bar, n)

    f = o_ref.shape[-1]
    for s in range(n - 1):
        cw_slot = lax.rem(me - s + n, n)       # block travelling clockwise
        ccw_slot = lax.rem(me + s, n)          # block travelling ccw
        cw = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[cw_slot, :, pl.ds(0, half)],
            dst_ref=o_ref.at[cw_slot, :, pl.ds(0, half)],
            send_sem=cw_send.at[s], recv_sem=cw_recv.at[s],
            device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)
        ccw = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[ccw_slot, :, pl.ds(half, f - half)],
            dst_ref=o_ref.at[ccw_slot, :, pl.ds(half, f - half)],
            send_sem=ccw_send.at[s], recv_sem=ccw_recv.at[s],
            device_id=(left,), device_id_type=pltpu.DeviceIdType.MESH)
        cw.start()                             # both links active
        ccw.start()
        cw.wait_send()
        ccw.wait_send()
        # incoming: cw block from left lands in slot (me-s-1); ccw block
        # from right lands in slot (me+s+1)
        cw.wait_recv()
        ccw.wait_recv()


def build_ring_allgather(shard_shape: tuple, dtype, num_devices: int, *,
                         axis_name: str = "dev", interpret: bool = True,
                         collective_id: int = 11):
    """Returns fn(x_local (rows, f)) -> (N*rows, f) for use in shard_map."""
    rows, f = shard_shape
    half = f // 2
    if half == 0:
        half = f  # degenerate narrow case: single direction

    kernel = functools.partial(
        _ring_ag_kernel, num_devices=num_devices, axis_name=axis_name,
        half=half)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_devices, rows, f), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA]
        + [pltpu.SemaphoreType.DMA((max(1, num_devices - 1),))] * 4,
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=pltpu.InterpretParams() if interpret else False,
    )

    def fn(x_local):
        return call(x_local).reshape(num_devices * rows, f)

    return fn
