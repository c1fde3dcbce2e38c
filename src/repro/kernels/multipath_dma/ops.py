"""Jit'd wrapper for the multipath DMA kernel (kernel-backed transfers).

``multipath_dma_transfer`` is the drop-in kernel-backed equivalent of
``repro.core.multipath.multipath_send_local``'s engine: same plans, same
cache key space, but the copy nodes execute as Pallas remote DMAs instead of
XLA collective-permutes. On CPU it runs the TPU interpreter
(``pltpu.InterpretParams``); on TPU set ``interpret=False``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.comm.plan import TransferPlan
from repro.kernels.multipath_dma.kernel import build_multipath_dma

AXIS = "dev"


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def multipath_dma_transfer(x: jax.Array, plan: TransferPlan,
                           mesh: jax.sharding.Mesh, *,
                           interpret: bool | None = None) -> jax.Array:
    """Execute ``plan`` on ``x: (num_devices, nelems)`` sharded over ``dev``.

    Returns the same-shape array with ``y[dst] = x[src]`` and identity
    elsewhere.
    """
    if interpret is None:
        interpret = _is_cpu()
    num_devices = mesh.devices.size
    nelems = x.shape[-1]
    inner = build_multipath_dma(plan, nelems, x.dtype, num_devices,
                                axis_name=AXIS, interpret=interpret)

    def local(xl):  # (1, nelems) per device
        return inner(xl[0])[None]

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=P(AXIS),
                           out_specs=P(AXIS), check_vma=False))
    x = jax.device_put(x, NamedSharding(mesh, P(AXIS)))
    return fn(x)


def captured_multipath_dma(cap, x, plan: TransferPlan, num_devices: int, *,
                           name: str = "multipath_dma",
                           axis_name: str = AXIS, telemetry=None,
                           interpret: bool | None = None):
    """Record the kernel-backed multipath DMA on a ``session.capture``
    step.

    ``x`` is a capture ref with local shape ``(nelems,)``; returns the
    same-shape ref with ``y[dst] = x[src]`` (identity elsewhere),
    executing ``plan``'s copy schedule as Pallas remote DMAs inside the
    captured program. The result spec is declared explicitly (``out=``)
    because the kernel's axis collectives cannot be abstractly
    evaluated outside the mesh. ``cost_ns`` is stamped from
    ``telemetry``'s recorded median for ``name`` when a recorder is
    passed, so the lane model prices the DMA kernel's measured
    duration.
    """
    if interpret is None:
        interpret = _is_cpu()
    from repro.comm.capture import BufferSpec
    spec = cap.buffers[cap._resolve(x)]
    (nelems,) = spec.shape
    inner = build_multipath_dma(plan, nelems, jnp.dtype(spec.dtype),
                                num_devices, axis_name=axis_name,
                                interpret=interpret)
    cost = int(telemetry.kernel_cost_ns(name)) if telemetry is not None \
        else 0
    return cap.kernel(inner, x, name=name,
                      out=BufferSpec((nelems,), spec.dtype),
                      cost_ns=cost)
