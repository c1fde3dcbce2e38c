"""Jit'd wrapper for the bidirectional ring all-gather kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.kernels.ring_allgather.kernel import build_ring_allgather

AXIS = "dev"


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def ring_allgather(x: jax.Array, mesh: jax.sharding.Mesh, *,
                   interpret: bool | None = None) -> jax.Array:
    """x: (N*rows, f) sharded over 'dev' → fully gathered (N*rows, f) on
    every device (replicated)."""
    if interpret is None:
        interpret = _is_cpu()
    n = mesh.devices.size
    rows = x.shape[0] // n
    inner = build_ring_allgather((rows, x.shape[1]), x.dtype, n,
                                 axis_name=AXIS, interpret=interpret)
    fn = jax.jit(shard_map(inner, mesh=mesh, in_specs=P(AXIS),
                           out_specs=P(None), check_vma=False))
    x = jax.device_put(x, NamedSharding(mesh, P(AXIS)))
    return fn(x)


def captured_ring_allgather(cap, x, num_devices: int, *,
                            name: str = "ring_allgather",
                            axis_name: str = AXIS, telemetry=None,
                            interpret: bool | None = None):
    """Record the ring all-gather kernel on a ``session.capture`` step.

    ``x`` is a capture ref with local shape ``(rows, f)``; returns the
    gathered ``(num_devices * rows, f)`` ref (every device holds the
    full result). ``axis_name`` must equal the session's SPMD axis —
    the kernel's collective permutes run inside the captured program's
    mesh. The result spec is declared explicitly (``out=``): the kernel
    uses axis collectives that cannot be abstractly evaluated outside
    the mesh. ``flops`` stays 0 — this is wire work — but ``cost_ns``
    is stamped from ``telemetry``'s recorded median for ``name`` when a
    recorder is passed, so its measured duration occupies the lane
    model's compute lane honestly.
    """
    if interpret is None:
        interpret = _is_cpu()
    from repro.comm.capture import BufferSpec
    spec = cap.buffers[cap._resolve(x)]
    rows, f = spec.shape
    inner = build_ring_allgather((rows, f), jnp.dtype(spec.dtype),
                                 num_devices, axis_name=axis_name,
                                 interpret=interpret)
    cost = int(telemetry.kernel_cost_ns(name)) if telemetry is not None \
        else 0
    return cap.kernel(inner, x, name=name,
                      out=BufferSpec((num_devices * rows, f), spec.dtype),
                      cost_ns=cost)
