"""Device time per sweep of the Jacobi sweep's XLA glue on device 0: the
ops whose ``tf_op`` path holds one of the sweep's glue scopes (the halo
exchange, the Dirichlet edges, the extended block, the kernel's shifted
views), the Pallas kernel's custom call left out. XLA gives a fused op
its root's path, so only the sum over the glue scopes is read, not one
scope's share."""

import devtrace
import xspace

UNIT = "ms"
BETTER = "lower"
LAYER = "Jacobi glue"
SOURCE = "device_trace"
MOVES = "step_ms"

SCOPES = {"jacobi.halo", "jacobi.edges", "jacobi.extend", "jacobi.views"}


def is_glue(ev, tf_op: dict[str, str]) -> bool:
    if devtrace.custom_call_target(ev.name) == "tpu_custom_call":
        return False
    return not SCOPES.isdisjoint(tf_op.get(ev.name, "").split("/"))


def read(x):
    run = xspace.load(x)
    if run is None:
        return None
    dev = x.trace.devices[0]
    ops = [e for e in dev.ops if is_glue(e, run.tf_op)]
    if not ops:
        return None
    return dev.busy_ns(ops) / x.ops / 1e6
