"""Device time per sweep of the Jacobi Pallas kernel, found by its op kind
(the TPU custom call that Pallas lowers to), not by its name."""

import devtrace

UNIT = "ms"
BETTER = "lower"
LAYER = "Jacobi kernel"
SOURCE = "device_trace"
MOVES = "step_ms"


def is_kernel(ev) -> bool:
    return devtrace.custom_call_target(ev.name) == "tpu_custom_call"


def read(x):
    dev = x.trace.devices[0] if x.trace.devices else None
    if dev is None:
        return None
    ops = [e for e in dev.ops if is_kernel(e)]
    if not ops:
        return None
    return dev.busy_ns(ops) / x.ops / 1e6
