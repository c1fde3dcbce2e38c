"""The whole sweep's share of the HBM roofline: the least time the chip
needs to read the grid once and write it once, over the device's busy time
per sweep (kernel and XLA glue). The bytes are the algorithm's, so the
share reads the same work whatever implements the sweep. Nothing where
the run gives no grid's shape."""

UNIT = "%"
BETTER = "higher"
LAYER = "Jacobi sweep"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(x):
    dev = x.trace.devices[0] if x.trace.devices else None
    busy = dev.busy_ns() if dev is not None else 0
    if busy == 0 or not {"rows", "cols"} <= x.extra.keys():
        return None
    nbytes = 2 * x.extra["rows"] * x.extra["cols"] * 4
    least_s = nbytes / (x.peaks["hbm_GBps"] * 1e9)
    return 100 * least_s / (busy / 1e9 / x.ops)
