"""Device busy time per send on the busiest chip, over every program a
send runs (placement, staging, transfer program, extraction): what the
chips do for a send, beside what the host does (``lat.host_us``)."""

UNIT = "us"
BETTER = "lower"
LAYER = "device programs of a send"
SOURCE = "device_trace"
MOVES = "lat_p95_us"


def read(x):
    busy = [d.busy_ns() for d in x.trace.devices[:x.chips]]
    if not busy or max(busy) == 0:
        return None
    return max(busy) / x.ops / 1e3
