"""A send's share of the ICI roofline: the least time the message's bytes
need on a chip's interconnect, ``nbytes`` over the chip's whole ICI
bandwidth (``ici_Gbps`` of the peak table, all its links together), over
the busiest chip's device busy time per send. The bytes are the
message's, so the share reads the same work whatever implements the send.

Every byte of the message leaves ``src`` over its links, and the busiest
chip is busy at least as long as ``src``, whose transfer program runs from
its permutes' start to their end. Against the chip's whole ICI bandwidth
the share therefore cannot pass 100 %: a reading above it means that the
trace left part of the transfer out of the busy time. Nothing where the
run gives no message size or no chip was busy."""

UNIT = "%"
BETTER = "higher"
LAYER = "send"
SOURCE = "device_trace"
MOVES = "send_ms"


def read(x):
    nbytes = x.extra.get("nbytes")
    busy = max((d.busy_ns() for d in x.trace.devices[:x.chips]), default=0)
    if nbytes is None or busy == 0:
        return None
    least_s = nbytes / (x.peaks["ici_Gbps"] * 1e9 / 8)
    return 100 * least_s / (busy / 1e9 / x.ops)
