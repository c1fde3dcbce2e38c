"""Device busy time per send on the busiest chip, over every program a
send runs (placement, staging, transfer program, extraction): what the
chips do for a send, beside what the host does (``send.host_us``).
Nothing where no transfer program ran."""

import re

UNIT = "us"
BETTER = "lower"
LAYER = "device programs of a send"
SOURCE = "device_trace"
MOVES = "step_ms"

#: The transfer program, as ``send.program_ms`` finds it.
MODULE = re.compile(r"local_body")


def read(x):
    chips = x.trace.devices[:x.chips]
    if not any(MODULE.search(m.name) for d in chips for m in d.modules):
        return None
    return max(d.busy_ns() for d in chips) / x.ops / 1e3
