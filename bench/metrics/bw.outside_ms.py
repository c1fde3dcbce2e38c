"""Device time per send in the programs outside the transfer program, on
the busiest chip: the staging write and the extraction of the received
message at ``dst``, and the replicated placement where it runs as a
program."""

import re

UNIT = "ms"
BETTER = "lower"
LAYER = "staging and result placement"
SOURCE = "device_trace"
MOVES = "bw_GBps"

#: The transfer program, as ``bw.program_ms`` finds it.
MODULE = re.compile(r"local_body")


def read(x):
    chips = x.trace.devices[:x.chips]
    if not any(MODULE.search(m.name) for d in chips for m in d.modules):
        return None
    return max(d.module_ns(MODULE, matching=False)
               for d in chips) / x.ops / 1e6
