"""Device time per send inside the compiled transfer program (one
single-pair ``ppermute`` per copy node), on the busiest chip: the time the
program's executions span, in-flight permutes included."""

import re

UNIT = "ms"
BETTER = "lower"
LAYER = "device program"
SOURCE = "device_trace"
MOVES = "send_ms"

#: The transfer program: the jitted ``shard_map`` of the engine's graph body.
MODULE = re.compile(r"local_body")


def read(x):
    per_chip = [d.module_ns(MODULE) for d in x.trace.devices[:x.chips]]
    if not per_chip or max(per_chip) == 0:
        return None
    return max(per_chip) / x.ops / 1e6
