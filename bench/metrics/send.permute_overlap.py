"""How many of the transfer program's collective-permutes are in flight at
once, on the chip that spends most time in the transfer program: the sum
of their durations over the length of their union. 1.0 means the paths ran
one after another. A permute runs from its ``collective-permute-start`` to
the end of its ``collective-permute-done`` (or is one synchronous
``collective-permute``)."""

import re

import devtrace

UNIT = "x"
BETTER = "higher"
LAYER = "device program"
SOURCE = "device_trace"
MOVES = "send_ms"

#: The transfer program, as ``send.program_ms`` finds it.
MODULE = re.compile(r"local_body")


def permute_intervals(ops) -> list[tuple[float, float]]:
    out, started = [], {}
    for e in ops:
        kind = devtrace.op_kind(e.name)
        if kind == "collective-permute":
            out.append((e.start_ns, e.end_ns))
        elif kind == "collective-permute-start":
            started[devtrace.op_name(e.name)] = e
        elif kind == "collective-permute-done":
            for name, s in list(started.items()):
                if f"%{name})" in e.name or f"%{name}," in e.name:
                    out.append((started.pop(name).start_ns, e.end_ns))
                    break
    return out


def read(x):
    chips = x.trace.devices[:x.chips]
    if not chips:
        return None
    busiest = max(chips, key=lambda d: d.module_ns(MODULE))
    spans = permute_intervals(busiest.ops_in(MODULE))
    if not spans:
        return None
    return sum(e - s for s, e in spans) / devtrace.length(
        devtrace.union(spans))
