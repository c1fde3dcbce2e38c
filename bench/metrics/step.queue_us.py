"""Time per sweep from the end of the enqueue of its program for device 0
(``DoEnqueueProgram``) to the start of the runtime's completion callbacks
for it (``CompleteCallbacks``, same ``run_id``), less the program's run on
device 0: the launch queue and the time the host took to see the
program's end, undivided (``xspace.host_split``). An upper bound on the
launch queue, read from the host's clock alone. The median over the
window; nothing when a call does not launch exactly one program on
device 0."""

import statistics

import xspace

UNIT = "us"
BETTER = "lower"
LAYER = "launch queue + completion signal"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(x):
    run = xspace.load(x)
    if run is None or not run.sweeps:
        return None
    return statistics.median(xspace.host_split(run.sweeps)["queue"]) / 1e3
