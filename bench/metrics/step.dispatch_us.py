"""Host time per sweep from the start of the call (``bench.call``) to the
end of the runtime's enqueue of that sweep's program for device 0
(``DoEnqueueProgram``, matched to the call by time and to the program by
``run_id``): Python, jit dispatch and PJRT's launch. The median over the
window; nothing when a call does not launch exactly one program on
device 0."""

import statistics

import xspace

UNIT = "us"
BETTER = "lower"
LAYER = "host dispatch"
SOURCE = "program_span"
MOVES = "step_ms"


def read(x):
    run = xspace.load(x)
    if run is None or not run.sweeps:
        return None
    return statistics.median(xspace.host_split(run.sweeps)["dispatch"]) / 1e3
