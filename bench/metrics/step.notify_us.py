"""Time per sweep from the start of the runtime's completion callbacks
for its program on device 0 (``CompleteCallbacks``, matched by
``run_id``) to the end of the host's wait for it (``bench.wait``): the
host's completion path once it has seen the program end, read from the
host's clock alone (``xspace.host_split``). The median over the window;
nothing when a call does not launch exactly one program on device 0."""

import statistics

import xspace

UNIT = "us"
BETTER = "lower"
LAYER = "completion callbacks"
SOURCE = "program_span"
MOVES = "step_ms"


def read(x):
    run = xspace.load(x)
    if run is None or not run.sweeps:
        return None
    return statistics.median(xspace.host_split(run.sweeps)["notify"]) / 1e3
