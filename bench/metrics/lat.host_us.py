"""Host time per send in the session and engine fast path
(``session.send`` -> ``_resolve`` -> ``_launch``): the median of the
engine's own ``staging_ns + launch_ns`` over the window's sends."""

import statistics

UNIT = "us"
BETTER = "lower"
LAYER = "session fast path"
SOURCE = "program_span"
MOVES = "lat_p95_us"


def read(x):
    samples = x.extra.get("telemetry", ())
    if not samples:
        return None
    return statistics.median(
        s.stages.staging_ns + s.stages.launch_ns for s in samples) / 1e3
