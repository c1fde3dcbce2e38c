"""What ``devtrace`` leaves out of a profiler trace: each XLA op's ``tf_op``
path, and the ``run_id`` that ties a program's enqueue on the host to its
execution on a device and to the host's completion callbacks for it.

``jax.profiler.ProfileData`` gives events and their own stats, not the
stats of the event metadata, where the ``tf_op`` path of an op lives; this
module decodes those from the ``XSpace`` wire format (tsl's ``xplane.proto``:
an ``XSpace`` holds ``XPlane``s, each with ``event_metadata`` and
``stat_metadata`` maps).

:func:`load` reads the trace that the run itself wrote, whose path
``run.py`` hands the per-layer readers (``LayerInputs.trace_path``).
"""

from __future__ import annotations

import functools
import struct
from dataclasses import dataclass
from pathlib import Path

import devtrace

DEVICE0 = "/device:TPU:0"
ENQUEUE = "DoEnqueueProgram"
CALLBACKS = "CompleteCallbacks"
#: Spans that ``run.py`` puts around each operation of the window.
CALL, WAIT = "bench.call", "bench.wait"


# -- the wire format ----------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message: an int, or a memoryview for
    a length-delimited field, or raw bytes for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = bytes(buf[i:i + width]), i + width
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield key >> 3, value


def _stat_value(stat, names: dict[int, str]):
    """One ``XStat``: (its name, its value)."""
    name = value = None
    for f, v in _fields(stat):
        if f == 1:
            name = names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = bytes(v).decode(errors="replace")
        elif f == 7:
            value = names.get(v)
    return name, value


def metadata_stats(path: str | Path, plane: str) -> dict[str, dict]:
    """Event-metadata name -> the metadata's stats, of the plane named
    ``plane``. An op event's name (as ``ProfileData`` gives it) is its
    metadata's name, so this maps each op to its ``tf_op``,
    ``hlo_category``, ``bytes_accessed`` and the rest."""
    space = memoryview(Path(path).read_bytes())
    for f, body in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(body))
        if not any(k == 2 and bytes(v).decode() == plane
                   for k, v in fields):
            continue
        entries = {4: [], 5: []}
        for k, v in fields:
            if k in entries:
                entries[k].append(dict(_fields(v)).get(2, b""))
        names = {}
        for meta in entries[5]:
            m = dict(_fields(meta))
            names[m.get(1, 0)] = bytes(m.get(2, b"")).decode()
        out = {}
        for meta in entries[4]:
            name, stats = None, {}
            for k, v in _fields(meta):
                if k == 2:
                    name = bytes(v).decode(errors="replace")
                elif k == 5:
                    key, value = _stat_value(v, names)
                    stats[key] = value
            if name is not None:
                out[name] = stats
        return out
    return {}


# -- one traced run -----------------------------------------------------------

@dataclass(frozen=True)
class Sweep:
    """One operation of the window, in nanoseconds on the host's clock:
    ``call`` the start of ``run.py``'s call span, ``enqueued`` the end of
    the runtime's ``DoEnqueueProgram`` for device 0, ``callbacks`` the
    start of the runtime's ``CompleteCallbacks`` for that program, and
    ``waited`` the end of the wait span; ``device`` the length of the
    program's execution on device 0."""

    call: float
    enqueued: float
    device: float
    callbacks: float
    waited: float


@dataclass(frozen=True)
class Run:
    #: Device 0's op event name -> the op's ``tf_op`` path.
    tf_op: dict[str, str]
    #: One entry per call of the window, or None when a call does not
    #: launch exactly one program on device 0.
    sweeps: list[Sweep] | None


def load(x) -> Run | None:
    """What the run's own trace holds beyond ``x.trace``; None where the
    run gives no trace file."""
    if x.trace_path is None or not x.trace.devices:
        return None
    return _read(str(x.trace_path))


@functools.lru_cache(maxsize=1)
def _read(path: str) -> Run:
    from jax.profiler import ProfileData

    tf_op = {k: v["tf_op"] for k, v in metadata_stats(path, DEVICE0).items()
             if "tf_op" in v}
    calls, waits, enqueues = [], [], []
    modules, callbacks = {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == DEVICE0 and line.name == devtrace.MODULES_LINE:
                for e in line.events:
                    run_id = dict(e.stats).get("run_id")
                    modules.setdefault(run_id, []).append(e)
                continue
            if not plane.name.startswith("/host:"):
                continue
            for e in line.events:
                if e.name == ENQUEUE:
                    stats = dict(e.stats)
                    if stats.get("device_ordinal") == 0:
                        enqueues.append((e.start_ns, e.end_ns,
                                         stats.get("run_id")))
                elif e.name == CALLBACKS:
                    run_id = dict(e.stats).get("run_id")
                    callbacks.setdefault(run_id, []).append(e.start_ns)
                elif e.name == CALL:
                    calls.append(e)
                elif e.name == WAIT:
                    waits.append(e)
    return Run(tf_op, _sweeps(calls, waits, enqueues, modules, callbacks))


def _sweeps(calls, waits, enqueues, modules, callbacks) -> list[Sweep] | None:
    calls.sort(key=lambda e: e.start_ns)
    waits.sort(key=lambda e: e.start_ns)
    enqueues.sort()
    if not calls or len(waits) != len(calls):
        return None
    out, j = [], 0
    for call, wait in zip(calls, waits):
        while j < len(enqueues) and enqueues[j][0] < call.start_ns:
            j += 1
        inside = []
        while j < len(enqueues) and enqueues[j][0] <= call.end_ns:
            inside.append(enqueues[j])
            j += 1
        if len(inside) != 1 or not call.end_ns <= wait.start_ns:
            return None
        (_, enqueued, run_id), = inside
        runs, done = modules.get(run_id, []), callbacks.get(run_id, [])
        if len(runs) != 1 or len(done) != 1:
            return None
        out.append(Sweep(call.start_ns, enqueued,
                         runs[0].end_ns - runs[0].start_ns, done[0],
                         wait.end_ns))
    return out


def host_split(sweeps: list[Sweep]) -> dict[str, list[float]]:
    """Each sweep's host hand-off in nanoseconds: ``dispatch`` (call to
    enqueued), ``queue`` (enqueued to the program's callbacks, less the
    program's run on device 0) and ``notify`` (the callbacks' start to the
    end of the wait).

    Only host times are set against each other: the profile does not put
    the device's clock on the host's (v5e traces place programs 0.2-1.5 ms
    before their enqueue began, by an offset that moves within a window).
    A program ends before the runtime runs its completion callbacks, so
    ``queue`` holds the launch queue and the time the host took to see the
    program's end, undivided: an upper bound on the launch queue.
    ``notify`` is what the host's completion path took once it saw it.
    """
    out = {"dispatch": [], "queue": [], "notify": []}
    for s in sweeps:
        out["dispatch"].append(s.enqueued - s.call)
        out["queue"].append(s.callbacks - s.enqueued - s.device)
        out["notify"].append(s.waited - s.callbacks)
    return out
