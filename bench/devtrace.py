"""Reduce a JAX profiler trace to what the per-layer metrics read.

``jax.profiler`` writes one ``*.xplane.pb`` per host. In it every TPU is a
plane named ``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
program execution and its ``XLA Ops`` line one event per operation. Host
threads are planes of their own (``/host:CPU``), whose lines hold the spans
that ``jax.profiler.TraceAnnotation`` and the runtime write.

Run it on a trace to look at one by hand:

    python bench/devtrace.py path/to/host.xplane.pb
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Device:
    """One chip's operations and program executions, sorted by start."""

    id: int
    ops: list[Event]
    modules: list[Event]

    def busy_ns(self, events=None) -> float:
        return length(union(self.ops if events is None else events))

    def ops_in(self, pattern: re.Pattern) -> list[Event]:
        """Operations run inside executions of a module whose name matches
        ``pattern``."""
        spans = union(m for m in self.modules if pattern.search(m.name))
        return [e for e in self.ops if _inside(e, spans)]

    def module_ns(self, pattern: re.Pattern, matching: bool = True
                  ) -> float:
        """Time spanned by executions of the modules whose name matches
        ``pattern`` (or, with ``matching=False``, of all the others)."""
        return length(union(m for m in self.modules
                            if bool(pattern.search(m.name)) == matching))


@dataclass
class Trace:
    devices: list[Device]
    #: Host thread name -> its spans, sorted by start.
    host: dict[str, list[Event]]

    def host_line(self, span: str) -> list[Event]:
        """The spans of the host thread that wrote a span named ``span``
        (the harness's window span marks the thread that drives the run)."""
        for events in self.host.values():
            if any(e.name == span for e in events):
                return events
        return []


def _events(line) -> list[Event]:
    out = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for e in line.events]
    out.sort(key=lambda e: e.start_ns)
    return out


def find_xplane(profile_dir: str | Path) -> Path:
    found = sorted(Path(profile_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    """Read one ``*.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    devices, host = [], {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                id=int(m.group(1)),
                ops=_events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                modules=(_events(lines[MODULES_LINE])
                         if MODULES_LINE in lines else [])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host[f"{plane.name}/{ln.name}"] = _events(ln)
    devices.sort(key=lambda d: d.id)
    return Trace(devices, host)


# -- interval arithmetic -----------------------------------------------------

def union(events) -> list[tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint spans."""
    spans = sorted((e.start_ns, e.end_ns) if isinstance(e, Event) else e
                   for e in events)
    out: list[list[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(spans) -> float:
    return sum(e - s for s, e in spans)


def gaps(spans) -> list[tuple[float, float]]:
    """The idle stretches between consecutive busy spans."""
    return [(a[1], b[0]) for a, b in zip(spans, spans[1:]) if b[0] > a[1]]


def _inside(ev: Event, spans) -> bool:
    mid = (ev.start_ns + ev.end_ns) / 2
    lo, hi = 0, len(spans)
    while lo < hi:
        m = (lo + hi) // 2
        if spans[m][1] < mid:
            lo = m + 1
        else:
            hi = m
    return lo < len(spans) and spans[lo][0] <= mid <= spans[lo][1]


def base_name(name: str) -> str:
    """A module or span name without its numeric suffix: ``jit_stage(3)``
    -> ``jit_stage``, ``fusion.12`` -> ``fusion``."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """An op event's name is its HLO instruction's text; this is the
    instruction's own name (``%fusion.3 = f32[8] fusion(...)`` ->
    ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def op_kind(name: str) -> str:
    """The HLO opcode of an op event: ``fusion``, ``custom-call``,
    ``collective-permute-start``..."""
    if " = " not in name:
        return base_name(name)
    rest = name.split(" = ", 1)[1]
    if rest.startswith("("):                # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    elif " " in rest:
        rest = rest.split(" ", 1)[1]
    return rest.lstrip().split("(", 1)[0]


def custom_call_target(name: str) -> str | None:
    m = _TARGET.search(name)
    return m.group(1) if m else None


# -- what the harness reports --------------------------------------------------

def top_ops(trace: Trace, devices: int, n: int = 10
            ) -> list[tuple[str, float]]:
    """The operations that took most device time, in seconds per chip."""
    total: dict[str, float] = defaultdict(float)
    for d in trace.devices[:devices]:
        for e in d.ops:
            total[op_name(e.name)] += e.dur_ns
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / devices / 1e9) for k, v in top]


def window(trace: Trace, window_span: str) -> Event | None:
    """The host span around the measured window."""
    return next((e for e in trace.host_line(window_span)
                 if e.name == window_span), None)


def cut_short(trace: Trace, window_span: str, call_span: str,
              margin: float = 0.1) -> list[Device]:
    """The chips whose record stops well before the window's last call
    (``call_span``) began: the profiler dropped the rest of their events,
    and reading them would count the lost time as idle. Every chip runs
    a part of each call's work, so a whole record ends after the last call
    began. Device and host clocks differ by a few milliseconds, so
    "well before" is by ``margin`` of the window's length."""
    line = trace.host_line(window_span)
    w = window(trace, window_span)
    calls = [e for e in line if e.name == call_span]
    if w is None or not calls:
        return []
    edge = calls[-1].start_ns - margin * (w.end_ns - w.start_ns)
    return [d for d in trace.devices
            if max((e.end_ns for e in d.ops), default=edge) < edge]


def idle_gaps(trace: Trace, window_span: str, n: int = 10
              ) -> list[tuple[str, float]]:
    """The first chip's idle time inside the window, grouped by what the
    driving host thread was doing in the middle of each gap (its innermost
    span), longest first, in seconds."""
    line = trace.host_line(window_span)
    w = window(trace, window_span)
    if not trace.devices or w is None:
        return []
    busy = union([(w.start_ns, w.start_ns), (w.end_ns, w.end_ns)]
                 + union(trace.devices[0].ops))
    total: dict[str, float] = defaultdict(float)
    stack: list[Event] = []
    j = 0
    for s, e in gaps(busy):                 # in time order
        s, e = max(s, w.start_ns), min(e, w.end_ns)
        if e <= s:
            continue
        mid = (s + e) / 2
        while j < len(line) and line[j].start_ns <= mid:
            while stack and stack[-1].end_ns < line[j].start_ns:
                stack.pop()
            stack.append(line[j])
            j += 1
        while stack and stack[-1].end_ns < mid:
            stack.pop()
        total[base_name(stack[-1].name) if stack else "none"] += e - s
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]


def describe(trace: Trace, limit: int = 8) -> str:
    """A look at a trace by hand: per device, its busy time, its modules and
    its commonest operations with their stats."""
    out = []
    for d in trace.devices:
        out.append(f"device {d.id}: {len(d.ops)} ops, {len(d.modules)} "
                   f"module runs, busy {d.busy_ns() / 1e6:.3f} ms")
        mods: dict[str, list[float]] = defaultdict(list)
        for m in d.modules:
            mods[base_name(m.name)].append(m.dur_ns)
        for k, v in sorted(mods.items(), key=lambda kv: -sum(kv[1])):
            out.append(f"  module {k}: {len(v)} runs, "
                       f"{sum(v) / 1e6:.3f} ms")
        ops: dict[str, list[Event]] = defaultdict(list)
        for e in d.ops:
            ops[op_kind(e.name)].append(e)
        for k, v in sorted(ops.items(),
                           key=lambda kv: -sum(e.dur_ns for e in kv[1])
                           )[:limit]:
            out.append(f"  op {k}: {len(v)} events, "
                       f"{sum(e.dur_ns for e in v) / 1e6:.3f} ms; e.g. "
                       f"{v[0].name[:300]}")
    for line, events in trace.host.items():
        names: dict[str, int] = defaultdict(int)
        for h in events:
            names[base_name(h.name)] += 1
        top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
        out.append(f"host {line}: {len(events)} spans; commonest {top}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
