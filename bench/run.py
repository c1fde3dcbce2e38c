#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``. The run enables JAX's
persistent compilation cache at a fixed path, builds the cell's data on the
device from ``--seed``, warms up the cell's own shapes (all of that is
``setup_s``), then drives the cell's operation for ``--seconds`` seconds in
a closed loop: one caller, one operation in flight, each waited on. After
the window it reads the devices' peak memory, frees the program's state and
compares a sample of the answers, drawn from the seed, with the plain
reference. The comparisons go to standard error as the last lines, and the
last line of standard output is one JSON object with the result.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
per-layer ones, read from the trace.

With no TPU, or fewer chips than the cell asks for, the run exits with
code 2 and prints no result. It never falls back to the CPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

#: Host span around the measured window in a traced run.
WINDOW_SPAN = "bench.window"
#: JAX records this once for every program it compiles or loads from the
#: persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclass
class Setup:
    """What a driver is given to build its run from."""

    cell: harness.Cell
    devices: list
    seed: int
    control: bool = False


@dataclass
class LayerInputs:
    """What a per-layer metric reads."""

    trace: object
    ops: int
    window_s: float
    chips: int
    peaks: dict
    params: dict
    config: dict
    extra: dict = field(default_factory=dict)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``: a
    fixed path inside the checkout, so that every run of a checkout after
    its first finds its programs there and two checkouts share nothing.
    Every program is kept, however fast it compiled."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded from the cache, and cache hits,
    while it is open."""

    def __init__(self):
        from jax._src import monitoring
        self._mon = monitoring
        self.compiles = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or None (with the reason on standard
    error) where JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); refusing "
              "to run on it", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(run, seconds: float, traced: bool = False) -> tuple[list, float]:
    """The closed loop: operations until ``seconds`` have passed, each
    waited on until what its caller receives is ready. Returns each
    operation's latency in nanoseconds, from the call until its answer is
    ready, and the window's length in seconds. ``traced`` puts host spans
    around the window, each call and each wait, so that the trace says
    what the host did in each idle gap of the device."""
    if traced:
        from jax.profiler import TraceAnnotation as span
    else:
        span = contextlib.nullcontext
    lat = []
    clock = time.perf_counter_ns
    with span(WINDOW_SPAN):
        t0 = clock()
        end = t0 + int(seconds * 1e9)
        while True:
            t = clock()
            with span("bench.call"):
                out = run.call()
            with span("bench.wait"):
                out.block_until_ready()
            now = clock()
            lat.append(now - t)
            run.done(out)
            if now >= end:
                break
        window_s = (clock() - t0) / 1e9
    return lat, window_s


def run_cell(cell: harness.Cell, devices, *, seed: int, seconds: float,
             trace: bool, control: bool = False, t_start: float = T_START,
             peaks: dict | None = None) -> str:
    """Set up, measure and check one cell on ``devices``; returns the
    result line. The caller has checked that they are the right chips;
    ``peaks`` defaults to the table's entry for their kind. ``control``
    puts the reference, computed in the next lower precision, in the
    program's place: its answers must come out as not correct
    (``calibrate.py`` reads it to set the limits)."""
    import jax

    driver = harness.load_driver(cell.driver)
    if peaks is None:
        peaks = harness.load_peaks(devices[0].device_kind)
    readers = (harness.metrics_for(cell, harness.load_metrics())
               if trace else [])
    counter = CompileCounter()
    run = driver.setup(Setup(cell=cell, devices=list(devices), seed=seed,
                             control=control))
    setup_s = time.perf_counter() - t_start
    setup_compiles, setup_hits = counter.compiles, counter.hits
    print(f"bench: {cell.name} seed {seed}: set-up {setup_s:.3f} s, "
          f"{setup_compiles} programs built, {setup_hits} of them from the "
          "persistent cache", file=sys.stderr)

    run.start_window(trace)
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=opts)
            lat, window_s = measure(run, seconds, traced=True)
            jax.profiler.stop_trace()
        else:
            lat, window_s = measure(run, seconds)
        n = len(lat)
        in_window = counter.compiles - setup_compiles
        counter.close()
        mem = memory_peak(devices)
        e2e = run.end_to_end(window_s, lat)
        extra = run.layer_inputs()
        run.release()
        gc.collect()
        checks, failed = run.check()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": mem}
        breakdown = None
        if trace:
            import devtrace
            tr = devtrace.load(tracedir)
            used = tr.devices[:len(devices)]
            device["busy_s"] = sum(d.busy_ns() for d in used) / len(
                used) / 1e9 if used else 0.0
            device["window_s"] = window_s
            inputs = LayerInputs(trace=tr, ops=n, window_s=window_s,
                                 chips=len(devices), peaks=peaks,
                                 params=cell.params, config=cell.config,
                                 extra=extra)
            metrics = {}
            for m in readers:
                v = m.read(inputs)
                if v is not None:
                    metrics[m.name] = (v, m.unit)
            breakdown = {
                "device_ops": devtrace.top_ops(tr, len(devices)),
                "idle_gaps": devtrace.idle_gaps(tr, WINDOW_SPAN)}
        else:
            units = driver.END_TO_END
            metrics = {k: (e2e[k], units[k]) for k in cell.end_to_end}
            metrics[harness.SETUP] = (setup_s, "s")
    finally:
        if tracedir:
            shutil.rmtree(tracedir, ignore_errors=True)
    print(f"bench: {n} operations in {window_s:.3f} s, {in_window} programs "
          "built inside the window", file=sys.stderr)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return harness.result_line(checks=checks, attempted=n, failed=failed,
                               metrics=metrics, device=device,
                               breakdown=breakdown,
                               compiles_in_window=in_window)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell = harness.load_cell(args.workload)
    devices = tpu_devices(cell.chips)
    if devices is None:
        return 2
    enable_compile_cache()
    line = run_cell(cell, devices, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace))
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
