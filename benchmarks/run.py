"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:

* bench_jacobi         → paper Fig. 12  (Jacobi solver speedup + halo group)
* bench_graph_overhead → paper Fig. 13/14 (plan lifecycle costs)
* bench_calibration    → DESIGN.md §4.4c (model error, cold vs fitted)
* bench_step_capture   → DESIGN.md §2.4 (captured vs uncaptured step)
* bench_collectives    → paper §6 future work (multipath collectives)
* bench_faults         → DESIGN.md §4.6 (degraded-mode ladder + recovery)

``--smoke`` shrinks every size sweep to its smallest point (CI's tier-1
benchmark smoke step); ``--json PATH`` additionally writes the rows as a
JSON artifact (the ``BENCH_*.json`` perf trajectory).
"""

import argparse
import json
from pathlib import Path

from benchmarks import common  # noqa: F401 — pins device count first


def _apply_smoke() -> None:
    # In-place so modules that did ``from benchmarks.common import
    # SIZES_*`` see the shrunken sweeps.
    common.DISPATCH_CHUNKS[:] = common.DISPATCH_CHUNKS[:1]


def collect() -> list:
    from benchmarks import (bench_calibration, bench_collectives,
                            bench_dispatch, bench_faults,
                            bench_graph_overhead, bench_jacobi,
                            bench_step_capture)

    rows = []
    for mod in (bench_jacobi, bench_graph_overhead, bench_dispatch,
                bench_calibration, bench_step_capture, bench_collectives,
                bench_faults):
        rows.extend(mod.run())
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes only (CI smoke step)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as a JSON artifact")
    ap.add_argument("--schedule", metavar="NAME", default=None,
                    choices=common.SCHEDULES,
                    help="restrict the bench_graph_overhead scheduler "
                         "sweep to one chunk-interleaving pass "
                         "(default: sweep all of "
                         f"{', '.join(common.SCHEDULES)})")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])
    if args.smoke:
        _apply_smoke()
    if args.schedule:
        common.SCHEDULES[:] = [args.schedule]

    rows = collect()
    print("name,us_per_call,derived")
    for row in rows:
        print(row.csv(), flush=True)
    if args.json:
        payload = [{"name": r.name, "us_per_call": round(r.us, 2),
                    "derived": r.derived,
                    **getattr(r, "extra", {})} for r in rows]
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
