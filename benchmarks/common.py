"""Shared benchmark utilities. Import this FIRST in every bench module —
it pins the CPU device count before jax initializes."""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402

MiB = 1 << 20

#: Chunk-interleaving schedulers swept by bench_graph_overhead (the
#: ``--schedule`` axis; ``run.py --schedule NAME`` narrows it in place).
SCHEDULES = ["round_robin", "depth_first", "critical_path", "overlap",
             "auto"]
#: Per-path chunk counts swept by bench_dispatch (the node-count axis of
#: the steady-state dispatch rows; --smoke shrinks it in place).
DISPATCH_CHUNKS = [1, 4, 16]


def timeit_us(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter_ns()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter_ns() - t0) / iters / 1e3


class Row:
    def __init__(self, name: str, us_per_call: float, derived: str,
                 extra: dict | None = None):
        self.name = name
        self.us = us_per_call
        self.derived = derived
        #: Structured extras (e.g. graph node/edge counts) — emitted into
        #: the ``--json`` artifact rows, not the CSV stream.
        self.extra = extra or {}

    def csv(self) -> str:
        return f"{self.name},{self.us:.2f},{self.derived}"
