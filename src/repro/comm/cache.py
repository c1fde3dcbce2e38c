"""TransferPlanCache — the CUDA-Graph-cache analogue (paper §4.2).

The paper caches instantiated ``cudaGraphExec_t`` objects in a fixed-size
LRU hash table keyed on (src, dst, size, path config). In JAX the analogue
of the CUDA-Graph lifecycle is the AOT pipeline (DESIGN.md §2):

=================  =========================================
paper (CUDA)       this repo (JAX/XLA)
=================  =========================================
creation           building the python callable / jaxpr trace
construction       ``jit(f).trace(...)`` → ``.lower()`` (StableHLO)
instantiation      ``lowered.compile()`` (expensive, one-time)
launch             dispatch of the compiled executable (cheap)
=================  =========================================

Every stage is timed so the lifecycle benchmark (paper Fig. 13/14) can report
first-iteration vs steady-state costs as a function of plan node count.

Steady-state dispatch additionally fronts this cache with a
:class:`FastPathCache` (DESIGN.md §2.3): entries memoize the *entire*
plan→lower→schedule→digest pipeline keyed on the request signature and an
explicit planner/topology epoch, so a repeat transfer is one dict lookup +
one staging write + one executable launch.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Callable, Hashable

import jax

from repro.comm.config import _env_int


@dataclasses.dataclass
class PlanLifecycle:
    """Nanosecond timings of each lifecycle stage for one cached plan.

    The per-stage attribution the paper's Fig. 13/14 overhead analysis
    needs (and ucTrace-style layered profiling motivates): build stages
    are one-time, ``launches``/``total_launch_ns`` accumulate steady
    state, ``staging_ns`` isolates the host-side *dispatch* of operand
    staging (staging execution overlaps the launch via dataflow and is
    accounted in the launch timings), and ``fastpath_hits`` counts
    dispatches that skipped the whole plan→lower→digest pipeline.
    Timings are measurements, not semantics — they carry no §4.5
    invariant obligations and must never feed cache keys (digest-derived
    keys only).
    """

    trace_ns: int = 0        # python trace → jaxpr ("construction" part 1)
    lower_ns: int = 0        # jaxpr → StableHLO ("construction" part 2)
    compile_ns: int = 0      # XLA compile ("instantiation")
    launches: int = 0
    total_launch_ns: int = 0
    num_nodes: int = 0       # copy-node count (chunks × hops)
    #: Dispatches of this executable served by the FastPathCache — the
    #: launches whose setup cost was one dict lookup.
    fastpath_hits: int = 0
    #: Cumulative nanoseconds spent dispatching operand staging (host-
    #: side enqueue) across every launch of this executable.
    staging_ns: int = 0
    #: Launch attempts of this executable that raised a link fault and
    #: were retried on a re-planned route (DESIGN §4.6). Windowed like
    #: ``launches``; a healthy window reports 0.
    retries: int = 0

    @property
    def build_ns(self) -> int:
        """One-time cost: trace + lower + compile (the paper's graph
        creation/construction/instantiation, amortized over launches)."""
        return self.trace_ns + self.lower_ns + self.compile_ns

    @property
    def mean_launch_ns(self) -> float:
        """Steady-state cost per launch (0.0 before the first launch)."""
        return self.total_launch_ns / self.launches if self.launches else 0.0

    def reset_window(self) -> None:
        """Zero the *per-window* accumulators (launches,
        ``total_launch_ns``, ``staging_ns``, ``fastpath_hits``,
        ``retries``) so
        long-running sessions can report rates instead of lifetime sums
        — the ``stats(reset=True)`` windowed-counter contract. The
        one-time build timings (trace/lower/compile) are preserved:
        they are identity facts of the executable, not a window."""
        self.launches = 0
        self.total_launch_ns = 0
        self.staging_ns = 0
        self.fastpath_hits = 0
        self.retries = 0


@dataclasses.dataclass
class CompiledPlan:
    """An instantiated transfer graph: XLA executable + lifecycle stats.

    The ``cudaGraphExec_t`` analogue. ``key`` must be digest-derived
    (:class:`~repro.comm.engine.GroupKey` /
    :class:`~repro.comm.session.CollectiveKey`) so the executable can
    never outlive the graph identity it was compiled for; callers must
    preserve the operand shapes/shardings the plan was compiled with —
    and, when the plan was compiled with donation
    (:func:`compile_plan` ``donate_argnums``), must not reuse operand
    arrays after a launch consumed them.
    """

    key: Hashable
    compiled: Any            # jax.stages.Compiled
    lifecycle: PlanLifecycle

    def __call__(self, *args):
        t0 = time.perf_counter_ns()
        out = self.compiled(*args)
        # Block so the timing covers execution, not just dispatch; dispatch
        # cost alone is measured by the lifecycle benchmark via donated runs.
        jax.block_until_ready(out)
        self.lifecycle.launches += 1
        self.lifecycle.total_launch_ns += time.perf_counter_ns() - t0
        return out

    def dispatch(self, *args):
        """Launch without blocking (pure launch-overhead measurement)."""
        t0 = time.perf_counter_ns()
        out = self.compiled(*args)
        self.lifecycle.launches += 1
        self.lifecycle.total_launch_ns += time.perf_counter_ns() - t0
        return out

    def wait(self, out):
        """Block until ``out`` of a :meth:`dispatch` is ready; the wait
        counts toward the launch's lifecycle time, as in a blocking
        call."""
        t0 = time.perf_counter_ns()
        jax.block_until_ready(out)
        self.lifecycle.total_launch_ns += time.perf_counter_ns() - t0
        return out


def compile_plan(key: Hashable, fn: Callable, abstract_args: tuple,
                 num_nodes: int = 0, **jit_kwargs) -> CompiledPlan:
    """Run the full trace→lower→compile pipeline with per-stage timing.

    ``jit_kwargs`` pass straight through to ``jax.jit`` — in particular
    ``donate_argnums``, which the engine uses so XLA reuses staging
    buffers launch-to-launch (a donated executable's contract obligates
    the caller never to reuse a consumed operand; the engine's pooled
    staging preserves that by rebuilding operands every launch).
    """
    life = PlanLifecycle(num_nodes=num_nodes)
    jitted = jax.jit(fn, **jit_kwargs)
    t0 = time.perf_counter_ns()
    traced = jitted.trace(*abstract_args)
    t1 = time.perf_counter_ns()
    lowered = traced.lower()
    t2 = time.perf_counter_ns()
    compiled = lowered.compile()
    t3 = time.perf_counter_ns()
    life.trace_ns, life.lower_ns, life.compile_ns = t1 - t0, t2 - t1, t3 - t2
    return CompiledPlan(key, compiled, life)


class TransferPlanCache:
    """Fixed-capacity LRU cache of :class:`CompiledPlan` objects.

    Capacity defaults to ``REPRO_PLAN_CACHE_SIZE`` (paper: tunable via
    environment variables). Eviction counts are exposed for the overhead
    analysis: an eviction forces a re-instantiation on the next use, the
    dominant first-iteration cost. Keys must be digest-derived
    (§2.2: schedules digest apart, so two dispatch orders of one plan can
    never cross-serve executables); the cache itself never inspects
    them.
    """

    def __init__(self, capacity: int | None = None):
        self.capacity = capacity if capacity is not None else _env_int(
            "REPRO_PLAN_CACHE_SIZE", 64)
        if self.capacity <= 0:
            raise ValueError("cache capacity must be positive")
        self._store: OrderedDict[Hashable, CompiledPlan] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._store

    def get(self, key: Hashable) -> CompiledPlan | None:
        """Look up a compiled plan, counting the hit/miss and refreshing
        LRU recency."""
        plan = self._store.get(key)
        if plan is None:
            self.misses += 1
            return None
        self._store.move_to_end(key)
        self.hits += 1
        return plan

    def put(self, key: Hashable, plan: CompiledPlan) -> None:
        """Insert (or refresh) an entry, evicting the LRU tail past
        capacity."""
        if key in self._store:
            self._store.move_to_end(key)
        self._store[key] = plan
        if len(self._store) > self.capacity:
            self._store.popitem(last=False)
            self.evictions += 1

    def get_or_build(self, key: Hashable,
                     builder: Callable[[], CompiledPlan]) -> CompiledPlan:
        """LaunchGraph's lookup-or-create (Algorithm 1 lines 25–28)."""
        plan = self.get(key)
        if plan is None:
            plan = builder()
            self.put(key, plan)
        return plan

    def keys(self) -> list[Hashable]:
        """Current keys, least-recently-used first (eviction order)."""
        return list(self._store)

    def stats(self, reset: bool = False) -> dict[str, int]:
        """Hit/miss/eviction counters plus current size and capacity.

        ``reset=True`` returns the snapshot then zeroes the counters and
        every cached plan's windowed lifecycle accumulators
        (:meth:`PlanLifecycle.reset_window`) — the windowed-stats
        contract for long-running sessions. Entries themselves are
        preserved: resetting a window must never force a rebuild."""
        out = {"hits": self.hits, "misses": self.misses,
               "evictions": self.evictions, "size": len(self._store),
               "capacity": self.capacity}
        if reset:
            self.hits = self.misses = self.evictions = 0
            for plan in self._store.values():
                plan.lifecycle.reset_window()
        return out

    def clear(self) -> None:
        """Drop every entry (counters are kept; they are cumulative —
        use ``stats(reset=True)`` for windowed counters)."""
        self._store.clear()


@dataclasses.dataclass
class FastPathEntry:
    """One memoized resolution of the plan→lower→schedule→digest pipeline.

    Everything steady-state dispatch needs without re-running any setup
    stage: the resolved plans, the SCHEDULED transfer graph (kept so
    ``REPRO_MP_VALIDATE=always`` can re-run ``graph.validate()`` on
    hits), its post-pass digest, the digest-derived plan-cache key, the
    compiled executable, and the concrete schedule name that was chosen.
    The §4.5 invariants were checked when the entry was built; the epoch
    stamp in :class:`FastPathCache` is what keeps that check valid —
    served entries are byte-identical to what the slow path would
    rebuild, or they are invalidated.
    """

    plans: tuple            # tuple[TransferPlan, ...]
    graph: Any              # the scheduled TransferGraph
    digest: str             # post-pass graph digest (cache-key ingredient)
    key: Hashable           # the GroupKey the executable is cached under
    compiled: CompiledPlan
    schedule: str           # concrete scheduler name resolved at build


class FastPathCache:
    """Front cache for steady-state dispatch (DESIGN.md §2.3).

    Maps a *request signature* — ``(mode, (src, dst, nelems, dtype)…,
    window, schedule name, planner knobs, device count)`` — to a
    :class:`FastPathEntry`, each stamped with the
    :attr:`~repro.comm.planner.PathPlanner.epoch` in force when it was
    built. Lookups compare the stamp against the live epoch: a mismatch
    (any planner/topology mutation since) drops the entry and counts an
    ``invalidation``, so a stale plan can never be served — the §4.5
    validity of a served entry is exactly the validity of its epoch.
    LRU-bounded like the plan cache; entries hold strong references to
    their executables, so eviction order follows use order.
    """

    def __init__(self, capacity: int = 256):
        if capacity <= 0:
            raise ValueError("fast-path cache capacity must be positive")
        self.capacity = capacity
        self._store: OrderedDict[Hashable,
                                 tuple[tuple, FastPathEntry]] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.invalidations = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, signature: Hashable) -> bool:
        return signature in self._store

    def get(self, signature: Hashable, epoch: tuple) -> FastPathEntry | None:
        """Return the entry for ``signature`` iff its epoch stamp matches
        the live ``epoch``; a stale stamp is dropped and counted as an
        invalidation (plus a miss — the caller re-plans)."""
        rec = self._store.get(signature)
        if rec is None:
            self.misses += 1
            return None
        stamped, entry = rec
        if stamped != epoch:
            del self._store[signature]
            self.invalidations += 1
            self.misses += 1
            return None
        self._store.move_to_end(signature)
        self.hits += 1
        return entry

    def put(self, signature: Hashable, epoch: tuple,
            entry: FastPathEntry) -> None:
        """Memoize a freshly-built resolution under its epoch stamp,
        evicting the LRU tail past capacity."""
        if signature in self._store:
            self._store.move_to_end(signature)
        self._store[signature] = (epoch, entry)
        if len(self._store) > self.capacity:
            self._store.popitem(last=False)
            self.evictions += 1

    def stats(self, reset: bool = False) -> dict[str, int]:
        """Hit/miss/invalidation/eviction counters plus size and
        capacity — surfaced as ``session.stats()["fastpath"]``.
        ``reset=True`` snapshots then zeroes the counters (windowed
        semantics; entries and their epoch stamps are preserved, so the
        §4.5 staleness check is unaffected)."""
        out = {"hits": self.hits, "misses": self.misses,
               "invalidations": self.invalidations,
               "evictions": self.evictions, "size": len(self._store),
               "capacity": self.capacity}
        if reset:
            self.hits = self.misses = 0
            self.invalidations = self.evictions = 0
        return out

    def clear(self) -> None:
        """Drop every entry (counters are kept; they are cumulative —
        use ``stats(reset=True)`` for windowed counters)."""
        self._store.clear()
