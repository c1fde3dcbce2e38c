"""MultiPathTransfer — executable multi-path P2P transfers on a JAX mesh.

This is the UCT-layer analogue (DESIGN.md §2): it lowers one or more
:class:`~repro.comm.plan.TransferPlan` objects to ONE
:class:`~repro.comm.graph.TransferGraph` (the CUDA Graph analogue), runs
the configured chunk-interleaving scheduler pass over it
(:mod:`repro.comm.passes`, DESIGN.md §2.2 — the emitter owns no ordering
of its own), walks the SCHEDULED graph's copy nodes in topological order
emitting one ``ppermute`` per node, compiles the resulting SPMD program
once, and caches the executable in a
:class:`~repro.comm.cache.TransferPlanCache` keyed on the scheduled
graph's canonical :meth:`~repro.comm.graph.TransferGraph.digest` — the
paper's graph cache keyed on (src, dst, size, path configuration), here
additionally distinguishing dispatch orders.

A **transfer group** (:meth:`MultiPathTransfer.transfer_group`) fuses a set
of concurrent messages — planned jointly by
:meth:`~repro.comm.planner.PathPlanner.plan_group` — into ONE graph, one
traced / lowered / compiled program, one cache entry, and one launch: the
paper's graph-per-message becomes one graph per traffic pattern (message
fusion à la Choi et al.). Single sends are the 1-message special case of
the same machinery.

Steady state takes the **dispatch fast path** (DESIGN.md §2.3): the whole
plan→lower→schedule→digest resolution is memoized per request signature
in an epoch-stamped :class:`~repro.comm.cache.FastPathCache`, operand
staging runs through pooled per-key staging programs, and repeat traffic
is one dict lookup + one staging write + one launch — the paper's "setup
once, launch many". Any planner/topology mutation bumps the epoch and
forces a re-plan; ``REPRO_MP_FASTPATH=0`` disables the front cache and
``REPRO_MP_VALIDATE=always`` re-validates even on hits.

Correctness model (§4.5 of the paper → functional dataflow here): the
graph's hop edges ARE the program's dataflow (hop *i+1* consumes hop *i*'s
value), chunks write disjoint precomputed destination offsets, paths never
share a directional link (validated on the same graph the program is
emitted from), and "final synchronization" is the functional join of all
terminal copy nodes. Because the emitter walks the same lowering the
model and the validators consume, the three can no longer diverge.

The engine runs on a flat 1-D device axis (default ``"dev"``); topology
device ids are mesh positions. Model-parallel meshes are a separate concern
(``repro/launch/mesh.py``). Most callers should go through
:class:`~repro.comm.session.CommSession` rather than constructing the
engine directly.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from collections import OrderedDict
from functools import lru_cache, partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.cache import (CompiledPlan, FastPathCache, FastPathEntry,
                              TransferPlanCache, compile_plan)
from repro.comm.capture import CapturedStep, StepCapture, emit_step, lower_step
from repro.comm.config import VALIDATE_MODES, _env_bool
from repro.comm.graph import ComputeNode, TransferGraph, lower
from repro.comm.health import (LADDER, CommFaultError, FaultInjector,
                               HealthMonitor, HealthStats, LinkFaultError)
from repro.comm.passes import AutoSchedule, GraphPass, apply_schedule
from repro.comm.plan import TransferGroup, TransferPlan, TransferRequest
from repro.comm.planner import PathPlanner
from repro.comm.telemetry import (DispatchSample, StageTimings,
                                  TimelineRecorder, span, tracing)
from repro.core.pipelining import validate_plan
from repro.core.topology import HOST, Topology

AXIS = "dev"


@dataclasses.dataclass(frozen=True)
class GroupKey:
    """Graph-cache key for a fused transfer group.

    ``digest`` is the canonical content hash of the lowered
    :class:`~repro.comm.graph.TransferGraph` (nodes + edges + window), so
    the key can never diverge from the program that was actually emitted
    — EVERY message's routes, chunking, and byte ranges contribute (the
    old hand-assembled key once dropped the reverse plan's signature; a
    digest of the whole graph cannot). ``entries`` adds the per-message
    element type/count, which the graph (byte-level) does not carry but
    the traced program shape depends on. The dispatch path canonicalizes
    message order before planning (see :meth:`MultiPathTransfer
    .transfer_group`), so structurally identical groups whose operands
    were merely permuted collide on one entry.

    Captured whole-iteration steps reuse this key: ``digest`` is the
    scheduled heterogeneous graph's digest (compute nodes included) and
    ``entries`` carries the capture signature plus one
    ``(kernel, flops, cost_ns)`` triple per compute node, so the key
    covers compute identity as well as routes.
    """

    digest: str
    entries: tuple   # ((src, dst, nelems, dtype_str), ...) per message
    window: int = 1
    #: Mesh size the program was compiled for: operand shapes/shardings are
    #: (window, num_devices, nelems), so a cache shared by sessions on
    #: different-sized meshes must not serve one mesh's executable to the
    #: other (the graph digest covers routes, not the device axis).
    num_devices: int = 0
    #: True when the program was compiled with operand donation
    #: (``donate_argnums``): a donated executable consumes its operands,
    #: so it must never be served to an AOT caller that reuses arrays
    #: across launches (``compiled_for*`` always compiles undonated).
    donated: bool = False


@dataclasses.dataclass
class _StepEntry:
    """Fast-path entry for a captured whole-iteration step.

    Same shape as :class:`~repro.comm.cache.FastPathEntry` (the front
    cache stores entries opaquely) plus the recording itself (``program``
    — needed to rebuild the SPMD program if the plan cache evicts the
    executable under us) and the step's output buffer ids.
    """

    plans: tuple
    graph: TransferGraph
    digest: str
    key: GroupKey
    compiled: CompiledPlan
    schedule: str
    program: StepCapture
    outputs: tuple


@partial(jax.jit, static_argnums=1)
def comm_extract(y: jax.Array, dst: int) -> jax.Array:
    """The received message out of a transfer program's output: window 0
    of device ``dst``'s row, replicated. One named program, so that the
    profiler shows the extraction as ``jit_comm_extract``. A gather, as
    eager indexing of the sharded output takes it: on a mesh that is one
    all-reduce, where a slice (``y[0, dst]`` under jit) adds a
    collective-permute of the whole message."""
    return jnp.take(y[0], dst, axis=0)


def plan_signature(plan: TransferPlan) -> tuple:
    """Human-readable per-path summary ((links, chunks, bytes), ...).

    Informational/diagnostic — cache keys use the graph digest instead.
    """
    return tuple((p.route.directional_links(), p.num_chunks, p.nbytes)
                 for p in plan.paths)


def group_signature(group: TransferGroup) -> tuple:
    """Per-plan (src, dst, nbytes, plan signature) for the whole group."""
    return tuple((p.src, p.dst, p.nbytes, plan_signature(p))
                 for p in group.plans)


@lru_cache(maxsize=256)
def _scheduled_graph(graph: TransferGraph, schedule: str,
                     topology: Topology,
                     topology_epoch: tuple) -> tuple[TransferGraph, str]:
    """Memoized schedule application for name-addressed schedulers.

    ``lower()`` memoizes the lowering, so steady-state launches replay
    the same graph object; without this cache every cache-hit dispatch
    would re-run the pass AND the full §2.2 contract check. Custom
    :class:`GraphPass` objects bypass the memo (their identity is not a
    stable key). ``topology_epoch`` is part of the key on purpose:
    ``Topology`` hashes by identity, so without it a link mutation
    (``add_link`` on an existing pair changes bandwidths in place) could
    serve a model-weighted scheduler (``critical_path``/``auto``) a
    dispatch order computed from stale link weights.
    """
    return apply_schedule(graph, schedule, topology)


def _check_executable(plan: TransferPlan) -> None:
    for pa in plan.paths:
        for link in pa.route.hops:
            if HOST in (link.src, link.dst):
                # Checked per HOP, not per route.via: a 3-hop detour can
                # stage through the host mid-route while its recorded via
                # is a device — it would otherwise reach ppermute as
                # device id -1.
                raise ValueError(
                    "host-staged path is not executable on the accelerator "
                    "mesh (DESIGN.md §2); plan with include_host=False")


def emit_graph(graph: TransferGraph, xs: Sequence[jax.Array],
               axis_name: str, itemsizes: Sequence[int]) -> list[jax.Array]:
    """Walk graph nodes in topological order, one ``ppermute`` per node.

    ``xs[i]`` is message *i*'s local shard of shape ``(window, 1,
    nelems_i)``; on the source device it holds the message, elsewhere
    contents are ignored. Returns same-shaped arrays holding each message
    on its destination device and zeros elsewhere.

    Dataflow follows the graph's hop edges exactly: a node with no hop
    predecessor slices its chunk from the input, every other node consumes
    its predecessor's ``ppermute`` output, and terminal nodes join into
    the zero-initialized output (the §4.5 "final synchronization").
    """
    outs = [jnp.zeros_like(x) for x in xs]
    preds = graph.hop_predecessor
    terminals = graph.terminal_nodes
    values: dict[int, jax.Array] = {}
    for idx in graph.topological_order():
        node = graph.nodes[idx]
        isz = itemsizes[node.msg_idx]
        if node.offset % isz or node.nbytes % isz:
            raise ValueError("chunk bounds not element-aligned; pass "
                             "granularity=itemsize to planner.plan()")
        off_e, size_e = node.offset // isz, node.nbytes // isz
        pred = preds.get(idx)
        if pred is None:
            chunk = jax.lax.slice(
                xs[node.msg_idx],
                (node.window, 0, off_e),
                (node.window + 1, 1, off_e + size_e))
        else:
            chunk = values.pop(pred)
        chunk = jax.lax.ppermute(chunk, axis_name, [node.link])
        if idx in terminals:
            outs[node.msg_idx] = jax.lax.dynamic_update_slice(
                outs[node.msg_idx], chunk, (node.window, 0, off_e))
        else:
            values[idx] = chunk
    return outs


def multipath_send_local(x: jax.Array, plan: TransferPlan, *,
                         axis_name: str = AXIS,
                         itemsize: int | None = None,
                         schedule: str | GraphPass = "round_robin",
                         topology: Topology | None = None) -> jax.Array:
    """Execute a plan *inside* a ``shard_map`` program.

    ``x`` is the local shard, shape ``(1, nelems)``; on the source device it
    holds the message, elsewhere contents are ignored. Returns an array of
    the same shape that holds the message on the destination device and
    zeros elsewhere. One ``ppermute`` per graph copy node, dispatched in
    the order the ``schedule`` pass (§2.2) produces. Pass ``topology``
    alongside a model-weighted scheduler (``"critical_path"``,
    ``"auto"``) to get the same dispatch order the engine derives for
    that name; without it, ``"critical_path"`` degrades to uniform
    raw-byte weights and ``"auto"`` raises.
    """
    _check_executable(plan)
    itemsize = itemsize or x.dtype.itemsize
    graph, _ = apply_schedule(lower(plan), schedule, topology)
    (out,) = emit_graph(graph, (x[None],), axis_name, (itemsize,))
    return out[0]


class MultiPathTransfer:
    """Build, cache, and launch compiled multi-path transfer programs."""

    def __init__(self, mesh: jax.sharding.Mesh | None = None, *,
                 topology: Topology | None = None,
                 planner: PathPlanner | None = None,
                 cache: TransferPlanCache | None = None,
                 schedule: str | GraphPass = "round_robin",
                 fastpath: bool | None = None,
                 validate: str | None = None,
                 fastpath_cache: FastPathCache | None = None,
                 telemetry: TimelineRecorder | None = None,
                 monitor: HealthMonitor | None = None,
                 faults: FaultInjector | None = None,
                 retry_limit: int = 2,
                 backoff_base_s: float = 0.001):
        if mesh is None:
            devs = jax.devices()
            mesh = jax.sharding.Mesh(devs, (AXIS,))
        self.mesh = mesh
        self.axis_name = mesh.axis_names[0]
        self.num_devices = mesh.devices.size
        if topology is None:
            topology = Topology.full_mesh(self.num_devices, with_host=True)
        self.topology = topology
        # `if ... is None` (not `or`): an *empty* TransferPlanCache is falsy
        # via __len__, and `or` would silently replace a caller's cache.
        self.planner = planner if planner is not None else PathPlanner(
            topology)
        self.cache = cache if cache is not None else TransferPlanCache()
        #: Default chunk-interleaving scheduler (DESIGN.md §2.2) applied
        #: to every lowering between ``lower()`` and the emitter; every
        #: public entry point takes a per-call ``schedule=`` override.
        self.schedule = schedule
        #: Steady-state dispatch fast path (DESIGN.md §2.3): memoize the
        #: whole plan→lower→schedule→digest resolution per request
        #: signature so repeat traffic is one dict lookup + staging +
        #: launch. ``REPRO_MP_FASTPATH=0`` (or ``fastpath=False``) turns
        #: it off; every dispatch then re-runs the full pipeline.
        self.fastpath = (_env_bool("REPRO_MP_FASTPATH", True)
                         if fastpath is None else fastpath)
        #: ``"miss"`` (default) validates plans/graphs only when they are
        #: (re)built; ``"always"`` re-validates on every dispatch, fast-
        #: path hits included (§4.5 safety escape hatch).
        self.validate = (os.environ.get("REPRO_MP_VALIDATE", "miss")
                         if validate is None else validate)
        if self.validate not in VALIDATE_MODES:
            raise ValueError(f"unknown validate mode {self.validate!r}; "
                             f"expected one of {VALIDATE_MODES}")
        self._fastpath = (fastpath_cache if fastpath_cache is not None
                          else FastPathCache())
        #: Optional dispatch-timeline recorder (DESIGN §4.4c). ``None``
        #: or a disabled recorder keeps the dispatch path at one boolean
        #: check — the zero-overhead-off telemetry contract.
        self.telemetry = telemetry
        # Per-dispatch telemetry carried from _resolve to _launch (the
        # two halves of one dispatch; the engine is not thread-safe and
        # never was — same invariant as the staging pool).
        self._pending_stages: StageTimings | None = None
        self._pending_hit = False
        #: Pooled staging programs keyed on (window, nelems, dtype, src):
        #: each one holds a zero operand template (device_put once) and a
        #: compiled write of the message into the src row — per-launch
        #: staging is ONE fused kernel instead of zeros + scatter +
        #: resharding of a fresh (window, ndev, nelems) array. LRU-bounded
        #: to the fast-path capacity: every entry pins a device-resident
        #: template, so the pool must not grow without bound under
        #: many-distinct-size traffic.
        self._staging: OrderedDict[tuple, object] = OrderedDict()
        #: Cumulative nanoseconds spent *dispatching* the staging kernels
        #: across every launch (host-side enqueue; per-executable totals
        #: in `PlanLifecycle.staging_ns`). Staging execution overlaps the
        #: launch — the compiled program consumes the staged operands
        #: through dataflow — so it lands in the launch timings, not here.
        self.staging_ns = 0
        # Operand donation lets XLA reuse staging buffers launch-to-launch
        # (paper: graph replay over the same buffers). The CPU backend
        # ignores donation (with a warning), so only enable it where it
        # takes effect; donated programs are keyed apart (GroupKey.donated)
        # from the undonated AOT handles `compiled_for*` returns.
        self._donate = jax.default_backend() not in ("cpu",)
        #: Concrete schedule name → dispatch/compile calls resolved to it
        #: (``auto`` counts as the candidate it picked; cache hits and
        #: memoized pass applications included). Surfaced via
        #: ``session.stats()``.
        self.schedule_counts: dict[str, int] = {}
        self._sharding = NamedSharding(mesh, P(None, self.axis_name))
        #: The staging programs take the message replicated over the mesh.
        self._replicated = NamedSharding(mesh, P())
        #: Number of compiled-program launches issued (one per transfer or
        #: per fused group — the paper's "one cudaGraphLaunch" count).
        self.dispatches = 0
        #: Copy nodes / dependency edges across every graph this engine
        #: compiled (cache misses only) — `session.stats()` surfaces them.
        #: `copy_nodes_compiled`/`compute_nodes_compiled` break the node
        #: total down by kind (heterogeneous captured-step graphs carry
        #: both); `nodes_compiled` stays the total of the two.
        self.nodes_compiled = 0
        self.edges_compiled = 0
        self.copy_nodes_compiled = 0
        self.compute_nodes_compiled = 0
        #: Degraded-mode accounting (DESIGN §4.6): retries/replans/ladder
        #: level, surfaced as the ``health`` stats section. Always
        #: present so counters exist whether or not a monitor is wired.
        self.health = HealthStats()
        #: Optional telemetry-driven link health monitor; when attached,
        #: dispatch faults quarantine through it (events logged) and the
        #: degraded loop probes quarantined links on its cadence.
        self.monitor = monitor
        #: Optional deterministic chaos injector (``REPRO_MP_FAULTS``);
        #: fires before each dispatch resolves so epoch bumps always
        #: precede planning — no stale executable survives an injection.
        self.faults = faults
        #: Retries per degradation-ladder rung before escalating, and
        #: the bounded exponential backoff base between them (§4.6).
        self.retry_limit = retry_limit
        self.backoff_base_s = backoff_base_s

    # -- planning -----------------------------------------------------------
    def plan_for(self, src: int, dst: int, nelems: int, dtype=jnp.float32,
                 **plan_kwargs) -> TransferPlan:
        itemsize = jnp.dtype(dtype).itemsize
        plan = self.planner.plan(src, dst, nelems * itemsize,
                                 granularity=itemsize,
                                 include_host=plan_kwargs.pop(
                                     "include_host", False),
                                 **plan_kwargs)
        validate_plan(plan)
        return plan

    def plan_group_for(self, specs: Sequence[tuple], *,
                       max_paths: int | None = None,
                       num_chunks: int | None = None,
                       exclusive: bool = False) -> TransferGroup:
        """Jointly plan executable messages; ``specs`` holds one
        ``(src, dst, nelems, dtype)`` tuple per message. Host paths are
        never admitted (they are not executable on the accelerator mesh).
        """
        requests = []
        for (src, dst, nelems, dtype) in specs:
            itemsize = jnp.dtype(dtype).itemsize
            requests.append(TransferRequest(src, dst, nelems * itemsize,
                                            granularity=itemsize))
        group = self.planner.plan_group(requests, max_paths=max_paths,
                                        include_host=False,
                                        num_chunks=num_chunks,
                                        exclusive=exclusive)
        for plan in group.plans:
            validate_plan(plan)
            _check_executable(plan)
        return group

    # -- program construction -----------------------------------------------
    def _group_graph(self, plans: Sequence[TransferPlan], window: int,
                     schedule: str | GraphPass | None = None,
                     stages: StageTimings | None = None
                     ) -> tuple[TransferGraph, str]:
        """Lower the fused group and run the scheduler pass (§2.2).

        Returns the SCHEDULED graph — the one the program is emitted
        from AND the one ``_group_key`` digests, so the cache key always
        incorporates the post-pass dispatch order (two schedules of one
        plan get distinct entries and can never cross-serve
        executables) — plus the concrete schedule name that was chosen.
        The emitter owns no ordering of its own. ``stages`` (telemetry
        only) receives the lower/schedule wall-time attribution; both
        stages are ``comm.lower`` / ``comm.schedule`` spans.
        """
        for p in plans:
            _check_executable(p)
        with span("lower", stages):
            graph = lower(TransferGroup(tuple(plans), self.topology.name),
                          window)
        sched = self.schedule if schedule is None else schedule
        with span("schedule", stages):
            if isinstance(sched, str):
                return _scheduled_graph(graph, sched, self.topology,
                                        self.topology.epoch)
            return apply_schedule(graph, sched, self.topology)

    def _count_schedule(self, chosen: str) -> None:
        self.schedule_counts[chosen] = self.schedule_counts.get(chosen,
                                                                0) + 1

    def _build_group_fn(self, graph: TransferGraph,
                        itemsizes: Sequence[int]):
        """Fused SPMD program: the graph's copy nodes, one trace."""
        ax = self.axis_name

        def local_body(*xs):  # x_i local: (window, 1, nelems_i)
            return tuple(emit_graph(graph, xs, ax, itemsizes))

        specs = tuple(P(None, ax) for _ in itemsizes)
        return jax.shard_map(local_body, mesh=self.mesh,
                             in_specs=specs, out_specs=specs,
                             check_vma=False)

    def _compile_group(self, key: GroupKey, graph: TransferGraph,
                       shapes: Sequence[tuple[int, object]],
                       stages: StageTimings | None = None) -> CompiledPlan:
        abstracts = tuple(
            jax.ShapeDtypeStruct((key.window, self.num_devices, nelems),
                                 dtype, sharding=self._sharding)
            for nelems, dtype in shapes)
        itemsizes = tuple(jnp.dtype(dtype).itemsize for _, dtype in shapes)
        fn = self._build_group_fn(graph, itemsizes)
        self.nodes_compiled += graph.num_nodes
        self.edges_compiled += graph.num_edges
        self.copy_nodes_compiled += graph.num_copy_nodes
        self.compute_nodes_compiled += graph.num_compute_nodes
        jit_kwargs = {}
        if key.donated:
            # XLA reuses the staged operand buffers for the outputs
            # launch-to-launch (the paper's graph replay over one buffer
            # set); safe because the dispatch path rebuilds operands
            # every launch and never touches them again.
            jit_kwargs["donate_argnums"] = tuple(range(len(shapes)))
        with span("compile", stages):
            return compile_plan(key, fn, abstracts,
                                num_nodes=graph.num_nodes, **jit_kwargs)

    def _group_key(self, graph: TransferGraph, plans: Sequence[TransferPlan],
                   shapes: Sequence[tuple[int, object]], window: int,
                   donated: bool = False) -> GroupKey:
        entries = tuple(
            (p.src, p.dst, nelems, str(jnp.dtype(dtype)))
            for p, (nelems, dtype) in zip(plans, shapes))
        return GroupKey(graph.digest(), entries, window, self.num_devices,
                        donated)

    # -- steady-state dispatch (DESIGN.md §2.3) -----------------------------
    def _request_signature(self, mode: str, specs: Sequence[tuple],
                           window: int, schedule: str,
                           max_paths: int | None, num_chunks: int | None,
                           exclusive: bool) -> tuple:
        """Request identity for the fast path: everything that determines
        the resolved plans + program BESIDES planner/topology state
        (which the epoch stamp covers). ``mode`` separates single-message
        planning (``plan``) from joint group planning (``plan_group``) —
        the two can legitimately resolve the same spec differently.
        """
        return (mode,
                tuple((src, dst, nelems, str(jnp.dtype(dtype)))
                      for src, dst, nelems, dtype in specs),
                window, schedule, max_paths, num_chunks, exclusive,
                self.num_devices)

    def _stage_fn(self, window: int, nelems: int, dtype, src: int):
        """Pooled staging program for one (window, nelems, dtype, src) key.

        Holds a zero operand template — device_put across the mesh ONCE —
        and a compiled write of the message into the src row, so per-
        launch staging is one fused kernel producing the sharded
        ``(window, ndev, nelems)`` operand instead of a fresh zero-fill +
        scatter + resharding of the whole array (the old per-launch
        O(window·ndev·nelems) host-side cost). The program is named
        ``comm_stage`` (``jit_comm_stage`` in a profile).
        """
        key = (window, nelems, str(jnp.dtype(dtype)), src)
        fn = self._staging.get(key)
        if fn is None:
            zeros = jax.device_put(
                jnp.zeros((window, self.num_devices, nelems), dtype),
                self._sharding)

            def comm_stage(m, _zeros=zeros):
                return _zeros.at[:, src].set(m)

            fn = jax.jit(comm_stage, out_shardings=self._sharding)
            # Warm the staging executable once at pool-insertion time so
            # steady-state `staging_ns` measures operand builds, not the
            # one-time jit compile (that is first-dispatch setup cost).
            jax.block_until_ready(fn(jax.device_put(
                jnp.zeros((nelems,), dtype), self._replicated)))
            self._staging[key] = fn
            if len(self._staging) > self._fastpath.capacity:
                self._staging.popitem(last=False)
        else:
            self._staging.move_to_end(key)
        return fn

    def _launch(self, entry: FastPathEntry, messages: Sequence[jax.Array],
                *, block: bool) -> list[jax.Array]:
        """Place and stage operands (pooled), launch the compiled program
        ONCE and extract each received message (``comm_extract``): the
        spans ``comm.place``, ``comm.stage``, ``comm.launch``, with
        ``block`` ``comm.execute``, and ``comm.extract``, kept while a
        profiler trace records or telemetry is on.

        When telemetry is enabled the stages' times fill the pending
        :class:`~repro.comm.telemetry.StageTimings` (placement and staging
        both count as ``staging``), recorded as one
        :class:`~repro.comm.telemetry.DispatchSample`; lifecycle
        accounting is identical either way.
        """
        stages, hit = self._pending_stages, self._pending_hit
        self._pending_stages, self._pending_hit = None, False
        window = entry.graph.window
        stagers = [self._stage_fn(window, m.shape[0], m.dtype, p.src)
                   for m, p in zip(messages, entry.plans)]
        compiled = entry.compiled
        # A message committed to one device (its source, say) cannot enter
        # a program over the whole mesh as it is: place it replicated, as
        # JAX does implicitly for an uncommitted one.
        t0 = time.perf_counter_ns()
        if stages is None and not tracing():
            # Nothing to record: the same stages without their spans, which
            # cost about a microsecond each on a CPU host's send path.
            xs = [stage(jax.device_put(m, self._replicated))
                  for stage, m in zip(stagers, messages)]
            staging = time.perf_counter_ns() - t0
            ys = compiled(*xs) if block else compiled.dispatch(*xs)
            out = [comm_extract(y, p.dst) for y, p in zip(ys, entry.plans)]
        else:
            with span("place"):
                xs = [jax.device_put(m, self._replicated) for m in messages]
            with span("stage"):
                xs = [stage(x) for stage, x in zip(stagers, xs)]
            staging = time.perf_counter_ns() - t0
            ys = self._execute(compiled, xs, stages, block)
            with span("extract"):
                out = [comm_extract(y, p.dst)
                       for y, p in zip(ys, entry.plans)]
        self.staging_ns += staging
        compiled.lifecycle.staging_ns += staging
        if stages is not None:
            stages.staging_ns = staging
            self._record(entry, stages, hit)
        self.dispatches += 1
        return out

    def _execute(self, compiled: CompiledPlan, xs: Sequence[jax.Array],
                 stages: StageTimings | None, block: bool):
        """Launch ``compiled`` once (``comm.launch``) and, with ``block``,
        wait for it (``comm.execute``); the lifecycle counts the launch
        and the wait."""
        with span("launch", stages):
            ys = compiled.dispatch(*xs)
        if block:
            with span("execute", stages):
                compiled.wait(ys)
        return ys

    def _record(self, entry, stages: StageTimings, hit: bool,
                compute: tuple = ()) -> None:
        """Record one finished dispatch of ``entry`` as a
        :class:`~repro.comm.telemetry.DispatchSample`."""
        routes = tuple(
            tuple((pa.route.directional_links(), pa.nbytes, pa.num_chunks)
                  for pa in p.paths)
            for p in entry.plans)
        self.telemetry.record(DispatchSample(
            routes=routes, nbytes=sum(p.nbytes for p in entry.plans),
            num_nodes=entry.graph.num_nodes, window=entry.graph.window,
            schedule=entry.schedule, stages=stages, fastpath_hit=hit,
            compute=compute))

    def _fast_entry(self, sig: tuple, epoch: tuple, recompile):
        """The fast-path entry for ``sig`` under ``epoch``, made ready to
        launch, or None on a miss. A hit still consults the plan cache by
        stored key so LRU stats/recency stay coherent; an executable
        evicted under us is rebuilt by ``recompile(entry)`` without
        re-planning. ``validate="always"`` re-validates the plans and
        graph here (§4.5)."""
        entry = self._fastpath.get(sig, epoch)
        if entry is None:
            return None
        compiled = self.cache.get(entry.key)
        if compiled is None:   # evicted under us: recompile only
            compiled = recompile(entry)
            self.cache.put(entry.key, compiled)
        entry.compiled = compiled
        if self.validate == "always":
            for p in entry.plans:
                validate_plan(p)
            entry.graph.validate(
                {i: p.nbytes for i, p in enumerate(entry.plans)},
                cross_flow_exclusive=False)
        compiled.lifecycle.fastpath_hits += 1
        self._count_schedule(entry.schedule)
        self._pending_hit = True
        return entry

    def _resolving(self, body, *args):
        """Resolve the dispatch about to launch by ``body(stages, *args)``,
        with fresh :class:`~repro.comm.telemetry.StageTimings` left
        pending for the launch when telemetry records (None otherwise).
        While there is anything to record, the resolution is one
        ``comm.resolve`` span, whose argument ``hit`` says whether the fast
        path served it."""
        tel = self.telemetry
        stages = (StageTimings() if tel is not None and tel.enabled
                  else None)
        self._pending_stages, self._pending_hit = stages, False
        if stages is None and not tracing():
            return body(stages, *args)
        with span("resolve") as resolve:
            entry = body(stages, *args)
            resolve.note(hit=self._pending_hit)
        return entry

    def _resolve(self, specs: Sequence[tuple], *, window: int,
                 max_paths: int | None, num_chunks: int | None,
                 exclusive: bool, schedule: str | GraphPass | None,
                 single: bool) -> FastPathEntry:
        """Resolve a request to a launchable :class:`FastPathEntry`.

        Fast path (hit): one dict lookup against the epoch-stamped
        :class:`FastPathCache` — planner, ``lower()``, scheduler pass,
        validation, and digest are all skipped (:meth:`_fast_entry`).
        Slow path (miss): the full pipeline, then the resolution is
        memoized under the current planner epoch. Custom
        :class:`GraphPass` objects bypass the fast path — their identity
        is not a stable signature. Spans as :meth:`_resolving` says; a
        miss adds ``comm.plan``, ``comm.lower``, ``comm.schedule`` and,
        when it builds a program, ``comm.compile``.
        """
        return self._resolving(self._resolve_group, specs, window,
                               max_paths, num_chunks, exclusive, schedule,
                               single)

    def _resolve_group(self, stages: StageTimings | None,
                       specs: Sequence[tuple], window: int,
                       max_paths: int | None, num_chunks: int | None,
                       exclusive: bool, schedule: str | GraphPass | None,
                       single: bool) -> FastPathEntry:
        """:meth:`_resolve` under the pending ``stages``."""
        sched = self.schedule if schedule is None else schedule
        sched_name = sched if isinstance(sched, str) else None
        use_fast = self.fastpath and sched_name is not None
        shapes = [(nelems, jnp.dtype(dtype))
                  for (_, _, nelems, dtype) in specs]
        sig = epoch = None
        if use_fast:
            sig = self._request_signature(
                "plan" if single else "plan_group", specs, window,
                sched_name, max_paths, num_chunks, exclusive)
            epoch = self.planner.epoch
            entry = self._fast_entry(sig, epoch, lambda e: (
                self._compile_group(e.key, e.graph, shapes, stages)))
            if entry is not None:
                return entry
        with span("plan", stages):
            if single:
                (src, dst, nelems, dtype) = specs[0]
                plans: tuple[TransferPlan, ...] = (self.plan_for(
                    src, dst, nelems, dtype, max_paths=max_paths,
                    num_chunks=num_chunks),)
            else:
                plans = self.plan_group_for(
                    specs, max_paths=max_paths, num_chunks=num_chunks,
                    exclusive=exclusive).plans
        graph, chosen = self._group_graph(plans, window, sched,
                                          stages=stages)
        self._count_schedule(chosen)
        key = self._group_key(graph, plans, shapes, window,
                              donated=self._donate)
        compiled = self.cache.get_or_build(
            key, lambda: self._compile_group(key, graph, shapes,
                                             stages))
        entry = FastPathEntry(plans=tuple(plans), graph=graph,
                              digest=key.digest, key=key,
                              compiled=compiled, schedule=chosen)
        if use_fast:
            self._fastpath.put(sig, epoch, entry)
        return entry

    # -- whole-iteration capture (heterogeneous graphs) ---------------------
    def capture(self, build_fn, *, schedule: str | None = None
                ) -> CapturedStep:
        """Record one iteration and return a launchable
        :class:`~repro.comm.capture.CapturedStep`.

        ``build_fn(cap)`` declares the step against a fresh
        :class:`~repro.comm.capture.StepCapture` and returns the output
        ref(s). Nothing is planned or compiled here — resolution happens
        on first launch (or :meth:`CapturedStep.resolve`) and is
        memoized on the fast path.
        """
        cap = StepCapture()
        outputs = build_fn(cap)
        if not isinstance(outputs, (tuple, list)):
            outputs = (outputs,)
        return CapturedStep(self, cap, tuple(outputs), schedule=schedule)

    def _build_step_fn(self, program: StepCapture, graph: TransferGraph,
                       outputs: tuple):
        """Fused whole-iteration SPMD program: the SCHEDULED graph's copy
        AND compute nodes, one trace. Each kernel is wrapped in an inner
        ``jax.jit`` named ``capk_<kernel>`` so traced kernel calls are
        countable in the jaxpr exactly like ``ppermute`` eqns — the
        one-launch acceptance check."""
        ax = self.axis_name
        buffers = tuple(program.buffers)
        input_ids = tuple(program.inputs)
        wrapped = {}
        for kname, fn in program.kernels.items():
            def _impl(*args, _fn=fn):
                return _fn(*args)
            _impl.__name__ = "capk_" + re.sub(r"\W", "_", kname)
            wrapped[kname] = jax.jit(_impl)

        def local_body(*xs):
            values = {}
            for bid, x in zip(input_ids, xs):
                values[bid] = x if buffers[bid].replicated else x[0]
            values = emit_step(graph, buffers, wrapped, values, ax)
            return tuple(values[o][None] for o in outputs)

        in_specs = tuple(P() if buffers[b].replicated else P(ax)
                         for b in input_ids)
        out_specs = tuple(P(ax) for _ in outputs)
        return jax.shard_map(local_body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _step_abstracts(self, program: StepCapture) -> tuple:
        abstracts = []
        for bid in program.inputs:
            spec = program.buffers[bid]
            dtype = jnp.dtype(spec.dtype)
            if spec.replicated:
                abstracts.append(jax.ShapeDtypeStruct(
                    spec.shape, dtype,
                    sharding=NamedSharding(self.mesh, P())))
            else:
                abstracts.append(jax.ShapeDtypeStruct(
                    (self.num_devices,) + spec.shape, dtype,
                    sharding=NamedSharding(self.mesh, P(self.axis_name))))
        return tuple(abstracts)

    def _compile_step(self, key: GroupKey, graph: TransferGraph,
                      program: StepCapture, outputs: tuple,
                      stages: StageTimings | None = None) -> CompiledPlan:
        """Compile one captured step (never donated: callers legitimately
        reuse input arrays, e.g. re-running a step on the same batch)."""
        fn = self._build_step_fn(program, graph, outputs)
        self.nodes_compiled += graph.num_nodes
        self.edges_compiled += graph.num_edges
        self.copy_nodes_compiled += graph.num_copy_nodes
        self.compute_nodes_compiled += graph.num_compute_nodes
        with span("compile", stages):
            return compile_plan(key, fn, self._step_abstracts(program),
                                num_nodes=graph.num_nodes)

    def resolve_step(self, step: CapturedStep,
                     schedule: str | GraphPass | None = None) -> _StepEntry:
        """Resolve a captured step to a launchable entry.

        Mirrors :meth:`_resolve`: fast-path hit is one dict lookup
        keyed on (capture signature, outputs, schedule name, mesh size)
        under the planner epoch; miss runs lower_step → scheduler pass →
        §4.5 validation (inside lowering) → compile, keyed on the
        scheduled graph digest + capture signature + per-kernel compute
        identity, then memoizes. Two schedules of the same capture
        digest apart and never cross-serve executables. Spans as in
        :meth:`_resolve`.
        """
        return self._resolving(self._resolve_step, step, schedule)

    def _resolve_step(self, stages: StageTimings | None, step: CapturedStep,
                      schedule: str | GraphPass | None) -> _StepEntry:
        """:meth:`resolve_step` under the pending ``stages``."""
        program = step.capture
        sched = self.schedule if schedule is None else schedule
        sched_name = sched if isinstance(sched, str) else None
        use_fast = self.fastpath and sched_name is not None
        sig = epoch = None
        if use_fast:
            sig = ("capture_step", program.signature(), step.outputs,
                   sched_name, self.num_devices)
            epoch = self.planner.epoch
            entry = self._fast_entry(sig, epoch, lambda e: (
                self._compile_step(e.key, e.graph, e.program,
                                   e.outputs, stages)))
            if entry is not None:
                return entry
        with span("lower", stages):
            graph, plans = lower_step(program, self.plan_group_for,
                                      self.topology.name)
        with span("schedule", stages):
            scheduled, chosen = apply_schedule(graph, sched,
                                               self.topology)
        self._count_schedule(chosen)
        compute_id = tuple((n.kernel, n.flops, n.cost_ns)
                           for n in scheduled.nodes
                           if isinstance(n, ComputeNode))
        key = GroupKey(scheduled.digest(),
                       entries=(program.signature(), step.outputs)
                       + compute_id,
                       window=1, num_devices=self.num_devices)
        compiled = self.cache.get_or_build(
            key, lambda: self._compile_step(key, scheduled, program,
                                            step.outputs, stages))
        entry = _StepEntry(plans=plans, graph=scheduled,
                           digest=key.digest, key=key,
                           compiled=compiled, schedule=chosen,
                           program=program, outputs=step.outputs)
        if use_fast:
            self._fastpath.put(sig, epoch, entry)
        return entry

    def _launch_step(self, entry: _StepEntry, arrays: Sequence[jax.Array],
                     *, block: bool) -> list[jax.Array]:
        """Stage the step inputs (device_put onto the declared shardings;
        staging a whole iteration's operands is dominated by the step
        itself, so inputs are not pooled like message staging) and launch
        the compiled whole-iteration program ONCE: the spans
        ``comm.place``, ``comm.launch`` and, with ``block``,
        ``comm.execute``."""
        stages, hit = self._pending_stages, self._pending_hit
        self._pending_stages, self._pending_hit = None, False
        program = entry.program
        if len(arrays) != len(program.inputs):
            raise ValueError(f"captured step takes {len(program.inputs)} "
                             f"input arrays, got {len(arrays)}")
        t0 = time.perf_counter_ns()
        with span("place"):
            xs = []
            for bid, arr in zip(program.inputs, arrays):
                spec = program.buffers[bid]
                arr = jnp.asarray(arr, jnp.dtype(spec.dtype))
                want = (spec.shape if spec.replicated
                        else (self.num_devices,) + spec.shape)
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"input for buffer {bid} must have shape {want} "
                        f"({'replicated' if spec.replicated else 'sharded'}"
                        f"), got {tuple(arr.shape)}")
                sh = NamedSharding(self.mesh, P() if spec.replicated
                                   else P(self.axis_name))
                xs.append(jax.device_put(arr, sh))
        staging = time.perf_counter_ns() - t0
        self.staging_ns += staging
        compiled = entry.compiled
        compiled.lifecycle.staging_ns += staging
        if stages is not None:
            stages.staging_ns = staging
        ys = self._execute(compiled, xs, stages, block)
        if stages is not None:
            self._record(entry, stages, hit, tuple(
                (n.kernel, n.flops, n.cost_ns) for n in entry.graph.nodes
                if isinstance(n, ComputeNode)))
        self.dispatches += 1
        return list(ys)

    def run_step(self, step: CapturedStep, arrays: Sequence[jax.Array], *,
                 schedule: str | GraphPass | None = None,
                 block: bool = True) -> list[jax.Array]:
        """Resolve + launch one captured iteration as ONE dispatch.

        Returns the step outputs device-stacked ``(num_devices,
        *local_shape)``, aligned with the capture's declared outputs.

        Under fault state (§4.6 hazard: live injector, quarantined or
        failed links) the captured step retries with bounded backoff —
        each :class:`~repro.comm.health.LinkFaultError` quarantines the
        blamed links so the re-resolve re-plans over surviving routes
        (``plan_group_for`` naturally narrows the path set; there is no
        host rung for captured steps). Exhaustion raises
        :class:`~repro.comm.health.CommFaultError` with the attempt
        history; the healthy path is byte-identical to before.
        """
        if self.faults is not None:
            self.faults.on_dispatch(self)
        if not self._hazard():
            entry = self.resolve_step(step, schedule)
            return self._launch_step(entry, arrays, block=block)
        hs = self.health
        delay = self.backoff_base_s
        history: list[str] = []
        for attempt in range(self.retry_limit + 2):
            if attempt:
                hs.replans += 1
            try:
                entry = self.resolve_step(step, schedule)
                self._fault_check(entry)
            except LinkFaultError as exc:
                history.append(f"step: {exc}")
                self._note_fault(exc, 1)
                if delay > 0:
                    time.sleep(delay)
                    delay = min(delay * 2, 0.05)
                continue
            except ValueError as exc:
                history.append(f"step: {exc}")
                raise CommFaultError(
                    f"captured-step ladder exhausted: {exc}",
                    history) from exc
            # Outside the handlers: an error of the launch itself (XLA or
            # Mosaic, placement, runtime) says nothing about routes.
            out = self._launch_step(entry, arrays, block=block)
            level = self._steady_rung(0)
            if hs.ladder_level != level:
                hs.note("ladder", level=level, rung=LADDER[level],
                        dispatch=self.dispatches)
            hs.ladder_level = level
            if self.monitor is not None:
                self.monitor.maybe_probe(self)
            return out
        raise CommFaultError(
            "captured-step dispatch failed after retries", history)

    # -- degraded-mode dispatch (DESIGN §4.6) -------------------------------
    def _hazard(self) -> bool:
        """True while any fault state can affect dispatch: a live
        injector, quarantined links, or failed topology links. The
        healthy path costs exactly these boolean reads — the §4.6
        zero-overhead-off contract."""
        return ((self.faults is not None and self.faults.active)
                or bool(self.planner.quarantined)
                or bool(self.topology.failed_links))

    def _fault_check(self, entry) -> None:
        """Validate a resolved entry against the live fault state.

        Raises :class:`~repro.comm.health.LinkFaultError` when the entry
        still routes over a failed or quarantined link (a fault landed
        between resolve and launch) or when the injector's active drop
        window blames one of the entry's links — the §4.6 invariant that
        no launch is ever issued onto a link known to be down.
        """
        links = tuple({link for p in entry.plans
                       for link in p.directional_links()})
        failed = self.topology.failed_links
        quarantined = self.planner.quarantined
        bad = [link for link in links
               if link in failed or link in quarantined]
        if bad:
            raise LinkFaultError(bad, "entry routes over faulted links")
        if self.faults is not None:
            link = self.faults.dropped_link(self.dispatches, links)
            if link is not None:
                raise LinkFaultError((link,), "injected dispatch drop")

    def _note_fault(self, exc: LinkFaultError, rung: int) -> None:
        """Account one failed attempt: bump the retry counter, log the
        event, and quarantine the blamed links (through the monitor when
        attached, so the event stream stays unified) — the epoch bump
        this causes is what makes the following re-resolve a re-plan
        over surviving links."""
        hs = self.health
        hs.retries += 1
        hs.note("retry", rung=LADDER[min(rung, len(LADDER) - 1)],
                links=list(exc.links), reason=exc.reason,
                dispatch=self.dispatches)
        for link in exc.links:
            if link in self.topology.failed_links:
                continue  # physically gone; quarantine is for suspects
            if self.monitor is not None:
                self.monitor.quarantine_link(link, reason=exc.reason,
                                             dispatch=self.dispatches)
            else:
                self.planner.quarantine(link)

    def _steady_rung(self, rung: int) -> int:
        """The :data:`~repro.comm.health.LADDER` level to record for a
        successful dispatch at ``rung``: multipath rungs report
        ``surviving_multipath`` whenever fault state constrained the
        route set (the invariant that ``ladder_level == 0`` means the
        full healthy plan)."""
        if rung >= 2:
            return rung
        if self.planner.quarantined or self.topology.failed_links:
            return 1
        return 0

    def _host_relay(self, specs: Sequence[tuple],
                    messages: Sequence[jax.Array],
                    history: Sequence[str]) -> list[jax.Array]:
        """Last ladder rung: deliver each message through a host (PCIe)
        round-trip — a device_get/device_put staging relay, the
        executable adaptation of the paper's host-staged path.

        Delivery over bandwidth: payloads arrive intact (the §4.5
        integrity contract still holds) at host-link speed, outside the
        compiled graph. Requires nominal host links on both endpoints;
        raises :class:`~repro.comm.health.CommFaultError` (the ladder is
        exhausted) when any message lacks them.
        """
        topo = self.topology
        for (src, dst, _, _) in specs:
            if (topo.link(src, HOST) is None
                    or topo.link(HOST, dst) is None):
                raise CommFaultError(
                    f"degradation ladder exhausted for {src}->{dst}: no "
                    f"surviving device route and no host-staged route",
                    history)
        devices = self.mesh.devices.flat
        outs = []
        for (_, dst, _, dtype), m in zip(specs, messages):
            staged = jax.device_get(m)                      # pull to host
            outs.append(jax.device_put(staged.astype(dtype, copy=False),
                                       devices[dst]))       # push to dst
        hs = self.health
        hs.host_relays += 1
        hs.ladder_level = 3
        hs.note("host_relay", messages=len(specs),
                dispatch=self.dispatches)
        self.dispatches += 1
        return outs

    def _dispatch(self, specs: Sequence[tuple],
                  messages: Sequence[jax.Array], *, window: int,
                  max_paths: int | None, num_chunks: int | None,
                  exclusive: bool, schedule: str | GraphPass | None,
                  single: bool, block: bool) -> list[jax.Array]:
        """Resolve + launch one request, degradation-aware (§4.6).

        Healthy state (no injector activity, no quarantine, no failed
        links) is the unchanged fast path: resolve, launch, done —
        exceptions propagate exactly as before, preserving every
        caller-visible contract (e.g. ``exclusive=True`` starvation
        raises). Under fault state the request walks
        :data:`~repro.comm.health.LADDER` instead.
        """
        if self.faults is not None:
            self.faults.on_dispatch(self)
        if not self._hazard():
            hs = self.health
            if hs.ladder_level:
                hs.ladder_level = 0  # fully recovered
            entry = self._resolve(specs, window=window,
                                  max_paths=max_paths,
                                  num_chunks=num_chunks,
                                  exclusive=exclusive, schedule=schedule,
                                  single=single)
            return self._launch(entry, messages, block=block)
        return self._dispatch_degraded(
            specs, messages, window=window, max_paths=max_paths,
            num_chunks=num_chunks, exclusive=exclusive, schedule=schedule,
            single=single, block=block)

    def _dispatch_degraded(self, specs: Sequence[tuple],
                           messages: Sequence[jax.Array], *, window: int,
                           max_paths: int | None, num_chunks: int | None,
                           exclusive: bool,
                           schedule: str | GraphPass | None,
                           single: bool, block: bool) -> list[jax.Array]:
        """Walk the §4.6 degradation ladder until the request delivers.

        Rung 0 resolves the request as asked; each
        :class:`~repro.comm.health.LinkFaultError` quarantines the
        blamed links (an epoch bump — the next resolve IS a re-plan over
        surviving links), sleeps the bounded exponential backoff, and
        retries up to ``retry_limit`` times per rung. A rung with no
        admissible route (planner ``ValueError``) escalates immediately:
        surviving multipath → single best path → host-staged relay.
        Degraded rungs drop the ``exclusive`` guarantee (delivery over
        exclusivity — documented in DESIGN §4.6); every launched plan
        still passes the same §4.5 validation as healthy traffic. Only
        when every rung is exhausted does
        :class:`~repro.comm.health.CommFaultError` reach the caller.
        """
        hs = self.health
        delay = self.backoff_base_s
        history: list[str] = []
        failed_once = False
        rungs = ((0, max_paths, 1),
                 (1, max_paths, self.retry_limit + 1),
                 (2, 1, self.retry_limit + 1))
        for rung, rung_paths, attempts in rungs:
            for _ in range(attempts):
                if failed_once:
                    hs.replans += 1
                try:
                    entry = self._resolve(
                        specs, window=window, max_paths=rung_paths,
                        num_chunks=num_chunks,
                        exclusive=exclusive and rung == 0,
                        schedule=schedule, single=single)
                    self._fault_check(entry)
                except LinkFaultError as exc:
                    failed_once = True
                    history.append(f"{LADDER[rung]}: {exc}")
                    entry.compiled.lifecycle.retries += 1
                    self._note_fault(exc, rung)
                    if delay > 0:
                        time.sleep(delay)
                        delay = min(delay * 2, 0.05)
                    continue
                except ValueError as exc:
                    failed_once = True
                    history.append(f"{LADDER[rung]}: {exc}")
                    break  # no admissible route at this rung: escalate
                # Outside the handlers: an error of the launch itself (XLA
                # or Mosaic, placement, runtime) says nothing about routes
                # and must reach the caller, not the host relay.
                out = self._launch(entry, messages, block=block)
                level = self._steady_rung(rung)
                if hs.ladder_level != level:
                    hs.note("ladder", level=level, rung=LADDER[level],
                            dispatch=self.dispatches)
                hs.ladder_level = level
                if self.monitor is not None:
                    self.monitor.maybe_probe(self)
                return out
        return self._host_relay(specs, messages, history)

    # -- public API ---------------------------------------------------------
    def transfer(self, message: jax.Array, src: int, dst: int, *,
                 window: int = 1, max_paths: int | None = None,
                 num_chunks: int | None = None,
                 schedule: str | GraphPass | None = None,
                 block: bool = True) -> jax.Array:
        """Move ``message`` (1-D array) from device ``src`` to ``dst``.

        Returns the received message (fetched from the destination shard).
        ``block=False`` launches without waiting; the caller syncs.
        ``schedule`` overrides the engine's chunk-interleaving scheduler
        for this call (DESIGN.md §2.2). For simultaneous
        opposite-direction traffic (OMB BIBW) or any other concurrent
        set, use :meth:`transfer_group` — the old ``bidirectional=True``
        flag is folded into the group API.
        """
        message = jnp.asarray(message)
        if message.ndim != 1:
            raise ValueError("message must be 1-D; reshape first")
        return self._dispatch(
            [(src, dst, message.shape[0], message.dtype)], [message],
            window=window, max_paths=max_paths, num_chunks=num_chunks,
            exclusive=False, schedule=schedule, single=True,
            block=block)[0]

    def transfer_group(self, messages: Sequence[jax.Array],
                       pairs: Sequence[tuple[int, int]], *,
                       window: int = 1, max_paths: int | None = None,
                       num_chunks: int | None = None,
                       exclusive: bool = False,
                       schedule: str | GraphPass | None = None,
                       block: bool = True) -> list[jax.Array]:
        """Move ``messages[i]`` (1-D) from ``pairs[i][0]`` to ``pairs[i][1]``
        — all of them in ONE compiled launch.

        The set is planned jointly (contention-aware; see
        :meth:`PathPlanner.plan_group`), lowered to one transfer graph,
        fused into one SPMD program, and cached under a :class:`GroupKey`
        derived from the graph digest. Returns the received messages,
        aligned with the inputs.

        Message identity is canonicalized before planning: the group is
        re-ordered by ``(src, dst, nelems, dtype)`` (stable), so
        structurally identical groups whose messages arrive in a
        different dispatch order resolve to the SAME plans, graph, cache
        entry, and fast-path signature instead of compiling a permuted
        twin (ROADMAP "graph-level cache dedup"). Results are returned in
        the caller's order.
        """
        msgs = [jnp.asarray(m) for m in messages]
        if len(msgs) != len(pairs):
            raise ValueError(f"{len(msgs)} messages vs {len(pairs)} pairs")
        if not msgs:
            return []
        for m in msgs:
            if m.ndim != 1:
                raise ValueError("messages must be 1-D; reshape first")
        specs = [(src, dst, m.shape[0], m.dtype)
                 for m, (src, dst) in zip(msgs, pairs)]
        order = sorted(range(len(msgs)),
                       key=lambda i: (specs[i][0], specs[i][1],
                                      specs[i][2], str(specs[i][3])))
        outs = self._dispatch([specs[i] for i in order],
                              [msgs[i] for i in order], window=window,
                              max_paths=max_paths, num_chunks=num_chunks,
                              exclusive=exclusive, schedule=schedule,
                              single=False, block=block)
        inverse = {i: k for k, i in enumerate(order)}
        return [outs[inverse[i]] for i in range(len(msgs))]

    def compiled_for(self, src: int, dst: int, nelems: int, dtype=jnp.float32,
                     *, window: int = 1, max_paths: int | None = None,
                     num_chunks: int | None = None,
                     schedule: str | GraphPass | None = None,
                     ) -> tuple[CompiledPlan, TransferPlan]:
        """AOT handle for benchmarks: returns (executable, plan).

        Always compiled WITHOUT operand donation (``GroupKey.donated`` is
        False) — AOT callers time repeated launches over the same operand
        arrays, which a donated executable would consume.
        """
        plan = self.plan_for(src, dst, nelems, dtype, max_paths=max_paths,
                             num_chunks=num_chunks)
        graph, chosen = self._group_graph((plan,), window, schedule)
        self._count_schedule(chosen)
        shapes = ((nelems, jnp.dtype(dtype)),)
        key = self._group_key(graph, (plan,), shapes, window)
        compiled = self.cache.get_or_build(
            key, lambda: self._compile_group(key, graph, shapes))
        return compiled, plan

    def compiled_for_group(self, specs: Sequence[tuple], *,
                           window: int = 1, max_paths: int | None = None,
                           num_chunks: int | None = None,
                           exclusive: bool = False,
                           schedule: str | GraphPass | None = None,
                           ) -> tuple[CompiledPlan, TransferGroup]:
        """AOT handle for a fused group; ``specs`` as in
        :meth:`plan_group_for`. Returns (executable, group). Specs are
        taken in the caller's order (no canonicalization — the executable
        expects operands aligned with ``group.plans``) and the program is
        compiled without donation, like :meth:`compiled_for`."""
        group = self.plan_group_for(specs, max_paths=max_paths,
                                    num_chunks=num_chunks,
                                    exclusive=exclusive)
        graph, chosen = self._group_graph(group.plans, window, schedule)
        self._count_schedule(chosen)
        shapes = [(nelems, jnp.dtype(dtype))
                  for (_, _, nelems, dtype) in specs]
        key = self._group_key(graph, group.plans, shapes, window)
        compiled = self.cache.get_or_build(
            key, lambda: self._compile_group(key, graph, shapes))
        return compiled, group

    # -- introspection ------------------------------------------------------
    def stats(self, reset: bool = False) -> dict:
        """Engine-level accounting: launches, plan-cache counters, fast-
        path counters (hits / misses / epoch invalidations), cumulative
        staging time, compiled graph totals, and per-schedule resolution
        counts. ``CommSession.stats()`` re-exports these sections.

        ``reset=True`` returns the snapshot then zeroes every windowed
        counter (engine counters, both caches' counters, cached plans'
        windowed lifecycles) so long-running sessions can report
        per-window rates instead of lifetime sums. Telemetry samples are
        NOT dropped — they feed calibration and are cleared explicitly
        via the recorder (``session.telemetry.clear()``).
        """
        out = {
            "dispatches": self.dispatches,
            "cache": self.cache.stats(reset=reset),
            "fastpath": {"enabled": self.fastpath,
                         "validate": self.validate,
                         "staging_ns": self.staging_ns,
                         **self._fastpath.stats(reset=reset)},
            "graph": {"nodes_compiled": self.nodes_compiled,
                      "edges_compiled": self.edges_compiled,
                      "copy_nodes_compiled": self.copy_nodes_compiled,
                      "compute_nodes_compiled":
                          self.compute_nodes_compiled},
            "schedules": dict(self.schedule_counts),
            # auto's candidate-score memo (keyed on digest + topology
            # epoch): hits are selections answered without re-scoring.
            "schedule_scores": AutoSchedule.score_stats(reset=reset),
            "health": self.health.snapshot(
                len(self.planner.quarantined), self.monitor is not None),
        }
        if self.telemetry is not None:
            out["telemetry"] = self.telemetry.stats()
        if reset:
            self.dispatches = 0
            self.staging_ns = 0
            self.nodes_compiled = 0
            self.edges_compiled = 0
            self.copy_nodes_compiled = 0
            self.compute_nodes_compiled = 0
            self.schedule_counts = {}
            self.health.reset_window()
        return out
