"""CommSession — the single typed entry point for multi-path communication.

The paper's handler owns path selection, graph construction, and graph
caching behind one send/recv call (Algorithm 1). ``CommSession`` is that
handler for this repo: it owns one :class:`~repro.core.topology.Topology`,
one :class:`~repro.comm.planner.PathPlanner` (with its pluggable
:class:`~repro.comm.policy.PathPolicy`), and one
:class:`~repro.comm.cache.TransferPlanCache`, and every subsystem —
training, serving, benchmarks, examples — drives communication through it:

* ``session.send(x, src, dst)`` / ``session.bidirectional(...)`` — compiled
  multi-path P2P (the executable engine),
* ``session.exchange([(x, src, dst), ...])`` — a *transfer group*: a set of
  concurrent messages planned jointly (contention-aware), fused into one
  compiled SPMD program, one cache entry, one launch,
* ``session.all_gather/reduce_scatter/all_reduce/all_to_all/psum(...)`` —
  driver-level launches of the bidirectional-ring collectives, compiled
  once per (op, shape, dtype) and cached in the *same* plan cache,
* ``session.collectives`` — the same collectives bound to the session's
  axis name, for use *inside* user ``shard_map`` programs,
* ``session.plan(...)`` / ``session.tune(...)`` — planning and the offline
  tuner (paper §4.4),
* every execution path runs the configured chunk-interleaving scheduler
  (``CommConfig.schedule`` / ``CommSession(schedule="auto")`` / per-call
  ``schedule=``) over the lowered transfer graph before compiling
  (:mod:`repro.comm.passes`, DESIGN.md §2.2),
* repeat traffic takes the steady-state dispatch fast path (DESIGN.md
  §2.3, ``CommConfig.fastpath`` / ``REPRO_MP_FASTPATH``): the whole
  plan→lower→schedule→digest resolution is served from an epoch-stamped
  :class:`~repro.comm.cache.FastPathCache`, so a repeat send is one dict
  lookup + one staging write + one launch (``session.stats()["fastpath"]``
  reports hits / misses / epoch invalidations),
* ``session.send_pytree(...)`` — P2P for arbitrary pytrees (e.g. serving
  KV-cache migration).

See DESIGN.md §5 for the session model and §6 for the migration guide from
the legacy ``MultiPathTransfer``/``PathPlanner`` wiring.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import collectives as coll
from repro.comm.cache import CompiledPlan, TransferPlanCache, compile_plan
from repro.comm.calibration import (CalibrationFitter, CalibrationProfile,
                                    modeled_vs_measured)
from repro.comm.config import CommConfig
from repro.comm.engine import MultiPathTransfer
from repro.comm.graph import canonical_digest, lower
from repro.comm.health import FaultInjector, HealthMonitor, HealthStats
from repro.comm.passes import GraphPass
from repro.comm.plan import TransferPlan
from repro.comm.planner import PathPlanner
from repro.comm.policy import PathPolicy, make_policy
from repro.comm.telemetry import TimelineRecorder, spanned
from repro.core.topology import Topology


@dataclasses.dataclass(frozen=True)
class CollectiveKey:
    """Plan-cache key for a compiled collective launch.

    The digest keys the mesh size along with op/shape/dtype/axis: a cache
    shared across sessions on different-sized meshes must not serve one
    mesh's executable to the other (P2P keys carry
    ``GroupKey.num_devices`` for the same reason — the transfer-graph
    digest covers routes, not the device axis).
    Like :class:`~repro.comm.engine.GroupKey`, the key's identity is a
    canonical digest (:func:`repro.comm.graph.canonical_digest`) so every
    entry in the shared plan cache is derived the same way.
    """

    op: str
    digest: str

    @classmethod
    def for_collective(cls, op: str, shape: tuple, dtype: str, axis: str,
                       num_devices: int) -> "CollectiveKey":
        return cls(op, canonical_digest(
            ("collective", op, tuple(shape), dtype, axis, num_devices)))


@dataclasses.dataclass(frozen=True)
class BoundCollectives:
    """Multipath collectives bound to a session's axis name.

    For use *inside* ``shard_map`` programs (e.g. the manual-collectives
    training mode); the driver-level compiled counterparts live on
    :class:`CommSession`.
    """

    axis_name: str

    def all_gather(self, x: jax.Array) -> jax.Array:
        return coll.bidir_ring_all_gather(x, self.axis_name)

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        return coll.bidir_ring_reduce_scatter(x, self.axis_name)

    def all_reduce(self, x: jax.Array) -> jax.Array:
        return coll.multipath_all_reduce(x, self.axis_name)

    def all_to_all(self, x: jax.Array) -> jax.Array:
        return coll.multipath_all_to_all(x, self.axis_name)

    def psum(self, x: jax.Array) -> jax.Array:
        return coll.psum_via_multipath(x, self.axis_name)

    def pmean(self, x: jax.Array) -> jax.Array:
        return self.psum(x) / jax.lax.axis_size(self.axis_name)


class CommSession:
    """Facade owning topology, planner, policy, engine, and plan cache."""

    def __init__(self, config: CommConfig | None = None, *,
                 mesh: jax.sharding.Mesh | None = None,
                 topology: Topology | None = None,
                 policy: PathPolicy | None = None,
                 cache: TransferPlanCache | None = None,
                 schedule: str | None = None):
        self.config = config if config is not None else CommConfig.from_env()
        if schedule is not None:
            # Convenience: CommSession(schedule="auto") — equivalent to
            # replacing config.schedule (validated there against
            # SCHEDULE_NAMES).
            self.config = self.config.replace(schedule=schedule)
        self._mesh = mesh
        self.axis_name = (mesh.axis_names[0] if mesh is not None
                          else self.config.axis_name)
        if topology is None:
            topology = Topology.full_mesh(self.mesh.devices.size,
                                          with_host=True)
        self.topology = topology
        self.policy = policy if policy is not None else make_policy(
            self.config.policy)
        self.planner = PathPlanner(topology, config=self.config,
                                   policy=self.policy)
        self.cache = cache if cache is not None else TransferPlanCache(
            self.config.cache_capacity)
        self.collectives = BoundCollectives(self.axis_name)
        #: Dispatch-timeline recorder (DESIGN §4.4c). ``config.telemetry``
        #: force-enables it; otherwise ``REPRO_MP_TELEMETRY`` decides
        #: (default off — one boolean per dispatch).
        self.telemetry = TimelineRecorder(
            capacity=self.config.telemetry_capacity,
            enabled=True if self.config.telemetry else None)
        #: Link-health monitor (DESIGN §4.6): watches telemetry residuals
        #: for droop, quarantines suspect links on the planner, and
        #: re-admits them after healthy probes. ``config.health`` /
        #: ``REPRO_MP_HEALTH`` gates construction — with it off the
        #: session carries no monitor and dispatch pays nothing.
        self.monitor: HealthMonitor | None = None
        if self.config.health:
            self.monitor = HealthMonitor(
                self.topology, self.planner,
                droop_threshold=self.config.droop_threshold,
                droop_samples=self.config.droop_samples,
                probe_healthy=self.config.probe_healthy,
                recovery_ratio=self.config.recovery_ratio,
                probe_interval=self.config.probe_interval)
            # Droop detection rides the telemetry ring's observer hook
            # (fires only while telemetry is enabled — the zero-cost-off
            # contract is the recorder's, not duplicated here).
            self.telemetry.on_record = self.monitor.observe
        #: Deterministic chaos injector parsed from ``config.faults`` /
        #: ``REPRO_MP_FAULTS`` (empty spec → no injector, no hazard).
        self.faults: FaultInjector | None = (
            FaultInjector.from_spec(self.config.faults)
            if self.config.faults else None)
        self._engine: MultiPathTransfer | None = None
        if self.config.profile_dir:
            self._load_calibration(self.config.profile_dir)

    def _load_calibration(self, profiles_dir: str) -> None:
        """Load-on-init: attach the persisted calibration profile whose
        digest matches this session's topology, if one exists. A corrupt
        or version-mismatched file degrades to a warning (the session
        runs on nominal constants) rather than failing construction."""
        try:
            profile = CalibrationProfile.load_for(self.topology,
                                                  profiles_dir)
        except (ValueError, OSError) as exc:
            warnings.warn(f"ignoring calibration profile in "
                          f"{profiles_dir!r}: {exc}", stacklevel=3)
            return
        if profile is not None:
            self.topology.set_calibration(profile)

    # -- lazy resources -----------------------------------------------------
    @property
    def mesh(self) -> jax.sharding.Mesh:
        if self._mesh is None:
            self._mesh = jax.sharding.Mesh(jax.devices(), (self.axis_name,))
        return self._mesh

    @property
    def engine(self) -> MultiPathTransfer:
        """The executable transfer engine (built on first use so planning-
        only sessions never initialize a device mesh)."""
        if self._engine is None:
            self._engine = MultiPathTransfer(
                self.mesh,
                topology=self.topology,
                planner=self.planner,
                cache=self.cache,
                schedule=self.config.schedule,
                fastpath=self.config.fastpath,
                validate=self.config.validate,
                telemetry=self.telemetry,
                monitor=self.monitor,
                faults=self.faults,
                retry_limit=self.config.retry_limit,
                backoff_base_s=self.config.backoff_base_s)
        return self._engine

    @property
    def num_devices(self) -> int:
        return self.topology.num_devices

    # -- planning and tuning ------------------------------------------------
    def plan(self, src: int, dst: int, nbytes: int, **kwargs) -> TransferPlan:
        """Plan one P2P message (Algorithm 1 lines 4–11) via the policy."""
        return self.planner.plan(src, dst, nbytes, **kwargs)

    def plan_for(self, src: int, dst: int, nelems: int, dtype=jnp.float32,
                 **kwargs) -> TransferPlan:
        """Element-granular plan for a typed 1-D message."""
        return self.engine.plan_for(src, dst, nelems, dtype, **kwargs)

    def tune(self, src: int, dst: int, nbytes: int, **kwargs) -> TransferPlan:
        """Offline tuner (paper §4.4): best (paths × chunks × host) config."""
        return self.planner.tune(src, dst, nbytes, **kwargs)

    # -- point-to-point -----------------------------------------------------
    @spanned("send")
    def send(self, x: jax.Array, src: int, dst: int, *,
             window: int | None = None, max_paths: int | None = None,
             num_chunks: int | None = None,
             schedule: str | GraphPass | None = None,
             block: bool = True) -> jax.Array:
        """Send 1-D ``x`` from device ``src`` to ``dst``; returns the
        received message. Compiled plans are cached (src, dst, size,
        config, dispatch schedule). ``schedule`` overrides the session's
        chunk-interleaving scheduler for this call (DESIGN.md §2.2). One
        ``comm.send`` span holds the call.
        """
        return self.engine.transfer(
            x, src, dst, window=self.config.window if window is None
            else window, max_paths=max_paths, num_chunks=num_chunks,
            schedule=schedule, block=block)

    def bidirectional(self, x: jax.Array, src: int, dst: int, *,
                      window: int | None = None, max_paths: int | None = None,
                      num_chunks: int | None = None,
                      schedule: str | GraphPass | None = None
                      ) -> tuple[jax.Array, jax.Array]:
        """Simultaneous src→dst and dst→src of the same message (OMB BIBW).

        Executes as a 2-transfer group (one fused launch, cache-keyed on
        BOTH plans' signatures) and returns ``(forward, reverse)`` — the
        reception at ``dst`` and the reception at ``src``. Earlier versions
        returned only the forward reception; see DESIGN.md §6.
        """
        fwd, rev = self.exchange(
            [(x, src, dst), (x, dst, src)],
            window=self.config.window if window is None else window,
            max_paths=max_paths, num_chunks=num_chunks, schedule=schedule)
        return fwd, rev

    @spanned("exchange")
    def exchange(self, items, *, window: int | None = None,
                 max_paths: int | None = None,
                 num_chunks: int | None = None,
                 exclusive: bool = False,
                 schedule: str | GraphPass | None = None,
                 block: bool = True) -> list[jax.Array]:
        """Execute a transfer group: ``items`` is a sequence of
        ``(x, src, dst)`` triples moved *concurrently*.

        The set is planned jointly — distinct flows get link-disjoint
        routes when the topology permits, and shares are derated for any
        sharing that remains (§4.4 model with ``concurrent_plans``) — then
        fused into ONE compiled SPMD program: one trace/lower/compile, one
        plan-cache entry keyed on every plan's signature, one launch.

        Arrays may be any shape/dtype (flattened on the wire, restored on
        return). Degenerate items are per-item no-ops returned unchanged:
        ``src == dst`` (nothing to move) and zero-size arrays (nothing to
        send — ``nbytes must be positive`` would otherwise reject them).
        ``exclusive=True`` demands group-level link exclusivity and raises
        if the topology cannot provide it. Returns the received arrays,
        aligned with ``items``. One ``comm.exchange`` span holds the call.
        """
        items = list(items)
        results: list[jax.Array | None] = [None] * len(items)
        live: list[tuple[int, jax.Array, int, int]] = []
        for i, (x, src, dst) in enumerate(items):
            x = jnp.asarray(x)
            if src == dst or x.size == 0:
                results[i] = x
                continue
            live.append((i, x, src, dst))
        if live:
            outs = self.engine.transfer_group(
                [x.reshape(-1) for _, x, _, _ in live],
                [(src, dst) for _, _, src, dst in live],
                window=self.config.window if window is None else window,
                max_paths=max_paths, num_chunks=num_chunks,
                exclusive=exclusive, schedule=schedule, block=block)
            for (i, x, _, _), out in zip(live, outs):
                results[i] = out.reshape(x.shape)
        return results  # type: ignore[return-value]

    def plan_group(self, requests, **kwargs):
        """Jointly plan concurrent messages without executing
        (:meth:`PathPlanner.plan_group`); ``requests`` are
        ``(src, dst, nbytes)`` tuples or :class:`TransferRequest`."""
        return self.planner.plan_group(requests, **kwargs)

    def compiled_for(self, src: int, dst: int, nelems: int,
                     dtype=jnp.float32, **kwargs
                     ) -> tuple[CompiledPlan, TransferPlan]:
        """AOT (executable, plan) handle for benchmarks."""
        return self.engine.compiled_for(src, dst, nelems, dtype, **kwargs)

    def capture(self, build_fn, *, schedule: str | None = None):
        """Capture one whole iteration (kernels + multipath exchanges) as
        ONE heterogeneous transfer graph; returns a launchable
        :class:`~repro.comm.capture.CapturedStep`.

        ``build_fn(cap)`` declares the step against a
        :class:`~repro.comm.capture.StepCapture` — inputs, kernel
        invocations, fused exchanges — and returns the output ref(s).
        The recording lowers to one graph of copy AND compute nodes,
        the session's chunk-interleaving scheduler (§2.2) interleaves
        copies into compute gaps, and every call launches ONE compiled
        SPMD program: ``stats()["dispatches"]`` increments by exactly
        one per captured iteration, however many kernels and messages
        it carries. Resolution rides the §2.3 fast path (memoized per
        capture signature + schedule + planner epoch).
        """
        return self.engine.capture(build_fn, schedule=schedule)

    def send_pytree(self, tree, src: int, dst: int):
        """Move every array leaf of ``tree`` from ``src`` to ``dst``.

        All leaves are fused into ONE transfer group: one compiled SPMD
        program covering every leaf (one plan-cache entry keyed on all
        leaf plans, not one per leaf), and one launch — steady-state KV
        migration is a single dispatch regardless of leaf count.
        Zero-size leaves and ``src == dst`` are per-leaf no-ops.
        """
        leaves, treedef = jax.tree.flatten(tree)
        moved = self.exchange([(leaf, src, dst) for leaf in leaves])
        jax.block_until_ready(moved)
        return jax.tree.unflatten(treedef, moved)

    # -- driver-level collectives ------------------------------------------
    def _run_collective(self, op: str, x: jax.Array, local_fn,
                        in_spec: P, out_spec: P,
                        num_nodes: int) -> jax.Array:
        x = jnp.asarray(x)
        key = CollectiveKey.for_collective(
            op, tuple(x.shape), str(x.dtype), self.axis_name,
            self.mesh.devices.size)
        in_sharding = NamedSharding(self.mesh, in_spec)

        def build() -> CompiledPlan:
            fn = jax.shard_map(local_fn, mesh=self.mesh, in_specs=in_spec,
                               out_specs=out_spec, check_vma=False)
            abstract = jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=in_sharding)
            return compile_plan(key, fn, (abstract,), num_nodes=num_nodes)

        compiled = self.cache.get_or_build(key, build)
        return compiled(jax.device_put(x, in_sharding))

    def _axis_size(self) -> int:
        return self.mesh.shape[self.axis_name]

    def all_gather(self, x: jax.Array) -> jax.Array:
        """Bidirectional-ring all-gather of ``x`` sharded on dim 0.

        Returns the same global array, fully replicated — both ring
        directions carry half the features each step.
        """
        n = self._axis_size()
        return self._run_collective(
            "all_gather", x, self.collectives.all_gather,
            P(self.axis_name), P(None), num_nodes=2 * (n - 1))

    def _check_ring_divisible(self, op: str, x: jax.Array, n: int) -> None:
        if x.shape[0] % n:
            raise ValueError(
                f"{op} needs dim 0 divisible by the axis size {n}, got "
                f"{x.shape[0]}; pad upstream or use psum for arbitrary "
                f"shapes")

    def reduce_scatter(self, x: jax.Array) -> jax.Array:
        """Bidirectional-ring reduce-scatter of a replicated operand; the
        result is sharded on dim 0 (device i owns the reduced block i)."""
        n = self._axis_size()
        self._check_ring_divisible("reduce_scatter", x, n)
        return self._run_collective(
            "reduce_scatter", x, self.collectives.reduce_scatter,
            P(None), P(self.axis_name), num_nodes=2 * (n - 1))

    def all_reduce(self, x: jax.Array) -> jax.Array:
        """All-reduce (sum over the axis) of a replicated operand whose
        dim 0 is divisible by the axis size; use :meth:`psum` otherwise."""
        n = self._axis_size()
        self._check_ring_divisible("all_reduce", x, n)
        return self._run_collective(
            "all_reduce", x, self.collectives.all_reduce,
            P(None), P(None), num_nodes=4 * (n - 1))

    def all_to_all(self, x: jax.Array) -> jax.Array:
        """All-to-all: ``x`` sharded on dim 0, one destination block per
        device pair — global dim 0 must be exactly n² (block payload goes
        in the trailing dims; reshape ``(n², r, ...)`` for multi-row
        blocks). The local operand must have leading dim n, one block per
        destination, or the ring algorithm would silently drop blocks."""
        n = self._axis_size()
        if x.shape[0] != n * n:
            raise ValueError(
                f"all_to_all needs global dim 0 == n²={n * n} (one block "
                f"per device pair), got {x.shape[0]}; put multi-row block "
                f"payloads in the trailing dims")
        return self._run_collective(
            "all_to_all", x, self.collectives.all_to_all,
            P(self.axis_name), P(self.axis_name), num_nodes=n - 1)

    def psum(self, x: jax.Array) -> jax.Array:
        """Sum a replicated arbitrary-shape operand over the axis (pads and
        stripes through the bidirectional ring)."""
        n = self._axis_size()
        nd = jnp.asarray(x).ndim
        return self._run_collective(
            "psum", x, self.collectives.psum,
            P(*([None] * nd)), P(*([None] * nd)), num_nodes=4 * (n - 1))

    # -- calibration (DESIGN §4.4c) -----------------------------------------
    def calibrate(self, *, fitter: CalibrationFitter | None = None,
                  attach: bool = True, persist: bool | str = False,
                  **fit_kwargs) -> CalibrationProfile:
        """Fit a :class:`CalibrationProfile` from the session's recorded
        telemetry samples and (by default) attach it to the topology.

        Attaching goes through
        :meth:`~repro.core.topology.Topology.set_calibration`, so the
        plan epoch bumps and every subsequent estimate, ``auto``
        arbitration, and planner derate consumes the fitted terms.
        ``persist=True`` saves under ``config.profile_dir`` (a string
        persists under that directory instead); ``fit_kwargs`` forward to
        :class:`CalibrationFitter` (min_samples / warmup / decay /
        max_ratio — the robustness gates). The recorder's per-kernel
        execute channel is forwarded too, so a session that timed
        captured kernels gets a fitted per-kernel compute term. Raises
        ``ValueError`` when no samples were recorded (enable
        ``REPRO_MP_TELEMETRY`` and run traffic first).
        """
        samples = self.telemetry.samples()
        if not samples:
            raise ValueError(
                "no telemetry samples recorded — enable REPRO_MP_TELEMETRY "
                "(or CommConfig.telemetry) and dispatch traffic before "
                "calibrating")
        if fitter is None:
            fitter = CalibrationFitter(self.topology, **fit_kwargs)
        elif fit_kwargs:
            raise ValueError("pass fit_kwargs or a fitter, not both")
        profile = fitter.fit(samples,
                             kernels=self.telemetry.kernel_samples())
        if attach:
            self.topology.set_calibration(profile)
        if persist:
            out_dir = (persist if isinstance(persist, str)
                       else self.config.profile_dir)
            if not out_dir:
                raise ValueError("persist=True needs config.profile_dir "
                                 "(or pass persist=<dir>)")
            profile.save(out_dir)
        return profile

    # -- introspection ------------------------------------------------------
    def describe(self, src: int, dst: int, nbytes: int, *,
                 window: int | None = None,
                 schedule: str | GraphPass | None = None,
                 **plan_kwargs) -> dict:
        """Plan one message and report its transfer graph + model costs.

        Pure planning — no mesh, no compilation — so it works on
        planning-only sessions and is what the dry-run reporter and the
        benchmarks consume. Returns the SCHEDULED graph's shape (copy
        nodes, dependency edges, critical-path depth, canonical post-pass
        digest — the cache-key ingredient) and the analytic model's
        costs, all derived from the SAME lowering + scheduler pass the
        engine would execute. The ``"schedule"`` section reports the
        requested scheduler, the concrete order chosen (``auto`` resolves
        to its winner), its modeled time, and the delta vs the
        ``round_robin`` baseline (≤ 0 when the chosen order is modeled
        faster); for ``auto`` it additionally carries the per-candidate
        ``"candidates"`` scores its selection already computed.
        """
        from repro.comm.passes import (AutoSchedule, apply_schedule,
                                       make_schedule)
        from repro.core import pipelining as pl

        window = self.config.window if window is None else window
        requested = self.config.schedule if schedule is None else schedule
        plan = self.plan(src, dst, nbytes, **plan_kwargs)
        base_graph = lower(plan, window)
        sched = (make_schedule(requested, self.topology)
                 if isinstance(requested, str) else requested)
        candidates = None
        if isinstance(sched, AutoSchedule):
            # Reuse the scores auto's selection computes anyway instead
            # of re-evaluating the winner and the baseline.
            chosen, graph, candidates = sched.select(base_graph)
            scheduled_t = candidates[chosen]
            baseline_t = candidates["round_robin"]
        else:
            graph, chosen = apply_schedule(base_graph, sched,
                                           self.topology)
            scheduled_t = pl.scheduled_time_s(graph, self.topology)
            baseline_t = (scheduled_t if graph is base_graph else
                          pl.scheduled_time_s(base_graph, self.topology))
        wire = pl.wire_time_s(plan, self.topology)
        schedule_info = {
            "requested": (requested if isinstance(requested, str)
                          else requested.name),
            "chosen": chosen,
            "scheduled_time_s": scheduled_t,
            "round_robin_time_s": baseline_t,
            "delta_vs_round_robin_s": scheduled_t - baseline_t,
        }
        if candidates is not None:
            schedule_info["candidates"] = candidates
        return {
            "src": src, "dst": dst, "nbytes": nbytes, "window": window,
            "topology": self.topology.name,
            "num_paths": plan.num_paths,
            "schedule": schedule_info,
            # Steady-state dispatch (§2.3): whether repeat traffic for
            # this request would skip the pipeline just replayed above,
            # and the epoch stamp such an entry would be keyed under.
            "fastpath": {
                "enabled": self.config.fastpath,
                "validate": self.config.validate,
                "epoch": list(self.planner.epoch),
            },
            "graph": {
                "digest": graph.digest(),
                "nodes": graph.num_nodes,
                "copy_nodes": graph.num_copy_nodes,
                "compute_nodes": graph.num_compute_nodes,
                "edges": graph.num_edges,
                "critical_path_nodes": graph.critical_path_nodes(),
            },
            "model": {
                "wire_time_s": wire,
                "time_s": pl.estimate_transfer_time_s(plan, self.topology),
                "time_first_iter_s": pl.estimate_transfer_time_s(
                    plan, self.topology, first_iteration=True),
                "launch_overhead_ns": pl.launch_overhead_ns(
                    plan, compiled_plan=True, topo=self.topology),
                "launch_overhead_nograph_ns": pl.launch_overhead_ns(
                    plan, compiled_plan=False, topo=self.topology),
                "effective_gbps": pl.effective_bandwidth_gbps(
                    plan, self.topology),
            },
            # Lane-model view (§2.2): how the scheduled order prices
            # under the resource-lane simulation vs the serialized
            # chain, and how many modeled copy seconds hide behind
            # compute. Zero hidden time on a pure-comm describe.
            "overlap": self._overlap_info(graph),
            # Measured feedback (§4.4c): which terms the model sections
            # above actually consumed, plus modeled-vs-measured residuals
            # over the recorded samples so drift is visible.
            "calibration": self._calibration_info(),
            # Island structure (§3.1): whether this request crosses a
            # node boundary, and the flat-vs-two-level modeled
            # all-reduce delta for a payload of this size.
            "hierarchy": self._hierarchy_info(src, dst, nbytes),
            # Fault state (§4.6): failed / degraded / quarantined links
            # and the monitor's thresholds, so a dry-run shows whether
            # this plan was produced under degradation.
            "health": self._health_info(),
        }

    def _overlap_info(self, graph) -> dict:
        """The ``describe()['overlap']`` section: lane vs serialized
        makespans of the scheduled graph plus modeled hidden-copy
        seconds and the fraction of total copy time hidden — the
        §2.2 overlap-visibility contract."""
        from repro.core import pipelining as pl
        lane = pl.scheduled_time_s(graph, self.topology, mode="lanes")
        serialized = pl.scheduled_time_s(graph, self.topology,
                                         mode="serialized")
        hidden = pl.hidden_copy_time_s(graph, self.topology)
        weights = pl.graph_node_weights_s(graph, self.topology)
        copy_s = sum(w for nd, w in zip(graph.nodes, weights)
                     if not hasattr(nd, "kernel"))
        return {"lane_makespan_s": lane,
                "serialized_makespan_s": serialized,
                "hidden_copy_s": hidden,
                "hidden_copy_fraction": (hidden / copy_s
                                         if copy_s > 0 else 0.0)}

    def _hierarchy_info(self, src: int, dst: int, nbytes: int) -> dict:
        """The ``describe()['hierarchy']`` section: island count, the
        request's island endpoints, and — on >1-island topologies — the
        §4.4 tier model's flat vs two-level all-reduce times for this
        payload plus the layout ``config.collective_strategy`` resolves
        to, so benchmarks report the flat-vs-hierarchical delta from the
        same model the selection contract uses."""
        topo = self.topology
        info: dict = {"islands": topo.num_islands,
                      "src_island": topo.node_of(src),
                      "dst_island": topo.node_of(dst),
                      "cross_island": topo.is_inter_island(src, dst)}
        if topo.num_islands > 1:
            chosen, times = coll.select_all_reduce_strategy(
                topo, nbytes, self.config.collective_strategy)
            info["all_reduce"] = {
                "chosen": chosen,
                "flat_time_s": times["flat"],
                "two_level_time_s": times["two_level"],
                "delta_two_level_vs_flat_s": (times["two_level"]
                                              - times["flat"]),
            }
        return info

    def _health_info(self) -> dict:
        """The ``describe()['health']`` section: whether monitoring is
        enabled, the topology's failed/degraded/flaky link overlays, the
        planner's quarantine set, and — when a monitor is attached — its
        counters and thresholds. Pure state, JSON-able, no side effects:
        the §4.6 visibility contract for dry-runs and reports."""
        topo = self.topology
        info: dict = {
            "enabled": self.monitor is not None,
            "failed": sorted(list(k) for k in topo.failed_links),
            "degraded": {f"{a}-{b}": r
                         for (a, b), r in sorted(
                             topo.degraded_links.items())},
            "quarantined": sorted(list(k)
                                  for k in self.planner.quarantined),
        }
        if self.monitor is not None:
            info["monitor"] = self.monitor.snapshot()
        return info

    def probe_links(self, nelems: int = 256) -> dict:
        """Actively probe every quarantined link (DESIGN §4.6 recovery).

        Each probe validates the link's served bandwidth against the
        recovery threshold AND pushes a payload over exactly that link
        through the compiled engine, verifying delivery intact (the
        §4.5 integrity contract applied to re-admission). A link is
        re-admitted only after ``probe_healthy`` consecutive healthy
        probes (doubled for flaky-marked links). Returns ``{(src, dst):
        ok}`` keyed by the probed links; empty when nothing is
        quarantined or health is off.
        """
        if self.monitor is None:
            return {}
        return self.monitor.probe_all(self.engine, nelems=nelems)

    def drain_health_events(self) -> list[dict]:
        """Return and clear the accumulated health event log — injector
        firings, retries, quarantines, probes, re-admissions, ladder
        moves — merged in arrival order. Draining preserves counters
        (``stats()['health']`` windows are unaffected); it exists so
        supervisors like ``ResilientTrainLoop`` can fold comm-fault
        history into their own event stream without double-reporting."""
        events: list[dict] = []
        eng = self._engine
        if eng is not None:
            events.extend(eng.health.events)
            eng.health.events.clear()
        if self.monitor is not None:
            events.extend(self.monitor.events)
            self.monitor.events.clear()
        return events

    def _calibration_info(self) -> dict:
        """The ``describe()['calibration']`` section: live-profile
        summary and modeled-vs-measured residuals (constant vs fitted)
        over the telemetry ring — the §4.4c drift-visibility contract."""
        profile = self.topology.calibration
        info: dict = {"active": profile is not None}
        if profile is not None:
            info["profile"] = profile.summary()
        samples = self.telemetry.samples()
        if samples:
            info["residuals"] = modeled_vs_measured(
                samples, self.topology, profile)
        return info

    def stats(self, reset: bool = False) -> dict:
        """One-stop accounting: cache hits/misses, launches, policy,
        topology. ``dispatches`` counts compiled-program launches — a fused
        group (``exchange``, ``send_pytree``, ``bidirectional``) is ONE
        dispatch however many messages it carries — as is a captured
        whole-iteration step (``session.capture``). ``graph`` totals the
        nodes / dependency edges of every transfer graph this session
        compiled (cache misses only); ``copy_nodes_compiled`` /
        ``compute_nodes_compiled`` break the node total down by kind
        (heterogeneous captured-step graphs carry both). ``schedule`` is the session's
        default scheduler and ``schedules`` counts dispatch/compile
        calls per concrete schedule resolved — ``auto`` counts as
        whichever candidate it picked, and cache-hit launches count too
        (unlike ``graph``, which totals cache misses only).
        ``schedule_scores`` reports ``auto``'s candidate-score memo
        (hits / misses keyed on graph digest + topology epoch) —
        repeat selections of an unchanged graph are answered without
        re-scoring every candidate. ``fastpath``
        is the steady-state dispatch front cache (DESIGN.md §2.3):
        hits / misses / epoch ``invalidations`` plus ``staging_ns``, the
        cumulative host-side staging-dispatch time (staging *execution*
        overlaps the launch and lands in the launch timings).

        ``health`` is the §4.6 degradation ledger: ``retries`` /
        ``replans`` / ``faults_seen`` / ``host_relays`` are windowed
        counters (zeroed by ``reset=True`` like the rest), while
        ``ladder_level`` and ``quarantined_links`` are live state and
        survive resets — a reset must not forget that links are still
        quarantined.

        ``reset=True`` returns the snapshot then zeroes every windowed
        counter (engine dispatches/staging, both caches, cached plans'
        windowed lifecycles) — rates instead of lifetime sums for
        long-running serving sessions. Telemetry samples survive a reset
        (they feed :meth:`calibrate`); drop them via
        ``session.telemetry.clear()``.
        """
        eng = self._engine
        if eng is not None:
            es = eng.stats(reset=reset)
        else:
            # Same schema (and real default capacity) as the live engine
            # sections, derived from an empty cache rather than spelled
            # out by hand.
            from repro.comm.cache import FastPathCache
            from repro.comm.passes import AutoSchedule
            es = {"dispatches": 0,
                  "cache": self.cache.stats(reset=reset),
                  "fastpath": {"enabled": self.config.fastpath,
                               "validate": self.config.validate,
                               "staging_ns": 0, **FastPathCache().stats()},
                  "graph": {"nodes_compiled": 0, "edges_compiled": 0,
                            "copy_nodes_compiled": 0,
                            "compute_nodes_compiled": 0},
                  "schedules": {},
                  "schedule_scores": AutoSchedule.score_stats(reset=reset),
                  "health": HealthStats().snapshot(
                      len(self.planner.quarantined),
                      self.monitor is not None)}
        return {
            "cache": es["cache"],
            "dispatches": es["dispatches"],
            "fastpath": es["fastpath"],
            "graph": es["graph"],
            "policy": self.policy.name,
            "schedule": self.config.schedule,
            "schedules": es["schedules"],
            "schedule_scores": es["schedule_scores"],
            "health": es["health"],
            "topology": self.topology.name,
            "num_devices": self.topology.num_devices,
            "axis_name": self.axis_name,
            "telemetry": self.telemetry.stats(),
            "calibration": {
                "active": self.topology.calibration is not None},
        }

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"CommSession(topology={self.topology.name!r}, "
                f"policy={self.policy.name!r}, "
                f"devices={self.topology.num_devices})")
