"""Link-fault injection and degraded-mode multipath (DESIGN.md §4.6).

Covers the whole resilience stack: the Topology fault model (fail /
degrade / restore / flaky overlays and their epoch semantics), the
deterministic FaultInjector chaos harness, planner-level quarantine and
its route-exclusion invariant, HealthMonitor droop detection and
probe-based re-admission, the engine's degradation ladder (retry →
re-plan on surviving links → single path → host-staged relay), the
captured-step retry path, collective strategy fallback, and the
ResilientTrainLoop integration. The acceptance scenario: a mid-traffic
link failure must never surface to a caller while any rung of the ladder
can still deliver, no stale executable may be served across a fault
(fast-path invalidation), and recovery must restore the exact pre-fault
plan (digest equality).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CommConfig, CommSession, FaultInjector,
                        HealthMonitor, HealthStats, LinkFaultError)
from repro.comm.health import FaultEvent, LADDER
from repro.core import HOST, PathPlanner, Topology
from repro.core.pipelining import validate_plan


@pytest.fixture()
def mesh4():
    return jax.sharding.Mesh(jax.devices()[:4], ("dev",))


def _session(topo, mesh, **cfg):
    cfg.setdefault("multipath_threshold", 1)
    cfg.setdefault("max_paths", 3)
    return CommSession(CommConfig(**cfg), mesh=mesh, topology=topo)


# ------------------------- topology fault model -----------------------------

def test_fail_link_removes_and_bumps_epoch(beluga4):
    epoch = beluga4.epoch
    digest = beluga4.digest()
    beluga4.fail_link(0, 1)
    assert (0, 1) not in beluga4.links
    assert (1, 0) in beluga4.links            # directional: reverse survives
    assert beluga4.link(0, 1) is None
    assert beluga4.link_state(0, 1) == "failed"
    assert (0, 1) in beluga4.failed_links
    assert beluga4.epoch != epoch
    assert beluga4.digest() != digest         # surviving shape differs
    # restore is exact: same Link object class/bandwidth, digest returns
    beluga4.restore_link(0, 1)
    assert beluga4.digest() == digest
    assert beluga4.link_state(0, 1) == "up"


def test_fail_link_rejects_absent_and_double(beluga4):
    with pytest.raises(KeyError):
        beluga4.fail_link(0, 99)
    beluga4.fail_link(0, 1)
    with pytest.raises(KeyError):
        beluga4.fail_link(0, 1)
    with pytest.raises(KeyError):
        beluga4.restore_link(2, 3)            # nothing to restore


def test_degrade_link_overlays_bandwidth_not_digest(beluga4):
    digest = beluga4.digest()
    nominal = beluga4.link(0, 1).bandwidth_gbps
    epoch = beluga4.epoch
    beluga4.degrade_link(0, 1, 0.25)
    assert beluga4.link(0, 1).bandwidth_gbps == pytest.approx(nominal / 4)
    assert beluga4.links[(0, 1)].bandwidth_gbps == nominal  # nominal kept
    assert beluga4.digest() == digest          # shape unchanged
    assert beluga4.epoch != epoch              # plans must re-price
    assert beluga4.link_state(0, 1) == "degraded"
    beluga4.degrade_link(0, 1, 1.0)            # ratio 1.0 clears
    assert beluga4.link_state(0, 1) == "up"
    with pytest.raises(ValueError):
        beluga4.degrade_link(0, 1, 0.0)
    with pytest.raises(ValueError):
        beluga4.degrade_link(0, 1, 1.5)


def test_degraded_bandwidth_feeds_planner_derate(beluga4):
    """A degraded link must price at its served (scaled) bandwidth so
    planning shifts load off it — the §4.4 model reads Topology.link."""
    planner = PathPlanner(beluga4)
    plan = planner.plan(0, 1, 8 << 20, max_paths=3)
    share_before = next(p.nbytes for p in plan.paths
                        if p.route.directional_links() == ((0, 1),))
    beluga4.degrade_link(0, 1, 0.1)
    plan2 = planner.plan(0, 1, 8 << 20, max_paths=3)
    share_after = sum(p.nbytes for p in plan2.paths
                      if p.route.directional_links() == ((0, 1),))
    assert share_after < share_before


def test_flaky_mark_is_advisory(beluga4):
    epoch = beluga4.epoch
    beluga4.mark_flaky(0, 1)
    assert (0, 1) in beluga4.flaky_links
    assert beluga4.link_state(0, 1) == "up"    # still routable
    assert beluga4.epoch != epoch
    beluga4.mark_flaky(0, 1, flaky=False)
    assert (0, 1) not in beluga4.flaky_links
    with pytest.raises(KeyError):
        beluga4.mark_flaky(7, 8)


# --------------------------- fault injector ---------------------------------

def test_injector_spec_grammar():
    inj = FaultInjector.from_spec(
        "fail@3:0-1; degrade@5x4:0-2*0.25, restore@9:0-1")
    acts = [(e.at, e.action, e.link) for e in inj._events]
    assert (3, "fail", (0, 1)) in acts
    assert (9, "restore", (0, 1)) in acts
    # degrade with a count carries a duration: its restore is scheduled
    # automatically when the event fires
    degrade = next(e for e in inj._events if e.action == "degrade")
    assert degrade.link == (0, 2) and degrade.duration == 4
    assert degrade.ratio == 0.25
    with pytest.raises(ValueError):
        FaultInjector.from_spec("explode@1:0-1")
    with pytest.raises(ValueError):
        FaultInjector.from_spec("fail:0-1")             # missing @AT
    with pytest.raises(ValueError):
        FaultInjector.from_spec("flap@2x2:0-1")         # flap needs ~PERIOD


def test_injector_flap_expands_to_cycles():
    inj = FaultInjector.from_spec("flap@2~3x2:0-1")
    assert [(e.at, e.action) for e in inj._events] == [
        (2, "fail"), (5, "restore"), (8, "fail"), (11, "restore")]


def test_injector_seeded_is_deterministic(beluga4):
    a = FaultInjector.seeded(beluga4, seed=7)
    b = FaultInjector.seeded(Topology.full_mesh(4), seed=7)
    assert [(e.at, e.action, e.link) for e in a._events] == \
        [(e.at, e.action, e.link) for e in b._events]
    assert a.active


def test_fault_event_validation():
    with pytest.raises(ValueError):
        FaultEvent(at=-1, action="fail", link=(0, 1))
    with pytest.raises(ValueError):
        FaultEvent(at=0, action="nope", link=(0, 1))
    with pytest.raises(ValueError):
        FaultEvent(at=0, action="degrade", link=(0, 1), ratio=0.0)


# ------------------------ planner quarantine --------------------------------

def test_quarantine_excludes_links_and_bumps_epoch(beluga4):
    planner = PathPlanner(beluga4)
    epoch = planner.epoch
    planner.quarantine((0, 1))
    assert planner.epoch != epoch              # fast-path must invalidate
    plan = planner.plan(0, 1, 4 << 20, max_paths=3)
    for p in plan.paths:
        assert (0, 1) not in p.route.directional_links()
    validate_plan(plan)                        # §4.5 invariants preserved
    # probes bypass the quarantine explicitly
    admitted = planner.plan(0, 1, 1 << 10, max_paths=1,
                            admit_quarantined=True)
    assert admitted.paths[0].route.directional_links() == ((0, 1),)
    epoch2 = planner.epoch
    planner.quarantine((0, 1))                 # idempotent: no spurious bump
    assert planner.epoch == epoch2
    planner.readmit((0, 1))
    assert planner.quarantined == frozenset()
    assert planner.epoch != epoch2


def test_quarantine_all_routes_raises(mesh4):
    """With every admissible route quarantined the planner refuses (the
    engine's ladder catches this and escalates to the host relay)."""
    topo = Topology.full_mesh(4, with_host=False, name="mesh4")
    planner = PathPlanner(topo)
    planner.quarantine(*[key for key in topo.links if 0 in key or
                         1 in key])
    with pytest.raises(ValueError):
        planner.plan(0, 1, 1 << 20)


# --------------------------- health monitor ---------------------------------

def _sample(links, measured_ns, nbytes=1 << 20):
    from repro.comm.telemetry import DispatchSample, StageTimings
    routes = (tuple((tuple(sorted(links)), nbytes, 1) for _ in (0,)),)
    return DispatchSample(routes=routes, nbytes=nbytes, num_nodes=1,
                          window=1, schedule="round_robin",
                          stages=StageTimings(execute_ns=measured_ns),
                          fastpath_hit=True)


def test_monitor_droop_quarantines_after_m_consecutive(beluga4):
    planner = PathPlanner(beluga4)
    mon = HealthMonitor(beluga4, planner, droop_threshold=2.0,
                        droop_samples=3, require_calibration=False)
    link = (0, 1)
    slow = _sample([link], measured_ns=int(1e9))     # ~1 s for 1 MiB: droop
    fast = _sample([link], measured_ns=1000)
    assert mon.observe(slow) > 2.0
    mon.observe(slow)
    assert planner.quarantined == frozenset()        # 2 < droop_samples
    mon.observe(fast)                                # healthy resets streak
    mon.observe(slow)
    mon.observe(slow)
    assert planner.quarantined == frozenset()        # consecutive, not sum
    mon.observe(slow)
    assert link in planner.quarantined
    assert mon.quarantines == 1
    assert any(e["kind"] == "quarantine" for e in mon.events)


def test_monitor_requires_calibration_by_default(beluga4):
    mon = HealthMonitor(beluga4, PathPlanner(beluga4))
    assert beluga4.calibration is None
    assert mon.observe(_sample([(0, 1)], int(1e9))) is None
    assert mon.observed == 0


def test_monitor_probe_readmits_after_healthy_streak(beluga4):
    planner = PathPlanner(beluga4)
    mon = HealthMonitor(beluga4, planner, probe_healthy=2,
                        recovery_ratio=0.5, require_calibration=False)
    mon.quarantine_link((0, 1), reason="test")
    beluga4.fail_link(0, 1)
    assert mon.probe((0, 1)) is False          # failed link never readmits
    beluga4.restore_link(0, 1)
    beluga4.degrade_link(0, 1, 0.25)           # below recovery_ratio
    assert mon.probe((0, 1)) is False
    beluga4.degrade_link(0, 1, 1.0)
    assert mon.probe((0, 1)) is True
    assert (0, 1) in planner.quarantined       # one healthy probe < 2
    assert mon.probe((0, 1)) is True
    assert (0, 1) not in planner.quarantined
    assert mon.readmissions == 1


def test_monitor_flaky_links_need_longer_streak(beluga4):
    planner = PathPlanner(beluga4)
    mon = HealthMonitor(beluga4, planner, probe_healthy=1, flaky_factor=3,
                        require_calibration=False)
    beluga4.mark_flaky(0, 1)
    mon.quarantine_link((0, 1), reason="flap")
    mon.probe((0, 1)), mon.probe((0, 1))
    assert (0, 1) in planner.quarantined       # 2 < 1 × flaky_factor
    mon.probe((0, 1))
    assert (0, 1) not in planner.quarantined


# ------------------- end-to-end chaos (acceptance) --------------------------

def test_midtraffic_link_failure_recovers_and_readmits(mesh4):
    """The ISSUE acceptance scenario: mid-traffic NVLink failure on the
    4-GPU fixture → the in-flight exchange completes on re-planned
    routes excluding the failed link (fast path invalidated, no stale
    executable), restore + healthy probes re-admit the link, and the
    steady-state plan digest returns to its pre-fault value."""
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    x = jnp.arange(4096, dtype=jnp.float32)
    y = jnp.arange(4096, dtype=jnp.float32) * 2

    outs = sess.exchange([(x, 0, 1), (y, 2, 3)])
    np.testing.assert_array_equal(outs[0], x)
    pre_digest = sess.describe(0, 1, 4096 * 4)["graph"]["digest"]
    inval0 = sess.stats()["fastpath"]["invalidations"]

    topo.fail_link(0, 1)                       # mid-traffic failure
    outs = sess.exchange([(x, 0, 1), (y, 2, 3)])
    np.testing.assert_array_equal(outs[0], x)  # delivered regardless
    np.testing.assert_array_equal(outs[1], y)
    s = sess.stats()
    assert s["fastpath"]["invalidations"] > inval0   # no stale executable
    assert s["health"]["ladder_level"] == 1          # surviving multipath
    plan = sess.plan(0, 1, 4096 * 4)
    for p in plan.paths:
        assert (0, 1) not in p.route.directional_links()
    validate_plan(plan)

    topo.restore_link(0, 1)
    for _ in range(3):
        sess.probe_links()                     # healthy probes re-admit
    assert sess.planner.quarantined == frozenset()
    outs = sess.exchange([(x, 0, 1), (y, 2, 3)])
    np.testing.assert_array_equal(outs[0], x)
    assert sess.describe(0, 1, 4096 * 4)["graph"]["digest"] == pre_digest
    assert sess.stats()["health"]["ladder_level"] == 0


def test_injected_drop_retries_and_quarantines(mesh4):
    """A dispatch-window drop fault must be survived by bounded retry on
    a re-planned route, counted in the windowed health stats."""
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4, faults="drop@1x1:0-1")
    x = jnp.arange(1024, dtype=jnp.float32)
    np.testing.assert_array_equal(sess.send(x, 0, 1), x)  # pre-fault
    np.testing.assert_array_equal(sess.send(x, 0, 1), x)  # drop fires
    s = sess.stats(reset=True)["health"]
    assert s["retries"] >= 1 and s["replans"] >= 1
    assert s["faults_seen"] == 1
    assert s["quarantined_links"] == 1          # blamed link quarantined
    # windowed counters zero on reset; quarantine state survives
    s2 = sess.stats()["health"]
    assert s2["retries"] == 0 and s2["quarantined_links"] == 1


def test_injected_fail_event_fires_at_dispatch(mesh4):
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4, faults="fail@1:0-1; restore@3:0-1")
    x = jnp.arange(512, dtype=jnp.float32)
    sess.send(x, 0, 1)
    assert (0, 1) in topo.links
    sess.send(x, 0, 1)                          # dispatch 1: fail fires
    assert (0, 1) in topo.failed_links
    sess.send(x, 0, 1)
    sess.send(x, 0, 1)                          # dispatch 3: restore fires
    assert (0, 1) in topo.links
    assert sess.stats()["health"]["faults_seen"] == 2


def test_ladder_host_relay_when_no_device_route(mesh4):
    """All device routes gone → the staged host rung delivers; no host
    path either → CommFaultError with the attempt history."""
    from repro.comm import CommFaultError

    topo = Topology.full_mesh(2)
    sess = _session(topo, jax.sharding.Mesh(jax.devices()[:2], ("dev",)))
    x = jnp.arange(128, dtype=jnp.float32)
    np.testing.assert_array_equal(sess.send(x, 0, 1), x)
    topo.fail_link(0, 1)
    out = sess.send(x, 0, 1)                   # host-staged relay
    np.testing.assert_array_equal(out, x)
    s = sess.stats()["health"]
    assert s["host_relays"] == 1 and s["ladder_level"] == 3

    topo2 = Topology.full_mesh(2, with_host=False, name="mesh2")
    sess2 = _session(topo2,
                     jax.sharding.Mesh(jax.devices()[:2], ("dev",)))
    np.testing.assert_array_equal(sess2.send(x, 0, 1), x)
    topo2.fail_link(0, 1)
    with pytest.raises(CommFaultError):
        sess2.send(x, 0, 1)                    # ladder truly exhausted


def test_host_relay_lands_on_destination_device(mesh4):
    """The staged-host rung delivers onto ``dst``'s device, not onto the
    default device."""
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    x = jnp.arange(1024, dtype=jnp.float32)
    for a in (0, 1, 2):
        topo.fail_link(a, 3)                   # no device route into 3
    out = sess.send(x, 0, 3)
    assert sess.stats()["health"]["host_relays"] == 1
    assert out.devices() == {mesh4.devices.flat[3]}
    np.testing.assert_array_equal(out, x)


def test_launch_error_under_fault_state_is_not_relayed(mesh4,
                                                       monkeypatch):
    """An error from the launch itself (an XLA, Mosaic or placement
    error on the chip) reaches the caller under fault state instead of
    escalating the ladder to the host relay."""
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    topo.fail_link(0, 1)                       # hazard: degraded loop
    x = jnp.arange(1024, dtype=jnp.float32)

    def refuse(*args, **kwargs):
        raise ValueError("refused by the device compiler")

    monkeypatch.setattr(sess.engine, "_launch", refuse)
    with pytest.raises(ValueError, match="device compiler"):
        sess.send(x, 0, 2)
    assert sess.stats()["health"]["host_relays"] == 0


def test_healthy_path_unchanged_and_exclusive_contract():
    """With health on but no fault state, dispatch takes the pristine
    path: exclusive=True starvation still raises ValueError (the ladder
    must not swallow healthy-path contract errors). Chain 2—0—1: flow
    (0,1) claims the only link into 1, starving flow (2,1)."""
    from repro.core import Link

    gb = 25.0
    links = [Link(a, b, "nvlink", gb)
             for (a, b) in ((0, 1), (1, 0), (2, 0), (0, 2))]
    topo = Topology(3, links, name="chain3")
    mesh3 = jax.sharding.Mesh(jax.devices()[:3], ("dev",))
    sess = _session(topo, mesh3, multipath_threshold=0)
    x = jnp.arange(256, dtype=jnp.float32)
    with pytest.raises(ValueError, match="link-exclusive"):
        sess.exchange([(x, 0, 1), (x, 2, 1)], exclusive=True)


def test_health_off_disables_monitor(mesh4):
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4, health=False)
    assert sess.monitor is None
    x = jnp.arange(64, dtype=jnp.float32)
    np.testing.assert_array_equal(sess.send(x, 0, 1), x)
    s = sess.stats()["health"]
    assert s["enabled"] is False
    assert sess.describe(0, 1, 1 << 20)["health"]["enabled"] is False


# -------------------- captured-step traffic under faults --------------------

def test_captured_decode_step_survives_link_failure(mesh4):
    """The serving acceptance scenario: a captured decode step keeps
    serving through a mid-traffic failure of a link its KV migration
    rides — re-resolved on surviving routes, numerics intact."""
    from repro.serving.engine import make_captured_decode_step

    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    n, kv_chunk = 4, 4096
    step = make_captured_decode_step(
        sess, batch=1, heads=2, kv_len=16, head_dim=8,
        kv_chunk=kv_chunk, src=0, dst=2)
    rng = np.random.default_rng(0)
    shp = (n, 1, 2, 16, 8)
    q, k, v = (rng.random(shp).astype(np.float32) for _ in range(3))
    kv = rng.random((n, kv_chunk)).astype(np.float32)

    def check(attn, new_kv):
        expect = kv.copy()
        expect[2] = kv[0]
        np.testing.assert_allclose(np.asarray(new_kv), expect, rtol=1e-6)

    check(*step(q, k, v, kv))
    topo.fail_link(0, 2)                       # the migration's direct link
    check(*step(q, k, v, kv))                  # re-planned, still serves
    plans = step.resolve().plans
    for p in plans:
        assert (0, 2) not in p.directional_links()
    topo.restore_link(0, 2)
    check(*step(q, k, v, kv))


def test_serve_engine_surfaces_health_events(mesh4):
    """ServeEngine drains comm health events after KV migration, so the
    serving layer sees the degradation that happened under its traffic."""
    from repro.configs import REGISTRY, load_all
    from repro.serving.engine import ServeEngine
    from repro.models import transformer as tfm

    load_all()
    cfg = REGISTRY["smollm_360m"].reduced()
    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    params = tfm.init_params(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, max_len=32, kv_chunks=2, comm=sess)
    _, cache = eng.prefill(jnp.ones((1, 4), jnp.int32))
    topo.fail_link(0, 1)
    eng.migrate_kv(cache, 0, 1)                # degraded but delivered
    kinds = {e["kind"] for e in eng.health_events}
    assert "ladder" in kinds                   # degradation was surfaced


# ----------------------- collectives degradation ----------------------------

def test_forced_two_level_falls_back_to_flat_when_egress_dead(two_island):
    from repro.comm import select_all_reduce_strategy

    chosen, _ = select_all_reduce_strategy(two_island, 1 << 20,
                                           "two_level")
    assert chosen == "two_level"
    for (a, b) in list(two_island.links):
        if two_island.is_inter_island(a, b):
            two_island.fail_link(a, b)
    chosen, times = select_all_reduce_strategy(two_island, 1 << 20,
                                               "two_level")
    assert chosen == "flat"                    # §4.6 egress fallback
    assert times["two_level"] == float("inf")


# ----------------------- ResilientTrainLoop ---------------------------------

def _fake_build(num_devices, ckpt):
    state = {"opt": {"step": jnp.asarray(0, jnp.int32)}}

    def step_fn(st, batch):
        st = {"opt": {"step": st["opt"]["step"] + 1}}
        return st, {"loss": jnp.asarray(1.0)}

    return step_fn, state, lambda s: {}


def test_loop_exhaustion_flushes_and_records_before_raise(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.fault_tolerance import (ResilientLoopConfig,
                                               ResilientTrainLoop)

    ckpt = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    loop = ResilientTrainLoop(ckpt, ResilientLoopConfig(max_restarts=0))
    with pytest.raises(RuntimeError, match="restart budget exhausted"):
        loop.run(_fake_build, total_steps=8, fail_at={2: 4})
    terminal = [e for e in loop.events if e["kind"] == "exhausted"]
    assert terminal and terminal[0]["step"] == 2
    assert terminal[0]["budget"] == 0


def test_loop_drains_comm_health_events(tmp_path, mesh4):
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.fault_tolerance import (ResilientLoopConfig,
                                               ResilientTrainLoop)

    topo = Topology.full_mesh(4)
    sess = _session(topo, mesh4)
    sess.monitor.quarantine_link((0, 1), reason="droop")  # pending event
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    loop = ResilientTrainLoop(ckpt, ResilientLoopConfig(), comm=sess)
    loop.run(_fake_build, total_steps=2)
    comm_events = [e for e in loop.events if e["kind"] == "comm_health"]
    assert comm_events and comm_events[0]["event"]["link"] == (0, 1)
    assert sess.drain_health_events() == []    # drained, not duplicated


# ----------------------------- stats surface --------------------------------

def test_health_stats_schema_and_reset():
    hs = HealthStats()
    hs.retries, hs.replans, hs.ladder_level = 2, 1, 1
    snap = hs.snapshot(quarantined=1, enabled=True)
    assert snap == {"enabled": True, "retries": 2, "replans": 1,
                    "faults_seen": 0, "host_relays": 0,
                    "ladder_level": 1, "quarantined_links": 1}
    hs.reset_window()
    assert hs.retries == 0 and hs.ladder_level == 1   # state survives


def test_link_fault_error_carries_links():
    err = LinkFaultError([(0, 1)], "injected")
    assert err.links == ((0, 1),) and "injected" in str(err)
    assert LADDER[0] == "multipath" and LADDER[-1] == "staged_host"
