"""Graph-pass pipeline: scheduler semantics, the §2.2 pass contract,
post-pass cache keys, and the per-scheduler equal-graph acceptance.

Acceptance criteria exercised here (ISSUE 4):

* ``round_robin`` scheduled graph is node-for-node identical (same
  digest, same object) to today's lowering,
* ``depth_first`` and ``critical_path`` outputs pass every §4.5
  invariant while digesting apart from the baseline,
* ``auto`` never selects a schedule the model scores worse than
  ``round_robin``,
* ``GroupKey`` incorporates the POST-pass digest: two schedules of the
  same plan get distinct cache entries and never cross-serve
  executables,
* traced ``ppermute`` count == scheduled ``graph.num_nodes`` for every
  shipped scheduler.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CommConfig, CommSession, PathPlanner,
                        SCHEDULE_NAMES, TransferPlanCache)
from repro.comm.graph import DepEdge, TransferGraph, lower
from repro.comm.passes import (AutoSchedule, CriticalPathSchedule,
                               DepthFirstSchedule, OverlapSchedule,
                               RoundRobinSchedule, apply_schedule,
                               check_pass, make_schedule, reindex,
                               run_pipeline)
from repro.core import Topology, scheduled_time_s

MiB = 1 << 20
CONCRETE = ("round_robin", "depth_first", "critical_path")


@pytest.fixture(scope="module")
def topo(mesh8):
    # Alias of the shared conftest.py ``mesh8`` fixture; tests needing a
    # distinct identity (memoization) build their own topologies below.
    return mesh8


@pytest.fixture(scope="module")
def planner(topo):
    return PathPlanner(topo, multipath_threshold=256)


@pytest.fixture(scope="module")
def plan(planner):
    # Multi-path, multi-chunk, uneven size: orders genuinely differ and
    # the remainder chunk gives critical_path something to move.
    return planner.plan(0, 1, 8 * MiB + 12_288, max_paths=3, num_chunks=4,
                        granularity=4)


# ------------------------- scheduler semantics ------------------------------

def test_round_robin_is_todays_lowering(plan):
    """ACCEPTANCE: round_robin == today's lowering, node-for-node."""
    for window in (1, 3):
        graph = lower(plan, window)
        scheduled, chosen = apply_schedule(graph, "round_robin")
        assert chosen == "round_robin"
        assert scheduled is graph                  # identity, not a copy
        assert scheduled.digest() == graph.digest()


@pytest.mark.parametrize("name", ["depth_first", "critical_path"])
def test_reordering_passes_preserve_invariants(plan, topo, name):
    """ACCEPTANCE: depth_first / critical_path pass all §4.5 invariants
    on the scheduled graph and keep the node multiset intact."""
    graph = lower(plan, 2)
    scheduled, _ = apply_schedule(graph, name, topo)
    scheduled.validate({0: plan.nbytes})           # §4.5 on the output
    assert scheduled.num_nodes == graph.num_nodes
    assert scheduled.num_edges == graph.num_edges
    assert (sorted(map(dataclasses.astuple, scheduled.nodes))
            == sorted(map(dataclasses.astuple, graph.nodes)))
    # index order is a valid topological order (the emitter's walk)
    order = scheduled.topological_order()
    assert order == sorted(order)


def test_depth_first_drains_paths(plan):
    graph, _ = apply_schedule(lower(plan), "depth_first")
    seen_paths = [n.path_idx for n in graph.nodes]
    # once we leave a path we never return to it (within one window/msg)
    firsts = {p: seen_paths.index(p) for p in set(seen_paths)}
    lasts = {p: len(seen_paths) - 1 - seen_paths[::-1].index(p)
             for p in set(seen_paths)}
    spans = sorted((firsts[p], lasts[p]) for p in firsts)
    for (_, last_a), (first_b, _) in zip(spans, spans[1:]):
        assert last_a < first_b


def test_schedules_digest_apart(plan, topo):
    graph = lower(plan)
    digests = {apply_schedule(graph, n, topo)[0].digest()
               for n in CONCRETE}
    assert len(digests) == 3


def test_auto_never_worse_than_round_robin(planner, topo):
    """ACCEPTANCE: auto's pick is never modeled slower than round_robin."""
    for nbytes in (256, 1 * MiB, 8 * MiB + 12_288, 64 * MiB):
        for max_paths in (1, 2, 3):
            p = planner.plan(0, 1, nbytes, max_paths=max_paths)
            graph = lower(p)
            auto = make_schedule("auto", topo)
            name, scheduled, scores = auto.select(graph)
            assert scores[name] == min(scores.values())
            assert scores[name] <= scores["round_robin"]
            assert scheduled_time_s(scheduled, topo) <= scheduled_time_s(
                graph, topo)


def test_auto_requires_topology():
    with pytest.raises(ValueError, match="topology"):
        make_schedule("auto")
    with pytest.raises(ValueError, match="unknown schedule"):
        make_schedule("zigzag")


def test_group_scheduling(planner, topo):
    group = planner.plan_group([(0, 1, 4 * MiB), (1, 0, 4 * MiB),
                                (2, 3, 2 * MiB)])
    graph = lower(group, 2)
    for name in CONCRETE + ("auto",):
        scheduled, _ = apply_schedule(graph, name, topo)
        scheduled.validate({i: p.nbytes for i, p in enumerate(group.plans)},
                           cross_flow_exclusive=False)
        assert scheduled.num_nodes == graph.num_nodes


def test_run_pipeline_composes(plan, topo):
    graph = lower(plan)
    out = run_pipeline(graph, ["depth_first", "round_robin"], topo)
    # round_robin restores the canonical order whatever came before
    assert out.digest() == graph.digest()
    out2 = run_pipeline(graph, [DepthFirstSchedule()], topo)
    assert out2.digest() == apply_schedule(graph, "depth_first")[0].digest()


# --------------------------- the §2.2 contract ------------------------------

def test_reindex_rejects_non_permutation(plan):
    graph = lower(plan)
    with pytest.raises(ValueError, match="permutation"):
        reindex(graph, list(range(graph.num_nodes - 1)))


def test_reindex_rejects_anti_topological_order(plan):
    graph = lower(plan)
    order = list(range(graph.num_nodes))[::-1]     # hop chains reversed
    with pytest.raises(ValueError, match="topological"):
        reindex(graph, order)


def test_check_pass_catches_node_mutation(plan):
    graph = lower(plan)
    n0 = graph.nodes[0]
    bad = TransferGraph(
        (dataclasses.replace(n0, nbytes=n0.nbytes + 4),) + graph.nodes[1:],
        graph.edges, graph.window, graph.num_messages, graph.topology_name)
    with pytest.raises(ValueError, match="node multiset"):
        check_pass(graph, bad)


def test_check_pass_catches_dropped_edge(plan):
    graph = lower(plan)
    bad = TransferGraph(graph.nodes, graph.edges[1:], graph.window,
                        graph.num_messages, graph.topology_name)
    with pytest.raises(ValueError, match="edge set"):
        check_pass(graph, bad)


def test_check_pass_catches_backward_edge(plan):
    graph = lower(plan)
    e0 = graph.edges[0]
    bad = TransferGraph(graph.nodes,
                        (DepEdge(e0.dst, e0.src, e0.kind),)
                        + graph.edges[1:], graph.window,
                        graph.num_messages, graph.topology_name)
    with pytest.raises(ValueError, match="edge set|topological"):
        check_pass(graph, bad)


def test_check_pass_accepts_shipped_passes(plan, topo):
    graph = lower(plan, 2)
    for sched in (RoundRobinSchedule(), DepthFirstSchedule(),
                  CriticalPathSchedule(topo), OverlapSchedule(topo),
                  AutoSchedule(topo)):
        check_pass(graph, sched(graph))


# ----------------- post-pass cache keys (GroupKey bugfix) -------------------

def test_group_key_uses_post_pass_digest(topo):
    """REGRESSION: two schedules of the same plan must get distinct cache
    entries (post-pass digest, not the pre-pass lowering digest) and never
    cross-serve executables."""
    cache = TransferPlanCache(capacity=8)
    sess = CommSession(CommConfig(multipath_threshold=64), topology=topo,
                       cache=cache)
    msg = jnp.asarray(np.random.RandomState(7).randn(3001), jnp.float32)
    out_rr = sess.send(msg, 0, 5, max_paths=3, num_chunks=4,
                       schedule="round_robin")
    out_df = sess.send(msg, 0, 5, max_paths=3, num_chunks=4,
                       schedule="depth_first")
    np.testing.assert_array_equal(np.asarray(out_rr), np.asarray(msg))
    np.testing.assert_array_equal(np.asarray(out_df), np.asarray(msg))
    keys = cache.keys()
    assert len(keys) == 2                          # no cross-serving
    assert keys[0].digest != keys[1].digest
    plan = sess.plan_for(0, 5, 3001, jnp.float32, max_paths=3,
                         num_chunks=4)
    pre_pass = lower(plan).digest()
    df_graph, _ = apply_schedule(lower(plan), "depth_first")
    assert pre_pass in {k.digest for k in keys}        # round_robin entry
    assert df_graph.digest() in {k.digest for k in keys}
    assert df_graph.digest() != pre_pass               # post-pass differs
    # re-sending under each schedule hits its own entry
    sess.send(msg, 0, 5, max_paths=3, num_chunks=4, schedule="round_robin")
    sess.send(msg, 0, 5, max_paths=3, num_chunks=4, schedule="depth_first")
    assert cache.stats()["misses"] == 2
    assert cache.stats()["hits"] == 2
    assert sess.stats()["schedules"] == {"round_robin": 2,
                                         "depth_first": 2}


def test_session_default_schedule_config(topo, monkeypatch):
    monkeypatch.setenv("REPRO_MP_SCHEDULE", "depth_first")
    assert CommConfig.from_env().schedule == "depth_first"
    with pytest.raises(ValueError, match="unknown schedule"):
        CommConfig(schedule="nope")
    sess = CommSession(schedule="auto", topology=topo)
    assert sess.config.schedule == "auto"
    assert sess.stats()["schedule"] == "auto"
    assert set(SCHEDULE_NAMES) == {"round_robin", "depth_first",
                                   "critical_path", "overlap", "auto"}


def test_describe_reports_schedule(topo):
    sess = CommSession(CommConfig(multipath_threshold=256), topology=topo)
    d = sess.describe(0, 1, 8 * MiB + 12_288, max_paths=3, schedule="auto",
                      granularity=4, num_chunks=4)
    s = d["schedule"]
    assert s["requested"] == "auto"
    assert s["chosen"] in CONCRETE + ("overlap",)
    assert s["scheduled_time_s"] <= s["round_robin_time_s"]
    assert s["delta_vs_round_robin_s"] <= 0
    plan = sess.plan(0, 1, 8 * MiB + 12_288, max_paths=3, granularity=4,
                     num_chunks=4)
    scheduled, _ = apply_schedule(lower(plan), s["chosen"], topo)
    assert d["graph"]["digest"] == scheduled.digest()


# ------------------- equal-graph acceptance per scheduler -------------------

@pytest.mark.parametrize("name", CONCRETE + ("auto",))
def test_equal_graph_per_scheduler(topo, name, count_eqns):
    """ACCEPTANCE: traced ppermute count == scheduled graph.num_nodes for
    every shipped scheduler — the executable is a view of the scheduled
    graph, whatever the dispatch order."""
    sess = CommSession(CommConfig(multipath_threshold=256), topology=topo)
    eng = sess.engine
    plan = eng.plan_for(0, 1, 4096, max_paths=3, num_chunks=4)
    graph, _ = eng._group_graph((plan,), 2, name)
    fn = eng._build_group_fn(graph, (4,))
    traced = count_eqns(fn, (jax.ShapeDtypeStruct(
        (2, eng.num_devices, 4096), jnp.float32),), "ppermute")
    assert traced == graph.num_nodes == 2 * plan.num_nodes


@pytest.mark.parametrize("name", CONCRETE)
def test_executed_transfer_per_scheduler(topo, name):
    """End-to-end: every scheduler's program still moves the bytes."""
    sess = CommSession(CommConfig(multipath_threshold=64), topology=topo)
    msg = jnp.asarray(np.random.RandomState(11).randn(1000), jnp.float32)
    out = sess.send(msg, 0, 5, max_paths=3, num_chunks=3, schedule=name)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msg))


def test_exchange_with_schedule(topo):
    sess = CommSession(CommConfig(multipath_threshold=64), topology=topo,
                       schedule="critical_path")
    a = jnp.arange(512, dtype=jnp.float32)
    b = -jnp.arange(512, dtype=jnp.float32)
    fwd, rev = sess.exchange([(a, 0, 1), (b, 1, 0)], num_chunks=2)
    np.testing.assert_array_equal(np.asarray(fwd), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(rev), np.asarray(b))
    assert sum(sess.stats()["schedules"].values()) == 1


# ---------------- overlap scheduler + lane makespan model -------------------

def _lower_capture(build, topo, threshold=2 * MiB):
    """Lower a StepCapture build fn against ``topo`` without a session."""
    from repro.comm import PathPlanner, StepCapture, TransferRequest
    from repro.comm.capture import lower_step

    planner = PathPlanner(topo, multipath_threshold=threshold)

    def plan_group_fn(specs, *, max_paths=None, num_chunks=None):
        reqs = [TransferRequest(s, d, ne * 4, granularity=4)
                for (s, d, ne, _) in specs]
        return planner.plan_group(reqs, max_paths=max_paths,
                                  include_host=False,
                                  num_chunks=num_chunks)

    cap = StepCapture()
    build(cap)
    graph, _ = lower_step(cap, plan_group_fn, topo.name)
    return graph


def _head_of_line_build(cap, *, slow_flops=5_000_000):
    """Mixed graph with a head-of-line hazard on link (0, 1): a big copy
    gated behind a slow kernel is emitted BEFORE a ready small copy on
    the same link, so the lowering order stalls the ready copy — a
    lane-aware reorder must pull it ahead of the gated one."""
    big = cap.input((1 << 15,), jnp.float32)       # 128 KiB payload
    small = cap.input((1 << 13,), jnp.float32)     # 32 KiB payload
    gated = cap.kernel(lambda v: v + 1.0, big, name="slow_kernel",
                       flops=slow_flops)
    ready = cap.kernel(lambda v: v * 2.0, small, name="cheap_kernel",
                       flops=0)
    (r_big,) = cap.exchange([(gated, 0, 1)], num_chunks=1)
    (r_small,) = cap.exchange([(ready, 0, 1)], num_chunks=1)
    cap.kernel(lambda a, b: a[: b.shape[0]] + b, r_big, r_small,
               name="sink", flops=0)


def _overlap_wins_build(cap):
    """Mixed graph where ONLY the lane-aware ``overlap`` order wins.

    Two copies share link (0, 1): a big one ready at t=0 and a small one
    gated behind the fast kernel; an independent slow kernel provides
    compute to hide behind. ``round_robin``/``depth_first`` dispatch the
    slow kernel before the fast one (program order), stalling the gated
    copy. ``critical_path``'s earliest-finish simulation serializes
    copies per *(message, path)* slot — it can't see the two messages
    contending for one link — so it dispatches the gated small copy
    first (it finishes sooner) and head-of-line blocks the big one.
    ``overlap``'s earliest-start rule over the true link lane issues the
    big copy at t=0 behind both kernels."""
    small = cap.input((1 << 15,), jnp.float32)     # 128 KiB staged payload
    big = cap.input((1 << 16,), jnp.float32)       # 256 KiB, ready at 0
    slow = cap.kernel(lambda v: v * 0.5, big, name="k_slow",
                      flops=700_000)               # ~14 us of compute
    fast = cap.kernel(lambda v: v + 1.0, small, name="k_fast",
                      flops=50_000)                # ~1 us of compute
    (r_small,) = cap.exchange([(fast, 0, 1)], num_chunks=1)
    (r_big,) = cap.exchange([(big, 0, 1)], num_chunks=1)
    cap.kernel(lambda a, b, c: a + b[: a.shape[0]] + c[: a.shape[0]],
               r_small, r_big, slow, name="sink", flops=0)


def test_overlap_contract_and_lane_win_on_head_of_line(topo):
    """ACCEPTANCE: on a mixed graph with a head-of-line hazard the
    ``overlap`` schedule passes the §2.2 contract, strictly beats every
    other candidate's lane makespan, hides copy time behind compute,
    and ``auto`` selects it."""
    from repro.core.pipelining import hidden_copy_time_s

    graph = _lower_capture(_overlap_wins_build, topo)
    assert graph.num_compute_nodes and graph.num_copy_nodes
    overlap = OverlapSchedule(topo)
    out = overlap(graph)
    check_pass(graph, out)                        # §2.2 contract
    lanes = {}
    for name in CONCRETE + ("overlap",):
        sg, _ = apply_schedule(graph, name, topo)
        lanes[name] = scheduled_time_s(sg, topo, mode="lanes")
    for name in CONCRETE:
        assert lanes["overlap"] < lanes[name]     # strict lane win
    # the reordered ready copy runs behind the slow kernel
    sg, _ = apply_schedule(graph, "overlap", topo)
    assert hidden_copy_time_s(sg, topo) > 0.0
    # and auto picks it under the lane objective
    name, chosen_graph, scores = make_schedule("auto", topo).select(graph)
    assert name == "overlap"
    assert chosen_graph.digest() == sg.digest()
    assert scores["overlap"] == min(scores.values())


def test_overlap_never_worse_than_input_on_pure_comm(plan, topo):
    """The anomaly guard: when greedy lane scheduling finds nothing
    strictly faster, overlap returns the input graph unchanged — so it
    can never model worse than round_robin."""
    graph = lower(plan, 2)
    out = OverlapSchedule(topo)(graph)
    check_pass(graph, out)
    assert (scheduled_time_s(out, topo, mode="lanes")
            <= scheduled_time_s(graph, topo, mode="lanes"))


def test_auto_never_worse_than_round_robin_mixed(topo):
    """auto's never-worse guarantee holds under the lane objective on
    heterogeneous graphs too."""
    for flops in (0, 10_000, 5_000_000):
        graph = _lower_capture(
            lambda cap: _head_of_line_build(cap, slow_flops=flops), topo)
        name, scheduled, scores = make_schedule("auto", topo).select(graph)
        assert scores[name] == min(scores.values())
        assert scores[name] <= scores["round_robin"]


def test_lane_model_reduces_to_serialized_on_pure_comm(planner, topo):
    """SATELLITE: on pure-comm graphs the default objective IS the
    serialized chain — numerically identical scores (so PR 5/6 digests
    and arbitrations are unperturbed) — while explicit lane pricing
    differs only by charging issue cost into lane occupancy."""
    from repro.core.pipelining import launch_model_for

    for nbytes, max_paths in ((256, 1), (1 * MiB, 1), (8 * MiB, 3)):
        p = planner.plan(0, 1, nbytes, max_paths=max_paths)
        graph = lower(p)
        assert graph.num_compute_nodes == 0
        default_s = scheduled_time_s(graph, topo)
        serialized_s = scheduled_time_s(graph, topo, mode="serialized")
        assert default_s == serialized_s          # bit-identical
        if max_paths == 1:
            # single-path chain: lane FIFO == the serialized chain up to
            # the per-node issue charge (documented exact relationship)
            lane_s = scheduled_time_s(graph, topo, mode="lanes")
            per_node_s = launch_model_for(topo).graph_launch_per_node_ns / 1e9
            assert lane_s == pytest.approx(
                serialized_s + graph.num_nodes * per_node_s, rel=1e-9)


def test_scheduled_time_rejects_unknown_mode(planner, topo):
    graph = lower(planner.plan(0, 1, 4096))
    with pytest.raises(ValueError, match="unknown scheduling model"):
        scheduled_time_s(graph, topo, mode="warp")


def test_auto_memoizes_candidate_scores(planner, topo):
    """SATELLITE bugfix: repeat selects of the same (digest, epoch) are
    memo hits; a topology epoch bump (set_calibration) re-scores."""
    from repro.comm.calibration import CalibrationProfile

    AutoSchedule.score_stats(reset=True)
    local = Topology.full_mesh(4, with_host=False, name="memo4")
    lp = type(planner)(local, multipath_threshold=256)
    graph = lower(lp.plan(0, 1, 4 * MiB, max_paths=2))
    auto = make_schedule("auto", local)
    first = auto.select(graph)
    assert AutoSchedule.score_stats() == {"hits": 0, "misses": 1}
    second = auto.select(graph)
    assert AutoSchedule.score_stats() == {"hits": 1, "misses": 1}
    assert first[0] == second[0] and first[2] == second[2]
    # a fresh AutoSchedule over the same topology shares the memo
    assert make_schedule("auto", local).select(graph)[0] == first[0]
    assert AutoSchedule.score_stats()["hits"] == 2
    # epoch bump invalidates: the memo key includes topology.epoch
    local.set_calibration(
        CalibrationProfile(topology_digest=local.digest()))
    auto.select(graph)
    assert AutoSchedule.score_stats() == {"hits": 2, "misses": 2}
    stats = AutoSchedule.score_stats(reset=True)
    assert stats == {"hits": 2, "misses": 2}
    assert AutoSchedule.score_stats() == {"hits": 0, "misses": 0}


def test_fitted_kernel_cost_flips_auto_choice():
    """ACCEPTANCE: a fitted per-kernel compute term (§4.4d) flips a
    scheduling decision. Without calibration the ``k_fast`` kernel is
    priced by declared FLOPs (~1 us) and only ``overlap`` finds the
    order that hides the contended copies; a synthetic skewed profile
    measuring ``k_fast`` at 50 us makes every candidate's order collapse
    to the same copy-first dispatch, the scores tie, and
    strict-improvement arbitration keeps the earliest candidate —
    ``auto``'s pick changes."""
    from repro.comm.calibration import CalibrationProfile

    local = Topology.full_mesh(8, with_host=False, name="flip8")
    graph = _lower_capture(_overlap_wins_build, local)
    auto = make_schedule("auto", local)
    cold_name, _, cold_scores = auto.select(graph)
    assert cold_name == "overlap"
    local.set_calibration(CalibrationProfile(
        topology_digest=local.digest(),
        kernel_cost_ns={"k_fast": 50_000.0},
        kernel_samples={"k_fast": 16}))
    hot_name, _, hot_scores = auto.select(graph)
    assert hot_name != "overlap"            # the decision flipped
    assert hot_scores[hot_name] <= hot_scores["overlap"]
    assert hot_scores != cold_scores        # the fitted term repriced


def test_session_stats_report_schedule_scores(topo):
    AutoSchedule.score_stats(reset=True)
    sess = CommSession(CommConfig(multipath_threshold=256), topology=topo)
    sess.describe(0, 1, 4 * MiB, schedule="auto", max_paths=2)
    s = sess.stats()["schedule_scores"]
    assert s["misses"] >= 1


# ------------- hypothesis: overlap contract on random mixed graphs ----------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _mixed_params = st.tuples(
        st.integers(min_value=0, max_value=3),          # extra kernels
        st.integers(min_value=8, max_value=1 << 14),    # payload elems
        st.integers(min_value=1, max_value=3),          # messages
        st.integers(min_value=1, max_value=3),          # chunks
        st.integers(min_value=0, max_value=10_000_000), # kernel flops
        st.randoms(use_true_random=False),
    )

    # The smallest draw of this tuple (five integers plus a Random)
    # already exceeds Hypothesis's size budget for a base example; that
    # health check is about the strategy's size, not the contract.
    @settings(max_examples=30, deadline=None,
              suppress_health_check=[HealthCheck.large_base_example])
    @given(_mixed_params)
    def test_overlap_contract_on_random_mixed_graphs(params):
        """SATELLITE property: ``overlap`` satisfies the §2.2 contract on
        randomized mixed graphs and its lane-model makespan is never
        worse than round_robin's (the lowering order)."""
        depth, nelems, n_msgs, chunks, flops, rnd = params
        topo = Topology.full_mesh(8, with_host=False, name="mesh8")

        def build(cap):
            x = cap.input((nelems,), jnp.float32)
            y = cap.kernel(lambda v: v + 1.0, x, name="k0", flops=flops)
            for i in range(depth):
                y = cap.kernel(lambda v: v * 2.0, y, name=f"k{i + 1}",
                               flops=rnd.randrange(0, 1_000_000))
            pairs = []
            while len(pairs) < n_msgs:
                s, d = rnd.randrange(8), rnd.randrange(8)
                if s != d:
                    pairs.append((s, d))
            recvs = cap.exchange([(y, s, d) for s, d in pairs],
                                 num_chunks=chunks)
            cap.kernel(lambda *rs: sum(rs), *recvs, name="sink", flops=0)

        graph = _lower_capture(build, topo)
        out = OverlapSchedule(topo)(graph)
        check_pass(graph, out)                           # §2.2 contract
        rr, _ = apply_schedule(graph, "round_robin", topo)
        assert (scheduled_time_s(out, topo, mode="lanes")
                <= scheduled_time_s(rr, topo, mode="lanes"))
