"""TransferGraph IR: lowering round-trips, digests, invariants, and the
equal-graph acceptance criterion (model node count == traced ``ppermute``
count for the identical plan — the executor, the cost model, and the
validator all consume ONE lowering, so they cannot silently diverge)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CommConfig, CommSession, PathPlanner,
                        TransferPlan, TransferPlanCache)
from repro.comm.engine import GroupKey
from repro.comm.graph import (HOP_EDGE, WINDOW_EDGE, CopyNode, DepEdge,
                              TransferGraph, canonical_digest, lower)
from repro.comm.plan import PathAssignment
from repro.core import Topology, build_schedule, validate_plan

MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    return Topology.full_mesh(8, with_host=False, name="mesh8")


@pytest.fixture(scope="module")
def planner(topo):
    return PathPlanner(topo, multipath_threshold=256)


def _expected_counts(plans, window):
    nodes = window * sum(len(pa.chunk_bounds()) * pa.route.num_hops
                         for p in plans for pa in p.paths)
    chunks = sum(len(pa.chunk_bounds()) for p in plans for pa in p.paths)
    hop_edges = window * sum(
        len(pa.chunk_bounds()) * (pa.route.num_hops - 1)
        for p in plans for pa in p.paths)
    return nodes, hop_edges + (window - 1) * chunks


# ------------------------------ lowering ------------------------------------

@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize("max_paths,chunks", [(1, 1), (3, 4), (4, 2)])
def test_lower_counts(planner, max_paths, chunks, window):
    plan = planner.plan(0, 1, 8 * MiB, max_paths=max_paths,
                        num_chunks=chunks)
    graph = lower(plan, window)
    nodes, edges = _expected_counts([plan], window)
    assert graph.num_nodes == nodes
    assert graph.num_edges == edges
    assert graph.window == window and graph.num_messages == 1


def test_lower_roundtrip_chunk_bounds(planner):
    """Node byte ranges reproduce ``chunk_bounds()`` exactly (the lowering
    loses no information about which bytes each copy node moves)."""
    plan = planner.plan(2, 5, 8 * MiB + 12_288, max_paths=3, granularity=4)
    graph = lower(plan)
    for p_idx, pa in enumerate(plan.paths):
        got = sorted({(n.offset, n.nbytes) for n in graph.nodes
                      if n.path_idx == p_idx})
        assert got == sorted(pa.chunk_bounds())
    # every node knows its flow and link chain position
    assert {n.flow for n in graph.nodes} == {(2, 5)}


def test_lower_group_roundtrip(planner):
    group = planner.plan_group([(0, 1, 4 * MiB), (1, 0, 4 * MiB),
                                (2, 3, 2 * MiB)])
    graph = lower(group, 2)
    nodes, edges = _expected_counts(group.plans, 2)
    assert graph.num_nodes == nodes and graph.num_edges == edges
    assert graph.num_messages == 3
    assert graph.flows() == tuple((p.src, p.dst) for p in group.plans)
    for m_idx, plan in enumerate(group.plans):
        for p_idx, pa in enumerate(plan.paths):
            got = sorted({(n.offset, n.nbytes) for n in graph.nodes
                          if n.msg_idx == m_idx and n.path_idx == p_idx
                          and n.window == 0})
            assert got == sorted(pa.chunk_bounds())


def test_lower_is_memoized(planner):
    plan = planner.plan(0, 1, 8 * MiB)
    assert lower(plan, 1) is lower(plan, 1)  # frozen plans → cached graph


def test_lower_rejects_bad_window(planner):
    with pytest.raises(ValueError, match="window"):
        lower(planner.plan(0, 1, MiB), 0)


def test_topological_order_and_edge_kinds(planner):
    plan = planner.plan(0, 1, 8 * MiB, max_paths=3, num_chunks=2)
    graph = lower(plan, 2)
    order = graph.topological_order()
    assert sorted(order) == list(range(graph.num_nodes))
    pos = {n: i for i, n in enumerate(order)}
    for e in graph.edges:
        assert pos[e.src] < pos[e.dst]
        assert e.kind in (HOP_EDGE, WINDOW_EDGE)
    # hop edges keep offset/bytes constant along the chain
    for e in graph.edges:
        if e.kind == HOP_EDGE:
            a, b = graph.nodes[e.src], graph.nodes[e.dst]
            assert (a.offset, a.nbytes) == (b.offset, b.nbytes)
            assert a.link[1] == b.link[0]          # chained hops
            assert b.hop_idx == a.hop_idx + 1


def test_critical_path_nodes(planner):
    direct = planner.plan(0, 1, 8 * MiB, max_paths=1, num_chunks=4)
    assert lower(direct).critical_path_nodes() == 4   # chunk serialization
    staged = planner.plan(0, 1, 8 * MiB, max_paths=3, num_chunks=4)
    hops = max(pa.route.num_hops for pa in staged.paths)
    assert lower(staged).critical_path_nodes() == hops + 3
    # window rounds chain through the window edges
    assert lower(direct, 2).critical_path_nodes() == 5


# ------------------------------ digests -------------------------------------

def test_digest_stable_across_lowerings(topo):
    p1 = PathPlanner(topo, multipath_threshold=256).plan(0, 1, 8 * MiB)
    p2 = PathPlanner(topo, multipath_threshold=256).plan(0, 1, 8 * MiB)
    assert p1 is not p2
    assert lower(p1).digest() == lower(p2).digest()


def test_digest_sensitive_to_structure(planner):
    base = lower(planner.plan(0, 1, 8 * MiB)).digest()
    assert lower(planner.plan(0, 1, 8 * MiB), 2).digest() != base  # window
    assert lower(planner.plan(0, 1, 8 * MiB, num_chunks=7)
                 ).digest() != base                                # chunking
    assert lower(planner.plan(0, 1, 4 * MiB)).digest() != base     # size
    assert lower(planner.plan(1, 0, 8 * MiB)).digest() != base     # flow


def test_group_digest_carries_every_message(planner):
    """The digest subsumes the old cache-key regression: two groups sharing
    a forward plan but differing in the second message digest apart."""
    g1 = planner.plan_group([(0, 1, 4 * MiB), (1, 0, 4 * MiB)])
    g2 = planner.plan_group([(0, 1, 4 * MiB), (1, 0, 2 * MiB)])
    g3 = planner.plan_group([(0, 1, 4 * MiB), (2, 0, 4 * MiB)])
    digests = {lower(g).digest() for g in (g1, g2, g3)}
    assert len(digests) == 3


def test_canonical_digest_deterministic():
    assert canonical_digest(("a", 1)) == canonical_digest(("a", 1))
    assert canonical_digest(("a", 1)) != canonical_digest(("a", 2))


# ------------------------- invariants on the graph --------------------------

def _hand_plan(topo, paths):
    return TransferPlan(0, 1, sum(pa.nbytes for pa in paths), tuple(paths),
                        topo.name)


def test_validate_catches_gap(topo):
    route = PathPlanner(topo).enumerate_routes(0, 1)[0]
    plan = _hand_plan(topo, [PathAssignment(route, 4096, 4096, 1, 1)])
    with pytest.raises(ValueError, match="gap/overlap"):
        validate_plan(plan)


def test_validate_catches_shared_link(topo):
    route = PathPlanner(topo).enumerate_routes(0, 1)[0]
    plan = _hand_plan(topo, [PathAssignment(route, 0, 4096, 1, 1),
                             PathAssignment(route, 4096, 4096, 1, 1)])
    with pytest.raises(ValueError, match="shared by paths"):
        validate_plan(plan)


def test_validate_catches_short_coverage(topo):
    route = PathPlanner(topo).enumerate_routes(0, 1)[0]
    plan = TransferPlan(0, 1, 8192,
                        (PathAssignment(route, 0, 4096, 1, 1),), topo.name)
    with pytest.raises(ValueError, match="coverage ends"):
        validate_plan(plan)


def test_validate_catches_wrong_endpoints(topo):
    route = PathPlanner(topo).enumerate_routes(2, 3)[0]  # not flow (0, 1)
    plan = _hand_plan(topo, [PathAssignment(route, 0, 4096, 1, 1)])
    with pytest.raises(ValueError, match="endpoints"):
        validate_plan(plan)


def test_graph_validate_cross_flow(planner):
    """Graph-level validate flags cross-flow link sharing (the §4.5 group
    invariant) directly on nodes — same check `validate_group` applies."""
    nodes = (CopyNode((0, 1), 0, 0, 0, 0, 0, (0, 2), 0, 64),
             CopyNode((0, 1), 0, 0, 0, 1, 0, (2, 1), 0, 64),
             CopyNode((4, 1), 1, 0, 0, 0, 0, (4, 2), 0, 64),
             CopyNode((4, 1), 1, 0, 0, 1, 0, (2, 1), 0, 64))
    edges = (DepEdge(0, 1, HOP_EDGE), DepEdge(2, 3, HOP_EDGE))
    graph = TransferGraph(nodes, edges, 1, 2, "t")
    with pytest.raises(ValueError, match="exclusivity"):
        graph.validate()
    graph.validate(cross_flow_exclusive=False)  # shared fallback: allowed


def test_graph_rejects_cycle():
    n = CopyNode((0, 1), 0, 0, 0, 0, 0, (0, 1), 0, 64)
    graph = TransferGraph((n, n), (DepEdge(0, 1, HOP_EDGE),
                                   DepEdge(1, 0, HOP_EDGE)), 1, 1, "t")
    with pytest.raises(ValueError, match="cycle"):
        graph.topological_order()


# --------------------------- views over the graph ---------------------------

def test_build_schedule_is_graph_view(planner):
    plan = planner.plan(0, 1, 8 * MiB, max_paths=3, num_chunks=4)
    graph = lower(plan)
    tasks = build_schedule(plan)
    assert len(tasks) == sum(len(pa.chunk_bounds()) for pa in plan.paths)
    chains = {}
    for n in graph.nodes:
        chains.setdefault((n.path_idx, n.chunk_idx), []).append(n)
    for t in tasks:
        nodes = sorted(chains[(t.path_idx, t.chunk_idx)],
                       key=lambda n: n.hop_idx)
        assert t.hops == tuple(n.link for n in nodes)
        assert (t.offset, t.nbytes) == (nodes[0].offset, nodes[0].nbytes)


# ----------------------- equal-graph acceptance test ------------------------

@pytest.mark.parametrize("window", [1, 2])
def test_equal_graph_invariant_single(topo, window, count_eqns):
    """ACCEPTANCE: the model's node count equals the number of ``ppermute``
    ops actually traced for the identical plan — the cost model and the
    executable are views of ONE graph."""
    sess = CommSession(CommConfig(multipath_threshold=256), topology=topo)
    eng = sess.engine
    plan = eng.plan_for(0, 1, 4096, max_paths=3, num_chunks=4)
    graph, _ = eng._group_graph((plan,), window)
    fn = eng._build_group_fn(graph, (4,))
    traced = count_eqns(fn, (jax.ShapeDtypeStruct(
        (window, eng.num_devices, 4096), jnp.float32),), "ppermute")
    assert traced == graph.num_nodes
    assert graph.num_nodes == window * plan.num_nodes


def test_equal_graph_invariant_group(topo, count_eqns):
    sess = CommSession(CommConfig(multipath_threshold=256), topology=topo)
    eng = sess.engine
    group = eng.plan_group_for([(0, 1, 1024, jnp.float32),
                                (1, 0, 2048, jnp.float32),
                                (2, 3, 512, jnp.int32)])
    graph, _ = eng._group_graph(group.plans, 1)
    fn = eng._build_group_fn(graph, (4, 4, 4))
    abstracts = [jax.ShapeDtypeStruct((1, eng.num_devices, n), dt)
                 for n, dt in ((1024, jnp.float32), (2048, jnp.float32),
                               (512, jnp.int32))]
    assert count_eqns(fn, abstracts, "ppermute") == graph.num_nodes
    assert graph.num_nodes == sum(p.num_nodes for p in group.plans)


def test_compiled_lifecycle_reports_graph_nodes(topo):
    sess = CommSession(CommConfig(multipath_threshold=64), topology=topo,
                       cache=TransferPlanCache(capacity=8))
    compiled, plan = sess.compiled_for(0, 1, 1024, num_chunks=2)
    assert compiled.lifecycle.num_nodes == lower(plan).num_nodes
    assert isinstance(compiled.key, GroupKey)
    assert compiled.key.digest == sess.engine._group_graph(
        (plan,), 1)[0].digest()
    s = sess.stats()
    assert s["graph"]["nodes_compiled"] == lower(plan).num_nodes
    assert s["graph"]["edges_compiled"] == lower(plan).num_edges


def test_shared_cache_across_mesh_sizes(topo):
    """Regression: 0→1 on a 4-mesh and an 8-mesh can lower to graphs with
    IDENTICAL digests (the digest covers routes, not the device axis), but
    the compiled operands are (window, num_devices, nelems) — the shared
    cache must keep the two meshes' executables apart via
    ``GroupKey.num_devices``."""
    cache = TransferPlanCache(capacity=8)
    cfg = CommConfig(multipath_threshold=1 << 30)     # direct route only
    mesh4 = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dev",))
    sess4 = CommSession(cfg, mesh=mesh4,
                        topology=Topology.full_mesh(4, with_host=False),
                        cache=cache)
    sess8 = CommSession(cfg, topology=topo, cache=cache)
    msg = jnp.arange(256, dtype=jnp.float32)
    out4 = sess4.send(msg, 0, 1)
    out8 = sess8.send(msg, 0, 1)                      # must NOT hit 4-mesh
    np.testing.assert_array_equal(np.asarray(out4), np.asarray(msg))
    np.testing.assert_array_equal(np.asarray(out8), np.asarray(msg))
    keys = cache.keys()
    assert len(keys) == 2
    assert keys[0].digest == keys[1].digest           # same graph...
    assert {k.num_devices for k in keys} == {4, 8}    # ...distinct meshes


def test_executed_transfer_still_correct(topo):
    """End-to-end: the graph-walked program moves the bytes."""
    sess = CommSession(CommConfig(multipath_threshold=64), topology=topo)
    msg = jnp.asarray(np.random.RandomState(3).randn(1000), jnp.float32)
    out = sess.send(msg, 0, 5, max_paths=3, num_chunks=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msg))


def test_describe_matches_lowering(topo):
    sess = CommSession(CommConfig(), topology=topo)
    d = sess.describe(0, 1, 8 * MiB, window=2, max_paths=3)
    plan = sess.plan(0, 1, 8 * MiB, max_paths=3)
    graph = lower(plan, 2)
    assert d["graph"]["nodes"] == graph.num_nodes
    assert d["graph"]["edges"] == graph.num_edges
    assert d["graph"]["digest"] == graph.digest()
    assert d["graph"]["critical_path_nodes"] == graph.critical_path_nodes()
    assert d["model"]["time_s"] > d["model"]["wire_time_s"] > 0
