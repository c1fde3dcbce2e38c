"""Hierarchical (multi-island) topology, planning, and collectives tests.

Covers the DESIGN §3.1 surface end-to-end: island queries and validation
on :meth:`Topology.hierarchical`, the planner's staged cross-island
routing (§4.5 link-disjointness across tiers), the node-boundary
digest/epoch regression (identical links, different islands must never
cross-serve cached plans), the two-level collective decomposition and
its §4.4 tier model, and the launch-spec resolution for the multi-pod
arch configs.
"""

import pytest

import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P

import jax

from repro.comm import (CommConfig, CommSession, FastPathCache,
                        PathPlanner, modeled_all_reduce_s,
                        select_all_reduce_strategy, tier_bandwidths_gbps,
                        two_level_all_reduce)
from repro.comm.cache import FastPathEntry
from repro.comm.config import COLLECTIVE_STRATEGIES
from repro.core import HOST, Link, Topology, validate_plan

MiB = 1 << 20


# -- topology: island queries and validation --------------------------------

def test_hierarchical_construction(two_island):
    assert two_island.num_devices == 8
    assert two_island.num_islands == 2
    assert two_island.islands() == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert [two_island.node_of(d) for d in range(8)] == [0] * 4 + [1] * 4
    assert two_island.egress_devices(0) == (0,)
    assert two_island.egress_devices(1) == (4,)
    assert two_island.is_inter_island(0, 4)
    assert not two_island.is_inter_island(0, 3)
    # HOST belongs to no island: host hops are never inter-island
    assert not two_island.is_inter_island(0, HOST)


def test_flat_topology_is_one_island(mesh4):
    assert mesh4.num_islands == 1
    assert mesh4.islands() == ((0, 1, 2, 3),)
    assert not mesh4.is_inter_island(0, 1)


def test_node_of_rejects_host_and_out_of_range(two_island):
    with pytest.raises(ValueError):
        two_island.node_of(HOST)
    with pytest.raises(ValueError):
        two_island.node_of(8)


def test_hierarchical_validation_errors():
    with pytest.raises(ValueError, match="num_islands"):
        Topology.hierarchical(0, 4)
    with pytest.raises(ValueError, match="egress_per_island"):
        Topology.hierarchical(2, 4, egress_per_island=5)
    with pytest.raises(ValueError, match="torus_shape"):
        Topology.hierarchical(2, 4, intra="torus", torus_shape=(4, 4))
    with pytest.raises(ValueError, match="intra"):
        Topology.hierarchical(2, 4, intra="ring")
    with pytest.raises(ValueError, match="node_assignment"):
        Topology(4, [Link(0, 1, "nvlink", 25.0)], node_assignment=(0, 1))


def test_node_assignment_in_digest_and_epoch():
    """SATELLITE regression: identical links, different node boundaries
    must yield distinct digests and distinct planner epochs — plans and
    fast-path entries for one island layout never serve the other."""
    links = [Link(a, b, "nvlink", 25.0)
             for a in range(4) for b in range(4) if a != b]
    flat = Topology(4, links, name="same")
    split = Topology(4, links, name="same", node_assignment=(0, 0, 1, 1))
    assert flat.digest() != split.digest()
    assert PathPlanner(flat).epoch != PathPlanner(split).epoch
    # and reassigning boundaries in place bumps the epoch + digest
    epoch0, digest0 = flat.epoch, flat.digest()
    flat.set_node_assignment((0, 1, 1, 1))
    assert flat.epoch != epoch0
    assert flat.digest() != digest0
    flat.set_node_assignment(None)          # flatten back to one island
    assert flat.num_islands == 1
    assert flat.digest() == digest0


def test_fastpath_entry_not_served_across_node_reassignment(mesh4):
    """A fast-path entry stamped under one island layout is invalidated
    (not served) after ``set_node_assignment`` bumps the epoch."""
    planner = PathPlanner(mesh4)
    cache = FastPathCache(capacity=4)
    entry = FastPathEntry(plans=(), graph=None, digest="d", key="k",
                          compiled=None, schedule="round_robin")
    cache.put("sig", planner.epoch, entry)
    assert cache.get("sig", planner.epoch) is entry
    mesh4.set_node_assignment((0, 0, 1, 1))
    assert cache.get("sig", planner.epoch) is None
    assert cache.invalidations == 1


# -- planner: staged cross-island routing ------------------------------------

def test_intra_island_routes_avoid_inter_links(two_island):
    planner = PathPlanner(two_island)
    for src, dst in ((0, 3), (1, 2), (5, 7)):
        for route in planner.enumerate_routes(src, dst):
            for a, b in route.directional_links():
                assert not two_island.is_inter_island(a, b), (route, a, b)


def test_cross_island_routes_have_one_inter_hop(two_island):
    planner = PathPlanner(two_island)
    routes = planner.cross_island_routes(1, 7)
    assert routes
    for route in routes:
        inter = [(a, b) for a, b in route.directional_links()
                 if two_island.is_inter_island(a, b)]
        assert len(inter) == 1
        assert inter[0] == (0, 4)          # the single egress pair


def test_cross_island_plan_link_disjoint(two_island):
    planner = PathPlanner(two_island, multipath_threshold=256)
    plan = planner.plan(1, 7, 8 * MiB, max_paths=4)
    validate_plan(plan)                    # §4.5 link exclusivity
    for pa in plan.paths:
        inter = [lk for lk in pa.route.directional_links()
                 if two_island.is_inter_island(*lk)]
        assert len(inter) == 1


def test_cross_island_multipath_uses_multiple_egress():
    topo = Topology.hierarchical(2, 4, egress_per_island=2, name="egress2")
    planner = PathPlanner(topo, multipath_threshold=256)
    plan = planner.plan(2, 6, 8 * MiB, max_paths=4)
    inter_links = {lk for pa in plan.paths
                   for lk in pa.route.directional_links()
                   if topo.is_inter_island(*lk)}
    assert inter_links == {(0, 4), (1, 5)}


def test_plan_group_across_tiers(two_island):
    """``plan_group`` keeps link-exclusive claiming across tiers: one
    cross-island and one intra-island message share no directional link."""
    planner = PathPlanner(two_island, multipath_threshold=256)
    group = planner.plan_group([(1, 7, 4 * MiB), (2, 3, 4 * MiB)],
                               exclusive=True)
    assert group.exclusive
    claimed: set = set()
    for plan in group.plans:
        for pa in plan.paths:
            for lk in pa.route.directional_links():
                assert lk not in claimed
                claimed.add(lk)


# -- collectives: tier model + two-level decomposition -----------------------

def test_tier_bandwidths(two_island, mesh4):
    intra, inter = tier_bandwidths_gbps(two_island)
    assert intra == pytest.approx(50.0)    # 2 × 25 NVLink sublinks
    assert inter == pytest.approx(12.5)
    intra, inter = tier_bandwidths_gbps(mesh4)
    assert inter is None


def test_two_level_models_strictly_faster_on_two_islands(two_island):
    """ISSUE acceptance: on the 2-island × 4-GPU topology the two-level
    all-reduce must model *strictly* faster than the flat ring."""
    for mb in (1, 8, 64):
        flat = modeled_all_reduce_s(two_island, mb * MiB, strategy="flat")
        two = modeled_all_reduce_s(two_island, mb * MiB,
                                   strategy="two_level")
        assert two < flat, (mb, two, flat)


def test_select_strategy_auto_and_forced(two_island, mesh4):
    chosen, times = select_all_reduce_strategy(two_island, 8 * MiB)
    assert chosen == "two_level"
    assert times["two_level"] < times["flat"]
    chosen, _ = select_all_reduce_strategy(two_island, 8 * MiB,
                                           strategy="flat")
    assert chosen == "flat"
    # single island: nothing to decompose — auto resolves flat
    chosen, times = select_all_reduce_strategy(mesh4, 8 * MiB)
    assert chosen == "flat"
    assert times["two_level"] == times["flat"]


def test_two_level_all_reduce_matches_joint_psum():
    mesh = jax.make_mesh((2, 4), ("pod", "dev"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.asarray(np.random.RandomState(0).randn(16, 64), jnp.float32)
    two = jax.jit(jax.shard_map(
        partial(two_level_all_reduce, inter_axis="pod", intra_axis="dev"),
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    ref = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, ("pod", "dev")),
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    np.testing.assert_allclose(np.asarray(two(x)), np.asarray(ref(x)),
                               rtol=1e-5)


# -- session + config + launch ----------------------------------------------

def test_describe_reports_hierarchy(two_island):
    sess = CommSession(CommConfig(multipath_threshold=256),
                       topology=two_island)
    d = sess.describe(1, 7, 8 * MiB)
    h = d["hierarchy"]
    assert h["islands"] == 2
    assert (h["src_island"], h["dst_island"]) == (0, 1)
    assert h["cross_island"]
    ar = h["all_reduce"]
    assert ar["chosen"] == "two_level"
    assert ar["delta_two_level_vs_flat_s"] == pytest.approx(
        ar["two_level_time_s"] - ar["flat_time_s"])
    assert ar["delta_two_level_vs_flat_s"] < 0     # modeled improvement
    d = sess.describe(1, 3, 8 * MiB)
    assert not d["hierarchy"]["cross_island"]


def test_describe_flat_topology_has_no_all_reduce_section(mesh4):
    sess = CommSession(CommConfig(multipath_threshold=256), topology=mesh4)
    h = sess.describe(0, 1, 8 * MiB)["hierarchy"]
    assert h["islands"] == 1
    assert "all_reduce" not in h


def test_collective_strategy_config(monkeypatch):
    assert CommConfig().collective_strategy == "auto"
    for s in COLLECTIVE_STRATEGIES:
        assert CommConfig(collective_strategy=s).collective_strategy == s
    with pytest.raises(ValueError, match="collective strategy"):
        CommConfig(collective_strategy="tree")
    monkeypatch.setenv("REPRO_MP_COLLECTIVES", "two_level")
    assert CommConfig.from_env().collective_strategy == "two_level"


def test_multi_pod_launch_specs_resolve_island_aware_meshes():
    """ISSUE acceptance: the kimi/nemotron specs resolve 2-pod meshes and
    hierarchical topologies; smaller archs stay on the flat pod."""
    from repro.configs import get_config, load_all
    from repro.launch.mesh import production_launch_spec

    load_all()
    for arch_name in ("kimi_k2_1t_a32b", "nemotron_4_340b"):
        spec = production_launch_spec(get_config(arch_name))
        assert spec["multi_pod"], arch_name
        assert spec["mesh_shape"] == (2, 16, 16)
        assert spec["mesh_axes"] == ("pod", "data", "model")
        assert spec["topology"].num_islands == 2
        assert spec["topology"].num_devices == 512
    spec = production_launch_spec(get_config("llama3_8b"))
    assert not spec["multi_pod"]
    assert spec["mesh_shape"] == (16, 16)
    assert spec["topology"].num_islands == 1
