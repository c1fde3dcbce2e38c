"""Unified comm session API: config round-trip, policies, cache accounting.

Covers the acceptance criteria of the ``repro.comm`` redesign:

* ``CommConfig.from_env`` reproduces the legacy ``REPRO_MP_*`` parsing,
* the greedy ``PathPolicy`` builds plans identical (byte-for-byte) to the
  pre-refactor ``PathPlanner.plan`` algorithm on the seed topologies,
* ``CommSession`` shares one plan cache across send / bidirectional /
  collective calls, with correct hit/miss accounting,
* the deprecated ``repro.core.*`` shims still work and warn.
"""

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CommConfig, CommSession, GreedyBandwidthPolicy,
                        PathPlanner, RoundRobinPolicy, TransferPlanCache,
                        TunerPolicy, make_policy)
from repro.core import HOST, Topology, validate_plan

MiB = 1 << 20


# --------------------------- CommConfig ------------------------------------

def test_from_env_defaults_match_dataclass():
    assert CommConfig.from_env() == CommConfig()


def test_from_env_reads_legacy_vars(monkeypatch):
    monkeypatch.setenv("REPRO_MP_MAX_PATHS", "2")
    monkeypatch.setenv("REPRO_MP_CHUNK_BYTES", str(2 * MiB))
    monkeypatch.setenv("REPRO_MP_MAX_CHUNKS", "5")
    monkeypatch.setenv("REPRO_MP_HOST_PATH", "1")
    monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "7")
    cfg = CommConfig.from_env()
    assert cfg.max_paths == 2
    assert cfg.chunk_bytes == 2 * MiB
    assert cfg.max_chunks == 5
    assert cfg.include_host is True
    assert cfg.cache_capacity == 7


def test_from_env_overrides_beat_env(monkeypatch):
    monkeypatch.setenv("REPRO_MP_MAX_PATHS", "2")
    assert CommConfig.from_env(max_paths=3).max_paths == 3


def test_planner_defaults_honor_env(monkeypatch):
    """Legacy behavior: a bare PathPlanner picks up REPRO_MP_* knobs."""
    monkeypatch.setenv("REPRO_MP_MAX_PATHS", "2")
    monkeypatch.setenv("REPRO_MP_CHUNK_BYTES", str(2 * MiB))
    planner = PathPlanner(Topology.full_mesh(4))
    assert planner.max_paths == 2
    assert planner.chunk_bytes == 2 * MiB
    plan = planner.plan(0, 1, 64 * MiB)
    assert plan.num_paths == 2


@pytest.mark.parametrize("field,value", [
    ("max_paths", 0), ("chunk_bytes", 0), ("max_chunks", 0),
    ("window", 0), ("cache_capacity", 0), ("policy", "nope"),
    ("multipath_threshold", -1), ("axis_name", ""),
])
def test_config_validation(field, value):
    with pytest.raises(ValueError):
        CommConfig(**{field: value})


# --------------------------- PathPolicy ------------------------------------

def _legacy_plan(planner, src, dst, nbytes, *, max_paths=None,
                 include_host=None, num_chunks=None, granularity=1):
    """The pre-refactor ``PathPlanner.plan`` algorithm, frozen verbatim as
    the equivalence oracle for the greedy policy."""
    from repro.comm.plan import PathAssignment, TransferPlan
    max_paths = max_paths or planner.max_paths
    routes = planner.enumerate_routes(src, dst, include_host=include_host)
    if nbytes < planner.multipath_threshold:
        routes = routes[:1]
    else:
        routes = routes[:max_paths]
    total_bw = sum(r.bottleneck_gbps for r in routes)
    paths = []
    offset = 0
    for i, route in enumerate(routes):
        if i == len(routes) - 1:
            share = nbytes - offset
        else:
            share = (int(nbytes * route.bottleneck_gbps / total_bw)
                     // granularity * granularity)
        if share <= 0:
            continue
        if num_chunks is not None:
            chunks = num_chunks
        else:
            chunks = max(1, min(planner.max_chunks,
                                -(-share // planner.chunk_bytes)))
        chunks = min(chunks, max(1, share // granularity))
        paths.append(PathAssignment(route, offset, share, chunks,
                                    granularity))
        offset += share
    return TransferPlan(src, dst, nbytes, tuple(paths),
                        planner.topology.name)


SEED_TOPOLOGIES = [
    Topology.full_mesh(4),                                # beluga
    Topology.full_mesh(4, sublinks_per_pair=4, name="narval4"),
    Topology.full_mesh(8, with_host=False, name="mesh8"),
    Topology.torus2d(4, 4),
]


@pytest.mark.parametrize("topo", SEED_TOPOLOGIES, ids=lambda t: t.name)
def test_greedy_policy_matches_legacy_planner(topo):
    """Acceptance: greedy plans identical to the pre-refactor planner."""
    planner = PathPlanner(topo, policy=GreedyBandwidthPolicy())
    host_opts = ([False, True] if any(
        HOST in k for k in topo.links) else [False])
    for nbytes in (4096, 1 * MiB, 2 * MiB, 64 * MiB, 512 * MiB + 4096):
        for max_paths in (1, 2, 3, 4, 16):
            for host in host_opts:
                for gran in (1, 4):
                    if nbytes % gran:
                        continue
                    got = planner.plan(0, 1, nbytes, max_paths=max_paths,
                                       include_host=host, granularity=gran)
                    ref = _legacy_plan(planner, 0, 1, nbytes,
                                       max_paths=max_paths,
                                       include_host=host, granularity=gran)
                    assert got == ref


def test_max_paths_zero_raises():
    planner = PathPlanner(Topology.full_mesh(4))
    with pytest.raises(ValueError, match="max_paths"):
        planner.plan(0, 1, 64 * MiB, max_paths=0)
    with pytest.raises(ValueError, match="max_paths"):
        planner.plan(0, 1, 64 * MiB, max_paths=-1)


def test_round_robin_equal_shares():
    planner = PathPlanner(Topology.full_mesh(4),
                          policy=RoundRobinPolicy())
    plan = planner.plan(0, 1, 64 * MiB, max_paths=3)
    validate_plan(plan)
    assert plan.num_paths == 3
    shares = [p.nbytes for p in plan.paths]
    assert max(shares) - min(shares) <= 4  # equal up to remainder
    # greedy on the same topology is NOT uniform (direct link is 50 GB/s
    # among equals here, but host-inclusive plans diverge)
    hostp = PathPlanner(Topology.full_mesh(4),
                        policy=GreedyBandwidthPolicy()).plan(
        0, 1, 64 * MiB, max_paths=4, include_host=True)
    hostshares = [p.nbytes for p in hostp.paths]
    assert max(hostshares) - min(hostshares) > 4


def test_tuner_policy_memoizes_and_matches_tune():
    topo = Topology.full_mesh(4)
    tuner = TunerPolicy()
    planner = PathPlanner(topo, policy=tuner)
    plan1 = planner.plan(0, 1, 128 * MiB)
    # plan() inherits the planner's include_host=False default, so it must
    # match a tune constrained the same way (NOT the unconstrained search,
    # which may pick a host-staged — unexecutable — configuration).
    assert plan1 == planner.tune(0, 1, 128 * MiB,
                                 include_host_options=(False,))
    assert all(p.route.via != HOST for p in plan1.paths)
    assert len(tuner._memo) == 1
    plan2 = planner.plan(0, 1, 128 * MiB)
    assert plan2 is plan1          # memo hit
    validate_plan(plan1)
    assert plan1.num_paths >= 2    # large message goes multipath


def test_tuner_policy_memo_keyed_on_max_paths():
    """Regression: a 1-path tune must not be served for a 4-path request."""
    planner = PathPlanner(Topology.full_mesh(4), policy=TunerPolicy())
    p1 = planner.plan(0, 1, 64 * MiB, max_paths=1)
    assert p1.num_paths == 1
    p4 = planner.plan(0, 1, 64 * MiB, max_paths=4)
    assert p4.num_paths >= 2


def test_tuner_policy_respects_include_host():
    """Regression: tuner plans for the engine must honor include_host=False
    (a host-staged plan would be rejected as unexecutable)."""
    planner = PathPlanner(Topology.full_mesh(4), policy=TunerPolicy())
    plan = planner.plan(0, 1, 64 * MiB, include_host=False)
    assert all(p.route.via != HOST for p in plan.paths)
    hosted = planner.plan(0, 1, 64 * MiB, include_host=True)
    assert any(p.route.via == HOST for p in hosted.paths)


def test_tuner_policy_session_send_executes():
    """End-to-end regression: tuner-policy sessions can actually send."""
    import jax.numpy as jnp
    sess = CommSession(CommConfig(policy="tuner"),
                       topology=Topology.full_mesh(4))
    msg = jnp.arange((4 * MiB) // 4, dtype=jnp.float32)
    got = sess.send(msg, 0, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msg))


def test_tuner_policy_memo_distinguishes_topologies():
    """Regression: topology NAMES are non-unique defaults (full_mesh() is
    always 'beluga4'); a shared policy must key on the object."""
    tuner = TunerPolicy()
    p8 = PathPlanner(Topology.full_mesh(8, with_host=False), policy=tuner)
    plan8 = p8.plan(0, 1, 64 * MiB)
    p4 = PathPlanner(Topology.full_mesh(4, with_host=False), policy=tuner)
    plan4 = p4.plan(0, 1, 64 * MiB)
    used4 = {d for pa in plan4.paths for link in pa.route.hops
             for d in (link.src, link.dst)}
    assert used4 <= set(range(4)), f"8-device routes leaked: {used4}"
    assert plan8 is not plan4


def test_make_policy_registry():
    assert make_policy("greedy").name == "greedy"
    assert make_policy("round_robin").name == "round_robin"
    assert make_policy("tuner").name == "tuner"
    with pytest.raises(ValueError):
        make_policy("best_effort")


# --------------------------- CommSession -----------------------------------

@pytest.fixture(scope="module")
def session():
    return CommSession(CommConfig(multipath_threshold=256),
                       topology=Topology.full_mesh(8, with_host=False,
                                                   name="mesh8"))


def test_session_send_roundtrip(session):
    msg = jnp.arange(4096, dtype=jnp.float32)
    got = session.send(msg, 0, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msg))


def test_session_cache_accounting_across_ops(session):
    """send / bidirectional / collective all hit the SAME plan cache."""
    cache = session.cache
    msg = jnp.arange(512, dtype=jnp.float32)
    base = cache.stats()

    session.send(msg, 1, 2)                      # miss (new key)
    session.send(msg * 2, 1, 2)                  # hit (same key)
    session.bidirectional(msg, 1, 2)             # miss (distinct key)
    session.bidirectional(msg, 1, 2)             # hit
    x = jnp.asarray(np.random.RandomState(0).randn(8, 4), jnp.float32)
    session.all_gather(x)                        # miss
    session.all_gather(x)                        # hit
    session.psum(jnp.ones((3, 3)))               # miss
    session.psum(jnp.ones((3, 3)))               # hit

    s = cache.stats()
    assert s["misses"] == base["misses"] + 4
    assert s["hits"] == base["hits"] + 4
    assert s["size"] == base["size"] + 4


def test_session_collectives_match_references(session):
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    x = jnp.asarray(np.random.RandomState(1).randn(16, 6), jnp.float32)
    got = session.all_gather(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-6)

    rs = session.reduce_scatter(x)
    ref = jax.jit(shard_map(
        lambda v: jax.lax.psum_scatter(v, "dev", tiled=True),
        mesh=session.mesh, in_specs=P(None), out_specs=P("dev"),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ref), rtol=1e-5)

    ar = session.all_reduce(x)
    np.testing.assert_allclose(np.asarray(ar), np.asarray(x) * 8, rtol=1e-5)

    pm = session.psum(jnp.ones((5, 2)))
    np.testing.assert_allclose(np.asarray(pm), 8.0, rtol=1e-6)


def test_session_all_to_all_roundtrip(session):
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = 8
    x = jnp.asarray(np.random.RandomState(2).randn(n * n, 4), jnp.float32)
    got = session.all_to_all(x)

    # reference via lax inside shard_map on block-indexed local operand
    def local_ref(v):  # v local: (n, 4) — one block per destination
        return jax.lax.all_to_all(v.reshape(n, 1, 4), "dev", 0, 0
                                  ).reshape(n, 4)
    ref = jax.jit(shard_map(local_ref, mesh=session.mesh, in_specs=P("dev"),
                            out_specs=P("dev"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_session_all_to_all_rejects_wrong_block_count(session):
    """Regression: dim 0 merely divisible by n silently dropped blocks."""
    with pytest.raises(ValueError, match="n²"):
        session.all_to_all(jnp.ones((8, 4), jnp.float32))     # local dim 1
    with pytest.raises(ValueError, match="n²"):
        session.all_to_all(jnp.ones((128, 4), jnp.float32))   # local dim 16


def test_session_ring_collectives_reject_indivisible(session):
    with pytest.raises(ValueError, match="divisible"):
        session.all_reduce(jnp.ones((6, 4), jnp.float32))
    with pytest.raises(ValueError, match="divisible"):
        session.reduce_scatter(jnp.ones((6, 4), jnp.float32))


def test_session_tune_delegates(session):
    best = session.tune(0, 1, 128 * MiB)
    validate_plan(best)
    assert best.num_paths >= 2


def test_session_send_pytree(session):
    tree = {"k": jnp.arange(24, dtype=jnp.bfloat16).reshape(2, 3, 4),
            "idx": jnp.arange(7, dtype=jnp.int32)}
    moved = session.send_pytree(tree, 0, 5)
    import jax
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(moved)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_session_stats_shape(session):
    s = session.stats()
    assert s["policy"] == "greedy"
    assert s["topology"] == "mesh8"
    assert set(s["cache"]) == {"hits", "misses", "evictions", "size",
                               "capacity"}


def test_session_respects_explicit_cache():
    cache = TransferPlanCache(capacity=2)
    sess = CommSession(CommConfig(multipath_threshold=64),
                       topology=Topology.full_mesh(8, with_host=False),
                       cache=cache)
    sess.send(jnp.arange(128, dtype=jnp.float32), 0, 1)
    assert len(cache) == 1         # engine really used OUR cache


# --------------------------- deprecated shims ------------------------------

def test_core_shims_warn_and_delegate():
    import importlib
    import repro.core.paths as legacy_paths
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        importlib.reload(legacy_paths)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    from repro.comm.planner import PathPlanner as NewPlanner
    assert legacy_paths.PathPlanner is NewPlanner


def test_core_lazy_reexports():
    from repro.core import (MultiPathTransfer, PathPlanner,
                            TransferPlanCache as TPC)
    from repro.comm import MultiPathTransfer as M2, PathPlanner as P2
    assert MultiPathTransfer is M2 and PathPlanner is P2
    assert TPC().capacity == 64


@pytest.mark.parametrize("module", ["repro.core.paths",
                                    "repro.core.multipath",
                                    "repro.core.plan_cache",
                                    "repro.core.collectives"])
def test_every_core_shim_warns_on_import(module):
    """Each deprecated ``repro.core.*`` shim fires a DeprecationWarning on
    (re)import and still resolves its legacy surface."""
    import importlib
    import sys
    sys.modules.pop(module, None)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        importlib.import_module(module)


def test_transfer_key_alias_warns():
    """The dead ``TransferKey`` is gone from the engine; the alias lives on
    ``repro.core`` only and warns on access."""
    import repro.comm
    import repro.comm.engine
    import repro.core
    assert not hasattr(repro.comm.engine, "TransferKey")
    assert not hasattr(repro.comm, "TransferKey")
    with pytest.warns(DeprecationWarning, match="TransferKey"):
        key_cls = repro.core.TransferKey
    # still constructible for any straggler pickles/tests downstream
    k = key_cls(0, 1, 64, "float32", ())
    assert (k.src, k.dst) == (0, 1)

    import repro.core.multipath as legacy_multipath
    with pytest.warns(DeprecationWarning, match="TransferKey"):
        assert legacy_multipath.TransferKey is key_cls
