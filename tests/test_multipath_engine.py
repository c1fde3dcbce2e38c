"""Executable multi-path transfer engine (shard_map/ppermute backend)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MultiPathTransfer, PathPlanner, Topology,
                        TransferPlanCache, plan_signature)


@pytest.fixture(scope="module")
def engine():
    topo = Topology.full_mesh(8, with_host=True)
    return MultiPathTransfer(topology=topo,
                             planner=PathPlanner(topo, multipath_threshold=256))


@pytest.mark.parametrize("nelems", [64, 1024, 100_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_transfer_roundtrip(engine, nelems, dtype):
    msg = jnp.arange(nelems).astype(dtype)
    got = engine.transfer(msg, 0, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msg))


def test_transfer_of_messages_committed_to_their_source(engine):
    """Messages placed on their source devices (as on a multi-chip host)
    are accepted, alone and in a group, and arrive intact."""
    devs = engine.mesh.devices.flat
    a = jax.device_put(jnp.arange(4096, dtype=jnp.float32), devs[2])
    b = jax.device_put(jnp.arange(4096, dtype=jnp.float32) * 2.0, devs[5])
    np.testing.assert_array_equal(np.asarray(engine.transfer(a, 2, 5)),
                                  np.asarray(a))
    fwd, rev = engine.transfer_group([a, b], [(2, 5), (5, 2)])
    np.testing.assert_array_equal(np.asarray(fwd), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(rev), np.asarray(b))


@pytest.mark.parametrize("dst", [1, 6])
def test_extraction_matches_eager_indexing(engine, dst):
    """``comm_extract`` gives what eager ``y[0, dst]`` gives on the
    engine's sharded ``(window, ndev, nelems)`` output: the same values,
    replicated over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm.engine import comm_extract
    n = engine.num_devices
    y = jax.device_put(
        jnp.arange(2 * n * 16, dtype=jnp.float32).reshape(2, n, 16),
        NamedSharding(engine.mesh, P(None, engine.axis_name)))
    got, eager = comm_extract(y, dst), y[0, dst]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(eager))
    assert got.sharding.is_fully_replicated
    assert got.sharding.is_equivalent_to(eager.sharding, 1)


def test_bidirectional_group(engine):
    """Opposite-direction traffic is a 2-transfer group (the old
    ``bidirectional=True`` flag); BOTH receptions are returned."""
    fwd_msg = jnp.arange(4096, dtype=jnp.float32)
    rev_msg = jnp.arange(4096, dtype=jnp.float32) * -3.0
    fwd, rev = engine.transfer_group([fwd_msg, rev_msg], [(2, 5), (5, 2)])
    np.testing.assert_array_equal(np.asarray(fwd), np.asarray(fwd_msg))
    np.testing.assert_array_equal(np.asarray(rev), np.asarray(rev_msg))


def test_transfer_group_mixed_sizes_dtypes(engine):
    msgs = [jnp.arange(1000, dtype=jnp.float32),
            jnp.arange(64, dtype=jnp.int32),
            jnp.arange(4096, dtype=jnp.bfloat16)]
    outs = engine.transfer_group(msgs, [(0, 1), (2, 3), (6, 4)])
    for m, o in zip(msgs, outs):
        assert o.dtype == m.dtype
        np.testing.assert_array_equal(np.asarray(o), np.asarray(m))


def test_transfer_group_one_cache_entry_one_dispatch(engine):
    c0, d0 = len(engine.cache), engine.dispatches
    msgs = [jnp.arange(256, dtype=jnp.float32) * i for i in range(3)]
    engine.transfer_group(msgs, [(0, 7), (0, 7), (0, 7)])
    assert len(engine.cache) == c0 + 1      # ONE fused program
    assert engine.dispatches == d0 + 1      # ONE launch
    engine.transfer_group(msgs, [(0, 7), (0, 7), (0, 7)])
    assert len(engine.cache) == c0 + 1      # steady state: pure cache hit


def test_window(engine):
    msg = jnp.arange(2048, dtype=jnp.float32)
    got = engine.transfer(msg, 1, 6, window=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msg))


def test_cache_hit_on_repeat(engine):
    cache = engine.cache
    msg = jnp.arange(512, dtype=jnp.float32)
    engine.transfer(msg, 3, 4)
    h0 = cache.stats()["hits"]
    engine.transfer(msg * 2, 3, 4)   # same key (src,dst,size,config)
    assert cache.stats()["hits"] == h0 + 1


def test_distinct_keys_for_distinct_sizes(engine):
    msg = jnp.arange(512, dtype=jnp.float32)
    c0 = len(engine.cache)
    engine.transfer(msg, 4, 5)
    engine.transfer(jnp.arange(513, dtype=jnp.float32), 4, 5)
    assert len(engine.cache) == c0 + 2


def test_host_route_rejected_on_device_mesh(engine):
    # host sorts last, so ask for every route to force it into the plan
    plan = engine.planner.plan(0, 1, 4096 * 4, include_host=True,
                               granularity=4, max_paths=16)
    assert any(p.route.kind == "staged_host" for p in plan.paths)
    from repro.core.multipath import _check_executable
    with pytest.raises(ValueError, match="host-staged"):
        _check_executable(plan)


def test_plan_signature_stable(engine):
    p1 = engine.plan_for(0, 1, 4096)
    p2 = engine.plan_for(0, 1, 4096)
    assert plan_signature(p1) == plan_signature(p2)


def test_torus_topology_transfer():
    topo = Topology.torus2d(2, 4)
    eng = MultiPathTransfer(topology=topo,
                            planner=PathPlanner(topo,
                                                multipath_threshold=64))
    msg = jnp.arange(8192, dtype=jnp.float32)
    got = eng.transfer(msg, 0, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msg))
